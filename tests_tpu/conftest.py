"""On-TPU gate configuration.

Unlike tests/conftest.py (which forces a virtual CPU mesh so the suite
runs anywhere), this directory runs on whatever backend JAX selects.
CPU tests run Pallas kernels in interpret mode, so a kernel the Mosaic
compiler rejects can stay green there while crashing every real TPU
run; this gate compiles and runs the kernels on the chip.

The backend is asked in this process. Where JAX selects the CPU (this
sandbox exports JAX_PLATFORMS=cpu) every test is skipped; where it is
told to use the TPU and cannot, JAX's own start-up error fails the run
— on the chip a missing TPU is never a skip.
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import pytest  # noqa: E402

from skypilot_tpu.utils import compile_cache  # noqa: E402

compile_cache.configure()


def pytest_configure(config):
    config.addinivalue_line(
        'markers', 'tpu: requires a real TPU device (skipped elsewhere)')


def pytest_collection_modifyitems(config, items):
    import jax
    backend = jax.default_backend()
    if backend == 'tpu':
        return
    skip = pytest.mark.skip(reason=f'JAX selected the {backend} backend')
    for item in items:
        if 'tpu' in item.keywords:
            item.add_marker(skip)
