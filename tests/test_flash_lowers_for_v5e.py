"""The flash kernels at the cells' real shapes, compiled by the TPU's own
compiler for a v5e that is described and not attached (no chip time; a
compile that passes is not a chip run). What interpret mode cannot see:
the scalar-prefetched list of visited tiles in SMEM, the index maps that
read it, the VMEM working sets. All in this one file, the topology in a
fixture: only the worker given this file loads the TPU's library."""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from skypilot_tpu.ops import dispatch
from skypilot_tpu.ops import flash_attention


@pytest.fixture(scope='module')
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault('TPU_LOG_DIR', 'disabled')
    try:
        topo = topologies.get_topology_desc(platform='tpu',
                                            topology_name='v5e:2x2')
    except Exception as e:  # pylint: disable=broad-except
        pytest.skip(f'no v5e:2x2 topology can be described here: {e}')
    # A program compiled for a described chip is written to the
    # persistent cache and cannot be read back without one.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update('jax_enable_compilation_cache', False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update('jax_enable_compilation_cache', was)
    compilation_cache.reset_cache()


# name, (batch, S, q heads, kv heads, head size or (q and k, v) widths),
# the mask, segment ids, the steps a head of (forward, dq, dk/dv)
CASES = [
    ('sft-swa-moe-16k-window', (1, 16384, 32, 4, 128),
     dict(causal=True, window=1024), False, (62, 31, 93)),
    ('sft-bd-moe-8k', (1, 16384, 32, 4, 128),
     dict(causal=False, block_diffusion=4), False, (160, 80, 288)),
    ('sft-swa-moe-16k-full', (1, 16384, 32, 4, 128), dict(causal=True),
     False, (272, 136, 528)),
    ('sft-moe-8k', (2, 8192, 32, 8, 64), dict(causal=True), False,
     (72, 36, 136)),
    ('sft-2k-packed', (4, 2048, 16, 8, 128), dict(causal=True), True,
     (6, 3, 10)),
    ('causal-65536', (1, 65536, 4, 1, 128), dict(causal=True), False,
     (4160, 2080, 8256)),
    # latent attention: q and k of 192, v of 128
    ('sft-kda-mla-16k', (1, 16384, 32, 32, (192, 128)), dict(causal=True),
     False, (272, 136, 528)),
    # DeepSeek-V2's latent attention at its YaRN softmax scale
    ('sft-mla-moe-16k', (1, 16384, 16, 16, (192, 128)),
     dict(causal=True, scale=0.1147214), False, (272, 136, 528)),
]


@pytest.mark.parametrize('case', CASES, ids=[c[0] for c in CASES])
def test_forward_and_backward_compile_on_the_list_of_visited_tiles(
        case, one_chip, monkeypatch):
    _, (b, s, hq, hkv, d), mask, segmented, steps = case
    d, dv = d if isinstance(d, tuple) else (d, d)
    monkeypatch.setattr(dispatch, 'interpret_mode', lambda: False)
    dispatch.reset_for_tests()
    q = jax.ShapeDtypeStruct((b, s, hq, d), jnp.bfloat16, sharding=one_chip)
    k = jax.ShapeDtypeStruct((b, s, hkv, d), jnp.bfloat16,
                             sharding=one_chip)
    v = jax.ShapeDtypeStruct((b, s, hkv, dv), jnp.bfloat16,
                             sharding=one_chip)
    seg = jax.ShapeDtypeStruct((b, s), jnp.int32, sharding=one_chip) \
        if segmented else None

    def loss(q_, k_, v_, seg_):
        return flash_attention.flash_attention(
            q_, k_, v_, segment_ids=seg_, **mask).astype(jnp.float32).sum()

    text = jax.jit(jax.grad(loss, (0, 1, 2))).lower(
        q, k, v, seg).compile().as_text()
    assert text.count('tpu_custom_call') >= 3
    plans = dispatch.flash_plan_snapshot()
    assert tuple(p['steps'] for p in plans.values()) == steps
    assert all(p['steps'] == p['visited'] for p in plans.values())


def test_the_kda_kernels_compile_at_the_cells_group_call(one_chip,
                                                         monkeypatch):
    """KDA's chunked form (ops/kda_kernel.py) at `sft-kda-mla-16k`'s
    call: 1 x 16,384 positions, a group of 8 heads of 128, chunks of
    64; forward and backward through the custom VJP, as the model takes
    its gradient, each kernel one `tpu_custom_call`."""
    from skypilot_tpu.ops import kda
    from skypilot_tpu.ops import kda_kernel
    monkeypatch.setattr(dispatch, 'interpret_mode', lambda: False)
    dispatch.reset_for_tests()
    b, s, h, d = 1, 16384, 8, 128
    x = jax.ShapeDtypeStruct((b, s, h, d), jnp.float32, sharding=one_chip)
    beta = jax.ShapeDtypeStruct((b, s, h), jnp.float32, sharding=one_chip)

    def loss(*args):
        return kda.chunk_kda(*args).sum()

    text = jax.jit(jax.grad(loss, range(5))).lower(
        x, x, x, x, beta).compile().as_text()
    assert text.count('tpu_custom_call') == 2
    assert dispatch.snapshot()['kda'] == 'pallas'
    assert kda.plan(b, s, h, d, d) == {
        'rung': 'pallas', 'steps': kda_kernel.grid_steps(b, s, h)}
