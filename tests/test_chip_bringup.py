"""What the chip bring-up added, as far as a CPU can check it: the
compile-cache placement, no fallback that hides the device, the
`device` block of /stats, sharded preset init, the append scatter's
new form, and chip_smoke.py's behaviour without a chip.
"""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from skypilot_tpu.utils import compile_cache

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(code, cwd, **env_over):
    env = {k: v for k, v in os.environ.items()
           if k != compile_cache.ENV}
    env.update(PYTHONPATH=ROOT, JAX_PLATFORMS='cpu', **env_over)
    return subprocess.run([sys.executable, '-c', code], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=120)


# ------------------------------------------------------- compile cache
_PLACE = ('import jax\n'
          'from skypilot_tpu.utils import compile_cache\n'
          'print(compile_cache.configure())\n'
          'print(jax.config.jax_compilation_cache_dir)\n')


def test_compile_cache_unset_is_checkout_dir_in_every_process(tmp_path):
    """Unset: <checkout>/.jax_cache whatever the cwd, the pid or the
    home directory — two processes agree, so the second can hit."""
    want = os.path.join(ROOT, '.jax_cache')
    other = tmp_path / 'elsewhere'
    other.mkdir()
    for cwd, home in ((ROOT, str(tmp_path)), (str(other), str(other))):
        r = _run(_PLACE, cwd, HOME=home)
        assert r.returncode == 0, r.stderr
        assert r.stdout.split() == [want, want]


def test_compile_cache_set_is_left_alone(tmp_path):
    """Set: JAX reads the variable itself; the helper sets no directory
    in code and nothing appears under the home directory."""
    placed = str(tmp_path / 'placed')
    code = ('import jax\n'
            'jax.config.update = None   # any config.update would raise\n'
            + _PLACE.replace('import jax\n', ''))
    r = _run(code, str(tmp_path), HOME=str(tmp_path),
             **{compile_cache.ENV: placed})
    assert r.returncode == 0, r.stderr
    assert r.stdout.split() == [placed, placed]
    assert os.listdir(tmp_path) == []


def test_compile_cache_counts_what_jax_reports():
    before = compile_cache.snapshot()
    assert before['dir'] == os.environ[compile_cache.ENV]
    compile_cache._on_event('/jax/compilation_cache/cache_hits')
    compile_cache._on_event('/jax/compilation_cache/cache_misses')
    compile_cache._on_event('/jax/some/other/event')
    compile_cache._on_duration(
        '/jax/core/compile/backend_compile_duration', 1.5)
    after = compile_cache.snapshot()
    assert after['hits'] == before['hits'] + 1
    assert after['misses'] == before['misses'] + 1
    assert after['compile_seconds'] == pytest.approx(
        before['compile_seconds'] + 1.5, abs=2e-3)


# ------------------------------------------- nothing hides the device
def test_interpret_mode_does_not_swallow_backend_errors(monkeypatch):
    from skypilot_tpu.ops import dispatch
    assert dispatch.interpret_mode() is True        # the CPU backend

    def no_backend():
        raise RuntimeError("Unable to initialize backend 'tpu'")
    monkeypatch.setattr(jax, 'default_backend', no_backend)
    with pytest.raises(RuntimeError, match='Unable to initialize'):
        dispatch.interpret_mode()


def test_stats_device_block():
    from skypilot_tpu.infer import server as server_lib
    engine = server_lib.build_engine('debug', num_slots=2,
                                     max_seq_len=64)
    stats = engine.stats()
    dev = stats['device']
    assert dev['platform'] == 'cpu' and dev['device_kind'] == 'cpu'
    assert dev['count'] == jax.device_count() == 8
    assert dev['pallas_interpret'] is True
    assert dev['versions']['jax'] == jax.__version__
    assert 'memory' not in dev      # the CPU backend reports none
    assert stats['compile_cache']['dir'] == os.environ[compile_cache.ENV]
    json.dumps(stats)               # /stats serialises it as is


class _FakeTpu:
    id = 3

    def memory_stats(self):
        return {'bytes_in_use': 5, 'peak_bytes_in_use': 7,
                'bytes_limit': 9, 'num_allocs': 1}


def test_device_block_lists_memory_where_reported(monkeypatch):
    from skypilot_tpu.ops import dispatch
    monkeypatch.setattr(jax, 'local_devices', lambda: [_FakeTpu()])
    assert dispatch.device_info()['memory'] == [
        {'id': 3, 'bytes_in_use': 5, 'peak_bytes_in_use': 7,
         'bytes_limit': 9}]


# ------------------------------------------------- sharded preset init
def test_preset_initialises_straight_into_the_sharded_layout(monkeypatch):
    """build_engine(preset, tp=N) draws the weights inside one jit whose
    outputs are the sharded layout — never whole on device 0 and
    re-placed afterwards (qwen3-8b --tp 4 would not fit chip 0) — and
    draws the same values as that older route."""
    from skypilot_tpu.infer import server as server_lib
    from skypilot_tpu.models import weights as weights_lib
    shard_params = weights_lib.shard_params

    def unsharded_first(*args, **kwargs):
        raise AssertionError('preset weights were initialised unsharded')
    monkeypatch.setattr(weights_lib, 'shard_params', unsharded_first)
    engine = server_lib.build_engine('debug', num_slots=2,
                                     max_seq_len=64, tp=2)
    params = engine.params['params']
    halved = 0
    for leaf in jax.tree.leaves(params):
        assert leaf.sharding.mesh == engine.mesh
        if leaf.sharding.shard_shape(leaf.shape) != leaf.shape:
            halved += 1
            assert all(2 * shard.data.size == leaf.size
                       for shard in leaf.addressable_shards)
    assert halved >= 8      # q/k/v/o, gate/up/down, embedding, lm_head
    model, cfg, mesh = engine.model, engine.cfg, engine.mesh
    whole = jax.jit(model.init)(jax.random.PRNGKey(0),
                                jnp.zeros((1, 8), jnp.int32))
    want = shard_params(whole, model, cfg, mesh)['params']
    jax.tree.map(np.testing.assert_array_equal, params, want)


# ------------------------------------------------- the append scatter
def test_set_rows_is_the_slab_scatter():
    """PagePool._set_rows writes what pool.at[page, :, off].set did —
    only the scatter's window changed (it decides the pool's layout on
    the TPU, tests_tpu TestPagedPoolLayout)."""
    from skypilot_tpu.infer.paged_cache import PagePool
    rng = np.random.default_rng(0)
    pool = jnp.asarray(rng.normal(size=(9, 2, 4, 8)), jnp.float32)
    scale = jnp.asarray(rng.normal(size=(9, 2, 4)), jnp.float32)
    page = jnp.asarray([3, 1, 8, 5], jnp.int32)
    off = jnp.asarray([0, 3, 2, 1], jnp.int32)
    rows = jnp.asarray(rng.normal(size=(4, 2, 8)), jnp.float32)
    np.testing.assert_array_equal(
        PagePool._set_rows(pool, page, off, rows),
        pool.at[page, :, off].set(rows))
    np.testing.assert_array_equal(
        PagePool._set_rows(scale, page, off, rows[..., 0]),
        scale.at[page, :, off].set(rows[..., 0]))


# ------------------------------------------------ kernels under a mesh
def test_per_shard_runs_the_kernel_on_each_devices_heads():
    """sharding.per_shard: under a mesh every device calls the kernel
    with its own batch rows and heads only, and the pieces reassemble
    to the unsharded answer (on the TPU Mosaic refuses anything else:
    'cannot be automatically partitioned')."""
    import flax.linen as nn

    from skypilot_tpu.parallel import mesh as mesh_lib
    from skypilot_tpu.parallel import sharding as sharding_lib
    seen = []

    def kernel(q, k):     # head-local, grouped like GQA attention
        seen.append((q.shape, k.shape))
        return q * jnp.repeat(k, q.shape[2] // k.shape[2], axis=2)
    q_axes = ('act_batch', None, 'act_heads', None)
    kv_axes = ('act_batch', None, 'act_kv_heads', None)
    q = jnp.arange(4 * 3 * 4 * 2, dtype=jnp.float32).reshape(4, 3, 4, 2)
    k = jnp.arange(4 * 3 * 2 * 2, dtype=jnp.float32).reshape(4, 3, 2, 2)
    assert sharding_lib.per_shard(kernel, (q_axes, kv_axes),
                                  q_axes) is kernel       # off-mesh
    mesh = mesh_lib.build_mesh(mesh_lib.MeshSpec(fsdp=2, tp=2),
                               jax.devices()[:4])
    with mesh, nn.logical_axis_rules(list(sharding_lib.DEFAULT_RULES)):
        got = jax.jit(lambda q, k: sharding_lib.per_shard(
            kernel, (q_axes, kv_axes), q_axes)(q, k))(q, k)
    assert seen[-1] == ((2, 3, 2, 2), (2, 3, 1, 2))
    np.testing.assert_array_equal(got, kernel(q, k))


# ----------------------------------------------------- chip_smoke.py
def test_chip_smoke_refuses_without_a_chip(tmp_path):
    """No TPU here: non-zero exit and no result on stdout, before any
    leg starts; likewise from a directory that holds only the script."""
    env = dict(os.environ, HOME=str(tmp_path))
    r = subprocess.run([sys.executable, 'chip_smoke.py'], cwd=ROOT,
                       env=env, capture_output=True, text=True,
                       timeout=300)
    assert r.returncode != 0
    assert r.stdout == ''
    assert 'no tpu device' in r.stderr
    alone = tmp_path / 'alone'
    alone.mkdir()
    with open(os.path.join(ROOT, 'chip_smoke.py'), 'rb') as f:
        (alone / 'chip_smoke.py').write_bytes(f.read())
    r = subprocess.run([sys.executable, 'chip_smoke.py'], cwd=alone,
                       env=env, capture_output=True, text=True,
                       timeout=300)
    assert r.returncode != 0
    assert r.stdout == ''
    assert os.listdir(alone) == ['chip_smoke.py']


def test_chip_smoke_parent_never_imports_jax():
    """The parent holds no chip: bringing up its in-process load
    balancer must not pull JAX in."""
    code = ('import sys\n'
            'import chip_smoke\n'
            "door = chip_smoke.FrontDoor('http://127.0.0.1:9')\n"
            'door.close()\n'
            "assert 'jax' not in sys.modules, 'jax imported'\n"
            "print('ok')\n")
    r = _run(code, ROOT)
    assert r.returncode == 0, r.stderr
    assert r.stdout.strip() == 'ok'
