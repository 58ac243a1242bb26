"""In-place weight hot-swap + rolling-update orchestration
(docs/robustness.md "Zero-downtime rollouts").

Engine/manager half: tree-validation reject table, tick-boundary
atomicity, drain vs continue semantics, prefix-cache flush, version
metrics, and abort-keeps-old-weights under every `weights.swap` fault
kind. Controller half: the canary -> bake -> fleet state machine with
auto-rollback, restart resume semantics, adoption composition, and
the weights-only spec diff routing — all against an injected swap
transport (the real-HTTP drills live in test_chaos_*.py).
"""
import dataclasses
import threading
import time

import pytest

from skypilot_tpu.utils import faults
from skypilot_tpu.utils import metrics as metrics_lib


@pytest.fixture(autouse=True)
def _reset_faults():
    faults.reset()
    yield
    faults.reset()


# ------------------------------------------------------ engine fixtures
@pytest.fixture(scope='module')
def debug_setup():
    import jax
    import jax.numpy as jnp

    from skypilot_tpu.models import llama
    cfg = dataclasses.replace(llama.CONFIGS['debug'], max_seq_len=64)
    model = llama.LlamaModel(cfg)
    zeros = jnp.zeros((1, 8), jnp.int32)
    p0 = jax.jit(model.init)(jax.random.PRNGKey(0), zeros)
    p1 = jax.jit(model.init)(jax.random.PRNGKey(7), zeros)
    return cfg, model, p0, p1


def _make_engine(debug_setup, reg, params=None, **kw):
    from skypilot_tpu.infer import engine as engine_lib
    _, model, p0, _ = debug_setup
    defaults = dict(num_slots=2, max_seq_len=64, decode_chunk=2,
                    prefill_buckets=[16], metrics_registry=reg)
    defaults.update(kw)
    return engine_lib.InferenceEngine(model, params if params is not None
                                      else p0, **defaults)


def _gen(eng, tokens, n=8):
    from skypilot_tpu.infer import engine as engine_lib
    return eng.generate(tokens,
                        engine_lib.SamplingParams(max_new_tokens=n))


# ------------------------------------------------- validation rejects
def _rekey(tree, drop=None, add=None):
    import copy
    t = copy.deepcopy(tree)
    p = t['params']
    if drop:
        del p[drop]
    if add:
        p[add] = {'extra': 0.0}
    return t


@pytest.mark.parametrize('mutate,needle', [
    (lambda t: _rekey(t, drop='final_norm'), 'missing'),
    (lambda t: _rekey(t, add='bogus_layer'), 'unexpected'),
    ('shape', 'shape'),
    ('dtype', 'dtype'),
])
def test_validate_reject_table(debug_setup, mutate, needle):
    """Structure / shape / dtype mismatches are rejected with the
    offending path named — before anything touches the engine."""
    import jax
    import jax.numpy as jnp

    from skypilot_tpu.infer import weight_swap
    _, _, p0, p1 = debug_setup
    if mutate == 'shape':
        bad = jax.tree_util.tree_map(
            lambda x: x[..., :1] if getattr(x, 'ndim', 0) else x, p1)
    elif mutate == 'dtype':
        bad = jax.tree_util.tree_map(
            lambda x: jnp.asarray(x, jnp.float16), p1)
    else:
        bad = mutate(p1)
    with pytest.raises(weight_swap.WeightSwapError) as ei:
        weight_swap.validate_tree(p0, bad)
    assert needle in str(ei.value)


def test_validate_accepts_matching_tree(debug_setup):
    from skypilot_tpu.infer import weight_swap
    _, _, p0, p1 = debug_setup
    weight_swap.validate_tree(p0, p1)   # no raise


# ------------------------------------------------- swap semantics
def test_swap_changes_outputs_version_and_metrics(debug_setup):
    from skypilot_tpu.infer import weight_swap
    reg = metrics_lib.MetricsRegistry()
    eng = _make_engine(debug_setup, reg)
    _, _, _, p1 = debug_setup
    eng.start()
    try:
        golden_old = _gen(eng, [1, 2, 3])
        mgr = weight_swap.WeightSwapManager(eng, registry=reg)
        res = mgr.swap(params=p1)
        assert res['ok'] and res['weight_version'] == 2
        assert eng.weight_version == 2
        assert eng.stats()['weight_version'] == 2
        out_new = _gen(eng, [1, 2, 3])
        assert out_new != golden_old
        # Metrics: version gauge, duration histogram, result counter.
        text = reg.expose()
        assert 'skyt_infer_weight_version 2' in text
        assert 'skyt_infer_weight_swaps_total{result="ok"} 1' in text
        assert 'skyt_infer_weight_swap_seconds_count 1' in text
        # swap_back restores the exact old behavior and version.
        back = mgr.swap_back()
        assert back['weight_version'] == 1
        assert _gen(eng, [1, 2, 3]) == golden_old
    finally:
        eng.stop()


def test_drain_true_finishes_inflight_on_old_weights(debug_setup):
    """drain=True (default): a request in flight when the swap lands
    completes ENTIRELY on the old weights — its stream is
    byte-identical to an unswapped run — and the swap applies right
    after its slot frees."""
    from skypilot_tpu.infer import engine as engine_lib
    from skypilot_tpu.infer import weight_swap
    reg = metrics_lib.MetricsRegistry()
    eng = _make_engine(debug_setup, reg)
    _, _, _, p1 = debug_setup
    eng.start()
    try:
        golden_old = _gen(eng, [5, 6, 7], n=24)
        mgr = weight_swap.WeightSwapManager(eng, registry=reg)
        rid, q = eng.submit([5, 6, 7], engine_lib.SamplingParams(
            max_new_tokens=24))
        first = q.get(timeout=60)          # request is mid-decode
        res = mgr.swap(params=p1, drain=True)
        out = [first]
        while True:
            tok = q.get(timeout=60)
            if tok is None:
                break
            out.append(tok)
        assert out == golden_old, 'drained request saw the new weights'
        assert res['weight_version'] == 2
        assert _gen(eng, [5, 6, 7], n=24) != golden_old
    finally:
        eng.stop()


def test_drain_false_swaps_while_inflight(debug_setup):
    """SKYT_SWAP_DRAIN=0 semantics: the swap applies at the next tick
    boundary with requests still running — they continue on the new
    weights (their stream diverges from the old-weights golden)."""
    from skypilot_tpu.infer import engine as engine_lib
    from skypilot_tpu.infer import weight_swap
    reg = metrics_lib.MetricsRegistry()
    eng = _make_engine(debug_setup, reg)
    _, _, _, p1 = debug_setup
    eng.start()
    try:
        golden_old = _gen(eng, [5, 6, 7], n=32)
        mgr = weight_swap.WeightSwapManager(eng, registry=reg)
        rid, q = eng.submit([5, 6, 7], engine_lib.SamplingParams(
            max_new_tokens=32))
        out = [q.get(timeout=60)]
        res = mgr.swap(params=p1, drain=False)
        swapped_at = time.monotonic()
        done_at = None
        while True:
            tok = q.get(timeout=60)
            if tok is None:
                done_at = time.monotonic()
                break
            out.append(tok)
        # The swap returned while the request was still streaming...
        assert done_at is not None and done_at >= swapped_at
        assert res['weight_version'] == 2
        # ...and the post-boundary suffix came from the NEW weights.
        assert out != golden_old
    finally:
        eng.stop()


def test_prefix_cache_flushed_on_swap(debug_setup):
    """Published prefix pages are stale KV after a version change:
    the swap flushes the registry, so post-swap admissions recompute
    (and republish) instead of silently mixing versions."""
    from skypilot_tpu.infer import weight_swap
    reg = metrics_lib.MetricsRegistry()
    eng = _make_engine(debug_setup, reg, cache_mode='paged',
                       page_size=8, prefix_caching=True)
    _, _, _, p1 = debug_setup
    eng.start()
    try:
        prompt = list(range(1, 18))      # 2 full pages and change
        _gen(eng, prompt)
        _gen(eng, prompt)                # second run shares pages
        assert eng.pool.prefix_stats['hit_pages'] >= 1
        assert eng.pool.prefix_cached_pages() >= 1
        mgr = weight_swap.WeightSwapManager(eng, registry=reg)
        res = mgr.swap(params=p1)
        assert res['flushed_prefix_pages'] >= 1
        assert eng.pool.prefix_cached_pages() == 0
        misses_before = eng.pool.prefix_stats['miss_pages']
        _gen(eng, prompt)                # recomputes under new weights
        assert eng.pool.prefix_stats['miss_pages'] > misses_before
    finally:
        eng.stop()


# ------------------------------------------------- faults + aborts
def test_fault_error_aborts_with_old_weights(debug_setup):
    from skypilot_tpu.infer import weight_swap
    reg = metrics_lib.MetricsRegistry()
    eng = _make_engine(debug_setup, reg)
    _, _, _, p1 = debug_setup
    eng.start()
    try:
        golden = _gen(eng, [1, 2, 3])
        mgr = weight_swap.WeightSwapManager(eng, registry=reg)
        faults.configure('weights.swap=error')
        with pytest.raises(weight_swap.WeightSwapError):
            mgr.swap(params=p1)
        faults.reset()
        assert eng.weight_version == 1
        assert _gen(eng, [1, 2, 3]) == golden
        assert 'skyt_infer_weight_swaps_total{result="aborted"} 1' \
            in reg.expose()
        assert mgr.last is not None and not mgr.last['ok']
        # The abort retained nothing to roll back to.
        with pytest.raises(weight_swap.WeightSwapError):
            mgr.swap_back()
    finally:
        eng.stop()


def test_fault_latency_delays_but_succeeds(debug_setup):
    from skypilot_tpu.infer import weight_swap
    reg = metrics_lib.MetricsRegistry()
    eng = _make_engine(debug_setup, reg)
    _, _, _, p1 = debug_setup
    mgr = weight_swap.WeightSwapManager(eng, registry=reg)
    faults.configure('weights.swap=latency,arg=0.3')
    t0 = time.monotonic()
    res = mgr.swap(params=p1)         # engine not started: inline apply
    assert res['ok'] and time.monotonic() - t0 >= 0.3


def test_fault_hang_holds_single_flight_409(debug_setup):
    """A hung swap (weights.swap=hang) keeps the single-flight lock:
    a concurrent push gets SwapInFlight (the server's 409), and the
    hung one still completes."""
    from skypilot_tpu.infer import weight_swap
    reg = metrics_lib.MetricsRegistry()
    eng = _make_engine(debug_setup, reg)
    _, _, _, p1 = debug_setup
    mgr = weight_swap.WeightSwapManager(eng, registry=reg)
    faults.configure('weights.swap=hang,arg=1.0,count=1')
    results = {}

    def slow():
        results['slow'] = mgr.swap(params=p1)

    th = threading.Thread(target=slow)
    th.start()
    time.sleep(0.3)                    # inside the hang window
    with pytest.raises(weight_swap.SwapInFlight):
        mgr.swap(params=p1)
    th.join(timeout=30)
    assert results['slow']['ok']


def test_engine_swap_timeout_leaves_old_weights(debug_setup):
    """A draining swap that cannot reach an empty boundary within its
    timeout aborts cleanly: TimeoutError, old weights live, and the
    pending request is CLEARED (it does not fire later)."""
    from skypilot_tpu.infer import engine as engine_lib
    reg = metrics_lib.MetricsRegistry()
    eng = _make_engine(debug_setup, reg)
    _, _, _, p1 = debug_setup
    eng.start()
    try:
        golden = _gen(eng, [9, 9, 9], n=4)
        # Slow the loop so the in-flight request outlives the swap
        # timeout (the debug model would otherwise finish in ms).
        faults.configure('engine.loop=latency,arg=0.1')
        rid, q = eng.submit([9, 9, 9], engine_lib.SamplingParams(
            max_new_tokens=48))
        q.get(timeout=60)              # slot occupied
        with pytest.raises(TimeoutError):
            eng.request_weight_swap(p1, drain=True, timeout=0.3)
        faults.reset()
        # Drain the long request; the cancelled swap must NOT land.
        while q.get(timeout=60) is not None:
            pass
        time.sleep(0.2)
        assert eng.weight_version == 1
        assert _gen(eng, [9, 9, 9], n=4) == golden
    finally:
        eng.stop()


# ------------------------------------------------- server admin route
def test_admin_weights_route_contract(debug_setup, monkeypatch):
    """403 unauthed / disabled, 400 malformed, 200 on a real swap,
    409 concurrent, swap_back — and weight_version in /stats."""
    import requests as req_lib

    from skypilot_tpu.infer import server as server_lib
    from chaos_helpers import _free_port, _run_app_bg, _wait_http
    reg = metrics_lib.MetricsRegistry()
    eng = _make_engine(debug_setup, reg)
    _, _, _, p1 = debug_setup
    # A checkpoint loader in miniature: one known path.
    eng.param_loader = lambda path: (
        p1 if path == 'ckpt-v2'
        else (_ for _ in ()).throw(FileNotFoundError(path)))
    eng.start()
    try:
        srv = server_lib.InferenceServer(eng)
        port = _free_port()
        _run_app_bg(srv.make_app(), port)
        base = f'http://127.0.0.1:{port}'
        _wait_http(base + '/health', timeout=120)
        body = {'checkpoint': 'ckpt-v2'}
        # Disabled without SKYT_ADMIN_TOKEN.
        monkeypatch.delenv('SKYT_ADMIN_TOKEN', raising=False)
        assert req_lib.post(base + '/admin/weights', json=body,
                            timeout=30).status_code == 403
        monkeypatch.setenv('SKYT_ADMIN_TOKEN', 'sesame')
        hdr = {'Authorization': 'Bearer sesame'}
        # Unauthed / wrong bearer.
        assert req_lib.post(base + '/admin/weights', json=body,
                            timeout=30).status_code == 403
        assert req_lib.post(
            base + '/admin/weights', json=body, timeout=30,
            headers={'Authorization': 'Bearer wrong'}).status_code == 403
        # Malformed bodies.
        for bad in ([1, 2], {'checkpoint': ''}, {'checkpoint': 7},
                    {'checkpoint': 'x', 'version': 'seven'},
                    {'checkpoint': 'x', 'version': 0},
                    {'checkpoint': 'x', 'drain': 'yes'}, {}):
            r = req_lib.post(base + '/admin/weights', json=bad,
                             headers=hdr, timeout=30)
            assert r.status_code == 400, (bad, r.status_code, r.text)
        # Loader failure: clean 400, old weights intact.
        r = req_lib.post(base + '/admin/weights',
                         json={'checkpoint': 'missing'}, headers=hdr,
                         timeout=60)
        assert r.status_code == 400 and r.json()['weight_version'] == 1
        # The real swap.
        r = req_lib.post(base + '/admin/weights',
                         json={'checkpoint': 'ckpt-v2', 'version': 5},
                         headers=hdr, timeout=120)
        assert r.status_code == 200, r.text
        assert r.json()['weight_version'] == 5
        stats = req_lib.get(base + '/stats', timeout=30).json()
        assert stats['weight_version'] == 5
        # Concurrent swap -> 409 (hold the flight with a hang fault).
        faults.configure('weights.swap=hang,arg=1.5,count=1')
        codes = {}

        def push(name):
            codes[name] = req_lib.post(
                base + '/admin/weights',
                json={'checkpoint': 'ckpt-v2'}, headers=hdr,
                timeout=120).status_code

        t1 = threading.Thread(target=push, args=('a',))
        t1.start()
        time.sleep(0.5)
        push('b')
        t1.join(timeout=60)
        faults.reset()
        assert sorted(codes.values()) == [200, 409], codes
        # swap_back restores the boot version.
        r = req_lib.post(base + '/admin/weights',
                         json={'swap_back': True}, headers=hdr,
                         timeout=120)
        assert r.status_code == 200
        assert r.json()['weight_version'] == 5  # back to pre-'a' state
    finally:
        eng.stop()


# ------------------------------------------------- elastic reshard
def test_reshard_changes_layout_not_weights(debug_setup):
    """In-place reshard (docs/robustness.md "Elastic capacity"): the
    virtual-node layout moves at a tick boundary, the weight VALUES
    and VERSION do not — outputs are identical before/after, and the
    layout lands in the gauge + result metrics. reshard_back restores
    the replaced layout."""
    from skypilot_tpu.infer import weight_swap
    reg = metrics_lib.MetricsRegistry()
    eng = _make_engine(debug_setup, reg)
    eng.start()
    try:
        golden = _gen(eng, [1, 2, 3])
        mgr = weight_swap.WeightSwapManager(eng, registry=reg)
        assert eng.virtual_nodes == 1
        res = mgr.reshard(2)
        assert res['ok'] and res['virtual_nodes'] == 2, res
        assert res['from_nodes'] == 1 and not res['reshard_back']
        assert res['weight_version'] == 1
        assert eng.virtual_nodes == 2
        assert eng.weight_version == 1     # version did NOT move
        assert _gen(eng, [1, 2, 3]) == golden   # same weights
        text = reg.expose()
        assert 'skyt_infer_virtual_nodes 2' in text
        assert 'skyt_infer_reshards_total{result="ok"} 1' in text
        assert 'skyt_infer_reshard_seconds_count 1' in text
        info = mgr.info()
        assert info['virtual_nodes'] == 2
        assert info['reshard_back_available']
        assert info['last_reshard']['ok']
        back = mgr.reshard_back()
        assert back['ok'] and back['virtual_nodes'] == 1
        assert back['reshard_back']
        assert eng.virtual_nodes == 1
        assert _gen(eng, [1, 2, 3]) == golden
    finally:
        eng.stop()


def test_reshard_noop_is_idempotent(debug_setup):
    """Re-asserting the current layout is an ok no-op (the controller
    retries through restarts) and retains no rollback history."""
    from skypilot_tpu.infer import weight_swap
    reg = metrics_lib.MetricsRegistry()
    eng = _make_engine(debug_setup, reg)
    mgr = weight_swap.WeightSwapManager(eng, registry=reg)
    res = mgr.reshard(1)
    assert res['ok'] and res.get('noop')
    assert eng.virtual_nodes == 1
    with pytest.raises(weight_swap.WeightSwapError):
        mgr.reshard_back()      # nothing was replaced


def test_reshard_validation_rejects(debug_setup):
    """Bad layouts are rejected BEFORE anything is staged: non-int,
    < 1, and a target that cannot tile the mesh (neither divides the
    other). Old layout intact in every case."""
    import types

    from skypilot_tpu.infer import weight_swap
    reg = metrics_lib.MetricsRegistry()
    eng = _make_engine(debug_setup, reg)
    mgr = weight_swap.WeightSwapManager(eng, registry=reg)
    for bad, needle in (('two', 'integer'), (None, 'integer'),
                        (0, '>= 1'), (-3, '>= 1')):
        with pytest.raises(weight_swap.WeightSwapError) as ei:
            mgr.reshard(bad)
        assert needle in str(ei.value), (bad, str(ei.value))
    eng.mesh = types.SimpleNamespace(size=4)
    with pytest.raises(weight_swap.WeightSwapError) as ei:
        mgr.reshard(3)          # 3 vs 4: neither divides the other
    assert 'tile' in str(ei.value)
    assert eng.virtual_nodes == 1
    assert mgr.last_reshard is not None and not mgr.last_reshard['ok']


def test_reshard_fault_error_aborts_with_old_layout(debug_setup):
    """`reshard=error` aborts with the old layout intact and lands in
    skyt_infer_reshards_total{result="aborted"}; a clean retry then
    succeeds."""
    from skypilot_tpu.infer import weight_swap
    reg = metrics_lib.MetricsRegistry()
    eng = _make_engine(debug_setup, reg)
    eng.start()
    try:
        golden = _gen(eng, [4, 5, 6])
        mgr = weight_swap.WeightSwapManager(eng, registry=reg)
        faults.configure('reshard=error,count=1')
        with pytest.raises(weight_swap.WeightSwapError) as ei:
            mgr.reshard(2)
        assert 'old layout intact' in str(ei.value)
        assert eng.virtual_nodes == 1
        assert _gen(eng, [4, 5, 6]) == golden
        assert 'skyt_infer_reshards_total{result="aborted"} 1' \
            in reg.expose()
        assert not mgr.last_reshard['ok']
        res = mgr.reshard(2)    # fault exhausted: clean retry lands
        assert res['ok'] and eng.virtual_nodes == 2
    finally:
        eng.stop()


def test_reshard_shares_swap_single_flight(debug_setup):
    """One flight lock for the whole staging surface: a hung reshard
    409s BOTH a concurrent reshard and a concurrent weight swap (they
    ride the same engine slot and must never race)."""
    from skypilot_tpu.infer import weight_swap
    reg = metrics_lib.MetricsRegistry()
    eng = _make_engine(debug_setup, reg)
    _, _, _, p1 = debug_setup
    mgr = weight_swap.WeightSwapManager(eng, registry=reg)
    faults.configure('reshard=hang,arg=1.0,count=1')
    results = {}

    def slow():
        results['slow'] = mgr.reshard(2)

    th = threading.Thread(target=slow)
    th.start()
    time.sleep(0.3)                    # inside the hang window
    with pytest.raises(weight_swap.SwapInFlight):
        mgr.reshard(4)
    with pytest.raises(weight_swap.SwapInFlight):
        mgr.swap(params=p1)
    th.join(timeout=30)
    assert results['slow']['ok']
    assert eng.weight_version == 1     # the blocked swap never landed


def test_reshard_preserves_swap_back_history(debug_setup):
    """A reshard between a swap and its swap_back must not eat the
    weight-rollback retention: swap to v2, reshard, swap_back still
    restores v1 behavior (on the resharded layout)."""
    from skypilot_tpu.infer import weight_swap
    reg = metrics_lib.MetricsRegistry()
    eng = _make_engine(debug_setup, reg)
    _, _, _, p1 = debug_setup
    eng.start()
    try:
        golden = _gen(eng, [1, 2, 3])
        mgr = weight_swap.WeightSwapManager(eng, registry=reg)
        assert mgr.swap(params=p1)['weight_version'] == 2
        assert mgr.reshard(2)['ok']
        back = mgr.swap_back()
        assert back['weight_version'] == 1
        assert eng.virtual_nodes == 2  # layout survives the swap_back
        assert _gen(eng, [1, 2, 3]) == golden
    finally:
        eng.stop()


def test_reshard_flushes_prefix_cache(debug_setup):
    """Page tiling is layout-derived: a reshard flushes the HBM prefix
    registry conservatively (host/fleet KV tiers stay valid — same
    weight version — and re-promote on demand)."""
    from skypilot_tpu.infer import weight_swap
    reg = metrics_lib.MetricsRegistry()
    eng = _make_engine(debug_setup, reg, cache_mode='paged',
                       page_size=8, prefix_caching=True)
    eng.start()
    try:
        prompt = list(range(1, 18))
        _gen(eng, prompt)
        _gen(eng, prompt)
        assert eng.pool.prefix_cached_pages() >= 1
        mgr = weight_swap.WeightSwapManager(eng, registry=reg)
        res = mgr.reshard(2)
        assert res['flushed_prefix_pages'] >= 1
        assert eng.pool.prefix_cached_pages() == 0
    finally:
        eng.stop()


def test_admin_reshard_route_contract(debug_setup, monkeypatch):
    """403 unauthed / disabled, 400 malformed or un-tileable, 200 on a
    real reshard, 409 concurrent, reshard_back — mirrors the
    /admin/weights contract on the same single-flight."""
    import requests as req_lib

    from skypilot_tpu.infer import server as server_lib
    from chaos_helpers import _free_port, _run_app_bg, _wait_http
    reg = metrics_lib.MetricsRegistry()
    eng = _make_engine(debug_setup, reg)
    eng.start()
    try:
        srv = server_lib.InferenceServer(eng)
        port = _free_port()
        _run_app_bg(srv.make_app(), port)
        base = f'http://127.0.0.1:{port}'
        _wait_http(base + '/health', timeout=120)
        body = {'virtual_nodes': 2}
        monkeypatch.delenv('SKYT_ADMIN_TOKEN', raising=False)
        assert req_lib.post(base + '/admin/reshard', json=body,
                            timeout=30).status_code == 403
        monkeypatch.setenv('SKYT_ADMIN_TOKEN', 'sesame')
        hdr = {'Authorization': 'Bearer sesame'}
        assert req_lib.post(base + '/admin/reshard', json=body,
                            timeout=30).status_code == 403
        for bad in ([1], {}, {'virtual_nodes': 0},
                    {'virtual_nodes': 'two'}, {'virtual_nodes': True},
                    {'virtual_nodes': 2, 'drain': 'yes'}):
            r = req_lib.post(base + '/admin/reshard', json=bad,
                             headers=hdr, timeout=30)
            assert r.status_code == 400, (bad, r.status_code, r.text)
        # reshard_back before any reshard: clean 400, layout named.
        r = req_lib.post(base + '/admin/reshard',
                         json={'reshard_back': True}, headers=hdr,
                         timeout=60)
        assert r.status_code == 400 and r.json()['virtual_nodes'] == 1
        # The real reshard.
        r = req_lib.post(base + '/admin/reshard', json=body,
                         headers=hdr, timeout=120)
        assert r.status_code == 200, r.text
        assert r.json()['virtual_nodes'] == 2
        assert eng.virtual_nodes == 2
        # Concurrent -> 409 (hold the flight with a hang fault).
        faults.configure('reshard=hang,arg=1.5,count=1')
        codes = {}

        def push(name, payload):
            codes[name] = req_lib.post(
                base + '/admin/reshard', json=payload, headers=hdr,
                timeout=120).status_code

        t1 = threading.Thread(target=push,
                              args=('a', {'virtual_nodes': 4}))
        t1.start()
        time.sleep(0.5)
        push('b', {'virtual_nodes': 8})
        t1.join(timeout=60)
        faults.reset()
        assert sorted(codes.values()) == [200, 409], codes
        # reshard_back restores what the LAST reshard replaced.
        r = req_lib.post(base + '/admin/reshard',
                         json={'reshard_back': True}, headers=hdr,
                         timeout=120)
        assert r.status_code == 200 and r.json()['virtual_nodes'] == 2
        stats = req_lib.get(base + '/stats', timeout=30).json()
        assert stats['weight_version'] == 1    # never moved
    finally:
        eng.stop()


# ===================================== rollout orchestrator (no HTTP)
class _FakeTelemetry:
    def __init__(self):
        self.firing = []

    def alerts_firing(self):
        return list(self.firing)

    def maybe_scrape(self, *a, **k):
        return None

    def drop_target(self, *a, **k):
        return None


@pytest.fixture()
def rollout_mgr(tmp_state_dir, monkeypatch):
    """A ReplicaManager with 3 fake READY replicas, an injected swap
    transport, and a fake SLO-alert source."""
    del tmp_state_dir
    from skypilot_tpu.serve import replica_managers
    from skypilot_tpu.serve import serve_state
    from skypilot_tpu.serve import service_spec as spec_lib
    serve_state.reset_db_for_testing()
    monkeypatch.setenv('SKYT_ROLLOUT_BAKE_S', '0.2')
    spec = spec_lib.ServiceSpec(readiness_path='/', min_replicas=3,
                                weights='/ckpts/v1')
    serve_state.add_service('wsvc', spec, '/tmp/none.yaml', 1, 2)
    tel = _FakeTelemetry()
    mgr = replica_managers.ReplicaManager('wsvc', spec,
                                          '/tmp/none.yaml',
                                          telemetry=tel)
    for rid in (1, 2, 3):
        info = replica_managers.ReplicaInfo(
            replica_id=rid, cluster_name=f'wsvc-{rid}', version=1,
            status=serve_state.ReplicaStatus.READY,
            endpoint=f'http://127.0.0.1:{9000 + rid}')
        mgr.replicas[rid] = info
        mgr._save(info)  # pylint: disable=protected-access
    calls = []

    def fake_swap(info, payload, _responses={}):
        calls.append((info.replica_id, dict(payload)))
        fail = getattr(fake_swap, 'fail_on', None)
        if fail and info.replica_id in fail and \
                not payload.get('swap_back'):
            return False, 'injected swap failure'
        if getattr(fake_swap, 'fail_back', False) and \
                payload.get('swap_back'):
            return False, 'injected swap-back failure'
        return True, None

    fake_swap.calls = calls
    mgr._swap_fn = fake_swap  # pylint: disable=protected-access
    return mgr, spec, tel, fake_swap


def _bump_spec(spec, weights):
    return dataclasses.replace(spec, weights=weights)


def test_weights_only_diff():
    from skypilot_tpu.serve import service_spec as spec_lib
    a = spec_lib.ServiceSpec(readiness_path='/', min_replicas=2,
                             weights='/ckpts/v1')
    assert a.weights_only_diff(_bump_spec(a, '/ckpts/v2'))
    assert not a.weights_only_diff(a)                     # no change
    b = dataclasses.replace(a, weights='/ckpts/v2', min_replicas=3)
    assert not a.weights_only_diff(b)                     # more changed
    no_w = spec_lib.ServiceSpec(readiness_path='/', min_replicas=2)
    assert no_w.weights_only_diff(_bump_spec(no_w, '/ckpts/v2'))
    assert not a.weights_only_diff(
        dataclasses.replace(a, weights=None))             # weights unset
    # And the field round-trips through yaml config + schema.
    cfg = _bump_spec(a, '/ckpts/v9').to_yaml_config()
    assert spec_lib.ServiceSpec.from_yaml_config(cfg).weights == \
        '/ckpts/v9'


def test_rollout_canary_bake_fleet_commit(rollout_mgr):
    from skypilot_tpu.serve import serve_state
    mgr, spec, _tel, fake = rollout_mgr
    mgr.start_rolling_update(_bump_spec(spec, '/ckpts/v2'),
                             '/tmp/none.yaml', 2)
    assert mgr.rollout_status()['phase'] == 'canary'
    mgr.rollout_tick()                       # canary swaps replica 1
    ro = mgr.rollout_status()
    assert ro['phase'] == 'bake' and ro['canary'] == 1
    assert ro['updated'] == [1]
    assert mgr.replicas[1].weight_version == 2
    assert mgr.replicas[1].version == 1      # spec version NOT committed
    # Mixed-version window is visible to the LB sync.
    wv = mgr.ready_weight_versions()
    assert sorted(wv.values()) == [1, 1, 2]
    mgr.rollout_tick()                       # still baking
    assert mgr.rollout_status()['phase'] == 'bake'
    time.sleep(0.25)
    mgr.rollout_tick()                       # bake over -> rollout
    mgr.rollout_tick()                       # replica 2
    mgr.rollout_tick()                       # replica 3
    mgr.rollout_tick()                       # all updated -> commit
    ro = mgr.rollout_status()
    assert ro['phase'] == 'done', ro
    assert mgr.version == 2 and mgr.spec.weights == '/ckpts/v2'
    assert all(r.version == 2 and r.weight_version == 2
               for r in mgr.replicas.values())
    svc = serve_state.get_service('wsvc')
    assert svc['version'] == 2 and svc['spec'].weights == '/ckpts/v2'
    # One replica per tick, canary first, no swap_back calls.
    assert [c[0] for c in fake.calls] == [1, 2, 3]
    assert all(not c[1].get('swap_back') for c in fake.calls)
    assert mgr._m_rollouts.value('wsvc', 'done') == 1  # pylint: disable=protected-access


def test_rollout_canary_failure_rolls_back(rollout_mgr):
    mgr, spec, _tel, fake = rollout_mgr
    fake.fail_on = {1}
    mgr.start_rolling_update(_bump_spec(spec, '/ckpts/v2'),
                             '/tmp/none.yaml', 2)
    mgr.rollout_tick()                       # canary fails
    assert mgr.rollout_status()['phase'] == 'rollback'
    mgr.rollout_tick()                       # nothing updated -> done
    ro = mgr.rollout_status()
    assert ro['phase'] == 'rolled_back'
    assert 'swap failed' in ro['error']
    # Fleet untouched: baseline spec + weights everywhere.
    assert mgr.version == 1
    assert all(r.weight_version == 1 for r in mgr.replicas.values())
    # Only the canary was ever touched.
    assert [c[0] for c in fake.calls] == [1]


def test_rollout_bake_alert_rolls_back(rollout_mgr):
    mgr, spec, tel, fake = rollout_mgr
    mgr.start_rolling_update(_bump_spec(spec, '/ckpts/v2'),
                             '/tmp/none.yaml', 2)
    mgr.rollout_tick()                       # canary ok -> bake
    tel.firing = ['interactive']             # SLO burn alert fires
    mgr.rollout_tick()
    assert mgr.rollout_status()['phase'] == 'rollback'
    mgr.rollout_tick()                       # swap canary back
    ro = mgr.rollout_status()
    assert ro['phase'] == 'rolled_back'
    assert 'burn-rate alert' in ro['error']
    assert mgr.replicas[1].weight_version == 1
    # The canary got exactly one forward swap and one swap_back.
    assert [(c[0], bool(c[1].get('swap_back')))
            for c in fake.calls] == [(1, False), (1, True)]


def test_rollout_canary_not_ready_rolls_back(rollout_mgr):
    from skypilot_tpu.serve import serve_state
    mgr, spec, _tel, _fake = rollout_mgr
    mgr.start_rolling_update(_bump_spec(spec, '/ckpts/v2'),
                             '/tmp/none.yaml', 2)
    mgr.rollout_tick()
    mgr.replicas[1].status = serve_state.ReplicaStatus.NOT_READY
    mgr.rollout_tick()
    assert mgr.rollout_status()['phase'] == 'rollback'


def test_rollout_swapback_escalates_to_relaunch(rollout_mgr,
                                                monkeypatch):
    """A replica that refuses to swap back after SKYT_ROLLOUT_RETRIES
    is drained+relaunched on the (uncommitted) baseline."""
    mgr, spec, tel, fake = rollout_mgr
    monkeypatch.setenv('SKYT_ROLLOUT_RETRIES', '2')
    drained = []
    monkeypatch.setattr(
        mgr, 'terminate_replica',
        lambda rid, sync=False, drain=False: drained.append((rid,
                                                             drain)))
    mgr.start_rolling_update(_bump_spec(spec, '/ckpts/v2'),
                             '/tmp/none.yaml', 2)
    mgr.rollout_tick()                       # canary ok -> bake
    fake.fail_back = True
    tel.firing = ['batch']
    mgr.rollout_tick()                       # -> rollback
    mgr.rollout_tick()                       # back attempt 1 fails
    mgr.rollout_tick()                       # attempt 2 fails -> drain
    mgr.rollout_tick()                       # nothing left -> terminal
    ro = mgr.rollout_status()
    assert ro['phase'] == 'rolled_back'
    assert drained == [(1, True)]


def test_rollout_resume_semantics(rollout_mgr, monkeypatch):
    """Persisted phases survive a controller restart: canary/bake
    conservatively roll back; 'rollout' resumes and commits."""
    from skypilot_tpu.serve import replica_managers
    from skypilot_tpu.serve import serve_state
    from skypilot_tpu.serve import service_spec as spec_lib
    mgr, spec, _tel, fake = rollout_mgr
    mgr.start_rolling_update(_bump_spec(spec, '/ckpts/v2'),
                             '/tmp/none.yaml', 2)
    mgr.rollout_tick()                       # canary -> bake; persisted
    assert serve_state.get_rollout('wsvc')['phase'] == 'bake'
    # "Restarted" managers reload the persisted replicas as-is: the
    # fake replicas have no cluster records, so the real adoption
    # ladder would reap them before the resume logic runs (the
    # adoption x rollout COMPOSITION has its own test below and a
    # real-process drill in test_chaos_*.py).
    monkeypatch.setattr(replica_managers.ReplicaManager,
                        '_reconcile_restart', lambda self: None)

    def new_mgr():
        m = replica_managers.ReplicaManager('wsvc', spec,
                                            '/tmp/none.yaml',
                                            telemetry=_FakeTelemetry())
        m._swap_fn = fake  # pylint: disable=protected-access
        return m

    # "Restart" #1: mid-bake -> rollback.
    mgr2 = new_mgr()
    ro = mgr2.rollout_status()
    assert ro['phase'] == 'rollback' and 'restarted' in ro['error']
    mgr2.rollout_tick()                      # roll the canary back
    assert mgr2.rollout_status()['phase'] == 'rolled_back'
    assert serve_state.get_rollout('wsvc')['phase'] == 'rolled_back'

    # Fresh rollout driven to phase 'rollout', then "restart" #2:
    # resumes where it stopped and commits.
    mgr2.start_rolling_update(_bump_spec(spec, '/ckpts/v3'),
                              '/tmp/none.yaml', 3)
    mgr2.rollout_tick()                      # canary
    time.sleep(0.25)
    mgr2.rollout_tick()                      # bake over -> rollout
    mgr2.rollout_tick()                      # replica 2 swapped
    assert serve_state.get_rollout('wsvc')['phase'] == 'rollout'
    mgr3 = new_mgr()
    assert mgr3.rollout_status()['phase'] == 'rollout'
    assert mgr3.rollout_status()['updated'] == [1, 2]
    mgr3.rollout_tick()                      # replica 3
    mgr3.rollout_tick()                      # commit
    assert mgr3.rollout_status()['phase'] == 'done'
    assert mgr3.version == 3
    svc = serve_state.get_service('wsvc')
    assert svc['version'] == 3 and svc['spec'].weights == '/ckpts/v3'
    assert isinstance(svc['spec'], spec_lib.ServiceSpec)


def test_adoption_guard_spares_rollout_versions(rollout_mgr):
    """A replica one version AHEAD of the committed spec (mid-commit
    crash window) is NOT reaped as stale when the recorded rollout
    names that version."""
    from skypilot_tpu.serve import replica_managers
    mgr, spec, _tel, _fake = rollout_mgr
    mgr.start_rolling_update(_bump_spec(spec, '/ckpts/v2'),
                             '/tmp/none.yaml', 2)
    info = mgr.replicas[2]
    info.version = 2                         # ahead of mgr.version == 1
    assert mgr._orphan_reason(info) != 'stale_spec_version'  # pylint: disable=protected-access
    # Without a recorded rollout the same skew IS stale.
    mgr._rollout = None  # pylint: disable=protected-access
    assert mgr._orphan_reason(info) == 'stale_spec_version'  # pylint: disable=protected-access
    # And a version NOT named by the rollout stays stale too.
    mgr._rollout = replica_managers.RolloutState(  # pylint: disable=protected-access
        phase='rollout', target_version=4, baseline_version=3,
        checkpoint='/ckpts/v4', baseline_checkpoint=None,
        spec_config={}, task_yaml='', started_at=0.0)
    assert mgr._orphan_reason(info) == 'stale_spec_version'  # pylint: disable=protected-access


def test_rollout_state_persistence_roundtrip(tmp_state_dir):
    del tmp_state_dir
    from skypilot_tpu.serve import serve_state
    from skypilot_tpu.serve import service_spec as spec_lib
    serve_state.reset_db_for_testing()
    spec = spec_lib.ServiceSpec(readiness_path='/')
    serve_state.add_service('psvc', spec, '/tmp/none.yaml', 1, 2)
    assert serve_state.get_rollout('psvc') is None
    state = {'phase': 'bake', 'target_version': 2, 'updated': [1]}
    serve_state.set_rollout('psvc', state)
    assert serve_state.get_rollout('psvc') == state
    serve_state.set_rollout('psvc', None)
    assert serve_state.get_rollout('psvc') is None


def test_concurrent_rollout_rejected(rollout_mgr):
    from skypilot_tpu import exceptions
    mgr, spec, _tel, _fake = rollout_mgr
    mgr.start_rolling_update(_bump_spec(spec, '/ckpts/v2'),
                             '/tmp/none.yaml', 2)
    with pytest.raises(exceptions.SkyTpuError):
        mgr.start_rolling_update(_bump_spec(spec, '/ckpts/v3'),
                                 '/tmp/none.yaml', 3)


def test_publish_checkpoint_atomic(tmp_path, debug_setup):
    """publish_checkpoint stages + renames: the destination is always
    absent or complete, and republish replaces in place."""
    import os

    from skypilot_tpu.models import weights as weights_lib
    from skypilot_tpu.train import push_weights
    cfg, _model, p0, p1 = debug_setup
    out = str(tmp_path / 'ckpt')
    got = push_weights.publish_checkpoint(cfg, p0, out)
    assert got == out
    assert sorted(os.listdir(out)) == ['config.json',
                                       'model.safetensors']
    first = open(os.path.join(out, 'model.safetensors'), 'rb').read()
    push_weights.publish_checkpoint(cfg, p1, out)   # replace in place
    second = open(os.path.join(out, 'model.safetensors'), 'rb').read()
    assert first != second
    assert not [d for d in os.listdir(tmp_path)
                if 'staging' in d or '.old' in d]
    # The published dir round-trips through the swap loader path.
    cfg2 = weights_lib.load_config(out, remat=False,
                                   param_dtype='float32',
                                   dtype='float32')
    assert cfg2.n_layers == cfg.n_layers


# ===================================== reshard orchestrator (no HTTP)
def _wire_reshard(rollout_mgr):
    """Point the rollout fixture's manager at an injectable reshard
    transport (same shape as the swap one)."""
    mgr, spec, tel, _fake = rollout_mgr
    calls = []

    def fake_reshard(info, payload):
        calls.append((info.replica_id, dict(payload)))
        fail = getattr(fake_reshard, 'fail_on', None)
        if fail and info.replica_id in fail and \
                not payload.get('reshard_back'):
            return False, 'injected reshard failure'
        if getattr(fake_reshard, 'fail_back', None) and \
                info.replica_id in fake_reshard.fail_back and \
                payload.get('reshard_back'):
            return False, 'injected reshard-back failure'
        return True, None

    fake_reshard.calls = calls
    mgr._reshard_fn = fake_reshard  # pylint: disable=protected-access
    return mgr, spec, tel, fake_reshard


def test_reshard_orchestrator_happy_path(rollout_mgr):
    """start -> one replica per tick in id order -> done; the fleet
    outcome and per-call results land in the service metrics."""
    mgr, _spec, _tel, fake = _wire_reshard(rollout_mgr)
    st = mgr.start_reshard(4)
    assert st['phase'] == 'reshard' and st['target_nodes'] == 4
    mgr.reshard_tick()
    assert mgr.reshard_status()['updated'] == [1]
    mgr.reshard_tick()
    mgr.reshard_tick()
    assert mgr.reshard_status()['updated'] == [1, 2, 3]
    mgr.reshard_tick()                     # no candidates left -> done
    st = mgr.reshard_status()
    assert st['phase'] == 'done' and st['error'] is None
    assert [c[0] for c in fake.calls] == [1, 2, 3]
    assert all(c[1] == {'virtual_nodes': 4} for c in fake.calls)
    assert mgr._m_reshards.value('wsvc', 'done') == 1  # pylint: disable=protected-access
    assert mgr._m_reshard_calls.value('wsvc', 'ok') == 3  # pylint: disable=protected-access
    # Terminal state: a new reshard may start.
    assert mgr.start_reshard(2)['phase'] == 'reshard'


def test_reshard_orchestrator_rolls_back_newest_first(rollout_mgr,
                                                      monkeypatch):
    """A replica that keeps refusing the new layout burns the retry
    budget; the already-resharded set rolls back NEWEST FIRST and the
    run ends rolled_back with the failure named."""
    monkeypatch.setenv('SKYT_ROLLOUT_RETRIES', '2')
    mgr, _spec, _tel, fake = _wire_reshard(rollout_mgr)
    fake.fail_on = {3}
    mgr.start_reshard(2)
    mgr.reshard_tick()                     # 1 ok
    mgr.reshard_tick()                     # 2 ok
    mgr.reshard_tick()                     # 3 fails (1/2)
    assert mgr.reshard_status()['phase'] == 'reshard'
    mgr.reshard_tick()                     # 3 fails (2/2) -> rollback
    assert mgr.reshard_status()['phase'] == 'rollback'
    mgr.reshard_tick()                     # rolls 2 then 1 back
    st = mgr.reshard_status()
    assert st['phase'] == 'rolled_back'
    assert 'replica 3' in st['error']
    backs = [c[0] for c in fake.calls if c[1].get('reshard_back')]
    assert backs == [2, 1]                 # newest first
    assert mgr._m_reshards.value('wsvc', 'rolled_back') == 1  # pylint: disable=protected-access
    # Nobody was drained or relaunched over a layout problem.
    from skypilot_tpu.serve import serve_state
    assert all(r.status is serve_state.ReplicaStatus.READY
               for r in mgr.replicas.values())


def test_reshard_rollback_skips_stubborn_replica(rollout_mgr,
                                                 monkeypatch):
    """A replica that refuses even the rollback is SKIPPED (layout
    left as-is), never drained: wrong layout is degraded throughput,
    not an outage worth a capacity dip."""
    monkeypatch.setenv('SKYT_ROLLOUT_RETRIES', '1')
    mgr, _spec, _tel, fake = _wire_reshard(rollout_mgr)
    fake.fail_on = {3}
    fake.fail_back = {2}
    mgr.start_reshard(2)
    mgr.reshard_tick()                     # 1 ok
    mgr.reshard_tick()                     # 2 ok
    mgr.reshard_tick()                     # 3 fails -> rollback
    assert mgr.reshard_status()['phase'] == 'rollback'
    mgr.reshard_tick()                     # 2 refuses (1/1) -> skipped
    mgr.reshard_tick()                     # 1 rolls back -> rolled_back
    st = mgr.reshard_status()
    assert st['phase'] == 'rolled_back', st
    from skypilot_tpu.serve import serve_state
    assert all(r.status is serve_state.ReplicaStatus.READY
               for r in mgr.replicas.values())
    assert mgr._m_reshard_calls.value('wsvc', 'rollback_error') >= 1  # pylint: disable=protected-access


def test_reshard_validation_and_concurrency(rollout_mgr):
    from skypilot_tpu import exceptions
    mgr, _spec, _tel, _fake = _wire_reshard(rollout_mgr)
    for bad in ('two', None, 0, -1):
        with pytest.raises(exceptions.SkyTpuError):
            mgr.start_reshard(bad)
    mgr.start_reshard(2)
    with pytest.raises(exceptions.SkyTpuError):
        mgr.start_reshard(4)               # one at a time


def test_reshard_and_rollout_are_mutually_exclusive(rollout_mgr):
    """Both ride the replicas' single-flight swap slot: a reshard
    refuses while a rollout is active, and vice versa."""
    from skypilot_tpu import exceptions
    mgr, spec, _tel, _fake = _wire_reshard(rollout_mgr)
    mgr.start_rolling_update(_bump_spec(spec, '/ckpts/v2'),
                             '/tmp/none.yaml', 2)
    with pytest.raises(exceptions.SkyTpuError) as ei:
        mgr.start_reshard(2)
    assert 'rolling update' in str(ei.value)
    # Finish the rollout, then invert the order.
    mgr.rollout_tick()                     # canary
    time.sleep(0.25)
    for _ in range(4):
        mgr.rollout_tick()
    assert mgr.rollout_status()['phase'] == 'done'
    mgr.start_reshard(2)
    with pytest.raises(exceptions.SkyTpuError) as ei:
        mgr.start_rolling_update(_bump_spec(spec, '/ckpts/v3'),
                                 '/tmp/none.yaml', 3)
    assert 'reshard' in str(ei.value)
