"""Tiered prefix cache (infer/kv_tier.py; docs/performance.md "Tiered
prefix cache"): host-store LRU semantics, transfer codec roundtrip,
promote-vs-recompute golden stream equality, weight-version
invalidation across tiers, and kv.fetch fault descent to recompute."""
import dataclasses
import threading
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from skypilot_tpu.infer import engine as engine_lib
from skypilot_tpu.infer import kv_tier as kv_tier_lib
from skypilot_tpu.infer import paged_cache
from skypilot_tpu.models import llama
from skypilot_tpu.utils import faults
from skypilot_tpu.utils import metrics as metrics_lib

# Engine tests compile the debug model (amortized by the XLA cache).
pytestmark = pytest.mark.heavy


def _h(i: int) -> bytes:
    return bytes([i]) * 16


def _arrays(nbytes: int = 100) -> dict:
    return {'k': np.full(nbytes, 7, np.uint8)}


# ------------------------------------------------------- transfer codec
class TestCodec:
    def test_roundtrip_int8_with_scales(self):
        pages = []
        rng = np.random.default_rng(0)
        for i in range(3):
            pages.append((_h(i), {
                'k': rng.integers(-128, 127, (2, 1, 4, 8)).astype(np.int8),
                'v': rng.integers(-128, 127, (2, 1, 4, 8)).astype(np.int8),
                'k_scale': rng.random((2, 1, 4)).astype(np.float32),
                'v_scale': rng.random((2, 1, 4)).astype(np.float32),
            }))
        blob = kv_tier_lib.encode_pages(pages, weight_version=5)
        version, out = kv_tier_lib.decode_pages(blob)
        assert version == 5
        assert [h for h, _ in out] == [h for h, _ in pages]
        for (_, a), (_, b) in zip(pages, out):
            assert sorted(a) == sorted(b)
            for name in a:
                assert b[name].dtype == a[name].dtype
                assert b[name].shape == a[name].shape
                assert b[name].tobytes() == a[name].tobytes()

    def test_roundtrip_bfloat16(self):
        import ml_dtypes
        a = np.arange(32, dtype=np.float32).astype(ml_dtypes.bfloat16)
        blob = kv_tier_lib.encode_pages(
            [(_h(1), {'k': a.reshape(2, 16)})], weight_version=1)
        _, out = kv_tier_lib.decode_pages(blob)
        got = out[0][1]['k']
        assert got.dtype == np.dtype(ml_dtypes.bfloat16)
        assert got.tobytes() == a.reshape(2, 16).tobytes()

    def test_malformed_raises(self):
        good = kv_tier_lib.encode_pages(
            [(_h(1), _arrays())], weight_version=1)
        for bad in (b'', b'junk', b'XXXX' + good[4:],
                    good[:10], good[:-5]):
            with pytest.raises(ValueError):
                kv_tier_lib.decode_pages(bad)


# ----------------------------------------------------------- host store
class TestHostStore:
    def test_lru_byte_budget(self):
        store = kv_tier_lib.HostKVStore(budget_bytes=250)
        assert store.put(_h(1), 1, _arrays(100))
        assert store.put(_h(2), 1, _arrays(100))
        # Refresh h1's recency, then overflow: h2 (now LRU) evicts.
        assert store.get(_h(1), 1) is not None
        assert store.put(_h(3), 1, _arrays(100))
        assert store.get(_h(2), 1) is None
        assert store.get(_h(1), 1) is not None
        assert store.get(_h(3), 1) is not None
        assert store.stats['evictions'] == 1
        assert store.nbytes() <= 250
        # An entry above the whole budget is dropped, not stored.
        assert not store.put(_h(4), 1, _arrays(1000))
        assert store.stats['put_drops'] == 1
        assert len(store) == 2

    def test_version_gate(self):
        store = kv_tier_lib.HostKVStore(budget_bytes=10_000)
        store.put(_h(1), 1, _arrays())
        store.put(_h(2), 1, _arrays())
        store.put(_h(3), 2, _arrays())
        # Lookup is version-checked even before any set_version.
        assert store.get(_h(1), 2) is None
        assert store.get(_h(1), 1) is not None
        # Swap: prune other versions AND gate in-flight old spills.
        assert store.set_version(2) == 2
        assert store.stats['invalidated'] == 2
        assert len(store) == 1
        assert not store.put(_h(4), 1, _arrays())   # stale spill
        assert store.put(_h(5), 2, _arrays())
        assert store.contains(_h(3), 2)
        assert not store.contains(_h(1), 1)

    def test_leading_run(self):
        store = kv_tier_lib.HostKVStore(budget_bytes=10_000)
        for i in (1, 2, 4):
            store.put(_h(i), 1, _arrays())
        run = store.run([_h(1), _h(2), _h(3), _h(4)], 1)
        assert [h for h, _ in run] == [_h(1), _h(2)]
        assert store.run([_h(9)], 1) == []


# ------------------------------------------------- pool splice + spill
class TestPoolSplice:
    def _pool(self):
        cfg = paged_cache.PagedConfig(page_size=4, n_pages=9,
                                      max_pages_per_slot=4)
        return paged_cache.PagePool(cfg, n_layers=2, kv_heads=2,
                                    head_dim=8, num_slots=3,
                                    dtype=jnp.float32)

    def test_install_prefix_free_list_only(self):
        pool = self._pool()
        h = paged_cache.page_hashes(list(range(1, 9)), 4)
        pages = pool.install_prefix(h)
        assert pages is not None and len(pages) == 2
        for hh, p in zip(h, pages):
            assert pool.registered_page(hh) == p
        # Installed pages are shared by the normal reserve path.
        row, matched = pool.try_reserve_prefix(0, 8, h)
        assert row is not None and matched == 2
        # Re-installing a registered run is refused (caller promotes
        # only genuinely missing hashes).
        assert pool.install_prefix(h) is None
        # A run larger than the free list is refused whole — promotion
        # never evicts published pages.
        big = [bytes([i]) * 16 for i in range(50)]
        assert pool.install_prefix(big) is None
        pool.release(0)

    def test_on_evict_hook_fires_with_hash(self):
        pool = self._pool()
        seen = []
        pool.on_evict = lambda page, h: seen.append((page, h))
        h = paged_cache.page_hashes(list(range(1, 9)), 4)
        pool.try_reserve_prefix(0, 8, ())
        pool.publish(0, h)
        pool.release(0)
        # Exhaust the free list: the warm published pages are
        # reclaimed LRU-first and the hook sees each (page, hash).
        pool.try_reserve_prefix(1, 16, ())
        pool.try_reserve_prefix(2, 16, ())
        assert pool.prefix_stats['evictions'] >= 2
        assert {hh for _, hh in seen} == set(h)


# ---------------------------------------------------- engine fixtures
@pytest.fixture(scope='module')
def kv_setup():
    cfg = dataclasses.replace(llama.CONFIGS['debug'], max_seq_len=128)
    model = llama.LlamaModel(cfg)
    zeros = jnp.zeros((1, 8), jnp.int32)
    p0 = jax.jit(model.init)(jax.random.PRNGKey(0), zeros)
    p1 = jax.jit(model.init)(jax.random.PRNGKey(7), zeros)
    return cfg, model, p0, p1


def _make_engine(kv_setup, monkeypatch, tier='host', **kw):
    monkeypatch.setenv('SKYT_KV_TIER', tier)
    _, model, p0, _ = kv_setup
    reg = metrics_lib.MetricsRegistry()
    defaults = dict(num_slots=2, max_seq_len=128, decode_chunk=2,
                    cache_mode='paged', prefix_caching=True,
                    pool_tokens=512, metrics_registry=reg)
    defaults.update(kw)
    params = defaults.pop('params', p0)
    return engine_lib.InferenceEngine(model, params, **defaults), reg


def _prompt(i: int):
    # 100 tokens = one full 64-token page (+ remainder) per prompt,
    # all distinct so ten of them overflow the 8-usable-page pool.
    return [(i * 37 + j) % 97 + 3 for j in range(100)]


def _gen(eng, tokens, n=8, kv_peer=None, **sp):
    _, q = eng.submit(list(tokens),
                      engine_lib.SamplingParams(max_new_tokens=n, **sp),
                      kv_peer=kv_peer)
    out = []
    while True:
        t = q.get(timeout=300)
        if t is None:
            return out
        out.append(t)


def _fill_until_evicted(eng, first_prompt, start=1, count=9):
    """Submit distinct prompts until first_prompt's lead page is
    evicted (LRU: oldest released goes first), then drain the spill
    writer."""
    for i in range(start, start + count):
        _gen(eng, _prompt(i))
    h0 = paged_cache.page_hashes(first_prompt, eng.pool.cfg.page_size)[0]
    assert eng.pool.registered_page(h0) is None, \
        'expected the first prompt\'s page to be LRU-evicted'
    assert eng.pool.prefix_stats['evictions'] > 0
    assert eng.kv_tier.drain()
    return h0


# --------------------------------------- golden: promote == recompute
class TestGoldenPromotion:
    @pytest.mark.parametrize('kv_dtype', ['auto', 'int8'])
    def test_promote_matches_recompute(self, kv_setup, monkeypatch,
                                       kv_dtype):
        eng, reg = _make_engine(kv_setup, monkeypatch,
                                kv_dtype=kv_dtype)
        eng.start()
        try:
            prompt = _prompt(0)
            golden_greedy = _gen(eng, prompt)
            # Sampling keys mix in the req_id (seed + req_id), so the
            # rerun compensates its seed to hit the SAME key — stream
            # equality then holds iff the promoted KV bytes match.
            rid1 = eng._next_id
            golden_seeded = _gen(eng, prompt, temperature=0.8,
                                 seed=1000)
            h0 = _fill_until_evicted(eng, prompt)
            assert eng.kv_tier.host.contains(h0, eng.weight_version)
            # Seeded rerun first: its admission promotes host->device.
            rid2 = eng._next_id
            assert _gen(eng, prompt, temperature=0.8,
                        seed=1000 + rid1 - rid2) == golden_seeded
            assert eng.kv_tier.stats['promotions'] >= 1
            assert eng.kv_tier.stats['promoted_pages'] >= 1
            # Greedy rerun now HBM-hits the promoted page. Un-throttle
            # the ~4Hz gauge refresh first so its ticks fold the
            # promotion delta into the per-tier counter even when the
            # warm-cache reruns all fit inside one throttle window.
            eng._last_gauge_t = 0.0
            assert _gen(eng, prompt) == golden_greedy
            # Satellite telemetry: eviction counter, occupancy gauges,
            # and the per-tier hit counter are exported.
            text = reg.expose()
            assert 'skyt_infer_prefix_cache_evictions_total' in text
            assert 'skyt_infer_prefix_cache_pages' in text
            assert 'skyt_infer_prefix_cache_occupancy' in text
            assert 'skyt_infer_kv_tier_hit_pages_total{tier="host"}' \
                in text
        finally:
            eng.stop()


# -------------------------------------------- swap invalidation (L2/L3)
class TestSwapInvalidation:
    def test_swap_empties_host_store_and_gates_spills(self, kv_setup,
                                                      monkeypatch):
        _, _, _, p1 = kv_setup
        eng, _ = _make_engine(kv_setup, monkeypatch)
        eng.start()
        try:
            prompt = _prompt(0)
            _gen(eng, prompt)
            _fill_until_evicted(eng, prompt)
            assert len(eng.kv_tier.host) > 0
            old_version = eng.weight_version
            res = eng.request_weight_swap(p1, drain=True, timeout=60)
            assert res['weight_version'] == old_version + 1
            # Every old-version entry pruned; late spills from the old
            # weights can never land.
            assert len(eng.kv_tier.host) == 0
            assert eng.kv_tier.host.stats['invalidated'] > 0
            assert not eng.kv_tier.host.put(
                _h(1), old_version, _arrays())
        finally:
            eng.stop()

    def test_fetch_rejects_peer_version_mismatch(self, monkeypatch):
        mgr = kv_tier_lib.KVTierManager('fleet', host_bytes=10_000,
                                        fetch_max_pages=8,
                                        fetch_timeout_s=1.0)
        monkeypatch.setattr(
            kv_tier_lib, 'fetch_pages',
            lambda *a, **k: (999, [(_h(1), _arrays())]))
        with pytest.raises(RuntimeError, match='weight_version'):
            mgr.fetch_into_host('http://peer', [_h(1)], 1, 'tok')
        assert len(mgr.host) == 0

    def test_fetch_rejects_pool_layout_mismatch(self, monkeypatch):
        """A well-formed SKV1 payload whose arrays do not match the
        local pool layout (misconfigured or malicious peer — other
        quantization, page size, or bogus keys) must fail the fetch
        (-> recompute) BEFORE anything enters the host store, never
        reach the engine-loop install path."""
        mgr = kv_tier_lib.KVTierManager('fleet', host_bytes=10_000,
                                        fetch_max_pages=8,
                                        fetch_timeout_s=1.0)
        mgr.set_page_layout({'k': (np.dtype(np.int8), (2, 4, 8))})
        for bad in ({'k': np.zeros((2, 4, 8), np.int16)},    # dtype
                    {'k': np.zeros((2, 4, 4), np.int8)},     # shape
                    {'v': np.zeros((2, 4, 8), np.int8)},     # keys
                    {'k': np.zeros((2, 4, 8), np.int8),
                     'extra': np.zeros(1, np.int8)}):        # extra key
            monkeypatch.setattr(
                kv_tier_lib, 'fetch_pages',
                lambda *a, bad=bad, **k: (1, [(_h(1), bad)]))
            with pytest.raises(ValueError, match='page'):
                mgr.fetch_into_host('http://peer', [_h(1)], 1, 'tok')
            assert len(mgr.host) == 0
        # A matching page passes; a later bad page in the same run
        # still fails the whole transfer.
        ok = {'k': np.zeros((2, 4, 8), np.int8)}
        monkeypatch.setattr(kv_tier_lib, 'fetch_pages',
                            lambda *a, **k: (1, [(_h(1), ok)]))
        assert mgr.fetch_into_host('http://peer', [_h(1)], 1,
                                   'tok') == 1
        assert mgr.host.contains(_h(1), 1)
        # Unconfigured layout (standalone use) skips the check.
        mgr2 = kv_tier_lib.KVTierManager('fleet', host_bytes=10_000,
                                         fetch_max_pages=8,
                                         fetch_timeout_s=1.0)
        monkeypatch.setattr(
            kv_tier_lib, 'fetch_pages',
            lambda *a, **k: (1, [(_h(2), _arrays())]))
        assert mgr2.fetch_into_host('http://peer', [_h(2)], 1,
                                    'tok') == 1

    def test_host_store_discard(self):
        store = kv_tier_lib.HostKVStore(budget_bytes=10_000)
        store.put(_h(1), 1, _arrays(100))
        store.put(_h(2), 1, _arrays(100))
        store.discard(_h(1))
        store.discard(_h(9))   # absent: no-op
        assert not store.contains(_h(1), 1)
        assert store.contains(_h(2), 1)
        assert store.nbytes() == 100


# ------------------------------------------- kv.fetch fault -> recompute
class TestFetchFaultDescent:
    def test_fetch_failures_degrade_to_recompute(self, kv_setup,
                                                 monkeypatch):
        monkeypatch.setenv('SKYT_KV_FETCH_TIMEOUT_S', '0.2')
        eng, _ = _make_engine(kv_setup, monkeypatch, tier='fleet')
        eng.start()
        try:
            # Injected error: the fetch worker raises, the parked
            # request re-admits and recomputes — tokens still flow.
            faults.configure('kv.fetch=error')
            out = _gen(eng, _prompt(20), kv_peer='http://127.0.0.1:9')
            assert len(out) == 8
            assert eng.kv_tier.stats['fetch_errors'] >= 1
            faults.reset()
            # Real transport failure (dead peer), same descent.
            errs = eng.kv_tier.stats['fetch_errors']
            out = _gen(eng, _prompt(21), kv_peer='http://127.0.0.1:9')
            assert len(out) == 8
            assert eng.kv_tier.stats['fetch_errors'] > errs
            # Hang: the engine abandons the wait at its deadline and
            # recomputes; the stale worker result is discarded.
            faults.configure('kv.fetch=hang,arg=5')
            t0 = time.monotonic()
            out = _gen(eng, _prompt(22), kv_peer='http://127.0.0.1:9')
            assert len(out) == 8
            assert time.monotonic() - t0 < 30
        finally:
            faults.reset()
            eng.stop()


# ------------------------------------- /kv/prefix endpoint + fleet e2e
def _run_app_bg(app, port):
    import asyncio

    from aiohttp import web

    def runner():
        loop = asyncio.new_event_loop()
        asyncio.set_event_loop(loop)
        r = web.AppRunner(app)
        loop.run_until_complete(r.setup())
        loop.run_until_complete(
            web.TCPSite(r, '127.0.0.1', port).start())
        loop.run_forever()
    threading.Thread(target=runner, daemon=True).start()


@pytest.mark.integration
class TestFleetTransfer:
    def test_endpoint_contract_and_fleet_golden(self, kv_setup,
                                                monkeypatch):
        import requests

        from skypilot_tpu.infer import server as server_lib
        from chaos_helpers import _free_port, _wait_http

        # Donor replica: engine + real HTTP surface.
        donor, _ = _make_engine(kv_setup, monkeypatch, tier='host')
        donor.start()
        fetcher = None
        try:
            prompt = _prompt(0)
            golden = _gen(donor, prompt)
            srv = server_lib.InferenceServer(donor)
            port = _free_port()
            _run_app_bg(srv.make_app(), port)
            base = f'http://127.0.0.1:{port}'
            _wait_http(base + '/health', timeout=120)
            h0 = paged_cache.page_hashes(
                prompt, donor.pool.cfg.page_size)[0]

            # Auth/validation contract.
            monkeypatch.delenv('SKYT_ADMIN_TOKEN', raising=False)
            assert requests.get(base + '/kv/prefix',
                                params={'hashes': h0.hex()},
                                timeout=30).status_code == 403
            monkeypatch.setenv('SKYT_ADMIN_TOKEN', 'sesame')
            hdr = {'Authorization': 'Bearer sesame'}
            assert requests.get(base + '/kv/prefix',
                                params={'hashes': h0.hex()},
                                timeout=30).status_code == 403
            for bad in ('', 'zz', 'abcd'):
                assert requests.get(
                    base + '/kv/prefix', params={'hashes': bad},
                    headers=hdr, timeout=30).status_code == 400
            assert requests.get(
                base + '/kv/prefix',
                params={'hashes': (b'\x99' * 16).hex()},
                headers=hdr, timeout=30).status_code == 404

            # Resident run: 200 + decodable payload, version stamped.
            r = requests.get(base + '/kv/prefix',
                             params={'hashes': h0.hex()},
                             headers=hdr, timeout=30)
            assert r.status_code == 200
            assert int(r.headers['X-Weight-Version']) == \
                donor.weight_version
            version, pages = kv_tier_lib.decode_pages(r.content)
            assert version == donor.weight_version
            assert [h for h, _ in pages] == [h0]

            # fetch_pages helper sees the same bytes.
            version2, pages2 = kv_tier_lib.fetch_pages(
                base, [h0], 'sesame', timeout_s=30, max_pages=4)
            assert version2 == version
            assert pages2[0][1]['k'].tobytes() == \
                pages[0][1]['k'].tobytes()

            # Fleet e2e: a cold peer engine warms from the donor and
            # streams byte-identical tokens.
            fetcher, _ = _make_engine(kv_setup, monkeypatch,
                                      tier='fleet')
            fetcher.start()
            assert _gen(fetcher, prompt, kv_peer=base) == golden
            assert fetcher.kv_tier.stats['fetched_pages'] >= 1
            assert fetcher.kv_tier.stats['promotions'] >= 1
        finally:
            if fetcher is not None:
                fetcher.stop()
            donor.stop()


# --------------------------------------------- replica-side peer check
def test_kv_peer_from_validates_against_known_replicas(monkeypatch):
    """The replica half of the X-KV-Peer defense (the LB strips the
    client-supplied header; this guards direct-to-replica callers):
    only loopback peers or SKYT_KV_PEER_ALLOW-listed scheme://host:port
    are accepted — the engine fetches from the peer with its admin
    bearer token, so an arbitrary URL would exfiltrate it."""
    from skypilot_tpu.infer import server as server_lib

    class _Req:
        def __init__(self, peer):
            self.headers = {} if peer is None else {'X-KV-Peer': peer}

    peer_from = server_lib.InferenceServer._kv_peer_from
    monkeypatch.delenv('SKYT_KV_PEER_ALLOW', raising=False)
    # Loopback (single-host fleets, the chaos drill) always passes.
    assert peer_from(_Req('http://127.0.0.1:8001')) == \
        'http://127.0.0.1:8001'
    assert peer_from(_Req('http://localhost:8001')) is not None
    # Everything else is dropped, never an error.
    for bad in (None, '', 'not-a-url', 'http://', 'ftp://127.0.0.1:1',
                'http://evil.example:8001', 'https://10.0.0.5:8001',
                'http://127.0.0.1:notaport',
                'http://127.0.0.1:' + '9' * 510):
        assert peer_from(_Req(bad)) is None
    # Fleets spanning hosts list replica base URLs explicitly;
    # matching is exact on scheme+host+port.
    monkeypatch.setenv('SKYT_KV_PEER_ALLOW',
                       'http://10.0.0.5:8001, http://10.0.0.6:8001,')
    assert peer_from(_Req('http://10.0.0.5:8001')) is not None
    assert peer_from(_Req('http://10.0.0.6:8001')) is not None
    assert peer_from(_Req('http://127.0.0.1:8001')) is not None
    for bad in ('http://10.0.0.5:9999', 'https://10.0.0.5:8001',
                'http://10.0.0.7:8001'):
        assert peer_from(_Req(bad)) is None


# --------------------------------------------------------- off == inert
def test_tier_off_leaves_engine_untouched(kv_setup, monkeypatch):
    monkeypatch.setenv('SKYT_KV_TIER', 'off')
    eng, _ = _make_engine(kv_setup, monkeypatch, tier='off')
    assert eng.kv_tier is None
    # Bad values degrade to off with a warning, never a crash.
    monkeypatch.setenv('SKYT_KV_TIER', 'warp-drive')
    assert kv_tier_lib.tier_from_env() == 'off'


# -------------------------------------- scale-up prewarm (ROADMAP 5c)
class TestPrewarm:
    """Proactive KV pre-warm on scale-up: a freshly READY replica
    pulls its rendezvous share of the fleet's resident prefix pages
    into the host store (docs/serving.md "Elastic capacity")."""

    def _mgr(self):
        return kv_tier_lib.KVTierManager('fleet', host_bytes=1 << 20,
                                         fetch_max_pages=1,
                                         fetch_timeout_s=1.0)

    def test_prewarm_claims_exactly_the_owned_share(self, monkeypatch):
        """Ownership is the same rendezvous-ring math the LB's
        prefix-affinity routing uses: the replica fetches the batches
        the ring ranks it first for — no more, no less — and they land
        in the host store under the prewarm counter."""
        from skypilot_tpu.serve import load_balancing_policies as \
            lb_policies
        mgr = self._mgr()
        hashes = [_h(i) for i in range(40)]
        monkeypatch.setattr(
            kv_tier_lib, 'fetch_index',
            lambda peer, token, timeout_s: (1, list(hashes)))
        monkeypatch.setattr(
            kv_tier_lib, 'fetch_pages',
            lambda peer, hs, token, timeout_s, max_pages:
            (1, [(h, _arrays()) for h in hs]))
        me, peer = 'http://127.0.0.1:9001', 'http://127.0.0.1:9002'
        res = mgr.prewarm_from_peers(me, [peer], 1, 'tok')
        ring = lb_policies.ConsistentHashRing()
        ring.set_nodes({me: 1.0, peer: 1.0})
        expected = [h for h in hashes if ring.owner(h.hex()) == me]
        # The split is real: both replicas own a nonempty share.
        assert 0 < len(expected) < len(hashes)
        assert res['owned_pages'] == res['stored_pages'] == \
            len(expected)
        assert res['errors'] == 0 and res['peers'] == 1
        assert mgr.stats['prewarm_pages'] == len(expected)
        assert all(mgr.host.contains(h, 1) for h in expected)
        assert not any(mgr.host.contains(h, 1)
                       for h in hashes if h not in expected)
        # A self-entry in the peer list is skipped, not fetched.
        res2 = self._mgr().prewarm_from_peers(me, [me], 1, 'tok')
        assert res2 == {'peers': 1, 'owned_pages': 0,
                        'stored_pages': 0, 'errors': 0}

    def test_prewarm_failures_counted_never_raised(self, monkeypatch):
        """Best-effort contract: version-mismatched peers and kv.fetch
        faults are counted and skipped — a failed prewarm costs
        recomputes, never readiness (and never an exception)."""
        mgr = self._mgr()
        # Peer on another weight version: its KV must never splice in.
        monkeypatch.setattr(
            kv_tier_lib, 'fetch_index',
            lambda peer, token, timeout_s: (2, [_h(1)]))
        res = mgr.prewarm_from_peers('http://a:1', ['http://b:2'],
                                     1, 'tok')
        assert res['errors'] == 1 and res['stored_pages'] == 0
        assert len(mgr.host) == 0
        # The shared kv.fetch fault point breaks prewarm the same way
        # it breaks demand fetches: degrade, count, carry on.
        monkeypatch.undo()
        faults.reset()
        faults.configure('kv.fetch=error')
        try:
            res = mgr.prewarm_from_peers('http://a:1',
                                         ['http://b:2',
                                          'http://c:3'], 1, 'tok')
        finally:
            faults.reset()
        assert res['errors'] == 2 and res['stored_pages'] == 0


@pytest.mark.integration
def test_kv_index_inventory_roundtrip(kv_setup, monkeypatch):
    """engine.kv_index() snapshots the resident inventory at a tick
    boundary: HBM registry pages first, host-store continuations
    deduplicated in, weight version stamped — the /kv/index body peers
    batch their prewarm claims over."""
    eng, _ = _make_engine(kv_setup, monkeypatch, tier='host')
    eng.start()
    try:
        prompt = _prompt(0)
        _gen(eng, prompt)
        idx = eng.kv_index()
        assert idx is not None
        assert idx['weight_version'] == eng.weight_version == 1
        h0 = paged_cache.page_hashes(prompt,
                                     eng.pool.cfg.page_size)[0]
        assert h0.hex() in idx['hashes']
        assert len(set(idx['hashes'])) == len(idx['hashes'])
        # Host-only pages (evicted from HBM) stay in the inventory.
        _fill_until_evicted(eng, prompt)
        idx2 = eng.kv_index()
        assert h0.hex() in idx2['hashes']
        # A host-tier engine refuses the prewarm pull itself (fleet
        # transfers are the fleet tier's contract) — explicitly, not
        # with an error.
        res = eng.kv_prewarm('http://me:1', ['http://peer:2'], 'tok')
        assert res['skipped'] and res['stored_pages'] == 0
    finally:
        eng.stop()
