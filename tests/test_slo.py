"""serve/slo.py + serve/fleet.py: burn-rate truth table across both
window pairs, alert hysteresis, goodput attribution per class/tenant,
deterministic replay under seeded scrape data, telemetry.scrape fault
descent, and the acceptance chaos drills (scrape-error mid-burst keeps
/fleet/slo serving; an induced server.request latency fault flips
skyt_slo_alert{class="interactive"} within one fast window)."""
import threading
import time

import pytest

from skypilot_tpu.serve import fleet as fleet_lib
from skypilot_tpu.serve import slo as slo_lib
from skypilot_tpu.utils import faults
from skypilot_tpu.utils import metrics as metrics_lib
from skypilot_tpu.utils import timeseries as ts_lib


class FakeClock:
    def __init__(self, t: float = 1_000_000.0) -> None:
        self.t = t

    def __call__(self) -> float:
        return self.t

    def tick(self, dt: float) -> float:
        self.t += dt
        return self.t


class FakeSource:
    """Truth-table source: per-window (bad_fraction, total) per class,
    served through the TimeSeriesStore read protocol."""

    def __init__(self, by_window):
        # {cls: {window_s: (bad_frac, total)}}
        self.by_window = by_window

    def sum_delta(self, name, match, window_s, now=None):
        cls = (match or {}).get('cls')
        spec = self.by_window.get(cls, {}).get(window_s)
        if spec is None:
            return None
        bad, total = spec
        if name == 'skyt_slo_requests_total':
            return total
        if name == 'skyt_slo_good_requests_total':
            return total * (1 - bad)
        return None

    def quantile(self, family, match, q, window_s, now=None):
        return None

    def grouped_delta(self, name, group_label, window_s, now=None,
                      match=None):
        return {}


def make_evaluator(source, clock=None, **kw):
    clock = clock or FakeClock()
    kw.setdefault('registry', metrics_lib.MetricsRegistry())
    kw.setdefault('windows', slo_lib.BurnWindows())
    return slo_lib.BurnRateEvaluator(source, clock=clock, **kw), clock


def windows_spec(fast=(0.0, 0.0), slow=(0.0, 0.0), total=100.0):
    """Per-window (bad_frac, total): fast=(5m, 1h), slow=(6h, 3d)."""
    w = slo_lib.BurnWindows()
    return {
        w.fast_short_s: (fast[0], total),
        w.fast_long_s: (fast[1], total),
        w.slow_short_s: (slow[0], total),
        w.slow_long_s: (slow[1], total),
    }


# ------------------------------------------------------------ objectives
def test_objectives_env_tunable(monkeypatch):
    monkeypatch.setenv('SKYT_SLO_TTFT_MS_INTERACTIVE', '123')
    monkeypatch.setenv('SKYT_SLO_TARGET', '0.9')
    monkeypatch.setenv('SKYT_SLO_TARGET_BATCH', '0.5')
    objs = slo_lib.objectives()
    assert objs['interactive'].ttft_ms == 123
    assert objs['interactive'].target == 0.9
    assert objs['batch'].target == 0.5
    assert abs(objs['standard'].budget - 0.1) < 1e-9


# --------------------------------------------------- goodput attribution
def test_goodput_tracker_attribution(monkeypatch):
    monkeypatch.setenv('SKYT_SLO_TTFT_MS_INTERACTIVE', '100')
    monkeypatch.setenv('SKYT_SLO_ITL_MS_INTERACTIVE', '50')
    reg = metrics_lib.MetricsRegistry()
    tr = slo_lib.GoodputTracker(registry=reg)
    # within SLO -> good
    assert tr.record('interactive', 'a', ok=True, ttft_s=0.05,
                     itl_s=0.01, tokens=10)
    # TTFT blown -> bad (tokens still counted as work done)
    assert not tr.record('interactive', 'a', ok=True, ttft_s=0.5,
                         tokens=10)
    # ITL blown -> bad
    assert not tr.record('interactive', 'a', ok=True, ttft_s=0.05,
                         itl_s=0.2, tokens=10)
    # error -> bad regardless of latency
    assert not tr.record('interactive', 'b', ok=False, ttft_s=0.01)
    # other tenant, other class (default objectives are looser)
    assert tr.record('batch', 'b', ok=True, ttft_s=0.5, tokens=3)
    g = reg.get('skyt_slo_good_requests_total')
    assert g.value('interactive', 'a') == 1
    assert g.value('interactive', 'b') == 0
    assert g.value('batch', 'b') == 1
    assert reg.get('skyt_slo_requests_total').value(
        'interactive', 'a') == 3
    assert reg.get('skyt_slo_good_tokens_total').value(
        'interactive', 'a') == 10
    assert reg.get('skyt_slo_tokens_total').value(
        'interactive', 'a') == 30
    # unknown class folds into the default class, never a crash
    assert tr.record('mystery', 't', ok=True, tokens=1)
    assert reg.get('skyt_slo_requests_total').value(
        'standard', 't') == 1


# --------------------------------------------------- burn-rate truth table
def test_burn_no_data_no_alert():
    ev, _ = make_evaluator(FakeSource({}))
    rep = ev.evaluate()
    for cls, rec in rep.items():
        assert rec['alert'] is False
        assert all(w['burn_rate'] == 0 for w in rec['windows'].values())


def test_burn_fast_pair_fires():
    # budget 0.01 (target .99); 20% bad on BOTH 5m and 1h => burn 20
    # >= 14.4 on both fast windows => page.
    src = FakeSource({'interactive': windows_spec(fast=(0.2, 0.2))})
    ev, _ = make_evaluator(src)
    rep = ev.evaluate()
    assert rep['interactive']['alert'] is True
    assert rep['interactive']['windows']['5m']['burn_rate'] == 20.0
    assert rep['standard']['alert'] is False


def test_burn_short_window_alone_does_not_fire():
    # 5m bad but the hour is clean: a blip, not a page.
    src = FakeSource({'interactive': windows_spec(fast=(0.2, 0.0))})
    ev, _ = make_evaluator(src)
    assert ev.evaluate()['interactive']['alert'] is False
    # and the long window alone (old burn, recovered) does not fire
    src2 = FakeSource({'interactive': windows_spec(fast=(0.0, 0.2))})
    ev2, _ = make_evaluator(src2)
    assert ev2.evaluate()['interactive']['alert'] is False


def test_burn_slow_pair_fires():
    # 7% bad over both 6h and 3d: burn 7 >= 6 on the slow pair.
    src = FakeSource({'batch': windows_spec(slow=(0.07, 0.07))})
    ev, _ = make_evaluator(src)
    rep = ev.evaluate()
    assert rep['batch']['alert'] is True
    assert rep['interactive']['alert'] is False


def test_alert_hysteresis_clears_on_short_windows():
    src = FakeSource({'interactive': windows_spec(fast=(0.2, 0.2))})
    reg = metrics_lib.MetricsRegistry()
    ev, _ = make_evaluator(src, registry=reg)
    assert ev.evaluate()['interactive']['alert'] is True
    assert reg.get('skyt_slo_alert').value('interactive') == 1
    # The hour window stays hot (it decays slowly) but the 5m window
    # recovered: the alert clears — fast-clear semantics.
    src.by_window = {'interactive': windows_spec(fast=(0.0, 0.2))}
    assert ev.evaluate()['interactive']['alert'] is False
    assert reg.get('skyt_slo_alert').value('interactive') == 0
    # Re-firing needs BOTH windows hot again, not the lingering hour.
    assert ev.evaluate()['interactive']['alert'] is False
    src.by_window = {'interactive': windows_spec(fast=(0.3, 0.2))}
    assert ev.evaluate()['interactive']['alert'] is True


def test_alert_stays_firing_while_short_window_burns():
    src = FakeSource({'interactive': windows_spec(fast=(0.2, 0.2))})
    ev, _ = make_evaluator(src)
    assert ev.evaluate()['interactive']['alert'] is True
    # long window drops first (shorter memory upstream): still firing
    # because the 5m window is still burning.
    src.by_window = {'interactive': windows_spec(fast=(0.2, 0.0))}
    assert ev.evaluate()['interactive']['alert'] is True


# ----------------------------------------- deterministic replay / store
def _seeded_store_run():
    """Feed a real TimeSeriesStore with deterministic scrape data and
    evaluate burn rates against it — the replay property."""
    clock = FakeClock()
    store = ts_lib.TimeSeriesStore(clock=clock)
    reg = metrics_lib.MetricsRegistry()
    ev = slo_lib.BurnRateEvaluator(store, registry=reg, clock=clock)
    good, total = 0, 0
    for i in range(40):
        clock.tick(10)
        total += 5
        good += 5 if i < 20 else 2   # the last 200s turn 60% bad
        store.observe('skyt_slo_requests_total',
                      {'cls': 'interactive', 'tenant': 'a'}, total)
        store.observe('skyt_slo_good_requests_total',
                      {'cls': 'interactive', 'tenant': 'a'}, good)
    rep = ev.evaluate()
    return rep, slo_lib.goodput_report(store, 300, clock.t, replicas=2)


def test_deterministic_replay_under_seeded_scrape_data():
    a = _seeded_store_run()
    b = _seeded_store_run()
    assert a == b
    rep, goodput = a
    # The 5m window holds 30 intervals: 20 bad-phase (60% bad) + 10
    # clean => 40% bad => burn 40; the 1h window dilutes further but
    # both stay >= 14.4, so the fast pair fires.
    assert rep['interactive']['windows']['5m']['burn_rate'] == \
        pytest.approx(40.0, rel=0.01)
    assert rep['interactive']['alert'] is True
    assert goodput['replicas'] == 2


def test_goodput_report_cost_math(monkeypatch):
    monkeypatch.setenv('SKYT_FLEET_CHIPS_PER_REPLICA', '4')
    clock = FakeClock()
    store = ts_lib.TimeSeriesStore(clock=clock)
    for i in range(2):
        ts = clock.tick(10)
        for tenant, tok in (('a', 100.0), ('b', 50.0)):
            store.observe('skyt_slo_tokens_total',
                          {'cls': 'interactive', 'tenant': tenant},
                          tok * (i + 1), ts=ts)
            store.observe('skyt_slo_good_tokens_total',
                          {'cls': 'interactive', 'tenant': tenant},
                          tok * (i + 1) * 0.9, ts=ts)
            store.observe('skyt_slo_requests_total',
                          {'cls': 'interactive', 'tenant': tenant},
                          float(i + 1), ts=ts)
            store.observe('skyt_slo_good_requests_total',
                          {'cls': 'interactive', 'tenant': tenant},
                          float(i + 1), ts=ts)
    rep = slo_lib.goodput_report(store, window_s=100.0, now=clock.t,
                                 replicas=2)
    assert rep['chips'] == 8
    tenants = rep['classes']['interactive']['tenants']
    assert tenants['a']['tokens'] == 100.0
    assert tenants['a']['good_tokens'] == pytest.approx(90.0)
    assert tenants['b']['good_tokens'] == pytest.approx(45.0)
    # 135 good tokens / (8 chips * 100 s)
    assert rep['good_tokens_per_chip_second'] == \
        pytest.approx(135.0 / 800.0, rel=1e-3)
    assert rep['chip_seconds_per_good_token'] == \
        pytest.approx(800.0 / 135.0, rel=1e-3)


# ------------------------------------------- fleet: scrape fault descent
def _expo(requests_n, good_n, cls='interactive', tenant='a'):
    return (
        '# TYPE skyt_slo_requests_total counter\n'
        f'skyt_slo_requests_total{{cls="{cls}",tenant="{tenant}"}} '
        f'{requests_n}\n'
        '# TYPE skyt_slo_good_requests_total counter\n'
        f'skyt_slo_good_requests_total{{cls="{cls}",'
        f'tenant="{tenant}"}} {good_n}\n')


def test_fleet_scrape_fault_descent_and_stale_ageout():
    """SKYT_FAULTS=telemetry.scrape=error against one replica: the
    scrape fails COUNTED (never raises into the prober), /fleet/slo
    keeps serving from the healthy replica, and the faulted replica's
    series age out after SKYT_FLEET_STALE_S."""
    clock = FakeClock()
    served = {}

    def fake_get(url, timeout):
        return served[url]

    reg = metrics_lib.MetricsRegistry()
    fl = fleet_lib.FleetTelemetry('svc', metrics_registry=reg,
                                  clock=clock, http_get=fake_get)
    served['http://r1/metrics'] = _expo(10, 10)
    served['http://r2/metrics'] = _expo(20, 20)
    assert fl.scrape('1', 'http://r1')
    assert fl.scrape('2', 'http://r2')
    faults.configure('telemetry.scrape=error,where=replica:1')
    try:
        clock.tick(10)
        served['http://r1/metrics'] = _expo(15, 15)
        served['http://r2/metrics'] = _expo(30, 30)
        assert fl.scrape('1', 'http://r1') is False   # fault fired
        assert fl.scrape('2', 'http://r2') is True    # unaffected
        assert reg.get('skyt_fleet_scrape_errors_total').value('1') == 1
        assert reg.get('skyt_fleet_scrapes_total').value('2', 'ok') == 2
        # /fleet/slo keeps serving: replica 2's data flows, replica 1
        # still contributes its PRE-fault series (not yet stale).
        rep = fl.fleet_slo(window_s=100)
        assert set(rep['targets']) == {'1', '2'}
        assert rep['goodput']['replicas'] == 2
        # Age replica 1 past the stale TTL (scrapes keep failing).
        for _ in range(8):
            clock.tick(10)
            served['http://r2/metrics'] = _expo(40, 40)
            fl.scrape('1', 'http://r1')
            fl.scrape('2', 'http://r2')
        rep = fl.fleet_slo(window_s=1000)
        assert set(rep['targets']) == {'2'}, \
            'faulted replica must age out of the aggregates'
        assert rep['goodput']['replicas'] == 1
        assert 'replica="1"' not in fl.fleet_metrics_text()
    finally:
        faults.reset()


def test_fleet_metrics_text_aggregates_with_replica_label():
    clock = FakeClock()
    served = {'http://r1/metrics': _expo(5, 5),
              'http://r2/metrics': _expo(7, 6, tenant='b')}
    fl = fleet_lib.FleetTelemetry(
        'svc', metrics_registry=metrics_lib.MetricsRegistry(),
        clock=clock, http_get=lambda url, t: served[url])
    fl.scrape('1', 'http://r1')
    fl.scrape('2', 'http://r2')
    text = fl.fleet_metrics_text()
    assert '# TYPE skyt_slo_requests_total counter' in text
    assert ('skyt_slo_requests_total{cls="interactive",replica="1",'
            'tenant="a"} 5') in text
    assert ('skyt_slo_requests_total{cls="interactive",replica="2",'
            'tenant="b"} 7') in text


def test_fleet_maybe_scrape_throttles():
    clock = FakeClock()
    calls = []

    def fake_get(url, timeout):
        calls.append(url)
        return _expo(1, 1)

    fl = fleet_lib.FleetTelemetry(
        'svc', metrics_registry=metrics_lib.MetricsRegistry(),
        clock=clock, http_get=fake_get)
    assert fl.maybe_scrape('1', 'http://r1') is True
    assert fl.maybe_scrape('1', 'http://r1') is None   # throttled
    clock.tick(fl.scrape_interval_s + 1)
    assert fl.maybe_scrape('1', 'http://r1') is True
    assert len(calls) == 2


def test_fleet_cross_replica_quantile():
    """TTFT p95 merges bucket increases ACROSS replica stores."""
    clock = FakeClock()
    hist = (
        '# TYPE skyt_slo_ttft_seconds histogram\n'
        'skyt_slo_ttft_seconds_bucket{{cls="interactive",le="0.1"}} {a}\n'
        'skyt_slo_ttft_seconds_bucket{{cls="interactive",le="1"}} {b}\n'
        'skyt_slo_ttft_seconds_bucket{{cls="interactive",le="+Inf"}} {b}\n')
    served = {}

    def fake_get(url, timeout):
        return served[url]

    fl = fleet_lib.FleetTelemetry(
        'svc', metrics_registry=metrics_lib.MetricsRegistry(),
        clock=clock, http_get=fake_get)
    served['http://r1/metrics'] = hist.format(a=0, b=0)
    served['http://r2/metrics'] = hist.format(a=0, b=0)
    fl.scrape('1', 'http://r1')
    fl.scrape('2', 'http://r2')
    clock.tick(10)
    # r1: 10 fast obs; r2: 10 slow obs => fleet p50 at the 0.1 bound.
    served['http://r1/metrics'] = hist.format(a=10, b=10)
    served['http://r2/metrics'] = hist.format(a=0, b=10)
    fl.scrape('1', 'http://r1')
    fl.scrape('2', 'http://r2')
    p50 = fl.quantile('skyt_slo_ttft_seconds', {'cls': 'interactive'},
                      0.5, 100, now=clock.t)
    assert p50 == pytest.approx(0.1, rel=1e-6)


# ------------------------------------------------ end-to-end chaos drills
def _start_server(env=None):
    """Debug engine + InferenceServer on a loopback port (private
    registry); returns (engine, base_url, registry)."""
    import dataclasses
    import socket

    import jax
    import jax.numpy as jnp
    import requests
    from aiohttp import web

    from skypilot_tpu.infer import engine as engine_lib
    from skypilot_tpu.infer import server as server_lib
    from skypilot_tpu.models import llama

    cfg = dataclasses.replace(llama.CONFIGS['debug'], max_seq_len=64)
    model = llama.LlamaModel(cfg)
    params = jax.jit(model.init)(jax.random.PRNGKey(0),
                                 jnp.zeros((1, 8), jnp.int32))
    reg = metrics_lib.MetricsRegistry()
    eng = engine_lib.InferenceEngine(model, params, num_slots=2,
                                     max_seq_len=64,
                                     prefill_buckets=[16],
                                     metrics_registry=reg)
    eng.start()
    srv = server_lib.InferenceServer(eng)
    with socket.socket() as s:
        s.bind(('127.0.0.1', 0))
        port = s.getsockname()[1]
    threading.Thread(target=lambda: web.run_app(
        srv.make_app(), port=port, print=None, handle_signals=False),
        daemon=True).start()
    base = f'http://127.0.0.1:{port}'
    deadline = time.time() + 60
    while time.time() < deadline:
        try:
            if requests.get(base + '/health',
                            timeout=2).status_code == 200:
                break
        except requests.RequestException:
            pass
        time.sleep(0.2)
    return eng, base, reg


@pytest.mark.integration
def test_latency_fault_flips_interactive_alert(monkeypatch):
    """THE acceptance drill, deterministically: with
    server.request=latency armed, every interactive request blows a
    50ms TTFT SLO, so within one fast window the 5m AND 1h burn rates
    pin high and skyt_slo_alert{class="interactive"} flips to firing —
    with zero client-visible 5xx."""
    import requests

    monkeypatch.setenv('SKYT_SLO_TTFT_MS_INTERACTIVE', '50')
    eng, base, _reg = _start_server()
    fleet_reg = metrics_lib.MetricsRegistry()
    fl = fleet_lib.FleetTelemetry('drill',
                                  metrics_registry=fleet_reg)
    try:
        # Prime the class/tenant series, then take the pre-burst
        # baseline scrape (a counter window needs both edges).
        r = requests.post(base + '/generate',
                          json={'tokens': [7, 8, 9], 'max_tokens': 2},
                          headers={'X-Priority': 'interactive'},
                          timeout=60)
        r.raise_for_status()
        assert fl.scrape('1', base)
        # Arm AFTER priming: 150ms injected ahead of every /generate.
        faults.configure(
            'server.request=latency,arg=0.15,where=path:/generate')
        codes = []
        for i in range(8):
            r = requests.post(
                base + '/generate',
                json={'tokens': [3 + i, 4, 5], 'max_tokens': 2},
                headers={'X-Priority': 'interactive'}, timeout=60)
            codes.append(r.status_code)
        assert all(c == 200 for c in codes), codes
        assert fl.scrape('1', base)
        rep = fl.fleet_slo(window_s=300)
        rec = rep['slo']['interactive']
        assert rec['alert'] is True, rec
        assert rec['windows']['5m']['burn_rate'] >= 14.4
        assert fleet_reg.get('skyt_slo_alert').value(
            'interactive') == 1
        # The injected latency is visible in the fleet TTFT quantile.
        assert rec['ttft_p95_ms'] is not None
        assert rec['ttft_p95_ms'] > 50
    finally:
        faults.reset()
        eng.stop()


@pytest.mark.integration
def test_debug_profile_endpoint(monkeypatch):
    """POST /debug/profile: 403 without SKYT_PROFILE_REMOTE, 400 on a
    malformed ms, 409 while another capture holds the single-flight
    lock, 200 with a real (CPU-degraded) trace dir."""
    import requests

    from skypilot_tpu.utils import profiling as profiling_lib

    eng, base, _reg = _start_server()
    try:
        monkeypatch.delenv('SKYT_PROFILE_REMOTE', raising=False)
        assert requests.post(base + '/debug/profile',
                             timeout=30).status_code == 403
        monkeypatch.setenv('SKYT_PROFILE_REMOTE', '1')
        assert requests.post(base + '/debug/profile',
                             params={'ms': 'nan'},
                             timeout=30).status_code == 400
        assert requests.post(base + '/debug/profile',
                             params={'ms': '999999'},
                             timeout=30).status_code == 400
        assert profiling_lib._CAPTURE_LOCK.acquire(blocking=False)
        try:
            assert requests.post(base + '/debug/profile',
                                 params={'ms': '20'},
                                 timeout=30).status_code == 409
        finally:
            profiling_lib._CAPTURE_LOCK.release()
        resp = requests.post(base + '/debug/profile',
                             params={'ms': '20'}, timeout=60)
        assert resp.status_code == 200, resp.text
        body = resp.json()
        assert body['trace_dir'] and body['duration_ms'] >= 20
    finally:
        eng.stop()


def test_fleet_routes_profile_proxy(monkeypatch):
    """/fleet/* HTTP surface via add_fleet_routes: metrics text, slo
    JSON, and the profile proxy's 400/404 paths."""
    import asyncio

    from aiohttp import web
    from aiohttp.test_utils import TestClient, TestServer

    clock = FakeClock()
    fl = fleet_lib.FleetTelemetry(
        'svc', metrics_registry=metrics_lib.MetricsRegistry(),
        clock=clock, http_get=lambda url, t: _expo(3, 3))
    fl.scrape('1', 'http://r1')

    async def run():
        app = web.Application()
        fleet_lib.add_fleet_routes(app, fl, lambda rid: None)
        client = TestClient(TestServer(app))
        await client.start_server()
        try:
            resp = await client.get('/fleet/metrics')
            assert resp.status == 200
            assert 'replica="1"' in await resp.text()
            resp = await client.get('/fleet/slo')
            assert resp.status == 200
            body = await resp.json()
            assert body['service'] == 'svc'
            assert 'interactive' in body['slo']
            resp = await client.get('/fleet/slo',
                                    params={'window_s': '-1'})
            assert resp.status == 400
            resp = await client.post('/fleet/profile')
            assert resp.status == 400
            resp = await client.post('/fleet/profile',
                                     params={'replica': '9'})
            assert resp.status == 404
        finally:
            await client.close()

    asyncio.run(run())
