"""Chaos suite: deterministic fault injection (utils/faults.py) and the
fault-tolerant serving/training behaviors it exercises — LB retries on
another replica, per-replica circuit breaker, request deadlines,
client-disconnect cancellation, replica drain/backoff, and
preemption-safe training exits (docs/robustness.md).

The integration tests drive the REAL LB -> server -> engine HTTP stack
on CPU; replica death is a SIGKILL'd subprocess, not a mock.
"""
import os
import re
import signal
import socket
import subprocess
import sys
import threading
import time

import pytest
import requests

from skypilot_tpu.utils import faults
from skypilot_tpu.utils import metrics as metrics_lib

pytestmark = pytest.mark.heavy


@pytest.fixture(autouse=True)
def _reset_faults():
    faults.reset()
    yield
    faults.reset()


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(('127.0.0.1', 0))
        return s.getsockname()[1]


def _run_app_bg(app, port) -> None:
    from aiohttp import web
    threading.Thread(target=lambda: web.run_app(
        app, port=port, print=None, handle_signals=False),
        daemon=True).start()


def _wait_http(url: str, timeout: float = 60, proc=None) -> None:
    deadline = time.time() + timeout
    while time.time() < deadline:
        if proc is not None and proc.poll() is not None:
            raise AssertionError(
                f'server died rc={proc.returncode} before {url} was up')
        try:
            if requests.get(url, timeout=2).status_code == 200:
                return
        except requests.RequestException:
            pass
        time.sleep(0.2)
    raise AssertionError(f'{url} never became healthy')


# ================================================== fault spec / triggers
def test_fault_spec_grammar():
    rules = faults.parse_spec(
        'lb.proxy=error,count=2;'
        'engine.loop=latency,arg=0.5,p=0.25,after=10;'
        'server.request=preempt,where=path:/generate')
    assert [r.point for r in rules] == ['lb.proxy', 'engine.loop',
                                       'server.request']
    assert rules[0].kind == 'error' and rules[0].count == 2
    assert rules[1].arg == 0.5 and rules[1].p == 0.25 \
        and rules[1].after == 10
    assert rules[2].where == ('path', '/generate')


@pytest.mark.parametrize('bad', [
    'nokind', 'a.b=doesnotexist', 'a.b=error,p=nope',
    'a.b=error,bogus=1', 'a.b=error,where=novalue', 'a.b=error,p=7',
])
def test_fault_spec_errors(bad):
    with pytest.raises(ValueError):
        faults.parse_spec(bad)


def test_fault_count_and_after_triggers():
    faults.configure('x.y=error,count=2,after=1')
    faults.inject('x.y')                      # after=1: first hit skips
    for _ in range(2):
        with pytest.raises(faults.FaultError):
            faults.inject('x.y')
    faults.inject('x.y')                      # count exhausted
    assert faults.fired_counts() == {('x.y', 'error'): 2}


def test_fault_probability_is_seed_deterministic():
    def pattern():
        faults.configure('x.y=error,p=0.5', seed=7)
        fired = []
        for _ in range(32):
            try:
                faults.inject('x.y')
                fired.append(False)
            except faults.FaultError:
                fired.append(True)
        return fired
    a, b = pattern(), pattern()
    assert a == b            # same seed => identical chaos run
    assert any(a) and not all(a)


def test_fault_where_filter_and_disconnect():
    faults.configure('p.q=disconnect,where=replica:r1')
    faults.inject('p.q', replica='r2')        # filtered out
    faults.inject('p.q')                      # attr absent: filtered
    with pytest.raises(ConnectionResetError):
        faults.inject('p.q', replica='r1')


def test_fault_env_arming_and_malformed_env(monkeypatch):
    monkeypatch.setenv('SKYT_FAULTS', 'e.f=error')
    with pytest.raises(faults.FaultError):
        faults.inject('e.f')
    # Programmatic reset() re-reads the env; clearing it disarms.
    monkeypatch.delenv('SKYT_FAULTS')
    faults.inject('e.f')
    assert not faults.enabled()
    # A malformed env spec is ignored (logged), never raises at the
    # injection site.
    monkeypatch.setenv('SKYT_FAULTS', 'this is not a spec')
    faults.inject('e.f')


def test_fault_fires_are_counted_in_metrics():
    before = metrics_lib.REGISTRY.counter(
        'skyt_faults_fired_total', 'Injected faults fired',
        ('point', 'kind')).value('m.n', 'error')
    faults.configure('m.n=error,count=1')
    with pytest.raises(faults.FaultError):
        faults.inject('m.n')
    after = metrics_lib.REGISTRY.counter(
        'skyt_faults_fired_total', 'Injected faults fired',
        ('point', 'kind')).value('m.n', 'error')
    assert after == before + 1


# ======================================================= circuit breaker
def _breaker(threshold=3, cooldown=0.2):
    from skypilot_tpu.serve import load_balancer as lb_lib
    return lb_lib.CircuitBreaker(threshold=threshold,
                                 cooldown_s=cooldown,
                                 registry=metrics_lib.MetricsRegistry())


def test_breaker_closed_open_halfopen_closed():
    br = _breaker(threshold=3, cooldown=0.15)
    r = 'http://r1'
    for _ in range(2):
        br.record_failure(r)
    assert br.state(r) == br.CLOSED and br.allow(r)
    br.record_failure(r)                       # 3rd consecutive: open
    assert br.state(r) == br.OPEN
    assert not br.allow(r)                     # cooldown not elapsed
    time.sleep(0.2)
    assert br.allow(r)                         # half-open trial granted
    assert br.state(r) == br.HALF_OPEN
    assert not br.allow(r)                     # one trial per window
    br.record_success(r)                       # trial succeeded
    assert br.state(r) == br.CLOSED and br.allow(r)


def test_breaker_blocked_is_read_only():
    """blocked() must never consume the half-open trial: candidate
    filtering checks every ready replica on every pick, and burning
    the trial on replicas the policy then doesn't select would keep a
    recovered replica ejected indefinitely."""
    br = _breaker(threshold=1, cooldown=0.15)
    r = 'http://r1'
    br.record_failure(r)
    time.sleep(0.2)
    for _ in range(10):
        assert not br.blocked(r)       # trial available, not claimed
    assert br.state(r) == br.OPEN      # still no trial in flight
    assert br.allow(r)                 # the actual pick claims it
    assert br.blocked(r)               # now others are filtered out
    br.record_success(r)
    assert not br.blocked(r)


def test_breaker_halfopen_failure_reopens():
    br = _breaker(threshold=1, cooldown=0.15)
    r = 'http://r1'
    br.record_failure(r)
    assert br.state(r) == br.OPEN
    time.sleep(0.2)
    assert br.allow(r)
    br.record_failure(r)                       # trial failed
    assert br.state(r) == br.OPEN
    assert not br.allow(r)                     # window restarted
    # success after a later trial fully resets the failure count
    time.sleep(0.2)
    assert br.allow(r)
    br.record_success(r)
    assert br.state(r) == br.CLOSED


def test_policy_exclude():
    from skypilot_tpu.serve import load_balancing_policies as lbp
    rr = lbp.RoundRobinPolicy()
    rr.set_ready_replicas(['a', 'b', 'c'])
    picks = {rr.select_replica(exclude={'b'}) for _ in range(6)}
    assert picks == {'a', 'c'}
    assert rr.select_replica(exclude={'a', 'b', 'c'}) is None
    lc = lbp.LeastConnectionsPolicy()
    lc.set_ready_replicas(['a', 'b'])
    assert lc.select_replica(exclude={'a'}) == 'b'
    assert lc.select_replica(exclude={'a', 'b'}) is None


# ============================================================ LB behavior
def _make_lb(replicas, monkeypatch=None, **env):
    """In-process LB with a private registry, controller sync parked."""
    from skypilot_tpu.serve import load_balancer as lb_lib
    os.environ.setdefault('SKYT_SERVE_LB_SYNC_INTERVAL', '3600')
    if monkeypatch is not None:
        monkeypatch.setenv('SKYT_SERVE_LB_SYNC_INTERVAL', '3600')
        for k, v in env.items():
            monkeypatch.setenv(k, str(v))
    reg = metrics_lib.MetricsRegistry()
    port = _free_port()
    lb = lb_lib.SkyServeLoadBalancer('http://127.0.0.1:9', port,
                                     metrics_registry=reg)
    lb.policy.set_ready_replicas(list(replicas))
    _run_app_bg(lb.make_app(), port)
    base = f'http://127.0.0.1:{port}'
    deadline = time.time() + 30
    while time.time() < deadline:
        try:
            requests.get(base + '/metrics', timeout=2)
            break
        except requests.RequestException:
            time.sleep(0.1)
    return lb, base, reg


def _ok_replica(name='ok'):
    """Tiny healthy replica app (no engine: LB behavior under test)."""
    from aiohttp import web

    async def handler(request):
        del request
        return web.Response(text=f'hello-{name}')

    app = web.Application()
    app.router.add_route('*', '/{p:.*}', handler)
    port = _free_port()
    _run_app_bg(app, port)
    url = f'http://127.0.0.1:{port}'
    _wait_http(url + '/x')
    return url


def test_lb_retries_on_another_replica(monkeypatch):
    """A dead replica (connection refused) must be retried on the live
    one with NOTHING visible to the client but the X-Replica-Id of the
    survivor — zero 5xx (tentpole acceptance for pre-header failures).
    """
    dead = f'http://127.0.0.1:{_free_port()}'    # nothing listens
    live = _ok_replica('live')
    lb, base, reg = _make_lb([dead, live], monkeypatch,
                             SKYT_LB_RETRY_BACKOFF_S='0.01')
    for _ in range(6):   # round robin: half land on the dead one first
        r = requests.get(base + '/gen', timeout=10)
        assert r.status_code == 200
        assert r.text == 'hello-live'
        assert r.headers['X-Replica-Id'] == live
    retries = reg.counter('skyt_lb_retries_total', '',
                          ('lb', 'replica'))
    assert retries.value(lb.lb_id, dead) >= 1
    errors = reg.counter('skyt_lb_errors_total', '', ('lb', 'replica'))
    assert errors.value(lb.lb_id, dead) >= 1
    del lb


def test_lb_breaker_opens_and_is_visible_in_metrics(monkeypatch):
    """Consecutive transport failures open the breaker (ejecting the
    replica ahead of the controller sync); state and transition
    counters are scrapeable at the LB's own /metrics."""
    dead = f'http://127.0.0.1:{_free_port()}'
    live = _ok_replica('ok2')
    lb, base, reg = _make_lb([dead, live], monkeypatch,
                             SKYT_LB_RETRY_BACKOFF_S='0.01',
                             SKYT_LB_BREAKER_THRESHOLD='2',
                             SKYT_LB_BREAKER_COOLDOWN_S='30')
    for _ in range(8):
        assert requests.get(base + '/g', timeout=10).status_code == 200
    assert lb.breaker.state(dead) == lb.breaker.OPEN
    requests_m = reg.counter('skyt_lb_requests_total', '',
                             ('lb', 'replica'))
    sent_to_dead = requests_m.value(lb.lb_id, dead)
    # Breaker open: further traffic skips the dead replica entirely.
    for _ in range(4):
        assert requests.get(base + '/g', timeout=10).status_code == 200
    assert requests_m.value(lb.lb_id, dead) == sent_to_dead
    text = requests.get(base + '/metrics', timeout=5).text
    assert (f'skyt_lb_breaker_state{{lb="{lb.lb_id}",'
            f'replica="{dead}"}} 2') in text
    assert (f'skyt_lb_breaker_opens_total{{lb="{lb.lb_id}",'
            f'replica="{dead}"}} 1') in text
    assert 'skyt_lb_retries_total' in text


def test_lb_breaker_halfopen_recovers(monkeypatch):
    """open -> half-open probe -> closed, end to end through the proxy:
    a replica that comes back is restored to rotation after one
    successful half-open trial."""
    from aiohttp import web
    port = _free_port()
    url = f'http://127.0.0.1:{port}'
    lb, base, _reg = _make_lb([url], monkeypatch,
                              SKYT_LB_RETRY_BACKOFF_S='0.01',
                              SKYT_LB_RETRY_BUDGET_S='1',
                              SKYT_LB_BREAKER_THRESHOLD='2',
                              SKYT_LB_BREAKER_COOLDOWN_S='0.3')
    # Nothing listening yet: requests 502 after the budget, breaker
    # opens after 2 transport failures.
    assert requests.get(base + '/g', timeout=10).status_code == 502
    assert lb.breaker.state(url) == lb.breaker.OPEN
    # Replica comes back up ON THE SAME PORT.
    async def handler(request):
        del request
        return web.Response(text='back')
    app = web.Application()
    app.router.add_route('*', '/{p:.*}', handler)
    _run_app_bg(app, port)
    _wait_http(url + '/x')
    time.sleep(0.35)     # past the breaker cooldown
    deadline = time.time() + 10
    while time.time() < deadline:
        r = requests.get(base + '/g', timeout=10)
        if r.status_code == 200:
            break
        time.sleep(0.2)
    assert r.status_code == 200 and r.text == 'back'
    assert lb.breaker.state(url) == lb.breaker.CLOSED


def test_lb_client_disconnect_is_not_a_replica_failure(monkeypatch):
    """A client hanging up mid-proxy must not poison the breaker or
    count as a replica error — with threshold 1, a single
    misclassified disconnect would eject the (healthy) replica."""
    from aiohttp import web

    async def handler(request):
        del request
        import asyncio as aio
        await aio.sleep(0.8)        # slower than the client's patience
        return web.Response(text='slow-ok')

    app = web.Application()
    app.router.add_route('*', '/{p:.*}', handler)
    port = _free_port()
    _run_app_bg(app, port)
    url = f'http://127.0.0.1:{port}'
    time.sleep(0.5)                  # app thread up (handler is slow)
    lb, base, reg = _make_lb([url], monkeypatch,
                             SKYT_LB_BREAKER_THRESHOLD='1')
    for _ in range(3):
        try:
            requests.get(base + '/g', timeout=0.3)   # client gives up
        except requests.RequestException:
            pass
    time.sleep(1.5)   # LB finishes handling the aborted exchanges
    assert lb.breaker.state(url) == lb.breaker.CLOSED
    errors = reg.counter('skyt_lb_errors_total', '', ('lb', 'replica'))
    assert errors.value(lb.lb_id, url) == 0
    disc = reg.counter('skyt_lb_client_disconnects_total', '', ('lb',))
    assert disc.value(lb.lb_id) >= 1
    # A patient client still gets proxied fine.
    r = requests.get(base + '/g', timeout=10)
    assert r.status_code == 200 and r.text == 'slow-ok'


def test_lb_retry_budget_exhaustion(monkeypatch):
    """With every replica down, the client's X-Request-Deadline bounds
    the retry storm: a 502 lands within the budget, not after the
    default 60s."""
    dead1 = f'http://127.0.0.1:{_free_port()}'
    dead2 = f'http://127.0.0.1:{_free_port()}'
    _lb, base, reg = _make_lb([dead1, dead2], monkeypatch,
                              SKYT_LB_RETRY_BACKOFF_S='0.02')
    t0 = time.time()
    r = requests.get(base + '/g', timeout=10,
                     headers={'X-Request-Deadline': '0.6'})
    elapsed = time.time() - t0
    assert r.status_code == 502
    assert 'failed after' in r.text
    assert elapsed < 5, elapsed
    retries = reg.counter('skyt_lb_retries_total', '',
                          ('lb', 'replica'))
    assert retries.value(_lb.lb_id, dead1) + \
        retries.value(_lb.lb_id, dead2) >= 1


def test_lb_no_replica_timeout_env(monkeypatch):
    """Satellite: the no-replica 503 deadline/poll are env knobs, not
    the hardcoded 30s/1s."""
    _lb, base, _reg = _make_lb([], monkeypatch,
                               SKYT_LB_NO_REPLICA_TIMEOUT_S='0.3',
                               SKYT_LB_NO_REPLICA_POLL_S='0.05')
    t0 = time.time()
    r = requests.get(base + '/g', timeout=10)
    assert r.status_code == 503
    assert 'No available replicas' in r.text
    assert time.time() - t0 < 3


def test_lb_timestamp_buffer_cap(monkeypatch):
    """Satellite: the unsent-timestamp buffer is bounded; overflow
    drops oldest and counts skyt_lb_sync_dropped_timestamps_total."""
    from skypilot_tpu.serve import load_balancer as lb_lib
    monkeypatch.setenv('SKYT_LB_MAX_PENDING_TIMESTAMPS', '10')
    reg = metrics_lib.MetricsRegistry()
    lb = lb_lib.SkyServeLoadBalancer('http://127.0.0.1:9', 1,
                                     metrics_registry=reg)
    lb.request_timestamps = list(range(25))
    lb._cap_timestamps()  # pylint: disable=protected-access
    assert lb.request_timestamps == list(range(15, 25))
    dropped = reg.counter('skyt_lb_sync_dropped_timestamps_total', '',
                          ('lb',))
    assert dropped.value(lb.lb_id) == 15


# ===================================================== replica lifecycle
def test_drain_grace_semantics(tmp_state_dir, monkeypatch):
    """A deliberately retired READY replica leaves the ready set
    immediately but its teardown waits the drain grace; failed
    replicas are torn down without grace."""
    del tmp_state_dir
    from skypilot_tpu.serve import replica_managers
    from skypilot_tpu.serve import serve_state
    from skypilot_tpu.serve import service_spec as spec_lib
    serve_state.reset_db_for_testing()
    monkeypatch.setenv('SKYT_SERVE_DRAIN_GRACE_S', '0.5')
    spec = spec_lib.ServiceSpec(readiness_path='/', min_replicas=1)
    serve_state.add_service('dsvc', spec, '/tmp/none.yaml', 1, 2)
    downed = []
    from skypilot_tpu import core as core_lib
    monkeypatch.setattr(
        core_lib, 'down',
        lambda name, purge=False: downed.append((name, time.time())))
    mgr = replica_managers.ReplicaManager('dsvc', spec, '/tmp/none.yaml')
    info = replica_managers.ReplicaInfo(
        replica_id=1, cluster_name='dsvc-1', version=1,
        status=serve_state.ReplicaStatus.READY,
        endpoint='http://127.0.0.1:1')
    mgr.replicas[1] = info
    t0 = time.time()
    mgr.terminate_replica(1, drain=True)
    # Ready set empties NOW (LB stops routing at its next sync) ...
    assert mgr.ready_urls() == []
    assert info.status is serve_state.ReplicaStatus.SHUTTING_DOWN
    deadline = time.time() + 10
    while not downed and time.time() < deadline:
        time.sleep(0.05)
    # ... but the actual teardown waited the grace period.
    assert downed and downed[0][1] - t0 >= 0.45
    reg = mgr._m_drains  # pylint: disable=protected-access
    assert reg.value('dsvc') == 1
    # Non-drain teardown (failure path) skips the grace.
    info2 = replica_managers.ReplicaInfo(
        replica_id=2, cluster_name='dsvc-2', version=1,
        status=serve_state.ReplicaStatus.NOT_READY,
        endpoint='http://127.0.0.1:2')
    mgr.replicas[2] = info2
    t1 = time.time()
    mgr.terminate_replica(2, sync=True, drain=True)  # not READY: no grace
    assert len(downed) == 2 and downed[1][1] - t1 < 0.4
    assert reg.value('dsvc') == 1


def test_relaunch_backoff_gates_reconcile(tmp_state_dir, monkeypatch):
    """Probe-failure -> FAILED relaunches go through exponential
    backoff instead of a tight launch loop; a READY replica resets it.
    """
    del tmp_state_dir
    from skypilot_tpu.serve import replica_managers
    from skypilot_tpu.serve import serve_state
    from skypilot_tpu.serve import service_spec as spec_lib
    serve_state.reset_db_for_testing()
    monkeypatch.setenv('SKYT_SERVE_RELAUNCH_BACKOFF_S', '30')
    spec = spec_lib.ServiceSpec(readiness_path='/', min_replicas=1)
    serve_state.add_service('bsvc', spec, '/tmp/none.yaml', 1, 2)
    mgr = replica_managers.ReplicaManager('bsvc', spec, '/tmp/none.yaml')
    launches = []
    monkeypatch.setattr(mgr, 'launch_replica',
                        lambda use_spot=None: launches.append(1))
    mgr.reconcile(target=1)
    assert len(launches) == 1            # no failures yet: launches
    mgr._note_replica_failed()           # pylint: disable=protected-access
    mgr.reconcile(target=1)
    assert len(launches) == 1            # gated by the backoff
    mgr._next_launch_ok = 0.0            # pylint: disable=protected-access
    mgr.reconcile(target=1)
    assert len(launches) == 2            # gate expired: launches again


# ============================================= real stack: engine deadline
def _debug_engine(reg, decode_chunk=2):
    import dataclasses
    import jax
    import jax.numpy as jnp
    from skypilot_tpu.infer import engine as engine_lib
    from skypilot_tpu.models import llama
    cfg = dataclasses.replace(llama.CONFIGS['debug'], max_seq_len=64)
    model = llama.LlamaModel(cfg)
    params = jax.jit(model.init)(jax.random.PRNGKey(0),
                                 jnp.zeros((1, 8), jnp.int32))
    return engine_lib.InferenceEngine(model, params, num_slots=2,
                                      max_seq_len=64,
                                      decode_chunk=decode_chunk,
                                      prefill_buckets=[16],
                                      metrics_registry=reg)


@pytest.mark.integration
def test_request_deadline_frees_slot():
    """A request past its deadline is cancelled by the decode loop: the
    slot frees, the trace records status='deadline', and the deadline
    counter ticks. A slow engine is simulated with an injected
    per-tick latency fault (dogfooding the subsystem under test)."""
    from skypilot_tpu.infer import engine as engine_lib
    faults.configure('engine.loop=latency,arg=0.05')
    reg = metrics_lib.MetricsRegistry()
    eng = _debug_engine(reg)
    eng.start()
    try:
        rid, q = eng.submit([3, 4, 5], engine_lib.SamplingParams(
            max_new_tokens=1000,
            deadline=time.time() + 0.4))
        toks = []
        deadline = time.time() + 30
        while time.time() < deadline:
            item = q.get(timeout=30)
            if item is None:
                break
            toks.append(item)
        assert len(toks) < 60          # expired before the length cap
        tr = eng.request_trace(rid)
        assert tr['status'] == 'deadline'
        assert eng.stats()['active_slots'] == 0
        expired = reg.counter('skyt_infer_deadline_expired_total', '')
        assert expired.value() == 1
    finally:
        eng.stop()


@pytest.mark.integration
def test_server_deadline_header_and_disconnect():
    """HTTP layer: malformed X-Request-Deadline 400s before submit; a
    tiny deadline yields a 200 with PARTIAL tokens (the engine freed
    the slot); a client disconnect mid-stream cancels the engine
    request and frees the slot instead of generating into a dead
    socket."""
    from skypilot_tpu.infer import server as server_lib

    faults.configure('engine.loop=latency,arg=0.05')
    reg = metrics_lib.MetricsRegistry()
    eng = _debug_engine(reg)
    eng.start()
    srv = server_lib.InferenceServer(eng)
    port = _free_port()
    _run_app_bg(srv.make_app(), port)
    base = f'http://127.0.0.1:{port}'
    _wait_http(base + '/health', timeout=60)
    try:
        r = requests.post(base + '/generate',
                          json={'tokens': [1, 2, 3], 'max_tokens': 4},
                          headers={'X-Request-Deadline': 'soon'},
                          timeout=10)
        assert r.status_code == 400
        assert "'soon'" in r.json()['error']

        r = requests.post(base + '/generate',
                          json={'tokens': [1, 2, 3],
                                'max_tokens': 1000},
                          headers={'X-Request-Deadline': '0.4'},
                          timeout=60)
        assert r.status_code == 200
        assert 0 < len(r.json()['tokens']) < 60

        # Mid-stream disconnect: read a couple of chunks, then drop
        # the connection; the engine request must cancel (slot frees).
        resp = requests.post(
            base + '/generate',
            json={'tokens': [5, 6, 7], 'max_tokens': 1000,
                  'stream': True},
            stream=True, timeout=60)
        it = resp.iter_lines()
        next(it)
        next(it)
        resp.close()
        deadline = time.time() + 20
        while time.time() < deadline:
            if eng.stats()['active_slots'] == 0:
                break
            time.sleep(0.1)
        assert eng.stats()['active_slots'] == 0
        disconnects = reg.counter(
            'skyt_server_client_disconnects_total', '')
        assert disconnects.value() >= 1
    finally:
        eng.stop()


def test_fault_event_lands_on_server_span(monkeypatch):
    """A server.request fault fired with tracing on must leave its
    `fault.<kind>` event on THAT request's server span (the injection
    runs inside the tracing middleware's span, not in the outermost
    metrics middleware where no span exists yet) — otherwise a chaos
    run's slowdowns are unexplainable at /debug/traces."""
    from skypilot_tpu.infer import server as server_lib

    monkeypatch.setenv('SKYT_TRACE', '1')
    monkeypatch.setenv('SKYT_TRACE_SAMPLE', '1')
    monkeypatch.setenv('SKYT_TRACE_SLOW_MS', '0')
    faults.configure(
        'server.request=latency,arg=0.01,where=path:/generate')
    reg = metrics_lib.MetricsRegistry()
    eng = _debug_engine(reg)
    eng.start()
    srv = server_lib.InferenceServer(eng)
    port = _free_port()
    _run_app_bg(srv.make_app(), port)
    base = f'http://127.0.0.1:{port}'
    try:
        _wait_http(base + '/health')
        r = requests.post(base + '/generate',
                          json={'tokens': [1, 2, 3], 'max_tokens': 4},
                          timeout=60)
        assert r.status_code == 200
        summaries = requests.get(base + '/debug/traces',
                                 timeout=5).json()['recent']
        gen = [t for t in summaries
               if t['attributes'].get('http.path') == '/generate']
        assert gen, summaries
        detail = requests.get(
            base + f"/debug/traces?trace_id={gen[0]['trace_id']}",
            timeout=5).json()
        events = [(s['name'], e['name']) for s in detail['spans']
                  for e in s.get('events', [])]
        assert ('server /generate', 'fault.latency') in events, events
    finally:
        eng.stop()


# ======================================== control plane: crash recovery
def test_fault_crash_kind_sigkills_process():
    """The new 'crash' kind is a true SIGKILL — no handlers, no
    cleanup — distinct from 'preempt' (SIGTERM, catchable)."""
    proc = subprocess.run(
        [sys.executable, '-c',
         'from skypilot_tpu.utils import faults\n'
         "faults.configure('x.y=crash')\n"
         "faults.inject('x.y')\n"
         "print('survived')"],
        capture_output=True, text=True, timeout=120)
    assert proc.returncode == -signal.SIGKILL, proc
    assert 'survived' not in proc.stdout


def test_lbstate_snapshot_roundtrip():
    """LBState is the serializable controller-synced view a standby
    mirrors; age survives the JSON round trip (monotonic stamps don't
    transfer between processes — age does)."""
    from skypilot_tpu.serve import load_balancer as lb_lib
    state = lb_lib.LBState(
        ready_replicas=['http://r1', 'http://r2'],
        replica_qos={'http://r1': {'level': 2}},
        replica_weight_version={'http://r1': 2, 'http://r2': 1},
        synced_at=time.monotonic() - 5.0, version=7)
    restored = lb_lib.LBState.from_json(state.to_json())
    assert restored.ready_replicas == state.ready_replicas
    assert restored.replica_qos == state.replica_qos
    assert restored.replica_weight_version == \
        state.replica_weight_version
    assert restored.version == 7
    assert 4.0 < restored.age_s() < 7.0
    # Fresh state: nothing to be stale about.
    assert lb_lib.LBState().age_s() == 0.0
    # Garbage weight versions are dropped, not crashed on.
    mangled = lb_lib.LBState.from_json(
        '{"ready_replicas": ["http://r1"], '
        '"replica_weight_version": {"http://r1": "bogus", '
        '"http://r2": 4}}')
    assert mangled.replica_weight_version == {'http://r2': 4}


def test_lb_peer_discovery_from_sync(monkeypatch):
    """`--lb-peers auto`: the tier's advertise URLs come from the
    controller's registered-LB list on each sync; a manual list keeps
    discovery off; own URL and own lb_id are filtered out."""
    from skypilot_tpu.serve import load_balancer as lb_lib
    reg = metrics_lib.MetricsRegistry()
    lb = lb_lib.SkyServeLoadBalancer(
        'http://127.0.0.1:1', 18080, metrics_registry=reg,
        lb_id='lb-me', peers=['auto'])
    assert lb.peer_discovery and lb.peers == []
    lb._discover_peers({  # pylint: disable=protected-access
        'lb-me': 'http://127.0.0.1:18080',        # own id: dropped
        'lb-b': 'http://h2:18081/',
        'lb-c': 'http://h3:18082'})
    assert lb.peers == ['http://h2:18081', 'http://h3:18082']
    # Membership churn propagates on the next sync.
    lb._discover_peers({'lb-b': 'http://h2:18081'})  # pylint: disable=protected-access
    assert lb.peers == ['http://h2:18081']
    # Garbage payloads are ignored.
    lb._discover_peers(['not', 'a', 'dict'])  # pylint: disable=protected-access
    assert lb.peers == ['http://h2:18081']
    # Manual list: discovery off, sync lists ignored.
    lb2 = lb_lib.SkyServeLoadBalancer(
        'http://127.0.0.1:1', 18090, metrics_registry=reg,
        lb_id='lb-2', peers=['http://manual:1'])
    assert not lb2.peer_discovery
    lb2._discover_peers({'lb-x': 'http://h9:1'})  # pylint: disable=protected-access
    assert lb2.peers == ['http://manual:1']
    # And weight versions land on the per-replica gauge via
    # apply_state, pruned with the snapshot.
    lb.apply_state(lb_lib.LBState(
        ready_replicas=['http://r1'],
        replica_weight_version={'http://r1': 5},
        synced_at=time.monotonic()))
    gauge = reg.gauge('skyt_lb_replica_weight_version', '',
                      ('lb', 'replica'))
    assert gauge.value('lb-me', 'http://r1') == 5
    lb.apply_state(lb_lib.LBState(
        ready_replicas=['http://r2'],
        replica_weight_version={'http://r2': 6},
        synced_at=time.monotonic()))
    assert ('lb-me', 'http://r1') not in gauge.label_keys()
    assert gauge.value('lb-me', 'http://r2') == 6


def test_lb_stale_mode_serves_and_recovers(monkeypatch):
    """Controller partition (the `lb.sync` fault point): the LB must
    keep serving the last-known ready set instead of draining to 503s,
    surface the mode in /metrics + /debug/lb_state, and leave it the
    moment the sync heals."""
    from aiohttp import web

    from skypilot_tpu.serve import load_balancer as lb_lib

    monkeypatch.setenv('SKYT_SERVE_LB_SYNC_INTERVAL', '0.2')
    monkeypatch.setenv('SKYT_LB_STALE_PROBE_TIMEOUT_S', '1')
    live = _ok_replica('stale-live')

    # Fake controller the LB really syncs from.
    ctrl_port = _free_port()

    async def sync_handler(request):
        del request
        return web.json_response({'ready_replica_urls': [live]})

    ctrl_app = web.Application()
    ctrl_app.router.add_post('/controller/load_balancer_sync',
                             sync_handler)
    _run_app_bg(ctrl_app, ctrl_port)

    reg = metrics_lib.MetricsRegistry()
    lb_port = _free_port()
    lb = lb_lib.SkyServeLoadBalancer(
        f'http://127.0.0.1:{ctrl_port}', lb_port, metrics_registry=reg)
    _run_app_bg(lb.make_app(), lb_port)
    base = f'http://127.0.0.1:{lb_port}'
    deadline = time.time() + 30
    while time.time() < deadline and \
            lb.policy.ready_replicas != [live]:
        time.sleep(0.1)
    assert lb.policy.ready_replicas == [live]

    # Partition: every further sync fails at the fault point.
    faults.configure('lb.sync=error')
    deadline = time.time() + 30
    while time.time() < deadline and not lb._stale:  # pylint: disable=protected-access
        time.sleep(0.1)
    assert lb._stale  # pylint: disable=protected-access

    # Degraded, not down: the stale replica set still serves, and the
    # mode is visible to operators and traces.
    for _ in range(4):
        r = requests.get(base + '/g', timeout=10)
        assert r.status_code == 200 and r.text == 'hello-stale-live'
    state = requests.get(base + '/debug/lb_state', timeout=5).json()
    assert state['stale'] is True
    assert state['ready_replicas'] == [live]
    assert f'skyt_lb_stale{{lb="{lb.lb_id}"}} 1' in requests.get(
        base + '/metrics', timeout=5).text

    # Sync heals: stale mode exits, fresh state applies.
    faults.reset()
    deadline = time.time() + 30
    while time.time() < deadline and lb._stale:  # pylint: disable=protected-access
        time.sleep(0.1)
    assert not lb._stale  # pylint: disable=protected-access
    assert f'skyt_lb_stale{{lb="{lb.lb_id}"}} 0' in requests.get(
        base + '/metrics', timeout=5).text


def test_lb_stale_probe_prunes_dead_replica(monkeypatch):
    """Stale-mode health probes: a replica that dies while the
    controller is partitioned away is pruned from the stale ready set
    (no traffic pinned on a corpse for the whole partition)."""
    from skypilot_tpu.serve import load_balancer as lb_lib

    monkeypatch.setenv('SKYT_SERVE_LB_SYNC_INTERVAL', '0.2')
    monkeypatch.setenv('SKYT_LB_STALE_PROBE_TIMEOUT_S', '1')
    monkeypatch.setenv('SKYT_LB_RETRY_BACKOFF_S', '0.01')
    live = _ok_replica('sp-live')
    # A REAL subprocess replica we can kill mid-partition.
    dead_port = _free_port()
    dead_proc = subprocess.Popen(
        [sys.executable, '-c',
         'import http.server, sys\n'
         'class H(http.server.BaseHTTPRequestHandler):\n'
         '    def do_GET(self):\n'
         '        self.send_response(200); self.end_headers()\n'
         '    def log_message(self, *a): pass\n'
         f'http.server.HTTPServer(("127.0.0.1", {dead_port}), '
         'H).serve_forever()'])
    dead = f'http://127.0.0.1:{dead_port}'
    ctrl_port = _free_port()

    from aiohttp import web

    async def sync_handler(request):
        del request
        return web.json_response({'ready_replica_urls': [live, dead]})

    ctrl_app = web.Application()
    ctrl_app.router.add_post('/controller/load_balancer_sync',
                             sync_handler)
    _run_app_bg(ctrl_app, ctrl_port)

    reg = metrics_lib.MetricsRegistry()
    lb_port = _free_port()
    lb = lb_lib.SkyServeLoadBalancer(
        f'http://127.0.0.1:{ctrl_port}', lb_port, metrics_registry=reg,
        stale_probe_path='/')     # the service's readiness contract
    _run_app_bg(lb.make_app(), lb_port)
    try:
        _wait_http(dead + '/x')
        deadline = time.time() + 30
        while time.time() < deadline and \
                sorted(lb.policy.ready_replicas) != sorted([live, dead]):
            time.sleep(0.1)
        assert sorted(lb.policy.ready_replicas) == sorted([live, dead])
        # Partition, then kill the replica DURING it.
        faults.configure('lb.sync=error')
        deadline = time.time() + 30
        while time.time() < deadline and not lb._stale:  # pylint: disable=protected-access
            time.sleep(0.1)
        dead_proc.kill()
        dead_proc.wait(timeout=30)
        deadline = time.time() + 30
        while time.time() < deadline and \
                dead in lb.policy.ready_replicas:
            time.sleep(0.1)
        assert lb.policy.ready_replicas == [live]
        pruned = reg.counter('skyt_lb_stale_pruned_total', '', ('lb',))
        assert pruned.value(lb.lb_id) >= 1
        # And traffic still flows on the survivor.
        r = requests.get(f'http://127.0.0.1:{lb_port}/g', timeout=10)
        assert r.status_code == 200 and r.text == 'hello-sp-live'
    finally:
        faults.reset()
        if dead_proc.poll() is None:
            dead_proc.kill()


def test_lb_stale_probe_threshold_recovery_and_no_contract(monkeypatch):
    """Stale-mode pruning discipline: (a) a replica is pruned only
    after SKYT_LB_STALE_PROBE_THRESHOLD CONSECUTIVE failures (one slow
    probe under partition load must not drop a loaded replica), (b) a
    pruned replica that recovers is RE-ADDED (probe rounds cover the
    full snapshot, not just survivors), (c) with no readiness contract
    configured the snapshot is served untouched — probing a path the
    replicas never promised would prune healthy ones."""
    import asyncio as aio

    import aiohttp
    from aiohttp import web

    from skypilot_tpu.serve import load_balancer as lb_lib

    monkeypatch.setenv('SKYT_LB_STALE_PROBE_THRESHOLD', '3')
    monkeypatch.setenv('SKYT_LB_STALE_PROBE_TIMEOUT_S', '1')
    health = {'ok': True}

    async def hc(request):
        del request
        return web.Response(status=200 if health['ok'] else 500)

    app = web.Application()
    app.router.add_get('/hc', hc)
    port = _free_port()
    _run_app_bg(app, port)
    url = f'http://127.0.0.1:{port}'
    _wait_http(url + '/hc')

    async def run():
        reg = metrics_lib.MetricsRegistry()
        lb = lb_lib.SkyServeLoadBalancer(
            'http://127.0.0.1:9', 1, metrics_registry=reg,
            stale_probe_path='/hc')
        lb._session = aiohttp.ClientSession()  # pylint: disable=protected-access
        try:
            lb.apply_state(lb_lib.LBState(
                ready_replicas=[url], synced_at=time.monotonic()))
            health['ok'] = False
            for i in range(2):
                await lb._prune_stale_replicas()  # pylint: disable=protected-access
                assert lb.policy.ready_replicas == [url], \
                    f'pruned after only {i + 1} failure(s)'
            await lb._prune_stale_replicas()  # pylint: disable=protected-access
            assert lb.policy.ready_replicas == []     # 3rd: pruned
            pruned = reg.counter('skyt_lb_stale_pruned_total', '',
                                 ('lb',))
            assert pruned.value(lb.lb_id) == 1
            # Recovery: the next round re-probes the full snapshot and
            # re-admits the healed replica.
            health['ok'] = True
            await lb._prune_stale_replicas()  # pylint: disable=protected-access
            assert lb.policy.ready_replicas == [url]
            assert pruned.value(lb.lb_id) == 1        # no double count

            # No contract, no env override: pruning is a no-op even
            # with a stone-dead replica in the snapshot.
            lb2 = lb_lib.SkyServeLoadBalancer(
                'http://127.0.0.1:9', 1,
                metrics_registry=metrics_lib.MetricsRegistry())
            lb2._session = lb._session  # pylint: disable=protected-access
            dead = f'http://127.0.0.1:{_free_port()}'
            lb2.apply_state(lb_lib.LBState(
                ready_replicas=[dead], synced_at=time.monotonic()))
            await lb2._prune_stale_replicas()  # pylint: disable=protected-access
            assert lb2.policy.ready_replicas == [dead]
        finally:
            await lb._session.close()  # pylint: disable=protected-access

    aio.run(run())


def test_lb_stale_ttl_drains(monkeypatch):
    """A stale snapshot older than SKYT_LB_STALE_TTL_S stops being
    served: a too-old world view is worse than an honest 503."""
    import asyncio as aio

    from skypilot_tpu.serve import load_balancer as lb_lib

    monkeypatch.setenv('SKYT_LB_STALE_TTL_S', '0.2')
    reg = metrics_lib.MetricsRegistry()
    lb = lb_lib.SkyServeLoadBalancer('http://127.0.0.1:9', 1,
                                     metrics_registry=reg)
    lb.apply_state(lb_lib.LBState(
        ready_replicas=['http://r1'], synced_at=time.monotonic() - 10))
    assert lb.policy.ready_replicas == ['http://r1']
    aio.run(lb._enter_or_hold_stale())  # pylint: disable=protected-access
    assert lb.policy.ready_replicas == []
    assert reg.gauge('skyt_lb_stale', '',
                     ('lb',)).value(lb.lb_id) == 1


def test_leader_lease_survives_nothing_flock_released_on_kill(tmp_path):
    """LeaderLease is kernel-backed: SIGKILLing the holder releases the
    flock instantly, and a waiting standby acquires on its next poll —
    no heartbeat-expiry guessing."""
    from skypilot_tpu.serve import load_balancer as lb_lib

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    lease_path = str(tmp_path / 'x.lease')
    holder = subprocess.Popen(
        [sys.executable, '-c',
         'import sys, time\n'
         f'sys.path.insert(0, {repo!r})\n'
         'from skypilot_tpu.serve import load_balancer as lb_lib\n'
         f'lease = lb_lib.LeaderLease({lease_path!r})\n'
         'assert lease.try_acquire()\n'
         "print('HELD', flush=True)\n"
         'time.sleep(3600)'],
        stdout=subprocess.PIPE, text=True)
    try:
        assert holder.stdout.readline().strip() == 'HELD'
        ours = lb_lib.LeaderLease(lease_path, interval_s=0.1)
        assert not ours.try_acquire()          # leader alive: denied
        info = ours.holder()
        assert info and info['pid'] == holder.pid
        holder.kill()
        holder.wait(timeout=30)
        deadline = time.time() + 5
        while time.time() < deadline and not ours.try_acquire():
            time.sleep(0.05)
        assert ours.held                       # takeover ≤ one interval
        ours.heartbeat()
        assert ours.holder()['pid'] == os.getpid()
        ours.release()
    finally:
        if holder.poll() is None:
            holder.kill()


def test_restart_adopts_live_and_reaps_orphans(tmp_state_dir,
                                               monkeypatch):
    """Restart adoption truth table, in-process: a live probed replica
    with a matching pid identity is ADOPTED (no relaunch); a dead-pid
    row is reaped even though its endpoint still answers (pid identity
    wins over a lucky probe); a stale-spec-version row is reaped; the
    `replica.orphan` fault point forces the reap path on demand."""
    del tmp_state_dir
    from skypilot_tpu import core as core_lib
    from skypilot_tpu import state as cluster_state
    from skypilot_tpu.runtime import reaper
    from skypilot_tpu.serve import replica_managers
    from skypilot_tpu.serve import serve_state
    from skypilot_tpu.serve import service_spec as spec_lib

    serve_state.reset_db_for_testing()
    spec = spec_lib.ServiceSpec(readiness_path='/', min_replicas=4,
                                probe_timeout_seconds=2)
    serve_state.add_service('rsvc', spec, '/t.yaml', 1, 2)
    live_url = _ok_replica('adopt')
    me = os.getpid()
    token = reaper.pid_start_token(me)

    def row(rid, **kw):
        info = replica_managers.ReplicaInfo(
            replica_id=rid, cluster_name=f'rsvc-{rid}', version=1,
            status=serve_state.ReplicaStatus.READY,
            endpoint=live_url, pid=me, pid_start=token)
        for k, v in kw.items():
            setattr(info, k, v)
        serve_state.upsert_replica('rsvc', rid, info)

    row(1)                                     # adoptable
    row(2, pid=999999)                         # dead pid, live endpoint
    row(3)                                     # fault-forced orphan
    row(4, version=2)                          # stale spec version
    # FAILED row whose teardown the old controller never finished:
    # must be reaped (cluster torn down), not leaked until the prune
    # sweep erases the only record of it.
    row(5, status=serve_state.ReplicaStatus.FAILED)
    faults.configure('replica.orphan=error,where=replica:3')
    monkeypatch.setattr(cluster_state, 'get_cluster',
                        lambda name: {'handle': None})
    downed = []
    monkeypatch.setattr(core_lib, 'down',
                        lambda name, purge=False: downed.append(name))
    reg = metrics_lib.MetricsRegistry()
    mgr = replica_managers.ReplicaManager(
        'rsvc', spec, '/t.yaml', metrics_registry=reg)
    assert mgr.replicas[1].status is serve_state.ReplicaStatus.READY
    assert mgr.replicas[1].adopted_at is not None
    adoptions = reg.counter('skyt_serve_replica_adoptions_total', '',
                            ('service',))
    reaps = reg.counter('skyt_serve_replica_reaps_total', '',
                        ('service', 'reason'))
    assert adoptions.value('rsvc') == 1
    assert reaps.value('rsvc', 'dead_pid') == 1
    assert reaps.value('rsvc', 'fault_injected') == 1
    assert reaps.value('rsvc', 'stale_spec_version') == 1
    assert reaps.value('rsvc', 'failed_pre_restart') == 1
    # Reaped rows head to teardown, not the ready set.
    assert mgr.ready_urls() == [live_url]
    deadline = time.time() + 10
    while time.time() < deadline and len(downed) < 4:
        time.sleep(0.05)
    assert sorted(downed) == ['rsvc-2', 'rsvc-3', 'rsvc-4', 'rsvc-5']


# The replica task for control-plane drills: a dumb 200-everything
# HTTP server (same shape as tests/test_serve.py REPLICA_SERVER).
_REPLICA_SERVER = (
    "python -c \""
    "import http.server, os;\n"
    "class H(http.server.BaseHTTPRequestHandler):\n"
    "    def do_GET(self):\n"
    "        self.send_response(200); self.end_headers();\n"
    "        self.wfile.write(('hello-from-' + "
    "os.environ['SKYT_REPLICA_PORT']).encode())\n"
    "    def do_POST(self):\n"
    "        self.do_GET()\n"
    "    def log_message(self, *a):\n"
    "        pass\n"
    "http.server.HTTPServer(('127.0.0.1', "
    "int(os.environ['SKYT_REPLICA_PORT'])), H).serve_forever()\"")


@pytest.fixture()
def control_plane_env(tmp_path, tmp_state_dir, monkeypatch):
    """Local-provider serve environment with fast control loops, for
    drills that run the real controller as a killable subprocess."""
    del tmp_state_dir
    from skypilot_tpu import state
    from skypilot_tpu.serve import serve_state
    monkeypatch.setenv('SKYT_LOCAL_ROOT', str(tmp_path / 'local'))
    monkeypatch.setenv('SKYT_DEFAULT_STORE', 'local')
    monkeypatch.setenv('SKYT_SERVE_CONTROLLER_INTERVAL', '0.3')
    monkeypatch.setenv('SKYT_SERVE_LB_SYNC_INTERVAL', '0.3')
    state.reset_db_for_testing()
    serve_state.reset_db_for_testing()
    yield tmp_path
    from skypilot_tpu import core as core_lib
    for rec in state.get_clusters():
        try:
            core_lib.down(rec['name'], purge=True)
        except Exception:  # pylint: disable=broad-except
            pass
    state.reset_db_for_testing()
    serve_state.reset_db_for_testing()


def _spawn_service(name, role):
    return subprocess.Popen(
        [sys.executable, '-m', 'skypilot_tpu.serve.service',
         '--service-name', name, '--role', role],
        env=dict(os.environ), stdout=subprocess.DEVNULL,
        stderr=subprocess.STDOUT)


def _wait_replicas_ready(name, want, timeout=120):
    from skypilot_tpu.serve import serve_state
    deadline = time.time() + timeout
    while time.time() < deadline:
        infos = serve_state.get_replicas(name)
        ready = [r for r in infos
                 if r.status is serve_state.ReplicaStatus.READY]
        if len(ready) >= want:
            return ready
        time.sleep(0.5)
    raise AssertionError(
        f'{want} replicas never READY: '
        f'{[(r.replica_id, r.status) for r in serve_state.get_replicas(name)]}')


@pytest.mark.integration
def test_chaos_controller_sigkill_adoption_zero_relaunches(
        control_plane_env):
    """THE control-plane acceptance drill: SIGKILL the controller
    mid-burst. In-flight and subsequent requests keep succeeding
    through the LB's stale-state mode (0 client-visible 5xx, replicas
    were never touched), and a restarted controller ADOPTS every READY
    replica — zero relaunches, asserted via /controller/metrics."""
    import yaml as yaml_lib

    import skypilot_tpu as sky
    from skypilot_tpu import resources as resources_lib
    from skypilot_tpu.serve import load_balancer as lb_lib
    from skypilot_tpu.serve import serve_state
    from skypilot_tpu.serve import service_spec as spec_lib

    tmp_path = control_plane_env
    task = sky.Task(name='ccp', run=_REPLICA_SERVER)
    task.set_resources(resources_lib.Resources(cloud='local'))
    spec = spec_lib.ServiceSpec(
        readiness_path='/', min_replicas=2,
        initial_delay_seconds=60, probe_timeout_seconds=2)
    task.service = spec
    task_yaml = str(tmp_path / 'ccp.task.yaml')
    with open(task_yaml, 'w', encoding='utf-8') as f:
        yaml_lib.safe_dump(task.to_yaml_config(), f)
    cport, lport = _free_port(), _free_port()
    assert serve_state.add_service('ccp', spec, task_yaml, cport, lport)
    token = serve_state.get_service('ccp')['auth_token']

    ctrl = _spawn_service('ccp', 'controller')
    lb = None
    try:
        _wait_replicas_ready('ccp', 2)
        # The LB runs in OUR process (it must survive the controller
        # kill), syncing from the real controller.
        reg = metrics_lib.MetricsRegistry()
        lb_port = _free_port()
        lb = lb_lib.SkyServeLoadBalancer(
            f'http://127.0.0.1:{cport}', lb_port,
            controller_auth=token, metrics_registry=reg)
        _run_app_bg(lb.make_app(), lb_port)
        base = f'http://127.0.0.1:{lb_port}'
        deadline = time.time() + 60
        while time.time() < deadline and \
                len(lb.policy.ready_replicas) < 2:
            time.sleep(0.2)
        assert len(lb.policy.ready_replicas) == 2

        results = []
        lock = threading.Lock()

        def one(i):
            r = requests.get(base + f'/burst-{i}', timeout=60)
            with lock:
                results.append(r.status_code)

        threads = [threading.Thread(target=one, args=(i,))
                   for i in range(12)]
        for th in threads[:4]:
            th.start()
        # The chaos event: controller dies mid-burst, no grace.
        ctrl.kill()
        for th in threads[4:]:
            th.start()
        for th in threads:
            th.join(timeout=120)
        ctrl.wait(timeout=30)
        assert results == [200] * 12, results

        # The LB noticed the partition and kept serving stale state.
        deadline = time.time() + 30
        while time.time() < deadline and not lb._stale:  # pylint: disable=protected-access
            time.sleep(0.2)
        assert lb._stale  # pylint: disable=protected-access
        r = requests.get(base + '/after-death', timeout=30)
        assert r.status_code == 200

        # Restart: the new controller must ADOPT, not relaunch.
        ctrl = _spawn_service('ccp', 'controller')
        _wait_replicas_ready('ccp', 2)
        headers = {'Authorization': f'Bearer {token}'}
        deadline = time.time() + 60
        metrics_text = ''
        while time.time() < deadline:
            try:
                metrics_text = requests.get(
                    f'http://127.0.0.1:{cport}/controller/metrics',
                    headers=headers, timeout=5).text
                if ('skyt_serve_replica_adoptions_total'
                        '{service="ccp"} 2') in metrics_text:
                    break
            except requests.RequestException:
                pass
            time.sleep(0.5)
        assert ('skyt_serve_replica_adoptions_total{service="ccp"} 2'
                in metrics_text), metrics_text
        # Zero relaunches: the launch counter never ticked in the
        # restarted process, and no reap happened.
        assert 'skyt_serve_replica_launches_total{service="ccp"}' \
            not in metrics_text, metrics_text
        # (sample lines carry labels — the bare name also appears in
        # HELP/TYPE headers, so match the labeled form)
        assert 'skyt_serve_replica_reaps_total{' not in metrics_text, \
            metrics_text
        # Same replica ids as before the crash — really the same
        # replicas, not lookalikes.
        ready = _wait_replicas_ready('ccp', 2)
        assert {r.replica_id for r in ready} == {1, 2}
        assert all(r.adopted_at is not None for r in ready)
        # And the healed sync pulls the LB out of stale mode.
        deadline = time.time() + 30
        while time.time() < deadline and lb._stale:  # pylint: disable=protected-access
            time.sleep(0.2)
        assert not lb._stale  # pylint: disable=protected-access
        assert requests.get(base + '/after-restart',
                            timeout=30).status_code == 200
    finally:
        if ctrl.poll() is None:
            ctrl.kill()
        del lb


@pytest.mark.integration
def test_controller_crash_fault_point_fires(control_plane_env,
                                            monkeypatch):
    """`SKYT_FAULTS=controller.crash=crash` SIGKILLs the controller
    from inside its own control loop — the arm-it-and-watch way to run
    the restart-adoption drill without test scaffolding kills."""
    import yaml as yaml_lib

    import skypilot_tpu as sky
    from skypilot_tpu import resources as resources_lib
    from skypilot_tpu.serve import serve_state
    from skypilot_tpu.serve import service_spec as spec_lib

    tmp_path = control_plane_env
    task = sky.Task(name='crsvc', run='sleep 3600')
    task.set_resources(resources_lib.Resources(cloud='local'))
    spec = spec_lib.ServiceSpec(readiness_path='/', min_replicas=0,
                                max_replicas=1,
                                target_qps_per_replica=1.0)
    task.service = spec
    task_yaml = str(tmp_path / 'crsvc.task.yaml')
    with open(task_yaml, 'w', encoding='utf-8') as f:
        yaml_lib.safe_dump(task.to_yaml_config(), f)
    assert serve_state.add_service('crsvc', spec, task_yaml,
                                   _free_port(), _free_port())
    monkeypatch.setenv('SKYT_FAULTS', 'controller.crash=crash,after=2')
    ctrl = _spawn_service('crsvc', 'controller')
    try:
        ctrl.wait(timeout=120)
        assert ctrl.returncode == -signal.SIGKILL, ctrl.returncode
    finally:
        if ctrl.poll() is None:
            ctrl.kill()


@pytest.mark.integration
def test_lb_standby_takes_over_port(tmp_state_dir, monkeypatch):
    """Hot-standby failover: two `--role lb` processes; the leader
    owns the port, the standby mirrors LBState via the same controller
    sync. SIGKILL the leader → the standby takes over the port within
    ~one lease interval and serves the same replica set."""
    from aiohttp import web

    from skypilot_tpu.serve import serve_state
    from skypilot_tpu.serve import service as service_lib
    from skypilot_tpu.serve import service_spec as spec_lib

    del tmp_state_dir
    serve_state.reset_db_for_testing()
    monkeypatch.setenv('SKYT_LB_LEASE_INTERVAL_S', '0.2')
    monkeypatch.setenv('SKYT_SERVE_LB_SYNC_INTERVAL', '0.3')
    replica = _ok_replica('standby-drill')
    cport, lport = _free_port(), _free_port()
    spec = spec_lib.ServiceSpec(readiness_path='/', min_replicas=1)
    assert serve_state.add_service('sbsvc', spec, '/t.yaml', cport,
                                   lport)

    async def sync_handler(request):
        del request
        return web.json_response({'ready_replica_urls': [replica]})

    ctrl_app = web.Application()
    ctrl_app.router.add_post('/controller/load_balancer_sync',
                             sync_handler)
    _run_app_bg(ctrl_app, cport)

    lbs = [_spawn_service('sbsvc', 'lb') for _ in range(2)]
    base = f'http://127.0.0.1:{lport}'
    lease_path = service_lib.lb_lease_path('sbsvc')
    try:
        _wait_http(base + '/g', timeout=120)
        r = requests.get(base + '/g', timeout=10)
        assert r.status_code == 200 and r.text == 'hello-standby-drill'
        with open(lease_path, 'r', encoding='utf-8') as f:
            leader_pid = __import__('json').loads(f.read())['pid']
        assert leader_pid in [p.pid for p in lbs]
        standby_pid = next(p.pid for p in lbs if p.pid != leader_pid)

        os.kill(leader_pid, signal.SIGKILL)
        t0 = time.time()
        deadline = t0 + 30
        took_over = None
        while time.time() < deadline:
            try:
                r = requests.get(base + '/g', timeout=5)
                if r.status_code == 200:
                    took_over = time.time() - t0
                    break
            except requests.RequestException:
                pass
            time.sleep(0.1)
        assert took_over is not None, 'standby never took the port'
        assert r.text == 'hello-standby-drill'
        with open(lease_path, 'r', encoding='utf-8') as f:
            assert __import__('json').loads(f.read())['pid'] == \
                standby_pid
        # The new leader advertises leadership on its own /metrics.
        assert f'skyt_lb_leader{{lb="lb-{lport}"}} 1' in requests.get(
            base + '/metrics', timeout=5).text
    finally:
        for p in lbs:
            if p.poll() is None:
                p.kill()
        serve_state.remove_service('sbsvc')


# ======================================= N-active LB tier (front door)
def test_lb_gossip_partition_and_reconverge(monkeypatch):
    """Two active LBs exchanging LBState via gossip. Partition BOTH
    planes (`lb.sync=error` + `lb.gossip=error`): each LB keeps
    serving from its own stale view (degraded, never down), the peer
    views age past SKYT_LB_PEER_STALE_S and leave the aggregates.
    Heal: stale mode exits and the peers reconverge to fresh."""
    from aiohttp import web

    from skypilot_tpu.serve import load_balancer as lb_lib

    monkeypatch.setenv('SKYT_SERVE_LB_SYNC_INTERVAL', '0.2')
    monkeypatch.setenv('SKYT_LB_PEER_SYNC_S', '0.2')
    monkeypatch.setenv('SKYT_LB_PEER_STALE_S', '0.6')
    live = _ok_replica('gsp')
    ctrl_port = _free_port()

    async def sync_handler(request):
        del request
        return web.json_response({'ready_replica_urls': [live]})

    ctrl_app = web.Application()
    ctrl_app.router.add_post('/controller/load_balancer_sync',
                             sync_handler)
    _run_app_bg(ctrl_app, ctrl_port)

    ports = [_free_port(), _free_port()]
    urls = [f'http://127.0.0.1:{p}' for p in ports]
    lbs = []
    for port, peer in zip(ports, reversed(urls)):
        lb = lb_lib.SkyServeLoadBalancer(
            f'http://127.0.0.1:{ctrl_port}', port,
            policy='prefix_affinity',
            metrics_registry=metrics_lib.MetricsRegistry(),
            peers=[peer])
        _run_app_bg(lb.make_app(), port)
        lbs.append(lb)

    def states():
        return [requests.get(u + '/debug/lb_state', timeout=5).json()
                for u in urls]

    def all_fresh(sts):
        return all(s['ready_replicas'] == [live] and s['peers'] and
                   all(p['fresh'] for p in s['peers'].values())
                   for s in sts)

    deadline = time.time() + 30
    while time.time() < deadline:
        try:
            if all_fresh(states()):
                break
        except requests.RequestException:
            pass            # LB apps still binding
        time.sleep(0.2)
    assert all_fresh(states()), states()

    # Full partition: controller sync AND gossip fail everywhere.
    faults.configure('lb.sync=error;lb.gossip=error')
    deadline = time.time() + 30
    while time.time() < deadline:
        sts = states()
        if all(s['stale'] for s in sts) and \
                not any(p['fresh'] for s in sts
                        for p in s['peers'].values()):
            break
        time.sleep(0.2)
    sts = states()
    assert all(s['stale'] for s in sts), sts
    assert not any(p['fresh'] for s in sts
                   for p in s['peers'].values()), sts
    # Degraded, not down: BOTH keep serving their stale views.
    for u in urls:
        r = requests.get(u + '/g', timeout=10)
        assert r.status_code == 200 and r.text == 'hello-gsp'

    # Heal: stale mode exits and the tier reconverges.
    faults.reset()
    deadline = time.time() + 30
    while time.time() < deadline:
        sts = states()
        if not any(s['stale'] for s in sts) and all_fresh(sts):
            break
        time.sleep(0.2)
    sts = states()
    assert not any(s['stale'] for s in sts), sts
    assert all_fresh(sts), sts
    del lbs


def test_lb_gossip_rejects_unauthenticated_and_unconfigured(monkeypatch):
    """/lb/gossip lives on the CLIENT-facing port: with the service
    token configured it 401s unauthenticated senders, and payloads
    whose advertised URL is not in the configured peer list never
    become a PeerView — an arbitrary client must not be able to
    poison the routing view or grow the peer table."""
    from skypilot_tpu.serve import load_balancer as lb_lib

    monkeypatch.setenv('SKYT_SERVE_LB_SYNC_INTERVAL', '3600')
    port = _free_port()
    lb = lb_lib.SkyServeLoadBalancer(
        'http://127.0.0.1:9', port, controller_auth='sekrit',
        metrics_registry=metrics_lib.MetricsRegistry(),
        peers=['http://127.0.0.1:1'])
    _run_app_bg(lb.make_app(), port)
    base = f'http://127.0.0.1:{port}'
    deadline = time.time() + 30
    while time.time() < deadline:
        try:
            requests.get(base + '/metrics', timeout=2)
            break
        except requests.RequestException:
            time.sleep(0.1)
    forged = {'lb_id': 'evil', 'url': 'http://attacker:80',
              'state': {'ready_replicas': ['http://attacker:80'],
                        'age_s': 0.0}}
    r = requests.post(base + '/lb/gossip', json=forged, timeout=5)
    assert r.status_code == 401
    assert lb._peer_views == {}  # pylint: disable=protected-access
    # Right token, but the sender's URL is not a configured peer:
    # answered (push-pull still works mid-rolling-update), absorbed
    # NOT — no PeerView, no poisoned avoid set, no adopted state.
    r = requests.post(base + '/lb/gossip', json=forged, timeout=5,
                      headers={'Authorization': 'Bearer sekrit'})
    assert r.status_code == 200
    assert lb._peer_views == {}  # pylint: disable=protected-access
    # A configured peer with the token IS absorbed.
    ok = {'lb_id': 'lb-1', 'url': 'http://127.0.0.1:1',
          'state': {'ready_replicas': ['http://r1'], 'age_s': 0.0}}
    r = requests.post(base + '/lb/gossip', json=ok, timeout=5,
                      headers={'Authorization': 'Bearer sekrit'})
    assert r.status_code == 200
    assert list(lb._peer_views) == ['lb-1']  # pylint: disable=protected-access


def _spawn_lb(name, port, peer_urls, extra_env=None):
    env = dict(os.environ)
    env.update(extra_env or {})
    return subprocess.Popen(
        [sys.executable, '-m', 'skypilot_tpu.serve.service',
         '--service-name', name, '--role', 'lb',
         '--lb-port', str(port), '--lb-peers', ','.join(peer_urls)],
        env=env, stdout=subprocess.DEVNULL, stderr=subprocess.STDOUT)


@pytest.mark.integration
def test_chaos_n_active_lb_sigkill_mid_burst(tmp_state_dir,
                                             monkeypatch):
    """THE front-door acceptance drill (docs/robustness.md "Front
    door"): 3 ACTIVE LB processes (prefix_affinity ring, peer gossip)
    serving a concurrent burst; one SIGKILLs itself mid-burst via the
    `lb.crash` fault point. Clients that fail over to a surviving LB
    see ZERO 5xx, the same affinity key keeps routing to the same
    replica through every survivor (deterministic ring — the dead
    LB's traffic is absorbed with affinity intact), and the dead peer
    leaves the survivors' fresh-peer sets within one exchange
    interval + staleness bound."""
    from aiohttp import web

    from skypilot_tpu.serve import serve_state
    from skypilot_tpu.serve import service_spec as spec_lib

    del tmp_state_dir
    serve_state.reset_db_for_testing()
    monkeypatch.setenv('SKYT_SERVE_LB_SYNC_INTERVAL', '0.2')
    monkeypatch.setenv('SKYT_LB_PEER_SYNC_S', '0.2')
    monkeypatch.setenv('SKYT_LB_PEER_STALE_S', '1.0')
    r1, r2 = _ok_replica('na-r1'), _ok_replica('na-r2')
    ctrl_port = _free_port()
    spec = spec_lib.ServiceSpec(
        readiness_path='/', min_replicas=2,
        load_balancing_policy='prefix_affinity')
    assert serve_state.add_service('nasvc', spec, '/t.yaml',
                                   ctrl_port, _free_port())

    ctrl_up = {'ok': True}   # flipped to partition the controller

    async def sync_handler(request):
        del request
        if not ctrl_up['ok']:
            return web.json_response({'error': 'partitioned'},
                                     status=503)
        return web.json_response({
            'ready_replica_urls': [r1, r2],
            'replica_prefix_cache': {r1: {'occupancy': 0.4},
                                     r2: {'occupancy': 0.1}}})

    ctrl_app = web.Application()
    ctrl_app.router.add_post('/controller/load_balancer_sync',
                             sync_handler)
    _run_app_bg(ctrl_app, ctrl_port)

    ports = [_free_port() for _ in range(3)]
    urls = [f'http://127.0.0.1:{p}' for p in ports]
    procs = []
    for i, port in enumerate(ports):
        peers = [u for u in urls if u != urls[i]]
        extra = None
        if i == 0:
            # The chaos event comes from INSIDE: the first LB SIGKILLs
            # itself on its 4th proxied request (lb.crash fires in the
            # proxy path only — /debug and /lb/gossip don't count).
            extra = {'SKYT_FAULTS': 'lb.crash=crash,after=3'}
        procs.append(_spawn_lb('nasvc', port, peers, extra_env=extra))

    def lb_state(u, timeout=5):
        return requests.get(u + '/debug/lb_state',
                            timeout=timeout).json()

    try:
        deadline = time.time() + 120
        while time.time() < deadline:
            try:
                sts = [lb_state(u) for u in urls]
                if all(sorted(s['ready_replicas']) == sorted([r1, r2])
                       and sum(1 for p in s['peers'].values()
                               if p['fresh']) == 2 for s in sts):
                    break
            except requests.RequestException:
                pass
            time.sleep(0.3)
        else:
            raise AssertionError('N-active tier never converged')

        # Ring consistency across the tier, pre-kill: the same keyed
        # body routes to the SAME replica through the two LBs that
        # will survive (the doomed one must not see proxy traffic
        # before the burst).
        keyed = {'tokens': [7, 8, 9], 'max_tokens': 2}
        homes = {requests.post(u + '/gen', json=keyed,
                               timeout=10).headers['X-Replica-Id']
                 for u in urls[1:]}
        assert len(homes) == 1, homes
        home = homes.pop()

        results = []
        lock = threading.Lock()

        def one(i):
            # A front-door client: try LBs in order until one answers
            # (the VIP/DNS failover a real deployment has). Transport
            # errors against a dead LB are expected; an HTTP 5xx from
            # a SURVIVOR is the failure this drill exists to catch.
            for attempt, u in enumerate(
                    urls[i % 3:] + urls[:i % 3]):
                try:
                    r = requests.post(
                        u + f'/burst-{i}', json=keyed
                        if i % 2 == 0 else {'tokens': [i], 'n': i},
                        headers={'X-Session-Id': f'sess-{i % 4}'},
                        timeout=30)
                    with lock:
                        results.append(
                            (r.status_code,
                             r.headers.get('X-Replica-Id')))
                    return
                except requests.RequestException:
                    continue
            with lock:
                results.append((599, None))   # no LB answered at all

        threads = [threading.Thread(target=one, args=(i,))
                   for i in range(24)]
        for th in threads[:8]:
            th.start()
        # lb.crash fires inside procs[0] during this window.
        for th in threads[8:]:
            th.start()
        for th in threads:
            th.join(timeout=120)
        assert len(results) == 24
        codes = [c for c, _ in results]
        # Zero client-visible 5xx: every request landed 200 on SOME
        # active LB.
        assert codes == [200] * 24, codes

        # The fault actually fired: LB 0 died by SIGKILL.
        deadline = time.time() + 30
        while time.time() < deadline and procs[0].poll() is None:
            time.sleep(0.2)
        assert procs[0].returncode == -signal.SIGKILL, \
            procs[0].returncode

        # Survivors drop the dead peer from their fresh sets within
        # one exchange interval + the staleness bound.
        dead_id = f'lb-{ports[0]}'
        deadline = time.time() + 30
        while time.time() < deadline:
            sts = [lb_state(u) for u in urls[1:]]
            if all(not s['peers'].get(dead_id, {}).get('fresh', True)
                   for s in sts):
                break
            time.sleep(0.2)
        sts = [lb_state(u) for u in urls[1:]]
        assert all(not s['peers'].get(dead_id, {}).get('fresh', True)
                   for s in sts), sts
        # Ring reconvergence: both survivors still route the key to
        # its pre-kill home (replicas never churned, so no key moved).
        for u in urls[1:]:
            r = requests.post(u + '/gen', json=keyed, timeout=10)
            assert r.status_code == 200
            assert r.headers['X-Replica-Id'] == home
            assert lb_state(u)['ring']['nodes'], 'ring emptied'

        # Same window, second chaos event: the CONTROLLER partitions.
        # Both survivors must degrade to per-LB stale mode — still
        # serving the full healthy replica set, nothing drained.
        ctrl_up['ok'] = False
        deadline = time.time() + 30
        while time.time() < deadline:
            if all(lb_state(u)['stale'] for u in urls[1:]):
                break
            time.sleep(0.2)
        for u in urls[1:]:
            s = lb_state(u)
            assert s['stale'], s
            assert sorted(s['ready_replicas']) == sorted([r1, r2]), \
                'stale mode drained healthy replicas'
            r = requests.post(u + '/gen', json=keyed, timeout=10)
            assert r.status_code == 200
            assert r.headers['X-Replica-Id'] == home
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
        serve_state.remove_service('nasvc')


# ================================================ preemption guard modes
def test_preemption_guard_immediate_exit_during_startup():
    """Startup phase (immediate=True): SIGTERM exits with
    EXIT_CODE_PREEMPTED on the spot — no step boundary is coming for
    minutes during weight streaming / first compile, and burning the
    preemption grace window there ends in SIGKILL + FAILED.
    cooperative() then hands the exit back to the step loop."""
    from skypilot_tpu.runtime.job_lib import EXIT_CODE_PREEMPTED
    from skypilot_tpu.train import checkpoint as ckpt_lib

    if threading.current_thread() is not threading.main_thread():
        pytest.skip('signal handlers need the main thread')
    guard = ckpt_lib.PreemptionGuard(immediate=True)
    try:
        with pytest.raises(SystemExit) as exc:
            os.kill(os.getpid(), signal.SIGTERM)
            deadline = time.time() + 10
            while time.time() < deadline:   # handler needs a bytecode
                time.sleep(0.001)           # boundary on this thread
            pytest.fail('immediate guard never fired')
        assert exc.value.code == EXIT_CODE_PREEMPTED
        assert guard.requested and guard.signum == signal.SIGTERM
    finally:
        guard.restore()

    guard = ckpt_lib.PreemptionGuard(immediate=True)
    try:
        guard.cooperative()   # step loop started: flag-only from here
        os.kill(os.getpid(), signal.SIGTERM)
        deadline = time.time() + 10
        while not guard.requested and time.time() < deadline:
            time.sleep(0.001)
        assert guard.requested
    finally:
        guard.restore()


# ==================================== real stack: replica kill mid-burst
def _spawn_replica(port: int, extra_env=None,
                   max_seq_len: int = 64) -> subprocess.Popen:
    env = dict(os.environ, JAX_PLATFORMS='cpu')
    env.update(extra_env or {})
    return subprocess.Popen(
        [sys.executable, '-m', 'skypilot_tpu.infer.server',
         '--model', 'debug', '--port', str(port),
         '--num-slots', '2', '--max-seq-len', str(max_seq_len)],
        env=env, stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL)


@pytest.mark.integration
def test_chaos_replica_kill_mid_burst(monkeypatch):
    """The acceptance scenario: a burst through the REAL LB -> server
    -> engine stack while one of two replica PROCESSES is SIGKILLed
    mid-burst. Every request whose response headers had not been sent
    completes on the surviving replica — zero client-visible 5xx —
    and the breaker opens on the dead replica."""
    p1, p2 = _free_port(), _free_port()
    procs = [_spawn_replica(p1), _spawn_replica(p2)]
    url1, url2 = (f'http://127.0.0.1:{p1}', f'http://127.0.0.1:{p2}')
    try:
        for proc, url in zip(procs, (url1, url2)):
            _wait_http(url + '/health', timeout=180, proc=proc)
        lb, base, reg = _make_lb([url1, url2], monkeypatch,
                                 SKYT_LB_RETRY_BACKOFF_S='0.02',
                                 SKYT_LB_BREAKER_THRESHOLD='2',
                                 SKYT_LB_BREAKER_COOLDOWN_S='30')
        results = []
        lock = threading.Lock()

        def one(i):
            r = requests.post(
                base + '/generate',
                json={'tokens': [i + 1, i + 2, i + 3],
                      'max_tokens': 8},
                timeout=60)
            with lock:
                results.append((r.status_code,
                                r.headers.get('X-Replica-Id')))

        threads = [threading.Thread(target=one, args=(i,))
                   for i in range(12)]
        for i, th in enumerate(threads[:4]):
            th.start()
        # Kill replica 1 mid-burst (SIGKILL: no graceful anything).
        procs[0].kill()
        for th in threads[4:]:
            th.start()
        for th in threads:
            th.join(timeout=120)
        assert len(results) == 12
        # Zero client-visible 5xx: every pre-header failure was
        # retried onto the survivor.
        assert all(code == 200 for code, _ in results), results
        survivors = {rep for code, rep in results}
        assert url2 in survivors
        # The breaker opened on the dead replica well before any
        # controller sync could eject it.
        assert lb.breaker.state(url1) == lb.breaker.OPEN
        text = requests.get(base + '/metrics', timeout=5).text
        assert (f'skyt_lb_breaker_state{{lb="{lb.lb_id}",'
                f'replica="{url1}"}} 2') in text
        retries = reg.counter('skyt_lb_retries_total', '',
                              ('lb', 'replica'))
        assert retries.value(lb.lb_id, url1) >= 1
    finally:
        for proc in procs:
            if proc.poll() is None:
                proc.kill()


@pytest.mark.integration
def test_chaos_interference_survives_replica_kill():
    """Tick-plane drill (docs/observability.md "Tick plane"): a
    mid-burst replica SIGKILL must not poison the survivor's
    interference accounting. The survivor's pure-decode baselines stay
    warm and finite, fresh requests still get a decode-floor/
    interference ITL split, and the fleet rollup ages the dead replica
    out past the stale horizon instead of carrying its frozen series
    into the advisor's inputs forever."""
    from skypilot_tpu.serve import fleet as fleet_lib

    class Clock:
        def __init__(self):
            self.t = time.time()

        def __call__(self):
            return self.t

    p1, p2 = _free_port(), _free_port()
    tick_env = {'SKYT_TICKSTATS': '1',
                'SKYT_INTERFERENCE_MIN_SAMPLES': '2'}
    procs = [_spawn_replica(p1, tick_env), _spawn_replica(p2, tick_env)]
    urls = [f'http://127.0.0.1:{p1}', f'http://127.0.0.1:{p2}']
    try:
        for proc, url in zip(procs, urls):
            _wait_http(url + '/health', timeout=180, proc=proc)
        # Warm both replicas: multi-chunk decodes give every tick/ITL
        # series a first scrape edge and warm the decode baselines.
        for url in urls:
            for _ in range(3):
                requests.post(
                    url + '/generate',
                    json={'tokens': [5, 6, 7], 'max_tokens': 24},
                    timeout=120).raise_for_status()
        clock = Clock()
        fl = fleet_lib.FleetTelemetry(
            'chaos', metrics_registry=metrics_lib.MetricsRegistry(),
            clock=clock)
        assert fl.scrape('0', urls[0])
        assert fl.scrape('1', urls[1])

        def burst(url):
            for i in range(30):
                try:
                    requests.post(
                        url + '/generate',
                        json={'tokens': [i % 13 + 2, 3, 4],
                              'max_tokens': 16},
                        timeout=30)
                except requests.RequestException:
                    pass   # in-flight work on the killed replica

        threads = [threading.Thread(target=burst, args=(u,))
                   for u in urls for _ in range(2)]
        for th in threads:
            th.start()
        time.sleep(1.0)
        procs[0].kill()   # SIGKILL mid-burst: no graceful anything
        for th in threads:
            th.join(timeout=180)

        time.sleep(0.3)
        clock.t += 40
        assert not fl.scrape('0', urls[0])   # dead: scrape fails
        assert fl.scrape('1', urls[1])

        # Survivor's baselines are warm, finite, and un-poisoned.
        summ = requests.get(urls[1] + '/debug/ticks?last=16',
                            timeout=10).json()['summary']
        assert summ['ticks'] > 0
        assert summ['baselines'], summ
        for b in summ['baselines'].values():
            assert 0.0 < b['ewma_s'] < 5.0, summ['baselines']
        # Fresh work after the kill still accrues an ITL split.
        before = summ['classes']['standard']['decode_floor_s']
        requests.post(urls[1] + '/generate',
                      json={'tokens': [9, 9, 9], 'max_tokens': 24},
                      timeout=120).raise_for_status()
        after = requests.get(urls[1] + '/debug/ticks?last=1',
                             timeout=10).json()['summary']
        assert after['classes']['standard']['decode_floor_s'] > before

        # Rollup at the scrape horizon: both targets present, the
        # survivor's families advanced through the burst.
        rep = fl.interference_report(window_s=600, now=clock.t)
        t1 = rep['targets']['1']
        assert sum(t1['ticks'].values()) > 0
        assert t1['itl_split'], t1
        assert t1['advisor']['recommendation'] in (
            'disaggregate', 'keep_colocated', 'insufficient_data')

        # Past the stale horizon the dead replica ages out of the
        # rollup; the recently-scraped survivor stays.
        rep2 = fl.interference_report(window_s=600,
                                      now=clock.t + fl.stale_s - 5)
        assert '0' not in rep2['targets'], sorted(rep2['targets'])
        assert '1' in rep2['targets'], sorted(rep2['targets'])
    finally:
        for proc in procs:
            if proc.poll() is None:
                proc.kill()


@pytest.mark.integration
def test_chaos_batch_flood_sheds_only_batch(monkeypatch):
    """QoS acceptance scenario (docs/qos.md) through the REAL LB ->
    server -> engine stack: a batch-class flood against one replica
    with SKYT_QOS=1 and aggressive shed thresholds. Every interactive
    request must succeed (zero 429/5xx) while batch sheds are > 0 —
    visible in the replica's /metrics AND in the LB's observed-shed
    counter (the QoS-aware autoscaler's scale-up signal)."""
    port = _free_port()
    proc = _spawn_replica(port, extra_env={
        'SKYT_QOS': '1',
        'SKYT_QOS_QUEUE_DEGRADE': '1',
        'SKYT_QOS_QUEUE_SHED': '2',
        'SKYT_QOS_DEGRADE_MAX_TOKENS': '4',
        'SKYT_QOS_RESERVE_SLOTS': '1',
        'SKYT_QOS_REFRESH_S': '0.05',
        'SKYT_QOS_HOLD_S': '5',
        # Queue depth drives the drill; the debug model's TTFT jitter
        # must not escalate the ladder on its own.
        'SKYT_QOS_TTFT_SLO_MS': '0',
    })
    url = f'http://127.0.0.1:{port}'
    try:
        _wait_http(url + '/health', timeout=180, proc=proc)
        lb, base, reg = _make_lb([url], monkeypatch, SKYT_QOS='1')
        stop = threading.Event()

        def flood():
            s = requests.Session()
            while not stop.is_set():
                try:
                    r = s.post(base + '/generate',
                               json={'tokens': [3, 4, 5],
                                     'max_tokens': 48},
                               headers={'X-Priority': 'batch',
                                        'X-Tenant': 'flooder'},
                               timeout=60)
                    if r.status_code == 429:
                        # Well-behaved batch clients honor Retry-After
                        # (capped so the flood persists through the
                        # interactive probes).
                        time.sleep(min(float(
                            r.headers.get('Retry-After', 1)), 0.25))
                except requests.RequestException:
                    pass

        flooders = [threading.Thread(target=flood, daemon=True)
                    for _ in range(6)]
        for th in flooders:
            th.start()
        time.sleep(2.0)             # let the backlog build + ladder arm
        sess = requests.Session()
        codes = []
        for i in range(10):
            r = sess.post(base + '/generate',
                          json={'tokens': [i + 1, i + 2],
                                'max_tokens': 4},
                          headers={'X-Priority': 'interactive'},
                          timeout=120)
            codes.append(r.status_code)
        stop.set()
        for th in flooders:
            th.join(timeout=60)
        # Zero interactive 429/5xx: the flood only ever sheds batch.
        assert codes == [200] * 10, codes
        text = requests.get(url + '/metrics', timeout=5).text

        def shed(cls):
            total = 0.0
            for line in text.splitlines():
                if line.startswith(
                        f'skyt_qos_shed_total{{class="{cls}"'):
                    total += float(line.rsplit(' ', 1)[1])
            return total

        assert shed('batch') > 0, 'batch flood never shed'
        assert shed('interactive') == 0, 'interactive was shed'
        # The LB saw the upstream 429s and attributed them to the
        # batch class (the autoscaler's shed-rate signal).
        observed = reg.counter('skyt_lb_qos_sheds_observed_total', '',
                               ('lb', 'class'))
        assert observed.value(lb.lb_id, 'batch') > 0
        assert observed.value(lb.lb_id, 'interactive') == 0
        del lb
    finally:
        if proc.poll() is None:
            proc.kill()


@pytest.mark.integration
def test_chaos_flash_crowd_sheds_only_sheddable_class(monkeypatch):
    """Capacity-plane acceptance drill (docs/observability.md
    "Capacity plane"): a deterministic workload-engine schedule with a
    20x flash-crowd step, replayed open-loop through the REAL
    in-process LB -> server -> engine stack with SKYT_QOS=1. The
    protected interactive class rides through the step with zero
    429/5xx, only the sheddable batch class sheds (and the sheds land
    inside the crowd window), and both classes serve again after the
    crowd passes."""
    from skypilot_tpu.benchmark import workload

    port = _free_port()
    proc = _spawn_replica(port, extra_env={
        'SKYT_QOS': '1',
        # Aggressive thresholds sized to the 2-slot debug replica:
        # batch sheds as soon as 2 requests queue (ratio q/slots >= 1).
        'SKYT_QOS_QUEUE_DEGRADE': '0.5',
        'SKYT_QOS_QUEUE_SHED': '1',
        'SKYT_QOS_DEGRADE_MAX_TOKENS': '4',
        'SKYT_QOS_RESERVE_SLOTS': '1',
        'SKYT_QOS_REFRESH_S': '0.05',
        'SKYT_QOS_HOLD_S': '2',
        'SKYT_QOS_TTFT_SLO_MS': '0',
    })
    url = f'http://127.0.0.1:{port}'
    try:
        _wait_http(url + '/health', timeout=180, proc=proc)
        lb, base, reg = _make_lb([url], monkeypatch, SKYT_QOS='1')
        spec = workload.WorkloadSpec(
            seed=7, duration_s=16.0, rate_rps=1.5, arrival='poisson',
            flash_at_s=6.0, flash_factor=20.0, flash_duration_s=4.0,
            tenants=(
                workload.TenantProfile(
                    tenant='clicky', cls='interactive', weight=1.0,
                    prompt_mean=3.0, prompt_sigma=0.3, prompt_cap=6,
                    output_mean=3.0, output_sigma=0.3, output_cap=4,
                    session_pool=2, session_reuse=0.5, prefix_len=2),
                workload.TenantProfile(
                    tenant='cruncher', cls='batch', weight=3.0,
                    prompt_mean=4.0, prompt_sigma=0.3, prompt_cap=8,
                    output_mean=40.0, output_sigma=0.5, output_cap=48,
                    session_pool=2, session_reuse=0.2, prefix_len=2)))
        sched = workload.generate_schedule(spec)
        # The drill is replayable: same spec, byte-identical schedule.
        assert workload.schedule_digest(sched) == \
            workload.schedule_digest(workload.generate_schedule(spec))
        runner = workload.OpenLoopRunner(
            workload.http_submitter(base, timeout_s=120.0),
            compression=2.0)
        outcomes = runner.run(sched)
        summary = workload.summarize(outcomes, compression=2.0)
        inter = summary['classes']['interactive']
        batch = summary['classes']['batch']
        # Protected class: zero 429/5xx/transport errors through a
        # 20x step the 2-slot replica cannot possibly serve in full.
        assert inter['shed'] == 0, summary
        assert inter['errors_5xx'] == 0, summary
        assert inter['transport_errors'] == 0, summary
        assert inter['ok'] == inter['offered'], summary
        # Sheddable class absorbed the crowd — sheds happened, inside
        # the flash window, and never as a 5xx.
        assert batch['shed'] > 0, summary
        assert any(o.status == 429 and 6.0 <= o.arrival.t < 10.0
                   for o in outcomes), summary
        assert batch['errors_5xx'] == 0, summary
        text = requests.get(url + '/metrics', timeout=5).text
        assert 'skyt_qos_shed_total{class="batch"' in text
        assert 'skyt_qos_shed_total{class="interactive"' not in text
        # The busy ledger attributed the drill's engine time to both
        # (class, tenant, model) slices — the cost half of the plane.
        led = requests.get(url + '/stats',
                           timeout=5).json()['capacity_ledger']
        attr = led['attributed_seconds']
        assert 'interactive/clicky/debug' in attr or \
            any(k.startswith('interactive/clicky/') for k in attr), led
        assert any(k.startswith('batch/cruncher/') for k in attr), led
        assert sum(attr.values()) <= led['busy_seconds'] + 1e-6
        # Recovery: once the crowd passes and the hold expires, BOTH
        # classes serve again (batch included).
        sess = requests.Session()
        for cls in ('interactive', 'batch'):
            deadline = time.time() + 60
            status = None
            while time.time() < deadline:
                r = sess.post(base + '/generate',
                              json={'tokens': [2, 3, 4],
                                    'max_tokens': 4},
                              headers={'X-Priority': cls,
                                       'X-Tenant': 'probe'},
                              timeout=60)
                status = r.status_code
                if status == 200:
                    break
                time.sleep(0.5)
            assert status == 200, \
                f'{cls} did not recover after the flash crowd'
        observed = reg.counter('skyt_lb_qos_sheds_observed_total', '',
                               ('lb', 'class'))
        assert observed.value(lb.lb_id, 'interactive') == 0
    finally:
        if proc.poll() is None:
            proc.kill()


# ========================================== preemption-safe training exit
@pytest.mark.integration
def test_sft_preemption_checkpoint_and_resume(tmp_path):
    """SIGTERM mid-run: sft checkpoints at the next step boundary,
    waits for the async save, and exits EXIT_CODE_PREEMPTED; a rerun
    resumes from that step instead of step 0."""
    from skypilot_tpu.runtime.job_lib import EXIT_CODE_PREEMPTED
    ckpt_dir = tmp_path / 'ckpt'
    env = dict(os.environ, JAX_PLATFORMS='cpu')
    args = [sys.executable, '-m', 'skypilot_tpu.train.sft',
            '--model', 'debug', '--steps', '100000',
            '--batch', '1', '--seq', '16',
            '--checkpoint-dir', str(ckpt_dir),
            '--checkpoint-every', '5', '--log-every', '5']
    proc = subprocess.Popen(args, env=env, stdout=subprocess.PIPE,
                            stderr=subprocess.STDOUT, text=True)
    try:
        # Wait until at least one periodic checkpoint landed.
        deadline = time.time() + 300
        while time.time() < deadline:
            if proc.poll() is not None:
                out = proc.stdout.read()
                raise AssertionError(
                    f'sft died early rc={proc.returncode}:\n{out[-2000:]}')
            steps = [int(p.name) for p in ckpt_dir.glob('[0-9]*')
                     if p.name.isdigit()]
            if steps:
                break
            time.sleep(0.5)
        else:
            raise AssertionError('no checkpoint appeared')
        proc.send_signal(signal.SIGTERM)
        out, _ = proc.communicate(timeout=120)
        assert proc.returncode == EXIT_CODE_PREEMPTED, out[-2000:]
        assert 'preemption requested' in out
        saved_steps = sorted(int(p.name) for p in ckpt_dir.glob('[0-9]*')
                             if p.name.isdigit())
        assert saved_steps, out[-2000:]
        resume_at = saved_steps[-1]

        # Resume run: must start from the preemption checkpoint.
        args2 = list(args)
        args2[args2.index('--steps') + 1] = str(resume_at + 3)
        out2 = subprocess.run(args2, env=env, stdout=subprocess.PIPE,
                              stderr=subprocess.STDOUT, text=True,
                              timeout=300, check=True).stdout
        assert f'resumed from step {resume_at}' in out2
    finally:
        if proc.poll() is None:
            proc.kill()


def test_preempted_exit_code_maps_to_preempted_status(tmp_path,
                                                      monkeypatch):
    """runtime layer: a gang rank exiting EXIT_CODE_PREEMPTED is not a
    failure — the job lands in PREEMPTED (which the managed-jobs
    controller recovers) instead of FAILED."""
    monkeypatch.setenv('SKYT_AGENT_HOME', str(tmp_path))
    from skypilot_tpu.runtime import job_lib
    jid = job_lib.add_job('prejob', {'num_nodes': 2})
    job_lib.gang_mark(jid, 0, 'DONE', 0)
    job_lib.gang_mark(jid, 1, 'DONE', job_lib.EXIT_CODE_PREEMPTED)
    assert not job_lib.gang_any_failed(jid)
    assert job_lib.gang_any_preempted(jid)
    assert job_lib.gang_all_done(jid)
    # A real nonzero exit still reads as failure.
    job_lib.gang_mark(jid, 0, 'DONE', 1)
    assert job_lib.gang_any_failed(jid)


def test_preempted_wins_over_collateral_rank_failure(tmp_path,
                                                     monkeypatch):
    """Report-ordering race: when a preemption SIGTERMs the gang, the
    non-signalled ranks' collectives abort with real nonzero codes and
    usually report FIRST. The later rc=75 must still flip the job to
    PREEMPTED (the recovery signal), whichever order reports land."""
    monkeypatch.setenv('SKYT_AGENT_HOME', str(tmp_path))
    from skypilot_tpu.runtime import job_lib
    from skypilot_tpu.runtime import server as rt_server
    head = rt_server.HeadState(rt_server.ClusterConfig(
        {'cluster_name': 'c', 'num_nodes': 2,
         'ips': ['127.0.0.1', '127.0.0.2']}))
    # Order A: collateral failure first, cooperative exit second.
    jid = head.submit({'name': 'j1', 'run': 'x', 'num_nodes': 2})
    head.report(jid, 1, 'done', 1)
    assert job_lib.get_job(jid)['status'] is job_lib.JobStatus.FAILED
    head.report(jid, 0, 'done', job_lib.EXIT_CODE_PREEMPTED)
    assert job_lib.get_job(jid)['status'] is \
        job_lib.JobStatus.PREEMPTED
    # Order B: cooperative exit first; a later collateral failure must
    # not downgrade PREEMPTED back to FAILED.
    jid2 = head.submit({'name': 'j2', 'run': 'x', 'num_nodes': 2})
    head.report(jid2, 0, 'done', job_lib.EXIT_CODE_PREEMPTED)
    head.report(jid2, 1, 'done', 1)
    assert job_lib.get_job(jid2)['status'] is \
        job_lib.JobStatus.PREEMPTED
    # No 75 anywhere: plain failure, no recovery.
    jid3 = head.submit({'name': 'j3', 'run': 'x', 'num_nodes': 2})
    head.report(jid3, 0, 'done', 1)
    head.report(jid3, 1, 'done', 0)
    assert job_lib.get_job(jid3)['status'] is job_lib.JobStatus.FAILED


# ===================================== gang hang watchdog recovery drill
@pytest.mark.integration
def test_chaos_gang_hang_watchdog_recovery(tmp_path, tmp_state_dir,
                                           monkeypatch):
    """THE training-plane acceptance drill (docs/observability.md
    "Training plane"): one rank of a REAL 2-rank gang wedges via
    SKYT_FAULTS=train.step=hang -> the head agent's gang watchdog
    confirms the hang and escalates the cluster job to HUNG -> every
    rank has dumped a postmortem bundle (the hung rank via its
    sentinel, the survivor via the SIGTERM guard) -> the managed-jobs
    controller recovers (kill gang, relaunch) -> sft RESUMES from its
    preemption-era checkpoint -> SUCCEEDED, zero manual intervention.
    """
    import json
    import pathlib

    import skypilot_tpu as sky
    from skypilot_tpu import resources as resources_lib
    from skypilot_tpu import state
    from skypilot_tpu.jobs import core as jobs_core
    from skypilot_tpu.jobs import state as jobs_state
    from skypilot_tpu.train import postmortem as postmortem_lib

    drill = tmp_path / 'drill'
    drill.mkdir()
    pm_dir = tmp_path / 'postmortems'   # durable across the relaunch
    monkeypatch.setenv('SKYT_LOCAL_ROOT', str(tmp_path / 'local'))
    monkeypatch.setenv('SKYT_JOBS_CHECK_GAP', '0.3')
    monkeypatch.setenv('SKYT_JOBS_PREEMPTION_GRACE', '1')
    # Fast watchdog thresholds (agents inherit this env at provision).
    monkeypatch.setenv('SKYT_WATCHDOG_MIN_S', '3')
    monkeypatch.setenv('SKYT_WATCHDOG_FACTOR', '2')
    monkeypatch.setenv('SKYT_WATCHDOG_CONFIRM', '2')
    monkeypatch.setenv('SKYT_WATCHDOG_INTERVAL_S', '0.5')
    monkeypatch.setenv('SKYT_WATCHDOG_POLL_S', '0.3')
    monkeypatch.setenv('SKYT_HEARTBEAT_INTERVAL_S', '0.1')
    state.reset_db_for_testing()
    jobs_state.reset_db_for_testing()

    # Rank 1 arms the hang fault ONCE (marker-guarded, so the
    # recovered incarnation runs clean); a small latency fault on
    # every step keeps rank 0 running long enough to be SIGTERM'd by
    # the HUNG kill (exercising its preempt-bundle path). The JAX
    # coordinator triplet is cleared: on the CPU backend each rank is
    # its own single-process jax runtime (multiprocess CPU collectives
    # are unimplemented in jax 0.4.x — the watchdog plane is what is
    # under test).
    run_cmd = f'''
RANK="$SKYT_NODE_RANK"
if [ "$RANK" = "1" ] && [ ! -f "{drill}/armed" ]; then
  touch "{drill}/armed"
  export SKYT_FAULTS="$SKYT_FAULTS;train.step=hang,arg=600,after=4"
fi
env SKYT_NUM_NODES=1 JAX_COORDINATOR_ADDRESS= JAX_NUM_PROCESSES= \\
    JAX_PROCESS_ID= \\
  {sys.executable} -m skypilot_tpu.train.sft --model debug \\
  --steps 120 --batch 1 --seq 16 --prefetch 0 \\
  --checkpoint-dir "{drill}/ckpt/rank-$RANK" --checkpoint-every 2 \\
  --log-every 10 2>&1 | tee -a "{drill}/rank-$RANK.out"
exit "${{PIPESTATUS[0]}}"
'''
    t = sky.Task(name='hangdrill', run=run_cmd, num_nodes=2,
                 envs={'SKYT_POSTMORTEM_DIR': str(pm_dir),
                       'SKYT_FAULTS': 'train.step=latency,arg=0.1',
                       'JAX_PLATFORMS': 'cpu'})
    t.set_resources(resources_lib.Resources(cloud='local'))

    jid = jobs_core.launch(t, retry_until_up=False)
    saw_recovering = False
    deadline = time.time() + 900
    job = None
    try:
        while time.time() < deadline:
            job = jobs_state.get_job(jid)
            if job['status'] == jobs_state.ManagedJobStatus.RECOVERING:
                saw_recovering = True
            if job['status'].is_terminal():
                break
            time.sleep(0.1)
        else:
            pytest.fail(f'drill never finished: {job}')

        out1 = (drill / 'rank-1.out').read_text() \
            if (drill / 'rank-1.out').exists() else ''
        assert job['status'] == jobs_state.ManagedJobStatus.SUCCEEDED, \
            (job, out1[-2000:])
        assert job['recovery_count'] >= 1
        assert saw_recovering

        # Bundles from EVERY rank, durable across the relaunch: the
        # hung rank's sentinel bundle plus the survivor's SIGTERM
        # (preempt) bundle — each with stacks + spans + train state.
        bundles = postmortem_lib.list_bundles(root=str(pm_dir))
        reasons = {(b.get('rank'), b.get('reason')) for b in bundles}
        assert (1, 'hang') in reasons, bundles
        assert (0, 'preempt') in reasons, bundles
        for b in bundles:
            assert {'stacks.txt', 'spans.json', 'state.json'} <= \
                set(b['files']), b
        hang_state = json.loads(
            (pathlib.Path(next(
                b['path'] for b in bundles
                if (b.get('rank'), b.get('reason')) == (1, 'hang')))
             / 'state.json').read_text())
        assert hang_state['heartbeat']['stall']['stalled'] is True

        # The recovered rank resumed from its pre-hang checkpoint
        # (resume-from-step-k, not step 0).
        assert 'resumed from step' in out1, out1[-2000:]
    finally:
        for j in jobs_state.get_jobs():
            if not j['status'].is_terminal():
                try:
                    jobs_core.cancel([j['job_id']])
                except Exception:  # pylint: disable=broad-except
                    pass
        t_end = time.time() + 30
        while time.time() < t_end and any(
                not j['status'].is_terminal()
                for j in jobs_state.get_jobs()):
            time.sleep(0.5)
        for rec in state.get_clusters():
            try:
                from skypilot_tpu import core as sky_core
                sky_core.down(rec['name'], purge=True)
            except Exception:  # pylint: disable=broad-except
                pass
        state.reset_db_for_testing()
        jobs_state.reset_db_for_testing()


# ===================================== zero-downtime rolling updates
def _save_debug_checkpoints(tmp_path, seeds=(0, 7, 11)):
    """HF-format debug-model checkpoints (one per seed) the engine
    server's swap loader can read."""
    import dataclasses as _dc

    import jax
    import jax.numpy as jnp

    from skypilot_tpu.models import llama
    from skypilot_tpu.models import weights as weights_lib
    cfg = _dc.replace(llama.CONFIGS['debug'], max_seq_len=64,
                      param_dtype='float32', dtype='float32')
    model = llama.LlamaModel(cfg)
    zeros = jnp.zeros((1, 8), jnp.int32)
    out = []
    for i, seed in enumerate(seeds):
        params = jax.jit(model.init)(jax.random.PRNGKey(seed), zeros)
        path = str(tmp_path / f'ckpt_{chr(ord("a") + i)}')
        weights_lib.save_hf_checkpoint(cfg, params, path)
        out.append(path)
    return out


_ENGINE_REPLICA = (
    'python -m skypilot_tpu.infer.server --model debug '
    '--port "$SKYT_REPLICA_PORT" --num-slots 2 --max-seq-len 64')


def _wait_rollout_phase(cport, token, phases, timeout=180):
    headers = {'Authorization': f'Bearer {token}'}
    deadline = time.time() + timeout
    last = None
    while time.time() < deadline:
        try:
            last = requests.get(
                f'http://127.0.0.1:{cport}/controller/status',
                headers=headers, timeout=10).json()
            ro = last.get('rollout') or {}
            if ro.get('phase') in phases:
                return last
        except requests.RequestException:
            pass
        time.sleep(0.3)
    raise AssertionError(
        f'rollout never reached {phases}: '
        f'{(last or {}).get("rollout")}')


@pytest.mark.integration
def test_chaos_rolling_update_canary_rollback(control_plane_env,
                                              monkeypatch):
    """THE zero-downtime-rollout drill (docs/robustness.md
    "Zero-downtime rollouts", validation step 15): 2 REAL engine
    replicas behind the real controller + an in-process LB.

    Run 1 (unfaulted): a mid-burst rolling update to checkpoint B
    lands the new weight version fleet-wide — zero client-visible
    5xx, zero relaunches (the launch counter never ticks past the
    initial 2), every replica at weight_version 2.

    Run 2 (faulted): `weights.swap=error` armed on checkpoint C — the
    canary's swap aborts with its old weights intact, the rollout
    auto-rolls-back, the mid-burst traffic still sees zero 5xx, and
    the fleet ends on the OLD version with the spec uncommitted."""
    import yaml as yaml_lib

    import skypilot_tpu as sky
    from skypilot_tpu import resources as resources_lib
    from skypilot_tpu.serve import load_balancer as lb_lib
    from skypilot_tpu.serve import serve_state
    from skypilot_tpu.serve import service_spec as spec_lib
    from skypilot_tpu.train import push_weights

    tmp_path = control_plane_env
    ckpt_a, ckpt_b, ckpt_c = _save_debug_checkpoints(tmp_path)
    # Arm the canary-kill for run 2 ONLY: the where= filter keys on
    # the pushed checkpoint, so run 1 (ckpt_b) is untouched. The env
    # is inherited by the replica processes at launch.
    monkeypatch.setenv('SKYT_FAULTS',
                       f'weights.swap=error,where=checkpoint:{ckpt_c}')
    monkeypatch.setenv('SKYT_ROLLOUT_BAKE_S', '0.5')
    task = sky.Task(name='rsvc', run=_ENGINE_REPLICA)
    task.set_resources(resources_lib.Resources(cloud='local'))
    spec = spec_lib.ServiceSpec(
        readiness_path='/health', min_replicas=2,
        initial_delay_seconds=600, probe_timeout_seconds=5,
        weights=ckpt_a)
    task.service = spec
    task_yaml = str(tmp_path / 'rsvc.task.yaml')
    with open(task_yaml, 'w', encoding='utf-8') as f:
        yaml_lib.safe_dump(task.to_yaml_config(), f)
    cport, lport = _free_port(), _free_port()
    assert serve_state.add_service('rsvc', spec, task_yaml, cport,
                                   lport)
    token = serve_state.get_service('rsvc')['auth_token']
    headers = {'Authorization': f'Bearer {token}'}
    curl = f'http://127.0.0.1:{cport}'

    ctrl = _spawn_service('rsvc', 'controller')
    lb = None
    try:
        _wait_replicas_ready('rsvc', 2, timeout=420)
        reg = metrics_lib.MetricsRegistry()
        lb_port = _free_port()
        lb = lb_lib.SkyServeLoadBalancer(
            curl, lb_port, controller_auth=token,
            metrics_registry=reg)
        _run_app_bg(lb.make_app(), lb_port)
        base = f'http://127.0.0.1:{lb_port}'
        deadline = time.time() + 120
        while time.time() < deadline and \
                len(lb.policy.ready_replicas) < 2:
            time.sleep(0.2)
        assert len(lb.policy.ready_replicas) == 2

        results = []
        stop_burst = threading.Event()
        lock = threading.Lock()

        def burst():
            i = 0
            while not stop_burst.is_set():
                i += 1
                try:
                    r = requests.post(
                        base + '/generate',
                        json={'tokens': [1 + (i % 5), 2, 3],
                              'max_tokens': 6},
                        timeout=120)
                    code = r.status_code
                except requests.RequestException as e:
                    code = f'EXC:{e!r}'
                with lock:
                    results.append(code)

        threads = [threading.Thread(target=burst) for _ in range(3)]
        for th in threads:
            th.start()
        try:
            # ---- run 1: clean rolling update, driven through the
            # real weight-push client (train/push_weights.py).
            state = push_weights.push(curl, ckpt_b, token=token,
                                      wait=True, timeout_s=300)
            assert state['phase'] == 'done'
        finally:
            time.sleep(1.0)     # a little post-rollout traffic
            stop_burst.set()
            for th in threads:
                th.join(timeout=120)
        with lock:
            run1 = list(results)
        assert run1 and all(c == 200 for c in run1), run1[:20]
        status = requests.get(curl + '/controller/status',
                              headers=headers, timeout=10).json()
        assert all(r['weight_version'] == 2 and r['version'] == 2
                   for r in status['replicas']), status['replicas']
        # Zero relaunches: the launch counter holds at the initial 2.
        mtext = requests.get(curl + '/controller/metrics',
                             headers=headers, timeout=10).text
        assert 'skyt_serve_replica_launches_total{service="rsvc"} 2' \
            in mtext, mtext
        # The LB saw the new version through the sync.
        deadline = time.time() + 30
        while time.time() < deadline and \
                set(lb.state.replica_weight_version.values()) != {2}:
            time.sleep(0.3)
        assert set(lb.state.replica_weight_version.values()) == {2}

        # ---- run 2: the armed fault kills the canary's swap.
        results.clear()
        stop_burst.clear()
        threads = [threading.Thread(target=burst) for _ in range(3)]
        for th in threads:
            th.start()
        try:
            resp = requests.post(curl + '/controller/rolling_update',
                                 json={'checkpoint': ckpt_c},
                                 headers=headers, timeout=30)
            assert resp.status_code == 200, resp.text
            status = _wait_rollout_phase(cport, token,
                                         ('rolled_back',),
                                         timeout=240)
        finally:
            time.sleep(1.0)
            stop_burst.set()
            for th in threads:
                th.join(timeout=120)
        with lock:
            run2 = list(results)
        assert run2 and all(c == 200 for c in run2), run2[:20]
        ro = status['rollout']
        assert ro['phase'] == 'rolled_back'
        assert 'swap failed' in (ro['error'] or '')
        # Fleet ends on the OLD version; spec never committed.
        assert all(r['weight_version'] == 2 and r['version'] == 2
                   for r in status['replicas']), status['replicas']
        assert serve_state.get_service('rsvc')['version'] == 2
        # Still zero relaunches across BOTH runs.
        mtext = requests.get(curl + '/controller/metrics',
                             headers=headers, timeout=10).text
        assert 'skyt_serve_replica_launches_total{service="rsvc"} 2' \
            in mtext, mtext
        assert ('skyt_serve_rollouts_total{service="rsvc",'
                'outcome="done"} 1') in mtext
        assert ('skyt_serve_rollouts_total{service="rsvc",'
                'outcome="rolled_back"} 1') in mtext
    finally:
        if ctrl.poll() is None:
            try:
                requests.post(curl + '/controller/terminate', json={},
                              headers=headers, timeout=60)
            except requests.RequestException:
                pass
            ctrl.kill()
        del lb


def _wait_adapter_phase(cport, token, phases, timeout=240):
    headers = {'Authorization': f'Bearer {token}'}
    deadline = time.time() + timeout
    last = None
    while time.time() < deadline:
        try:
            last = requests.get(
                f'http://127.0.0.1:{cport}/controller/status',
                headers=headers, timeout=10).json()
            au = last.get('adapter_update') or {}
            if au.get('phase') in phases:
                return last
        except requests.RequestException:
            pass
        time.sleep(0.3)
    raise AssertionError(
        f'adapter update never reached {phases}: '
        f'{(last or {}).get("adapter_update")}')


def _save_debug_adapter(tmp_path, rank=2, alpha=4.0, seed=9):
    """An Orbax adapter dir shaped exactly like an `sft --lora-rank`
    run writes (TrainStateS), for the debug model the drill's
    replicas serve."""
    import dataclasses as _dc

    import jax
    import jax.numpy as jnp
    import numpy as np
    import flax.linen as nn

    from skypilot_tpu.models import llama
    from skypilot_tpu.train import checkpoint as ckpt_lib
    from skypilot_tpu.train import lora as tlora
    from skypilot_tpu.train import trainer

    cfg = _dc.replace(llama.CONFIGS['debug'], max_seq_len=64)
    model = llama.LlamaModel(cfg)
    params = nn.meta.unbox(
        jax.jit(model.init)(jax.random.PRNGKey(0),
                            jnp.zeros((1, 8), jnp.int32))['params'])
    lcfg = tlora.LoRAConfig(rank=rank, alpha=alpha)
    tree = tlora.init_lora_params(params, lcfg,
                                  jax.random.PRNGKey(seed))
    rng = np.random.default_rng(seed)
    tree = jax.tree.map(
        lambda x: jnp.asarray(rng.normal(0, 0.1, x.shape), x.dtype),
        tree)
    tx = trainer.make_optimizer(trainer.TrainerConfig())
    state = trainer.TrainStateS(step=jnp.zeros((), jnp.int32),
                                params=tree, opt_state=tx.init(tree))
    path = str(tmp_path / 'adapter_fr')
    ck = ckpt_lib.Checkpointer(path, async_save=False)
    ck.save(0, state, force=True)
    ck.wait()
    ck.close()
    return path


@pytest.mark.integration
def test_chaos_adapter_hot_load_drill(control_plane_env):
    """THE adapter hot-load drill (docs/serving.md "Adapter fleet",
    validation step 21): 2 REAL engine replicas behind the real
    controller + an in-process LB. A fleet-wide adapter load lands
    mid-burst through POST /controller/adapters — zero client-visible
    5xx, zero relaunches — then the front door routes by model name
    (aggregated /v1/models, honest 404), a direct unload is REFUSED
    while requests reference the adapter, and the fleet-wide unload
    converges clean."""
    import yaml as yaml_lib

    import skypilot_tpu as sky
    from skypilot_tpu import resources as resources_lib
    from skypilot_tpu.serve import load_balancer as lb_lib
    from skypilot_tpu.serve import serve_state
    from skypilot_tpu.serve import service_spec as spec_lib

    tmp_path = control_plane_env
    adapter_dir = _save_debug_adapter(tmp_path)
    task = sky.Task(name='asvc', run=_ENGINE_REPLICA)
    task.set_resources(resources_lib.Resources(cloud='local'))
    spec = spec_lib.ServiceSpec(
        readiness_path='/health', min_replicas=2,
        initial_delay_seconds=600, probe_timeout_seconds=5)
    task.service = spec
    task_yaml = str(tmp_path / 'asvc.task.yaml')
    with open(task_yaml, 'w', encoding='utf-8') as f:
        yaml_lib.safe_dump(task.to_yaml_config(), f)
    cport, lport = _free_port(), _free_port()
    assert serve_state.add_service('asvc', spec, task_yaml, cport,
                                   lport)
    token = serve_state.get_service('asvc')['auth_token']
    headers = {'Authorization': f'Bearer {token}'}
    curl = f'http://127.0.0.1:{cport}'

    ctrl = _spawn_service('asvc', 'controller')
    lb = None
    try:
        _wait_replicas_ready('asvc', 2, timeout=420)
        reg = metrics_lib.MetricsRegistry()
        lb_port = _free_port()
        lb = lb_lib.SkyServeLoadBalancer(
            curl, lb_port, controller_auth=token,
            metrics_registry=reg)
        _run_app_bg(lb.make_app(), lb_port)
        base = f'http://127.0.0.1:{lb_port}'
        deadline = time.time() + 120
        while time.time() < deadline and \
                len(lb.policy.ready_replicas) < 2:
            time.sleep(0.2)
        assert len(lb.policy.ready_replicas) == 2

        results = []
        stop_burst = threading.Event()
        lock = threading.Lock()

        def burst(lora=None):
            i = 0
            while not stop_burst.is_set():
                i += 1
                body = {'tokens': [1 + (i % 5), 2, 3],
                        'max_tokens': 6}
                if lora:
                    body['lora'] = lora
                try:
                    r = requests.post(base + '/generate', json=body,
                                      timeout=120)
                    code = r.status_code
                except requests.RequestException as e:
                    code = f'EXC:{e!r}'
                with lock:
                    results.append(code)

        threads = [threading.Thread(target=burst) for _ in range(3)]
        for th in threads:
            th.start()
        try:
            # ---- fleet-wide hot load, mid-burst.
            resp = requests.post(
                curl + '/controller/adapters',
                json={'op': 'load', 'name': 'fr',
                      'checkpoint': adapter_dir, 'alpha': 4.0},
                headers=headers, timeout=30)
            assert resp.status_code == 200, resp.text
            # A second update while one is active: 409, not a queue.
            resp2 = requests.post(
                curl + '/controller/adapters',
                json={'op': 'load', 'name': 'de',
                      'checkpoint': adapter_dir},
                headers=headers, timeout=30)
            assert resp2.status_code == 409, resp2.text
            status = _wait_adapter_phase(cport, token, ('done',))
        finally:
            time.sleep(1.0)     # a little post-load traffic
            stop_burst.set()
            for th in threads:
                th.join(timeout=120)
        with lock:
            run1 = list(results)
        assert run1 and all(c == 200 for c in run1), run1[:20]
        au = status['adapter_update']
        assert au['op'] == 'load' and au['name'] == 'fr'
        assert len(au['updated']) == 2, au
        # Zero relaunches: hot load never restarted a replica.
        mtext = requests.get(curl + '/controller/metrics',
                             headers=headers, timeout=10).text
        assert 'skyt_serve_replica_launches_total{service="asvc"} 2' \
            in mtext, mtext
        # The adapter set rides the sync into the LB's world view.
        deadline = time.time() + 60
        while time.time() < deadline and not (
                len(lb.state.replica_adapters) == 2 and
                all('fr' in named for named in
                    lb.state.replica_adapters.values())):
            time.sleep(0.3)
        assert all('fr' in named for named in
                   lb.state.replica_adapters.values()), \
            lb.state.replica_adapters

        # Front door model surface: aggregated /v1/models lists the
        # adapter fleet-wide (and teaches the LB the base id).
        models = requests.get(base + '/v1/models', timeout=30).json()
        by_id = {e['id']: e for e in models['data']}
        assert 'fr' in by_id and by_id['fr'].get('parent') == 'debug'
        assert by_id['fr'].get('replicas') == 2
        # Model-named request serves through the adapter...
        r = requests.post(base + '/v1/completions',
                          json={'model': 'fr', 'prompt': 'hi',
                                'max_tokens': 4}, timeout=120)
        assert r.status_code == 200, r.text
        # ...and a model NOBODY hosts is an honest front-door 404.
        r = requests.post(base + '/v1/completions',
                          json={'model': 'ghost', 'prompt': 'hi',
                                'max_tokens': 4}, timeout=120)
        assert r.status_code == 404, r.text
        assert r.json()['error']['code'] == 'model_not_found'

        # ---- unload-while-referenced: long adapter generations hold
        # the id in flight on a specific replica; its direct unload
        # must 409 with the stack untouched.
        cstat = requests.get(curl + '/controller/status',
                             headers=headers, timeout=10).json()
        endpoint = cstat['replicas'][0]['endpoint']
        long_results = []

        def long_gen():
            r = requests.post(
                endpoint + '/generate',
                json={'tokens': [1, 2, 3], 'max_tokens': 60,
                      'lora': 'fr'}, timeout=120)
            long_results.append(r.status_code)

        lthreads = [threading.Thread(target=long_gen)
                    for _ in range(6)]
        for th in lthreads:
            th.start()
        time.sleep(0.05)
        r = requests.post(endpoint + '/admin/adapters',
                          json={'op': 'unload', 'name': 'fr'},
                          headers=headers, timeout=30)
        assert r.status_code == 409, (r.status_code, r.text)
        assert 'referenced' in r.json()['error']
        for th in lthreads:
            th.join(timeout=120)
        assert long_results == [200] * 6, long_results

        # ---- fleet-wide unload converges clean once drained.
        resp = requests.post(curl + '/controller/adapters',
                             json={'op': 'unload', 'name': 'fr'},
                             headers=headers, timeout=30)
        assert resp.status_code == 200, resp.text
        _wait_adapter_phase(cport, token, ('done',))
        deadline = time.time() + 60
        while time.time() < deadline and any(
                'fr' in named for named in
                lb.state.replica_adapters.values()):
            time.sleep(0.3)
        assert not any('fr' in named for named in
                       lb.state.replica_adapters.values())
        # Both converges visible in the orchestrator counter; still
        # zero relaunches across the whole drill.
        mtext = requests.get(curl + '/controller/metrics',
                             headers=headers, timeout=10).text
        assert ('skyt_serve_adapter_updates_total{service="asvc",'
                'outcome="done"} 2') in mtext, mtext
        assert 'skyt_serve_replica_launches_total{service="asvc"} 2' \
            in mtext, mtext
    finally:
        if ctrl.poll() is None:
            try:
                requests.post(curl + '/controller/terminate', json={},
                              headers=headers, timeout=60)
            except requests.RequestException:
                pass
            ctrl.kill()
        del lb


_ADMIN_FAKE_REPLICA = (
    "python -c \""
    "import http.server, json, os;\n"
    "class H(http.server.BaseHTTPRequestHandler):\n"
    "    def _ok(self, body=b'ok'):\n"
    "        self.send_response(200); self.end_headers();\n"
    "        self.wfile.write(body)\n"
    "    def do_GET(self):\n"
    "        self._ok()\n"
    "    def do_POST(self):\n"
    "        n = int(self.headers.get('Content-Length') or 0);\n"
    "        self.rfile.read(n);\n"
    "        self._ok(json.dumps({'ok': True}).encode())\n"
    "    def log_message(self, *a):\n"
    "        pass\n"
    "http.server.HTTPServer(('127.0.0.1', "
    "int(os.environ['SKYT_REPLICA_PORT'])), H).serve_forever()\"")


@pytest.mark.integration
def test_chaos_rollout_resume_after_controller_sigkill(
        control_plane_env, monkeypatch):
    """Controller SIGKILLed mid-BAKE: the restarted controller adopts
    both replicas (zero relaunches) AND recovers the persisted
    rollout — canary/bake observations died with the process, so it
    conservatively swaps the canary back and lands 'rolled_back' with
    the baseline spec intact."""
    import yaml as yaml_lib

    import skypilot_tpu as sky
    from skypilot_tpu import resources as resources_lib
    from skypilot_tpu.serve import serve_state
    from skypilot_tpu.serve import service_spec as spec_lib

    tmp_path = control_plane_env
    # A bake long enough that the kill lands inside it.
    monkeypatch.setenv('SKYT_ROLLOUT_BAKE_S', '600')
    task = sky.Task(name='rrsvc', run=_ADMIN_FAKE_REPLICA)
    task.set_resources(resources_lib.Resources(cloud='local'))
    spec = spec_lib.ServiceSpec(
        readiness_path='/', min_replicas=2, initial_delay_seconds=60,
        probe_timeout_seconds=2, weights=str(tmp_path / 'w1'))
    task.service = spec
    task_yaml = str(tmp_path / 'rrsvc.task.yaml')
    with open(task_yaml, 'w', encoding='utf-8') as f:
        yaml_lib.safe_dump(task.to_yaml_config(), f)
    cport = _free_port()
    assert serve_state.add_service('rrsvc', spec, task_yaml, cport,
                                   _free_port())
    token = serve_state.get_service('rrsvc')['auth_token']
    headers = {'Authorization': f'Bearer {token}'}
    curl = f'http://127.0.0.1:{cport}'

    ctrl = _spawn_service('rrsvc', 'controller')
    try:
        _wait_replicas_ready('rrsvc', 2)
        resp = requests.post(curl + '/controller/rolling_update',
                             json={'checkpoint': str(tmp_path / 'w2')},
                             headers=headers, timeout=30)
        assert resp.status_code == 200, resp.text
        _wait_rollout_phase(cport, token, ('bake',), timeout=60)
        # The chaos event: SIGKILL mid-bake, no cleanup of any kind.
        ctrl.kill()
        ctrl.wait(timeout=30)
        assert serve_state.get_rollout('rrsvc')['phase'] == 'bake'

        ctrl = _spawn_service('rrsvc', 'controller')
        status = _wait_rollout_phase(cport, token, ('rolled_back',),
                                     timeout=120)
        ro = status['rollout']
        assert 'restarted during bake' in ro['error']
        assert ro['updated'] == []
        # Adopted, not relaunched — and back on the baseline.
        assert all(r['weight_version'] == 1 and r['version'] == 1
                   for r in status['replicas']), status['replicas']
        mtext = requests.get(curl + '/controller/metrics',
                             headers=headers, timeout=10).text
        assert ('skyt_serve_replica_adoptions_total{service="rrsvc"} '
                '2') in mtext, mtext
        assert 'skyt_serve_replica_launches_total{service="rrsvc"}' \
            not in mtext, mtext
        assert serve_state.get_service('rrsvc')['version'] == 1
    finally:
        if ctrl.poll() is None:
            try:
                requests.post(curl + '/controller/terminate', json={},
                              headers=headers, timeout=60)
            except requests.RequestException:
                pass
            ctrl.kill()


@pytest.mark.integration
def test_chaos_kv_warm_restart_drill(monkeypatch):
    """Tiered-KV warm restart (docs/performance.md "Tiered prefix
    cache"): two SKYT_KV_TIER=fleet replica processes behind a
    prefix-affinity LB; the prefix's owner is SIGKILLed mid-burst
    (failover publishes the prefix on the survivor, zero 5xx), then
    relaunched on the same port. The relaunched replica warms from its
    peer over /kv/prefix — fleet-tier hits > 0 — and every burst's
    token stream is byte-identical to the pre-kill golden."""
    from skypilot_tpu.serve import load_balancer as lb_lib
    kv_env = {'SKYT_KV_TIER': 'fleet', 'SKYT_ADMIN_TOKEN': 'kv-drill'}
    p1, p2 = _free_port(), _free_port()
    urls = [f'http://127.0.0.1:{p1}', f'http://127.0.0.1:{p2}']
    procs = {urls[0]: _spawn_replica(p1, kv_env, max_seq_len=128),
             urls[1]: _spawn_replica(p2, kv_env, max_seq_len=128)}
    # One shared 100-token prompt: its first 64-token page is the
    # prefix the fleet economy moves between replicas.
    prompt = [(j * 37) % 97 + 3 for j in range(100)]
    body = {'tokens': prompt, 'max_tokens': 8}
    try:
        for url in urls:
            _wait_http(url + '/health', timeout=300,
                       proc=procs[url])
        for k, v in (('SKYT_SERVE_LB_SYNC_INTERVAL', '3600'),
                     ('SKYT_LB_RETRY_BACKOFF_S', '0.02'),
                     ('SKYT_LB_BREAKER_THRESHOLD', '2'),
                     ('SKYT_LB_BREAKER_COOLDOWN_S', '1')):
            monkeypatch.setenv(k, v)
        lb_port = _free_port()
        lb = lb_lib.SkyServeLoadBalancer(
            'http://127.0.0.1:9', lb_port, policy='prefix_affinity',
            metrics_registry=metrics_lib.MetricsRegistry())
        lb.policy.set_ready_replicas(list(urls))
        _run_app_bg(lb.make_app(), lb_port)
        base = f'http://127.0.0.1:{lb_port}'
        _wait_http(base + '/metrics', timeout=30)

        def burst(n=4):
            out = []
            for _ in range(n):
                r = requests.post(base + '/generate', json=body,
                                  timeout=120)
                out.append((r.status_code,
                            r.headers.get('X-Replica-Id'),
                            tuple(r.json().get('tokens', ()))
                            if r.status_code == 200 else None))
            return out

        # Warm burst: the affinity ring homes every request on one
        # owner; later requests prefix-hit its published page.
        first = burst()
        assert all(code == 200 for code, _, _ in first), first
        owner = first[0][1]
        assert owner in urls and \
            all(rep == owner for _, rep, _ in first), first
        golden = first[0][2]
        assert len(golden) == 8
        assert all(toks == golden for _, _, toks in first), first
        survivor = urls[1 - urls.index(owner)]

        # Kill the owner MID-burst: concurrent requests fail over to
        # the survivor — zero client-visible 5xx, identical streams —
        # and the survivor now holds (and publishes) the prefix.
        results, lock = [], threading.Lock()

        def one():
            r = requests.post(base + '/generate', json=body,
                              timeout=120)
            with lock:
                results.append((r.status_code,
                                tuple(r.json().get('tokens', ()))
                                if r.status_code == 200 else None))

        threads = [threading.Thread(target=one) for _ in range(6)]
        for th in threads[:2]:
            th.start()
        procs[owner].kill()
        for th in threads[2:]:
            th.start()
        for th in threads:
            th.join(timeout=180)
        assert len(results) == 6
        assert all(code == 200 for code, _ in results), results
        assert all(toks == golden for _, toks in results), results

        # Relaunch the owner on ITS port (cold HBM, empty host store)
        # and let the breaker's cooldown lapse.
        procs[owner] = _spawn_replica(
            int(owner.rsplit(':', 1)[1]), kv_env, max_seq_len=128)
        _wait_http(owner + '/health', timeout=300, proc=procs[owner])
        time.sleep(1.2)

        # Re-burst: the ring still homes the key on the relaunched
        # owner; the LB's X-KV-Peer hint names the survivor and the
        # owner warms from it instead of recomputing.
        deadline = time.time() + 60
        warmed = None
        while time.time() < deadline:
            third = burst(2)
            assert all(code == 200 for code, _, _ in third), third
            assert all(toks == golden for _, _, toks in third), third
            stats = requests.get(owner + '/stats', timeout=30).json()
            warmed = stats.get('kv_tier')
            if warmed and warmed.get('fetched_pages', 0) > 0:
                break
            time.sleep(0.5)
        assert warmed and warmed['fetched_pages'] > 0, warmed
        assert warmed['promotions'] > 0, warmed
        served = requests.get(owner + '/stats', timeout=30).json()
        assert served['prefix_cache']['hit_pages'] > 0, served
    finally:
        for proc in procs.values():
            if proc.poll() is None:
                proc.kill()


# ==================== elastic capacity: surge queue + reshard drills
def _surge_metrics(reg, lb):
    outcomes = reg.counter('skyt_lb_surge_requests_total', '',
                           ('lb', 'outcome'))
    depth = reg.gauge('skyt_lb_surge_queue_depth', '', ('lb',))
    return (lambda o: outcomes.value(lb.lb_id, o),
            lambda: depth.value(lb.lb_id))


def _wait_gauge(read, want, timeout=10):
    deadline = time.time() + timeout
    while time.time() < deadline:
        if read() == want:
            return
        time.sleep(0.02)
    raise AssertionError(f'gauge never reached {want}: {read()}')


def test_lb_surge_queue_parks_then_serves(monkeypatch):
    """Scale-to-zero survival: with the ready set EMPTY a request
    parks in the surge queue (depth gauge ticks up) instead of
    eating the 503 — and is served the moment a replica appears."""
    lb, base, reg = _make_lb([], monkeypatch,
                             SKYT_LB_NO_REPLICA_POLL_S='0.05',
                             SKYT_LB_NO_REPLICA_TIMEOUT_S='30')
    outcome, depth = _surge_metrics(reg, lb)
    results = []

    def one():
        results.append(requests.get(base + '/g', timeout=30))

    th = threading.Thread(target=one)
    th.start()
    _wait_gauge(depth, 1)           # parked, not rejected
    url = _ok_replica('woke')
    lb.policy.set_ready_replicas([url])
    th.join(timeout=30)
    assert results and results[0].status_code == 200
    assert results[0].text == 'hello-woke'
    assert outcome('served') == 1
    assert outcome('overflow') == 0 and outcome('timeout') == 0
    _wait_gauge(depth, 0)


def test_lb_surge_queue_overflow_is_honest_503(monkeypatch):
    """At SKYT_LB_SURGE_QUEUE_MAX the queue answers 503 + Retry-After
    IMMEDIATELY (no park): a flash crowd against a scaled-to-zero
    fleet must not become a memory bomb plus timeouts."""
    lb, base, reg = _make_lb([], monkeypatch,
                             SKYT_LB_SURGE_QUEUE_MAX='2',
                             SKYT_LB_NO_REPLICA_POLL_S='0.05',
                             SKYT_LB_NO_REPLICA_TIMEOUT_S='30')
    outcome, depth = _surge_metrics(reg, lb)
    parked = []

    def one():
        parked.append(requests.get(base + '/g', timeout=30))

    threads = [threading.Thread(target=one) for _ in range(2)]
    for th in threads:
        th.start()
    _wait_gauge(depth, 2)
    t0 = time.time()
    r = requests.get(base + '/g', timeout=10)    # third: over cap
    assert r.status_code == 503
    assert time.time() - t0 < 3                  # immediate, no park
    assert float(r.headers['Retry-After']) >= 1.0
    assert outcome('overflow') == 1
    lb.policy.set_ready_replicas([_ok_replica()])
    for th in threads:
        th.join(timeout=30)
    assert [p.status_code for p in parked] == [200, 200]
    assert outcome('served') == 2


def test_lb_surge_queue_timeout_is_bounded(monkeypatch):
    """A parked request past the no-replica deadline gets an honest
    503 + Retry-After in bounded time — never a silent hang."""
    lb, base, reg = _make_lb([], monkeypatch,
                             SKYT_LB_NO_REPLICA_POLL_S='0.05',
                             SKYT_LB_NO_REPLICA_TIMEOUT_S='0.5')
    outcome, _depth = _surge_metrics(reg, lb)
    t0 = time.time()
    r = requests.get(base + '/g', timeout=10)
    elapsed = time.time() - t0
    assert r.status_code == 503
    assert elapsed < 5, elapsed
    assert float(r.headers['Retry-After']) >= 1.0
    assert outcome('timeout') == 1 and outcome('served') == 0


def test_chaos_flash_crowd_scaled_to_zero(monkeypatch):
    """THE flash-crowd-vs-scaled-to-zero drill (docs/robustness.md
    "Elastic capacity"): 8 simultaneous arrivals against an EMPTY
    ready set with a 4-deep surge queue. Exactly 4 park (the queue is
    deterministic: the LB's event loop admits serially); the 4
    overflows get an immediate honest 503 + Retry-After. When the
    fleet wakes, every parked request is served 200 — zero 5xx for
    the protected (parked) class across the cold start."""
    lb, base, reg = _make_lb([], monkeypatch,
                             SKYT_LB_SURGE_QUEUE_MAX='4',
                             SKYT_LB_NO_REPLICA_POLL_S='0.05',
                             SKYT_LB_NO_REPLICA_TIMEOUT_S='60')
    outcome, depth = _surge_metrics(reg, lb)
    results, lock = [], threading.Lock()

    def one():
        r = requests.get(base + '/g', timeout=60)
        with lock:
            results.append((r.status_code, r.headers.get('Retry-After')))

    threads = [threading.Thread(target=one) for _ in range(8)]
    for th in threads:
        th.start()
    # The crowd splits 4 parked / 4 overflowed before any wake.
    _wait_gauge(depth, 4, timeout=20)
    deadline = time.time() + 20
    while time.time() < deadline and outcome('overflow') < 4:
        time.sleep(0.05)
    assert outcome('overflow') == 4
    # Fleet wakes: one replica appears (controller sync, simulated).
    lb.policy.set_ready_replicas([_ok_replica('cold')])
    for th in threads:
        th.join(timeout=60)
    assert len(results) == 8
    served = [r for r in results if r[0] == 200]
    rejected = [r for r in results if r[0] == 503]
    assert len(served) == 4 and len(rejected) == 4, results
    # Every overflow carried an actionable Retry-After.
    assert all(ra is not None and float(ra) >= 1.0
               for _, ra in rejected), rejected
    assert outcome('served') == 4 and outcome('timeout') == 0
    _wait_gauge(depth, 0)


def _wait_reshard_phase(cport, token, phases, timeout=180):
    headers = {'Authorization': f'Bearer {token}'}
    deadline = time.time() + timeout
    last = None
    while time.time() < deadline:
        try:
            last = requests.get(
                f'http://127.0.0.1:{cport}/controller/status',
                headers=headers, timeout=10).json()
            rs = last.get('reshard') or {}
            if rs.get('phase') in phases:
                return last
        except requests.RequestException:
            pass
        time.sleep(0.3)
    raise AssertionError(
        f'reshard never reached {phases}: '
        f'{(last or {}).get("reshard")}')


@pytest.mark.integration
def test_chaos_reshard_rollback_and_controller_sigkill(
        control_plane_env, monkeypatch):
    """THE mid-reshard chaos drill (docs/robustness.md "Elastic
    capacity"): 2 REAL engine replicas behind the real controller +
    an in-process LB.

    Run 1 (clean): an in-place reshard 1 -> 2 virtual nodes lands
    fleet-wide mid-burst — zero client-visible 5xx, zero relaunches,
    weight_version untouched.

    Run 2 (faulted): `reshard=error` armed on target 4 — every
    replica refuses, the orchestrator rolls back automatically, the
    mid-burst traffic still sees zero 5xx and the fleet keeps the
    old layout.

    Run 3 (SIGKILL mid-reshard): the controller is SIGKILLed while a
    replica's reshard POST is in flight. Reshard state is in-memory
    BY DESIGN: the restarted controller adopts both replicas (zero
    relaunches), reports no reshard, the mixed-layout fleet keeps
    serving 200s, and re-issuing the reshard converges — the
    already-flipped replica no-ops (idempotent re-assert)."""
    import yaml as yaml_lib

    import skypilot_tpu as sky
    from skypilot_tpu import resources as resources_lib
    from skypilot_tpu.serve import load_balancer as lb_lib
    from skypilot_tpu.serve import serve_state
    from skypilot_tpu.serve import service_spec as spec_lib

    tmp_path = control_plane_env
    # where= keys on the reshard target, so each run picks its fault:
    # target 4 errors (run 2); target 1 stalls 2.5s (run 3's kill
    # window + the idempotent re-assert). Inherited by the replica
    # processes at launch.
    monkeypatch.setenv('SKYT_FAULTS',
                       'reshard=error,where=virtual_nodes:4;'
                       'reshard=latency,arg=2.5,where=virtual_nodes:1')
    monkeypatch.setenv('SKYT_ROLLOUT_RETRIES', '2')
    task = sky.Task(name='esvc', run=_ENGINE_REPLICA)
    task.set_resources(resources_lib.Resources(cloud='local'))
    spec = spec_lib.ServiceSpec(
        readiness_path='/health', min_replicas=2,
        initial_delay_seconds=600, probe_timeout_seconds=5)
    task.service = spec
    task_yaml = str(tmp_path / 'esvc.task.yaml')
    with open(task_yaml, 'w', encoding='utf-8') as f:
        yaml_lib.safe_dump(task.to_yaml_config(), f)
    cport, lport = _free_port(), _free_port()
    assert serve_state.add_service('esvc', spec, task_yaml, cport,
                                   lport)
    token = serve_state.get_service('esvc')['auth_token']
    headers = {'Authorization': f'Bearer {token}'}
    curl = f'http://127.0.0.1:{cport}'

    ctrl = _spawn_service('esvc', 'controller')
    lb = None
    try:
        _wait_replicas_ready('esvc', 2, timeout=420)
        reg = metrics_lib.MetricsRegistry()
        lb_port = _free_port()
        lb = lb_lib.SkyServeLoadBalancer(
            curl, lb_port, controller_auth=token,
            metrics_registry=reg)
        _run_app_bg(lb.make_app(), lb_port)
        base = f'http://127.0.0.1:{lb_port}'
        deadline = time.time() + 120
        while time.time() < deadline and \
                len(lb.policy.ready_replicas) < 2:
            time.sleep(0.2)
        assert len(lb.policy.ready_replicas) == 2

        def replica_stats():
            status = requests.get(curl + '/controller/status',
                                  headers=headers, timeout=10).json()
            out = {}
            for rep in status['replicas']:
                stats = requests.get(rep['endpoint'] + '/stats',
                                     timeout=30).json()
                out[rep['replica_id']] = (stats['virtual_nodes'],
                                          stats['weight_version'])
            return out

        assert set(replica_stats().values()) == {(1, 1)}

        results = []
        stop_burst = threading.Event()
        lock = threading.Lock()

        def burst():
            i = 0
            while not stop_burst.is_set():
                i += 1
                try:
                    r = requests.post(
                        base + '/generate',
                        json={'tokens': [1 + (i % 5), 2, 3],
                              'max_tokens': 6},
                        timeout=120)
                    code = r.status_code
                except requests.RequestException as e:
                    code = f'EXC:{e!r}'
                with lock:
                    results.append(code)

        def run_burst_during(fn):
            results.clear()
            stop_burst.clear()
            threads = [threading.Thread(target=burst)
                       for _ in range(2)]
            for th in threads:
                th.start()
            try:
                out = fn()
            finally:
                time.sleep(0.5)
                stop_burst.set()
                for th in threads:
                    th.join(timeout=120)
            with lock:
                codes = list(results)
            assert codes and all(c == 200 for c in codes), codes[:20]
            return out

        # ---- run 1: clean elastic flip 1 -> 2, mid-burst.
        def clean_flip():
            resp = requests.post(curl + '/controller/reshard',
                                 json={'virtual_nodes': 2},
                                 headers=headers, timeout=30)
            assert resp.status_code == 200, resp.text
            return _wait_reshard_phase(cport, token, ('done',),
                                       timeout=120)

        status = run_burst_during(clean_flip)
        assert status['reshard']['phase'] == 'done'
        # Layout flipped fleet-wide; the weights plane untouched.
        assert set(replica_stats().values()) == {(2, 1)}

        # ---- run 2: the armed fault refuses target 4 -> rollback.
        def faulted_flip():
            resp = requests.post(curl + '/controller/reshard',
                                 json={'virtual_nodes': 4},
                                 headers=headers, timeout=30)
            assert resp.status_code == 200, resp.text
            return _wait_reshard_phase(cport, token, ('rolled_back',),
                                       timeout=120)

        status = run_burst_during(faulted_flip)
        rs = status['reshard']
        assert rs['phase'] == 'rolled_back'
        assert 'replica' in (rs['error'] or '')
        # Old layout intact everywhere; still zero relaunches.
        assert set(replica_stats().values()) == {(2, 1)}
        mtext = requests.get(curl + '/controller/metrics',
                             headers=headers, timeout=10).text
        assert 'skyt_serve_replica_launches_total{service="esvc"} 2' \
            in mtext, mtext
        assert ('skyt_serve_reshards_total{service="esvc",'
                'outcome="done"} 1') in mtext
        assert ('skyt_serve_reshards_total{service="esvc",'
                'outcome="rolled_back"} 1') in mtext

        # ---- run 3: SIGKILL mid-reshard (target 1 stalls 2.5s per
        # replica call — the kill lands inside the first POST).
        resp = requests.post(curl + '/controller/reshard',
                             json={'virtual_nodes': 1},
                             headers=headers, timeout=30)
        assert resp.status_code == 200, resp.text
        _wait_reshard_phase(cport, token, ('reshard',), timeout=30)
        time.sleep(1.0)
        ctrl.kill()
        ctrl.wait(timeout=30)

        ctrl = _spawn_service('esvc', 'controller')
        _wait_replicas_ready('esvc', 2, timeout=120)
        deadline = time.time() + 60
        status = None
        while time.time() < deadline:
            try:
                status = requests.get(curl + '/controller/status',
                                      headers=headers,
                                      timeout=10).json()
                break
            except requests.RequestException:
                time.sleep(0.3)
        assert status is not None
        # In-memory by design: the restarted controller has no
        # reshard; the replicas were adopted, not relaunched.
        assert status['reshard'] is None
        mtext = requests.get(curl + '/controller/metrics',
                             headers=headers, timeout=10).text
        assert ('skyt_serve_replica_adoptions_total{service="esvc"} '
                '2') in mtext, mtext
        assert 'skyt_serve_replica_launches_total{service="esvc"}' \
            not in mtext, mtext
        # Mixed layouts are fine to serve: zero 5xx either way.
        for i in range(4):
            r = requests.post(base + '/generate',
                              json={'tokens': [2 + i, 3, 4],
                                    'max_tokens': 4},
                              timeout=120)
            assert r.status_code == 200, r.text
        # Re-issue: the operator's recovery lever. The already-
        # flipped replica no-ops; the straggler flips.
        resp = requests.post(curl + '/controller/reshard',
                             json={'virtual_nodes': 1},
                             headers=headers, timeout=30)
        assert resp.status_code == 200, resp.text
        _wait_reshard_phase(cport, token, ('done',), timeout=120)
        assert set(replica_stats().values()) == {(1, 1)}
    finally:
        if ctrl.poll() is None:
            try:
                requests.post(curl + '/controller/terminate', json={},
                              headers=headers, timeout=60)
            except requests.RequestException:
                pass
            ctrl.kill()
        del lb


@pytest.mark.integration
def test_chaos_scale_provision_latency_surge_honesty(
        control_plane_env, monkeypatch):
    """THE surge-honesty drill: provisioning of the only replica is
    stalled (`scale.provision=latency`) while a client arrives — the
    request parks in the surge queue and gets a BOUNDED honest
    503 + Retry-After (never a silent hang). Once the stalled launch
    completes, traffic serves and the cold start is attributed:
    skyt_serve_cold_starts_total{kind="wake_from_zero"} with
    cold-start seconds covering the stall."""
    import yaml as yaml_lib

    import skypilot_tpu as sky
    from skypilot_tpu import resources as resources_lib
    from skypilot_tpu.serve import load_balancer as lb_lib
    from skypilot_tpu.serve import serve_state
    from skypilot_tpu.serve import service_spec as spec_lib

    tmp_path = control_plane_env
    monkeypatch.setenv('SKYT_FAULTS',
                       'scale.provision=latency,arg=6,count=1')
    monkeypatch.setenv('SKYT_LB_NO_REPLICA_TIMEOUT_S', '2')
    monkeypatch.setenv('SKYT_LB_NO_REPLICA_POLL_S', '0.1')
    task = sky.Task(name='zsvc', run=_ADMIN_FAKE_REPLICA)
    task.set_resources(resources_lib.Resources(cloud='local'))
    spec = spec_lib.ServiceSpec(
        readiness_path='/', min_replicas=1, initial_delay_seconds=60,
        probe_timeout_seconds=2)
    task.service = spec
    task_yaml = str(tmp_path / 'zsvc.task.yaml')
    with open(task_yaml, 'w', encoding='utf-8') as f:
        yaml_lib.safe_dump(task.to_yaml_config(), f)
    cport = _free_port()
    assert serve_state.add_service('zsvc', spec, task_yaml, cport,
                                   _free_port())
    token = serve_state.get_service('zsvc')['auth_token']
    headers = {'Authorization': f'Bearer {token}'}
    curl = f'http://127.0.0.1:{cport}'

    ctrl = _spawn_service('zsvc', 'controller')
    lb = None
    try:
        reg = metrics_lib.MetricsRegistry()
        lb_port = _free_port()
        lb = lb_lib.SkyServeLoadBalancer(
            curl, lb_port, controller_auth=token,
            metrics_registry=reg)
        _run_app_bg(lb.make_app(), lb_port)
        base = f'http://127.0.0.1:{lb_port}'
        _wait_http(base + '/metrics', timeout=30)
        outcome, _depth = _surge_metrics(reg, lb)

        # The flash arrival during the stalled provision: parked,
        # then honestly rejected within the bounded window.
        t0 = time.time()
        r = requests.get(base + '/g', timeout=20)
        elapsed = time.time() - t0
        assert r.status_code == 503, r.text
        assert elapsed < 10, elapsed          # bounded, not a hang
        assert float(r.headers['Retry-After']) >= 1.0
        assert outcome('timeout') == 1

        # The stalled launch eventually lands; the fleet wakes.
        _wait_replicas_ready('zsvc', 1, timeout=180)
        deadline = time.time() + 60
        while time.time() < deadline and not lb.policy.ready_replicas:
            time.sleep(0.2)
        assert lb.policy.ready_replicas
        r = requests.get(base + '/g', timeout=30)
        assert r.status_code == 200

        # Cold-start attribution: a wake-from-zero whose seconds
        # include the provisioning stall.
        mtext = requests.get(curl + '/controller/metrics',
                             headers=headers, timeout=10).text
        assert ('skyt_serve_cold_starts_total{service="zsvc",'
                'kind="wake_from_zero"} 1') in mtext, mtext
        m = re.search(r'skyt_serve_cold_start_seconds_total'
                      r'\{service="zsvc"\} ([0-9.e+-]+)', mtext)
        assert m is not None, mtext
        assert float(m.group(1)) >= 5.0, m.group(1)
    finally:
        if ctrl.poll() is None:
            try:
                requests.post(curl + '/controller/terminate', json={},
                              headers=headers, timeout=60)
            except requests.RequestException:
                pass
            ctrl.kill()
        del lb
