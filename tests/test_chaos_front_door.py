"""Chaos suite, the front door: the per-replica circuit breaker, LB
retries, stale mode, gossip between active LBs, standby take-over and the
surge queue
(docs/robustness.md).

The drills run the REAL LB -> server -> engine HTTP stack on the CPU;
a death is a SIGKILLed subprocess, not a mock. Shared helpers:
tests/chaos_helpers.py.
"""
import os
import re
import signal
import subprocess
import sys
import threading
import time

import pytest
import requests

from skypilot_tpu.utils import faults
from skypilot_tpu.utils import metrics as metrics_lib

from chaos_helpers import (
    _ADMIN_FAKE_REPLICA, _free_port, _make_lb, _ok_replica, _run_app_bg,
    _spawn_service, _wait_http, _wait_replicas_ready,
)
# Fixtures, used by name:
from chaos_helpers import _reset_faults  # noqa: unused-import
from chaos_helpers import control_plane_env  # noqa: unused-import

pytestmark = pytest.mark.heavy


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
    _wait_gauge(lambda: lb.breaker.blocked(url), False)   # cooldown over
    deadline = time.time() + 10
    while time.time() < deadline:
        r = requests.get(base + '/g', timeout=10)
        if r.status_code == 200:
            break
        time.sleep(0.2)
    assert r.status_code == 200 and r.text == 'back'
    # The LB records the trial's success on its own thread AFTER the
    # last byte has gone to the client, so the client can be here first:
    # wait for the state, do not read it once.
    _wait_gauge(lambda: lb.breaker.state(url), lb.breaker.CLOSED)


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
