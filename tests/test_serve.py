"""Serve layer tests: policies + autoscaler offline; full service
lifecycle (up → ready → proxy → replica recovery → update → down) on the
local provider with real controller/LB/replica processes.

Reference test strategy: sky tests/skyserve/ (tiny HTTP servers per
scenario) + load_balancer/test_round_robin.py (SURVEY.md §4.5).
"""
import os
import time

import pytest
import requests

import skypilot_tpu as sky
from skypilot_tpu import resources as resources_lib
from skypilot_tpu import state
from skypilot_tpu.serve import autoscalers
from skypilot_tpu.serve import core as serve_core
from skypilot_tpu.serve import load_balancing_policies as lb_policies
from skypilot_tpu.serve import replica_managers
from skypilot_tpu.serve import serve_state
from skypilot_tpu.serve import service_spec as spec_lib

# Compile-heavy (JAX jit on the 1-core CPU host) or subprocess-driven:
pytestmark = pytest.mark.heavy

REPLICA_SERVER = (
    "python -c \""
    "import http.server, os, json;\n"
    "me = os.environ.get('SKYT_NODE_RANK', '?');\n"
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


# ------------------------------------------------------------ unit: policy
def test_round_robin_policy():
    p = lb_policies.RoundRobinPolicy()
    assert p.select_replica() is None
    p.set_ready_replicas(['a', 'b', 'c'])
    picks = [p.select_replica() for _ in range(6)]
    assert sorted(picks[:3]) == ['a', 'b', 'c']
    assert picks[:3] == picks[3:]  # cycles deterministically


def test_least_connections_policy():
    p = lb_policies.LeastConnectionsPolicy()
    p.set_ready_replicas(['a', 'b'])
    r1 = p.select_replica()
    r2 = p.select_replica()
    assert {r1, r2} == {'a', 'b'}  # spreads across both
    p.on_request_done(r1)
    assert p.select_replica() == r1  # freed one is least-loaded


# -------------------------------------------------------- unit: autoscaler
def _spec(**kw):
    base = dict(readiness_path='/', min_replicas=1, max_replicas=4,
                target_qps_per_replica=1.0, upscale_delay_seconds=0.2,
                downscale_delay_seconds=0.2)
    base.update(kw)
    return spec_lib.ServiceSpec(**base)


def test_autoscaler_upscale_after_delay():
    a = autoscalers.RequestRateAutoscaler(_spec())
    now = time.time()
    # 120 requests in the window => qps 2 => target 2 replicas.
    a.collect_request_timestamps([now] * 120)
    d = a.evaluate_scaling(num_ready=1)
    assert d.target_num_replicas == 1  # delay not yet met
    time.sleep(0.25)
    d = a.evaluate_scaling(num_ready=1)
    assert d.target_num_replicas == 2


def test_autoscaler_downscale_after_delay():
    a = autoscalers.RequestRateAutoscaler(_spec())
    a.target_num_replicas = 3
    d = a.evaluate_scaling(num_ready=3)
    assert d.target_num_replicas == 3
    time.sleep(0.25)
    d = a.evaluate_scaling(num_ready=3)
    assert d.target_num_replicas == 1  # no traffic -> min


def test_autoscaler_fixed_when_not_autoscaling():
    spec = spec_lib.ServiceSpec(readiness_path='/', min_replicas=2)
    a = autoscalers.RequestRateAutoscaler(spec)
    a.collect_request_timestamps([time.time()] * 1000)
    time.sleep(0.05)
    assert a.evaluate_scaling(2).target_num_replicas == 2


# ------------------------------------------------- integration: lifecycle
@pytest.fixture()
def serve_env(tmp_path, tmp_state_dir, monkeypatch):
    monkeypatch.setenv('SKYT_LOCAL_ROOT', str(tmp_path / 'local'))
    monkeypatch.setenv('SKYT_DEFAULT_STORE', 'local')
    monkeypatch.setenv('SKYT_SERVE_CONTROLLER_INTERVAL', '0.3')
    monkeypatch.setenv('SKYT_SERVE_LB_SYNC_INTERVAL', '0.3')
    state.reset_db_for_testing()
    serve_state.reset_db_for_testing()
    yield
    for svc in serve_state.get_services():
        try:
            serve_core.down(svc['name'], purge=True)
        except Exception:  # pylint: disable=broad-except
            pass
    from skypilot_tpu import core
    for rec in state.get_clusters():
        try:
            core.down(rec['name'], purge=True)
        except Exception:  # pylint: disable=broad-except
            pass
    state.reset_db_for_testing()
    serve_state.reset_db_for_testing()


def _service_task(name='svc', min_replicas=2):
    t = sky.Task(name=name, run=REPLICA_SERVER)
    t.set_resources(resources_lib.Resources(cloud='local'))
    t.service = spec_lib.ServiceSpec(
        readiness_path='/', min_replicas=min_replicas,
        initial_delay_seconds=30, probe_timeout_seconds=2)
    return t


def _wait_ready(name, want_ready, timeout=90):
    deadline = time.time() + timeout
    while time.time() < deadline:
        svcs = serve_core.status([name])
        if svcs:
            ready = [r for r in svcs[0]['replicas']
                     if r['status'] is serve_state.ReplicaStatus.READY]
            if len(ready) >= want_ready:
                return svcs[0]
        time.sleep(0.5)
    pytest.fail(f'{name}: {want_ready} replicas not READY in {timeout}s: '
                f'{serve_core.status([name])}')


def test_replica_manager_recovers_orphans(serve_env):
    """Controller killed mid-launch: the persisted PROVISIONING row has
    no cluster. A fresh manager (restart) must tear the orphan down so
    reconcile() can relaunch to target
    (reference: sky/serve/replica_managers.py:940-1019 supervision)."""
    from skypilot_tpu.serve import replica_managers

    spec = spec_lib.ServiceSpec(readiness_path='/', min_replicas=1)
    serve_state.add_service('osvc', spec, '/tmp/nonexistent.yaml', 1, 2)
    # Simulate the dead controller's persisted launch intent.
    orphan = replica_managers.ReplicaInfo(
        replica_id=1, cluster_name='osvc-1', version=1,
        status=serve_state.ReplicaStatus.PROVISIONING)
    serve_state.upsert_replica('osvc', 1, orphan)

    mgr = replica_managers.ReplicaManager('osvc', spec,
                                          '/tmp/nonexistent.yaml')
    deadline = time.time() + 10
    while time.time() < deadline and 1 in mgr.replicas:
        time.sleep(0.1)
    assert 1 not in mgr.replicas, 'orphan not reconciled'
    assert all(r.replica_id != 1
               for r in serve_state.get_replicas('osvc'))


def test_replica_manager_keeps_live_cluster_on_restart(serve_env):
    """Mid-launch rows whose cluster DID come up are adopted as
    STARTING, not torn down."""
    import skypilot_tpu as sky
    from skypilot_tpu import execution
    from skypilot_tpu.serve import replica_managers

    t = sky.Task(name='osvc2-1', run='true')
    t.set_resources(resources_lib.Resources(cloud='local'))
    execution.launch(t, cluster_name='osvc2-1', detach_run=True,
                     stream_logs=False)

    spec = spec_lib.ServiceSpec(readiness_path='/', min_replicas=1)
    serve_state.add_service('osvc2', spec, '/tmp/nonexistent.yaml', 3, 4)
    row = replica_managers.ReplicaInfo(
        replica_id=1, cluster_name='osvc2-1', version=1,
        status=serve_state.ReplicaStatus.PROVISIONING)
    serve_state.upsert_replica('osvc2', 1, row)

    mgr = replica_managers.ReplicaManager('osvc2', spec,
                                          '/tmp/nonexistent.yaml')
    assert 1 in mgr.replicas
    assert mgr.replicas[1].status is serve_state.ReplicaStatus.STARTING
    assert mgr.replicas[1].endpoint is not None


def test_failed_add_service_releases_write_lock(serve_env):
    """A duplicate add_service (failed INSERT) must roll back its
    implicit transaction: leaving it open pins the write lock, and every
    other process's serve.db writes then die with 'database is locked'
    (found live: duplicate `serve up` wedged the controller's
    terminate)."""
    import sqlite3

    from skypilot_tpu import state as state_lib

    spec = spec_lib.ServiceSpec(readiness_path='/', min_replicas=1)
    assert serve_state.add_service('locksvc', spec, '/t.yaml', 1, 2)
    assert not serve_state.add_service('locksvc', spec, '/t.yaml', 3, 4)
    # A second connection stands in for the controller process: its
    # write must succeed immediately, not wait on our busy timeout.
    path = os.path.join(state_lib.state_dir(), 'serve.db')
    conn = sqlite3.connect(path, timeout=2)
    conn.execute("UPDATE services SET status='READY' WHERE name='locksvc'")
    conn.commit()
    conn.close()


def test_controller_auth_rejects_unauthenticated(serve_env):
    """Admin endpoints require the per-service bearer token minted at
    add_service: no token / wrong token => 401 before the handler runs;
    the right token passes (the reference gets this property from
    SSH-tunneled codegen instead)."""
    import asyncio

    import aiohttp
    from aiohttp import web

    from skypilot_tpu.serve import controller as controller_lib

    spec = spec_lib.ServiceSpec(readiness_path='/', min_replicas=1)
    assert serve_state.add_service('asvc', spec, '/tmp/nonexistent.yaml',
                                   1, 2)
    svc = serve_state.get_service('asvc')
    token = svc['auth_token']
    assert token, 'token must be minted at add_service'

    ctrl = controller_lib.SkyServeController(
        'asvc', spec, '/tmp/nonexistent.yaml', svc['controller_port'])

    async def _run():
        runner = web.AppRunner(ctrl.make_app(token))
        await runner.setup()
        site = web.TCPSite(runner, '127.0.0.1', 0)
        await site.start()
        base = f'http://{runner.addresses[0][0]}:{runner.addresses[0][1]}'
        res = {}
        async with aiohttp.ClientSession() as sess:
            for ep in ('/controller/update_service',
                       '/controller/terminate'):
                async with sess.post(base + ep, json={}) as r:
                    res[ep] = r.status
            async with sess.post(
                    base + '/controller/terminate', json={},
                    headers={'Authorization': 'Bearer wrong'}) as r:
                res['bad-token'] = r.status
            async with sess.get(base + '/controller/status') as r:
                res['status-noauth'] = r.status
            async with sess.get(
                    base + '/controller/status',
                    headers={'Authorization': f'Bearer {token}'}) as r:
                res['status-auth'] = r.status
        await runner.cleanup()
        return res

    res = asyncio.run(_run())
    assert res['/controller/update_service'] == 401
    assert res['/controller/terminate'] == 401
    assert res['bad-token'] == 401
    assert res['status-noauth'] == 401
    assert res['status-auth'] == 200


@pytest.mark.integration
def test_serve_cluster_controller(serve_env, tmp_path, monkeypatch):
    """Controller+LB run as a job on the serve controller cluster (the
    reference's sky-serve-controller VM): no client-side controller
    pid; service serves and tears down normally."""
    cfg = tmp_path / 'skyt_config.yaml'
    cfg.write_text(
        'serve:\n  controller:\n    resources:\n      cloud: local\n')
    monkeypatch.setenv('SKYT_CONFIG', str(cfg))
    from skypilot_tpu import skyt_config
    skyt_config.reload_for_testing()
    try:
        name, endpoint = serve_core.up(_service_task(min_replicas=1),
                                       'csvc', controller='cluster')
        svc = serve_state.get_service('csvc')
        assert not svc.get('controller_pid')
        _wait_ready(name, 1)
        resp = requests.get(endpoint, timeout=5)
        assert resp.status_code == 200
        assert resp.text.startswith('hello-from-')
        assert state.get_cluster('skyt-serve-controller') is not None
        serve_core.down(name)
        deadline = time.time() + 60
        while time.time() < deadline and serve_state.get_service(name):
            time.sleep(0.5)
        assert serve_state.get_service(name) is None
    finally:
        skyt_config.reload_for_testing()


@pytest.mark.integration
def test_serve_multihost_replica(serve_env):
    """A replica spanning MULTIPLE hosts (the reference's
    TP-across-a-replica-cluster shape, llm/vllm/serve.yaml): the task
    gang-runs on every host, only rank 0 binds SKYT_REPLICA_PORT (the
    multihost engine's contract), and the replica endpoint routes to
    the head — service goes READY and proxies."""
    run = (
        "if [ \"$SKYT_NODE_RANK\" = 0 ]; then " + REPLICA_SERVER +
        "; else sleep 3600; fi")
    t = sky.Task(name='mh', run=run, num_nodes=2)
    t.set_resources(resources_lib.Resources(cloud='local'))
    t.service = spec_lib.ServiceSpec(
        readiness_path='/', min_replicas=1,
        initial_delay_seconds=60, probe_timeout_seconds=2)
    name, endpoint = serve_core.up(t, 'mhsvc')
    svc = _wait_ready(name, 1)
    replica = svc['replicas'][0]
    handle = state.get_cluster(replica['cluster_name'])['handle']
    assert handle.num_hosts == 2          # really a 2-host replica
    resp = requests.get(endpoint + '/', timeout=10)
    assert resp.status_code == 200
    assert resp.text.startswith('hello-from-')
    serve_core.down(name)


@pytest.mark.integration
def test_serve_lifecycle(serve_env):
    name, endpoint = serve_core.up(_service_task(min_replicas=2), 'svc')
    svc = _wait_ready(name, 2)
    assert svc['status'] is serve_state.ServiceStatus.READY

    # Proxy round-robins across both replicas (reference:
    # tests/skyserve/load_balancer/test_round_robin.py).
    # Poll until both replicas answer: the LB's replica-set sync can lag
    # READY status by one sync interval (a fixed request count flakes on
    # slow machines).
    seen = set()
    deadline = time.time() + 30
    while time.time() < deadline and len(seen) < 2:
        resp = requests.get(endpoint + '/', timeout=10)
        assert resp.status_code == 200
        assert resp.text.startswith('hello-from-')
        seen.add(resp.text)
        time.sleep(0.1)
    assert len(seen) == 2

    # Replica failure -> detected -> replaced (preemption semantics).
    from skypilot_tpu import core
    victim = svc['replicas'][0]['cluster_name']
    core.down(victim, purge=True)
    deadline = time.time() + 60
    while time.time() < deadline:
        svcs = serve_core.status([name])[0]
        clusters = {r['cluster_name'] for r in svcs['replicas']
                    if r['status'] is serve_state.ReplicaStatus.READY}
        if victim not in clusters and len(clusters) >= 2:
            break
        time.sleep(0.5)
    else:
        pytest.fail(f'replica not replaced: {serve_core.status([name])}')

    # Rolling update bumps the version; replicas roll to it.
    v = serve_core.update(_service_task(min_replicas=2), name)
    assert v == 2
    deadline = time.time() + 90
    while time.time() < deadline:
        svcs = serve_core.status([name])[0]
        ready = [r for r in svcs['replicas']
                 if r['status'] is serve_state.ReplicaStatus.READY]
        if ready and all(r['version'] == 2 for r in ready) and \
                len(ready) >= 2:
            break
        time.sleep(0.5)
    else:
        pytest.fail(f'rolling update stuck: {serve_core.status([name])}')

    # Down removes service + all replica clusters.
    serve_core.down(name)
    assert serve_core.status([name]) == []
    assert state.get_clusters() == []


def test_scale_to_zero_and_wake():
    """min_replicas: 0 — sustained idle scales the service to nothing;
    the first request wakes it immediately (no upscale delay: with
    zero replicas the delay would just be guaranteed 503s)."""
    import time as time_lib

    from skypilot_tpu.serve import autoscalers, service_spec

    spec = service_spec.ServiceSpec(
        readiness_path='/health', min_replicas=0, max_replicas=2,
        target_qps_per_replica=1.0, upscale_delay_seconds=60.0,
        downscale_delay_seconds=0.0)
    a = autoscalers.RequestRateAutoscaler(spec)
    assert a.target_num_replicas == 0
    # Idle: stays at zero.
    d = a.evaluate_scaling(num_ready=0)
    assert d.target_num_replicas == 0
    # A request arrives -> wake instantly despite the 60s upscale delay.
    a.collect_request_timestamps([time_lib.time()])
    d = a.evaluate_scaling(num_ready=0)
    assert d.target_num_replicas >= 1
    assert 'wake from zero' in d.reason
    # Traffic stops -> back to zero after the (zero) downscale delay.
    a.request_timestamps.clear()
    d = a.evaluate_scaling(num_ready=1)
    assert d.target_num_replicas == 0


def test_replica_stats_scrape(tmp_state_dir):
    """The prober scrapes /stats off a READY inference replica and
    `serve status` surfaces it; a replica without /stats yields None."""
    import http.server
    import json as json_lib
    import threading

    stats_payload = {'ttft_ms': {'p50': 42.0, 'p90': 50.0, 'p99': 60.0,
                                 'count': 7},
                     'steady_decode_tok_per_sec': 900.0,
                     'active_slots': 2, 'num_slots': 8, 'waiting': 0,
                     'irrelevant': 'dropped'}

    class H(http.server.BaseHTTPRequestHandler):
        def do_GET(self):
            self.send_response(200)
            self.end_headers()
            if self.path == '/stats':
                self.wfile.write(json_lib.dumps(stats_payload).encode())
            else:
                self.wfile.write(b'ok')

        def log_message(self, *a):
            pass

    srv = http.server.HTTPServer(('127.0.0.1', 0), H)
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    try:
        spec = spec_lib.ServiceSpec(readiness_path='/health')
        mgr = replica_managers.ReplicaManager('stats-svc', spec,
                                              task_yaml='/dev/null')
        info = replica_managers.ReplicaInfo(
            replica_id=1, cluster_name='nonexistent-c', version=1,
            status=serve_state.ReplicaStatus.READY,
            endpoint=f'http://127.0.0.1:{srv.server_port}')
        got = mgr._fetch_stats(info)
        assert got == {k: v for k, v in stats_payload.items()
                       if k != 'irrelevant'}
    finally:
        srv.shutdown()
    # No server at all -> None, not an exception.
    info.endpoint = 'http://127.0.0.1:1'
    assert mgr._fetch_stats(info) is None


def test_cold_start_attribution_and_prewarm(tmp_state_dir, monkeypatch):
    """First-READY fires cold-start attribution exactly once per
    replica: kind wake_from_zero when no other replica was READY,
    scale_up otherwise, seconds = launch -> first READY. With
    SKYT_SERVE_PREWARM=1 the new replica is asked to pre-warm its KV
    from the already-READY peers (daemon push, injectable transport);
    off by default."""
    import threading

    class _Telemetry:
        def __init__(self):
            self.cold = []

        def note_cold_start(self, kind, seconds):
            self.cold.append((kind, seconds))

    tel = _Telemetry()
    spec = spec_lib.ServiceSpec(readiness_path='/health')
    mgr = replica_managers.ReplicaManager('cold-svc', spec,
                                          task_yaml='/dev/null',
                                          telemetry=tel)
    prewarms = []
    done = threading.Event()

    def fake_prewarm(info, peers):
        prewarms.append((info.replica_id, list(peers)))
        done.set()
        return True, None

    mgr._prewarm_fn = fake_prewarm  # pylint: disable=protected-access
    now = time.time()

    def _ready(rid):
        info = replica_managers.ReplicaInfo(
            replica_id=rid, cluster_name=f'c-{rid}', version=1,
            status=serve_state.ReplicaStatus.READY,
            endpoint=f'http://127.0.0.1:{9100 + rid}',
            launched_at=now - 5.0, first_ready_at=now)
        mgr.replicas[rid] = info
        return info

    # Fleet was scaled to zero: the first arrival is the wake.
    monkeypatch.delenv('SKYT_SERVE_PREWARM', raising=False)
    mgr._note_first_ready(_ready(1))  # pylint: disable=protected-access
    assert tel.cold == [('wake_from_zero', pytest.approx(5.0, abs=1.0))]
    assert not prewarms                # prewarm is opt-in
    # A second replica joins a serving fleet: scale_up.
    mgr._note_first_ready(_ready(2))  # pylint: disable=protected-access
    assert tel.cold[-1][0] == 'scale_up'
    # Opt in: the NEW replica pulls from the already-READY peers.
    monkeypatch.setenv('SKYT_SERVE_PREWARM', '1')
    mgr._note_first_ready(_ready(3))  # pylint: disable=protected-access
    assert done.wait(10)
    assert prewarms == [(3, ['http://127.0.0.1:9101',
                             'http://127.0.0.1:9102'])]
    assert tel.cold[-1][0] == 'scale_up'
    # The fleet capacity report attributes the burned chip-seconds.
    from skypilot_tpu.serve import fleet as fleet_lib
    from skypilot_tpu.utils import metrics as metrics_lib
    monkeypatch.setenv('SKYT_FLEET_CHIPS_PER_REPLICA', '4')
    ft = fleet_lib.FleetTelemetry(
        'cold-svc', metrics_registry=metrics_lib.MetricsRegistry())
    for kind, seconds in tel.cold:
        ft.note_cold_start(kind, seconds)
    rep = ft.capacity_report()
    assert rep['cold_start']['count'] == {'wake_from_zero': 1,
                                          'scale_up': 2}
    assert rep['cold_start']['chip_seconds'] == \
        pytest.approx(3 * 5.0 * 4, rel=0.3)
