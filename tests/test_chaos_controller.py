"""Chaos suite, the control plane: drain and relaunch back-off, the
leader lease, and a restarted controller that adopts live replicas and
resumes a rollout
(docs/robustness.md).

The drills run the REAL LB -> server -> engine HTTP stack on the CPU;
a death is a SIGKILLed subprocess, not a mock. Shared helpers:
tests/chaos_helpers.py.
"""
import os
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
    _ADMIN_FAKE_REPLICA, _free_port, _ok_replica, _run_app_bg,
    _spawn_service, _wait_replicas_ready, _wait_rollout_phase,
)
# Fixtures, used by name:
from chaos_helpers import _reset_faults  # noqa: unused-import
from chaos_helpers import control_plane_env  # noqa: unused-import

pytestmark = pytest.mark.heavy


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
