"""Training-plane observability: heartbeats, gang watchdog, postmortem
bundles, HUNG escalation, and the prefix-cache sync satellite
(docs/observability.md "Training plane").

Everything here runs under injected clocks — the hang/straggler/desync
truth table is deterministic, no sleeps except the (real-thread)
sentinel test.
"""
import json
import os
import time

import pytest

from skypilot_tpu.train import heartbeat as heartbeat_lib
from skypilot_tpu.train import postmortem as postmortem_lib
from skypilot_tpu.train import watchdog as watchdog_lib
from skypilot_tpu.utils import metrics as metrics_lib


class FakeClock:
    def __init__(self, t=1000.0):
        self.t = t

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


@pytest.fixture()
def wd_env(monkeypatch):
    """Deterministic watchdog thresholds for the truth table."""
    monkeypatch.setenv('SKYT_WATCHDOG_MIN_S', '1')
    monkeypatch.setenv('SKYT_WATCHDOG_FACTOR', '5')
    monkeypatch.setenv('SKYT_WATCHDOG_STRAGGLER_K', '3')
    monkeypatch.setenv('SKYT_WATCHDOG_PIPELINE_DEPTH', '2')
    monkeypatch.setenv('SKYT_WATCHDOG_CONFIRM', '2')


# ================================================================ heartbeat
def test_heartbeat_record_and_ewma_deterministic(tmp_path):
    clock = FakeClock()
    path = str(tmp_path / 'hb.json')
    w = heartbeat_lib.HeartbeatWriter(path, 3, clock=clock,
                                      interval_s=0)
    w.mark_phase('compile')
    assert heartbeat_lib.read(path)['phase'] == 'compile'
    for i in range(6):
        clock.advance(0.5)
        w.on_step(i, tokens_per_sec=42.0)
    rec = heartbeat_lib.read(path)
    assert rec['rank'] == 3 and rec['step'] == 5
    assert rec['phase'] == 'step' and rec['ts'] == clock.t
    # Constant 0.5s steps -> EWMA converges to exactly 0.5.
    assert abs(rec['ewma_step_s'] - 0.5) < 1e-9
    assert rec['tokens_per_sec'] == 42.0
    # No torn/tmp files left behind by the atomic write.
    assert [p.name for p in tmp_path.iterdir()] == ['hb.json']


def test_heartbeat_write_throttle(tmp_path):
    clock = FakeClock()
    path = str(tmp_path / 'hb.json')
    w = heartbeat_lib.HeartbeatWriter(path, 0, clock=clock,
                                      interval_s=10)
    clock.advance(1)
    w.on_step(1)
    clock.advance(1)
    w.on_step(2)          # within the interval: file stays at step 1
    assert heartbeat_lib.read(path)['step'] == 1
    clock.advance(10)
    w.on_step(3)
    assert heartbeat_lib.read(path)['step'] == 3
    # In-memory snapshot is always current, whatever the file throttle.
    assert w.snapshot()['step'] == 3


def test_heartbeat_read_tolerates_garbage(tmp_path):
    p = tmp_path / 'hb.json'
    assert heartbeat_lib.read(str(p)) is None
    p.write_text('{torn')
    assert heartbeat_lib.read(str(p)) is None
    p.write_text('[1, 2]')
    assert heartbeat_lib.read(str(p)) is None


def test_writer_from_env_gating(monkeypatch, tmp_path):
    monkeypatch.setenv('SKYT_WATCHDOG', '0')
    assert heartbeat_lib.writer_from_env() is None
    monkeypatch.setenv('SKYT_WATCHDOG', '1')
    monkeypatch.setenv('SKYT_NODE_RANK', '2')
    monkeypatch.setenv('SKYT_HEARTBEAT_FILE', str(tmp_path / 'h.json'))
    w = heartbeat_lib.writer_from_env()
    assert w is not None and w.rank == 2
    assert w.path == str(tmp_path / 'h.json')


# ================================================== watchdog truth table
def _rec(rank, ts, step=10, ewma=0.1, phase='step'):
    return {'rank': rank, 'step': step, 'phase': phase, 'ts': ts,
            'ewma_step_s': ewma}


def _gang(clock, n=2, registry=None):
    return watchdog_lib.GangWatchdog(
        n, clock=clock,
        registry=registry or metrics_lib.MetricsRegistry())


def test_verdict_init_before_any_stepping(wd_env):
    clock = FakeClock()
    wd = _gang(clock)
    assert wd.evaluate().state == 'init'
    wd.observe(0, _rec(0, clock.t, phase='compile'))
    wd.observe(1, _rec(1, clock.t, phase='init'))
    # Compiling for a long time is NOT a hang: no stall budget applies
    # until a rank reaches phase 'step'.
    clock.advance(3600)
    assert wd.evaluate().state == 'init'


def test_verdict_ok_and_hang_budget(wd_env):
    clock = FakeClock()
    wd = _gang(clock)
    wd.observe(0, _rec(0, clock.t))
    wd.observe(1, _rec(1, clock.t))
    assert wd.evaluate().state == 'ok'
    # Silence below the floor (min_s=1 > 5*0.1 ewma budget) stays ok.
    clock.advance(0.9)
    assert wd.evaluate().state == 'ok'
    # Past max(factor*ewma, min_s): hang, naming the stalled rank.
    clock.advance(0.2)
    v = wd.evaluate()
    assert v.state == 'hang'
    assert set(v.detail['stalled_ranks']) == {0, 1}


def test_hang_floor_scales_with_ewma(wd_env):
    clock = FakeClock()
    wd = _gang(clock)
    # Slow steps (1s EWMA): budget = 5*1 = 5s > the 1s floor.
    wd.observe(0, _rec(0, clock.t, ewma=1.0))
    wd.observe(1, _rec(1, clock.t, ewma=1.0))
    clock.advance(4.5)
    assert wd.evaluate().state == 'ok'
    clock.advance(1.0)
    assert wd.evaluate().state == 'hang'


def test_hang_confirmation_streak(wd_env):
    clock = FakeClock()
    wd = _gang(clock)
    wd.observe(0, _rec(0, clock.t))
    wd.observe(1, _rec(1, clock.t))
    clock.advance(5)
    v1 = wd.evaluate()
    assert v1.state == 'hang' and not v1.confirmed
    v2 = wd.evaluate()
    assert v2.confirmed
    # A fresh heartbeat resets the streak.
    wd.observe(0, _rec(0, clock.t))
    wd.observe(1, _rec(1, clock.t))
    assert wd.evaluate().state == 'ok'
    clock.advance(5)
    assert not wd.evaluate().confirmed


def test_verdict_straggler(wd_env):
    clock = FakeClock()
    wd = _gang(clock, n=3)
    wd.observe(0, _rec(0, clock.t, ewma=0.1))
    wd.observe(1, _rec(1, clock.t, ewma=0.12))
    wd.observe(2, _rec(2, clock.t, ewma=0.9))   # > 3x median (0.12)
    v = wd.evaluate()
    assert v.state == 'straggler'
    assert list(v.detail['straggler_ranks']) == [2]
    # K is env-tunable: a huge K clears the verdict.
    os.environ['SKYT_WATCHDOG_STRAGGLER_K'] = '100'
    try:
        assert wd.evaluate().state == 'ok'
    finally:
        os.environ['SKYT_WATCHDOG_STRAGGLER_K'] = '3'


def test_verdict_desync_and_hang_precedence(wd_env):
    clock = FakeClock()
    wd = _gang(clock)
    wd.observe(0, _rec(0, clock.t, step=10))
    wd.observe(1, _rec(1, clock.t, step=20))    # skew 10 > depth 2
    assert wd.evaluate().state == 'desync'
    # Hang wins over desync (a hung rank drags survivors apart —
    # report the cause, not the symptom).
    clock.advance(5)
    assert wd.evaluate().state == 'hang'


def test_watchdog_metrics_and_spans(wd_env, monkeypatch):
    from skypilot_tpu.utils import tracing
    monkeypatch.setenv('SKYT_TRACE', '1')
    monkeypatch.setenv('SKYT_TRACE_SAMPLE', '0')
    clock = FakeClock()
    reg = metrics_lib.MetricsRegistry()
    tracer = tracing.Tracer(service='wd-test')
    wd = watchdog_lib.GangWatchdog(2, clock=clock, registry=reg,
                                   tracer=tracer, job='7')
    wd.observe(0, _rec(0, clock.t))
    wd.observe(1, _rec(1, clock.t))
    wd.evaluate()
    gauge = reg.get('skyt_train_gang_state')
    assert gauge.value('7', 'ok') == 1.0
    assert gauge.value('7', 'hang') == 0.0
    clock.advance(5)
    wd.evaluate()
    assert gauge.value('7', 'hang') == 1.0
    assert gauge.value('7', 'ok') == 0.0
    assert reg.get(
        'skyt_train_watchdog_verdicts_total').value('7', 'hang') == 1.0
    # Concurrent jobs don't clobber each other's series (the head runs
    # one evaluator per job on a shared registry)...
    other = watchdog_lib.GangWatchdog(2, clock=clock, registry=reg,
                                      job='8')
    other.observe(0, _rec(0, clock.t))
    other.observe(1, _rec(1, clock.t))
    other.evaluate()
    assert gauge.value('8', 'ok') == 1.0
    assert gauge.value('7', 'hang') == 1.0   # job 7's verdict intact
    # ...and a retired job's series are dropped, not leaked.
    wd.retire()
    assert ('7', 'hang') not in gauge.label_keys()
    assert ('8', 'ok') in gauge.label_keys()
    # Forced-sampled transition span survives head-sampling at 0.
    names = [s['name'] for r in tracer.store.records()
             for s in r['spans']]
    assert 'watchdog.hang' in names


def test_classify_stall_shared_helper(wd_env):
    now = 100.0
    assert not watchdog_lib.classify_stall(None, now)['stalled']
    assert not watchdog_lib.classify_stall(
        _rec(0, now - 999, phase='compile'), now)['stalled']
    c = watchdog_lib.classify_stall(_rec(0, now - 2.0), now)
    assert c['stalled'] and c['stalled_for_s'] == 2.0
    assert c['budget_s'] == 1.0


# =============================================================== sentinel
def test_rank_sentinel_fires_once_and_dumps(tmp_path, monkeypatch):
    """Real-thread sentinel: stall past the budget -> exactly one
    on_stall callback carrying the stall classification."""
    monkeypatch.setenv('SKYT_WATCHDOG_MIN_S', '0.3')
    monkeypatch.setenv('SKYT_WATCHDOG_FACTOR', '2')
    w = heartbeat_lib.HeartbeatWriter(None, 0, interval_s=0)
    fired = []
    s = watchdog_lib.RankSentinel(w, fired.append, poll_s=0.05).start()
    try:
        w.on_step(1)
        time.sleep(0.15)
        assert not fired          # still within budget
        deadline = time.time() + 10
        while not s.fired.is_set() and time.time() < deadline:
            time.sleep(0.05)
        assert len(fired) == 1
        assert fired[0]['stall']['stalled']
        time.sleep(0.2)
        assert len(fired) == 1    # one bundle per stall episode
    finally:
        s.stop()


# ============================================================= postmortem
def test_postmortem_bundle_contents_and_index(tmp_path, monkeypatch):
    monkeypatch.setenv('SKYT_POSTMORTEM_DIR', str(tmp_path))
    monkeypatch.setenv('SKYT_JOB_ID', '7')
    path = postmortem_lib.dump_bundle(
        'hang', rank=1, heartbeat={'step': 4, 'phase': 'step'},
        train_state={'step': 4, 'prefetch_resident': 2})
    assert path and os.path.isdir(path)
    # py-stacks include THIS thread (faulthandler all_threads).
    stacks = open(os.path.join(path, 'stacks.txt')).read()
    assert 'test_postmortem_bundle_contents_and_index' in stacks
    spans = json.load(open(os.path.join(path, 'spans.json')))
    assert 'traces' in spans and 'summaries' in spans
    state = json.load(open(os.path.join(path, 'state.json')))
    assert state['reason'] == 'hang' and state['rank'] == 1
    assert state['job_id'] == '7'
    assert state['heartbeat']['step'] == 4
    assert state['train']['prefetch_resident'] == 2
    assert state['env']['SKYT_JOB_ID'] == '7'
    # Atomic: no .tmp staging dirs remain.
    assert not [n for n in os.listdir(tmp_path) if n.startswith('.tmp')]
    idx = postmortem_lib.list_bundles()
    assert len(idx) == 1
    assert idx[0]['reason'] == 'hang' and idx[0]['rank'] == 1
    assert sorted(idx[0]['files']) == ['spans.json', 'stacks.txt',
                                       'state.json']
    # Foreign files and torn bundles don't break the index.
    (tmp_path / 'unrelated.txt').write_text('x')
    broken = tmp_path / 'postmortem-19700101-000000-rank9-1'
    broken.mkdir()
    idx = postmortem_lib.list_bundles()
    assert len(idx) == 2
    assert any('error' in e for e in idx)


def test_postmortem_stacks_survive_thread_truncation(tmp_path,
                                                     monkeypatch):
    """faulthandler's all-threads dump caps at 100 threads (newest
    first), so in a thread-heavy process the requesting thread — the
    one that diagnosed the hang — is exactly the one truncated away.
    dump_bundle writes it separately so it always survives."""
    import threading
    monkeypatch.setenv('SKYT_POSTMORTEM_DIR', str(tmp_path))
    release = threading.Event()
    extra = [threading.Thread(target=release.wait, daemon=True)
             for _ in range(110)]
    try:
        for t in extra:
            t.start()
        path = postmortem_lib.dump_bundle('hang', rank=0)
        stacks = open(os.path.join(path, 'stacks.txt')).read()
        assert '...' in stacks          # the cap really was hit
        assert 'test_postmortem_stacks_survive_thread_truncation' \
            in stacks
    finally:
        release.set()
        for t in extra:
            t.join(timeout=5)


def test_postmortem_dump_never_raises(tmp_path, monkeypatch):
    # Unusable root (a FILE occupies the path — mkdir can never
    # succeed, even for root): dump returns None instead of raising
    # into a dying process.
    (tmp_path / 'f').write_text('not a dir')
    monkeypatch.setenv('SKYT_POSTMORTEM_DIR',
                       str(tmp_path / 'f' / 'x'))
    assert postmortem_lib.dump_bundle('crash') is None


# ====================================================== head escalation
def test_head_state_hang_escalates_to_hung(tmp_path, monkeypatch,
                                           wd_env):
    """Relayed heartbeats -> confirmed hang -> terminal HUNG + kill
    directives for every rank; a later cooperative rc=75 from a
    SIGTERM'd survivor must not relabel the hang."""
    monkeypatch.setenv('SKYT_AGENT_HOME', str(tmp_path))
    from skypilot_tpu.runtime import job_lib
    from skypilot_tpu.runtime import server as rt_server
    job_lib.reset_db_for_testing()
    clock = FakeClock()
    head = rt_server.HeadState(rt_server.ClusterConfig(
        {'cluster_name': 'c', 'num_nodes': 2,
         'ips': ['127.0.0.1', '127.0.0.2']}), clock=clock)
    jid = head.submit({'name': 'j', 'run': 'x', 'num_nodes': 2})
    head.schedule_step()
    head.report(jid, 0, 'run_started')
    head.report(jid, 1, 'run_started')

    head.record_heartbeat(jid, 0, _rec(0, clock.t))
    head.record_heartbeat(jid, 1, _rec(1, clock.t),
                          postmortems=['/logs/postmortem-a-rank1-9'])
    head.watchdog_tick()
    assert job_lib.get_job(jid)['status'] is job_lib.JobStatus.RUNNING

    clock.advance(10)                       # rank 1 goes silent
    head.record_heartbeat(jid, 0, _rec(0, clock.t))
    head.watchdog_tick()                    # hang streak 1
    assert job_lib.get_job(jid)['status'] is job_lib.JobStatus.RUNNING
    head.watchdog_tick()                    # confirmed
    assert job_lib.get_job(jid)['status'] is job_lib.JobStatus.HUNG
    for rank in (0, 1):
        assert any(d['action'] == 'kill'
                   for d in head.work_for_rank(rank))
    obs = head.job_observability(jid)
    assert obs['watchdog']['state'] == 'hang'
    assert obs['watchdog']['confirmed'] is True
    assert obs['postmortems']['1'] == ['/logs/postmortem-a-rank1-9']
    assert obs['heartbeats']['0']['step'] == 10
    # Survivor's SIGTERM-path 75 must not downgrade HUNG -> PREEMPTED.
    head.report(jid, 0, 'done', job_lib.EXIT_CODE_PREEMPTED)
    assert job_lib.get_job(jid)['status'] is job_lib.JobStatus.HUNG
    # Terminal job: the next tick retires the evaluator but keeps the
    # verdict for the wire.
    head.watchdog_tick()
    assert jid not in head.watchdogs
    assert head.job_observability(jid)['watchdog']['state'] == 'hang'


def test_hung_is_terminal_and_recovered_by_controller():
    from skypilot_tpu.runtime import job_lib
    assert job_lib.JobStatus.HUNG.is_terminal()
    # The managed-jobs watch loop recovers HUNG via the same branch as
    # PREEMPTED (jobs/controller.py) — pin the literal the probe
    # compares against so a status rename can't silently break it.
    import inspect

    from skypilot_tpu.jobs import controller as jobs_controller
    src = inspect.getsource(jobs_controller.JobsController._run_one_task)
    assert "'HUNG'" in src and "'PREEMPTED'" in src


# ============================================== /fleet/postmortems route
def test_fleet_postmortems_route(tmp_path, monkeypatch):
    import asyncio

    from aiohttp import web
    from aiohttp.test_utils import TestClient, TestServer

    from skypilot_tpu.serve import fleet as fleet_lib
    monkeypatch.setenv('SKYT_POSTMORTEM_DIR', str(tmp_path))
    postmortem_lib.dump_bundle('hang', rank=0)
    fl = fleet_lib.FleetTelemetry(
        'svc', metrics_registry=metrics_lib.MetricsRegistry())

    async def run():
        app = web.Application()
        fleet_lib.add_fleet_routes(app, fl, lambda rid: None)
        client = TestClient(TestServer(app))
        await client.start_server()
        try:
            resp = await client.get('/fleet/postmortems')
            assert resp.status == 200
            body = await resp.json()
            assert body['root'] == str(tmp_path)
            assert len(body['bundles']) == 1
            assert body['bundles'][0]['reason'] == 'hang'
            resp = await client.get('/fleet/postmortems',
                                    params={'limit': '0'})
            assert resp.status == 400
        finally:
            await client.close()

    asyncio.run(run())


# ================================== prefix-cache sync satellite (LB side)
def test_lb_prefix_cache_gauge_tracks_sync(monkeypatch):
    from skypilot_tpu.serve import load_balancer as lb_lib
    monkeypatch.setenv('SKYT_SERVE_LB_SYNC_INTERVAL', '3600')
    reg = metrics_lib.MetricsRegistry()
    lb = lb_lib.SkyServeLoadBalancer('http://127.0.0.1:9', 0,
                                     metrics_registry=reg)
    state = lb_lib.LBState(
        ready_replicas=['http://a', 'http://b'],
        replica_prefix_cache={
            'http://a': {'occupancy': 0.75, 'cached_pages': 12},
            'http://b': {'hit_pages': 3}},        # no occupancy: skip
        synced_at=1.0, version=1)
    lb.apply_state(state)
    gauge = reg.get('skyt_lb_replica_prefix_cache')
    assert gauge.value(lb.lb_id, 'http://a') == 0.75
    assert (lb.lb_id, 'http://b') not in gauge.label_keys()
    # Replica leaves the sync: its series is pruned.
    lb.apply_state(lb_lib.LBState(ready_replicas=['http://b'],
                                  synced_at=2.0, version=2))
    assert (lb.lb_id, 'http://a') not in gauge.label_keys()
    # Snapshot roundtrip carries the block (standby mirrors see it).
    restored = lb_lib.LBState.from_json(state.to_json())
    assert restored.replica_prefix_cache['http://a']['occupancy'] == \
        0.75


def test_replica_manager_scrapes_prefix_cache(monkeypatch):
    """ready_prefix_cache() narrows to READY replicas whose /stats
    carried a prefix_cache block (the controller sync source)."""
    from skypilot_tpu.serve import replica_managers
    from skypilot_tpu.serve import serve_state
    assert 'prefix_cache' in replica_managers.ReplicaManager._STATS_KEYS

    rm = object.__new__(replica_managers.ReplicaManager)
    rm._lock = __import__('threading').Lock()

    class R:
        def __init__(self, status, endpoint, stats):
            self.status = status
            self.endpoint = endpoint
            self.stats = stats

    ready = serve_state.ReplicaStatus.READY
    rm.replicas = {
        1: R(ready, 'http://a', {'prefix_cache': {'occupancy': 0.5}}),
        2: R(ready, 'http://b', {'qos': {}}),            # no block
        3: R(serve_state.ReplicaStatus.NOT_READY, 'http://c',
             {'prefix_cache': {'occupancy': 0.9}}),      # not ready
    }
    out = replica_managers.ReplicaManager.ready_prefix_cache(rm)
    assert out == {'http://a': {'occupancy': 0.5}}


def test_engine_prefix_cache_occupancy_in_stats():
    """The paged pool reports cached pages; the engine folds occupancy
    into the /stats prefix_cache block the controller scrapes."""
    import jax.numpy as jnp

    from skypilot_tpu.infer import paged_cache
    cfg = paged_cache.PagedConfig(page_size=4, n_pages=9,
                                  max_pages_per_slot=4)
    pool = paged_cache.PagePool(cfg, n_layers=1, kv_heads=1, head_dim=4,
                                num_slots=2, dtype=jnp.float32)
    assert pool.prefix_cached_pages() == 0
    row = pool.try_reserve_prefix(0, 8, ())
    assert row is not None
    pool.publish(0, [b'h0', b'h1'])
    assert pool.prefix_cached_pages() == 2
