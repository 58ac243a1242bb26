"""Chaos suite, replica faults: the fault-spec grammar, request deadlines,
disconnects, crash faults, replica kills mid-burst, QoS shedding and the
KV warm restart
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

from chaos_helpers import _free_port, _make_lb, _run_app_bg, _wait_http
# Fixtures, used by name:
from chaos_helpers import _reset_faults  # noqa: unused-import

pytestmark = [pytest.mark.heavy,
              pytest.mark.usefixtures('one_device_children')]


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


# 18 s here: two engine replicas, one killed and started again warm.
# Measured on an idle 8-core box; the driver's is some three times slower.
@pytest.mark.time_limit(300)
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
