"""QoS admission control (serve/qos.py + its wiring, docs/qos.md):

Fast tier — pure scheduling/parsing logic, no model:
  * header contract: X-Priority / X-Tenant / OpenAI service_tier
    parsing, malformed forms rejected;
  * token-bucket refill determinism under a seeded clock;
  * DRR fair queue: strict class order, FIFO within a flow, fairness
    under a single-tenant batch flood, aging prevents starvation;
  * ClassedRequestQueue reorder/apply_order semantics;
  * overload ladder levels + hysteresis, shed/degrade decisions, and
    the qos.shed / qos.throttle fault points;
  * autoscaler satellites: timestamp-buffer cap + QoS-aware targets;
  * lint rule: direct _waiting.put( outside the admission path flags.

Heavy tier — the real engine/server with SKYT_QOS=1:
  * priority ordering through engine.submit + per-class metrics;
  * server 400s on malformed headers, 429 + Retry-After on forced
    sheds, degrade clamps max_tokens;
  * LB 503 carries Retry-After (satellite).
"""
import os
import socket
import threading
import time

import pytest

from skypilot_tpu.serve import qos
from skypilot_tpu.utils import faults
from skypilot_tpu.utils import metrics as metrics_lib


@pytest.fixture(autouse=True)
def _reset_faults():
    faults.reset()
    yield
    faults.reset()


class FakeClock:
    def __init__(self, t: float = 0.0) -> None:
        self.t = t

    def __call__(self) -> float:
        return self.t

    def advance(self, dt: float) -> None:
        self.t += dt


# ========================================================= header contract
def test_parse_priority():
    assert qos.parse_priority(None) == 'standard'
    assert qos.parse_priority('') == 'standard'
    assert qos.parse_priority('interactive') == 'interactive'
    assert qos.parse_priority(' Batch ') == 'batch'
    with pytest.raises(ValueError, match='urgent'):
        qos.parse_priority('urgent')


def test_parse_tenant():
    assert qos.parse_tenant(None) == 'default'
    assert qos.parse_tenant('team-a_1.prod') == 'team-a_1.prod'
    with pytest.raises(ValueError):
        qos.parse_tenant('bad tenant!')
    with pytest.raises(ValueError):
        qos.parse_tenant('x' * 65)


def test_map_service_tier():
    assert qos.map_service_tier(None) is None
    assert qos.map_service_tier('priority') == 'interactive'
    assert qos.map_service_tier('default') == 'standard'
    assert qos.map_service_tier('flex') == 'batch'
    with pytest.raises(ValueError, match='gold'):
        qos.map_service_tier('gold')


def test_retry_after_header_rounds_up():
    assert qos.retry_after_header(0.2) == '1'
    assert qos.retry_after_header(1.0) == '1'
    assert qos.retry_after_header(1.2) == '2'


# ============================================================ token bucket
def test_token_bucket_refill_determinism():
    """Same seeded clock trajectory => identical grant pattern, and
    the refill math is exact (no wall-clock dependence)."""
    def pattern():
        clock = FakeClock()
        tb = qos.TokenBucket(rate=2.0, burst=4.0, clock=clock)
        grants = []
        for step in range(20):
            ok, retry = tb.try_take()
            grants.append((ok, round(retry, 6)))
            clock.advance(0.25 if step % 3 else 0.0)
        return grants
    a, b = pattern(), pattern()
    assert a == b
    assert a[0] == (True, 0.0)
    assert any(not ok for ok, _ in a)          # bucket does run dry


def test_token_bucket_retry_after_is_exact():
    clock = FakeClock()
    tb = qos.TokenBucket(rate=2.0, burst=1.0, clock=clock)
    assert tb.try_take() == (True, 0.0)
    ok, retry = tb.try_take()
    assert not ok and retry == pytest.approx(0.5)   # 1 token / 2 per s
    clock.advance(0.5)
    assert tb.try_take() == (True, 0.0)


def test_tenant_rate_limiter_isolates_tenants():
    clock = FakeClock()
    lim = qos.TenantRateLimiter(rate=1.0, burst=1.0, clock=clock)
    assert lim.try_take('a')[0]
    assert not lim.try_take('a')[0]        # a's bucket is dry
    assert lim.try_take('b')[0]            # b unaffected
    # rate <= 0 disables limiting
    off = qos.TenantRateLimiter(rate=0.0, burst=0.0, clock=clock)
    assert all(off.try_take('x')[0] for _ in range(100))


def test_tenant_rate_limiter_bounded_tenants():
    clock = FakeClock()
    lim = qos.TenantRateLimiter(rate=1.0, burst=1.0, max_tenants=4,
                                clock=clock)
    for i in range(100):
        lim.try_take(f't{i}')
    assert len(lim._buckets) <= 4   # pylint: disable=protected-access


# ========================================================== DRR fair queue
def test_fairqueue_strict_class_order():
    clock = FakeClock()
    fq = qos.FairQueue(quantum=10, aging_s=1000, clock=clock)
    fq.push('b1', 'batch', cost=1)
    fq.push('s1', 'standard', cost=1)
    fq.push('i1', 'interactive', cost=1)
    fq.push('i2', 'interactive', cost=1)
    assert fq.drain() == ['i1', 'i2', 's1', 'b1']


def test_fairqueue_fifo_within_flow():
    fq = qos.FairQueue(quantum=10, aging_s=1000, clock=FakeClock())
    for i in range(8):
        fq.push(i, 'standard', 'tA', cost=3)
    assert fq.drain() == list(range(8))


def test_fairqueue_drr_fairness_under_batch_flood():
    """One tenant floods the batch class; a second tenant's handful of
    batch requests must be served interleaved (within a couple of DRR
    rounds), not after the entire flood."""
    fq = qos.FairQueue(quantum=10, aging_s=1000, clock=FakeClock())
    for i in range(50):
        fq.push(('flood', i), 'batch', 'flooder', cost=10)
    for i in range(5):
        fq.push(('small', i), 'batch', 'small-tenant', cost=10)
    order = fq.drain()
    positions = [order.index(('small', i)) for i in range(5)]
    # Equal costs and weights => near-perfect alternation: the small
    # tenant's 5 requests all land in the first ~12 pops.
    assert max(positions) <= 12, positions
    # And within the small tenant, FIFO survives.
    assert positions == sorted(positions)


def test_fairqueue_weighted_drr():
    """Unequal costs: the DRR quantum meters out service by COST, so a
    tenant with expensive requests gets fewer of them per round."""
    fq = qos.FairQueue(quantum=10, aging_s=1000, clock=FakeClock())
    for i in range(6):
        fq.push(('cheap', i), 'batch', 'cheap', cost=5)
    for i in range(6):
        fq.push(('fat', i), 'batch', 'fat', cost=20)
    order = fq.drain()
    # After 12 pops: cheap got ~2x the requests of fat in any prefix
    # covering whole rounds.
    first8 = order[:8]
    n_cheap = sum(1 for x in first8 if x[0] == 'cheap')
    n_fat = sum(1 for x in first8 if x[0] == 'fat')
    assert n_cheap > n_fat, order


def test_fairqueue_aging_prevents_starvation():
    """A batch request older than 2*aging_s outranks fresh interactive
    traffic (its band descends below rank 0)."""
    clock = FakeClock(1000.0)
    fq = qos.FairQueue(quantum=10, aging_s=10, clock=clock)
    fq.push('old-batch', 'batch', cost=1, t=1000.0 - 25)   # aged 2 bands
    fq.push('fresh-i', 'interactive', cost=1, t=1000.0)
    assert fq.pop() == 'old-batch'
    # Without aging the same shape serves interactive first.
    fq2 = qos.FairQueue(quantum=10, aging_s=10, clock=clock)
    fq2.push('batch', 'batch', cost=1, t=1000.0 - 5)       # not aged yet
    fq2.push('fresh-i', 'interactive', cost=1, t=1000.0)
    assert fq2.pop() == 'fresh-i'


def test_fairqueue_depths():
    fq = qos.FairQueue(clock=FakeClock())
    fq.push('a', 'batch')
    fq.push('b', 'batch')
    fq.push('c', 'interactive')
    assert fq.depths() == {'interactive': 1, 'standard': 0, 'batch': 2}
    assert len(fq) == 3


# ================================================== ClassedRequestQueue
class _Item:
    def __init__(self, seq, cls='standard', tenant='default',
                 cost=1.0, t=0.0):
        self.seq = seq
        self.cls = cls
        self.tenant = tenant
        self.cost = cost
        self.t = t

    def __repr__(self):
        return f'<{self.seq}:{self.cls}>'


def _crq(clock=None, **kw):
    clock = clock or FakeClock()
    return qos.ClassedRequestQueue(
        meta=lambda it: qos.RequestMeta(
            cls=it.cls, tenant=it.tenant, cost=it.cost, seq=it.seq,
            enq_t=it.t),
        quantum=10, aging_s=1000, debt_halflife_s=30, clock=clock), \
        clock


def test_classed_queue_reorder_and_pop():
    q, clock = _crq()
    for i in range(3):
        q.put(_Item(i, 'batch'))
    q.put(_Item(3, 'interactive'))
    q.put(_Item(4, 'standard'))
    order, changed = q.reorder(clock())
    assert changed
    assert order == [3, 4, 0, 1, 2]
    assert q.get_nowait().seq == 3      # pops follow the schedule
    assert q.get_nowait().seq == 4
    # A second reorder with no new arrivals: already in order.
    order2, changed2 = q.reorder(clock())
    assert order2 == [0, 1, 2] and not changed2


def test_classed_queue_apply_order():
    q, _clock = _crq()
    for i in range(4):
        q.put(_Item(i))
    q.apply_order([2, 0, 3, 1])
    assert [q.get_nowait().seq for _ in range(4)] == [2, 0, 3, 1]


def test_classed_queue_debt_biases_next_round():
    """A tenant whose burst was just served starts the next round
    indebted: a fresh arrival from a peer tenant schedules ahead of
    the indebted tenant's backlog."""
    q, clock = _crq()
    for i in range(6):
        q.put(_Item(i, 'batch', 'greedy', cost=10))
    q.reorder(clock())
    for _ in range(4):                      # serve greedy's head burst
        q.get_nowait()
    q.put(_Item(100, 'batch', 'polite', cost=10))
    order, _ = q.reorder(clock())
    assert order[0] == 100, order           # polite jumps the backlog


def test_classed_queue_batch_bucket_prefix_preserved():
    """Within a class the schedule is arrival-ordered per tenant, so a
    same-bucket FIFO prefix (what batched admission collects) never
    straddles a class boundary: all interactive items sort strictly
    before all batch items."""
    q, clock = _crq()
    for i in range(4):
        q.put(_Item(i, 'batch'))
    for i in range(4, 8):
        q.put(_Item(i, 'interactive'))
    order, _ = q.reorder(clock())
    assert order == [4, 5, 6, 7, 0, 1, 2, 3]


# ========================================================= overload ladder
def _controller(sig, clock=None, **env):
    clock = clock or FakeClock()
    defaults = {'SKYT_QOS_QUEUE_DEGRADE': '4',
                'SKYT_QOS_QUEUE_SHED': '8',
                'SKYT_QOS_HOLD_S': '2', 'SKYT_QOS_REFRESH_S': '0',
                'SKYT_QOS_TTFT_SLO_MS': '500'}
    defaults.update({k: str(v) for k, v in env.items()})
    old = {k: os.environ.get(k) for k in defaults}
    os.environ.update(defaults)
    try:
        ctl = qos.OverloadController(sig, clock=clock)
    finally:
        for k, v in old.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
    return ctl, clock


def test_overload_levels_from_queue_depth():
    state = {'queue_depth': 0.0, 'num_slots': 2.0}
    ctl, clock = _controller(lambda: state)
    assert ctl.level() == 0
    state['queue_depth'] = 9.0        # ratio 4.5 >= degrade(4)
    clock.advance(1)
    assert ctl.level() == 1
    state['queue_depth'] = 17.0       # ratio 8.5 >= shed(8)
    clock.advance(1)
    assert ctl.level() == 2
    state['queue_depth'] = 33.0       # ratio 16.5 >= 2*shed
    clock.advance(1)
    assert ctl.level() == 3


def test_overload_kv_and_ttft_signals():
    state = {'queue_depth': 0.0, 'num_slots': 8.0, 'kv_util': 0.95}
    ctl, clock = _controller(lambda: state)
    assert ctl.level() == 1            # kv >= degrade(0.90)
    state['kv_util'] = 0.99
    clock.advance(1)
    assert ctl.level() == 2            # kv >= shed(0.97)
    state['kv_util'] = 0.0
    state['ttft_p95_s'] = 1.2          # > 2 * 500ms SLO
    clock.advance(10)                  # past the de-escalation hold
    assert ctl.level() == 2


def test_overload_hysteresis_holds_before_deescalating():
    state = {'queue_depth': 20.0, 'num_slots': 2.0}
    ctl, clock = _controller(lambda: state)
    assert ctl.level() == 2
    state['queue_depth'] = 0.0
    clock.advance(0.5)
    assert ctl.level() == 2            # still inside the hold window
    clock.advance(3.0)
    assert ctl.level() == 0            # held below long enough


def test_overload_retry_after_scales_with_level():
    ctl, _ = _controller(lambda: {})
    assert ctl.retry_after(1) == pytest.approx(1.0)
    assert ctl.retry_after(3) == pytest.approx(4.0)
    assert ctl.retry_after(30) == 30.0          # capped


# ========================================================= ServerQoS gate
def _server_qos(sig, clock=None, **env):
    clock = clock or FakeClock()
    defaults = {'SKYT_QOS_QUEUE_DEGRADE': '4',
                'SKYT_QOS_QUEUE_SHED': '8',
                'SKYT_QOS_HOLD_S': '2', 'SKYT_QOS_REFRESH_S': '0',
                'SKYT_QOS_DEGRADE_MAX_TOKENS': '32',
                'SKYT_QOS_TENANT_RPS': '0'}
    defaults.update({k: str(v) for k, v in env.items()})
    old = {k: os.environ.get(k) for k in defaults}
    os.environ.update(defaults)
    try:
        sq = qos.ServerQoS(sig, registry=metrics_lib.MetricsRegistry(),
                           clock=clock)
    finally:
        for k, v in old.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
    return sq, clock


def test_shed_ladder_lowest_class_first():
    state = {'queue_depth': 17.0, 'num_slots': 2.0}   # level 2
    sq, _ = _server_qos(lambda: state)
    assert sq.admit('batch', 't').action == 'shed'
    d = sq.admit('standard', 't', max_new_tokens=128)
    assert d.action == 'degrade' and d.max_new_tokens == 32
    assert sq.admit('interactive', 't').action == 'admit'
    state['queue_depth'] = 40.0                        # level 3
    sq2, _ = _server_qos(lambda: state)
    assert sq2.admit('standard', 't').action == 'shed'
    assert sq2.admit('batch', 't').action == 'shed'
    # Interactive is NEVER shed by the overload controller.
    assert sq2.admit('interactive', 't').action == 'admit'


def test_degrade_before_shed_for_batch():
    state = {'queue_depth': 9.0, 'num_slots': 2.0}     # level 1
    sq, _ = _server_qos(lambda: state)
    d = sq.admit('batch', 't', max_new_tokens=500)
    assert d.action == 'degrade' and d.max_new_tokens == 32
    # Small batch requests under the clamp are admitted untouched.
    assert sq.admit('batch', 't', max_new_tokens=8).action == 'admit'
    assert sq.admit('standard', 't',
                    max_new_tokens=500).action == 'admit'


def test_shed_retry_after_positive():
    state = {'queue_depth': 17.0, 'num_slots': 2.0}
    sq, _ = _server_qos(lambda: state)
    d = sq.admit('batch', 't')
    assert d.action == 'shed' and d.retry_after > 0


def test_throttle_via_token_bucket():
    sq, _ = _server_qos(lambda: {}, SKYT_QOS_TENANT_RPS='1',
                        SKYT_QOS_TENANT_BURST='2')
    actions = [sq.admit('interactive', 'spammer').action
               for _ in range(4)]
    assert actions[:2] == ['admit', 'admit']
    assert actions[2] == 'throttle'
    # Another tenant is unaffected.
    assert sq.admit('interactive', 'quiet').action == 'admit'


def test_qos_fault_points_force_paths():
    """Chaos hook: armed qos.shed / qos.throttle rules force the
    decision regardless of load, honoring where= class filters."""
    sq, _ = _server_qos(lambda: {})
    faults.configure('qos.shed=error,where=cls:batch')
    assert sq.admit('batch', 't').action == 'shed'
    assert sq.admit('interactive', 't').action == 'admit'
    faults.configure('qos.throttle=error,where=cls:interactive')
    assert sq.admit('interactive', 't').action == 'throttle'
    assert faults.fired_counts()[('qos.throttle', 'error')] == 1


def test_shed_metrics_count_by_class():
    state = {'queue_depth': 17.0, 'num_slots': 2.0}
    reg = metrics_lib.MetricsRegistry()
    os.environ.update({'SKYT_QOS_QUEUE_SHED': '8',
                       'SKYT_QOS_REFRESH_S': '0',
                       'SKYT_QOS_HOLD_S': '2'})
    try:
        sq = qos.ServerQoS(lambda: state, registry=reg,
                           clock=FakeClock())
        sq.admit('batch', 't')
        sq.admit('interactive', 't')
    finally:
        for k in ('SKYT_QOS_QUEUE_SHED', 'SKYT_QOS_REFRESH_S',
                  'SKYT_QOS_HOLD_S'):
            os.environ.pop(k, None)
    shed = reg.counter('skyt_qos_shed_total', '', ('class', 'model'))
    assert shed.value('batch', '') == 1
    assert shed.value('interactive', '') == 0


def test_snapshot_shape():
    sq, _ = _server_qos(lambda: {'queue_depth': 17, 'num_slots': 2})
    snap = sq.snapshot({'interactive': 0, 'standard': 1, 'batch': 16})
    assert snap['level'] == 2
    assert 0 <= snap['pressure'] <= 1
    assert snap['retry_after_s'] > 0
    assert snap['classes']['batch'] == 16


def test_shed_avoid_classes():
    assert qos.shed_avoid_classes(0) == ()
    assert qos.shed_avoid_classes(2) == ('batch',)
    assert set(qos.shed_avoid_classes(3)) == {'standard', 'batch'}


# ======================================================= autoscaler plane
def test_autoscaler_timestamp_buffer_cap(monkeypatch):
    """Satellite: the request-timestamp buffer is bounded drop-oldest
    with a drop counter (mirrors the PR 4 LB sync-buffer fix)."""
    from skypilot_tpu.serve import autoscalers
    from skypilot_tpu.serve import service_spec as spec_lib
    monkeypatch.setenv('SKYT_AUTOSCALER_MAX_TIMESTAMPS', '100')
    reg = metrics_lib.MetricsRegistry()
    spec = spec_lib.ServiceSpec(readiness_path='/health',
                                min_replicas=1)
    a = autoscalers.RequestRateAutoscaler(spec, metrics_registry=reg)
    now = time.time()
    a.collect_request_timestamps([now] * 250)
    assert len(a.request_timestamps) == 100
    dropped = reg.counter(
        'skyt_autoscaler_dropped_timestamps_total', '')
    assert dropped.value() == 150


def test_qos_autoscaler_weighted_demand_and_sheds(monkeypatch):
    from skypilot_tpu.serve import autoscalers
    from skypilot_tpu.serve import service_spec as spec_lib
    spec = spec_lib.ServiceSpec(readiness_path='/health',
                                min_replicas=1, max_replicas=10,
                                target_qps_per_replica=1.0)
    a = autoscalers.QoSAwareAutoscaler(
        spec, metrics_registry=metrics_lib.MetricsRegistry())
    now = time.time()
    # 120 interactive + 240 batch over the 60s window. Weighted QPS =
    # 1.0*2 + 0.25*4 = 3 -> 3 replicas.
    a.collect_qos([[now, 'interactive']] * 120 +
                  [[now, 'batch']] * 240, [])
    assert a._raw_target() == 3   # pylint: disable=protected-access
    # 60 observed sheds (1 shed QPS): +1 replica on top.
    a.collect_qos([], [[now, 'batch']] * 60)
    assert a._raw_target() == 4   # pylint: disable=protected-access


def test_qos_autoscaler_falls_back_to_raw_rate():
    from skypilot_tpu.serve import autoscalers
    from skypilot_tpu.serve import service_spec as spec_lib
    spec = spec_lib.ServiceSpec(readiness_path='/health',
                                min_replicas=1, max_replicas=10,
                                target_qps_per_replica=1.0)
    a = autoscalers.QoSAwareAutoscaler(
        spec, metrics_registry=metrics_lib.MetricsRegistry())
    now = time.time()
    a.collect_request_timestamps([now] * 120)   # 2 QPS, no class data
    assert a._raw_target() == 2   # pylint: disable=protected-access


def test_pick_autoscaler_cls(monkeypatch):
    from skypilot_tpu.serve import autoscalers
    from skypilot_tpu.serve import service_spec as spec_lib
    spec = spec_lib.ServiceSpec(readiness_path='/health',
                                min_replicas=1)
    monkeypatch.delenv('SKYT_QOS', raising=False)
    assert autoscalers.pick_autoscaler_cls(spec) is \
        autoscalers.RequestRateAutoscaler
    monkeypatch.setenv('SKYT_QOS', '1')
    assert autoscalers.pick_autoscaler_cls(spec) is \
        autoscalers.QoSAwareAutoscaler
    spec_fb = spec_lib.ServiceSpec(readiness_path='/health',
                                   min_replicas=1,
                                   base_ondemand_fallback_replicas=1)
    assert autoscalers.pick_autoscaler_cls(spec_fb) is \
        autoscalers.FallbackRequestRateAutoscaler


# ============================================================= lint rule
def test_lint_forbids_direct_waiting_put(tmp_path):
    """tools/lint.py flags new direct _waiting.put( callsites in
    infer/ outside the sanctioned admission path (satellite)."""
    import sys
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))), 'tools'))
    import lint   # noqa: E402
    d = tmp_path / 'skypilot_tpu' / 'infer'
    d.mkdir(parents=True)
    bad = d / 'sneaky.py'
    bad.write_text('def f(eng, req):\n'
                   '    eng._waiting.put(req)\n')
    issues = lint.check_file(bad)
    assert any('_waiting.put' in i for i in issues), issues
    ok = d / 'fine.py'
    ok.write_text('def f(eng, req):\n'
                  '    eng._waiting.put(req)   # qos-admission\n')
    assert not lint.check_file(ok)
    # Outside infer/ the rule does not apply.
    d2 = tmp_path / 'skypilot_tpu' / 'serve'
    d2.mkdir(parents=True)
    other = d2 / 'x.py'
    other.write_text('def f(eng, req):\n'
                     '    eng._waiting.put(req)\n')
    assert not lint.check_file(other)


# ============================================= engine + server integration
def _free_port() -> int:
    with socket.socket() as s:
        s.bind(('127.0.0.1', 0))
        return s.getsockname()[1]


def _run_app_bg(app, port) -> None:
    from aiohttp import web
    threading.Thread(target=lambda: web.run_app(
        app, port=port, print=None, handle_signals=False),
        daemon=True).start()


def _wait_http(url: str, timeout: float = 60) -> None:
    import requests
    deadline = time.time() + timeout
    while time.time() < deadline:
        try:
            if requests.get(url, timeout=2).status_code == 200:
                return
        except requests.RequestException:
            pass
        time.sleep(0.2)
    raise AssertionError(f'{url} never became healthy')


def _debug_engine(reg, num_slots=2):
    import dataclasses
    import jax
    import jax.numpy as jnp
    from skypilot_tpu.infer import engine as engine_lib
    from skypilot_tpu.models import llama
    cfg = dataclasses.replace(llama.CONFIGS['debug'], max_seq_len=64)
    model = llama.LlamaModel(cfg)
    params = jax.jit(model.init)(jax.random.PRNGKey(0),
                                 jnp.zeros((1, 8), jnp.int32))
    return engine_lib.InferenceEngine(model, params,
                                      num_slots=num_slots,
                                      max_seq_len=64, decode_chunk=4,
                                      prefill_buckets=[16],
                                      metrics_registry=reg)


@pytest.mark.heavy
def test_sampling_params_priority_validation():
    from skypilot_tpu.infer import engine as engine_lib
    engine_lib.SamplingParams(priority='batch',
                              tenant='team-a').validate()
    with pytest.raises(ValueError, match='priority'):
        engine_lib.SamplingParams(priority='vip').validate()
    with pytest.raises(ValueError, match='tenant'):
        engine_lib.SamplingParams(tenant=7).validate()


@pytest.mark.heavy
@pytest.mark.integration
def test_engine_priority_ordering_and_metrics(monkeypatch):
    """With SKYT_QOS=1 the engine schedules interactive ahead of a
    queued batch backlog (observable via first_token order), records
    per-class queue-wait/TTFT histograms, and exposes per-class
    depths/signals for the server layers."""
    monkeypatch.setenv('SKYT_QOS', '1')
    from skypilot_tpu.infer import engine as engine_lib
    reg = metrics_lib.MetricsRegistry()
    eng = _debug_engine(reg)
    # All batch requests first, then one interactive: with FIFO the
    # interactive one would be admitted LAST.
    batch = [eng.submit([1, 2, 3], engine_lib.SamplingParams(
        max_new_tokens=6, priority='batch', tenant='flooder'))
        for _ in range(6)]
    rid_i, q_i = eng.submit([4, 5, 6], engine_lib.SamplingParams(
        max_new_tokens=6, priority='interactive', tenant='user'))
    eng.start()
    try:
        queues = [q for _, q in batch] + [q_i]
        for q in queues:
            while q.get(timeout=120) is not None:
                pass
    finally:
        eng.stop()
    t_i = eng.request_trace(rid_i)['first_token']
    batch_firsts = sorted(
        eng.request_trace(rid)['first_token'] for rid, _ in batch)
    # The interactive request got its first token before at least the
    # back half of the batch backlog (it may share the very first
    # admission round with batch head(s) already popped).
    assert t_i < batch_firsts[2], (t_i, batch_firsts)
    ttft = reg.histogram('skyt_qos_ttft_seconds', '', ('class',))
    samples = {tuple(s['labels'].values()): s
               for s in ttft.sample_dicts()}
    assert ('interactive',) in samples and ('batch',) in samples
    assert eng.qos_depths() == {'interactive': 0, 'standard': 0,
                                'batch': 0}
    sig = eng.qos_signals()
    assert sig['num_slots'] == 2.0 and 'ttft_p95_s' in sig


@pytest.mark.heavy
@pytest.mark.integration
def test_engine_reserved_slots_gate_batch(monkeypatch):
    """SKYT_QOS_RESERVE_SLOTS=1: batch admissions leave one slot free
    for interactive arrivals."""
    monkeypatch.setenv('SKYT_QOS', '1')
    monkeypatch.setenv('SKYT_QOS_RESERVE_SLOTS', '1')
    from skypilot_tpu.infer import engine as engine_lib
    reg = metrics_lib.MetricsRegistry()
    eng = _debug_engine(reg, num_slots=2)
    eng.start()
    try:
        # Long-running batch requests: only ONE may occupy a slot.
        subs = [eng.submit([1, 2, 3], engine_lib.SamplingParams(
            max_new_tokens=40, priority='batch'))
            for _ in range(3)]
        deadline = time.time() + 60
        while time.time() < deadline and \
                eng.stats()['active_slots'] == 0:
            time.sleep(0.02)
        # Give the loop a chance to (wrongly) seat 2. With warm
        # programs all three requests can be over inside this window,
        # so watch it: one reading at its end may find no slot in use.
        seated = {eng.stats()['active_slots']}
        window_end = time.time() + 0.3
        while time.time() < window_end:
            seated.add(eng.stats()['active_slots'])
            time.sleep(0.005)
        assert max(seated) == 1, seated
        # An interactive request takes the reserved slot immediately.
        rid, q = eng.submit([7, 8, 9], engine_lib.SamplingParams(
            max_new_tokens=2, priority='interactive'))
        while q.get(timeout=60) is not None:
            pass
        assert eng.request_trace(rid)['status'] == 'done'
        for _, qb in subs:
            while qb.get(timeout=120) is not None:
                pass
    finally:
        eng.stop()


@pytest.mark.heavy
@pytest.mark.integration
def test_server_qos_headers_and_forced_shed(monkeypatch):
    """HTTP surface: malformed X-Priority/X-Tenant 400 naming the
    offender (QoS on or off); a forced qos.shed returns 429 +
    Retry-After and never reaches the engine; degrade clamps
    max_tokens; /stats exposes the qos snapshot."""
    import requests
    from skypilot_tpu.infer import server as server_lib
    monkeypatch.setenv('SKYT_QOS', '1')
    monkeypatch.setenv('SKYT_QOS_TTFT_SLO_MS', '0')
    reg = metrics_lib.MetricsRegistry()
    eng = _debug_engine(reg)
    eng.start()
    srv = server_lib.InferenceServer(eng)
    port = _free_port()
    _run_app_bg(srv.make_app(), port)
    base = f'http://127.0.0.1:{port}'
    _wait_http(base + '/health', timeout=120)
    try:
        r = requests.post(base + '/generate',
                          json={'tokens': [1, 2], 'max_tokens': 2},
                          headers={'X-Priority': 'vip'}, timeout=30)
        assert r.status_code == 400 and 'vip' in r.json()['error']
        r = requests.post(base + '/generate',
                          json={'tokens': [1, 2], 'max_tokens': 2},
                          headers={'X-Tenant': 'bad tenant!'},
                          timeout=30)
        assert r.status_code == 400
        r = requests.post(base + '/v1/completions',
                          json={'prompt': 'hi', 'max_tokens': 2,
                                'service_tier': 'gold'}, timeout=30)
        assert r.status_code == 400 and 'gold' in r.json()['error']
        # Forced shed via the fault point: batch 429s with
        # Retry-After, interactive unaffected.
        faults.configure('qos.shed=error,where=cls:batch')
        r = requests.post(base + '/generate',
                          json={'tokens': [1, 2], 'max_tokens': 2},
                          headers={'X-Priority': 'batch'}, timeout=30)
        assert r.status_code == 429
        assert int(r.headers['Retry-After']) >= 1
        assert r.json()['qos']['action'] == 'shed'
        r = requests.post(base + '/generate',
                          json={'tokens': [1, 2], 'max_tokens': 2},
                          headers={'X-Priority': 'interactive'},
                          timeout=60)
        assert r.status_code == 200
        faults.reset()
        # OpenAI route: service_tier=flex maps to batch.
        faults.configure('qos.shed=error,where=cls:batch')
        r = requests.post(base + '/v1/completions',
                          json={'prompt': 'hi', 'max_tokens': 2,
                                'service_tier': 'flex'}, timeout=30)
        assert r.status_code == 429
        faults.reset()
        stats = requests.get(base + '/stats', timeout=10).json()
        assert 'qos' in stats and 'level' in stats['qos']
        assert stats['qos']['classes'] == {
            'interactive': 0, 'standard': 0, 'batch': 0}
        # Shed decisions visible at /metrics by class.
        text = requests.get(base + '/metrics', timeout=10).text
        shed_batch = sum(
            float(line.rsplit(' ', 1)[1]) for line in text.splitlines()
            if line.startswith('skyt_qos_shed_total{class="batch"'))
        assert shed_batch == 2, text
    finally:
        eng.stop()


@pytest.mark.heavy
@pytest.mark.integration
def test_server_qos_off_headers_still_validated(monkeypatch):
    """SKYT_QOS=0: no admission control (no 429 path), but the header
    CONTRACT holds — malformed X-Priority is still a 400 and a valid
    one is accepted."""
    import requests
    from skypilot_tpu.infer import server as server_lib
    monkeypatch.delenv('SKYT_QOS', raising=False)
    reg = metrics_lib.MetricsRegistry()
    eng = _debug_engine(reg)
    eng.start()
    srv = server_lib.InferenceServer(eng)
    assert srv._qos is None   # pylint: disable=protected-access
    port = _free_port()
    _run_app_bg(srv.make_app(), port)
    base = f'http://127.0.0.1:{port}'
    _wait_http(base + '/health', timeout=120)
    try:
        r = requests.post(base + '/generate',
                          json={'tokens': [1, 2], 'max_tokens': 2},
                          headers={'X-Priority': 'nope'}, timeout=30)
        assert r.status_code == 400
        r = requests.post(base + '/generate',
                          json={'tokens': [1, 2], 'max_tokens': 2},
                          headers={'X-Priority': 'batch',
                                   'X-Tenant': 'team-a'}, timeout=60)
        assert r.status_code == 200
        assert 'qos' not in requests.get(base + '/stats',
                                         timeout=10).json()
    finally:
        eng.stop()


@pytest.mark.heavy
def test_lb_503_carries_retry_after(monkeypatch):
    """Satellite: the LB's no-replica 503 advertises Retry-After
    derived from the sync/backoff state."""
    import requests
    from skypilot_tpu.serve import load_balancer as lb_lib
    monkeypatch.setenv('SKYT_SERVE_LB_SYNC_INTERVAL', '3600')
    monkeypatch.setenv('SKYT_LB_NO_REPLICA_TIMEOUT_S', '0.2')
    reg = metrics_lib.MetricsRegistry()
    port = _free_port()
    lb = lb_lib.SkyServeLoadBalancer('http://127.0.0.1:9', port,
                                     metrics_registry=reg)
    _run_app_bg(lb.make_app(), port)
    base = f'http://127.0.0.1:{port}'
    deadline = time.time() + 30
    while time.time() < deadline:
        try:
            requests.get(base + '/metrics', timeout=2)
            break
        except requests.RequestException:
            time.sleep(0.1)
    r = requests.post(base + '/generate', json={'tokens': [1]},
                      timeout=30)
    assert r.status_code == 503
    assert int(r.headers['Retry-After']) >= 1
    del lb


@pytest.mark.heavy
def test_lb_rejects_malformed_priority_and_tracks_demand(monkeypatch):
    """QoS on: the LB 400s malformed X-Priority before proxying and
    records per-class demand for the autoscaler sync."""
    import requests
    from aiohttp import web as aio_web
    from skypilot_tpu.serve import load_balancer as lb_lib
    monkeypatch.setenv('SKYT_SERVE_LB_SYNC_INTERVAL', '3600')
    monkeypatch.setenv('SKYT_QOS', '1')

    async def handler(request):
        del request
        return aio_web.Response(text='ok')

    app = aio_web.Application()
    app.router.add_route('*', '/{p:.*}', handler)
    rport = _free_port()
    _run_app_bg(app, rport)
    replica = f'http://127.0.0.1:{rport}'
    _wait_http(replica + '/x')
    reg = metrics_lib.MetricsRegistry()
    port = _free_port()
    lb = lb_lib.SkyServeLoadBalancer('http://127.0.0.1:9', port,
                                     metrics_registry=reg)
    lb.policy.set_ready_replicas([replica])
    _run_app_bg(lb.make_app(), port)
    base = f'http://127.0.0.1:{port}'
    deadline = time.time() + 30
    while time.time() < deadline:
        try:
            requests.get(base + '/metrics', timeout=2)
            break
        except requests.RequestException:
            time.sleep(0.1)
    r = requests.get(base + '/gen',
                     headers={'X-Priority': 'nope'}, timeout=30)
    assert r.status_code == 400
    r = requests.get(base + '/gen',
                     headers={'X-Priority': 'interactive'}, timeout=30)
    assert r.status_code == 200
    assert ('interactive' in
            {cls for _, cls in lb._qos_demand})  # pylint: disable=protected-access


@pytest.mark.heavy
def test_lb_qos_pressure_steers_picks(monkeypatch):
    """A replica advertising level 2 (sheds batch) is avoided for
    batch-class picks while an unpressured replica exists, but still
    used when it is the only one left."""
    from skypilot_tpu.serve import load_balancer as lb_lib
    monkeypatch.setenv('SKYT_SERVE_LB_SYNC_INTERVAL', '3600')
    monkeypatch.setenv('SKYT_QOS', '1')
    reg = metrics_lib.MetricsRegistry()
    lb = lb_lib.SkyServeLoadBalancer('http://127.0.0.1:9',
                                     _free_port(),
                                     metrics_registry=reg)
    lb.policy.set_ready_replicas(['http://a', 'http://b'])
    lb._replica_qos = {   # pylint: disable=protected-access
        'http://a': {'level': 2, 'pressure': 0.9}}
    avoid = lb._qos_avoid_for('batch')  # pylint: disable=protected-access
    assert avoid == {'http://a'}
    assert lb._qos_avoid_for('interactive') == set()  # pylint: disable=protected-access
    picks = {lb._pick_replica_once(set(), avoid)  # pylint: disable=protected-access
             for _ in range(4)}
    assert picks == {'http://b'}
    # Only the pressured replica left: pressure avoidance is soft.
    lb.policy.set_ready_replicas(['http://a'])
    assert lb._pick_replica_once(set(), {'http://a'}) == 'http://a'  # pylint: disable=protected-access


def test_controller_sync_payload_roundtrip(monkeypatch):
    """The controller sync handler feeds qos_demand/qos_sheds to the
    autoscaler and returns replica_qos from the prober's scrapes."""
    import asyncio
    from skypilot_tpu.serve import autoscalers
    monkeypatch.setenv('SKYT_QOS', '1')

    class FakeRM:
        def ready_urls(self):
            return ['http://r1']

        def ready_qos(self):
            return {'http://r1': {'level': 2, 'pressure': 0.8}}

        def ready_prefix_cache(self):
            return {'http://r1': {'occupancy': 0.25,
                                  'cached_pages': 4}}

        def ready_weight_versions(self):
            return {'http://r1': 3}

        def ready_adapters(self):
            return {'http://r1': {'summarize': 1}}

    class FakeController:
        def registered_lbs(self):
            return {'lb-a': {'url': 'http://lb-a:8080',
                             'last_sync': time.time()}}

    from skypilot_tpu.serve import controller as controller_lib
    from skypilot_tpu.serve import service_spec as spec_lib
    ctl = FakeController()
    ctl.replica_manager = FakeRM()
    spec = spec_lib.ServiceSpec(readiness_path='/health',
                                min_replicas=1, max_replicas=4,
                                target_qps_per_replica=1.0)
    ctl.autoscaler = autoscalers.QoSAwareAutoscaler(
        spec, metrics_registry=metrics_lib.MetricsRegistry())

    class FakeRequest:
        async def json(self):
            now = time.time()
            return {'request_timestamps': [now],
                    'qos_demand': [[now, 'interactive']],
                    'qos_sheds': [[now, 'batch']]}

    resp = asyncio.new_event_loop().run_until_complete(
        controller_lib.SkyServeController._handle_lb_sync(
            ctl, FakeRequest()))
    import json
    data = json.loads(resp.body)
    assert data['ready_replica_urls'] == ['http://r1']
    assert data['replica_qos']['http://r1']['level'] == 2
    # Prefix-cache occupancy rides the same sync (the LB turns it into
    # skyt_lb_replica_prefix_cache{replica} — ROADMAP item 2 groundwork).
    assert data['replica_prefix_cache']['http://r1']['occupancy'] == \
        0.25
    # Serving weight versions + the registered-LB list (peer
    # discovery) ride the same sync (docs/robustness.md
    # "Zero-downtime rollouts").
    assert data['replica_weight_versions'] == {'http://r1': 3}
    assert data['lbs'] == {'lb-a': 'http://lb-a:8080'}
    assert len(ctl.autoscaler._shed_ts) == 1  # pylint: disable=protected-access
