"""Metrics plane tests (utils/metrics.py + its wiring).

Covers the registry itself (label cardinality, histogram bucket math,
golden exposition output, concurrent increments) and the serving
integration: /metrics scrapes cleanly while a completion streams, the
response carries an X-Request-Id whose phase trace /stats returns.
"""
import math
import threading

import pytest

from skypilot_tpu.utils import metrics as metrics_lib


# ------------------------------------------------------------- registry
def test_counter_gauge_basics():
    reg = metrics_lib.MetricsRegistry()
    c = reg.counter('c_total', 'a counter')
    c.inc()
    c.inc(2.5)
    assert c.value() == 3.5
    with pytest.raises(ValueError):
        c.inc(-1)          # counters only go up
    g = reg.gauge('g', 'a gauge')
    g.set(5)
    g.inc()
    g.dec(3)
    assert g.value() == 3.0


def test_label_cardinality_and_validation():
    reg = metrics_lib.MetricsRegistry()
    c = reg.counter('req_total', 'requests', ('method', 'code'))
    c.labels('GET', '200').inc()
    c.labels('GET', '200').inc()        # same child
    c.labels('POST', '200').inc()       # new child
    c.labels(method='GET', code='500').inc()
    assert c.value('GET', '200') == 2
    assert c.value(method='POST', code='200') == 1
    assert len(c._children) == 3
    with pytest.raises(ValueError):
        c.labels('GET')                  # wrong arity
    with pytest.raises(ValueError):
        c.labels(method='GET', verb='x')  # wrong label names
    with pytest.raises(ValueError):
        c.inc()                          # labeled metric needs labels()
    with pytest.raises(ValueError):
        reg.counter('bad name', 'x')     # invalid metric name
    with pytest.raises(ValueError):
        reg.counter('ok', 'x', ('0bad',))  # invalid label name
    # Same name, different shape -> loud collision, not silent reuse.
    with pytest.raises(ValueError):
        reg.gauge('req_total', 'oops')
    with pytest.raises(ValueError):
        reg.counter('req_total', 'oops', ('method',))
    # Same name, same shape -> get-or-create returns the same object.
    assert reg.counter('req_total', 'requests',
                       ('method', 'code')) is c
    # value() is read-only: an unseen combination reads 0 WITHOUT
    # creating a phantom zero series in the exposition.
    assert c.value('GET', '418') == 0.0
    assert 'code="418"' not in reg.expose()
    with pytest.raises(ValueError):
        c.value('GET')                   # wrong arity still raises


def test_label_eviction():
    """remove_labels drops a churned series from the exposition (the
    LB prunes dead-replica children this way); re-use restarts at 0."""
    reg = metrics_lib.MetricsRegistry()
    c = reg.counter('lb_total', 'x', ('replica',))
    c.labels('http://a:1').inc(5)
    c.labels('http://b:2').inc(3)
    assert sorted(c.label_keys()) == [('http://a:1',), ('http://b:2',)]
    c.remove_labels('http://a:1')
    c.remove_labels('http://gone:9')      # absent -> no-op
    assert c.label_keys() == [('http://b:2',)]
    assert 'http://a:1' not in reg.expose()
    c.labels('http://a:1').inc()          # churned back: fresh series
    assert c.value('http://a:1') == 1


def test_lb_prunes_dead_replica_series():
    from skypilot_tpu.serve import load_balancer as lb_lib
    reg = metrics_lib.MetricsRegistry()
    lb = lb_lib.SkyServeLoadBalancer('http://c', 0,
                                     metrics_registry=reg)
    me = lb.lb_id
    lb._m_requests.labels(me, 'http://r1').inc(4)
    lb._m_errors.labels(me, 'none').inc()
    lb._m_inflight.labels(me, 'http://r1').inc()   # still draining
    lb._m_inflight.labels(me, 'http://r2').inc()
    lb._m_inflight.labels(me, 'http://r2').dec()   # idle
    # Another tier member's series in the SAME registry must survive
    # this LB's prune untouched (the N-active `lb` label contract).
    lb._m_requests.labels('lb-other', 'http://r9').inc()
    lb._prune_replica_metrics(['http://r3'])
    assert lb._m_requests.label_keys() == [('lb-other', 'http://r9')]
    assert lb._m_errors.label_keys() == [(me, 'none')]   # kept
    # Nonzero inflight survives (the drain must dec its own child).
    assert lb._m_inflight.label_keys() == [(me, 'http://r1')]


def test_histogram_bucket_collision():
    reg = metrics_lib.MetricsRegistry()
    h = reg.histogram('lat_seconds', 'x', buckets=(0.1, 1.0))
    # Same buckets (+Inf normalization included) -> same object.
    assert reg.histogram('lat_seconds', 'x', buckets=(0.1, 1.0)) is h
    # Different buckets -> loud collision, not silent mis-bucketing.
    with pytest.raises(ValueError):
        reg.histogram('lat_seconds', 'x', buckets=(10.0, 60.0))


def test_histogram_bucket_math():
    reg = metrics_lib.MetricsRegistry()
    h = reg.histogram('lat_seconds', 'latency', buckets=(0.1, 1.0, 10.0))
    for v in (0.05, 0.1, 0.5, 2.0, 100.0):
        h.observe(v)
    child = h.labels()
    # +Inf is appended automatically.
    assert h.buckets == (0.1, 1.0, 10.0, math.inf)
    # Cumulative counts: <=0.1 -> 2 (0.05 and the boundary 0.1),
    # <=1.0 -> 3, <=10 -> 4, +Inf -> 5.
    assert child.cumulative() == [2, 3, 4, 5]
    assert child.count == 5
    assert child.sum == pytest.approx(102.65)


def test_exposition_golden():
    """Exact text exposition 0.0.4 output — the format other tooling
    (Prometheus, the TPU validation scrape) parses."""
    reg = metrics_lib.MetricsRegistry()
    c = reg.counter('skyt_req_total', 'Requests served', ('code',))
    c.labels('200').inc(3)
    c.labels('500').inc()
    g = reg.gauge('skyt_util', 'Utilization (0-1)')
    g.set(0.25)
    h = reg.histogram('skyt_lat_seconds', 'Latency', buckets=(0.5, 2.0))
    h.observe(0.1)
    h.observe(1.0)
    assert reg.expose() == (
        '# HELP skyt_req_total Requests served\n'
        '# TYPE skyt_req_total counter\n'
        'skyt_req_total{code="200"} 3\n'
        'skyt_req_total{code="500"} 1\n'
        '# HELP skyt_util Utilization (0-1)\n'
        '# TYPE skyt_util gauge\n'
        'skyt_util 0.25\n'
        '# HELP skyt_lat_seconds Latency\n'
        '# TYPE skyt_lat_seconds histogram\n'
        'skyt_lat_seconds_bucket{le="0.5"} 1\n'
        'skyt_lat_seconds_bucket{le="2"} 2\n'
        'skyt_lat_seconds_bucket{le="+Inf"} 2\n'
        'skyt_lat_seconds_sum 1.1\n'
        'skyt_lat_seconds_count 2\n')


def test_exposition_escaping():
    reg = metrics_lib.MetricsRegistry()
    c = reg.counter('esc_total', 'help with \\ and\nnewline', ('p',))
    c.labels('a"b\\c\nd').inc()
    text = reg.expose()
    assert '# HELP esc_total help with \\\\ and\\nnewline\n' in text
    assert 'esc_total{p="a\\"b\\\\c\\nd"} 1\n' in text


def test_concurrent_increments():
    """No lost updates under thread contention (the engine loop, HTTP
    handlers, and the control loop all write concurrently)."""
    reg = metrics_lib.MetricsRegistry()
    c = reg.counter('conc_total', 'x', ('t',))
    h = reg.histogram('conc_seconds', 'x', buckets=(0.5,))
    n_threads, n_iter = 8, 2000

    def work(i):
        for _ in range(n_iter):
            c.labels(str(i % 2)).inc()
            h.observe(0.1)

    threads = [threading.Thread(target=work, args=(i,))
               for i in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert c.value('0') + c.value('1') == n_threads * n_iter
    assert h.labels().count == n_threads * n_iter
    assert h.labels().cumulative()[-1] == n_threads * n_iter


def test_snapshot_shape():
    reg = metrics_lib.MetricsRegistry()
    reg.counter('a_total', 'a', ('x',)).labels('1').inc()
    reg.histogram('b_seconds', 'b', buckets=(1.0,)).observe(0.5)
    snap = reg.snapshot()
    assert [m['name'] for m in snap] == ['a_total', 'b_seconds']
    assert snap[0]['samples'][0] == {'labels': {'x': '1'}, 'value': 1.0}
    assert snap[1]['samples'][0]['count'] == 1
    assert snap[1]['samples'][0]['buckets']['+Inf'] == 1


def test_autoscaler_decision_counter():
    from skypilot_tpu.serve import autoscalers
    from skypilot_tpu.serve import service_spec as spec_lib
    reg = metrics_lib.MetricsRegistry()
    spec = spec_lib.ServiceSpec(readiness_path='/health',
                                min_replicas=1, max_replicas=2,
                                target_qps_per_replica=1.0,
                                upscale_delay_seconds=0,
                                downscale_delay_seconds=0)
    a = autoscalers.RequestRateAutoscaler(spec, metrics_registry=reg)
    a.evaluate_scaling(1)                       # steady at min
    import time
    a.collect_request_timestamps([time.time()] * 600)  # 10 qps
    a.evaluate_scaling(1)                       # upscale to max
    dec = reg.get('skyt_autoscaler_decisions_total')
    assert dec.value('steady') == 1
    assert dec.value('upscale') == 1
    assert reg.get('skyt_autoscaler_target_replicas').value() == 2
