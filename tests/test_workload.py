"""Capacity-plane unit tests (docs/observability.md "Capacity
plane"): the workload engine's determinism contract (same seed =>
byte-identical schedule), arrival-process shape, session-reuse
mechanics, capacity-search convergence on a closed-form attainment
model, and the busy-ledger's sums-to-busy-time invariant.

The end-to-end half (real replica + real LB tier) lives in
tests/test_chaos_*.py's flash-crowd drill.
"""
import math

import pytest

from skypilot_tpu.benchmark import capacity
from skypilot_tpu.benchmark import workload
from skypilot_tpu.infer import ledger as ledger_lib
from skypilot_tpu.utils import faults
from skypilot_tpu.utils import metrics as metrics_lib

MIX = (
    workload.TenantProfile(tenant='acme', cls='interactive',
                           weight=2.0, session_pool=4,
                           session_reuse=0.6),
    workload.TenantProfile(tenant='burst', cls='batch',
                           model='adapter-a', weight=1.0,
                           prompt_mean=128.0, output_mean=64.0),
)


def _spec(**kw):
    base = dict(seed=7, duration_s=20.0, rate_rps=5.0,
                arrival='poisson', tenants=MIX)
    base.update(kw)
    return workload.WorkloadSpec(**base)


# ------------------------------------------------------- determinism
def test_same_seed_byte_identical_schedule():
    a = workload.generate_schedule(_spec())
    b = workload.generate_schedule(_spec())
    assert workload.schedule_json(a) == workload.schedule_json(b)
    assert workload.schedule_digest(a) == workload.schedule_digest(b)
    assert len(a) > 10


def test_different_seed_different_schedule():
    a = workload.generate_schedule(_spec(seed=7))
    b = workload.generate_schedule(_spec(seed=8))
    assert workload.schedule_digest(a) != workload.schedule_digest(b)


def test_schedule_is_compression_independent():
    # Compression scales when arrivals FIRE, never the schedule: the
    # spec has no compression knob at all, so the digest cannot
    # depend on it. Pin that the digest keys on (seed, process, mix).
    d1 = workload.schedule_digest(workload.generate_schedule(_spec()))
    d2 = workload.schedule_digest(
        workload.generate_schedule(_spec(rate_rps=6.0)))
    assert d1 != d2


# -------------------------------------------------- arrival processes
def test_steady_arrivals_evenly_spaced():
    sched = workload.generate_schedule(
        _spec(arrival='steady', rate_rps=10.0, duration_s=2.0))
    assert len(sched) == 20
    gaps = [b.t - a.t for a, b in zip(sched, sched[1:])]
    assert all(abs(g - 0.1) < 1e-9 for g in gaps)


def test_poisson_count_tracks_rate():
    spec = _spec(duration_s=200.0, rate_rps=10.0)
    n = len(workload.generate_schedule(spec))
    # mean 2000, sd ~45 — +/-5 sd keeps this deterministic-seed test
    # robust to spec tweaks without being vacuous.
    assert 1775 < n < 2225


def test_flash_crowd_multiplies_arrivals_in_window():
    spec = _spec(duration_s=60.0, rate_rps=5.0, flash_at_s=20.0,
                 flash_factor=10.0, flash_duration_s=10.0)
    sched = workload.generate_schedule(spec)
    inside = sum(1 for a in sched if 20.0 <= a.t < 30.0)
    before = sum(1 for a in sched if a.t < 20.0)
    # 10s at 50 rps vs 20s at 5 rps: ~500 vs ~100.
    assert inside > 3 * before
    assert spec.rate_at(25.0) == pytest.approx(50.0)
    assert spec.rate_at(35.0) == pytest.approx(5.0)


def test_diurnal_modulation_shapes_rate():
    spec = _spec(diurnal_amplitude=0.5, diurnal_period_s=100.0)
    assert spec.rate_at(25.0) == pytest.approx(7.5)   # sin peak
    assert spec.rate_at(75.0) == pytest.approx(2.5)   # sin trough
    assert spec.peak_rate() == pytest.approx(7.5)


def test_unknown_arrival_process_rejected():
    with pytest.raises(ValueError, match='unknown arrival'):
        workload.generate_schedule(_spec(arrival='bursty'))


# ------------------------------------------------------ session reuse
def test_session_reuse_shares_prefix_and_bounds_pool():
    spec = _spec(duration_s=100.0, tenants=(
        workload.TenantProfile(tenant='acme', session_pool=3,
                               session_reuse=0.7, prefix_len=8),))
    sched = workload.generate_schedule(spec)
    # Pool is bounded: session NAMES cycle through at most 3 slots.
    assert len({a.session for a in sched}) <= 3
    # A reused session resends its prefix verbatim (that's what LB
    # affinity and the prefix cache key on): a solid fraction of
    # arrivals repeat an already-seen (session, prefix) pair.
    seen, reused = set(), 0
    for a in sched:
        pair = (a.session, a.prompt_tokens[:8])
        if pair in seen:
            reused += 1
        seen.add(pair)
    assert reused > 0.3 * len(sched)


def test_lengths_respect_caps():
    spec = _spec(duration_s=100.0, tenants=(
        workload.TenantProfile(tenant='t', prompt_mean=600.0,
                               prompt_sigma=1.5, prompt_cap=64,
                               output_mean=400.0, output_cap=16),))
    for a in workload.generate_schedule(spec):
        assert 1 <= len(a.prompt_tokens) <= 64
        assert 1 <= a.max_new_tokens <= 16


# ---------------------------------------------------- open-loop runner
def test_open_loop_runner_fires_all_and_respects_faults():
    sched = workload.generate_schedule(
        _spec(duration_s=4.0, rate_rps=10.0))
    seen = []

    def submit(a):
        seen.append(a.index)
        return (200, 0.01, 0.02, a.max_new_tokens)

    faults.configure('traffic.arrival=error,where=tenant:burst')
    try:
        runner = workload.OpenLoopRunner(submit, compression=40.0)
        outcomes = runner.run(sched)
    finally:
        faults.reset()
    assert len(outcomes) == len(sched)
    dropped = [o for o in outcomes if o.error
               and o.error.startswith('fault:')]
    assert dropped and all(
        o.arrival.tenant == 'burst' for o in dropped)
    ok = [o for o in outcomes if o.status == 200]
    assert len(ok) + len(dropped) == len(sched)
    assert sorted(seen) == sorted(o.arrival.index for o in ok)
    summary = workload.summarize(outcomes, compression=40.0)
    assert summary['offered'] == len(sched)
    assert summary['ok'] == len(ok)
    assert summary['classes']['batch']['transport_errors'] == \
        len(dropped)


# -------------------------------------------------- capacity search
def test_capacity_search_converges_on_closed_form():
    # Transient M/M/1-flavored attainment: with service rate mu and
    # window T, P(a request is good) ~ 1 - exp(-(mu - r) * T) for
    # r < mu. Solving attainment(r*) = target gives
    # r* = mu - ln(1/(1-target)) / T — a closed form the search must
    # land on without knowing it.
    mu, t_win, target = 100.0, 1.0, 0.99
    r_star = mu - math.log(1.0 / (1.0 - target)) / t_win

    def measure(rate):
        return max(0.0, 1.0 - math.exp(-(mu - rate) * t_win)) \
            if rate < mu else 0.0

    res = capacity.capacity_search(
        measure, target=target, rate_lo=1.0, rate_hi=4096.0,
        resolution=0.02)
    assert res.max_sustained_qps <= r_star + 1e-9
    assert res.bracket_hi is not None and res.bracket_hi > r_star
    # Bisection stops at 2% relative bracket width.
    assert (r_star - res.max_sustained_qps) <= \
        0.025 * res.max_sustained_qps
    assert res.slo_attainment >= target
    assert len(res.trials) <= 20
    assert res.as_dict()['target'] == target


def test_capacity_search_zero_when_floor_fails():
    res = capacity.capacity_search(
        lambda rate: 0.5, target=0.99, rate_lo=1.0)
    assert res.max_sustained_qps == 0.0
    assert res.bracket_hi == 1.0
    assert res.trials[0].passed is False


def test_capacity_search_validates_inputs():
    with pytest.raises(ValueError, match='target'):
        capacity.capacity_search(lambda r: 1.0, target=1.5)
    with pytest.raises(ValueError, match='rate range'):
        capacity.capacity_search(lambda r: 1.0, rate_lo=8.0,
                                 rate_hi=2.0)


# ------------------------------------------------------- busy ledger
def test_ledger_attribution_sums_to_busy_time():
    led = ledger_lib.BusyLedger(metrics_lib.MetricsRegistry(),
                                enabled=True)
    k1 = ('interactive', 'acme', 'base')
    k2 = ('batch', 'burst', 'adapter-a')
    # Interval 1: 3:1 token split.
    led.note(k1, 30)
    led.note(k2, 10)
    led.settle(0.4)
    # Interval 2: only k2 works.
    led.note(k2, 5)
    led.settle(0.1)
    # Interval 3: busy but nothing attributable (all-cancelled chunk):
    # stays in the busy total, attributes to nobody.
    led.settle(0.25)
    snap = led.snapshot()
    assert snap['busy_seconds'] == pytest.approx(0.75)
    attr = snap['attributed_seconds']
    assert attr['interactive/acme/base'] == pytest.approx(0.3)
    assert attr['batch/burst/adapter-a'] == pytest.approx(0.2)
    # Sums-to-busy-time invariant, minus the honest unattributed gap.
    assert sum(attr.values()) == pytest.approx(0.5, abs=1e-6)
    assert snap['tokens'] == {'batch/burst/adapter-a': 15,
                              'interactive/acme/base': 30}


def test_ledger_disabled_is_inert():
    led = ledger_lib.BusyLedger(metrics_lib.MetricsRegistry(),
                                enabled=False)
    led.note(('a', 'b', 'c'), 10)
    led.settle(1.0)
    assert led.pending() is False
    assert led.snapshot()['busy_seconds'] == 0.0
