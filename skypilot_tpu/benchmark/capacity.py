"""Capacity search: max sustained QPS at SLO attainment >= target.

The capacity plane's headline number (docs/observability.md "Capacity
plane"). The search drives the real serving path (LB tier included)
with the open-loop workload engine at increasing arrival rates and
finds the largest rate whose SLO attainment still meets the target:

  1. **Geometric ramp** from ``rate_lo``, doubling while the measured
     attainment holds (each trial is a fresh open-loop run at that
     rate — open-loop, so an over-capacity trial actually shows its
     overload instead of self-throttling);
  2. **Bisection** between the last passing and first failing rate
     until the bracket is within ``resolution`` (relative).

Attainment is monotone non-increasing in offered rate for a
work-conserving server, which is what makes bisection sound; real
measurements are noisy near the knee, so the artifact reports the
bracket, not just the point estimate.

``measure`` is any callable ``rate_rps -> attainment`` (fraction in
[0, 1]). Production use wraps a workload run + the fleet SLO report;
the convergence test wraps the closed-form M/M/1 attainment model.
"""
import dataclasses
from typing import Any, Callable, Dict, List, Optional

from skypilot_tpu.utils import env


def default_target() -> float:
    """The search's attainment target: SKYT_CAPACITY_TARGET, falling
    back to the serve plane's global SKYT_SLO_TARGET."""
    t = env.get_float('SKYT_CAPACITY_TARGET', 0.0)
    return t if t > 0 else env.get_float('SKYT_SLO_TARGET', 0.99)


@dataclasses.dataclass(frozen=True)
class Trial:
    rate_rps: float
    attainment: float
    passed: bool


@dataclasses.dataclass(frozen=True)
class CapacityResult:
    """Structured capacity artifact."""
    max_sustained_qps: float      # highest PASSING rate observed
    slo_attainment: float         # attainment measured at that rate
    target: float
    bracket_lo: float             # highest passing rate
    bracket_hi: Optional[float]   # lowest failing rate (None: never
    #                               failed inside the search range)
    trials: List[Trial]

    def as_dict(self) -> Dict[str, Any]:
        d = dataclasses.asdict(self)
        d['trials'] = [dataclasses.asdict(t) if not isinstance(t, dict)
                       else t for t in self.trials]
        return d


def capacity_search(measure: Callable[[float], float], *,
                    target: Optional[float] = None,
                    rate_lo: float = 1.0,
                    rate_hi: float = 4096.0,
                    resolution: float = 0.05,
                    max_trials: int = 20) -> CapacityResult:
    """Find max sustained QPS with attainment >= ``target``.

    ``rate_lo`` must be a sane starting offer (the search fails
    meaningfully — result rate 0.0 — if even rate_lo misses the
    target). ``resolution`` is the relative bracket width at which
    bisection stops; ``max_trials`` bounds total measurements so a
    wedged server cannot spin the search forever.
    """
    if target is None:
        target = default_target()
    if not 0.0 < target <= 1.0:
        raise ValueError(f'target must be in (0, 1], got {target}')
    if rate_lo <= 0 or rate_hi < rate_lo:
        raise ValueError(
            f'bad rate range [{rate_lo}, {rate_hi}]')
    trials: List[Trial] = []

    def probe(rate: float) -> Trial:
        att = float(measure(rate))
        t = Trial(rate_rps=rate, attainment=att,
                  passed=att >= target)
        trials.append(t)
        return t

    # Geometric ramp.
    best: Optional[Trial] = None
    first_fail: Optional[Trial] = None
    rate = rate_lo
    while len(trials) < max_trials:
        t = probe(rate)
        if t.passed:
            best = t
            if rate >= rate_hi:
                break
            rate = min(rate * 2.0, rate_hi)
        else:
            first_fail = t
            break
    if best is None:
        return CapacityResult(
            max_sustained_qps=0.0,
            slo_attainment=trials[0].attainment if trials else 0.0,
            target=target, bracket_lo=0.0,
            bracket_hi=trials[0].rate_rps if trials else rate_lo,
            trials=trials)
    # Bisection inside (best, first_fail).
    while first_fail is not None and len(trials) < max_trials and \
            (first_fail.rate_rps - best.rate_rps) > \
            resolution * best.rate_rps:
        mid = 0.5 * (best.rate_rps + first_fail.rate_rps)
        t = probe(mid)
        if t.passed:
            best = t
        else:
            first_fail = t
    return CapacityResult(
        max_sustained_qps=best.rate_rps,
        slo_attainment=best.attainment,
        target=target,
        bracket_lo=best.rate_rps,
        bracket_hi=first_fail.rate_rps if first_fail else None,
        trials=trials)
