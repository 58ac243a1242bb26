"""Gang watchdog: turn per-rank heartbeats into hang/straggler/desync
verdicts, plus the rank-local sentinel that dumps postmortem bundles.

Two consumers share the threshold math here:

* ``GangWatchdog`` — head-agent side (runtime/server.py): aggregates
  every rank's relayed heartbeat, and classifies the gang each tick:

    hang       a rank reported no step progress within
               ``SKYT_WATCHDOG_FACTOR`` × its rolling step-time EWMA
               (floor ``SKYT_WATCHDOG_MIN_S``)
    desync     step skew across ranks beyond the pipeline depth
               (``SKYT_WATCHDOG_PIPELINE_DEPTH``) — ranks are running
               but no longer the same program step
    straggler  one rank's step-time EWMA exceeds
               ``SKYT_WATCHDOG_STRAGGLER_K`` × the gang median
    init/ok    not stepping yet / healthy

  A hang is *confirmed* after ``SKYT_WATCHDOG_CONFIRM`` consecutive
  hang evaluations; the head then escalates the job to the terminal
  ``HUNG`` status, which the managed-jobs controller recovers exactly
  like a preemption (kill gang → checkpoint-resume relaunch,
  docs/robustness.md).

* ``RankSentinel`` — inside each training process: a daemon thread
  watching its own rank's heartbeat with the same budget. When the
  main thread wedges in a device call (the hang case — Python signal
  handlers can never run there), the sentinel is what still executes:
  it dumps the rank's postmortem bundle (train/postmortem.py) locally,
  so "bundles from every rank" needs no cross-host signalling.

Verdicts land in ``skyt_train_gang_state{state}`` gauges,
``skyt_train_watchdog_verdicts_total{verdict}`` counters, and
forced-sampled ``watchdog.<state>`` spans on every transition.

Clock discipline: all time flows through injectable clocks
(tools/lint.py enforces no direct wall-clock calls in this file).
"""
import dataclasses
import threading
import time
from typing import Any, Callable, Dict, Optional

from skypilot_tpu.utils import log_utils
from skypilot_tpu.utils import metrics as metrics_lib
from skypilot_tpu.utils import env

logger = log_utils.init_logger(__name__)

STATES = ('init', 'ok', 'straggler', 'desync', 'hang')


def factor() -> float:
    """Stall budget multiplier over the rank's rolling step time."""
    return env.get_float('SKYT_WATCHDOG_FACTOR', 10.0)


def min_stall_s() -> float:
    """Stall budget floor: below this, silence is never a hang (log
    boundaries, checkpoint writes, and GC all pause heartbeats)."""
    return env.get_float('SKYT_WATCHDOG_MIN_S', 60.0)


def straggler_k() -> float:
    return env.get_float('SKYT_WATCHDOG_STRAGGLER_K', 3.0)


def pipeline_depth() -> int:
    """Step skew tolerated before 'desync': pipeline stages (and the
    prefetch depth) legitimately put ranks a few steps apart."""
    return int(env.get_float('SKYT_WATCHDOG_PIPELINE_DEPTH', 2))


def confirm_evals() -> int:
    """Consecutive hang evaluations before the verdict escalates."""
    return max(1, int(env.get_float('SKYT_WATCHDOG_CONFIRM', 2)))


def stall_budget(ewma_step_s: Optional[float]) -> float:
    """Seconds of heartbeat silence tolerated for a stepping rank."""
    ewma = ewma_step_s or 0.0
    return max(factor() * ewma, min_stall_s())


def classify_stall(record: Optional[Dict[str, Any]], now: float
                   ) -> Dict[str, Any]:
    """One-rank stall check: {stalled, stalled_for_s, budget_s,
    phase}."""
    if not record or record.get('phase') != 'step':
        return {'stalled': False, 'stalled_for_s': 0.0,
                'budget_s': stall_budget(None),
                'phase': (record or {}).get('phase', 'unknown')}
    age = max(now - float(record.get('ts') or 0.0), 0.0)
    budget = stall_budget(record.get('ewma_step_s'))
    return {'stalled': age > budget, 'stalled_for_s': round(age, 3),
            'budget_s': round(budget, 3), 'phase': 'step'}


@dataclasses.dataclass
class Verdict:
    state: str                       # one of STATES
    detail: Dict[str, Any]
    confirmed: bool = False          # hang only: streak >= confirm

    def to_wire(self) -> Dict[str, Any]:
        return {'state': self.state, 'confirmed': self.confirmed,
                **self.detail}


class GangWatchdog:
    """Aggregate per-rank heartbeats and classify the gang.

    ``observe(rank, record)`` ingests a heartbeat; ``evaluate()``
    returns the current ``Verdict`` and maintains the metrics/spans.
    Precedence: hang > desync > straggler > ok (a hung rank usually
    drags the survivors into apparent desync — report the cause)."""

    def __init__(self, num_ranks: int, *,
                 clock: Callable[[], float] = time.time,
                 registry: Optional[
                     'metrics_lib.MetricsRegistry'] = None,
                 tracer=None, job: str = '') -> None:
        self.num_ranks = int(num_ranks)
        self._clock = clock
        self._tracer = tracer
        self._lock = threading.Lock()
        self._records: Dict[int, Dict[str, Any]] = {}
        self._state = 'init'
        self._state_since = clock()
        self._hang_streak = 0
        # `job` labels this evaluator's series: the head runs one
        # GangWatchdog per active job on the shared registry, and
        # unlabeled gauges would let concurrent jobs overwrite each
        # other's verdict every tick.
        self.job = str(job)
        reg = registry or metrics_lib.REGISTRY
        self._m_state = reg.gauge(
            'skyt_train_gang_state',
            'Gang watchdog verdict (1 on the current state\'s series, '
            '0 elsewhere)', ('job', 'state'))
        self._m_verdicts = reg.counter(
            'skyt_train_watchdog_verdicts_total',
            'Watchdog state transitions into each non-ok verdict',
            ('job', 'verdict'))

    # ----------------------------------------------------------- ingest
    def observe(self, rank: int, record: Dict[str, Any]) -> None:
        if not isinstance(record, dict):
            return
        with self._lock:
            self._records[int(rank)] = dict(record)

    def records(self) -> Dict[int, Dict[str, Any]]:
        with self._lock:
            return {r: dict(rec) for r, rec in self._records.items()}

    # --------------------------------------------------------- evaluate
    def evaluate(self, now: Optional[float] = None) -> Verdict:
        if now is None:
            now = self._clock()
        with self._lock:
            records = {r: dict(rec) for r, rec in self._records.items()}
        stepping = {r: rec for r, rec in records.items()
                    if rec.get('phase') == 'step'}
        detail: Dict[str, Any] = {
            'ranks_reporting': len(records),
            'ranks_stepping': len(stepping),
            'num_ranks': self.num_ranks,
        }
        state = 'ok'
        if not stepping:
            state = 'init'
        else:
            stalled = {}
            for r, rec in stepping.items():
                c = classify_stall(rec, now)
                if c['stalled']:
                    stalled[r] = {'stalled_for_s': c['stalled_for_s'],
                                  'budget_s': c['budget_s'],
                                  'step': rec.get('step')}
            steps = [int(rec.get('step') or 0)
                     for rec in stepping.values()]
            skew = max(steps) - min(steps) if steps else 0
            detail['step_skew'] = skew
            if stalled:
                state = 'hang'
                detail['stalled_ranks'] = stalled
            elif len(stepping) >= 2 and skew > pipeline_depth():
                state = 'desync'
                detail['pipeline_depth'] = pipeline_depth()
            elif len(stepping) >= 2:
                ewmas = {r: float(rec.get('ewma_step_s') or 0.0)
                         for r, rec in stepping.items()}
                vals = sorted(ewmas.values())
                mid = len(vals) // 2
                median = (vals[mid] if len(vals) % 2 else
                          (vals[mid - 1] + vals[mid]) / 2.0)
                if median > 0:
                    slow = {r: round(e, 4) for r, e in ewmas.items()
                            if e > straggler_k() * median}
                    if slow:
                        state = 'straggler'
                        detail['straggler_ranks'] = slow
                        detail['gang_median_step_s'] = round(median, 4)
        # Confirmation streak: recovery escalation needs consecutive
        # hang verdicts, not one missed relay.
        self._hang_streak = self._hang_streak + 1 if state == 'hang' \
            else 0
        confirmed = state == 'hang' and \
            self._hang_streak >= confirm_evals()
        detail['hang_streak'] = self._hang_streak
        self._publish(state, detail, now)
        return Verdict(state=state, detail=detail, confirmed=confirmed)

    def retire(self) -> None:
        """Drop this evaluator's gauge series (the job is terminal; a
        long-lived head agent must not accumulate dead-job children)."""
        for s in STATES:
            self._m_state.remove_labels(self.job, s)

    # ---------------------------------------------------------- metrics
    def _publish(self, state: str, detail: Dict[str, Any],
                 now: float) -> None:
        for s in STATES:
            self._m_state.labels(self.job, s).set(
                1.0 if s == state else 0.0)
        if state == self._state:
            return
        prev, since = self._state, self._state_since
        self._state = state
        self._state_since = now
        if state not in ('ok', 'init'):
            self._m_verdicts.labels(self.job, state).inc()
            logger.warning('gang watchdog: %s -> %s (%s)', prev, state,
                           detail)
        # Forced-sampled span over the time spent in the PREVIOUS
        # state: hang verdicts are rare and each one is the span an
        # operator wants retained, never head-sampled away.
        from skypilot_tpu.utils import tracing
        if tracing.enabled():
            (self._tracer or tracing.TRACER).record_span(
                f'watchdog.{state}', since, now, sampled=True,
                attributes={'prev_state': prev, 'job': self.job,
                            **{k: str(v) for k, v in detail.items()}})


class RankSentinel:
    """Rank-local stall watcher: a daemon thread that applies the same
    stall budget to its OWN heartbeat and calls ``on_stall(snapshot)``
    once when it trips.

    This is the piece that still runs when the main thread is wedged
    inside a device call — the exact situation signal handlers cannot
    handle — so the postmortem bundle gets written by the rank itself,
    before the head's kill directive arrives."""

    def __init__(self, writer, on_stall: Callable[[Dict[str, Any]], Any],
                 *, clock: Callable[[], float] = time.time,
                 poll_s: Optional[float] = None) -> None:
        self._writer = writer
        self._on_stall = on_stall
        self._clock = clock
        self._poll = env.get_float('SKYT_WATCHDOG_POLL_S', 1.0) \
            if poll_s is None else float(poll_s)
        self._stop = threading.Event()
        self.fired = threading.Event()
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name='watchdog-sentinel')

    def start(self) -> 'RankSentinel':
        self._thread.start()
        return self

    def stop(self) -> None:
        self._stop.set()

    def _run(self) -> None:
        while not self._stop.wait(self._poll):
            snap = self._writer.snapshot()
            # Measure from the writer's live progress stamp, not the
            # (interval-throttled) file record.
            snap['ts'] = self._writer.last_progress()
            verdict = classify_stall(snap, self._clock())
            if not verdict['stalled']:
                continue
            self.fired.set()
            try:
                self._on_stall({**snap, 'stall': verdict})
            except Exception:  # pylint: disable=broad-except
                logger.exception('sentinel on_stall hook failed')
            return   # one bundle per stall episode
