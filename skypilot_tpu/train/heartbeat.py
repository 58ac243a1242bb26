"""Per-rank training heartbeats: the training plane's liveness signal.

SPMD gangs fail by *hanging* — one stalled rank blocks every collective
and the job looks RUNNING forever. The fix starts with a cheap,
always-on progress record: every rank writes, at most once per
``SKYT_HEARTBEAT_INTERVAL_S``, a small JSON heartbeat (step, rolling
step-time EWMA, tokens/s, host timestamp, phase) to a local file the
per-host agent relays to the head, where the gang watchdog
(train/watchdog.py) turns absence-of-progress into a verdict.

The write is atomic (tmp + rename) so a reader never sees a torn
record, and the whole module is dormant when ``SKYT_WATCHDOG=0`` —
sft's hot path then contains no heartbeat call at all
(docs/observability.md "Training plane").

Clock discipline: every timestamp comes through the injectable
``clock`` so the watchdog truth table replays deterministically in
tests (tools/lint.py enforces no direct wall-clock calls here).
"""
import json
import os
import threading
import time
from typing import Any, Callable, Dict, Optional

from skypilot_tpu.utils import env

ENV_FILE = 'SKYT_HEARTBEAT_FILE'
ENV_ENABLED = 'SKYT_WATCHDOG'
ENV_INTERVAL = 'SKYT_HEARTBEAT_INTERVAL_S'

# Lifecycle phases a rank reports. The watchdog only applies its stall
# budget to 'step' — 'init'/'compile' can legitimately sit for minutes
# (weight streaming, first jit compile).
PHASES = ('init', 'compile', 'step', 'done')


def enabled() -> bool:
    """Master switch for the whole training-observability plane
    (heartbeats, rank sentinel, gang watchdog). Default ON; with
    SKYT_WATCHDOG=0 sft never constructs a writer and the step loop is
    byte-identical to before this plane existed."""
    return env.get_bool(ENV_ENABLED, True)


def _interval_s() -> float:
    return env.get_float(ENV_INTERVAL, 1.0)


def read(path: str) -> Optional[Dict[str, Any]]:
    """Best-effort heartbeat read: None for a missing, torn, or
    foreign-shaped file (the relay and watchdog must never crash on a
    half-provisioned rank)."""
    try:
        with open(path, 'r', encoding='utf-8') as f:
            rec = json.load(f)
    except (OSError, ValueError):
        return None
    return rec if isinstance(rec, dict) else None


class HeartbeatWriter:
    """One rank's heartbeat: in-memory progress state updated every
    step (cheap — a few float ops under a lock), flushed to ``path``
    at most once per interval.

    ``path=None`` keeps the in-memory side live without file
    IO (bench and single-process runs outside a gang).
    """

    def __init__(self, path: Optional[str], rank: int, *,
                 clock: Callable[[], float] = time.time,
                 interval_s: Optional[float] = None,
                 ewma_alpha: float = 0.2,
                 device_kind: Optional[str] = None) -> None:
        self.path = path
        self.rank = int(rank)
        self._clock = clock
        self._interval = _interval_s() if interval_s is None \
            else float(interval_s)
        self._alpha = float(ewma_alpha)
        self._lock = threading.Lock()
        self._phase = 'init'
        self._step = -1
        self._ewma: Optional[float] = None
        self._tokens_per_sec = 0.0
        self._last_step_t: Optional[float] = None
        # Last PROGRESS timestamp (step completion or phase change) —
        # what the stall budget measures against.
        self._progress_t = clock()
        self._last_write = float('-inf')
        self._device_kind = device_kind

    # ------------------------------------------------------------ updates
    def mark_phase(self, phase: str) -> None:
        """Record a lifecycle transition (always flushed immediately —
        transitions are rare and the watchdog keys its grace on them)."""
        if phase not in PHASES:
            raise ValueError(f'unknown heartbeat phase {phase!r} '
                             f'(have {PHASES})')
        now = self._clock()
        with self._lock:
            self._phase = phase
            self._progress_t = now
            rec = self._record_locked(now)
        self._write(rec, now, force=True)

    def on_step(self, step: int, tokens_per_sec: Optional[float] = None
                ) -> None:
        """Record one completed step. EWMA over host-side
        step-boundary-to-step-boundary time; file write throttled to
        the heartbeat interval."""
        now = self._clock()
        with self._lock:
            if self._last_step_t is not None:
                dt = max(now - self._last_step_t, 0.0)
                self._ewma = dt if self._ewma is None else \
                    self._alpha * dt + (1 - self._alpha) * self._ewma
            self._last_step_t = now
            self._progress_t = now
            self._step = int(step)
            self._phase = 'step'
            if tokens_per_sec is not None:
                self._tokens_per_sec = float(tokens_per_sec)
            rec = self._record_locked(now)
        self._write(rec, now)

    # ------------------------------------------------------------- views
    def _record_locked(self, now: float  # guarded-by: _lock
                       ) -> Dict[str, Any]:
        return {
            'rank': self.rank,
            'step': self._step,
            'phase': self._phase,
            'ts': now,
            'ewma_step_s': self._ewma,
            'tokens_per_sec': round(self._tokens_per_sec, 3),
            'device': self._device_kind,
            'pid': os.getpid(),
        }

    def snapshot(self) -> Dict[str, Any]:
        """The current record (no file IO) — what the rank-local
        sentinel and postmortem bundles read."""
        with self._lock:
            return self._record_locked(self._clock())

    def last_progress(self) -> float:
        """Timestamp of the last step completion or phase change."""
        with self._lock:
            return self._progress_t

    # ------------------------------------------------------------- write
    def _write(self, rec: Dict[str, Any], now: float,
               force: bool = False) -> None:
        if self.path is None:
            return
        if not force and now - self._last_write < self._interval:
            return
        self._last_write = now
        tmp = f'{self.path}.tmp.{os.getpid()}'
        try:
            d = os.path.dirname(self.path)
            if d:
                os.makedirs(d, exist_ok=True)
            with open(tmp, 'w', encoding='utf-8') as f:
                json.dump(rec, f)
            os.replace(tmp, self.path)
        except OSError:
            # Heartbeats are diagnostics: a full disk or a yanked job
            # dir must never take the training step loop down.
            try:
                os.unlink(tmp)
            except OSError:
                pass


def writer_from_env(rank: Optional[int] = None,
                    clock: Callable[[], float] = time.time,
                    device_kind: Optional[str] = None
                    ) -> Optional[HeartbeatWriter]:
    """The sft entry point: None when SKYT_WATCHDOG=0 (zero-overhead
    path), else a writer targeting SKYT_HEARTBEAT_FILE (the per-host
    agent exports it per rank; unset → an in-memory heartbeat, for the
    sentinel and the bundles)."""
    if not enabled():
        return None
    if rank is None:
        rank = env.get_int('SKYT_NODE_RANK', 0)
    return HeartbeatWriter(env.get(ENV_FILE) or None, rank,
                           clock=clock, device_kind=device_kind)
