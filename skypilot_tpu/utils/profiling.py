"""jax.profiler trace collection for jobs (SURVEY.md §5 plan:
`skyt logs --profile`; beats the reference's client-only Chrome timeline,
sky/utils/timeline.py:21, which never sees device time).

Env contract (set per-job by the agent, runtime/agent.py):
  SKYT_PROFILE         "1" on the *launch* side requests profiling;
  SKYT_PROFILE_DIR     where the trace lands — the agent points this
                       inside the job's log dir so the existing
                       `skyt logs --sync-down` machinery ships traces
                       with no extra transport;
  SKYT_PROFILE_START_STEP   first profiled step, default 2 (skip
                            compile);
  SKYT_PROFILE_NUM_STEPS    profiled step count, default 3.

The trace is TensorBoard-loadable (plugins/profile/<ts>/*.xplane.pb):
`tensorboard --logdir <dir>` -> Profile tab, or xprof. Training loops
call `StepProfiler.on_step(i)` at the top of every step and `stop()`
after the loop; both are no-ops unless SKYT_PROFILE_DIR is set, so the
hook costs nothing in production runs.

This module additionally owns (docs/observability.md "Fleet plane"):

  * :func:`capture_trace` — a bounded ON-DEMAND capture behind a
    process-wide single-flight lock, the backend of the infer server's
    ``POST /debug/profile`` (and, via the controller proxy,
    ``POST /fleet/profile``). Works degraded on CPU: the host trace is
    still real data;
  * the MFU estimator — :func:`train_step_flops` reads FLOPs from the
    step's own HLO ``cost_analysis()`` at the LOWERED stage (global,
    pre-SPMD-partition, no backend compile) and falls back to the
    caller's analytic 6ND-style count only when the backend cannot
    answer, so the published ``skyt_train_mfu`` metric no longer
    depends on hand-maintained formulas.
"""
import os
import threading
import time
from typing import Any, Callable, Dict, Optional, Tuple

from skypilot_tpu.utils import log_utils
from skypilot_tpu.utils import env

logger = log_utils.init_logger(__name__)

# bf16 peak FLOPs per chip (the MFU denominator): owned here so the
# trainer's published MFU and the fleet cost report divide by the same
# numbers.
PEAK_FLOPS = {
    'TPU v5 lite': 197e12,
    'TPU v5': 459e12,
    'TPU v4': 275e12,
    'TPU v6 lite': 918e12,
}


def peak_flops(device) -> float:
    """Peak bf16 FLOPs of one device. A device that is not in the
    table is an error, not a default: a utilization against a made-up
    peak is not a measurement."""
    kind = device.device_kind
    for prefix, flops in PEAK_FLOPS.items():
        if kind.startswith(prefix):
            return flops
    raise ValueError(
        f'no peak FLOP/s on record for device kind {kind!r} '
        f'(known: {sorted(PEAK_FLOPS)})')


class ProfilerBusy(RuntimeError):
    """A capture is already in flight (single-flight lock held)."""


# One capture at a time per process: jax.profiler keeps global state,
# and overlapping start_trace calls abort the collector. Shared by
# capture_trace AND StepProfiler so an on-demand capture cannot race a
# step-window profile.
_CAPTURE_LOCK = threading.Lock()


def capture_trace(duration_ms: float,
                  base_dir: Optional[str] = None) -> Dict[str, Any]:
    """Capture a jax.profiler trace for `duration_ms` into a fresh
    temp dir; returns {'trace_dir', 'duration_ms', 'files', 'n_files'}.

    Raises ProfilerBusy when another capture holds the single-flight
    lock (HTTP callers map it to 409). The caller is responsible for
    authorization (the server gates on SKYT_PROFILE_REMOTE)."""
    import tempfile

    import jax
    if not _CAPTURE_LOCK.acquire(blocking=False):
        raise ProfilerBusy('a profile capture is already in flight')
    try:
        out_dir = tempfile.mkdtemp(
            prefix='skyt-profile-',
            dir=base_dir or env.get('SKYT_PROFILE_DIR') or None)
        t0 = time.perf_counter()
        jax.profiler.start_trace(out_dir)
        try:
            time.sleep(max(0.0, duration_ms) / 1e3)
        finally:
            try:
                jax.effects_barrier()
            except Exception:  # noqa — best-effort flush, see stop()
                pass
            jax.profiler.stop_trace()
        files = []
        for root, _dirs, names in os.walk(out_dir):
            for name in names:
                files.append(os.path.relpath(os.path.join(root, name),
                                             out_dir))
        files.sort()
        return {'trace_dir': out_dir,
                'duration_ms': round((time.perf_counter() - t0) * 1e3,
                                     1),
                'files': files[:50], 'n_files': len(files)}
    finally:
        _CAPTURE_LOCK.release()


# ----------------------------------------------------- MFU estimation
def cost_analysis_flops(stage) -> Optional[float]:
    """FLOPs from a jax stage's ``cost_analysis()`` (a ``Lowered`` or
    a compiled executable), or None when the backend does not report
    them."""
    try:
        ca = stage.cost_analysis()
        if not isinstance(ca, dict):
            return None
        flops = float(ca.get('flops', 0.0) or 0.0)
        return flops if flops > 0 else None
    except Exception as e:  # pylint: disable=broad-except
        logger.debug('cost_analysis unavailable: %r', e)
        return None


# Back-compat alias (the original name; same function — any stage with
# a cost_analysis() works).
compiled_flops = cost_analysis_flops


def train_step_flops(step_fn: Callable, *args,
                     analytic: Optional[Any] = None,
                     lowered: Optional[Any] = None
                     ) -> 'Tuple[Optional[float], str]':
    """FLOPs of one call of `step_fn(*args)` -> (flops, source).

    Tries the HLO cost analysis first: `step_fn` must expose
    ``.lower`` (jax.jit functions do; trainer.make_train_step attaches
    one that re-enters its mesh/axis-rules context). Deliberately the
    LOWERED stage's cost analysis, not the compiled executable's:
    lowering costs no backend compile (no mid-run stall on large
    models), and its count is GLOBAL and pre-optimization — the right
    MFU numerator on both axes, since SPMD partitioning would report
    per-device FLOPs against our global-peak denominator and remat
    recompute must not inflate MFU. Falls back to `analytic` (a float
    or zero-arg callable — the hand-maintained 6ND-style count) and
    ultimately (None, 'unavailable').

    ``lowered``: a precomputed ``step_fn.lower(*args)`` stage, so a
    caller that also feeds the comms census (sft) lowers once for
    both reads."""
    if lowered is not None or getattr(step_fn, 'lower', None) \
            is not None:
        try:
            if lowered is None:
                lowered = step_fn.lower(*args)
            flops = cost_analysis_flops(lowered)
            if flops is not None:
                return flops, 'hlo_cost_analysis'
        except Exception as e:  # pylint: disable=broad-except
            logger.warning('HLO cost analysis failed (%r); falling '
                           'back to the analytic FLOPs count', e)
    try:
        if callable(analytic):
            analytic = analytic()
        if analytic:
            return float(analytic), 'analytic'
    except Exception as e:  # pylint: disable=broad-except
        logger.warning('analytic FLOPs count failed: %r', e)
    return None, 'unavailable'


class StepProfiler:
    """Profiles steps [start, start + num) of a training loop."""

    def __init__(self, trace_dir: Optional[str] = None) -> None:
        self.trace_dir = trace_dir or env.get('SKYT_PROFILE_DIR')
        self.start_step = env.get_int('SKYT_PROFILE_START_STEP', 2)
        self.num_steps = env.get_int('SKYT_PROFILE_NUM_STEPS', 3,
                                  minimum=1)
        self._active = False
        self._done = False

    @property
    def enabled(self) -> bool:
        return self.trace_dir is not None

    def on_step(self, step: int) -> None:
        """Call at the top of every step with a 0-based loop index."""
        if not self.enabled or self._done:
            return
        if self._active and step >= self.start_step + self.num_steps:
            self.stop()
        elif not self._active and step >= self.start_step:
            if not _CAPTURE_LOCK.acquire(blocking=False):
                # An on-demand capture_trace is in flight: skip this
                # window (jax.profiler is process-global; overlapping
                # start_trace calls abort the collector).
                logger.warning('profiler busy; skipping the step-'
                               'window profile')
                self._done = True
                return
            try:
                import jax
                os.makedirs(self.trace_dir, exist_ok=True)
                jax.profiler.start_trace(self.trace_dir)
            except Exception as e:  # pylint: disable=broad-except
                # Release (a leaked lock would 409 every later
                # on-demand capture in this process) and degrade: an
                # unwritable profile dir must cost the profile, not
                # the training job.
                _CAPTURE_LOCK.release()
                self._done = True
                logger.warning('step-window profile failed to start '
                               '(%r); continuing unprofiled', e)
                return
            self._active = True
            logger.info('profiling steps %d..%d -> %s', step,
                        step + self.num_steps - 1, self.trace_dir)

    def stop(self) -> None:
        """Idempotent; call after the loop in case it ended mid-trace."""
        if not self._active:
            return
        import jax
        # Make sure the profiled steps' device work is in the trace, not
        # still in flight when the collector stops.
        try:
            jax.effects_barrier()
        except Exception:  # pylint: disable=broad-except
            pass
        jax.profiler.stop_trace()
        self._active = False
        self._done = True
        _CAPTURE_LOCK.release()
        logger.info('profile trace written to %s', self.trace_dir)
