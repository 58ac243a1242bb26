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

This module additionally owns :func:`capture_trace` — a bounded
ON-DEMAND capture behind a process-wide single-flight lock, the backend
of the infer server's ``POST /debug/profile`` (and, via the controller
proxy, ``POST /fleet/profile``; docs/observability.md "Fleet plane").
Works degraded on CPU: the host trace is still real data.

What a step-window profile of sft holds (docs/observability.md "Device
profiles"): the device's operations under the step's named scopes
(forward and backward by the transform in their path, `optimizer`,
`loss`), and on the host plane the step loop's `train.step` spans with
their children and the prefetcher's `prefetch.*` spans, on one clock.
"""
import os
import threading
import time
from typing import Any, Dict, Optional

from skypilot_tpu.utils import log_utils
from skypilot_tpu.utils import env

logger = log_utils.init_logger(__name__)


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
