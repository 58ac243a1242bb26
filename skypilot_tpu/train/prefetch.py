"""Host-device overlap for the train input pipeline.

The sft loop used to build and upload each batch synchronously between
steps: tokenize/assemble on the host, then hand a numpy batch to the
jitted step, which transfers it before the device can start. Every
millisecond of that host work sat on the device's critical path
(Podracer, arXiv:2104.06272: TPU utilization is won by keeping host
work off the step chain).

Prefetcher moves it off: a producer thread pulls the next batches from
the source iterator, `jax.device_put`s them to their sharded layout
(an async enqueue — it returns as soon as the transfer is scheduled),
and parks them in a BOUNDED queue. While step k runs on device, batch
k+1..k+depth are already resident. The consumer's next() is then a
queue pop of an already-transferred batch.

Contracts:
  * bounded queue => backpressure: the producer can never run more
    than `depth` batches (plus the one it is building) ahead, so host
    memory stays flat on infinite iterators.
  * a producer exception is re-raised at the consumer's next() — a
    data bug fails the step loop, not a silent stall.
  * close() always unblocks and joins the producer, whether it is
    blocked on a full queue or mid-iteration.
  * in a device profile the producer thread's line holds
    `prefetch.build` (the source's next) and `prefetch.place` (the
    device_put) spans. The consumer's wait is the caller's to time
    (sft's `input_wait_ms=`): a next() that finds a batch staged is a
    queue pop.
"""
import queue
import threading
from typing import Any, Callable, Dict, Iterator, Optional

import jax
import numpy as np

from skypilot_tpu.utils import log_utils

logger = log_utils.init_logger(__name__)

_DONE = object()          # producer exhausted the source
_ERROR = object()         # producer raised; .error carries it


def make_sharded_placer(mesh, rules=None) -> Optional[
        Callable[[Dict[str, np.ndarray]], Dict[str, Any]]]:
    """A batch -> device_put(batch, sharded layout) function for the
    standard [B, S] train batch ({'tokens', 'targets', ...}), or None
    when placement must stay with jit (multi-process meshes: host data
    is process-local, and a device_put to a non-addressable sharding
    is not well defined — jit's own transfer handles that case the way
    it always has)."""
    if mesh is None or mesh.empty or jax.process_count() > 1:
        return None
    from skypilot_tpu.parallel import sharding as sharding_lib
    sharding = sharding_lib.named_sharding(
        mesh, ('act_batch', 'act_seq'),
        list(rules) if rules is not None else sharding_lib.DEFAULT_RULES)

    def place(batch: Dict[str, np.ndarray]) -> Dict[str, Any]:
        placed = {}
        for k, v in batch.items():
            try:
                placed[k] = jax.device_put(v, sharding)
            except ValueError:
                # Uneven shape for this mesh (explicit device_put
                # requires divisibility; jit's internal constraint
                # does not) — leave the host array for jit's own
                # transfer, exactly the pre-prefetch behavior.
                placed[k] = v
        return placed
    return place


class Prefetcher:
    """Bounded background prefetcher over a batch iterator.

    depth: max batches resident ahead of the consumer (the knob
    documented in docs/performance.md; 2 hides host assembly + upload
    without tying up meaningful extra HBM — each unit is one batch).
    place: optional batch -> placed-batch function (make_sharded_placer)
    run on the PRODUCER thread, so device_put's enqueue cost also moves
    off the step chain.
    """

    def __init__(self, source: Iterator[Dict[str, np.ndarray]],
                 depth: int = 2,
                 place: Optional[Callable] = None) -> None:
        if depth < 1:
            raise ValueError(f'prefetch depth must be >= 1, got {depth}')
        self._source = source
        self._place = place
        self._q: 'queue.Queue[Any]' = queue.Queue(maxsize=depth)
        self._stop = threading.Event()
        self.error: Optional[BaseException] = None
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name='train-prefetch')
        self._thread.start()

    # ------------------------------------------------------------ producer
    def _run(self) -> None:
        try:
            source = iter(self._source)
            while True:
                with jax.profiler.TraceAnnotation('prefetch.build'):
                    batch = next(source, _DONE)
                if batch is _DONE:
                    break
                if self._stop.is_set():
                    return
                if self._place is not None:
                    with jax.profiler.TraceAnnotation('prefetch.place'):
                        batch = self._place(batch)
                if not self._offer(batch):
                    return
            self._offer(_DONE)
        except BaseException as e:  # pylint: disable=broad-except
            # Surface at the consumer; swallowing would look like a hang.
            self.error = e
            self._offer(_ERROR)

    def _offer(self, item: Any) -> bool:
        """put() that stays responsive to close() while the queue is
        full (the backpressure wait)."""
        while not self._stop.is_set():
            try:
                self._q.put(item, timeout=0.05)
                return True
            except queue.Full:
                continue
        return False

    def resident(self) -> int:
        """Batches currently staged ahead of the consumer — a hung
        step loop shows a FULL queue here (producer kept up, device
        stopped pulling), which is exactly the signal postmortem
        bundles record (train/postmortem.py)."""
        return self._q.qsize()

    # ------------------------------------------------------------ consumer
    def __iter__(self) -> 'Prefetcher':
        return self

    def __next__(self) -> Dict[str, Any]:
        while True:
            if self.error is not None and self._q.empty():
                raise self.error
            try:
                item = self._q.get(timeout=0.05)
            except queue.Empty:
                if not self._thread.is_alive() and self._q.empty():
                    if self.error is not None:
                        raise self.error
                    raise StopIteration
                continue
            if item is _DONE:
                raise StopIteration
            if item is _ERROR:
                raise self.error
            return item

    def close(self) -> None:
        """Stop the producer and join it. Idempotent; safe from any
        thread; never raises the producer's error (a shutdown path
        must not die on a data bug the loop already saw or no longer
        cares about)."""
        self._stop.set()
        # Unblock a producer parked in the full-queue wait.
        while True:
            try:
                self._q.get_nowait()
            except queue.Empty:
                break
        self._thread.join(timeout=10)
        if self._thread.is_alive():   # pragma: no cover - diagnostics
            logger.warning('prefetch producer did not exit within 10s')
