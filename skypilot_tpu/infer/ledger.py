"""Engine busy-time ledger: chip-seconds attributed per (class,
tenant, model) — the cost half of the capacity plane
(docs/observability.md "Capacity plane").

Continuous batching multiplexes every in-flight request onto the same
device dispatches, so "how many chip-seconds did tenant A burn?" has
no direct measurement — a decode chunk advances eight requests at
once. This ledger recovers it by ATTRIBUTION:

  * the engine loop measures its busy intervals at the pipeline's
    sync points (`_finish_chunk` pulls, plus a flush when the loop
    goes idle): `settle(dt)` adds ``dt`` to the busy total;
  * between settles, the loop registers per-request work weights —
    prompt tokens at admission, delivered tokens at chunk delivery —
    via `note(key, tokens)`;
  * each settled interval is split across the registered keys
    proportionally to their token weights. An interval with no
    registered work (e.g. a chunk whose every slot was cancelled)
    stays in the busy total but attributes to nobody — the
    busy-vs-attributed gap is itself an honest overhead signal.

Tokens are the weight because they are what the device work scales
with at fixed model; the caveat (prefill tokens are cheaper than
decode tokens per position at short contexts) is documented with the
plane — the ledger is a cost ALLOCATOR, not a profiler.

Keys must be bounded: class is one of the parsed QoS classes, tenant
is charset/length-bounded by qos.parse_tenant, model is the served
base id or a loaded adapter name. The metric-cardinality analysis
pass enforces this discipline for every labeled family.

Gated by SKYT_CAPACITY_LEDGER (default on — the per-chunk cost is a
dict update and two counter incs).
"""
import threading
from typing import Dict, Optional, Tuple

from skypilot_tpu.utils import env
from skypilot_tpu.utils import metrics as metrics_lib

Key = Tuple[str, str, str]          # (class, tenant, model)


class BusyLedger:
    def __init__(self, registry: Optional[
            'metrics_lib.MetricsRegistry'] = None,
            enabled: Optional[bool] = None) -> None:
        if enabled is None:
            enabled = env.get_bool('SKYT_CAPACITY_LEDGER', True)
        self.enabled = enabled
        self._lock = threading.Lock()
        self._pending: Dict[Key, int] = {}
        self._busy_s = 0.0
        self._attr_s: Dict[Key, float] = {}
        self._tokens: Dict[Key, int] = {}
        reg = registry or metrics_lib.REGISTRY
        self._m_busy = reg.counter(
            'skyt_capacity_busy_seconds_total',
            'Engine-loop busy seconds (non-idle intervals between '
            'pipeline sync points; the attribution denominator)')
        self._m_attr = reg.counter(
            'skyt_capacity_attributed_seconds_total',
            'Engine busy seconds attributed to a class/tenant/model '
            'slice, proportional to its token weights',
            ('class', 'tenant', 'model'))

    def note(self, key: Key, tokens: int) -> None:
        """Register ``tokens`` of work for ``key`` in the interval
        being accumulated (engine-loop thread only)."""
        if not self.enabled or tokens <= 0:
            return
        with self._lock:
            self._pending[key] = self._pending.get(key, 0) + tokens
            self._tokens[key] = self._tokens.get(key, 0) + tokens

    def pending(self) -> bool:
        with self._lock:
            return bool(self._pending)

    def settle(self, dt: float) -> None:
        """Close one busy interval of ``dt`` seconds: add to the busy
        total and split across the pending weights."""
        if not self.enabled or dt <= 0:
            return
        with self._lock:
            self._busy_s += dt
            pending, self._pending = self._pending, {}
            total = sum(pending.values())
        self._m_busy.inc(dt)
        if total <= 0:
            return
        for key, w in pending.items():
            share = dt * (w / total)
            with self._lock:
                self._attr_s[key] = self._attr_s.get(key, 0.0) + share
            self._m_attr.labels(*key).inc(share)

    def snapshot(self) -> Dict[str, object]:
        """Totals for /stats and the sums-to-busy-time test."""
        with self._lock:
            return {
                'busy_seconds': round(self._busy_s, 6),
                'attributed_seconds': {
                    '/'.join(k): round(v, 6)
                    for k, v in sorted(self._attr_s.items())},
                'tokens': {'/'.join(k): v
                           for k, v in sorted(self._tokens.items())},
            }
