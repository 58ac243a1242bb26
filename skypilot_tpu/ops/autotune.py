"""Kernel block-size autotuning with a persistent on-disk cache.

The flash kernels' shape rule (dispatch.flash_blocks) was tuned on one
device at one head size; the best blocks depend on (device generation,
sequence lengths, head dim, dtype). This module sweeps the small legal
candidate set ONCE per (device_kind, op, shape-bucket, dtype) key, on the
real device, and persists the winner so every later process — train
jobs, serve replicas — starts tuned.

Design constraints (docs/kernels.md):

* Sweeping executes kernels, so it can only run on CONCRETE arrays —
  never inside a jit trace. ``maybe_sweep_flash`` is a no-op on
  tracers; at trace time the dispatcher only READS the cache
  (``lookup_flash``). Sweeps therefore happen at setup/bench time
  (ops.attention called eagerly with ``SKYT_AUTOTUNE=1``).
* A candidate that fails for ANY reason is skipped, never propagated:
  a broken candidate must cost one log line, not the run.
* Cache writes are atomic (tmpfile + os.replace) so a preempted
  process can never leave a half-written file; a corrupt/unreadable
  cache file degrades to a cold start, never a crash.

Cache file format (``SKYT_AUTOTUNE_CACHE``, default
``~/.cache/skypilot_tpu/autotune.json``)::

    {"version": 1,
     "entries": {"<device_kind>|<op>|<bucket>|<dtype>":
                 {"block_q": 256, "block_k": 128, "us": 123.4}}}

Env vars: SKYT_AUTOTUNE=1 enables sweeping (reads are always on),
SKYT_AUTOTUNE_CACHE overrides the path, SKYT_AUTOTUNE_REPEATS the
per-candidate timing repeats (default 3, best-of).
"""
import json
import os
import tempfile
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

from skypilot_tpu.ops import dispatch
from skypilot_tpu.utils import log_utils
from skypilot_tpu.utils import metrics as metrics_lib
from skypilot_tpu.utils import env

logger = log_utils.init_logger(__name__)

_ENV_ENABLE = 'SKYT_AUTOTUNE'
_ENV_CACHE = 'SKYT_AUTOTUNE_CACHE'
_ENV_REPEATS = 'SKYT_AUTOTUNE_REPEATS'

_VERSION = 1

# Candidate seq-block extents, pruned per shape by legality.
_FLASH_CANDIDATE_BLOCKS = (128, 256, 512)


def enabled() -> bool:
    return env.get(_ENV_ENABLE, '0') == '1'


def cache_path() -> str:
    return env.get(_ENV_CACHE) or os.path.expanduser(
        '~/.cache/skypilot_tpu/autotune.json')


def _sweeps() -> 'metrics_lib.Counter':
    return metrics_lib.REGISTRY.counter(
        'skyt_ops_autotune_sweeps_total',
        'Autotune block-size sweeps executed', ('op',))


def _hits() -> 'metrics_lib.Counter':
    return metrics_lib.REGISTRY.counter(
        'skyt_ops_autotune_cache_hits_total',
        'Autotune cache hits (sweep skipped)', ('op',))


class AutotuneCache:
    """Thread-safe persistent key -> dict cache. Never raises from
    load (corrupt file == cold start); writes are atomic."""

    def __init__(self, path: str) -> None:
        self.path = path
        self._lock = threading.Lock()
        self._entries: Optional[Dict[str, Dict[str, Any]]] = None

    def _load_locked(self) -> Dict[str, Dict[str, Any]]:  # guarded-by: _lock
        if self._entries is not None:
            return self._entries
        entries: Dict[str, Dict[str, Any]] = {}
        try:
            with open(self.path, encoding='utf-8') as f:
                data = json.load(f)
            if (isinstance(data, dict) and
                    data.get('version') == _VERSION and
                    isinstance(data.get('entries'), dict)):
                entries = {k: v for k, v in data['entries'].items()
                           if isinstance(v, dict)}
            else:
                logger.warning(
                    'autotune cache %s has unexpected layout '
                    '(version %r); starting cold', self.path,
                    data.get('version') if isinstance(data, dict)
                    else type(data).__name__)
        except FileNotFoundError:
            pass
        except (OSError, ValueError) as e:
            # json.JSONDecodeError is a ValueError: a corrupt cache
            # (killed mid-debug-edit, disk hiccup) costs a re-sweep,
            # never the process.
            logger.warning('autotune cache %s unreadable (%s); '
                           'starting cold', self.path, e)
        self._entries = entries
        return entries

    def get(self, key: str) -> Optional[Dict[str, Any]]:
        with self._lock:
            return self._load_locked().get(key)

    def put(self, key: str, value: Dict[str, Any]) -> None:
        with self._lock:
            entries = self._load_locked()
            entries[key] = value
            payload = json.dumps(
                {'version': _VERSION, 'entries': entries},
                indent=1, sort_keys=True)
            try:
                d = os.path.dirname(self.path) or '.'
                os.makedirs(d, exist_ok=True)
                fd, tmp = tempfile.mkstemp(dir=d, prefix='.autotune.')
                try:
                    with os.fdopen(fd, 'w', encoding='utf-8') as f:
                        f.write(payload)
                    os.replace(tmp, self.path)   # atomic on POSIX
                except BaseException:
                    try:
                        os.unlink(tmp)
                    except OSError:
                        pass
                    raise
            except OSError as e:
                # Read-only FS / ENOSPC: the in-memory winner still
                # serves this process; only persistence is lost.
                logger.warning('autotune cache %s not persisted (%s)',
                               self.path, e)

    def forget_loaded(self) -> None:
        """Drop the in-memory copy so the next access re-reads disk
        (tests simulating a fresh process)."""
        with self._lock:
            self._entries = None


_caches: Dict[str, AutotuneCache] = {}
_caches_lock = threading.Lock()


def get_cache(path: Optional[str] = None) -> AutotuneCache:
    path = path or cache_path()
    with _caches_lock:
        c = _caches.get(path)
        if c is None:
            c = _caches[path] = AutotuneCache(path)
        return c


def flash_key(b: int, sq: int, sk: int, hq: int, hkv: int, d: int,
              dtype, causal: bool, has_seg: bool, window: int) -> str:
    bucket = (f'b{dispatch.shape_bucket(b)}'
              f'.sq{dispatch.shape_bucket(sq)}'
              f'.sk{dispatch.shape_bucket(sk)}'
              f'.h{hq}x{hkv}.d{d}'
              f'.c{int(causal)}.seg{int(has_seg)}.w{window}')
    import jax.numpy as jnp
    return (f'{dispatch.device_kind()}|flash_attention|{bucket}'
            f'|{jnp.dtype(dtype).name}')


def lookup_flash(q_shape: Sequence[int], k_shape: Sequence[int], dtype,
                 causal: bool, has_seg: bool,
                 window: int) -> Optional[Tuple[int, int]]:
    """Trace-time cache read: tuned (block_q, block_k) or None. Shapes
    are concrete even on tracers, so this works under jit."""
    b, sq, hq, d = q_shape
    sk, hkv = k_shape[1], k_shape[2]
    entry = get_cache().get(
        flash_key(b, sq, sk, hq, hkv, d, dtype, causal, has_seg, window))
    if not entry:
        return None
    try:
        return int(entry['block_q']), int(entry['block_k'])
    except (KeyError, TypeError, ValueError):
        return None   # stale/hand-edited entry: behave as a miss


def sweep(op: str, key: str, candidates: Sequence[Any],
          run: Callable[[Any], Any],
          describe: Callable[[Any], Dict[str, Any]]) -> Optional[dict]:
    """Generic timed sweep: run(cand) per candidate (must block until
    the device finishes), best wall time wins, failures are skipped.
    Persists describe(winner) + timing under `key`. Returns the stored
    entry, or None when every candidate failed."""
    cache = get_cache()
    hit = cache.get(key)
    if hit is not None:
        _hits().labels(op).inc()
        return hit
    repeats = env.get_int(_ENV_REPEATS, 3, minimum=1)
    _sweeps().labels(op).inc()
    best: Optional[Tuple[float, Any]] = None
    for cand in candidates:
        try:
            run(cand)                       # warmup / compile
            dt = min(_timed(run, cand) for _ in range(repeats))
        except Exception as e:  # pylint: disable=broad-except
            # "Any candidate failure is a skip, never a propagate."
            logger.info('autotune %s: candidate %r failed (%s: %s); '
                        'skipped', op, cand, type(e).__name__, e)
            continue
        if best is None or dt < best[0]:
            best = (dt, cand)
    if best is None:
        logger.warning('autotune %s: every candidate failed for %s; '
                       'falling back to defaults', op, key)
        # Negative-cache the failure: without this, every later eager
        # call for the bucket re-runs the whole failing sweep
        # (minutes on-device). lookup_flash reads it as a miss (no
        # block_q), so dispatch defaults still apply.
        cache.put(key, {'failed': True})
        return None
    entry = dict(describe(best[1]))
    entry['us'] = round(best[0] * 1e6, 2)
    cache.put(key, entry)
    logger.info('autotune %s: %s -> %s', op, key, entry)
    return entry


def _timed(run: Callable[[Any], Any], cand: Any) -> float:
    t0 = time.perf_counter()
    run(cand)
    return time.perf_counter() - t0


def flash_candidates(sq: int, sk: int, dtype,
                     has_seg: bool) -> List[Tuple[int, int]]:
    """Legal (block_q, block_k) candidates: the cross product of the
    candidate extents clamped through the divisibility-safe selector,
    deduplicated, plus the conservative full-array pair."""
    out: List[Tuple[int, int]] = []
    for wq in _FLASH_CANDIDATE_BLOCKS:
        for wk in _FLASH_CANDIDATE_BLOCKS:
            cand = dispatch.clamp_flash_blocks(sq, sk, wq, wk, dtype, has_seg)
            if cand not in out:
                out.append(cand)
    if (sq, sk) not in out:
        out.append((sq, sk))
    return out


def maybe_sweep_flash(q, k, v, causal: bool, segment_ids,
                      window: int) -> None:
    """Sweep flash block sizes for this shape if enabled, concrete,
    and not already cached. Called from ops.attention's eager wrapper;
    one env read when disabled."""
    if not enabled() or dispatch.is_tracer(q):
        return
    b, sq, hq, d = q.shape
    sk, hkv = k.shape[1], k.shape[2]
    has_seg = segment_ids is not None
    key = flash_key(b, sq, sk, hq, hkv, d, q.dtype, causal, has_seg,
                    window)
    from skypilot_tpu.ops import flash_attention as flash_lib

    def run(cand):
        bq, bk = cand
        out = flash_lib.flash_attention(
            q, k, v, causal=causal, segment_ids=segment_ids,
            block_q=bq, block_k=bk, window=window)
        out.block_until_ready()

    sweep('flash_attention', key,
          flash_candidates(sq, sk, q.dtype, has_seg), run,
          lambda cand: {'block_q': cand[0], 'block_k': cand[1]})


def reset_for_tests() -> None:
    """Drop all in-memory cache instances (tests swap cache paths)."""
    with _caches_lock:
        _caches.clear()
