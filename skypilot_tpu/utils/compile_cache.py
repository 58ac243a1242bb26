"""Where XLA's persistent compile cache lives, and what it did.

Every entry point (infer/server.py, train/sft.py, infer/multihost.py,
parallel/collectives.py, tests/conftest.py) calls
:func:`configure` first thing. The rule is one sentence: where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it and this module sets
nothing; where it is not, the cache is ``<checkout>/.jax_cache``. The
path is part of how a later process finds the entries again, so it is
derived from this file's own location and never from the home
directory, a temp name, a pid or a time.

The counters come from ``jax.monitoring`` and are read by the server's
/stats and the sft log, so a run can say how long it compiled and
whether the cache answered.
"""
import os
import threading
import time
from typing import Any, Dict

ENV = 'JAX_COMPILATION_CACHE_DIR'

DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), '.jax_cache')

_HIT = '/jax/compilation_cache/cache_hits'
_MISS = '/jax/compilation_cache/cache_misses'
# Seconds by stage, summed over every program this process made:
# tracing to a jaxpr, lowering that to an MLIR module, and the backend's
# compile (or its read from the persistent cache).
_DURATIONS = {
    '/jax/core/compile/jaxpr_trace_duration': 'trace_seconds',
    '/jax/core/compile/jaxpr_to_mlir_module_duration': 'lower_seconds',
    '/jax/core/compile/backend_compile_duration': 'compile_seconds',
}

_lock = threading.Lock()
_stats = {'hits': 0, 'misses': 0, 'compile_seconds': 0.0,
          'trace_seconds': 0.0, 'lower_seconds': 0.0}
_listening = False
# By thread, (end, seconds) of the traces counted so far, the newest last.
_traced: Dict[int, list] = {}


def _on_event(event: str, **_kwargs) -> None:
    if event == _HIT or event == _MISS:
        with _lock:
            _stats['hits' if event == _HIT else 'misses'] += 1


def _on_duration(event: str, duration: float, **_kwargs) -> None:
    key = _DURATIONS.get(event)
    if key is None:
        return
    with _lock:
        if key == 'trace_seconds':
            # A jitted function traced inside another's trace reports
            # first, on the same thread (listeners run on the caller's),
            # and the outer one's duration holds it: count an interval
            # once. One entry a traced program is kept, as the jit
            # caches keep the program itself.
            now = time.perf_counter()
            mine = _traced.setdefault(threading.get_ident(), [])
            while mine and mine[-1][0] > now - duration:
                _stats[key] -= mine.pop()[1]
            mine.append((now, duration))
        _stats[key] += duration


def configure() -> str:
    """Place the compile cache and start counting; returns its path.
    Idempotent. The variable is exported when this sets it, so child
    processes share the parent's cache."""
    global _listening
    import jax
    path = os.environ.get(ENV)
    if not path:
        path = os.environ[ENV] = DEFAULT_DIR
        jax.config.update('jax_compilation_cache_dir', path)
    with _lock:
        listen, _listening = not _listening, True
    if listen:
        jax.monitoring.register_event_listener(_on_event)
        jax.monitoring.register_event_duration_secs_listener(_on_duration)
    return path


def snapshot() -> Dict[str, Any]:
    """{'dir', 'hits', 'misses', 'compile_seconds', 'trace_seconds',
    'lower_seconds'}: persistent-cache reads that answered, entries
    written after a real compile, and the seconds spent in backend
    compilation (cache reads included), in tracing and in lowering."""
    with _lock:
        out: Dict[str, Any] = dict(_stats)
    for key in _DURATIONS.values():
        out[key] = round(out[key], 3)
    out['dir'] = os.environ.get(ENV)
    return out
