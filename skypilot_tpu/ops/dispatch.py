"""Kernel dispatch: shape-robust block selection + a runtime fallback
ladder so the kernel layer NEVER crashes on a legal input shape.

Why this exists: Mosaic (the Pallas TPU backend) requires the last two
dims of every block to be divisible by (8, 128) — or equal to the
array's dims (jax _check_block_mappings; the exact rule this module
mirrors in ``block_dim_ok``). A decode-shaped flash block once
hard-crashed TPU lowering; a shape the static rule can refuse must
take a slower correct path instead of taking down a train step or a
serve replica.

Two pieces:

* **Divisibility-safe block selection** (``choose_block``): clamp a
  requested block size to the largest legal divisor of the dim, or
  fall back to the full array dim (always legal by the "equal" arm of
  the Mosaic rule). Kernels built this way are statically legal.

* **A fallback ladder** (``run_ladder``): tuned-Pallas →
  conservative-Pallas (full-array blocks) → pure-XLA reference,
  selected at TRACE time. Each non-final rung carries the
  ``ops.lowering`` fault point, so ``SKYT_FAULTS=ops.lowering=error``
  forces ladder descent — the whole subsystem is chaos-testable on
  CPU. The chosen path is recorded in
  ``skyt_ops_kernel_path_total{op,path}`` and as an attribute on the
  current trace span, so silent degradation is VISIBLE in the
  metrics/tracing plane (docs/kernels.md).

Trace-time semantics: the ladder runs while jax traces the enclosing
jit, i.e. once per compiled (shape, dtype) — the counter measures
compilations, not calls, and re-arming faults after a shape has
compiled does not change its baked-in path. Lowering errors raised by
the Mosaic compiler itself (AFTER tracing) cannot be caught here —
that is exactly why rung selection is static-validation-first: a rung
is only offered if its block specs pass the mirrored legality rule.
"""
import functools
import math
import threading
from typing import Any, Callable, Dict, List, Tuple

from skypilot_tpu.utils import log_utils
from skypilot_tpu.utils import metrics as metrics_lib
from skypilot_tpu.utils import env

logger = log_utils.init_logger(__name__)

LANES = 128

# Minimum second-minor (sublane) tile per dtype itemsize
# (pallas_guide.md: f32 (8,128), bf16 (16,128), int8/fp8 (32,128)).
# Mosaic's block-mapping check only demands 8, but a block aligned to
# the dtype's real tile never hits packing slow paths.
_SUBLANE_BY_ITEMSIZE = {4: 8, 2: 16, 1: 32}

# A Pallas rung whose VMEM working set exceeds this is not offered:
# a compile-time OOM inside Mosaic is as fatal as an illegal block
# (and as invisible to a trace-time try/except). v5e has 16MB less
# scratch overheads.
VMEM_BUDGET_BYTES = int(
    env.get('SKYT_OPS_VMEM_BUDGET', str(12 * 1024 * 1024)))

_ENV_FORCE = 'SKYT_OPS_FORCE_PATH'

_lock = threading.Lock()
# op -> most recently selected path (trace-time); surfaced in engine
# /stats and flight-recorder snapshots.
_paths: Dict[str, str] = {}


def sublane_multiple(dtype) -> int:
    """Preferred sublane alignment for a dtype (8/16/32)."""
    import jax.numpy as jnp
    return _SUBLANE_BY_ITEMSIZE.get(jnp.dtype(dtype).itemsize, 8)


def block_dim_ok(block: int, dim: int, multiple: int) -> bool:
    """One dim of the Mosaic last-two-dims rule: the block extent must
    be a multiple of the tile (8 sublane / 128 lane) or equal to the
    array dim. Our kernels' index maps additionally assume blocks
    divide the dim exactly."""
    if block == dim:
        return True
    return block % multiple == 0 and dim % block == 0


def choose_block(dim: int, want: int, multiple: int = 8) -> int:
    """Largest legal block <= want for an array dim: a multiple of
    `multiple` that divides `dim`, else the full dim (always legal).

    This is the divisibility-safe selection that makes decode shapes
    (e.g. sq=8 with a 256 default) lower instead of raising."""
    want = min(want, dim)
    if want <= 0 or want == dim:
        return dim
    # Largest multiple of `multiple` <= want that divides dim.
    for cand in range(want - want % multiple, 0, -multiple):
        if dim % cand == 0:
            return cand
    return dim


def flash_blocks(sq: int, sk: int, want_q: int, want_k: int,
                 q_dtype, has_seg: bool) -> Tuple[int, int]:
    """Legal (block_q, block_k) for the flash kernels.

    Segment-id blocks place the seq extent in the LANE position
    ([b, 1, s] layout), so with packed sequences the seq blocks must
    be 128-aligned (or full); without, the q/k blocks only need the
    dtype's sublane alignment."""
    mult = LANES if has_seg else sublane_multiple(q_dtype)
    return (choose_block(sq, want_q, mult), choose_block(sk, want_k, mult))


def flash_vmem_bytes(block_q: int, block_k: int, d: int,
                     itemsize: int) -> int:
    """Rough per-invocation VMEM working set of the flash forward:
    q/k/v/out blocks + f32 scratch (acc, m, l, lse) + the f32 score
    block. The backward's is the same order of magnitude."""
    io = (block_q * d * 2 + block_k * d * 2) * itemsize
    scratch = (block_q * d + block_q * 2 + block_q * LANES) * 4
    scores = block_q * block_k * 4
    return io + scratch + scores


def flash_vmem_ok(block_q: int, block_k: int, d: int, itemsize: int) -> bool:
    return flash_vmem_bytes(block_q, block_k, d,
                            itemsize) <= VMEM_BUDGET_BYTES


def is_tracer(x: Any) -> bool:
    """True when x is a jax tracer (inside jit/grad tracing) — i.e.
    its VALUES are not available, only shape/dtype."""
    import jax
    return isinstance(x, jax.core.Tracer)


def _counter() -> 'metrics_lib.Counter':
    return metrics_lib.REGISTRY.counter(
        'skyt_ops_kernel_path_total',
        'Kernel dispatch path selected at trace time', ('op', 'path'))


def record_path(op: str, path: str) -> None:
    """Count + remember the selected path and stamp it on the current
    trace span so a degraded kernel is visible on flight-recorded
    traces, not just in aggregate."""
    _counter().labels(op, path).inc()
    with _lock:
        _paths[op] = path
    from skypilot_tpu.utils import tracing
    span = tracing.current_span()
    if span is not None:
        span.set_attribute(f'ops.path.{op}', path)


def snapshot() -> Dict[str, str]:
    """op -> last selected path (engine /stats + flight recorder)."""
    with _lock:
        return dict(_paths)


def run_ladder(op: str,
               rungs: List[Tuple[str, Callable[[], Any]]]) -> Any:
    """Run the first rung that works; record which one did.

    Each rung is (path_name, thunk). Non-final rungs carry the
    ``ops.lowering`` fault point (attrs: op, path — target one rung
    with ``where=path:<name>``) and any exception they raise at trace
    time descends the ladder with a warning. The FINAL rung is the
    correctness floor (pure XLA): it is not fault-injected and its
    errors propagate — there is nothing further to fall back to.

    SKYT_OPS_FORCE_PATH=<name> keeps only that rung plus the final
    one (debug escape hatch; an unknown name is ignored loudly).
    """
    if not rungs:
        raise ValueError(f'ops.{op}: empty dispatch ladder')
    forced = env.get(_ENV_FORCE, '')
    if forced and len(rungs) > 1:
        kept = [r for r in rungs if r[0] == forced]
        if kept:
            if rungs[-1][0] != forced:
                kept.append(rungs[-1])
            rungs = kept
        elif forced != rungs[-1][0]:
            logger.warning('%s=%r matches no rung of ops.%s (have %s)',
                           _ENV_FORCE, forced, op, [r[0] for r in rungs])
    from skypilot_tpu.utils import faults
    last = len(rungs) - 1
    for i, (path, thunk) in enumerate(rungs):
        try:
            if i < last:
                faults.inject('ops.lowering', op=op, path=path)
            out = thunk()
        except Exception as e:  # pylint: disable=broad-except
            if i == last:
                record_path(op, 'error')
                raise
            logger.warning(
                'ops.%s: %r path failed at trace time (%s: %s); '
                'falling back to %r', op, path, type(e).__name__, e,
                rungs[i + 1][0])
            continue
        record_path(op, path)
        return out
    raise AssertionError('unreachable')


def shape_bucket(n: int) -> int:
    """Round a dim up to the next power of two (autotune cache keys
    bucket shapes so one sweep covers the whole padded-bucket family)."""
    if n <= 1:
        return 1
    return 1 << math.ceil(math.log2(n))


def interpret_mode() -> bool:
    """Whether Pallas kernels run interpreted (any backend but the
    TPU) instead of compiled through Mosaic. The one place that
    decides it, and it asks JAX: a backend that cannot initialise is
    an error here, never a quiet "interpret"."""
    import jax
    return jax.default_backend() != 'tpu'


def device_kind() -> str:
    """Device kind for autotune cache keys ('TPU v5 lite', 'cpu', ...)."""
    import jax
    return jax.devices()[0].device_kind


@functools.lru_cache(maxsize=None)
def _versions() -> Dict[str, str]:
    import importlib.metadata
    out = {}
    for pkg in ('jax', 'jaxlib', 'libtpu'):
        try:
            out[pkg] = importlib.metadata.version(pkg)
        except importlib.metadata.PackageNotFoundError:
            pass
    return out


def device_info() -> Dict[str, Any]:
    """What the program is running on, as JAX reports it: the `device`
    block of the server's /stats and of the sft log. Per-device memory
    is listed where the backend reports it (the CPU does not)."""
    import jax
    devices = jax.devices()
    info: Dict[str, Any] = {
        'platform': devices[0].platform,
        'device_kind': devices[0].device_kind,
        'count': len(devices),
        'pallas_interpret': interpret_mode(),
        'versions': dict(_versions()),
    }
    memory = []
    for d in jax.local_devices():
        stats = d.memory_stats()
        if stats:
            memory.append({
                'id': d.id,
                'bytes_in_use': stats.get('bytes_in_use'),
                'peak_bytes_in_use': stats.get('peak_bytes_in_use'),
                'bytes_limit': stats.get('bytes_limit')})
    if memory:
        info['memory'] = memory
    return info


def reset_for_tests() -> None:
    """Clear the path snapshot (unit tests)."""
    with _lock:
        _paths.clear()
