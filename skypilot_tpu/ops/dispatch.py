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

* **A fallback ladder** (``run_ladder``): Pallas at the blocks the
  shape rule gives → pure-XLA reference, selected at TRACE time. Each
  non-final rung carries the ``ops.lowering`` fault point, so
  ``SKYT_FAULTS=ops.lowering=error`` forces ladder descent — the whole
  subsystem is chaos-testable on CPU. The chosen path is recorded in
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
import threading
from typing import Any, Callable, Dict, List, Optional, Tuple

from skypilot_tpu.utils import log_utils
from skypilot_tpu.utils import metrics as metrics_lib

logger = log_utils.init_logger(__name__)

LANES = 128

# Minimum second-minor (sublane) tile per dtype itemsize
# (pallas_guide.md: f32 (8,128), bf16 (16,128), int8/fp8 (32,128)).
# Mosaic's block-mapping check only demands 8, but a block aligned to
# the dtype's real tile never hits packing slow paths.
_SUBLANE_BY_ITEMSIZE = {4: 8, 2: 16, 1: 32}

# A Pallas rung whose VMEM working set exceeds this is not offered:
# a compile-time OOM inside Mosaic is as fatal as an illegal block
# (and as invisible to a trace-time try/except). A constant of the tile
# rule, not a setting: the per-kernel byte counts of `flash_vmem_bytes`
# were fitted to it on one v5e (16 MiB of scoped VMEM less what the
# count cannot see; PERF.md §6, PR 27).
VMEM_BUDGET_BYTES = 12 * 1024 * 1024

_lock = threading.Lock()
# op -> most recently selected path (trace-time); surfaced in engine
# /stats and flight-recorder snapshots.
_paths: Dict[str, str] = {}
# flash kernel -> the tile plan of the shape traced last (trace-time);
# logged by sft after its first step. A kernel traced with a sliding
# window is filed as `window_<kernel>`, one traced with the
# block-diffusion mask as `bd_<kernel>`, beside the full-attention plan.
_flash_plans: Dict[str, Dict[str, Any]] = {}
# The mask `flash_blocks` planned for last on this thread ('window_',
# 'bd_' or ''). A flash kernel asks for its tiles and records them in
# one breath (flash_attention._plan), and only the first of the two
# calls is told the mask.
_planned = threading.local()
# The block-diffusion objective a train step was traced with.
_bd_plan: Dict[str, Any] = {}
# The routing plan of the expert layer traced last (trace-time).
_moe_plan: Dict[str, Any] = {}
# (form, a, b) of a grouped product -> the tiles it was traced with.
_grouped_plan: Dict[Tuple[str, int, int], Tuple[int, int, int]] = {}


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


FLASH_KERNELS = ('fwd', 'dq', 'dkv')

# The largest (block_q, block_k) the shape rule gives each flash kernel:
# of the extents whose working set fits VMEM_BUDGET_BYTES, the fastest on
# one v5e at head 128 in bf16, at S 512 to 8,192 (PERF.md §6, PR 27).
# Every kernel ran faster the larger its tiles, whole-sequence tiles
# included: a tile's fixed cost (the grid step, and per query row the
# column statistics and the accumulator's read-modify-write) outweighs
# the extra masked area. The forward gains from a wide block_k, dq from a
# tall block_q; dk/dv at 1,024 x 1,024 is over the budget, and the
# rectangles between are slower than 512 x 512.
_FLASH_MAX_BLOCKS = {'fwd': (512, 1024), 'dq': (1024, 1024),
                     'dkv': (512, 512)}
_FLASH_MIN_WINDOW_BLOCK = 256

# Mosaic's scoped VMEM when `vmem_limit_bytes` is not given (v5e).
_MOSAIC_DEFAULT_VMEM_BYTES = 16 * 1024 * 1024


def clamp_flash_blocks(sq: int, sk: int, want_q: int, want_k: int,
                       q_dtype, has_seg: bool,
                       kernel: str = 'fwd') -> Tuple[int, int]:
    """Legal (block_q, block_k) nearest a request.

    An extent that rides the LANE axis of some block must be
    128-aligned (or full): with packed sequences both do (the
    [b, 1, s] segment-id blocks), and the dk/dv kernel reads its row
    statistics as [b, h, 1, sq] rows, so its q extent always does.
    Otherwise the blocks only need the dtype's sublane alignment."""
    sub = sublane_multiple(q_dtype)
    mult_q = LANES if has_seg or kernel == 'dkv' else sub
    mult_k = LANES if has_seg else sub
    return (choose_block(sq, want_q, mult_q),
            choose_block(sk, want_k, mult_k))


def flash_blocks(sq: int, sk: int, d: int, q_dtype, has_seg: bool,
                 window: int = 0,
                 want: Optional[Tuple[int, int]] = None,
                 block_diffusion: bool = False
                 ) -> Dict[str, Tuple[int, int]]:
    """The tile rule: kernel ('fwd', 'dq', 'dkv') -> (block_q, block_k).

    With no request the extents come from the shape: the kernel's
    tuned maximum (`_FLASH_MAX_BLOCKS`), no wider than a sliding
    window, halved while the kernel's working set (`flash_vmem_bytes`)
    is over the VMEM budget, then clamped to what the shape allows
    (short, odd and decode shapes get a legal divisor or the full dim,
    never a tile larger than the sequence). A request (`want`: the
    tests' `flash_attention(block_q=, block_k=)`) is clamped the same
    way and given to all three kernels.

    Under the block-diffusion mask (sq = sk = 2L) an extent divides L,
    so that a tile lies in one quadrant of the square: the same maxima,
    clamped to a legal divisor of L, or the whole 2L where L has none."""
    import jax.numpy as jnp
    itemsize = jnp.dtype(q_dtype).itemsize
    _planned.prefix = ('bd_' if block_diffusion else
                       'window_' if window > 0 else '')
    plan = {}
    for kernel in FLASH_KERNELS:
        wq, wk = want or _FLASH_MAX_BLOCKS[kernel]
        if want is None:
            if window > 0:
                # A tile wider than the window is mostly masked.
                cap = max(_FLASH_MIN_WINDOW_BLOCK, window)
                wq, wk = min(wq, cap), min(wk, cap)
            wq, wk = min(wq, sq), min(wk, sk)
            while (max(wq, wk) > LANES and flash_vmem_bytes(
                    kernel, wq, wk, d, itemsize, has_seg)
                    > VMEM_BUDGET_BYTES):
                if wq >= wk:
                    wq //= 2
                else:
                    wk //= 2
        if block_diffusion:
            bq, bk = clamp_flash_blocks(sq // 2, sk // 2, wq, wk, q_dtype,
                                        has_seg, kernel)
            legal = clamp_flash_blocks(sq, sk, bq, bk, q_dtype, has_seg,
                                       kernel)
            plan[kernel] = (bq if legal[0] == bq else sq,
                            bk if legal[1] == bk else sk)
            continue
        plan[kernel] = clamp_flash_blocks(sq, sk, wq, wk, q_dtype,
                                          has_seg, kernel)
    return plan


def flash_vmem_bytes(kernel: str, block_q: int, block_k: int, d: int,
                     itemsize: int, has_seg: bool = False) -> int:
    """VMEM one invocation of a flash kernel holds: every streamed
    block twice (the pipeline's double buffer), the float32 scratch
    (a [n, 1] column pads to 128 lanes, a [1, n] row to 8 sublanes),
    and the score-sized temporaries Mosaic keeps: two float32 and the
    cast one in the forward (s waits for the row maximum), one and the
    cast one in dq, two in dk/dv — read off the smallest
    `vmem_limit_bytes` each kernel still compiles under (v5e, jaxlib
    0.9.0: 10, 6 and 8 bytes an entry in bf16)."""
    q_blk = block_q * d * itemsize
    k_blk = block_k * d * itemsize
    col = block_q * LANES * 4           # [block_q, 1] or lane-replicated
    row = 8 * block_q * 4               # [1, block_q]
    seg = 2 * 8 * (block_q + block_k) * 4 if has_seg else 0
    tile = block_q * block_k
    if kernel == 'fwd':
        io = 2 * (2 * q_blk + 2 * k_blk + col)        # q, o; k, v; lse
        scratch = 2 * col + block_q * d * 4           # m, l; acc
        live = tile * (2 * 4 + itemsize)
    elif kernel == 'dq':
        io = 2 * (4 * q_blk + 2 * k_blk + col)        # q, dO, o, dq; lse
        scratch = col + block_q * d * 4               # delta; dq
        live = tile * (4 + itemsize)
    elif kernel == 'dkv':
        io = 2 * (2 * q_blk + 4 * k_blk + 2 * row)    # q, dO; k, v, dk, dv
        scratch = 2 * block_k * d * 4
        live = tile * 2 * 4
    else:
        raise ValueError(f'unknown flash kernel {kernel!r}')
    return io + seg + scratch + live


def flash_vmem_ok(plan: Dict[str, Tuple[int, int]], d: int, itemsize: int,
                  has_seg: bool = False) -> bool:
    """Whether each kernel of a tile plan fits the budget."""
    return all(flash_vmem_bytes(kernel, bq, bk, d, itemsize, has_seg)
               <= VMEM_BUDGET_BYTES for kernel, (bq, bk) in plan.items())


def flash_vmem_limit(need: int) -> Optional[int]:
    """`vmem_limit_bytes` for a flash kernel whose `flash_vmem_bytes`
    is `need`: None while twice that fits Mosaic's default scoped VMEM
    (the count cannot see what the compiler spills), else twice it."""
    return 2 * need if 2 * need > _MOSAIC_DEFAULT_VMEM_BYTES else None


GROUPED_FORMS = ('rows', 'rows_t', 'over_rows')

# What the counted working set of a grouped kernel may come to. The
# v5e has 128 MiB of VMEM; Mosaic scopes a kernel to the
# `vmem_limit_bytes` it is given, which is twice the count
# (`flash_vmem_limit`), so 96 MiB at most.
GROUPED_VMEM_BUDGET_BYTES = 48 * 1024 * 1024


def grouped_vmem_bytes(form: str, tm: int, tk: int, tn: int,
                       itemsize: int) -> int:
    """VMEM one invocation of a grouped kernel (ops/grouped_kernel.py)
    holds, counted from above: every streamed block twice (the
    pipeline's double buffer) and the float32 product. `rows` and
    `rows_t`: a row tile [tm, tk], a group's matrix tile [tk, tn], the
    result tile [tm, tn]; the product [tm, tn], and once more as the
    accumulator of a tiled contraction. `over_rows`: the row tiles
    [tm, tk] and [tm, tn], the result [tk, tn] twice, its float32
    accumulator, and a row tile's float32 temporaries. The smallest
    `vmem_limit_bytes` each kernel compiles under for the v5e (jaxlib
    0.9.0, bf16, whole matrices of 2,304 x 896 and 2,048 x 1,536, row
    tiles of 256 and 512) is 0.7 to 1.0 of this count."""
    if form == 'over_rows':
        return (2 * (tm * tk + tm * tn) * itemsize +
                tk * tn * (2 * itemsize + 4) + tm * max(tk, tn) * 4)
    if form not in GROUPED_FORMS:
        raise ValueError(f'unknown grouped product {form!r}')
    return 2 * (tm * tk + tk * tn + tm * tn) * itemsize + 2 * tm * tn * 4


def grouped_blocks(form: str, rows: int, a: int, b: int, groups: int,
                   dtype) -> Optional[Tuple[int, int, int]]:
    """The tile rule of the grouped kernels (ops/grouped_kernel.py):
    (tm, tk, tn) from the shape alone, for a product of `form` over
    `rows` rows and matrices [groups, a, b], or None where no legal
    tiles fit (the ladder then stands on `ragged_dot`).

    tm, the row tile: 256, or 128 where 256 does not divide the rows.
    tk and tn: the whole extents, so that a group's matrix is fetched
    once and stays in VMEM across the group's row tiles (`rows`: tk = a
    contracted, tn = b; `rows_t`: tk = b contracted, tn = a; `over_rows`:
    tk over a, tn over b, the result's tile). Where the count
    (`grouped_vmem_bytes`) is over `GROUPED_VMEM_BUDGET_BYTES` the
    larger of the two is halved, the columns on a tie, while a half is
    whole lanes. Extents that are no whole lanes, and rows that
    neither row tile divides, have no tiles.

    Fitted on one v5e in bf16 (PERF.md §6, PR 34), ms a call, at
    `sft-swa-moe-16k`'s calls (34,816 rows, 32,816 of them in 16
    groups; `over_rows` over 139,264) and `sft-moe-8k`'s (8,704 rows,
    8,192 in 8 groups; 69,632), `ragged_dot` first:

    | product | ragged_dot | tm 128 | 256 | 512 | 1,024 | 2,048 |
    | rows 2,304 x 896 | 3.39 | 0.915 | 0.894 | 0.958 | 1.126 | 1.477 |
    | rows 896 x 2,304 | 3.05 | 0.918 | 0.911 | 0.978 | 1.117 | 1.479 |
    | rows_t by 2,304 x 896 | 3.06 | 0.932 | 0.920 | 0.972 | 1.130 | 1.483 |
    | over_rows 2,304 x 896 | 4.03 | - | 1.062 | 1.090 | 1.169 | 1.516 |
    | rows 2,048 x 1,536 | 0.590 | 0.416 | 0.425 | 0.454 | - | - |
    | rows_t by 2,048 x 1,536 | 0.597 | 0.417 | 0.416 | 0.462 | - | - |
    | over_rows 2,048 x 1,536 | 0.667 | - | 0.522 | 0.538 | 0.612 | 0.970 |

    A small row tile wins: a tile that two groups share is multiplied
    once for each, and the matrix is resident either way (tiles of 256
    visit 151 for 136 at 2,176 rows a group, 512 visit 83 for 68).
    Tiling costs: half the columns 0.948 for 0.911 and 0.442 for 0.425
    (the rows are read twice), half the contraction 1.075 for 0.958 (an
    accumulator's pass, and another order of the float32 sums), columns
    of 128 lanes 1.86-2.19 for 0.96. The count of groups does not enter:
    256 rows win at 2,051 rows a group and at 1,024."""
    del groups
    import jax.numpy as jnp
    itemsize = jnp.dtype(dtype).itemsize
    tm = next((t for t in (256, 128)
               if rows % t == 0 and t % sublane_multiple(dtype) == 0), None)
    if tm is None or a % LANES or b % LANES:
        return None
    tk, tn = (b, a) if form == 'rows_t' else (a, b)
    while grouped_vmem_bytes(form, tm, tk, tn, itemsize) > \
            GROUPED_VMEM_BUDGET_BYTES:
        halves = [n % (2 * LANES) == 0 for n in (tk, tn)]
        if halves[1] and (tn >= tk or not halves[0]):
            tn //= 2
        elif halves[0]:
            tk //= 2
        else:
            return None
    return tm, tk, tn


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


def record_flash_plan(kernel: str, plan: Dict[str, Any]) -> None:
    """Remember the tile plan a flash kernel was traced with (extents
    and, per head, tiles visited, masked and skipped) and stamp it on
    the current trace span, beside `ops.path.flash_attention`. Planned
    for a sliding window or the block-diffusion mask (the `flash_blocks`
    call before this one), it is the plan of `window_<kernel>` or
    `bd_<kernel>`."""
    kernel = getattr(_planned, 'prefix', '') + kernel
    with _lock:
        _flash_plans[kernel] = dict(plan)
    from skypilot_tpu.utils import tracing
    span = tracing.current_span()
    if span is not None:
        span.set_attribute(
            f'ops.flash_plan.{kernel}',
            ' '.join(f'{k}={v}' for k, v in plan.items()))


def flash_plan_snapshot() -> Dict[str, Dict[str, Any]]:
    """flash kernel -> its last traced tile plan."""
    with _lock:
        return {k: dict(v) for k, v in _flash_plans.items()}


def moe_chunk_rows(tokens: int, k: int, held: int, experts: int) -> int:
    """The chunk rule: rows a trip of the expert layer's loop gathers,
    multiplies and adds back, from the shape alone: what an even router
    sends to the held experts, tokens * k * held / experts pairs, with
    a sixteenth of room, in whole sublane tiles of 8, and no more than
    the worst case, tokens * min(k, held) rows: 8,704 rows where 16,384
    tokens choose 4 of 64 experts and 8 are held, the whole worst case
    where every expert is. A trip's fixed costs outweigh its rows', so
    such a router's pairs go in one trip; a skewed router takes more
    trips of the same chunk. Against chunks of 2,048 and 4,096 on the
    v5e that is within 0.5 ms a layer or faster (10 ms at the worst
    case) at every count of held pairs but the few hundred just past a
    multiple of the chunk, where it is 2.2 ms slower at worst (PERF.md
    §6, PR 32)."""
    even = -(-tokens * k * held // experts)
    chunk = -(-(even + even // 16) // 8) * 8
    return min(chunk, -(-tokens * min(k, held) // 8) * 8)


def _record_plan(store: Dict[str, Any], attribute: str,
                 plan: Dict[str, Any]) -> None:
    with _lock:
        store.clear()
        store.update(plan)
    from skypilot_tpu.utils import tracing
    span = tracing.current_span()
    if span is not None:
        span.set_attribute(
            attribute, ' '.join(f'{k}={v}' for k, v in plan.items()))


def record_moe_plan(plan: Dict[str, Any]) -> None:
    """Remember the routing plan an expert layer was traced with
    (experts routed over and held, k, tokens, the worst-case rows and
    the loop's chunk) and stamp it on the current trace span as
    `ops.moe_plan`."""
    _record_plan(_moe_plan, 'ops.moe_plan', plan)


def record_bd_plan(plan: Dict[str, Any]) -> None:
    """Remember the block-diffusion objective a train step was traced
    with (block length, the data's length, the positions of the pass,
    the allowed pairs a head, the mask id) and stamp it on the current
    trace span as `ops.bd_plan`."""
    _record_plan(_bd_plan, 'ops.bd_plan', plan)


def bd_plan_snapshot() -> Dict[str, Any]:
    """The last traced block-diffusion objective ({} if none)."""
    with _lock:
        return dict(_bd_plan)


def moe_plan_snapshot() -> Dict[str, Any]:
    """The last traced expert layer's routing plan ({} if none)."""
    with _lock:
        return dict(_moe_plan)


def record_grouped_plan(form: str, a: int, b: int,
                        tiles: Tuple[int, int, int]) -> None:
    """Remember the tiles a grouped product of the expert layer was
    traced with, by its form and its matrix's extents, and stamp the
    plan so far on the current trace span as `ops.grouped_plan`."""
    with _lock:
        _grouped_plan[(form, a, b)] = tuple(tiles)
    from skypilot_tpu.utils import tracing
    span = tracing.current_span()
    if span is not None:
        span.set_attribute('ops.grouped_plan', grouped_plan_line())


def grouped_plan_line() -> str:
    """The grouped products traced last, one `<form> <rows>x<contracted
    or a>x<columns>` each ('' where the ladder stood on `ragged_dot`):
    sft's `grouped tile plan:` line."""
    with _lock:
        return ', '.join(f'{form} ' + 'x'.join(map(str, tiles))
                         for (form, _, _), tiles in _grouped_plan.items())


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
    """
    if not rungs:
        raise ValueError(f'ops.{op}: empty dispatch ladder')
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


def interpret_mode() -> bool:
    """Whether Pallas kernels run interpreted (any backend but the
    TPU) instead of compiled through Mosaic. The one place that
    decides it, and it asks JAX: a backend that cannot initialise is
    an error here, never a quiet "interpret"."""
    import jax
    return jax.default_backend() != 'tpu'


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
    """Clear the path and tile-plan snapshots (unit tests)."""
    with _lock:
        _paths.clear()
        _flash_plans.clear()
        _moe_plan.clear()
        _bd_plan.clear()
        _grouped_plan.clear()
