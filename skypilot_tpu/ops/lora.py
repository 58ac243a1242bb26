"""Grouped multi-LoRA delta as a Pallas TPU kernel + dispatch ladder.

The batched multi-LoRA delta (models/llama.py ``_lora_delta``) is two
rank-r contractions per projection, preceded by a per-sequence gather
of each request's A/B out of the stacked ``lora`` collection. The XLA
path materializes the gathered [B, in, r] / [B, r, out] operands in
HBM before contracting; this module fuses the gather INTO the kernel —
the adapter id rides a scalar-prefetched BlockSpec index map (the same
trick the paged attention kernels use for block tables), so each grid
step DMAs only its own sequence's A/B slices straight from the stack.

Two input shapes, one op (``lora_grouped`` in
``skyt_ops_kernel_path_total``):

* per-sequence ids (``lora_ids`` of shape [B] — the decode path and
  uniform prefill rows): grid (B, S-blocks), A/B blocks selected by
  ``ids[b]`` at index-map time; no accumulation, each grid step owns
  its output block.
* per-token ids (``lora_ids`` of shape [B, S] — ragged prefill packs
  mixing adapters in one packed row): tokens flatten to [T, in] and
  the grid becomes (T-blocks, adapters) with adapters innermost; each
  adapter pass masks the token block to its own segments and
  accumulates into the output block (init under ``pl.when(k == 0)``).

The final rung is the pure-XLA floor: for per-sequence ids the exact
gather-einsum the model ran before this op existed; for per-token ids
a ``lax.scan`` over adapters with the same mask-and-accumulate math
(gathering per token would materialize [B, S, in, r]). The per-id
alpha/rank scale is applied OUTSIDE the kernels, as the floor's final
multiply, so every rung shares that op byte-for-byte. Ladder
selection, fault injection (``ops.lowering``), and path accounting
ride ops/dispatch.py; the token/seq block is the largest legal
divisor of the dim up to ``_DEFAULT_BLOCK``.
"""
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from skypilot_tpu.ops import dispatch
from skypilot_tpu.parallel import mesh as mesh_lib

OP = 'lora_grouped'

# Token/seq block extent asked for, clamped per shape by legality.
_DEFAULT_BLOCK = 256


# ------------------------------------------------------------ kernels
def _dot(x, w):
    """x @ w accumulated in f32 and rounded once to x's dtype: Mosaic
    refuses a matmul whose accumulator is narrower than 32 bits, and
    this is the rounding the XLA floor's einsum performs."""
    return jnp.dot(x, w.astype(x.dtype),
                   preferred_element_type=jnp.float32).astype(x.dtype)


def _gather_kernel(ids_ref, x_ref, a_ref, b_ref, o_ref):
    """Per-sequence ids: one grid step = one (sequence, seq-block);
    the A/B blocks arriving here were already selected by ids[b] in
    the BlockSpec index maps — the gather happened in the DMA."""
    del ids_ref  # consumed by the index maps
    x = x_ref[0]                               # [bs, in]
    t = _dot(x, a_ref[0])                      # [bs, r]
    o_ref[0] = _dot(t, b_ref[0])


def _grouped_kernel(x_ref, ids_ref, a_ref, b_ref, o_ref):
    """Per-token ids: grid (T-blocks, adapters), adapters innermost so
    the output block stays resident across the accumulation sweep.
    Adapter 0 is the zeros no-op entry: its pass adds exact zeros, so
    no special-casing is needed for parity with the floor."""
    k = pl.program_id(1)

    @pl.when(k == 0)
    def _init():
        o_ref[:] = jnp.zeros_like(o_ref)

    x = x_ref[:]                                   # [bt, in]
    mask = (ids_ref[:] == k).astype(x.dtype)       # [bt, 1]
    t = _dot(x * mask, a_ref[0])
    o_ref[:] += _dot(t, b_ref[0])


# ----------------------------------------------------- pallas wrappers
@functools.partial(jax.jit, static_argnames=('block_s',))
def _pallas_gather(x, a, b, lora_ids, lora_scale,
                   block_s: int) -> jax.Array:
    bsz, seq, din = x.shape
    r = a.shape[-1]
    dout = b.shape[-1]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(bsz, seq // block_s),
        in_specs=[
            pl.BlockSpec((1, block_s, din), lambda bi, j, ids: (bi, j, 0)),
            pl.BlockSpec((1, din, r), lambda bi, j, ids: (ids[bi], 0, 0)),
            pl.BlockSpec((1, r, dout), lambda bi, j, ids: (ids[bi], 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_s, dout),
                               lambda bi, j, ids: (bi, j, 0)),
    )
    d = pl.pallas_call(
        _gather_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((bsz, seq, dout), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=('parallel', 'parallel')),
        interpret=dispatch.interpret_mode(),
    )(lora_ids.astype(jnp.int32), x, a, b)
    return d * lora_scale[:, None, None].astype(x.dtype)


@functools.partial(jax.jit, static_argnames=('block_t',))
def _pallas_grouped(x, a, b, lora_ids, lora_scale,
                    block_t: int) -> jax.Array:
    bsz, seq, din = x.shape
    n, _, r = a.shape
    dout = b.shape[-1]
    tok = bsz * seq
    xt = x.reshape(tok, din)
    ids = lora_ids.reshape(tok, 1).astype(jnp.int32)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=0,
        grid=(tok // block_t, n),
        in_specs=[
            pl.BlockSpec((block_t, din), lambda j, k: (j, 0)),
            pl.BlockSpec((block_t, 1), lambda j, k: (j, 0)),
            pl.BlockSpec((1, din, r), lambda j, k: (k, 0, 0)),
            pl.BlockSpec((1, r, dout), lambda j, k: (k, 0, 0)),
        ],
        out_specs=pl.BlockSpec((block_t, dout), lambda j, k: (j, 0)),
    )
    d = pl.pallas_call(
        _grouped_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((tok, dout), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=('parallel', 'arbitrary')),
        interpret=dispatch.interpret_mode(),
    )(xt, ids, a, b)
    return d.reshape(bsz, seq, dout) * \
        lora_scale[..., None].astype(x.dtype)


# --------------------------------------------------------- XLA floors
def _xla_gather(x, a, b, lora_ids, lora_scale) -> jax.Array:
    """The exact einsum path _lora_delta ran before this op existed —
    the correctness floor per-sequence requests must stay byte-
    identical to."""
    dtype = x.dtype
    ga = jnp.take(a, lora_ids, axis=0).astype(dtype)    # [B, in, r]
    gb = jnp.take(b, lora_ids, axis=0).astype(dtype)    # [B, r, out]
    t = jnp.einsum('bsi,bir->bsr', x, ga)
    d = jnp.einsum('bsr,bro->bso', t, gb)
    return d * lora_scale[:, None, None].astype(dtype)


def _xla_grouped(x, a, b, lora_ids, lora_scale) -> jax.Array:
    """Per-token floor: scan over adapters with mask-and-accumulate —
    a per-token gather would materialize [B, S, in, r]. Adapter 0 is
    skipped (zeros by construction; its tokens contribute exactly 0)."""
    dtype = x.dtype
    n = a.shape[0]
    dout = b.shape[-1]
    acc0 = jnp.zeros(x.shape[:2] + (dout,), dtype)
    if n <= 1:
        return acc0

    def body(acc, k):
        mask = (lora_ids == k).astype(dtype)            # [B, S]
        t = jnp.einsum('bsi,ir->bsr', x * mask[..., None],
                       a[k].astype(dtype))
        d = jnp.einsum('bsr,ro->bso', t, b[k].astype(dtype))
        return acc + d, None

    acc, _ = jax.lax.scan(body, acc0, jnp.arange(1, n))
    return acc * lora_scale[..., None].astype(dtype)


# ------------------------------------------------------------ dispatch
def _vmem_bytes(block: int, din: int, r: int, dout: int,
                itemsize: int) -> int:
    """Per-invocation VMEM working set: x/out token blocks + one
    adapter's A/B + the rank-r intermediate."""
    io = (block * din + block * dout + din * r + r * dout) * itemsize
    return io + block * r * itemsize


def grouped_lora_delta(x, a, b, lora_ids, lora_scale) -> jax.Array:
    """Batched multi-LoRA delta through the dispatch ladder.

    x: [B, S, in] activations (model dtype); a: [N, in, r] stacked
    down-projections; b: [N, r, out]; lora_ids: [B] (per-sequence) or
    [B, S] (per-token, ragged mixed packs) int adapter ids;
    lora_scale: alpha/rank per id, same shape as lora_ids. Returns the
    [B, S, out] delta in x's dtype."""
    bsz, seq, din = x.shape
    r = a.shape[-1]
    dout = b.shape[-1]
    per_token = lora_ids.ndim == 2
    itemsize = jnp.dtype(x.dtype).itemsize
    mult = dispatch.sublane_multiple(x.dtype)
    # Per-token ids flatten the tokens; per-sequence ids block the seq.
    dim = bsz * seq if per_token else seq
    kernel, floor = ((_pallas_grouped, _xla_grouped) if per_token else
                     (_pallas_gather, _xla_gather))

    # Mosaic kernels cannot be partitioned by GSPMD, and the
    # projections these deltas join are sharded on their in or out
    # features by the model's rules: under a multi-device mesh the
    # einsum floor, which GSPMD partitions like the base matmul, is
    # the required path ('xla_native'), not a descent.
    mesh = mesh_lib.current_mesh()
    if mesh is not None and mesh.size > 1:
        return dispatch.run_ladder(OP, [('xla_native', functools.partial(
            floor, x, a, b, lora_ids, lora_scale))])

    blk = dispatch.choose_block(dim, _DEFAULT_BLOCK, mult)
    rungs = []
    if dispatch.block_dim_ok(blk, dim, mult) and \
            _vmem_bytes(blk, din, r, dout, itemsize) <= \
            dispatch.VMEM_BUDGET_BYTES:
        rungs.append(('pallas', functools.partial(
            kernel, x, a, b, lora_ids, lora_scale, blk)))
    rungs.append(('xla', functools.partial(
        floor, x, a, b, lora_ids, lora_scale)))
    return dispatch.run_ladder(OP, rungs)
