"""Paged decode attention as a Pallas TPU kernel.

One decode step attends each slot's single query against that slot's
pages of the shared KV pool. The XLA fallback materializes a contiguous
[slots, max_seq, H, d] view per layer (gather + write + re-read ≈ 3x the
KV bytes); this kernel DMAs exactly the pages each slot owns, selected
by a SCALAR-PREFETCHED block table in the k/v BlockSpec index maps — the
vLLM-paged-attention idea expressed the Pallas way
(pltpu.PrefetchScalarGridSpec; pallas_guide.md §PrefetchScalarGridSpec).

Grid: (slots, pages) — ONE block per page carrying ALL kv heads
([H, P, d], page-major pool layout), pages innermost ('arbitrary') so
the flash-style running-softmax scratch (m, l, acc) persists across a
slot's pages. A first cut used grid (slots, heads, pages) with [P, d]
blocks; at decode sizes the per-invocation + DMA-issue overhead of
slots*heads*pages tiny kernels made it SLOWER than the XLA gather —
folding heads into the block cut invocations 8x and made the DMAs 8x
bigger. Per-page work is skipped when the page is past the slot's
current length or not reserved (unreserved block-table entries are 0,
the dummy page). GQA: q heads of one kv head ride the sublane axis of
the [H, G, d] query block; the in-kernel matmuls batch over H.

Reference counterpart: none (the reference delegates to vLLM's CUDA
paged attention).
"""
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from skypilot_tpu.ops import dispatch

NEG_INF = -1e30
LANES = 128


def _kernel(tables_ref, lens_ref, q_ref, k_ref, v_ref, o_ref,
            m_scr, l_scr, acc_scr, *, page_size: int, num_pages: int,
            scale: float):
    s = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    pos = lens_ref[s]            # current token's position (attendable)
    page_id = tables_ref[s, j]

    # Skip pages past the slot's length and unreserved (dummy) entries.
    @pl.when(jnp.logical_and(j * page_size <= pos,
                             jnp.logical_or(page_id != 0, j == 0)))
    def _compute():
        q = q_ref[0]                        # [H, G, d]
        k = k_ref[0]                        # [H, P, d]
        st = jax.lax.dot_general(
            q, k, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32) * scale   # [H, G, P]
        idx = j * page_size + jax.lax.broadcasted_iota(
            jnp.int32, st.shape, 2)
        st = jnp.where(idx <= pos, st, NEG_INF)
        m_prev = m_scr[..., :1]             # [H, G, 1] (lane-replicated)
        m_cur = jnp.max(st, axis=2, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(st - m_new)             # [H, G, P]
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_scr[..., :1] + jnp.sum(p, axis=2,
                                                 keepdims=True)
        acc_scr[:] = acc_scr[:] * alpha + jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[0],
            (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)           # [H, G, d]
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(j == num_pages - 1)
    def _finalize():
        l = l_scr[..., :1]
        safe_l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_scr[:] / safe_l).astype(o_ref.dtype)


def _kernel_mq(tables_ref, lens_ref, q_ref, k_ref, v_ref, o_ref,
               m_scr, l_scr, acc_scr, *, page_size: int, num_pages: int,
               scale: float, g: int, t: int):
    """Multi-query (speculative-verify) variant: the query block folds
    T consecutive tokens into the sublane axis as [H, T*G, d]; row r is
    query token r // G at position lens[s] + r // G, masked causally
    per token. Same flash running-softmax scratch scheme as _kernel."""
    s = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    pos = lens_ref[s]            # FIRST query token's position
    page_id = tables_ref[s, j]

    # A page is useful if any of the T queries can attend into it.
    @pl.when(jnp.logical_and(j * page_size <= pos + (t - 1),
                             jnp.logical_or(page_id != 0, j == 0)))
    def _compute():
        q = q_ref[0]                        # [H, T*G, d]
        st = jax.lax.dot_general(
            q, k_ref[0], (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32) * scale   # [H, T*G, P]
        idx = j * page_size + jax.lax.broadcasted_iota(
            jnp.int32, st.shape, 2)
        t_idx = jax.lax.broadcasted_iota(jnp.int32, st.shape, 1) // g
        st = jnp.where(idx <= pos + t_idx, st, NEG_INF)
        m_prev = m_scr[..., :1]             # [H, T*G, 1]
        m_cur = jnp.max(st, axis=2, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(st - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_scr[..., :1] + jnp.sum(p, axis=2,
                                                 keepdims=True)
        acc_scr[:] = acc_scr[:] * alpha + jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[0],
            (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)           # [H, T*G, d]
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(j == num_pages - 1)
    def _finalize():
        l = l_scr[..., :1]
        safe_l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_scr[:] / safe_l).astype(o_ref.dtype)


def _kernel_q(tables_ref, lens_ref, q_ref, k_ref, v_ref, ks_ref,
              vs_ref, o_ref, m_scr, l_scr, acc_scr, *, page_size: int,
              num_pages: int, scale: float):
    """int8-KV variant of _kernel: k/v blocks are int8 pages and
    ks/vs are their per-token per-head f32 scales ([H, P] per page,
    infer/paged_cache.py layout). Dequantization folds into the two
    matmuls — scores multiply by the key scales (constant over d per
    (h, p), so (q . k_q) * s_k is exact), and the value scales fold
    into the probability weights before the PV product. The int8
    operands cast to the QUERY dtype, not f32: every int8 code
    (-127..127) is exactly representable in bf16, so the matmuls run
    at full MXU rate with the same f32-accumulated result."""
    s = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    pos = lens_ref[s]
    page_id = tables_ref[s, j]

    @pl.when(jnp.logical_and(j * page_size <= pos,
                             jnp.logical_or(page_id != 0, j == 0)))
    def _compute():
        q = q_ref[0]                        # [H, G, d]
        k = k_ref[0].astype(q_ref.dtype)    # [H, P, d] int8: exact
        st = jax.lax.dot_general(
            q, k, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32) * scale   # [H, G, P]
        st = st * ks_ref[0][:, None, :]     # key scales [H, 1, P]
        idx = j * page_size + jax.lax.broadcasted_iota(
            jnp.int32, st.shape, 2)
        st = jnp.where(idx <= pos, st, NEG_INF)
        m_prev = m_scr[..., :1]
        m_cur = jnp.max(st, axis=2, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(st - m_new)             # [H, G, P]
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_scr[..., :1] + jnp.sum(p, axis=2,
                                                 keepdims=True)
        # Value scales fold into the weights; the weighted p rounds to
        # the query dtype like the fp kernel's p.astype(v_ref.dtype).
        pd = (p * vs_ref[0][:, None, :]).astype(q_ref.dtype)
        acc_scr[:] = acc_scr[:] * alpha + jax.lax.dot_general(
            pd, v_ref[0].astype(q_ref.dtype),
            (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)           # [H, G, d]
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(j == num_pages - 1)
    def _finalize():
        l = l_scr[..., :1]
        safe_l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_scr[:] / safe_l).astype(o_ref.dtype)


def _kernel_mq_q(tables_ref, lens_ref, q_ref, k_ref, v_ref, ks_ref,
                 vs_ref, o_ref, m_scr, l_scr, acc_scr, *,
                 page_size: int, num_pages: int, scale: float, g: int,
                 t: int):
    """int8-KV variant of _kernel_mq (speculative multi-query verify):
    same scale folding and query-dtype casting as _kernel_q over the
    [H, T*G, d] query block."""
    s = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    pos = lens_ref[s]
    page_id = tables_ref[s, j]

    @pl.when(jnp.logical_and(j * page_size <= pos + (t - 1),
                             jnp.logical_or(page_id != 0, j == 0)))
    def _compute():
        q = q_ref[0]                        # [H, T*G, d]
        st = jax.lax.dot_general(
            q, k_ref[0].astype(q_ref.dtype),
            (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32) * scale   # [H, T*G, P]
        st = st * ks_ref[0][:, None, :]
        idx = j * page_size + jax.lax.broadcasted_iota(
            jnp.int32, st.shape, 2)
        t_idx = jax.lax.broadcasted_iota(jnp.int32, st.shape, 1) // g
        st = jnp.where(idx <= pos + t_idx, st, NEG_INF)
        m_prev = m_scr[..., :1]
        m_cur = jnp.max(st, axis=2, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(st - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_scr[..., :1] + jnp.sum(p, axis=2,
                                                 keepdims=True)
        pd = (p * vs_ref[0][:, None, :]).astype(q_ref.dtype)
        acc_scr[:] = acc_scr[:] * alpha + jax.lax.dot_general(
            pd, v_ref[0].astype(q_ref.dtype),
            (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)           # [H, T*G, d]
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(j == num_pages - 1)
    def _finalize():
        l = l_scr[..., :1]
        safe_l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_scr[:] / safe_l).astype(o_ref.dtype)


@jax.jit
def paged_decode_attention_q(q: jax.Array, k_pool: jax.Array,
                             v_pool: jax.Array, k_scale: jax.Array,
                             v_scale: jax.Array, tables: jax.Array,
                             lengths: jax.Array) -> jax.Array:
    """int8-KV single-query paged decode: same contract as
    paged_decode_attention plus the scale pools [n_pages, Hkv, P]
    (one layer). Scale blocks ride their own scalar-prefetched
    BlockSpec indexed by the same table lookup as the pages."""
    s_slots, hq, d = q.shape
    _, hkv, page_size, _ = k_pool.shape
    assert hq % hkv == 0, (hq, hkv)
    g = hq // hkv
    mp = tables.shape[1]
    scale = d ** -0.5
    qg = q.reshape(s_slots, hkv, g, d)

    kernel = functools.partial(_kernel_q, page_size=page_size,
                               num_pages=mp, scale=scale)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(s_slots, mp),
        in_specs=[
            pl.BlockSpec((1, hkv, g, d),
                         lambda s, j, tbl, lns: (s, 0, 0, 0)),
            pl.BlockSpec((1, hkv, page_size, d),
                         lambda s, j, tbl, lns: (tbl[s, j], 0, 0, 0)),
            pl.BlockSpec((1, hkv, page_size, d),
                         lambda s, j, tbl, lns: (tbl[s, j], 0, 0, 0)),
            pl.BlockSpec((1, hkv, page_size),
                         lambda s, j, tbl, lns: (tbl[s, j], 0, 0)),
            pl.BlockSpec((1, hkv, page_size),
                         lambda s, j, tbl, lns: (tbl[s, j], 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, hkv, g, d),
                               lambda s, j, tbl, lns: (s, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((hkv, g, LANES), jnp.float32),   # running max
            pltpu.VMEM((hkv, g, LANES), jnp.float32),   # running sum
            pltpu.VMEM((hkv, g, d), jnp.float32),       # out accumulator
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((s_slots, hkv, g, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=('parallel', 'arbitrary')),
        interpret=dispatch.interpret_mode(),
    )(tables.astype(jnp.int32), lengths.astype(jnp.int32), qg, k_pool,
      v_pool, k_scale, v_scale)
    return out.reshape(s_slots, hq, d)


@jax.jit
def paged_decode_attention_mq_q(q: jax.Array, k_pool: jax.Array,
                                v_pool: jax.Array, k_scale: jax.Array,
                                v_scale: jax.Array, tables: jax.Array,
                                lengths: jax.Array) -> jax.Array:
    """int8-KV multi-query paged decode (speculative verify): same
    contract as paged_decode_attention_mq plus the scale pools."""
    s_slots, t, hq, d = q.shape
    _, hkv, page_size, _ = k_pool.shape
    assert hq % hkv == 0, (hq, hkv)
    g = hq // hkv
    mp = tables.shape[1]
    scale = d ** -0.5
    qg = q.reshape(s_slots, t, hkv, g, d).transpose(0, 2, 1, 3, 4) \
         .reshape(s_slots, hkv, t * g, d)

    kernel = functools.partial(_kernel_mq_q, page_size=page_size,
                               num_pages=mp, scale=scale, g=g, t=t)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(s_slots, mp),
        in_specs=[
            pl.BlockSpec((1, hkv, t * g, d),
                         lambda s, j, tbl, lns: (s, 0, 0, 0)),
            pl.BlockSpec((1, hkv, page_size, d),
                         lambda s, j, tbl, lns: (tbl[s, j], 0, 0, 0)),
            pl.BlockSpec((1, hkv, page_size, d),
                         lambda s, j, tbl, lns: (tbl[s, j], 0, 0, 0)),
            pl.BlockSpec((1, hkv, page_size),
                         lambda s, j, tbl, lns: (tbl[s, j], 0, 0)),
            pl.BlockSpec((1, hkv, page_size),
                         lambda s, j, tbl, lns: (tbl[s, j], 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, hkv, t * g, d),
                               lambda s, j, tbl, lns: (s, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((hkv, t * g, LANES), jnp.float32),  # running max
            pltpu.VMEM((hkv, t * g, LANES), jnp.float32),  # running sum
            pltpu.VMEM((hkv, t * g, d), jnp.float32),      # accumulator
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((s_slots, hkv, t * g, d),
                                       q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=('parallel', 'arbitrary')),
        interpret=dispatch.interpret_mode(),
    )(tables.astype(jnp.int32), lengths.astype(jnp.int32), qg, k_pool,
      v_pool, k_scale, v_scale)
    return out.reshape(s_slots, hkv, t, g, d).transpose(0, 2, 1, 3, 4) \
              .reshape(s_slots, t, hq, d)


@jax.jit
def paged_decode_attention_mq(q: jax.Array, k_pool: jax.Array,
                              v_pool: jax.Array, tables: jax.Array,
                              lengths: jax.Array) -> jax.Array:
    """Multi-query paged decode (speculative verify): q [S, T, Hq, d] —
    T consecutive tokens per slot, token t at position lengths[s] + t
    (all T tokens' KV already appended). Returns [S, T, Hq, d].
    """
    s_slots, t, hq, d = q.shape
    _, hkv, page_size, _ = k_pool.shape
    assert hq % hkv == 0, (hq, hkv)
    g = hq // hkv
    mp = tables.shape[1]
    scale = d ** -0.5
    # [S, T, Hkv, G, d] -> [S, Hkv, T, G, d] -> [S, Hkv, T*G, d]:
    # row r of the sublane axis is (token r // G, q-head-in-group r % G).
    qg = q.reshape(s_slots, t, hkv, g, d).transpose(0, 2, 1, 3, 4) \
         .reshape(s_slots, hkv, t * g, d)

    kernel = functools.partial(_kernel_mq, page_size=page_size,
                               num_pages=mp, scale=scale, g=g, t=t)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(s_slots, mp),
        in_specs=[
            pl.BlockSpec((1, hkv, t * g, d),
                         lambda s, j, tbl, lns: (s, 0, 0, 0)),
            pl.BlockSpec((1, hkv, page_size, d),
                         lambda s, j, tbl, lns: (tbl[s, j], 0, 0, 0)),
            pl.BlockSpec((1, hkv, page_size, d),
                         lambda s, j, tbl, lns: (tbl[s, j], 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, hkv, t * g, d),
                               lambda s, j, tbl, lns: (s, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((hkv, t * g, LANES), jnp.float32),  # running max
            pltpu.VMEM((hkv, t * g, LANES), jnp.float32),  # running sum
            pltpu.VMEM((hkv, t * g, d), jnp.float32),      # accumulator
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((s_slots, hkv, t * g, d),
                                       q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=('parallel', 'arbitrary')),
        interpret=dispatch.interpret_mode(),
    )(tables.astype(jnp.int32), lengths.astype(jnp.int32), qg, k_pool,
      v_pool)
    return out.reshape(s_slots, hkv, t, g, d).transpose(0, 2, 1, 3, 4) \
              .reshape(s_slots, t, hq, d)


@jax.jit
def paged_decode_attention(q: jax.Array, k_pool: jax.Array,
                           v_pool: jax.Array, tables: jax.Array,
                           lengths: jax.Array) -> jax.Array:
    """q: [S, Hq, d] (one token per slot); k_pool/v_pool:
    [n_pages, Hkv, P, d] (one layer, page-major); tables: [S, mp] int32;
    lengths: [S] int32 — the position each slot's query token sits at
    (it attends positions <= lengths[s], its own KV already written).

    Returns [S, Hq, d].
    """
    s_slots, hq, d = q.shape
    _, hkv, page_size, _ = k_pool.shape
    assert hq % hkv == 0, (hq, hkv)
    g = hq // hkv
    mp = tables.shape[1]
    scale = d ** -0.5
    qg = q.reshape(s_slots, hkv, g, d)

    kernel = functools.partial(_kernel, page_size=page_size,
                               num_pages=mp, scale=scale)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(s_slots, mp),
        in_specs=[
            pl.BlockSpec((1, hkv, g, d),
                         lambda s, j, tbl, lns: (s, 0, 0, 0)),
            pl.BlockSpec((1, hkv, page_size, d),
                         lambda s, j, tbl, lns: (tbl[s, j], 0, 0, 0)),
            pl.BlockSpec((1, hkv, page_size, d),
                         lambda s, j, tbl, lns: (tbl[s, j], 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, hkv, g, d),
                               lambda s, j, tbl, lns: (s, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((hkv, g, LANES), jnp.float32),   # running max
            pltpu.VMEM((hkv, g, LANES), jnp.float32),   # running sum
            pltpu.VMEM((hkv, g, d), jnp.float32),       # out accumulator
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((s_slots, hkv, g, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=('parallel', 'arbitrary')),
        interpret=dispatch.interpret_mode(),
    )(tables.astype(jnp.int32), lengths.astype(jnp.int32), qg, k_pool,
      v_pool)
    return out.reshape(s_slots, hq, d)
