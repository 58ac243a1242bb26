"""Flash attention (forward + backward) as Pallas TPU kernels.

Blockwise online-softmax attention (Flash-Attention-2 schedule):

* forward: grid over (batch, q_heads, q_blocks, k_blocks) with the k axis
  innermost so the VMEM scratch accumulators (running max m, running sum
  l, output acc) persist across k iterations of one q block; also emits
  the per-row logsumexp L for the backward. Causal masking skips
  fully-masked k blocks via pl.when; GQA is folded into the k/v index_map
  (head h reads kv head h // group). Segment ids (packed sequences) are
  masked in-kernel.
* backward: two kernels, both recomputing p = exp(s - L) blockwise from
  the saved residuals (q, k, v, L, delta = rowsum(dO*O)) — no O(S^2)
  materialization:
    - dq kernel: same grid as forward (k innermost), accumulates
      dq += ds @ k in VMEM scratch;
    - dk/dv kernel: grid (batch, q_heads, k_blocks, q_blocks) with q
      innermost, accumulates dk/dv per *query* head; the GQA group sum
      down to kv heads happens outside the kernel (one cheap XLA
      reduce), avoiding non-contiguous output revisits.

Kernel conventions follow /opt/skills/guides/pallas_guide.md (block
specs, scratch via pl.pallas_call scratch_shapes, MXU-aligned tiles).
"""
import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from skypilot_tpu.ops import dispatch
from skypilot_tpu.utils import env

NEG_INF = -1e30

# Row statistics (lse, delta) are carried as [..., seq, LANES] arrays with
# the value replicated across the 128 lanes: Mosaic requires the last two
# dims of every block to be (8k, 128)-tileable or equal to the array dims,
# so a (1, block_q)-shaped row block does not lower. Same layout as
# jax.experimental.pallas.ops.tpu.flash_attention (its MIN_BLOCK_SIZE).
LANES = 128


def bwd_impl_choice() -> str:
    """'pallas' (default) or 'xla' — SKYT_FLASH_BWD overrides. The XLA
    path recomputes reference attention under custom_vjp (the round-1
    behavior); the escape hatch exists so a pathological kernel compile
    can never take down a training run."""
    return env.get('SKYT_FLASH_BWD', 'pallas')

DEFAULT_BLOCK_Q = 256
DEFAULT_BLOCK_K = 256


def _block_mask(s, qi, ki, block_q, block_k, causal, window,
                q_seg_ref, k_seg_ref):
    """Apply causal / sliding-window / segment masking to a
    [block_q, block_k] score block. window > 0 (Mistral, every other
    Gemma-2 layer, Phi-3): query p also requires p - k_pos < window.
    Returns the masked scores."""
    if causal or window > 0:
        q_pos = qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 0)
        k_pos = ki * block_k + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1)
        if causal:
            s = jnp.where(q_pos >= k_pos, s, NEG_INF)
        if window > 0:
            s = jnp.where(q_pos - k_pos < window, s, NEG_INF)
    if q_seg_ref is not None:
        q_seg = q_seg_ref[0, 0]           # [block_q]
        k_seg = k_seg_ref[0, 0]           # [block_k]
        s = jnp.where(q_seg[:, None] == k_seg[None, :], s, NEG_INF)
    return s


def _qk_block_overlaps(qi, ki, block_q, block_k, causal, window):
    """Traced bool: does this (q block, k block) pair contain ANY
    unmasked (q, k) entry under causal+window? Used to skip whole
    blocks: above the diagonal (causal) and, with a window, entirely
    below it."""
    cond = True
    if causal:
        cond = jnp.logical_and(cond, ki * block_k < (qi + 1) * block_q)
    if window > 0:
        # Highest k in the block must reach the lowest q's window
        # start: (ki+1)*bk - 1 >= qi*bq - (window - 1).
        cond = jnp.logical_and(
            cond, (ki + 1) * block_k > qi * block_q - window + 1)
    return cond


def _fwd_kernel(*refs, scale: float, causal: bool, window: int,
                block_q: int, block_k: int, num_k_blocks: int,
                has_seg: bool):
    if has_seg:
        (q_ref, k_ref, v_ref, q_seg_ref, k_seg_ref,
         o_ref, lse_ref, m_scr, l_scr, acc_scr) = refs
    else:
        q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr = refs
        q_seg_ref = k_seg_ref = None
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    def _compute():
        q = q_ref[0, 0]                   # [block_q, d]
        k = k_ref[0, 0]                   # [block_k, d]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # [bq, bk]
        s = _block_mask(s, qi, ki, block_q, block_k, causal, window,
                        q_seg_ref, k_seg_ref)
        m_prev = m_scr[:]                 # [bq, 1]
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        # Exact 0 for masked entries: a row whose FIRST visited block is
        # fully masked has m_new == NEG_INF, and exp(NEG_INF - NEG_INF)
        # would be 1 — with a sliding window that case is routine (rows
        # near the end of a q block whose window starts past this k
        # block), so guard by value rather than rely on underflow.
        p = jnp.where(s <= NEG_INF / 2, 0.0, jnp.exp(s - m_new))
        alpha = jnp.exp(m_prev - m_new)   # [bq, 1]
        l_new = alpha * l_scr[:] + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[:] = acc_scr[:] * alpha + jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[0, 0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[:] = m_new
        l_scr[:] = l_new

    if causal or window > 0:
        pl.when(_qk_block_overlaps(qi, ki, block_q, block_k, causal,
                                   window))(_compute)
    else:
        _compute()

    @pl.when(ki == num_k_blocks - 1)
    def _finalize():
        l = l_scr[:]
        safe_l = jnp.where(l == 0.0, 1.0, l)  # fully-masked rows -> out 0
        o_ref[0, 0] = (acc_scr[:] / safe_l).astype(o_ref.dtype)
        # Logsumexp residual; 0 for fully-masked rows so the backward's
        # p = exp(NEG_INF - 0) is exactly 0.
        lse = jnp.where(l > 0.0, m_scr[:] + jnp.log(safe_l), 0.0)
        lse_ref[0, 0] = jnp.broadcast_to(lse, (lse.shape[0], LANES))


def _dq_kernel(*refs, scale: float, causal: bool, window: int,
               block_q: int, block_k: int, num_k_blocks: int,
               has_seg: bool):
    if has_seg:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
         q_seg_ref, k_seg_ref, dq_ref, dq_scr) = refs
    else:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
         dq_ref, dq_scr) = refs
        q_seg_ref = k_seg_ref = None
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    def _compute():
        q = q_ref[0, 0]                   # [bq, d]
        k = k_ref[0, 0]                   # [bk, d]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        s = _block_mask(s, qi, ki, block_q, block_k, causal, window,
                        q_seg_ref, k_seg_ref)
        lse = lse_ref[0, 0][:, :1]        # [bq, 1] (lane-replicated)
        p = jnp.exp(s - lse)              # [bq, bk]
        do = do_ref[0, 0]                 # [bq, d]
        dp = jax.lax.dot_general(
            do, v_ref[0, 0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)  # [bq, bk]
        delta = delta_ref[0, 0][:, :1]    # [bq, 1]
        ds = p * (dp - delta) * scale
        dq_scr[:] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    if causal or window > 0:
        pl.when(_qk_block_overlaps(qi, ki, block_q, block_k, causal,
                                   window))(_compute)
    else:
        _compute()

    @pl.when(ki == num_k_blocks - 1)
    def _finalize():
        dq_ref[0, 0] = dq_scr[:].astype(dq_ref.dtype)


def _dkv_kernel(*refs, scale: float, causal: bool, window: int,
                block_q: int, block_k: int, num_q_blocks: int,
                has_seg: bool):
    if has_seg:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
         q_seg_ref, k_seg_ref, dk_ref, dv_ref, dk_scr, dv_scr) = refs
    else:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
         dk_ref, dv_ref, dk_scr, dv_scr) = refs
        q_seg_ref = k_seg_ref = None
    ki = pl.program_id(2)
    qi = pl.program_id(3)

    @pl.when(qi == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    def _compute():
        q = q_ref[0, 0]                   # [bq, d]
        k = k_ref[0, 0]                   # [bk, d]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        s = _block_mask(s, qi, ki, block_q, block_k, causal, window,
                        q_seg_ref, k_seg_ref)
        lse = lse_ref[0, 0][:, :1]        # [bq, 1] (lane-replicated)
        p = jnp.exp(s - lse)              # [bq, bk]
        do = do_ref[0, 0]                 # [bq, d]
        dv_scr[:] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)  # [bk, d]
        dp = jax.lax.dot_general(
            do, v_ref[0, 0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)  # [bq, bk]
        delta = delta_ref[0, 0][:, :1]    # [bq, 1]
        ds = p * (dp - delta) * scale
        dk_scr[:] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)  # [bk, d]

    if causal or window > 0:
        # Same overlap predicate, evaluated from this kernel's
        # (ki outer, qi inner) grid order.
        pl.when(_qk_block_overlaps(qi, ki, block_q, block_k, causal,
                                   window))(_compute)
    else:
        _compute()

    @pl.when(qi == num_q_blocks - 1)
    def _finalize():
        dk_ref[0, 0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_scr[:].astype(dv_ref.dtype)


def flash_attention_fwd_lse(q: jax.Array, k: jax.Array, v: jax.Array,
                            causal: bool = True):
    """FORWARD-ONLY flash attention that also returns the per-row
    logsumexp: (out [B,Sq,Hq,D], lse [B,Hq,Sq] f32).

    For callers that merge partial attentions themselves (ring
    attention's cross-chunk online-softmax combine). Not differentiable
    — wrap it in your own custom_vjp (parallel/ring_attention.py routes
    its backward through the einsum path).
    """
    out, lse = _flash_fwd_impl(q, k, v, None, causal, DEFAULT_BLOCK_Q,
                               DEFAULT_BLOCK_K, 0)
    return out, lse[..., 0]


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    causal: bool = True,
                    segment_ids: Optional[jax.Array] = None,
                    block_q: int = DEFAULT_BLOCK_Q,
                    block_k: int = DEFAULT_BLOCK_K,
                    window: int = 0) -> jax.Array:
    """q: [B, Sq, Hq, D]; k, v: [B, Sk, Hkv, D] -> [B, Sq, Hq, D].

    block_q/block_k are REQUESTS, not contracts: they are clamped
    through the divisibility-safe selector (ops/dispatch.py) to a
    tile-aligned divisor of the seq dims or to the full dims, so any
    legal input shape lowers — decode shapes included. Serving/train
    call sites should go through ops.attention's dispatch ladder,
    which adds the conservative-Pallas and XLA fallback rungs.

    segment_ids: optional [B, S] int32 packed-sequence ids, masked
    in-kernel (forward and backward).
    window: sliding-window attention (> 0: query p sees k in
    (p - window, p]). Out-of-window blocks skip their COMPUTE (the
    same pl.when structure as the causal above-diagonal skip — a FLOP
    saving; the grid still fetches every k/v block, so memory traffic
    is unchanged).
    """
    return _flash(q, k, v, segment_ids, causal, block_q, block_k,
                  window)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def _flash(q, k, v, segment_ids, causal, block_q, block_k, window):
    out, _ = _flash_fwd_impl(q, k, v, segment_ids, causal, block_q,
                             block_k, window)
    return out


def _shape_checks(q, k, block_q, block_k, has_seg=False):
    """Shape-robust block selection (docs/kernels.md): requested
    blocks are CLAMPED through the divisibility-safe selector — to a
    tile-aligned divisor of the seq dim, or to the full dim (always
    legal) — so any legal input shape lowers, decode shapes like
    (4, 32, 8, 256) included. A block pair whose
    VMEM working set cannot fit is refused at TRACE time (a
    ValueError the dispatch ladder catches), because the Mosaic
    compile error it would become is not catchable."""
    b, sq, hq, d = q.shape
    _, sk, hkv, _ = k.shape
    if hq % hkv != 0:
        raise ValueError(
            f'q heads ({hq}) must be a multiple of kv heads ({hkv})')
    block_q, block_k = dispatch.flash_blocks(sq, sk, block_q, block_k,
                                             q.dtype, has_seg)
    if not dispatch.interpret_mode() and not dispatch.flash_vmem_ok(
            block_q, block_k, d, jnp.dtype(q.dtype).itemsize):
        raise ValueError(
            f'flash blocks ({block_q}, {block_k}) x d={d} exceed the '
            f'VMEM budget ({dispatch.VMEM_BUDGET_BYTES}B) — refusing '
            'a certain Mosaic compile failure')
    return b, sq, sk, hq, hkv, d, block_q, block_k


def _flash_fwd_impl(q, k, v, segment_ids, causal, block_q, block_k,
                    window=0):
    has_seg = segment_ids is not None
    b, sq, sk, hq, hkv, d, block_q, block_k = _shape_checks(
        q, k, block_q, block_k, has_seg)
    group = hq // hkv
    nq, nk = sq // block_q, sk // block_k
    scale = d ** -0.5

    # Kernel layout: [B, H, S, D] (head-major so blocks are contiguous).
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)

    kernel = functools.partial(
        _fwd_kernel, scale=scale, causal=causal, window=window,
        block_q=block_q, block_k=block_k, num_k_blocks=nk,
        has_seg=has_seg)

    in_specs = [
        pl.BlockSpec((1, 1, block_q, d),
                     lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
        pl.BlockSpec((1, 1, block_k, d),
                     lambda bi, hi, qi, ki: (bi, hi // group, ki, 0)),
        pl.BlockSpec((1, 1, block_k, d),
                     lambda bi, hi, qi, ki: (bi, hi // group, ki, 0)),
    ]
    operands = [qt, kt, vt]
    if has_seg:
        # [b, 1, s] so the seq extent rides the LANE axis of the block
        # ((1, 1, block) passes the Mosaic last-two-dims rule for any
        # batch; the old [b, s] layout put the batch in the sublane
        # slot, where a 1-extent block is illegal whenever b > 1).
        seg = segment_ids.astype(jnp.int32)[:, None, :]
        in_specs += [
            pl.BlockSpec((1, 1, block_q),
                         lambda bi, hi, qi, ki: (bi, 0, qi)),
            pl.BlockSpec((1, 1, block_k),
                         lambda bi, hi, qi, ki: (bi, 0, ki)),
        ]
        operands += [seg, seg]

    out, lse = pl.pallas_call(
        kernel,
        grid=(b, hq, nq, nk),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, 1, block_q, d),
                         lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
            pl.BlockSpec((1, 1, block_q, LANES),
                         lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, hq, sq, d), q.dtype),
            jax.ShapeDtypeStruct((b, hq, sq, LANES), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),   # running max
            pltpu.VMEM((block_q, 1), jnp.float32),   # running sum
            pltpu.VMEM((block_q, d), jnp.float32),   # output accumulator
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=('parallel', 'parallel', 'parallel',
                                 'arbitrary')),
        interpret=dispatch.interpret_mode(),
    )(*operands)
    return out.transpose(0, 2, 1, 3), lse


def _fwd_rule(q, k, v, segment_ids, causal, block_q, block_k, window):
    out, lse = _flash_fwd_impl(q, k, v, segment_ids, causal, block_q,
                               block_k, window)
    return out, (q, k, v, segment_ids, out, lse)


def _bwd_rule(causal, block_q, block_k, window, res, g):
    q, k, v, segment_ids, out, lse = res
    if bwd_impl_choice() == 'xla':
        from skypilot_tpu.ops import attention as attention_ops
        _, vjp = jax.vjp(
            lambda q_, k_, v_: attention_ops.mha_reference(
                q_, k_, v_, causal=causal, segment_ids=segment_ids,
                window=window),
            q, k, v)
        return (*vjp(g), None)
    has_seg = segment_ids is not None
    b, sq, sk, hq, hkv, d, block_q, block_k = _shape_checks(
        q, k, block_q, block_k, has_seg)
    group = hq // hkv
    nq, nk = sq // block_q, sk // block_k
    scale = d ** -0.5

    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    dot = g.transpose(0, 2, 1, 3)         # dO, [b, hq, sq, d]
    ot = out.transpose(0, 2, 1, 3)

    # delta_i = sum_d dO_i * O_i, the softmax-grad row correction,
    # lane-replicated to the Mosaic-friendly [b, hq, sq, LANES] layout.
    delta = (dot.astype(jnp.float32) * ot.astype(jnp.float32)).sum(-1)
    delta = jnp.broadcast_to(delta[..., None], (b, hq, sq, LANES))

    qkv_spec = lambda bi, hi, qi, ki: (bi, hi, qi, 0)  # noqa: E731
    kv_spec = lambda bi, hi, qi, ki: (bi, hi // group, ki, 0)  # noqa: E731
    row_spec = lambda bi, hi, qi, ki: (bi, hi, qi, 0)  # noqa: E731

    common_in_specs = [
        pl.BlockSpec((1, 1, block_q, d), qkv_spec),       # q
        pl.BlockSpec((1, 1, block_k, d), kv_spec),        # k
        pl.BlockSpec((1, 1, block_k, d), kv_spec),        # v
        pl.BlockSpec((1, 1, block_q, d), qkv_spec),       # dO
        pl.BlockSpec((1, 1, block_q, LANES), row_spec),   # lse
        pl.BlockSpec((1, 1, block_q, LANES), row_spec),   # delta
    ]
    operands = [qt, kt, vt, dot, lse, delta]
    if has_seg:
        seg = segment_ids.astype(jnp.int32)[:, None, :]  # lane-axis seq
        common_in_specs += [
            pl.BlockSpec((1, 1, block_q),
                         lambda bi, hi, qi, ki: (bi, 0, qi)),
            pl.BlockSpec((1, 1, block_k),
                         lambda bi, hi, qi, ki: (bi, 0, ki)),
        ]
        operands += [seg, seg]

    dq_kernel = functools.partial(
        _dq_kernel, scale=scale, causal=causal, window=window,
        block_q=block_q, block_k=block_k, num_k_blocks=nk,
        has_seg=has_seg)
    dqt = pl.pallas_call(
        dq_kernel,
        grid=(b, hq, nq, nk),
        in_specs=list(common_in_specs),
        out_specs=pl.BlockSpec((1, 1, block_q, d), qkv_spec),
        out_shape=jax.ShapeDtypeStruct((b, hq, sq, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=('parallel', 'parallel', 'parallel',
                                 'arbitrary')),
        interpret=dispatch.interpret_mode(),
    )(*operands)

    # dk/dv per *query* head: the kernel walks q blocks innermost for a
    # fixed k block; the kv-head (GQA group) reduction is one XLA sum.
    def dkv_q_spec(bi, hi, ki, qi):
        return (bi, hi, qi, 0)

    def dkv_kv_spec(bi, hi, ki, qi):
        return (bi, hi // group, ki, 0)

    def dkv_row_spec(bi, hi, ki, qi):
        return (bi, hi, qi, 0)

    dkv_in_specs = [
        pl.BlockSpec((1, 1, block_q, d), dkv_q_spec),      # q
        pl.BlockSpec((1, 1, block_k, d), dkv_kv_spec),     # k
        pl.BlockSpec((1, 1, block_k, d), dkv_kv_spec),     # v
        pl.BlockSpec((1, 1, block_q, d), dkv_q_spec),      # dO
        pl.BlockSpec((1, 1, block_q, LANES), dkv_row_spec),  # lse
        pl.BlockSpec((1, 1, block_q, LANES), dkv_row_spec),  # delta
    ]
    if has_seg:
        dkv_in_specs += [
            pl.BlockSpec((1, 1, block_q),
                         lambda bi, hi, ki, qi: (bi, 0, qi)),
            pl.BlockSpec((1, 1, block_k),
                         lambda bi, hi, ki, qi: (bi, 0, ki)),
        ]

    dkv_kernel = functools.partial(
        _dkv_kernel, scale=scale, causal=causal, window=window,
        block_q=block_q, block_k=block_k, num_q_blocks=nq,
        has_seg=has_seg)
    dk_spec = lambda bi, hi, ki, qi: (bi, hi, ki, 0)  # noqa: E731
    dkt, dvt = pl.pallas_call(
        dkv_kernel,
        grid=(b, hq, nk, nq),
        in_specs=dkv_in_specs,
        out_specs=[
            pl.BlockSpec((1, 1, block_k, d), dk_spec),
            pl.BlockSpec((1, 1, block_k, d), dk_spec),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, hq, sk, d), k.dtype),
            jax.ShapeDtypeStruct((b, hq, sk, d), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=('parallel', 'parallel', 'parallel',
                                 'arbitrary')),
        interpret=dispatch.interpret_mode(),
    )(*operands)

    if group > 1:
        dkt = dkt.reshape(b, hkv, group, sk, d).sum(2)
        dvt = dvt.reshape(b, hkv, group, sk, d).sum(2)

    dq = dqt.transpose(0, 2, 1, 3)
    dk = dkt.transpose(0, 2, 1, 3)
    dv = dvt.transpose(0, 2, 1, 3)
    return dq, dk, dv, None


_flash.defvjp(_fwd_rule, _bwd_rule)
