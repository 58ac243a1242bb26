"""Flash attention (forward + backward) as Pallas TPU kernels.

Blockwise online-softmax attention (Flash-Attention-2 schedule):

* forward: grid over (batch, q_heads, q_blocks, k_blocks) with the k axis
  innermost so the VMEM scratch accumulators (running max m, running sum
  l, output acc) persist across k iterations of one q block; also emits
  the per-row logsumexp L for the backward. GQA is folded into the k/v
  index_map (head h reads kv head h // group). Segment ids (packed
  sequences) are masked in-kernel.
* backward: two kernels, both recomputing p = exp(s - L) blockwise from
  the saved residuals (q, k, v, out, L) — no O(S^2) materialization:
    - dq kernel: same grid as forward (k innermost), accumulates
      dq += ds @ k in VMEM scratch; delta = rowsum(dO*O) is made once
      per q block, in the kernel, as the column it is used as;
    - dk/dv kernel: grid (batch, q_heads, k_blocks, q_blocks) with q
      innermost, computed in the transposed orientation (s^T = k q^T,
      [block_k, block_q]) so that no product contracts dimension 0 of
      an operand and L and delta are read as compact [1, block_q] rows;
      accumulates dk/dv per *query* head; the GQA group sum down to kv
      heads happens outside the kernel (one cheap XLA reduce), avoiding
      non-contiguous output revisits.

The tile plan (docs/kernels.md): each kernel gets its own (block_q,
block_k) from the shape (dispatch.flash_blocks). Under a causal or
sliding-window mask a tile is *skipped* (no allowed entry: no compute,
and the index_maps clamp the streamed block index to the nearest
visited tile, so nothing is copied in for it), *masked* (the diagonal
or the window's edge crosses it: iota, compare, select) or *plain*
(wholly allowed: none of that). With segment ids every visited tile is
masked. The plan is recorded at trace time (dispatch.record_flash_plan).

The block-diffusion mask (`bd = (L, B)`: the row is `[x_t | x_0]`, L
noised positions then their L clean copies, in blocks of B) is the one
mask whose visited tiles are not one run a row of tiles: a noised query
block visits its own noised blocks and, after a gap, the clean blocks
before it; a clean key block is visited by the noised query blocks after
it and by the clean ones from it on. The grid is the same; the index
maps name, for a skipped step, the nearer end of the run before or
after it (`_two_runs`), so nothing is copied in for it. The extents the
tile rule gives divide L (or are the whole 2L), so a tile lies in one
quadrant of the square.

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

NEG_INF = -1e30

# The forward writes the logsumexp as [b, hq, sq, LANES] with the value
# replicated across the 128 lanes: it is made as a [block_q, 1] column,
# the dq kernel reads it back as one, and Mosaic requires the last two
# dims of every block to be (8k, 128)-tileable or equal to the array
# dims. The dk/dv kernel wants rows and gets lane 0 as [b, hq, 1, sq].
LANES = 128

_NT = (((1,), (1,)), ((), ()))    # a @ b^T: contract the last dims
_NN = (((1,), (0,)), ((), ()))    # a @ b


def _shift(x, n):
    """x // n for non-negative x (a shift where n is a power of two)."""
    if n & (n - 1) == 0:
        return x >> (n.bit_length() - 1)
    return jax.lax.div(x, jnp.int32(n))


def _bd_codes(start, n, axis, bd, query):
    """The block-diffusion rule as two compares: a key's code is its
    block, plus L where it is noised; a query allows the codes under
    `below` (the clean blocks before its own if it is noised, up to its
    own if it is clean) and the code `own` (its own noised block; none
    for a clean query). Computed on a column or a row of the tile."""
    half, block = bd
    g = start + jax.lax.broadcasted_iota(
        jnp.int32, (n, 1) if axis == 0 else (1, n), axis)
    noised = g < half
    blk = _shift(jnp.where(noised, g, g - half), block)
    if not query:
        return jnp.where(noised, blk + half, blk)
    return jnp.where(noised, blk, blk + 1), jnp.where(noised, blk + half, -1)


def _block_mask(s, q_start, k_start, causal, window, q_seg, k_seg,
                q_axis=0, bd=()):
    """Apply causal / sliding-window / segment / block-diffusion
    masking to a score block whose queries run along `q_axis` (0:
    [block_q, block_k]; 1: the dk/dv kernel's transposed [block_k,
    block_q]). window > 0 (Mistral, every other Gemma-2 layer, Phi-3):
    query p also requires p - k_pos < window. Returns the masked
    scores."""
    if bd:
        below, own = _bd_codes(q_start, s.shape[q_axis], q_axis, bd, True)
        code = _bd_codes(k_start, s.shape[1 - q_axis], 1 - q_axis, bd,
                         False)
        s = jnp.where((code < below) | (code == own), s, NEG_INF)
    if causal or window > 0:
        # q_pos - k_pos of every entry.
        rel = (jax.lax.broadcasted_iota(jnp.int32, s.shape, q_axis) -
               jax.lax.broadcasted_iota(jnp.int32, s.shape, 1 - q_axis) +
               (q_start - k_start))
        if causal:
            s = jnp.where(rel >= 0, s, NEG_INF)
        if window > 0:
            s = jnp.where(rel < window, s, NEG_INF)
    if q_seg is not None:
        same = (q_seg[:, None] == k_seg[None, :] if q_axis == 0 else
                k_seg[:, None] == q_seg[None, :])
        s = jnp.where(same, s, NEG_INF)
    return s


def _bd_tile(qi, ki, block_q, block_k, bd):
    """(visited, plain) of a tile under the block-diffusion mask: has
    it any allowed entry, and is every entry allowed. A tile lies in
    one quadrant (its extents divide L), or an extent is the whole 2L
    and every tile is visited and masked. Python ints or traced."""
    half, block = bd
    if block_q > half or block_k > half:
        return True, False
    q0, k0 = qi * block_q, ki * block_k
    q_noised, q_clean = q0 < half, q0 >= half
    k_noised, k_clean = k0 < half, k0 >= half
    # First and last block of the tile's queries and of its keys.
    qa, qz = (q0 % half) // block, (q0 % half + block_q - 1) // block
    ka, kz = (k0 % half) // block, (k0 % half + block_k - 1) // block
    k_first, k_last = k0 % half, k0 % half + block_k - 1
    visited = ((q_noised & k_noised & (qa <= kz) & (ka <= qz)) |
               (q_noised & k_clean & (k_first < qz * block)) |
               (q_clean & k_clean & (k_first < (qz + 1) * block)))
    plain = ((q_noised & k_noised & (qa == qz) & (ka == kz) & (qa == ka)) |
             (q_noised & k_clean & (k_last < qa * block)) |
             (q_clean & k_clean & (k_last < (qa + 1) * block)))
    return visited, plain


def _tile_visited(qi, ki, block_q, block_k, causal, window, bd=()):
    """Does this (q block, k block) pair contain ANY unmasked (q, k)
    entry under causal+window, or under the block-diffusion mask? The
    others are skipped whole: above the diagonal (causal) and, with a
    window, entirely below it. Python ints (the trace-time tile count)
    or traced scalars (the kernels)."""
    if bd:
        return _bd_tile(qi, ki, block_q, block_k, bd)[0]
    cond = True
    if causal:
        cond = cond & (ki * block_k < (qi + 1) * block_q)
    if window > 0:
        # Highest k in the block must reach the lowest q's window
        # start: (ki+1)*bk - 1 >= qi*bq - (window - 1).
        cond = cond & ((ki + 1) * block_k > qi * block_q - window + 1)
    return cond


def _tile_crossed(qi, ki, block_q, block_k, causal, window, bd=()):
    """Does the pair contain ANY masked entry under causal+window: does
    the diagonal, or the window's lower edge, cross the tile? Under the
    block-diffusion mask: is any entry of it not allowed?"""
    if bd:
        return _bd_tile(qi, ki, block_q, block_k, bd)[1] ^ True
    cond = False
    if causal:
        cond = cond | ((ki + 1) * block_k - 1 > qi * block_q)
    if window > 0:
        cond = cond | ((qi + 1) * block_q - 1 - ki * block_k >= window)
    return cond


def _visited_k_blocks(qi, block_q, block_k, num_k_blocks, causal, window):
    """(first, last) k block that q block `qi` visits (traced)."""
    lo, hi = 0, num_k_blocks - 1
    if causal:
        hi = jnp.minimum(hi, ((qi + 1) * block_q - 1) // block_k)
    if window > 0:
        lo = jnp.maximum(qi * block_q - window + 1, 0) // block_k
    return lo, hi


def _visited_q_blocks(ki, block_q, block_k, num_q_blocks, causal, window):
    """(first, last) q block that k block `ki` visits (traced)."""
    lo, hi = 0, num_q_blocks - 1
    if causal:
        lo = (ki * block_k) // block_q
    if window > 0:
        hi = jnp.minimum(hi, ((ki + 1) * block_k + window - 2) // block_q)
    return lo, hi


def _clamp(i, lo, hi):
    return jnp.minimum(jnp.maximum(i, lo), hi)


def _two_runs(i, lo1, hi1, lo2, hi2):
    """Block `i` of a walk that visits [lo1, hi1] and then [lo2, hi2]
    (either may be empty, lo > hi, not both): itself where it is
    visited, else the nearer end of the run before or after it, which
    is resident then."""
    lo1, hi1, lo2, hi2 = (jnp.where(lo1 > hi1, lo2, lo1),
                          jnp.where(lo1 > hi1, hi2, hi1),
                          jnp.where(lo2 > hi2, lo1, lo2),
                          jnp.where(lo2 > hi2, hi1, hi2))
    return jnp.where(i < lo2, _clamp(i, lo1, hi1), _clamp(i, lo2, hi2))


def _bd_k_block(qi, ki, block_q, block_k, bd):
    """The k block a grid step of q block `qi` names under the
    block-diffusion mask (traced). A noised query block visits the
    noised blocks of its own positions, then the clean blocks before
    its last one; a clean query block the clean blocks up to its last
    one."""
    half, block = bd
    if block_q > half or block_k > half:
        return ki
    per_half = half // block_k
    q0 = qi * block_q
    first = q0 % half
    last_blk = (first + block_q - 1) // block
    noised = q0 < half
    own_lo = first // block * block // block_k
    own_hi = ((last_blk + 1) * block - 1) // block_k
    # Clean keys a query of the tile may see: positions under `upto`.
    upto = jnp.where(noised, last_blk * block, (last_blk + 1) * block)
    clean_hi = per_half + (upto - 1) // block_k    # upto 0: under per_half
    return _two_runs(ki, jnp.where(noised, own_lo, 1),
                     jnp.where(noised, own_hi, 0), per_half, clean_hi)


def _bd_q_block(ki, qi, block_q, block_k, bd):
    """The q block a grid step of k block `ki` names under the
    block-diffusion mask (traced). A noised key block is visited by the
    noised blocks of its own positions; a clean one by the noised
    blocks after its first block, and by the clean ones from it on."""
    half, block = bd
    if block_q > half or block_k > half:
        return qi
    per_half = half // block_q
    k0 = ki * block_k
    first = k0 % half
    first_blk = first // block
    noised = k0 < half
    own_lo = first_blk * block // block_q
    own_hi = (((first + block_k - 1) // block + 1) * block - 1) // block_q
    after = (first_blk + 1) * block // block_q     # past the half: none
    return _two_runs(
        qi, jnp.where(noised, own_lo, after),
        jnp.where(noised, own_hi, per_half - 1),
        jnp.where(noised, 1, per_half + own_lo),
        jnp.where(noised, 0, 2 * per_half - 1))


def allowed_pairs(half: int, block: int) -> int:
    """(query, key) pairs a head computes under the block-diffusion
    mask: clean to clean L(L + B)/2, noised to clean L(L - B)/2, a
    noised block to itself L * B."""
    return half * half + half * block


def tile_counts(sq, sk, block_q, block_k, causal, window, has_seg,
                bd=()):
    """Tiles of one head the plan visits, masks and skips; under the
    block-diffusion mask also `needed`, the allowed pairs in tiles."""
    visited = masked = 0
    nq, nk = sq // block_q, sk // block_k
    for qi in range(nq):
        for ki in range(nk):
            if _tile_visited(qi, ki, block_q, block_k, causal, window, bd):
                visited += 1
                masked += bool(has_seg or _tile_crossed(
                    qi, ki, block_q, block_k, causal, window, bd))
    counts = {'visited': visited, 'masked': masked,
              'skipped': nq * nk - visited}
    if bd:
        counts['needed'] = round(allowed_pairs(*bd) / (block_q * block_k), 2)
    return counts


def _for_tile(qi, ki, block_q, block_k, causal, window, has_seg, tile,
              bd=()):
    """Run `tile(masked)` for the grid step's tile: not at all where it
    is skipped, with the mask work only where a mask can bite."""
    if not (causal or window > 0 or bd):
        tile(has_seg)
        return
    visited = _tile_visited(qi, ki, block_q, block_k, causal, window, bd)
    if visited is True:     # block diffusion at a whole-sequence extent
        tile(True)
        return
    if has_seg:
        pl.when(visited)(lambda: tile(True))
        return
    crossed = _tile_crossed(qi, ki, block_q, block_k, causal, window, bd)
    pl.when(visited & crossed)(lambda: tile(True))
    pl.when(visited & jnp.logical_not(crossed))(lambda: tile(False))


def _fwd_kernel(*refs, scale: float, causal: bool, window: int,
                block_q: int, block_k: int, num_k_blocks: int,
                has_seg: bool, bd: tuple = ()):
    if has_seg:
        (q_ref, k_ref, v_ref, q_seg_ref, k_seg_ref,
         o_ref, lse_ref, m_scr, l_scr, acc_scr) = refs
    else:
        q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr = refs
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    def _tile(masked):
        q = q_ref[0, 0]                   # [block_q, d]
        k = k_ref[0, 0]                   # [block_k, d]
        s = jax.lax.dot_general(
            q, k, _NT, preferred_element_type=jnp.float32) * scale
        if masked:
            s = _block_mask(
                s, qi * block_q, ki * block_k, causal, window,
                q_seg_ref[0, 0] if has_seg else None,
                k_seg_ref[0, 0] if has_seg else None, bd=bd)
        m_prev = m_scr[:]                 # [bq, 1]
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)
        if masked:
            # Exact 0 for masked entries: a row whose FIRST visited
            # block is fully masked has m_new == NEG_INF, and
            # exp(NEG_INF - NEG_INF) would be 1 — with a sliding window
            # that case is routine (rows near the end of a q block
            # whose window starts past this k block), so guard by value
            # rather than rely on underflow.
            p = jnp.where(s <= NEG_INF / 2, 0.0, p)
        alpha = jnp.exp(m_prev - m_new)   # [bq, 1]
        l_new = alpha * l_scr[:] + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[:] = acc_scr[:] * alpha + jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[0, 0], _NN,
            preferred_element_type=jnp.float32)
        m_scr[:] = m_new
        l_scr[:] = l_new

    _for_tile(qi, ki, block_q, block_k, causal, window, has_seg, _tile, bd)

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
               has_seg: bool, bd: tuple = ()):
    if has_seg:
        (q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref,
         q_seg_ref, k_seg_ref, dq_ref, dq_scr, delta_scr) = refs
    else:
        (q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref,
         dq_ref, dq_scr, delta_scr) = refs
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)
        # delta_i = sum_d dO_i * O_i, the softmax-grad row correction.
        delta_scr[:] = jnp.sum(
            do_ref[0, 0].astype(jnp.float32) *
            o_ref[0, 0].astype(jnp.float32), axis=1, keepdims=True)

    def _tile(masked):
        q = q_ref[0, 0]                   # [bq, d]
        k = k_ref[0, 0]                   # [bk, d]
        s = jax.lax.dot_general(
            q, k, _NT, preferred_element_type=jnp.float32) * scale
        if masked:
            s = _block_mask(
                s, qi * block_q, ki * block_k, causal, window,
                q_seg_ref[0, 0] if has_seg else None,
                k_seg_ref[0, 0] if has_seg else None, bd=bd)
        lse = lse_ref[0, 0][:, :1]        # [bq, 1] (lane-replicated)
        p = jnp.exp(s - lse)              # [bq, bk]
        dp = jax.lax.dot_general(
            do_ref[0, 0], v_ref[0, 0], _NT,
            preferred_element_type=jnp.float32)  # [bq, bk]
        ds = p * (dp - delta_scr[:]) * scale
        dq_scr[:] += jax.lax.dot_general(
            ds.astype(k.dtype), k, _NN,
            preferred_element_type=jnp.float32)

    _for_tile(qi, ki, block_q, block_k, causal, window, has_seg, _tile, bd)

    @pl.when(ki == num_k_blocks - 1)
    def _finalize():
        dq_ref[0, 0] = dq_scr[:].astype(dq_ref.dtype)


def _dkv_kernel(*refs, scale: float, causal: bool, window: int,
                block_q: int, block_k: int, num_q_blocks: int,
                has_seg: bool, bd: tuple = ()):
    if has_seg:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
         q_seg_ref, k_seg_ref, dk_ref, dv_ref, dk_scr, dv_scr) = refs
    else:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
         dk_ref, dv_ref, dk_scr, dv_scr) = refs
    ki = pl.program_id(2)
    qi = pl.program_id(3)

    @pl.when(qi == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    def _tile(masked):
        # Transposed orientation: every score-sized value is
        # [block_k, block_q], the row statistics broadcast down the
        # sublanes, and all four products are a @ b or a @ b^T.
        q = q_ref[0, 0]                   # [bq, d]
        k = k_ref[0, 0]                   # [bk, d]
        do = do_ref[0, 0]                 # [bq, d]
        st = jax.lax.dot_general(
            k, q, _NT, preferred_element_type=jnp.float32) * scale
        if masked:
            st = _block_mask(
                st, qi * block_q, ki * block_k, causal, window,
                q_seg_ref[0, 0] if has_seg else None,
                k_seg_ref[0, 0] if has_seg else None, q_axis=1, bd=bd)
        pt = jnp.exp(st - lse_ref[0, 0])  # [bk, bq] - [1, bq]
        dv_scr[:] += jax.lax.dot_general(
            pt.astype(do.dtype), do, _NN,
            preferred_element_type=jnp.float32)  # [bk, d]
        dpt = jax.lax.dot_general(
            v_ref[0, 0], do, _NT,
            preferred_element_type=jnp.float32)  # [bk, bq]
        dst = pt * (dpt - delta_ref[0, 0]) * scale
        dk_scr[:] += jax.lax.dot_general(
            dst.astype(q.dtype), q, _NN,
            preferred_element_type=jnp.float32)  # [bk, d]

    _for_tile(qi, ki, block_q, block_k, causal, window, has_seg, _tile, bd)

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
    out, lse = _flash_fwd_impl(q, k, v, None, causal, None, None, 0)
    return out, lse[..., 0]


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    causal: bool = True,
                    segment_ids: Optional[jax.Array] = None,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None,
                    window: int = 0,
                    block_diffusion: int = 0) -> jax.Array:
    """q: [B, Sq, Hq, D]; k, v: [B, Sk, Hkv, D] -> [B, Sq, Hq, D].

    With no block_q/block_k the tile rule (dispatch.flash_blocks)
    gives the forward, dq and dk/dv kernels each their extents from
    the shape. Given, they are REQUESTS, not contracts, for all three:
    clamped through the divisibility-safe selector (ops/dispatch.py)
    to a tile-aligned divisor of the seq dims or to the full dims, so
    any legal input shape lowers — decode shapes included.
    Serving/train call sites should go through ops.attention's
    dispatch ladder, which adds the XLA fallback rung.

    segment_ids: optional [B, S] int32 packed-sequence ids, masked
    in-kernel (forward and backward).
    window: sliding-window attention (> 0: query p sees k in
    (p - window, p]). Out-of-window tiles are skipped like the tiles
    above the causal diagonal: no compute and no fetch.
    block_diffusion: the block length B (> 0) of the block-diffusion
    mask over a row `[x_t | x_0]` of Sq = Sk = 2L positions (the module
    docstring and ops/attention.block_diffusion_allowed have the rule);
    `causal` is not read, and segment ids and a window do not combine
    with it.
    """
    bd = ()
    if block_diffusion > 0:
        half = q.shape[1] // 2
        if (segment_ids is not None or window > 0 or
                q.shape[1] != k.shape[1] or q.shape[1] % 2 or
                half % block_diffusion):
            raise ValueError(
                f'the block-diffusion mask needs Sq = Sk = 2L with L a '
                f'multiple of the block {block_diffusion}, no segment '
                f'ids and no window; got Sq {q.shape[1]}, Sk {k.shape[1]}')
        bd, causal = (half, block_diffusion), False
    return _flash(q, k, v, segment_ids, causal, block_q, block_k,
                  window, bd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8))
def _flash(q, k, v, segment_ids, causal, block_q, block_k, window, bd=()):
    out, _ = _flash_fwd_impl(q, k, v, segment_ids, causal, block_q,
                             block_k, window, bd)
    return out


def _plan(q, k, block_q, block_k, has_seg, causal, window, kernels,
          bd=()):
    """Shape checks, then (tile plan, `vmem_limit_bytes`) of `kernels`
    (docs/kernels.md): extents from the shape rule, or the requested
    blocks CLAMPED through the divisibility-safe selector — to a
    tile-aligned divisor of the seq dim, or to the full dim (always
    legal) — so any legal input shape lowers, decode shapes like
    (4, 32, 8, 256) included. A block pair whose VMEM working set
    cannot fit is refused at TRACE time (a ValueError the dispatch
    ladder catches), because the Mosaic compile error it would become
    is not catchable. What is traced is recorded, with its tile counts
    (dispatch.record_flash_plan)."""
    sq, hq, d = q.shape[1:]
    sk, hkv = k.shape[1:3]
    if hq % hkv != 0:
        raise ValueError(
            f'q heads ({hq}) must be a multiple of kv heads ({hkv})')
    want = None
    if block_q is not None or block_k is not None:
        want = (block_q or sq, block_k or sk)
    plan = dispatch.flash_blocks(sq, sk, d, q.dtype, has_seg, window, want,
                                 block_diffusion=bool(bd))
    limits = {}
    for kernel in kernels:
        bq, bk = plan[kernel]
        need = dispatch.flash_vmem_bytes(
            kernel, bq, bk, d, jnp.dtype(q.dtype).itemsize, has_seg)
        if not dispatch.interpret_mode() and \
                need > dispatch.VMEM_BUDGET_BYTES:
            raise ValueError(
                f'flash {kernel} blocks {plan[kernel]} x d={d} need '
                f'{need}B of VMEM, over the budget '
                f'({dispatch.VMEM_BUDGET_BYTES}B) — refusing a certain '
                'Mosaic compile failure')
        limits[kernel] = dispatch.flash_vmem_limit(need)
        dispatch.record_flash_plan(kernel, {
            'block_q': bq, 'block_k': bk,
            **tile_counts(sq, sk, bq, bk, causal, window, has_seg, bd)})
    return plan, limits


def _params(vmem_limit):
    return pltpu.CompilerParams(
        dimension_semantics=('parallel', 'parallel', 'parallel',
                             'arbitrary'),
        vmem_limit_bytes=vmem_limit)


def _flash_fwd_impl(q, k, v, segment_ids, causal, block_q, block_k,
                    window=0, bd=()):
    has_seg = segment_ids is not None
    plan, limits = _plan(q, k, block_q, block_k, has_seg, causal, window,
                         ('fwd',), bd)
    block_q, block_k = plan['fwd']
    b, sq, hq, d = q.shape
    sk, hkv = k.shape[1:3]
    group = hq // hkv
    nq, nk = sq // block_q, sk // block_k

    # Kernel layout: [B, H, S, D] (head-major so blocks are contiguous).
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)

    kernel = functools.partial(
        _fwd_kernel, scale=d ** -0.5, causal=causal, window=window,
        block_q=block_q, block_k=block_k, num_k_blocks=nk,
        has_seg=has_seg, bd=bd)

    def kc(qi, ki):
        # A skipped step names the nearest visited k block: already
        # resident, so nothing is copied in for it.
        if bd:
            return _bd_k_block(qi, ki, block_q, block_k, bd)
        return _clamp(ki, *_visited_k_blocks(qi, block_q, block_k, nk,
                                             causal, window))

    q_spec = pl.BlockSpec((1, 1, block_q, d),
                          lambda bi, hi, qi, ki: (bi, hi, qi, 0))
    kv_spec = pl.BlockSpec(
        (1, 1, block_k, d),
        lambda bi, hi, qi, ki: (bi, hi // group, kc(qi, ki), 0))
    in_specs = [q_spec, kv_spec, kv_spec]
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
                         lambda bi, hi, qi, ki: (bi, 0, kc(qi, ki))),
        ]
        operands += [seg, seg]

    out, lse = pl.pallas_call(
        kernel,
        grid=(b, hq, nq, nk),
        in_specs=in_specs,
        out_specs=[
            q_spec,
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
        compiler_params=_params(limits['fwd']),
        interpret=dispatch.interpret_mode(),
    )(*operands)
    return out.transpose(0, 2, 1, 3), lse


def _fwd_rule(q, k, v, segment_ids, causal, block_q, block_k, window,
              bd=()):
    out, lse = _flash_fwd_impl(q, k, v, segment_ids, causal, block_q,
                               block_k, window, bd)
    return out, (q, k, v, segment_ids, out, lse)


def _bwd_rule(causal, block_q, block_k, window, bd, res, g):
    q, k, v, segment_ids, out, lse = res
    has_seg = segment_ids is not None
    plan, limits = _plan(q, k, block_q, block_k, has_seg, causal, window,
                         ('dq', 'dkv'), bd)
    b, sq, hq, d = q.shape
    sk, hkv = k.shape[1:3]
    group = hq // hkv
    scale = d ** -0.5

    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    dot = g.transpose(0, 2, 1, 3)         # dO, [b, hq, sq, d]
    ot = out.transpose(0, 2, 1, 3)
    seg = segment_ids.astype(jnp.int32)[:, None, :] if has_seg else None

    # dq: the forward's grid; q, dO, O and L stay for a q block's k loop.
    bq, bk = plan['dq']
    nq, nk = sq // bq, sk // bk

    def kc(qi, ki):
        if bd:
            return _bd_k_block(qi, ki, bq, bk, bd)
        return _clamp(ki, *_visited_k_blocks(qi, bq, bk, nk, causal,
                                             window))

    q_spec = pl.BlockSpec((1, 1, bq, d),
                          lambda bi, hi, qi, ki: (bi, hi, qi, 0))
    kv_spec = pl.BlockSpec(
        (1, 1, bk, d),
        lambda bi, hi, qi, ki: (bi, hi // group, kc(qi, ki), 0))
    in_specs = [q_spec, kv_spec, kv_spec, q_spec, q_spec,
                pl.BlockSpec((1, 1, bq, LANES),
                             lambda bi, hi, qi, ki: (bi, hi, qi, 0))]
    operands = [qt, kt, vt, dot, ot, lse]
    if has_seg:
        in_specs += [
            pl.BlockSpec((1, 1, bq), lambda bi, hi, qi, ki: (bi, 0, qi)),
            pl.BlockSpec((1, 1, bk),
                         lambda bi, hi, qi, ki: (bi, 0, kc(qi, ki))),
        ]
        operands += [seg, seg]
    dqt = pl.pallas_call(
        functools.partial(
            _dq_kernel, scale=scale, causal=causal, window=window,
            block_q=bq, block_k=bk, num_k_blocks=nk, has_seg=has_seg,
            bd=bd),
        grid=(b, hq, nq, nk),
        in_specs=in_specs,
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct((b, hq, sq, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32),    # dq
                        pltpu.VMEM((bq, 1), jnp.float32)],   # delta
        compiler_params=_params(limits['dq']),
        interpret=dispatch.interpret_mode(),
    )(*operands)

    # dk/dv per *query* head: the kernel walks q blocks innermost for a
    # fixed k block; the kv-head (GQA group) reduction is one XLA sum.
    # Its row statistics cross HBM compact, as [b, hq, 1, sq] rows.
    bq, bk = plan['dkv']
    nq, nk = sq // bq, sk // bk
    lse_row = lse[..., 0][:, :, None, :]
    delta_row = (dot.astype(jnp.float32) *
                 ot.astype(jnp.float32)).sum(-1)[:, :, None, :]

    def qc(ki, qi):
        if bd:
            return _bd_q_block(ki, qi, bq, bk, bd)
        return _clamp(qi, *_visited_q_blocks(ki, bq, bk, nq, causal,
                                             window))

    q_spec = pl.BlockSpec((1, 1, bq, d),
                          lambda bi, hi, ki, qi: (bi, hi, qc(ki, qi), 0))
    kv_spec = pl.BlockSpec((1, 1, bk, d),
                           lambda bi, hi, ki, qi: (bi, hi // group, ki, 0))
    row_spec = pl.BlockSpec((1, 1, 1, bq),
                            lambda bi, hi, ki, qi: (bi, hi, 0, qc(ki, qi)))
    dkv_spec = pl.BlockSpec((1, 1, bk, d),
                            lambda bi, hi, ki, qi: (bi, hi, ki, 0))
    in_specs = [q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec]
    operands = [qt, kt, vt, dot, lse_row, delta_row]
    if has_seg:
        in_specs += [
            pl.BlockSpec((1, 1, bq),
                         lambda bi, hi, ki, qi: (bi, 0, qc(ki, qi))),
            pl.BlockSpec((1, 1, bk), lambda bi, hi, ki, qi: (bi, 0, ki)),
        ]
        operands += [seg, seg]
    dkt, dvt = pl.pallas_call(
        functools.partial(
            _dkv_kernel, scale=scale, causal=causal, window=window,
            block_q=bq, block_k=bk, num_q_blocks=nq, has_seg=has_seg,
            bd=bd),
        grid=(b, hq, nk, nq),
        in_specs=in_specs,
        out_specs=[dkv_spec, dkv_spec],
        out_shape=[
            jax.ShapeDtypeStruct((b, hq, sk, d), k.dtype),
            jax.ShapeDtypeStruct((b, hq, sk, d), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((bk, d), jnp.float32),
            pltpu.VMEM((bk, d), jnp.float32),
        ],
        compiler_params=_params(limits['dkv']),
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
