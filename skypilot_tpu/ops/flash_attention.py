"""Flash attention (forward + backward) as Pallas TPU kernels.

Blockwise online-softmax attention (Flash-Attention-2 schedule):

* forward: a q tile's k tiles in turn, so the VMEM scratch accumulators
  (running max m, running sum l, output acc) persist across them; also
  emits the per-row logsumexp L for the backward. GQA is folded into the
  k/v index_map (head h reads kv head h // group). Segment ids (packed
  sequences) are masked in-kernel.
* backward: two kernels, both recomputing p = exp(s - L) blockwise from
  the saved residuals (q, k, v, out, L) — no O(S^2) materialization:
    - dq kernel: the forward's walk, accumulates dq += ds @ k in VMEM
      scratch; delta = rowsum(dO*O) and the column of L are made once
      per q tile, in the kernel, as the columns they are used as;
    - dk/dv kernel: a k tile's q tiles in turn, computed in the
      transposed orientation (s^T = k q^T, [block_k, block_q]) so that
      no product contracts dimension 0 of an operand and L and delta
      are read as compact [1, block_q] rows; accumulates dk/dv per
      *query* head; the GQA group sum down to kv heads happens outside
      the kernel (one cheap XLA reduce), avoiding non-contiguous output
      revisits.

The tile plan (docs/kernels.md): each kernel gets its own (block_q,
block_k) from the shape (dispatch.flash_blocks). Under a mask a tile is
*skipped* (no allowed entry), *masked* (the diagonal, the window's edge
or a block's boundary crosses it: iota, compare, select) or *plain*
(wholly allowed: none of that). With segment ids every visited tile is
masked. The plan is recorded at trace time (dispatch.record_flash_plan).

The grid is the list of visited tiles: (batch, q heads, steps), where a
step is one tile with an allowed entry and a skipped tile has no step.
Every mask here is static, so the list is a constant made in numpy at
trace time (`_walk`: `_tile_visited` and `_tile_crossed` over all tiles
at once) and prefetched as scalars, an int32 a step: the q tile, the k
tile, and whether the step is the first or the last of its row of tiles
(zero the accumulators, store the result) and plain or masked. The
index maps read the step's tile from it; consecutive steps of a row
name the same block of the row's operands and results, which stay
resident. One walk serves the causal, window, segment and
block-diffusion masks and no mask at all (the whole grid, in the old
order); a row of tiles that visits nothing keeps one step that computes
nothing, so its zeros are stored.

The block-diffusion mask (`bd = (L, B)`: the row is `[x_t | x_0]`, L
noised positions then their L clean copies, in blocks of B) is the one
mask whose visited tiles are not one run a row of tiles: a noised query
block visits its own noised blocks and, after a gap, the clean blocks
before it; a clean key block is visited by the noised query blocks after
it and by the clean ones from it on. The list holds both runs, one
after the other. The extents the tile rule gives divide L (or are the
whole 2L), so a tile lies in one quadrant of the square.

The residuals the backward reads of the forward, its output and L (one
float32 a row, [b, hq, sq]), carry the names `OUT` and `LSE`: a block
under remat whose policy saves them (models/hybrid.py, its
full-attention blocks) keeps them from the first pass, and its backward
runs no forward kernel again.

Kernel conventions follow /opt/skills/guides/pallas_guide.md (block
specs, scratch via pl.pallas_call scratch_shapes, MXU-aligned tiles).
"""
import functools
import types
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from skypilot_tpu.ops import dispatch

NEG_INF = -1e30

# The forward writes the logsumexp as [b, hq, sq, LANES] with the value
# replicated across the 128 lanes: it is made as a [block_q, 1] column,
# and Mosaic requires the last two dims of every block to be
# (8k, 128)-tileable or equal to the array dims. What the backward keeps
# and reads is lane 0, a row [b, hq, 1, sq] to its kernels.
LANES = 128

# The names of the residuals a remat policy may save (the forward's
# output and the compact logsumexp), as `moe.SELECTED` names the experts
# chosen.
OUT = 'flash_out'
LSE = 'flash_lse'

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
    and every tile is visited and masked. Python ints or arrays."""
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
    window, entirely below it. Python ints, or arrays over every tile
    (`_walk`, the one reader: the list of a call's grid)."""
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


def allowed_pairs(half: int, block: int) -> int:
    """(query, key) pairs a head computes under the block-diffusion
    mask: clean to clean L(L + B)/2, noised to clean L(L - B)/2, a
    noised block to itself L * B."""
    return half * half + half * block


# A grid step's tile and what to do there, in one int32: the q tile and
# the k tile (13 bits each: 8,192 tiles a side), then four flags. An
# empty row's one step has neither `plain` nor `masked`: not computed.
_TILE_BITS = 13
MAX_TILES = 1 << _TILE_BITS
_FIRST, _LAST, _PLAIN, _MASKED = (1 << (2 * _TILE_BITS + i) for i in range(4))

# The longest list a call may prefetch (docs/kernels.md): it lives in
# SMEM whole, 1 MiB on a v5e; 261,051 steps compiled and 261,173 did not,
# for every kernel (the compiler for a described chip, PR 36).
MAX_STEPS = 252 * 1024


def _decode(code):
    """(q tile, k tile, first, last, plain, masked) of a step's entry:
    a traced scalar in the kernels and index maps, an array in tests."""
    tile = MAX_TILES - 1
    return (code & tile, (code >> _TILE_BITS) & tile, (code & _FIRST) != 0,
            (code & _LAST) != 0, (code & _PLAIN) != 0, (code & _MASKED) != 0)


@functools.lru_cache(maxsize=None)
def _walk(sq, sk, block_q, block_k, causal, window, has_seg, bd, by_k):
    """(the list of visited tiles, the counts of one head) of a call:
    `_tile_visited` and `_tile_crossed` over every tile at once, in
    numpy at trace time, cached for the call sites of an unrolled
    stack. The walk is over rows of tiles (q tiles; k tiles with
    `by_k`, the dk/dv kernel), each row's visited tiles in rising
    order; a row that visits nothing gets one step that computes
    nothing, so that its zeros are stored."""
    nq, nk = sq // block_q, sk // block_k
    qi, ki = np.arange(nq)[:, None], np.arange(nk)[None, :]
    mask = (block_q, block_k, causal, window, bd)
    visited = np.broadcast_to(_tile_visited(qi, ki, *mask), (nq, nk))
    masked = visited & (has_seg | np.broadcast_to(
        _tile_crossed(qi, ki, *mask), (nq, nk)))
    counts = {'visited': int(visited.sum()), 'masked': int(masked.sum()),
              'skipped': int((~visited).sum())}
    if by_k:
        visited, masked = visited.T, masked.T
    # A row that visits nothing steps once, on its first tile.
    stepped = visited.copy()
    stepped[~visited.any(axis=1), 0] = True
    row, col = np.nonzero(stepped)        # row-major, columns rising
    kind = np.select([masked[row, col], visited[row, col]],
                     [_MASKED, _PLAIN], 0)
    edge = np.concatenate([[True], row[1:] != row[:-1], [True]])
    q_tile, k_tile = (col, row) if by_k else (row, col)
    codes = (q_tile | k_tile << _TILE_BITS | edge[:-1] * _FIRST |
             edge[1:] * _LAST | kind).astype(np.int32)
    codes.flags.writeable = False
    counts['steps'] = len(codes)
    if bd:
        counts['needed'] = round(allowed_pairs(*bd) / (block_q * block_k), 2)
    return codes, counts


def tile_counts(sq, sk, block_q, block_k, causal, window, has_seg,
                bd=(), by_k=False):
    """Tiles of one head the plan visits, masks and skips, and the grid
    steps it takes (the visited tiles, and one for a row of tiles that
    visits nothing); under the block-diffusion mask also `needed`, the
    allowed pairs in tiles."""
    return dict(_walk(sq, sk, block_q, block_k, causal, window, has_seg,
                      bd, by_k)[1])


def _for_tile(kind, kinds, tile):
    """Run `tile(masked)` for the grid step's tile, with the mask work
    only where a mask can bite. `kind`: the step's (plain, masked)
    flags; `kinds`: which of the two the call's list holds at all (the
    other body is not traced)."""
    for masked in (False, True):
        if kinds[masked]:
            pl.when(kind[masked])(functools.partial(tile, masked))


def _fwd_kernel(*refs, scale: float, causal: bool, window: int,
                block_q: int, block_k: int, kinds: tuple,
                has_seg: bool, bd: tuple = ()):
    if has_seg:
        (visits_ref, q_ref, k_ref, v_ref, q_seg_ref, k_seg_ref,
         o_ref, lse_ref, m_scr, l_scr, acc_scr) = refs
    else:
        (visits_ref, q_ref, k_ref, v_ref, o_ref, lse_ref,
         m_scr, l_scr, acc_scr) = refs
    qi, ki, first, last, *kind = _decode(visits_ref[pl.program_id(2)])

    @pl.when(first)
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

    _for_tile(kind, kinds, _tile)

    @pl.when(last)
    def _finalize():
        l = l_scr[:]
        safe_l = jnp.where(l == 0.0, 1.0, l)  # fully-masked rows -> out 0
        o_ref[0, 0] = (acc_scr[:] / safe_l).astype(o_ref.dtype)
        # Logsumexp residual; 0 for fully-masked rows so the backward's
        # p = exp(NEG_INF - 0) is exactly 0.
        lse = jnp.where(l > 0.0, m_scr[:] + jnp.log(safe_l), 0.0)
        lse_ref[0, 0] = jnp.broadcast_to(lse, (lse.shape[0], LANES))


def _dq_kernel(*refs, scale: float, causal: bool, window: int,
               block_q: int, block_k: int, kinds: tuple,
               has_seg: bool, bd: tuple = ()):
    if has_seg:
        (visits_ref, q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref,
         q_seg_ref, k_seg_ref, dq_ref, dq_scr, delta_scr, lse_scr) = refs
    else:
        (visits_ref, q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref,
         dq_ref, dq_scr, delta_scr, lse_scr) = refs
    qi, ki, first, last, *kind = _decode(visits_ref[pl.program_id(2)])

    @pl.when(first)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)
        # delta_i = sum_d dO_i * O_i, the softmax-grad row correction.
        delta_scr[:] = jnp.sum(
            do_ref[0, 0].astype(jnp.float32) *
            o_ref[0, 0].astype(jnp.float32), axis=1, keepdims=True)
        # The tile's row of L, turned into the column it is used as.
        lse_scr[:] = lse_ref[0, 0].T

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
        p = jnp.exp(s - lse_scr[:])       # [bq, bk] - [bq, 1]
        dp = jax.lax.dot_general(
            do_ref[0, 0], v_ref[0, 0], _NT,
            preferred_element_type=jnp.float32)  # [bq, bk]
        ds = p * (dp - delta_scr[:]) * scale
        dq_scr[:] += jax.lax.dot_general(
            ds.astype(k.dtype), k, _NN,
            preferred_element_type=jnp.float32)

    _for_tile(kind, kinds, _tile)

    @pl.when(last)
    def _finalize():
        dq_ref[0, 0] = dq_scr[:].astype(dq_ref.dtype)


def _dkv_kernel(*refs, scale: float, causal: bool, window: int,
                block_q: int, block_k: int, kinds: tuple,
                has_seg: bool, bd: tuple = ()):
    if has_seg:
        (visits_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
         q_seg_ref, k_seg_ref, dk_ref, dv_ref, dk_scr, dv_scr) = refs
    else:
        (visits_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
         dk_ref, dv_ref, dk_scr, dv_scr) = refs
    qi, ki, first, last, *kind = _decode(visits_ref[pl.program_id(2)])

    @pl.when(first)
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

    _for_tile(kind, kinds, _tile)

    @pl.when(last)
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
    out, lse = _flash_fwd_impl(q, k, v, None, causal, None, None, 0, (),
                               q.shape[-1] ** -0.5)
    return out, lse[..., 0]


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    causal: bool = True,
                    segment_ids: Optional[jax.Array] = None,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None,
                    window: int = 0,
                    block_diffusion: int = 0,
                    scale: Optional[float] = None) -> jax.Array:
    """q, k: [B, Sq|Sk, Hq|Hkv, D]; v: [B, Sk, Hkv, Dv] ->
    [B, Sq, Hq, Dv]. The value width may differ from the query and key
    width (latent attention: 192 and 128). The scores are q k^T times
    `scale`, 1/sqrt(D) where none is given (DeepSeek-V2's YaRN gives its
    own); the kernels take it as a static argument.

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
    above the causal diagonal: no grid step.
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
    scale = q.shape[-1] ** -0.5 if scale is None else float(scale)
    return _flash(q, k, v, segment_ids, causal, block_q, block_k,
                  window, bd, scale)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9))
def _flash(q, k, v, segment_ids, causal, block_q, block_k, window, bd,
           scale):
    out, _ = _flash_fwd_impl(q, k, v, segment_ids, causal, block_q,
                             block_k, window, bd, scale)
    return out


class _Tiles(NamedTuple):
    """What `_plan` fixes for one kernel of a call."""
    block_q: int
    block_k: int
    vmem_limit: Optional[int]
    visits: np.ndarray      # the grid: an int32 a step (`_walk`)
    kinds: tuple            # has the list (plain, masked) tiles at all


def _plan(q, k, block_q, block_k, has_seg, causal, window, kernels,
          bd=(), dv=None):
    """Shape checks, then kernel -> `_Tiles` for `kernels`
    (docs/kernels.md; `dv` the values' width, q's where not given):
    extents from the shape rule, or the requested
    blocks CLAMPED through the divisibility-safe selector — to a
    tile-aligned divisor of the seq dim, or to the full dim (always
    legal) — so any legal input shape lowers, decode shapes like
    (4, 32, 8, 256) included; and the list of tiles the grid visits. A
    block pair whose VMEM working set cannot fit, or a list longer than
    SMEM takes, is refused at TRACE time (a ValueError the dispatch
    ladder catches), because the Mosaic compile error it would become
    is not catchable. What is traced is recorded, with its tile counts
    (dispatch.record_flash_plan)."""
    sq, hq, d = q.shape[1:]
    sk, hkv = k.shape[1:3]
    dv = dv or d
    if hq % hkv != 0:
        raise ValueError(
            f'q heads ({hq}) must be a multiple of kv heads ({hkv})')
    want = None
    if block_q is not None or block_k is not None:
        want = (block_q or sq, block_k or sk)
    plan = dispatch.flash_blocks(sq, sk, d, q.dtype, has_seg, window, want,
                                 block_diffusion=bool(bd), dv=dv)
    tiles = {}
    for kernel in kernels:
        bq, bk = plan[kernel]
        need = dispatch.flash_vmem_bytes(
            kernel, bq, bk, d, jnp.dtype(q.dtype).itemsize, has_seg, dv)
        if max(sq // bq, sk // bk) > MAX_TILES:
            raise ValueError(
                f'flash {kernel} blocks {plan[kernel]} cut ({sq}, {sk}) '
                f'into more than {MAX_TILES} tiles a side')
        visits, counts = _walk(sq, sk, bq, bk, causal, window, has_seg, bd,
                               kernel == 'dkv')
        if not dispatch.interpret_mode():
            if need > dispatch.VMEM_BUDGET_BYTES:
                raise ValueError(
                    f'flash {kernel} blocks {plan[kernel]} x d={d}/{dv} need '
                    f'{need}B of VMEM, over the budget '
                    f'({dispatch.VMEM_BUDGET_BYTES}B) — refusing a '
                    'certain Mosaic compile failure')
            if len(visits) > MAX_STEPS:
                raise ValueError(
                    f'flash {kernel} blocks {plan[kernel]} visit '
                    f'{len(visits)} tiles of ({sq}, {sk}), a list longer '
                    f'than SMEM takes ({MAX_STEPS}) — refusing a certain '
                    'Mosaic compile failure')
        tiles[kernel] = _Tiles(
            bq, bk, dispatch.flash_vmem_limit(need), visits,
            (counts['masked'] < counts['visited'], counts['masked'] > 0))
        widths = {'dqk': d, 'dv': dv} if dv != d else {}
        dispatch.record_flash_plan(
            kernel, {'block_q': bq, 'block_k': bk, **counts, **widths})
    return tiles


def _specs(tiles, d, group, dv):
    """The block specs of a call's operands, q and k of width d, v and
    what is made from it (out, dO, dv) of width dv. Every index map
    reads the step's tile from the prefetched list: one scalar load and
    a shift. Consecutive steps of a row of tiles name the same block of
    the row's operands and results, which stay resident."""
    bq, bk = tiles.block_q, tiles.block_k

    def spec(block, index):
        return pl.BlockSpec(block, lambda bi, hi, s, visits: index(
            bi, hi, *_decode(visits[s])[:2]))

    return types.SimpleNamespace(
        q=spec((1, 1, bq, d), lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
        o=spec((1, 1, bq, dv), lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
        k=spec((1, 1, bk, d),
               lambda bi, hi, qi, ki: (bi, hi // group, ki, 0)),
        v=spec((1, 1, bk, dv),
               lambda bi, hi, qi, ki: (bi, hi // group, ki, 0)),
        # dk and dv of a *query* head.
        dk=spec((1, 1, bk, d), lambda bi, hi, qi, ki: (bi, hi, ki, 0)),
        dv=spec((1, 1, bk, dv), lambda bi, hi, qi, ki: (bi, hi, ki, 0)),
        col=spec((1, 1, bq, LANES), lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
        row=spec((1, 1, 1, bq), lambda bi, hi, qi, ki: (bi, hi, 0, qi)),
        # [b, 1, s] so the seq extent rides the LANE axis of the block
        # ((1, 1, block) passes the Mosaic last-two-dims rule for any
        # batch; the old [b, s] layout put the batch in the sublane
        # slot, where a 1-extent block is illegal whenever b > 1).
        segs=[spec((1, 1, bq), lambda bi, hi, qi, ki: (bi, 0, qi)),
              spec((1, 1, bk), lambda bi, hi, qi, ki: (bi, 0, ki))])


def _call(body, tiles, heads, in_specs, out_specs, out_shape, scratch,
          operands):
    """One kernel over the grid (batch, q heads, the list's steps)."""
    return pl.pallas_call(
        body,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(*heads, len(tiles.visits)),
            in_specs=in_specs, out_specs=out_specs,
            scratch_shapes=scratch),
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=('parallel', 'parallel', 'arbitrary'),
            vmem_limit_bytes=tiles.vmem_limit),
        interpret=dispatch.interpret_mode(),
    )(jnp.asarray(tiles.visits), *operands)


def _flash_fwd_impl(q, k, v, segment_ids, causal, block_q, block_k,
                    window, bd, scale):
    has_seg = segment_ids is not None
    tiles = _plan(q, k, block_q, block_k, has_seg, causal, window,
                  ('fwd',), bd, v.shape[-1])['fwd']
    b, sq, hq, d = q.shape
    hkv, dv = k.shape[2], v.shape[3]
    specs = _specs(tiles, d, hq // hkv, dv)

    # Kernel layout: [B, H, S, D] (head-major so blocks are contiguous).
    operands = [q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
                v.transpose(0, 2, 1, 3)]
    in_specs = [specs.q, specs.k, specs.v]
    if has_seg:
        in_specs += specs.segs
        operands += [segment_ids.astype(jnp.int32)[:, None, :]] * 2

    out, lse = _call(
        functools.partial(
            _fwd_kernel, scale=scale, causal=causal, window=window,
            block_q=tiles.block_q, block_k=tiles.block_k,
            kinds=tiles.kinds, has_seg=has_seg, bd=bd),
        tiles, (b, hq), in_specs, [specs.o, specs.col],
        [jax.ShapeDtypeStruct((b, hq, sq, dv), q.dtype),
         jax.ShapeDtypeStruct((b, hq, sq, LANES), jnp.float32)],
        [pltpu.VMEM((tiles.block_q, 1), jnp.float32),   # running max
         pltpu.VMEM((tiles.block_q, 1), jnp.float32),   # running sum
         pltpu.VMEM((tiles.block_q, dv), jnp.float32)],  # output accumulator
        operands)
    return out.transpose(0, 2, 1, 3), lse


def _fwd_rule(q, k, v, segment_ids, causal, block_q, block_k, window,
              bd, scale):
    out, lse = _flash_fwd_impl(q, k, v, segment_ids, causal, block_q,
                               block_k, window, bd, scale)
    out = checkpoint_name(out, OUT)
    return out, (q, k, v, segment_ids, out,
                 checkpoint_name(lse[..., 0], LSE))


def _bwd_rule(causal, block_q, block_k, window, bd, scale, res, g):
    q, k, v, segment_ids, out, lse = res  # lse: [b, hq, sq]
    has_seg = segment_ids is not None
    plan = _plan(q, k, block_q, block_k, has_seg, causal, window,
                 ('dq', 'dkv'), bd, v.shape[-1])
    b, sq, hq, d = q.shape
    sk, hkv = k.shape[1:3]
    dv = v.shape[3]
    group = hq // hkv
    static = dict(scale=scale, causal=causal, window=window,
                  has_seg=has_seg, bd=bd)

    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    dot = g.transpose(0, 2, 1, 3)         # dO, [b, hq, sq, dv]
    ot = out.transpose(0, 2, 1, 3)
    segs = [segment_ids.astype(jnp.int32)[:, None, :]] * 2 if has_seg \
        else []

    # Row statistics cross HBM compact, as [b, hq, 1, sq] rows.
    lse_row = lse[:, :, None, :]

    # dq: the forward's walk; q, dO, O and L stay for a q tile's k tiles.
    tiles = plan['dq']
    specs = _specs(tiles, d, group, dv)
    dqt = _call(
        functools.partial(_dq_kernel, block_q=tiles.block_q,
                          block_k=tiles.block_k, kinds=tiles.kinds,
                          **static),
        tiles, (b, hq),
        [specs.q, specs.k, specs.v, specs.o, specs.o, specs.row] +
        specs.segs[:len(segs)],
        specs.q, jax.ShapeDtypeStruct((b, hq, sq, d), q.dtype),
        [pltpu.VMEM((tiles.block_q, d), jnp.float32),    # dq
         pltpu.VMEM((tiles.block_q, 1), jnp.float32),    # delta
         pltpu.VMEM((tiles.block_q, 1), jnp.float32)],   # L
        [qt, kt, vt, dot, ot, lse_row] + segs)

    # dk/dv per *query* head: the kernel walks a k tile's q tiles; the
    # kv-head (GQA group) reduction is one XLA sum.
    tiles = plan['dkv']
    specs = _specs(tiles, d, group, dv)
    delta_row = (dot.astype(jnp.float32) *
                 ot.astype(jnp.float32)).sum(-1)[:, :, None, :]
    dkt, dvt = _call(
        functools.partial(_dkv_kernel, block_q=tiles.block_q,
                          block_k=tiles.block_k, kinds=tiles.kinds,
                          **static),
        tiles, (b, hq),
        [specs.q, specs.k, specs.v, specs.o, specs.row, specs.row] +
        specs.segs[:len(segs)],
        [specs.dk, specs.dv],
        [jax.ShapeDtypeStruct((b, hq, sk, d), k.dtype),
         jax.ShapeDtypeStruct((b, hq, sk, dv), v.dtype)],
        [pltpu.VMEM((tiles.block_k, d), jnp.float32),
         pltpu.VMEM((tiles.block_k, dv), jnp.float32)],
        [qt, kt, vt, dot, lse_row, delta_row] + segs)

    if group > 1:
        dkt = dkt.reshape(b, hkv, group, sk, d).sum(2)
        dvt = dvt.reshape(b, hkv, group, sk, dv).sum(2)

    dq = dqt.transpose(0, 2, 1, 3)
    dk = dkt.transpose(0, 2, 1, 3)
    dv = dvt.transpose(0, 2, 1, 3)
    return dq, dk, dv, None


_flash.defvjp(_fwd_rule, _bwd_rule)
