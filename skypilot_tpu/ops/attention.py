"""Attention ops: XLA reference implementation + dispatch.

The XLA path is the correctness baseline and the grad path on CPU; on TPU
the Pallas flash kernel (ops/flash_attention.py) is used for the hot
forward/backward. GQA (grouped KV heads) handled by logical head repeat
folded into the einsum — no materialized K/V repeat.
"""
import functools
from typing import Optional

import jax
import jax.numpy as jnp

from skypilot_tpu.ops import dispatch
from skypilot_tpu.parallel import sharding as sharding_lib

NEG_INF = -1e9  # logits are f32 until softmax, so -1e9 never overflows


def block_diffusion_allowed(q_idx, k_idx, half: int, block: int):
    """The block-diffusion mask (BD3-LM's `block_diff_mask`) over a row
    `[x_t | x_0]`: indices under `half` (L) are the noised positions,
    those from it on their clean copies, and the block of an index is
    (index mod L) // block. Query p may see key r iff both are noised
    and of one block; or p is noised, r clean and of a block before
    p's; or both are clean and r's block is not after p's. A clean
    query never sees a noised key, and a noised one never the clean
    copy of its own block. Broadcasts q_idx against k_idx."""
    q_noised, k_noised = q_idx < half, k_idx < half
    q_blk = jnp.where(q_noised, q_idx, q_idx - half) // block
    k_blk = jnp.where(k_noised, k_idx, k_idx - half) // block
    return ((q_noised & k_noised & (q_blk == k_blk)) |
            (q_noised & ~k_noised & (k_blk < q_blk)) |
            (~q_noised & ~k_noised & (k_blk <= q_blk)))


# Scores of the XLA rung's block-diffusion attention held at a time.
_BD_XLA_SCORE_BYTES = 1 << 30


def _bd_reference(q, k, v, block: int, softmax_scale=None):
    """The XLA rung under the block-diffusion mask: `mha_reference` a
    block of queries at a time where the float32 scores of the whole
    row would not fit (32 heads x 16,384^2 are 34 GB)."""
    b, sq, hq, _ = q.shape
    rows = max(1, _BD_XLA_SCORE_BYTES // (4 * b * hq * k.shape[1]))
    chunk = next(c for c in range(min(sq, rows), 0, -1) if sq % c == 0)
    if chunk == sq:
        return mha_reference(q, k, v, causal=False, block_diffusion=block,
                             softmax_scale=softmax_scale)

    @jax.checkpoint     # the backward makes a block's scores again
    def rows_of(q1, start, k, v):
        return mha_reference(q1, k, v, causal=False, q_offset=start,
                             block_diffusion=block,
                             softmax_scale=softmax_scale)
    out = jax.lax.map(
        lambda args: rows_of(*args, k, v),
        (q.reshape(b, sq // chunk, chunk, *q.shape[2:]).swapaxes(0, 1),
         jnp.arange(0, sq, chunk)))
    return out.swapaxes(0, 1).reshape(*q.shape[:3], v.shape[-1])


def mha_reference(q: jax.Array, k: jax.Array, v: jax.Array,
                  causal: bool = True,
                  segment_ids: Optional[jax.Array] = None,
                  kv_segment_ids: Optional[jax.Array] = None,
                  q_offset: int = 0,
                  q_positions: Optional[jax.Array] = None,
                  softmax_scale: Optional[float] = None,
                  window: int = 0,
                  window_active=None,
                  logit_softcap: float = 0.0,
                  block_diffusion: int = 0) -> jax.Array:
    """q, k: [B, Sq|Sk, Hq|Hkv, D]; v: [B, Sk, Hkv, Dv]; Hq % Hkv == 0.

    Returns [B, Sq, Hq, Dv]. Logits and softmax in f32; the scale is
    1/sqrt(D) unless given.

    q_positions: optional [B, Sq] global query positions for the causal
    mask (per-batch offsets — the KV-cache decode path); overrides
    q_offset. Keys are assumed at positions 0..Sk-1.

    window: sliding-window attention (Mistral / every other Gemma-2
    layer): query at position p also requires p - k_pos < window.
    window_active: optional traced BOOL — False disables the window
    restriction at runtime. This is how Gemma-2's alternating
    global/sliding layers stay a single homogeneous nn.scan body: the
    per-layer choice is arithmetic on the scanned layer index, not a
    Python branch.

    logit_softcap: Gemma-2 style soft-capping, cap*tanh(logits/cap),
    applied after the scale, before the mask.

    block_diffusion: the block length (> 0) of the block-diffusion mask
    (`block_diffusion_allowed`) over Sk = 2L keys; the queries are rows
    q_offset .. q_offset + Sq - 1 of the 2L.
    """
    b, sq, hq, d = q.shape
    _, sk, hkv, _ = k.shape
    assert hq % hkv == 0, (hq, hkv)
    groups = hq // hkv
    scale = softmax_scale if softmax_scale is not None else d ** -0.5

    qg = q.reshape(b, sq, hkv, groups, d)
    logits = jnp.einsum('bqhgd,bkhd->bhgqk', qg, k,
                        preferred_element_type=jnp.float32)
    logits = logits * scale
    if logit_softcap > 0.0:
        logits = logit_softcap * jnp.tanh(logits / logit_softcap)

    k_pos = jnp.arange(sk)[None, None, None, None, :]
    if q_positions is not None:
        q_pos = q_positions[:, None, None, :, None]
    else:
        q_pos = (jnp.arange(sq) + q_offset)[None, None, None, :, None]
    mask = (q_pos >= k_pos) if (causal or q_positions is not None) \
        else None
    if window > 0:
        wmask = (q_pos - k_pos) < window
        if window_active is not None:
            wmask = wmask | jnp.logical_not(window_active)
        mask = wmask if mask is None else (mask & wmask)
    if segment_ids is not None:
        kv_seg = kv_segment_ids if kv_segment_ids is not None else segment_ids
        seg_mask = (segment_ids[:, None, None, :, None] ==
                    kv_seg[:, None, None, None, :])
        mask = seg_mask if mask is None else (mask & seg_mask)
    if block_diffusion > 0:
        bd_mask = block_diffusion_allowed(q_pos, k_pos, sk // 2,
                                          block_diffusion)
        mask = bd_mask if mask is None else (mask & bd_mask)
    if mask is not None:
        logits = jnp.where(mask, logits, NEG_INF)

    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum('bhgqk,bkhd->bqhgd', probs.astype(v.dtype), v)
    return out.reshape(b, sq, hq, v.shape[-1])


@functools.partial(jax.jit, static_argnames=('causal', 'impl', 'window',
                                             'logit_softcap',
                                             'softmax_scale',
                                             'block_diffusion'))
def _attention(q: jax.Array, k: jax.Array, v: jax.Array,
               causal: bool = True,
               segment_ids: Optional[jax.Array] = None,
               impl: str = 'auto',
               window: int = 0,
               window_active=None,
               logit_softcap: float = 0.0,
               softmax_scale: Optional[float] = None,
               block_diffusion: int = 0) -> jax.Array:
    """Dispatch: 'auto' prefers the Pallas flash kernel on TPU when
    shapes allow (`_resolve_impl`), else the XLA reference. The flash
    choice runs through the fallback ladder (ops/dispatch.py): Pallas
    at the tiles the shape rule gives (`dispatch.flash_blocks`) → XLA
    reference, with the selected path recorded in
    skyt_ops_kernel_path_total{op,path} and on the current trace span:
    op `flash_attention` for full attention, `flash_window_attention`
    for a static sliding window (Mistral, Phi-3, a kind-table model's
    window layers), whose kernels skip the tiles outside the band
    (O(S*window) block visits) and are chosen by the same shape rule;
    `flash_block_diffusion_attention` for the block-diffusion mask
    (`block_diffusion` > 0: the block length, over a row `[x_t | x_0]`;
    not causal, and no segment ids or window beside it), whose kernels
    walk the two runs of tiles a row of tiles has under it.
    A scale of the caller's (`softmax_scale`: DeepSeek-V2's YaRN) is
    the kernels' static `scale`, a call like any other. Soft-capped
    attention (Gemma-2) always takes the XLA path — the flash kernel
    does not implement it, and a silent wrong-math fast path is worse
    than a slower correct one. So does Gemma-2's per-layer traced
    window gate (window_active): the skip predicate must be
    static-per-kernel."""
    if block_diffusion > 0 and (segment_ids is not None or window > 0):
        raise ValueError('the block-diffusion mask takes no segment ids '
                         'and no window beside it')
    flash_unsupported = (logit_softcap > 0.0 or
                         (window > 0 and window_active is not None))
    impl = _resolve_impl(q, k, impl, window, flash_unsupported,
                         segment_ids is not None, block_diffusion > 0,
                         v.shape[-1])

    def xla():
        if block_diffusion > 0 and not flash_unsupported:
            return _bd_reference(q, k, v, block_diffusion, softmax_scale)
        return mha_reference(q, k, v, causal=causal,
                             block_diffusion=block_diffusion,
                             segment_ids=segment_ids, window=window,
                             window_active=window_active,
                             logit_softcap=logit_softcap,
                             softmax_scale=softmax_scale)

    if impl == 'flash':
        if flash_unsupported:
            offender = ('logit_softcap' if logit_softcap > 0.0 else
                        'a traced window gate (window_active)')
            raise ValueError(
                f'flash attention does not support {offender}')
        from skypilot_tpu.ops import flash_attention as flash_lib
        has_seg = segment_ids is not None

        # Each device runs the kernel on its own batch rows and heads
        # (the seq dims stay whole: every query needs every key).
        q_axes = ('act_batch', None, 'act_heads', None)
        kv_axes = ('act_batch', None, 'act_kv_heads', None)
        in_axes = (q_axes, kv_axes, kv_axes) + \
            ((('act_batch', None),) if has_seg else ())
        operands = (q, k, v) + ((segment_ids,) if has_seg else ())

        def kernel(q, k, v, seg=None):
            # No blocks asked for: the shape rule plans the tiles.
            return flash_lib.flash_attention(
                q, k, v, causal=causal, segment_ids=seg, window=window,
                block_diffusion=block_diffusion, scale=softmax_scale)

        def pallas():
            return sharding_lib.per_shard(kernel, in_axes, q_axes)(*operands)

        return dispatch.run_ladder(
            'flash_block_diffusion_attention' if block_diffusion > 0 else
            'flash_window_attention' if window > 0 else 'flash_attention',
            [('pallas', pallas), ('xla', xla)])
    # 'xla_native': XLA is the CORRECT path for this op (softcap /
    # traced window / auto-resolved shape), not ladder
    # degradation — keep it distinguishable from the 'xla' floor so
    # operators (and chip_smoke.py's /stats check) don't learn to
    # ignore the real degradation signal.
    return dispatch.run_ladder('attention', [('xla_native', xla)])


def _resolve_impl(q, k, impl: str, window: int, flash_unsupported: bool,
                  has_seg: bool, block_diffusion: bool = False,
                  dv: Optional[int] = None) -> str:
    """The 'auto' gate: flash or XLA, from the shape and the features
    asked for. A static window is a flash call like any other; what
    flash cannot do (a soft cap, a traced window gate) is XLA's."""
    if impl != 'auto':
        return impl
    return ('flash' if not flash_unsupported and
            _flash_ok(q, k, has_seg, window, block_diffusion, dv)
            else 'xla')


def _flash_ok(q: jax.Array, k: jax.Array, has_seg: bool = False,
              window: int = 0, block_diffusion: bool = False,
              dv: Optional[int] = None) -> bool:
    """Auto-dispatch gate: shapes where the flash kernel is expected
    to WIN on TPU (tile-aligned seqs, MXU-friendly head dim, blocks
    that fit VMEM). Any shape outside this set still works — it takes
    the XLA reference rung instead, and an explicit impl='flash' gets
    the shape-robust clamped blocks. has_seg matters: packed-sequence
    blocks must be 128-aligned or full-array, so a seq that clamps to
    a full-array block can blow the VMEM guard that a seg-less probe
    would pass. dv: the values' width where it is not the queries'."""
    sq, sk, d = q.shape[1], k.shape[1], q.shape[3]
    dv = dv or d
    if not (not dispatch.interpret_mode() and
            sq % 8 == 0 and sk % 8 == 0 and
            d % 64 == 0 and d <= 512 and dv % 64 == 0 and dv <= 512):
        return False
    return dispatch.flash_vmem_ok(
        dispatch.flash_blocks(sq, sk, d, q.dtype, has_seg, window,
                              block_diffusion=block_diffusion, dv=dv), d,
        jnp.dtype(q.dtype).itemsize, has_seg, dv)


def attention(q: jax.Array, k: jax.Array, v: jax.Array,
              causal: bool = True,
              segment_ids: Optional[jax.Array] = None,
              impl: str = 'auto',
              window: int = 0,
              window_active=None,
              logit_softcap: float = 0.0,
              softmax_scale: Optional[float] = None,
              block_diffusion: int = 0) -> jax.Array:
    """The public entry: `_attention` under the named scope of its kind
    of call, `flash_block_diffusion` under the block-diffusion mask,
    `flash_window` with a sliding window and `flash_full` without. The
    scope stands outside the jitted function: the compiled
    kernels keep its name (`_attention.N [tpu_custom_call]` in a device
    trace, which trace readers match) and their `tf_op` says which kind
    of layer called them (`.../flash_window/jit(_attention)/...`)."""
    with jax.named_scope('flash_block_diffusion' if block_diffusion > 0
                         else 'flash_window' if window > 0
                         else 'flash_full'):
        return _attention(q, k, v, causal=causal, segment_ids=segment_ids,
                          impl=impl, window=window,
                          window_active=window_active,
                          logit_softcap=logit_softcap,
                          softmax_scale=softmax_scale,
                          block_diffusion=block_diffusion)
