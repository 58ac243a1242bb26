"""Ring attention: context parallelism over the 'cp' mesh axis.

The reference has NO sequence/context parallelism anywhere (SURVEY.md §5
"Long-context: Absent") — this is designed fresh for the TPU torus:
sequence-sharded Q stays resident; K/V chunks rotate around the ring of
'cp'-axis neighbors via jax.lax.ppermute (ICI neighbor hops), with online
softmax (flash-style m/l accumulators) merging each chunk's contribution.
Peak memory per device is O(S/cp · S/cp) per chunk pair — long contexts
scale with ring size. XLA overlaps each hop's ppermute with the previous
chunk's attention math (the collective is issued before its result is
needed).

Causality: chunks are ordered by global offset; fully-future chunks
contribute zero through the online-softmax merge (masked to -inf).
"""
import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P
from skypilot_tpu.utils import env

NEG_INF = -1e30


def _chunk_attention(q, k, v, q_offset, k_offset, scale):
    """One K/V chunk's contribution, flash-style.

    q: [B, Sq, Hq, D]; k, v: [B, Sk, Hkv, D].
    Returns (numerator [B,Sq,Hq,D] f32, rowmax [B,Sq,Hq,1] f32,
             rowsum [B,Sq,Hq,1] f32).
    """
    b, sq, hq, d = q.shape
    _, sk, hkv, _ = k.shape
    group = hq // hkv
    qg = q.reshape(b, sq, hkv, group, d)
    s = jnp.einsum('bqhgd,bkhd->bqhgk', qg, k,
                   preferred_element_type=jnp.float32) * scale
    q_pos = q_offset + jnp.arange(sq)
    k_pos = k_offset + jnp.arange(sk)
    mask = q_pos[:, None] >= k_pos[None, :]          # [Sq, Sk]
    s = jnp.where(mask[None, :, None, None, :], s, NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)           # [B,Sq,Hkv,G,1]
    # Fully-masked rows: clamp m to 0 so p = exp(NEG_INF) = 0 (instead of
    # exp(NEG_INF - NEG_INF) = 1).
    m_safe = jnp.where(m <= NEG_INF / 2, 0.0, m)
    p = jnp.exp(s - m_safe)                          # [B,Sq,Hkv,G,Sk]
    l = jnp.sum(p, axis=-1, keepdims=True)
    num = jnp.einsum('bqhgk,bkhd->bqhgd', p,
                     v.astype(jnp.float32))
    return (num.reshape(b, sq, hq, d),
            m_safe.reshape(b, sq, hq, 1),
            l.reshape(b, sq, hq, 1))


def ring_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                   axis_name: str = 'cp', causal: bool = True,
                   softmax_scale: Optional[float] = None,
                   impl: str = 'auto') -> jax.Array:
    """Per-shard computation; must run inside shard_map with q/k/v
    sequence-sharded over `axis_name`. For the jit/GSPMD entry point see
    ring_attention_sharded().

    impl: 'auto' picks the flash-forward variant (Pallas blockwise
    kernel per chunk — no materialized [chunk, chunk] score tensor) when
    shapes allow, else the einsum path; the backward always runs the
    einsum path (see _ring_flash). 'xla' forces einsum;
    SKYT_RING_IMPL=xla overrides globally.
    """
    assert causal, 'non-causal ring attention not yet wired'
    b, sq, hq, d = q.shape
    scale = softmax_scale if softmax_scale is not None else d ** -0.5
    if impl == 'auto':
        impl = 'xla' if env.get('SKYT_RING_IMPL') == 'xla' \
            else 'flash'
    flash_ok = (d in (64, 128, 256) and sq % 128 == 0 and
                (sq <= 256 or sq % 256 == 0))
    if impl == 'flash' and flash_ok:
        return _ring_flash(q, k, v, axis_name, scale)
    return _ring_einsum(q, k, v, axis_name, scale)


def _ring_einsum(q, k, v, axis_name, scale):
    """Differentiable einsum ring (the backward path for _ring_flash and
    the fallback for flash-incompatible shapes)."""
    b, sq, hq, d = q.shape
    cp = jax.lax.psum(1, axis_name)
    my_idx = jax.lax.axis_index(axis_name)
    chunk = sq  # local chunk length; global seq = cp * chunk

    acc0 = jnp.zeros((b, sq, hq, d), jnp.float32)
    m0 = jnp.full((b, sq, hq, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, sq, hq, 1), jnp.float32)
    perm = [(i, (i + 1) % cp) for i in range(cp)]

    def body(carry, step):
        k_c, v_c, acc, m_run, l_run = carry
        # The chunk we hold at `step` originated at rank (my_idx - step).
        src = jax.lax.rem(my_idx - step + cp, cp)
        num, m_new, l_new = _chunk_attention(
            q, k_c, v_c, my_idx * chunk, src * chunk, scale)
        m_tot = jnp.maximum(m_run, m_new)
        alpha_run = jnp.exp(m_run - m_tot)
        alpha_new = jnp.exp(m_new - m_tot)
        acc = acc * alpha_run + num * alpha_new
        l_run = l_run * alpha_run + l_new * alpha_new
        k_c = jax.lax.ppermute(k_c, axis_name, perm)
        v_c = jax.lax.ppermute(v_c, axis_name, perm)
        return (k_c, v_c, acc, m_tot, l_run), None

    (_, _, acc, _, l_run), _ = jax.lax.scan(
        body, (k, v, acc0, m0, l0), jnp.arange(cp))
    l_safe = jnp.where(l_run == 0.0, 1.0, l_run)
    return (acc / l_safe).astype(q.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _ring_flash(q, k, v, axis_name, scale):
    """Flash-forward ring: each chunk pair runs the Pallas flash kernel
    (diag chunk causal, past chunks full, future chunks skipped) and the
    per-chunk (out, lse) pairs merge with a stable log-sum-exp combine.
    Backward recomputes through the einsum ring — same cost as before
    this existed; the forward is the hot path (inference, and the fwd
    half of training)."""
    return _ring_flash_impl(q, k, v, axis_name, scale)


def _ring_flash_impl(q, k, v, axis_name, scale):
    from skypilot_tpu.ops import flash_attention as flash_lib

    b, sq, hq, d = q.shape
    cp = jax.lax.psum(1, axis_name)
    my_idx = jax.lax.axis_index(axis_name)
    perm = [(i, (i + 1) % cp) for i in range(cp)]

    def diag(args):
        q_, k_, v_ = args
        o, lse = flash_lib.flash_attention_fwd_lse(q_, k_, v_,
                                                   causal=True)
        return o.astype(jnp.float32), lse.transpose(0, 2, 1)

    def past(args):
        q_, k_, v_ = args
        o, lse = flash_lib.flash_attention_fwd_lse(q_, k_, v_,
                                                   causal=False)
        return o.astype(jnp.float32), lse.transpose(0, 2, 1)

    def future(args):
        q_, _, _ = args
        return (jnp.zeros(q_.shape, jnp.float32),
                jnp.full((b, sq, hq), NEG_INF, jnp.float32))

    out0 = jnp.zeros((b, sq, hq, d), jnp.float32)
    lse0 = jnp.full((b, sq, hq), NEG_INF, jnp.float32)

    def body(carry, step):
        k_c, v_c, out_run, lse_run = carry
        src = jax.lax.rem(my_idx - step + cp, cp)
        o_c, lse_c = jax.lax.cond(
            src == my_idx, diag,
            lambda a: jax.lax.cond(src < my_idx, past, future, a),
            (q, k_c, v_c))
        # Stable pairwise combine of normalized partial attentions:
        # out = (out_run*e^lse_run + o_c*e^lse_c) / (e^lse_run+e^lse_c).
        m = jnp.maximum(lse_run, lse_c)
        m_safe = jnp.where(m <= NEG_INF / 2, 0.0, m)
        w_run = jnp.exp(lse_run - m_safe)
        w_c = jnp.exp(lse_c - m_safe)
        denom = w_run + w_c
        safe = jnp.where(denom == 0.0, 1.0, denom)
        out_new = (out_run * w_run[..., None] +
                   o_c * w_c[..., None]) / safe[..., None]
        lse_new = jnp.where(denom == 0.0, NEG_INF,
                            m_safe + jnp.log(safe))
        k_c = jax.lax.ppermute(k_c, axis_name, perm)
        v_c = jax.lax.ppermute(v_c, axis_name, perm)
        return (k_c, v_c, out_new, lse_new), None

    (_, _, out, _), _ = jax.lax.scan(body, (k, v, out0, lse0),
                                     jnp.arange(cp))
    return out.astype(q.dtype)


def _ring_flash_fwd_rule(q, k, v, axis_name, scale):
    return _ring_flash_impl(q, k, v, axis_name, scale), (q, k, v)


def _ring_flash_bwd_rule(axis_name, scale, res, g):
    q, k, v = res
    _, vjp = jax.vjp(
        lambda q_, k_, v_: _ring_einsum(q_, k_, v_, axis_name, scale),
        q, k, v)
    return vjp(g)


_ring_flash.defvjp(_ring_flash_fwd_rule, _ring_flash_bwd_rule)


def ring_attention_sharded(q, k, v, mesh: Mesh, causal: bool = True,
                           axis_name: str = 'cp'):
    """jit/GSPMD entry: wraps ring_attention in shard_map over `mesh`.

    q, k, v: [B, S, H, D]; S is split over `axis_name` (GSPMD inserts the
    reshard if the inputs arrive with a different layout).
    """
    spec = P(None, axis_name, None, None)
    fn = functools.partial(ring_attention, axis_name=axis_name,
                           causal=causal)
    return jax.shard_map(fn, mesh=mesh, in_specs=(spec, spec, spec),
                         out_specs=spec, check_vma=False)(q, k, v)
