"""Kimi Delta Attention's recurrence (the Kimi Linear report,
arXiv:2510.26692): the gated delta rule with a decay per key channel,
in its chunked form, in jax.numpy.

Per head, a state S of [dk, dv] starts at zero and each position t
updates it and reads it:

    S'_t = Diag(exp(g_t)) S_{t-1}
    S_t  = S'_t + beta_t k_t (v_t - S'_t^T k_t)^T
    o_t  = S_t^T q_t

g_t <= 0 is a log-decay per key channel, beta_t in [0, 1] the write's
strength; q arrives scaled. The chunked form (the WY/UT form of the
delta rule, as DeltaNet's and the flash-linear-attention library's
`chunk_kda`) cuts the row into chunks of C positions (`dispatch.kda_chunk`)
and, with G_r the decay summed over a chunk's positions up to r and
S_0 the state before the chunk:

    A_kk[r, i] = beta_r sum_d k_r k_i exp(G_r - G_i)    (i < r)
    A_qk[r, i] =        sum_d q_r k_i exp(G_r - G_i)    (i <= r)
    W = (I + A_kk)^-1 diag(beta) (exp(G) * K),  U~ = (I + A_kk)^-1 diag(beta) V
    U = U~ - W S_0
    O = (exp(G) * Q) S_0 + A_qk U
    S_C = Diag(exp(G_C)) S_0 + (exp(G_C - G) * K)^T U

Two rungs, the ladder `kda`, decided by `_resolve_rung` from the
backend and the shape alone: `pallas`, the kernels of
ops/kda_kernel.py (on the TPU, dk and dv multiples of 128: a grid over
(batch, head blocks, chunks), the state carried across the chunks in
VMEM scratch, a chunk's decay sums, pairs, inverse and state update in
VMEM; forward and backward under one custom VJP, the chunk-start
states the residual; docs/kernels.md has the grids, blocks, scratch,
VMEM and the inverse), and `jnp`, this module's form, everywhere else
and as the tests' oracle: everything but the last three lines batched
over all chunks of the row at once, those three a `lax.scan` over the
chunks, the state passed on. The whole form stands under the scope
`kda_scan`, on either rung.

Numerics: a factor exp(G_r - G_i) is never made as exp(G_r) times
exp(-G_i): at the library's initialisation a channel decays by up to
16 * softplus(.) a step, and exp(-G) overflows float32 within a few
positions. A chunk is cut into sub-chunks of `dispatch.KDA_SUB_CHUNK`;
a pair of positions in different sub-chunks takes the factor through
the position just before the later sub-chunk (two factors, each at most
1: one product on the MXU), a pair within one sub-chunk takes it
directly as exp of the difference (`_diag_pairs`, whose backward is
written out so that the [C, C, dk] decays are never kept). Every
factor is at most 1. The products take the default precision, one
bf16 pass on the TPU with float32 sums, as the model's other products
do: at the highest precision (six passes) the sft step's loss, gradients
and change of the parameters read as far from the float32 reference on
a v5e, to 0.02 points of the gradients' 1.4% (PERF.md §6).

Backward: on `jnp` autodiff of this form, but for `_diag_pairs`; on
`pallas` the backward kernel (the same precision rule: float32 where
this form works elementwise in float32).
"""
import jax
import jax.numpy as jnp

from skypilot_tpu.ops import dispatch
from skypilot_tpu.ops import kda_kernel


def _decays(gam):
    """gam [..., s, d] -> [..., s, s, d]: exp(G_r - G_i) where i <= r,
    else 0."""
    s = gam.shape[-2]
    lower = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
    diff = gam[..., :, None, :] - gam[..., None, :, :]
    return jnp.where(lower[:, :, None], jnp.exp(jnp.minimum(diff, 0.0)), 0.0)


@jax.custom_vjp
def _diag_pairs(a, b, gam):
    """a, b, gam [..., s, d] -> [..., s, s]: sum_d a_r b_i exp(G_r - G_i)
    for i <= r, else 0. One reduction; the backward forms the decays
    again in its own."""
    return jnp.sum(a[..., :, None, :] * b[..., None, :, :] * _decays(gam),
                   axis=-1)


def _diag_pairs_fwd(a, b, gam):
    return _diag_pairs(a, b, gam), (a, b, gam)


def _diag_pairs_bwd(res, g):
    a, b, gam = res
    e = _decays(gam) * g[..., None]
    da = jnp.sum(e * b[..., None, :, :], axis=-2)
    db = jnp.sum(e * a[..., :, None, :], axis=-3)
    # d/dG_r of a term is the term itself; d/dG_i, minus it.
    return da, db, a * da - b * db


_diag_pairs.defvjp(_diag_pairs_fwd, _diag_pairs_bwd)


def _pairs(a, b, gam, strict: bool):
    """a, b, gam [..., C, d] -> [..., C, C]: sum_d a_r b_i exp(G_r - G_i)
    for i < r (strict) or i <= r, else 0, by sub-chunks (module
    docstring)."""
    c, d = gam.shape[-2:]
    sub = min(dispatch.KDA_SUB_CHUNK, c)
    n = c // sub
    split = a.shape[:-2] + (n, sub, d)
    diag = _diag_pairs(a.reshape(split), b.reshape(split), gam.reshape(split))
    if strict:
        diag = diag * (1.0 - jnp.eye(sub, dtype=diag.dtype))
    rows = []
    for j in range(n):
        lo, hi = j * sub, (j + 1) * sub
        parts = []
        if j:
            ref = gam[..., lo - 1:lo, :]
            parts.append(jnp.einsum(
                '...rd,...id->...ri', a[..., lo:hi, :] *
                jnp.exp(gam[..., lo:hi, :] - ref),
                b[..., :lo, :] * jnp.exp(ref - gam[..., :lo, :])))
        parts.append(diag[..., j, :, :])
        if hi < c:
            parts.append(jnp.zeros(diag.shape[:-3] + (sub, c - hi),
                                   diag.dtype))
        rows.append(jnp.concatenate(parts, axis=-1))
    return jnp.concatenate(rows, axis=-2)


def _resolve_rung(dk: int, dv: int) -> str:
    """The one place that decides the first rung of the ladder `kda`,
    from the backend and the shape: `pallas` (ops/kda_kernel.py) on the
    TPU where the key and value widths are whole lanes (multiples of
    128), `jnp` everywhere else: off the TPU (the kernels run there
    only interpreted, in the tests) and at narrower heads."""
    if dispatch.interpret_mode():
        return 'jnp'
    return 'pallas' if dk % dispatch.LANES == 0 and \
        dv % dispatch.LANES == 0 else 'jnp'


def chunk_kda(q, k, v, g, beta):
    """q, k, g: [B, S, H, dk]; v: [B, S, H, dv]; beta: [B, S, H] ->
    o [B, S, H, dv], float32. q arrives scaled (and, as in the model,
    L2-normed with k); g is the log-decay, <= 0. The state starts at
    zero at position 0. On the first rung that works: `pallas` where
    `_resolve_rung` offers it, then `jnp`."""
    with jax.named_scope('kda_scan'):
        rungs = [('jnp', lambda: _chunk_form(q, k, v, g, beta))]
        if _resolve_rung(q.shape[-1], v.shape[-1]) == 'pallas':
            rungs.insert(0, ('pallas', lambda: kda_kernel.chunk_kda(
                q, k, v, g, beta)))
        return dispatch.run_ladder('kda', rungs)


def _chunk_form(q, k, v, g, beta):
    """`chunk_kda` in jax.numpy: the rung `jnp`, and the tests' oracle."""
    b, s, h, dk = q.shape
    dv = v.shape[-1]
    c = dispatch.kda_chunk(s)
    pad = -s % c
    n = (s + pad) // c

    def chunks(x):
        x = jnp.pad(x.astype(jnp.float32),
                    [(0, 0), (0, pad)] + [(0, 0)] * (x.ndim - 2))
        x = x.reshape(b, n, c, h, -1).transpose(0, 3, 1, 2, 4)
        return x                                  # [B, H, N, C, d]
    q, k, v, g = map(chunks, (q, k, v, g))
    beta = chunks(beta[..., None])                # [B, H, N, C, 1]
    gam = jnp.cumsum(g, axis=-2)
    decay = jnp.exp(gam)                          # <= 1
    a_kk = beta * _pairs(k, k, gam, strict=True)
    a_qk = _pairs(q, k, gam, strict=False)
    eye = jnp.eye(c, dtype=jnp.float32)
    wu = jax.lax.linalg.triangular_solve(
        eye + a_kk, beta * jnp.concatenate([decay * k, v], axis=-1),
        left_side=True, lower=True, unit_diagonal=True)
    w, u_t = wu[..., :dk], wu[..., dk:]
    last = gam[..., -1:, :]
    k_hat = k * jnp.exp(last - gam)               # <= 1
    carry = jnp.exp(last[..., 0, :])              # [B, H, N, dk]

    def step(state, xs):
        w, u_t, k_hat, carry = xs
        u = u_t - jnp.einsum('bhcd,bhde->bhce', w, state)
        state_next = carry[..., None] * state + \
            jnp.einsum('bhcd,bhce->bhde', k_hat, u)
        return state_next, (state, u)

    def along(x):
        return jnp.moveaxis(x, 2, 0)
    _, (starts, u) = jax.lax.scan(
        step, jnp.zeros((b, h, dk, dv), jnp.float32),
        (along(w), along(u_t), along(k_hat), along(carry)))
    starts, u = jnp.moveaxis(starts, 0, 2), jnp.moveaxis(u, 0, 2)
    o = jnp.einsum('bhncd,bhnde->bhnce', decay * q, starts) + \
        jnp.einsum('bhnri,bhnie->bhnre', a_qk, u)
    o = o.transpose(0, 2, 3, 1, 4).reshape(b, n * c, h, dv)
    return o[:, :s]


HEADS_A_PASS = 8


def head_groups(heads: int) -> int:
    """Groups a KDA layer's heads are worked on in, one after another,
    from the head count alone: passes of `HEADS_A_PASS` heads. A
    layer's float32 temporaries in its backward (the gates, the chunked
    form, the output gate: some 6 GB at 16,384 positions and 32 heads of
    128) are then a group's; the price is a group's forward run once
    more inside the block's backward."""
    return heads // HEADS_A_PASS if heads % HEADS_A_PASS == 0 else 1


def plan(batch: int, seq: int, heads: int, dk: int, dv: int) -> dict:
    """The rung one call of `chunk_kda` over `heads` heads takes, and
    the grid steps of its forward kernel (0 on the `jnp` rung)."""
    rung = _resolve_rung(dk, dv)
    return {'rung': rung, 'steps': kda_kernel.grid_steps(batch, seq, heads)
            if rung == 'pallas' else 0}


def state_bytes(batch: int, seq: int, heads: int, dk: int, dv: int) -> int:
    """Bytes of one layer's chunk-start states (float32), what the
    scan's backward keeps of a layer."""
    c = dispatch.kda_chunk(seq)
    return batch * -(-seq // c) * heads * dk * dv * 4
