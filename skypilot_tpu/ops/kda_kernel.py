"""Kimi Delta Attention's chunked form (ops/kda.py) as Pallas TPU
kernels, forward and backward, under one `jax.custom_vjp`: the rung
`pallas` of the ladder `kda` (docs/kernels.md).

Layout: q, k, g [B, S, H, dk] and v [B, S, H, dv] are read in place as
[B, S, H x d] (a free reshape), a block of `chunks_a_step` chunks by
the lanes of `heads_a_step` heads; beta [B, S, H] as [B, H / hb, S,
hb]. No copy of an input is made in HBM. The grid is (batch, head
blocks, chunk blocks); the chunks are walked in order by the forward
and in reverse by the backward, which carry the heads' states (their
cotangent) in VMEM scratch.

A step stacks each chunk's heads on rows, [hb x C, d], so that a
chunk's pairs and inverse are one tile of the MXU with the heads'
blocks on its diagonal, and works on its chunks in lockstep (`_each`):
what does not depend on the state, stage by stage for every chunk; the
state's recurrence, chunk after chunk. Per chunk, in VMEM (ops/kda.py
has the equations): G, the decay summed over the chunk's positions,
as a [C, C] lower-triangular product at float32 accuracy; the pairs
A_kk and A_qk by the sub-chunk scheme of the `jnp` form (across
sub-chunks through the position before the later one, within one
through exp of the difference: every factor at most 1); (I + A_kk)^-1
(`unit_lower_inverse`); W, U~, U, O and the next state. The state is
kept transposed, [dv, dk], so that its decay is a row's broadcast.

The products take the form's precision: one bf16 pass with float32
sums on the TPU, float32 where the kernels run interpreted (as the
default precision of `jnp` does). Float32 where the `jnp` form is
float32: the decay sums, which are exponentiated, the pairs within a
sub-chunk in the backward, and the inverse (one bf16 pass refined
once on the TPU).

Residuals: the inputs and the chunk-start states [B, N, H x dv, dk]
(what `kda.state_bytes` counts). The backward recomputes a chunk's
G, pairs, inverse, W, U~ and U from them.
"""
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from skypilot_tpu.ops import dispatch

SUB = dispatch.KDA_SUB_CHUNK
ROWS_A_STEP = 128
CHUNKS_A_STEP = 4

_NN = (((1,), (0,)), ((), ()))
_NT = (((1,), (1,)), ((), ()))
_TN = (((0,), (0,)), ((), ()))
_F32 = jnp.float32


def heads_a_step(heads: int, c: int) -> int:
    """Heads a grid step works on, stacked: the most that divide the
    heads with `ROWS_A_STEP` rows at most (2 at chunks of 64), at least
    one. Their pairs and inverse are then one tile of the MXU, the
    heads' blocks on its diagonal."""
    return max([h for h in range(1, heads + 1)
                if heads % h == 0 and h * c <= ROWS_A_STEP] + [1])


def chunks_a_step(chunks: int) -> int:
    """Chunks a grid step works on, one after another: the most that
    divide the row's chunks, `CHUNKS_A_STEP` at most."""
    return max(n for n in range(1, CHUNKS_A_STEP + 1) if chunks % n == 0)


def grid_steps(batch: int, seq: int, heads: int) -> int:
    """Grid steps of one forward (or backward) call."""
    c = dispatch.kda_chunk(seq)
    n = -(-seq // c)
    return batch * (heads // heads_a_step(heads, c)) * (n // chunks_a_step(n))


def _dot(a, b, dims, fast):
    """A product at the form's precision: with `fast` (compiled for the
    TPU) one bf16 pass with float32 sums, else float32."""
    if fast:
        a, b = a.astype(jnp.bfloat16), b.astype(jnp.bfloat16)
    return jax.lax.dot_general(a, b, dims, preferred_element_type=_F32)


def _mask_product(mask, x, fast, ones=None, dims=_NN):
    """A 0/1 matrix times x (`mask` [n, n] as 0 and 1 on the left), or x
    times one (`ones` on the right, `dims` _NN or _TN), at float32
    accuracy: a decay sum, which is exponentiated, or a gather of
    entries. With `fast` one bf16 pass over x cut into three bf16
    parts, which sum to it exactly, side by side in one product."""
    left = ones is None
    if fast:
        hi = x.astype(jnp.bfloat16)
        rest = x - hi.astype(_F32)
        mid = rest.astype(jnp.bfloat16)
        lo = (rest - mid.astype(_F32)).astype(jnp.bfloat16)
        # the parts along x's side that the product keeps
        x = jnp.concatenate([hi, mid, lo], axis=1 if left or dims == _TN
                            else 0)
    dtype = x.dtype
    if left:
        y = jax.lax.dot_general(jnp.where(mask, 1.0, 0.0).astype(dtype), x,
                                _NN, preferred_element_type=_F32)
    else:
        y = jax.lax.dot_general(x, ones.astype(dtype), dims,
                                preferred_element_type=_F32)
    if not fast:
        return y
    if left:
        d = y.shape[1] // 3
        return y[:, :d] + y[:, d:2 * d] + y[:, 2 * d:]
    m = y.shape[0] // 3
    return y[:m] + y[m:2 * m] + y[2 * m:]


def _block_of(x, size, blocks):
    """x // size for int32 x in [0, size x blocks), by comparisons (the
    vector unit has no divider)."""
    out = jnp.zeros_like(x)
    for b in range(1, blocks):
        out = out + jnp.where(x >= b * size, 1, 0)
    return out


def _split_product(a, b, dims):
    """a times b to float32's accuracy within a few units of bf16's
    square, on the MXU in one bf16 pass: a and b each cut into a bf16
    part and a bf16 rest, and the rests' product left out."""
    bf = jnp.bfloat16
    a_hi, b_hi = a.astype(bf), b.astype(bf)
    a_lo = (a - a_hi.astype(_F32)).astype(bf)
    b_lo = (b - b_hi.astype(_F32)).astype(bf)

    def mm(x, y):
        return jax.lax.dot_general(x, y, dims, preferred_element_type=_F32)
    return mm(a_hi, b_hi) + mm(a_lo, b_hi) + mm(a_hi, b_lo)


def _inverses(mats, nilpotent, fast, dims=_NN):
    """(I + a)^-1 of each strictly lower a [..., n, n] of `mats`, in
    lockstep: the product (I - N)(I + N^2)(I + N^4)... over N = a,
    exact once the power reaches `nilpotent` (N^nilpotent = 0), so
    log2 of it squarings and as many products. Without `fast` in
    float32. With it the products take one bf16 pass and the result is
    refined once, x + x (I - (I + a) x), the residual's a x by
    `_split_product`: float32 arithmetic, within 3e-6 of
    `triangular_solve` (tests/test_kda.py)."""
    n = mats[0].shape[-1]
    row = jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (n, n), 1)
    eye = jnp.where(row == col, 1.0, 0.0).astype(_F32)

    def mm(x, y):
        return _dot(x, y, dims, fast)
    xs = [eye - a for a in mats]
    powers, span = mats, 2
    while span < nilpotent:
        powers = [mm(p, p) for p in powers]
        xs = [x + mm(x, p) for x, p in zip(xs, powers)]
        span *= 2
    if fast:
        xs = [x + mm(x, eye - x - _split_product(a, x, dims))
              for a, x in zip(mats, xs)]
    return xs


def unit_lower_inverse(a, nilpotent=None, fast=False):
    """(I + a)^-1 for a strictly lower a [..., n, n] (`_inverses`);
    `nilpotent` n where not given (a block-diagonal a of blocks of c
    needs c)."""
    batch = tuple(range(a.ndim - 2))
    dims = (((a.ndim - 1,), (a.ndim - 2,)), (batch, batch))
    return _inverses([a], nilpotent or a.shape[-1], fast, dims)[0]


def _spread(x, size, i):
    """Each block of `size` rows set to that block's row i."""
    n, d = x.shape
    return jnp.broadcast_to(x.reshape(n // size, size, d)[:, i:i + 1, :],
                            (n // size, size, d)).reshape(n, d)


def _block_diag(x, c, heads):
    """x [heads x c, d] -> [heads x c, heads x d]: head h's rows in the
    lanes of block h, zero elsewhere; a product with the heads' stacked
    states is then each head's with its own."""
    if heads == 1:
        return x
    n, d = x.shape
    row = jax.lax.broadcasted_iota(jnp.int32, (n, heads * d), 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (n, heads * d), 1)
    return jnp.where(_block_of(row, c, heads) == _block_of(lane, d, heads),
                     jnp.concatenate([x] * heads, axis=1), 0.0)


def _diag_blocks(x, c, heads):
    """x [heads x c, heads x d] -> [heads x c, d]: head h's rows of lane
    block h."""
    d = x.shape[1] // heads
    return jnp.concatenate([x[h * c:(h + 1) * c, h * d:(h + 1) * d]
                            for h in range(heads)])


def _each(f, *lists):
    """f over the chunks' lists, one stage for every chunk before the
    next stage: the chunks' chains of small products then overlap on
    the MXU (a chain alone waits on each product's latency)."""
    return [f(*xs) for xs in zip(*lists)]


class _Chunks:
    """A grid step's chunks: lists with one entry a chunk, each the
    step's heads stacked ([heads x c, d]). What the forward and the
    backward share, and what does not depend on the state, is worked
    out here in lockstep (`_each`)."""

    def __init__(self, q, k, v, g, beta, c, fast):
        n, dk = q[0].shape
        self.c, self.heads, self.fast = c, n // c, fast
        self.dk, self.dv = dk, v[0].shape[1]
        self.q, self.k, self.v, self.beta = q, k, v, beta
        row = jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
        col = jax.lax.broadcasted_iota(jnp.int32, (n, n), 1)
        head = _block_of(row, c, self.heads)
        same = head == _block_of(col, c, self.heads)
        self.lower, self.strict = same & (col <= row), same & (col < row)
        self.upper = same & (col >= row)
        # each row's sub-chunk within its chunk, and the stacked row its
        # sub-chunk starts at (SUB is a power of two)
        self.sub = _block_of(row - head * c, SUB, c // SUB)
        self.start = jnp.bitwise_and(row, -SUB)
        self.col = col
        self.gam = _each(lambda x: _mask_product(self.lower, x, fast), g)
        self.p_qk, self.p_kk = self._pairs()
        self.t = _inverses(_each(lambda b, p: b * p, beta, self.p_kk), c,
                           fast)
        self.decay = _each(jnp.exp, self.gam)                 # <= 1
        self.wu = _each(lambda t, b, d, k, v: self.dot(t, b * jnp.concatenate(
            [d * k, v], axis=1), _NN), self.t, beta, self.decay, k, v)
        self.last = _each(lambda x: _spread(x, c, c - 1), self.gam)
        self.k_hat = _each(lambda k, last, x: k * jnp.exp(last - x), k,
                           self.last, self.gam)               # <= 1
        # each head's decay over the chunk, on its rows of the state
        self.carry = _each(lambda last: jnp.concatenate([
            jnp.broadcast_to(jnp.exp(last[h * c:h * c + 1]), (self.dv, dk))
            for h in range(self.heads)]), self.last)

    def dot(self, a, b, dims):
        return _dot(a, b, dims, self.fast)

    def bd(self, x):
        return _block_diag(x, self.c, self.heads)

    def diag(self, x):
        return _diag_blocks(x, self.c, self.heads)

    def _cross(self, gam, j):
        """Sub-chunk j's factors through the position before it, in
        every head: exp(G_r - G_ref) on all rows (the rows of j are
        read), and exp(G_ref - G_i) on the positions before j, zero from
        j on. Both at most 1."""
        lo = j * SUB
        ref = _spread(gam, self.c, lo - 1)
        row = jax.lax.broadcasted_iota(jnp.int32, gam.shape, 0)
        pos = row - self.c * _block_of(row, self.c, self.heads)
        fa = jnp.exp(jnp.minimum(gam - ref, 0.0))
        fb = jnp.where(pos < lo, jnp.exp(jnp.minimum(ref - gam, 0.0)), 0.0)
        return fa, fb

    def _offset(self, gam, i):
        """exp(G_r - G_s), s the row at offset i of r's sub-chunk: at
        most 1 where s <= r, the pairs read."""
        return jnp.exp(jnp.minimum(gam - _spread(gam, SUB, i), 0.0))

    def _pairs(self):
        """A_qk (i <= r) and the unweighted A_kk (i < r) of every head,
        zero across heads, [n, n] each: sum_d a_r k_i exp(G_r - G_i) for
        a = q, k. Within a sub-chunk one product a row offset i, the
        factors exp of the difference to that row; across, one product a
        sub-chunk, through the position before it."""
        n = self.q[0].shape[0]
        pair = [jnp.zeros((2 * n, n), _F32)] * len(self.q)
        for i in range(SUB):
            at = self.col == self.start + i
            at = jnp.concatenate([at, at])
            pair = _each(lambda p, q, k, x: jnp.where(at, self.dot(
                jnp.concatenate([q, k]) * jnp.concatenate(
                    [self._offset(x, i)] * 2), k, _NT), p),
                pair, self.q, self.k, self.gam)
        for j in range(1, self.c // SUB):
            rows = self.sub == j
            rows = jnp.concatenate([rows, rows])

            def cross(p, q, k, x):
                fa, fb = self._cross(x, j)
                return p + jnp.where(rows, self.dot(
                    jnp.concatenate([q * fa, k * fa]), k * fb, _NT), 0.0)
            pair = _each(cross, pair, self.q, self.k, self.gam)
        return (_each(lambda p: jnp.where(self.lower, p[:n], 0.0), pair),
                _each(lambda p: jnp.where(self.strict, p[n:], 0.0), pair))

    def _pairs_bwd(self, dp_qk, dp_kk):
        """The transpose of `_pairs`: the gradients of q (as a), of k as
        a and of k as the key side, and what the reference positions
        take of dG, [n, d] each, a chunk each. Within a
        sub-chunk elementwise in float32, as the `jnp` form's
        `_diag_pairs_bwd` (dG is a difference of near-equal sums there,
        which one bf16 pass would leave in their rounding): a pair's
        cotangent reaches one row of the other side, so the sub-chunk's
        entries of dP, picked out a row offset a column by an exact
        product with a 0/1 matrix, scale the other side's rows spread
        over the sub-chunk."""
        n, d = self.q[0].shape
        start_col = jnp.bitwise_and(self.col, -SUB)
        in_sub = self.start == start_col
        pick = jnp.where(jax.lax.broadcasted_iota(jnp.int32, (n, SUB), 0) %
                         SUB == jax.lax.broadcasted_iota(
                             jnp.int32, (n, SUB), 1), 1.0, 0.0)

        def diag(q, k, gam, pq, pk):
            # dP_qk's and dP_kk's entries (r, start(r) + i), and (start(c)
            # + i, c), stacked, [2n, SUB]
            rows = _mask_product(None, jnp.where(
                jnp.concatenate([in_sub, in_sub]), jnp.concatenate([pq, pk]),
                0.0), self.fast, pick)
            cols = _mask_product(None, jnp.where(
                jnp.concatenate([in_sub, in_sub], axis=1),
                jnp.concatenate([pq, pk], axis=1), 0.0), self.fast, pick, _TN)
            da_q = da_k = db = jnp.zeros((n, d), _F32)
            for i in range(SUB):
                x = gam - _spread(gam, SUB, i)
                t = jnp.exp(-jnp.abs(x))
                e = jnp.where(x <= 0.0, t, 1.0)       # exp(G_r - G_s)
                f = jnp.where(x <= 0.0, 1.0, t)       # exp(G_s - G_r)
                k_i = _spread(k, SUB, i)
                ke = k_i * e
                da_q = da_q + rows[:n, i:i + 1] * ke
                da_k = da_k + rows[n:, i:i + 1] * ke
                db = db + (cols[:n, i:i + 1] * _spread(q, SUB, i) +
                           cols[n:, i:i + 1] * k_i) * f
            return jnp.concatenate([da_q, da_k]), db
        da, db = zip(*_each(diag, self.q, self.k, self.gam, dp_qk, dp_kk))
        dref = [jnp.zeros((n, d), _F32)] * len(self.q)
        pos = jax.lax.broadcasted_iota(jnp.int32, (n, d), 0)
        for j in range(1, self.c // SUB):
            rows = self.sub == j

            def cross(da, db, dref, q, k, x, pq, pk):
                fa, fb = self._cross(x, j)
                dm = jnp.concatenate([jnp.where(rows, pq, 0.0),
                                      jnp.where(rows, pk, 0.0)])
                da_j = jnp.concatenate([fa, fa]) * self.dot(dm, k * fb, _NN)
                db_j = fb * self.dot(dm, jnp.concatenate([q * fa, k * fa]),
                                     _TN)
                # The reference position's share of dG, in each head:
                # minus what the sub-chunk's rows and the keys before it
                # take. It is zero in exact arithmetic; with the products
                # rounded it keeps each pair's dG a difference of one
                # rounded term, so that dG sums to zero over a head's
                # positions, as autodiff of the `jnp` form's products
                # keeps it (else A_log's gradient, a sum over every
                # position, drifts by the rounding: 10% on the chip).
                t = q * da_j[:n] + k * (da_j[n:] - db_j)
                for h in range(self.heads):
                    dref = jnp.where(
                        pos == h * self.c + j * SUB - 1,
                        dref - jnp.sum(t[h * self.c:(h + 1) * self.c],
                                       axis=0, keepdims=True), dref)
                return da + da_j, db + db_j, dref
            da, db, dref = zip(*_each(cross, da, db, dref, self.q, self.k,
                                      self.gam, dp_qk, dp_kk))
        return [x[:n] for x in da], [x[n:] for x in da], list(db), list(dref)

    def forward(self, st):
        """o of each chunk [n, dv], each chunk's start states and the last
        chunk's end states [heads x dv, dk], from the first's start."""
        n, dk = self.q[0].shape
        starts, u, o_state = [], [], []
        for w, d, q, k_hat, carry, wu in zip(
                (x[:, :dk] for x in self.wu), self.decay, self.q,
                self.k_hat, self.carry, self.wu):
            starts.append(st)
            y = self.dot(jnp.concatenate([w, d * q]), st, _NT)
            u.append(wu[:, dk:] - self.diag(y[:n]))
            o_state.append(self.diag(y[n:]))
            st = st * carry + self.dot(self.bd(u[-1]), k_hat, _TN)
        o = _each(lambda s, p, u: s + self.dot(p, u, _NN), o_state,
                  self.p_qk, u)
        return o, starts, st

    def backward(self, starts, do, dst):
        """Each chunk's gradients (dq, dk, dv, dg [n, d]; dbeta [n, 1])
        and the first chunk's start states' cotangent, from the last
        chunk's end states', dst."""
        dk, m = self.dk, len(self.q)
        w = [x[:, :dk] for x in self.wu]
        u = _each(lambda wu, w, st: wu[:, dk:] - self.diag(
            self.dot(w, st, _NT)), self.wu, w, starts)
        pq_do = _each(lambda p, do: self.dot(p, do, _TN), self.p_qk, do)
        do_qd = _each(lambda do, d, q: self.dot(self.bd(do), d * q, _TN),
                      do, self.decay, self.q)
        # the one chain: the state's cotangent, the last chunk first
        du, ends = [None] * m, [None] * m
        for i in reversed(range(m)):
            ends[i] = dst
            du[i] = pq_do[i] + self.diag(self.dot(self.k_hat[i], dst, _NT))
            dst = do_qd[i] + dst * self.carry[i] - \
                self.dot(self.bd(du[i]), w[i], _TN)
        dp_qk = _each(lambda do, u: jnp.where(self.lower, self.dot(
            do, u, _NT), 0.0), do, u)
        n = self.q[0].shape[0]
        by_state = _each(lambda do, du, st: self.dot(jnp.concatenate(
            [self.bd(do), self.bd(du)]), st, _NN), do, du, starts)
        dqd = [y[:n] for y in by_state]
        dw = [-y[n:] for y in by_state]
        dk_hat = _each(lambda u, e: self.dot(self.bd(u), e, _NN), u, ends)
        dr = _each(lambda t, dw, du: self.dot(t, jnp.concatenate(
            [dw, du], axis=1), _TN), self.t, dw, du)
        da = _each(lambda dr, wu: jnp.where(self.strict, -self.dot(
            dr, wu, _NT), 0.0), dr, self.wu)
        dq_p, dk_a, dk_b, dref = self._pairs_bwd(
            dp_qk, _each(lambda b, a: b * a, self.beta, da))

        def grads(q, k, v, beta, gam, decay, last, k_hat, p_kk, dr, da,
                  dqd, dk_hat, dq_p, dk_a, dk_b, dref, st, end):
            kd = decay * k
            dbeta = jnp.sum(dr[:, :dk] * kd, axis=1, keepdims=True) + \
                jnp.sum(dr[:, dk:] * v, axis=1, keepdims=True) + \
                jnp.sum(da * p_kk, axis=1, keepdims=True)
            dkd = beta * dr[:, :dk]
            dq = decay * dqd + dq_p
            dk_ = decay * dkd + jnp.exp(last - gam) * dk_hat + dk_a + dk_b
            dgam = decay * (q * dqd + k * dkd) - k_hat * dk_hat + \
                q * dq_p + k * (dk_a - dk_b) + dref
            # the last position's sum, in k_hat and in the state's decay,
            # reaches every position of the head
            c, dv = self.c, self.dv
            dlast = jnp.concatenate([jnp.broadcast_to(
                jnp.sum((k_hat * dk_hat)[h * c:(h + 1) * c], axis=0,
                        keepdims=True) +
                jnp.exp(last[h * c:h * c + 1]) *
                jnp.sum((st * end)[h * dv:(h + 1) * dv], axis=0,
                        keepdims=True), (c, dk)) for h in range(self.heads)])
            dg = _mask_product(self.upper, dgam, self.fast) + dlast
            return dq, dk_, beta * dr[:, dk:], dg, dbeta
        return _each(grads, self.q, self.k, self.v, self.beta, self.gam,
                     self.decay, self.last, self.k_hat, self.p_kk, dr, da,
                     dqd, dk_hat, dq_p, dk_a, dk_b, dref, starts, ends), dst


def _stacked(ref, rows, heads, d):
    """A block's `rows` of `heads` heads' lanes as one stack of rows,
    [heads x c, d]."""
    return jnp.concatenate([ref[rows, h * d:(h + 1) * d]
                            for h in range(heads)])


def _unstack(ref, rows, x, heads, c):
    """The inverse of `_stacked`, into ref."""
    d = x.shape[1]
    for h in range(heads):
        ref[rows, h * d:(h + 1) * d] = x[h * c:(h + 1) * c]


def _read(refs, widths, heads, c, chunks):
    """Each ref's chunks, stacked: one list a ref."""
    return [[_stacked(r, slice(i * c, (i + 1) * c), heads, d)
             for i in range(chunks)] for r, d in zip(refs, widths)]


def _forward_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, o_ref, start_ref,
                    state, *, heads, c, chunks, dk, dv, fast):
    @pl.when(pl.program_id(2) == 0)
    def _():
        state[...] = jnp.zeros_like(state)

    q, k, v, g, beta = _read((q_ref, k_ref, v_ref, g_ref, beta_ref),
                             (dk, dk, dv, dk, 1), heads, c, chunks)
    o, starts, state[...] = _Chunks(q, k, v, g, beta, c, fast).forward(
        state[...])
    for i in range(chunks):
        start_ref[i] = starts[i]
        _unstack(o_ref, slice(i * c, (i + 1) * c), o[i], heads, c)


def _backward_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, start_ref, do_ref,
                     dq_ref, dk_ref, dv_ref, dg_ref, dbeta_ref, dstate, *,
                     heads, c, chunks, dk, dv, fast):
    @pl.when(pl.program_id(2) == 0)
    def _():
        dstate[...] = jnp.zeros_like(dstate)

    q, k, v, g, beta, do = _read(
        (q_ref, k_ref, v_ref, g_ref, beta_ref, do_ref),
        (dk, dk, dv, dk, 1, dv), heads, c, chunks)
    grads, dstate[...] = _Chunks(q, k, v, g, beta, c, fast).backward(
        [start_ref[i] for i in range(chunks)], do, dstate[...])
    for i in range(chunks):
        for ref, x in zip((dq_ref, dk_ref, dv_ref, dg_ref, dbeta_ref),
                          grads[i]):
            _unstack(ref, slice(i * c, (i + 1) * c), x, heads, c)


def _vmem_bytes(c, hb, cb, dk, dv, backward):
    """VMEM a grid step holds, counted from above: each streamed block
    twice (the pipeline's double buffer), the state's scratch, and the
    chunks' float32 temporaries (some forty [2 hb c, max(hb c, d)] a
    chunk, all chunks' at once in lockstep)."""
    # q, k, g; v and o (or dO); beta; and in the backward the gradients
    width = hb * (3 * dk + 2 * dv + 1)
    if backward:
        width += hb * (3 * dk + dv + 1)
    n = hb * c
    blocks = 2 * cb * (c * width + hb * dk * dv) * 4
    return blocks + hb * dk * dv * 4 + cb * 40 * 2 * n * max(n, dk, dv) * 4


def _params(need):
    return pltpu.CompilerParams(
        dimension_semantics=('parallel', 'parallel', 'arbitrary'),
        vmem_limit_bytes=dispatch.flash_vmem_limit(need))


def _layout(q, k, v, g, beta):
    """The inputs padded to whole chunks and flattened over the heads."""
    b, s, h, _ = q.shape
    c = dispatch.kda_chunk(s)
    pad = -s % c
    hb = heads_a_step(h, c)

    def flat(x):
        x = jnp.pad(x.astype(_F32), [(0, 0), (0, pad), (0, 0), (0, 0)])
        return x.reshape(b, s + pad, -1)
    beta = jnp.pad(beta.astype(_F32), [(0, 0), (0, pad), (0, 0)])
    beta = beta.reshape(b, s + pad, h // hb, hb).transpose(0, 2, 1, 3)
    return (*map(flat, (q, k, v, g)), beta), c, hb


def _specs(c, hb, cb, dk, dv, steps, reverse):
    def at(i):
        return steps - 1 - i if reverse else i
    key = pl.BlockSpec((None, cb * c, hb * dk), lambda b, h, i: (b, at(i), h))
    val = pl.BlockSpec((None, cb * c, hb * dv), lambda b, h, i: (b, at(i), h))
    beta = pl.BlockSpec((None, None, cb * c, hb),
                        lambda b, h, i: (b, h, at(i), 0))
    state = pl.BlockSpec((None, cb, hb * dv, dk),
                         lambda b, h, i: (b, at(i), h, 0))
    return key, val, beta, state


@jax.jit
def kda_forward(q, k, v, g, beta):
    """o [B, S, H, dv] and the chunk-start states [B, N, H x dv, dk]."""
    b, s, h, dk = q.shape
    dv = v.shape[-1]
    (q, k, v, g, beta), c, hb = _layout(q, k, v, g, beta)
    n = q.shape[1] // c
    cb = chunks_a_step(n)
    key, val, beta_spec, state = _specs(c, hb, cb, dk, dv, n // cb,
                                        reverse=False)
    fast = not dispatch.interpret_mode()
    o, starts = pl.pallas_call(
        functools.partial(_forward_kernel, heads=hb, c=c, chunks=cb, dk=dk,
                          dv=dv, fast=fast),
        out_shape=(jax.ShapeDtypeStruct((b, n * c, h * dv), _F32),
                   jax.ShapeDtypeStruct((b, n, h * dv, dk), _F32)),
        grid=(b, h // hb, n // cb),
        in_specs=[key, key, val, key, beta_spec],
        out_specs=(val, state),
        scratch_shapes=[pltpu.VMEM((hb * dv, dk), _F32)],
        compiler_params=_params(_vmem_bytes(c, hb, cb, dk, dv, False)),
        interpret=not fast,
        name='kda_forward',
    )(q, k, v, g, beta)
    return o.reshape(b, n * c, h, dv)[:, :s], starts


@jax.jit
def kda_backward(q, k, v, g, beta, starts, do):
    """The gradients of q, k, v, g and beta from o's, `do`."""
    b, s, h, dk = q.shape
    dv = v.shape[-1]
    (q, k, v, g, beta), c, hb = _layout(q, k, v, g, beta)
    n = q.shape[1] // c
    do = jnp.pad(do.astype(_F32), [(0, 0), (0, n * c - s), (0, 0), (0, 0)])
    do = do.reshape(b, n * c, h * dv)
    cb = chunks_a_step(n)
    key, val, beta_spec, state = _specs(c, hb, cb, dk, dv, n // cb,
                                        reverse=True)
    fast = not dispatch.interpret_mode()
    dq, dk_, dv_, dg, dbeta = pl.pallas_call(
        functools.partial(_backward_kernel, heads=hb, c=c, chunks=cb, dk=dk,
                          dv=dv, fast=fast),
        out_shape=(jax.ShapeDtypeStruct(q.shape, _F32),
                   jax.ShapeDtypeStruct(k.shape, _F32),
                   jax.ShapeDtypeStruct(v.shape, _F32),
                   jax.ShapeDtypeStruct(g.shape, _F32),
                   jax.ShapeDtypeStruct(beta.shape, _F32)),
        grid=(b, h // hb, n // cb),
        in_specs=[key, key, val, key, beta_spec, state, val],
        out_specs=(key, key, val, key, beta_spec),
        scratch_shapes=[pltpu.VMEM((hb * dv, dk), _F32)],
        compiler_params=_params(_vmem_bytes(c, hb, cb, dk, dv, True)),
        interpret=not fast,
        name='kda_backward',
    )(q, k, v, g, beta, starts, do)

    def unflat(x, d):
        return x.reshape(b, n * c, h, d)[:, :s]
    dbeta = dbeta.transpose(0, 2, 1, 3).reshape(b, n * c, h)[:, :s]
    return (unflat(dq, dk), unflat(dk_, dk), unflat(dv_, dv),
            unflat(dg, dk), dbeta)


@jax.custom_vjp
def chunk_kda(q, k, v, g, beta):
    """`kda.chunk_kda` on the kernels: the same arguments and result."""
    return kda_forward(q, k, v, g, beta)[0]


def _chunk_kda_fwd(q, k, v, g, beta):
    o, starts = kda_forward(q, k, v, g, beta)
    return o, (q, k, v, g, beta, starts)


def _chunk_kda_bwd(res, do):
    # The rule is traced where the transpose stands, outside the scope
    # the forward was called under: open it again, so that the backward
    # kernel's time reads under `kda_scan` too.
    with jax.named_scope('kda_scan'):
        grads = kda_backward(*res, do)
    return tuple(x.astype(r.dtype) for x, r in zip(grads, res))


chunk_kda.defvjp(_chunk_kda_fwd, _chunk_kda_bwd)
