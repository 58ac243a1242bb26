"""Kimi Delta Attention's chunked form (ops/kda.py) against the
recurrence one position at a time (the reference's `kda_sequential`),
forward and every input's gradient, across chunk and sub-chunk
boundaries and at the ends of its ranges: no write (beta 0), a full
write (beta 1), almost no decay, and a decay of 30 a step, which
overflows float32 within a few positions wherever a factor is made as
exp(-G). Both rungs of the ladder `kda`: the `jnp` form, and the Pallas
kernels (ops/kda_kernel.py), interpreted here, at widths of 128 and
four heads, two a grid step, against the `jnp` form too."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from skypilot_tpu.models import kimi_linear_reference as reference
from skypilot_tpu.ops import dispatch
from skypilot_tpu.ops import kda
from skypilot_tpu.ops import kda_kernel


def _inputs(seed, seq, beta, decay, b=2, h=3, dk=16, dv=8):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    q = jax.random.normal(ks[0], (b, seq, h, dk))
    k = jax.random.normal(ks[1], (b, seq, h, dk))
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) * dk ** -0.5
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(ks[2], (b, seq, h, dv))
    g = -decay * jax.random.uniform(ks[3], (b, seq, h, dk), minval=0.5,
                                    maxval=1.0)
    if beta == 'drawn':
        beta_v = jax.nn.sigmoid(jax.random.normal(ks[4], (b, seq, h)))
    else:
        beta_v = jnp.full((b, seq, h), float(beta))
    return q, k, v, g, beta_v


def _sequential(q, k, v, g, beta):
    return jax.vmap(reference.kda_sequential)(q, k, v, g, beta)


CASES = [
    # name, seq (chunks of 64, sub-chunks of 16), beta, decay a step
    ('three_chunks_and_a_part', 200, 'drawn', 1.0),
    ('one_short_chunk', 37, 'drawn', 0.3),
    ('no_write', 80, 0.0, 0.5),
    ('full_write', 130, 1.0, 0.5),
    ('near_zero_decay', 96, 'drawn', 1e-6),
    ('decay_30_a_step', 150, 'drawn', 30.0),
]


RUNGS = [pytest.param('jnp', c, id=c[0]) for c in CASES] + [
    pytest.param('pallas', c, id=f'pallas-{c[0]}') for c in CASES]


@pytest.mark.parametrize('rung,case', RUNGS)
def test_the_chunked_form_is_the_recurrence_forward_and_backward(rung, case):
    _, seq, beta, decay = case
    widths = dict(h=4, dk=128, dv=128) if rung == 'pallas' else {}
    args = _inputs(7, seq, beta, decay, **widths)
    fn = kda_kernel.chunk_kda if rung == 'pallas' else kda.chunk_kda
    weight = jnp.cos(jnp.arange(args[2].shape[-1], dtype=jnp.float32))

    def loss(fn):
        return lambda *a: jnp.sum(fn(*a) * weight)
    out = jax.jit(fn)(*args)
    with jax.default_matmul_precision('highest'):
        want = jax.jit(_sequential)(*args)
        grads_want = jax.jit(jax.grad(loss(_sequential),
                                      argnums=range(5)))(*args)
    grads = jax.jit(jax.grad(loss(fn), argnums=range(5)))(*args)
    wants = [(want, grads_want)]
    if rung == 'pallas':
        wants.append((jax.jit(kda._chunk_form)(*args), jax.jit(jax.grad(
            loss(kda._chunk_form), argnums=range(5)))(*args)))
    assert out.shape == want.shape and out.dtype == jnp.float32
    assert bool(jnp.isfinite(out).all())
    for want, grads_want in wants:
        np.testing.assert_allclose(out, want, atol=2e-5 * float(
            jnp.abs(want).max()) + 1e-7)
        for name, a, b in zip('q k v g beta'.split(), grads, grads_want):
            assert bool(jnp.isfinite(a).all()), name
            scale = float(jnp.abs(b).max())
            np.testing.assert_allclose(a, b, atol=1e-4 * scale + 1e-7,
                                       err_msg=name)
    if beta == 0.0:
        # nothing written: the state stays zero and so does the output
        assert float(jnp.abs(out).max()) == 0.0


def test_the_rung_is_pallas_on_the_tpu_at_whole_lanes_only(monkeypatch):
    assert kda._resolve_rung(128, 128) == 'jnp'        # here: the CPU
    monkeypatch.setattr(dispatch, 'interpret_mode', lambda: False)
    assert kda._resolve_rung(128, 128) == 'pallas'
    assert kda._resolve_rung(256, 128) == 'pallas'
    for dk, dv in ((16, 16), (64, 64), (128, 64), (192, 128)):
        assert kda._resolve_rung(dk, dv) == 'jnp'


def test_the_plan_carries_the_rung_and_the_grid(monkeypatch):
    assert kda.plan(1, 16384, 8, 128, 128) == {'rung': 'jnp', 'steps': 0}
    monkeypatch.setattr(dispatch, 'interpret_mode', lambda: False)
    # the cell's call: 256 chunks of 64, two heads stacked a step, four
    # chunks a step
    assert kda.plan(1, 16384, 8, 128, 128) == {'rung': 'pallas',
                                               'steps': 256}
    # one chunk of 48: two heads of 48 rows a step; three heads: one
    assert kda.plan(2, 37, 4, 128, 128) == {'rung': 'pallas', 'steps': 4}
    assert kda.plan(1, 200, 3, 128, 128)['steps'] == 3
    assert kda.plan(1, 16384, 8, 16, 16) == {'rung': 'jnp', 'steps': 0}


@pytest.mark.parametrize('case', ['decay_30_a_step', 'full_write'])
@pytest.mark.parametrize('fast', [False, True], ids=['float32', 'bf16'])
def test_the_inverse_is_the_triangular_solve(case, fast):
    """`unit_lower_inverse` on A_kk of the case's inputs (chunks of 64),
    two heads' on the diagonal of one matrix as a grid step stacks
    them, against XLA's solve: in float32 (interpreted), and as the
    TPU computes it, one bf16 pass refined once."""
    _, seq, beta, decay = next(c for c in CASES if c[0] == case)
    q, k, v, g, b = _inputs(7, 128, beta, decay, b=1, h=4, dk=128, dv=128)

    def chunks(x):
        return x.reshape(2, 64, 4, -1).transpose(2, 0, 1, 3)  # h, n, c, d
    gam = jnp.cumsum(chunks(g), axis=-2)
    a_kk = chunks(b[..., None]) * kda._pairs(chunks(k), chunks(k), gam,
                                             strict=True)
    a_kk = a_kk.reshape(8, 64, 64)
    eye = jnp.eye(64)
    with jax.default_matmul_precision('highest'):
        want = jax.lax.linalg.triangular_solve(
            eye + a_kk, jnp.broadcast_to(eye, a_kk.shape), left_side=True,
            lower=True, unit_diagonal=True)
    pairs = jnp.zeros((4, 128, 128)).at[:, :64, :64].set(
        a_kk[0::2]).at[:, 64:, 64:].set(a_kk[1::2])
    got = jax.jit(lambda a: kda_kernel.unit_lower_inverse(
        a, 64, fast=fast))(pairs)
    assert float(jnp.abs(got[:, :64, 64:]).max()) == 0.0
    assert float(jnp.abs(got[:, 64:, :64]).max()) == 0.0
    got = jnp.stack([got[:, :64, :64], got[:, 64:, 64:]], 1).reshape(
        8, 64, 64)
    err = float(jnp.abs(got - want).max() / jnp.abs(want).max())
    assert err < (5e-6 if fast else 5e-7), err


def test_the_chunk_rule_and_the_plan():
    assert [dispatch.kda_chunk(s) for s in (16384, 200, 64, 37, 8)] == \
        [64, 64, 64, 48, 16]
    # a layer's chunk-start states at the cell's shape: 256 chunks of
    # 32 heads x 128 x 128 float32
    assert kda.state_bytes(1, 16384, 32, 128, 128) == 536870912
