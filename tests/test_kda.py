"""Kimi Delta Attention's chunked form (ops/kda.py) against the
recurrence one position at a time (the reference's `kda_sequential`),
forward and every input's gradient, across chunk and sub-chunk
boundaries and at the ends of its ranges: no write (beta 0), a full
write (beta 1), almost no decay, and a decay of 30 a step, which
overflows float32 within a few positions wherever a factor is made as
exp(-G)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from skypilot_tpu.models import kimi_linear_reference as reference
from skypilot_tpu.ops import dispatch
from skypilot_tpu.ops import kda


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


@pytest.mark.parametrize('case', CASES, ids=[c[0] for c in CASES])
def test_the_chunked_form_is_the_recurrence_forward_and_backward(case):
    _, seq, beta, decay = case
    args = _inputs(7, seq, beta, decay)
    weight = jnp.cos(jnp.arange(args[2].shape[-1], dtype=jnp.float32))

    def loss(fn):
        return lambda *a: jnp.sum(fn(*a) * weight)
    out = jax.jit(kda.chunk_kda)(*args)
    with jax.default_matmul_precision('highest'):
        want = jax.jit(_sequential)(*args)
        grads_want = jax.jit(jax.grad(loss(_sequential),
                                      argnums=range(5)))(*args)
    grads = jax.jit(jax.grad(loss(kda.chunk_kda), argnums=range(5)))(*args)
    assert out.shape == want.shape and out.dtype == jnp.float32
    assert bool(jnp.isfinite(out).all())
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


def test_the_chunk_rule_and_the_plan():
    assert [dispatch.kda_chunk(s) for s in (16384, 200, 64, 37, 8)] == \
        [64, 64, 64, 48, 16]
    # a layer's chunk-start states at the cell's shape: 256 chunks of
    # 32 heads x 128 x 128 float32
    assert kda.state_bytes(1, 16384, 32, 128, 128) == 536870912
