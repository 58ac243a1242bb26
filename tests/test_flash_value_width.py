"""Flash attention with a value width of its own (latent attention: q
and k of 192, v of 128), in interpret mode: the forward and the three
gradients against the XLA rung and against a plain softmax written here,
and the tile rule and VMEM count reading both widths."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from skypilot_tpu.ops import attention as attention_ops
from skypilot_tpu.ops import dispatch
from skypilot_tpu.ops import flash_attention


def _plain(q, k, v, causal, window):
    """softmax(q k^T / sqrt(dqk)) v per head, the mask by positions."""
    group = q.shape[2] // k.shape[2]
    k, v = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
    s = jnp.einsum('bqhd,bkhd->bhqk', q, k) / jnp.sqrt(q.shape[-1])
    pos = jnp.arange(q.shape[1])
    allowed = jnp.ones((q.shape[1],) * 2, bool)
    if causal:
        allowed &= pos[:, None] >= pos[None, :]
    if window:
        allowed &= pos[:, None] - pos[None, :] < window
    p = jax.nn.softmax(jnp.where(allowed, s, -jnp.inf), axis=-1)
    return jnp.einsum('bhqk,bkhd->bqhd', p, v)


CASES = [
    # name, (batch, S, q heads, kv heads, q/k width, v width), mask
    ('latent_causal', (1, 256, 4, 4, 96, 64), dict(causal=True)),
    ('grouped_window', (2, 128, 4, 2, 64, 32), dict(causal=True, window=48)),
    ('wider_values', (1, 128, 2, 1, 32, 64), dict(causal=False)),
]


@pytest.mark.parametrize('case', CASES, ids=[c[0] for c in CASES])
def test_forward_dq_dk_dv_match_the_xla_rung_and_a_plain_softmax(case):
    _, (b, s, hq, hkv, d, dv), mask = case
    ks = jax.random.split(jax.random.PRNGKey(4), 4)
    q = jax.random.normal(ks[0], (b, s, hq, d))
    k = jax.random.normal(ks[1], (b, s, hkv, d))
    v = jax.random.normal(ks[2], (b, s, hkv, dv))
    w = jax.random.normal(ks[3], (b, s, hq, dv))

    def loss(fn):
        return lambda q, k, v: jnp.sum(fn(q, k, v) * w)

    def flash(q, k, v):
        return flash_attention.flash_attention(q, k, v, block_q=64,
                                               block_k=64, **mask)

    def xla(q, k, v):
        return attention_ops.mha_reference(q, k, v, **mask)

    def plain(q, k, v):
        return _plain(q, k, v, mask['causal'], mask.get('window', 0))
    outs = [jax.jit(fn)(q, k, v) for fn in (flash, xla, plain)]
    grads = [jax.jit(jax.grad(loss(fn), (0, 1, 2)))(q, k, v)
             for fn in (flash, xla, plain)]
    assert outs[0].shape == (b, s, hq, dv)
    for other in outs[1:]:
        np.testing.assert_allclose(outs[0], other, atol=2e-5)
    for other in grads[1:]:
        for name, a, c in zip(('dq', 'dk', 'dv'), grads[0], other):
            assert a.shape == c.shape
            np.testing.assert_allclose(a, c, atol=5e-5, err_msg=name)


def test_the_tile_rule_and_vmem_count_read_both_widths():
    # the same width twice is the rule as it was
    same = dispatch.flash_blocks(16384, 16384, 128, jnp.bfloat16, False)
    assert same == dispatch.flash_blocks(16384, 16384, 128, jnp.bfloat16,
                                         False, dv=128)
    for kernel in dispatch.FLASH_KERNELS:
        assert dispatch.flash_vmem_bytes(kernel, 512, 512, 128, 2) == \
            dispatch.flash_vmem_bytes(kernel, 512, 512, 128, 2, dv=128)
        # values narrower than q and k take less, wider more
        assert dispatch.flash_vmem_bytes(kernel, 512, 512, 192, 2, dv=128) \
            < dispatch.flash_vmem_bytes(kernel, 512, 512, 192, 2) < \
            dispatch.flash_vmem_bytes(kernel, 512, 512, 192, 2, dv=256)
    # latent attention's widths keep the tuned extents within the budget
    plan = dispatch.flash_blocks(16384, 16384, 192, jnp.bfloat16, False,
                                 dv=128)
    assert plan == {'fwd': (512, 1024), 'dq': (1024, 1024),
                    'dkv': (512, 512)}
    assert dispatch.flash_vmem_ok(plan, 192, 2, dv=128)
