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


def _plain(q, k, v, causal, window, scale=None):
    """softmax(q k^T * scale) v per head, scale 1/sqrt(dqk) where none
    is given, the mask by positions."""
    group = q.shape[2] // k.shape[2]
    k, v = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
    s = jnp.einsum('bqhd,bkhd->bhqk', q, k) * (
        q.shape[-1] ** -0.5 if scale is None else scale)
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


def test_a_callers_scale_at_the_latent_widths_forward_and_backward():
    """DeepSeek-V2's latent attention: q and k of 192, v of 128, the
    scale 1/sqrt(192) times YaRN's mscale^2 = 0.1147214; the kernels
    against the XLA rung at that scale and a plain softmax, and the
    dispatcher taking the flash rung for it."""
    b, s, h, d, dv, scale = 1, 128, 2, 192, 128, 0.1147214
    ks = jax.random.split(jax.random.PRNGKey(7), 4)
    q = jax.random.normal(ks[0], (b, s, h, d))
    k = jax.random.normal(ks[1], (b, s, h, d))
    v = jax.random.normal(ks[2], (b, s, h, dv))
    w = jax.random.normal(ks[3], (b, s, h, dv))

    def flash(q, k, v):
        return flash_attention.flash_attention(q, k, v, block_q=64,
                                               block_k=64, scale=scale)

    def xla(q, k, v):
        return attention_ops.mha_reference(q, k, v, softmax_scale=scale)

    def plain(q, k, v):
        return _plain(q, k, v, True, 0, scale)

    def dispatched(q, k, v):
        return attention_ops.attention(q, k, v, impl='flash',
                                       softmax_scale=scale)
    fns = (flash, xla, plain, dispatched)
    dispatch.reset_for_tests()
    outs = [jax.jit(fn)(q, k, v) for fn in fns]
    grads = [jax.jit(jax.grad(lambda q, k, v, fn=fn: jnp.sum(
        fn(q, k, v) * w), (0, 1, 2)))(q, k, v) for fn in fns]
    for other in outs[1:]:
        np.testing.assert_allclose(outs[0], other, atol=2e-5)
    for other in grads[1:]:
        for name, a, c in zip(('dq', 'dk', 'dv'), grads[0], other):
            np.testing.assert_allclose(a, c, atol=5e-5, err_msg=name)
    # the scale matters: the default one is another function
    assert float(jnp.abs(jax.jit(flash_attention.flash_attention)(
        q, k, v) - outs[0]).max()) > 1e-2
    assert dispatch.snapshot() == {'flash_attention': 'pallas'}


@pytest.mark.parametrize('given', [None, 0.1147214],
                         ids=['default', 'the_callers'])
def test_every_kernel_takes_the_scale_as_a_static_argument(given,
                                                           monkeypatch):
    """Without a scale the three kernels are given 1/sqrt(D), the float
    they were always given, so a call lowers to the kernels it lowered
    to; with one, that float."""
    seen = []
    real = flash_attention._call

    def spy(body, *args):
        seen.append((body.func.__name__, body.keywords['scale']))
        return real(body, *args)
    monkeypatch.setattr(flash_attention, '_call', spy)
    q = jax.random.normal(jax.random.PRNGKey(0), (1, 64, 2, 96))
    jax.jit(jax.grad(lambda q: flash_attention.flash_attention(
        q, q, q, scale=given).sum()))(q)
    want = 96 ** -0.5 if given is None else given
    assert {name for name, _ in seen} == {'_fwd_kernel', '_dq_kernel',
                                          '_dkv_kernel'}
    assert all(type(scale) is float and scale == want for _, scale in seen)


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
