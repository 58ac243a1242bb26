"""Flash kernel (interpret mode), ring attention, and MoE tests on the
virtual 8-device CPU mesh."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from skypilot_tpu.models import moe as moe_lib
from skypilot_tpu.ops.attention import mha_reference
from skypilot_tpu.parallel import mesh as mesh_lib
from skypilot_tpu.parallel import ring_attention
from skypilot_tpu.train import trainer

# Compile-heavy (JAX jit on the 1-core CPU host) or subprocess-driven:
pytestmark = pytest.mark.heavy


def _qkv(b=2, s=64, hq=4, hkv=2, d=16, seed=0):
    rng = np.random.default_rng(seed)
    q = jnp.array(rng.normal(size=(b, s, hq, d)), jnp.float32)
    k = jnp.array(rng.normal(size=(b, s, hkv, d)), jnp.float32)
    v = jnp.array(rng.normal(size=(b, s, hkv, d)), jnp.float32)
    return q, k, v


class TestFlashKernel:
    """Interpret-mode equivalence with the XLA reference (the same kernel
    runs compiled on TPU; see tests_tpu/)."""

    @pytest.mark.parametrize('causal', [True, False])
    def test_matches_reference(self, causal):
        from skypilot_tpu.ops.flash_attention import flash_attention
        q, k, v = _qkv(s=256, d=64)
        out_f = flash_attention(q, k, v, causal, None, 128, 128)
        out_r = mha_reference(q, k, v, causal=causal)
        np.testing.assert_allclose(np.asarray(out_f), np.asarray(out_r),
                                   rtol=1e-5, atol=1e-5)

    def test_gqa_index_map(self):
        from skypilot_tpu.ops.flash_attention import flash_attention
        q, k, v = _qkv(s=128, hq=8, hkv=2, d=64)
        out_f = flash_attention(q, k, v, True, None, 128, 128)
        out_r = mha_reference(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(out_f), np.asarray(out_r),
                                   rtol=1e-5, atol=1e-5)

    def test_grad_flows(self):
        from skypilot_tpu.ops.flash_attention import flash_attention
        q, k, v = _qkv(s=128, d=64)
        g_f = jax.grad(
            lambda q: flash_attention(q, k, v, True, None, 128, 128).sum()
        )(q)
        g_r = jax.grad(
            lambda q: mha_reference(q, k, v, causal=True).sum())(q)
        np.testing.assert_allclose(np.asarray(g_f), np.asarray(g_r),
                                   rtol=1e-4, atol=1e-4)

    @pytest.mark.parametrize('causal', [True, False])
    def test_backward_kernel_dqkv(self, causal):
        """Pallas dq/dkv kernels vs XLA reference grads — a non-trivial
        upstream cotangent exercises delta = rowsum(dO*O)."""
        from skypilot_tpu.ops.flash_attention import flash_attention
        q, k, v = _qkv(s=256, d=64)
        w = jnp.array(np.random.default_rng(3).normal(
            size=(2, 256, 4, 64)), jnp.float32)

        def loss_f(q, k, v):
            return (flash_attention(q, k, v, causal, None,
                                    128, 128) * w).sum()

        def loss_r(q, k, v):
            return (mha_reference(q, k, v, causal=causal) * w).sum()

        gf = jax.grad(loss_f, argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(loss_r, argnums=(0, 1, 2))(q, k, v)
        for a, b, name in zip(gf, gr, 'q k v'.split()):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-4, atol=2e-4,
                                       err_msg=f'd{name}')

    def test_backward_kernel_gqa(self):
        from skypilot_tpu.ops.flash_attention import flash_attention
        q, k, v = _qkv(s=128, hq=8, hkv=2, d=64)

        def loss_f(q, k, v):
            return flash_attention(q, k, v, True, None, 128, 128).sum()

        def loss_r(q, k, v):
            return mha_reference(q, k, v, causal=True).sum()

        gf = jax.grad(loss_f, argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(loss_r, argnums=(0, 1, 2))(q, k, v)
        for a, b, name in zip(gf, gr, 'q k v'.split()):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-4, atol=2e-4,
                                       err_msg=f'd{name}')

    def test_segment_ids_in_kernel(self):
        """Packed sequences masked in-kernel, forward and backward."""
        from skypilot_tpu.ops.flash_attention import flash_attention
        q, k, v = _qkv(s=256, d=64)
        seg = np.zeros((2, 256), np.int32)
        seg[:, 100:180] = 1
        seg[:, 180:] = 2
        seg = jnp.asarray(seg)

        out_f = flash_attention(q, k, v, True, seg, 128, 128)
        out_r = mha_reference(q, k, v, causal=True, segment_ids=seg)
        np.testing.assert_allclose(np.asarray(out_f), np.asarray(out_r),
                                   rtol=1e-5, atol=1e-5)

        gf = jax.grad(lambda q, k, v: flash_attention(
            q, k, v, True, seg, 128, 128).sum(), argnums=(0, 1, 2))(
                q, k, v)
        gr = jax.grad(lambda q, k, v: mha_reference(
            q, k, v, causal=True, segment_ids=seg).sum(),
            argnums=(0, 1, 2))(q, k, v)
        for a, b, name in zip(gf, gr, 'q k v'.split()):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-4, atol=2e-4,
                                       err_msg=f'd{name}')


class TestRingAttention:
    def test_matches_reference(self):
        mesh = mesh_lib.build_mesh(mesh_lib.MeshSpec(cp=4, tp=2))
        q, k, v = _qkv()
        out = ring_attention.ring_attention_sharded(q, k, v, mesh)
        ref = mha_reference(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)

    def test_grads_match(self):
        mesh = mesh_lib.build_mesh(mesh_lib.MeshSpec(cp=8))
        q, k, v = _qkv(s=32)
        g1 = jax.grad(lambda q: ring_attention.ring_attention_sharded(
            q, k, v, mesh).sum())(q)
        g2 = jax.grad(lambda q: mha_reference(q, k, v, causal=True).sum())(q)
        np.testing.assert_allclose(np.asarray(g1), np.asarray(g2),
                                   rtol=1e-4, atol=1e-4)

    def test_flash_ring_matches_einsum_and_reference(self):
        """Flash-eligible shapes (chunk 128, d=64): the flash-forward
        ring must match both the einsum ring and full attention, and its
        grads (routed through the einsum backward) must match too."""
        mesh = mesh_lib.build_mesh(mesh_lib.MeshSpec(cp=4))
        q, k, v = _qkv(b=1, s=512, hq=2, hkv=2, d=64)
        out_flash = ring_attention.ring_attention_sharded(q, k, v, mesh)
        ref = mha_reference(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(out_flash),
                                   np.asarray(ref), rtol=2e-5,
                                   atol=2e-5)
        import os
        os.environ['SKYT_RING_IMPL'] = 'xla'
        try:
            out_einsum = ring_attention.ring_attention_sharded(
                q, k, v, mesh)
        finally:
            del os.environ['SKYT_RING_IMPL']
        np.testing.assert_allclose(np.asarray(out_flash),
                                   np.asarray(out_einsum), rtol=2e-5,
                                   atol=2e-5)
        g1 = jax.grad(lambda q: ring_attention.ring_attention_sharded(
            q, k, v, mesh).astype(jnp.float32).sum())(q)
        g2 = jax.grad(lambda q: mha_reference(
            q, k, v, causal=True).astype(jnp.float32).sum())(q)
        np.testing.assert_allclose(np.asarray(g1), np.asarray(g2),
                                   rtol=1e-4, atol=1e-4)

    def test_model_with_ring_attention(self):
        """cfg.attn_impl='ring' trains end-to-end on a cp mesh."""
        import dataclasses
        from skypilot_tpu.models import llama
        cfg = dataclasses.replace(llama.CONFIGS['debug'], attn_impl='ring')
        model = llama.LlamaModel(cfg)
        mesh = mesh_lib.build_mesh(mesh_lib.MeshSpec(cp=2, fsdp=2, tp=2))
        tx = trainer.make_optimizer(
            trainer.TrainerConfig(warmup_steps=1, total_steps=5))
        sample = jnp.zeros((4, 64), jnp.int32)
        state, _ = trainer.create_sharded_state(model, tx, mesh, sample,
                                                jax.random.PRNGKey(0))
        step = trainer.make_train_step(model, tx, mesh, donate=False)
        rng = np.random.default_rng(0)
        batch = {'tokens': jnp.array(rng.integers(0, 256, (4, 64)),
                                     jnp.int32),
                 'targets': jnp.array(rng.integers(0, 256, (4, 64)),
                                      jnp.int32)}
        state, m = step(state, batch)
        assert np.isfinite(float(m['loss']))


class TestMoE:
    def test_trains_on_ep_mesh(self):
        cfg, mcfg = moe_lib.MIXTRAL_CONFIGS['debug-moe']
        model = moe_lib.MixtralModel(cfg, mcfg)
        mesh = mesh_lib.build_mesh(mesh_lib.MeshSpec(dp=2, ep=2, tp=2))
        tx = trainer.make_optimizer(
            trainer.TrainerConfig(warmup_steps=1, total_steps=10,
                                  learning_rate=1e-2))
        sample = jnp.zeros((8, 32), jnp.int32)
        state, _ = trainer.create_sharded_state(model, tx, mesh, sample,
                                                jax.random.PRNGKey(0))
        step = trainer.make_train_step(model, tx, mesh, donate=False)
        rng = np.random.default_rng(0)
        batch = {'tokens': jnp.array(rng.integers(0, 256, (8, 32)),
                                     jnp.int32),
                 'targets': jnp.array(rng.integers(0, 256, (8, 32)),
                                      jnp.int32)}
        losses = []
        for _ in range(4):
            state, m = step(state, batch)
            losses.append(float(m['loss']))
        assert losses[-1] < losses[0]
        specs = {str(x.sharding.spec) for x in jax.tree.leaves(state.params)}
        assert any('ep' in s for s in specs)

    def test_capacity_drops_overflow(self):
        """With capacity_factor tiny, most tokens are dropped but the layer
        still runs and the output stays finite."""
        import dataclasses
        cfg, mcfg = moe_lib.MIXTRAL_CONFIGS['debug-moe']
        mcfg = dataclasses.replace(mcfg, capacity_factor=0.1)
        layer = moe_lib.MoeMLP(cfg, mcfg)
        x = jnp.ones((2, 32, cfg.dim), jnp.float32)
        vars_ = layer.init(jax.random.PRNGKey(0), x)
        out, aux = layer.apply(vars_, x)
        assert np.isfinite(np.asarray(out)).all()
        assert out.shape == x.shape

    def test_topk_no_capacity_slot_collision(self):
        """Regression: with k=2, a token routed to expert X as 1st choice
        and another routed to X as 2nd choice must land in DIFFERENT
        capacity slots (GShard slot-major positions). Asserts on the
        layer's OWN dispatch tensor (sown intermediate), so reverting the
        moe.py fix fails this test."""
        cfg, mcfg = moe_lib.MIXTRAL_CONFIGS['debug-moe']
        layer = moe_lib.MoeMLP(cfg, mcfg)
        rng = np.random.default_rng(1)
        x = jnp.array(rng.normal(size=(2, 16, cfg.dim)), jnp.float32)
        vars_ = layer.init(jax.random.PRNGKey(0), x)
        (_, _), inter = layer.apply(vars_, x, mutable=['intermediates'])
        dispatch, = inter['intermediates']['dispatch']  # [B,S,E,C]
        # At most one token occupies any (expert, capacity slot).
        occupancy = np.asarray(dispatch.sum(axis=1))    # [B,E,C]
        assert occupancy.max() <= 1.0 + 1e-6, occupancy.max()
        # With the default capacity factor at least one expert receives
        # second-choice traffic in this random batch (the collision case).
        assert dispatch.sum() > 0


class TestWindowedFlash:
    """Sliding-window flash attention (Mistral/Phi-3 prefill): parity
    with the masked XLA reference, forward and backward, including
    windows smaller than a block (the fully-masked-first-block case
    the online-softmax guard exists for)."""

    def _qkv(self, s=256, d=64):
        rng = np.random.default_rng(3)
        q = jnp.asarray(rng.normal(size=(2, s, 4, d)), jnp.float32)
        k = jnp.asarray(rng.normal(size=(2, s, 2, d)), jnp.float32)
        v = jnp.asarray(rng.normal(size=(2, s, 2, d)), jnp.float32)
        return q, k, v

    @pytest.mark.parametrize('window', [7, 64, 100, 256])
    def test_fwd_matches_reference(self, window):
        from skypilot_tpu.ops import attention as attention_ops
        from skypilot_tpu.ops.flash_attention import flash_attention
        q, k, v = self._qkv()
        ref = attention_ops.mha_reference(q, k, v, causal=True,
                                          window=window)
        out = flash_attention(q, k, v, True, None, 64, 64,
                              window=window)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

    def test_grads_match_reference(self):
        from skypilot_tpu.ops import attention as attention_ops
        from skypilot_tpu.ops.flash_attention import flash_attention
        q, k, v = self._qkv(s=128)
        w = 48

        gf = jax.grad(lambda q_, k_, v_: (flash_attention(
            q_, k_, v_, True, None, 64, 64, window=w) ** 2).sum(),
            argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(lambda q_, k_, v_: (attention_ops.mha_reference(
            q_, k_, v_, causal=True, window=w) ** 2).sum(),
            argnums=(0, 1, 2))(q, k, v)
        for a, b, name in zip(gf, gr, 'qkv'):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-3, atol=1e-4,
                                       err_msg=f'd{name}')

    def test_dispatch_opt_in(self):
        """attention(): explicit impl='flash' honors a static window
        (it IS the opt-in) and ACTUALLY runs the kernel (interpret
        mode on CPU); a traced window gate is rejected with a message
        naming it."""
        from skypilot_tpu.ops import attention as attention_ops
        q, k, v = self._qkv(s=128)
        ref = attention_ops.mha_reference(q, k, v, causal=True,
                                          window=32)
        out = attention_ops.attention(q, k, v, causal=True, window=32,
                                      impl='flash')
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)
        with pytest.raises(ValueError, match='window_active'):
            attention_ops.attention(
                q, k, v, causal=True, window=32,
                window_active=jnp.asarray(True), impl='flash')
