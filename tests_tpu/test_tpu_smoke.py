"""On-TPU lowering gate: the kernels and hot paths must COMPILE AND RUN
on the real chip, not just in interpret mode.

Covers the regression class CPU tests cannot see: a Pallas BlockSpec
that passes interpret mode but is rejected by Mosaic, and a layout or
partitioning decision only XLA:TPU makes.

Skipped where JAX selects another backend (conftest.py). Shapes are the
real ones: seq 2048 bf16 GQA for the kernel, a flash-routed train step,
engine prefill+decode, and the compiled decode chunk at a published
width.
"""
import dataclasses
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

pytestmark = pytest.mark.tpu


def _rand(key, shape):
    return jax.random.normal(jax.random.PRNGKey(key), shape, jnp.bfloat16)


class TestFlashKernelLowers:
    """Compile + run fwd/bwd at seq 2048 bf16 GQA and check vs reference."""

    def test_fwd_bwd_seq2048_gqa(self):
        from skypilot_tpu.ops.attention import mha_reference
        from skypilot_tpu.ops.flash_attention import flash_attention

        b, s, hq, hkv, d = 2, 2048, 8, 4, 128
        q = _rand(0, (b, s, hq, d))
        k = _rand(1, (b, s, hkv, d))
        v = _rand(2, (b, s, hkv, d))

        def loss(fn):
            return lambda q, k, v: fn(q, k, v, causal=True).astype(
                jnp.float32).mean()

        out = jax.jit(flash_attention, static_argnames=('causal',))(
            q, k, v, causal=True)
        ref = jax.jit(mha_reference, static_argnames=('causal',))(
            q, k, v, causal=True)
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(ref, np.float32),
            atol=3e-2, rtol=3e-2)

        grads = jax.jit(jax.grad(loss(flash_attention), argnums=(0, 1, 2)))(
            q, k, v)
        grefs = jax.jit(jax.grad(loss(mha_reference), argnums=(0, 1, 2)))(
            q, k, v)
        for g, gr in zip(grads, grefs):
            # bf16 inputs + different accumulation order: loose tolerance,
            # this is a lowering gate, not the numerics test (tests/ has
            # the tight interpret-mode comparison).
            np.testing.assert_allclose(
                np.asarray(g, np.float32), np.asarray(gr, np.float32),
                atol=5e-2, rtol=5e-2)

    def test_windowed_fwd_bwd(self):
        """Sliding-window flash (Mistral/Phi-3 prefill, a kind-table
        model's window layers): Mosaic lowering + parity vs the masked
        reference at seq 2048, window 512. A static window is chosen by
        the shape rule like any other flash call (ops/attention.py)."""
        from skypilot_tpu.ops.attention import mha_reference
        from skypilot_tpu.ops.flash_attention import flash_attention

        b, s, hq, hkv, d, w = 2, 2048, 8, 4, 128, 512
        q = _rand(0, (b, s, hq, d))
        k = _rand(1, (b, s, hkv, d))
        v = _rand(2, (b, s, hkv, d))

        out = jax.jit(flash_attention,
                      static_argnames=('causal', 'window'))(
            q, k, v, causal=True, window=w)
        ref = jax.jit(mha_reference,
                      static_argnames=('causal', 'window'))(
            q, k, v, causal=True, window=w)
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(ref, np.float32),
            atol=3e-2, rtol=3e-2)

        def loss(fn):
            return lambda q, k, v: fn(
                q, k, v, causal=True, window=w).astype(
                jnp.float32).mean()
        grads = jax.jit(jax.grad(loss(flash_attention),
                                 argnums=(0, 1, 2)))(q, k, v)
        grefs = jax.jit(jax.grad(loss(mha_reference),
                                 argnums=(0, 1, 2)))(q, k, v)
        for g, gr in zip(grads, grefs):
            np.testing.assert_allclose(
                np.asarray(g, np.float32), np.asarray(gr, np.float32),
                atol=5e-2, rtol=5e-2)

    def test_windowed_fwd_bwd_at_the_16k_cells_shape(self):
        """The window layers of `sft-swa-moe-16k`: 32 / 4 heads x 128,
        S 16,384, window 1,024, through `attention` as the model calls
        it (the shape rule's tiles, capped at the window). Forward, dq
        and dk/dv under a random cotangent against the masked reference
        computed a block of 1,024 queries at a time over the 2,047 keys
        its band can reach (the whole square is 34 GB in float32)."""
        from skypilot_tpu.ops import attention, dispatch

        b, s, hq, hkv, d, w, bq = 1, 16384, 32, 4, 128, 1024, 1024
        q = _rand(0, (b, s, hq, d))
        k = _rand(1, (b, s, hkv, d))
        v = _rand(2, (b, s, hkv, d))
        cot = _rand(3, (b, s, hq, d)).astype(jnp.float32)

        def flash(q, k, v):
            return attention.attention(q, k, v, causal=True, window=w)

        def blocked(q, k, v):
            @jax.checkpoint
            def block(q1, k1, v1, offset):
                return attention.mha_reference(
                    q1, k1, v1, causal=True, window=w, q_offset=offset)
            outs = []
            for s0 in range(0, s, bq):
                k0 = max(0, s0 - w + 1)
                outs.append(block(q[:, s0:s0 + bq], k[:, k0:s0 + bq],
                                  v[:, k0:s0 + bq], s0 - k0))
            return jnp.concatenate(outs, axis=1)

        def both(fn):
            # cot is an argument: closed over, its 268 MB would be a
            # constant of the compiled program
            def loss(q, k, v, cot):
                out = fn(q, k, v)
                return jnp.sum(out.astype(jnp.float32) * cot), out
            return jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2),
                                              has_aux=True))
        dispatch.reset_for_tests()
        (_, out), grads = both(flash)(q, k, v, cot)
        (_, ref), grefs = both(blocked)(q, k, v, cot)
        assert dispatch.snapshot() == {'flash_window_attention': 'pallas'}
        plan = dispatch.flash_plan_snapshot()
        assert {name: (p['block_q'], p['block_k'])
                for name, p in plan.items()} == {
            'window_fwd': (512, 1024), 'window_dq': (1024, 1024),
            'window_dkv': (512, 512)}
        # per head: the band's tiles of the 32 x 16 (or 16 x 16, 32 x 32)
        assert (plan['window_fwd']['visited'],
                plan['window_dq']['visited'],
                plan['window_dkv']['visited']) == (62, 31, 93)
        # the grid is those tiles: no step for a skipped one
        assert all(p['steps'] == p['visited'] for p in plan.values())
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(ref, np.float32),
            atol=3e-2, rtol=3e-2)
        for name, g, gr in zip(('dq', 'dk', 'dv'), grads, grefs):
            g, gr = (np.asarray(x, np.float32) for x in (g, gr))
            assert np.linalg.norm(g - gr) / np.linalg.norm(gr) < 3e-2, name

    def test_block_diffusion_fwd_bwd_at_the_8k_cells_shape(self):
        """The attention layers of `sft-bd-moe-8k`: 32 / 4 heads x 128,
        2L = 16,384 positions, blocks of 4, through `attention` as the
        model calls it (the shape rule's tiles, which divide L).
        Forward, dq and dk/dv under a random cotangent against the XLA
        rung, which computes the same mask from the same rule a block of
        queries at a time (the whole square is 34 GB in float32)."""
        from skypilot_tpu.ops import attention, dispatch

        b, s, hq, hkv, d, blk = 1, 16384, 32, 4, 128, 4
        q = _rand(0, (b, s, hq, d))
        k = _rand(1, (b, s, hkv, d))
        v = _rand(2, (b, s, hkv, d))
        cot = _rand(3, (b, s, hq, d)).astype(jnp.float32)

        def both(impl):
            def loss(q, k, v, cot):
                out = attention.attention(q, k, v, impl=impl,
                                          block_diffusion=blk)
                return jnp.sum(out.astype(jnp.float32) * cot), out
            return jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2),
                                              has_aux=True))
        dispatch.reset_for_tests()
        (_, out), grads = both('auto')(q, k, v, cot)
        assert dispatch.snapshot() == {
            'flash_block_diffusion_attention': 'pallas'}
        plan = dispatch.flash_plan_snapshot()
        assert {name: (p['block_q'], p['block_k'], p['visited'],
                       p['masked']) for name, p in plan.items()} == {
            'bd_fwd': (512, 1024, 160, 48), 'bd_dq': (1024, 1024, 80, 24),
            'bd_dkv': (512, 512, 288, 48)}
        assert all(p['steps'] == p['visited'] for p in plan.values())
        (_, ref), grefs = both('xla')(q, k, v, cot)
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(ref, np.float32),
            atol=3e-2, rtol=3e-2)
        for name, g, gr in zip(('dq', 'dk', 'dv'), grads, grefs):
            g, gr = (np.asarray(x, np.float32) for x in (g, gr))
            assert np.linalg.norm(g - gr) / np.linalg.norm(gr) < 3e-2, name

    def test_fwd_with_segment_ids(self):
        from skypilot_tpu.ops.flash_attention import flash_attention

        b, s, hq, hkv, d = 1, 1024, 4, 2, 128
        q = _rand(0, (b, s, hq, d))
        k = _rand(1, (b, s, hkv, d))
        v = _rand(2, (b, s, hkv, d))
        seg = jnp.concatenate(
            [jnp.zeros((b, s // 2), jnp.int32),
             jnp.ones((b, s // 2), jnp.int32)], axis=1)
        out = jax.jit(flash_attention,
                      static_argnames=('causal',))(q, k, v, causal=True,
                                                   segment_ids=seg)
        assert out.shape == (b, s, hq, d)
        assert bool(jnp.isfinite(out.astype(jnp.float32)).all())


    def test_bwd_with_segment_ids(self):
        """Packed rows through all three kernels at the training shape:
        every visited tile takes the masked body, and the dk/dv kernel
        reads its segment ids and row statistics as lane-axis rows."""
        from skypilot_tpu.ops.attention import mha_reference
        from skypilot_tpu.ops.flash_attention import flash_attention

        b, s, hq, hkv, d = 2, 2048, 4, 2, 128
        q = _rand(0, (b, s, hq, d))
        k = _rand(1, (b, s, hkv, d))
        v = _rand(2, (b, s, hkv, d))
        seg = jnp.stack([jnp.arange(s) // 768, jnp.arange(s) // 300]
                        ).astype(jnp.int32)

        w = _rand(3, (b, s, hq, d)).astype(jnp.float32)

        def loss(fn):
            return lambda q, k, v: (fn(
                q, k, v, causal=True, segment_ids=seg).astype(
                jnp.float32) * w).sum()
        grads = jax.jit(jax.grad(loss(flash_attention),
                                 argnums=(0, 1, 2)))(q, k, v)
        grefs = jax.jit(jax.grad(loss(mha_reference),
                                 argnums=(0, 1, 2)))(q, k, v)
        for name, g, gr in zip(('dq', 'dk', 'dv'), grads, grefs):
            # Cotangents of order 1, so the comparison bites: bf16
            # operands against the reference's, in norm.
            g, gr = np.asarray(g, np.float32), np.asarray(gr, np.float32)
            assert np.linalg.norm(g - gr) <= 2e-2 * np.linalg.norm(gr), name


class TestDispatchShapeGridLowers:
    """The never-crash contract ON-CHIP: every adversarial shape in
    the CPU grid (tests/test_ops_dispatch.py) must lower through the
    real Mosaic pipeline — this is the half the static mirror in
    ops/dispatch.py cannot prove from CPU. Includes the decode shape
    whose 256-row block once crashed the lowering."""

    @pytest.mark.parametrize('shape', [
        (4, 32, 32, 8, 8, 256),     # decode shape, API layout
        (4, 8, 8, 32, 32, 256),     # same, kernel-layout reading
        (2, 1, 1, 4, 2, 64),        # single-query decode
        (1, 300, 300, 2, 2, 64),    # non-8-divisible seq
        (3, 24, 24, 2, 1, 128),     # odd batch + GQA
    ], ids=lambda s: 'x'.join(map(str, s)))
    def test_grid_shape_lowers(self, shape):
        from skypilot_tpu.ops.attention import mha_reference
        from skypilot_tpu.ops.flash_attention import flash_attention

        b, sq, sk, hq, hkv, d = shape
        q = _rand(0, (b, sq, hq, d))
        k = _rand(1, (b, sk, hkv, d))
        v = _rand(2, (b, sk, hkv, d))
        causal = sq == sk
        out = jax.jit(flash_attention, static_argnames=('causal',))(
            q, k, v, causal=causal)
        ref = jax.jit(mha_reference, static_argnames=('causal',))(
            q, k, v, causal=causal)
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(ref, np.float32),
            atol=3e-2, rtol=3e-2)

    def test_segment_ids_batch_gt_one_lowers(self):
        """Packed sequences with batch > 1: the [B, 1, S] lane-axis
        segment layout must pass Mosaic (the old [B, S] layout was
        illegal for any B > 1 — a latent train crash)."""
        from skypilot_tpu.ops.flash_attention import flash_attention

        b, s, hq, hkv, d = 2, 512, 4, 2, 128
        q = _rand(0, (b, s, hq, d))
        k = _rand(1, (b, s, hkv, d))
        v = _rand(2, (b, s, hkv, d))
        seg = jnp.concatenate(
            [jnp.zeros((b, s // 2), jnp.int32),
             jnp.ones((b, s // 2), jnp.int32)], axis=1)
        out = jax.jit(flash_attention,
                      static_argnames=('causal',))(q, k, v, causal=True,
                                                   segment_ids=seg)
        assert out.shape == (b, s, hq, d)
        assert bool(jnp.isfinite(out.astype(jnp.float32)).all())


class TestTrainStepFlash:
    """One real train step with attn_impl='flash' at seq 512 (the r2 bug
    crashed any seq > 256)."""

    def test_one_train_step(self):
        import flax.linen as nn

        from skypilot_tpu.models import llama
        from skypilot_tpu.parallel import mesh as mesh_lib
        from skypilot_tpu.parallel import sharding as sharding_lib
        from skypilot_tpu.train import trainer

        cfg = dataclasses.replace(
            llama.CONFIGS['debug'],
            dim=512, n_heads=4, n_kv_heads=2, mlp_dim=1024,
            max_seq_len=512, dtype='bfloat16', param_dtype='bfloat16',
            attn_impl='flash')
        assert cfg.head_dim == 128  # flash-compatible head dim
        model = llama.LlamaModel(cfg)
        mesh = mesh_lib.build_mesh(mesh_lib.MeshSpec())
        tcfg = trainer.TrainerConfig(warmup_steps=2, total_steps=10)
        tx = trainer.make_optimizer(tcfg)
        batch, seq = 2, 512
        sample = jnp.zeros((batch, seq), jnp.int32)
        state, _ = trainer.create_sharded_state(
            model, tx, mesh, sample, jax.random.PRNGKey(0))
        step = trainer.make_train_step(model, tx, mesh, donate=False)
        toks = jax.random.randint(jax.random.PRNGKey(1),
                                  (batch, seq + 1), 0, cfg.vocab_size,
                                  jnp.int32)
        data = {'tokens': toks[:, :-1], 'targets': toks[:, 1:]}
        with mesh, nn.logical_axis_rules(list(sharding_lib.DEFAULT_RULES)):
            state, metrics = step(state, data)
            loss = float(metrics['loss'])
        assert np.isfinite(loss)


class TestExpertLayerLowers:
    """The dropless expert layer's grouped products (ladder
    `moe_experts`) and the kind-table decoder's train step must lower
    and run on the chip: the repo's Pallas kernels forward and
    backward (ops/grouped_kernel.py, the rung the rule gives here),
    the rows past the groups' total undefined and masked."""

    @pytest.mark.parametrize('chunk,buffer_rows,d,width,groups,live', [
        (34816, 139264, 2304, 896, 16, 32816),      # sft-swa-moe-16k
        (8704, 69632, 2048, 1536, 8, 8192),         # sft-moe-8k
    ])
    def test_pallas_forms_match_ragged_dot_at_a_cells_call(
            self, chunk, buffer_rows, d, width, groups, live):
        """Each form at the tiles the rule gives, against the
        compiler's kernel on the same operands: uneven groups, one
        empty, the rows past `live` in none."""
        from skypilot_tpu.ops import grouped_matmul

        assert grouped_matmul._resolve_rung(
            chunk, d, width, groups, jnp.bfloat16) == 'pallas'
        cut = np.sort(np.random.default_rng(0).choice(
            live, groups - 2, replace=False))
        sizes = jnp.asarray(np.diff(np.concatenate(
            [[0], cut[:3], cut[2:], [live]])), jnp.int32)
        assert sizes.shape == (groups,) and int(sizes.sum()) == live

        def on(products, form):
            # a fresh set of products a trace: `_Pallas` keeps the
            # visits it has made
            return jax.jit(lambda *a: getattr(products(sizes), form)(*a))

        def off(got, want):
            got, want = (np.asarray(a, np.float32) for a in (got, want))
            assert np.isfinite(got).all()
            return np.linalg.norm(got - want) / np.linalg.norm(want)

        for a, b in ((d, width), (width, d)):
            w = _rand(1, (groups, a, b)) * a ** -0.5
            x, y = _rand(2, (chunk, a)), _rand(3, (chunk, b))
            for form, arg in (('rows', x), ('rows_t', y)):
                got = on(grouped_matmul._Pallas, form)(arg, w)[:live]
                want = on(grouped_matmul._Ragged, form)(arg, w)[:live]
                assert off(got, want) < 1e-3, (form, a, b)
            x, y = _rand(4, (buffer_rows, a)), _rand(5, (buffer_rows, b))
            assert off(on(grouped_matmul._Pallas, 'over_rows')(x, y),
                       on(grouped_matmul._Ragged, 'over_rows')(x, y)) \
                < 1e-2, (a, b)

    def test_grouped_products_match_the_dense_masked_products(self):
        """A chunk as the layer's loop hands it over, 1,000 of its rows
        in no group: the forward, and the hand-written transpose
        (`expert_ffn_bwd`, `expert_weight_grads`) against the dense
        products' own gradients."""
        from skypilot_tpu.ops import dispatch, grouped_matmul

        rows, d, width, groups = 4096, 256, 384, 8
        x = _rand(0, (rows, d))
        w_gate = _rand(1, (groups, d, width)) * d ** -0.5
        w_up = _rand(2, (groups, d, width)) * d ** -0.5
        w_down = _rand(3, (groups, width, d)) * width ** -0.5
        g = _rand(4, (rows, d))
        # uneven groups, one empty
        sizes = jnp.asarray([900, 0, 1, 700, 500, 300, 95, 600], jnp.int32)
        live = (jnp.arange(rows) < sizes.sum())[:, None]

        def dense(x, w_gate, w_up, w_down):
            owner = jnp.searchsorted(jnp.cumsum(sizes), jnp.arange(rows),
                                     side='right')
            out = jnp.zeros((rows, d), jnp.float32)
            for e in range(groups):
                y = (jax.nn.silu(x @ w_gate[e]) * (x @ w_up[e])) @ w_down[e]
                out = jnp.where((owner == e)[:, None],
                                y.astype(jnp.float32), out)
            return out.astype(x.dtype)

        def close(got, want, what):
            got, want = (np.asarray(a, np.float32) for a in (got, want))
            assert np.isfinite(got).all(), what
            off = np.linalg.norm(got - want) / np.linalg.norm(want)
            assert off < 2e-2, (what, off)

        out = jax.jit(grouped_matmul.expert_ffn)(
            x, live, w_gate, w_up, w_down, sizes)
        assert dispatch.snapshot()['moe_experts'] == 'pallas'
        assert not bool(out[int(sizes.sum()):].any())
        want, pull = jax.vjp(jax.jit(dense), x, w_gate, w_up, w_down)
        close(out, want, 'forward')

        @jax.jit
        def transpose(x, w_gate, w_up, w_down, g):
            dx, again, (hidden, d_gate, d_up) = grouped_matmul.expert_ffn_bwd(
                x, live, w_gate, w_up, w_down, sizes, g)
            return (again, dx) + grouped_matmul.expert_weight_grads(
                x, hidden, g, d_gate, d_up, sizes)
        again, *grads = transpose(x, w_gate, w_up, w_down, g)
        close(again, want, 'the result computed again')
        assert not bool(grads[0][int(sizes.sum()):].any())
        for got, ref, what in zip(grads, pull(g),
                                  ('x', 'w_gate', 'w_up', 'w_down')):
            close(got, ref, 'gradient of ' + what)

    def test_the_layers_loop_matches_the_plain_reference(self):
        """RoutedExperts in bf16 on the chip, its loop taking several
        trips and the last chunk partly live, against the plain float32
        layer at the program's own selections: the output and the
        gradients of x, the router (through the float32 weighting and
        its hand-written transpose) and the three expert matrices."""
        import flax.linen as nn

        from skypilot_tpu.models import hybrid, moe
        from skypilot_tpu.models import lfm2_moe_reference as reference
        from skypilot_tpu.ops import dispatch

        base = dataclasses.replace(hybrid.CONFIGS['debug-lfm2'].base,
                                   dim=512, dtype='bfloat16')
        ex = moe.ExpertsConfig(16, 4, 256, scoring='sigmoid_bias',
                               held=(4, 8))
        layer = moe.RoutedExperts(base, ex)
        x = _rand(0, (2, 1024, 512))
        p = nn.meta.unbox(jax.jit(layer.init)(jax.random.PRNGKey(1), x)
                          ['params'])
        # every token takes experts 4 and 6, and some a third held one
        p['expert_bias'] = jnp.zeros(16).at[jnp.array([4, 6])].set(1.0)
        probe = _rand(2, x.shape).astype(jnp.float32)
        sizes = {'num_experts_per_tok': 4, 'norm_topk_prob': True,
                 'routed_scaling_factor': ex.routed_scaling,
                 'use_expert_bias': True, 'experts_held': [4, 8]}

        def program(p, x):
            (out, stats), sown = layer.apply({'params': p}, x,
                                             mutable=['intermediates'])
            return jnp.sum(out.astype(jnp.float32) * probe), (
                out, stats, sown['intermediates']['selected'][0])

        def plain(p, x, sel):
            out = jax.vmap(lambda row, s: reference._experts(
                row, p, sizes, s)[0])(x, sel)
            return jnp.sum(out * probe), out
        (_, (out, stats, sel)), grads = jax.jit(jax.value_and_grad(
            program, argnums=(0, 1), has_aux=True))(p, x)
        with jax.default_matmul_precision('highest'):
            (_, want), want_grads = jax.jit(jax.value_and_grad(
                plain, argnums=(0, 1), has_aux=True))(
                    p, x.astype(jnp.float32), sel)
        chunk = dispatch.moe_plan_snapshot()['chunk_rows']
        held, _, dropped, worked, worst = stats.tolist()
        assert dropped == 0 and worst == -(-2048 * 4 // chunk) * chunk
        assert 4096 <= held and 2 * chunk <= worked < held + chunk <= worst
        assert dispatch.snapshot()['moe_experts'] == 'pallas'
        off = {'out': (out, want), 'x': (grads[1], want_grads[1]), **{
            name: (grads[0][name], want_grads[0][name])
            for name in ('router', 'w_gate', 'w_up', 'w_down')}}
        off = {name: float(jnp.linalg.norm(got.astype(jnp.float32) - ref) /
                           jnp.linalg.norm(ref))
               for name, (got, ref) in off.items()}
        print('relative error against float32:', off)
        # bf16 products: half a per cent on the v5e (0.46-0.51%, PR 32);
        # a wrong term reads 50% or more
        assert all(v < 0.02 for v in off.values()), off

    def test_one_train_step_of_the_kind_table_decoder(self):
        import flax.linen as nn

        from skypilot_tpu.models import hybrid, moe
        from skypilot_tpu.ops import dispatch
        from skypilot_tpu.parallel import mesh as mesh_lib
        from skypilot_tpu.parallel import sharding as sharding_lib
        from skypilot_tpu.train import trainer

        tiny = hybrid.CONFIGS['debug-lfm2']
        cfg = dataclasses.replace(
            tiny,
            base=dataclasses.replace(
                tiny.base, dim=512, n_heads=8, n_kv_heads=2, mlp_dim=1024,
                max_seq_len=1024, dtype='bfloat16', remat=True),
            experts=moe.ExpertsConfig(16, 4, 256, scoring='sigmoid_bias',
                                      held=(4, 8)))
        assert cfg.base.head_dim == 64      # the published head size
        model = hybrid.HybridModel(cfg)
        mesh = mesh_lib.build_mesh(mesh_lib.MeshSpec())
        tx = trainer.make_optimizer(
            trainer.TrainerConfig(warmup_steps=2, total_steps=10))
        batch, seq = 2, 1024
        state, _ = trainer.create_sharded_state(
            model, tx, mesh, jnp.zeros((batch, seq), jnp.int32),
            jax.random.PRNGKey(0))
        step = trainer.make_train_step(model, tx, mesh, donate=False)
        toks = jax.random.randint(jax.random.PRNGKey(1), (batch, seq + 1),
                                  0, cfg.vocab_size, jnp.int32)
        with mesh, nn.logical_axis_rules(list(sharding_lib.DEFAULT_RULES)):
            state, metrics = step(
                state, {'tokens': toks[:, :-1], 'targets': toks[:, 1:]})
        assert np.isfinite(float(metrics['loss']))
        assert int(metrics['moe_pairs_dropped']) == 0
        assert 0 < int(metrics['moe_pairs_held']) < int(metrics['moe_pairs'])
        paths = dispatch.snapshot()
        assert paths['flash_attention'].startswith('pallas')
        assert paths['moe_experts'] == 'pallas'


class TestPagedAttentionLowers:
    """The paged decode kernel must compile through Mosaic at serving
    shapes (1B-like: hkv=8, G=4, d=64, P=64) and match the gather
    reference."""

    def test_paged_kernel_matches_gather(self):
        from skypilot_tpu.infer.paged_cache import PagePool
        from skypilot_tpu.ops import attention as attention_ops
        from skypilot_tpu.ops import paged_attention

        rng = np.random.default_rng(0)
        slots, hq, hkv, d, p, mp = 8, 32, 8, 64, 64, 16
        n_pages = slots * mp + 1
        q = jnp.asarray(rng.normal(size=(slots, hq, d)), jnp.bfloat16)
        kp = jnp.asarray(rng.normal(size=(n_pages, hkv, p, d)),
                         jnp.bfloat16)
        vp = jnp.asarray(rng.normal(size=(n_pages, hkv, p, d)),
                         jnp.bfloat16)
        tables = jnp.asarray(
            np.arange(1, 1 + slots * mp).reshape(slots, mp), jnp.int32)
        lengths = jnp.asarray([575, 3, 100, 64, 63, 200, 17, 512],
                              jnp.int32)
        out = paged_attention.paged_decode_attention(q, kp, vp, tables,
                                                     lengths)
        kv = PagePool.gather_view_layer(kp, tables)
        vv = PagePool.gather_view_layer(vp, tables)
        ref = attention_ops.mha_reference(q[:, None], kv, vv,
                                          q_positions=lengths[:, None])
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(ref[:, 0],
                                                    np.float32),
            atol=3e-2, rtol=3e-2)

    def test_paged_int8_kernel_matches_dequant_gather(self):
        """The int8-KV kernel (k/v int8 pages + scale blocks, dequant
        folded into the matmuls) must lower through Mosaic at the same
        serving shapes and match the dequantizing gather floor."""
        from skypilot_tpu.infer.paged_cache import PagePool
        from skypilot_tpu.ops import attention as attention_ops
        from skypilot_tpu.ops import paged_attention

        rng = np.random.default_rng(1)
        slots, hq, hkv, d, p, mp = 8, 32, 8, 64, 64, 16
        n_pages = slots * mp + 1
        q = jnp.asarray(rng.normal(size=(slots, hq, d)), jnp.bfloat16)
        kp = jnp.asarray(rng.integers(-127, 128,
                                      (n_pages, hkv, p, d)), jnp.int8)
        vp = jnp.asarray(rng.integers(-127, 128,
                                      (n_pages, hkv, p, d)), jnp.int8)
        ks = jnp.asarray(rng.uniform(0.005, 0.02, (n_pages, hkv, p)),
                         jnp.float32)
        vs = jnp.asarray(rng.uniform(0.005, 0.02, (n_pages, hkv, p)),
                         jnp.float32)
        tables = jnp.asarray(
            np.arange(1, 1 + slots * mp).reshape(slots, mp), jnp.int32)
        lengths = jnp.asarray([575, 3, 100, 64, 63, 200, 17, 512],
                              jnp.int32)
        out = paged_attention.paged_decode_attention_q(
            q, kp, vp, ks, vs, tables, lengths)
        kv = PagePool.gather_view_layer_q(kp, ks, tables, jnp.float32)
        vv = PagePool.gather_view_layer_q(vp, vs, tables, jnp.float32)
        ref = attention_ops.mha_reference(
            q.astype(jnp.float32)[:, None], kv, vv,
            q_positions=lengths[:, None])
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(ref[:, 0],
                                                    np.float32),
            atol=3e-2, rtol=3e-2)


class TestEnginePrefillDecode:
    """One prefill + a few decode steps on the chip, both cache modes
    (paged engages the Pallas paged-attention kernel)."""

    @pytest.mark.parametrize('cache_mode', ['dense', 'paged'])
    def test_prefill_decode(self, cache_mode):
        from skypilot_tpu.infer import engine as engine_lib
        from skypilot_tpu.infer import server as server_lib

        engine = server_lib.build_engine('debug', num_slots=2,
                                         max_seq_len=128,
                                         cache_mode=cache_mode)
        engine.start()
        try:
            params = engine_lib.SamplingParams(max_new_tokens=4)
            _, q = engine.submit([1, 2, 3, 4, 5, 6, 7, 8], params)
            toks = []
            while True:
                t = q.get(timeout=300)
                if t is None:
                    break
                toks.append(t)
            assert len(toks) == 4
        finally:
            engine.stop()

    def test_spec_mq_kernel_lowers(self, monkeypatch):
        """The multi-query paged-attention kernel must lower through
        Mosaic and match the plain engine (validates flipping
        SKYT_SPEC_PAGED_ATTN to default-pallas)."""
        monkeypatch.setenv('SKYT_SPEC_PAGED_ATTN', 'pallas')
        from skypilot_tpu.infer import engine as engine_lib
        from skypilot_tpu.infer import server as server_lib

        prompt = [5, 9, 2] * 8
        outs = {}
        for spec in (4, 0):
            engine = server_lib.build_engine(
                'debug', num_slots=2, max_seq_len=256,
                cache_mode='paged', spec_decode=spec)
            engine.start()
            try:
                outs[spec] = engine.generate(
                    prompt,
                    engine_lib.SamplingParams(max_new_tokens=16))
            finally:
                engine.stop()
        assert outs[4] == outs[0]

    def test_spec_decode_lowers(self):
        """The speculative decode step (multi-token paged append +
        gather-view attention + on-device verify) must lower and match
        the plain greedy engine on the chip."""
        from skypilot_tpu.infer import engine as engine_lib
        from skypilot_tpu.infer import server as server_lib

        prompt = [5, 9, 2] * 8

        def gen(spec):
            engine = server_lib.build_engine(
                'debug', num_slots=2, max_seq_len=256,
                cache_mode='paged', spec_decode=spec)
            engine.start()
            try:
                return engine.generate(
                    prompt,
                    engine_lib.SamplingParams(max_new_tokens=16))
            finally:
                engine.stop()

        assert gen(4) == gen(0)

    def test_spec_sampling_and_topp_lower(self):
        """Round-4 sampling additions must lower on the real chip: the
        rejection-sampling spec verify (per-slot keys + categorical in
        a scan) and the combined top-k/top-p filter in the plain path."""
        from skypilot_tpu.infer import engine as engine_lib
        from skypilot_tpu.infer import server as server_lib

        prompt = [5, 9, 2] * 8

        def gen(spec):
            engine = server_lib.build_engine(
                'debug', num_slots=2, max_seq_len=256,
                cache_mode='paged', spec_decode=spec)
            engine.start()
            try:
                return engine.generate(
                    prompt,
                    engine_lib.SamplingParams(
                        max_new_tokens=12, temperature=0.8,
                        top_k=16, top_p=0.8))
            finally:
                engine.stop()

        out_spec = gen(3)       # rejection-sampling verify path
        out_plain = gen(0)      # _sampling_filter in decode_n
        assert len(out_spec) == 12 and len(out_plain) == 12

    def test_chunked_prefill_lowers(self):
        """Chunked prefill's page-write path (insert w/o table install,
        suffix continuation per chunk) must lower and match."""
        from skypilot_tpu.infer import engine as engine_lib
        from skypilot_tpu.infer import server as server_lib

        prompt = list(range(1, 101))

        def gen(chunk):
            engine = server_lib.build_engine(
                'debug', num_slots=2, max_seq_len=256,
                cache_mode='paged', prefill_chunk=chunk)
            engine.start()
            try:
                return engine.generate(
                    prompt,
                    engine_lib.SamplingParams(max_new_tokens=8))
            finally:
                engine.stop()

        assert gen(64) == gen(0)

    def test_quantized_engine_lowers(self):
        """int8 weight-only serving (QuantDense) must lower and decode
        on the chip."""
        from skypilot_tpu.infer import engine as engine_lib
        from skypilot_tpu.infer import server as server_lib

        engine = server_lib.build_engine('debug', num_slots=2,
                                         max_seq_len=128,
                                         cache_mode='paged',
                                         quantize='int8')
        engine.start()
        try:
            out = engine.generate(
                [1, 2, 3, 4, 5, 6, 7, 8],
                engine_lib.SamplingParams(max_new_tokens=4))
            assert len(out) == 4
        finally:
            engine.stop()

    def test_int8_kv_engine_lowers(self):
        """int8 KV serving (quantized pools + in-kernel dequant read
        path + quantizing insert/append scatters) must lower and
        decode on the chip, agreeing with the fp engine's first
        token."""
        from skypilot_tpu.infer import engine as engine_lib
        from skypilot_tpu.infer import server as server_lib

        prompt = [1, 2, 3, 4, 5, 6, 7, 8]

        def run(kv_dtype):
            engine = server_lib.build_engine('debug', num_slots=2,
                                             max_seq_len=128,
                                             cache_mode='paged',
                                             kv_dtype=kv_dtype)
            engine.start()
            try:
                return engine.generate(
                    prompt,
                    engine_lib.SamplingParams(max_new_tokens=4))
            finally:
                engine.stop()

        q8 = run('int8')
        fp = run('auto')
        assert len(q8) == 4
        assert q8[0] == fp[0]   # prefill is float either way

    def test_ragged_prefill_lowers(self):
        """The packed ragged admission path (segment-masked prefill +
        per-request src_off page scatters) must lower on the chip and
        match sequential admission byte-for-byte."""
        from skypilot_tpu.infer import engine as engine_lib
        from skypilot_tpu.infer import server as server_lib

        prompts = [list(range(1, 20)), list(range(5, 55)),
                   list(range(7, 40))]
        base = server_lib.build_engine('debug', num_slots=4,
                                       max_seq_len=128,
                                       cache_mode='paged')
        model, params = base.model, base.params

        def run(**kw):
            engine = engine_lib.InferenceEngine(
                model, params, num_slots=4, max_seq_len=128,
                cache_mode='paged', **kw)
            qs = [engine.submit(
                p, engine_lib.SamplingParams(max_new_tokens=4))[1]
                for p in prompts]
            engine.start()
            try:
                outs = []
                for q in qs:
                    toks = []
                    while True:
                        t = q.get(timeout=300)
                        if t is None:
                            break
                        toks.append(t)
                    outs.append(toks)
                return outs, dict(engine.perf)
            finally:
                engine.stop()

        rag, perf = run()
        assert perf['ragged_dispatches'] >= 1
        seq, _ = run(batch_admission=False)
        assert rag == seq

    def test_prefix_cached_admission(self):
        """The prefix-cache suffix-prefill path (pool gather + dense
        continuation + offset page scatter) must lower on the chip and
        reproduce the uncached outputs."""
        from skypilot_tpu.infer import engine as engine_lib
        from skypilot_tpu.infer import server as server_lib

        rng = np.random.default_rng(5)
        prompt = rng.integers(1, 250, 80).tolist()   # > 1 page of 64

        def run_twice(prefix_caching):
            engine = server_lib.build_engine(
                'debug', num_slots=2, max_seq_len=256,
                cache_mode='paged', prefix_caching=prefix_caching)
            engine.start()
            try:
                outs = []
                for _ in range(2):
                    outs.append(engine.generate(
                        prompt,
                        engine_lib.SamplingParams(max_new_tokens=4)))
                hits = engine.pool.prefix_stats['hit_pages']
                return outs, hits
            finally:
                engine.stop()

        cached, hits = run_twice(True)
        assert hits >= 1, 'second admission should share prefix pages'
        uncached, _ = run_twice(False)
        assert cached == uncached

    def test_lora_grouped_lowers(self):
        """The grouped-LoRA delta kernels (per-sequence gather and
        per-token grouped paths, docs/serving.md "Adapter fleet") must
        lower through Mosaic on the chip — not silently descend to the
        XLA floor — and match it numerically."""
        from skypilot_tpu.ops import dispatch
        from skypilot_tpu.ops import lora as lora_ops

        b, s, din, r, dout, n = 4, 256, 512, 8, 512, 4
        x = _rand(0, (b, s, din))
        a = _rand(1, (n, din, r))
        bb = _rand(2, (n, r, dout))
        # Slot 0 is the base model: its adapter rows are zero.
        a = a.at[0].set(0)
        bb = bb.at[0].set(0)
        key = jax.random.PRNGKey(3)
        scale_of = jnp.asarray([0.0, 2.0, 0.5, 1.0], jnp.float32)

        dispatch.reset_for_tests()
        jax.clear_caches()
        # Per-sequence ids [B]: the assigned-slot decode path.
        ids = jax.random.randint(key, (b,), 0, n)
        got = jax.jit(lora_ops.grouped_lora_delta)(
            x, a, bb, ids, scale_of[ids])
        ref = jax.jit(lora_ops._xla_gather)(x, a, bb, ids,
                                            scale_of[ids])
        assert dispatch.snapshot().get(lora_ops.OP) == 'pallas', \
            dispatch.snapshot()
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(ref, np.float32),
            atol=3e-2, rtol=3e-2)

        dispatch.reset_for_tests()
        jax.clear_caches()
        # Per-token ids [B, S]: the mixed-adapter ragged-pack path.
        tids = jax.random.randint(key, (b, s), 0, n)
        got = jax.jit(lora_ops.grouped_lora_delta)(
            x, a, bb, tids, scale_of[tids])
        ref = jax.jit(lora_ops._xla_grouped)(x, a, bb, tids,
                                             scale_of[tids])
        assert dispatch.snapshot().get(lora_ops.OP) == 'pallas', \
            dispatch.snapshot()
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(ref, np.float32),
            atol=3e-2, rtol=3e-2)


class TestPagedPoolLayout:
    """The compiled decode chunk at a published width (qwen2-1.5b,
    depth cut to two layers): the page pools must stay in the row-major
    layout the Pallas kernel reads, from the jit's parameters to its
    results. With the [H, d] slab as the append scatter's window,
    XLA:TPU kept the pools as [pages, P, H, d] and transposed them to
    row-major and back around the kernel on every layer of every step
    (PagePool._set_rows)."""

    def test_pools_stay_row_major(self):
        from skypilot_tpu.infer import engine as engine_lib
        from skypilot_tpu.models import llama

        cfg = dataclasses.replace(
            llama.CONFIGS['qwen2-1.5b'], n_layers=2, remat=False,
            param_dtype='bfloat16', max_seq_len=2048)
        model = llama.LlamaModel(cfg)
        params = jax.jit(model.init)(jax.random.PRNGKey(0),
                                     jnp.zeros((1, 8), jnp.int32))
        engine = engine_lib.InferenceEngine(
            model, params, num_slots=8, max_seq_len=2048,
            cache_mode='paged')
        engine._ensure_dev_args()
        d = engine._dev_args
        hlo = engine._jit_decode_n.lower(
            engine.params, engine.cache, *d[:9], None, *d[9:],
            n=16, sampling=False, penalize=False,
            biased=False).compile().as_text()
        assert 'paged_decode_attention' in hlo   # the Mosaic call
        pool = engine.cache['k'].shape           # [L, pages, H, P, d]
        for shape in (pool, pool[1:]):
            dims = ','.join(map(str, shape))
            layouts = set(re.findall(
                r'\[' + re.escape(dims) + r'\]\{([0-9,]+)', hlo))
            want = ','.join(map(str, reversed(range(len(shape)))))
            assert layouts == {want}, (
                f'a [{dims}] pool takes layouts {layouts} in the '
                f'compiled decode chunk; only {want} (row-major) '
                f'moves no data')


class TestCommsPlane:
    """On-chip comms plane gate (docs/observability.md "Comms plane"):
    the probe must measure real links and the census must count real
    SPMD collectives on the chip — the CPU suite can only prove the
    math, not the lowering."""

    def test_probe_and_census_on_chip(self, tmp_path, monkeypatch):
        from skypilot_tpu.parallel import comms_census
        from skypilot_tpu.parallel import comms_profile
        from skypilot_tpu.parallel import mesh as mesh_lib
        from skypilot_tpu.models import llama
        from skypilot_tpu.train import trainer

        n = jax.device_count()
        if n < 2:
            pytest.skip('needs >= 2 devices for collectives')
        monkeypatch.setenv('SKYT_COMMS_CACHE',
                           str(tmp_path / 'comms.json'))
        comms_profile.reset_for_tests()
        mesh = mesh_lib.build_mesh(
            mesh_lib.MeshSpec(fsdp=n))
        profile, src = comms_profile.load_or_probe(
            mesh, payloads_mb=[1.0], iters=3, budget_s=240.0)
        assert src == 'probed'
        summ = comms_profile.summary(profile)
        assert summ.get('ici.all_reduce', {}).get('busbw_gbps', 0) > 0

        cfg = llama.CONFIGS['debug']
        model = llama.LlamaModel(cfg)
        tx = trainer.make_optimizer(trainer.TrainerConfig(
            warmup_steps=1, total_steps=4))
        sample = jnp.zeros((4, 64), jnp.int32)
        state, _ = trainer.create_sharded_state(
            model, tx, mesh, sample, jax.random.PRNGKey(0))
        step = trainer.make_train_step(model, tx, mesh, donate=False)
        data = {'tokens': sample, 'targets': sample}
        entries, source = comms_census.census_step(
            step, state, data, mesh=mesh, mode='compiled')
        assert source == 'hlo_compiled'
        assert entries, 'no collectives counted on a real sharded step'
        assert all(e.axes == ('fsdp',) for e in entries)
        rep = comms_census.report(
            entries, source, profile=profile,
            link_classes=comms_profile.axis_link_classes(mesh))
        assert rep['axes']['fsdp']['bytes'] > 0
        assert rep['axes']['fsdp']['seconds'] is not None

    def test_ici_beats_dcn_on_multislice(self, tmp_path, monkeypatch):
        """The physical claim the whole plane rests on: measured ICI
        bus bandwidth must exceed measured DCN bus bandwidth. Only a
        real multi-slice topology can answer."""
        from skypilot_tpu.parallel import comms_profile
        from skypilot_tpu.parallel import mesh as mesh_lib

        devices = jax.devices()
        slices = {getattr(d, 'slice_index', 0) for d in devices}
        if len(slices) < 2:
            pytest.skip('needs a real multi-slice topology '
                        '(device.slice_index)')
        monkeypatch.setenv('SKYT_COMMS_CACHE',
                           str(tmp_path / 'comms.json'))
        comms_profile.reset_for_tests()
        n_slices = len(slices)
        per_slice = len(devices) // n_slices
        mesh = mesh_lib.build_hybrid_mesh(
            mesh_lib.MeshSpec(fsdp=per_slice),
            mesh_lib.MeshSpec(dp=n_slices))
        profile, _src = comms_profile.load_or_probe(
            mesh, payloads_mb=[4.0], iters=3, budget_s=300.0)
        summ = comms_profile.summary(profile)
        ici = summ.get('ici.all_gather', {}).get('busbw_gbps', 0.0)
        dcn = summ.get('dcn.all_gather', {}).get('busbw_gbps', 0.0)
        assert ici > 0 and dcn > 0, summ
        assert ici > dcn, (
            f'ICI busbw {ici} GB/s should exceed DCN {dcn} GB/s')
