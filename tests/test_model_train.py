"""Model + sharded-training tests on the virtual 8-device CPU mesh — the
fake multi-host harness the reference lacks (SURVEY.md §4 implication)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from skypilot_tpu.models import llama
from skypilot_tpu.parallel import mesh as mesh_lib
from skypilot_tpu.train import trainer

# Compile-heavy (JAX jit on the 1-core CPU host) or subprocess-driven:
pytestmark = pytest.mark.heavy


@pytest.fixture(scope='module')
def debug_setup():
    cfg = llama.CONFIGS['debug']
    model = llama.LlamaModel(cfg)
    mesh = mesh_lib.build_mesh(mesh_lib.MeshSpec(dp=2, fsdp=2, tp=2))
    tcfg = trainer.TrainerConfig(warmup_steps=2, total_steps=10,
                                 learning_rate=1e-2)
    tx = trainer.make_optimizer(tcfg)
    sample = jnp.zeros((8, 32), jnp.int32)
    state, shardings = trainer.create_sharded_state(
        model, tx, mesh, sample, jax.random.PRNGKey(0))
    return cfg, model, mesh, tx, state


def _batch(b=8, s=32, vocab=256, seed=0):
    rng = np.random.default_rng(seed)
    toks = rng.integers(0, vocab, (b, s + 1))
    return {'tokens': jnp.array(toks[:, :-1], jnp.int32),
            'targets': jnp.array(toks[:, 1:], jnp.int32)}


class TestMeshSpec:
    def test_shapes(self):
        spec = mesh_lib.MeshSpec(dp=2, fsdp=2, tp=2)
        assert spec.num_devices == 8
        assert mesh_lib.build_mesh(spec).shape['tp'] == 2

    def test_auto_spec_defaults_to_fsdp(self):
        spec = mesh_lib.auto_spec(8)
        assert spec.fsdp == 8 and spec.num_devices == 8

    def test_auto_spec_model_size(self):
        # 8B params (~134 GiB state) on 16GiB chips: needs fsdp >= 16/tp=4.
        spec = mesh_lib.auto_spec(16, tp=4, model_params_b=8.0,
                                  hbm_gib_per_device=16.0)
        assert spec.num_devices == 16
        assert spec.fsdp * spec.tp >= 8

    def test_topology_mesh(self):
        from skypilot_tpu.accelerators import parse_tpu
        spec = mesh_lib.mesh_for_topology(parse_tpu('tpu-v5e-16'))
        assert spec.num_devices == 16
        assert spec.tp == 4  # chips per host

    def test_indivisible_raises(self):
        with pytest.raises(ValueError):
            mesh_lib.auto_spec(8, tp=3)


class TestModel:
    def test_param_count_matches_analytic(self, debug_setup):
        cfg, model, mesh, tx, state = debug_setup
        n = sum(x.size for x in jax.tree.leaves(state.params))
        assert n == cfg.num_params()

    def test_params_are_sharded(self, debug_setup):
        cfg, model, mesh, tx, state = debug_setup
        shardings = {jax.tree_util.keystr(k): v.sharding
                     for k, v in jax.tree_util.tree_leaves_with_path(
                         state.params)}
        # At least one param must be sharded over fsdp and one over tp.
        specs = [tuple(s.spec) for s in shardings.values()]
        flat = [ax for spec in specs for ax in spec if ax is not None]
        assert 'fsdp' in str(flat) and 'tp' in str(flat), specs

    def test_loss_decreases(self, debug_setup):
        cfg, model, mesh, tx, state = debug_setup
        # donate=False: the module-scoped fixture state must survive for
        # later tests (donation invalidates the input buffers).
        step = trainer.make_train_step(model, tx, mesh, donate=False)
        batch = _batch()
        losses = []
        for _ in range(5):
            state, m = step(state, batch)
            losses.append(float(m['loss']))
        assert losses[-1] < losses[0]
        assert np.isfinite(losses).all()

    def test_scan_and_unrolled_agree(self):
        import dataclasses
        cfg = dataclasses.replace(llama.CONFIGS['debug'], scan_layers=True)
        cfg_u = dataclasses.replace(cfg, scan_layers=False)
        tokens = _batch(b=2, s=16)['tokens']
        m_s = llama.LlamaModel(cfg)
        vars_s = m_s.init(jax.random.PRNGKey(1), tokens)
        out_s = m_s.apply(vars_s, tokens)
        # Map scanned params (stacked on axis 0) to unrolled layer params.
        import flax
        p = flax.core.unfreeze(vars_s)['params']
        stacked = p.pop('layers')
        for i in range(cfg.n_layers):
            p[f'layer_{i}'] = jax.tree.map(lambda x: x[i], stacked)
        out_u = llama.LlamaModel(cfg_u).apply({'params': p}, tokens)
        np.testing.assert_allclose(np.asarray(out_s), np.asarray(out_u),
                                   rtol=2e-5, atol=2e-5)

    def test_gqa_head_shapes(self):
        cfg = llama.CONFIGS['debug']
        assert cfg.n_heads % cfg.n_kv_heads == 0

    def test_remat_policies_agree(self):
        """remat_policy changes WHAT the backward recomputes, never the
        math: loss and grads under 'dots' (save matmul outputs) must
        match 'full' (save nothing) — the bench's SKYT_BENCH_REMAT knob
        flips between them."""
        import dataclasses

        import flax

        tokens = _batch(b=2, s=16)
        grads = {}
        for pol in ('full', 'dots'):
            cfg = dataclasses.replace(llama.CONFIGS['debug'],
                                      remat=True, remat_policy=pol)
            model = llama.LlamaModel(cfg)
            variables = model.init(jax.random.PRNGKey(3),
                                   tokens['tokens'])

            def loss_fn(params):
                logits = model.apply({'params': params},
                                     tokens['tokens'])
                loss, _ = trainer.cross_entropy_loss(logits,
                                                     tokens['targets'])
                return loss

            loss, g = jax.value_and_grad(loss_fn)(
                flax.core.unfreeze(variables)['params'])
            grads[pol] = (float(loss), g)
        assert np.isclose(grads['full'][0], grads['dots'][0], rtol=1e-5)
        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-5),
            grads['full'][1], grads['dots'][1])

    def test_the_full_policy_runs_each_flash_forward_twice(self):
        """remat_policy 'full' saves no name, the flash residuals'
        neither: each block's backward runs its flash forward again
        (two forwards a block, one dq and one dk/dv), which keeps the
        outputs of every layer out of memory (PERF.md §6)."""
        import dataclasses

        import flax
        from flash_walk_helpers import kernel_calls

        tokens = _batch(b=2, s=64)
        cfg = dataclasses.replace(llama.CONFIGS['debug'], remat=True,
                                  remat_policy='full', attn_impl='flash')
        model = llama.LlamaModel(cfg)
        params = flax.core.unfreeze(model.init(
            jax.random.PRNGKey(3), tokens['tokens']))['params']

        def loss_fn(p):
            logits = model.apply({'params': p}, tokens['tokens'])
            return trainer.cross_entropy_loss(logits, tokens['targets'])[0]
        calls = kernel_calls(jax.make_jaxpr(jax.grad(loss_fn))(params).jaxpr)
        # one scanned block: its forward, and again in the backward's body
        assert calls == {'_fwd_kernel': 2, '_dq_kernel': 1,
                         '_dkv_kernel': 1}

    def test_eval_step(self, debug_setup):
        cfg, model, mesh, tx, state = debug_setup
        ev = trainer.make_eval_step(model, mesh)
        m = ev(state.params, _batch())
        assert np.isfinite(float(m['loss']))


class TestOps:
    def test_gqa_matches_repeated_mha(self):
        from skypilot_tpu.ops.attention import mha_reference
        rng = np.random.default_rng(0)
        b, s, hq, hkv, d = 2, 16, 4, 2, 8
        q = jnp.array(rng.normal(size=(b, s, hq, d)), jnp.float32)
        k = jnp.array(rng.normal(size=(b, s, hkv, d)), jnp.float32)
        v = jnp.array(rng.normal(size=(b, s, hkv, d)), jnp.float32)
        out = mha_reference(q, k, v, causal=True)
        # repeat kv to full heads -> plain MHA must agree
        k_full = jnp.repeat(k, hq // hkv, axis=2)
        v_full = jnp.repeat(v, hq // hkv, axis=2)
        out_full = mha_reference(q, k_full, v_full, causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(out_full),
                                   rtol=1e-5, atol=1e-5)

    def test_causality(self):
        from skypilot_tpu.ops.attention import mha_reference
        rng = np.random.default_rng(0)
        b, s, h, d = 1, 8, 2, 4
        q = jnp.array(rng.normal(size=(b, s, h, d)), jnp.float32)
        k = jnp.array(rng.normal(size=(b, s, h, d)), jnp.float32)
        v = jnp.array(rng.normal(size=(b, s, h, d)), jnp.float32)
        out1 = mha_reference(q, k, v, causal=True)
        # Perturbing the future must not change earlier outputs.
        k2 = k.at[:, -1].set(99.0)
        v2 = v.at[:, -1].set(99.0)
        out2 = mha_reference(q, k2, v2, causal=True)
        np.testing.assert_allclose(np.asarray(out1[:, :-1]),
                                   np.asarray(out2[:, :-1]), atol=1e-6)

    def test_segment_isolation(self):
        from skypilot_tpu.ops.attention import mha_reference
        rng = np.random.default_rng(0)
        b, s, h, d = 1, 8, 2, 4
        q = jnp.array(rng.normal(size=(b, s, h, d)), jnp.float32)
        k = jnp.array(rng.normal(size=(b, s, h, d)), jnp.float32)
        v = jnp.array(rng.normal(size=(b, s, h, d)), jnp.float32)
        seg = jnp.array([[0, 0, 0, 0, 1, 1, 1, 1]])
        out = mha_reference(q, k, v, causal=True, segment_ids=seg)
        # second segment must ignore first-segment K/V entirely
        out_iso = mha_reference(q[:, 4:], k[:, 4:], v[:, 4:], causal=True)
        np.testing.assert_allclose(np.asarray(out[:, 4:]),
                                   np.asarray(out_iso), rtol=1e-5, atol=1e-5)

    def test_rope_rotation_preserves_norm(self):
        from skypilot_tpu.ops import rope
        pos = jnp.arange(16)[None]
        cos, sin = rope.rope_freqs(pos, 8, 10000.0)
        x = jnp.ones((1, 16, 2, 8))
        y = rope.apply_rope(x, cos, sin)
        np.testing.assert_allclose(np.linalg.norm(np.asarray(x), axis=-1),
                                   np.linalg.norm(np.asarray(y), axis=-1),
                                   rtol=1e-5)

    def test_rms_norm(self):
        from skypilot_tpu.ops import norms
        x = jnp.array(np.random.default_rng(0).normal(size=(4, 8)) * 10,
                      jnp.float32)
        y = norms.rms_norm(x, jnp.ones((8,)))
        rms = np.sqrt(np.mean(np.asarray(y) ** 2, -1))
        np.testing.assert_allclose(rms, 1.0, rtol=1e-3)


def test_jsonl_batches_with_hf_tokenizer(tmp_path):
    """--data-tokenizer: JSONL 'text' rows tokenize through the real
    tokenizer instead of the byte fallback."""
    import json as _json

    from skypilot_tpu.train import sft

    data = tmp_path / 'd.jsonl'
    data.write_text(_json.dumps({'text': 'hello world'}) + '\n')

    class FakeTok:
        def encode(self, text):
            return [7] * len(text.split())
    got = next(sft.jsonl_batches(str(data), 256, 1, 4,
                                 tokenizer=FakeTok()))
    # stream: 7 7 0 7 7 0 ... packed into [1, 5] -> tokens [1,4]
    assert got['tokens'].tolist() == [[7, 7, 0, 7]]
    byte = next(sft.jsonl_batches(str(data), 256, 1, 4))
    assert byte['tokens'].tolist() == [[104, 101, 108, 108]]  # 'hell'


@pytest.mark.parametrize('family', ['gemma2', 'qwen3', 'phi3'])
def test_family_train_step(family):
    """One train step (forward + backward, remat + scan) through each
    family's special machinery — the gradient of the windowed/
    soft-capped/qk-normed attention has no other coverage. Gemma-2 is
    the hard case: traced layer-index window gating inside a
    rematerialized scan body."""
    import dataclasses

    base = dataclasses.replace(llama.CONFIGS['debug'], remat=True,
                               max_seq_len=64)
    cfg = {
        'gemma2': dataclasses.replace(
            base, n_layers=4, mlp_act='gelu_tanh',
            norm_zero_centered=True, embed_scale=True,
            tie_embeddings=True, head_dim_override=16,
            sliding_window=8, window_pattern=2, attn_softcap=30.0,
            final_softcap=20.0, attn_scale=32.0 ** -0.5,
            sandwich_norms=True),
        'qwen3': dataclasses.replace(base, qk_norm=True,
                                     head_dim_override=32,
                                     tie_embeddings=True),
        'phi3': dataclasses.replace(base, sliding_window=8),
    }[family]
    model = llama.LlamaModel(cfg)
    mesh = mesh_lib.build_mesh(mesh_lib.MeshSpec(fsdp=2, tp=2, dp=2))
    tcfg = trainer.TrainerConfig(warmup_steps=2, total_steps=100)
    tx = trainer.make_optimizer(tcfg)
    sample = jnp.zeros((2, 32), jnp.int32)
    state, _ = trainer.create_sharded_state(model, tx, mesh, sample,
                                            jax.random.PRNGKey(0))
    step = trainer.make_train_step(model, tx, mesh, donate=False)
    rng = np.random.default_rng(0)
    toks = rng.integers(0, cfg.vocab_size, (2, 33))
    batch = {'tokens': jnp.asarray(toks[:, :-1], jnp.int32),
             'targets': jnp.asarray(toks[:, 1:], jnp.int32)}
    losses = []
    for _ in range(3):
        state, m = step(state, batch)
        losses.append(float(m['loss']))
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0]   # same batch: must overfit
