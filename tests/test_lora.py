"""LoRA adapter tests (reference parity target:
llm/llama-3_1-finetuning/lora.yaml)."""
import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from skypilot_tpu.models import llama
from skypilot_tpu.parallel import mesh as mesh_lib
from skypilot_tpu.train import lora as lora_lib
from skypilot_tpu.train import trainer

# Compile-heavy (JAX jit on the 1-core CPU host) or subprocess-driven:
pytestmark = pytest.mark.heavy


@pytest.fixture(scope='module')
def base():
    cfg = llama.CONFIGS['debug']
    model = llama.LlamaModel(cfg)
    variables = jax.jit(model.init)(jax.random.PRNGKey(0),
                                    jnp.zeros((1, 16), jnp.int32))
    return cfg, model, nn.meta.unbox(variables['params'])


def test_init_targets_all_linears(base):
    cfg, model, params = base
    lcfg = lora_lib.LoRAConfig(rank=4)
    lora = lora_lib.init_lora_params(params, lcfg, jax.random.PRNGKey(1))
    leaves = jax.tree_util.tree_leaves_with_path(lora)
    # 7 targets x (a, b) on the scanned layer stack.
    assert len(leaves) == 14
    for path, leaf in leaves:
        keys = tuple(k.key for k in path
                     if isinstance(k, jax.tree_util.DictKey))
        assert keys[-1] in ('a', 'b')
        if keys[-1] == 'b':
            np.testing.assert_array_equal(np.asarray(leaf), 0.0)
        # scanned stack: leading layer axis preserved
        assert leaf.shape[0] == cfg.n_layers
        assert 4 in leaf.shape


def test_merge_identity_at_init(base):
    cfg, model, params = base
    lcfg = lora_lib.LoRAConfig(rank=4)
    lora = lora_lib.init_lora_params(params, lcfg, jax.random.PRNGKey(1))
    merged = lora_lib.merge_lora(params, lora, lcfg)
    toks = jnp.asarray([[1, 2, 3, 4, 5]], jnp.int32)
    out_base = model.apply({'params': params}, toks)
    out_merged = model.apply({'params': merged}, toks)
    np.testing.assert_allclose(np.asarray(out_merged),
                               np.asarray(out_base), rtol=1e-6,
                               atol=1e-6)


def test_merge_changes_output_when_b_nonzero(base):
    cfg, model, params = base
    lcfg = lora_lib.LoRAConfig(rank=4)
    lora = lora_lib.init_lora_params(params, lcfg, jax.random.PRNGKey(1))
    lora = jax.tree.map(
        lambda x: x + 0.05, lora)  # push B off zero
    merged = lora_lib.merge_lora(params, lora, lcfg)
    toks = jnp.asarray([[1, 2, 3, 4, 5]], jnp.int32)
    out_base = np.asarray(model.apply({'params': params}, toks))
    out_merged = np.asarray(model.apply({'params': merged}, toks))
    assert np.abs(out_merged - out_base).max() > 1e-4


def test_only_adapters_train(base):
    """Two LoRA steps: frozen base params bit-identical, adapter params
    move, loss finite, optimizer state shaped like the adapter tree."""
    cfg, model, params = base
    lcfg = lora_lib.LoRAConfig(rank=4)
    mesh = mesh_lib.build_mesh(mesh_lib.MeshSpec())  # single device
    tcfg = trainer.TrainerConfig(warmup_steps=1, total_steps=4,
                                 learning_rate=1e-2)
    tx = trainer.make_optimizer(tcfg)
    state = lora_lib.create_lora_state(model, params, tx, lcfg,
                                       jax.random.PRNGKey(1))
    assert (jax.tree_util.tree_structure(state.params) ==
            jax.tree_util.tree_structure(
                jax.tree.map(lambda x: x,
                             state.opt_state[1][0].mu)))

    frozen_before = jax.tree.map(lambda x: np.asarray(x).copy(), params)
    lora_before = jax.tree.map(lambda x: np.asarray(x).copy(),
                               state.params)
    step = lora_lib.make_lora_train_step(model, params, tx, mesh, lcfg)
    rng = np.random.default_rng(0)
    for _ in range(2):
        toks = rng.integers(0, cfg.vocab_size, (2, 17), dtype=np.int32)
        batch = {'tokens': jnp.asarray(toks[:, :-1]),
                 'targets': jnp.asarray(toks[:, 1:])}
        state, metrics = step(state, batch)
    assert np.isfinite(float(metrics['loss']))

    # Base params untouched.
    for (pa, a), (pb, b) in zip(
            jax.tree_util.tree_leaves_with_path(frozen_before),
            jax.tree_util.tree_leaves_with_path(params)):
        np.testing.assert_array_equal(a, np.asarray(b), err_msg=str(pa))
    # Adapters moved.
    moved = [
        not np.array_equal(a, np.asarray(b))
        for a, b in zip(jax.tree.leaves(lora_before),
                        jax.tree.leaves(state.params))]
    assert any(moved)


def test_num_lora_params_small(base):
    cfg, model, params = base
    lcfg = lora_lib.LoRAConfig(rank=4)
    lora = lora_lib.init_lora_params(params, lcfg, jax.random.PRNGKey(1))
    n_lora = lora_lib.num_lora_params(lora)
    n_base = sum(int(x.size) for x in jax.tree.leaves(params))
    assert n_lora < 0.2 * n_base


@pytest.mark.usefixtures('one_device_children')
def test_finetune_export_serve_loop(tmp_path):
    """The full reference-recipe loop on debug shapes: real base
    checkpoint -> sft --lora-rank -> export_lora merge -> the merged
    HF dir serves through build_engine."""
    import dataclasses

    from skypilot_tpu.infer import engine as engine_lib
    from skypilot_tpu.infer import server as server_lib
    from skypilot_tpu.models import weights
    from skypilot_tpu.train import export_lora, sft

    cfg = dataclasses.replace(llama.CONFIGS['debug'], max_seq_len=64)
    model = llama.LlamaModel(cfg)
    params = jax.jit(model.init)(jax.random.PRNGKey(5),
                                 jnp.zeros((1, 8), jnp.int32))
    base_dir = tmp_path / 'base'
    weights.save_hf_checkpoint(cfg, params, str(base_dir))

    run_dir = tmp_path / 'lora-run'
    sft.main(['--model', 'debug', '--base-checkpoint', str(base_dir),
              '--lora-rank', '2', '--steps', '2', '--batch', '2',
              '--seq', '16', '--checkpoint-dir', str(run_dir),
              '--checkpoint-every', '1', '--log-every', '1'])

    out_dir = tmp_path / 'merged'
    export_lora.main(['--base', str(base_dir), '--adapter', str(run_dir),
                      '--out', str(out_dir), '--lora-rank', '2'])

    def gen(ckpt):
        eng = server_lib.build_engine(checkpoint=str(ckpt), num_slots=1,
                                      max_seq_len=64, dtype='float32')
        eng.start()
        try:
            return eng.generate([5, 9, 2, 31],
                                engine_lib.SamplingParams(
                                    max_new_tokens=8))
        finally:
            eng.stop()

    merged_out = gen(out_dir)
    assert len(merged_out) == 8
    # The merge is not an identity: the merged kernels differ from the
    # base (B inits at zero, but 2 train steps moved it). Token-level
    # output can coincide on a tiny model, so compare weights directly.
    base_params = weights.load_llama_params(cfg, str(base_dir))
    merged_params = weights.load_llama_params(
        weights.load_config(str(out_dir), max_seq_len=64),
        str(out_dir))
    wq_base = np.asarray(
        base_params['params']['layers']['attn']['wq']['kernel'])
    wq_merged = np.asarray(
        merged_params['params']['layers']['attn']['wq']['kernel'])
    assert not np.allclose(wq_base, wq_merged)
