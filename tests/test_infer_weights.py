"""Weight loading, tokenizer, and tp-sharded inference tests.

Parity target: the reference serves real HF checkpoints via vLLM
(llm/vllm/serve.yaml); these tests prove our safetensors loader produces
the same logits as transformers' LlamaForCausalLM on the same checkpoint,
and that the engine decodes correctly when params + KV cache are
tp-sharded over a mesh.
"""
import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from skypilot_tpu.infer import engine as engine_lib
from skypilot_tpu.infer import tokenizer as tokenizer_lib
from skypilot_tpu.models import llama, weights
from skypilot_tpu.parallel import mesh as mesh_lib

# Compile-heavy (JAX jit on the 1-core CPU host) or subprocess-driven:
pytestmark = pytest.mark.heavy


@pytest.fixture(scope='module')
def debug_ckpt(tmp_path_factory):
    """A debug-size HF-format checkpoint written by save_hf_checkpoint."""
    cfg = dataclasses.replace(llama.CONFIGS['debug'], max_seq_len=64)
    model = llama.LlamaModel(cfg)
    params = jax.jit(model.init)(jax.random.PRNGKey(42),
                                 jnp.zeros((1, 8), jnp.int32))
    out = tmp_path_factory.mktemp('ckpt')
    weights.save_hf_checkpoint(cfg, params, str(out))
    return cfg, model, params, str(out)


def test_roundtrip_save_load(debug_ckpt):
    import flax.linen as nn
    cfg, _, params, ckpt_dir = debug_ckpt
    loaded = weights.load_llama_params(cfg, ckpt_dir)
    flat_a = jax.tree_util.tree_leaves_with_path(
        nn.meta.unbox(params['params']))
    flat_b = jax.tree_util.tree_leaves_with_path(loaded['params'])
    assert len(flat_a) == len(flat_b)
    for (pa, a), (pb, b) in zip(sorted(flat_a, key=lambda x: str(x[0])),
                                sorted(flat_b, key=lambda x: str(x[0]))):
        assert str(pa) == str(pb)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=0, atol=0, err_msg=str(pa))


def test_load_config_roundtrip(debug_ckpt):
    cfg, _, _, ckpt_dir = debug_ckpt
    cfg2 = weights.load_config(ckpt_dir, max_seq_len=cfg.max_seq_len,
                               dtype=cfg.dtype,
                               param_dtype=cfg.param_dtype,
                               use_llama31_rope=cfg.use_llama31_rope,
                               remat=cfg.remat)
    assert cfg2.vocab_size == cfg.vocab_size
    assert cfg2.dim == cfg.dim
    assert cfg2.n_layers == cfg.n_layers
    assert cfg2.n_kv_heads == cfg.n_kv_heads
    assert cfg2.mlp_dim == cfg.mlp_dim


# 41 s here, most of it the first import of torch and transformers in
# this worker. Measured on an idle 8-core box;
# the driver's is some three times slower.
@pytest.mark.time_limit(300)
def test_logits_match_transformers(debug_ckpt):
    """Our model on loaded weights == HF LlamaForCausalLM on the same
    checkpoint (the strongest correctness proof available offline)."""
    torch = pytest.importorskip('torch')
    transformers = pytest.importorskip('transformers')

    cfg, model, params, ckpt_dir = debug_ckpt
    hf_model = transformers.LlamaForCausalLM.from_pretrained(
        ckpt_dir, torch_dtype=torch.float32)
    hf_model.eval()

    rng = np.random.default_rng(0)
    tokens = rng.integers(0, cfg.vocab_size, (2, 12))
    with torch.no_grad():
        hf_logits = hf_model(torch.tensor(tokens)).logits.numpy()

    ours = np.asarray(model.apply(params, jnp.asarray(tokens, jnp.int32)))
    np.testing.assert_allclose(ours, hf_logits, rtol=2e-4, atol=2e-4)


def test_sharded_load_matches_unsharded(debug_ckpt):
    cfg, model, params, ckpt_dir = debug_ckpt
    mesh = mesh_lib.build_mesh(mesh_lib.MeshSpec(tp=2, fsdp=2, dp=2))
    loaded = weights.load_llama_params(cfg, ckpt_dir, mesh=mesh)
    # Sharding actually applied: wq kernel [L, D, H*hd] has heads on tp.
    wq = loaded['params']['layers']['attn']['wq']['kernel']
    assert wq.sharding.spec[-1] == 'tp'
    tokens = jnp.asarray(
        np.random.default_rng(1).integers(0, cfg.vocab_size, (2, 16)),
        jnp.int32)
    import flax.linen as nn
    from skypilot_tpu.parallel import sharding as sharding_lib
    with mesh, nn.logical_axis_rules(list(sharding_lib.DEFAULT_RULES)):
        sharded_out = np.asarray(jax.jit(model.apply)(loaded, tokens))
    plain_out = np.asarray(model.apply(params, tokens))
    np.testing.assert_allclose(sharded_out, plain_out, rtol=2e-4,
                               atol=2e-4)


def test_nonscan_layout_load(debug_ckpt):
    cfg, _, params, ckpt_dir = debug_ckpt
    cfg_ns = dataclasses.replace(cfg, scan_layers=False)
    model_ns = llama.LlamaModel(cfg_ns)
    loaded = weights.load_llama_params(cfg_ns, ckpt_dir)
    tokens = jnp.asarray([[3, 1, 4, 1, 5, 9, 2, 6]], jnp.int32)
    out_ns = np.asarray(model_ns.apply(loaded, tokens))
    model_s = llama.LlamaModel(cfg)
    out_s = np.asarray(model_s.apply(params, tokens))
    np.testing.assert_allclose(out_ns, out_s, rtol=2e-4, atol=2e-4)


def test_tied_checkpoint_into_untied_config(tmp_path):
    cfg = dataclasses.replace(llama.CONFIGS['debug'], max_seq_len=64,
                              tie_embeddings=True)
    model = llama.LlamaModel(cfg)
    params = jax.jit(model.init)(jax.random.PRNGKey(0),
                                 jnp.zeros((1, 8), jnp.int32))
    weights.save_hf_checkpoint(cfg, params, str(tmp_path))
    cfg_untied = dataclasses.replace(cfg, tie_embeddings=False)
    loaded = weights.load_llama_params(cfg_untied, str(tmp_path))
    embed = np.asarray(loaded['params']['tok_embed'])
    head = np.asarray(loaded['params']['lm_head']['kernel'])
    np.testing.assert_array_equal(embed.T, head)


def test_engine_sharded_decode_matches_unsharded(debug_ckpt):
    cfg, model, params, ckpt_dir = debug_ckpt
    prompt = [5, 17, 3, 99, 42]

    eng_plain = engine_lib.InferenceEngine(model, params, num_slots=2,
                                           max_seq_len=64,
                                           prefill_buckets=[16])
    eng_plain.start()
    try:
        want = eng_plain.generate(prompt, engine_lib.SamplingParams(
            max_new_tokens=8))
    finally:
        eng_plain.stop()

    mesh = mesh_lib.build_mesh(mesh_lib.MeshSpec(tp=2))
    sharded = weights.load_llama_params(cfg, ckpt_dir, mesh=mesh)
    eng = engine_lib.InferenceEngine(model, sharded, num_slots=2,
                                     max_seq_len=64,
                                     prefill_buckets=[16], mesh=mesh)
    eng.start()
    try:
        got = eng.generate(prompt, engine_lib.SamplingParams(
            max_new_tokens=8))
    finally:
        eng.stop()
    assert got == want
    # The KV cache stayed sharded over tp through decode.
    assert eng.cache['k'].sharding.spec[3] == 'tp'


def test_engine_sharded_paged_decode_matches_unsharded(debug_ckpt):
    """tp-sharded PAGED engine: the page pool shards kv_heads on axis 2
    ([L, pages, H, P, d]) and decode matches the unsharded engine."""
    cfg, model, params, ckpt_dir = debug_ckpt
    prompt = [5, 17, 3, 99, 42]

    eng_plain = engine_lib.InferenceEngine(model, params, num_slots=2,
                                           max_seq_len=64,
                                           prefill_buckets=[16],
                                           cache_mode='paged',
                                           page_size=16)
    eng_plain.start()
    try:
        want = eng_plain.generate(prompt, engine_lib.SamplingParams(
            max_new_tokens=8))
    finally:
        eng_plain.stop()

    mesh = mesh_lib.build_mesh(mesh_lib.MeshSpec(tp=2))
    sharded = weights.load_llama_params(cfg, ckpt_dir, mesh=mesh)
    eng = engine_lib.InferenceEngine(model, sharded, num_slots=2,
                                     max_seq_len=64,
                                     prefill_buckets=[16], mesh=mesh,
                                     cache_mode='paged', page_size=16)
    eng.start()
    try:
        got = eng.generate(prompt, engine_lib.SamplingParams(
            max_new_tokens=8))
    finally:
        eng.stop()
    assert got == want
    assert eng.cache['k'].sharding.spec[2] == 'tp'


# ---------------------------------------------------------------- tokenizer
def test_byte_tokenizer_roundtrip():
    tok = tokenizer_lib.ByteTokenizer(256)
    text = 'hello tpu'
    assert tok.decode(tok.encode(text)) == text


def _write_wordlevel_tokenizer(path):
    """Build a tiny real tokenizer.json with the tokenizers runtime."""
    import tokenizers
    from tokenizers import models as tok_models
    from tokenizers import pre_tokenizers

    vocab = {'<s>': 0, '</s>': 1, '<unk>': 2, 'hello': 3, 'tpu': 4,
             'world': 5}
    tok = tokenizers.Tokenizer(
        tok_models.WordLevel(vocab, unk_token='<unk>'))
    tok.pre_tokenizer = pre_tokenizers.Whitespace()
    tok.save(str(path))


def test_hf_tokenizer_loads_and_roundtrips(tmp_path):
    tj = tmp_path / 'tokenizer.json'
    _write_wordlevel_tokenizer(tj)
    with open(tmp_path / 'tokenizer_config.json', 'w') as f:
        json.dump({'bos_token': '<s>', 'eos_token': '</s>'}, f)
    tok = tokenizer_lib.load_tokenizer(str(tmp_path))
    assert tok.bos_id == 0
    assert tok.eos_id == 1
    ids = tok.encode('hello tpu world')
    assert ids[0] == 0  # bos prepended
    assert ids[1:] == [3, 4, 5]
    assert tok.decode(ids) == 'hello tpu world'


def test_hf_tokenizer_config_json_ids(tmp_path):
    tj = tmp_path / 'tokenizer.json'
    _write_wordlevel_tokenizer(tj)
    with open(tmp_path / 'config.json', 'w') as f:
        json.dump({'bos_token_id': 0, 'eos_token_id': [1, 2]}, f)
    tok = tokenizer_lib.load_tokenizer(str(tmp_path))
    assert tok.bos_id == 0
    assert tok.eos_id == 1


def test_load_tokenizer_missing_raises(tmp_path):
    with pytest.raises(FileNotFoundError):
        tokenizer_lib.load_tokenizer(str(tmp_path))


def test_checkpoint_int8_stream_load_matches_post_quantize(debug_ckpt):
    """quantize='int8' streams each kernel through host-side
    quantization during load; the tree must match load-then-
    quantize_params (± 1 quantization step from host/device float
    rounding), with no bf16 kernel ever placed on device."""
    from skypilot_tpu.models import quant

    cfg, model, params, ckpt_dir = debug_ckpt
    want = quant.quantize_params(
        weights.load_llama_params(cfg, ckpt_dir))
    got = weights.load_llama_params(cfg, ckpt_dir, quantize='int8')
    la = jax.tree.leaves_with_path(want)
    lb = jax.tree.leaves_with_path(got)
    assert [p for p, _ in la] == [p for p, _ in lb]
    for (path, a), (_, b) in zip(la, lb):
        a, b = np.asarray(a), np.asarray(b)
        assert a.dtype == b.dtype, path
        if a.dtype == np.int8:
            assert np.abs(a.astype(np.int32) -
                          b.astype(np.int32)).max() <= 1, path
        else:
            np.testing.assert_allclose(a.astype(np.float32),
                                       b.astype(np.float32),
                                       rtol=1e-5, atol=1e-8)


def test_engine_from_checkpoint_int8_serves(debug_ckpt, tmp_path):
    """build_engine(checkpoint=..., quantize='int8'): the stream-
    quantized engine decodes identically to an engine quantized after a
    full-precision load."""
    from skypilot_tpu.infer import server as server_lib
    from skypilot_tpu.models import quant

    cfg, model, params, ckpt_dir = debug_ckpt
    prompt = [5, 17, 3, 99, 42]

    eng_stream = server_lib.build_engine(
        checkpoint=ckpt_dir, num_slots=2, max_seq_len=64,
        quantize='int8')
    eng_stream.start()
    try:
        got = eng_stream.generate(prompt, engine_lib.SamplingParams(
            max_new_tokens=8))
    finally:
        eng_stream.stop()

    import dataclasses as _dc
    qcfg = _dc.replace(eng_stream.cfg)
    qparams = quant.quantize_params(
        weights.load_llama_params(cfg, ckpt_dir))
    qmodel = llama.LlamaModel(qcfg)
    eng_post = engine_lib.InferenceEngine(qmodel, qparams, num_slots=2,
                                          max_seq_len=64)
    eng_post.start()
    try:
        want = eng_post.generate(prompt, engine_lib.SamplingParams(
            max_new_tokens=8))
    finally:
        eng_post.stop()
    assert got == want


# ------------------------------------------------------------- mixtral
@pytest.fixture(scope='module')
def mixtral_ckpt(tmp_path_factory):
    """A debug-size HF-format Mixtral checkpoint."""
    from skypilot_tpu.models import moe

    cfg, moe_cfg = moe.MIXTRAL_CONFIGS['debug-moe']
    cfg = dataclasses.replace(cfg, max_seq_len=64)
    model = moe.MixtralModel(cfg, moe_cfg)
    params = jax.jit(model.init)(jax.random.PRNGKey(11),
                                 jnp.zeros((1, 8), jnp.int32))
    out = tmp_path_factory.mktemp('mixtral_ckpt')
    weights.save_hf_mixtral_checkpoint(cfg, moe_cfg, params, str(out))
    return cfg, moe_cfg, model, params, str(out)


def test_mixtral_roundtrip_save_load(mixtral_ckpt):
    import flax.linen as nn
    cfg, moe_cfg, _, params, ckpt_dir = mixtral_ckpt
    assert weights.checkpoint_model_type(ckpt_dir) == 'mixtral'
    cfg2, moe_cfg2 = weights.load_mixtral_config(
        ckpt_dir, max_seq_len=cfg.max_seq_len, dtype=cfg.dtype,
        param_dtype=cfg.param_dtype,
        use_llama31_rope=cfg.use_llama31_rope, remat=cfg.remat)
    assert moe_cfg2.num_experts == moe_cfg.num_experts
    assert moe_cfg2.experts_per_token == moe_cfg.experts_per_token
    loaded = weights.load_mixtral_params(cfg2, moe_cfg2, ckpt_dir)
    flat_a = jax.tree_util.tree_leaves_with_path(
        nn.meta.unbox(params['params']))
    flat_b = jax.tree_util.tree_leaves_with_path(loaded['params'])
    assert len(flat_a) == len(flat_b)
    for (pa, a), (pb, b) in zip(sorted(flat_a, key=lambda x: str(x[0])),
                                sorted(flat_b, key=lambda x: str(x[0]))):
        assert str(pa) == str(pb)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=0, atol=0, err_msg=str(pa))


def test_mixtral_logits_match_transformers(mixtral_ckpt):
    """Our MoE model on loaded weights == HF MixtralForCausalLM on the
    same checkpoint. Dropless (high capacity) so no tokens drop."""
    torch = pytest.importorskip('torch')
    transformers = pytest.importorskip('transformers')
    from skypilot_tpu.models import moe

    cfg, moe_cfg, _, _, ckpt_dir = mixtral_ckpt
    hf_model = transformers.MixtralForCausalLM.from_pretrained(
        ckpt_dir, torch_dtype=torch.float32)
    hf_model.eval()

    rng = np.random.default_rng(0)
    tokens = rng.integers(0, cfg.vocab_size, (2, 12))
    with torch.no_grad():
        hf_logits = hf_model(torch.tensor(tokens)).logits.numpy()

    dropless = dataclasses.replace(moe_cfg, capacity_factor=8.0)
    model = moe.MixtralModel(cfg, dropless)
    loaded = weights.load_mixtral_params(cfg, dropless, ckpt_dir)
    ours = np.asarray(model.apply(loaded, jnp.asarray(tokens, jnp.int32)))
    np.testing.assert_allclose(ours, hf_logits, rtol=2e-4, atol=2e-4)


def test_engine_from_mixtral_checkpoint_serves(mixtral_ckpt):
    """build_engine auto-detects model_type=mixtral and serves it."""
    from skypilot_tpu.infer import server as server_lib

    cfg, moe_cfg, model, params, ckpt_dir = mixtral_ckpt
    eng = server_lib.build_engine(checkpoint=ckpt_dir, num_slots=2,
                                  max_seq_len=64, dtype='float32')
    eng.start()
    try:
        out = eng.generate([5, 9, 2, 31], engine_lib.SamplingParams(
            max_new_tokens=8))
    finally:
        eng.stop()
    assert len(out) == 8


def test_mixtral_int8_stream_load_matches_post_quantize(mixtral_ckpt):
    """Expert weights stream-quantize on host; router/norms stay float;
    tree matches quantize_params(load(...))."""
    from skypilot_tpu.models import quant

    cfg, moe_cfg, _, _, ckpt_dir = mixtral_ckpt
    want = quant.quantize_params(
        weights.load_mixtral_params(cfg, moe_cfg, ckpt_dir))
    got = weights.load_mixtral_params(cfg, moe_cfg, ckpt_dir,
                                      quantize='int8')
    la = jax.tree.leaves_with_path(want)
    lb = jax.tree.leaves_with_path(got)
    assert [p for p, _ in la] == [p for p, _ in lb]
    n_int8 = 0
    for (path, a), (_, b) in zip(la, lb):
        a, b = np.asarray(a), np.asarray(b)
        assert a.dtype == b.dtype, path
        if a.dtype == np.int8:
            n_int8 += 1
            assert np.abs(a.astype(np.int32) -
                          b.astype(np.int32)).max() <= 1, path
        else:
            np.testing.assert_allclose(a.astype(np.float32),
                                       b.astype(np.float32),
                                       rtol=1e-5, atol=1e-8)
    # 3 expert tensors + lm_head at minimum went int8; router did not.
    assert n_int8 >= 4
    router = got['params']['layers']['moe_mlp']['router']
    assert router.dtype != np.int8


# ------------------------------------------------- model families
# The reference serves Qwen/Gemma by pointing vLLM at the HF checkpoint
# (llm/vllm/serve.yaml, llm/gemma/serve.yaml); here the same LlamaModel
# covers them via config knobs (models/llama.py: attn_bias, mlp_act,
# norm_zero_centered, embed_scale, head_dim_override) and the loader's
# family dispatch (models/weights.py config_from_hf).

def _family_debug_cfg(family):
    base = dataclasses.replace(llama.CONFIGS['debug'], max_seq_len=64)
    if family == 'qwen2':
        return dataclasses.replace(base, attn_bias=True, norm_eps=1e-6,
                                   rope_theta=1e6)
    if family == 'qwen3':
        return dataclasses.replace(base, qk_norm=True, norm_eps=1e-6,
                                   rope_theta=1e6, head_dim_override=32,
                                   tie_embeddings=True)
    if family == 'phi3':
        # Fused-tensor HF layout + a window smaller than the 12-token
        # test prompts.
        return dataclasses.replace(base, hf_layout='phi3',
                                   sliding_window=8, rope_theta=10000.0)
    if family == 'gemma':
        return dataclasses.replace(
            base, mlp_act='gelu_tanh', norm_zero_centered=True,
            embed_scale=True, tie_embeddings=True, head_dim_override=32,
            norm_eps=1e-6, rope_theta=10000.0)
    if family == 'gemma2':
        # Window 8 < the 12-token test prompts and pattern 2, so the
        # sliding/global alternation and both soft-caps are exercised;
        # attn_scale deliberately != head_dim**-0.5.
        return dataclasses.replace(
            base, n_layers=4, mlp_act='gelu_tanh',
            norm_zero_centered=True, embed_scale=True,
            tie_embeddings=True, head_dim_override=16,
            norm_eps=1e-6, rope_theta=10000.0, sliding_window=8,
            window_pattern=2, attn_softcap=30.0, final_softcap=20.0,
            attn_scale=32.0 ** -0.5, sandwich_norms=True)
    raise ValueError(family)


def _random_family_params(cfg, seed=7):
    """init() then randomize the zero-init bias leaves so the parity
    test actually exercises the bias load path."""
    import flax.linen as nn
    model = llama.LlamaModel(cfg)
    params = nn.meta.unbox(
        jax.jit(model.init)(jax.random.PRNGKey(seed),
                            jnp.zeros((1, 8), jnp.int32))['params'])
    rng = np.random.default_rng(seed)

    def bump(path, leaf):
        if path[-1].key == 'bias':
            return np.asarray(rng.normal(0.0, 0.5, leaf.shape),
                              np.float32)
        return leaf
    params = jax.tree_util.tree_map_with_path(bump, params)
    return model, {'params': params}


@pytest.mark.parametrize('family',
                         ['qwen2', 'qwen3', 'gemma', 'gemma2', 'phi3'])
def test_family_logits_match_transformers(family, tmp_path):
    """save -> config round-trip -> load -> logits == transformers'
    family implementation on the same checkpoint."""
    torch = pytest.importorskip('torch')
    transformers = pytest.importorskip('transformers')

    cfg = _family_debug_cfg(family)
    model, variables = _random_family_params(cfg)
    ckpt = tmp_path / family
    weights.save_hf_checkpoint(cfg, variables, str(ckpt))

    # config.json carries the family: load_config must reconstruct the
    # same knobs without being told the model type.
    cfg2 = weights.load_config(str(ckpt), max_seq_len=cfg.max_seq_len,
                               dtype=cfg.dtype,
                               param_dtype=cfg.param_dtype,
                               remat=cfg.remat)
    for field in ('attn_bias', 'mlp_act', 'norm_zero_centered',
                  'embed_scale', 'head_dim', 'tie_embeddings',
                  'sliding_window', 'window_pattern', 'attn_softcap',
                  'final_softcap', 'sandwich_norms'):
        assert getattr(cfg2, field) == getattr(cfg, field), field
    assert abs(cfg2.attn_scale - cfg.attn_scale) < 1e-9

    loaded = weights.load_llama_params(cfg2, str(ckpt))

    # eager attention: HF's sdpa path skips Gemma-2 soft-capping and
    # (on some versions) sliding windows; eager implements both.
    hf_model = transformers.AutoModelForCausalLM.from_pretrained(
        str(ckpt), torch_dtype=torch.float32,
        attn_implementation='eager')
    assert type(hf_model).__name__ == {
        'qwen2': 'Qwen2ForCausalLM', 'qwen3': 'Qwen3ForCausalLM',
        'gemma': 'GemmaForCausalLM', 'gemma2': 'Gemma2ForCausalLM',
        'phi3': 'Phi3ForCausalLM'}[family]
    hf_model.eval()

    rng = np.random.default_rng(3)
    tokens = rng.integers(0, cfg.vocab_size, (2, 12))
    with torch.no_grad():
        hf_logits = hf_model(torch.tensor(tokens)).logits.numpy()
    ours = np.asarray(
        llama.LlamaModel(cfg2).apply(loaded,
                                     jnp.asarray(tokens, jnp.int32)))
    np.testing.assert_allclose(ours, hf_logits, rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize('family',
                         ['qwen2', 'qwen3', 'gemma', 'gemma2', 'phi3'])
def test_family_engine_decode(family, tmp_path):
    """build_engine(checkpoint=<family ckpt>) decodes end-to-end —
    proves the serve path's model-type dispatch, not just logits."""
    from skypilot_tpu.infer import server as server_lib

    cfg = _family_debug_cfg(family)
    _, variables = _random_family_params(cfg)
    ckpt = tmp_path / family
    weights.save_hf_checkpoint(cfg, variables, str(ckpt))

    eng = server_lib.build_engine(checkpoint=str(ckpt), num_slots=2,
                                  max_seq_len=64, dtype='float32')
    eng.start()
    try:
        out = eng.generate([5, 17, 3, 99, 42],
                           engine_lib.SamplingParams(max_new_tokens=8))
    finally:
        eng.stop()
    assert len(out) == 8


def test_qwen2_int8_stream_load_matches_post_quantize(tmp_path):
    """Biased (attn_bias) projection scopes still quantize: kernel ->
    int8 + scale, bias rides along float — stream-load == post-hoc
    quantize_params (the invariant load_llama_params documents)."""
    from skypilot_tpu.models import quant

    cfg = _family_debug_cfg('qwen2')
    _, variables = _random_family_params(cfg)
    ckpt = tmp_path / 'qwen2'
    weights.save_hf_checkpoint(cfg, variables, str(ckpt))

    want = quant.quantize_params(
        weights.load_llama_params(cfg, str(ckpt)))
    got = weights.load_llama_params(cfg, str(ckpt), quantize='int8')
    la = jax.tree.leaves_with_path(want)
    lb = jax.tree.leaves_with_path(got)
    assert [p for p, _ in la] == [p for p, _ in lb]
    n_int8 = 0
    for (path, a), (_, b) in zip(la, lb):
        a, b = np.asarray(a), np.asarray(b)
        assert a.dtype == b.dtype, path
        if a.dtype == np.int8:
            n_int8 += 1
            assert np.abs(a.astype(np.int32) -
                          b.astype(np.int32)).max() <= 1, path
        else:
            np.testing.assert_allclose(a.astype(np.float32),
                                       b.astype(np.float32),
                                       rtol=1e-5, atol=1e-8,
                                       err_msg=str(path))
    # All 7 scan-stacked projections (wq/wk/wv/wo + gate/up/down) plus
    # lm_head went int8 despite the q/k/v biases in the same scopes.
    assert n_int8 == 8


def test_mistral_checkpoint_dispatch(tmp_path):
    """model_type=mistral loads through the llama path with
    sliding-window attention: logits match transformers'
    MistralForCausalLM on prompts LONGER than the window (the windowed
    mask is the only difference from llama)."""
    torch = pytest.importorskip('torch')
    transformers = pytest.importorskip('transformers')

    cfg = dataclasses.replace(llama.CONFIGS['debug'], max_seq_len=64,
                              norm_eps=1e-6, rope_theta=10000.0)
    model = llama.LlamaModel(cfg)
    params = jax.jit(model.init)(jax.random.PRNGKey(5),
                                 jnp.zeros((1, 8), jnp.int32))
    weights.save_hf_checkpoint(cfg, params, str(tmp_path))
    # Rewrite the config as a Mistral checkpoint with a sliding window
    # SMALLER than the test prompt so the window actually bites.
    cfg_path = tmp_path / 'config.json'
    hf_cfg = json.loads(cfg_path.read_text())
    hf_cfg.update(model_type='mistral',
                  architectures=['MistralForCausalLM'],
                  sliding_window=8)
    cfg_path.write_text(json.dumps(hf_cfg))

    cfg2 = weights.load_config(str(tmp_path), dtype=cfg.dtype,
                               param_dtype=cfg.param_dtype,
                               remat=False)
    assert cfg2.sliding_window == 8
    assert cfg2.max_seq_len == 64   # no clamp: the window is real now
    loaded = weights.load_llama_params(cfg2, str(tmp_path))

    hf_model = transformers.AutoModelForCausalLM.from_pretrained(
        str(tmp_path), torch_dtype=torch.float32,
        attn_implementation='eager')
    assert type(hf_model).__name__ == 'MistralForCausalLM'
    hf_model.eval()
    tokens = np.random.default_rng(4).integers(0, cfg.vocab_size,
                                               (2, 12))
    with torch.no_grad():
        hf_logits = hf_model(torch.tensor(tokens)).logits.numpy()
    ours = np.asarray(
        llama.LlamaModel(cfg2).apply(loaded,
                                     jnp.asarray(tokens, jnp.int32)))
    np.testing.assert_allclose(ours, hf_logits, rtol=2e-4, atol=2e-4)
    # Sanity that the window changed the math vs no-window weights.
    plain = np.asarray(model.apply(params,
                                   jnp.asarray(tokens, jnp.int32)))
    assert np.abs(plain - ours).max() > 1e-3


def test_windowed_engine_decode_matches_full_forward(tmp_path):
    """Gemma-2-style incremental decode (windowed + soft-capped cached
    attention, alternating layers) == greedy rollout by full forward
    recompute — the cache path's window mask is position-exact."""
    cfg = _family_debug_cfg('gemma2')
    _, variables = _random_family_params(cfg)
    ckpt = tmp_path / 'g2'
    weights.save_hf_checkpoint(cfg, variables, str(ckpt))
    cfg2 = weights.load_config(str(ckpt), max_seq_len=64,
                               dtype=cfg.dtype,
                               param_dtype=cfg.param_dtype, remat=False)
    loaded = weights.load_llama_params(cfg2, str(ckpt))
    model = llama.LlamaModel(cfg2)

    prompt = list(np.random.default_rng(6).integers(
        1, cfg.vocab_size, 12))          # longer than the 8-token window
    toks = [int(t) for t in prompt]
    for _ in range(6):
        logits = model.apply(loaded, jnp.asarray([toks], jnp.int32))
        toks.append(int(np.argmax(np.asarray(logits[0, -1]))))
    want = toks[len(prompt):]

    eng = engine_lib.InferenceEngine(model, loaded, num_slots=2,
                                     max_seq_len=64,
                                     prefill_buckets=[16],
                                     cache_mode='paged', page_size=16)
    eng.start()
    try:
        got = eng.generate([int(t) for t in prompt],
                           engine_lib.SamplingParams(max_new_tokens=6))
    finally:
        eng.stop()
    assert got == want


def test_gemma2_tp_sharded_decode_matches_unsharded(tmp_path):
    """The windowed/soft-capped family under tp=2: the traced
    layer-index window gating and the masked XLA decode path hold up
    under GSPMD sharding (token-exact vs the unsharded engine)."""
    cfg = _family_debug_cfg('gemma2')
    _, variables = _random_family_params(cfg)
    ckpt = tmp_path / 'g2'
    weights.save_hf_checkpoint(cfg, variables, str(ckpt))
    cfg2 = weights.load_config(str(ckpt), max_seq_len=64,
                               dtype=cfg.dtype,
                               param_dtype=cfg.param_dtype, remat=False)
    model = llama.LlamaModel(cfg2)
    prompt = list(range(1, 13))   # > the 8-token window

    def run(mesh):
        loaded = weights.load_llama_params(cfg2, str(ckpt), mesh=mesh)
        eng = engine_lib.InferenceEngine(model, loaded, num_slots=2,
                                         max_seq_len=64,
                                         prefill_buckets=[16],
                                         cache_mode='paged',
                                         page_size=16, mesh=mesh)
        eng.start()
        try:
            return eng.generate(prompt, engine_lib.SamplingParams(
                max_new_tokens=6))
        finally:
            eng.stop()

    want = run(None)
    got = run(mesh_lib.build_mesh(mesh_lib.MeshSpec(tp=2)))
    assert got == want


# ------------------------------------------------------ qwen3_moe
def test_qwen3_moe_logits_and_engine(tmp_path):
    """Qwen3-MoE (qk-norm attention + llama-named expert tensors under
    mlp.experts): our MixtralModel on a saved qwen3_moe checkpoint
    matches transformers' Qwen3MoeForCausalLM, and build_engine
    dispatches it."""
    import dataclasses as _dc

    torch = pytest.importorskip('torch')
    transformers = pytest.importorskip('transformers')

    from skypilot_tpu.infer import server as server_lib
    from skypilot_tpu.models import moe

    cfg, moe_cfg = moe.MIXTRAL_CONFIGS['debug-moe']
    cfg = _dc.replace(cfg, max_seq_len=64, qk_norm=True,
                      head_dim_override=32, norm_eps=1e-6,
                      rope_theta=1e6)
    # Dropless so the capacity-based routing equals exact top-k.
    moe_cfg = _dc.replace(moe_cfg, capacity_factor=8.0)
    model = moe.MixtralModel(cfg, moe_cfg)
    params = jax.jit(model.init)(jax.random.PRNGKey(13),
                                 jnp.zeros((1, 8), jnp.int32))
    ckpt = tmp_path / 'q3moe'
    weights.save_hf_mixtral_checkpoint(cfg, moe_cfg, params, str(ckpt))
    assert weights.checkpoint_model_type(str(ckpt)) == 'qwen3_moe'

    cfg2, moe_cfg2 = weights.load_mixtral_config(
        str(ckpt), max_seq_len=cfg.max_seq_len, dtype=cfg.dtype,
        param_dtype=cfg.param_dtype, remat=cfg.remat)
    assert cfg2.qk_norm and cfg2.mlp_dim == cfg.mlp_dim
    moe_cfg2 = _dc.replace(moe_cfg2, capacity_factor=8.0)
    loaded = weights.load_mixtral_params(cfg2, moe_cfg2, str(ckpt))

    hf_model = transformers.AutoModelForCausalLM.from_pretrained(
        str(ckpt), torch_dtype=torch.float32,
        attn_implementation='eager')
    assert type(hf_model).__name__ == 'Qwen3MoeForCausalLM'
    hf_model.eval()
    tokens = np.random.default_rng(9).integers(0, cfg.vocab_size,
                                               (2, 12))
    with torch.no_grad():
        hf_logits = hf_model(torch.tensor(tokens)).logits.numpy()
    ours = np.asarray(moe.MixtralModel(cfg2, moe_cfg2).apply(
        loaded, jnp.asarray(tokens, jnp.int32)))
    np.testing.assert_allclose(ours, hf_logits, rtol=2e-4, atol=2e-4)

    eng = server_lib.build_engine(checkpoint=str(ckpt), num_slots=2,
                                  max_seq_len=64, dtype='float32')
    eng.start()
    try:
        out = eng.generate([5, 9, 2, 31],
                           engine_lib.SamplingParams(max_new_tokens=6))
    finally:
        eng.stop()
    assert len(out) == 6


def test_qwen3_moe_with_attention_bias_roundtrips(tmp_path):
    """attention_bias=true on a MoE config loads/saves its bias
    tensors (no released qwen3_moe uses it, but config_from_hf honors
    the field, so the loader must too rather than fail opaquely)."""
    import dataclasses as _dc

    from skypilot_tpu.models import moe

    cfg, moe_cfg = moe.MIXTRAL_CONFIGS['debug-moe']
    cfg = _dc.replace(cfg, max_seq_len=64, qk_norm=True, attn_bias=True,
                      head_dim_override=32, norm_eps=1e-6)
    moe_cfg = _dc.replace(moe_cfg, capacity_factor=8.0)
    model = moe.MixtralModel(cfg, moe_cfg)
    params = jax.jit(model.init)(jax.random.PRNGKey(17),
                                 jnp.zeros((1, 8), jnp.int32))
    # Randomize the zero-init biases: a dropped bias tensor must CHANGE
    # the outputs, or this roundtrip proves nothing.
    import flax.linen as nn
    rng = np.random.default_rng(17)
    params = {'params': jax.tree_util.tree_map_with_path(
        lambda p, a: (jnp.asarray(rng.normal(0, 0.5, a.shape),
                                  a.dtype)
                      if p[-1].key == 'bias' else a),
        nn.meta.unbox(params['params']))}
    ckpt = tmp_path / 'biased'
    weights.save_hf_mixtral_checkpoint(cfg, moe_cfg, params, str(ckpt))
    cfg2, moe_cfg2 = weights.load_mixtral_config(
        str(ckpt), max_seq_len=64, dtype=cfg.dtype,
        param_dtype=cfg.param_dtype, remat=cfg.remat)
    assert cfg2.attn_bias
    moe_cfg2 = _dc.replace(moe_cfg2, capacity_factor=8.0)
    loaded = weights.load_mixtral_params(cfg2, moe_cfg2, str(ckpt))
    toks = jnp.asarray([[3, 1, 4, 1, 5]], jnp.int32)
    import flax.linen as nn
    a = np.asarray(model.apply(params, toks))
    b = np.asarray(moe.MixtralModel(cfg2, moe_cfg2).apply(loaded, toks))
    np.testing.assert_allclose(a, b, rtol=2e-5, atol=2e-5)
