"""Checkpoint I/O for the Llama family: HF safetensors <-> flax params.

The reference serves real checkpoints by pointing vLLM at a HF model dir
(llm/vllm/serve.yaml `--model meta-llama/...`); the TPU-native equivalent
is a direct safetensors -> sharded-jax-array loader:

  * reads the standard HF Llama layout (model.safetensors[.index.json] +
    config.json) without importing torch/transformers;
  * transposes HF [out, in] weights into flax Dense [in, out] kernels and
    stacks per-layer tensors along a leading axis when the model scans
    layers (models/llama.py nn.scan);
  * when a mesh is given, every leaf is device_put with the NamedSharding
    derived from the model's logical axis annotations (parallel/
    sharding.py) — params land tp/fsdp-sharded without ever
    materializing a full replica per device (required at 70B scale).

RoPE note: our apply_rope uses the split-half convention (ops/rope.py),
which is exactly the HF Llama layout — q/k projections load with no
permutation.
"""
import json
import os
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from skypilot_tpu.parallel import sharding as sharding_lib
from skypilot_tpu.utils import log_utils

logger = log_utils.init_logger(__name__)

# (our leaf under a layer) -> (HF suffix, transpose?)
_LAYER_MAP = {
    ('attn_norm', 'weight'): ('input_layernorm.weight', False),
    ('attn', 'wq', 'kernel'): ('self_attn.q_proj.weight', True),
    ('attn', 'wk', 'kernel'): ('self_attn.k_proj.weight', True),
    ('attn', 'wv', 'kernel'): ('self_attn.v_proj.weight', True),
    ('attn', 'wo', 'kernel'): ('self_attn.o_proj.weight', True),
    ('mlp_norm', 'weight'): ('post_attention_layernorm.weight', False),
    ('mlp', 'w_gate', 'kernel'): ('mlp.gate_proj.weight', True),
    ('mlp', 'w_up', 'kernel'): ('mlp.up_proj.weight', True),
    ('mlp', 'w_down', 'kernel'): ('mlp.down_proj.weight', True),
}

# Qwen2-family checkpoints add biases on the q/k/v projections only
# (HF Qwen2Attention); merged into the layer map when cfg.attn_bias.
_ATTN_BIAS_MAP = {
    ('attn', 'wq', 'bias'): ('self_attn.q_proj.bias', False),
    ('attn', 'wk', 'bias'): ('self_attn.k_proj.bias', False),
    ('attn', 'wv', 'bias'): ('self_attn.v_proj.bias', False),
}

_TOP_MAP = {
    ('tok_embed',): ('model.embed_tokens.weight', False),
    ('final_norm', 'weight'): ('model.norm.weight', False),
    ('lm_head', 'kernel'): ('lm_head.weight', True),
}


# Qwen3(+MoE) per-head q/k norms ([head_dim] weights) — shared by the
# dense layer map, the MoE loader and the MoE saver.
_QK_NORM_MAP = {
    ('attn', 'q_norm', 'weight'): ('self_attn.q_norm.weight', False),
    ('attn', 'k_norm', 'weight'): ('self_attn.k_norm.weight', False),
}


def _layer_map(cfg) -> Dict[tuple, tuple]:
    m = dict(_LAYER_MAP)
    if getattr(cfg, 'attn_bias', False):
        m.update(_ATTN_BIAS_MAP)
    if getattr(cfg, 'qk_norm', False):
        m.update(_QK_NORM_MAP)
    if getattr(cfg, 'sandwich_norms', False):
        # Gemma-2 names its four per-layer norms differently: HF
        # 'post_attention_layernorm' is the POST-attention sandwich
        # norm (for llama it is the MLP pre-norm), and the MLP gets
        # pre/post 'feedforward' norms.
        m[('attn_post_norm', 'weight')] = \
            ('post_attention_layernorm.weight', False)
        m[('mlp_norm', 'weight')] = \
            ('pre_feedforward_layernorm.weight', False)
        m[('mlp_post_norm', 'weight')] = \
            ('post_feedforward_layernorm.weight', False)
    return m


class _ShardReader:
    """Random access over a sharded/unsharded safetensors checkpoint."""

    def __init__(self, ckpt_dir: str) -> None:
        import safetensors  # local import: serving-path dependency

        self._safe_open = safetensors.safe_open
        self.ckpt_dir = ckpt_dir
        index = os.path.join(ckpt_dir, 'model.safetensors.index.json')
        self._weight_map: Dict[str, str] = {}
        if os.path.exists(index):
            with open(index, encoding='utf-8') as f:
                self._weight_map = json.load(f)['weight_map']
        else:
            files = sorted(f for f in os.listdir(ckpt_dir)
                           if f.endswith('.safetensors'))
            if not files:
                raise FileNotFoundError(
                    f'no *.safetensors under {ckpt_dir}')
            for fname in files:
                with self._safe_open(os.path.join(ckpt_dir, fname),
                                     framework='np') as f:
                    for key in f.keys():
                        self._weight_map[key] = fname
        self._handles: Dict[str, Any] = {}

    def __contains__(self, name: str) -> bool:
        return name in self._weight_map

    def _handle(self, name: str):
        fname = self._weight_map[name]
        if fname not in self._handles:
            self._handles[fname] = self._safe_open(
                os.path.join(self.ckpt_dir, fname), framework='np')
        return self._handles[fname]

    def get(self, name: str) -> np.ndarray:
        return self._handle(name).get_tensor(name)

    def get_rows(self, name: str, start: int, stop: int) -> np.ndarray:
        """Read only rows [start, stop) of a tensor — safetensors
        slices straight from the mmap, so splitting a fused tensor
        (phi3 qkv_proj) never materializes the unneeded rows."""
        return self._handle(name).get_slice(name)[start:stop]


class _FusedSplitView:
    """Reader adapter for hf_layout='phi3': q/k/v_proj rows are
    slices of self_attn.qkv_proj (q, then k, then v) and gate/up_proj
    rows are halves of mlp.gate_up_proj — the loader keeps speaking
    the per-tensor llama names."""

    _RE = None

    def __init__(self, reader, cfg) -> None:
        import re
        self._r = reader
        self._cfg = cfg
        if _FusedSplitView._RE is None:
            _FusedSplitView._RE = re.compile(
                r'(model\.layers\.\d+\.)'
                r'(?:self_attn\.(q|k|v)_proj|mlp\.(gate|up)_proj)'
                r'\.weight$')

    def __contains__(self, name: str) -> bool:
        m = self._RE.match(name)
        if m is None:
            return name in self._r
        if m.group(2):
            return m.group(1) + 'self_attn.qkv_proj.weight' in self._r
        return m.group(1) + 'mlp.gate_up_proj.weight' in self._r

    def get(self, name: str) -> np.ndarray:
        m = self._RE.match(name)
        if m is None:
            return self._r.get(name)
        cfg = self._cfg
        if m.group(2):
            fused_name = m.group(1) + 'self_attn.qkv_proj.weight'
            q_rows = cfg.n_heads * cfg.head_dim
            kv_rows = cfg.n_kv_heads * cfg.head_dim
            bounds = {'q': (0, q_rows),
                      'k': (q_rows, q_rows + kv_rows),
                      'v': (q_rows + kv_rows, q_rows + 2 * kv_rows)}
            lo, hi = bounds[m.group(2)]
        else:
            fused_name = m.group(1) + 'mlp.gate_up_proj.weight'
            lo, hi = ((0, cfg.mlp_dim) if m.group(3) == 'gate'
                      else (cfg.mlp_dim, 2 * cfg.mlp_dim))
        # Row-sliced read: only the requested projection's rows leave
        # the mmap — the loader iterates suffix-major (all layers' wq,
        # then wk, ...), so whole-tensor reads would be paid 3x for
        # qkv and 2x for gate_up.
        return self._r.get_rows(fused_name, lo, hi)


def _np_cast(arr: np.ndarray, dtype) -> np.ndarray:
    # bfloat16 safetensors arrive as ml_dtypes bfloat16 numpy arrays;
    # astype handles both directions.
    return arr.astype(dtype) if arr.dtype != dtype else arr


def _np_quantize_kernel(arr: np.ndarray) -> 'tuple[np.ndarray, np.ndarray]':
    """Host-side mirror of models/quant.py _quantize_kernel (same
    per-output-channel symmetric scheme, numpy so the full-precision
    tensor never reaches the device)."""
    wf = arr.astype(np.float32)
    amax = np.max(np.abs(wf), axis=-2)
    scale = np.where(amax > 0, amax / 127.0, 1.0).astype(np.float32)
    q = np.clip(np.round(wf / scale[..., None, :]), -127,
                127).astype(np.int8)
    return q, scale


def _np_quantize_kernel_int4(
        arr: np.ndarray) -> 'tuple[np.ndarray, np.ndarray]':
    """Host-side mirror of models/quant.py _quantize_kernel_int4
    (group-wise G=128 along `in`, symmetric ±7)."""
    import ml_dtypes

    from skypilot_tpu.models import quant as quant_lib
    *lead, din, dout = arr.shape
    g = quant_lib.int4_group_size(din)
    n_g = din // g
    wf = arr.astype(np.float32).reshape(*lead, n_g, g, dout)
    amax = np.max(np.abs(wf), axis=-2)
    scale = np.where(amax > 0, amax / 7.0, 1.0).astype(np.float32)
    q = np.clip(np.round(wf / scale[..., None, :]), -7, 7)
    q = q.astype(ml_dtypes.int4).reshape(*lead, din, dout)
    return q, scale


def _resolve_dtype(cfg, param_dtype: Optional[str]):
    target = param_dtype or cfg.param_dtype
    if target == 'bfloat16':
        import ml_dtypes
        return np.dtype(ml_dtypes.bfloat16)
    return np.dtype(target)


def _make_store(params: Dict[str, Any], put, quantize: str, dtype):
    """The shared cast/quantize-and-place closure both loaders use.

    int8 mode splits projection kernels (path leaf 'kernel', ndim >= 2 —
    the same scopes models/quant.quantize_params converts) into int8 q +
    f32 scale ON HOST; expert_weight=True uses the MoeMLP sibling-key
    convention ('<name>' + '<name>_scale')."""
    def store(path: tuple, arr: np.ndarray, expert_weight=False):
        if quantize in ('int8', 'int4') and \
                (expert_weight or (path[-1] == 'kernel'
                                   and arr.ndim >= 2)):
            if quantize == 'int4':
                if expert_weight:
                    raise NotImplementedError(
                        'int4 is llama-family only; MoE expert '
                        'weights support int8')
                q, scale = _np_quantize_kernel_int4(arr)
            else:
                q, scale = _np_quantize_kernel(arr)
            spath = (path[:-1] + (f'{path[-1]}_scale',) if expert_weight
                     else path[:-1] + ('scale',))
            _set_at(params, path, put(path, q))
            _set_at(params, spath, put(spath, scale))
            return
        _set_at(params, path, put(path, _np_cast(arr, dtype)))
    return store


def load_llama_params(cfg, ckpt_dir: str, *,
                      mesh=None,
                      rules=sharding_lib.DEFAULT_RULES,
                      param_dtype: Optional[str] = None,
                      quantize: str = 'none') -> Dict[str, Any]:
    """HF Llama checkpoint dir -> {'params': ...} for models/llama.py.

    cfg: LlamaConfig matching the checkpoint shapes. mesh: optional
    jax.sharding.Mesh — leaves are placed with their logical shardings
    (tp/fsdp per parallel/sharding.py DEFAULT_RULES).

    quantize='int8': each projection kernel is quantized ON HOST as it
    streams out of the safetensors shards, so only int8 (+ scale) ever
    reaches the device — the full bf16 tree (2x the bytes) is never
    resident in HBM. This is what lets an 8B checkpoint load onto a
    single 16GB chip. The emitted tree matches what
    models/quant.quantize_params produces (projection scopes gain
    int8 kernel + f32 scale; embeddings/norms stay float).
    """
    from skypilot_tpu.models import llama as llama_lib

    if quantize not in ('none', 'int8', 'int4'):
        raise ValueError(f'unknown quantize mode {quantize!r}')
    dtype = _resolve_dtype(cfg, param_dtype)

    reader = _ShardReader(ckpt_dir)
    if getattr(cfg, 'hf_layout', 'llama') == 'phi3':
        reader = _FusedSplitView(reader, cfg)
    shardings = None
    if mesh is not None:
        import dataclasses as _dc
        scfg = cfg if quantize == 'none' \
            else _dc.replace(cfg, quant=quantize)
        model = llama_lib.LlamaModel(scfg)
        shardings = param_shardings(model, scfg, mesh, rules)

    def put(path: tuple, arr: np.ndarray):
        if shardings is not None:
            return jax.device_put(arr, _leaf_at(shardings, path))
        return jnp.asarray(arr)

    params: Dict[str, Any] = {}
    store = _make_store(params, put, quantize, dtype)

    def assemble(path: tuple, hf_name: str, transpose: bool):
        arr = reader.get(hf_name)
        if transpose:
            arr = arr.T
        store(path, arr)

    for path, (hf_name, transpose) in _TOP_MAP.items():
        if path == ('lm_head', 'kernel'):
            if cfg.tie_embeddings:
                continue
            if hf_name not in reader:
                # Tied checkpoint loaded into an untied config: reuse the
                # embedding, transposed.
                store(path, reader.get('model.embed_tokens.weight').T)
                logger.info('lm_head tied to embeddings in checkpoint')
                continue
        assemble(path, hf_name, transpose)

    for path, (suffix, transpose) in _layer_map(cfg).items():
        if cfg.scan_layers:
            per_layer = [
                reader.get(f'model.layers.{i}.{suffix}')
                for i in range(cfg.n_layers)]
            arr = np.stack([a.T if transpose else a for a in per_layer])
            store(('layers',) + path, arr)
        else:
            for i in range(cfg.n_layers):
                arr = reader.get(f'model.layers.{i}.{suffix}')
                if transpose:
                    arr = arr.T
                store((f'layer_{i}',) + path, arr)

    logger.info('loaded %d-layer llama params from %s (sharded=%s, '
                'quantize=%s)', cfg.n_layers, ckpt_dir,
                mesh is not None, quantize)
    return {'params': params}


# HF Mixtral layout: llama attention + per-expert MLPs under
# block_sparse_moe (experts.{e}.w1/w3/w2 = gate/up/down, gate = router).
_MOE_ATTN_MAP = {
    ('attn_norm', 'weight'): ('input_layernorm.weight', False),
    ('attn', 'wq', 'kernel'): ('self_attn.q_proj.weight', True),
    ('attn', 'wk', 'kernel'): ('self_attn.k_proj.weight', True),
    ('attn', 'wv', 'kernel'): ('self_attn.v_proj.weight', True),
    ('attn', 'wo', 'kernel'): ('self_attn.o_proj.weight', True),
    ('mlp_norm', 'weight'): ('post_attention_layernorm.weight', False),
}
# Per-model_type MoE tensor naming: mixtral nests experts under
# block_sparse_moe with w1/w3/w2; qwen3_moe uses llama-style names
# under mlp. The math (softmax -> top-k -> renormalize) is identical.
_MOE_SCHEMES = {
    'mixtral': {'prefix': 'block_sparse_moe',
                # ours [dim, mlp] <-> HF [mlp, dim] (w1=gate, w3=up,
                # w2=down)
                'experts': {'w_gate': 'w1', 'w_up': 'w3',
                            'w_down': 'w2'}},
    'qwen3_moe': {'prefix': 'mlp',
                  'experts': {'w_gate': 'gate_proj', 'w_up': 'up_proj',
                              'w_down': 'down_proj'}},
}


def checkpoint_model_type(ckpt_dir: str) -> str:
    """'llama' | 'mixtral' | ... from the checkpoint's config.json."""
    with open(os.path.join(ckpt_dir, 'config.json'),
              encoding='utf-8') as f:
        return json.load(f).get('model_type', 'llama')


def load_mixtral_config(ckpt_dir: str, **overrides):
    """config.json -> (LlamaConfig, MoeConfig) for models/moe.py.
    Handles mixtral AND qwen3_moe (qk-norm attention, experts sized by
    moe_intermediate_size)."""
    from skypilot_tpu.models import moe as moe_lib

    with open(os.path.join(ckpt_dir, 'config.json'),
              encoding='utf-8') as f:
        hf = json.load(f)
    if hf.get('model_type') == 'qwen3_moe':
        # Our routing renormalizes the top-k weights (the convention
        # every released Qwen3-MoE uses); a checkpoint trained without
        # it would silently mis-scale expert outputs.
        if not hf.get('norm_topk_prob', False):
            raise NotImplementedError(
                'qwen3_moe with norm_topk_prob=false is not supported')
        if hf.get('decoder_sparse_step', 1) != 1 or \
                hf.get('mlp_only_layers'):
            raise NotImplementedError(
                'qwen3_moe with dense layers interleaved '
                '(decoder_sparse_step/mlp_only_layers) is not '
                'supported — every layer must be MoE')
        # Experts are sized by moe_intermediate_size, not the dense
        # intermediate_size.
        overrides.setdefault('mlp_dim', hf['moe_intermediate_size'])
    cfg = config_from_hf(hf, **overrides)
    moe_cfg = moe_lib.MoeConfig(
        num_experts=hf.get('num_experts',
                           hf.get('num_local_experts', 8)),
        experts_per_token=hf.get('num_experts_per_tok', 2))
    return cfg, moe_cfg


def load_mixtral_params(cfg, moe_cfg, ckpt_dir: str, *,
                        mesh=None,
                        rules=sharding_lib.DEFAULT_RULES,
                        param_dtype: Optional[str] = None,
                        quantize: str = 'none') -> Dict[str, Any]:
    """HF Mixtral checkpoint dir -> {'params': ...} for MixtralModel.

    Reference analog: the reference serves Mixtral through vLLM
    (llm/mixtral/serve.yaml); here the expert weights load straight
    into the scan-stacked [L, E, in, out] einsum tensors of
    models/moe.py. quantize='int8' stream-quantizes expert weights on
    host (router + norms stay float, matching quantize_params).
    """
    from skypilot_tpu.models import moe as moe_lib

    if quantize == 'int4':
        raise NotImplementedError(
            'int4 is llama-family only; MoE expert weights support int8')
    if quantize not in ('none', 'int8'):
        raise ValueError(f'unknown quantize mode {quantize!r}')
    dtype = _resolve_dtype(cfg, param_dtype)

    reader = _ShardReader(ckpt_dir)
    shardings = None
    if mesh is not None:
        import dataclasses as _dc
        scfg = cfg if quantize == 'none' \
            else _dc.replace(cfg, quant=quantize)
        model = moe_lib.MixtralModel(scfg, moe_cfg)
        shardings = param_shardings(model, scfg, mesh, rules)

    def put(path: tuple, arr: np.ndarray):
        if shardings is not None:
            return jax.device_put(arr, _leaf_at(shardings, path))
        return jnp.asarray(arr)

    params: Dict[str, Any] = {}
    store = _make_store(params, put, quantize, dtype)

    for path, (hf_name, transpose) in _TOP_MAP.items():
        if path == ('lm_head', 'kernel') and cfg.tie_embeddings:
            continue
        arr = reader.get(hf_name)
        store(path, arr.T if transpose else arr)

    L, E = cfg.n_layers, moe_cfg.num_experts
    assert cfg.scan_layers, 'MixtralModel is scan-stacked'
    scheme = _MOE_SCHEMES[checkpoint_model_type(ckpt_dir)]
    moe_prefix, expert_names = scheme['prefix'], scheme['experts']
    attn_map = dict(_MOE_ATTN_MAP)
    if getattr(cfg, 'qk_norm', False):   # qwen3_moe attention norms
        attn_map.update(_QK_NORM_MAP)
    if getattr(cfg, 'attn_bias', False):
        attn_map.update(_ATTN_BIAS_MAP)
    for path, (suffix, transpose) in attn_map.items():
        per_layer = [reader.get(f'model.layers.{i}.{suffix}')
                     for i in range(L)]
        arr = np.stack([a.T if transpose else a for a in per_layer])
        store(('layers',) + path, arr)
    # Router: [L, dim, E] (HF gate.weight is [E, dim]); stays float.
    router = np.stack([
        reader.get(f'model.layers.{i}.{moe_prefix}.gate.weight').T
        for i in range(L)])
    _set_at(params, ('layers', 'moe_mlp', 'router'),
            put(('layers', 'moe_mlp', 'router'),
                _np_cast(router, dtype)))
    # Experts: [L, E, in, out]. Work per LAYER so host peak stays at
    # one layer's experts in full precision (~1GB at 8x7B): int8 mode
    # quantizes each layer as it streams (the stacked result is int8,
    # ~1/2 the bytes); float mode casts each layer to the target dtype
    # before stacking (never inflates bf16 shards to f32).
    for ours, hf_w in expert_names.items():
        epath = ('layers', 'moe_mlp', ours)
        if quantize == 'int8':
            qs, scales = [], []
            for i in range(L):
                layer = np.stack([reader.get(
                    f'model.layers.{i}.{moe_prefix}.experts.{e}'
                    f'.{hf_w}.weight').T for e in range(E)])
                q, s = _np_quantize_kernel(layer)
                qs.append(q)
                scales.append(s)
            _set_at(params, epath, put(epath, np.stack(qs)))
            spath = epath[:-1] + (f'{ours}_scale',)
            _set_at(params, spath, put(spath, np.stack(scales)))
        else:
            stacked = np.stack([
                np.stack([_np_cast(reader.get(
                    f'model.layers.{i}.{moe_prefix}.experts.{e}'
                    f'.{hf_w}.weight').T, dtype) for e in range(E)])
                for i in range(L)])
            _set_at(params, epath, put(epath, stacked))

    logger.info('loaded %d-layer %d-expert mixtral params from %s '
                '(sharded=%s, quantize=%s)', L, E, ckpt_dir,
                mesh is not None, quantize)
    return {'params': params}


def save_hf_mixtral_checkpoint(cfg, moe_cfg, variables: Dict[str, Any],
                               out_dir: str) -> None:
    """Inverse of load_mixtral_params (export + loader round-trip
    tests)."""
    import flax.linen as nn
    import safetensors.numpy

    params = nn.meta.unbox(variables['params'])
    os.makedirs(out_dir, exist_ok=True)
    out: Dict[str, np.ndarray] = {}

    def grab(path: tuple) -> Optional[np.ndarray]:
        leaf = _get_at(params, path)
        return None if leaf is None else np.asarray(jax.device_get(leaf))

    for path, (hf_name, transpose) in _TOP_MAP.items():
        arr = grab(path)
        if arr is None:
            continue
        out[hf_name] = arr.T if transpose else arr
    attn_map = dict(_MOE_ATTN_MAP)
    if getattr(cfg, 'attn_bias', False):
        attn_map.update(_ATTN_BIAS_MAP)
    for path, (suffix, transpose) in attn_map.items():
        stacked = grab(('layers',) + path)
        for i in range(cfg.n_layers):
            arr = stacked[i]
            out[f'model.layers.{i}.{suffix}'] = arr.T if transpose else arr
    moe_type = 'qwen3_moe' if getattr(cfg, 'qk_norm', False) \
        else 'mixtral'
    scheme = _MOE_SCHEMES[moe_type]
    moe_prefix = scheme['prefix']
    if moe_type == 'qwen3_moe':
        for path, (suffix, _t) in _QK_NORM_MAP.items():
            stacked = grab(('layers',) + path)
            for i in range(cfg.n_layers):
                out[f'model.layers.{i}.{suffix}'] = stacked[i]
    router = grab(('layers', 'moe_mlp', 'router'))
    for i in range(cfg.n_layers):
        out[f'model.layers.{i}.{moe_prefix}.gate.weight'] = \
            router[i].T
    for ours, hf_w in scheme['experts'].items():
        stacked = grab(('layers', 'moe_mlp', ours))
        for i in range(cfg.n_layers):
            for e in range(moe_cfg.num_experts):
                out[f'model.layers.{i}.{moe_prefix}.experts.{e}'
                    f'.{hf_w}.weight'] = stacked[i, e].T

    out = {k: np.ascontiguousarray(v) for k, v in out.items()}
    safetensors.numpy.save_file(
        out, os.path.join(out_dir, 'model.safetensors'))
    hf = config_to_hf(cfg)
    if moe_type == 'qwen3_moe':
        hf.update({'architectures': ['Qwen3MoeForCausalLM'],
                   'model_type': 'qwen3_moe',
                   'num_experts': moe_cfg.num_experts,
                   'num_experts_per_tok': moe_cfg.experts_per_token,
                   'moe_intermediate_size': cfg.mlp_dim,
                   'norm_topk_prob': True,
                   'decoder_sparse_step': 1,
                   'mlp_only_layers': []})
    else:
        hf.update({'architectures': ['MixtralForCausalLM'],
                   'model_type': 'mixtral',
                   'num_local_experts': moe_cfg.num_experts,
                   'num_experts_per_tok': moe_cfg.experts_per_token})
    with open(os.path.join(out_dir, 'config.json'), 'w',
              encoding='utf-8') as f:
        json.dump(hf, f, indent=2)


def load_checkpoint(ckpt_dir: str, *, mesh=None,
                    quantize: str = 'none',
                    param_dtype: Optional[str] = None,
                    **config_overrides):
    """Family-dispatching loader: (cfg, moe_cfg_or_None, model, params).

    The one place that routes a checkpoint dir to the right config/
    loader/model constructor (llama vs mixtral) — sft --base-checkpoint,
    export_lora, and any future tool share it instead of copying the
    routing."""
    from skypilot_tpu.models import llama as llama_lib

    if checkpoint_model_type(ckpt_dir) in ('mixtral', 'qwen3_moe'):
        from skypilot_tpu.models import moe as moe_lib
        cfg, moe_cfg = load_mixtral_config(ckpt_dir, **config_overrides)
        model = moe_lib.MixtralModel(cfg, moe_cfg)
        params = load_mixtral_params(cfg, moe_cfg, ckpt_dir, mesh=mesh,
                                     quantize=quantize,
                                     param_dtype=param_dtype)
        return cfg, moe_cfg, model, params
    cfg = load_config(ckpt_dir, **config_overrides)
    model = llama_lib.LlamaModel(cfg)
    params = load_llama_params(cfg, ckpt_dir, mesh=mesh,
                               quantize=quantize, param_dtype=param_dtype)
    return cfg, None, model, params


def save_hf_checkpoint(cfg, variables: Dict[str, Any],
                       out_dir: str) -> None:
    """Inverse of load_llama_params: write our params as an HF-format
    safetensors checkpoint (single shard) + config.json. Used for export
    and for loader round-trip tests.

    A mapped tensor absent from the params tree is only skipped
    SILENTLY when the config knob explains it (tie_embeddings => no
    lm_head leaf; HF reloads via the tied embedding). Any other miss is
    a config-flag/variable-tree mismatch (e.g. attn_bias=True with no
    bias leaves) that would otherwise surface as a confusing
    transformers reload failure — those are written out as a loud
    warning listing the missing HF names (ADVICE r5)."""
    import flax.linen as nn
    import safetensors.numpy

    # init() returns nn.Partitioned-boxed leaves; strip the metadata.
    params = nn.meta.unbox(variables['params'])
    os.makedirs(out_dir, exist_ok=True)
    out: Dict[str, np.ndarray] = {}
    missing: list = []

    def grab(path: tuple) -> Optional[np.ndarray]:
        leaf = _get_at(params, path)
        return None if leaf is None else np.asarray(jax.device_get(leaf))

    def _optional(path: tuple) -> bool:
        # Knob-gated absences that are CORRECT by construction.
        return path == ('lm_head', 'kernel') and \
            getattr(cfg, 'tie_embeddings', False)

    for path, (hf_name, transpose) in _TOP_MAP.items():
        arr = grab(path)
        if arr is None:
            if not _optional(path):
                missing.append(hf_name)
            continue
        out[hf_name] = arr.T if transpose else arr
    for path, (suffix, transpose) in _layer_map(cfg).items():
        if cfg.scan_layers:
            stacked = grab(('layers',) + path)
            if stacked is None:
                missing.append(f'model.layers.*.{suffix}')
                continue
            for i in range(cfg.n_layers):
                arr = stacked[i]
                out[f'model.layers.{i}.{suffix}'] = (
                    arr.T if transpose else arr)
        else:
            for i in range(cfg.n_layers):
                arr = grab((f'layer_{i}',) + path)
                if arr is None:
                    missing.append(f'model.layers.{i}.{suffix}')
                    continue
                out[f'model.layers.{i}.{suffix}'] = (
                    arr.T if transpose else arr)
    if missing:
        logger.warning(
            'save_hf_checkpoint: %d mapped tensor(s) missing from the '
            'params tree and SKIPPED — the checkpoint at %s will not '
            'reload cleanly (config flag / variable-tree mismatch?): '
            '%s%s', len(missing), out_dir, ', '.join(missing[:8]),
            ' ...' if len(missing) > 8 else '')

    if getattr(cfg, 'hf_layout', 'llama') == 'phi3':
        # Fuse back into phi3's qkv_proj/gate_up_proj layout (HF
        # [out, in]: concatenate along the out-rows axis).
        for i in range(cfg.n_layers):
            pre = f'model.layers.{i}.'
            out[pre + 'self_attn.qkv_proj.weight'] = np.concatenate(
                [out.pop(pre + f'self_attn.{p}_proj.weight')
                 for p in ('q', 'k', 'v')], axis=0)
            out[pre + 'mlp.gate_up_proj.weight'] = np.concatenate(
                [out.pop(pre + 'mlp.gate_proj.weight'),
                 out.pop(pre + 'mlp.up_proj.weight')], axis=0)

    # safetensors requires contiguous, native-endian arrays.
    out = {k: np.ascontiguousarray(v) for k, v in out.items()}
    safetensors.numpy.save_file(
        out, os.path.join(out_dir, 'model.safetensors'))
    with open(os.path.join(out_dir, 'config.json'), 'w',
              encoding='utf-8') as f:
        json.dump(config_to_hf(cfg), f, indent=2)


def param_shardings(model, cfg, mesh, rules=sharding_lib.DEFAULT_RULES):
    """NamedShardings for the model's {'params': ...} tree from its
    logical annotations (eval_shape: no memory allocated)."""
    import flax.linen as nn

    sample = jnp.zeros((1, 8), jnp.int32)
    abs_vars = jax.eval_shape(model.init, jax.random.PRNGKey(0), sample)
    logical = nn.get_partition_spec(abs_vars)
    return nn.logical_to_mesh_sharding(logical, mesh, list(rules))['params']


def init_sharded_params(model, cfg, mesh, rng, sample,
                        rules=sharding_lib.DEFAULT_RULES) -> Dict[str, Any]:
    """Randomly initialise {'params': ...} straight into its sharded
    layout (the serving twin of trainer.create_sharded_state): each
    device only ever holds its own shard, so a preset larger than one
    chip's memory initialises on a mesh it fits."""
    import flax.linen as nn

    shardings = param_shardings(model, cfg, mesh, rules)

    def init(key):
        return nn.meta.unbox(model.init(key, sample)['params'])
    with mesh, nn.logical_axis_rules(list(rules)):
        return {'params': jax.jit(init, out_shardings=shardings)(rng)}


def shard_params(variables: Dict[str, Any], model, cfg, mesh,
                 rules=sharding_lib.DEFAULT_RULES) -> Dict[str, Any]:
    """Re-place an existing params tree onto `mesh` per the logical
    rules (for params that were initialized unsharded, e.g. tests)."""
    import flax.linen as nn

    shardings = param_shardings(model, cfg, mesh, rules)
    params = jax.tree.map(jax.device_put,
                          nn.meta.unbox(variables['params']), shardings)
    return {'params': params}


def config_from_hf(hf_config: Dict[str, Any], **overrides):
    """HF config.json dict -> LlamaConfig.

    Family dispatch mirrors what vLLM does for the reference
    (llm/vllm/serve.yaml accepts any HF model id): model_type 'llama'
    maps 1:1; 'qwen2' adds the q/k/v biases; 'gemma' adds GeGLU,
    zero-centered norms, the sqrt(dim) embedding scale, a decoupled
    head_dim, and tied embeddings (the HF GemmaConfig defaults)."""
    from skypilot_tpu.models import llama as llama_lib

    model_type = hf_config.get('model_type', 'llama')
    rope_scaling = hf_config.get('rope_scaling') or {}
    rs_type = rope_scaling.get('rope_type', rope_scaling.get('type'))
    if rs_type not in (None, 'default', 'llama3'):
        # longrope/yarn/etc. would silently produce wrong positions.
        raise ValueError(
            f'unsupported rope_scaling type {rs_type!r} in checkpoint '
            f'config (supported: llama3); long-context variants using '
            f'longrope/yarn are not implemented')
    if rs_type == 'llama3':
        # ops/rope.py implements the Llama-3.1 constants; a different
        # factor set (e.g. Llama-3.2's factor=32) would silently serve
        # wrong long-context positions.
        want = {'factor': 8.0, 'low_freq_factor': 1.0,
                'high_freq_factor': 4.0,
                'original_max_position_embeddings': 8192}
        got = {k: rope_scaling.get(k) for k in want}
        if any(got[k] is not None and float(got[k]) != v
               for k, v in want.items()):
            raise ValueError(
                f'llama3 rope_scaling with non-3.1 factors is not '
                f'implemented: checkpoint has {got}, ops/rope.py '
                f'implements {want}')
    kw = dict(
        vocab_size=hf_config['vocab_size'],
        dim=hf_config['hidden_size'],
        n_layers=hf_config['num_hidden_layers'],
        n_heads=hf_config['num_attention_heads'],
        n_kv_heads=hf_config.get('num_key_value_heads',
                                 hf_config['num_attention_heads']),
        mlp_dim=hf_config['intermediate_size'],
        max_seq_len=hf_config.get('max_position_embeddings', 8192),
        rope_theta=hf_config.get('rope_theta', 500000.0),
        use_llama31_rope=rs_type == 'llama3',
        norm_eps=hf_config.get('rms_norm_eps', 1e-5),
        tie_embeddings=hf_config.get('tie_word_embeddings', False),
    )
    if model_type == 'qwen2':
        # HF Qwen2Attention hardcodes q/k/v biases (no config field).
        kw['attn_bias'] = True
    elif model_type in ('qwen3', 'qwen3_moe'):
        # Qwen3 drops the biases for per-head q/k RMSNorm.
        kw['qk_norm'] = True
        kw['attn_bias'] = hf_config.get('attention_bias', False)
    elif model_type == 'mistral':
        # Architecturally llama + sliding-window attention on every
        # layer (ops/attention.py implements the window mask, so the
        # full max_position_embeddings context serves correctly).
        kw['sliding_window'] = hf_config.get('sliding_window') or 0
    elif model_type == 'phi3':
        # Llama math behind fused qkv_proj/gate_up_proj tensors
        # (split on load, fused on save); -4k minis also carry a
        # sliding window.
        kw['hf_layout'] = 'phi3'
        kw['sliding_window'] = hf_config.get('sliding_window') or 0
    elif model_type == 'gemma':
        kw['mlp_act'] = 'gelu_tanh'
        kw['norm_zero_centered'] = True
        kw['embed_scale'] = True
        kw['tie_embeddings'] = hf_config.get('tie_word_embeddings', True)
    elif model_type == 'gemma2':
        kw['mlp_act'] = 'gelu_tanh'
        kw['norm_zero_centered'] = True
        kw['embed_scale'] = True
        kw['tie_embeddings'] = hf_config.get('tie_word_embeddings', True)
        kw['sandwich_norms'] = True
        kw['sliding_window'] = hf_config.get('sliding_window') or 0
        # HF Gemma2: even layers sliding, odd global.
        kw['window_pattern'] = 2
        kw['attn_softcap'] = hf_config.get('attn_logit_softcapping') \
            or 0.0
        kw['final_softcap'] = hf_config.get('final_logit_softcapping') \
            or 0.0
        qpas = hf_config.get('query_pre_attn_scalar')
        if qpas:
            kw['attn_scale'] = float(qpas) ** -0.5
    head_dim = hf_config.get('head_dim') or 0
    if head_dim and head_dim != kw['dim'] // kw['n_heads']:
        kw['head_dim_override'] = head_dim
    kw.update(overrides)
    return llama_lib.LlamaConfig(**kw)


def config_to_hf(cfg) -> Dict[str, Any]:
    """LlamaConfig -> HF config.json dict (what save_hf_checkpoint
    writes; enough for transformers' matching *ForCausalLM to reload).

    The family is recovered from the knobs: sandwich_norms -> gemma2,
    norm_zero_centered -> gemma, qk_norm -> qwen3, attn_bias -> qwen2,
    sliding_window (non-gemma2) -> mistral, else llama (the inverse of
    config_from_hf's dispatch)."""
    if cfg.sandwich_norms:
        model_type, arch = 'gemma2', 'Gemma2ForCausalLM'
    elif cfg.norm_zero_centered:
        model_type, arch = 'gemma', 'GemmaForCausalLM'
    elif cfg.qk_norm:
        model_type, arch = 'qwen3', 'Qwen3ForCausalLM'
    elif cfg.attn_bias:
        model_type, arch = 'qwen2', 'Qwen2ForCausalLM'
    elif getattr(cfg, 'hf_layout', 'llama') == 'phi3':
        model_type, arch = 'phi3', 'Phi3ForCausalLM'
    elif cfg.sliding_window > 0:
        model_type, arch = 'mistral', 'MistralForCausalLM'
    else:
        model_type, arch = 'llama', 'LlamaForCausalLM'
    out = {
        'architectures': [arch],
        'model_type': model_type,
        'vocab_size': cfg.vocab_size,
        'hidden_size': cfg.dim,
        'num_hidden_layers': cfg.n_layers,
        'num_attention_heads': cfg.n_heads,
        'num_key_value_heads': cfg.n_kv_heads,
        'intermediate_size': cfg.mlp_dim,
        'max_position_embeddings': cfg.max_seq_len,
        'rope_theta': cfg.rope_theta,
        'rms_norm_eps': cfg.norm_eps,
        'tie_word_embeddings': cfg.tie_embeddings,
        'head_dim': cfg.head_dim,
        'hidden_act': ('gelu_pytorch_tanh'
                       if cfg.mlp_act == 'gelu_tanh' else 'silu'),
        'torch_dtype': 'float32',
    }
    if model_type in ('gemma', 'gemma2'):
        # GemmaConfig reads 'hidden_activation' (hidden_act is legacy).
        out['hidden_activation'] = out['hidden_act']
    if model_type == 'qwen3':
        # Read back by config_from_hf; HF defaults attention_bias to
        # False, so an explicit value keeps biased qwen3 checkpoints
        # round-tripping (transformers would otherwise silently drop
        # the saved bias tensors on reload).
        out['attention_bias'] = cfg.attn_bias
    if model_type in ('mistral', 'phi3'):
        out['sliding_window'] = cfg.sliding_window or None
    if model_type == 'phi3':
        # Phi3Config defaults pad_token_id=32000, which explodes on
        # smaller vocabs; no padding index is the general truth here.
        out['pad_token_id'] = None
    if model_type == 'gemma2':
        out['sliding_window'] = cfg.sliding_window
        out['attn_logit_softcapping'] = cfg.attn_softcap or None
        out['final_logit_softcapping'] = cfg.final_softcap or None
        # ALWAYS emitted: HF Gemma2Config defaults the scalar to 256,
        # so omitting it when we scale by 1/sqrt(head_dim) would make
        # transformers reload the checkpoint with a different scale.
        out['query_pre_attn_scalar'] = round(
            (cfg.attn_scale or cfg.head_dim ** -0.5) ** -2)
    if cfg.use_llama31_rope:
        out['rope_scaling'] = {
            'rope_type': 'llama3', 'factor': 8.0,
            'low_freq_factor': 1.0, 'high_freq_factor': 4.0,
            'original_max_position_embeddings': 8192,
        }
    return out


def load_config(ckpt_dir: str, **overrides):
    """Read config.json from a checkpoint dir -> LlamaConfig."""
    with open(os.path.join(ckpt_dir, 'config.json'),
              encoding='utf-8') as f:
        return config_from_hf(json.load(f), **overrides)


# ------------------------------------------------------------- tree utils
def _set_at(tree: Dict[str, Any], path: tuple, value) -> None:
    node = tree
    for key in path[:-1]:
        node = node.setdefault(key, {})
    node[path[-1]] = value


def _get_at(tree: Dict[str, Any], path: tuple):
    node = tree
    for key in path:
        if not isinstance(node, dict) or key not in node:
            return None
        node = node[key]
    return node


def _leaf_at(tree, path: tuple):
    node = tree
    for key in path:
        node = node[key]
    return node
