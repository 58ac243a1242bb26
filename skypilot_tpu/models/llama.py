"""Llama-family transformer, TPU-first.

The flagship model of the framework: the reference orchestrates
llm/llama-3_1-finetuning/lora.yaml (torchtune LoRA over NCCL) as an opaque
container; here the model is a first-class flax.linen module designed for
GSPMD — every parameter and activation carries logical axis names
(parallel/sharding.py rules map them to the pp/dp/cp/fsdp/ep/tp mesh), the
layer stack is an `nn.scan` (one XLA while-loop body instead of n_layers
unrolled layers → fast compiles at 70B scale), and attention dispatches to
the Pallas flash kernel on TPU.

Shapes follow Llama 3 (GQA, SwiGLU, RMSNorm, RoPE theta 5e5, vocab 128256).
"""
import dataclasses
import flax.linen as nn
import jax
import jax.numpy as jnp

from skypilot_tpu.ops import attention as attention_ops
from skypilot_tpu.ops import lora as ops_lora
from skypilot_tpu.ops import norms, rope
from skypilot_tpu.utils import env as _env


@dataclasses.dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 128256
    dim: int = 4096
    n_layers: int = 32
    n_heads: int = 32
    n_kv_heads: int = 8
    mlp_dim: int = 14336
    max_seq_len: int = 8192
    rope_theta: float = 500000.0
    use_llama31_rope: bool = True
    norm_eps: float = 1e-5
    dtype: str = 'bfloat16'          # activations/params compute dtype
    param_dtype: str = 'float32'     # master param dtype
    remat: bool = True               # checkpoint each block
    # What the per-block checkpoint saves: 'full' recomputes everything
    # (min memory, ~+2N FLOPs of recompute per bwd token), 'dots' saves
    # matmul outputs and recomputes only elementwise ops (near-zero
    # recompute cost, ~2x activation memory) — jax dots_saveable policy.
    remat_policy: str = 'full'
    scan_layers: bool = True
    attn_impl: str = 'auto'          # 'auto' | 'flash' | 'xla' | 'ring'
    tie_embeddings: bool = False
    # Weight-only quantization for serving: 'none' | 'int8' | 'int4'.
    # int8 stores every projection kernel as int8 + per-output-channel
    # scales; int4 stores group-wise (G=128) scales
    # (models/quant.py quantize_params converts a float tree); decode is
    # weight-HBM-bound, so halving (int8) or quartering (int4) the
    # bytes per step is a direct decode-throughput win.
    # Embeddings/norms stay high precision.
    quant: str = 'none'
    # Family knobs: the reference serves any HF decoder family by
    # pointing vLLM at the checkpoint (llm/vllm/serve.yaml); this one
    # module covers the Llama-layout families the same way —
    # Qwen2(.5) = llama + q/k/v biases; Gemma = GeGLU + zero-centered
    # RMSNorm + sqrt(dim) embedding scale + decoupled head_dim.
    attn_bias: bool = False          # Qwen2: bias on q/k/v projections
    # Qwen3: per-head RMSNorm on q and k (over head_dim, weights shaped
    # [head_dim]) applied BEFORE rope; replaces Qwen2's q/k/v biases.
    qk_norm: bool = False
    head_dim_override: int = 0       # Gemma: head_dim != dim/n_heads
    mlp_act: str = 'silu'            # 'silu' | 'gelu_tanh' (Gemma)
    norm_zero_centered: bool = False  # Gemma: weight applied as (1+w)
    embed_scale: bool = False        # Gemma: embeddings * sqrt(dim)
    # Sliding-window attention (Mistral: every layer; Gemma-2: every
    # other layer): query p attends keys in (p - window, p]. 0 = off.
    sliding_window: int = 0
    # Layer i is windowed iff i % window_pattern == 0 (1 = every
    # layer; 2 = Gemma-2's sliding/global alternation, which starts
    # with a sliding layer). Under nn.scan the per-layer choice is
    # arithmetic on the scanned layer index — the body stays one
    # homogeneous trace.
    window_pattern: int = 1
    attn_softcap: float = 0.0        # Gemma-2: 50.0 (tanh soft-cap)
    final_softcap: float = 0.0       # Gemma-2: 30.0 (lm-head logits)
    # Attention softmax scale override; 0 = 1/sqrt(head_dim). Gemma-2
    # uses 1/sqrt(query_pre_attn_scalar).
    attn_scale: float = 0.0
    # Gemma-2 sandwich norms: post-attention and pre/post-feedforward
    # RMSNorms in addition to the two pre-norms.
    sandwich_norms: bool = False
    # HF checkpoint tensor layout: 'llama' (separate q/k/v and
    # gate/up tensors) or 'phi3' (fused qkv_proj and gate_up_proj) —
    # an I/O-only knob (models/weights.py splits on load, fuses on
    # save); the module math is identical.
    hf_layout: str = 'llama'
    # Block diffusion (models/hybrid.py sets it from its objective):
    # > 0 is the block length of the block-diffusion mask over a row
    # `[x_t | x_0]`, in place of the causal one (ops/attention.py).
    attn_block_diffusion: int = 0

    @property
    def head_dim(self) -> int:
        return self.head_dim_override or self.dim // self.n_heads

    @property
    def needs_xla_attention(self) -> bool:
        """Window/softcap/scale-override models run attention on the
        XLA path everywhere (incl. paged decode): the Pallas kernels
        do not implement them, and silence would be wrong math."""
        return (self.sliding_window > 0 or self.attn_softcap > 0.0 or
                self.attn_scale != 0.0)

    def num_params(self) -> int:
        """Analytic parameter count (embedding counted once if tied)."""
        d, v = self.dim, self.vocab_size
        attn = d * self.n_heads * self.head_dim + \
            2 * d * self.n_kv_heads * self.head_dim + \
            self.n_heads * self.head_dim * d
        if self.attn_bias:
            attn += (self.n_heads + 2 * self.n_kv_heads) * self.head_dim
        if self.qk_norm:
            attn += 2 * self.head_dim
        mlp = 3 * d * self.mlp_dim
        per_layer = attn + mlp + (4 if self.sandwich_norms else 2) * d
        embeds = v * d * (1 if self.tie_embeddings else 2)
        return self.n_layers * per_layer + embeds + d


# Presets. 'debug' is for unit tests (runs on the 8-device CPU mesh);
# 1B/8B/70B follow the Llama-3.x released shapes.
CONFIGS = {
    'debug': LlamaConfig(vocab_size=256, dim=64, n_layers=2, n_heads=4,
                         n_kv_heads=2, mlp_dim=128, max_seq_len=128,
                         dtype='float32', param_dtype='float32',
                         use_llama31_rope=False, remat=False),
    'llama3-1b': LlamaConfig(vocab_size=128256, dim=2048, n_layers=16,
                             n_heads=32, n_kv_heads=8, mlp_dim=8192,
                             tie_embeddings=True),
    'llama3-8b': LlamaConfig(),  # the defaults above are 8B
    'llama3-70b': LlamaConfig(dim=8192, n_layers=80, n_heads=64,
                              n_kv_heads=8, mlp_dim=28672),
    # Qwen2.5 released shapes (HF Qwen2Config: q/k/v biases, rope 1e6).
    'qwen2-1.5b': LlamaConfig(vocab_size=151936, dim=1536, n_layers=28,
                              n_heads=12, n_kv_heads=2, mlp_dim=8960,
                              max_seq_len=32768, rope_theta=1e6,
                              use_llama31_rope=False, norm_eps=1e-6,
                              tie_embeddings=True, attn_bias=True),
    'qwen2-7b': LlamaConfig(vocab_size=152064, dim=3584, n_layers=28,
                            n_heads=28, n_kv_heads=4, mlp_dim=18944,
                            max_seq_len=32768, rope_theta=1e6,
                            use_llama31_rope=False, norm_eps=1e-6,
                            attn_bias=True),
    # Qwen3 released shapes (HF Qwen3Config: per-head q/k RMSNorm, no
    # attention biases, decoupled head_dim 128).
    'qwen3-0.6b': LlamaConfig(vocab_size=151936, dim=1024, n_layers=28,
                              n_heads=16, n_kv_heads=8, mlp_dim=3072,
                              head_dim_override=128, max_seq_len=32768,
                              rope_theta=1e6, use_llama31_rope=False,
                              norm_eps=1e-6, tie_embeddings=True,
                              qk_norm=True),
    'qwen3-8b': LlamaConfig(vocab_size=151936, dim=4096, n_layers=36,
                            n_heads=32, n_kv_heads=8, mlp_dim=12288,
                            head_dim_override=128, max_seq_len=32768,
                            rope_theta=1e6, use_llama31_rope=False,
                            norm_eps=1e-6, qk_norm=True),
    # Phi-3-mini shape (HF Phi3Config): llama math behind fused
    # qkv_proj/gate_up_proj checkpoint tensors; the -4k variant also
    # carries a 2047-token sliding window.
    'phi3-mini': LlamaConfig(vocab_size=32064, dim=3072, n_layers=32,
                             n_heads=32, n_kv_heads=32, mlp_dim=8192,
                             max_seq_len=4096, rope_theta=10000.0,
                             use_llama31_rope=False, norm_eps=1e-5,
                             sliding_window=2047, hf_layout='phi3'),
    # Mistral-7B-v0.1 shape (HF MistralConfig): llama + sliding-window
    # attention on every layer.
    'mistral-7b': LlamaConfig(vocab_size=32000, dim=4096, n_layers=32,
                              n_heads=32, n_kv_heads=8, mlp_dim=14336,
                              max_seq_len=32768, sliding_window=4096,
                              rope_theta=10000.0,
                              use_llama31_rope=False, norm_eps=1e-6),
    # Gemma-2 released shapes (HF Gemma2Config): Gemma conventions plus
    # sandwich norms, tanh soft-caps (attn 50 / lm-head 30),
    # 1/sqrt(query_pre_attn_scalar) attention scale, and sliding-window
    # attention on every other layer (pattern 2, window 4096).
    'gemma2-2b': LlamaConfig(vocab_size=256000, dim=2304, n_layers=26,
                             n_heads=8, n_kv_heads=4, mlp_dim=9216,
                             head_dim_override=256, max_seq_len=8192,
                             rope_theta=10000.0, use_llama31_rope=False,
                             norm_eps=1e-6, tie_embeddings=True,
                             mlp_act='gelu_tanh', norm_zero_centered=True,
                             embed_scale=True, sliding_window=4096,
                             window_pattern=2, attn_softcap=50.0,
                             final_softcap=30.0,
                             attn_scale=256.0 ** -0.5,
                             sandwich_norms=True),
    'gemma2-9b': LlamaConfig(vocab_size=256000, dim=3584, n_layers=42,
                             n_heads=16, n_kv_heads=8, mlp_dim=14336,
                             head_dim_override=256, max_seq_len=8192,
                             rope_theta=10000.0, use_llama31_rope=False,
                             norm_eps=1e-6, tie_embeddings=True,
                             mlp_act='gelu_tanh', norm_zero_centered=True,
                             embed_scale=True, sliding_window=4096,
                             window_pattern=2, attn_softcap=50.0,
                             final_softcap=30.0,
                             attn_scale=256.0 ** -0.5,
                             sandwich_norms=True),
    # Gemma released shapes (HF GemmaConfig: GeGLU, 1+w norms,
    # sqrt(dim) embed scale, head_dim 256, tied embeddings).
    'gemma-2b': LlamaConfig(vocab_size=256000, dim=2048, n_layers=18,
                            n_heads=8, n_kv_heads=1, mlp_dim=16384,
                            head_dim_override=256, max_seq_len=8192,
                            rope_theta=10000.0, use_llama31_rope=False,
                            norm_eps=1e-6, tie_embeddings=True,
                            mlp_act='gelu_tanh', norm_zero_centered=True,
                            embed_scale=True),
    'gemma-7b': LlamaConfig(vocab_size=256000, dim=3072, n_layers=28,
                            n_heads=16, n_kv_heads=16, mlp_dim=24576,
                            head_dim_override=256, max_seq_len=8192,
                            rope_theta=10000.0, use_llama31_rope=False,
                            norm_eps=1e-6, tie_embeddings=True,
                            mlp_act='gelu_tanh', norm_zero_centered=True,
                            embed_scale=True),
}


class QuantDense(nn.Module):
    """Weight-only int8 linear: kernel int8 [in, out] + per-output-
    channel float scale [out]. `y = (x @ int8_kernel) * scale` is exact
    for per-column scales — XLA fuses the cast and the scale multiply
    into the matmul, so HBM reads half the bytes per decode step while
    the MXU still runs the compute dtype."""
    features: int
    logical_axes: tuple
    dtype: jnp.dtype
    use_bias: bool = False

    @nn.compact
    def __call__(self, x):
        kernel = self.param(
            'kernel',
            nn.with_logical_partitioning(
                nn.initializers.zeros_init(), self.logical_axes),
            (x.shape[-1], self.features), jnp.int8)
        scale = self.param(
            'scale',
            nn.with_logical_partitioning(
                nn.initializers.ones_init(), (self.logical_axes[-1],)),
            (self.features,), jnp.float32)
        y = jnp.dot(x, kernel.astype(self.dtype))
        y = y * scale.astype(self.dtype)
        if self.use_bias:
            # Biases are tiny (one row); they stay float, only the
            # kernel is quantized.
            bias = self.param(
                'bias',
                nn.with_logical_partitioning(
                    nn.initializers.zeros_init(),
                    (self.logical_axes[-1],)),
                (self.features,), jnp.float32)
            y = y + bias.astype(self.dtype)
        return y


class QuantDense4(nn.Module):
    """Weight-only int4 linear: kernel int4 [in, out] + group-wise
    float scales [in/G, out] (G = quant.INT4_GROUP along `in`).

    y = sum_g (x_g @ k4_g) * s_g. Each group dot runs in the compute
    dtype (inside a dot the MXU accumulates bf16 products in f32
    natively); the cross-group scale-multiply + sum runs in f32 with
    one final rounding, so the n_g-way accumulation cannot drift in
    bf16 — near the error profile of a single f32-accumulated dot over
    the dequantized kernel (pinned by test at f32 and bf16), while the
    HBM read is a quarter of bf16. The per-group contraction is
    [.., G] x [G, out] with G=128, a clean MXU tile."""
    features: int
    logical_axes: tuple
    dtype: jnp.dtype
    use_bias: bool = False

    @nn.compact
    def __call__(self, x):
        from skypilot_tpu.models import quant as quant_lib
        din = x.shape[-1]
        g = quant_lib.int4_group_size(din)
        n_g = din // g
        kernel = self.param(
            'kernel',
            nn.with_logical_partitioning(
                nn.initializers.zeros_init(), self.logical_axes),
            (din, self.features), jnp.int4)
        # Group axis unnamed: scales replicate across an in-sharded
        # kernel (~0.4% of the kernel bytes) — always correct, and
        # avoids indivisible tiny group counts on small models.
        scale = self.param(
            'scale',
            nn.with_logical_partitioning(
                nn.initializers.ones_init(),
                (None, self.logical_axes[-1])),
            (n_g, self.features), jnp.float32)
        xg = x.reshape(*x.shape[:-1], n_g, g)
        kg = kernel.astype(self.dtype).reshape(n_g, g, self.features)
        # Each group dot runs in the compute dtype (the MXU accumulates
        # bf16 products in f32 inside a dot anyway); the cross-group
        # scale-multiply + sum runs in f32 so n_g-way accumulation
        # cannot drift in bf16 — one final rounding at the end.
        partial = jnp.einsum('...gi,gio->...go', xg, kg)
        y = (partial.astype(jnp.float32) * scale).sum(
            axis=-2).astype(self.dtype)
        if self.use_bias:
            bias = self.param(
                'bias',
                nn.with_logical_partitioning(
                    nn.initializers.zeros_init(),
                    (self.logical_axes[-1],)),
                (self.features,), jnp.float32)
            y = y + bias.astype(self.dtype)
        return y


def _dense(features, logical_axes, name, param_dtype, dtype, quant='none',
           use_bias=False):
    if quant == 'int8':
        return QuantDense(features=features, logical_axes=logical_axes,
                          name=name, dtype=dtype, use_bias=use_bias)
    if quant == 'int4':
        return QuantDense4(features=features, logical_axes=logical_axes,
                           name=name, dtype=dtype, use_bias=use_bias)
    return nn.Dense(
        features=features, use_bias=use_bias, name=name,
        dtype=dtype, param_dtype=param_dtype,
        kernel_init=nn.with_logical_partitioning(
            nn.initializers.lecun_normal(), logical_axes),
        bias_init=nn.with_logical_partitioning(
            nn.initializers.zeros_init(), (logical_axes[-1],)))


def _lora_delta(mdl, name, x, lora_ids, lora_scale, dtype):
    """Batched multi-LoRA delta for projection `name` (S-LoRA style).

    Serving analog of the reference's llm/lorax recipe (LoRAX
    container): adapters for ALL requests live stacked in the 'lora'
    variable collection — a [n_adapters, in, r] / [n_adapters, r, out]
    pair per projection at this module's scope, id 0 = zeros (no
    adapter) — and each sequence in the batch gathers its own A/B by
    `lora_ids` ([B] per-sequence, or [B, S] per-token for ragged
    prefill packs mixing adapters). The gather + two rank-r
    contractions (~r/in of the main matmul's FLOPs) dispatch through
    the ops/lora.py 'lora_grouped' ladder (fused Pallas kernel, exact
    einsum floor); returns None when no adapters are loaded so the
    base path traces unchanged."""
    if lora_ids is None or not mdl.has_variable('lora', f'{name}_ab'):
        return None
    ab = mdl.get_variable('lora', f'{name}_ab')
    return ops_lora.grouped_lora_delta(x.astype(dtype), ab['a'],
                                       ab['b'], lora_ids, lora_scale)


def _proj(mdl, cfg, dtype, lora_ids, lora_scale, name, feats, axes,
          inp, use_bias=False):
    """A projection + its (optional) multi-LoRA delta — the one place
    the adapter path wires into the base matmul (submodule parenting
    follows the calling module's compact context, so `name` scopes
    under the caller as usual)."""
    y = _dense(feats, axes, name, cfg.param_dtype, dtype, cfg.quant,
               use_bias=use_bias)(inp)
    d = _lora_delta(mdl, name, inp, lora_ids, lora_scale, dtype)
    return y if d is None else y + d


def _window_args(cfg, layer_idx):
    """(window, window_active) for one layer. A static layer index
    (non-scan path) resolves the alternation statically; a traced index
    (nn.scan xs) yields a traced bool gate so the scan body stays one
    homogeneous trace (Gemma-2's sliding/global alternation)."""
    if cfg.sliding_window <= 0:
        return 0, None
    if layer_idx is None or cfg.window_pattern <= 1:
        return cfg.sliding_window, None
    if isinstance(layer_idx, int):
        if layer_idx % cfg.window_pattern == 0:
            return cfg.sliding_window, None
        return 0, None
    return cfg.sliding_window, (layer_idx % cfg.window_pattern) == 0


class LlamaAttention(nn.Module):
    cfg: LlamaConfig

    @nn.compact
    def __call__(self, x, cos, sin, segment_ids=None, cache=None,
                 positions=None, lora_ids=None, lora_scale=None,
                 layer_idx=None):
        """cache: optional (k,v) of [B, S_cache, Hkv, Hd] for incremental
        decoding — new K/V are written at `positions` (per-batch write
        offsets) and attention runs against the whole cache with a
        position mask. Returns (out, new_cache) when cache is given.

        lora_ids/lora_scale: optional [B] per-sequence adapter index +
        scaling for batched multi-LoRA serving (see _lora_delta)."""
        cfg = self.cfg
        dtype = jnp.dtype(cfg.dtype)
        h, hk, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
        b, s, _ = x.shape

        window, window_active = _window_args(cfg, layer_idx)

        def proj(name, feats, axes, inp, use_bias=False):
            return _proj(self, cfg, dtype, lora_ids, lora_scale,
                         name, feats, axes, inp, use_bias)

        q = proj('wq', h * hd, ('embed', 'heads'), x,
                 cfg.attn_bias).reshape(b, s, h, hd)
        k = proj('wk', hk * hd, ('embed', 'kv_heads'), x,
                 cfg.attn_bias).reshape(b, s, hk, hd)
        v = proj('wv', hk * hd, ('embed', 'kv_heads'), x,
                 cfg.attn_bias).reshape(b, s, hk, hd)

        if cfg.qk_norm:
            # Norm over head_dim of the reshaped [b, s, h, hd] — the
            # Qwen3 convention (weights [hd], shared across heads).
            q = RMSNorm(cfg, name='q_norm', axis_name=None)(q)
            k = RMSNorm(cfg, name='k_norm', axis_name=None)(k)
        q = rope.apply_rope(q, cos, sin)
        k = rope.apply_rope(k, cos, sin)
        q = nn.with_logical_constraint(
            q, ('act_batch', 'act_seq', 'act_heads', None))
        k = nn.with_logical_constraint(
            k, ('act_batch', 'act_seq', 'act_kv_heads', None))
        v = nn.with_logical_constraint(
            v, ('act_batch', 'act_seq', 'act_kv_heads', None))

        if cache is not None:
            assert positions is not None, 'cache path needs positions'
            if len(cache) in (3, 5):
                # Paged decode path: cache = (k_pool [n_pages, Hkv, P,
                # hd], v_pool, tables [B, max_pages]) — plus per-token
                # scale pools (k_scale, v_scale) when the KV pool is
                # int8-quantized (infer/paged_cache.py module doc).
                # Each sequence's new token(s) scatter into
                # (tables[b, pos//P], pos%P); attention either runs
                # the Pallas paged kernel (reads pages directly) or
                # the gathered per-layer view — the page indirection
                # lives HERE so at most one layer's KV is ever
                # materialized contiguously (infer/paged_cache.py
                # holds the pool accounting).

                from skypilot_tpu.infer.paged_cache import PagePool
                quantized = len(cache) == 5
                k_scale = v_scale = None
                if quantized:
                    k_pool, v_pool, tables, k_scale, v_scale = cache
                else:
                    k_pool, v_pool, tables = cache
                pos = positions[:, 0]
                if s == 1:
                    if quantized:
                        k_pool, k_scale = PagePool.append_token_layer_q(
                            k_pool, k_scale, k[:, 0], tables, pos)
                        v_pool, v_scale = PagePool.append_token_layer_q(
                            v_pool, v_scale, v[:, 0], tables, pos)
                    else:
                        k_pool = PagePool.append_token_layer(
                            k_pool, k[:, 0], tables, pos)
                        v_pool = PagePool.append_token_layer(
                            v_pool, v[:, 0], tables, pos)
                else:
                    # Speculative decode: a short run of s = draft+1
                    # tokens per slot is written and attended in one
                    # step (infer/engine.py _decode_spec_impl).
                    if quantized:
                        k_pool, k_scale = \
                            PagePool.append_tokens_layer_q(
                                k_pool, k_scale, k, tables, pos)
                        v_pool, v_scale = \
                            PagePool.append_tokens_layer_q(
                                v_pool, v_scale, v, tables, pos)
                    else:
                        k_pool = PagePool.append_tokens_layer(
                            k_pool, k, tables, pos)
                        v_pool = PagePool.append_tokens_layer(
                            v_pool, v, tables, pos)
                from skypilot_tpu.ops import dispatch
                from skypilot_tpu.parallel.sharding import per_shard

                def _paged_kernel(kernel, kernel_q, qx, qx_axes):
                    """The paged Pallas kernel (its int8 twin on a
                    quantized pool) on each device's own heads: the
                    pool is sharded on kv_heads under tp, like q; the
                    slots, tables and lengths stay whole."""
                    pool_axes = (None, 'act_kv_heads', None, None)
                    scale_axes = (None, 'act_kv_heads', None)
                    if quantized:
                        return per_shard(
                            kernel_q,
                            (qx_axes, pool_axes, pool_axes, scale_axes,
                             scale_axes, (), ()), qx_axes)(
                                 qx, k_pool, v_pool, k_scale, v_scale,
                                 tables, pos)
                    return per_shard(
                        kernel, (qx_axes, pool_axes, pool_axes, (), ()),
                        qx_axes)(qx, k_pool, v_pool, tables, pos)

                def _xla_gather():
                    # Gather view + masked XLA reference: the
                    # correctness floor of the paged ladder, and the
                    # only correct math for window/softcap/scale
                    # models (cfg.needs_xla_attention). Quantized
                    # pools dequantize at the gather.
                    if quantized:
                        k_view = PagePool.gather_view_layer_q(
                            k_pool, k_scale, tables, dtype)
                        v_view = PagePool.gather_view_layer_q(
                            v_pool, v_scale, tables, dtype)
                    else:
                        k_view = PagePool.gather_view_layer(k_pool,
                                                            tables)
                        v_view = PagePool.gather_view_layer(v_pool,
                                                            tables)
                    return _cached_attention(q, k_view, v_view,
                                             positions, cfg, window,
                                             window_active)

                # Quantized pools dispatch under their own op labels
                # (paged_attention{,_mq}_int8) so the kernel-path
                # counter tells the int8 read path apart from fp.
                op_sq = 'paged_attention_int8' if quantized \
                    else 'paged_attention'
                op_mq = 'paged_attention_mq_int8' if quantized \
                    else 'paged_attention_mq'
                if s == 1 and not cfg.needs_xla_attention and \
                        _env.get(
                            'SKYT_PAGED_ATTN', 'pallas') == 'pallas':
                    # Pallas kernel DMAs each slot's pages directly
                    # (no materialized contiguous view; escape hatch:
                    # SKYT_PAGED_ATTN=xla). The append scatter above
                    # leaves the pool in the row-major layout this
                    # kernel reads (PagePool._set_rows). Routed
                    # through the dispatch ladder: a trace-time kernel
                    # failure (or an armed ops.lowering fault) degrades
                    # to the gather view instead of killing the serve
                    # path, and the chosen path lands in
                    # skyt_ops_kernel_path_total{op="paged_attention"}.
                    from skypilot_tpu.ops import paged_attention

                    def _pallas_sq():
                        return _paged_kernel(
                            paged_attention.paged_decode_attention,
                            paged_attention.paged_decode_attention_q,
                            q[:, 0], (None, 'act_heads', None))[:, None]
                    out = dispatch.run_ladder(op_sq, [
                        ('pallas', _pallas_sq),
                        ('xla', _xla_gather),
                    ])
                elif s > 1 and not cfg.needs_xla_attention and \
                        _env.get(
                            'SKYT_SPEC_PAGED_ATTN',
                            'pallas') == 'pallas':
                    # Multi-query kernel for the speculative verify
                    # step: DMAs only each slot's owned pages instead
                    # of gathering the max_pages*P view (lowering and
                    # engine parity: tests_tpu
                    # test_spec_mq_kernel_lowers); escape hatch:
                    # SKYT_SPEC_PAGED_ATTN=xla. Same ladder as the
                    # single-query path.
                    from skypilot_tpu.ops import paged_attention

                    def _pallas_mq():
                        return _paged_kernel(
                            paged_attention.paged_decode_attention_mq,
                            paged_attention.paged_decode_attention_mq_q,
                            q, (None, None, 'act_heads', None))
                    out = dispatch.run_ladder(op_mq, [
                        ('pallas', _pallas_mq),
                        ('xla', _xla_gather),
                    ])
                else:
                    # 'xla_native': XLA is the REQUIRED math here
                    # (needs_xla_attention / env escape hatch), not
                    # ladder degradation — distinct label so the
                    # degradation signal stays clean.
                    out = dispatch.run_ladder(
                        op_sq if s == 1 else op_mq,
                        [('xla_native', _xla_gather)])
                new_cache = (k_pool, v_pool, k_scale, v_scale) \
                    if quantized else (k_pool, v_pool)
            else:
                k_cache, v_cache = cache
                start = positions[:, 0]  # write offset per sequence
                k_cache = jax.vmap(
                    lambda c, kk, i: jax.lax.dynamic_update_slice(
                        c, kk, (i, 0, 0)))(k_cache, k, start)
                v_cache = jax.vmap(
                    lambda c, vv, i: jax.lax.dynamic_update_slice(
                        c, vv, (i, 0, 0)))(v_cache, v, start)
                if segment_ids is not None:
                    # Packed RAGGED prefill (infer/engine.py
                    # _try_admit_ragged): several variable-length
                    # prompts ride ONE [1, T] row, separated by
                    # segment ids (pad positions carry id 0). The
                    # cache starts zeroed and the writes above cover
                    # the whole packed span, so attending the fresh
                    # k/v with segment masking IS attention over the
                    # cache — and it runs the packed-sequence flash
                    # machinery (ops/flash_attention.py segment
                    # blocks) instead of a positions-vs-index mask
                    # that packed (per-segment-restarting) positions
                    # would break.
                    out = attention_ops.attention(
                        q, k, v, causal=True, segment_ids=segment_ids,
                        impl=cfg.attn_impl, window=window,
                        window_active=window_active,
                        logit_softcap=cfg.attn_softcap,
                        softmax_scale=cfg.attn_scale or None)
                else:
                    out = _cached_attention(q, k_cache, v_cache,
                                            positions, cfg, window,
                                            window_active)
                new_cache = (k_cache, v_cache)
            out = out.reshape(b, s, h * hd)
            out = proj('wo', cfg.dim, ('heads', 'embed'), out)
            return nn.with_logical_constraint(
                out, ('act_batch', 'act_seq', 'act_embed')), new_cache

        if cfg.attn_impl == 'ring':
            if cfg.needs_xla_attention:
                raise ValueError('ring attention does not support '
                                 'window/softcap/scale-override models')
            from skypilot_tpu.parallel import mesh as mesh_lib
            from skypilot_tpu.parallel import ring_attention
            mesh = mesh_lib.current_mesh()
            if mesh is None or mesh.shape.get('cp', 1) == 1:
                # No cp axis to ride — plain attention is the same math.
                out = attention_ops.attention(q, k, v, causal=True,
                                              segment_ids=segment_ids)
            else:
                out = ring_attention.ring_attention_sharded(
                    q, k, v, mesh, causal=True)
        else:
            out = attention_ops.attention(
                q, k, v, causal=True, segment_ids=segment_ids,
                impl=cfg.attn_impl, window=window,
                window_active=window_active,
                logit_softcap=cfg.attn_softcap,
                softmax_scale=cfg.attn_scale or None,
                block_diffusion=cfg.attn_block_diffusion)
        out = out.reshape(b, s, h * hd)
        out = proj('wo', cfg.dim, ('heads', 'embed'), out)
        return nn.with_logical_constraint(
            out, ('act_batch', 'act_seq', 'act_embed'))


def _cached_attention(q, k_cache, v_cache, positions, cfg=None,
                      window=0, window_active=None):
    """Attention of q [B,S,H,Hd] against the full cache [B,Sc,Hkv,Hd],
    masked so query at global position p sees keys at positions <= p
    (cache slots beyond the written prefix are masked out by the same
    rule because writes are left-aligned). Delegates to the tested GQA
    reference (ops/attention.py) with per-batch query positions; the
    window/softcap/scale family knobs flow through when cfg is
    given."""
    softcap = cfg.attn_softcap if cfg is not None else 0.0
    scale = (cfg.attn_scale or None) if cfg is not None else None
    return attention_ops.mha_reference(q, k_cache, v_cache,
                                       q_positions=positions,
                                       window=window,
                                       window_active=window_active,
                                       logit_softcap=softcap,
                                       softmax_scale=scale)


class LlamaMLP(nn.Module):
    cfg: LlamaConfig

    @nn.compact
    def __call__(self, x, lora_ids=None, lora_scale=None):
        cfg = self.cfg
        dtype = jnp.dtype(cfg.dtype)

        def proj(name, feats, axes, inp):
            return _proj(self, cfg, dtype, lora_ids, lora_scale,
                         name, feats, axes, inp)

        gate = proj('w_gate', cfg.mlp_dim, ('embed', 'mlp'), x)
        up = proj('w_up', cfg.mlp_dim, ('embed', 'mlp'), x)
        if cfg.mlp_act == 'silu':
            hidden = nn.silu(gate) * up
        elif cfg.mlp_act == 'gelu_tanh':   # Gemma GeGLU (tanh approx)
            hidden = nn.gelu(gate, approximate=True) * up
        else:
            raise ValueError(f'unknown mlp_act {cfg.mlp_act!r}')
        hidden = nn.with_logical_constraint(
            hidden, ('act_batch', 'act_seq', 'act_mlp'))
        out = proj('w_down', cfg.dim, ('mlp', 'embed'), hidden)
        return nn.with_logical_constraint(
            out, ('act_batch', 'act_seq', 'act_embed'))


class RMSNorm(nn.Module):
    cfg: LlamaConfig
    axis_name: str = 'embed'

    @nn.compact
    def __call__(self, x):
        # Zero-centered (Gemma) stores w and applies (1+w): identity at
        # init is w=0, so the init must flip with the convention.
        init = (nn.initializers.zeros_init()
                if self.cfg.norm_zero_centered else nn.initializers.ones)
        w = self.param(
            'weight',
            nn.with_logical_partitioning(init, (self.axis_name,)),
            (x.shape[-1],), jnp.dtype(self.cfg.param_dtype))
        return norms.rms_norm(x, w, eps=self.cfg.norm_eps,
                              zero_centered=self.cfg.norm_zero_centered)


class LlamaBlock(nn.Module):
    cfg: LlamaConfig

    @nn.compact
    def __call__(self, x, cos, sin, segment_ids=None, cache=None,
                 positions=None, lora_ids=None, lora_scale=None,
                 layer_idx=None):
        cfg = self.cfg
        attn_in = RMSNorm(cfg, name='attn_norm')(x)
        if cache is not None:
            attn_out, new_cache = LlamaAttention(cfg, name='attn')(
                attn_in, cos, sin, segment_ids, cache, positions,
                lora_ids=lora_ids, lora_scale=lora_scale,
                layer_idx=layer_idx)
        else:
            attn_out = LlamaAttention(cfg, name='attn')(
                attn_in, cos, sin, segment_ids,
                lora_ids=lora_ids, lora_scale=lora_scale,
                layer_idx=layer_idx)
            new_cache = None
        if cfg.sandwich_norms:   # Gemma-2: norm the residual branch
            attn_out = RMSNorm(cfg, name='attn_post_norm')(attn_out)
        x = x + attn_out
        mlp_out = LlamaMLP(cfg, name='mlp')(
            RMSNorm(cfg, name='mlp_norm')(x),
            lora_ids=lora_ids, lora_scale=lora_scale)
        if cfg.sandwich_norms:
            mlp_out = RMSNorm(cfg, name='mlp_post_norm')(mlp_out)
        x = x + mlp_out
        return (x, new_cache) if cache is not None else x


class LlamaModel(nn.Module):
    cfg: LlamaConfig

    @nn.compact
    def __call__(self, tokens, positions=None, segment_ids=None,
                 cache=None, logit_positions=None):
        """tokens: [B, S] int32 -> logits [B, S, vocab] (compute dtype).

        cache: optional {'k': [L,B,Sc,Hkv,Hd], 'v': ...} for incremental
        decoding (see infer/engine.py). With a cache, `positions` must be
        the global positions of `tokens` (per batch) and the return is
        (logits, new_cache).

        logit_positions: optional [B, P] — compute logits only at these
        token indices (prefill wants just the last position; the lm_head
        over a 128k vocab at every prompt position is ~20% of prefill
        FLOPs plus a [S, vocab] HBM write, all wasted)."""
        cfg = self.cfg
        dtype = jnp.dtype(cfg.dtype)
        b, s = tokens.shape
        embed = self.param(
            'tok_embed',
            nn.with_logical_partitioning(
                nn.initializers.normal(stddev=0.02), ('vocab', 'embed')),
            (cfg.vocab_size, cfg.dim), jnp.dtype(cfg.param_dtype))
        x = embed.astype(dtype)[tokens]
        if cfg.embed_scale:
            # Gemma scales embeddings by sqrt(dim); HF rounds the
            # normalizer to the compute dtype first — match that.
            x = x * jnp.asarray(cfg.dim ** 0.5, dtype)
        x = nn.with_logical_constraint(
            x, ('act_batch', 'act_seq', 'act_embed'))

        if positions is None:
            positions = rope.positions_from_segment_ids(segment_ids, b, s)
        cos, sin = rope.rope_freqs(
            positions, cfg.head_dim, cfg.rope_theta,
            use_llama31_scaling=cfg.use_llama31_rope)

        # Batched multi-LoRA (serving): apply() with a 'lora' collection
        # (stacked adapters, infer/lora.py build_stack) + a 'lora_ids'
        # pseudo-collection ({'ids': [B] int32}) routes every sequence
        # through its own adapter. Absent collections -> identical
        # trace to the plain model.
        lora_ids = lora_scale = None
        if self.has_variable('lora_ids', 'ids'):
            lora_ids = self.get_variable('lora_ids', 'ids')
            scaling = self.get_variable('lora', 'scaling')  # [n_adapters]
            lora_scale = jnp.take(scaling, lora_ids)        # [B]

        block = LlamaBlock
        if cfg.remat and cache is None:
            policy = (jax.checkpoint_policies.dots_saveable
                      if cfg.remat_policy == 'dots' else
                      jax.checkpoint_policies.save_only_these_names())
            block = nn.remat(
                LlamaBlock,
                policy=policy,
                prevent_cse=not cfg.scan_layers)
        new_cache = None
        # Paged decode: 'tables' is the per-slot block table shared by
        # every layer — kept OUT of the per-layer scan/stack (closure /
        # passthrough), while k/v are the per-layer page pools.
        tables = cache.get('tables') if cache is not None else None
        # Alternating-window models (Gemma-2) thread the layer index
        # through the scan as xs — the per-layer sliding/global choice
        # becomes traced arithmetic, keeping ONE scan body.
        need_idx = cfg.sliding_window > 0 and cfg.window_pattern > 1
        if cfg.scan_layers:
            if cache is not None:
                kv_cache = {'k': cache['k'], 'v': cache['v']}
                # int8-quantized paged pools carry per-layer scale
                # pools; they scan alongside k/v (paged_cache.py).
                quant_kv = 'k_scale' in cache
                if quant_kv:
                    kv_cache['k_scale'] = cache['k_scale']
                    kv_cache['v_scale'] = cache['v_scale']
                if need_idx:
                    kv_cache['idx'] = jnp.arange(cfg.n_layers)

                def body(mdl, carry, layer_cache):
                    lc = (layer_cache['k'], layer_cache['v'])
                    if tables is not None:
                        lc = lc + (tables,)
                        if 'k_scale' in layer_cache:
                            lc = lc + (layer_cache['k_scale'],
                                       layer_cache['v_scale'])
                    y, upd = mdl(carry, cos, sin, segment_ids, lc,
                                 positions, lora_ids=lora_ids,
                                 lora_scale=lora_scale,
                                 layer_idx=layer_cache.get('idx'))
                    out = {'k': upd[0], 'v': upd[1]}
                    if len(upd) == 4:
                        out['k_scale'] = upd[2]
                        out['v_scale'] = upd[3]
                    return y, out
                x, new_cache = nn.scan(
                    body,
                    variable_axes={'params': 0, 'lora': 0},
                    split_rngs={'params': True},
                    length=cfg.n_layers,
                    in_axes=0, out_axes=0,
                    metadata_params={nn.PARTITION_NAME: 'layers'},
                )(block(cfg, name='layers'), x, kv_cache)
                if tables is not None:
                    new_cache = {**new_cache, 'tables': tables}
            else:
                x, _ = nn.scan(
                    lambda mdl, carry, idx: (
                        mdl(carry, cos, sin, segment_ids,
                            lora_ids=lora_ids,
                            lora_scale=lora_scale,
                            layer_idx=idx), None),
                    variable_axes={'params': 0, 'lora': 0},
                    split_rngs={'params': True},
                    length=cfg.n_layers,
                    metadata_params={nn.PARTITION_NAME: 'layers'},
                )(block(cfg, name='layers'), x,
                  jnp.arange(cfg.n_layers) if need_idx else None)
        else:
            caches_out = []
            for i in range(cfg.n_layers):
                if cache is not None:
                    layer_cache = (cache['k'][i], cache['v'][i])
                    if tables is not None:
                        layer_cache = layer_cache + (tables,)
                        if 'k_scale' in cache:
                            layer_cache = layer_cache + (
                                cache['k_scale'][i], cache['v_scale'][i])
                    x, upd = block(cfg, name=f'layer_{i}')(
                        x, cos, sin, segment_ids, layer_cache, positions,
                        lora_ids=lora_ids, lora_scale=lora_scale,
                        layer_idx=i)
                    caches_out.append(upd)
                else:
                    x = block(cfg, name=f'layer_{i}')(
                        x, cos, sin, segment_ids,
                        lora_ids=lora_ids, lora_scale=lora_scale,
                        layer_idx=i)
            if cache is not None:
                new_cache = {
                    'k': jnp.stack([c[0] for c in caches_out]),
                    'v': jnp.stack([c[1] for c in caches_out]),
                }
                if caches_out and len(caches_out[0]) == 4:
                    new_cache['k_scale'] = jnp.stack(
                        [c[2] for c in caches_out])
                    new_cache['v_scale'] = jnp.stack(
                        [c[3] for c in caches_out])
                if tables is not None:
                    new_cache['tables'] = tables

        x = RMSNorm(cfg, name='final_norm')(x)
        if logit_positions is not None:
            x = jnp.take_along_axis(
                x, logit_positions[:, :, None], axis=1)
        if cfg.tie_embeddings:
            # Under the untied head's name, so that a profile finds the
            # head's product by it either way.
            with jax.named_scope('lm_head'):
                logits = jnp.einsum('bsd,vd->bsv', x, embed.astype(dtype))
        else:
            logits = _dense(cfg.vocab_size, ('embed', 'vocab'), 'lm_head',
                            cfg.param_dtype, dtype, cfg.quant)(x)
        if cfg.final_softcap > 0.0:   # Gemma-2 lm-head soft-cap
            cap = jnp.asarray(cfg.final_softcap, logits.dtype)
            logits = cap * jnp.tanh(logits / cap)
        logits = nn.with_logical_constraint(
            logits, ('act_batch', 'act_seq', 'act_vocab'))
        return (logits, new_cache) if cache is not None else logits
