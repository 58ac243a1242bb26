"""A decoder whose layers differ: one stack driven by a per-layer kind
table.

`LlamaModel` is one `nn.scan` over identical blocks, and a family is a
knob on that body. Here each layer names its token mixer ('attention':
`LlamaAttention` as it is; 'window_attention': the same with a static
sliding window and a rotary table of its own; 'conv': the gated short
convolution below) and its feed-forward ('dense': `LlamaMLP`;
'experts': the dropless `moe.RoutedExperts`), and the stack is
unrolled, each block under its own remat (which recomputes all of a
block but its experts' selection). LFM2-MoE
(huggingface.co/LiquidAI/LFM2-24B-A2B, `model_type` `lfm2_moe`) is the
first family written this way: 30 of its 40 layers have no attention
at all. Mellum2
(huggingface.co/JetBrains/Mellum2-12B-A2.5B-Instruct, `mellum`) is the
second: three window layers to every full one, YaRN on the full layers
only, every feed-forward routed, the output head untied. SDAR
(huggingface.co/JetLM/SDAR-30B-A3B-Chat, `sdar_moe`) is the third: the
Qwen3-MoE block in every layer, trained by block diffusion, which is a
property of the configuration (`block_diffusion`) and not of a flag:
the model then reads a row `[x_t | x_0]` of 2L positions under the
block-diffusion mask and gives logits for the L noised ones
(train/block_diffusion.py draws the noise and has the loss).

Training only: there is no cache argument. Serving a convolution-state
layer is ROADMAP work (the engine's cache holds K/V and nothing else).
"""
import contextlib
import dataclasses
from typing import Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from skypilot_tpu.models import llama as llama_lib
from skypilot_tpu.models import moe as moe_lib
from skypilot_tpu.ops import rope

OPERATORS = ('attention', 'window_attention', 'conv')
FEED_FORWARDS = ('dense', 'experts')


@dataclasses.dataclass(frozen=True)
class BlockDiffusion:
    """The block-diffusion objective (SDAR, arXiv:2510.06303; the
    vectorised pass is BD3-LM's, arXiv:2503.09573): blocks of
    `block_length` positions, each noised at its own level t, a
    position masked with probability t (replaced by the mask's id, the
    last row of the vocabulary held: `HybridConfig.mask_id`), the loss
    weighted 1 / t (the linear schedule; train/block_diffusion.py)."""
    block_length: int


@dataclasses.dataclass(frozen=True)
class HybridConfig:
    """`base` carries every width the shared modules read (hidden size,
    heads, the dense feed-forward's `mlp_dim`, norms, rope, vocabulary,
    dtypes, remat); `layers` is the kind table, one (operator,
    feed-forward) pair per layer. `window` is what a 'window_attention'
    layer sees (the query's own position and the window - 1 before it);
    `yarn` scales the rotary table of the 'attention' layers, and the
    window layers keep the plain one. `block_diffusion`, where given,
    is the objective the model is trained under: its 'attention' layers
    then run the block-diffusion mask over rows of 2L positions and the
    head is applied to the first L."""
    base: llama_lib.LlamaConfig
    layers: Tuple[Tuple[str, str], ...]
    conv_kernel: int = 3
    experts: Optional[moe_lib.ExpertsConfig] = None
    window: int = 0
    yarn: Optional[rope.Yarn] = None
    block_diffusion: Optional[BlockDiffusion] = None

    def __post_init__(self):
        for op, ffn in self.layers:
            if op not in OPERATORS or ffn not in FEED_FORWARDS:
                raise ValueError(f'unknown layer kind {(op, ffn)!r}')
        if self.experts is None and any(
                ffn == 'experts' for _, ffn in self.layers):
            raise ValueError('expert layers need an ExpertsConfig')
        if self.window <= 0 and any(
                op == 'window_attention' for op, _ in self.layers):
            raise ValueError('window layers need a window')
        if self.block_diffusion is not None and any(
                op != 'attention' for op, _ in self.layers):
            raise ValueError('block diffusion needs attention layers only')

    # What sft reads of a model's configuration.
    @property
    def vocab_size(self) -> int:
        return self.base.vocab_size

    @property
    def dim(self) -> int:
        return self.base.dim

    @property
    def n_layers(self) -> int:
        return len(self.layers)

    @property
    def mask_id(self) -> int:
        """Under block diffusion, the id a masked position holds: a row
        of the vocabulary held that no data id reaches, the last one."""
        return self.vocab_size - 1

    @property
    def data_vocab_size(self) -> int:
        """Ids the data may hold: those under the mask's under block
        diffusion."""
        return self.vocab_size - (self.block_diffusion is not None)

    def num_params(self) -> int:
        """Analytic parameter count of what this configuration holds."""
        c, d = self.base, self.base.dim
        attn = 2 * d * c.n_heads * c.head_dim + \
            2 * d * c.n_kv_heads * c.head_dim + \
            (2 * c.head_dim if c.qk_norm else 0)
        conv = 4 * d * d + self.conv_kernel * d
        ex = self.experts
        ffn = {'dense': 3 * d * c.mlp_dim,
               'experts': 0 if ex is None else
               ex.num_held * 3 * d * ex.mlp_dim + d * ex.num_experts +
               (ex.num_experts if ex.scoring == 'sigmoid_bias' else 0)}
        mix = {'attention': attn, 'window_attention': attn, 'conv': conv}
        return sum(mix[op] + ffn[f] + 2 * d for op, f in self.layers) + \
            c.vocab_size * d * (1 if c.tie_embeddings else 2) + d


class ShortConv(nn.Module):
    """LFM2's gated short convolution: `[B, C, u] = split3(W_in x)`,
    `y = W_out (C * causalconv(B * u))`, `causalconv` a depthwise
    convolution over the sequence, one `kernel`-tap filter a channel,
    left-padded so that position t sees t-kernel+1..t; no bias, no
    activation. Taps do not reach across a packed sequence's boundary."""
    cfg: llama_lib.LlamaConfig
    kernel: int = 3

    @nn.compact
    def __call__(self, x, segment_ids=None):
        cfg = self.cfg
        dtype, pdtype = jnp.dtype(cfg.dtype), jnp.dtype(cfg.param_dtype)
        d = cfg.dim
        # One d -> 3d product; the 3 is an axis of its own so that a
        # tensor-parallel split of the channels lines B, C and u up.
        w_in = self.param('w_in', nn.with_logical_partitioning(
            nn.initializers.lecun_normal(in_axis=0, out_axis=(1, 2)),
            ('embed', None, 'mlp')), (d, 3, d), pdtype)
        filt = self.param('conv', nn.with_logical_partitioning(
            nn.initializers.lecun_normal(in_axis=0, out_axis=1),
            (None, 'mlp')), (self.kernel, d), pdtype)
        bcu = jnp.einsum('bsd,dcf->bscf', x.astype(dtype),
                         w_in.astype(dtype))
        with jax.named_scope('short_conv'):
            gate_b, gate_c, u = (bcu[:, :, i].astype(jnp.float32)
                                 for i in range(3))
            z = gate_b * u
            taps = filt.astype(jnp.float32)
            s = z.shape[1]
            y = taps[-1] * z
            for back in range(1, self.kernel):
                shifted = jnp.pad(z, ((0, 0), (back, 0), (0, 0)))[:, :s]
                if segment_ids is not None:
                    same = segment_ids == jnp.pad(
                        segment_ids, ((0, 0), (back, 0)),
                        constant_values=-1)[:, :s]
                    shifted = jnp.where(same[..., None], shifted, 0.0)
                y = y + taps[-1 - back] * shifted
            y = (gate_c * y).astype(dtype)
        y = nn.with_logical_constraint(
            y, ('act_batch', 'act_seq', 'act_mlp'))
        out = llama_lib._dense(d, ('mlp', 'embed'), 'w_out',
                               cfg.param_dtype, dtype)(y)
        return nn.with_logical_constraint(
            out, ('act_batch', 'act_seq', 'act_embed'))


class HybridBlock(nn.Module):
    """`h = x + operator(norm(x))`, `y = h + ffn(norm(h))`; returns
    (y, the expert layer's stats or zeros)."""
    cfg: HybridConfig
    kind: Tuple[str, str]

    @nn.compact
    def __call__(self, x, cos, sin, segment_ids=None):
        base = self.cfg.base
        if self.cfg.block_diffusion is not None:
            # What the layers read of the objective: the mask of the
            # attention.
            base = dataclasses.replace(
                base,
                attn_block_diffusion=self.cfg.block_diffusion.block_length)
        operator, ffn = self.kind
        h = llama_lib.RMSNorm(base, name='op_norm')(x)
        if operator == 'attention':
            h = llama_lib.LlamaAttention(base, name='attn')(
                h, cos, sin, segment_ids)
        elif operator == 'window_attention':
            # A window of the layer's own, static: no traced gate, so
            # the flash kernels take it (ops/attention.py).
            h = llama_lib.LlamaAttention(dataclasses.replace(
                base, sliding_window=self.cfg.window), name='attn')(
                    h, cos, sin, segment_ids)
        else:
            h = ShortConv(base, self.cfg.conv_kernel, name='conv')(
                h, segment_ids)
        x = x + h
        h = llama_lib.RMSNorm(base, name='ffn_norm')(x)
        if ffn == 'dense':
            h, stats = llama_lib.LlamaMLP(base, name='mlp')(h), \
                jnp.zeros((5,), jnp.int32)
        else:
            h, stats = moe_lib.RoutedExperts(
                base, self.cfg.experts, name='experts')(h)
        return x + h, stats


class HybridModel(nn.Module):
    cfg: HybridConfig

    @nn.compact
    def __call__(self, tokens, positions=None, segment_ids=None,
                 loss_of=None):
        """tokens: [B, S] int32 -> logits [B, S, vocab] (compute dtype).
        Sows `moe_stats`, the routing counters of trainer.MOE_STAT_KEYS
        over all expert layers, for the trainer's step metrics. Under
        block diffusion the row is `[x_t | x_0]` (S = 2L, `positions`
        the same ids for both halves) and the logits are [B, L, vocab],
        of the noised half; `loss_of`, where given, is applied to them
        beside the head and its result returned in their place, so that
        head and loss stand under one scope of a trace."""
        cfg, base = self.cfg, self.cfg.base
        dtype = jnp.dtype(base.dtype)
        b, s = tokens.shape
        # A tied embedding is also the output head and starts small. An
        # untied one starts at unit variance, the scale every later
        # input of a block has: at 0.02 the first attention layer's
        # output (the mean of a window of value vectors, 0.04-0.08 rms)
        # outweighs the token's own row, every token of a sequence
        # looks alike to the first router, and the routing follows the
        # sequence's noise (fullest expert 3.4-3.9 times the mean, the
        # pairs to a quarter of the experts 71,000-149,000 a step where
        # an even router sends 131,072; PERF.md §6, PR 33).
        embed = self.param(
            'tok_embed',
            nn.with_logical_partitioning(nn.initializers.normal(
                stddev=0.02 if base.tie_embeddings else 1.0),
                ('vocab', 'embed')),
            (base.vocab_size, base.dim), jnp.dtype(base.param_dtype))
        x = embed.astype(dtype)[tokens]
        x = nn.with_logical_constraint(
            x, ('act_batch', 'act_seq', 'act_embed'))
        if positions is None:
            positions = rope.positions_from_segment_ids(segment_ids, b, s)
        # A rotary table by operator kind: the window layers' is the
        # plain rule whatever scales the full layers'.
        operators = {op for op, _ in cfg.layers}
        tables = {op: rope.rope_freqs(
            positions, base.head_dim, base.rope_theta,
            use_llama31_scaling=base.use_llama31_rope,
            yarn=cfg.yarn if op == 'attention' else None)
            for op in ('attention', 'window_attention') if op in operators}
        # Recompute everything but which experts each token chose
        # (moe.route has why).
        block = nn.remat(
            HybridBlock, policy=jax.checkpoint_policies.save_only_these_names(
                moe_lib.SELECTED)) if base.remat else HybridBlock
        routed = fullest = dropped = rows = worst = jnp.zeros(
            (), jnp.int32)
        for i, kind in enumerate(cfg.layers):
            x, stats = block(cfg, kind, name=f'layer_{i}')(
                x, *tables.get(kind[0], (None, None)), segment_ids)
            routed, fullest, dropped, rows, worst = (
                routed + stats[0], jnp.maximum(fullest, stats[1]),
                dropped + stats[2], rows + stats[3], worst + stats[4])
        if cfg.experts is not None:
            ex = cfg.experts
            pairs = b * s * ex.experts_per_token
            self.sow('intermediates', 'moe_stats', {
                'moe_pairs_held': routed,
                'moe_pairs': jnp.int32(pairs * sum(
                    ffn == 'experts' for _, ffn in cfg.layers)),
                'moe_fullest_over_mean':
                    fullest * (ex.num_experts / pairs),
                'moe_pairs_dropped': dropped,
                'moe_rows': rows, 'moe_rows_worst': worst})
        with contextlib.ExitStack() as scopes:
            if cfg.block_diffusion is not None:
                # The objective's part of the pass: the head over the
                # noised half alone, and the loss the caller gives
                # (train/block_diffusion.loss_given_noise).
                scopes.enter_context(jax.named_scope('bd_objective'))
                scopes.enter_context(jax.named_scope('bd_loss'))
                x = x[:, :s // 2]
            x = llama_lib.RMSNorm(base, name='final_norm')(x)
            if base.tie_embeddings:
                with jax.named_scope('lm_head'):   # as the untied head
                    logits = jnp.einsum('bsd,vd->bsv', x,
                                        embed.astype(dtype))
            else:
                logits = llama_lib._dense(
                    base.vocab_size, ('embed', 'vocab'), 'lm_head',
                    base.param_dtype, dtype)(x)
            logits = nn.with_logical_constraint(
                logits, ('act_batch', 'act_seq', 'act_vocab'))
            return logits if loss_of is None else loss_of(logits)


def _lfm2(layer_types, num_dense, experts, **base):
    """LFM2-MoE's layout: `layer_types` names each layer's operator;
    the first `num_dense` feed-forwards are dense SwiGLU, the rest
    routed experts."""
    op = {'conv': 'conv', 'full_attention': 'attention'}
    return HybridConfig(
        base=llama_lib.LlamaConfig(
            use_llama31_rope=False, norm_eps=1e-5, tie_embeddings=True,
            qk_norm=True, **base),
        layers=tuple((op[t], 'dense' if i < num_dense else 'experts')
                     for i, t in enumerate(layer_types)),
        conv_kernel=3, experts=experts)


def _mellum(layer_types, experts, window, yarn, **base):
    """Mellum2's layout: `layer_types` names each layer's attention
    (`sliding_attention`: a window and the plain rotary table;
    `full_attention`: YaRN); every feed-forward is routed experts
    (`mlp_layer_types` all `sparse`); per-head q/k norm, head size
    given beside the hidden size, the output head untied."""
    op = {'sliding_attention': 'window_attention',
          'full_attention': 'attention'}
    return HybridConfig(
        base=llama_lib.LlamaConfig(
            use_llama31_rope=False, norm_eps=1e-6, tie_embeddings=False,
            qk_norm=True, **base),
        layers=tuple((op[t], 'experts') for t in layer_types),
        experts=experts, window=window, yarn=yarn)


def _sdar(n_layers, experts, block_length, **base):
    """SDAR-MoE's layout: the Qwen3-MoE block in every layer (full
    attention with per-head q/k norm, routed experts; `mlp_only_layers`
    empty, `decoder_sparse_step` 1), the output head untied, the plain
    rotary rule; trained by block diffusion."""
    return HybridConfig(
        base=llama_lib.LlamaConfig(
            use_llama31_rope=False, norm_eps=1e-6, tie_embeddings=False,
            qk_norm=True, **base),
        layers=(('attention', 'experts'),) * n_layers, experts=experts,
        block_diffusion=BlockDiffusion(block_length))


_LFM2_PERIOD = ('full_attention', 'conv', 'conv', 'conv')
_MELLUM2_PERIOD = ('sliding_attention',) * 3 + ('full_attention',)
_MELLUM2_12B = dict(
    vocab_size=98304, dim=2304, n_heads=32, n_kv_heads=4,
    head_dim_override=128, mlp_dim=7168, max_seq_len=131072,
    rope_theta=5e5)
_MELLUM2_YARN = rope.Yarn(factor=16.0, original_max_position=8192,
                          beta_fast=32.0, beta_slow=1.0,
                          attention_factor=1.2772588722239782)
_SDAR_30B = dict(vocab_size=151936, dim=2048, n_heads=32, n_kv_heads=4,
                 head_dim_override=128, mlp_dim=6144, max_seq_len=32768,
                 rope_theta=1e6)
_LFM2_24B = dict(vocab_size=65536, dim=2048, n_heads=32, n_kv_heads=8,
                 mlp_dim=11776, max_seq_len=128000, rope_theta=1e6)

CONFIGS = {
    # Tests: the structure of LFM2-MoE at toy widths (a leading dense
    # conv layer, then attention and conv expert layers).
    'debug-lfm2': _lfm2(
        ('conv', 'full_attention', 'conv'), 1,
        moe_lib.ExpertsConfig(16, 4, 48, scoring='sigmoid_bias'),
        vocab_size=256, dim=64, n_heads=4, n_kv_heads=2, mlp_dim=128,
        max_seq_len=128, rope_theta=1e6, dtype='float32', remat=False),
    # LFM2-24B-A2B as published (config.json): 40 layers, the first two
    # dense; 64 experts of width 1,536, four a token; head size 64.
    'lfm2-24b-a2b': _lfm2(
        ('conv', 'conv') + _LFM2_PERIOD * 9 + ('full_attention', 'conv'),
        2, moe_lib.ExpertsConfig(64, 4, 1536, scoring='sigmoid_bias'),
        **_LFM2_24B),
    # One chip's share of it with eight chips sharing each layer
    # (chipbench/configs/lfm2-24b-a2b-ep8-sft.json): experts 0-7 of 64,
    # an eighth of the vocabulary, one leading dense layer and two
    # whole periods of the layers after it (published layers 2-9).
    'lfm2-24b-a2b-ep8': _lfm2(
        ('conv',) + _LFM2_PERIOD * 2, 1,
        moe_lib.ExpertsConfig(64, 4, 1536, scoring='sigmoid_bias',
                              held=(0, 8)),
        **{**_LFM2_24B, 'vocab_size': 8192}),
    # Tests: the structure of Mellum2 at toy widths (three window
    # layers of 8 and a full layer under YaRN, whose ramp over the 16
    # pairs of a head runs from pair 1 to pair 7; all routed experts;
    # an untied head).
    'debug-mellum2': _mellum(
        _MELLUM2_PERIOD,
        moe_lib.ExpertsConfig(16, 4, 48, scoring='softmax'), 8,
        rope.Yarn(factor=4.0, original_max_position=16, beta_fast=1.0,
                  beta_slow=0.05),
        vocab_size=256, dim=64, n_heads=4, n_kv_heads=2,
        head_dim_override=32, mlp_dim=128, max_seq_len=128,
        rope_theta=1e4, dtype='float32', remat=False),
    # Mellum2-12B-A2.5B-Instruct as published (config.json): 28 layers,
    # window 1,024 in three of every four; 64 experts of width 896,
    # eight a token, softmax-routed; head size 128 at hidden 2,304.
    # `intermediate_size` (mlp_dim) is published and unused: no layer
    # is dense.
    'mellum2-12b-a2.5b': _mellum(
        _MELLUM2_PERIOD * 7,
        moe_lib.ExpertsConfig(64, 8, 896, scoring='softmax'), 1024,
        _MELLUM2_YARN, **_MELLUM2_12B),
    # One chip's share of it with four chips sharing each layer
    # (chipbench/configs/mellum2-12b-a2.5b-ep4-sft.json): experts 0-15
    # of 64, a quarter of each vocabulary matrix, one whole period
    # (published layers 0-3).
    'mellum2-12b-a2.5b-ep4': _mellum(
        _MELLUM2_PERIOD,
        moe_lib.ExpertsConfig(64, 8, 896, scoring='softmax',
                              held=(0, 16)), 1024,
        _MELLUM2_YARN, **{**_MELLUM2_12B, 'vocab_size': 24576}),
    # Tests: the structure of SDAR-MoE at toy widths (two layers of full
    # attention and softmax-routed experts, an untied head, blocks of 4).
    'debug-sdar': _sdar(
        2, moe_lib.ExpertsConfig(16, 4, 48, scoring='softmax'), 4,
        vocab_size=256, dim=64, n_heads=4, n_kv_heads=2,
        head_dim_override=32, mlp_dim=128, max_seq_len=128,
        rope_theta=1e6, dtype='float32', remat=False),
    # SDAR-30B-A3B-Chat as published (config.json): 48 layers, 128
    # experts of width 768, eight a token, softmax-routed; head size 128
    # at hidden 2,048. `intermediate_size` (mlp_dim) is published and
    # unused: no layer is dense. The block length is the family's
    # (config.json has no key for it).
    'sdar-30b-a3b': _sdar(
        48, moe_lib.ExpertsConfig(128, 8, 768, scoring='softmax'), 4,
        **_SDAR_30B),
    # One chip's share of it with eight chips sharing each layer
    # (chipbench/configs/sdar-30b-a3b-ep8-sft.json): experts 0-15 of
    # 128, an eighth of each vocabulary matrix, six layers.
    'sdar-30b-a3b-ep8': _sdar(
        6, moe_lib.ExpertsConfig(128, 8, 768, scoring='softmax',
                                 held=(0, 16)), 4,
        **{**_SDAR_30B, 'vocab_size': 18992}),
}
