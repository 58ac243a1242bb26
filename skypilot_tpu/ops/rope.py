"""Rotary position embeddings (RoPE), including the Llama-3.1 frequency
scaling and YaRN (HF's, and DeepSeek-V2's with its softmax scale). Pure
function of (positions, head_dim); computed in f32 and applied via the
split-half rotation (the HF/Llama convention), or to adjacent pairs
(`apply_rope_pairs`, DeepSeek-V2's layout).
"""
import dataclasses
import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class Yarn:
    """YaRN (arXiv:2309.00071) as HF's `rope_type: yarn` computes it:
    the keys of a config.json's `rope_parameters` entry.
    `original_max_position` is the length the model was trained at
    before the extension by `factor`; `attention_factor` None is the
    paper's 0.1 ln(factor) + 1."""
    factor: float
    original_max_position: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    attention_factor: Optional[float] = None

    @property
    def scale(self) -> float:
        """What cos and sin are both multiplied by, at every position."""
        if self.attention_factor is not None:
            return self.attention_factor
        return 0.1 * math.log(self.factor) + 1.0

    def correction_range(self, head_dim: int,
                         theta: float) -> Tuple[int, int]:
        """(low, high): the pairs j of the head below `low` keep their
        frequency (they turn more than `beta_fast` times over the
        original length), those above `high` (fewer than `beta_slow`
        turns) have it divided by `factor`; a linear ramp between."""
        def pair(turns: float) -> float:
            return head_dim * math.log(self.original_max_position / (
                turns * 2 * math.pi)) / (2 * math.log(theta))
        return (max(math.floor(pair(self.beta_fast)), 0),
                min(math.ceil(pair(self.beta_slow)), head_dim - 1))

    @property
    def softmax_factor(self) -> float:
        """What the attention's softmax scale is multiplied by: nothing,
        where the factor stands on cos and sin."""
        return 1.0


def yarn_mscale(factor: float, mscale: float) -> float:
    """DeepSeek's `yarn_get_mscale`: 0.1 mscale ln(factor) + 1 (1 where
    the table is not stretched)."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


@dataclasses.dataclass(frozen=True)
class DeepSeekYarn(Yarn):
    """YaRN as DeepSeek-V2's modeling code computes it (config.json's
    `rope_scaling` of `type` yarn with `mscale` and `mscale_all_dim`):
    the frequencies are `Yarn`'s ramp, cos and sin carry
    yarn_mscale(factor, mscale) / yarn_mscale(factor, mscale_all_dim),
    and the softmax scale yarn_mscale(factor, mscale_all_dim)^2, which
    the attention applies (Yarn's `attention_factor` is not read)."""
    mscale: float = 1.0
    mscale_all_dim: float = 0.0

    @property
    def scale(self) -> float:
        return yarn_mscale(self.factor, self.mscale) / \
            yarn_mscale(self.factor, self.mscale_all_dim)

    @property
    def softmax_factor(self) -> float:
        return yarn_mscale(self.factor, self.mscale_all_dim) ** 2


def inv_freqs(head_dim: int, theta: float,
              yarn: Optional[Yarn] = None) -> jax.Array:
    """The head_dim // 2 rotary frequencies: theta^(-2j/head_dim), and
    under YaRN that mixed with its `factor`-th by the ramp."""
    freqs = 1.0 / (theta ** (jnp.arange(0, head_dim, 2,
                                        dtype=jnp.float32) / head_dim))
    if yarn is None:
        return freqs
    low, high = yarn.correction_range(head_dim, theta)
    ramp = jnp.clip(
        (jnp.arange(head_dim // 2, dtype=jnp.float32) - low) /
        (high - low if high != low else 0.001), 0.0, 1.0)
    return (1.0 - ramp) * freqs + ramp * freqs / yarn.factor


@functools.partial(jax.jit, static_argnames=('head_dim', 'theta',
                                             'use_llama31_scaling', 'yarn'))
def rope_freqs(positions: jax.Array, head_dim: int,
               theta: float = 500000.0,
               use_llama31_scaling: bool = False,
               yarn: Optional[Yarn] = None):
    """Return (cos, sin) of shape positions.shape + (head_dim//2,).
    Under `yarn` both carry its `scale`."""
    freqs = inv_freqs(head_dim, theta, yarn)
    if use_llama31_scaling:
        # Llama-3.1 long-context NTK-by-parts scaling (factor 8, original
        # context 8192), reference implementation in Meta's llama3 repo.
        factor, low_mult, high_mult, old_ctx = 8.0, 1.0, 4.0, 8192
        low = old_ctx / low_mult
        high = old_ctx / high_mult
        wavelen = 2.0 * jnp.pi / freqs
        smooth = jnp.clip((old_ctx / wavelen - low_mult) /
                          (high_mult - low_mult), 0.0, 1.0)
        scaled = jnp.where(wavelen > low, freqs / factor, freqs)
        mid = (1.0 - smooth) * freqs / factor + smooth * freqs
        in_mid = (wavelen <= low) & (wavelen >= high)
        freqs = jnp.where(in_mid, mid, scaled)
    angles = positions.astype(jnp.float32)[..., None] * freqs
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    if yarn is not None:
        cos, sin = cos * yarn.scale, sin * yarn.scale
    return cos, sin


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """x: [..., seq, heads, head_dim]; cos/sin: [..., seq, head_dim//2]."""
    dtype = x.dtype
    x = x.astype(jnp.float32)
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    # broadcast cos/sin over the heads axis
    c = cos[..., None, :]
    s = sin[..., None, :]
    out = jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)
    return out.astype(dtype)


def apply_rope_pairs(x: jax.Array, cos: jax.Array,
                     sin: jax.Array) -> jax.Array:
    """The rotation of adjacent pairs (2j, 2j + 1), pair j by angle j
    (DeepSeek-V2's published layout): x de-interleaved into its even and
    odd members, then `apply_rope`. The result holds the pairs' first
    members in one half and their second in the other, as DeepSeek-V2's
    modeling code leaves them; where q and k both take it, their dot
    products are those of the pairs rotated in place."""
    return apply_rope(jnp.concatenate([x[..., 0::2], x[..., 1::2]], -1),
                      cos, sin)


def positions_from_segment_ids(
        segment_ids: Optional[jax.Array], batch: int,
        seq: int) -> jax.Array:
    """Default positions 0..seq-1 per example (packing-aware later)."""
    if segment_ids is None:
        return jnp.broadcast_to(jnp.arange(seq), (batch, seq))
    # restart positions at each segment boundary
    def per_example(seg):
        def step(carry, s):
            prev_seg, pos = carry
            pos = jnp.where(s == prev_seg, pos + 1, 0)
            return (s, pos), pos
        (_, _), out = jax.lax.scan(step, (seg[0], -1), seg)
        return out
    return jax.vmap(per_example)(segment_ids)
