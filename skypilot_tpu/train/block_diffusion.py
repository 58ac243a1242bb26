"""The block-diffusion training objective (SDAR, arXiv:2510.06303; the
vectorised pass and its mask are BD3-LM's, arXiv:2503.09573 section 3).

A row x_0 of L token ids is cut into blocks of B positions. Each block
draws its own noise level t ~ U(t_min, 1]; each of its positions is
masked (replaced by the mask id) with probability t, independently. The
model reads `[x_t | x_0]`, 2L positions whose two halves share the
position ids 0..L-1, under the block-diffusion mask
(ops/attention.block_diffusion_allowed): a noised block sees itself and
the clean blocks before it, so all blocks are trained in one pass. The
logits of the L noised positions are for x_0 at the same position (no
shift), and the loss is the linear schedule's NELBO,
`(1 / L) sum_i m_i (1 / t_b(i)) (-log softmax(logits_i)[x_0[i]])`.

Three functions, apart on purpose: `noise` draws (x_t, m, t) from a key,
`model_inputs` builds the 2L row, `weighted_loss` is the loss given
(logits, x_0, m, t); `loss_given_noise` strings the last two round a
model, so that a check hands the program and a reference the same noise
(`step_noise` is `noise` as the train step calls it).
Which model trains under it is the model's configuration's to say
(models/hybrid.BlockDiffusion, with the block length; the mask's id is
the configuration's `mask_id`); trainer.make_train_step asks it.
"""
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from skypilot_tpu.ops import dispatch

# What a block-diffusion step reports beside the loss: of `bd_targets`
# positions, `bd_masked` were masked and count in the loss, at the mean
# weight `bd_weight_mean` (the mean of 1 / t over them).
BD_STAT_KEYS = ('bd_masked', 'bd_targets', 'bd_weight_mean')

# A train step's noise is drawn from PRNGKey(NOISE_KEY) folded with the
# state's step (trainer.make_train_step): a function of the step alone.
NOISE_KEY = 0

# A block's level is drawn from U(T_MIN, 1]: BD3-LM's widest range. The
# weight 1 / t of a masked position is then at most a thousand.
T_MIN = 1e-3


def objective_of(model) -> Any:
    """The configuration of a model that trains by block diffusion
    (`block_diffusion.block_length`, `mask_id`), or None where the model
    trains next-token."""
    cfg = getattr(model, 'cfg', None)
    return cfg if getattr(cfg, 'block_diffusion', None) else None


def format_stats(host: Dict[str, float]) -> str:
    """The objective's counters as they stand on sft's step line ('' for
    a next-token model)."""
    if 'bd_masked' not in host:
        return ''
    return ' bd_masked={:.0f}/{:.0f} bd_weight_mean={:.3f}'.format(
        host['bd_masked'], host['bd_targets'], host['bd_weight_mean'])


def noise(x0: jax.Array, key: jax.Array, cfg) -> Tuple[jax.Array, ...]:
    """x0 [rows, L] ids -> (x_t [rows, L], m [rows, L] bool, t [rows, L]
    float32, a block's level at each of its positions), for the model
    configuration `cfg` (`objective_of`)."""
    rows, length = x0.shape
    block = cfg.block_diffusion.block_length
    if length % block:
        raise ValueError(f'a row of {length} ids is no whole number of '
                         f'blocks of {block}')
    k_t, k_m = jax.random.split(key)
    # 1 - U[0, 1) lies in (0, 1]: t in (T_MIN, 1].
    t = T_MIN + (1.0 - T_MIN) * (1.0 - jax.random.uniform(
        k_t, (rows, length // block), jnp.float32))
    t = jnp.repeat(t, block, axis=1)
    m = jax.random.uniform(k_m, (rows, length), jnp.float32) < t
    return jnp.where(m, jnp.int32(cfg.mask_id), x0), m, t


def model_inputs(x_t: jax.Array, x0: jax.Array) -> Tuple[jax.Array, ...]:
    """(tokens [rows, 2L], positions [rows, 2L]): the noised half first,
    both halves at the position ids 0..L-1."""
    ids = jnp.broadcast_to(jnp.arange(x0.shape[1], dtype=jnp.int32),
                           x0.shape)
    return (jnp.concatenate([x_t, x0], axis=1),
            jnp.concatenate([ids, ids], axis=1))


def weighted_loss(logits: jax.Array, x0: jax.Array, m: jax.Array,
                  t: jax.Array) -> jax.Array:
    """The mean over all rows' positions of m / t times the
    cross-entropy of logits [rows, L, vocab] against x0, in float32."""
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    nll = -jnp.take_along_axis(logp, x0[..., None], axis=-1)[..., 0]
    return jnp.mean(jnp.where(m, nll / t, 0.0))


def loss_given_noise(model, params, x0, x_t, m, t):
    """(loss, what the model sowed) of `model` on rows x0 noised to x_t
    with the mask m at the levels t. The model applies the loss to its
    logits itself, under the scope its head stands under
    (`bd_objective/bd_loss`, models/hybrid.py)."""
    tokens, positions = model_inputs(x_t, x0)
    return model.apply(
        {'params': params}, tokens, positions=positions,
        loss_of=lambda logits: weighted_loss(logits, x0, m, t),
        mutable=['intermediates'])


def step_noise(x0: jax.Array, key: jax.Array, cfg):
    """One step's (x_t, m, t, stats) from `key`, under the objective's
    scope; records the objective's plan (dispatch.record_bd_plan)."""
    from skypilot_tpu.ops import flash_attention
    rows, length = x0.shape
    block = cfg.block_diffusion.block_length
    dispatch.record_bd_plan({
        'block': block, 'data': length, 'positions': 2 * length,
        'allowed_pairs': flash_attention.allowed_pairs(length, block),
        'mask_id': cfg.mask_id})
    with jax.named_scope('bd_objective'), jax.named_scope('bd_noise'):
        x_t, m, t = noise(x0, key, cfg)
        masked = jnp.sum(m)
        stats = {'bd_masked': masked,
                 'bd_targets': jnp.int32(rows * length),
                 'bd_weight_mean': jnp.sum(jnp.where(m, 1.0 / t, 0.0)) /
                 jnp.maximum(masked, 1)}
    return x_t, m, t, stats
