"""The correctness child of a `bd_moe_train` cell: the program's model
under its block-diffusion objective (bf16, the flash kernels under the
block-diffusion mask, the grouped products' rung the run used) against
the configuration's plain float32 reference at the highest matmul
precision, on seeded weights, seeded rows of the cell's traffic and
seeded noise handed to both, at the widths and depth the cell runs, on
the device the cell ran on, after the measured child has gone.

    python chipbench/children/bd_moe_check_child.py '<json spec>'

spec: preset, sizes (the configuration's sizes, for the reference),
reference (module under references/), seed, rows, seq (the data's
length L). Five more keys are for reading what a fault or a cause reads
(PERF.md has the readings; no cell sets them): `program_weight_bits`,
[exponent, mantissa] bits the program's weights are rounded to ([4, 3]
is float8_e4m3, the nearest format below bfloat16's [8, 7]);
`reference_sizes`, sizes the reference is given instead; `fault`, one
of FAULTS: the reference computed with one of its rules replaced by a
plausible wrong one (the reference's rules are small named functions
for this); `program_dtype`, the dtype the program computes in instead
of the preset's ('float32', then at the highest matmul precision:
what of a reading is the program's rounding goes); and `level_floor`,
which raises the levels t handed to both sides to at least that (the
weights 1 / t are then at most its inverse). `prepare` makes what the
comparisons of one spec share (the weights, the rows, the noise, the
program's results) and `report` runs the reference against it, so that
a reader of several faults prepares once.

The noise (which positions are masked, each block's level) is drawn
once, by the program's own `block_diffusion.noise`, from a key made of
--seed; the program is given (x_0, x_t, m, t) and the reference
(x_0, m, t): it makes its own x_t, its own mask and its own weights.
Since a fault in the draw would reach both sides, the report says of
(m, t) alone what the draw has to be (`noise`: one level a block, the
levels' range, the masked count beside the sum of the levels and its
standard deviation), and the driver holds it to the configuration.

What is compared, and why three things, is children/moe_check_child.py's
(a bf16 activation can flip a near-tied last choice of the router, after
which the two models compute different functions): routing layer by
layer with the reference given the program's selections, over all 2L
positions, with the `deficit` of every selection the reference does not
make (in units of the router's softmax); the loss against the reference
routing freely; the gradients, whole tree and worst leaf, against the
reference given the program's selections; beside the kernel rungs taken
(attention's under `flash_block_diffusion_attention`), the tile plans,
the dtypes of the parameters and of the optimizer state the trainer
would build, and the pairs routed to held experts by both.
"""
import importlib
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))


def _faults(jnp):
    """name -> (the reference's rule it replaces, the wrong rule)."""
    def causal(p, r, length, block):
        return r <= p

    def leak(p, r, length, block):
        n_p, n_r = p < length, r < length
        b_p, b_r = (p % length) // block, (r % length) // block
        return (n_p & n_r & (b_p == b_r)) | (n_p & ~n_r & (b_r <= b_p)) | \
            (~n_p & ~n_r & (b_r <= b_p))

    def noised_see_noised(p, r, length, block):
        n_p, n_r = p < length, r < length
        b_p, b_r = (p % length) // block, (r % length) // block
        return (n_p & n_r & (b_r <= b_p)) | (n_p & ~n_r & (b_r < b_p)) | \
            (~n_p & ~n_r & (b_r <= b_p))
    return {
        'causal_mask': ('allowed', causal),
        'leak': ('allowed', leak),
        'noised_see_noised': ('allowed', noised_see_noised),
        'no_weight': ('token_weights',
                      lambda m, t: m.astype(jnp.float32)),
        'positions_0_to_2L': ('position_ids',
                              lambda length: jnp.arange(2 * length)),
        'shifted_logits': ('targets', lambda x0: jnp.roll(x0, -1)),
    }


def _noise_facts(masked, level, block: int) -> dict:
    """What the draw is, from (m, t) alone: a block has one level, the
    levels lie in a range, and the positions are masked with
    probability t (the count beside its expectation sum(t) and its
    standard deviation sqrt(sum(t (1 - t))))."""
    import numpy as np
    masked = np.asarray(masked)
    level = np.asarray(level, np.float64)
    blocks = level.reshape(level.shape[0], -1, block)
    return {'one_level_a_block': bool((blocks == blocks[..., :1]).all()),
            'level_min': float(level.min()), 'level_max': float(level.max()),
            'masked': int(masked.sum()),
            'masked_expected': float(level.sum()),
            'masked_sd': float(np.sqrt((level * (1.0 - level)).sum()))}


def prepare(spec: dict) -> dict:
    """What every comparison of one spec shares: the model, seeded
    weights, rows and noise, and the program's loss, selections and
    gradients on them."""
    t0 = time.monotonic()
    from skypilot_tpu.utils import compile_cache
    compile_cache.configure()
    import flax.linen as nn
    import jax
    import jax.numpy as jnp
    from skypilot_tpu.models import registry
    from skypilot_tpu.train import block_diffusion

    import traffic_gen
    sizes, seq = spec['sizes'], spec['seq']
    x0 = jnp.asarray(traffic_gen.train_rows(
        sizes['vocab_size'] - 1, spec['seed'], spec['rows'], seq))[:, :seq]

    model, cfg = registry.build(spec['preset'])
    key = jax.random.PRNGKey(spec['seed'] % (2 ** 31 - 1))
    x_t, masked, level = block_diffusion.noise(
        x0, jax.random.fold_in(key, 1), cfg)
    drawn = _noise_facts(masked, level, sizes['block_length'])
    if spec.get('level_floor'):
        level = jnp.maximum(level, spec['level_floor'])

    def weights(key):
        return nn.meta.unbox(model.init(
            key, jnp.zeros((1, 8), jnp.int32))['params'])
    params = jax.block_until_ready(jax.jit(weights)(key))
    t1 = time.monotonic()

    # rows and noise are arguments, not constants of the programs: the
    # compile cache then holds one program for every seed.
    def program_loss(p, x0, x_t, masked, level):
        loss, sown = block_diffusion.loss_given_noise(
            model, p, x0, x_t, masked, level)
        picked = {name: layer['experts']['selected'][0] for name, layer
                  in sown['intermediates'].items() if name != 'moe_stats'}
        return loss, picked

    precision = None
    if spec.get('program_dtype'):
        import dataclasses
        model = type(model)(dataclasses.replace(
            cfg, base=dataclasses.replace(
                cfg.base, dtype=spec['program_dtype'])))
        precision = 'highest'
    rounded = params
    if spec.get('program_weight_bits'):
        # reduce_precision, not a cast there and back: the TPU compiler
        # simplifies a pair of converts away
        rounded = jax.jit(lambda p: jax.tree.map(
            lambda x: jax.lax.reduce_precision(
                x, *spec['program_weight_bits']), p))(params)
    with jax.default_matmul_precision(precision):
        (loss_p, picked), grad_p = jax.block_until_ready(jax.jit(
            jax.value_and_grad(program_loss, has_aux=True))(
                rounded, x0, x_t, masked, level))
    return {'params': params, 'x0': x0, 'masked': masked, 'level': level,
            'noise': drawn,
            'loss_p': loss_p, 'picked': picked, 'grad_p': grad_p,
            'seconds': {'weights': round(t1 - t0, 1),
                        'program': round(time.monotonic() - t1, 1)}}


def report(spec: dict, ready: dict) -> dict:
    """The reference on what `prepare` made, and the comparison."""
    import jax
    import jax.numpy as jnp
    from skypilot_tpu.ops import dispatch
    from skypilot_tpu.train import trainer
    t2 = time.monotonic()
    reference = importlib.import_module('references.' + spec['reference'])
    if spec.get('fault'):
        name, rule = _faults(jnp)[spec['fault']]
        setattr(reference, name, rule)
    sizes = dict(spec['sizes'], **spec.get('reference_sizes', {}))
    k = sizes['num_experts_per_tok']
    lo, hi = sizes['experts_held']
    params, x0, masked, level, loss_p, picked, grad_p = (
        ready[name] for name in ('params', 'x0', 'masked', 'level',
                                 'loss_p', 'picked', 'grad_p'))

    def reference_given(p, x0, masked, level, sel):
        return reference.loss_with_routing(p, x0, masked, level, sizes, sel)

    with jax.default_matmul_precision('highest'):
        loss_free, routed_free = jax.block_until_ready(
            jax.jit(reference_given)(params, x0, masked, level, None))
        (loss_r, routed), grad_r = jax.block_until_ready(jax.jit(
            jax.value_and_grad(reference_given, has_aux=True))(
                params, x0, masked, level, picked))
    t3 = time.monotonic()
    # --- routing: each layer's selections against what the reference's
    # router selects on the same history (the reference given the
    # program's selections in every layer, so that a flipped choice in
    # one layer is not counted again as different inputs to the next)
    agree = total = agree_free = 0
    deficit = gap_there = 0.0
    held_p = held_r = 0
    for name, sel_p in picked.items():
        sel_r, ranked = routed[name]
        same = (sel_p[..., :, None] == sel_r[..., None, :]).any(-1)
        top = jax.lax.top_k(ranked, k + 1)[0]
        short = top[..., k - 1:k] - jnp.take_along_axis(ranked, sel_p, -1)
        agree += int(same.sum())
        total += same.size
        agree_free += int((sel_p[..., :, None] ==
                           routed_free[name][0][..., None, :]).any(-1).sum())
        if not bool(same.all()):
            deficit = max(deficit, float(jnp.where(same, 0.0, short).max()))
            gap_there = max(gap_there, float(jnp.where(
                same.all(-1), 0.0, top[..., k - 1] - top[..., k]).max()))
        held_p += int(((sel_p >= lo) & (sel_p < hi)).sum())
        held_r += int(((sel_r >= lo) & (sel_r < hi)).sum())

    # --- gradients: at the program's own selections
    def sq(tree):
        return jax.tree.map(lambda x: jnp.sum(jnp.square(
            x.astype(jnp.float32))), tree)
    err = sq(jax.tree.map(lambda a, b: a.astype(jnp.float32) - b,
                          grad_p, grad_r))
    ref = sq(grad_r)
    paths = [jax.tree_util.keystr(path) for path, _ in
             jax.tree_util.tree_leaves_with_path(err)]
    leaves = {path: float(jnp.sqrt(e / r)) if r > 0 else float(e > 0)
              for path, e, r in zip(paths, jax.tree.leaves(err),
                                    jax.tree.leaves(ref))}
    worst = max(leaves, key=leaves.get)
    # How much of the whole gradient the worst leaf is: a leaf that is
    # a thousandth of it reads the others' rounding as its own error.
    worst_share = float(jnp.sqrt(dict(zip(paths, jax.tree.leaves(ref)))[
        worst] / sum(jax.tree.leaves(ref))))
    total_err = float(jnp.sqrt(sum(jax.tree.leaves(err)) /
                               sum(jax.tree.leaves(ref))))
    opt = jax.eval_shape(
        trainer.make_optimizer(trainer.TrainerConfig()).init, params)

    def dtypes(tree):
        return sorted({str(x.dtype) for x in jax.tree.leaves(tree)
                       if jnp.issubdtype(x.dtype, jnp.floating)})
    return {
        'loss_program': float(loss_p), 'loss_reference': float(loss_free),
        'loss_reference_given_selections': float(loss_r),
        'masked_targets': int(masked.sum()), 'targets': int(masked.size),
        'noise': ready['noise'],
        'weight_mean': float(jnp.where(masked, 1.0 / level, 0.0).sum() /
                             masked.sum()),
        'selection_agreement': agree / total, 'selections': total,
        'selection_agreement_free_routing': agree_free / total,
        'selection_deficit_max': deficit,
        'reference_gap_max_where_they_differ': gap_there,
        'pairs_held_program': held_p, 'pairs_held_reference': held_r,
        'grad_rel_err': total_err, 'grad_rel_err_worst_leaf': leaves[worst],
        'worst_leaf': worst, 'worst_leaf_share_of_norm': worst_share,
        'grad_norm_reference':
        float(jnp.sqrt(sum(jax.tree.leaves(ref)))),
        'kernel_paths': dispatch.snapshot(),
        'moe_plan': dispatch.moe_plan_snapshot(),
        'flash_plan': dispatch.flash_plan_snapshot(),
        'pallas_interpret': dispatch.interpret_mode(),
        'param_dtypes': dtypes(params), 'opt_state_dtypes': dtypes(opt),
        'platform': jax.default_backend(),
        'seconds': dict(ready['seconds'], reference=round(t3 - t2, 1))}


def main() -> None:
    spec = json.loads(sys.argv[1])
    print('chipbench-check: ' + json.dumps(report(spec, prepare(spec))),
          flush=True)


if __name__ == '__main__':
    main()
