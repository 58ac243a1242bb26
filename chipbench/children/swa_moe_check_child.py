"""The correctness child of a `swa_moe_train` cell: the program's model
(bf16, the flash kernels on window and full layers, the grouped
products' rung the run used) against the configuration's plain float32
reference at the highest matmul precision, on seeded weights and seeded
rows of the cell's traffic, at the widths and depth the cell runs, on
the device the cell ran on, after the measured child has gone.

    python chipbench/children/swa_moe_check_child.py '<json spec>'

spec: preset, sizes (the configuration's sizes, for the reference),
reference (module under references/), seed, rows, seq. Two more keys
are for reading what a fault reads (PERF.md has the readings; no cell
sets them): `program_weight_bits`, [exponent, mantissa] bits the
program's weights are rounded to ([4, 3] is float8_e4m3, the nearest
format below bfloat16's [8, 7]), and `reference_sizes`, sizes the
reference is given instead (`{"norm_topk_prob": false}` is a missing
term; a `sliding_window` beyond the row is full attention in the window
layers; a `rope_parameters` group whose `full_attention` entry is the
default rule, or whose `attention_factor` is 1, is a wrong rotary
table).

What is compared, and why three things, is children/moe_check_child.py's
(a bf16 activation can flip a near-tied last choice of the router, after
which the two models compute different functions): routing layer by
layer with the reference given the program's selections, with the
`deficit` of every selection the reference does not make (in units of
the router's softmax); the loss against the reference routing freely;
the gradients, whole tree and worst leaf, against the reference given
the program's selections; beside the kernel rungs taken (the window
layers' under `flash_window_attention`), the tile plans, the dtypes of
the parameters and of the optimizer state the trainer would build, and
the pairs routed to held experts by both.
"""
import importlib
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))


def main() -> None:
    spec = json.loads(sys.argv[1])
    t0 = time.monotonic()
    from skypilot_tpu.utils import compile_cache
    compile_cache.configure()
    import flax.linen as nn
    import jax
    import jax.numpy as jnp
    from skypilot_tpu.models import registry
    from skypilot_tpu.ops import dispatch
    from skypilot_tpu.train import trainer

    import traffic_gen
    reference = importlib.import_module('references.' + spec['reference'])
    sizes, seq = spec['sizes'], spec['seq']
    k = sizes['num_experts_per_tok']
    lo, hi = sizes['experts_held']
    rows = jnp.asarray(traffic_gen.train_rows(
        sizes['vocab_size'], spec['seed'], spec['rows'], seq))
    tokens, targets = rows[:, :-1], rows[:, 1:]

    model, _ = registry.build(spec['preset'])
    key = jax.random.PRNGKey(spec['seed'] % (2 ** 31 - 1))

    def weights(key):
        return nn.meta.unbox(model.init(
            key, jnp.zeros((1, 8), jnp.int32))['params'])
    params = jax.block_until_ready(jax.jit(weights)(key))
    t1 = time.monotonic()

    # tokens and targets are arguments, not constants of the programs:
    # the compile cache then holds one program for every seed.
    def program_loss(p, tok, tgt):
        logits, sown = model.apply({'params': p}, tok,
                                   mutable=['intermediates'])
        picked = {name: layer['experts']['selected'][0] for name, layer
                  in sown['intermediates'].items() if name != 'moe_stats'}
        return trainer.cross_entropy_loss(logits, tgt)[0], picked

    rounded = params
    if spec.get('program_weight_bits'):
        # reduce_precision, not a cast there and back: the TPU compiler
        # simplifies a pair of converts away
        rounded = jax.jit(lambda p: jax.tree.map(
            lambda x: jax.lax.reduce_precision(
                x, *spec['program_weight_bits']), p))(params)
    (loss_p, picked), grad_p = jax.block_until_ready(jax.jit(
        jax.value_and_grad(program_loss, has_aux=True))(
            rounded, tokens, targets))
    del rounded
    sizes = dict(sizes, **spec.get('reference_sizes', {}))
    t2 = time.monotonic()

    def reference_given(p, tok, tgt, sel):
        return reference.loss_with_routing(p, tok, tgt, sizes, sel)

    with jax.default_matmul_precision('highest'):
        loss_free, routed_free = jax.block_until_ready(
            jax.jit(reference_given)(params, tokens, targets, None))
        (loss_r, routed), grad_r = jax.block_until_ready(jax.jit(
            jax.value_and_grad(reference_given, has_aux=True))(
                params, tokens, targets, picked))
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
    leaves = {jax.tree_util.keystr(path): float(jnp.sqrt(e / r)) if r > 0
              else float(e > 0) for (path, e), r in zip(
                  jax.tree_util.tree_leaves_with_path(err),
                  jax.tree.leaves(ref))}
    worst = max(leaves, key=leaves.get)
    total_err = float(jnp.sqrt(sum(jax.tree.leaves(err)) /
                               sum(jax.tree.leaves(ref))))
    opt = jax.eval_shape(
        trainer.make_optimizer(trainer.TrainerConfig()).init, params)

    def dtypes(tree):
        return sorted({str(x.dtype) for x in jax.tree.leaves(tree)
                       if jnp.issubdtype(x.dtype, jnp.floating)})
    print('chipbench-check: ' + json.dumps({
        'loss_program': float(loss_p), 'loss_reference': float(loss_free),
        'loss_reference_given_selections': float(loss_r),
        'selection_agreement': agree / total, 'selections': total,
        'selection_agreement_free_routing': agree_free / total,
        'selection_deficit_max': deficit,
        'reference_gap_max_where_they_differ': gap_there,
        'pairs_held_program': held_p, 'pairs_held_reference': held_r,
        'grad_rel_err': total_err, 'grad_rel_err_worst_leaf': leaves[worst],
        'worst_leaf': worst, 'grad_norm_reference':
        float(jnp.sqrt(sum(jax.tree.leaves(ref)))),
        'kernel_paths': dispatch.snapshot(),
        'moe_plan': dispatch.moe_plan_snapshot(),
        'flash_plan': dispatch.flash_plan_snapshot(),
        'pallas_interpret': dispatch.interpret_mode(),
        'param_dtypes': dtypes(params), 'opt_state_dtypes': dtypes(opt),
        'platform': jax.default_backend(),
        'seconds': {'weights': round(t1 - t0, 1), 'program': round(t2 - t1, 1),
                    'reference': round(t3 - t2, 1)}}), flush=True)


if __name__ == '__main__':
    main()
