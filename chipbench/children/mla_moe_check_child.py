"""The correctness child of an `mla_moe_train` cell: the program's model
(bf16, the latent flash kernels at YaRN's softmax scale, the grouped
products' rung the run used) and the trainer's own step against the
configuration's plain float32 reference at the highest matmul precision,
on seeded weights and seeded rows of the cell's traffic, at the widths
and depth the cell runs, on the device the cell ran on, after the
measured child has gone.

    python chipbench/children/mla_moe_check_child.py '<json spec>'

spec: preset, sizes (the configuration's sizes, for the reference),
reference (module under references/), seed, rows, seq, and the trainer's
settings as sft is started with them: lr, steps (sft's `--steps`, the
schedule's length) and mesh. Three more keys are for reading what a
fault reads (PERF.md and `check.why` have the readings; no cell sets
them): `program_weight_bits`, [exponent, mantissa] bits the program's
weights are rounded to ([4, 3] is float8_e4m3, the nearest format below
bfloat16's [8, 7]); `program_update` 'none', the program's parameters
taken as unchanged by its steps; and `faults`, a list of sizes the
reference is given instead, each compared with the program in turn
after the sound comparison and reported on a line `chipbench-fault:
{...}` of its own (`{"fault": "half_split"}` and the others the
reference's docstring names).

What is compared is children/kda_moe_check_child.py's, with the balance
loss beside the loss: routing layer by layer with the reference given
the program's selections, with the `deficit` of every selection the
reference does not make (in units of the router's softmax); the loss
(cross-entropy plus the balance loss) against the reference routing
freely; the balance loss against the reference's given the program's
selections (the counts follow the selections); the gradients, whole
tree and worst leaf, against the reference given the program's
selections; and sft's jitted train step taken twice on the row from the
seeded state, the parameters' change against the same optimizer's two
steps on the reference's gradients, with the step's own loss.
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
    import numpy as np
    from skypilot_tpu.models import registry
    from skypilot_tpu.ops import dispatch
    from skypilot_tpu.parallel import mesh as mesh_lib
    from skypilot_tpu.train import sft
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
    seeded = jax.jit(weights)
    program_weights = seeded
    if spec.get('program_weight_bits'):
        # reduce_precision, not a cast there and back: the TPU compiler
        # simplifies a pair of converts away
        program_weights = jax.jit(lambda key: jax.tree.map(
            lambda x: jax.lax.reduce_precision(
                x, *spec['program_weight_bits']), weights(key)))

    def dtypes(tree):
        return sorted({str(x.dtype) for x in jax.tree.leaves(tree)
                       if jnp.issubdtype(x.dtype, jnp.floating)})

    # --- the step, first, while the chip holds nothing else: sft's
    # optimizer and jitted train step, twice on the row from the seeded
    # state (donated, as sft's is). The parameters' change waits on the
    # host for the reference's.
    tx = trainer.make_optimizer(trainer.TrainerConfig(
        learning_rate=spec['lr'], total_steps=spec['steps']))
    train_step = trainer.make_train_step(model, tx, mesh_lib.build_mesh(
        sft.parse_mesh(spec['mesh'], jax.device_count())))

    def fresh(p):
        return trainer.TrainStateS(step=jnp.zeros((), jnp.int32), params=p,
                                   opt_state=tx.init(p))
    state = jax.jit(fresh, donate_argnums=0)(program_weights(key))
    state_dtypes = (dtypes(state.params), dtypes(state.opt_state))
    batch = {'tokens': tokens, 'targets': targets}
    state, first = train_step(state, batch)
    state, _ = train_step(state, batch)
    moved, loss_step = state.params, float(first['loss'])
    del state, first
    change_p = jax.device_get(jax.jit(
        lambda new, old: jax.tree.map(jnp.subtract, new, old),
        donate_argnums=0)(moved, program_weights(key)))
    del moved
    if spec.get('program_update') == 'none':
        change_p = jax.tree.map(np.zeros_like, change_p)
    params = jax.block_until_ready(seeded(key))
    t1 = time.monotonic()

    # tokens and targets are arguments, not constants of the programs:
    # the compile cache then holds one program for every seed.
    def program_loss(p, tok, tgt):
        logits, sown = model.apply({'params': p}, tok,
                                   mutable=['intermediates'])
        inter = sown['intermediates']
        picked = {name: layer['experts']['selected'][0]
                  for name, layer in inter.items()
                  if name.startswith('layer_') and 'experts' in layer}
        aux = inter['moe_aux_loss'][0]
        return trainer.cross_entropy_loss(logits, tgt)[0] + aux, (picked,
                                                                  aux)

    rounded = params if program_weights is seeded else program_weights(key)
    (loss_p, (picked, aux_p)), grad_p = jax.block_until_ready(jax.jit(
        jax.value_and_grad(program_loss, has_aux=True))(
            rounded, tokens, targets))
    del rounded
    t2 = time.monotonic()

    @jax.jit
    def apart(a, b):
        """Per leaf: the squared norm of a - b and of b, and the entries
        whose sign differs."""
        def sq(x):
            return jnp.sum(jnp.square(x.astype(jnp.float32)))
        return (jax.tree.map(lambda x, y: sq(x.astype(jnp.float32) - y),
                             a, b),
                jax.tree.map(sq, b),
                jax.tree.map(lambda x, y: jnp.sum(jnp.sign(x) != jnp.sign(y)),
                             a, b))

    def relative(err, ref):
        """Whole tree and each leaf; a leaf nothing reaches is exact or
        wrong."""
        err, ref = jax.device_get((err, ref))
        leaves = {jax.tree_util.keystr(path): float(np.sqrt(e / r))
                  if r > 0 else float(e > 0) for (path, e), r in zip(
                      jax.tree_util.tree_leaves_with_path(err),
                      jax.tree.leaves(ref))}
        whole = float(np.sqrt(sum(jax.tree.leaves(err)) /
                              sum(jax.tree.leaves(ref))))
        return whole, leaves

    def reference_change(p, g):
        """The same optimizer's two steps on these gradients from the
        same state."""
        s = fresh(p)
        for _ in range(2):
            s = s.apply_gradients(g, tx)
        return jax.tree.map(jnp.subtract, s.params, p)

    def compare(sizes):
        """The four comparisons against the reference of these sizes."""
        def reference_given(p, tok, tgt, sel):
            ce, aux, routed = reference.loss_parts(p, tok, tgt, sizes, sel)
            return ce + aux, (routed, aux)

        with jax.default_matmul_precision('highest'):
            loss_free, (routed_free, aux_free) = jax.block_until_ready(
                jax.jit(reference_given)(params, tokens, targets, None))
            (loss_r, (routed, aux_r)), grad_r = jax.block_until_ready(
                jax.jit(jax.value_and_grad(reference_given, has_aux=True))(
                    params, tokens, targets, picked))

        # --- routing: each layer's selections against what the
        # reference's router selects on the same history (the reference
        # given the program's selections in every layer, so that a
        # flipped choice in one layer is not counted again as different
        # inputs to the next)
        agree = total = agree_free = 0
        deficit = gap_there = 0.0
        held_p = held_r = 0
        for name, sel_p in picked.items():
            sel_r, ranked = routed[name]
            same = (sel_p[..., :, None] == sel_r[..., None, :]).any(-1)
            top = jax.lax.top_k(ranked, k + 1)[0]
            short = top[..., k - 1:k] - jnp.take_along_axis(ranked, sel_p,
                                                            -1)
            agree += int(same.sum())
            total += same.size
            agree_free += int((sel_p[..., :, None] == routed_free[name][0][
                ..., None, :]).any(-1).sum())
            if not bool(same.all()):
                deficit = max(deficit,
                              float(jnp.where(same, 0.0, short).max()))
                gap_there = max(gap_there, float(jnp.where(
                    same.all(-1), 0.0, top[..., k - 1] - top[..., k]).max()))
            held_p += int(((sel_p >= lo) & (sel_p < hi)).sum())
            held_r += int(((sel_r >= lo) & (sel_r < hi)).sum())

        # --- gradients: at the program's own selections
        err, ref, _ = apart(grad_p, grad_r)
        grad_whole, grad_leaves = relative(err, ref)
        grad_worst = max(grad_leaves, key=grad_leaves.get)
        grad_norm = float(np.sqrt(sum(jax.tree.leaves(jax.device_get(ref)))))

        # --- the step: the program's change against the optimizer's on
        # the reference's gradients
        change_r = jax.jit(reference_change, donate_argnums=1)(params, grad_r)
        del grad_r
        err, ref, flips = apart(jax.device_put(change_p), change_r)
        del change_r
        step_whole, step_leaves = relative(err, ref)
        step_worst = max(step_leaves, key=step_leaves.get)
        return {
            'loss_program': float(loss_p), 'loss_reference': float(loss_free),
            'loss_reference_given_selections': float(loss_r),
            'aux_program': float(aux_p), 'aux_reference': float(aux_r),
            'aux_reference_free_routing': float(aux_free),
            'loss_step': loss_step,
            'selection_agreement': agree / total, 'selections': total,
            'selection_agreement_free_routing': agree_free / total,
            'selection_deficit_max': deficit,
            'reference_gap_max_where_they_differ': gap_there,
            'pairs_held_program': held_p, 'pairs_held_reference': held_r,
            'grad_rel_err': grad_whole,
            'grad_rel_err_worst_leaf': grad_leaves[grad_worst],
            'worst_leaf': grad_worst, 'grad_norm_reference': grad_norm,
            'update_rel_err': step_whole,
            'update_rel_err_worst_leaf': step_leaves[step_worst],
            'worst_update_leaf': step_worst,
            'update_sign_disagreement': int(sum(jax.tree.leaves(
                jax.device_get(flips)))) / sum(
                    x.size for x in jax.tree.leaves(change_p))}

    report = compare(sizes)
    t3 = time.monotonic()
    print('chipbench-check: ' + json.dumps(dict(
        report, kernel_paths=dispatch.snapshot(),
        moe_plan=dispatch.moe_plan_snapshot(),
        flash_plan=dispatch.flash_plan_snapshot(),
        mla_plan=dispatch.mla_plan_snapshot(),
        pallas_interpret=dispatch.interpret_mode(),
        param_dtypes=state_dtypes[0], opt_state_dtypes=state_dtypes[1],
        platform=jax.default_backend(),
        seconds={'weights_and_step': round(t1 - t0, 1),
                 'program': round(t2 - t1, 1),
                 'reference': round(t3 - t2, 1)})), flush=True)
    for fault in spec.get('faults', []):
        print('chipbench-fault: ' + json.dumps(dict(
            compare(dict(sizes, **fault)), reference_sizes=fault)),
            flush=True)


if __name__ == '__main__':
    main()
