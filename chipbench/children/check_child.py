"""The correctness child of a training cell: the program's model, loss
and gradients against the configuration's plain float32 reference
(references/<name>.py), on seeded weights and a seeded sample of the
cell's training rows, at the widths the cell runs, on the device the
cell ran on. It runs after the measured child has gone, outside set-up
and the window, and alone holds the chip.

    python chipbench/children/check_child.py '<json spec>'

spec: preset (the program's name for the configuration), model (the
published sizes, for the reference), reference (module under
references/), seed, rows, seq. Prints one line `chipbench-check: {...}`:
both losses, the relative error of the gradients (whole tree, and the
worst leaf), the kernel rungs taken, and the dtypes of the parameters
and of the optimizer state the trainer would build.
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
    from skypilot_tpu.models import llama
    from skypilot_tpu.ops import dispatch
    from skypilot_tpu.train import trainer

    import traffic_gen
    reference = importlib.import_module('references.' + spec['reference'])
    sizes, seq = spec['model'], spec['seq']
    rows = jnp.asarray(traffic_gen.train_rows(
        sizes['vocab_size'], spec['seed'], spec['rows'], seq))
    tokens, targets = rows[:, :-1], rows[:, 1:]

    model = llama.LlamaModel(llama.CONFIGS[spec['preset']])
    key = jax.random.PRNGKey(spec['seed'] % (2 ** 31 - 1))
    params = nn.meta.unbox(jax.jit(model.init)(
        key, jnp.zeros((1, 8), jnp.int32))['params'])

    # tokens and targets are arguments, not constants of the programs:
    # the compile cache then holds one program for every seed.
    def program_loss(p, tok, tgt):
        return trainer.cross_entropy_loss(
            model.apply({'params': p}, tok), tgt)[0]

    def reference_loss(p, tok, tgt):
        return reference.loss(p, tok, tgt, sizes)

    jax.block_until_ready(params)
    t1 = time.monotonic()
    loss_p, grad_p = jax.block_until_ready(jax.jit(jax.value_and_grad(
        program_loss))(params, tokens, targets))
    t2 = time.monotonic()
    with jax.default_matmul_precision('highest'):
        loss_r, grad_r = jax.block_until_ready(jax.jit(jax.value_and_grad(
            reference_loss))(params, tokens, targets))
    t3 = time.monotonic()

    def sq(tree):
        return jax.tree.map(lambda x: jnp.sum(jnp.square(
            x.astype(jnp.float32))), tree)
    err = sq(jax.tree.map(lambda a, b: a.astype(jnp.float32) - b,
                          grad_p, grad_r))
    ref = sq(grad_r)
    leaves = {jax.tree_util.keystr(k): float(jnp.sqrt(e / r))
              for (k, e), r in zip(jax.tree_util.tree_leaves_with_path(err),
                                   jax.tree.leaves(ref))}
    worst = max(leaves, key=leaves.get)
    total = float(jnp.sqrt(sum(jax.tree.leaves(err)) /
                           sum(jax.tree.leaves(ref))))
    opt = jax.eval_shape(
        trainer.make_optimizer(trainer.TrainerConfig()).init, params)

    def dtypes(tree):
        return sorted({str(x.dtype) for x in jax.tree.leaves(tree)
                       if jnp.issubdtype(x.dtype, jnp.floating)})
    print('chipbench-check: ' + json.dumps({
        'loss_program': float(loss_p), 'loss_reference': float(loss_r),
        'grad_rel_err': total, 'grad_rel_err_worst_leaf': leaves[worst],
        'worst_leaf': worst, 'grad_norm_reference':
        float(jnp.sqrt(sum(jax.tree.leaves(ref)))),
        'kernel_paths': dispatch.snapshot(),
        'pallas_interpret': dispatch.interpret_mode(),
        'param_dtypes': dtypes(params), 'opt_state_dtypes': dtypes(opt),
        'platform': jax.default_backend(),
        'seconds': {'weights': round(t1 - t0, 1), 'program': round(t2 - t1, 1),
                    'reference': round(t3 - t2, 1)}}), flush=True)


if __name__ == '__main__':
    main()
