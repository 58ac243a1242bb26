"""Logical-axis sharding rules: GSPMD partition specs for model code.

Model code annotates arrays with *logical* axis names ('batch', 'seq',
'embed', ...); these rules map them onto the canonical mesh axes
(parallel/mesh.py). This is the pjit/GSPMD replacement for everything the
reference's recipes do with NCCL launchers (SURVEY.md §2.10 table): change
the rules (or mesh sizes), not the model, to move between DP / FSDP / TP /
EP / CP layouts.
"""
from typing import Optional, Sequence, Tuple, Union

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

AxisRules = Sequence[Tuple[str, Union[None, str, Tuple[str, ...]]]]

# The standard rule set (MaxText-style). Parameter axes and activation axes
# use distinct logical names: 'embed' on a weight shards over fsdp (ZeRO-3),
# but the same dimension on an activation must stay unsharded (it would
# collide with 'act_batch' being sharded over fsdp). First match wins.
DEFAULT_RULES: AxisRules = (
    # --- parameters ---
    ('embed', 'fsdp'),             # ZeRO-3-style parameter sharding
    ('heads', 'tp'),               # megatron attention head sharding
    ('kv_heads', 'tp'),
    ('mlp', 'tp'),                 # megatron MLP column/row sharding
    ('vocab', 'tp'),
    ('expert', 'ep'),              # MoE expert sharding
    ('layers', 'pp'),              # scanned-layer axis: pipeline stages
    ('head_dim', None),
    # --- activations ---
    ('act_batch', ('dp', 'fsdp')),  # per-example over all data axes
    ('act_seq', 'cp'),             # context parallelism (ring attention)
    ('act_embed', None),
    ('act_heads', 'tp'),
    ('act_kv_heads', 'tp'),
    ('act_mlp', 'tp'),
    ('act_vocab', 'tp'),
    ('act_expert', 'ep'),
)


def logical_to_spec(logical_axes: Sequence[Optional[str]],
                    rules: AxisRules = DEFAULT_RULES) -> P:
    """('batch','seq','embed') -> PartitionSpec(('dp','fsdp'),'cp','fsdp')."""
    used = set()
    out = []
    for name in logical_axes:
        if name is None:
            out.append(None)
            continue
        mesh_axes = None
        for rule_name, rule_axes in rules:
            if rule_name == name:
                mesh_axes = rule_axes
                break
        if mesh_axes is None:
            out.append(None)
            continue
        if isinstance(mesh_axes, str):
            mesh_axes = (mesh_axes,)
        free = tuple(a for a in mesh_axes if a not in used)
        used.update(free)
        if not free:
            out.append(None)
        elif len(free) == 1:
            out.append(free[0])
        else:
            out.append(free)
    return P(*out)


def named_sharding(mesh: Mesh, logical_axes: Sequence[Optional[str]],
                   rules: AxisRules = DEFAULT_RULES) -> NamedSharding:
    return NamedSharding(mesh, logical_to_spec(logical_axes, rules))


def constrain(x: jax.Array, mesh: Mesh,
              logical_axes: Sequence[Optional[str]],
              rules: AxisRules = DEFAULT_RULES) -> jax.Array:
    """with_sharding_constraint by logical names (no-op off-mesh)."""
    if mesh is None or mesh.empty:
        return x
    return jax.lax.with_sharding_constraint(
        x, named_sharding(mesh, logical_axes, rules))


def per_shard(fn, in_axes, out_axes):
    """`fn` run on each device's own shard of its operands, under the
    ambient mesh: how a Pallas kernel is called inside a GSPMD jit.

    Mosaic refuses to be partitioned automatically ("wrap the call in
    a shard_map"), so a kernel whose operands are head- or
    batch-sharded has to sit in a shard_map over exactly those axes.
    in_axes / out_axes give one tuple of LOGICAL axis names per operand
    / result, resolved by the ambient flax rules — the same resolution
    as the nn.with_logical_constraint calls around the call site, so
    entering the shard_map moves no data. Off-mesh, or on a one-device
    mesh, `fn` is returned unchanged. A dim its mesh axes do not
    divide raises at trace time (the dispatch ladder's next rung)."""
    from skypilot_tpu.parallel import mesh as mesh_lib
    mesh = mesh_lib.current_mesh()
    if mesh is None or mesh.size == 1:
        return fn
    import flax.linen as nn
    return jax.shard_map(
        fn, mesh=mesh,
        in_specs=tuple(nn.logical_to_mesh_axes(a) for a in in_axes),
        out_specs=nn.logical_to_mesh_axes(out_axes), check_vma=False)


def tree_shardings(mesh: Mesh, logical_tree,
                   rules: AxisRules = DEFAULT_RULES):
    """Map a pytree of logical-axis tuples to NamedShardings."""
    return jax.tree.map(
        lambda axes: named_sharding(mesh, axes, rules),
        logical_tree,
        is_leaf=lambda x: isinstance(x, tuple) and all(
            a is None or isinstance(a, str) for a in x))
