"""Grouped matrix products: the expert feed-forwards of a dropless
mixture-of-experts layer (models/moe.py RoutedExperts).

Rows arrive sorted by the expert that owns them, a chunk of the sorted
rows at a time (the layer's loop hands over `dispatch.moe_chunk_rows`
rows a trip): `group_sizes[g]` of a chunk's rows belong to group g,
each group's range clipped to the chunk. Only the last chunk of a pass
holds rows that belong to no expert (`live` is false there). One
product multiplies every group's rows by that group's matrix. The
ladder (`moe_experts`) has one rung: `jax.lax.ragged_dot`, which the
TPU compiler lowers to its own grouped kernel (a `ragged-dot` custom
call that visits only the row tiles the groups cover) and every other
backend to masked dense products. No rung was added for the chunks:
they are the same products over fewer rows, and a Pallas rung goes in
front of `ragged_dot` when one wins at a cell's shapes
(docs/kernels.md).

What the kernel leaves in the rows of no group is undefined on the
TPU, so every product's result is masked by `live` before it is used.
The matrices come in the compute dtype: the layer casts them once a
pass, outside its loop.
"""
import jax
import jax.numpy as jnp

from skypilot_tpu.ops import dispatch


def _rows_by_transposed(g, w, group_sizes):
    """g [rows, b] by each group's w [groups, a, b] transposed ->
    [rows, a]: the rows' gradient of `ragged_dot(rows, w)`. A copy of
    w transposed and the plain product, as JAX's own rule has it: the
    TPU compiler has its grouped kernel for that form only (contracted
    over w's last dimension in place it multiplies every row by every
    group's matrix). The copy does not depend on the chunk, so it is
    made once a pass, ahead of the loop."""
    return jax.lax.ragged_dot(g, jnp.swapaxes(w, 1, 2), group_sizes)


def _over_rows(x, g, group_sizes):
    """x [rows, a] and g [rows, b] -> [groups, a, b]: the matrices'
    gradient of `ragged_dot(x, w)`, each group's rows contracted."""
    dims = jax.lax.RaggedDotDimensionNumbers(
        dot_dimension_numbers=(((0,), (0,)), ((), ())),
        lhs_ragged_dimensions=[0], rhs_group_dimensions=[])
    return jax.lax.ragged_dot_general(x, g, group_sizes, dims)


def _swiglu(gate, up):
    """silu(gate) * up, in float32 and rounded once to the products'
    dtype."""
    act = jax.nn.silu(gate.astype(jnp.float32)) * up.astype(jnp.float32)
    return act.astype(gate.dtype)


def _gate_up(x, w_gate, w_up, group_sizes, live):
    gate = jax.lax.ragged_dot(x, w_gate, group_sizes)
    up = jax.lax.ragged_dot(x, w_up, group_sizes)
    return jnp.where(live, gate, 0), jnp.where(live, up, 0)


def expert_ffn(x: jax.Array, live: jax.Array, w_gate: jax.Array,
               w_up: jax.Array, w_down: jax.Array,
               group_sizes: jax.Array) -> jax.Array:
    """SwiGLU feed-forward of each group's rows through its own expert.

    x: [rows, dim] sorted by group; live: [rows, 1] bool, false for the
    rows of no group; w_gate, w_up: [groups, dim, width]; w_down:
    [groups, width, dim], in x's dtype; group_sizes: [groups] int32.
    Returns [rows, dim], zero where not live."""
    with jax.named_scope('moe_experts'):
        def ragged():
            gate, up = _gate_up(x, w_gate, w_up, group_sizes, live)
            return jax.lax.ragged_dot(_swiglu(gate, up), w_down,
                                      group_sizes)

        out = dispatch.run_ladder('moe_experts', [('ragged_dot', ragged)])
        return jnp.where(live, out, 0)


def expert_ffn_bwd(x, live, w_gate, w_up, w_down, group_sizes, g):
    """The transpose of `expert_ffn` for one chunk, without the
    matrices' gradients: g [rows, dim] is the result's cotangent.
    Returns (dx [rows, dim]; the result itself, which the gradient of
    whatever weighs the rows needs; and what `expert_weight_grads`
    needs of this chunk: the hidden rows and the cotangents of gate and
    up, [rows, width] each). Nothing of the forward was kept: gate, up
    and the result are computed again here, from x."""
    with jax.named_scope('moe_experts'):
        gate, up = _gate_up(x, w_gate, w_up, group_sizes, live)
        hidden, pull = jax.vjp(_swiglu, gate, up)
        out = jnp.where(
            live, jax.lax.ragged_dot(hidden, w_down, group_sizes), 0)
        d_hidden = jnp.where(
            live, _rows_by_transposed(g, w_down, group_sizes), 0)
        d_gate, d_up = pull(d_hidden)
        dx = _rows_by_transposed(d_gate, w_gate, group_sizes) + \
            _rows_by_transposed(d_up, w_up, group_sizes)
        return jnp.where(live, dx, 0), out, (hidden, d_gate, d_up)


def expert_weight_grads(x, hidden, g, d_gate, d_up, group_sizes):
    """The gradients of (w_gate, w_up, w_down) from whole row buffers:
    what a pass's chunks left of x, `expert_ffn_bwd`'s three and g,
    each [buffer rows, .], with the whole pass's `group_sizes`. Rows
    past the groups' total are in no group and are not read. Adding a
    chunk's share to float32 sums inside the loop instead reads and
    writes all three matrices every trip."""
    with jax.named_scope('moe_experts'):
        return (_over_rows(x, d_gate, group_sizes),
                _over_rows(x, d_up, group_sizes),
                _over_rows(hidden, g, group_sizes))
