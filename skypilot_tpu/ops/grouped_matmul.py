"""Grouped matrix products: the expert feed-forwards of a dropless
mixture-of-experts layer (models/moe.py RoutedExperts).

Rows arrive sorted by the expert that owns them, a chunk of the sorted
rows at a time (the layer's loop hands over `dispatch.moe_chunk_rows`
rows a trip): `group_sizes[g]` of a chunk's rows belong to group g,
each group's range clipped to the chunk. Only the last chunk of a pass
holds rows that belong to no expert (`live` is false there). One
product multiplies every group's rows by that group's matrix. The
ladder (`moe_experts`) has two rungs, chosen in one place
(`_resolve_rung`): `pallas`, the repo's own kernels
(ops/grouped_kernel.py) at the tiles the shape gives
(`dispatch.grouped_blocks`), on the TPU; and the floor `ragged_dot`,
`jax.lax.ragged_dot`, which the TPU compiler lowers to its own grouped
kernel (a `ragged-dot` custom call that visits only the row tiles the
groups cover) and every other backend to masked dense products. Both
visit only the tiles a group covers; the repo's kernels keep a group's
whole matrix in VMEM, which the compiler's does not, and run at three
to four times its speed where an extent is 7 x 128 (docs/kernels.md).

What a kernel leaves in the rows of no group is undefined on the TPU,
so every product's result is masked by `live` before it is used. The
matrices come in the compute dtype: the layer casts them once a pass,
outside its loop.
"""
import functools

import jax
import jax.numpy as jnp

from skypilot_tpu.ops import dispatch
from skypilot_tpu.ops import grouped_kernel
from skypilot_tpu.utils import log_utils

logger = log_utils.init_logger(__name__)


class _Ragged:
    """The three products on `jax.lax.ragged_dot`: the ladder's floor."""

    def __init__(self, group_sizes):
        self.group_sizes = group_sizes

    def rows(self, x, w):
        """x [rows, a] by each group's w [groups, a, b] -> [rows, b]."""
        return jax.lax.ragged_dot(x, w, self.group_sizes)

    def rows_t(self, g, w):
        """g [rows, b] by each group's w [groups, a, b] transposed ->
        [rows, a]: the rows' gradient of `rows`. A copy of w transposed
        and the plain product, as JAX's own rule has it: the TPU
        compiler has its grouped kernel for that form only (contracted
        over w's last dimension in place it multiplies every row by
        every group's matrix). The copy does not depend on the chunk,
        so the compiler makes it once a pass, ahead of the loop."""
        return jax.lax.ragged_dot(g, jnp.swapaxes(w, 1, 2),
                                  self.group_sizes)

    def over_rows(self, x, g):
        """x [rows, a] and g [rows, b] -> [groups, a, b]: the matrices'
        gradient of `rows`, each group's rows contracted."""
        dims = jax.lax.RaggedDotDimensionNumbers(
            dot_dimension_numbers=(((0,), (0,)), ((), ())),
            lhs_ragged_dimensions=[0], rhs_group_dimensions=[])
        return jax.lax.ragged_dot_general(x, g, self.group_sizes, dims)


class _Pallas(_Ragged):
    """The same three on the repo's kernels (ops/grouped_kernel.py) at
    the tiles the shape gives (`dispatch.grouped_blocks`). The visits
    of a row tile size are made once and shared by the products of a
    call. `rows_t` reads w in place: no transposed copy."""

    def __init__(self, group_sizes):
        super().__init__(group_sizes)
        self._visits = {}

    def _plan(self, form, x, a, b):
        tiles = dispatch.grouped_blocks(form, x.shape[0], a, b,
                                        self.group_sizes.shape[0], x.dtype)
        if tiles is None:
            raise ValueError(f'no legal tiles for the grouped product '
                             f'{form} {x.shape[0]} x {a} x {b}')
        dispatch.record_grouped_plan(form, a, b, tiles)
        key = (x.shape[0], tiles[0], form == 'over_rows')
        if key not in self._visits:
            self._visits[key] = grouped_kernel.group_visits(
                self.group_sizes, *key)
        return self._visits[key], tiles

    def rows(self, x, w):
        return grouped_kernel.rows_product(
            x, w, *self._plan('rows', x, *w.shape[1:]))

    def rows_t(self, g, w):
        return grouped_kernel.rows_product(
            g, w, *self._plan('rows_t', g, *w.shape[1:]), transposed=True)

    def over_rows(self, x, g):
        return grouped_kernel.over_rows_product(
            x, g, *self._plan('over_rows', x, x.shape[1], g.shape[1]))


@functools.lru_cache(maxsize=None)
def _say_not_sharded(devices: int) -> None:
    """Once a process, not once a layer and pass."""
    logger.info('moe_experts: %d devices in the mesh and no per-shard '
                'call of the grouped kernels: ragged_dot', devices)


def _resolve_rung(rows: int, d: int, width: int, groups: int,
                  dtype) -> str:
    """The one place that decides the first rung, from the backend and
    the shape: `pallas` on the TPU where the tile rule has legal tiles
    that fit for every form of product over `rows` rows between d and
    width columns, `ragged_dot` everywhere else: off the TPU (no model
    test runs an interpreted kernel, as `attention._resolve_impl`
    answers XLA for flash), and under a mesh of more than one device,
    where the kernels would need a `shard_map` over the expert axis
    with each shard's own rows and sizes
    (`parallel/sharding.per_shard`), which is not wired: no cell shards
    the expert matrices."""
    if dispatch.interpret_mode():
        return 'ragged_dot'
    from skypilot_tpu.parallel import mesh as mesh_lib
    mesh = mesh_lib.current_mesh()
    if mesh is not None and mesh.size > 1:
        _say_not_sharded(mesh.size)
        return 'ragged_dot'
    if all(dispatch.grouped_blocks(form, rows, a, b, groups, dtype)
           for form in dispatch.GROUPED_FORMS
           for a, b in ((d, width), (width, d))):
        return 'pallas'
    return 'ragged_dot'


def _on_ladder(body, group_sizes, x, width):
    """`body(products)` on the first rung that works, for products
    over x's rows between its columns and `width`: `pallas` where
    `_resolve_rung` offers it, then `ragged_dot`."""
    rungs = [('ragged_dot', lambda: body(_Ragged(group_sizes)))]
    if _resolve_rung(*x.shape, width, group_sizes.shape[0],
                     x.dtype) == 'pallas':
        rungs.insert(0, ('pallas', lambda: body(_Pallas(group_sizes))))
    return dispatch.run_ladder('moe_experts', rungs)


def _swiglu(gate, up):
    """silu(gate) * up, in float32 and rounded once to the products'
    dtype."""
    act = jax.nn.silu(gate.astype(jnp.float32)) * up.astype(jnp.float32)
    return act.astype(gate.dtype)


def _gate_up(products, x, w_gate, w_up, live):
    gate = products.rows(x, w_gate)
    up = products.rows(x, w_up)
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
        def body(products):
            gate, up = _gate_up(products, x, w_gate, w_up, live)
            return products.rows(_swiglu(gate, up), w_down)

        out = _on_ladder(body, group_sizes, x, w_gate.shape[2])
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
        def body(products):
            gate, up = _gate_up(products, x, w_gate, w_up, live)
            hidden, pull = jax.vjp(_swiglu, gate, up)
            out = jnp.where(live, products.rows(hidden, w_down), 0)
            d_hidden = jnp.where(live, products.rows_t(g, w_down), 0)
            d_gate, d_up = pull(d_hidden)
            dx = products.rows_t(d_gate, w_gate) + \
                products.rows_t(d_up, w_up)
            return jnp.where(live, dx, 0), out, (hidden, d_gate, d_up)

        return _on_ladder(body, group_sizes, x, w_gate.shape[2])


def expert_weight_grads(x, hidden, g, d_gate, d_up, group_sizes):
    """The gradients of (w_gate, w_up, w_down) from whole row buffers:
    what a pass's chunks left of x, `expert_ffn_bwd`'s three and g,
    each [buffer rows, .], with the whole pass's `group_sizes`. Rows
    past the groups' total are in no group and are not read. Adding a
    chunk's share to float32 sums inside the loop instead reads and
    writes all three matrices every trip."""
    with jax.named_scope('moe_experts'):
        def body(products):
            return (products.over_rows(x, d_gate),
                    products.over_rows(x, d_up),
                    products.over_rows(hidden, g))

        return _on_ladder(body, group_sizes, x, hidden.shape[1])
