"""Grouped matrix products as Pallas TPU kernels: the rung `pallas` of
the ladder `moe_experts` (ops/grouped_matmul.py; docs/kernels.md).

Rows sorted by group, `group_sizes[g]` of them in group g, rows past
the groups' total in none. Three forms, the three `ragged_dot` calls
of the expert layer:

* `rows`: x [m, a] by each group's w[g] [a, b] -> [m, b];
* `rows_t`: x [m, b] by each group's w[g] [a, b] transposed -> [m, a],
  w read in place (the MXU takes its stationary operand either way);
* `over_rows`: x [m, a] transposed by y [m, b], each group's rows
  contracted -> [groups, a, b].

One grid step is one (row tile, group) pair, a *visit*. The visits
come from `group_sizes` on the device (`group_visits`) and reach the
kernels by scalar prefetch: the index maps read which row tile and
which group's matrix a step works on, and the grid's extent is the
count of visits, so only tiles a group covers are fetched. A tile two
groups share is visited once for each, consecutively, and each stores
(or contracts) its own rows only. An empty group has no visit in the
first two forms and one in the third, which stores its zeros. Rows in
no group are never stored: what the first two forms leave there is
undefined, and the caller masks it.

bf16 (or any) inputs, float32 accumulation, one rounding to the
result's dtype, which is the inputs': what `ragged_dot` does. The
tiles (tm rows, tk of the contraction or of a, tn columns) are
`dispatch.grouped_blocks`'s: with tk and tn the whole extents a
group's matrix is fetched once and stays in VMEM across that group's
row tiles.
"""
import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from skypilot_tpu.ops import dispatch


class Visits(NamedTuple):
    """The (row tile, group) pairs a kernel's grid walks, in order.
    offsets: [groups + 1] the row each group starts at; group, tile:
    [most visits] (past `count` they repeat the last visit); count:
    [1]. All int32, all scalar-prefetched."""
    offsets: jax.Array
    group: jax.Array
    tile: jax.Array
    count: jax.Array


def group_visits(group_sizes: jax.Array, rows: int, tm: int,
                 visit_empty: bool = False) -> Visits:
    """Which row tiles of tm rows each group covers, as one list of
    visits sorted by group, then tile. A group covers the tiles from
    the one its first row is in to the one its last row is in; an
    empty group covers none, or with `visit_empty` one (any: nothing
    of it is read into the result). At most rows / tm + groups - 1
    visits: every tile once, and once more for each group that starts
    inside one."""
    groups = group_sizes.shape[0]
    most = rows // tm + groups - 1
    ends = jnp.cumsum(group_sizes.astype(jnp.int32))
    starts = ends - group_sizes
    first = jnp.minimum(starts // tm, rows // tm - 1)
    tiles = jnp.where(group_sizes > 0, (ends - 1) // tm - first + 1,
                      1 if visit_empty else 0)
    visit_ends = jnp.cumsum(tiles)
    count = visit_ends[-1]
    v = jnp.minimum(jnp.arange(most, dtype=jnp.int32),
                    jnp.maximum(count - 1, 0))
    group = jnp.sum(v[:, None] >= visit_ends[None, :], axis=1,
                    dtype=jnp.int32)
    group = jnp.minimum(group, groups - 1)
    at = group[:, None] == jnp.arange(groups)[None, :]
    tile = v + jnp.sum(jnp.where(at, (first - visit_ends + tiles)[None, :],
                                 0), axis=1)
    offsets = jnp.concatenate([jnp.zeros(1, jnp.int32), ends])
    return Visits(offsets, group, tile.astype(jnp.int32),
                  count.reshape(1).astype(jnp.int32))


def _in_group(visits: Visits, v, tm: int, shape):
    """Which rows of visit v's tile are its group's, as a mask of
    `shape` (rows first)."""
    offsets, group, tile, _ = visits
    g = group[v]
    row = tile[v] * tm + jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    return (row >= offsets[g]) & (row < offsets[g + 1])


def _rows_kernel(*refs, tm: int, transposed: bool, k_tiles: int):
    visits, (x_ref, w_ref, o_ref), scratch = Visits(*refs[:4]), \
        refs[4:7], refs[7:]
    v, ki = pl.program_id(1), pl.program_id(2)
    dims = (((1,), (1 if transposed else 0,)), ((), ()))
    part = jax.lax.dot_general(x_ref[...], w_ref[...], dims,
                               preferred_element_type=jnp.float32)

    def store(result):
        mask = _in_group(visits, v, tm, o_ref.shape)
        o_ref[...] = jnp.where(mask, result.astype(o_ref.dtype), o_ref[...])

    if k_tiles == 1:
        store(part)
        return
    acc_ref, = scratch

    @pl.when(ki == 0)
    def _():
        acc_ref[...] = part

    @pl.when(ki > 0)
    def _():
        acc_ref[...] += part

    @pl.when(ki == k_tiles - 1)
    def _():
        store(acc_ref[...])


def _over_rows_kernel(*refs, tm: int):
    visits, (x_ref, y_ref, o_ref, acc_ref) = Visits(*refs[:4]), refs[4:]
    offsets, group, _, _ = visits
    v, last = pl.program_id(2), pl.num_programs(2) - 1
    g = group[v]

    @pl.when((v == 0) | (group[jnp.maximum(v - 1, 0)] != g))
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(offsets[g + 1] > offsets[g])
    def _():
        # One operand zeroed outside the group is enough.
        y = y_ref[...]
        y = jnp.where(_in_group(visits, v, tm, y.shape), y,
                      jnp.zeros_like(y))
        acc_ref[...] += jax.lax.dot_general(
            x_ref[...], y, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when((v == last) | (group[jnp.minimum(v + 1, last)] != g))
    def _():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _params(semantics, need):
    return pltpu.CompilerParams(
        dimension_semantics=semantics,
        vmem_limit_bytes=dispatch.flash_vmem_limit(need))


# The products are jitted so that the layers of an unrolled model, which
# call them at the same shapes, trace and lower each kernel once: a
# `pallas_call` traced at every call site cost the step's set-up 80 ms
# each, 96 of them in `sft-moe-8k` (PERF.md §6, PR 34).
@functools.partial(jax.jit, static_argnames=('tiles', 'transposed'))
def rows_product(x: jax.Array, w: jax.Array, visits: Visits, tiles,
                 transposed: bool = False) -> jax.Array:
    """x [m, a] by w[g] [a, b] -> [m, b], or with `transposed` x [m, b]
    by w[g] transposed -> [m, a]; `visits` made at tiles[0] rows."""
    tm, tk, tn = tiles
    m, k = x.shape
    n = w.shape[1 if transposed else 2]
    k_tiles = k // tk
    if transposed:
        w_spec = pl.BlockSpec((None, tn, tk),
                              lambda ni, v, ki, o, g, t, c: (g[v], ni, ki))
    else:
        w_spec = pl.BlockSpec((None, tk, tn),
                              lambda ni, v, ki, o, g, t, c: (g[v], ki, ni))
    itemsize = jnp.dtype(x.dtype).itemsize
    return pl.pallas_call(
        functools.partial(_rows_kernel, tm=tm, transposed=transposed,
                          k_tiles=k_tiles),
        out_shape=jax.ShapeDtypeStruct((m, n), x.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(n // tn, visits.count[0], k_tiles),
            in_specs=[
                pl.BlockSpec((tm, tk),
                             lambda ni, v, ki, o, g, t, c: (t[v], ki)),
                w_spec],
            out_specs=pl.BlockSpec(
                (tm, tn), lambda ni, v, ki, o, g, t, c: (t[v], ni)),
            scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32)]
            if k_tiles > 1 else []),
        compiler_params=_params(
            ('parallel', 'arbitrary', 'arbitrary'),
            dispatch.grouped_vmem_bytes(
                'rows_t' if transposed else 'rows', tm, tk, tn, itemsize)),
        interpret=dispatch.interpret_mode(),
        name='grouped_rows_t' if transposed else 'grouped_rows',
    )(*visits, x, w)


@functools.partial(jax.jit, static_argnames=('tiles',))
def over_rows_product(x: jax.Array, y: jax.Array, visits: Visits,
                      tiles) -> jax.Array:
    """x [m, a] and y [m, b] -> [groups, a, b] in x's dtype, each
    group's rows contracted; `visits` made at tiles[0] rows with
    `visit_empty`."""
    tm, tk, tn = tiles
    a, b = x.shape[1], y.shape[1]
    groups = visits.offsets.shape[0] - 1
    itemsize = jnp.dtype(x.dtype).itemsize
    return pl.pallas_call(
        functools.partial(_over_rows_kernel, tm=tm),
        out_shape=jax.ShapeDtypeStruct((groups, a, b), x.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(a // tk, b // tn, visits.count[0]),
            in_specs=[
                pl.BlockSpec((tm, tk),
                             lambda ai, bi, v, o, g, t, c: (t[v], ai)),
                pl.BlockSpec((tm, tn),
                             lambda ai, bi, v, o, g, t, c: (t[v], bi))],
            out_specs=pl.BlockSpec(
                (None, tk, tn),
                lambda ai, bi, v, o, g, t, c: (g[v], ai, bi)),
            scratch_shapes=[pltpu.VMEM((tk, tn), jnp.float32)]),
        compiler_params=_params(
            ('parallel', 'parallel', 'arbitrary'),
            dispatch.grouped_vmem_bytes('over_rows', tm, tk, tn, itemsize)),
        interpret=dispatch.interpret_mode(),
        name='grouped_over_rows',
    )(*visits, x, y)
