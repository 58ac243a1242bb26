"""Pipeline parallelism over the 'pp' mesh axis.

The reference ships pipeline parallelism only as a DeepSpeed recipe
(examples/deepspeed-multinode/sky.yaml — launcher + NCCL, SURVEY.md
§2.10); here it is a first-class SPMD transform: stages are the
pp-sharded leading axis of a stacked parameter pytree, activations flow
stage-to-stage via `jax.lax.ppermute` ring hops (ICI neighbors on a TPU
torus), and the GPipe fill/drain schedule is a `lax.scan` — so XLA sees
one fused program and overlaps each hop with the next microbatch's
compute.

Schedule (fill-and-drain, M microbatches over S stages, T = M+S-1 ticks):

    tick t: stage 0 ingests microbatch t (while t < M);
            every stage applies its layer block to its current activation;
            results rotate +1 around the ring;
            stage S-1 emits microbatch t-S+1 (once t >= S-1).

Bubble fraction is (S-1)/T — choose M >= 4*S to amortize. Gradients flow
through ppermute (it is linear), so `jax.grad` of a pipelined forward
works unmodified.
"""
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def pipeline_apply(
    stage_fn: Callable[[Any, jax.Array], jax.Array],
    stacked_params: Any,
    microbatches: jax.Array,
    mesh: Mesh,
) -> jax.Array:
    """Run a pipelined forward pass.

    Args:
      stage_fn: (stage_params, activation [B, ...]) -> activation. One
        stage's computation (e.g. L/S transformer layers).
      stacked_params: pytree whose leaves have leading axis S (= pp size);
        leaf i holds stage i's params. Shard this axis over 'pp'.
      microbatches: [M, B, ...] microbatched input (replicated over pp).
      mesh: a mesh containing a 'pp' axis (other axes may be in use by
        the stage_fn's own shardings).

    Returns: [M, B, ...] outputs (replicated over pp).
    """
    num_stages = mesh.shape['pp']
    num_micro = microbatches.shape[0]
    if num_micro < num_stages:
        raise ValueError(
            f'need at least as many microbatches ({num_micro}) as pipeline '
            f'stages ({num_stages})')

    def _pipelined(params, xs):
        # Inside shard_map over 'pp': params leaves are [1, ...] local
        # slices; xs is the full [M, B, ...] (replicated).
        stage = jax.lax.axis_index('pp')
        local = jax.tree.map(lambda p: jnp.squeeze(p, axis=0), params)
        total = num_micro + num_stages - 1
        # Mark the carries as device-varying over 'pp' up front: the scan
        # body produces pp-varying values (ppermute / stage-dependent
        # writes), and scan requires carry types to be invariant.
        def _vary(x):
            return jax.lax.pcast(x, ('pp',), to='varying')
        out_buf = _vary(jnp.zeros_like(xs))
        # Carry: activation entering this stage at the current tick.
        state = _vary(jnp.zeros_like(xs[0]))

        def tick(carry, t):
            state, out_buf = carry
            x_t = jax.lax.dynamic_index_in_dim(
                xs, jnp.clip(t, 0, num_micro - 1), axis=0,
                keepdims=False)
            inp = jnp.where(stage == 0, x_t, state)
            y = stage_fn(local, inp)
            # Last stage writes microbatch (t - S + 1) once the pipe is
            # full. Clamp the index and mask the write elsewhere.
            m_idx = jnp.clip(t - (num_stages - 1), 0, num_micro - 1)
            is_emit = jnp.logical_and(stage == num_stages - 1,
                                      t >= num_stages - 1)
            cur = jax.lax.dynamic_index_in_dim(out_buf, m_idx, axis=0,
                                               keepdims=False)
            new = jnp.where(is_emit, y, cur)
            out_buf = jax.lax.dynamic_update_index_in_dim(
                out_buf, new, m_idx, axis=0)
            # Rotate activations one stage forward (ICI neighbor hop).
            perm = [(i, (i + 1) % num_stages) for i in range(num_stages)]
            state = jax.lax.ppermute(y, 'pp', perm)
            return (state, out_buf), None

        (state, out_buf), _ = jax.lax.scan(
            tick, (state, out_buf), jnp.arange(total))
        # Only the last stage holds real outputs; psum replicates them
        # (every other stage contributes zeros).
        out_buf = jnp.where(stage == num_stages - 1, out_buf,
                            jnp.zeros_like(out_buf))
        return jax.lax.psum(out_buf, 'pp')

    in_specs = (jax.tree.map(lambda _: P('pp'), stacked_params), P())
    return jax.shard_map(_pipelined, mesh=mesh, in_specs=in_specs,
                         out_specs=P())(stacked_params, microbatches)


def stack_stage_params(per_stage_params) -> Any:
    """[pytree, ...] (one per stage, same structure) -> stacked pytree
    with leading stage axis, ready to shard over 'pp'."""
    return jax.tree.map(lambda *leaves: jnp.stack(leaves),
                        *per_stage_params)


def microbatch(x: jax.Array, num_microbatches: int) -> jax.Array:
    """[B, ...] -> [M, B/M, ...]."""
    if x.shape[0] % num_microbatches:
        raise ValueError(f'batch {x.shape[0]} not divisible by '
                         f'{num_microbatches} microbatches')
    return x.reshape(num_microbatches, x.shape[0] // num_microbatches,
                     *x.shape[1:])


def unmicrobatch(x: jax.Array) -> jax.Array:
    """[M, Bm, ...] -> [M*Bm, ...]."""
    return x.reshape(x.shape[0] * x.shape[1], *x.shape[2:])


def pipeline_loss_fn(
    stage_fn: Callable[[Any, jax.Array], jax.Array],
    loss_fn: Callable[[jax.Array, jax.Array], jax.Array],
    mesh: Mesh,
    num_microbatches: int,
) -> Callable[[Any, jax.Array, jax.Array], jax.Array]:
    """Wrap a stage function into a pipelined scalar-loss function
    suitable for jax.grad: (stacked_params, batch, targets) -> loss."""

    def fn(stacked_params, batch, targets):
        mb = microbatch(batch, num_microbatches)
        out = pipeline_apply(stage_fn, stacked_params, mb, mesh)
        return loss_fn(unmicrobatch(out), targets)

    return fn
