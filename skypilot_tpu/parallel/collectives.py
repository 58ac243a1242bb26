"""Collective-communication benchmark: the ICI/DCN `nccl_test` analog.

Reference: examples/nccl_test.yaml runs nccl-tests' all_reduce_perf over
2 nodes (sample output 3.85 GBps bus bandwidth, 16 ranks — BASELINE.md).
On TPU the collectives are XLA-compiled over ICI, so the benchmark is a
jitted psum/all-gather/ppermute over a mesh axis, timed after warmup.

Run standalone on any host (real TPU slice or CPU mesh):
    python -m skypilot_tpu.parallel.collectives --axis tp --mb 64

``--json <path>`` additionally writes a structured artifact
(``status: ok | device_error``) so harnesses parse results instead of
scraping prose; a failed op also makes the exit code non-zero. A
backend that cannot initialise is JAX's own error. Payloads are MiB
(2**20 bytes), matching the docs.
"""
import argparse
import json
import os
import sys
import time
from typing import Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from skypilot_tpu.parallel import mesh as mesh_lib

# bus-bandwidth correction factors (match nccl-tests conventions):
# all-reduce moves 2(n-1)/n bytes per byte of payload per rank.
def _busbw_factor(op: str, n: int) -> float:
    if n <= 1:
        return 0.0
    if op == 'all_reduce':
        return 2.0 * (n - 1) / n
    if op in ('all_gather', 'reduce_scatter'):
        return (n - 1) / n
    if op == 'ppermute':
        return 1.0
    raise ValueError(f'unknown op {op}')


# Public name for the census/estimate consumers (comms_census.py):
# predicted_time = payload_bytes * busbw_factor(op, n) / busbw.
busbw_factor = _busbw_factor

# The canonical op set, shared by bench_all, the CLI, and the comms
# probe sweep (comms_profile.probe_mesh) — one list, no drift.
DEFAULT_OPS = ('all_reduce', 'all_gather', 'reduce_scatter',
               'ppermute')


def _make_op(op: str, axis: str, mesh: Mesh):
    n = mesh.shape[axis]

    def all_reduce(x):
        return jax.lax.psum(x, axis)

    def all_gather(x):
        return jax.lax.all_gather(x, axis)

    def reduce_scatter(x):
        return jax.lax.psum_scatter(x, axis, tiled=True)

    def ppermute(x):
        perm = [(i, (i + 1) % n) for i in range(n)]
        return jax.lax.ppermute(x, axis, perm)

    fns = {'all_reduce': all_reduce, 'all_gather': all_gather,
           'reduce_scatter': reduce_scatter, 'ppermute': ppermute}
    return fns[op]


def bench_collective(mesh: Mesh, axis: str, op: str,
                     payload_mb: float = 64.0,
                     iters: int = 10,
                     clock: Callable[[], float] = time.perf_counter
                     ) -> Dict[str, float]:
    """Time `op` over `axis`; returns {algbw_gbps, busbw_gbps, time_ms}.

    Payload is the per-device shard size in MiB (matching nccl-tests'
    per-rank message size convention). `clock` is injectable so the
    comms-profile probe replays deterministically in tests.
    """
    n = mesh.shape[axis]
    # Round to a multiple of n: psum_scatter(tiled=True) needs the
    # scattered dimension divisible by the axis size. MiB, not 1e6:
    # the docs and the profile's payload buckets are power-of-two.
    elems = max(n, int(payload_mb * (2 ** 20) / 4) // n * n)
    spec = P(axis)
    sharding = NamedSharding(mesh, spec)
    # Materialize directly sharded (jit with out_shardings): a host-side
    # global array would hold n x payload on one device first and cannot
    # target non-addressable (multi-host) meshes at all.
    x = jax.jit(lambda: jnp.ones((n * elems,), jnp.float32),
                out_shardings=sharding)()

    inner = _make_op(op, axis, mesh)

    def _sharded(x):
        y = inner(x)
        # Reduce to a scalar so the collective cannot be DCE'd and the
        # output layout doesn't dominate timing; the closing psum makes
        # the output provably replicated (shard_map out_specs=P()).
        return jax.lax.psum(jnp.sum(y[..., :1]), axis)

    fn = jax.jit(jax.shard_map(_sharded, mesh=mesh, in_specs=spec,
                               out_specs=P()))

    fn(x).block_until_ready()  # compile + warm
    start = clock()
    for _ in range(iters):
        out = fn(x)
    out.block_until_ready()
    elapsed = max((clock() - start) / iters, 1e-12)

    # nccl-tests size conventions: all_reduce/ppermute report the
    # per-rank buffer; all_gather/reduce_scatter report the total
    # (gathered / pre-reduce) buffer — busbw factors above assume this.
    payload_bytes = elems * 4
    if op in ('all_gather', 'reduce_scatter'):
        payload_bytes *= n
    algbw = payload_bytes / elapsed / 1e9
    busbw = algbw * _busbw_factor(op, n)
    return {'op': op, 'axis': axis, 'ranks': n,
            'payload_mb': payload_mb,
            'time_ms': elapsed * 1e3,
            'algbw_gbps': algbw, 'busbw_gbps': busbw}


def bench_all(mesh: Mesh, axis: str, payload_mb: float = 64.0,
              ops: Optional[List[str]] = None,
              iters: int = 10) -> List[Dict[str, float]]:
    ops = ops or list(DEFAULT_OPS)
    return [bench_collective(mesh, axis, op, payload_mb, iters=iters)
            for op in ops]


def main(argv=None) -> None:
    from skypilot_tpu.utils import compile_cache
    compile_cache.configure()
    parser = argparse.ArgumentParser()
    parser.add_argument('--axis', default='tp')
    parser.add_argument('--mb', type=float, default=64.0,
                        help='per-device payload in MiB (2**20 bytes)')
    parser.add_argument('--ops', nargs='*', default=None)
    parser.add_argument('--iters', type=int, default=10)
    parser.add_argument('--json', default=None, metavar='PATH',
                        help='write a structured artifact (results + '
                             'status) instead of relying on prose')
    args = parser.parse_args(argv)

    artifact: Dict[str, object] = {
        'axis': args.axis, 'payload_mib': args.mb,
        'ops': args.ops, 'results': [], 'status': 'ok',
    }

    def _emit() -> None:
        if args.json:
            tmp = args.json + '.tmp'
            with open(tmp, 'w', encoding='utf-8') as f:
                json.dump(artifact, f, indent=1)
            os.replace(tmp, args.json)
        if artifact['status'] != 'ok':
            print(f"status: {artifact['status']}: "
                  f"{artifact.get('error')}", file=sys.stderr)

    devices = jax.devices()
    n = len(devices)
    spec = mesh_lib.MeshSpec(**{args.axis: n})
    mesh = mesh_lib.build_mesh(spec, devices)
    artifact.update(n_devices=n, device_kind=devices[0].device_kind,
                    platform=devices[0].platform)
    print(f'# {n}x {devices[0].device_kind} over axis {args.axis!r}')
    ops = args.ops or list(DEFAULT_OPS)
    results: List[Dict[str, float]] = artifact['results']  # type: ignore
    for op in ops:
        try:
            r = bench_collective(mesh, args.axis, op, args.mb,
                                 iters=args.iters)
        except Exception as e:  # pylint: disable=broad-except
            # One op lowering/executing badly must not cost the other
            # ops' numbers; the artifact names the failure.
            artifact['status'] = 'device_error'
            artifact['error'] = f'{op}: {e!r}'
            print(f'# {op} failed: {e!r}', file=sys.stderr)
            continue
        results.append(r)
        print(f"{r['op']:<16} ranks={r['ranks']} "
              f"payload={r['payload_mb']:.0f}MiB "
              f"time={r['time_ms']:.2f}ms "
              f"algbw={r['algbw_gbps']:.2f}GB/s "
              f"busbw={r['busbw_gbps']:.2f}GB/s")
    _emit()
    if artifact['status'] != 'ok':
        raise SystemExit(1)


if __name__ == '__main__':
    main()
