"""Multi-host serving: one engine per host in SPMD lockstep.

A serving replica can be a whole multi-host TPU slice (the reference
serves TP across a full replica cluster: llm/vllm/serve.yaml
`--tensor-parallel-size $SKYPILOT_NUM_GPUS_PER_NODE`, replica = cluster
in sky/serve/replica_managers.py:57). On TPU the natural analog is the
training gang contract (runtime/gang.py): every host process joins one
`jax.distributed` runtime, the model + KV cache shard over a global
mesh, and — because multi-host XLA is SPMD — every process must issue
the SAME device computations in the same order.

Design: host 0 (the *primary*) owns HTTP, admission and sampling
decisions exactly as in the single-host engine; follower hosts run the
same engine loop but take their control inputs (new requests, cancels,
stop) from a per-tick broadcast instead of a local queue. Everything
else the loop decides — admission order, chunk sizes, termination — is
a deterministic function of those inputs plus device results that are
themselves identical on every host (one global computation), so the
hosts stay in lockstep without any further coordination. The broadcast
rides the same ICI/DCN fabric as the compute
(jax.experimental.multihost_utils.broadcast_one_to_all), no side RPC
channel.

An idle tick broadcasts 8 bytes (the empty-control fast path); a tick
with traffic broadcasts length + pickled control blob.
"""
import pickle
from typing import Any, Optional

import numpy as np

from skypilot_tpu.utils import log_utils

logger = log_utils.init_logger(__name__)


class LockstepSync:
    """Per-tick control-plane broadcast from the primary host.

    All hosts must call broadcast() the same number of times in the
    same order (the engine loop guarantees one call per tick).
    """

    def __init__(self) -> None:
        import jax
        self.process_index = jax.process_index()
        self.num_processes = jax.process_count()
        self.is_primary = self.process_index == 0

    def broadcast(self, obj: Optional[Any]) -> Any:
        """Primary: broadcast `obj` to every host; followers pass None
        and receive the primary's object. None/empty objects take the
        8-byte fast path (no payload round)."""
        from jax.experimental import multihost_utils
        if self.is_primary:
            payload = (np.frombuffer(pickle.dumps(obj), np.uint8)
                       if obj is not None else
                       np.zeros((0,), np.uint8))
            n = np.array([payload.size], np.int64)
        else:
            payload = None
            n = np.zeros((1,), np.int64)
        n = multihost_utils.broadcast_one_to_all(n)
        size = int(n[0])
        if size == 0:
            return None
        buf = payload if self.is_primary else np.zeros((size,), np.uint8)
        buf = multihost_utils.broadcast_one_to_all(buf)
        return pickle.loads(np.asarray(buf).tobytes())


class DiscardQueue:
    """out_queue stand-in on follower hosts: tokens are delivered by
    the primary; followers only need the queue protocol to exist."""

    def put(self, item: Any) -> None:
        del item

    def get(self, *args: Any, **kwargs: Any) -> None:
        raise RuntimeError('follower-host queues carry no tokens; '
                           'consume results on the primary host')


def initialize_from_env(coordinator: Optional[str] = None,
                        num_processes: Optional[int] = None,
                        process_id: Optional[int] = None) -> LockstepSync:
    """Join the jax.distributed runtime and return the sync handle.

    With no args this honors the gang env contract
    (JAX_COORDINATOR_ADDRESS / JAX_NUM_PROCESSES / JAX_PROCESS_ID,
    runtime/gang.py:70) — the same bootstrap a training job uses, so a
    serve replica spanning a multi-host slice needs no extra config.
    """
    import jax
    if coordinator is not None:
        jax.distributed.initialize(coordinator_address=coordinator,
                                   num_processes=num_processes,
                                   process_id=process_id)
    else:
        from skypilot_tpu.runtime import gang
        gang.initialize_jax_distributed()
    logger.info('multihost serving: process %d/%d, %d global devices',
                jax.process_index(), jax.process_count(),
                jax.device_count())
    return LockstepSync()


# --------------------------------------------------------------- selftest
# Reused by tests/test_multihost_engine.py AND __graft_entry__.py's
# serving dryrun: N real processes on the CPU backend prove the
# lockstep protocol end to end without TPU hosts.

def _selftest_worker(coord_port: int, nprocs: int, rank: int,
                     out_path: str) -> None:
    import json

    import jax

    sync = initialize_from_env(coordinator=f'127.0.0.1:{coord_port}',
                               num_processes=nprocs, process_id=rank)
    from skypilot_tpu.infer import engine as engine_lib
    from skypilot_tpu.infer import server as server_lib
    eng = server_lib.build_engine(
        'debug', num_slots=2, max_seq_len=64, tp=jax.device_count(),
        cache_mode='paged', lockstep=sync)
    eng.start()
    if sync.is_primary:
        greedy = eng.generate(
            [5, 17, 3, 99, 42],
            engine_lib.SamplingParams(max_new_tokens=6))
        sampled = eng.generate(
            [9, 9, 9],
            engine_lib.SamplingParams(max_new_tokens=5, temperature=0.7,
                                      top_k=8, seed=3))
        # Cancel under lockstep: the flag must flip on every host at
        # the SAME tick (slot release changes the next tick's batch) —
        # the most divergence-prone path. Cancel a long request
        # mid-stream, then prove the hosts are still in lockstep by
        # running one more request to completion.
        rid, q = eng.submit([2, 4, 6], engine_lib.SamplingParams(
            max_new_tokens=48))
        got = 0
        while got < 2:
            if q.get(timeout=300) is None:
                break
            got += 1
        eng.cancel(rid)   # may race completion; either way drains
        while q.get(timeout=300) is not None:
            pass                       # drained to the terminal None
        after_cancel = eng.generate(
            [5, 17, 3, 99, 42],
            engine_lib.SamplingParams(max_new_tokens=6))
        with open(out_path, 'w', encoding='utf-8') as f:
            json.dump({'greedy': greedy, 'sampled': sampled,
                       'after_cancel': after_cancel}, f)
        eng.stop()
    else:
        eng.join()


def run_selftest_gang(nprocs: int, devices_per_proc: int, out_path: str,
                      log_dir: str, timeout: float = 900.0) -> dict:
    """Spawn the selftest as `nprocs` REAL OS processes on the CPU
    backend and return rank 0's output dict.

    Shared by tests/test_multihost_engine.py and __graft_entry__.py's
    serving dryrun — one harness, so cleanup rules (kill survivors on
    any failure, log files instead of undrained PIPEs) can't drift
    between the two.
    """
    import json
    import os
    import socket
    import subprocess
    import sys

    with socket.socket() as s:
        s.bind(('127.0.0.1', 0))
        port = s.getsockname()[1]
    env = dict(os.environ)
    env['JAX_PLATFORMS'] = 'cpu'
    env['XLA_FLAGS'] = ('--xla_force_host_platform_device_count='
                        f'{devices_per_proc}')
    # A leftover gang env (from an outer harness) must not leak into
    # the workers' initialize path.
    for k in ('JAX_COORDINATOR_ADDRESS', 'JAX_NUM_PROCESSES',
              'JAX_PROCESS_ID'):
        env.pop(k, None)
    log_paths = [os.path.join(log_dir, f'mh-rank{r}.log')
                 for r in range(nprocs)]
    logs = [open(p, 'wb') for p in log_paths]
    procs = [subprocess.Popen(
        [sys.executable, '-m', 'skypilot_tpu.infer.multihost',
         '--selftest-port', str(port),
         '--selftest-nprocs', str(nprocs),
         '--selftest-rank', str(rank),
         '--selftest-out', out_path],
        stdout=logs[rank], stderr=subprocess.STDOUT, env=env)
        for rank in range(nprocs)]
    try:
        for rank, p in enumerate(procs):
            rc = p.wait(timeout=timeout)
            with open(log_paths[rank], encoding='utf-8',
                      errors='replace') as f:
                tail = f.read()[-3000:]
            assert rc == 0, \
                f'multihost selftest rank {rank} rc={rc}:\n{tail}'
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
        for f in logs:
            f.close()
    with open(out_path, encoding='utf-8') as f:
        return json.load(f)


def main(argv=None) -> None:
    import argparse

    from skypilot_tpu.utils import compile_cache
    compile_cache.configure()

    parser = argparse.ArgumentParser()
    parser.add_argument('--selftest-port', type=int, required=True)
    parser.add_argument('--selftest-nprocs', type=int, required=True)
    parser.add_argument('--selftest-rank', type=int, required=True)
    parser.add_argument('--selftest-out', required=True)
    args = parser.parse_args(argv)
    _selftest_worker(args.selftest_port, args.selftest_nprocs,
                     args.selftest_rank, args.selftest_out)


if __name__ == '__main__':
    main()
