"""Multi-host serving engine: REAL multi-process lockstep on the CPU
backend.

Two processes join one jax.distributed runtime (1 CPU device each →
a tp=2 global mesh), run the paged engine in lockstep (primary owns
submissions; follower driven by tick broadcasts), and the primary's
tokens must equal a single-process tp=2 run of the same engine — the
same mesh partitioning, so the computation (and therefore every token)
is identical; only the process topology differs. This is the CPU
stand-in for a serving replica spanning a multi-host TPU slice
(reference: TP across a whole replica cluster, llm/vllm/serve.yaml
--tensor-parallel-size over $SKYPILOT_NUM_GPUS_PER_NODE).
"""
import pytest

from skypilot_tpu.infer import multihost

pytestmark = pytest.mark.heavy


# slow: 25 s in a six-worker run: two processes that each import JAX,
# join one jax.distributed runtime and compile the engine in lockstep,
# then a third engine in this process to compare with.
@pytest.mark.slow
@pytest.mark.integration
def test_two_process_lockstep_matches_single_process(tmp_path):
    # Reference: ONE process, 2 local devices, same tp=2 mesh.
    ref = multihost.run_selftest_gang(
        nprocs=1, devices_per_proc=2,
        out_path=str(tmp_path / 'single.json'), log_dir=str(tmp_path))
    # System under test: TWO processes, 1 device each, tp=2 global mesh.
    got = multihost.run_selftest_gang(
        nprocs=2, devices_per_proc=1,
        out_path=str(tmp_path / 'multi.json'), log_dir=str(tmp_path))

    assert got['greedy'] == ref['greedy'], (got, ref)
    assert 1 <= len(got['greedy']) <= 6
    # Sampled path: the device rng is keyed identically and the mesh
    # partitioning is identical, so tokens match too.
    assert got['sampled'] == ref['sampled'], (got, ref)
    assert 1 <= len(got['sampled']) <= 5
    # A cancel happened between the sampled run and this one (see
    # _selftest_worker): identical output proves the hosts stayed in
    # lockstep through the mid-stream slot release.
    assert got['after_cancel'] == ref['after_cancel'] == got['greedy']
