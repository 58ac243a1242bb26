"""Host-device overlap layer of the inference engine: batched prefill
admission and the vectorized chunk-delivery path.

Golden contract: with batch_admission on, token streams (including
logprobs, EOS cutoffs, and seeded sampling) must match the sequential
admission path's exactly — batching may only change HOW MANY device
dispatches admission takes, never what any request receives.
"""
import dataclasses
import queue
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from skypilot_tpu.infer import engine as engine_lib
from skypilot_tpu.models import llama

pytestmark = pytest.mark.heavy


@pytest.fixture(scope='module')
def small_model():
    cfg = dataclasses.replace(llama.CONFIGS['debug'], max_seq_len=64)
    model = llama.LlamaModel(cfg)
    params = jax.jit(model.init)(jax.random.PRNGKey(0),
                                 jnp.zeros((1, 8), jnp.int32))
    return model, params


def _run_burst(model, params, prompts, params_list, *, batch, **kw):
    """Submit all prompts BEFORE starting the loop (a deterministic
    same-tick burst), drain every stream, return (streams, perf)."""
    eng = engine_lib.InferenceEngine(model, params, num_slots=4,
                                     max_seq_len=64,
                                     prefill_buckets=[16],
                                     batch_admission=batch, **kw)
    qs = [eng.submit(p, sp)[1] for p, sp in zip(prompts, params_list)]
    eng.start()
    try:
        outs = []
        for q in qs:
            items = []
            while True:
                it = q.get(timeout=120)
                if it is None:
                    break
                items.append(it)
            outs.append(items)
    finally:
        eng.stop()
    return outs, dict(eng.perf)


def test_burst_uses_one_prefill_dispatch(small_model):
    """A same-bucket burst that fits the free slots must prefill in ONE
    device dispatch (the sequential path takes one per request)."""
    model, params = small_model
    prompts = [[1, 2, 3], [7, 8], [5, 5, 5, 5]]   # all bucket 16
    sps = [engine_lib.SamplingParams(max_new_tokens=4)
           for _ in prompts]
    outs, perf = _run_burst(model, params, prompts, sps, batch=True)
    assert perf['admitted_requests'] == 3
    assert perf['prefill_dispatches'] == 1
    assert perf['admission_batch_size'] == 3
    assert all(len(o) == 4 for o in outs)
    # And the sequential reference really does take one per request.
    _, perf_seq = _run_burst(model, params, prompts, sps, batch=False)
    assert perf_seq['prefill_dispatches'] == 3
    assert perf['prefill_dispatches'] < perf_seq['prefill_dispatches']


def test_batched_streams_match_sequential_greedy(small_model):
    model, params = small_model
    prompts = [[1, 2, 3], [7, 8], [5, 5, 5, 5], [9, 1]]
    sps = [engine_lib.SamplingParams(max_new_tokens=6)
           for _ in prompts]
    got, _ = _run_burst(model, params, prompts, sps, batch=True)
    want, _ = _run_burst(model, params, prompts, sps, batch=False)
    assert got == want


def test_batched_streams_match_sequential_sampled(small_model):
    """Seeded temperature/top-k/top-p sampling: identical req-id order
    means identical rng streams, so outputs must match token for
    token."""
    model, params = small_model
    prompts = [[3, 1, 4], [1, 5, 9, 2], [6, 5]]
    sps = [engine_lib.SamplingParams(max_new_tokens=6, temperature=0.9,
                                     top_k=8, top_p=0.95, seed=s)
           for s in (11, 22, 33)]
    got, _ = _run_burst(model, params, prompts, sps, batch=True)
    want, _ = _run_burst(model, params, prompts, sps, batch=False)
    assert got == want


def test_batched_streams_match_sequential_logprobs(small_model):
    model, params = small_model
    prompts = [[2, 4, 6], [8, 10]]
    sps = [engine_lib.SamplingParams(max_new_tokens=5, logprobs=True)
           for _ in prompts]
    got, _ = _run_burst(model, params, prompts, sps, batch=True)
    want, _ = _run_burst(model, params, prompts, sps, batch=False)
    for g, w in zip(got, want):
        assert [t for t, _ in g] == [t for t, _ in w]
        np.testing.assert_allclose([lp for _, lp in g],
                                   [lp for _, lp in w],
                                   rtol=1e-5, atol=1e-6)


def test_eos_mid_chunk_cutoff_matches(small_model):
    """EOS landing mid-decode-chunk: the vectorized cutoff must deliver
    exactly up to and including the EOS token on both paths."""
    model, params = small_model
    prompt = [5, 17, 3, 99, 42]
    sp = engine_lib.SamplingParams(max_new_tokens=12)
    ref, _ = _run_burst(model, params, [prompt], [sp], batch=False)
    assert len(ref[0]) >= 4
    # The first token past the second that the stream has not produced
    # before: as EOS it cuts mid-chunk whatever the seeded numerics.
    cut = next(i for i in range(2, len(ref[0]))
               if ref[0][i] not in ref[0][:i])
    sp_eos = engine_lib.SamplingParams(max_new_tokens=12,
                                       eos_token=ref[0][cut])
    for batch in (False, True):
        got, _ = _run_burst(model, params, [prompt, [7, 8]],
                            [sp_eos, engine_lib.SamplingParams(
                                max_new_tokens=12)], batch=batch)
        assert got[0] == ref[0][:cut + 1]    # ends AT the eos token
        assert got[1] == _run_burst(model, params, [[7, 8]],
                                    [engine_lib.SamplingParams(
                                        max_new_tokens=12)],
                                    batch=False)[0][0]


def test_cancel_mid_stream_terminates_and_frees_slot(small_model):
    """Cancel while decoding: the stream ends (None) without the full
    max_new_tokens, the slot frees, and the engine keeps serving."""
    model, params = small_model
    eng = engine_lib.InferenceEngine(model, params, num_slots=2,
                                     max_seq_len=64,
                                     prefill_buckets=[16],
                                     decode_chunk=2)
    eng.start()
    try:
        rid, q = eng.submit([5, 17, 3],
                            engine_lib.SamplingParams(
                                max_new_tokens=48))
        got = [q.get(timeout=120)]           # stream is live
        assert eng.cancel(rid)
        deadline = time.time() + 60
        while time.time() < deadline:
            it = q.get(timeout=120)
            got.append(it)
            if it is None:
                break
        assert got[-1] is None
        assert len(got) - 1 < 48             # actually cut short
        # Slot really freed: a fresh request still completes.
        out = eng.generate([7, 8], engine_lib.SamplingParams(
            max_new_tokens=3))
        assert len(out) == 3
    finally:
        eng.stop()


def test_burst_larger_than_slots_batches_in_waves(small_model):
    """More requests than slots: admission proceeds in batched waves as
    slots free; total dispatches stay below one per request."""
    model, params = small_model
    prompts = [[(i * 3 + j) % 50 + 1 for j in range(6)]
               for i in range(8)]
    sps = [engine_lib.SamplingParams(max_new_tokens=5)
           for _ in prompts]
    got, perf = _run_burst(model, params, prompts, sps, batch=True)
    assert perf['admitted_requests'] == 8
    assert perf['prefill_dispatches'] < 8
    want, _ = _run_burst(model, params, prompts, sps, batch=False)
    assert got == want


def test_batched_admission_paged_mode(small_model):
    """Paged cache: the batch path reserves pages per request and
    scatters rows from one batched prefill; streams match the
    sequential paged path."""
    model, params = small_model
    prompts = [[1, 2, 3], [7, 8], [5, 5, 5, 5]]
    sps = [engine_lib.SamplingParams(max_new_tokens=5)
           for _ in prompts]
    got, perf = _run_burst(model, params, prompts, sps, batch=True,
                           cache_mode='paged', page_size=16,
                           prefix_caching=False)
    want, _ = _run_burst(model, params, prompts, sps, batch=False,
                         cache_mode='paged', page_size=16,
                         prefix_caching=False)
    assert got == want
    assert perf['prefill_dispatches'] == 1
    assert perf['admitted_requests'] == 3


def test_perf_stats_concurrent_with_appends(small_model):
    """ADVICE r5: /stats percentile math over the TTFT deque must not
    race the engine thread's appends — hammer perf_stats() while
    requests complete."""
    model, params = small_model
    eng = engine_lib.InferenceEngine(model, params, num_slots=2,
                                     max_seq_len=64,
                                     prefill_buckets=[16])
    eng.start()
    errs = []

    def hammer():
        deadline = time.time() + 8
        while time.time() < deadline:
            try:
                eng.perf_stats()
                eng.stats()
            except Exception as e:  # pylint: disable=broad-except
                errs.append(e)
                return
    t = threading.Thread(target=hammer)
    t.start()
    try:
        for i in range(6):
            eng.generate([i + 1, i + 2],
                         engine_lib.SamplingParams(max_new_tokens=2))
    finally:
        t.join()
        eng.stop()
    assert not errs


def test_batched_put_preserves_queue_protocol():
    q = queue.Queue()
    engine_lib._put_many(q, [1, 2, 3])
    engine_lib._put_many(q, [])
    q.put(None)
    assert [q.get() for _ in range(4)] == [1, 2, 3, None]
