"""Paged-cache engine integration: outputs must match the dense engine
token-for-token, more requests must fit at equal HBM, and pool
exhaustion must defer (not drop or corrupt) admissions."""
import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from skypilot_tpu.infer import engine as engine_lib
from skypilot_tpu.models import llama

# Compile-heavy (JAX jit on the 1-core CPU host) or subprocess-driven:
pytestmark = pytest.mark.heavy


def _model_and_params(scan_layers=True):
    cfg = dataclasses.replace(llama.CONFIGS['debug'],
                              scan_layers=scan_layers)
    model = llama.LlamaModel(cfg)
    sample = jnp.zeros((1, 8), jnp.int32)
    params = jax.jit(model.init)(jax.random.PRNGKey(0), sample)
    return model, params


@pytest.fixture(scope='module')
def debug_model():
    """The debug model and its parameters, once for the module."""
    return _model_and_params()


@pytest.fixture(scope='module')
def dense_2x64(debug_model):
    """The dense reference at the shape most tests compare against
    (2 slots, 64 tokens), once for the module: it is restartable and
    keeps nothing of one run in the next."""
    model, params = debug_model
    return engine_lib.InferenceEngine(model, params, num_slots=2,
                                      max_seq_len=64, cache_mode='dense')


def _run(engine, prompts, max_new=8):
    engine.start()
    try:
        pairs = [engine.submit(p, engine_lib.SamplingParams(
            max_new_tokens=max_new)) for p in prompts]
        outs = []
        for _, q in pairs:
            toks = []
            while True:
                t = q.get(timeout=300)
                if t is None:
                    break
                toks.append(t)
            outs.append(toks)
        return outs
    finally:
        engine.stop()


def _prompts(vocab, lens, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, vocab, n).tolist() for n in lens]


@pytest.mark.parametrize('scan_layers', [True, False])
def test_paged_matches_dense(scan_layers, debug_model):
    # The unrolled layout is this test's alone, so it builds its own.
    model, params = (debug_model if scan_layers
                     else _model_and_params(scan_layers=False))
    vocab = model.cfg.vocab_size
    prompts = _prompts(vocab, [5, 17, 33, 9])
    dense = engine_lib.InferenceEngine(model, params, num_slots=2,
                                       max_seq_len=64,
                                       cache_mode='dense')
    paged = engine_lib.InferenceEngine(model, params, num_slots=2,
                                       max_seq_len=64,
                                       cache_mode='paged', page_size=16)
    out_d = _run(dense, prompts)
    out_p = _run(paged, prompts)
    assert out_d == out_p
    assert all(len(o) == 8 for o in out_p)


def test_paged_holds_more_requests_at_equal_hbm(debug_model):
    """Pool sized to the DENSE equivalent of 2 slots serves 4 concurrent
    requests (2x request depth at equal cache HBM) because reservations
    track prompt+max_new, not max_seq."""
    model, params = debug_model
    vocab = model.cfg.vocab_size
    max_seq, p = 64, 16
    paged = engine_lib.InferenceEngine(
        model, params, num_slots=4, max_seq_len=max_seq,
        cache_mode='paged', page_size=p,
        pool_tokens=2 * max_seq)   # = dense 2-slot cache HBM
    # 4 requests x (prompt 17 + 8 new = 25 tokens -> 2 pages = 32
    # tokens) = 128 tokens = the whole pool: all four fit concurrently.
    prompts = _prompts(vocab, [17, 17, 17, 17])
    outs = _run(paged, prompts)
    assert all(len(o) == 8 for o in outs)
    # And the pool really was capped at the dense-2-slot budget.
    assert (paged.pool.cfg.n_pages - 1) * p == 2 * max_seq

    # Reference: dense engine (4 slots, plenty of HBM) same outputs.
    dense = engine_lib.InferenceEngine(model, params, num_slots=4,
                                       max_seq_len=max_seq,
                                       cache_mode='dense')
    assert _run(dense, prompts) == outs


def test_pool_exhaustion_defers_not_drops(debug_model, dense_2x64):
    """A pool that fits only one request at a time still completes a
    burst of three, in order, with correct outputs."""
    model, params = debug_model
    vocab = model.cfg.vocab_size
    paged = engine_lib.InferenceEngine(
        model, params, num_slots=2, max_seq_len=64,
        cache_mode='paged', page_size=16,
        pool_tokens=32)   # 2 pages: one 17+8 request at a time
    prompts = _prompts(vocab, [17, 17, 17])
    outs = _run(paged, prompts)
    assert all(len(o) == 8 for o in outs)
    assert _run(dense_2x64, prompts) == outs
    # All pages returned to the free list after the burst.
    assert paged.pool.free_pages() == paged.pool.cfg.n_pages - 1


def test_slot_reuse_no_corruption(debug_model, dense_2x64):
    """Sequential waves re-admit into released slots/pages; later waves
    must not see earlier waves' KV."""
    model, params = debug_model
    vocab = model.cfg.vocab_size
    paged = engine_lib.InferenceEngine(model, params, num_slots=2,
                                       max_seq_len=64,
                                       cache_mode='paged', page_size=16)
    dense = dense_2x64
    w1 = _prompts(vocab, [9, 21], seed=1)
    w2 = _prompts(vocab, [33, 5], seed=2)
    paged.start()
    dense.start()
    try:
        for wave in (w1, w2):
            p_out = [q for _, q in
                     [paged.submit(x, engine_lib.SamplingParams(
                         max_new_tokens=6)) for x in wave]]
            d_out = [q for _, q in
                     [dense.submit(x, engine_lib.SamplingParams(
                         max_new_tokens=6)) for x in wave]]

            def drain(qs):
                res = []
                for q in qs:
                    toks = []
                    while True:
                        t = q.get(timeout=300)
                        if t is None:
                            break
                        toks.append(t)
                    res.append(toks)
                return res
            assert drain(p_out) == drain(d_out)
    finally:
        paged.stop()
        dense.stop()


def test_moe_paged_matches_dense():
    """The MoE model shares LlamaAttention, so paged decode works for
    Mixtral-style serving too (reference analog: llm/mixtral/serve.yaml
    via vLLM's paged attention)."""
    import dataclasses as _dc

    from skypilot_tpu.models import moe

    cfg, moe_cfg = moe.MIXTRAL_CONFIGS['debug-moe']
    cfg = _dc.replace(cfg, max_seq_len=64)
    moe_cfg = _dc.replace(moe_cfg, capacity_factor=8.0)
    model = moe.MixtralModel(cfg, moe_cfg)
    params = jax.jit(model.init)(jax.random.PRNGKey(0),
                                 jnp.zeros((1, 8), jnp.int32))
    prompts = _prompts(cfg.vocab_size, [5, 19, 33])
    dense = engine_lib.InferenceEngine(model, params, num_slots=2,
                                       max_seq_len=64,
                                       cache_mode='dense')
    paged = engine_lib.InferenceEngine(model, params, num_slots=2,
                                       max_seq_len=64,
                                       cache_mode='paged', page_size=16)
    assert _run(dense, prompts, max_new=6) == _run(paged, prompts,
                                                   max_new=6)


def test_prefix_cache_matches_dense(debug_model):
    """Requests sharing a long system-prompt prefix: the paged engine
    with prefix caching must produce dense-engine outputs token-for-
    token while actually hitting the prefix cache (vLLM automatic
    prefix caching analog, llm/vllm/serve.yaml)."""
    model, params = debug_model
    vocab = model.cfg.vocab_size
    rng = np.random.default_rng(7)
    system = rng.integers(1, vocab, 40).tolist()   # 2.5 pages of 16
    prompts = [system + rng.integers(1, vocab, k).tolist()
               for k in (3, 9, 5)]
    prompts.append(list(prompts[0]))               # exact repeat
    dense = engine_lib.InferenceEngine(model, params, num_slots=2,
                                       max_seq_len=128,
                                       cache_mode='dense')
    paged = engine_lib.InferenceEngine(model, params, num_slots=2,
                                       max_seq_len=128,
                                       cache_mode='paged', page_size=16)
    assert paged.prefix_caching
    out_d = _run(dense, prompts)
    out_p = _run(paged, prompts)
    assert out_d == out_p
    # Later requests really shared the system prefix's full pages.
    assert paged.pool.prefix_stats['hit_pages'] >= 2


def test_prefix_cache_sequential_repeat(debug_model):
    """The same prompt served twice: the second admission reuses every
    full page except the last-token page and still matches."""
    model, params = debug_model
    vocab = model.cfg.vocab_size
    prompt = _prompts(vocab, [50], seed=3)[0]
    paged = engine_lib.InferenceEngine(model, params, num_slots=1,
                                       max_seq_len=128,
                                       cache_mode='paged', page_size=16)
    out1 = _run(paged, [prompt])
    hits0 = paged.pool.prefix_stats['hit_pages']
    out2 = _run(paged, [prompt])
    assert out1 == out2
    # 50 tokens / 16 = 3 full pages; lookup capped at (50-1)//16 = 3.
    assert paged.pool.prefix_stats['hit_pages'] - hits0 == 3


def test_prefix_caching_off(debug_model):
    model, params = debug_model
    vocab = model.cfg.vocab_size
    prompt = _prompts(vocab, [40], seed=4)[0]
    paged = engine_lib.InferenceEngine(model, params, num_slots=1,
                                       max_seq_len=128,
                                       cache_mode='paged', page_size=16,
                                       prefix_caching=False)
    _run(paged, [prompt])
    out = _run(paged, [prompt])
    assert paged.pool.prefix_stats['hit_pages'] == 0
    dense = engine_lib.InferenceEngine(model, params, num_slots=1,
                                       max_seq_len=128,
                                       cache_mode='dense')
    assert _run(dense, [prompt]) == out


def test_prefix_cache_suffix_bucket_overflow_falls_back(debug_model):
    """A cached prefix whose suffix bucket would spill past the per-slot
    view must fall back to a full prefill (not corrupt the cache):
    max_seq 64, pages of 16 -> view span 64; prompt 50 with 16 cached
    leaves a 34-token suffix that buckets to 64 -> 16+64 > 64."""
    model, params = debug_model
    vocab = model.cfg.vocab_size
    rng = np.random.default_rng(11)
    head = rng.integers(1, vocab, 16).tolist()
    p_a = head + rng.integers(1, vocab, 34).tolist()
    p_b = head + rng.integers(1, vocab, 34).tolist()
    paged = engine_lib.InferenceEngine(model, params, num_slots=1,
                                       max_seq_len=64,
                                       prefill_buckets=[32],
                                       cache_mode='paged', page_size=16)
    dense = engine_lib.InferenceEngine(model, params, num_slots=1,
                                       max_seq_len=64,
                                       prefill_buckets=[32],
                                       cache_mode='dense')
    assert _run(paged, [p_a, p_b], max_new=6) == \
        _run(dense, [p_a, p_b], max_new=6)


@pytest.mark.parametrize('prefix_caching', [True, False])
def test_chunked_prefill_matches(debug_model, prefix_caching):
    """Chunked prefill (vLLM analog): a long prompt prefilled in
    page-aligned chunks interleaved with the engine loop must produce
    EXACTLY the non-chunked engine's outputs, long and short requests
    alike."""
    model, params = debug_model
    vocab = model.cfg.vocab_size
    rng = np.random.default_rng(13)
    long_p = rng.integers(1, vocab, 100).tolist()
    prompts = [long_p, rng.integers(1, vocab, 9).tolist(),
               rng.integers(1, vocab, 70).tolist()]
    plain = engine_lib.InferenceEngine(
        model, params, num_slots=2, max_seq_len=256,
        cache_mode='paged', page_size=16,
        prefix_caching=prefix_caching)
    chunked = engine_lib.InferenceEngine(
        model, params, num_slots=2, max_seq_len=256,
        cache_mode='paged', page_size=16,
        prefix_caching=prefix_caching, prefill_chunk=32)
    out_p = _run(plain, prompts, max_new=8)
    out_c = _run(chunked, prompts, max_new=8)
    assert out_p == out_c
    # The long prompts really went through the chunked path.
    assert chunked.perf['prefill_chunks'] >= 100 // 32 + 70 // 32


def test_chunked_prefill_with_prefix_reuse(debug_model):
    """A chunked admission sharing a published prefix starts its chunks
    AFTER the cached span and still matches."""
    model, params = debug_model
    vocab = model.cfg.vocab_size
    rng = np.random.default_rng(17)
    base = rng.integers(1, vocab, 96).tolist()
    variants = [base + rng.integers(1, vocab, k).tolist()
                for k in (5, 40)]
    plain = engine_lib.InferenceEngine(
        model, params, num_slots=1, max_seq_len=256,
        cache_mode='paged', page_size=16)
    chunked = engine_lib.InferenceEngine(
        model, params, num_slots=1, max_seq_len=256,
        cache_mode='paged', page_size=16, prefill_chunk=32)
    assert _run(plain, variants, max_new=6) == \
        _run(chunked, variants, max_new=6)
    assert chunked.pool.prefix_stats['hit_pages'] > 0


def test_bucket_smaller_than_page(debug_model):
    """Prompt bucket (32) smaller than a page (64): the insert pads the
    prefill KV up to the page span. Regression: the pad length was read
    off the wrong pool axis after the page-major relayout, crashing
    every admission at the server's default page size."""
    model, params = debug_model
    vocab = model.cfg.vocab_size
    prompts = _prompts(vocab, [5, 9])
    paged = engine_lib.InferenceEngine(model, params, num_slots=2,
                                       max_seq_len=128,
                                       prefill_buckets=[32],
                                       cache_mode='paged', page_size=64)
    dense = engine_lib.InferenceEngine(model, params, num_slots=2,
                                       max_seq_len=128,
                                       prefill_buckets=[32],
                                       cache_mode='dense')
    assert _run(paged, prompts, max_new=4) == _run(dense, prompts,
                                                   max_new=4)


def test_chunked_prefill_delivers_logprobs():
    """The chunked-prefill admission tail must deliver the first
    token's logprob like the plain admission path (regression: the
    first_lp wiring initially missed this site and killed the loop)."""
    import dataclasses

    cfg = dataclasses.replace(llama.CONFIGS['debug'], max_seq_len=256)
    model = llama.LlamaModel(cfg)
    params = jax.jit(model.init)(jax.random.PRNGKey(0),
                                 jnp.zeros((1, 8), jnp.int32))
    eng = engine_lib.InferenceEngine(
        model, params, num_slots=2, max_seq_len=256,
        cache_mode='paged', page_size=16, prefill_chunk=32)
    eng.start()
    try:
        prompt = list(np.random.default_rng(0).integers(
            1, cfg.vocab_size, 80))   # > prefill_chunk -> chunked path
        _, q = eng.submit([int(t) for t in prompt],
                          engine_lib.SamplingParams(max_new_tokens=4,
                                                    logprobs=True))
        got = []
        while True:
            item = q.get(timeout=300)
            if item is None:
                break
            got.append(item)
    finally:
        eng.stop()
    assert len(got) == 4
    assert all(isinstance(t, tuple) and t[1] <= 0.0 for t in got)
