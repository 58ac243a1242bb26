"""Speculative decoding (n-gram prompt-lookup, greedy): outputs must be
EXACTLY the plain greedy engine's — drafts only ever change speed, the
acceptance gate rejects anything the model wouldn't have emitted itself.

Reference analog: vLLM speculative decoding / prompt-lookup decoding
(the reference serves via vLLM, llm/vllm/serve.yaml); here the engine is
first-class so speculation is too.
"""
import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from skypilot_tpu.infer import engine as engine_lib
from skypilot_tpu.models import llama

# Compile-heavy (JAX jit on the 1-core CPU host) or subprocess-driven:
pytestmark = pytest.mark.heavy


def _model_and_params():
    cfg = dataclasses.replace(llama.CONFIGS['debug'])
    model = llama.LlamaModel(cfg)
    sample = jnp.zeros((1, 8), jnp.int32)
    params = jax.jit(model.init)(jax.random.PRNGKey(0), sample)
    return model, params


@pytest.fixture(scope='module')
def debug_model():
    """The debug model and its parameters, once for the module."""
    return _model_and_params()


def _run(engine, prompts, max_new=16):
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


@pytest.mark.parametrize('cache_mode', ['dense', 'paged'])
def test_spec_matches_plain_greedy(debug_model, cache_mode):
    """Random prompts (low acceptance) and a periodic prompt (high
    acceptance): token-for-token equality either way."""
    model, params = debug_model
    vocab = model.cfg.vocab_size
    prompts = _prompts(vocab, [7, 19, 33])
    prompts.append([5, 9, 2] * 8)          # periodic: n-gram heaven
    plain = engine_lib.InferenceEngine(model, params, num_slots=2,
                                       max_seq_len=128,
                                       cache_mode=cache_mode)
    spec = engine_lib.InferenceEngine(model, params, num_slots=2,
                                      max_seq_len=128,
                                      cache_mode=cache_mode,
                                      spec_decode=3)
    out_p = _run(plain, prompts)
    out_s = _run(spec, prompts)
    assert out_p == out_s
    assert all(len(o) == 16 for o in out_s)
    assert spec.perf['spec_steps'] > 0


def _draft_model_and_params(seed=1, n_layers=1):
    """A smaller, independently initialized llama as the draft."""
    cfg = dataclasses.replace(llama.CONFIGS['debug'], n_layers=n_layers)
    model = llama.LlamaModel(cfg)
    params = jax.jit(model.init)(jax.random.PRNGKey(seed),
                                 jnp.zeros((1, 8), jnp.int32))
    return model, params


@pytest.mark.parametrize('cache_mode', ['dense', 'paged'])
def test_draft_model_spec_matches_plain_greedy(debug_model, cache_mode):
    """A DIFFERENT (smaller, independently initialized) draft model:
    outputs must still be token-for-token the plain greedy engine's —
    the acceptance gate makes draft quality a pure speed knob."""
    model, params = debug_model
    draft_model, draft_params = _draft_model_and_params()
    vocab = model.cfg.vocab_size
    prompts = _prompts(vocab, [7, 19, 33])
    plain = engine_lib.InferenceEngine(model, params, num_slots=2,
                                       max_seq_len=128,
                                       cache_mode=cache_mode)
    spec = engine_lib.InferenceEngine(model, params, num_slots=2,
                                      max_seq_len=128,
                                      cache_mode=cache_mode,
                                      spec_decode=3,
                                      draft_model=draft_model,
                                      draft_params=draft_params)
    out_p = _run(plain, prompts)
    out_s = _run(spec, prompts)
    assert out_p == out_s
    assert all(len(o) == 16 for o in out_s)
    assert spec.perf['spec_verify_steps'] > 0


def test_self_draft_accepts_everything(debug_model):
    """Draft == target (params shared): every greedy draft token IS the
    target's argmax, so acceptance is exactly k on every verify step —
    the mechanism's upper bound, and a strong end-to-end check that
    draft cache positions stay aligned with the target's."""
    model, params = debug_model
    k = 3
    spec = engine_lib.InferenceEngine(model, params, num_slots=2,
                                      max_seq_len=128,
                                      cache_mode='paged',
                                      spec_decode=k,
                                      draft_model=model,
                                      draft_params=params)
    plain = engine_lib.InferenceEngine(model, params, num_slots=2,
                                       max_seq_len=128,
                                       cache_mode='paged')
    prompts = _prompts(model.cfg.vocab_size, [9, 21])
    out_s = _run(spec, prompts)
    assert out_s == _run(plain, prompts)
    assert spec.perf['spec_verify_steps'] > 0
    # Full acceptance: k drafts accepted at every verify step.
    assert spec.perf['spec_accepted'] == \
        k * spec.perf['spec_verify_steps'], spec.perf


def test_draft_model_spec_sampled_completes(debug_model):
    """Sampled requests ride the same rejection-sampling verify with a
    draft-model point mass: requests complete with valid lengths and a
    same-seed rerun is deterministic."""
    model, params = debug_model
    draft_model, draft_params = _draft_model_and_params()

    def run_once():
        eng = engine_lib.InferenceEngine(model, params, num_slots=2,
                                         max_seq_len=128,
                                         cache_mode='paged',
                                         spec_decode=3,
                                         draft_model=draft_model,
                                         draft_params=draft_params)
        eng.start()
        try:
            _, q = eng.submit([3, 1, 4, 1, 5], engine_lib.SamplingParams(
                max_new_tokens=12, temperature=0.8, top_k=8, seed=42))
            toks = []
            while True:
                t = q.get(timeout=300)
                if t is None:
                    return toks
                toks.append(t)
        finally:
            eng.stop()

    a = run_once()
    b = run_once()
    assert 1 <= len(a) <= 12
    assert a == b     # keyed rng: reruns are bit-identical


def test_spec_accepts_on_looping_output(debug_model):
    """Greedy decode from a random-weight model falls into short loops;
    the proposer must convert those into accepted multi-token steps."""
    model, params = debug_model
    prompt = [5, 9, 2] * 8
    spec = engine_lib.InferenceEngine(model, params, num_slots=1,
                                      max_seq_len=256,
                                      cache_mode='paged', page_size=16,
                                      spec_decode=4)
    out = _run(spec, [prompt], max_new=64)
    assert len(out[0]) == 64
    p = spec.perf_stats()
    # Real draft acceptance happened (spec_accepted counts accepted
    # draft tokens exactly, per delivered verify step — immune to the
    # pipelined full-chunk step inflation).
    assert p['spec_accepted'] > 0, p
    # And verify steps beat 1-token-per-step on the looping tail.
    assert p['spec_accept_per_step'] > 0.2, p


def test_spec_xla_gather_fallback_matches(debug_model, monkeypatch):
    """The SKYT_SPEC_PAGED_ATTN=xla escape hatch (gather verify path)
    produces identical outputs to plain decode. The pallas MQ kernel is
    the default since the on-chip gate, so every other spec test covers
    it — this keeps the documented fallback from rotting."""
    monkeypatch.setenv('SKYT_SPEC_PAGED_ATTN', 'xla')
    model, params = debug_model
    vocab = model.cfg.vocab_size
    prompts = _prompts(vocab, [7, 19], seed=6) + [[5, 9, 2] * 8]
    plain = engine_lib.InferenceEngine(model, params, num_slots=2,
                                       max_seq_len=128,
                                       cache_mode='paged', page_size=16)
    spec = engine_lib.InferenceEngine(model, params, num_slots=2,
                                      max_seq_len=128,
                                      cache_mode='paged', page_size=16,
                                      spec_decode=3)
    assert _run(plain, prompts, max_new=12) == \
        _run(spec, prompts, max_new=12)


def test_spec_with_sampling_mix_rides_spec_path(debug_model):
    """A batch mixing greedy and temperature-sampled requests rides the
    SPEC path (rejection-sampling verify for the sampled slot, argmax
    verify for the greedy one) and finishes both."""
    model, params = debug_model
    vocab = model.cfg.vocab_size
    spec = engine_lib.InferenceEngine(model, params, num_slots=2,
                                      max_seq_len=128,
                                      cache_mode='paged', page_size=16,
                                      spec_decode=3)
    spec.start()
    try:
        _, q_g = spec.submit(_prompts(vocab, [9])[0],
                             engine_lib.SamplingParams(max_new_tokens=8))
        _, q_s = spec.submit(
            _prompts(vocab, [11], seed=1)[0],
            engine_lib.SamplingParams(max_new_tokens=8,
                                      temperature=0.9, top_k=8))
        for q in (q_g, q_s):
            toks = []
            while True:
                t = q.get(timeout=300)
                if t is None:
                    break
                toks.append(t)
            assert len(toks) == 8
        assert spec.perf['spec_verify_steps'] > 0
    finally:
        spec.stop()


def test_spec_survives_plain_interlude(debug_model):
    """While a sampled request shares the batch, chunks route through
    the plain path — which must keep the device history current so
    speculation resumes with real acceptance (and identical output)
    once the batch is greedy-only again (regression: plain chunks once
    skipped the history write, silently zeroing acceptance forever)."""
    model, params = debug_model
    vocab = model.cfg.vocab_size
    prompt = [5, 9, 2] * 8
    plain = engine_lib.InferenceEngine(model, params, num_slots=2,
                                       max_seq_len=256,
                                       cache_mode='paged', page_size=16)
    ref = _run(plain, [prompt], max_new=48)[0]

    spec = engine_lib.InferenceEngine(model, params, num_slots=2,
                                      max_seq_len=256,
                                      cache_mode='paged', page_size=16,
                                      spec_decode=4)
    spec.start()
    try:
        _, q_g = spec.submit(prompt, engine_lib.SamplingParams(
            max_new_tokens=48))
        # Sampled co-tenant forces plain-path chunks early on.
        _, q_s = spec.submit(
            _prompts(vocab, [9], seed=5)[0],
            engine_lib.SamplingParams(max_new_tokens=4,
                                      temperature=0.8))
        for q, want in ((q_s, 4), (q_g, 48)):
            toks = []
            while True:
                t = q.get(timeout=300)
                if t is None:
                    break
                toks.append(t)
            assert len(toks) == want
            if want == 48:
                assert toks == ref
    finally:
        spec.stop()
    assert spec.perf['spec_accepted'] > 0, spec.perf


def test_spec_eos_and_slot_reuse(debug_model):
    """EOS mid-accepted-run releases the slot after the EOS token and a
    re-admitted request into the same slot stays correct."""
    model, params = debug_model
    vocab = model.cfg.vocab_size
    prompts = _prompts(vocab, [9, 21, 13], seed=2)
    plain = engine_lib.InferenceEngine(model, params, num_slots=1,
                                       max_seq_len=128,
                                       cache_mode='paged', page_size=16)
    spec = engine_lib.InferenceEngine(model, params, num_slots=1,
                                      max_seq_len=128,
                                      cache_mode='paged', page_size=16,
                                      spec_decode=3)
    # Learn what token plain greedy emits 4th, then use it as EOS.
    probe = _run(plain, [prompts[0]], max_new=8)[0]
    eos = probe[3]

    def run_eos(engine):
        engine.start()
        try:
            outs = []
            for pr in prompts:
                _, q = engine.submit(pr, engine_lib.SamplingParams(
                    max_new_tokens=8, eos_token=eos))
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

    assert run_eos(plain) == run_eos(spec)


def test_spec_max_seq_tail(debug_model):
    """Requests running into max_seq_len: the spec path must hand the
    tail to the plain path instead of overrunning the cache."""
    model, params = debug_model
    vocab = model.cfg.vocab_size
    prompt = _prompts(vocab, [40], seed=3)[0]
    plain = engine_lib.InferenceEngine(model, params, num_slots=1,
                                       max_seq_len=64,
                                       cache_mode='paged', page_size=16)
    spec = engine_lib.InferenceEngine(model, params, num_slots=1,
                                      max_seq_len=64,
                                      cache_mode='paged', page_size=16,
                                      spec_decode=3)
    out_p = _run(plain, [prompt], max_new=64)
    out_s = _run(spec, [prompt], max_new=64)
    assert out_p == out_s
    # Cut off by max_seq_len, not max_new.
    assert len(out_s[0]) < 64


def test_spec_non_pow2_max_seq_hist_width(debug_model):
    """Regression: with a non-power-of-two max_seq_len, a long prompt's
    pow2 admission bucket can exceed the history buffer's
    max_seq_len + k + 2 width; the insert must clamp, not error out
    (an unclamped dynamic_update_slice kills the engine loop thread and
    every request hangs)."""
    model, params = debug_model
    vocab = model.cfg.vocab_size
    # width = 48 + 2 + 2 = 52; n=40 buckets to 64 > 52 without the clamp
    prompt = _prompts(vocab, [40], seed=7)[0]
    plain = engine_lib.InferenceEngine(model, params, num_slots=1,
                                       max_seq_len=48)
    spec = engine_lib.InferenceEngine(model, params, num_slots=1,
                                      max_seq_len=48, spec_decode=2)
    out_p = _run(plain, [prompt], max_new=8)
    out_s = _run(spec, [prompt], max_new=8)
    assert out_p == out_s
    assert all(len(o) == 8 for o in out_s)


def test_speculative_sample_step_unbiased():
    """The rejection rule's first emitted token must be distributed
    EXACTLY as sequential sampling from the target distribution —
    accept d w.p. p(d), else residual — regardless of which draft the
    proposer picked (the speculative-sampling guarantee)."""
    import jax.numpy as jnp

    vocab, k, trials = 8, 2, 20000
    rng = np.random.default_rng(0)
    logits = jnp.asarray(rng.normal(size=(1, k + 1, vocab)) * 2.0,
                         jnp.float32)
    temps = jnp.asarray([0.7], jnp.float32)
    # An arbitrary (deliberately mediocre) draft.
    draft = jnp.asarray([[3, 5]], jnp.int32)

    def run(topk, topp=1.0):
        topks = jnp.asarray([topk], jnp.int32)
        topps = jnp.asarray([topp], jnp.float32)
        stepped = jax.jit(jax.vmap(
            lambda key: engine_lib.speculative_sample_step(
                logits, draft, temps, topks, topps, key[None])))
        keys = jax.vmap(jax.random.PRNGKey)(jnp.arange(trials))
        out, acc = stepped(keys)
        return np.asarray(out[:, 0, 0]), np.asarray(acc)

    # topk off: marginal == softmax(logits_0 / T).
    first, acc = run(0)
    p0 = np.asarray(jax.nn.softmax(logits[0, 0] / temps[0]))
    emp = np.bincount(first, minlength=vocab) / trials
    np.testing.assert_allclose(emp, p0, atol=0.015)
    # Acceptance really happens (draft token 3 has nonzero mass).
    assert 0 < int(np.sum(acc > 0)) < trials

    # topk active: marginal == the top-3-FILTERED softmax — exercising
    # _topk_filter's 3-D broadcast on the spec path.
    first3, _ = run(3)
    l0 = np.asarray(logits[0, 0])
    kth = np.sort(l0)[-3]
    lf = np.where(l0 < kth, -np.inf, l0) / float(temps[0])
    p3 = np.exp(lf - lf.max()); p3 /= p3.sum()
    emp3 = np.bincount(first3, minlength=vocab) / trials
    np.testing.assert_allclose(emp3, p3, atol=0.015)

    # top_p active: marginal == the NUCLEUS-filtered softmax (smallest
    # descending-prob prefix reaching p; exclusive cumsum).
    firstp, _ = run(0, topp=0.6)
    s = np.sort(np.asarray(logits[0, 0]) / float(temps[0]))[::-1]
    order = np.argsort(-np.asarray(logits[0, 0]))
    sp = np.exp(s - s.max()); sp /= sp.sum()
    before = np.cumsum(sp) - sp
    keep = order[before < 0.6]
    lp = np.full(vocab, -np.inf)
    lp[keep] = np.asarray(logits[0, 0])[keep] / float(temps[0])
    pn = np.exp(lp - lp[keep].max()); pn /= pn.sum()
    empp = np.bincount(firstp, minlength=vocab) / trials
    np.testing.assert_allclose(empp, pn, atol=0.015)


def test_speculative_sample_step_greedy_slots_exact():
    """temp == 0 slots are bit-identical to the argmax verify."""
    import jax.numpy as jnp

    vocab, k = 16, 3
    rng = np.random.default_rng(1)
    logits = jnp.asarray(rng.normal(size=(2, k + 1, vocab)), jnp.float32)
    greedy = np.asarray(jnp.argmax(logits, axis=-1))
    # Slot 0: draft = argmax prefix (fully accepted); slot 1: junk.
    draft = jnp.asarray([greedy[0, :k], [0, 0, 0]], jnp.int32)
    temps = jnp.zeros((2,), jnp.float32)
    topks = jnp.zeros((2,), jnp.int32)
    keys = jax.vmap(jax.random.PRNGKey)(jnp.arange(2))
    out, acc = engine_lib.speculative_sample_step(
        logits, draft, temps, topks, jnp.ones((2,), jnp.float32), keys)
    np.testing.assert_array_equal(np.asarray(out), greedy)
    assert int(acc[0]) == k
    assert int(acc[1]) == (1 if greedy[1, 0] == 0 else 0)


def test_sampling_filter_matches_host_semantics():
    """Device _sampling_filter and host _sample must induce the same
    support when top_k and top_p are BOTH active (HF/vLLM warper order:
    top-k first, nucleus over the renormalized survivors)."""
    import jax.numpy as jnp

    rng = np.random.default_rng(3)
    for trial in range(20):
        vocab = 12
        logits = rng.normal(size=(vocab,)) * 2.0
        temp, top_k, top_p = 0.7, 4, 0.55
        scaled = logits / temp
        # Host reference: top-k mask, renormalize, exclusive-cumsum
        # nucleus (mirrors engine._sample).
        l = scaled.copy()
        kth = np.partition(l, -top_k)[-top_k]
        l = np.where(l < kth, -np.inf, l)
        order = np.argsort(-l)
        s = l[order]
        sp = np.exp(s - s.max()); sp /= sp.sum()
        before = np.cumsum(sp) - sp
        cut = order[before >= top_p]
        l[cut] = -np.inf
        host_support = set(np.where(np.isfinite(l))[0].tolist())

        dev = engine_lib._sampling_filter(
            jnp.asarray(scaled, jnp.float32)[None, :],
            jnp.asarray([top_k], jnp.int32),
            jnp.asarray([top_p], jnp.float32))
        dev_support = set(np.where(np.isfinite(np.asarray(dev[0])))[0]
                          .tolist())
        assert dev_support == host_support, (trial, dev_support,
                                             host_support)
