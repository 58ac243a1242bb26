"""Multi-LoRA serving: stacked adapters, per-request routing, parity.

Reference analog: llm/lorax (the reference serves many adapters by
deploying the LoRAX container); here adapters are first-class in the
engine (infer/lora.py + models/llama.py _lora_delta). The correctness
bar: a request routed through adapter i must produce EXACTLY the
tokens a single-model engine over merge_lora(base, adapter_i) produces
— batched together with requests on other adapters and on the base.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import flax.linen as nn

from skypilot_tpu.infer import engine as engine_lib
from skypilot_tpu.infer import lora as slora
from skypilot_tpu.models import llama
from skypilot_tpu.train import lora as tlora

pytestmark = pytest.mark.heavy


def _base(max_seq_len=64):
    cfg = dataclasses.replace(llama.CONFIGS['debug'],
                              max_seq_len=max_seq_len)
    model = llama.LlamaModel(cfg)
    params = nn.meta.unbox(
        jax.jit(model.init)(jax.random.PRNGKey(0),
                            jnp.zeros((1, 8), jnp.int32))['params'])
    return cfg, model, params


@pytest.fixture(scope='module')
def lora_base():
    """The debug config, model and unboxed parameters, once for the
    module."""
    return _base()


def _rand_adapter(params, rank, alpha, seed):
    """A trained-looking adapter: random A AND B (init's B=0 would make
    the delta vanish and the test vacuous)."""
    lcfg = tlora.LoRAConfig(rank=rank, alpha=alpha)
    tree = tlora.init_lora_params(params, lcfg, jax.random.PRNGKey(seed))
    rng = np.random.default_rng(seed)
    tree = jax.tree.map(
        lambda x: jnp.asarray(rng.normal(0, 0.1, x.shape), x.dtype),
        tree)
    return tree, lcfg


def test_model_level_parity_and_id0(lora_base):
    cfg, model, params = lora_base
    tree, lcfg = _rand_adapter(params, rank=4, alpha=8.0, seed=1)
    stack = slora.build_stack([(tree, lcfg.alpha)], dtype='float32')
    tokens = jnp.asarray(
        np.random.default_rng(2).integers(0, cfg.vocab_size, (2, 12)),
        jnp.int32)
    out = model.apply(
        {'params': params, 'lora': stack,
         'lora_ids': {'ids': jnp.asarray([1, 0], jnp.int32)}}, tokens)
    base_out = model.apply({'params': params}, tokens)
    merged_out = model.apply(
        {'params': tlora.merge_lora(params, tree, lcfg)}, tokens)
    np.testing.assert_allclose(np.asarray(out[0]),
                               np.asarray(merged_out[0]),
                               rtol=2e-4, atol=2e-4)
    # id 0 is bit-exact base: the zeros adapter contributes nothing.
    np.testing.assert_array_equal(np.asarray(out[1]),
                                  np.asarray(base_out[1]))


def _greedy(eng, prompt, n=8, lora_id=0):
    return eng.generate(prompt, engine_lib.SamplingParams(
        max_new_tokens=n, lora_id=lora_id))


def _engine(model, params, stack=None, **kw):
    kw.setdefault('num_slots', 3)
    kw.setdefault('max_seq_len', 64)
    kw.setdefault('prefill_buckets', [16])
    return engine_lib.InferenceEngine(model, {'params': params},
                                      lora_stack=stack, **kw)


def test_mixed_batch_matches_merged_engines(lora_base):
    """Three concurrent requests — adapter A, adapter B (different
    rank!), and base — decode in the same continuous batch and each
    matches its own merged-model engine token-for-token."""
    cfg, model, params = lora_base
    tree_a, cfg_a = _rand_adapter(params, rank=4, alpha=8.0, seed=3)
    tree_b, cfg_b = _rand_adapter(params, rank=2, alpha=4.0, seed=4)
    stack = slora.build_stack([(tree_a, cfg_a.alpha),
                               (tree_b, cfg_b.alpha)], dtype='float32')

    prompts = {1: [5, 17, 3, 99, 42], 2: [7, 7, 23, 11], 0: [9, 1, 4]}

    want = {}
    for lid, tree, lcfg in ((1, tree_a, cfg_a), (2, tree_b, cfg_b)):
        merged = tlora.merge_lora(params, tree, lcfg)
        eng = _engine(model, merged)
        eng.start()
        try:
            want[lid] = _greedy(eng, prompts[lid])
        finally:
            eng.stop()
    eng = _engine(model, params)
    eng.start()
    try:
        want[0] = _greedy(eng, prompts[0])
    finally:
        eng.stop()

    eng = _engine(model, params, stack=stack)
    assert eng.num_adapters == 3  # id 0 + two adapters
    eng.start()
    got = {}
    try:
        # Submit all three before draining so they share decode steps.
        qs = {lid: eng.submit(p, engine_lib.SamplingParams(
            max_new_tokens=8, lora_id=lid))[1]
            for lid, p in prompts.items()}
        for lid, q in qs.items():
            out = []
            while True:
                t = q.get(timeout=120)
                if t is None:
                    break
                out.append(t)
            got[lid] = out
    finally:
        eng.stop()
    assert got == want


def test_paged_prefix_cache_isolated_per_adapter(lora_base):
    """Same prompt under two adapters with prefix caching ON: the
    second request must NOT reuse the first adapter's KV pages (K/V
    depend on the adapter's wk/wv) — outputs match per-adapter merged
    engines."""
    cfg, model, params = lora_base
    tree_a, cfg_a = _rand_adapter(params, rank=4, alpha=8.0, seed=5)
    stack = slora.build_stack([(tree_a, cfg_a.alpha)], dtype='float32')
    prompt = list(range(1, 33))   # two full 16-token pages

    merged = tlora.merge_lora(params, tree_a, cfg_a)
    for ref_params, lid in ((merged, 1), (params, 0)):
        eng = _engine(model, ref_params, cache_mode='paged',
                      page_size=16, prefix_caching=True)
        eng.start()
        try:
            want = _greedy(eng, prompt)
        finally:
            eng.stop()

        eng = _engine(model, params, stack=stack, cache_mode='paged',
                      page_size=16, prefix_caching=True)
        eng.start()
        try:
            # Prime the cache with the OTHER route first, then request
            # with `lid`: a cross-adapter page hit would corrupt this.
            _greedy(eng, prompt, lora_id=1 - lid)
            got = _greedy(eng, prompt, lora_id=lid)
        finally:
            eng.stop()
        assert got == want, f'lora_id={lid}'


def test_spec_decode_with_adapter_stays_exact(lora_base):
    """n-gram speculative decoding verifies against the ADAPTER model
    (the lora collection rides into the verify step), so outputs equal
    the merged engine's plain decode."""
    cfg, model, params = lora_base
    tree_a, cfg_a = _rand_adapter(params, rank=4, alpha=8.0, seed=6)
    stack = slora.build_stack([(tree_a, cfg_a.alpha)], dtype='float32')
    prompt = [5, 6, 5, 6, 5, 6, 5, 6]   # repetitive: n-gram drafts fire

    eng = _engine(model, tlora.merge_lora(params, tree_a, cfg_a),
                  cache_mode='paged', page_size=16)
    eng.start()
    try:
        want = _greedy(eng, prompt, n=10)
    finally:
        eng.stop()

    eng = _engine(model, params, stack=stack, cache_mode='paged',
                  page_size=16, spec_decode=2)
    eng.start()
    try:
        got = _greedy(eng, prompt, n=10, lora_id=1)
    finally:
        eng.stop()
    assert got == want


def test_out_of_range_lora_id_rejected(lora_base):
    cfg, model, params = lora_base
    eng = _engine(model, params)   # no stack
    with pytest.raises(ValueError, match='lora_id 1 out of range'):
        eng.submit([1, 2, 3], engine_lib.SamplingParams(lora_id=1))
    tree_a, cfg_a = _rand_adapter(params, rank=2, alpha=4.0, seed=7)
    stack = slora.build_stack([(tree_a, cfg_a.alpha)], dtype='float32')
    eng = _engine(model, params, stack=stack)
    with pytest.raises(ValueError, match='lora_id 2 out of range'):
        eng.submit([1, 2, 3], engine_lib.SamplingParams(lora_id=2))


def test_adapter_roundtrip_through_orbax(lora_base, tmp_path):
    """load_adapter_dir reads what an sft LoRA run writes (Orbax
    TrainStateS), and build_stack_from_specs maps names to ids."""
    from skypilot_tpu.train import checkpoint as ckpt_lib
    from skypilot_tpu.train import trainer

    cfg, model, params = lora_base
    tree, lcfg = _rand_adapter(params, rank=2, alpha=4.0, seed=8)
    tx = trainer.make_optimizer(trainer.TrainerConfig())
    state = trainer.TrainStateS(step=jnp.zeros((), jnp.int32),
                                params=tree, opt_state=tx.init(tree))
    ck = ckpt_lib.Checkpointer(str(tmp_path / 'adpt'), async_save=False)
    ck.save(0, state, force=True)
    ck.wait()

    stack, names = slora.build_stack_from_specs(
        [slora.AdapterSpec(name='my-ft', path=str(tmp_path / 'adpt'),
                           alpha=lcfg.alpha)], dtype='float32')
    assert names == {'my-ft': 1}
    want = slora.build_stack([(tree, lcfg.alpha)], dtype='float32')
    for (pa, a), (pb, b) in zip(
            jax.tree_util.tree_leaves_with_path(want),
            jax.tree_util.tree_leaves_with_path(stack)):
        assert str(pa) == str(pb)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-6, atol=1e-7)


def test_server_model_routing(lora_base):
    """OpenAI 'model' field routes: base id -> 0, adapter name -> its
    id, unknown -> model_not_found."""
    from skypilot_tpu.infer import server as server_lib

    cfg, model, params = lora_base
    eng = _engine(model, params)
    srv = server_lib.InferenceServer(eng, model_id='base',
                                     lora_names={'ft-a': 1})
    assert srv._resolve_lora({}) == (0, None)
    assert srv._resolve_lora({'model': 'base'}) == (0, None)
    assert srv._resolve_lora({'model': 'ft-a'})[0] == 1
    lid, err = srv._resolve_lora({'model': 'nope'})
    assert lid == 0 and err is not None and err.status == 404


def test_parse_lora_flag():
    specs = slora.parse_lora_flag(
        ['a=/tmp/x', 'b=gs://bkt/path:32', 'c=/tmp/y:8.5'])
    assert specs[0] == slora.AdapterSpec('a', '/tmp/x', 16.0)
    assert specs[1] == slora.AdapterSpec('b', 'gs://bkt/path', 32.0)
    assert specs[2] == slora.AdapterSpec('c', '/tmp/y', 8.5)
    with pytest.raises(ValueError, match='name=path'):
        slora.parse_lora_flag(['justapath'])
    with pytest.raises(ValueError, match='duplicate'):
        slora.parse_lora_flag(['a=/x', 'a=/y'])


def test_multilora_tp_sharded_matches_tp1(lora_base):
    """tp=2 over the CPU mesh: adapter stack replicates, outputs match
    the tp=1 multi-LoRA engine token-for-token."""
    from skypilot_tpu.models import weights
    from skypilot_tpu.parallel import mesh as mesh_lib

    cfg, model, params = lora_base
    tree_a, cfg_a = _rand_adapter(params, rank=4, alpha=8.0, seed=9)
    stack = slora.build_stack([(tree_a, cfg_a.alpha)], dtype='float32')
    prompt = [5, 17, 3, 99, 42]

    def run(mesh):
        p = params
        if mesh is not None:
            p = weights.shard_params({'params': params}, model, cfg,
                                     mesh)['params']
        eng = _engine(model, p, stack=stack, mesh=mesh)
        eng.start()
        try:
            return _greedy(eng, prompt, lora_id=1)
        finally:
            eng.stop()

    want = run(None)
    got = run(mesh_lib.build_mesh(mesh_lib.MeshSpec(tp=2)))
    assert got == want


def test_stack_layout_mismatch_rejected(lora_base):
    """An adapter trained under a different layer layout must fail
    loudly at engine build, not silently serve base-model outputs."""
    cfg, model, params = lora_base
    cfg_ns = dataclasses.replace(cfg, scan_layers=False)
    model_ns = llama.LlamaModel(cfg_ns)
    params_ns = nn.meta.unbox(
        jax.jit(model_ns.init)(jax.random.PRNGKey(0),
                               jnp.zeros((1, 8), jnp.int32))['params'])
    tree_ns, cfg_a = _rand_adapter(params_ns, rank=2, alpha=4.0,
                                   seed=10)
    stack_ns = slora.build_stack([(tree_ns, cfg_a.alpha)],
                                 dtype='float32')
    with pytest.raises(ValueError, match='does not match the serving'):
        _engine(model, params, stack=stack_ns)


def test_adapter_name_collides_with_model_id(lora_base):
    from skypilot_tpu.infer import server as server_lib
    cfg, model, params = lora_base
    eng = _engine(model, params)
    with pytest.raises(ValueError, match='collides'):
        server_lib.InferenceServer(eng, model_id='sql-ft',
                                   lora_names={'sql-ft': 1})


def test_stats_report_ttft_percentiles(lora_base):
    """/stats surfaces TTFT p50/p90/p99 from the rolling window (the
    reference reads these off vLLM's metrics endpoint)."""
    cfg, model, params = lora_base
    eng = _engine(model, params)
    eng.start()
    try:
        for _ in range(3):
            _greedy(eng, [5, 17, 3], n=2)
        s = eng.stats()
    finally:
        eng.stop()
    t = s['ttft_ms']
    assert t['count'] == 3
    assert 0 < t['p50'] <= t['p90'] <= t['p99']


# ---------------------------------------------------------- logit_bias
# OpenAI logit_bias (vLLM serves it too): device-side scatter-add on
# the decode path, host-side add on the admission (first-token) path.

def test_logit_bias_forces_and_bans_tokens(lora_base):
    cfg, model, params = lora_base
    eng = _engine(model, params)
    eng.start()
    try:
        plain = _greedy(eng, [5, 17, 3], n=4)
        # +100 on one token dominates every logit: all outputs = 9.
        forced = eng.generate([5, 17, 3], engine_lib.SamplingParams(
            max_new_tokens=4, logit_bias={9: 100.0}))
        assert forced == [9, 9, 9, 9]
        # -100 on the greedy first token bans it everywhere.
        banned = eng.generate([5, 17, 3], engine_lib.SamplingParams(
            max_new_tokens=4, logit_bias={plain[0]: -100.0}))
        assert plain[0] not in banned
    finally:
        eng.stop()


def test_logit_bias_sampling_path(lora_base):
    """temperature > 0 with a dominating bias still lands on the
    biased token (the bias applies before temperature/top-k)."""
    cfg, model, params = lora_base
    eng = _engine(model, params)
    eng.start()
    try:
        out = eng.generate([5, 17, 3], engine_lib.SamplingParams(
            max_new_tokens=4, temperature=1.0, seed=7,
            logit_bias={11: 100.0}))
        assert out == [11, 11, 11, 11]
    finally:
        eng.stop()


def test_logit_bias_spec_decode_falls_back_exact(lora_base):
    """Spec decoding falls back to the plain path for biased requests;
    outputs equal the non-spec engine's."""
    cfg, model, params = lora_base
    prompt = [5, 6, 5, 6, 5, 6]

    def run(spec):
        eng = _engine(model, params, cache_mode='paged', page_size=16,
                      spec_decode=spec)
        eng.start()
        try:
            return eng.generate(prompt, engine_lib.SamplingParams(
                max_new_tokens=6, logit_bias={3: 5.0, 8: -5.0}))
        finally:
            eng.stop()
    assert run(2) == run(0)


def test_logit_bias_validation(lora_base):
    cfg, model, params = lora_base
    eng = _engine(model, params)
    with pytest.raises(ValueError, match='at most 64'):
        engine_lib.SamplingParams(
            logit_bias={i: 1.0 for i in range(65)}).validate()
    with pytest.raises(ValueError, match=r'\[-100, 100\]'):
        engine_lib.SamplingParams(logit_bias={1: 200.0}).validate()
    with pytest.raises(ValueError, match='out of vocab'):
        eng.submit([1, 2], engine_lib.SamplingParams(
            logit_bias={cfg.vocab_size + 5: 1.0}))
