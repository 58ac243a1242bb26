"""Adapter fleet (docs/serving.md "Adapter fleet"): the dynamic
AdapterRegistry's full lifecycle against a live engine, the
ops/lora.py grouped-LoRA ladder op's golden parity vs its einsum
floor, mixed-adapter ragged packs, the LB's adapter-aware state and
routing helpers, and per-model QoS fairness.

The correctness bars, in order: a hot-loaded adapter must serve
EXACTLY the tokens a single-model engine over merge_lora(base,
adapter) produces, with the base and every other adapter unperturbed
by the mutation; the grouped op must match its XLA floor
byte-for-byte on CPU; and a ragged pack mixing adapters in one packed
row must equal the same requests run sequentially.
"""
import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import flax.linen as nn

from skypilot_tpu.infer import engine as engine_lib
from skypilot_tpu.infer import lora as slora
from skypilot_tpu.infer import weight_swap
from skypilot_tpu.models import llama
from skypilot_tpu.ops import dispatch
from skypilot_tpu.ops import lora as lora_ops
from skypilot_tpu.serve import qos
from skypilot_tpu.train import lora as tlora
from skypilot_tpu.utils import faults
from skypilot_tpu.utils import metrics as metrics_lib


# ----------------------------------------------------- grouped ladder op
def _rand_stack(n, din, r, dout, seed=0, dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    a = jnp.asarray(rng.normal(0, 0.1, (n, din, r)), dtype)
    b = jnp.asarray(rng.normal(0, 0.1, (n, r, dout)), dtype)
    # Id 0 is the zeros (base) adapter, like infer/lora.py stacks.
    a = a.at[0].set(0.0)
    b = b.at[0].set(0.0)
    return a, b


class TestGroupedOp:

    def teardown_method(self):
        faults.reset()

    @pytest.mark.parametrize('per_token,bsz,seq,n_blocks', [
        (False, 3, 16, 1), (False, 2, 512, 2), (True, 2, 24, 1),
        (True, 2, 320, 4)],
        ids=['seq-one-block', 'seq-two-blocks', 'tok-one-block',
             'tok-four-blocks'])
    def test_pallas_rung_byte_identical_to_floor(self, monkeypatch,
                                                 per_token, bsz, seq,
                                                 n_blocks):
        """[B] ids (decode / uniform prefill) and [B, S] ids (ragged
        packs mixing adapters in one row): the Pallas rung, at the one
        block the shape gives, must be byte-identical to the XLA floor
        on CPU (gather-einsum, or the scan over adapters; the per-id
        scale is applied outside every rung, so the final multiply is
        shared)."""
        # pylint: disable=protected-access
        rng = np.random.default_rng(1 + seq)
        x = jnp.asarray(rng.normal(0, 1, (bsz, seq, 32)), jnp.float32)
        a, b = _rand_stack(4, 32, 4, 24, seed=2)
        if per_token:
            ids = jnp.asarray(rng.integers(0, 4, (bsz, seq)), jnp.int32)
            name, floor = '_pallas_grouped', lora_ops._xla_grouped
        else:
            ids = jnp.asarray([2, 0, 1][:bsz], jnp.int32)
            name, floor = '_pallas_gather', lora_ops._xla_gather
        scale = jnp.where(ids == 0, 0.0, 1.5).astype(jnp.float32)
        blocks = []
        kernel = getattr(lora_ops, name)

        def spy(x, a, b, ids, scale, block):
            blocks.append(block)
            return kernel(x, a, b, ids, scale, block)

        monkeypatch.setattr(lora_ops, name, spy)
        dispatch.reset_for_tests()
        out = lora_ops.grouped_lora_delta(x, a, b, ids, scale)
        assert dispatch.snapshot()[lora_ops.OP] == 'pallas'
        dim = bsz * seq if per_token else seq
        assert blocks == [dispatch.choose_block(
            dim, lora_ops._DEFAULT_BLOCK, dispatch.sublane_multiple(x.dtype))]
        assert dim // blocks[0] == n_blocks
        np.testing.assert_array_equal(np.asarray(out), np.asarray(
            floor(x, a, b, ids, scale)))
        if not per_token:
            # Id 0 rows are exactly zero: the zeros adapter contributes
            # nothing, bit-for-bit.
            assert not np.any(np.asarray(out)[1])

    def test_mixed_rank_padded_stack(self):
        """Mixed-rank adapters live in one stack padded to the max
        rank with zero columns (infer/lora.py build_stack) — padding
        must be numerically inert through the grouped op."""
        rng = np.random.default_rng(5)
        x = jnp.asarray(rng.normal(0, 1, (2, 8, 16)), jnp.float32)
        a, b = _rand_stack(3, 16, 4, 12, seed=6)
        # Adapter 2 is rank 2: zero its padding columns/rows.
        a = a.at[2, :, 2:].set(0.0)
        b = b.at[2, 2:, :].set(0.0)
        ids = jnp.asarray([1, 2], jnp.int32)
        scale = jnp.asarray([2.0, 4.0], jnp.float32)
        out = lora_ops.grouped_lora_delta(x, a, b, ids, scale)
        # Golden: dense per-sequence einsum over the TRUE ranks.
        want = np.stack([
            np.asarray(x[0]) @ np.asarray(a[1]) @ np.asarray(b[1]) * 2.0,
            np.asarray(x[1]) @ np.asarray(a[2, :, :2]) @
            np.asarray(b[2, :2, :]) * 4.0])
        np.testing.assert_allclose(np.asarray(out), want,
                                   rtol=1e-5, atol=1e-5)

    def test_lowering_fault_descends_to_xla_floor(self):
        """ops.lowering chaos kills every Pallas rung; the floor must
        serve the exact same output and the descent is observable in
        skyt_ops_kernel_path_total{op="lora_grouped"}."""
        dispatch.reset_for_tests()
        jax.clear_caches()
        faults.configure('ops.lowering=error')
        rng = np.random.default_rng(7)
        x = jnp.asarray(rng.normal(0, 1, (2, 40, 16)), jnp.float32)
        a, b = _rand_stack(2, 16, 4, 16, seed=8)
        ids = jnp.asarray([1, 1], jnp.int32)
        scale = jnp.asarray([2.0, 2.0], jnp.float32)
        out = lora_ops.grouped_lora_delta(x, a, b, ids, scale)
        ref = lora_ops._xla_gather(x, a, b, ids, scale)  # pylint: disable=protected-access
        np.testing.assert_array_equal(np.asarray(out),
                                      np.asarray(ref))
        assert dispatch.snapshot().get(lora_ops.OP) == 'xla'


# --------------------------------------------------- registry lifecycle
def _base(max_seq_len=64):
    cfg = dataclasses.replace(llama.CONFIGS['debug'],
                              max_seq_len=max_seq_len)
    model = llama.LlamaModel(cfg)
    params = nn.meta.unbox(
        jax.jit(model.init)(jax.random.PRNGKey(0),
                            jnp.zeros((1, 8), jnp.int32))['params'])
    return cfg, model, params


def _rand_adapter(params, rank, alpha, seed):
    lcfg = tlora.LoRAConfig(rank=rank, alpha=alpha)
    tree = tlora.init_lora_params(params, lcfg,
                                  jax.random.PRNGKey(seed))
    rng = np.random.default_rng(seed)
    tree = jax.tree.map(
        lambda x: jnp.asarray(rng.normal(0, 0.1, x.shape), x.dtype),
        tree)
    return tree, lcfg


def _engine(model, params, stack=None, **kw):
    kw.setdefault('num_slots', 3)
    kw.setdefault('max_seq_len', 64)
    kw.setdefault('prefill_buckets', [16])
    return engine_lib.InferenceEngine(model, {'params': params},
                                      lora_stack=stack, **kw)


def _greedy(eng, prompt, n=6, lora_id=0):
    return eng.generate(prompt, engine_lib.SamplingParams(
        max_new_tokens=n, lora_id=lora_id))


@pytest.mark.heavy
def test_adapter_registry_lifecycle():
    """The whole hot-load story against a live engine: load parity vs
    the merged-weights golden, graft append, replace-with-rebuild
    (bigger rank forces the full-rebuild path), validation reject with
    the old stack intact, unload-while-referenced refused, id reuse
    after unload, and single-flight with the weight-swap slot."""
    _cfg, model, params = _base()
    t1, c1 = _rand_adapter(params, rank=4, alpha=8.0, seed=1)
    t2, c2 = _rand_adapter(params, rank=2, alpha=4.0, seed=2)
    t3, c3 = _rand_adapter(params, rank=8, alpha=16.0, seed=3)

    eng = _engine(model, params)
    eng.start()
    mreg = metrics_lib.MetricsRegistry()
    mgr = weight_swap.WeightSwapManager(eng, registry=mreg)
    areg = weight_swap.AdapterRegistry(eng, mgr, dtype='float32',
                                       registry=mreg)
    prompt = [1, 5, 9, 13]

    def merged_golden(tree, lcfg):
        m = _engine(model, tlora.merge_lora(params, tree, lcfg))
        m.start()
        try:
            return _greedy(m, prompt)
        finally:
            m.stop()

    try:
        base_out = _greedy(eng, prompt)
        # Fresh load (no stack yet -> build path), exact parity.
        r = areg.load('fr', params=t1, alpha=c1.alpha)
        assert r['id'] == 1 and r['num_adapters'] == 2
        m1 = merged_golden(t1, c1)
        assert _greedy(eng, prompt, lora_id=1) == m1
        assert _greedy(eng, prompt) == base_out

        # Second load: graft append.
        r = areg.load('de', params=t2, alpha=c2.alpha)
        assert r['id'] == 2 and r['num_adapters'] == 3

        # Replace in place with a BIGGER rank: graft cannot fit the
        # padded stack -> full rebuild; the sibling must survive.
        r = areg.load('fr', params=t3, alpha=c3.alpha)
        assert r['id'] == 1 and r['replaced'] and r['version'] == 2
        m3 = merged_golden(t3, c3)
        m2 = merged_golden(t2, c2)
        assert _greedy(eng, prompt, lora_id=1) == m3
        assert _greedy(eng, prompt, lora_id=2) == m2

        # Validation reject: old stack intact, failure recorded.
        with pytest.raises(weight_swap.WeightSwapError):
            areg.load('bad', params={'nope': {
                'a': jnp.zeros((4, 2)), 'b': jnp.zeros((2, 4))}})
        assert areg.last['ok'] is False and areg.last['name'] == 'bad'
        assert _greedy(eng, prompt, lora_id=1) == m3

        # Unload refused while a queued request references the id.
        class _P:  # pylint: disable=too-few-public-methods
            lora_id = 2

        class _R:  # pylint: disable=too-few-public-methods
            params = _P()

        eng._waiting.put(_R())  # pylint: disable=protected-access
        with pytest.raises(weight_swap.AdapterInUse):
            areg.unload('de')
        with eng._waiting.mutex:  # pylint: disable=protected-access
            eng._waiting.queue.clear()

        # Unload succeeds now; siblings and base unperturbed.
        areg.unload('de')
        assert 'de' not in areg.snapshot()['adapters']
        assert _greedy(eng, prompt, lora_id=1) == m3
        assert _greedy(eng, prompt) == base_out

        # Id reuse: the next load takes the lowest free slot.
        r = areg.load('de2', params=t2, alpha=c2.alpha)
        assert r['id'] == 2
        assert _greedy(eng, prompt, lora_id=2) == m2

        # Single-flight: the registry shares the weight-swap slot.
        mgr._flight.acquire()  # pylint: disable=protected-access
        try:
            with pytest.raises(weight_swap.SwapInFlight):
                areg.load('x', params=t2)
        finally:
            mgr._flight.release()  # pylint: disable=protected-access

        snap = areg.snapshot()
        assert snap['count'] == 2 and snap['stack_slots'] == 3
        fams = mreg.expose()
        assert 'skyt_infer_adapters_loaded' in fams
        assert 'skyt_infer_adapter_loads_total' in fams
        assert 'skyt_infer_adapter_unloads_total' in fams
    finally:
        eng.stop()


def _drain(q):
    items = []
    while True:
        it = q.get(timeout=120)
        if it is None:
            return items
        items.append(it)


@pytest.mark.heavy
def test_mixed_adapter_ragged_pack_matches_sequential():
    """A ragged prefill pack mixing adapters in ONE packed row (the
    per-token lora-id path through the grouped op) must produce
    exactly the tokens the same requests produce run one at a time."""
    _cfg, model, params = _base(max_seq_len=128)
    t1, c1 = _rand_adapter(params, rank=4, alpha=8.0, seed=1)
    t2, c2 = _rand_adapter(params, rank=2, alpha=4.0, seed=2)
    stack = slora.build_stack([(t1, c1.alpha), (t2, c2.alpha)],
                              dtype='float32')
    prompts = [list(range(1, 14)), list(range(5, 40)),
               list(range(7, 30))]
    ids = [1, 2, 0]
    sps = [engine_lib.SamplingParams(max_new_tokens=6, lora_id=i)
           for i in ids]

    def burst(**kw):
        eng = engine_lib.InferenceEngine(
            model, {'params': params}, lora_stack=stack, num_slots=4,
            max_seq_len=128, decode_chunk=4, cache_mode='paged',
            page_size=16, prefill_buckets=[16, 64], **kw)
        qs = [eng.submit(p, sp)[1] for p, sp in zip(prompts, sps)]
        eng.start()
        try:
            outs = [_drain(q) for q in qs]
        finally:
            eng.stop()
        return outs, dict(eng.perf)

    seq, _ = burst(batch_admission=False)
    rag, perf = burst()
    assert rag == seq
    assert perf['ragged_dispatches'] >= 1


# ----------------------------------------------------- LB state/routing
def test_lbstate_adapters_roundtrip_and_garbage():
    from skypilot_tpu.serve import load_balancer as lb_lib
    st = lb_lib.LBState(ready_replicas=['http://r1'],
                        replica_adapters={'http://r1': {'fr': 2}})
    back = lb_lib.LBState.from_json(st.to_json())
    assert back.replica_adapters == {'http://r1': {'fr': 2}}
    # Garbage-tolerant: wrong shapes contribute nothing, never raise.
    assert lb_lib.LBState._parse_adapters(  # pylint: disable=protected-access
        {'r1': [1, 2], 'r2': {'a': 'x', 'b': 3}, 3: None}) == \
        {'r2': {'b': 3}}
    assert lb_lib.LBState._parse_adapters('junk') == {}  # pylint: disable=protected-access
    txt = json.dumps({'ready_replicas': [], 'replica_adapters': 7})
    assert lb_lib.LBState.from_json(txt).replica_adapters == {}


def _make_lb(policy='prefix_affinity'):
    from skypilot_tpu.serve import load_balancer as lb_lib
    return lb_lib.SkyServeLoadBalancer(
        'http://127.0.0.1:9', 0, policy=policy,
        metrics_registry=metrics_lib.MetricsRegistry())


def test_affinity_key_folds_model():
    """Two requests over the same prompt but different adapters must
    land on DIFFERENT affinity keys — prefix pages are salted by
    lora id, so colliding them would plant guaranteed misses."""
    lb = _make_lb()
    plain = json.dumps({'prompt': 'Once upon a time'}).encode()
    fr = json.dumps({'prompt': 'Once upon a time',
                     'model': 'fr'}).encode()
    fr2 = json.dumps({'model': 'fr',
                      'prompt': 'Once upon a time'}).encode()
    de = json.dumps({'prompt': 'Once upon a time',
                     'model': 'de'}).encode()
    kp, kf, kf2, kd = (lb._affinity_key(b)  # pylint: disable=protected-access
                       for b in (plain, fr, fr2, de))
    assert kf == kf2          # key order in the body is irrelevant
    assert kp != kf and kf != kd and kp != kd


def test_adapter_avoid_and_honest_404():
    from skypilot_tpu.serve import load_balancer as lb_lib
    lb = _make_lb(policy='round_robin')
    lb.policy.set_ready_replicas(['http://a', 'http://b'])
    lb.state = lb_lib.LBState(
        ready_replicas=['http://a', 'http://b'],
        replica_adapters={'http://a': {'fr': 1}, 'http://b': {}})
    # Model parsing is gated on a non-empty adapter view.
    assert lb._request_model(  # pylint: disable=protected-access
        json.dumps({'model': 'fr'}).encode()) == 'fr'
    assert lb._request_model(b'not json') is None  # pylint: disable=protected-access
    # Soft-avoid: replicas reporting a set WITHOUT the adapter.
    assert lb._adapter_avoid_for('fr') == {'http://b'}  # pylint: disable=protected-access
    # Hosted nowhere -> no steering (base model / 404 / stale view).
    assert lb._adapter_avoid_for('ghost') == set()  # pylint: disable=protected-access
    assert lb._adapter_avoid_for(None) == set()  # pylint: disable=protected-access
    # Honest 404 needs a learned base id; conservative before then.
    assert lb._model_not_found('ghost') is None  # pylint: disable=protected-access
    lb._base_model_id = 'debug'  # pylint: disable=protected-access
    resp = lb._model_not_found('ghost')  # pylint: disable=protected-access
    assert resp is not None and resp.status == 404
    assert b'model_not_found' in resp.body
    # The base model and hosted adapters never 404.
    assert lb._model_not_found('debug') is None  # pylint: disable=protected-access
    assert lb._model_not_found('fr') is None  # pylint: disable=protected-access
    # Stale view: the replica's own 404 stays the source of truth.
    lb._stale = True  # pylint: disable=protected-access
    assert lb._model_not_found('ghost') is None  # pylint: disable=protected-access


# ------------------------------------------------------- per-model QoS
def test_fairqueue_per_model_isolation():
    """Two fine-tunes of one (class, tenant) are separate DRR flows:
    one model's flood cannot starve its sibling, and per-model weights
    skew service proportionally."""
    fq = qos.FairQueue(quantum=1.0, weights={'batch': 1.0},
                       model_weights={'b': 2.0})
    for i in range(6):
        fq.push(f'a{i}', cls='batch', tenant='t', model='a')
    for i in range(6):
        fq.push(f'b{i}', cls='batch', tenant='t', model='b')
    first6 = [fq.pop() for _ in range(6)]
    # Weight 2 vs 1: model b gets twice the service per DRR round.
    assert sum(1 for it in first6 if it.startswith('b')) == 4
    assert sum(1 for it in first6 if it.startswith('a')) == 2
    # Unweighted flood vs trickle: the sibling is never starved.
    fq2 = qos.FairQueue(quantum=1.0, weights={'batch': 1.0})
    for i in range(50):
        fq2.push(f'x{i}', cls='batch', tenant='t', model='x')
    fq2.push('y0', cls='batch', tenant='t', model='y')
    assert 'y0' in [fq2.pop() for _ in range(3)]


def test_model_weights_env_parse(monkeypatch):
    monkeypatch.setenv('SKYT_QOS_MODEL_WEIGHTS',
                       'fr:4, de:0.5 ,bad, x:y')
    assert qos._model_weights() == {'fr': 4.0, 'de': 0.5}  # pylint: disable=protected-access
    monkeypatch.setenv('SKYT_QOS_MODEL_WEIGHTS', '')
    assert qos._model_weights() == {}  # pylint: disable=protected-access
