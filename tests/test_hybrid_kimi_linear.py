"""Kimi Linear on the kind table (models/hybrid.py): KDA and latent
attention layers, a dense first layer, sigmoid-routed experts with a
shared one. The program (float32 here, the chunked KDA form, the XLA
rung of attention) against the plain reference
(models/kimi_linear_reference.py: the recurrence one position at a time,
plain softmax attention), the shares of an expert layer against the
uncut layer, and the presets against the benchmark's configuration
file."""
import dataclasses
import io
import json
import logging
import math
import os
import re

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from skypilot_tpu.models import hybrid
from skypilot_tpu.models import kimi_linear_reference as reference
from skypilot_tpu.models import moe
from skypilot_tpu.models import registry
from skypilot_tpu.ops import dispatch
from skypilot_tpu.train import trainer

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG = os.path.join(REPO, 'chipbench', 'configs',
                      'kimi-linear-48b-a3b-ep32-sft.json')


def _file():
    with open(CONFIG, encoding='utf-8') as f:
        return json.load(f)


def _merged(base: dict, over: dict) -> dict:
    out = dict(base)
    for k, v in over.items():
        out[k] = _merged(out[k], v) if isinstance(v, dict) and \
            isinstance(out.get(k), dict) else v
    return out


def _sizes(held=(0, 16)) -> dict:
    """The configuration file's sizes as its CPU rehearsal has them
    (`debug-kimi-linear`), the held range given."""
    cfg = _file()
    return dict(_merged(cfg, cfg['rehearsal']), experts_held=list(held))


def _seeded(seed=3, held=None, rows=2, seq=80):
    cfg = hybrid.CONFIGS['debug-kimi-linear']
    if held is not None:
        cfg = dataclasses.replace(cfg, experts=dataclasses.replace(
            cfg.experts, held=held))
    model = hybrid.HybridModel(cfg)
    rng = np.random.default_rng(seed)
    tokens = jnp.asarray(rng.integers(0, 256, (rows, seq + 1)), jnp.int32)
    params = nn.meta.unbox(jax.jit(model.init)(
        jax.random.PRNGKey(seed), tokens[:, :-1])['params'])
    # A selection bias that selects: drawn non-zero, as the check draws it
    for i, name in enumerate(sorted(params)):
        if 'experts' in params[name]:
            params[name]['experts']['expert_bias'] = 0.1 * \
                jax.random.normal(jax.random.PRNGKey(100 + i), (16,))
    return cfg, model, params, tokens[:, :-1], tokens[:, 1:]


def _rel(a, b):
    return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))


@pytest.mark.parametrize('held', [None, (4, 10)],
                         ids=['all_experts', 'a_share'])
def test_loss_and_every_gradient_leaf_match_the_reference(held):
    cfg, model, params, tokens, targets = _seeded(held=held)
    sizes = _sizes(cfg.experts.held_range)
    assert sorted(params['layer_0']) == ['ffn_norm', 'kda', 'mlp',
                                         'op_norm']
    assert 'mla' in params['layer_3'] and \
        'shared_expert' in params['layer_1']['experts']

    def program(p):
        logits, sown = model.apply({'params': p}, tokens,
                                   mutable=['intermediates'])
        picked = {name: layer['experts']['selected'][0] for name, layer
                  in sown['intermediates'].items() if name != 'moe_stats'}
        return trainer.cross_entropy_loss(logits, targets)[0], picked
    (loss_p, picked), grad_p = jax.jit(jax.value_and_grad(
        program, has_aux=True))(params)
    with jax.default_matmul_precision('highest'):
        (loss_r, routed), grad_r = jax.jit(jax.value_and_grad(
            lambda p: reference.loss_with_routing(p, tokens, targets, sizes),
            has_aux=True))(params)
    assert sorted(routed) == sorted(picked) == [
        'layer_1', 'layer_2', 'layer_3', 'layer_4']
    for name in picked:
        assert (jnp.sort(picked[name], -1) ==
                jnp.sort(routed[name][0], -1)).all()
    assert float(loss_p) == pytest.approx(float(loss_r), abs=1e-5)
    assert jax.tree.structure(grad_p) == jax.tree.structure(grad_r)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(grad_p),
                            jax.tree.leaves(grad_r)):
        if 'expert_bias' in jax.tree_util.keystr(path):
            assert float(jnp.abs(a).max()) == 0.0 == float(jnp.abs(b).max())
            continue
        # A head's decay rate sums a gradient over every position and
        # channel of the head, with cancellation: float32 order matters.
        limit = 2e-3 if 'A_log' in jax.tree_util.keystr(path) else 2e-4
        assert _rel(a, b) < limit, jax.tree_util.keystr(path)


def test_the_shares_of_a_layer_add_up_to_the_uncut_layer():
    """Four chips' shares of 4 of the 16 experts, one router, the shared
    expert on every chip: the shares' outputs, with the shared expert
    counted once, sum to the uncut reference's layer, and each share is
    the reference's for its range."""
    cfg = hybrid.CONFIGS['debug-kimi-linear']
    sizes = _sizes()
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 64, cfg.base.dim))
    whole = moe.RoutedExperts(cfg.base, cfg.experts)
    p = nn.meta.unbox(whole.init(jax.random.PRNGKey(2), x)['params'])
    p['expert_bias'] = 0.1 * jax.random.normal(jax.random.PRNGKey(9), (16,))

    def plain(p, held):
        with jax.default_matmul_precision('highest'):
            return jax.vmap(lambda row: reference._experts(
                row, p, dict(sizes, experts_held=list(held)))[0])(x)
    with jax.default_matmul_precision('highest'):
        shared = reference._swiglu(x, p['shared_expert'])
    total, routed = 0.0, 0
    for lo in range(0, 16, 4):
        share = moe.RoutedExperts(cfg.base, dataclasses.replace(
            cfg.experts, held=(lo, lo + 4)))
        ps = dict(p, **{k: p[k][lo:lo + 4]
                        for k in ('w_gate', 'w_up', 'w_down')})
        out, stats, _ = jax.jit(share.apply)({'params': ps}, x)
        np.testing.assert_allclose(out, plain(ps, (lo, lo + 4)), atol=2e-5)
        assert int(stats[2]) == 0                      # nothing dropped
        total, routed = total + out - shared, routed + int(stats[0])
    total = total + shared
    assert routed == 2 * 64 * 4                        # every pair, once
    np.testing.assert_allclose(total, plain(p, (0, 16)), atol=5e-5)
    np.testing.assert_allclose(
        total, jax.jit(whole.apply)({'params': p}, x)[0], atol=5e-5)


def test_the_presets_are_the_published_model_and_the_configuration_file():
    whole = hybrid.CONFIGS['kimi-linear-48b-a3b']
    share = hybrid.CONFIGS['kimi-linear-48b-a3b-ep32']
    m = _file()
    la = m['linear_attn_config']
    assert len(whole.layers) == 27 and whole.layers[0] == ('kda', 'dense')
    assert [i + 1 for i, (op, _) in enumerate(whole.layers)
            if op == 'latent_attention'] == [4, 8, 12, 16, 20, 24, 27]
    assert all(ffn == 'experts' for _, ffn in whole.layers[1:])
    # the share: the configuration file's layers, sizes and slice
    assert share.layers == tuple(
        ('kda' if i + 1 in la['kda_layers'] else 'latent_attention',
         'dense' if i < m['first_k_dense_replace'] else 'experts')
        for i in range(m['num_hidden_layers']))
    assert la['full_attn_layers'] == [4]
    assert share.base == dataclasses.replace(whole.base,
                                             vocab_size=m['vocab_size'])
    assert (share.base.dim, share.base.mlp_dim, share.base.norm_eps,
            share.base.tie_embeddings) == (
        m['hidden_size'], m['intermediate_size'], m['rms_norm_eps'],
        m['tie_word_embeddings'])
    assert share.kda == hybrid.Kda(la['num_heads'], la['head_dim'],
                                   la['short_conv_kernel_size'])
    assert share.mla == hybrid.Mla(
        m['num_attention_heads'], m['kv_lora_rank'], m['qk_nope_head_dim'],
        m['qk_rope_head_dim'], m['v_head_dim'])
    ex = share.experts
    assert (ex.num_experts, ex.experts_per_token, ex.mlp_dim, ex.scoring,
            ex.routed_scaling, ex.shared, list(ex.held_range)) == (
        m['router_outputs'], m['num_experts_per_token'],
        m['moe_intermediate_size'], 'sigmoid_bias',
        m['routed_scaling_factor'],
        (m['num_shared_experts'], m['moe_intermediate_size']),
        m['experts_held'])
    assert m['experts_held'][1] - m['experts_held'][0] == m['num_experts']
    assert whole.experts.held_range == (0, 256)
    # the parameters held on one chip, part by part, and the whole model
    kda, mla = share.kda.num_params(2304), share.mla.num_params(2304)
    experts = 8 * 7077888 + 7077888 + 589824 + 256
    assert (kda, mla, experts) == (39518368, 29114880, 64291072)
    assert kda + 63700992 + 2 * 2304 == 103223968
    assert share.num_params() == 602450816 == m['params_held'] == \
        103223968 + 3 * 103814048 + 93410560 + 94374144
    assert whole.num_params() == 49122763648
    _, cfg = registry.build('debug-kimi-linear')
    assert cfg.n_layers == 5 and cfg.vocab_size == 256


def test_the_two_copies_of_the_reference_are_identical():
    with open(os.path.join(REPO, 'chipbench', 'references',
                           'kimi_linear.py'), 'rb') as a, \
            open(reference.__file__, 'rb') as b:
        assert a.read() == b.read()


def test_a_kda_layer_takes_no_segment_ids_and_latent_flash_keeps_its_widths():
    cfg = hybrid.CONFIGS['debug-kimi-linear']
    layer = hybrid.KimiDeltaAttention(cfg.base, cfg.kda)
    x = jnp.zeros((1, 16, cfg.base.dim))
    with pytest.raises(ValueError, match='segment ids'):
        layer.init(jax.random.PRNGKey(0), x, jnp.zeros((1, 16), jnp.int32))
    with pytest.raises(ValueError, match='kda layers need'):
        dataclasses.replace(cfg, kda=None)
    dispatch.reset_for_tests()
    jax.eval_shape(hybrid.HybridModel(cfg).init, jax.random.PRNGKey(0),
                   jnp.zeros((1, 200), jnp.int32))
    assert dispatch.kda_plan_snapshot() == {
        'layers': 4, 'chunk': 64, 'chunks': 4, 'heads': 4, 'dk': 16,
        'dv': 16, 'state_bytes': 4 * 4 * 16 * 16 * 4, 'rung': 'jnp',
        'steps': 0}
    assert dispatch.residuals_plan_snapshot()['blocks'] == 1


STEP_RE = re.compile(r'step (\d+)/\d+ loss=(\S+) tokens/s')
MOE_RE = re.compile(r'moe_pairs=(\d+)/(\d+) moe_fullest_over_mean=(\S+) '
                    r'moe_dropped=(\d+)')


def test_sft_trains_the_preset_and_prints_the_lines_the_driver_parses(
        tmp_path):
    from skypilot_tpu.train import sft
    data = tmp_path / 'rows.jsonl'
    rows = np.random.default_rng(0).integers(0, 256, (40, 65))
    data.write_text(''.join(
        '{"tokens": ' + str(row.tolist()) + '}\n' for row in rows))
    buf = io.StringIO()
    handler = logging.StreamHandler(buf)
    sft.logger.addHandler(handler)
    try:
        sft.main(['--model', 'debug-kimi-linear', '--mesh', 'fsdp=1',
                  '--steps', '3', '--batch', '2', '--seq', '64',
                  '--log-every', '1', '--data', str(data)])
    finally:
        sft.logger.removeHandler(handler)
    text = buf.getvalue()
    assert 'kda plan: layers=4 chunk=64 chunks=1 heads=4 dk=16 dv=16 ' \
        'state_bytes=8192 rung=jnp steps=0\n' in text
    assert 'moe routing plan: experts=16 held=0-15 k=4 tokens=128 ' in text
    steps = STEP_RE.findall(text)
    assert [int(n) for n, _ in steps] == [1, 2, 3]
    assert abs(float(steps[0][1]) - math.log(256)) < 2.5
    # 4 expert layers x 128 tokens x 4 slots, all held, none dropped
    assert [m[:2] + m[3:] for m in MOE_RE.findall(text)] == \
        [('2048', '2048', '0')] * 3


def test_a_kda_layer_in_head_groups_is_the_references_layer():
    """16 heads of 8: two groups of `kda.HEADS_A_PASS` worked one after
    another, each checkpointed; the layer's output and the gradients of
    its input and every weight are the reference's."""
    from skypilot_tpu.ops import kda as kda_ops
    cfg = hybrid.CONFIGS['debug-kimi-linear']
    sizes = hybrid.Kda(heads=16, head_dim=8)
    assert kda_ops.head_groups(16) == 2 and kda_ops.head_groups(4) == 1
    layer = hybrid.KimiDeltaAttention(cfg.base, sizes)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 40, cfg.base.dim))
    p = nn.meta.unbox(layer.init(jax.random.PRNGKey(2), x)['params'])
    m = dict(_sizes(), linear_attn_config={'num_heads': 16, 'head_dim': 8,
                                           'short_conv_kernel_size': 4})
    w = jax.random.normal(jax.random.PRNGKey(3), x.shape)

    def program(p, x):
        return jnp.sum(layer.apply({'params': p}, x) * w)

    def plain(p, x):
        return jnp.sum(jax.vmap(lambda row: reference._kda(row, p, m))(x)
                       * w)
    got = jax.jit(jax.value_and_grad(program, (0, 1)))(p, x)
    with jax.default_matmul_precision('highest'):
        want = jax.jit(jax.value_and_grad(plain, (0, 1)))(p, x)
    assert float(got[0]) == pytest.approx(float(want[0]), rel=1e-5)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(got[1]),
                            jax.tree.leaves(want[1])):
        limit = 2e-3 if 'A_log' in jax.tree_util.keystr(path) else 2e-4
        assert _rel(a, b) < limit, jax.tree_util.keystr(path)
