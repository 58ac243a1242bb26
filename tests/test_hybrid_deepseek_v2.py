"""DeepSeek-V2 on the kind table (models/hybrid.py): latent attention in
every layer with its decoupled key part rotated under DeepSeek's YaRN, a
dense first layer, softmax top-k experts without renormalisation, two
shared experts and the sequence-wise balance loss. The program (float32
here, the XLA rung of attention) against the plain reference
(models/deepseek_v2_reference.py), the wrong models the reference can be
made into, the shares of an expert layer against the uncut layer, and
the presets against the benchmark's configuration file."""
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

from skypilot_tpu.models import deepseek_v2_reference as reference
from skypilot_tpu.models import hybrid
from skypilot_tpu.models import moe
from skypilot_tpu.models import registry
from skypilot_tpu.ops import dispatch
from skypilot_tpu.ops import rope
from skypilot_tpu.train import trainer

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG = os.path.join(REPO, 'chipbench', 'configs',
                      'deepseek-v2-lite-ep8-sft.json')


def _file():
    with open(CONFIG, encoding='utf-8') as f:
        return json.load(f)


def _sizes(held=(0, 16), **more) -> dict:
    """The configuration file's sizes as its CPU rehearsal has them
    (`debug-deepseek-v2`), the held range given."""
    cfg = _file()
    return {**cfg, **cfg['rehearsal'], 'experts_held': list(held), **more}


def _seeded(seed=3, held=None, rows=2, seq=80):
    cfg = hybrid.CONFIGS['debug-deepseek-v2']
    if held is not None:
        cfg = dataclasses.replace(cfg, experts=dataclasses.replace(
            cfg.experts, held=held))
    model = hybrid.HybridModel(cfg)
    rng = np.random.default_rng(seed)
    tokens = jnp.asarray(rng.integers(0, 256, (rows, seq + 1)), jnp.int32)
    params = nn.meta.unbox(jax.jit(model.init)(
        jax.random.PRNGKey(seed), tokens[:, :-1])['params'])
    return cfg, model, params, tokens[:, :-1], tokens[:, 1:]


def _rel(a, b):
    return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))


def _program(model, tokens, targets):
    def run(p):
        logits, sown = model.apply({'params': p}, tokens,
                                   mutable=['intermediates'])
        inter = sown['intermediates']
        picked = {name: layer['experts']['selected'][0]
                  for name, layer in inter.items() if name.startswith(
                      'layer_') and 'experts' in layer}
        ce = trainer.cross_entropy_loss(logits, targets)[0]
        aux = inter['moe_aux_loss'][0]
        return ce + aux, (logits, ce, aux, picked)
    return run


@pytest.mark.parametrize('held', [None, (4, 10)],
                         ids=['all_experts', 'a_share'])
def test_logits_loss_balance_loss_and_every_gradient_leaf_match_the_reference(
        held):
    cfg, model, params, tokens, targets = _seeded(held=held)
    sizes = _sizes(cfg.experts.held_range)
    assert sorted(params['layer_0']) == ['ffn_norm', 'mla', 'mlp', 'op_norm']
    assert 'shared_expert' in params['layer_1']['experts'] and \
        'expert_bias' not in params['layer_1']['experts']
    (loss_p, (logits_p, ce_p, aux_p, picked)), grad_p = jax.jit(
        jax.value_and_grad(_program(model, tokens, targets),
                           has_aux=True))(params)
    with jax.default_matmul_precision('highest'):
        (loss_r, routed), grad_r = jax.jit(jax.value_and_grad(
            lambda p: reference.loss_with_routing(p, tokens, targets, sizes),
            has_aux=True))(params)
        ce_r, aux_r, _ = jax.jit(lambda p: reference.loss_parts(
            p, tokens, targets, sizes))(params)
        logits_r = jax.jit(jax.vmap(lambda t: reference.logits(
            params, t, sizes)))(tokens)
    assert sorted(routed) == sorted(picked) == ['layer_1', 'layer_2']
    for name in picked:
        assert (jnp.sort(picked[name], -1) ==
                jnp.sort(routed[name][0], -1)).all()
    np.testing.assert_allclose(logits_p, logits_r, atol=2e-4, rtol=1e-4)
    assert float(ce_p) == pytest.approx(float(ce_r), abs=1e-5)
    # the balance loss is there, near alpha a layer on an even router
    assert 0.5e-3 < float(aux_r) < 2 * 0.001 * 2 * 4
    assert float(aux_p) == pytest.approx(float(aux_r), rel=1e-5)
    assert float(loss_p) == pytest.approx(float(loss_r), abs=1e-5)
    assert jax.tree.structure(grad_p) == jax.tree.structure(grad_r)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(grad_p),
                            jax.tree.leaves(grad_r)):
        assert _rel(a, b) < 2e-4, jax.tree_util.keystr(path)


def _apart_from(fault, seed=3):
    """(how far the program's logits lie from the sound reference's,
    from the reference made into `fault`), relative, on one row."""
    cfg, model, params, tokens, _ = _seeded(seed=seed, rows=1, seq=96)
    logits_p = jax.jit(model.apply)({'params': params}, tokens)[0]
    with jax.default_matmul_precision('highest'):
        sound, wrong = (jax.jit(lambda p, m=m: reference.logits(
            p, tokens[0], m))(params) for m in (
                _sizes(), _sizes(fault=fault)))
    return _rel(logits_p, sound), _rel(logits_p, wrong)


@pytest.mark.parametrize('fault', ['half_split', 'no_rotation'])
def test_the_rotation_is_the_published_pairing(fault):
    """The program rotates the adjacent pairs of the published columns:
    the reference that rotates pairs (j, j + d/2) on them, or nothing,
    is another model."""
    sound, wrong = _apart_from(fault)
    assert sound < 1e-5 and wrong > 100 * sound and wrong > 1e-3


def test_the_pairs_are_rotated_as_deepseek_rotates_them():
    """`rope.apply_rope_pairs` gives what HF's DeepSeek-V2 code gives:
    the pairs de-interleaved, then rotate_half; and the dot products of
    the pairs rotated in place, which the reference computes."""
    x = jax.random.normal(jax.random.PRNGKey(0), (5, 3, 16))
    y = jax.random.normal(jax.random.PRNGKey(1), (5, 3, 16))
    cos, sin = rope.rope_freqs(jnp.arange(5), 16, 1e4)
    got = rope.apply_rope_pairs(x, cos, sin)
    de = x.reshape(5, 3, 8, 2).swapaxes(-1, -2).reshape(5, 3, 16)
    c = jnp.concatenate([cos, cos], -1)[:, None]
    s = jnp.concatenate([sin, sin], -1)[:, None]
    half = jnp.concatenate([-de[..., 8:], de[..., :8]], -1)
    np.testing.assert_allclose(got, de * c + half * s, atol=1e-6)
    m = dict(qk_rope_head_dim=16, rope_theta=1e4, rope_scaling=None)
    dots = jnp.einsum('shd,shd->sh', got, rope.apply_rope_pairs(y, cos, sin))
    np.testing.assert_allclose(dots, jnp.einsum(
        'shd,shd->sh', reference._rotate(x, m), reference._rotate(y, m)),
        atol=1e-5)


def test_the_softmax_scale_carries_mscale_squared():
    mla = hybrid.CONFIGS['deepseek-v2-lite-ep8'].mla
    assert mla.softmax_scale == pytest.approx(0.1147214, abs=5e-8)
    assert mla.softmax_scale == pytest.approx(
        (0.1 * 0.707 * math.log(40) + 1) ** 2 / math.sqrt(192), rel=1e-12)
    assert mla.yarn.scale == 1.0            # nothing on cos and sin
    assert reference.softmax_scale(_file()) == pytest.approx(
        mla.softmax_scale, rel=1e-12)
    low, high = mla.yarn.correction_range(64, 1e4)
    assert (low, high) == (10, 23)
    np.testing.assert_allclose(
        rope.inv_freqs(64, 1e4, mla.yarn),
        reference.rotary_frequencies(_file()), rtol=1e-6)
    # Mellum2's YaRN keeps its factor on cos and sin, none on the scale
    mellum = hybrid.CONFIGS['mellum2-12b-a2.5b-ep4'].yarn
    assert (mellum.scale, mellum.softmax_factor) == (1.2772588722239782, 1.0)
    sound, wrong = _apart_from('plain_scale')
    assert sound < 1e-5 and wrong > 100 * sound


def test_softmax_top_k_without_renormalisation():
    logits = jax.random.normal(jax.random.PRNGKey(0), (32, 16)) * 2
    kept = moe.ExpertsConfig(16, 4, 8, renormalize=False)
    sel, w = moe.route(logits, None, kept)
    p = jax.nn.softmax(logits, -1)
    np.testing.assert_allclose(w, jnp.take_along_axis(p, sel, -1),
                               rtol=1e-6)
    assert float(w.sum(-1).max()) < 1.0
    sel2, w2 = moe.route(logits, None, dataclasses.replace(
        kept, renormalize=True))
    assert (sel2 == sel).all()
    np.testing.assert_allclose(w2.sum(-1), 1.0, rtol=1e-6)
    # Mellum2's and SDAR's routes renormalise, as before
    for name in ('mellum2-12b-a2.5b-ep4', 'sdar-30b-a3b-ep8'):
        ex = hybrid.CONFIGS[name].experts
        assert ex.renormalize and ex.seq_aux_alpha == 0.0
    sound, wrong = _apart_from('renormalized')
    assert sound < 1e-5 and wrong > 100 * sound


def test_the_balance_loss_is_deepseeks_sequence_wise_loss():
    """alpha sum_i f_i P_i a row, averaged over rows: a router that
    sends every token to the same k experts reads alpha E / k times
    their mean probability; dropping the loss is another model."""
    ex = moe.ExpertsConfig(8, 2, 8, seq_aux_alpha=0.01)
    logits = jnp.zeros((2, 5, 8)).at[..., :2].set(3.0)
    sel = jnp.broadcast_to(jnp.array([0, 1]), (2, 5, 2))
    p = jax.nn.softmax(logits[0, 0])
    want = 0.01 * (8 / 2) * float(p[0] + p[1])
    assert float(moe.seq_aux_loss(logits, sel, ex)) == pytest.approx(
        want, rel=1e-6)
    one_row = reference.balance_loss(jax.nn.softmax(logits[0], -1), sel[0],
                                     {'seq_aux': True, 'aux_loss_alpha':
                                      0.01, 'num_experts_per_tok': 2})
    assert float(one_row) == pytest.approx(want, rel=1e-6)
    cfg, model, params, tokens, targets = _seeded()
    with jax.default_matmul_precision('highest'):
        _, aux, _ = reference.loss_parts(params, tokens, targets,
                                         _sizes(fault='no_balance_loss'))
    assert float(aux) == 0.0


def test_the_shares_of_a_layer_add_up_to_the_uncut_layer():
    """Four chips' shares of 4 of the 16 experts, one router, the shared
    experts on every chip: the shares' outputs, with the shared experts
    counted once, sum to the uncut reference's layer, each share is the
    reference's for its range, and each share's balance loss is the
    whole router's."""
    cfg = hybrid.CONFIGS['debug-deepseek-v2']
    sizes = _sizes()
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 64, cfg.base.dim))
    whole = moe.RoutedExperts(cfg.base, cfg.experts)
    p = nn.meta.unbox(whole.init(jax.random.PRNGKey(2), x)['params'])

    def plain(p, held):
        with jax.default_matmul_precision('highest'):
            return jax.vmap(lambda row: reference._experts(
                row, p, dict(sizes, experts_held=list(held))))(x)
    with jax.default_matmul_precision('highest'):
        shared = reference._swiglu(x, p['shared_expert'])
    want, _, _, want_aux = plain(p, (0, 16))
    total, routed = 0.0, 0
    for lo in range(0, 16, 4):
        share = moe.RoutedExperts(cfg.base, dataclasses.replace(
            cfg.experts, held=(lo, lo + 4)))
        ps = dict(p, **{k: p[k][lo:lo + 4]
                        for k in ('w_gate', 'w_up', 'w_down')})
        out, stats, aux = jax.jit(share.apply)({'params': ps}, x)
        np.testing.assert_allclose(out, plain(ps, (lo, lo + 4))[0],
                                   atol=2e-5)
        assert float(aux) == pytest.approx(float(want_aux.mean()), rel=1e-5)
        assert int(stats[2]) == 0                      # nothing dropped
        total, routed = total + out - shared, routed + int(stats[0])
    total = total + shared
    assert routed == 2 * 64 * 4                        # every pair, once
    np.testing.assert_allclose(total, want, atol=5e-5)
    np.testing.assert_allclose(
        total, jax.jit(whole.apply)({'params': p}, x)[0], atol=5e-5)


def test_the_presets_are_the_published_model_and_the_configuration_file():
    whole = hybrid.CONFIGS['deepseek-v2-lite']
    share = hybrid.CONFIGS['deepseek-v2-lite-ep8']
    m = _file()
    assert len(whole.layers) == 27 and whole.layers[0] == (
        'latent_attention', 'dense')
    assert all(layer == ('latent_attention', 'experts')
               for layer in whole.layers[1:])
    assert share.layers == whole.layers[:m['num_hidden_layers']]
    assert share.base == dataclasses.replace(whole.base,
                                             vocab_size=m['vocab_size'])
    assert (share.base.dim, share.base.mlp_dim, share.base.norm_eps,
            share.base.tie_embeddings) == (
        m['hidden_size'], m['intermediate_size'], m['rms_norm_eps'],
        m['tie_word_embeddings'])
    rs = m['rope_scaling']
    assert share.mla == hybrid.Mla(
        m['num_attention_heads'], m['kv_lora_rank'], m['qk_nope_head_dim'],
        m['qk_rope_head_dim'], m['v_head_dim'], m['rope_theta'],
        rope.DeepSeekYarn(rs['factor'], rs['original_max_position_embeddings'],
                          rs['beta_fast'], rs['beta_slow'],
                          mscale=rs['mscale'],
                          mscale_all_dim=rs['mscale_all_dim']))
    ex = share.experts
    assert (ex.num_experts, ex.experts_per_token, ex.mlp_dim, ex.scoring,
            ex.renormalize, ex.routed_scaling, ex.shared,
            ex.seq_aux_alpha, list(ex.held_range)) == (
        m['router_outputs'], m['num_experts_per_tok'],
        m['moe_intermediate_size'], m['scoring_func'], m['norm_topk_prob'],
        m['routed_scaling_factor'],
        (m['n_shared_experts'], m['moe_intermediate_size']),
        m['aux_loss_alpha'], m['experts_held'])
    assert m['experts_held'][1] - m['experts_held'][0] == \
        m['n_routed_experts']
    assert whole.experts.held_range == (0, 64)
    # the parameters held on one chip, part by part, and the whole model
    mla = share.mla.num_params(2048)
    assert mla + 2 * 2048 == 13767168
    assert share.num_params() == 635466752 == m['params_held'] == \
        (13767168 + 67239936) + 5 * (13767168 + 131072 + 17301504 +
                                     8 * 8650752) + 52430848
    assert whole.num_params() == 15706484224
    _, cfg = registry.build('debug-deepseek-v2')
    assert cfg.n_layers == 3 and cfg.vocab_size == 256


def test_the_two_copies_of_the_reference_are_identical():
    with open(os.path.join(REPO, 'chipbench', 'references',
                           'deepseek_v2.py'), 'rb') as a, \
            open(reference.__file__, 'rb') as b:
        assert a.read() == b.read()


def test_kimi_linears_latent_layer_is_unchanged_by_the_new_fields():
    """Kimi Linear's latent layer rotates nothing and keeps the kernels'
    own scale: no table, no `mla_rope` scope, the parameter tree of the
    published layer, and its outputs the (unrotated) reference's."""
    from skypilot_tpu.models import kimi_linear_reference
    share = hybrid.CONFIGS['kimi-linear-48b-a3b-ep32']
    assert (share.mla.rope_theta, share.mla.yarn,
            share.mla.softmax_scale) == (None, None, None)
    shapes = jax.eval_shape(
        hybrid.HybridModel(share).init, jax.random.PRNGKey(0),
        jnp.zeros((1, 16), jnp.int32))['params']['layer_3']['mla']
    assert jax.tree.map(lambda x: x.value.shape, shapes,
                        is_leaf=lambda x: hasattr(x, 'value')) == {
        'q_proj': {'kernel': (2304, 32 * 192)},
        'kv_a_proj': {'kernel': (2304, 576)},
        'kv_a_norm': {'weight': (512,)},
        'kv_b_proj': {'kernel': (512, 32 * 256)},
        'o_proj': {'kernel': (32 * 128, 2304)}}
    assert dispatch.mla_plan_snapshot() == {
        'layers': 1, 'heads': 32, 'qk': '128+64', 'v': 128, 'kv_rank': 512,
        'rope': 'none', 'softmax_scale': '0.0721688'}
    cfg = hybrid.CONFIGS['debug-kimi-linear']
    model = hybrid.HybridModel(cfg)
    tokens = jnp.asarray(np.random.default_rng(1).integers(0, 256, (1, 48)),
                         jnp.int32)
    params = nn.meta.unbox(model.init(jax.random.PRNGKey(1),
                                      tokens)['params'])
    text = str(jax.make_jaxpr(model.apply)({'params': params}, tokens))
    assert 'mla_rope' not in text and 'rope_freqs' not in text
    with open(os.path.join(REPO, 'chipbench', 'configs',
                           'kimi-linear-48b-a3b-ep32-sft.json'),
              encoding='utf-8') as f:
        kimi = json.load(f)
    sizes = {**kimi, **kimi['rehearsal'],
             'linear_attn_config': {
                 **kimi['linear_attn_config'],
                 **kimi['rehearsal']['linear_attn_config']}}
    got = jax.jit(model.apply)({'params': params}, tokens)[0]
    with jax.default_matmul_precision('highest'):
        want = kimi_linear_reference.logits(params, tokens[0], sizes)
    np.testing.assert_allclose(got, want, atol=2e-4, rtol=1e-4)


STEP_RE = re.compile(r'step (\d+)/\d+ loss=(\S+) tokens/s')
MOE_RE = re.compile(r'moe_pairs=(\d+)/(\d+) moe_fullest_over_mean=(\S+) '
                    r'moe_dropped=(\d+) moe_rows=\S+ moe_aux=(\S+)')


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
        sft.main(['--model', 'debug-deepseek-v2', '--mesh', 'fsdp=1',
                  '--steps', '3', '--batch', '2', '--seq', '64',
                  '--log-every', '1', '--data', str(data)])
    finally:
        sft.logger.removeHandler(handler)
    text = buf.getvalue()
    assert 'latent attention plan: layers=3 heads=4 qk=16+16 v=16 ' \
        'kv_rank=32 rope=yarn(40,4096,2-6) softmax_scale=0.2810089\n' in text
    assert 'moe routing plan: experts=16 held=0-15 k=4 tokens=128 ' in text
    steps = STEP_RE.findall(text)
    assert [int(n) for n, _ in steps] == [1, 2, 3]
    assert abs(float(steps[0][1]) - math.log(256)) < 2.5
    # 2 expert layers x 128 tokens x 4 slots, all held, none dropped; the
    # balance loss of two layers near alpha each
    found = MOE_RE.findall(text)
    assert [m[:2] + m[3:4] for m in found] == [('1024', '1024', '0')] * 3
    assert all(0.001 < float(m[4]) < 0.01 for m in found)
