"""Mellum2 on the kind-table decoder (models/hybrid.py): window and full
attention layers with a rotary table each (YaRN on the full ones,
ops/rope.py), softmax-routed experts in every layer (models/moe.py), an
untied head; against the plain float32 reference
(models/mellum_moe_reference.py) on seeded random weights, at the toy
preset `debug-mellum2`: three window layers of 8 and a full layer, 16
experts, top 4.
"""
import dataclasses
import io
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
from skypilot_tpu.models import mellum_moe_reference as reference
from skypilot_tpu.models import moe
from skypilot_tpu.models import registry
from skypilot_tpu.ops import attention as attention_ops
from skypilot_tpu.ops import dispatch
from skypilot_tpu.ops import rope
from skypilot_tpu.train import trainer

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LAYERS = tuple(f'layer_{i}' for i in range(4))


def _rope_entry(base, yarn):
    if yarn is None:
        return {'rope_type': 'default', 'rope_theta': base.rope_theta}
    return {'rope_type': 'yarn', 'rope_theta': base.rope_theta,
            'factor': yarn.factor,
            'original_max_position_embeddings': yarn.original_max_position,
            'beta_fast': yarn.beta_fast, 'beta_slow': yarn.beta_slow,
            'attention_factor': yarn.scale}


def _sizes(cfg: hybrid.HybridConfig) -> dict:
    """The published config.json keys the reference reads, from a preset."""
    base, ex = cfg.base, cfg.experts
    return {
        'hidden_size': base.dim, 'head_dim': base.head_dim,
        'num_attention_heads': base.n_heads,
        'num_key_value_heads': base.n_kv_heads,
        'rms_norm_eps': base.norm_eps, 'sliding_window': cfg.window,
        'rope_parameters': {
            'full_attention': _rope_entry(base, cfg.yarn),
            'sliding_attention': _rope_entry(base, None)},
        'layer_types': ['full_attention' if op == 'attention'
                        else 'sliding_attention' for op, _ in cfg.layers],
        'num_experts_per_tok': ex.experts_per_token, 'norm_topk_prob': True,
        'experts_held': list(ex.held_range)}


def _seeded(cfg, seed=3, held=None, rows=2, seq=32):
    if held is not None:
        cfg = dataclasses.replace(cfg, experts=dataclasses.replace(
            cfg.experts, held=held))
    model = hybrid.HybridModel(cfg)
    ids = jnp.asarray(np.random.default_rng(seed).integers(
        0, cfg.vocab_size, (rows, seq + 1)), jnp.int32)
    tokens, targets = ids[:, :-1], ids[:, 1:]
    params = nn.meta.unbox(jax.jit(model.init)(
        jax.random.PRNGKey(seed), tokens)['params'])
    return cfg, model, params, tokens, targets


def _rel(a, b):
    return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))


@pytest.mark.parametrize('held', [None, (4, 10)],
                         ids=['all_experts', 'a_share'])
def test_logits_loss_and_every_gradient_leaf_match_the_reference(held):
    cfg, model, params, tokens, targets = _seeded(
        hybrid.CONFIGS['debug-mellum2'], held=held)
    sizes = _sizes(cfg)
    assert 'lm_head' in params and 'expert_bias' not in params[
        'layer_0']['experts']

    def program(p):
        logits = model.apply({'params': p}, tokens)
        return trainer.cross_entropy_loss(logits, targets)[0], logits
    (loss_p, logits_p), grad_p = jax.jit(jax.value_and_grad(
        program, has_aux=True))(params)
    with jax.default_matmul_precision('highest'):
        loss_r, grad_r = jax.jit(jax.value_and_grad(
            lambda p: reference.loss(p, tokens, targets, sizes)))(params)
        logits_r = jax.jit(jax.vmap(
            lambda t: reference.logits(params, t, sizes)))(tokens)
    np.testing.assert_allclose(logits_p, logits_r, atol=2e-5)
    assert float(loss_p) == pytest.approx(float(loss_r), abs=1e-5)
    assert jax.tree.structure(grad_p) == jax.tree.structure(grad_r)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(grad_p),
                            jax.tree.leaves(grad_r)):
        assert _rel(a, b) < 2e-5, jax.tree_util.keystr(path)


def test_packed_segments_are_the_reference_on_each_document():
    """One row of two packed documents (20 and 12 tokens: the first
    longer than the window): positions restart, neither a window nor a
    full layer looks across the boundary, and loss and gradients are
    those of the reference run on each document by itself."""
    cfg, model, params, tokens, targets = _seeded(
        hybrid.CONFIGS['debug-mellum2'], rows=1)
    sizes = _sizes(cfg)
    seg = jnp.asarray([[1] * 20 + [2] * 12])

    def program(p):
        logits = model.apply({'params': p}, tokens, segment_ids=seg)
        return trainer.cross_entropy_loss(logits, targets)[0]

    def plain(p):
        return (20 * reference.loss(p, tokens[:, :20], targets[:, :20],
                                    sizes) +
                12 * reference.loss(p, tokens[:, 20:], targets[:, 20:],
                                    sizes)) / 32
    loss_p, grad_p = jax.jit(jax.value_and_grad(program))(params)
    with jax.default_matmul_precision('highest'):
        loss_r, grad_r = jax.jit(jax.value_and_grad(plain))(params)
    assert float(loss_p) == pytest.approx(float(loss_r), abs=1e-5)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(grad_p),
                            jax.tree.leaves(grad_r)):
        assert _rel(a, b) < 2e-5, jax.tree_util.keystr(path)
    # and the boundary matters: unpacked, the row reads otherwise
    assert abs(float(jax.jit(lambda p: trainer.cross_entropy_loss(
        model.apply({'params': p}, tokens), targets)[0])(params)) -
        float(loss_p)) > 1e-4


def test_the_reference_routes_as_the_program_does():
    cfg, model, params, tokens, _ = _seeded(hybrid.CONFIGS['debug-mellum2'])
    _, sown = model.apply({'params': params}, tokens,
                          mutable=['intermediates'])
    with jax.default_matmul_precision('highest'):
        routed = jax.vmap(lambda t: reference.routing(
            params, t, _sizes(cfg)))(tokens)
    assert sorted(routed) == list(LAYERS)
    for name in LAYERS:
        sel = sown['intermediates'][name]['experts']['selected'][0]
        own, probs = routed[name]
        assert (jnp.sort(sel.reshape(own.shape), -1) ==
                jnp.sort(own, -1)).all()
        np.testing.assert_allclose(probs.sum(-1), 1.0, atol=1e-5)


def test_the_four_shares_of_a_layer_add_up_to_the_whole_layer():
    """Four chips' shares of 4 of the 16 experts, one router: their
    partial outputs sum to the uncut reference's layer, and each share
    is the reference's for its range."""
    cfg = hybrid.CONFIGS['debug-mellum2']
    sizes = _sizes(cfg)
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 32, cfg.base.dim))
    whole = moe.RoutedExperts(cfg.base, cfg.experts)
    p = nn.meta.unbox(whole.init(jax.random.PRNGKey(2), x)['params'])

    def plain(p, held):
        with jax.default_matmul_precision('highest'):
            return jax.vmap(lambda row: reference._experts(
                row, p, dict(sizes, experts_held=list(held)))[0])(x)
    total, routed = 0.0, 0
    for lo in range(0, 16, 4):
        share = moe.RoutedExperts(cfg.base, dataclasses.replace(
            cfg.experts, held=(lo, lo + 4)))
        ps = dict(p, **{k: p[k][lo:lo + 4]
                        for k in ('w_gate', 'w_up', 'w_down')})
        out, stats = jax.jit(share.apply)({'params': ps}, x)
        np.testing.assert_allclose(out, plain(ps, (lo, lo + 4)), atol=1e-5)
        assert int(stats[2]) == 0                      # nothing dropped
        total, routed = total + out, routed + int(stats[0])
    assert routed == 2 * 32 * 4                        # every pair, once
    np.testing.assert_allclose(total, plain(p, (0, 16)), atol=2e-5)
    np.testing.assert_allclose(
        total, jax.jit(whole.apply)({'params': p}, x)[0], atol=2e-5)


def test_yarn_for_the_published_parameters_is_what_the_hand_gives():
    """rope_parameters.full_attention of Mellum2-12B-A2.5B: theta
    500,000, head 128, factor 16, original length 8,192, beta 32 / 1.
    c(r) = 128 ln(8192 / (2 pi r)) / (2 ln 500000): c(32) = 18.08,
    c(1) = 34.98, so the ramp runs from pair 18 to pair 35."""
    yarn = hybrid.CONFIGS['mellum2-12b-a2.5b'].yarn
    assert yarn.correction_range(128, 5e5) == (18, 35)
    assert yarn.scale == 1.2772588722239782
    assert rope.Yarn(16.0, 8192).scale == pytest.approx(
        0.1 * math.log(16) + 1, abs=1e-15)
    inv = rope.inv_freqs(128, 5e5, yarn)
    plain = rope.inv_freqs(128, 5e5)
    # pair 0: untouched; pair 20: ramp 2/17, plain 500000^(-40/128);
    # pairs 40 and 63: past the ramp, a sixteenth of the plain rule
    want = {0: 1.0, 20: 0.014733920954414175, 40: 1.7140510979762956e-05,
            63: 1.5344629944572555e-07}
    for j, value in want.items():
        assert float(inv[j]) == pytest.approx(value, rel=2e-6), j
    assert float(plain[20]) == pytest.approx(0.016560440080994446, rel=2e-6)
    assert (inv[:19] == plain[:19]).all()
    np.testing.assert_allclose(inv[35:], plain[35:] / 16, rtol=1e-6)
    # both halves of the table carry the factor, at every position, and
    # the reference's own table (written from the same equations) agrees
    pos = jnp.arange(0, 16384, 61)
    cos, sin = rope.rope_freqs(pos, 128, 5e5, yarn=yarn)
    np.testing.assert_allclose(cos ** 2 + sin ** 2, yarn.scale ** 2,
                               rtol=1e-5)
    sizes = _sizes(hybrid.CONFIGS['mellum2-12b-a2.5b'])
    ref_cos, ref_sin = reference.rotary_table(
        sizes['rope_parameters']['full_attention'], 128, 16384)
    np.testing.assert_allclose(cos, ref_cos[pos], atol=2e-3)
    np.testing.assert_allclose(sin, ref_sin[pos], atol=2e-3)
    win_cos, _ = rope.rope_freqs(pos, 128, 5e5)
    np.testing.assert_allclose(win_cos, reference.rotary_table(
        sizes['rope_parameters']['sliding_attention'], 128, 16384)[0][pos],
        atol=2e-3)


def _band_probe(seq=2048, hd=64):
    """q = k = 0, so a query's output is the mean of v over the keys it
    is allowed; channel 0 of v marks key 477 and channel 1 key 476."""
    q = jnp.zeros((1, seq, 1, hd))
    v = jnp.zeros((1, seq, 1, hd)).at[0, 477, 0, 0].set(1.0) \
        .at[0, 476, 0, 1].set(1.0)
    return q, v


@pytest.mark.parametrize('impl', ['xla', 'flash'])
def test_the_band_ends_at_the_windows_edge(impl):
    """Window 1,024: query 1,500 sees keys 477..1,500 (i - j = 1,023 is
    allowed, 1,024 is not), on the XLA rung and through the Pallas
    kernels (interpreted; 512 x 1,024 tiles of a 2,048 x 2,048 square,
    so the edge crosses tiles)."""
    q, v = _band_probe()
    dispatch.reset_for_tests()
    out = attention_ops.attention(q, q, v, impl=impl, window=1024)
    np.testing.assert_allclose(out[0, 1500, 0, :2], [1 / 1024, 0.0],
                               atol=1e-7)
    np.testing.assert_allclose(out[0, 1499, 0, :2], [1 / 1024, 1 / 1024],
                               atol=1e-7)
    np.testing.assert_allclose(out[0, 1501, 0, :2], [0.0, 0.0], atol=1e-7)
    np.testing.assert_allclose(out[0, 600, 0, :2], [1 / 601, 1 / 601],
                               atol=1e-7)
    if impl == 'flash':
        assert dispatch.snapshot() == {'flash_window_attention': 'pallas'}
        plan = dispatch.flash_plan_snapshot()
        assert list(plan) == ['window_fwd']
        assert (plan['window_fwd']['block_q'],
                plan['window_fwd']['block_k']) == (512, 1024)
    else:
        assert dispatch.snapshot() == {'attention': 'xla_native'}


def test_the_window_kernels_gradients_are_the_masked_references():
    """dq and dk/dv through the Pallas kernels (interpreted) at a shape
    of several tiles, window 1,024, grouped heads."""
    key = jax.random.PRNGKey(7)
    q = jax.random.normal(key, (1, 2048, 2, 64))
    k, v = (jax.random.normal(jax.random.fold_in(key, i), (1, 2048, 1, 64))
            for i in (1, 2))
    w = jax.random.normal(jax.random.fold_in(key, 3), q.shape)
    dispatch.reset_for_tests()
    out, vjp = jax.vjp(lambda *a: attention_ops.attention(
        *a, impl='flash', window=1024), q, k, v)
    ref, ref_vjp = jax.vjp(lambda *a: attention_ops.mha_reference(
        *a, window=1024), q, k, v)
    assert jnp.max(jnp.abs(out - ref)) < 2e-5
    for name, got, want in zip(('dq', 'dk', 'dv'), vjp(w), ref_vjp(w)):
        assert jnp.max(jnp.abs(got - want)) < 1e-4, name
    assert sorted(dispatch.flash_plan_snapshot()) == [
        'window_dkv', 'window_dq', 'window_fwd']


def test_a_static_window_goes_to_flash_by_the_shape_rule(monkeypatch):
    """No opt-in beside the rule: on the TPU a static window is a flash
    call where the shape allows one, and a traced window gate is XLA's."""
    from skypilot_tpu.utils import env
    assert not [name for name in env.registry() if 'FLASH' in name]
    monkeypatch.setattr(dispatch, 'interpret_mode', lambda: False)
    q = jax.ShapeDtypeStruct((1, 16384, 32, 128), jnp.bfloat16)
    k = jax.ShapeDtypeStruct((1, 16384, 4, 128), jnp.bfloat16)
    for window in (0, 1024):
        assert attention_ops._resolve_impl(
            q, k, 'auto', window, False, False) == 'flash'
    odd = jax.ShapeDtypeStruct((1, 1027, 32, 128), jnp.bfloat16)
    assert attention_ops._resolve_impl(
        odd, odd, 'auto', 1024, False, False) == 'xla'
    assert attention_ops._resolve_impl(
        q, k, 'auto', 1024, True, False) == 'xla'
    # the rule's tiles at the cell's shape: no wider than the window
    assert dispatch.flash_blocks(16384, 16384, 128, jnp.bfloat16, False,
                                 1024) == {
        'fwd': (512, 1024), 'dq': (1024, 1024), 'dkv': (512, 512)}
    # a traced gate (Gemma-2's alternation under nn.scan) stays XLA
    dispatch.reset_for_tests()
    x = jnp.ones((1, 64, 2, 64))
    jax.jit(lambda gate: attention_ops.attention(
        x, x, x, window=8, window_active=gate))(jnp.bool_(True))
    assert dispatch.snapshot() == {'attention': 'xla_native'}
    with pytest.raises(ValueError, match='window_active'):
        attention_ops.attention(x, x, x, impl='flash', window=8,
                                window_active=jnp.bool_(True))


def test_window_and_full_layers_record_their_own_rung_and_plan():
    cfg, _, params, tokens, targets = _seeded(
        hybrid.CONFIGS['debug-mellum2'], seq=64)
    model, _ = registry.build('debug-mellum2', 'flash')
    dispatch.reset_for_tests()
    jax.grad(lambda p: trainer.cross_entropy_loss(
        model.apply({'params': p}, tokens), targets)[0])(params)
    paths = dispatch.snapshot()
    assert paths['flash_window_attention'] == paths['flash_attention'] == \
        'pallas'
    plans = dispatch.flash_plan_snapshot()
    assert sorted(plans) == ['dkv', 'dq', 'fwd', 'window_dkv', 'window_dq',
                             'window_fwd']
    # each call under the scope of its kind, which a device trace keeps
    text = jax.jit(lambda p: model.apply({'params': p}, tokens)).lower(
        params).as_text(debug_info=True)
    for i, scope in enumerate(['flash_window'] * 3 + ['flash_full']):
        assert f'layer_{i}/attn/{scope}/' in text, (i, scope)


def test_the_presets_are_the_published_model_and_one_chips_share_of_it():
    whole = hybrid.CONFIGS['mellum2-12b-a2.5b']
    share = hybrid.CONFIGS['mellum2-12b-a2.5b-ep4']
    tiny = hybrid.CONFIGS['debug-mellum2']
    period = (('window_attention', 'experts'),) * 3 + \
        (('attention', 'experts'),)
    assert whole.layers == period * 7 and share.layers == period == \
        tiny.layers
    assert share.base == dataclasses.replace(whole.base, vocab_size=24576)
    assert (whole.base.dim, whole.base.n_heads, whole.base.n_kv_heads,
            whole.base.head_dim, whole.window, whole.base.norm_eps) == \
        (2304, 32, 4, 128, 1024, 1e-6)
    assert not whole.base.tie_embeddings and whole.base.qk_norm
    assert whole.base.sliding_window == 0       # the kind table's, not this
    assert (share.experts.num_experts, share.experts.experts_per_token,
            share.experts.mlp_dim, share.experts.scoring,
            share.experts.held_range) == (64, 8, 896, 'softmax', (0, 16))
    assert whole.experts.held_range == (0, 64)
    # ISSUE 33's arithmetic
    attn = 2 * 2304 * 4096 + 2 * 2304 * 512
    expert, rest = 3 * 2304 * 896, 2304 * 64 + 2 * 2304 + 2 * 128
    assert (attn, expert, rest) == (21233664, 6193152, 152320)
    assert share.num_params() == 595154176 == \
        4 * (attn + 16 * expert + rest) + 2 * 24576 * 2304 + 2304
    assert whole.num_params() == 12149923072 == \
        28 * (attn + 64 * expert + rest) + 2 * 98304 * 2304 + 2304
    assert tiny.num_params() == 725824
    model, cfg = registry.build('debug-mellum2')
    assert cfg.vocab_size == 256 and cfg.n_layers == 4
    with pytest.raises(ValueError, match='window'):
        hybrid.HybridConfig(base=tiny.base, layers=tiny.layers,
                            experts=tiny.experts)


def test_the_two_copies_of_the_reference_are_identical():
    with open(os.path.join(REPO, 'chipbench', 'references',
                           'mellum_moe.py'), 'rb') as a, \
            open(reference.__file__, 'rb') as b:
        assert a.read() == b.read()


# As chipbench/moe_train_cell.py and chipbench/train_cell.py have them.
MOE_RE = re.compile(r'moe_pairs=(\d+)/(\d+) moe_fullest_over_mean=(\S+) '
                    r'moe_dropped=(\d+)')
STEP_RE = re.compile(r'step (\d+)/\d+ loss=(\S+) tokens/s')


def test_sft_trains_the_preset_and_prints_the_lines_the_driver_parses():
    from skypilot_tpu.train import sft
    buf = io.StringIO()
    handler = logging.StreamHandler(buf)
    sft.logger.addHandler(handler)
    try:
        sft.main(['--model', 'debug-mellum2', '--mesh', 'fsdp=1', '--steps',
                  '3', '--batch', '2', '--seq', '64', '--log-every', '1'])
    finally:
        sft.logger.removeHandler(handler)
    text = buf.getvalue()
    assert 'moe routing plan: experts=16 held=0-15 k=4 tokens=128 ' \
        'buffer_rows=512 chunk_rows=512\n' in text
    paths = re.search(r'kernel dispatch paths: (\{.*?\}) '
                      r'\(pallas (\w+), flash backward (\w+)\)', text)
    assert paths.group(2, 3) == ('interpreted', 'pallas')
    assert "'moe_experts': 'ragged_dot'" in paths.group(1)
    steps = STEP_RE.findall(text)
    assert [int(n) for n, _ in steps] == [1, 2, 3]
    # an untied head of variance 1 / dim: half a nat over ln(256)
    assert abs(float(steps[0][1]) - math.log(256)) < 1.0
    # 4 expert layers x 128 tokens x 4 slots, all held, none dropped
    assert [m[:2] + m[3:] for m in MOE_RE.findall(text)] == \
        [('2048', '2048', '0')] * 3
