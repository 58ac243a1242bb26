"""SDAR-MoE on the kind-table decoder (models/hybrid.py), trained by
block diffusion (train/block_diffusion.py): the Qwen3-MoE block in every
layer, the row `[x_t | x_0]` of 2L positions under the block-diffusion
mask (ops/attention.py, ops/flash_attention.py), the head on the noised
half, the weighted masked loss; against the plain float32 reference
(models/sdar_moe_reference.py) on seeded random weights and the same
noise, at the toy preset `debug-sdar`: two layers, 16 experts, top 4,
blocks of 4.
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
from skypilot_tpu.models import moe
from skypilot_tpu.models import registry
from skypilot_tpu.models import sdar_moe_reference as reference
from skypilot_tpu.ops import attention as attention_ops
from skypilot_tpu.ops import dispatch
from skypilot_tpu.ops import flash_attention
from skypilot_tpu.train import block_diffusion
from skypilot_tpu.train import trainer

from flash_walk_helpers import check_walk

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LAYERS = ('layer_0', 'layer_1')


def _sizes(cfg: hybrid.HybridConfig) -> dict:
    """The configuration keys the reference reads, from a preset."""
    base, ex, bd = cfg.base, cfg.experts, cfg.block_diffusion
    return {
        'hidden_size': base.dim, 'head_dim': base.head_dim,
        'num_attention_heads': base.n_heads,
        'num_key_value_heads': base.n_kv_heads,
        'num_hidden_layers': cfg.n_layers,
        'rms_norm_eps': base.norm_eps, 'rope_theta': base.rope_theta,
        'num_experts_per_tok': ex.experts_per_token, 'norm_topk_prob': True,
        'experts_held': list(ex.held_range),
        'block_length': bd.block_length, 'mask_id': cfg.mask_id}


def _seeded(cfg, seed=3, held=None, rows=2, seq=32, block=None):
    if held is not None:
        cfg = dataclasses.replace(cfg, experts=dataclasses.replace(
            cfg.experts, held=held))
    if block is not None:
        cfg = dataclasses.replace(cfg, block_diffusion=dataclasses.replace(
            cfg.block_diffusion, block_length=block))
    model = hybrid.HybridModel(cfg)
    x0 = jnp.asarray(np.random.default_rng(seed).integers(
        0, cfg.data_vocab_size, (rows, seq)), jnp.int32)
    params = nn.meta.unbox(jax.jit(model.init)(
        jax.random.PRNGKey(seed), jnp.zeros((rows, 2 * seq), jnp.int32))[
            'params'])
    x_t, m, t = block_diffusion.noise(x0, jax.random.PRNGKey(seed + 1), cfg)
    return cfg, model, params, x0, (x_t, m, t)


def _rel(a, b):
    return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))


def _dense_mask(length, block):
    """The three clauses of ISSUE 35, by three nested loops."""
    mask = np.zeros((2 * length, 2 * length), bool)
    for p in range(2 * length):
        for r in range(2 * length):
            for clause in range(3):
                noised_p, noised_r = p < length, r < length
                b_p, b_r = (p % length) // block, (r % length) // block
                mask[p, r] |= (
                    (noised_p and noised_r and b_p == b_r) if clause == 0
                    else (noised_p and not noised_r and b_r < b_p)
                    if clause == 1
                    else (not noised_p and not noised_r and b_r <= b_p))
    return mask


# ------------------------------------------------------------- the model
@pytest.mark.parametrize('held', [None, (4, 10)],
                         ids=['all_experts', 'a_share'])
def test_loss_and_every_gradient_leaf_match_the_reference(held):
    cfg, model, params, x0, (x_t, m, t) = _seeded(
        hybrid.CONFIGS['debug-sdar'], held=held)
    sizes = _sizes(cfg)
    assert 'lm_head' in params and 'q_norm' in params['layer_0']['attn']
    assert bool(m.any()) and not bool(m.all())

    def program(p):
        return block_diffusion.loss_given_noise(model, p, x0, x_t, m, t)[0]
    loss_p, grad_p = jax.jit(jax.value_and_grad(program))(params)
    with jax.default_matmul_precision('highest'):
        loss_r, grad_r = jax.jit(jax.value_and_grad(
            lambda p: reference.loss(p, x0, m, t, sizes)))(params)
    assert float(loss_p) == pytest.approx(float(loss_r), abs=1e-5)
    assert jax.tree.structure(grad_p) == jax.tree.structure(grad_r)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(grad_p),
                            jax.tree.leaves(grad_r)):
        assert _rel(a, b) < 2e-5, jax.tree_util.keystr(path)


def test_the_logits_are_of_the_noised_half_and_for_the_same_position():
    cfg, model, params, x0, (x_t, m, t) = _seeded(
        hybrid.CONFIGS['debug-sdar'])
    tokens, positions = block_diffusion.model_inputs(x_t, x0)
    assert tokens.shape == positions.shape == (2, 64)
    assert (positions[:, :32] == positions[:, 32:]).all()
    assert (tokens[:, 32:] == x0).all()
    assert (tokens[:, :32] == jnp.where(m, 255, x0)).all()
    logits = jax.jit(lambda p: model.apply(
        {'params': p}, tokens, positions=positions))(params)
    assert logits.shape == (2, 32, 256)
    # the loss reads logits[i] against x0[i]: no shift
    want = block_diffusion.weighted_loss(logits, x0, m, t)
    logp = jax.nn.log_softmax(logits, -1)
    by_hand = sum(
        -float(logp[r, i, x0[r, i]]) / float(t[r, i])
        for r in range(2) for i in range(32) if bool(m[r, i])) / 64
    assert float(want) == pytest.approx(by_hand, rel=1e-5)


def test_a_masked_positions_clean_copy_reaches_its_logits_by_no_path():
    """The leak test: change x_0 inside one noised block (the clean
    copy of that block, and of no other): the logits of that block and
    of every block before it do not move; those of the blocks after it
    do (they see the clean copy)."""
    cfg, model, params, x0, (x_t, m, t) = _seeded(
        hybrid.CONFIGS['debug-sdar'], rows=1)
    x_t = jnp.full_like(x0, cfg.mask_id)   # all masked

    def logits(clean):
        tokens, positions = block_diffusion.model_inputs(x_t, clean)
        return model.apply({'params': params}, tokens, positions=positions)
    other = x0.at[0, 12:16].set((x0[0, 12:16] + 7) % 255)   # block 3
    a, b = jax.jit(logits)(x0), jax.jit(logits)(other)
    np.testing.assert_array_equal(a[0, :16], b[0, :16])
    assert float(jnp.abs(a[0, 16:] - b[0, 16:]).max()) > 1e-3
    # ... and noised blocks do not see one another: a change of x_t in
    # block 3 moves block 3's logits alone
    def logits_t(noised):
        tokens, positions = block_diffusion.model_inputs(noised, x0)
        return model.apply({'params': params}, tokens, positions=positions)
    c = jax.jit(logits_t)(x_t.at[0, 13].set(5))
    moved = jnp.abs(c - a)[0].max(-1) > 0
    assert moved[12:16].all() and not moved[:12].any() and \
        not moved[16:].any()


def test_the_reference_routes_as_the_program_does():
    cfg, model, params, x0, (x_t, m, t) = _seeded(
        hybrid.CONFIGS['debug-sdar'])
    _, sown = block_diffusion.loss_given_noise(model, params, x0, x_t, m, t)
    with jax.default_matmul_precision('highest'):
        routed = jax.vmap(lambda x, msk: reference.routing(
            params, x, msk, _sizes(cfg)))(x0, m)
    assert sorted(routed) == list(LAYERS)
    for name in LAYERS:
        sel = sown['intermediates'][name]['experts']['selected'][0]
        own, probs = routed[name]
        assert own.shape == (2, 64, 4)                # all 2L are routed
        assert (jnp.sort(sel.reshape(own.shape), -1) ==
                jnp.sort(own, -1)).all()
        np.testing.assert_allclose(probs.sum(-1), 1.0, atol=1e-5)


def test_the_eight_shares_of_a_layer_add_up_to_the_whole_layer():
    """Eight chips' shares of 2 of the 16 experts, one router: their
    partial outputs sum to the uncut reference's layer, and each share
    is the reference's for its range."""
    cfg = hybrid.CONFIGS['debug-sdar']
    sizes = _sizes(cfg)
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 64, cfg.base.dim))
    whole = moe.RoutedExperts(cfg.base, cfg.experts)
    p = nn.meta.unbox(whole.init(jax.random.PRNGKey(2), x)['params'])

    def plain(p, held):
        with jax.default_matmul_precision('highest'):
            return jax.vmap(lambda row: reference._experts(
                row, p, dict(sizes, experts_held=list(held)))[0])(x)
    total, routed = 0.0, 0
    for lo in range(0, 16, 2):
        share = moe.RoutedExperts(cfg.base, dataclasses.replace(
            cfg.experts, held=(lo, lo + 2)))
        ps = dict(p, **{k: p[k][lo:lo + 2]
                        for k in ('w_gate', 'w_up', 'w_down')})
        out, stats = jax.jit(share.apply)({'params': ps}, x)
        np.testing.assert_allclose(out, plain(ps, (lo, lo + 2)), atol=1e-5)
        assert int(stats[2]) == 0                      # nothing dropped
        total, routed = total + out, routed + int(stats[0])
    assert routed == 2 * 64 * 4                        # every pair, once
    np.testing.assert_allclose(total, plain(p, (0, 16)), atol=2e-5)
    np.testing.assert_allclose(
        total, jax.jit(whole.apply)({'params': p}, x)[0], atol=2e-5)


# -------------------------------------------------------------- the mask
@pytest.mark.parametrize('length,block', [(20, 1), (20, 4), (20, 20),
                                          (24, 4), (24, 24)])
def test_the_mask_is_the_three_clauses_by_enumeration(length, block):
    dense = _dense_mask(length, block)
    idx = jnp.arange(2 * length)
    for rule in (attention_ops.block_diffusion_allowed, reference.allowed):
        np.testing.assert_array_equal(
            rule(idx[:, None], idx[None, :], length, block), dense)
    assert dense.sum() == flash_attention.allowed_pairs(length, block) == \
        length * length + length * block
    noised, clean = dense[:length], dense[length:]
    assert not clean[:, :length].any()      # clean never sees noised
    assert dense.diagonal().all()           # every position sees itself
    # a noised position never sees its own block's clean copy
    for p in range(length):
        own = slice(length + p // block * block,
                    length + (p // block + 1) * block)
        assert not noised[p, own].any()
    if block == 1:
        # the clean half is plain causal; a noised position sees itself
        # and the clean positions before it
        np.testing.assert_array_equal(clean[:, length:],
                                      np.tril(np.ones((length,) * 2, bool)))
        np.testing.assert_array_equal(noised[:, :length],
                                      np.eye(length, dtype=bool))
        np.testing.assert_array_equal(
            noised[:, length:], np.tril(np.ones((length,) * 2, bool), -1))
    if block == length:
        # each half sees itself both ways; noised never sees clean
        assert noised[:, :length].all() and clean[:, length:].all()
        assert not noised[:, length:].any()


def _count_tiles(dense, block_q, block_k):
    visited = masked = 0
    for qi in range(dense.shape[0] // block_q):
        for ki in range(dense.shape[1] // block_k):
            tile = dense[qi * block_q:(qi + 1) * block_q,
                         ki * block_k:(ki + 1) * block_k]
            visited += bool(tile.any())
            masked += bool(tile.any() and not tile.all())
    return visited, masked


@pytest.mark.parametrize('length,block,want', [
    (24, 4, (8, 8)), (24, 1, (8, 16)), (32, 4, (16, 8)), (32, 32, (16, 16)),
    (20, 4, (16, 16)), (24, 24, (8, 8)), (64, 4, (16, 32))])
def test_the_flash_kernels_compute_the_mask_forward_and_backward(
        length, block, want):
    """Interpreted Pallas against `mha_reference` given the dense mask by
    enumeration, grouped heads; L = 20 and 24 are no multiple of 16 (at
    20 no legal tile divides L and the tile is the whole row); and the
    tile counts of the plan and the list of tiles the grid visits are
    those of an enumeration."""
    dense = _dense_mask(length, block)
    key = jax.random.PRNGKey(length + block)
    q = jax.random.normal(key, (1, 2 * length, 4, 16))
    k, v, w = (jax.random.normal(jax.random.fold_in(key, i),
                                 (1, 2 * length, 2 if i < 3 else 4, 16))
               for i in (1, 2, 3))

    def plain(q, k, v):
        scores = jnp.einsum('bqhgd,bkhd->bhgqk',
                            q.reshape(1, 2 * length, 2, 2, 16), k) / 4.0
        probs = jax.nn.softmax(jnp.where(dense, scores, -jnp.inf), -1)
        return jnp.einsum('bhgqk,bkhd->bqhgd', probs, v).reshape(q.shape)
    dispatch.reset_for_tests()
    out, vjp = jax.vjp(lambda *a: flash_attention.flash_attention(
        *a, block_q=want[0], block_k=want[1], block_diffusion=block),
        q, k, v)
    ref, ref_vjp = jax.vjp(plain, q, k, v)
    assert jnp.max(jnp.abs(out - ref)) < 2e-5
    for name, got, wanted in zip(('dq', 'dk', 'dv'), vjp(w), ref_vjp(w)):
        assert jnp.max(jnp.abs(got - wanted)) < 1e-4, name
    xla = attention_ops.mha_reference(q, k, v, causal=False,
                                      block_diffusion=block)
    assert jnp.max(jnp.abs(xla - ref)) < 2e-5
    plans = dispatch.flash_plan_snapshot()
    assert sorted(plans) == ['bd_dkv', 'bd_dq', 'bd_fwd']
    for kernel, plan in plans.items():
        bq, bk = plan['block_q'], plan['block_k']
        assert all(e == 2 * length or length % e == 0 for e in (bq, bk))
        assert (plan['visited'], plan['masked']) == \
            _count_tiles(dense, bq, bk)
        assert plan['needed'] == round(dense.sum() / (bq * bk), 2)
        # the grid: each tile with an allowed entry once, in walk order,
        # both runs of a row; no step for a skipped tile, no empty row
        by_k = kernel == 'bd_dkv'
        codes, _ = flash_attention._walk(
            2 * length, 2 * length, bq, bk, False, 0, False,
            (length, block), by_k)
        assert len(codes) == plan['steps'] == plan['visited']
        assert check_walk(codes, dense, bq, bk, by_k) == 0


def test_the_xla_rung_works_a_block_of_queries_at_a_time(monkeypatch):
    key = jax.random.PRNGKey(0)
    q, k, v = (jax.random.normal(jax.random.fold_in(key, i), (2, 48, 2, 16))
               for i in range(3))
    whole = attention_ops._bd_reference(q, k, v, 4)
    monkeypatch.setattr(attention_ops, '_BD_XLA_SCORE_BYTES',
                        4 * 2 * 2 * 48 * 16)          # 16 queries at a time
    parts = attention_ops._bd_reference(q, k, v, 4)
    np.testing.assert_allclose(parts, whole, atol=1e-6)
    np.testing.assert_allclose(whole, attention_ops.mha_reference(
        q, k, v, causal=False, block_diffusion=4), atol=1e-6)


def test_the_mask_goes_to_flash_by_the_shape_rule(monkeypatch):
    """No flag and no environment variable: on the TPU the mask is a
    flash call where the shape allows one, under its own op."""
    from skypilot_tpu.utils import env
    assert not [name for name in env.registry()
                if 'DIFFUSION' in name or 'SDAR' in name]
    monkeypatch.setattr(dispatch, 'interpret_mode', lambda: False)
    q = jax.ShapeDtypeStruct((1, 16384, 32, 128), jnp.bfloat16)
    k = jax.ShapeDtypeStruct((1, 16384, 4, 128), jnp.bfloat16)
    assert attention_ops._resolve_impl(q, k, 'auto', 0, False, False,
                                       True) == 'flash'
    plan = dispatch.flash_blocks(16384, 16384, 128, jnp.bfloat16, False,
                                 block_diffusion=True)
    assert plan == {'fwd': (512, 1024), 'dq': (1024, 1024),
                    'dkv': (512, 512)}
    counts = flash_attention.tile_counts(16384, 16384, 512, 1024, False, 0,
                                         False, (8192, 4))
    assert counts == {'visited': 160, 'masked': 48, 'skipped': 352,
                      'steps': 160, 'needed': 128.06}
    with pytest.raises(ValueError, match='block-diffusion'):
        attention_ops.attention(jnp.ones((1, 16, 2, 16)),
                                jnp.ones((1, 16, 2, 16)),
                                jnp.ones((1, 16, 2, 16)), block_diffusion=4,
                                segment_ids=jnp.ones((1, 16), jnp.int32))
    with pytest.raises(ValueError, match='block-diffusion'):
        flash_attention.flash_attention(
            jnp.ones((1, 24, 2, 16)), jnp.ones((1, 24, 2, 16)),
            jnp.ones((1, 24, 2, 16)), block_diffusion=8)


def test_the_attention_layers_record_their_own_rung_plan_and_scope():
    cfg, _, params, x0, (x_t, m, t) = _seeded(hybrid.CONFIGS['debug-sdar'],
                                              seq=64)
    model, _ = registry.build('debug-sdar', 'flash')
    dispatch.reset_for_tests()
    jax.grad(lambda p: block_diffusion.loss_given_noise(
        model, p, x0, x_t, m, t)[0])(params)
    assert dispatch.snapshot()['flash_block_diffusion_attention'] == 'pallas'
    assert 'flash_attention' not in dispatch.snapshot()
    assert sorted(dispatch.flash_plan_snapshot()) == [
        'bd_dkv', 'bd_dq', 'bd_fwd']
    text = jax.jit(lambda p: block_diffusion.loss_given_noise(
        model, p, x0, x_t, m, t)[0]).lower(params).as_text(debug_info=True)
    for i in range(2):
        assert f'layer_{i}/attn/flash_block_diffusion/' in text
    assert 'bd_objective/bd_loss' in text


# --------------------------------------------------- noise and the trainer
def test_the_noise_is_the_objectives_and_a_function_of_the_key():
    bd = dataclasses.replace(
        hybrid.CONFIGS['debug-sdar'], base=dataclasses.replace(
            hybrid.CONFIGS['debug-sdar'].base, vocab_size=1000))
    assert bd.mask_id == 999 and bd.block_diffusion.block_length == 4
    x0 = jnp.arange(4 * 4096).reshape(4, 4096) % 999
    x_t, m, t = jax.jit(lambda k: block_diffusion.noise(x0, k, bd))(
        jax.random.PRNGKey(1))
    again = block_diffusion.noise(x0, jax.random.PRNGKey(1), bd)
    other = block_diffusion.noise(x0, jax.random.PRNGKey(2), bd)
    assert (x_t == again[0]).all() and not (x_t == other[0]).all()
    assert (x_t == jnp.where(m, 999, x0)).all()
    levels = t.reshape(4, 1024, 4)
    assert (levels == levels[..., :1]).all()          # one level a block
    assert float(t.min()) > block_diffusion.T_MIN == 1e-3
    assert float(t.max()) <= 1.0
    assert abs(float(t.mean()) - 0.5005) < 0.02
    assert abs(float(m.mean()) - 0.5005) < 0.02       # masked about half
    # masked with probability t: the share masked follows the level
    low, high = t < 0.25, t > 0.75
    assert float(m[low].mean()) < 0.2 < 0.8 < float(m[high].mean())
    with pytest.raises(ValueError, match='whole number of blocks'):
        block_diffusion.noise(x0[:, :4094], jax.random.PRNGKey(1), bd)


def test_the_trainer_asks_the_model_and_a_resumed_state_continues_its_noise():
    """make_train_step takes no flag for the objective; the step's noise
    is a function of state.step alone."""
    import optax
    from skypilot_tpu.parallel import mesh as mesh_lib
    model, cfg = registry.build('debug-sdar')
    assert block_diffusion.objective_of(model) is cfg
    assert block_diffusion.objective_of(
        registry.build('debug-mellum2')[0]) is None
    mesh = mesh_lib.build_mesh(mesh_lib.MeshSpec(fsdp=1))
    tx = optax.sgd(0.0)                 # the weights stay: only step moves
    state, _ = trainer.create_sharded_state(
        model, tx, mesh, jnp.zeros((2, 64), jnp.int32),
        jax.random.PRNGKey(0))
    step = trainer.make_train_step(model, tx, mesh, donate=False)
    x0 = jnp.asarray(np.random.default_rng(0).integers(0, 255, (2, 32)),
                     jnp.int32)
    batch = {'tokens': x0}
    s1, m0 = step(state, batch)
    s2, m1 = step(s1, batch)
    assert int(s2.step) == 2 and float(m0['tokens']) == 64.0
    assert int(m0['bd_targets']) == 64 and 0 < int(m0['bd_masked']) < 64
    assert float(m0['loss']) != float(m1['loss'])      # another step's noise
    # a state put back to step 1 (a resumed job) draws step 1's noise
    _, again = step(s1.replace(step=jnp.int32(1)), batch)
    assert float(again['loss']) == float(m1['loss'])
    assert int(again['bd_masked']) == int(m1['bd_masked'])
    # and it is the noise of fold_in(PRNGKey(NOISE_KEY), step)
    x_t, m, t = block_diffusion.noise(
        x0, jax.random.fold_in(jax.random.PRNGKey(
            block_diffusion.NOISE_KEY), 1), cfg)
    assert int(m.sum()) == int(m1['bd_masked'])
    want = block_diffusion.loss_given_noise(
        model, nn.meta.unbox(state.params), x0, x_t, m, t)[0]
    assert float(m1['loss']) == pytest.approx(float(want), rel=1e-5)
    assert float(m1['bd_weight_mean']) == pytest.approx(
        float(jnp.where(m, 1 / t, 0).sum() / m.sum()), rel=1e-5)
    assert dispatch.bd_plan_snapshot() == {
        'block': 4, 'data': 32, 'positions': 64, 'allowed_pairs': 1152,
        'mask_id': 255}


def test_the_presets_are_the_published_model_and_one_chips_share_of_it():
    whole = hybrid.CONFIGS['sdar-30b-a3b']
    share = hybrid.CONFIGS['sdar-30b-a3b-ep8']
    tiny = hybrid.CONFIGS['debug-sdar']
    assert whole.layers == (('attention', 'experts'),) * 48
    assert share.layers == (('attention', 'experts'),) * 6
    assert share.base == dataclasses.replace(whole.base, vocab_size=18992)
    assert (whole.base.dim, whole.base.n_heads, whole.base.n_kv_heads,
            whole.base.head_dim, whole.base.norm_eps,
            whole.base.rope_theta, whole.base.mlp_dim) == \
        (2048, 32, 4, 128, 1e-6, 1e6, 6144)
    assert not whole.base.tie_embeddings and whole.base.qk_norm
    assert whole.yarn is None and whole.window == 0
    assert (share.experts.num_experts, share.experts.experts_per_token,
            share.experts.mlp_dim, share.experts.scoring,
            share.experts.held_range) == (128, 8, 768, 'softmax', (0, 16))
    assert whole.experts.held_range == (0, 128)
    assert share.block_diffusion == hybrid.BlockDiffusion(4)
    assert (share.mask_id, whole.mask_id) == (18991, 151935)
    assert share.data_vocab_size == 18991 and \
        hybrid.CONFIGS['debug-mellum2'].data_vocab_size == 256
    # ISSUE 35's arithmetic
    attn = 2 * 2048 * 4096 + 2 * 2048 * 512
    expert, rest = 3 * 2048 * 768, 2048 * 128 + 2 * 2048 + 2 * 128
    assert (attn, expert, rest) == (18874368, 4718592, 266496)
    assert share.num_params() == 645623296 == \
        6 * (attn + 16 * expert + rest) + 2 * 18992 * 2048 + 2048
    assert whole.num_params() == 30532122624 == \
        48 * (attn + 128 * expert + rest) + 2 * 151936 * 2048 + 2048
    model, cfg = registry.build('debug-sdar')
    assert cfg.vocab_size == 256 and cfg.n_layers == 2
    assert tiny.mask_id == 255 and tiny.data_vocab_size == 255
    with pytest.raises(ValueError, match='block diffusion'):
        dataclasses.replace(
            hybrid.CONFIGS['debug-mellum2'],
            block_diffusion=hybrid.BlockDiffusion(4))


def test_the_chunk_rule_is_every_models():
    """The expert layer knows nothing of the objective above it: a
    block-diffusion model's chunk is the shape's, an even router's
    pairs over its 2L positions and a sixteenth, as every model's."""
    assert dispatch.moe_chunk_rows(16384, 8, 16, 128) == 17408
    cfg, model, params, x0, (x_t, m, t) = _seeded(
        dataclasses.replace(hybrid.CONFIGS['debug-sdar'],
                            experts=dataclasses.replace(
            hybrid.CONFIGS['debug-sdar'].experts, held=(0, 4))))
    dispatch.reset_for_tests()
    block_diffusion.loss_given_noise(model, params, x0, x_t, m, t)
    # 128 positions a row pair, top 4 of 16 with 4 held: 128 even, 8 of
    # room
    assert dispatch.moe_plan_snapshot()['chunk_rows'] == \
        dispatch.moe_chunk_rows(128, 4, 4, 16) == 136
    other, _ = registry.build('debug-mellum2')
    dispatch.reset_for_tests()
    jax.eval_shape(other.init, jax.random.PRNGKey(0),
                   jnp.zeros((2, 64), jnp.int32))
    assert dispatch.moe_plan_snapshot()['chunk_rows'] == \
        dispatch.moe_chunk_rows(128, 4, 16, 16) == 512


def test_the_two_copies_of_the_reference_are_identical():
    with open(os.path.join(REPO, 'chipbench', 'references',
                           'sdar_moe.py'), 'rb') as a, \
            open(reference.__file__, 'rb') as b:
        assert a.read() == b.read()


# As chipbench/bd_moe_train_cell.py, chipbench/moe_train_cell.py and
# chipbench/train_cell.py have them.
BD_RE = re.compile(r'bd_masked=(\d+)/(\d+) bd_weight_mean=(\S+)')
MOE_RE = re.compile(r'moe_pairs=(\d+)/(\d+) moe_fullest_over_mean=(\S+) '
                    r'moe_dropped=(\d+)')
STEP_RE = re.compile(r'step (\d+)/\d+ loss=(\S+) tokens/s')


def test_sft_trains_the_preset_and_prints_the_lines_the_driver_parses(
        tmp_path):
    from skypilot_tpu.train import sft
    data = tmp_path / 'rows.jsonl'
    rows = np.random.default_rng(0).integers(0, 255, (40, 65))
    data.write_text(''.join(
        '{"tokens": ' + str(row.tolist()) + '}\n' for row in rows))
    buf = io.StringIO()
    handler = logging.StreamHandler(buf)
    sft.logger.addHandler(handler)
    try:
        sft.main(['--model', 'debug-sdar', '--mesh', 'fsdp=1', '--steps',
                  '3', '--batch', '2', '--seq', '64', '--log-every', '1',
                  '--data', str(data)])
    finally:
        sft.logger.removeHandler(handler)
    text = buf.getvalue()
    # --seq is the data's length; the model reads twice as many
    assert 'block diffusion plan: block=4 data=64 positions=128 ' \
        'allowed_pairs=4352 mask_id=255\n' in text
    assert 'moe routing plan: experts=16 held=0-15 k=4 tokens=256 ' \
        'buffer_rows=1024 chunk_rows=1024\n' in text
    steps = STEP_RE.findall(text)
    assert [int(n) for n, _ in steps] == [1, 2, 3]
    # 128 targets a step (2 rows x 64), weights 1 / t: a noisy first loss
    assert abs(float(steps[0][1]) - math.log(256)) < 2.0
    assert re.search(r'tokens/s=\d+ moe_pairs', text)
    # 2 expert layers x 256 positions x 4 slots, all held, none dropped
    assert [m[:2] + m[3:] for m in MOE_RE.findall(text)] == \
        [('2048', '2048', '0')] * 3
    found = BD_RE.findall(text)
    assert len(found) == 3 and all(
        total == '128' and 20 < int(masked) < 108 and float(mean) >= 1.0
        for masked, total, mean in found)


def test_the_data_path_gives_rows_of_the_data_without_the_shift(tmp_path):
    from skypilot_tpu.train import sft
    batch = next(sft.synthetic_batches(255, 2, 16, shift=False))
    assert sorted(batch) == ['tokens'] and batch['tokens'].shape == (2, 16)
    assert batch['tokens'].max() < 255
    shifted = next(sft.synthetic_batches(255, 2, 16))
    assert (shifted['tokens'][:, 1:] == shifted['targets'][:, :-1]).all()
    data = tmp_path / 'rows.jsonl'
    data.write_text('{"tokens": ' + str(list(range(300, 340))) + '}\n')
    rows = next(sft.jsonl_batches(str(data), 255, 2, 16, shift=False))
    assert sorted(rows) == ['tokens'] and rows['tokens'].shape == (2, 16)
    assert rows['tokens'][0].tolist() == [i % 255 for i in range(300, 316)]
    assert rows['tokens'][1].tolist() == [i % 255 for i in range(316, 332)]
    with pytest.raises(SystemExit, match='block diffusion'):
        sft.main(['--model', 'debug-sdar', '--mesh', 'fsdp=1', '--steps',
                  '1', '--lora-rank', '4'])
