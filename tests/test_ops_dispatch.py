"""Kernel dispatch layer: shape-robust block selection, the tile rule,
the Pallas -> XLA fallback ladder, and the ops.lowering chaos path
(docs/kernels.md).

Everything here runs on CPU: the Pallas rungs execute in interpreter
mode (kernel logic exercised; the Mosaic legality rules are checked
against the STATIC mirror in ops/dispatch.py, the same predicate jax's
_check_block_mappings enforces on-chip).
"""
import os
import subprocess
import sys
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
import requests

from skypilot_tpu.ops import attention as attention_ops
from skypilot_tpu.ops import dispatch
from skypilot_tpu.ops import flash_attention as flash_lib
from skypilot_tpu.utils import env
from skypilot_tpu.utils import faults
from skypilot_tpu.utils import metrics as metrics_lib

from flash_walk_helpers import check_walk


def _qkv(b, sq, sk, hq, hkv, d, dtype=jnp.float32, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (b, sq, hq, d), dtype)
    k = jax.random.normal(ks[1], (b, sk, hkv, d), dtype)
    v = jax.random.normal(ks[2], (b, sk, hkv, d), dtype)
    return q, k, v


# ------------------------------------------ static block-spec selection
class TestBlockSelection:

    def test_choose_block_mirrors_mosaic_rule(self):
        """Every selection must satisfy the exact predicate jax's
        _check_block_mappings enforces: block % tile == 0 or block ==
        dim — plus our kernels' exact-division invariant."""
        for dim in (1, 3, 8, 12, 17, 48, 128, 256, 300, 1000, 4096):
            for want in (1, 8, 100, 128, 256, 512):
                for mult in (8, 16, 32, 128):
                    b = dispatch.choose_block(dim, want, mult)
                    assert dispatch.block_dim_ok(b, dim, mult), \
                        (dim, want, mult, b)
                    assert dim % b == 0, (dim, want, mult, b)
                    assert b <= dim

    def test_choose_block_prefers_tile_aligned_divisor(self):
        assert dispatch.choose_block(512, 256, 128) == 256
        assert dispatch.choose_block(48, 256, 8) == 48   # full dim
        assert dispatch.choose_block(48, 24, 8) == 24
        # 300 has no 8-aligned divisor <= 256 -> full-array block.
        assert dispatch.choose_block(300, 256, 8) == 300
        # Decode-shaped: tiny dim -> full dim (equal arm of the rule).
        assert dispatch.choose_block(8, 256, 8) == 8
        assert dispatch.choose_block(1, 256, 8) == 1

    def test_flash_blocks_seg_uses_lane_alignment(self):
        # Packed sequences put the seq extent on the lane axis of the
        # segment-id blocks -> 128-aligned (or full-dim) blocks only.
        for want in (None, (256, 256), (192, 192)):
            plan = dispatch.flash_blocks(512, 512, 64, jnp.float32, True,
                                         want=want)
            assert sorted(plan) == sorted(dispatch.FLASH_KERNELS)
            for bq, bk in plan.values():
                assert bq % 128 == 0 and bk % 128 == 0
        plan = dispatch.flash_blocks(48, 48, 64, jnp.float32, True,
                                     want=(32, 32))
        for bq, _ in plan.values():
            assert bq == 48   # no 128-aligned divisor -> full dim
        # The dk/dv kernel reads its row statistics as [1, block_q]
        # rows: its q extent rides the lane axis with or without
        # segment ids; the other kernels keep the sublane-aligned 160.
        plan = dispatch.flash_blocks(320, 320, 64, jnp.bfloat16, False,
                                     want=(256, 256))
        assert plan['fwd'] == (160, 160) and plan['dkv'] == (320, 160)

    def test_vmem_guard_refuses_impossible_blocks(self):
        every = dispatch.FLASH_KERNELS
        assert dispatch.flash_vmem_ok(dict.fromkeys(every, (256, 256)),
                                      128, 2)
        assert not dispatch.flash_vmem_ok(
            dict.fromkeys(every, (8192, 8192)), 256, 4)

    @pytest.mark.parametrize('seq,head', [(2048, 128), (8192, 64)],
                             ids=['sft-2k', 'sft-moe-8k'])
    def test_rule_plan_fits_vmem_budget_at_training_shape(self, seq, head):
        """What the rule gives the two training cells (bf16; sft-2k: S
        2,048 at head 128; sft-moe-8k: S 8,192 at head 64) is the plan
        PERF.md section 5 records, and fits VMEM_BUDGET_BYTES by the
        per-kernel count; past Mosaic's default scoped VMEM a limit is
        passed."""
        assert dispatch.VMEM_BUDGET_BYTES == 12 * 1024 * 1024
        for has_seg in (False, True):
            plan = dispatch.flash_blocks(seq, seq, head, jnp.bfloat16,
                                         has_seg)
            assert plan == {'fwd': (512, 1024), 'dq': (1024, 1024),
                            'dkv': (512, 512)}
            for kernel, (bq, bk) in plan.items():
                need = dispatch.flash_vmem_bytes(kernel, bq, bk, head, 2,
                                                 has_seg)
                assert need <= dispatch.VMEM_BUDGET_BYTES, (kernel, need)
                limit = dispatch.flash_vmem_limit(need)
                assert limit is None or limit >= 2 * need
        # A score tile the budget cannot hold is halved, not offered.
        wide = dispatch.flash_blocks(65536, 65536, 512, jnp.float32, False)
        for kernel, (bq, bk) in wide.items():
            assert dispatch.flash_vmem_bytes(
                kernel, bq, bk, 512, 4) <= dispatch.VMEM_BUDGET_BYTES
        assert dispatch.flash_vmem_limit(9 * 1024 * 1024) == \
            18 * 1024 * 1024
        assert dispatch.flash_vmem_limit(8 * 1024 * 1024) is None


# ------------------------------------- shape grid over the public entry
# Adversarial shapes: (b, sq, sk, hq, hkv, d). Includes the decode
# shape (4, 32, 8, 256) whose 256-row block once crashed the Mosaic
# lowering, in BOTH layout readings — [B,Sq,Hq,D] and the [B,Hq,Sq,D]
# kernel layout it was logged in. And lengths between 512 and 1,024,
# with and without packed segments ('seg'): longer than every tile the
# rule gives, shorter than what a whole-sequence tile may be, so the
# rule's own tiles are the only Pallas plan there is for them.
SHAPE_GRID = [
    (4, 32, 32, 8, 8, 256),     # that shape, API layout
    (4, 8, 8, 32, 32, 256),     # that shape, kernel-layout reading
    (2, 1, 1, 4, 2, 64),        # decode: single query token
    (1, 300, 300, 2, 2, 64),    # non-pow2, non-8-divisible seq
    (1, 48, 48, 4, 4, 64),      # tiny batch, sub-block seq
    (3, 24, 24, 2, 1, 128),     # odd batch + GQA
    (1, 640, 640, 2, 1, 64),
    (1, 768, 768, 2, 2, 64),
    (1, 896, 896, 2, 1, 64),
    (2, 640, 640, 2, 2, 64, 'seg'),
    (2, 768, 768, 2, 1, 64, 'seg'),
    (2, 896, 896, 2, 2, 64, 'seg'),
]


class TestShapeGrid:

    @pytest.mark.parametrize('shape', SHAPE_GRID,
                             ids=['x'.join(map(str, s))
                                  for s in SHAPE_GRID])
    def test_no_shape_raises_and_matches_reference(self, shape):
        """No grid shape may raise from the public ops entry point, and
        each lands on the Pallas rung; golden numerics vs the XLA
        reference in interpreter mode."""
        b, sq, sk, hq, hkv, d = shape[:6]
        has_seg = shape[6:] == ('seg',)
        q, k, v = _qkv(b, sq, sk, hq, hkv, d)
        seg = _seg_ids(b, sq) if has_seg else None
        causal = sq == sk   # cross-length decode shapes: plain attn
        dispatch.reset_for_tests()
        out = attention_ops.attention(q, k, v, causal=causal,
                                      segment_ids=seg, impl='flash')
        ref = attention_ops.mha_reference(q, k, v, causal=causal,
                                          segment_ids=seg)
        assert jnp.max(jnp.abs(out - ref)) < 2e-5
        assert dispatch.snapshot()['flash_attention'] == 'pallas'
        # The grid shape must also be statically LEGAL on the Pallas
        # rung it took (the part interpreter mode cannot prove).
        mult = dispatch.LANES if has_seg else 8
        for bq, bk in dispatch.flash_blocks(sq, sk, d, q.dtype,
                                            has_seg).values():
            assert dispatch.block_dim_ok(bq, sq, mult)
            assert dispatch.block_dim_ok(bk, sk, mult)
            assert bq <= sq and bk <= sk

    def test_bench_r02_shape_lowers_via_flash_impl(self):
        """The headline regression: (4, 32, 8, 256) decode-shaped
        arrays crashed Pallas lowering in r2. Assert the flash path is
        actually TAKEN (not silently descended past)."""
        dispatch.reset_for_tests()
        jax.clear_caches()   # path records at TRACE time; force one
        q, k, v = _qkv(4, 32, 32, 8, 8, 256, seed=7)
        out = attention_ops.attention(q, k, v, impl='flash')
        assert out.shape == q.shape
        assert dispatch.snapshot().get('flash_attention') == 'pallas'

    def test_grad_through_clamped_blocks(self):
        q, k, v = _qkv(1, 24, 24, 2, 2, 64, seed=3)
        g = jax.grad(lambda q_: flash_lib.flash_attention(
            q_, k, v).sum())(q)
        gr = jax.grad(lambda q_: attention_ops.mha_reference(
            q_, k, v).sum())(q)
        assert jnp.max(jnp.abs(g - gr)) < 2e-4

    def test_segment_ids_batch_gt_one(self):
        """Packed sequences with batch > 1: the [b, 1, s] lane-axis
        segment layout must be legal AND numerically golden."""
        q, k, v = _qkv(2, 64, 64, 4, 4, 64, seed=5)
        seg = jnp.stack([jnp.repeat(jnp.arange(2), 32),
                         jnp.repeat(jnp.arange(4), 16)]).astype(
                             jnp.int32)
        out = flash_lib.flash_attention(q, k, v, segment_ids=seg)
        ref = attention_ops.mha_reference(q, k, v, segment_ids=seg)
        assert jnp.max(jnp.abs(out - ref)) < 2e-5


# ------------------------------------------------------- the tile plan
def _seg_ids(b, s):
    """Batch rows packed differently: 2 and 4 documents, uneven."""
    cuts = [(s // 2,), (s // 8, s // 2, s - s // 8)]
    return jnp.stack([jnp.searchsorted(jnp.array(cuts[i % 2]),
                                       jnp.arange(s), side='right')
                      for i in range(b)]).astype(jnp.int32)


# (id, b, sq, sk, hq, hkv, causal, segmented, window, request): every
# distinct plan the rule can give, and requests that cut it finer.
TILE_PLAN_CASES = [
    ('causal256-g1', 1, 256, 256, 2, 2, True, False, 0, None),
    ('causal512-g2', 1, 512, 512, 2, 1, True, False, 0, None),
    ('causal1024-g4', 1, 1024, 1024, 4, 1, True, False, 0, None),
    ('causal2048-g2', 1, 2048, 2048, 2, 1, True, False, 0, None),
    ('causal300-odd', 1, 300, 300, 2, 2, True, False, 0, None),
    ('causal320-dkv-full-q', 1, 320, 320, 2, 1, True, False, 0,
     (256, 256)),
    ('segments-b2', 2, 512, 512, 4, 2, True, True, 0, None),
    ('window300', 1, 1024, 1024, 2, 2, True, False, 300, None),
    ('window300-noncausal', 1, 512, 512, 2, 2, False, False, 300, None),
    ('noncausal', 1, 512, 512, 2, 1, False, False, 0, None),
    ('cross-lengths', 1, 128, 512, 2, 2, False, False, 0, None),
    ('request-128x256', 1, 512, 512, 2, 2, True, False, 0, (128, 256)),
    ('request-256x128-window', 1, 512, 512, 2, 1, True, False, 200,
     (256, 128)),
    ('request-128x128-segments', 2, 256, 256, 2, 2, True, True, 0,
     (128, 128)),
    ('window256-segments-g4', 2, 768, 768, 4, 1, True, True, 256, None),
    ('segments640-g4', 1, 640, 640, 4, 1, True, True, 0, None),
    ('window384-896', 1, 896, 896, 2, 1, True, False, 384, None),
]


# name, sq, sk, causal, window, segment ids, requested blocks, rows of
# tiles that visit nothing (forward and dq's walk, dk/dv's walk)
WALK_CASES = [
    ('causal-2k', 2048, 2048, True, 0, False, None, (0, 0)),
    ('causal-request', 1024, 1024, True, 0, False, (128, 256), (0, 0)),
    ('mellum2-window1024', 2048, 2048, True, 1024, False, None, (0, 0)),
    ('window-request', 2048, 2048, True, 300, False, (256, 128), (0, 0)),
    ('window-noncausal', 1024, 1024, False, 300, False, (128, 128), (0, 0)),
    ('causal-sq-under-sk', 512, 1024, True, 0, False, (128, 128), (0, 4)),
    ('causal-sq-over-sk', 1024, 512, True, 0, False, (128, 128), (0, 0)),
    ('window-sq-over-sk', 1024, 512, True, 200, False, (128, 128), (2, 0)),
    ('segments', 1024, 1024, True, 0, True, (128, 128), (0, 0)),
    ('segments-window', 768, 768, True, 256, True, None, (0, 0)),
    ('no-mask', 512, 1024, False, 0, False, (128, 256), (0, 0)),
]


class TestTilePlan:

    @pytest.mark.parametrize('case', TILE_PLAN_CASES,
                             ids=[c[0] for c in TILE_PLAN_CASES])
    def test_forward_and_gradients_match_reference(self, case):
        """Forward and dq, dk, dv against mha_reference for each plan:
        skipped tiles (and their clamped fetches), masked and plain
        bodies, the transposed dk/dv kernel and its row statistics."""
        _, b, sq, sk, hq, hkv, causal, segmented, window, want = case
        q, k, v = _qkv(b, sq, sk, hq, hkv, 64, seed=sq + hq)
        w = jax.random.normal(jax.random.PRNGKey(1), q.shape)
        seg = _seg_ids(b, sq) if segmented else None
        bq, bk = want or (None, None)

        def flash(q_, k_, v_):
            return flash_lib.flash_attention(
                q_, k_, v_, causal=causal, segment_ids=seg,
                window=window, block_q=bq, block_k=bk)

        def reference(q_, k_, v_):
            return attention_ops.mha_reference(
                q_, k_, v_, causal=causal, segment_ids=seg, window=window)

        dispatch.reset_for_tests()
        out, vjp = jax.vjp(flash, q, k, v)
        ref, ref_vjp = jax.vjp(reference, q, k, v)
        assert jnp.max(jnp.abs(out - ref)) < 2e-5
        for name, got, exp in zip(('dq', 'dk', 'dv'), vjp(w), ref_vjp(w)):
            assert jnp.max(jnp.abs(got - exp)) < 1e-4, name
        # What was recorded at trace time is what the mask gives: a
        # tile is visited iff some entry of it is allowed, and needs
        # mask work iff some entry of it is not.
        q_pos = jnp.arange(sq)[:, None]
        k_pos = jnp.arange(sk)[None, :]
        allowed = jnp.ones((sq, sk), bool)
        if causal:
            allowed &= q_pos >= k_pos
        if window > 0:
            allowed &= q_pos - k_pos < window
        # The backward is the dq and dk/dv kernels, always. A plan
        # for a window is filed under a name of its own.
        plans = dispatch.flash_plan_snapshot()
        prefix = 'window_' if window > 0 else ''
        assert sorted(plans) == sorted(
            prefix + kernel for kernel in dispatch.FLASH_KERNELS)
        rule = dispatch.flash_blocks(sq, sk, 64, q.dtype, segmented,
                                     window, want)
        for kernel, plan in plans.items():
            tq, tk = plan['block_q'], plan['block_k']
            assert (tq, tk) == rule[kernel[len(prefix):]]
            tiles = allowed.reshape(sq // tq, tq, sk // tk, tk)
            some = tiles.any(axis=(1, 3))
            every = tiles.all(axis=(1, 3))
            assert plan['visited'] == int(some.sum()), kernel
            assert plan['skipped'] == int((~some).sum()), kernel
            assert plan['masked'] == int(
                (some if segmented else some & ~every).sum()), kernel
            # the grid is the visited tiles, and one step for a row of
            # tiles that visits nothing (none in these cases)
            assert plan['steps'] == plan['visited'], kernel

    def test_training_shape_counts(self):
        """The numbers PERF.md quotes for sft-2k, per head."""
        assert flash_lib.tile_counts(2048, 2048, 256, 256, True, 0,
                                     False) == {
            'visited': 36, 'masked': 8, 'skipped': 28, 'steps': 36}
        assert flash_lib.tile_counts(2048, 2048, 512, 512, True, 0,
                                     False) == {
            'visited': 10, 'masked': 4, 'skipped': 6, 'steps': 10}

    @pytest.mark.parametrize('case', WALK_CASES, ids=[c[0] for c in
                                                      WALK_CASES])
    def test_the_grid_is_the_list_of_visited_tiles(self, case):
        """The list a call prefetches against a dense enumeration of
        its mask, for each kernel's walk: every tile with an allowed
        entry once, rows of tiles in turn and a row's tiles rising,
        first and last flagged once a row, masked where some entry is
        not allowed (with segment ids: everywhere); `steps` of the
        recorded plan is its length. Mellum2's shapes are
        test_hybrid_mellum2.py's, the cell's its traffic's."""
        _, sq, sk, causal, window, segmented, blocks, empty_rows = case
        q_pos, k_pos = np.arange(sq)[:, None], np.arange(sk)[None, :]
        dense = np.ones((sq, sk), bool)
        if causal:
            dense &= q_pos >= k_pos
        if window > 0:
            dense &= q_pos - k_pos < window
        dispatch.reset_for_tests()
        q = jax.ShapeDtypeStruct((1, sq, 2, 64), jnp.float32)
        k = jax.ShapeDtypeStruct((1, sk, 1, 64), jnp.float32)
        seg = jax.ShapeDtypeStruct((1, sq), jnp.int32) if segmented \
            else None
        bq, bk = blocks or (None, None)
        jax.eval_shape(jax.grad(lambda q_, k_, v_, seg_: (
            flash_lib.flash_attention(
                q_, k_, v_, causal=causal, window=window, segment_ids=seg_,
                block_q=bq, block_k=bk).sum()), (0, 1, 2)), q, k, k, seg)
        plans = dispatch.flash_plan_snapshot()
        assert len(plans) == 3
        for kernel, plan in plans.items():
            by_k = kernel.endswith('dkv')
            codes, _ = flash_lib._walk(
                sq, sk, plan['block_q'], plan['block_k'], causal, window,
                segmented, (), by_k)
            assert not codes.flags.writeable and codes.dtype == np.int32
            assert check_walk(
                codes, dense, plan['block_q'], plan['block_k'], by_k,
                segmented) == plan['steps'] - plan['visited'] == (
                    empty_rows[by_k])
            assert len(codes) == plan['steps']
            assert plan['visited'] + plan['skipped'] == (
                sq // plan['block_q']) * (sk // plan['block_k'])

    def test_a_row_of_tiles_that_visits_nothing_stores_zeros(self):
        """A window over Sq > Sk leaves the last q tiles no key (query
        p sees k in (p - window, p], and there is no key past Sk):
        their one step stores zeros and an `lse` of 0, their gradients
        are zero, nothing is NaN, and the rows that do see keys are
        the reference's."""
        q, k, v = _qkv(1, 512, 128, 2, 1, 64, seed=5)
        w = jax.random.normal(jax.random.PRNGKey(2), q.shape)

        def flash(q_, k_, v_):
            return flash_lib.flash_attention(
                q_, k_, v_, causal=True, window=64, block_q=128,
                block_k=128)

        dispatch.reset_for_tests()
        out, vjp = jax.vjp(flash, q, k, v)
        dq, dk, dv = vjp(w)
        plans = dispatch.flash_plan_snapshot()
        # q tiles 2 and 3 (queries 256..511) see no key under 128
        for kernel, visited, steps in (('fwd', 2, 4), ('dq', 2, 4),
                                       ('dkv', 2, 2)):
            assert (plans[f'window_{kernel}']['visited'],
                    plans[f'window_{kernel}']['steps']) == (visited, steps)
        assert not any(bool(jnp.isnan(x).any()) for x in (out, dq, dk, dv))
        # queries from 128 + 63 on see no key at all
        assert float(jnp.abs(out[:, 191:]).max()) == 0.0
        assert float(jnp.abs(dq[:, 191:]).max()) == 0.0
        seen = slice(0, 191)
        ref, ref_vjp = jax.vjp(lambda *a: attention_ops.mha_reference(
            *a, causal=True, window=64)[:, seen], q, k, v)
        assert jnp.max(jnp.abs(out[:, seen] - ref)) < 2e-5
        for name, got, exp in zip(('dq', 'dk', 'dv'), (dq, dk, dv),
                                  ref_vjp(w[:, seen])):
            assert jnp.max(jnp.abs(got - exp)) < 1e-4, name

    def test_the_list_is_held_to_what_smem_takes(self, monkeypatch):
        """Compiled, a list longer than SMEM takes is refused at trace
        time like a tile pair over the VMEM budget (a ValueError, which
        the ladder catches); S 65,536 causal at the rule's extents is
        far under it."""
        monkeypatch.setattr(dispatch, 'interpret_mode', lambda: False)
        dispatch.reset_for_tests()
        long = jax.ShapeDtypeStruct((1, 65536, 4, 128), jnp.bfloat16)
        tiles = flash_lib._plan(long, long, None, None, False, True, 0,
                                dispatch.FLASH_KERNELS)
        assert {n: len(t.visits) for n, t in tiles.items()} == {
            'fwd': 4160, 'dq': 2080, 'dkv': 8256}
        assert max(p['steps'] for p in
                   dispatch.flash_plan_snapshot().values()) == 8256 < \
            flash_lib.MAX_STEPS
        longer = jax.ShapeDtypeStruct((1, 131072, 4, 128), jnp.bfloat16)
        with pytest.raises(ValueError, match='longer than SMEM takes'):
            flash_lib._plan(longer, longer, 128, 128, False, True, 0,
                            ('fwd',))
        # and through the ladder's eyes: the same refusal, not a crash
        with pytest.raises(ValueError, match='longer than SMEM takes'):
            jax.eval_shape(lambda x: flash_lib.flash_attention(
                x, x, x, block_q=128, block_k=128), longer)

    def test_fwd_lse_shape_and_values(self):
        """Ring attention's entry: [B, Hq, Sq] float32 logsumexp of the
        scaled, masked scores, whatever layout the kernel writes."""
        q, k, v = _qkv(2, 512, 512, 4, 2, 64, seed=41)
        out, lse = flash_lib.flash_attention_fwd_lse(q, k, v, causal=True)
        assert out.shape == q.shape
        assert lse.shape == (2, 4, 512) and lse.dtype == jnp.float32
        logits = jnp.einsum('bqhd,bkhd->bhqk', q,
                            jnp.repeat(k, 2, axis=2)) * 64 ** -0.5
        mask = jnp.arange(512)[:, None] >= jnp.arange(512)[None, :]
        want = jax.nn.logsumexp(jnp.where(mask, logits, -jnp.inf), axis=-1)
        assert jnp.max(jnp.abs(lse - want)) < 2e-5
        ref = attention_ops.mha_reference(q, k, v, causal=True)
        assert jnp.max(jnp.abs(out - ref)) < 2e-5

    # name, causal, window, segment ids, block-diffusion block
    RESIDUAL_CASES = [('causal', True, 0, False, 0),
                      ('window', True, 96, False, 0),
                      ('block-diffusion', False, 0, False, 4),
                      ('segments', True, 0, True, 0)]

    @pytest.mark.parametrize('case', RESIDUAL_CASES,
                             ids=[c[0] for c in RESIDUAL_CASES])
    def test_the_backward_reads_the_compact_named_residuals(self, case):
        """The forward rule hands the backward the output and the
        logsumexp as one float32 a row ([b, hq, sq]: lane 0 of the
        kernel's block), both named for a remat policy to keep; from
        them dq (which reads the row and turns it into its column),
        dk and dv are the reference's."""
        _, causal, window, segmented, block = case
        q, k, v = _qkv(2, 256, 256, 4, 2, 64, seed=7)
        w = jax.random.normal(jax.random.PRNGKey(8), q.shape)
        seg = _seg_ids(2, 256) if segmented else None
        bd = (128, block) if block else ()

        def forward(q_, k_, v_):
            return flash_lib._fwd_rule(q_, k_, v_, seg, causal, None, None,
                                       window, bd, 64 ** -0.5)

        out, res = forward(q, k, v)
        assert res[4] is out
        assert res[5].shape == (2, 4, 256) and res[5].dtype == jnp.float32
        named = str(jax.make_jaxpr(forward)(q, k, v))
        assert f'name={flash_lib.OUT}' in named
        assert f'name={flash_lib.LSE}' in named
        if block:
            scores = jnp.einsum('bqhd,bkhd->bhqk', q,
                                jnp.repeat(k, 2, axis=2)) * 64 ** -0.5
            pos = jnp.arange(256)
            allowed = attention_ops.block_diffusion_allowed(
                pos[:, None], pos[None, :], 128, block)
            want = jax.nn.logsumexp(
                jnp.where(allowed, scores, -jnp.inf), axis=-1)
            assert jnp.max(jnp.abs(res[5] - want)) < 2e-5
        dq, dk, dv, _ = flash_lib._bwd_rule(causal, None, None, window, bd,
                                            64 ** -0.5, res, w)
        ref, ref_vjp = jax.vjp(lambda *a: attention_ops.mha_reference(
            *a, causal=causal, segment_ids=seg, window=window,
            block_diffusion=block), q, k, v)
        assert jnp.max(jnp.abs(out - ref)) < 2e-5
        for name, got, exp in zip(('dq', 'dk', 'dv'), (dq, dk, dv),
                                  ref_vjp(w)):
            assert jnp.max(jnp.abs(got - exp)) < 1e-4, name

    def test_plan_lands_on_span_and_snapshot(self, monkeypatch):
        from skypilot_tpu.utils import tracing
        monkeypatch.setenv('SKYT_TRACE', '1')
        monkeypatch.setenv('SKYT_TRACE_SAMPLE', '1')
        dispatch.reset_for_tests()
        q, k, v = _qkv(1, 512, 512, 1, 1, 64, seed=43)
        with tracing.Tracer('test').start_span('trace-flash') as span:
            flash_lib.flash_attention(q, k, v, block_q=128, block_k=256)
        assert span.attributes['ops.flash_plan.fwd'] == (
            'block_q=128 block_k=256 visited=6 masked=4 skipped=2 '
            'steps=6')
        assert dispatch.flash_plan_snapshot()['fwd']['visited'] == 6


# --------------------------------------------------- the fallback ladder
class TestLadder:

    def teardown_method(self):
        faults.reset()

    def test_chaos_fault_descends_to_xla(self):
        """SKYT_FAULTS=ops.lowering=error forces every Pallas rung to
        fail at trace time; the XLA floor must serve the exact
        reference output and the descent must be observable."""
        dispatch.reset_for_tests()
        faults.configure('ops.lowering=error')
        c = metrics_lib.REGISTRY.counter(
            'skyt_ops_kernel_path_total',
            'Kernel dispatch path selected at trace time',
            ('op', 'path'))
        before = c.value('flash_attention', 'xla')
        q, k, v = _qkv(1, 40, 40, 2, 2, 64, seed=11)  # fresh shape
        out = attention_ops.attention(q, k, v, impl='flash')
        ref = attention_ops.mha_reference(q, k, v)
        assert jnp.max(jnp.abs(out - ref)) < 1e-6
        assert dispatch.snapshot()['flash_attention'] == 'xla'
        assert c.value('flash_attention', 'xla') == before + 1

    def test_where_filter_targets_one_rung(self):
        """where=path:pallas kills the Pallas rung by name; at 2,048
        (where the rule's tiles are smaller than the sequence) there is
        no second Pallas plan to try, so the XLA floor serves it."""
        dispatch.reset_for_tests()
        faults.configure('ops.lowering=error,where=path:pallas')
        q, k, v = _qkv(1, 2048, 2048, 1, 1, 64, seed=13)
        out = attention_ops.attention(q, k, v, impl='flash')
        ref = attention_ops.mha_reference(q, k, v)
        assert jnp.max(jnp.abs(out - ref)) < 1e-6
        assert dispatch.snapshot()['flash_attention'] == 'xla'

    def test_final_rung_never_fault_injected(self):
        """The XLA floor is the correctness guarantee: an armed
        ops.lowering fault must not be able to kill it."""
        faults.configure('ops.lowering=error')
        out = dispatch.run_ladder('t_final', [('xla', lambda: 42)])
        assert out == 42

    def test_one_flash_plan_and_no_setting_beside_it(self, monkeypatch):
        """The flash ladder is the rule's Pallas plan and the XLA floor,
        and no setting chooses a kernel, a tile or a budget."""
        seen = []
        real = dispatch.run_ladder

        def spy(op, rungs):
            seen.append((op, [name for name, _ in rungs]))
            return real(op, rungs)

        monkeypatch.setattr(dispatch, 'run_ladder', spy)
        q, k, v = _qkv(1, 1152, 1152, 1, 1, 64, seed=17)  # fresh shape
        attention_ops.attention(q, k, v, impl='flash')
        assert seen == [('flash_attention', ['pallas', 'xla'])]
        gone = ('SKYT_AUTOTUNE', 'SKYT_AUTOTUNE_CACHE',
                'SKYT_AUTOTUNE_REPEATS', 'SKYT_FLASH_BWD',
                'SKYT_OPS_FORCE_PATH', 'SKYT_OPS_VMEM_BUDGET')
        assert not set(gone) & set(env.registry())


# ------------------------------------- chaos: ops.lowering mid-serve
@pytest.mark.integration
def test_mid_serve_lowering_chaos_zero_5xx():
    """Acceptance drill: a serve burst with SKYT_FAULTS=
    ops.lowering=error armed — every Pallas rung refuses to lower, the
    engine compiles onto the XLA floor, and ALL requests complete with
    output identical to an unfaulted replica's. Zero client-visible
    5xx, skyt_ops_kernel_path_total{path="xla"} > 0."""
    import socket

    def free_port():
        with socket.socket() as s:
            s.bind(('127.0.0.1', 0))
            return s.getsockname()[1]

    ports = [free_port(), free_port()]
    envs = [{'SKYT_FAULTS': 'ops.lowering=error'}, {}]
    procs = []
    for port, extra in zip(ports, envs):
        env = dict(os.environ, JAX_PLATFORMS='cpu', **extra)
        procs.append(subprocess.Popen(
            [sys.executable, '-m', 'skypilot_tpu.infer.server',
             '--model', 'debug', '--port', str(port),
             '--num-slots', '2', '--max-seq-len', '64'],
            env=env, stdout=subprocess.DEVNULL,
            stderr=subprocess.DEVNULL))
    urls = [f'http://127.0.0.1:{p}' for p in ports]
    try:
        for proc, url in zip(procs, urls):
            deadline = time.time() + 240
            while time.time() < deadline:
                if proc.poll() is not None:
                    raise AssertionError(
                        f'replica died rc={proc.returncode}')
                try:
                    if requests.get(url + '/health',
                                    timeout=2).status_code == 200:
                        break
                except requests.RequestException:
                    pass
                time.sleep(0.5)
            else:
                raise AssertionError('replica never became healthy')

        # Burst at the FAULTED replica (concurrent, mid-stream).
        results = [None] * 8
        def one(i):
            r = requests.post(
                urls[0] + '/generate',
                json={'tokens': [i % 4 + 1, 5, 9], 'max_tokens': 6},
                timeout=120)
            results[i] = (r.status_code, r.json().get('tokens'))
        threads = [threading.Thread(target=one, args=(i,))
                   for i in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        assert all(r is not None for r in results), results
        bad = [r for r in results if r[0] != 200]
        assert not bad, f'client-visible failures: {bad}'

        # Correctness through the degraded path: the unfaulted replica
        # (same deterministic debug init) must emit identical tokens.
        for i in (0, 1, 2, 3):
            want = requests.post(
                urls[1] + '/generate',
                json={'tokens': [i % 4 + 1, 5, 9], 'max_tokens': 6},
                timeout=120).json()['tokens']
            assert results[i][1] == want, (i, results[i][1], want)

        # The descent is observable: faulted replica compiled onto the
        # XLA rung; the clean one is on Pallas.
        text = requests.get(urls[0] + '/metrics', timeout=5).text
        xla = [l for l in text.splitlines()
               if l.startswith('skyt_ops_kernel_path_total')
               and 'path="xla"' in l]
        assert xla and any(float(l.rsplit(' ', 1)[1]) > 0
                           for l in xla), text[:2000]
        assert 'skyt_faults_fired_total{' in text
        stats = requests.get(urls[0] + '/stats', timeout=5).json()
        assert 'xla' in stats['kernel_paths'].values()
        clean = requests.get(urls[1] + '/metrics', timeout=5).text
        assert any(
            l.startswith('skyt_ops_kernel_path_total')
            and 'path="pallas' in l and float(l.rsplit(' ', 1)[1]) > 0
            for l in clean.splitlines())
    finally:
        for proc in procs:
            if proc.poll() is None:
                proc.kill()
