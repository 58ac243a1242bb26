"""Serving memory plans: the 70B-on-v5e recipes are pinned here.

These tests are the feasibility proof for examples/llama_70b_serve.yaml:
the plan reproduces the engine's real placement
arithmetic, so a passing assertion means the engine's arrays fit.
"""
import dataclasses

import pytest

from skypilot_tpu.infer import memory_plan
from skypilot_tpu.models import llama


def _cfg70b():
    return dataclasses.replace(llama.CONFIGS['llama3-70b'],
                               dtype='bfloat16', param_dtype='bfloat16')


def test_70b_int8_tp8_fits_v5e8():
    """The recipe: 70B int8 over a v5e-8 (2 hosts x 4 chips, tp=8).
    KV shards 8-ways (8 kv heads), params ~8.5 GiB/chip."""
    plan = memory_plan.plan_serving(_cfg70b(), tp=8, num_slots=8,
                                    max_seq_len=4096, quantize='int8')
    assert plan.kv_sharded
    assert plan.fits, plan.summary()
    assert plan.headroom_gib > 2.0, plan.summary()


def test_70b_int8_tp16_replicated_kv_does_not_fit():
    """tp=16 does NOT divide the 8 kv heads -> the engine replicates
    the pool on every chip and the plan correctly rejects it: more
    chips is not automatically more capacity. This is why the recipe
    says tp=8."""
    plan = memory_plan.plan_serving(_cfg70b(), tp=16, num_slots=8,
                                    max_seq_len=4096, quantize='int8')
    assert not plan.kv_sharded
    assert not plan.fits, plan.summary()


def test_70b_bf16_needs_more_than_v5e8():
    """bf16 70B (~141 GiB of weights) cannot fit 8 x 16 GiB — int8 is
    load-bearing for the recipe, not an optimization."""
    plan = memory_plan.plan_serving(_cfg70b(), tp=8, num_slots=8,
                                    max_seq_len=4096, quantize='none')
    assert not plan.fits, plan.summary()


def test_8b_int8_fits_one_chip():
    """Cross-check against the measured config: 8B int8 on a single
    v5e chip (examples/llama_8b_int8_serve.yaml runs this today)."""
    cfg = dataclasses.replace(llama.CONFIGS['llama3-8b'],
                              dtype='bfloat16', param_dtype='bfloat16')
    plan = memory_plan.plan_serving(cfg, tp=1, num_slots=8,
                                    max_seq_len=2048, quantize='int8')
    assert plan.fits, plan.summary()


def test_pool_tokens_shrinks_kv():
    cfg = _cfg70b()
    full = memory_plan.plan_serving(cfg, tp=8, quantize='int8')
    half = memory_plan.plan_serving(cfg, tp=8, quantize='int8',
                                    pool_tokens=8 * 4096 // 2)
    assert half.kv_pool_bytes < full.kv_pool_bytes


def test_unknown_quant_rejected():
    with pytest.raises(ValueError, match='quantize'):
        memory_plan.plan_serving(_cfg70b(), tp=8, quantize='int4')


def test_stream_load_budget_reads_checkpoint_bytes():
    """int8 serving still reads the full bf16 checkpoint (quantize
    happens on host mid-stream): ~141 GiB -> ~141 s/host at 1 GB/s."""
    s = memory_plan.stream_load_budget_s(_cfg70b(), read_gbps=1.0)
    assert 130 < s < 160, s
