"""The `mla_moe_train` kind of cell: its count, its readers, its demands,
its configuration against the program's preset and the catalog, what
the check reads under each fault, and its CPU rehearsal. Beside
test_kda_moe_train.py; same rules."""
import importlib
import json
import os
import subprocess
import sys

import pytest

import common
import mla_moe_flops
import mla_moe_train_cell
from test_moe_train import _trace_file

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
# The published config.json of the model, as one row: its source and its
# numbers.
CATALOG = os.path.join(BENCH, 'tests', 'deepseek-v2-lite.published.jsonl')
CELL = 'sft-mla-moe-16k'


def _cfg():
    return common.load_json(os.path.join(
        BENCH, 'configs', 'deepseek-v2-lite-ep8-sft.json'))


def _sizes():
    cfg = _cfg()
    return {k: cfg[k] for k in mla_moe_train_cell.SIZE_KEYS}


# ---------------------------------------------------------------- the count
def test_the_count_is_the_presets_and_the_issues_arithmetic():
    from skypilot_tpu.models import hybrid
    m = _sizes()
    preset = hybrid.CONFIGS['deepseek-v2-lite-ep8']
    assert mla_moe_flops.held_params(m) == preset.num_params() == \
        635466752 == _cfg()['params_held']
    # an even router sends each held expert 1,536 rows a layer: 5 layers
    # x 8 held x 16,384 x 6 / 64
    by = mla_moe_flops.train_flops_per_step(m, 1, 16384, 5 * 8 * 1536)
    assert 53.7e12 < by['total'] < 53.9e12
    share = {k: v / by['total'] for k, v in by.items()}
    assert 0.45 < share['mla_scores'] < 0.47
    assert 0.14 < share['mla_projections'] < 0.16
    assert 0.15 < share['shared_experts'] < 0.17
    assert 0.11 < share['dense'] < 0.13
    assert 0.05 < share['experts'] < 0.07
    assert 0.04 < share['vocabulary'] < 0.06
    # the latent flash is compute-bound: 24.7 TFLOP, 125.6 ms at peak
    flops = mla_moe_flops.latent_flash_flops(m, 1, 16384)
    assert flops == by['mla_scores']
    assert mla_moe_flops.latent_flash_bytes(m, 1, 16384) / 819e9 < \
        flops / 197e12 / 10


def test_every_number_of_the_catalogs_config_is_in_the_file_or_reduced():
    with open(CATALOG, encoding='utf-8') as f:
        row = next(r for r in map(json.loads, f)
                   if r['name'] == 'DeepSeek-V2-Lite')
    cfg = _cfg()
    assert cfg['source'] == row['source_url']
    for key, value in row['config'].items():
        if key in cfg['reduced']:
            assert (cfg['reduced'][key]['published'],
                    cfg['reduced'][key]['here']) == (value, cfg[key]), key
        else:
            assert cfg[key] == value, key
    assert sorted(cfg['reduced']) == ['n_routed_experts',
                                      'num_hidden_layers', 'vocab_size']
    assert 'aux_loss_alpha' in cfg['assumed'] and \
        cfg['aux_loss_alpha'] == 0.001
    bench = common.load_json(os.path.join(ROOT, 'BENCHMARK.json'))
    entry = next(c for c in bench['configs'] if c['name'] == cfg['name'])
    assert sorted(entry['reduced']) == sorted(cfg['reduced'])


# -------------------------------------------------------------- the demands
def test_the_latent_attention_plan_is_held_to_the_sizes():
    line = ('latent attention plan: layers=6 heads=16 qk=128+64 v=128 '
            'kv_rank=512 rope=yarn(40,4096,10-23) softmax_scale=0.1147214')
    plan = dict(kv.split('=') for kv in line.split(': ')[1].split())
    obs = {'sizes': _sizes(), 'mla_plan': plan}
    assert mla_moe_train_cell._mla_problems(obs) == []
    want = mla_moe_train_cell.expected_plan(_sizes())
    assert want['softmax_scale'] == pytest.approx(0.1147214, abs=5e-8)
    # the program's own line for the preset is the same
    import jax
    import jax.numpy as jnp
    from skypilot_tpu.models import hybrid
    from skypilot_tpu.ops import dispatch
    dispatch.reset_for_tests()
    jax.eval_shape(hybrid.HybridModel(hybrid.CONFIGS[
        'deepseek-v2-lite-ep8']).init, jax.random.PRNGKey(0),
        jnp.zeros((1, 16), jnp.int32))
    said = {k: str(v) for k, v in dispatch.mla_plan_snapshot().items()}
    assert said == plan
    # a scale without mscale^2, no rotation, another ramp, fewer layers
    for key, value in (('softmax_scale', '0.0721688'), ('rope', 'none'),
                       ('rope', 'yarn(40,4096,9-23)'), ('layers', '5')):
        obs['mla_plan'] = dict(plan, **{key: value})
        assert mla_moe_train_cell._mla_problems(obs), key
    obs['mla_plan'] = {}
    assert mla_moe_train_cell._mla_problems(obs)


def test_every_step_line_carries_a_finite_balance_loss():
    cfg = _cfg()
    steps = [{'held': 1, 'pairs': 1, 'fullest': 1.0, 'dropped': 0}] * 2
    obs = {'moe_steps': steps, 'aux_steps': [0.00511, 0.00509],
           'check': {'aux_program': 0.005, 'aux_reference': 0.0050000005}}
    assert mla_moe_train_cell._aux_problems(obs, cfg) == []
    for aux in ([0.005], [0.005, float('nan')], []):
        obs['aux_steps'] = aux
        assert mla_moe_train_cell._aux_problems(obs, cfg)
    obs['aux_steps'] = [0.005, 0.005]
    obs['check'] = {'aux_program': 0.0, 'aux_reference': 0.005}
    assert mla_moe_train_cell._aux_problems(obs, cfg)
    line = ('step 7/1000000 loss=9.4567 tokens/s=17000 moe_pairs=49000/'
            '491520 moe_fullest_over_mean=1.100 moe_dropped=0 '
            'moe_rows=49152/81920 moe_aux=0.005123 grad_norm=1.0')
    assert mla_moe_train_cell.AUX_RE.findall(line) == ['0.005123']


def test_the_readers_take_their_metrics_from_what_was_observed(tmp_path):
    step = 'jit(step_fn)/jvp(HybridModel)/'
    back = 'jit(step_fn)/transpose(jvp(HybridModel))/jvp(HybridModel)/' \
        'checkpoint/'
    call = ' = bf16[1,16384,16,128] custom-call(), ' \
        'custom_call_target="tpu_custom_call"'
    ops = [('%_attention.13' + call,
            step + 'layer_3/mla/flash_full/jit(_attention)/pallas_call'),
           ('%fusion.17 = bf16[1,16384,16,64] fusion()',
            step + 'layer_1/mla/mla_rope/mul:'),
           ('%fusion.18 = bf16[1,16384,16,64] fusion()',
            back + 'layer_2/mla/mla_rope/mul:'),
           ('%fusion.370 = bf16[1,16384,3072] fusion()',
            step + 'layer_0/mla/q_proj/dot_general:'),
           ('%fusion.371 = bf16[1,16384,4096] fusion()',
            back + 'layer_3/mla/kv_b_proj/dot_general:')]
    _trace_file(str(tmp_path), ops)
    seconds = [1.6, 0.08, 0.04, 0.4, 0.12]
    obs = {'boundaries': [10.0, 11.0, 12.0, 14.0], 'tokens_per_step': 16384,
           'mix': {'seq': 16384}, 'rows': 1, 'chips': 1, 'sizes': _sizes(),
           'peak': {'bf16_flops_per_s': 197e12, 'hbm_bytes_per_s': 819e9},
           'profile_dir': str(tmp_path),
           'moe_steps': [{'held': h} for h in (60000, 61440, 63000)],
           'trace': {'chips': 1, 'steps': 4, 'ops_s': [
               [n, s] for (n, _), s in zip(ops, seconds)]}}
    bench = common.load_json(os.path.join(ROOT, 'BENCHMARK.json'))

    def read(metric):
        entry = next(m for m in bench['per_layer'] if m['name'] == metric)
        assert CELL in entry['workloads']
        assert entry['moves'] == 'train_tokens_per_s'
        spec = common.load_json(os.path.join(BENCH, 'metrics',
                                             metric + '.json'))
        assert set(spec) == {'reader', 'params'}
        return importlib.import_module('readers.' + spec['reader']).read(
            obs, spec['params'])
    assert read('kernel.mla_rope_ms_per_step.train') == pytest.approx(30.0)
    assert read('kernel.mixer_proj_ms_per_step.train') == \
        pytest.approx(130.0)
    assert read('kernel.flash_ms_per_step.train') == pytest.approx(400.0)
    bound = mla_moe_flops.latent_flash_flops(_sizes(), 1, 16384) / 197e12
    assert read('kernel.flash_latent_roofline.train') == pytest.approx(
        100 * bound / 0.4)
    by = mla_moe_flops.train_flops_per_step(_sizes(), 1, 16384, 61440)
    assert read('model.mfu.train.mla_moe') == pytest.approx(
        100 * by['total'] / 1.0 / 197e12)
    # the metrics of layers this cell does not have read nothing here
    for name in ('kernel.attn_proj_ms_per_step.train',
                 'kernel.kda_scan_ms_per_step.train',
                 'kernel.flash_window_roofline.train'):
        entry = next(m for m in bench['per_layer'] if m['name'] == name)
        assert CELL not in entry['workloads']
    # a program without the scope, or an untraced run
    obs['_op_scopes'] = {}
    assert read('kernel.mla_rope_ms_per_step.train') is None
    assert read('kernel.flash_latent_roofline.train') is None
    obs['trace'] = None
    assert read('model.mfu.train.mla_moe') is not None
    del obs['moe_steps']
    assert read('model.mfu.train.mla_moe') is None


# ------------------------------------------------------------- the faults
@pytest.mark.parametrize('fault,caught', [
    ({}, False),
    ({'program_weight_bits': [4, 3]}, True),        # float8_e4m3 weights
    ({'faults': [{'fault': 'no_rotation'}]}, True),
    ({'faults': [{'fault': 'half_split'}]}, True),
    ({'faults': [{'fault': 'plain_scale'}]}, True),
    ({'faults': [{'fault': 'renormalized'}]}, True),
    ({'faults': [{'fault': 'no_balance_loss'}]}, True),
    ({'program_update': 'none'}, True)],             # the state unchanged
    ids=['sound', 'float8_weights', 'no_rotation', 'half_split',
         'plain_scale', 'renormalized', 'no_balance_loss',
         'unchanged_state'])
def test_what_a_fault_reads_in_the_check_is_not_correct(fault, caught):
    """The check child at the rehearsal's sizes on the CPU, sound and
    under the faults whose chip readings the configuration's
    `check.why` gives; a fault's comparison is its own line. A state
    the step leaves unchanged reads 1 by the step's limits."""
    import kda_moe_train_cell
    import moe_train_cell
    import run
    import train_cell
    cfg = _cfg()
    cfg = run._merge(cfg, cfg['rehearsal'])
    spec = {'preset': 'debug-deepseek-v2', 'reference': cfg['reference'],
            'sizes': {k: cfg[k] for k in mla_moe_train_cell.SIZE_KEYS},
            'seed': 3000000019, 'rows': 2, 'seq': 64, 'lr': 1e-5,
            'mesh': 'fsdp=1', 'steps': 1000000, **fault}
    res = subprocess.run(
        [sys.executable, os.path.join(BENCH, 'children',
                                      'mla_moe_check_child.py'),
         json.dumps(spec)], cwd=ROOT, capture_output=True, text=True,
        timeout=300, env=common.child_env('cpu', {}))
    assert res.returncode == 0, res.stderr[-2000:]
    lines = [line.split(': ', 1) for line in res.stdout.splitlines()
             if line.startswith('chipbench-')]
    check = json.loads(lines[-1][1])
    assert lines[0][0] == 'chipbench-check'
    assert len(lines) == 1 + len(fault.get('faults', []))
    obs = dict(check=check, losses=[6.0, 6.0], exit_code=75,
               traceback=False, ln_vocab=5.545, compiles_in_window=0,
               kernel_paths={}, pallas=None, flash_backward=None,
               device={}, state_bytes=0, aux_steps=[0.002],
               moe_steps=[{'held': 1024, 'pairs': 1024, 'fullest': 1.2,
                           'dropped': 0}])
    check.setdefault('kernel_paths', {'moe_experts': 'ragged_dot'})
    check.setdefault('param_dtypes', ['float32'])
    check.setdefault('opt_state_dtypes', ['float32'])
    bad = train_cell._problems(obs, cfg, False) + \
        moe_train_cell._routing_problems(obs, cfg) + \
        mla_moe_train_cell._aux_problems(obs, cfg) + \
        kda_moe_train_cell._step_problems(obs, cfg)
    assert bool(bad) == caught, bad
    if fault.get('program_update') == 'none':
        assert check['update_rel_err'] == 1.0 and \
            check['update_rel_err_worst_leaf'] == 1.0
        assert kda_moe_train_cell._step_problems(obs, cfg) == bad


# ---------------------------------------------------------- the rehearsal
@pytest.mark.parametrize('trace', [0, 1])
def test_the_cpu_rehearsal_of_the_cell_ends_in_a_line_that_parses(trace):
    res = subprocess.run(
        [sys.executable, os.path.join(BENCH, 'run.py'), '--workload',
         CELL, '--seed', '3000000019', '--seconds', '3', '--trace',
         str(trace), '--rehearse-cpu'], cwd=ROOT, capture_output=True,
        text=True, timeout=300)
    assert res.returncode == 0, res.stderr[-2000:] + res.stdout[-2000:]
    line = json.loads(res.stdout.strip().splitlines()[-1])
    assert line['correct'] is True and line['failed'] == 0
    assert line['attempted'] > 0 and line['metrics']
