"""The `kda_moe_train` kind of cell: its count, its readers, its demands,
its configuration against the program's preset and the catalog, what
the check reads under each fault, and its CPU rehearsal. Beside
test_swa_moe_train.py; same rules."""
import importlib
import json
import os
import subprocess
import sys

import pytest

import common
import kda_moe_flops
import kda_moe_train_cell
from test_moe_train import _trace_file

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
# The published config.json of the model, as one row: its source and its
# numbers.
CATALOG = os.path.join(BENCH, 'tests',
                       'kimi-linear-48b-a3b-instruct.published.jsonl')
CELL = 'sft-kda-mla-16k'


def _cfg():
    return common.load_json(os.path.join(
        BENCH, 'configs', 'kimi-linear-48b-a3b-ep32-sft.json'))


def _sizes():
    cfg = _cfg()
    return {k: cfg[k] for k in kda_moe_train_cell.SIZE_KEYS}


# ---------------------------------------------------------------- the count
def test_the_count_is_the_presets_and_the_issues_arithmetic():
    from skypilot_tpu.models import hybrid
    m = _sizes()
    preset = hybrid.CONFIGS['kimi-linear-48b-a3b-ep32']
    assert kda_moe_flops.held_params(m) == preset.num_params() == \
        602450816 == _cfg()['params_held']
    # each held expert sees 512 rows a layer on an even router: 4 layers
    # x 8 held x 512
    by = kda_moe_flops.train_flops_per_step(m, 1, 16384, 4 * 8 * 512, 64)
    forward = by['total'] / 3 / 16384
    assert 850e6 < forward < 865e6          # ~862 MFLOP a token
    share = {k: v / by['total'] for k, v in by.items()}
    assert 0.36 < share['kda_projections'] < 0.38
    assert 0.19 < share['mla_scores'] < 0.20
    assert 0.14 < share['dense'] < 0.16 and 0.10 < share['vocabulary'] < 0.12
    assert 0.02 < share['kda_scan'] < 0.03
    # the form is bytes-bound at the cell's shape in chunks of 64: 20.4 GB,
    # 24.9 ms
    assert kda_moe_flops.scan_bytes(m, 1, 16384, 64) / 819e9 > \
        kda_moe_flops.scan_flops(m, 1, 16384, 64) / 197e12


def test_every_number_of_the_catalogs_config_is_in_the_file_or_reduced():
    with open(CATALOG, encoding='utf-8') as f:
        row = next(r for r in map(json.loads, f)
                   if r['name'] == 'Kimi-Linear-48B-A3B-Instruct')
    cfg = _cfg()
    assert cfg['source'] == row['source_url']
    for key, value in row['config'].items():
        if key in cfg['reduced']:
            published, here = (cfg['reduced'][key][k]
                               for k in ('published', 'here'))
            if isinstance(value, dict):
                # a group: the keys that changed, and every other as it is
                assert {k: value[k] for k in published} == published
                assert {k: cfg[key][k] for k in here} == here
                assert {k: v for k, v in value.items()
                        if k not in published} == \
                    {k: v for k, v in cfg[key].items() if k not in here}
            else:
                assert (published, here) == (value, cfg[key]), key
        else:
            assert cfg[key] == value, key
    # no width is reduced
    assert not [k for k in cfg['reduced']
                if k.endswith(('_dim', '_rank', '_size')) and
                k != 'vocab_size']
    bench = common.load_json(os.path.join(ROOT, 'BENCHMARK.json'))
    entry = next(c for c in bench['configs'] if c['name'] == cfg['name'])
    assert sorted(entry['reduced']) == sorted(cfg['reduced'])


# -------------------------------------------------------------- the demands
def test_the_kda_plan_is_held_to_the_sizes():
    obs = {'sizes': _sizes(), 'mix': {'seq': 16384},
           'kda_plan': {'layers': '4', 'chunk': '64', 'chunks': '256',
                        'heads': '32', 'dk': '128', 'dv': '128',
                        'state_bytes': '536870912'}}
    assert kda_moe_train_cell._kda_problems(obs) == []
    # the chunk is the program's to choose, so long as the chunks cover
    # the row
    obs['kda_plan'].update(chunk='32', chunks='512')
    assert kda_moe_train_cell._kda_problems(obs) == []
    obs['kda_plan']['chunks'] = '256'
    assert kda_moe_train_cell._kda_problems(obs)
    obs['kda_plan'].update(chunks='512', heads='8')
    assert kda_moe_train_cell._kda_problems(obs)
    obs['kda_plan'] = {}
    assert kda_moe_train_cell._kda_problems(obs)


def test_the_readers_take_their_metrics_from_what_was_observed(tmp_path):
    step = 'jit(step_fn)/jvp(HybridModel)/'
    back = 'jit(step_fn)/transpose(jvp(HybridModel))/jvp(HybridModel)/' \
        'checkpoint/'
    call = ' = bf16[1,16384,32,128] custom-call(), ' \
        'custom_call_target="tpu_custom_call"'
    ops = [('%_attention.13' + call,
            step + 'layer_3/mla/flash_full/jit(_attention)/pallas_call'),
           ('%fusion.17 = f32[1,8,256,64,128] fusion()',
            step + 'layer_1/kda/while/body/checkpoint/kda_scan/dot_general:'),
           ('%fusion.18 = f32[1,16384,8,128] fusion()',
            back + 'layer_1/kda/while/body/checkpoint/kda_gates/mul:'),
           ('%fusion.370 = bf16[1,16384,4096] fusion()',
            step + 'layer_0/kda/q_proj/dot_general:'),
           ('%fusion.371 = bf16[1,16384,6144] fusion()',
            back + 'layer_3/mla/kv_b_proj/dot_general:')]
    _trace_file(str(tmp_path), ops)
    seconds = [0.2, 0.8, 0.24, 0.4, 0.12]
    obs = {'boundaries': [10.0, 11.0, 12.0, 14.0], 'tokens_per_step': 16384,
           'mix': {'seq': 16384}, 'rows': 1, 'chips': 1, 'sizes': _sizes(),
           'peak': {'bf16_flops_per_s': 197e12, 'hbm_bytes_per_s': 819e9},
           'profile_dir': str(tmp_path),
           'moe_steps': [{'held': h} for h in (16000, 16384, 17000)],
           'kda_plan': {'chunk': '64'},
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
    assert read('kernel.kda_scan_ms_per_step.train') == pytest.approx(200.0)
    assert read('kernel.kda_gates_ms_per_step.train') == pytest.approx(60.0)
    assert read('kernel.mixer_proj_ms_per_step.train') == \
        pytest.approx(130.0)
    assert read('kernel.flash_ms_per_step.train') == pytest.approx(50.0)
    bound = kda_moe_flops.scan_bytes(_sizes(), 1, 16384, 64) / 819e9
    assert read('kernel.kda_scan_roofline.train') == pytest.approx(
        100 * bound / 0.2)
    by = kda_moe_flops.train_flops_per_step(_sizes(), 1, 16384, 16384, 64)
    assert read('model.mfu.train.kda_moe') == pytest.approx(
        100 * by['total'] / 1.0 / 197e12)
    # the chunk is the one the program reported
    obs['kda_plan'] = {'chunk': '32'}
    assert read('model.mfu.train.kda_moe') == pytest.approx(100 * (
        by['total'] - by['kda_scan'] + kda_moe_flops.scan_flops(
            _sizes(), 1, 16384, 32)) / 1.0 / 197e12)
    obs['kda_plan'] = {}
    assert read('kernel.kda_scan_roofline.train') is None
    assert read('model.mfu.train.kda_moe') is None
    obs['kda_plan'] = {'chunk': '64'}
    # the attention projections' metric reads nothing in this cell
    entry = next(m for m in bench['per_layer']
                 if m['name'] == 'kernel.attn_proj_ms_per_step.train')
    assert CELL not in entry['workloads']
    # a program without the scope, or an untraced run
    obs['_op_scopes'] = {}
    assert read('kernel.kda_scan_ms_per_step.train') is None
    assert read('kernel.kda_scan_roofline.train') is None
    obs['trace'] = None
    assert read('model.mfu.train.kda_moe') is not None
    del obs['moe_steps']
    assert read('model.mfu.train.kda_moe') is None


# ------------------------------------------------------------- the faults
@pytest.mark.parametrize('fault,caught', [
    ({}, False),
    ({'program_weight_bits': [4, 3]}, True),        # float8_e4m3 weights
    ({'faults': [{'fault': 'per_head_decay'}]}, True),
    ({'faults': [{'fault': 'no_erase'}]}, True),
    ({'faults': [{'fault': 'no_l2norm'}]}, True),
    ({'faults': [{'fault': 'centered_conv'}]}, True),
    ({'faults': [{'fault': 'mla_rotary'}]}, True),
    ({'program_update': 'none'}, True)],             # the state unchanged
    ids=['sound', 'float8_weights', 'per_head_decay', 'no_erase',
         'no_l2norm', 'centered_conv', 'mla_rotary', 'unchanged_state'])
def test_what_a_fault_reads_in_the_check_is_not_correct(fault, caught):
    """The check child at the rehearsal's sizes on the CPU, sound and
    under the faults whose chip readings the configuration's
    `check.why` gives; a fault's comparison is its own line. A state
    the step leaves unchanged reads 1 by the step's limits."""
    import moe_train_cell
    import run
    import train_cell
    cfg = _cfg()
    cfg = run._merge(cfg, cfg['rehearsal'])
    spec = {'preset': 'debug-kimi-linear', 'reference': cfg['reference'],
            'sizes': {k: cfg[k] for k in kda_moe_train_cell.SIZE_KEYS},
            'seed': 3000000019, 'rows': 2, 'seq': 64, 'bias_scale': 0.1,
            'lr': 1e-5, 'mesh': 'fsdp=1', 'steps': 1000000, **fault}
    res = subprocess.run(
        [sys.executable, os.path.join(BENCH, 'children',
                                      'kda_moe_check_child.py'),
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
               device={}, state_bytes=0,
               moe_steps=[{'held': 2048, 'pairs': 2048, 'fullest': 1.2,
                           'dropped': 0}])
    check.setdefault('kernel_paths', {'moe_experts': 'ragged_dot'})
    check.setdefault('param_dtypes', ['float32'])
    check.setdefault('opt_state_dtypes', ['float32'])
    bad = train_cell._problems(obs, cfg, False) + \
        moe_train_cell._routing_problems(obs, cfg) + \
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
