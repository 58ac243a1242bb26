"""The `swa_moe_train` kind of cell: its count, its readers, its demands,
its configuration against the program's preset and the catalog, and its
CPU rehearsal. Beside test_moe_train.py; same rules."""
import importlib
import json
import os
import subprocess
import sys

import pytest

import common
import swa_moe_flops
import swa_moe_train_cell
from test_moe_train import _trace_file

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
CATALOG = '/opt/skills/guides/model-configs/architectures.jsonl'
CELL = 'sft-swa-moe-16k'


def _cfg():
    return common.load_json(os.path.join(
        BENCH, 'configs', 'mellum2-12b-a2.5b-ep4-sft.json'))


def _sizes():
    cfg = _cfg()
    return {k: cfg[k] for k in swa_moe_train_cell.SIZE_KEYS}


# ---------------------------------------------------------------- the count
def test_the_count_is_the_presets_and_the_issues_arithmetic():
    from skypilot_tpu.models import hybrid
    m = _sizes()
    preset = hybrid.CONFIGS['mellum2-12b-a2.5b-ep4']
    assert swa_moe_flops.held_params(m) == preset.num_params() == 595154176
    # each held expert sees 2,048 rows a layer on average: 4 x 16 x 2,048
    by = swa_moe_flops.train_flops_per_step(m, 1, 16384, 4 * 16 * 2048)
    assert by['total'] == sum(v for k, v in by.items() if k != 'total')
    assert by['total'] == pytest.approx(27.8e12, rel=5e-3)
    share = {k: v / by['total'] for k, v in by.items()}
    assert share['attention_projections'] == pytest.approx(0.30, abs=0.005)
    assert share['vocabulary'] == pytest.approx(0.20, abs=0.005)
    assert share['experts'] == pytest.approx(0.175, abs=0.005)
    assert share['full_scores'] == pytest.approx(0.24, abs=0.005)
    assert share['window_scores'] == pytest.approx(0.09, abs=0.005)
    # the band: W (W + 1) / 2 pairs in the first W rows, W a row after
    assert swa_moe_flops.band_keys(16384, 1024) == \
        (1024 * 1025 / 2 + 15360 * 1024) / 16384
    assert swa_moe_flops.band_keys(512, 1024) == 513 / 2     # a short row
    assert by['window_scores'] == 12 * 32 * 128 * 16384 * 3 * \
        swa_moe_flops.band_keys(16384, 1024)
    assert by['full_scores'] == 12 * 32 * 128 * 16384 * 16385 / 2
    # twice the pairs, twice the experts' work and nothing else
    more = swa_moe_flops.train_flops_per_step(m, 1, 16384, 8 * 16 * 2048)
    assert more['experts'] == 2 * by['experts']
    assert more['total'] - by['total'] == by['experts']


# -------------------------------------------------------- the configuration
def test_the_configuration_says_what_the_preset_is():
    from skypilot_tpu.models import hybrid
    from skypilot_tpu.train import checkpoint
    cfg, m = _cfg(), _sizes()
    c = hybrid.CONFIGS[cfg['flags'][cfg['flags'].index('--model') + 1]]
    ops = {'sliding_attention': 'window_attention',
           'full_attention': 'attention'}
    assert [(ops[t], 'experts') for t in m['layer_types']] == list(c.layers)
    assert m['mlp_layer_types'] == ['sparse'] * len(m['layer_types'])
    assert m['num_hidden_layers'] == len(m['layer_types']) == c.n_layers
    assert (m['hidden_size'], m['head_dim'], m['num_attention_heads'],
            m['num_key_value_heads'], m['vocab_size'], m['rms_norm_eps'],
            m['sliding_window'], m['tie_word_embeddings'],
            m['attention_bias']) == \
        (c.base.dim, c.base.head_dim, c.base.n_heads, c.base.n_kv_heads,
         c.base.vocab_size, c.base.norm_eps, c.window,
         c.base.tie_embeddings, c.base.attn_bias)
    full = m['rope_parameters']['full_attention']
    assert (full['rope_type'], full['rope_theta'], full['factor'],
            full['original_max_position_embeddings'], full['beta_fast'],
            full['beta_slow'], full['attention_factor']) == \
        ('yarn', c.base.rope_theta, c.yarn.factor,
         c.yarn.original_max_position, c.yarn.beta_fast, c.yarn.beta_slow,
         c.yarn.scale)
    assert m['rope_parameters']['sliding_attention'] == {
        'rope_type': 'default', 'rope_theta': c.base.rope_theta}
    ex = c.experts
    assert (m['router_outputs'], m['num_experts_per_tok'],
            m['moe_intermediate_size'], tuple(m['experts_held']),
            m['num_experts']) == \
        (ex.num_experts, ex.experts_per_token, ex.mlp_dim, ex.held_range,
         ex.num_held)
    assert ex.scoring == 'softmax' and m['norm_topk_prob']
    assert c.base.qk_norm and c.base.param_dtype == cfg['state_dtype']
    assert cfg['sigterm_exit_code'] == checkpoint.PreemptionGuard.EXIT_CODE
    # the floors of a cut: a whole period and four layers, eight experts
    # a layer, an eighth of the vocabulary
    assert m['num_hidden_layers'] >= 4 and m['num_experts'] >= 8
    assert m['vocab_size'] * 8 >= cfg['reduced']['vocab_size']['published']
    # the cell is the issue's
    bench = common.load_json(os.path.join(ROOT, 'BENCHMARK.json'))
    cell = next(w for w in bench['workloads'] if w['name'] == CELL)
    assert (cell['config'], cell['traffic'], cell['chips']) == \
        (cfg['name'], 'sft-steps-16k', 1)
    mix = common.load_json(os.path.join(BENCH, 'traffic',
                                        'sft-steps-16k.json'))
    assert (mix['kind'], mix['rows'], mix['seq'], mix['warm_steps'],
            mix['traced_steps'], mix['distinct_steps']) == \
        ('steps', 1, 16384, 6, 5, 40)
    entry = next(e for e in bench['configs'] if e['name'] == cfg['name'])
    assert entry['reduced'] == list(cfg['reduced'])
    assert entry['source'] == cfg['source']


@pytest.mark.skipif(not os.path.exists(CATALOG), reason='no catalog here')
def test_every_number_of_the_catalogs_config_is_in_the_file_or_reduced():
    with open(CATALOG, encoding='utf-8') as f:
        row = next(r for r in map(json.loads, f)
                   if r['name'] == 'Mellum2-12B-A2.5B-Instruct')
    cfg = _cfg()
    assert cfg['source'] == row['source_url']
    listed = ('layer_types', 'mlp_layer_types')
    for key, value in row['config'].items():
        if key in cfg['reduced']:
            assert cfg['reduced'][key]['published'] == value or key in listed
            assert cfg['reduced'][key]['here'] == cfg[key] or key in listed
            if key in listed:       # the first period, in order
                assert cfg[key] == value[:len(cfg[key])]
        else:
            assert cfg[key] == value, key
    # no width is reduced
    assert not [k for k in cfg['reduced']
                if k.endswith(('_dim', '_rank', '_size')) and
                k != 'vocab_size']


# -------------------------------------------------------------- the demands
def _obs():
    paths = {'flash_attention': 'pallas', 'flash_window_attention': 'pallas',
             'moe_experts': 'ragged_dot'}
    check = {'selection_agreement': 0.9991, 'selection_deficit_max': 0.0,
             'pairs_held_program': 131000, 'pairs_held_reference': 131004,
             'kernel_paths': dict(paths)}
    return {'check': check, 'kernel_paths': dict(paths)}


def test_window_layers_off_the_pallas_rung_are_not_correct():
    obs = _obs()
    assert swa_moe_train_cell._window_problems(obs) == []
    # the full layers' rung says nothing about the window layers'
    for who in ('sft', 'check'):
        paths = {'flash_attention': 'pallas', 'moe_experts': 'ragged_dot'}
        bad = dict(obs, kernel_paths=paths) if who == 'sft' else \
            dict(obs, check=dict(obs['check'], kernel_paths=paths))
        assert len(swa_moe_train_cell._window_problems(bad)) == 1, who
    fell = dict(obs, kernel_paths=dict(obs['kernel_paths'],
                                       flash_window_attention='xla'))
    assert len(swa_moe_train_cell._window_problems(fell)) == 1
    # the child's own failure is train_cell's to report, once
    assert swa_moe_train_cell._window_problems(
        dict(obs, check={'error': 'died'})) == []


# -------------------------------------------------------------- the readers
def test_the_readers_take_their_metrics_from_what_was_observed(tmp_path):
    step = 'jit(step_fn)/jvp(HybridModel)/'
    back = 'jit(step_fn)/transpose(jvp(HybridModel))/jvp(HybridModel)/' \
        'checkpoint/'
    call = ' = bf16[1,16384,32,128] custom-call(), ' \
        'custom_call_target="tpu_custom_call"'
    ops = [('%_attention.10' + call,
            step + 'layer_0/attn/flash_window/jit(_attention)/pallas_call'),
           ('%_attention.24' + call,
            back + 'layer_0/attn/flash_window/jit(_attention)/pallas_call'),
           ('%_attention.13' + call,
            step + 'layer_3/attn/flash_full/jit(_attention)/pallas_call'),
           ('%fusion.17 = bf16[34816,2304] fusion(bf16[16384,2304] %x)',
            step + 'layer_1/experts/moe_route/gather:'),
           ('%ragged-dot-none.91 = bf16[34816,896] custom-call(), '
            'custom_call_target="tpu_custom_call"', 'ragged-dot-none:'),
           ('%fusion.402 = bf16[34816,896] fusion(bf16[34816,896] %z)',
            step + 'layer_1/experts/moe_experts/select_n:'),
           ('%fusion.370 = bf16[1,16384,4096] fusion()',
            step + 'layer_0/attn/wq/dot_general:')]
    _trace_file(str(tmp_path), ops)
    seconds = [0.04, 0.12, 0.4, 0.2, 0.3, 0.1, 0.5]
    obs = {'boundaries': [10.0, 10.5, 11.0, 13.0], 'tokens_per_step': 16384,
           'mix': {'seq': 16384}, 'rows': 1, 'chips': 1, 'sizes': _sizes(),
           'peak': {'bf16_flops_per_s': 197e12},
           'profile_dir': str(tmp_path),
           'moe_steps': [{'held': h} for h in (130000, 131072, 132000)],
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
    # the window layers' calls alone, forward and backward
    assert read('kernel.flash_window_ms_per_step.train') == \
        pytest.approx(40.0)
    # all three flash calls, by the kernels' name
    assert read('kernel.flash_ms_per_step.train') == pytest.approx(140.0)
    assert read('kernel.moe_route_ms_per_step.train') == pytest.approx(50.0)
    assert read('kernel.moe_experts_ms_per_step.train') == \
        pytest.approx(100.0)
    by = swa_moe_flops.train_flops_per_step(_sizes(), 1, 16384, 131072)
    assert read('kernel.flash_window_roofline.train') == pytest.approx(
        100 * by['window_scores'] / 0.040 / 197e12)
    assert 25 < read('kernel.flash_window_roofline.train') < 35
    # the median gap (0.5 s) and the median of the pairs reported
    assert read('model.mfu.train.swa_moe') == pytest.approx(
        100 * by['total'] / 0.5 / 197e12)
    assert 25 < read('model.mfu.train.swa_moe') < 30
    # a program without the scope, or an untraced run
    obs['_op_scopes'] = {}
    assert read('kernel.flash_window_ms_per_step.train') is None
    assert read('kernel.flash_window_roofline.train') is None
    assert read('kernel.flash_ms_per_step.train') == pytest.approx(140.0)
    obs['trace'] = None
    assert read('kernel.flash_window_roofline.train') is None
    assert read('model.mfu.train.swa_moe') is not None
    del obs['moe_steps']
    assert read('model.mfu.train.swa_moe') is None


# ------------------------------------------------------------- the faults
FULL = {'rope_type': 'yarn', 'rope_theta': 10000, 'factor': 4,
        'original_max_position_embeddings': 16, 'beta_fast': 1.0,
        'beta_slow': 0.05, 'attention_factor': 1.138629436111989}
PLAIN = {'rope_type': 'default', 'rope_theta': 10000}


@pytest.mark.parametrize('fault,caught', [
    ({}, False),
    ({'program_weight_bits': [4, 3]}, True),        # float8_e4m3 weights
    ({'reference_sizes': {'norm_topk_prob': False}}, True),
    ({'reference_sizes': {'sliding_window': 1 << 20}}, True),
    ({'reference_sizes': {'rope_parameters': {
        'full_attention': PLAIN, 'sliding_attention': PLAIN}}}, True),
    ({'reference_sizes': {'rope_parameters': {
        'full_attention': dict(FULL, attention_factor=1.0),
        'sliding_attention': PLAIN}}}, True)],
    ids=['sound', 'float8_weights', 'no_renormalisation',
         'full_attention_in_window_layers', 'plain_rotary_on_the_full_layer',
         'no_attention_factor'])
def test_what_a_fault_reads_in_the_check_is_not_correct(fault, caught):
    """The check child at the rehearsal's sizes on the CPU, sound and
    under the faults whose chip readings PERF.md gives: a precision
    below the stated one, a missing term, a missing band, a wrong
    rotary table, a missing factor."""
    import moe_train_cell
    import run
    import train_cell
    cfg = _cfg()
    cfg = run._merge(cfg, cfg['rehearsal'])
    spec = {'preset': 'debug-mellum2', 'reference': cfg['reference'],
            'sizes': {k: cfg[k] for k in swa_moe_train_cell.SIZE_KEYS},
            'seed': 3000000019, 'rows': 2, 'seq': 64, **fault}
    assert spec['sizes']['rope_parameters']['full_attention'] == FULL
    res = subprocess.run(
        [sys.executable, os.path.join(BENCH, 'children',
                                      'swa_moe_check_child.py'),
         json.dumps(spec)], cwd=ROOT, capture_output=True, text=True,
        timeout=300, env=common.child_env('cpu', {}))
    assert res.returncode == 0, res.stderr[-2000:]
    check = json.loads(next(
        line for line in res.stdout.splitlines()
        if line.startswith('chipbench-check: ')).split(': ', 1)[1])
    obs = dict(check=check, losses=[6.0, 6.0], exit_code=75,
               traceback=False, ln_vocab=5.545, compiles_in_window=0,
               kernel_paths={}, pallas=None, flash_backward=None,
               device={}, state_bytes=0,
               moe_steps=[{'held': 2048, 'pairs': 2048, 'fullest': 1.2,
                           'dropped': 0}])
    bad = train_cell._problems(obs, cfg, False) + \
        moe_train_cell._routing_problems(obs, cfg)
    assert bool(bad) == caught, bad


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
    assert all(k.startswith('cpu_rehearsal.') for k in line['metrics'])
    assert 'routing: plan' in res.stdout
