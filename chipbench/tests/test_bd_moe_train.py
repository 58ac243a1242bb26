"""The `bd_moe_train` kind of cell: its count, its readers, its demands,
its configuration against the program's preset and the catalog, what its
check reads under each fault, and its CPU rehearsal. Beside
test_swa_moe_train.py; same rules."""
import importlib
import json
import os
import subprocess
import sys

import pytest

import bd_moe_flops
import bd_moe_train_cell
import common
from test_moe_train import _trace_file

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
CATALOG = '/opt/skills/guides/model-configs/architectures.jsonl'
CELL = 'sft-bd-moe-8k'


def _cfg():
    return common.load_json(os.path.join(
        BENCH, 'configs', 'sdar-30b-a3b-ep8-sft.json'))


def _sizes():
    cfg = _cfg()
    return {k: cfg[k] for k in bd_moe_train_cell.SIZE_KEYS}


# ---------------------------------------------------------------- the count
def test_the_count_is_the_presets_and_the_issues_arithmetic():
    from skypilot_tpu.models import hybrid
    m = _sizes()
    preset = hybrid.CONFIGS['sdar-30b-a3b-ep8']
    assert bd_moe_flops.held_params(m) == preset.num_params() == \
        645623296 == _cfg()['params_held']
    # clean to clean L (L + B) / 2, noised to clean L (L - B) / 2, a
    # noised block to itself L B
    assert bd_moe_flops.allowed_pairs(8192, 4) == 67141632 == \
        8192 * 8196 // 2 + 8192 * 8188 // 2 + 8192 * 4
    # each held expert sees 1,024 rows a layer on average: 6 x 16 x 1,024
    by = bd_moe_flops.train_flops_per_step(m, 1, 8192, 6 * 16 * 1024)
    assert by['total'] == sum(v for k, v in by.items() if k != 'total')
    # ISSUE 35: 11.93 TFLOP forward, 35.8 with the backward
    assert by['total'] == pytest.approx(35.8e12, rel=5e-3)
    share = {k: v / by['total'] for k, v in by.items()}
    assert share['bd_scores'] == pytest.approx(0.55, abs=0.005)
    assert share['attention_projections'] == pytest.approx(0.31, abs=0.005)
    assert share['experts'] == pytest.approx(0.078, abs=0.002)
    assert share['vocabulary'] == pytest.approx(0.053, abs=0.002)
    # by hand: the pass is over 2L positions, the head over L
    assert by['bd_scores'] == 12 * 32 * 128 * 6 * 67141632
    assert by['attention_projections'] == 6 * 16384 * 6 * (
        2 * 2048 * 4096 + 2 * 2048 * 512)
    assert by['router'] == 6 * 16384 * 6 * 2048 * 128
    assert by['experts'] == 6 * 98304 * 3 * 2048 * 768
    assert by['vocabulary'] == 6 * 8192 * 2048 * 18992
    # twice the pairs, twice the experts' work and nothing else
    more = bd_moe_flops.train_flops_per_step(m, 1, 8192, 12 * 16 * 1024)
    assert more['experts'] == 2 * by['experts']
    assert more['total'] - by['total'] == by['experts']


# -------------------------------------------------------- the configuration
def test_the_configuration_says_what_the_preset_is():
    from skypilot_tpu.models import hybrid
    from skypilot_tpu.train import checkpoint
    cfg, m = _cfg(), _sizes()
    c = hybrid.CONFIGS[cfg['flags'][cfg['flags'].index('--model') + 1]]
    assert c.layers == (('attention', 'experts'),) * m['num_hidden_layers']
    assert m['mlp_only_layers'] == [] and m['decoder_sparse_step'] == 1
    assert (m['hidden_size'], m['head_dim'], m['num_attention_heads'],
            m['num_key_value_heads'], m['vocab_size'], m['rms_norm_eps'],
            m['rope_theta'], m['tie_word_embeddings'], m['attention_bias'],
            m['intermediate_size'], m['max_position_embeddings']) == \
        (c.base.dim, c.base.head_dim, c.base.n_heads, c.base.n_kv_heads,
         c.base.vocab_size, c.base.norm_eps, c.base.rope_theta,
         c.base.tie_embeddings, c.base.attn_bias, c.base.mlp_dim,
         c.base.max_seq_len)
    assert m['rope_scaling'] is None and c.yarn is None and \
        not c.base.use_llama31_rope
    ex = c.experts
    assert (m['router_outputs'], m['num_experts_per_tok'],
            m['moe_intermediate_size'], tuple(m['experts_held']),
            m['num_experts']) == \
        (ex.num_experts, ex.experts_per_token, ex.mlp_dim, ex.held_range,
         ex.num_held)
    assert ex.scoring == 'softmax' and m['norm_topk_prob']
    assert (m['block_length'], m['mask_id']) == (
        c.block_diffusion.block_length, c.mask_id)
    assert m['mask_id'] == m['vocab_size'] - 1 == c.data_vocab_size
    from skypilot_tpu.train import block_diffusion
    assert m['level_min'] == block_diffusion.T_MIN
    assert c.base.qk_norm and c.base.param_dtype == cfg['state_dtype']
    assert cfg['sigterm_exit_code'] == checkpoint.PreemptionGuard.EXIT_CODE
    # every assumption ISSUE 35 names has its reason
    assert {'block_length', 'noise', 'no_shift', 'mask_id', 'qk_norm',
            'router'} <= set(cfg['assumed'])
    # a fine-tune's rate, and the CPU rehearsal runs at the same one
    lr = cfg['flags'][cfg['flags'].index('--lr') + 1]
    dry = cfg['rehearsal']['flags']
    assert float(lr) == 1e-5 and dry[dry.index('--lr') + 1] == lr
    assert f'--lr {lr}' in cfg['assumed']['optimizer']
    # the floors of a cut: four layers, eight experts a layer, an
    # eighth of the vocabulary
    assert m['num_hidden_layers'] >= 4 and m['num_experts'] >= 8
    assert m['vocab_size'] * 8 >= cfg['reduced']['vocab_size']['published']
    # the cell is the issue's
    bench = common.load_json(os.path.join(ROOT, 'BENCHMARK.json'))
    cell = next(w for w in bench['workloads'] if w['name'] == CELL)
    assert (cell['config'], cell['traffic'], cell['chips']) == \
        (cfg['name'], 'sft-bd-steps-8k', 1)
    mix = common.load_json(os.path.join(BENCH, 'traffic',
                                        'sft-bd-steps-8k.json'))
    wide = common.load_json(os.path.join(BENCH, 'traffic',
                                         'sft-steps-16k.json'))
    assert (mix['kind'], mix['rows'], mix['seq']) == ('steps', 1, 8192)
    assert [mix[k] for k in ('warm_steps', 'traced_steps',
                             'distinct_steps')] == \
        [wide[k] for k in ('warm_steps', 'traced_steps', 'distinct_steps')]
    assert mix['rehearsal'] == {'rows': 2, 'seq': 64,
                                'distinct_steps': 2000}
    entry = next(e for e in bench['configs'] if e['name'] == cfg['name'])
    assert entry['reduced'] == list(cfg['reduced'])
    assert entry['source'] == cfg['source']
    assert len(bench['workloads']) == 4


@pytest.mark.skipif(not os.path.exists(CATALOG), reason='no catalog here')
def test_every_number_of_the_catalogs_config_is_in_the_file_or_reduced():
    with open(CATALOG, encoding='utf-8') as f:
        row = next(r for r in map(json.loads, f)
                   if r['name'] == 'SDAR-30B-A3B-Chat')
    cfg = _cfg()
    assert cfg['source'] == row['source_url']
    for key, value in row['config'].items():
        if key in cfg['reduced']:
            assert cfg['reduced'][key]['published'] == value
            assert cfg['reduced'][key]['here'] == cfg[key]
        else:
            assert cfg[key] == value, key
    assert list(cfg['reduced']) == ['num_hidden_layers', 'num_experts',
                                    'vocab_size']
    # no width is reduced
    assert not [k for k in cfg['reduced']
                if k.endswith(('_dim', '_rank', '_size')) and
                k != 'vocab_size']


# -------------------------------------------------------------- the demands
def _obs():
    paths = {'flash_block_diffusion_attention': 'pallas',
             'moe_experts': 'pallas'}
    check = {'loss_program': 10.3, 'loss_reference': 10.3,
             'grad_rel_err': 0.005, 'grad_rel_err_worst_leaf': 0.01,
             'worst_leaf': 'x', 'param_dtypes': ['float32'],
             'opt_state_dtypes': ['float32'], 'pallas_interpret': False,
             'kernel_paths': dict(paths)}
    return dict(check=check, losses=[10.4, 10.2], exit_code=75,
                traceback=False, ln_vocab=9.85, compiles_in_window=0,
                kernel_paths=dict(paths), pallas='compiled',
                flash_backward='pallas', state_bytes=100,
                device={'memory': [{'peak_bytes_in_use': 200}]})


def test_the_flash_rung_is_read_under_the_models_own_op():
    cfg = _cfg()
    assert bd_moe_train_cell._problems(_obs(), cfg, True) == []
    # train_cell itself would demand a causal flash call of this model
    assert len(bd_moe_train_cell._TRAIN_PROBLEMS(_obs(), cfg, True)) == 2
    for who in ('sft', 'check'):
        fell = {'flash_block_diffusion_attention': 'xla',
                'moe_experts': 'pallas'}
        obs = _obs()
        if who == 'sft':
            obs['kernel_paths'] = fell
        else:
            obs['check']['kernel_paths'] = fell
        bad = bd_moe_train_cell._problems(obs, cfg, True)
        assert len(bad) == 1 and 'Pallas' in bad[0], who
    # a causal flash call beside a missing one of the mask's does not do
    obs = _obs()
    obs['kernel_paths'] = {'flash_attention': 'pallas'}
    assert len(bd_moe_train_cell._problems(obs, cfg, True)) == 1
    # the child's own failure is reported once
    obs = dict(_obs(), check={'error': 'died'})
    assert bd_moe_train_cell._problems(obs, cfg, True) == ['died']


def test_the_objectives_counters_and_plan_are_demanded():
    cfg = _cfg()
    mix = {'rows': 1, 'seq': 8192}
    plan = {'block': '4', 'data': '8192', 'positions': '16384',
            'allowed_pairs': '67141632', 'mask_id': '18991'}
    steps = [{'masked': n, 'targets': 8192, 'weight': 2.0}
             for n in (4000, 4100, 4200)]
    drawn = {'one_level_a_block': True, 'level_min': 0.0011,
             'level_max': 0.9999, 'masked': 4127, 'masked_expected': 4100.3,
             'masked_sd': 36.9}
    obs = {'sizes': _sizes(), 'bd_plan': plan, 'bd_steps': steps,
           'moe_steps': [{}] * 3, 'check': {'noise': drawn}}
    assert bd_moe_train_cell._objective_problems(obs, cfg, mix) == []
    # the check child's own failure is train_cell's to report
    assert bd_moe_train_cell._objective_problems(
        dict(obs, check={'error': 'died'}), cfg, mix) == []
    # a third of the targets masked (m drawn at t squared), or all
    for masked in (2731, 8192):
        bad = bd_moe_train_cell._objective_problems(dict(obs, bd_steps=[
            dict(s, masked=masked) for s in steps]), cfg, mix)
        assert len(bad) == 1 and 'half' in bad[0]
    # the mean weight: m drawn at t squared weighs 1.5, t drawn from
    # U(0.5, 1] 1.33, where the inverse of the mean level is 1.998
    for weight in (1.5, 1.33, 2.2):
        bad = bd_moe_train_cell._objective_problems(dict(obs, bd_steps=[
            dict(s, weight=weight) for s in steps]), cfg, mix)
        assert len(bad) == 1 and 'weigh' in bad[0]
    # the noise handed to both sides of the check, from (m, t) alone: a
    # level that changes inside a block, a level under the least or over
    # 1, a mask that is not Bernoulli(t) (drawn at t squared)
    for fault in ({'one_level_a_block': False}, {'level_min': 0.0004},
                  {'level_max': 1.2}, {'masked': 2731}):
        bad = bd_moe_train_cell._objective_problems(
            dict(obs, check={'noise': dict(drawn, **fault)}), cfg, mix)
        assert len(bad) == 1 and 'noise' in bad[0], fault
    # a step line without the counters; the positions' count as targets
    assert len(bd_moe_train_cell._objective_problems(
        dict(obs, bd_steps=steps[:2]), cfg, mix)) == 1
    assert len(bd_moe_train_cell._objective_problems(dict(obs, bd_steps=[
        dict(s, targets=16384) for s in steps]), cfg, mix)) == 1
    # another block length, or no plan line at all
    assert len(bd_moe_train_cell._objective_problems(
        dict(obs, bd_plan=dict(plan, block='8')), cfg, mix)) == 1
    assert len(bd_moe_train_cell._objective_problems(
        dict(obs, bd_plan={}), cfg, mix)) == 1
    # the step line as sft prints it
    line = ('step 7/1000000 loss=10.2531 tokens/s=11873 moe_pairs=98211/'
            '786432 moe_fullest_over_mean=1.310 moe_dropped=0 moe_rows='
            '104448/786432 bd_masked=4127/8192 bd_weight_mean=1.997')
    assert bd_moe_train_cell.BD_RE.findall(line) == [
        ('4127', '8192', '1.997')]
    import train_cell
    assert train_cell.STEP_RE.search(line).group(1, 2) == ('7', '10.2531')
    # the rows of the data lie under the mask's id
    rows = bd_moe_train_cell._ROWS.train_rows(18992, 3000000019, 4, 8192)
    assert rows.shape == (4, 8193) and rows.max() == 18990


# -------------------------------------------------------------- the readers
def test_the_readers_take_their_metrics_from_what_was_observed(tmp_path):
    step = 'jit(step_fn)/jvp(HybridModel)/'
    back = 'jit(step_fn)/transpose(jvp(HybridModel))/jvp(HybridModel)/' \
        'checkpoint/'
    call = ' = bf16[1,16384,32,128] custom-call(), ' \
        'custom_call_target="tpu_custom_call"'
    ops = [('%_attention.10' + call, step + 'layer_0/attn/'
            'flash_block_diffusion/jit(_attention)/pallas_call'),
           ('%_attention.24' + call, back + 'layer_0/attn/'
            'flash_block_diffusion/jit(_attention)/pallas_call'),
           ('%fusion.9 = bf16[1,32,16384,128] fusion()', step + 'layer_0/'
            'attn/flash_block_diffusion/jit(_attention)/transpose'),
           ('%fusion.17 = bf16[17408,2048] fusion(bf16[16384,2048] %x)',
            step + 'layer_1/experts/moe_route/gather:'),
           ('%grouped_rows.3 = bf16[17408,768] custom-call(), '
            'custom_call_target="tpu_custom_call"', step + 'layer_1/'
            'experts/while/body/moe_experts/jit(rows_product)/'
            'grouped_rows/pallas_call'),
           ('%fusion.5 = s32[1,8192] fusion()',
            'jit(step_fn)/bd_objective/bd_noise/jit(_uniform)/max'),
           ('%fusion.6 = bf16[1,8192,18992] fusion()', step +
            'bd_objective/bd_loss/HybridModel._head/lm_head/dot_general'),
           ('%fusion.7 = f32[1,8192] fusion()', 'jit(step_fn)/'
            'transpose(jvp(HybridModel))/bd_objective/bd_loss/div'),
           ('%fusion.370 = bf16[1,16384,4096] fusion()',
            step + 'layer_0/attn/wq/dot_general:')]
    _trace_file(str(tmp_path), ops)
    seconds = [0.4, 0.6, 0.04, 0.2, 0.1, 0.004, 0.05, 0.006, 0.5]
    obs = {'boundaries': [10.0, 10.7, 11.4, 13.0], 'tokens_per_step': 8192,
           'mix': {'seq': 8192}, 'rows': 1, 'chips': 1, 'sizes': _sizes(),
           'peak': {'bf16_flops_per_s': 197e12},
           'profile_dir': str(tmp_path),
           'moe_steps': [{'held': h} for h in (98000, 98304, 99000)],
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
    # the scope: the kernels and `_attention`'s own work beside them
    assert read('kernel.flash_bd_ms_per_step.train') == pytest.approx(260.0)
    # the kernels alone, by their name
    assert read('kernel.flash_ms_per_step.train') == pytest.approx(250.0)
    assert read('kernel.moe_route_ms_per_step.train') == pytest.approx(50.0)
    assert read('kernel.moe_experts_ms_per_step.train') == \
        pytest.approx(25.0)
    # noise, head and loss: the nested scopes count, forward and backward
    assert read('kernel.bd_objective_ms_per_step.train') == \
        pytest.approx(15.0)
    by = bd_moe_flops.train_flops_per_step(_sizes(), 1, 8192, 98304)
    assert read('kernel.flash_bd_roofline.train') == pytest.approx(
        100 * by['bd_scores'] / 0.260 / 197e12)
    assert 35 < read('kernel.flash_bd_roofline.train') < 42
    # the median gap (0.7 s) and the median of the pairs reported
    assert read('model.mfu.train.bd_moe') == pytest.approx(
        100 * by['total'] / 0.7 / 197e12)
    assert 24 < read('model.mfu.train.bd_moe') < 28
    # the new metrics are this cell's alone; four are new
    new = [m for m in bench['per_layer'] if m['workloads'] == [CELL]]
    assert [m['name'] for m in new] == [
        'model.mfu.train.bd_moe', 'kernel.flash_bd_ms_per_step.train',
        'kernel.flash_bd_roofline.train',
        'kernel.bd_objective_ms_per_step.train']
    # a program without the scopes (the parent's), or an untraced run:
    # nothing, and no error
    obs['_op_scopes'] = {}
    assert read('kernel.flash_bd_ms_per_step.train') is None
    assert read('kernel.flash_bd_roofline.train') is None
    assert read('kernel.bd_objective_ms_per_step.train') is None
    obs['trace'] = None
    assert read('kernel.flash_bd_roofline.train') is None
    assert read('model.mfu.train.bd_moe') is not None
    # another kind's observations: no objective in the sizes
    other = dict(obs, sizes={k: v for k, v in _sizes().items()
                             if k != 'block_length'})
    spec = common.load_json(os.path.join(
        BENCH, 'metrics', 'model.mfu.train.bd_moe.json'))
    assert importlib.import_module('readers.mfu_bd_moe').read(
        other, spec['params']) is None
    del obs['moe_steps']
    assert read('model.mfu.train.bd_moe') is None


# ------------------------------------------------------------- the faults
@pytest.mark.parametrize('fault,caught', [
    ({}, False),
    ({'program_weight_bits': [4, 3]}, True),        # float8_e4m3 weights
    ({'fault': 'causal_mask'}, True),
    ({'fault': 'leak'}, True),
    ({'fault': 'noised_see_noised'}, True),
    ({'fault': 'no_weight'}, True),
    ({'fault': 'positions_0_to_2L'}, True),
    ({'fault': 'shifted_logits'}, True),
    # the two readings of a cause (`check.why`): both sides still agree
    ({'level_floor': 0.25}, False),
    ({'program_dtype': 'float32'}, False)],
    ids=['sound', 'float8_weights', 'causal_mask', 'leak',
         'noised_see_noised', 'no_weight', 'positions_0_to_2L',
         'shifted_logits', 'level_floor', 'program_float32'])
def test_what_a_fault_reads_in_the_check_is_not_correct(fault, caught):
    """The check child at the rehearsal's sizes on the CPU, sound and
    under the faults whose chip readings `check.why` gives: a precision
    below the stated one, a plain causal mask over the 2L positions, a
    noised block seeing its own clean copy, noised blocks seeing one
    another, the loss without 1 / t, positions 0..2L-1, logits shifted
    by one."""
    import moe_train_cell
    import run
    cfg = _cfg()
    cfg = run._merge(cfg, cfg['rehearsal'])
    spec = {'preset': 'debug-sdar', 'reference': cfg['reference'],
            'sizes': {k: cfg[k] for k in bd_moe_train_cell.SIZE_KEYS},
            'seed': 3000000019, 'rows': 2, 'seq': 64, **fault}
    res = subprocess.run(
        [sys.executable, os.path.join(BENCH, 'children',
                                      'bd_moe_check_child.py'),
         json.dumps(spec)], cwd=ROOT, capture_output=True, text=True,
        timeout=300, env=common.child_env('cpu', {}))
    assert res.returncode == 0, res.stderr[-2000:]
    check = json.loads(next(
        line for line in res.stdout.splitlines()
        if line.startswith('chipbench-check: ')).split(': ', 1)[1])
    assert check['targets'] == 128 and 0 < check['masked_targets'] < 128
    if 'level_floor' in fault:      # weights 1 / t at most 4
        assert check['weight_mean'] <= 4.0
    drawn = check['noise']
    assert drawn['one_level_a_block'] and drawn['masked'] == \
        check['masked_targets']
    assert cfg['level_min'] <= drawn['level_min'] < drawn['level_max'] <= 1
    assert abs(drawn['masked'] - drawn['masked_expected']) <= \
        5 * drawn['masked_sd']
    obs = dict(check=check, losses=[6.0, 6.0], exit_code=75,
               traceback=False, ln_vocab=5.545, compiles_in_window=0,
               kernel_paths={}, pallas=None, flash_backward=None,
               device={}, state_bytes=0,
               moe_steps=[{'held': 2048, 'pairs': 2048, 'fullest': 1.2,
                           'dropped': 0}])
    bad = bd_moe_train_cell._problems(obs, cfg, False) + \
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
    assert 'routing: plan' in res.stdout and 'objective: plan' in res.stdout
