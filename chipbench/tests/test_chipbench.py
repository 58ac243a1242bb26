import json
import os
import subprocess
import sys

import pytest

import common
import traffic_gen
import xplane

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)


# ------------------------------------------------------------- the traffic
def test_train_rows_come_from_the_seed():
    a = traffic_gen.train_rows(151936, 3000000019, 4, 16)
    assert a.shape == (4, 17) and a.max() < 151936
    assert (a == traffic_gen.train_rows(151936, 3000000019, 4, 16)).all()
    assert (a != traffic_gen.train_rows(151936, 1, 4, 16)).any()


# ----------------------------------------------------------- the statistics
def test_quantiles_interpolate():
    assert common.quantile([1, 2, 3, 4], 0.5) == 2.5 == common.median(
        [4, 1, 3, 2])
    with pytest.raises(ValueError):
        common.quantile([], 0.5)


# ------------------------------------------------------- the trace reducer
def test_the_reducer_gives_the_recorded_traces_busy_and_idle_to_the_digit():
    red = xplane.reduce_file(os.path.join(BENCH, 'fixtures',
                                          'sft_cut.xplane.pb'), True)
    want = common.load_json(os.path.join(BENCH, 'fixtures',
                                         'sft_cut.expected.json'))
    assert red['chips'] == 1 and red['steps'] == want['whole_steps']
    assert red['busy_s'] == want['busy_s']
    assert red['window_s'] == want['window_s']
    assert [xplane.short_name(n) for n, _ in red['ops_s'][:4]] == \
        want['top_ops']
    assert red['gaps_s'][0] == want['longest_gap_s']


def test_self_time_takes_a_loop_bodys_time_off_the_loop():
    red = xplane.reduce_plane(
        [(0, 100, 'prog')],
        [(0, 60, 'while'), (10, 30, 'a'), (30, 50, 'b'), (70, 90, 'c')],
        False)
    assert red['busy_ns'] == 80 and red['window_ns'] == 100
    assert red['steps'] == 1
    assert red['self_ns'] == {'while': 20, 'a': 20, 'b': 20, 'c': 20}
    assert red['gaps_ns'][:2] == [10, 10]
    assert xplane.short_name(
        '%x.1 = f32[2] custom-call(f32[2] %y), custom_call_target="k"') == \
        'x.1 [k]'


# ------------------------------------------------------------- the files
def test_benchmark_json_and_the_files_by_name_agree():
    bench = common.load_json(os.path.join(ROOT, 'BENCHMARK.json'))
    cells = {w['name'] for w in bench['workloads']}
    for w in bench['workloads']:
        assert os.path.exists(os.path.join(BENCH, 'traffic',
                                           w['traffic'] + '.json'))
    for c in bench['configs']:
        cfg = common.load_json(os.path.join(ROOT, c['file']))
        assert cfg['name'] == c['name'] and cfg['source'] == c['source']
        assert sorted(cfg['reduced']) == sorted(c['reduced'])
        assert os.path.exists(os.path.join(BENCH, cfg['kind'] + '_cell.py'))
        assert os.path.exists(os.path.join(BENCH, 'references',
                                           cfg['reference'] + '.py'))
    e2e = {m['name'] for m in bench['end_to_end']}
    for m in bench['end_to_end'] + bench['per_layer']:
        spec = common.load_json(os.path.join(BENCH, 'metrics',
                                             m['name'] + '.json'))
        assert set(spec) == {'reader', 'params'}
        assert os.path.exists(os.path.join(BENCH, 'readers',
                                           spec['reader'] + '.py'))
        assert set(m.get('workloads', cells)) <= cells
        if 'moves' in m:
            assert m['moves'] in e2e
    from skypilot_tpu.train import checkpoint
    sft = common.load_json(os.path.join(BENCH, 'configs',
                                        'qwen3-0.6b-sft.json'))
    assert sft['sigterm_exit_code'] == checkpoint.PreemptionGuard.EXIT_CODE
    peaks = common.load_json(os.path.join(BENCH, 'peaks.json'))
    assert peaks['TPU v5 lite']['bf16_flops_per_s'] == 197e12


def test_the_configuration_says_what_the_preset_is():
    import flops
    from skypilot_tpu.models import llama
    cfg = common.load_json(os.path.join(BENCH, 'configs',
                                        'qwen3-0.6b-sft.json'))
    m, c = cfg['model'], llama.CONFIGS['qwen3-0.6b']
    assert (m['hidden_size'], m['num_hidden_layers'],
            m['num_attention_heads'], m['num_key_value_heads'],
            m['intermediate_size'], m['vocab_size'], m['head_dim'],
            m['rope_theta'], m['rms_norm_eps'], m['tie_word_embeddings']) == \
        (c.dim, c.n_layers, c.n_heads, c.n_kv_heads, c.mlp_dim,
         c.vocab_size, c.head_dim, c.rope_theta, c.norm_eps,
         c.tie_embeddings)
    assert c.qk_norm and c.param_dtype == cfg['state_dtype']
    # tied embeddings: the matrices that multiply are all the weights
    # but the norms
    assert abs(flops.matmul_params(m) - 596e6) < 1e6
    assert abs(flops.matmul_params(m) - c.num_params()) < 1e5
    assert flops.train_flops_per_token(m, 2048) == pytest.approx(
        6 * flops.matmul_params(m) + 6 * 28 * 2048 * 2049)


# ----------------------------------------------------------- the reference
def test_the_program_agrees_with_the_plain_reference_on_qwen3s_parts():
    """Debug widths with what Qwen3 adds to the debug preset (q/k norm,
    tied embeddings, a head size of its own, remat), float32 on the CPU:
    loss and every gradient leaf agree to rounding. At the published
    widths the same comparison runs on the chip in every run
    (children/check_child.py)."""
    import dataclasses

    import flax.linen as nn
    import jax
    import jax.numpy as jnp
    from references import llama_dense
    from skypilot_tpu.models import llama
    from skypilot_tpu.train import trainer
    cfg = dataclasses.replace(
        llama.CONFIGS['debug'], qk_norm=True, tie_embeddings=True,
        head_dim_override=32, rope_theta=1e6, norm_eps=1e-6, remat=True)
    sizes = {'hidden_size': 64, 'num_attention_heads': 4,
             'num_key_value_heads': 2, 'head_dim': 32, 'rms_norm_eps': 1e-6,
             'rope_theta': 1e6, 'tie_word_embeddings': True}
    model = llama.LlamaModel(cfg)
    params = nn.meta.unbox(jax.jit(model.init)(
        jax.random.PRNGKey(5), jnp.zeros((1, 8), jnp.int32))['params'])
    rows = jnp.asarray(traffic_gen.train_rows(256, 7, 2, 64))
    tok, tgt = rows[:, :-1], rows[:, 1:]
    loss_p, grad_p = jax.jit(jax.value_and_grad(
        lambda p: trainer.cross_entropy_loss(
            model.apply({'params': p}, tok), tgt)[0]))(params)
    with jax.default_matmul_precision('highest'):
        loss_r, grad_r = jax.jit(jax.value_and_grad(
            lambda p: llama_dense.loss(p, tok, tgt, sizes)))(params)
    assert float(loss_p) == pytest.approx(float(loss_r), abs=1e-5)
    worst = max(jax.tree.leaves(jax.tree.map(
        lambda a, b: float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b)),
        grad_p, grad_r)))
    assert worst < 1e-5


def test_a_gradient_off_the_reference_is_not_correct():
    import train_cell
    cfg = common.load_json(os.path.join(BENCH, 'configs',
                                        'qwen3-0.6b-sft.json'))
    check = {'loss_program': 12.13, 'loss_reference': 12.13,
             'grad_rel_err': 0.0, 'grad_rel_err_worst_leaf': 0.0,
             'worst_leaf': 'x', 'param_dtypes': ['float32'],
             'opt_state_dtypes': ['float32'], 'pallas_interpret': False,
             'kernel_paths': {'flash_attention': 'pallas'}}
    obs = {'losses': [12.13, 12.12], 'check': check, 'exit_code': 75,
           'traceback': False, 'ln_vocab': 11.931, 'compiles_in_window': 0,
           'kernel_paths': {'flash_attention': 'pallas'},
           'pallas': 'compiled', 'flash_backward': 'pallas',
           'device': {'memory': [{'peak_bytes_in_use': 7.2e9}]},
           'state_bytes': 7.15e9}
    assert train_cell._problems(obs, cfg, True) == []
    for key, value in (('grad_rel_err', 2 * cfg['check']['grad_rel']),
                       ('grad_rel_err_worst_leaf', float('nan')),
                       ('loss_program', 12.13 + 2 * cfg['check']['loss_abs']),
                       ('opt_state_dtypes', ['bfloat16']),
                       ('kernel_paths', {'flash_attention': 'xla_native'})):
        bad = dict(obs, check=dict(check, **{key: value}))
        assert len(train_cell._problems(bad, cfg, True)) == 1, key
    assert train_cell._problems(dict(obs, check={'error': 'died'}), cfg,
                                True) == ['died']
    assert len(train_cell._problems(dict(obs, compiles_in_window=1), cfg,
                                    True)) == 1
    half = dict(obs, device={'memory': [{'peak_bytes_in_use': 3.6e9}]})
    assert len(train_cell._problems(half, cfg, True)) == 1


# ------------------------------------------------------------- the readers
def test_the_readers_take_their_metrics_from_what_was_observed():
    import importlib
    cfg = common.load_json(os.path.join(BENCH, 'configs',
                                        'qwen3-0.6b-sft.json'))
    obs = {'boundaries': [10.0, 10.5, 11.0, 12.5], 'tokens_per_step': 8192,
           'setup_s': 26.0, 'model': cfg['model'], 'mix': {'seq': 2048},
           'chips': 1, 'peak': {'bf16_flops_per_s': 197e12},
           'trace': {'busy_s': 1.9, 'window_s': 2.0, 'chips': 1, 'steps': 4,
                     'ops_s': [['%_attention.44 = f32[] custom-call(), '
                                'custom_call_target="tpu_custom_call"', 0.8],
                               ['%fusion.1 = f32[] fusion()', 0.5]]}}

    def read(metric):
        spec = common.load_json(os.path.join(BENCH, 'metrics',
                                             metric + '.json'))
        return importlib.import_module('readers.' + spec['reader']).read(
            obs, spec['params'])
    assert read('train_tokens_per_s') == pytest.approx(3 * 8192 / 2.5)
    assert read('setup_s') == 26.0
    assert read('trainer.step_ms') == pytest.approx(500.0)
    # the median gap, not the mean: the 1.5 s stall does not count
    assert read('model.mfu.train') == pytest.approx(
        100 * 4.2797e9 * 8192 / 0.5 / 197e12, rel=1e-3)
    assert read('kernel.flash_ms_per_step.train') == pytest.approx(200.0)
    assert read('device.idle_share.train') == pytest.approx(5.0)
    obs['trace'] = None
    assert read('kernel.flash_ms_per_step.train') is None
    assert read('device.idle_share.train') is None


# ---------------------------------------------------------- the rehearsal
@pytest.mark.parametrize('cell,trace', [('sft-2k', 0), ('sft-2k', 1)])
def test_the_cpu_rehearsal_of_each_cell_ends_in_a_line_that_parses(
        cell, trace):
    res = subprocess.run(
        [sys.executable, os.path.join(BENCH, 'run.py'), '--workload', cell,
         '--seed', '3000000019', '--seconds', '3', '--trace', str(trace),
         '--rehearse-cpu'], cwd=ROOT, capture_output=True, text=True,
        timeout=300)
    assert res.returncode == 0, res.stderr[-2000:] + res.stdout[-2000:]
    line = json.loads(res.stdout.strip().splitlines()[-1])
    assert set(line) >= {'correct', 'attempted', 'failed', 'metrics',
                         'device'}
    assert line['correct'] is True and line['failed'] == 0
    assert line['attempted'] > 0 and line['metrics']
    assert all(k.startswith('cpu_rehearsal.') for k in line['metrics'])
    assert line['device']['platform'] == 'cpu'


def test_a_directory_without_the_program_gives_no_result(tmp_path):
    import shutil
    shutil.copytree(BENCH, tmp_path / 'chipbench',
                    ignore=shutil.ignore_patterns('__pycache__'))
    shutil.copy(os.path.join(ROOT, 'BENCHMARK.json'), tmp_path)
    res = subprocess.run(
        [sys.executable, 'chipbench/run.py', '--workload', 'sft-2k',
         '--seed', '1', '--seconds', '1', '--trace', '0'],
        cwd=tmp_path, capture_output=True, text=True, timeout=60)
    assert res.returncode != 0 and not res.stdout.strip()
