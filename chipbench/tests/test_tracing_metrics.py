"""The readers of what the program says of itself (PR 37): operations
by the path the program named them under (`opname_ms_per_step`), the step
loop's host spans in the trace (`xplane_host`, `host_span_ms_per_step`),
and the phases of set-up on sft's one line (`log_phase_s`).

`fixtures/host_spans.xplane.pb` was recorded on the CPU with the Python
tracer off: three iterations (steps 7, 8, 9) of a loop annotated as sft's
is (`StepTraceAnnotation('train.step', step_num=...)` around
`TraceAnnotation`s that sleep 1 (3 in step 8), 2, and 10 + 1 ms, the 10
in a `train.pull` inside `train.log`; step 8 sleeps 2 ms more under no
span of its own), and a second thread that builds for 4 ms and places
for 1 ms each time the loop has taken a batch.
"""
import importlib
import os
import shutil

import pytest

import common
import xplane_host

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
FIXTURE = os.path.join(BENCH, 'fixtures', 'host_spans.xplane.pb')

NEW = [
    'trainer.forward_ms_per_step.train', 'trainer.backward_ms_per_step.train',
    'trainer.optimizer_ms_per_step.train', 'device.unowned_ms_per_step.train',
    'kernel.attn_proj_ms_per_step.train', 'kernel.mlp_ms_per_step.train',
    'kernel.head_loss_ms_per_step.train',
    'kernel.norm_rope_ms_per_step.train',
    'trainer.input_wait_ms_per_step.train',
    'trainer.host_work_ms_per_step.train', 'device.setup_runtime_s',
    'trainer.setup_state_init_s', 'trainer.setup_first_step_s',
    'trainer.recompute_ms_per_step.train',
    'trainer.dispatch_ms_per_step.train', 'trainer.log_ms_per_step.train',
    'trainer.prefetch_build_ms_per_step.train',
    'trainer.prefetch_place_ms_per_step.train']


def _read(metric, obs):
    spec = common.load_json(os.path.join(BENCH, 'metrics', metric + '.json'))
    return importlib.import_module('readers.' + spec['reader']).read(
        obs, spec['params'])


# ------------------------------------------------------------- the files
def test_every_new_metric_has_its_entry_its_file_and_its_reader():
    bench = common.load_json(os.path.join(ROOT, 'BENCHMARK.json'))
    entries = {m['name']: m for m in bench['per_layer']}
    e2e = {m['name'] for m in bench['end_to_end']}
    cells = {w['name'] for w in bench['workloads']}
    # appended, in this order, behind what the benchmark had
    assert [m['name'] for m in bench['per_layer']][-len(NEW):] == NEW
    for name in NEW:
        entry = entries[name]
        assert set(entry) == {'name', 'unit', 'better', 'source', 'layer',
                              'moves', 'workloads'}
        assert entry['moves'] in e2e and set(entry['workloads']) <= cells
        assert entry['layer'] in ('trainer', 'kernels', 'device')
        assert (entry['moves'] == 'setup_s') == name.endswith('_s')
        spec = common.load_json(os.path.join(BENCH, 'metrics',
                                             name + '.json'))
        assert set(spec) == {'reader', 'params'}
        reader = importlib.import_module('readers.' + spec['reader'])
        assert callable(reader.read)
        # a run that brought nothing back gives nothing and does not raise
        assert reader.read({'trace': None, 'profile_dir': None,
                            'log': os.path.join(BENCH, 'no-such.log')},
                           spec['params']) is None


# ------------------------------------------------- operations by their path
def _ops_obs():
    """Eight operations of two steps: self seconds and `tf_op` paths as the
    profiler keeps them (a trailing ':')."""
    step = 'jit(step_fn)/'
    model = step + 'jvp(HybridModel)/layer_0/'
    back = step + 'transpose(jvp(HybridModel))/jvp(HybridModel)/checkpoint/'
    paths = {
        'fwd_wq': (0.010, model + 'attn/wq/dot_general:'),
        'fwd_flash': (0.040, model + 'attn/flash_full/jit(_attention)/'
                      'pallas_call:'),
        'fwd_rope': (0.002, model + 'attn/mul:'),
        'bwd_wo': (0.020, back + 'layer_0/attn/wo/dot_general:'),
        'again_norm': (0.004, back + 'rematted_computation/layer_0/'
                       'op_norm/mul:'),
        'bwd_loss': (0.006, step + 'transpose(jvp(loss))/jit(log_softmax)/'
                     'sub:'),
        'adam': (0.030, step + 'optimizer/mul:'),
        'rope_table': (0.001, step + 'jvp(HybridModel)/jit(rope_freqs)/cos:'),
        'zero_fill': (0.008, ''),             # the profiler kept no path
    }
    return {'profile_dir': 'unused', 'chips': 1,
            'trace': {'steps': 2, 'chips': 1,
                      'ops_s': [[n, s] for n, (s, _) in paths.items()]},
            '_op_scopes': {n: p for n, (_, p) in paths.items() if p}}


def test_an_element_names_a_scope_bare_or_wrapped_and_a_pass_by_how_it_opens():
    from readers import opname_ms_per_step as reader
    assert [reader.names(e) for e in
            ('loss', 'jvp(loss)', 'transpose(jvp(loss))', 'jit(rope_freqs)',
             'dot_general')] == ['loss', 'loss', 'loss', 'rope_freqs',
                                 'dot_general']
    assert reader.which_pass(['jit(step_fn)', 'jvp(M)', 'attn']) == 'forward'
    assert reader.which_pass(['jit(step_fn)', 'transpose(jvp(M))', 'jvp(M)',
                              'checkpoint']) == 'backward'
    assert reader.which_pass(['jit(step_fn)', 'optimizer', 'mul']) == \
        reader.which_pass([]) == 'neither'


def test_operations_count_by_a_list_an_exclusion_and_a_pass():
    from readers import opname_ms_per_step as reader
    obs = _ops_obs()

    def ms(**params):
        return reader.read(obs, params)
    # a list of modules, either pass; ms a step over the two steps
    assert ms(any=['wq', 'wk', 'wv', 'wo']) == pytest.approx(15.0)
    # `attn` without its projections and kernels
    assert ms(any=['attn'], none=['wq', 'wo', 'flash_full']) == \
        pytest.approx(1.0)
    # a scope the transform wrapped, and a jitted helper
    assert ms(any=['loss']) == pytest.approx(3.0)
    assert ms(any=['rope_freqs']) == pytest.approx(0.5)
    assert ms(any=['rematted_computation']) == pytest.approx(2.0)
    # a scope nothing stands under
    assert ms(any=['short_conv']) is None
    # the passes: `transpose(` wins where a path has both
    assert ms(**{'pass': 'forward'}) == pytest.approx(26.5)
    assert ms(**{'pass': 'backward'}) == pytest.approx(15.0)
    assert ms(any=['attn'], **{'pass': 'backward'}) == pytest.approx(10.0)
    # an operation with no path counts only where `any` is absent
    assert ms(any=['optimizer']) == pytest.approx(15.0)
    assert ms(none=['optimizer'], **{'pass': 'neither'}) == \
        pytest.approx(4.0)
    assert ms() == pytest.approx(60.5)


def test_the_four_way_split_shares_out_the_devices_busy_time():
    obs = _ops_obs()
    parts = [_read(m, obs) for m in NEW[:4]]
    whole = sum(s for _, s in obs['trace']['ops_s']) / 2 * 1e3
    assert sum(parts) == pytest.approx(whole) == pytest.approx(60.5)
    assert _read('kernel.attn_proj_ms_per_step.train', obs) == \
        pytest.approx(15.0)
    assert _read('kernel.head_loss_ms_per_step.train', obs) == \
        pytest.approx(3.0)
    # norms, the rope table, and `attn` outside projections and kernels
    assert _read('kernel.norm_rope_ms_per_step.train', obs) == \
        pytest.approx(1.0 + 2.0 + 0.5)
    assert _read('trainer.recompute_ms_per_step.train', obs) == \
        pytest.approx(2.0)
    obs['_op_scopes'] = {}        # a trace whose operations kept no path
    assert _read('trainer.forward_ms_per_step.train', obs) is None
    assert _read('device.unowned_ms_per_step.train', obs) is None


# ------------------------------------------------------- the host's spans
def test_spans_gives_each_span_its_thread_line_and_its_step():
    spans = xplane_host.spans(FIXTURE)
    steps = [s for s in spans if s[0] == 'train.step']
    assert [s[4] for s in steps] == [7, 8, 9]
    loop, = {s[1] for s in steps}
    assert [(s[0], s[4]) for s in spans if s[1] == loop][:5] == [
        ('train.step', 7), ('train.input_wait', 7), ('train.dispatch', 7),
        ('train.log', 7), ('train.pull', 7)]
    assert [s[0] for s in spans if s[4] == 8] == [
        'train.step', 'train.input_wait', 'train.dispatch', 'train.log',
        'train.pull']
    for name, _, start, end, step in spans:
        assert start < end
        if step is not None and name != 'train.step':
            whole = next(s for s in steps if s[4] == step)
            assert whole[2] <= start and end <= whole[3]
    produced = [s for s in spans if s[0].startswith('prefetch.')]
    assert [s[0] for s in produced] == ['prefetch.build',
                                        'prefetch.place'] * 3
    assert {s[1] for s in produced}.isdisjoint({loop})
    assert all(s[4] is None for s in produced)
    # by start, on the trace's clock
    assert [s[2] for s in spans] == sorted(s[2] for s in spans)


def test_a_spans_time_a_step_and_the_steps_time_less_its_children():
    spans = xplane_host.spans(FIXTURE)
    waits = xplane_host.per_step(spans, 'train.input_wait')
    assert len(waits) == 3 and 3.0 < waits[1] < 4.5
    assert all(1.0 < w < 2.5 for w in (waits[0], waits[2]))
    assert xplane_host.per_step(spans, 'train.nothing') == [0.0] * 3
    whole = xplane_host.per_step(spans, 'train.step')
    work = xplane_host.per_step(spans, 'train.step',
                                ('train.input_wait', 'train.pull'))
    pulls = xplane_host.per_step(spans, 'train.pull')
    for step, less, wait, pull in zip(whole, work, waits, pulls):
        assert less == pytest.approx(step - wait - pull)
    # dispatch 2 ms and the log's own 1 ms; step 8 has 2 ms more that
    # stand under no span
    assert 3.0 < work[0] < 5.0 and work[1] > work[0] + 1.5
    # a span less what stands inside it: the log boundary's own work
    logs = xplane_host.per_step(spans, 'train.log')
    own = xplane_host.per_step(spans, 'train.log', ('train.pull',))
    for log, less, pull in zip(logs, own, pulls):
        assert less == pytest.approx(log - pull) and 1.0 < less < 2.5
    # `less` takes off only what lies inside the named span
    assert xplane_host.per_step(spans, 'train.dispatch',
                                ('train.pull',)) == \
        xplane_host.per_step(spans, 'train.dispatch')


def test_a_thread_beside_the_loop_is_read_a_turn():
    spans = xplane_host.spans(FIXTURE)
    builds = xplane_host.each(spans, 'prefetch.build')
    places = xplane_host.each(spans, 'prefetch.place')
    assert len(builds) == 3 and all(4.0 < b < 5.5 for b in builds)
    assert 2 <= len(places) <= 3 and all(1.0 < p < 2.5 for p in places)
    # only what lies within the whole steps' stretch counts, and a trace
    # with no whole step has no stretch
    assert xplane_host.each(spans, 'train.nothing') == []
    assert xplane_host.each([s for s in spans if s[0] != 'train.step'],
                            'prefetch.build') == []


def test_the_host_metrics_read_the_trace_through_a_child(tmp_path):
    profile = tmp_path / 'profile' / 'plugins' / 'profile' / 'now'
    profile.mkdir(parents=True)
    shutil.copy(FIXTURE, profile / 'host.xplane.pb')
    obs = {'profile_dir': str(tmp_path / 'profile')}
    spans = xplane_host.spans(FIXTURE)
    assert _read('trainer.input_wait_ms_per_step.train', obs) == \
        common.median(xplane_host.per_step(spans, 'train.input_wait'))
    assert obs['_host_spans'] == spans        # read once, kept
    assert _read('trainer.host_work_ms_per_step.train', obs) == \
        common.median(xplane_host.per_step(
            spans, 'train.step', ('train.input_wait', 'train.pull')))
    assert _read('trainer.dispatch_ms_per_step.train', obs) == \
        common.median(xplane_host.per_step(spans, 'train.dispatch'))
    assert _read('trainer.log_ms_per_step.train', obs) == \
        common.median(xplane_host.per_step(spans, 'train.log',
                                           ('train.pull',)))
    assert _read('trainer.prefetch_build_ms_per_step.train', obs) == \
        common.median(xplane_host.each(spans, 'prefetch.build'))
    assert _read('trainer.prefetch_place_ms_per_step.train', obs) == \
        common.median(xplane_host.each(spans, 'prefetch.place'))
    from readers import host_span_ms_each, host_span_ms_per_step as reader
    assert reader.read(obs, {'span': 'train.nothing'}) is None
    assert host_span_ms_each.read(obs, {'span': 'train.nothing'}) is None
    assert reader.read({'profile_dir': str(tmp_path / 'empty')},
                       {'span': 'train.step'}) is None


# ------------------------------------------------------ set-up's phases
def test_a_phase_is_read_off_sfts_line(tmp_path):
    log = tmp_path / 'sft.log'
    log.write_text(
        'I 10-05 11:08:53 skypilot_tpu.__main__:618] step 1/1000000 '
        'loss=12.1341 tokens/s=451 grad_norm=2.1138 input_wait_ms=0.313\n'
        'I 10-05 11:08:53 skypilot_tpu.__main__:629] setup phases: '
        'imports=0.021 runtime=7.443 build=2.527 state_init=3.000 '
        'load=0.036 first_batch=0.022 first_step=16.298 (trace=2.087 '
        'lower=0.465 compile_or_read=14.419) first_boundary=0.435 '
        'total=29.782\n')
    obs = {'log': str(log)}
    assert _read('device.setup_runtime_s', obs) == 7.443
    assert _read('trainer.setup_state_init_s', obs) == 3.0
    assert _read('trainer.setup_first_step_s', obs) == 16.298
    from readers import log_phase_s
    assert log_phase_s.read(obs, {'phase': 'trace'}) == 2.087
    assert log_phase_s.read(obs, {'phase': 'step'}) is None
    assert log_phase_s.read(obs, {'phase': 'total'}) == 29.782
    log.write_text('step 1/3 loss=1.0 tokens/s=5\n')     # the parent's log
    assert _read('device.setup_runtime_s', obs) is None
