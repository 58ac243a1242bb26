"""A training run's time has an owner from inside the program
(docs/observability.md "Device profiles"): every operation of the
compiled step stands under a pass, `optimizer` or the objective; the
step loop and the prefetcher write host spans into a step-window
profile; sft times the wait for its input and prints where
set-up went.
"""
import dataclasses
import importlib.util
import io
import logging
import os
import re
import threading
import time

import jax
import jax.numpy as jnp
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ------------------------------------------- (a) the step's op_names
def _step_op_names(preset: str) -> list:
    """Every `op_name` of the compiled train step of `preset` at a tiny
    shape, compiled from shapes alone (no state is made). The compiled
    module's and not the lowered one's: an operation in a called
    computation (a scanned layer, a loop's body) gets its caller's path,
    passes included, when the compiler inlines the call."""
    from skypilot_tpu.models import registry
    from skypilot_tpu.parallel import mesh as mesh_lib
    from skypilot_tpu.train import block_diffusion
    from skypilot_tpu.train import trainer
    model, cfg = registry.build(preset, 'auto')
    # One layer of each kind the preset has: the names are a layer's,
    # and a second layer of a kind only compiles them again.
    if preset in _KEPT_LAYERS:
        model = type(model)(dataclasses.replace(cfg, layers=tuple(
            cfg.layers[i] for i in _KEPT_LAYERS[preset])))
    else:
        model = type(model)(dataclasses.replace(cfg, n_layers=1))
    bd = block_diffusion.objective_of(model)
    mesh = mesh_lib.build_mesh(mesh_lib.MeshSpec(fsdp=1))
    tx = trainer.make_optimizer(trainer.TrainerConfig())
    rows, seq = 2, 64
    sample = jnp.zeros((rows, seq * (2 if bd else 1)), jnp.int32)
    _, init = trainer.logical_state_shardings(model, tx, mesh, sample)
    state = jax.eval_shape(init, jax.random.PRNGKey(0))
    ids = jax.ShapeDtypeStruct((rows, seq), jnp.int32)
    batch = {'tokens': ids} if bd else {'tokens': ids, 'targets': ids}
    step = trainer.make_train_step(model, tx, mesh, donate=False)
    text = step.lower(state, batch).compile().as_text()
    return re.findall(r'op_name="([^"]*)"', text)


_KEPT_LAYERS = {'debug-lfm2': (0, 1),       # conv + dense, attention + experts
                'debug-mellum2': (0, 3),    # a window layer, the full layer
                'debug-sdar': (0,)}


def _owner(op_name: str) -> str:
    parts = op_name.split('/')
    if any(p.startswith('transpose(') for p in parts):
        return 'backward'
    if any(p.startswith('jvp(') for p in parts):
        return 'forward'
    for scope in ('optimizer', 'bd_objective'):
        if scope in parts:
            return scope
    # The kernel wrapper's own integer work (a mask's iotas and
    # comparisons): no derivative passes through it, so no transform
    # wraps its path.
    if 'jit(_attention)' in parts:
        return 'attention'
    return ''


@pytest.mark.parametrize('preset', ['debug', 'debug-lfm2', 'debug-mellum2',
                                    'debug-sdar'])
def test_nothing_of_the_step_stands_under_bare_step_fn(preset):
    names = _step_op_names(preset)
    inside = [n for n in names if n.startswith('jit(step_fn)/')]
    assert len(inside) > 1000
    # The listed few outside `jit(step_fn)`: the step's arguments by
    # their own names (the step counter among them) and the bodies of
    # reducers and sorts, which carry the primitive's name alone.
    outside = {n for n in names if not n.startswith('jit(step_fn)/')}
    assert all(n.startswith(('state.', 'batch[')) or 'jit(' not in n
               for n in outside), sorted(outside)[:5]
    owners = {}
    for n in inside:
        owners.setdefault(_owner(n), []).append(n)
    assert not owners.get(''), sorted(set(owners['']))[:10]
    assert {'forward', 'backward', 'optimizer'} <= set(owners)
    bd = preset == 'debug-sdar'
    # The objective: `loss` wrapped by both passes, or the model's own.
    assert any('/jvp(loss)/' in n for n in inside) != bd
    assert any('/transpose(jvp(loss))/' in n for n in inside) != bd
    assert ('bd_objective' in owners) == bd
    # AdamW, the schedule, clipping and the global norm, by name.
    assert len(owners['optimizer']) > 500
    assert any(n.endswith('/optimizer/sqrt') for n in owners['optimizer'])
    # `_attention`'s kernels keep their name: the flash scope stands
    # outside the jitted wrapper and nothing is opened inside it.
    flash = {m.group(1) for n in inside for m in
             [re.search(r'/(flash_\w+)/jit\(_attention\)(/|$)', n)] if m}
    assert flash == {'debug': {'flash_full'},
                     'debug-lfm2': {'flash_full'},
                     'debug-mellum2': {'flash_full', 'flash_window'},
                     'debug-sdar': {'flash_block_diffusion'}}[preset]
    assert not any(re.search(r'jit\(_attention\)/.*(optimizer|loss)/', n)
                   for n in inside)


# ------------------------------------------- (b) the prefetcher's side
def _batches(n: int, sleep_s: float = 0.0):
    for i in range(n):
        if sleep_s:
            time.sleep(sleep_s)
        yield {'tokens': i}


def test_prefetcher_next_is_a_queue_pop_when_the_source_runs_ahead():
    """What lets sft time all of next(batches) as its input wait: with a
    batch staged the call blocks on nothing; with none it lasts as long
    as the source does."""
    from skypilot_tpu.train import prefetch
    ahead = prefetch.Prefetcher(_batches(100), depth=2)
    slow = prefetch.Prefetcher(_batches(4, sleep_s=0.05), depth=2)
    try:
        deadline = time.monotonic() + 5
        took = []
        for _ in range(3):
            while ahead.resident() < 2 and time.monotonic() < deadline:
                time.sleep(0.005)    # the consumer is the slow side
            t0 = time.perf_counter()
            next(ahead)
            took.append(time.perf_counter() - t0)
        assert min(took) < 0.005
        t0 = time.perf_counter()
        assert [b['tokens'] for b in slow] == [0, 1, 2, 3]
        assert 0.15 < time.perf_counter() - t0 < 2.0
    finally:
        ahead.close()
        slow.close()


def test_prefetcher_close_and_the_error_path_are_as_they_were():
    from skypilot_tpu.train import prefetch

    def broken():
        yield {'tokens': 0}
        time.sleep(0.05)
        raise ValueError('bad row')

    pf = prefetch.Prefetcher(broken(), depth=2)
    assert next(pf) == {'tokens': 0}
    with pytest.raises(ValueError, match='bad row'):
        next(pf)
    pf.close()
    pf.close()                                    # idempotent
    assert not pf._thread.is_alive()
    # close() unblocks a producer parked on the full queue
    parked = prefetch.Prefetcher(_batches(1000), depth=1)
    parked.close()
    assert not parked._thread.is_alive()


def test_prefetcher_fails_the_consumer_when_the_source_cannot_be_iterated():
    """iter() runs on the producer's thread too: what it raises reaches
    next() like a bad row, and the consumer does not block for good."""
    from skypilot_tpu.train import prefetch

    class NoIterator:
        def __iter__(self):
            raise OSError('no such data file')

    pf = prefetch.Prefetcher(NoIterator(), depth=2)
    try:
        with pytest.raises(OSError, match='no such data file'):
            next(pf)
    finally:
        pf.close()
    assert not pf._thread.is_alive()


# ---------------------- (c), (d) a three-step run under a profile
@pytest.fixture(scope='module')
def profiled_run(tmp_path_factory):
    """sft's log and profile directory of one three-step run of `debug`
    on the CPU, steps 1 and 2 profiled."""
    from skypilot_tpu.train import sft
    out = tmp_path_factory.mktemp('profiled_run')
    env = {'SKYT_PROFILE_DIR': str(out / 'profile'),
           'SKYT_PROFILE_START_STEP': '1', 'SKYT_PROFILE_NUM_STEPS': '2'}
    old = {k: os.environ.get(k) for k in env}
    os.environ.update(env)
    buf = io.StringIO()
    handler = logging.StreamHandler(buf)
    sft.logger.addHandler(handler)
    try:
        sft.main(['--model', 'debug', '--mesh', 'fsdp=1', '--steps', '3',
                  '--batch', '2', '--seq', '64', '--log-every', '1'])
    finally:
        sft.logger.removeHandler(handler)
        for k, v in old.items():
            os.environ.pop(k) if v is None else os.environ.update({k: v})
    return buf.getvalue(), str(out / 'profile')


def _xplane_host():
    spec = importlib.util.spec_from_file_location(
        'chipbench_xplane_host',
        os.path.join(REPO, 'chipbench', 'xplane_host.py'))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def test_a_profile_holds_the_step_loops_spans_nested_and_numbered(
        profiled_run):
    import glob
    _, profile_dir = profiled_run
    trace, = glob.glob(os.path.join(profile_dir, '**', '*.xplane.pb'),
                       recursive=True)
    spans = _xplane_host().spans(trace)
    steps = [s for s in spans if s[0] == 'train.step']
    assert [s[4] for s in steps] == [1, 2]
    loop_line = steps[0][1]
    for _, line, start, end, step_num in steps:
        assert line == loop_line
        inside = {n: (a, b) for n, t, a, b, k in spans
                  if t == line and k == step_num and n != 'train.step'}
        assert set(inside) == {'train.input_wait', 'train.dispatch',
                               'train.log', 'train.pull'}
        assert all(start <= a <= b <= end for a, b in inside.values())
        assert inside['train.input_wait'][1] <= inside['train.dispatch'][0]
        assert inside['train.dispatch'][1] <= inside['train.log'][0]
        log, pull = inside['train.log'], inside['train.pull']
        assert log[0] <= pull[0] <= pull[1] <= log[1]
    # The producer thread's spans stand on a line of their own, in no step.
    produced = [s for s in spans if s[0].startswith('prefetch.')]
    assert {s[0] for s in produced} == {'prefetch.build', 'prefetch.place'}
    assert {s[1] for s in produced}.isdisjoint({loop_line})
    assert all(s[4] is None for s in produced)


def test_sft_prints_where_setup_went_and_the_input_wait(profiled_run):
    text, _ = profiled_run
    line, = re.findall(r'setup phases: (.*)', text)
    assert re.fullmatch(
        r'imports=\S+ runtime=\S+ build=\S+ state_init=\S+ load=\S+ '
        r'first_batch=\S+ first_step=\S+ \(trace=\S+ lower=\S+ '
        r'compile_or_read=\S+\) first_boundary=\S+ total=\S+', line)
    inner = re.search(r'\((.*)\)', line).group(1)
    parts = dict(kv.split('=') for kv in
                 line.replace(f'({inner}) ', '').split())
    total = float(parts.pop('total'))
    assert abs(sum(float(v) for v in parts.values()) - total) < 0.05
    stages = dict(kv.split('=') for kv in inner.split())
    # the first step traced, lowered and compiled (or read) its program
    assert float(stages['trace']) > 0 and float(stages['lower']) > 0
    assert sum(float(v) for v in stages.values()) <= \
        float(parts['first_step']) + 0.05
    # it comes once, after the first step line
    assert text.index('step 1/3 ') < text.index('setup phases:') < \
        text.index('step 2/3 ')
    lines = re.findall(r'step \d/3 loss=\S+ tokens/s=\d+ (.*)', text)
    assert len(lines) == 3
    assert all(re.fullmatch(r'grad_norm=\d+\.\d{4} input_wait_ms=\d+\.\d{3}',
                            rest) for rest in lines)


@pytest.mark.parametrize('prefetch_depth', [0, 2])
def test_sft_input_wait_is_the_slow_sources_time(tmp_path, prefetch_depth):
    """One path with a prefetcher or none: sft times next(batches). A
    source that takes 60 ms a batch, before steps of a few ms, holds the
    loop about that long a step either way."""
    from skypilot_tpu.train import sft
    data = tmp_path / 'rows.jsonl'
    data.write_text('{"tokens": ' + str(list(range(200))) + '}\n')
    real = sft.jsonl_batches

    def slow(*args, **kwargs):
        for batch in real(*args, **kwargs):
            time.sleep(0.06)
            yield batch

    buf = io.StringIO()
    handler = logging.StreamHandler(buf)
    sft.logger.addHandler(handler)
    sft.jsonl_batches = slow
    try:
        sft.main(['--model', 'debug', '--mesh', 'fsdp=1', '--steps', '6',
                  '--batch', '2', '--seq', '64', '--log-every', '1',
                  '--prefetch', str(prefetch_depth), '--data', str(data)])
    finally:
        sft.jsonl_batches = real
        sft.logger.removeHandler(handler)
    waits = [float(x) for x in
             re.findall(r'input_wait_ms=(\S+)', buf.getvalue())]
    assert len(waits) == 6 and waits[0] >= 55.0
    if prefetch_depth:
        # The first step's compile lets the producer run ahead by its
        # depth and one: once that is used up the loop is held again, for
        # the source's time less a step's.
        assert waits[-1] >= 20.0
    else:
        assert all(w >= 55.0 for w in waits)


# ------------------------------------ (e) the compile stages' seconds
def test_compile_cache_snapshot_has_trace_and_lowering_seconds():
    from skypilot_tpu.utils import compile_cache
    compile_cache.configure()
    time.sleep(0.3)      # no real trace ended inside the made-up ones
    before = compile_cache.snapshot()
    assert {'trace_seconds', 'lower_seconds'} <= set(before)
    # A function traced inside another's trace reports first, and the
    # outer duration holds it: the interval counts once.
    for seconds in (0.1, 0.25):
        jax.monitoring.record_event_duration_secs(
            '/jax/core/compile/jaxpr_trace_duration', seconds)
    jax.monitoring.record_event_duration_secs(
        '/jax/core/compile/jaxpr_to_mlir_module_duration', 0.5)
    jax.monitoring.record_event_duration_secs(
        '/jax/core/compile/some_other_duration', 9.0)
    after = compile_cache.snapshot()
    assert after['trace_seconds'] == pytest.approx(
        before['trace_seconds'] + 0.25, abs=2e-3)
    assert after['lower_seconds'] == pytest.approx(
        before['lower_seconds'] + 0.5, abs=2e-3)
    assert after['compile_seconds'] == before['compile_seconds']
    # Only a trace of the same thread can stand inside another: one that
    # another thread made meanwhile is counted beside it.
    other = threading.Thread(
        target=jax.monitoring.record_event_duration_secs,
        args=('/jax/core/compile/jaxpr_trace_duration', 0.2))
    other.start()
    other.join()
    jax.monitoring.record_event_duration_secs(
        '/jax/core/compile/jaxpr_trace_duration', 0.3)
    assert compile_cache.snapshot()['trace_seconds'] == pytest.approx(
        before['trace_seconds'] + 0.2 + 0.3, abs=2e-3)
