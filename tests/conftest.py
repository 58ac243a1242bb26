"""Test configuration.

Force JAX onto a virtual 8-device CPU mesh so multi-chip sharding paths are
exercised without TPUs (the fake multi-host harness the reference lacks —
SURVEY.md §4 implication). Must run before jax is imported anywhere.
"""
import os
import signal
import sys
import threading

# Force-set (not setdefault): the suite is written for the virtual CPU
# mesh whatever platform the environment names.
os.environ['JAX_PLATFORMS'] = 'cpu'
_flags = os.environ.get('XLA_FLAGS', '')
if '--xla_force_host_platform_device_count' not in _flags:
    os.environ['XLA_FLAGS'] = (
        _flags + ' --xla_force_host_platform_device_count=8').strip()

# Make the repo root importable when pytest is run from anywhere.
_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)

# Where the environment forbids writing bytecode (this image sets
# PYTHONDONTWRITEBYTECODE), every process compiles every module it
# imports from source: 2 s of each `import jax`, in every worker and in
# each of the servers, trainers, agents and controllers the drills
# start. Keep the bytecode in the checkout, beside the compile cache,
# for this process and its children; nothing outside the tree is written.
if sys.dont_write_bytecode and sys.pycache_prefix is None:
    sys.dont_write_bytecode = False
    sys.pycache_prefix = os.path.join(_REPO, '.pycache')
    os.environ.pop('PYTHONDONTWRITEBYTECODE', None)
    os.environ['PYTHONPYCACHEPREFIX'] = sys.pycache_prefix

# Persistent XLA compilation cache: the heavy tier's cost is almost
# entirely re-compiling the same debug-model programs in every test
# process. Subprocess-driven tests (agents, multihost selftests,
# local-provider jobs) inherit the variable, so they hit the same cache.
# JAX writes to that cache only programs that took a second to compile.
# The debug model's programs take less, and most tests build a fresh
# engine, so every one would be compiled again by every engine, worker
# and child process. The threshold is JAX's own setting, and is set
# only for the tests: the program's entry points keep JAX's default.
os.environ.setdefault('JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS', '0')
# Every read from that cache logs two 2 KB error lines from XLA's CPU
# loader (a pseudo-feature check that trips on same-host artifacts).
# A child whose output is a pipe nobody drains blocks for good once
# they fill it, so XLA's C++ logging is off unless the caller asks.
os.environ.setdefault('TF_CPP_MIN_LOG_LEVEL', '3')

from skypilot_tpu.utils import compile_cache  # noqa: E402

compile_cache.configure()

import pytest  # noqa: E402


@pytest.fixture()
def tmp_state_dir(tmp_path, monkeypatch):
    """Redirect the framework's state directory (~/.skypilot_tpu) to tmp."""
    monkeypatch.setenv('SKYT_STATE_DIR', str(tmp_path / 'state'))
    # Reset cached module-level state DB handles between tests.
    import skypilot_tpu.state as state
    state.reset_db_for_testing()
    yield tmp_path / 'state'
    state.reset_db_for_testing()


@pytest.fixture()
def one_device_children(monkeypatch):
    """The processes this test starts get one virtual CPU device, not
    this suite's eight: a replica, a trainer or an example that is not
    about a mesh compiles and starts faster with one, beside five
    other workers. This process has read the flag and keeps its eight."""
    import jax
    jax.devices()
    monkeypatch.setenv('XLA_FLAGS',
                       '--xla_force_host_platform_device_count=1')


# The three markers. `heavy` and `integration` choose format.sh's fast
# tier and nothing else: the driver's tier-1 command does not read them.
# `slow` is the one the driver honours (`-m 'not slow'`): a test that
# carries it leaves tier-1 and runs under `format.sh --full` only.
def pytest_configure(config):
    config.addinivalue_line(
        'markers', 'integration: spawns real agent/controller '
        'subprocesses; out of format.sh\'s fast tier, in tier-1')
    config.addinivalue_line(
        'markers', 'heavy: compile-heavy JAX suites / long subprocess '
        'suites; out of format.sh\'s fast tier, in tier-1')
    config.addinivalue_line(
        'markers', 'slow: left out of the driver\'s tier-1 '
        '(-m "not slow"); format.sh --full still runs it')
    config.addinivalue_line(
        'markers', 'time_limit(seconds): this test\'s own limit for each '
        f'of set-up, call and teardown (default {_TIME_LIMIT_S} s); '
        'state the reason beside it')


# `--dist loadfile` hands whole files to the workers in collection order,
# which is the alphabet, so a long file late in it is the tail of the run:
# five workers stand idle while the sixth works through it. These are the
# files that take longest (seconds in a six-worker run, PR 26's CHANGES.md
# entry); they are handed out first, longest first, and the quick files
# fill the gaps behind them. A file that grows past the shortest of them
# joins the list.
_LONGEST_FIRST = (
    'test_infer_weights.py', 'test_spec_decode.py', 'test_parallel.py',
    'test_chaos_rollout.py', 'test_infer.py', 'test_paged_engine.py',
    'test_multilora.py', 'test_kv_ragged.py', 'test_chaos_replica.py',
    'test_managed_jobs.py', 'test_quant.py', 'test_ops_dispatch.py',
    'test_model_train.py', 'test_managed_jobs_cluster_controller.py',
    'test_weight_swap.py', 'test_chaos_front_door.py', 'test_lora_fleet.py',
    'test_serve.py', 'test_engine_overlap.py', 'test_chaos_training.py',
)


def pytest_collection_modifyitems(items):
    rank = {name: i for i, name in enumerate(_LONGEST_FIRST)}
    items.sort(key=lambda item: rank.get(item.path.name, len(rank)))


# A time limit of its own for every test: one that hangs fails by name
# and the run goes on, where it would otherwise hold its xdist worker
# (and, under --dist loadfile, the rest of its file) until the run's
# own clock cuts everything.
_TIME_LIMIT_S = 120


def _time_limited(item, phase):
    marker = item.get_closest_marker('time_limit')
    limit = float(marker.args[0]) if marker else _TIME_LIMIT_S

    def _expired(signum, frame):
        del signum, frame
        # Raised from the frame that was running, so the traceback says
        # where the test was held.
        pytest.fail(f'{item.nodeid}: {phase} exceeded its time limit of '
                    f'{limit:g} s')

    # Signals reach the main thread only; xdist workers and plain pytest
    # run tests there. Anywhere else the test runs unlimited, as before.
    armed = threading.current_thread() is threading.main_thread()
    if armed:
        previous = signal.signal(signal.SIGALRM, _expired)
        signal.setitimer(signal.ITIMER_REAL, limit)
    try:
        return (yield)
    finally:
        if armed:
            signal.setitimer(signal.ITIMER_REAL, 0)
            signal.signal(signal.SIGALRM, previous)


@pytest.hookimpl(wrapper=True)
def pytest_runtest_setup(item):
    return (yield from _time_limited(item, 'set-up'))


@pytest.hookimpl(wrapper=True)
def pytest_runtest_call(item):
    return (yield from _time_limited(item, 'call'))


@pytest.hookimpl(wrapper=True)
def pytest_runtest_teardown(item):
    return (yield from _time_limited(item, 'teardown'))


@pytest.fixture(scope='session', autouse=True)
def _reap_orphaned_test_agents(tmp_path_factory):
    """Kill pytest-spawned runtime agents left running at session end.
    Some kill -9 scenarios (dead-controller tests) can leave an agent
    polling forever — 0.3% CPU + ~200MB each on the 1-core host.

    Two precise rules (so concurrent pytest sessions never kill each
    other's live agents):
      * any agent whose --config lives under THIS session's basetemp —
        every cluster of ours is down by now, so a survivor is an
        orphan (pytest retains the last 3 basetemps, so "config file
        still exists" does NOT imply live);
      * any agent whose --config file no longer exists (stale leftover
        from an older, rotated-out session).
    """
    yield
    import re
    import signal as sig
    import subprocess
    base = str(tmp_path_factory.getbasetemp().resolve())
    try:
        out = subprocess.run(['ps', '-eo', 'pid,args'], text=True,
                             capture_output=True, timeout=10).stdout
    except Exception:  # pylint: disable=broad-except
        return
    for line in out.splitlines():
        m = re.search(r'^\s*(\d+)\s+.*skypilot_tpu\.runtime\.agent'
                      r'\s+--config\s+(\S+)', line)
        if not m:
            continue
        cfg_path = m.group(2)
        ours = os.path.realpath(cfg_path).startswith(base + os.sep)
        if ours or not os.path.exists(cfg_path):
            try:
                os.kill(int(m.group(1)), sig.SIGKILL)
            except (ProcessLookupError, PermissionError):
                pass
