"""Test configuration.

Force JAX onto a virtual 8-device CPU mesh so multi-chip sharding paths are
exercised without TPUs (the fake multi-host harness the reference lacks —
SURVEY.md §4 implication). Must run before jax is imported anywhere.
"""
import os
import sys

# Force-set (not setdefault): the suite is written for the virtual CPU
# mesh whatever platform the environment names.
os.environ['JAX_PLATFORMS'] = 'cpu'
_flags = os.environ.get('XLA_FLAGS', '')
if '--xla_force_host_platform_device_count' not in _flags:
    os.environ['XLA_FLAGS'] = (
        _flags + ' --xla_force_host_platform_device_count=8').strip()

# Make the repo root importable when pytest is run from anywhere.
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# Persistent XLA compilation cache: the heavy tier's cost is almost
# entirely re-compiling the same debug-model programs in every test
# process. Subprocess-driven tests (agents, multihost selftests,
# local-provider jobs) inherit the variable, so they hit the same cache.
# The cpu_aot_loader 'machine feature' stderr warnings this produces
# are the loader's pseudo-feature check tripping on same-host artifacts.
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


def pytest_configure(config):
    config.addinivalue_line(
        'markers', 'integration: spawns real agent/controller subprocesses')
    config.addinivalue_line(
        'markers', 'heavy: compile-heavy JAX suites / long subprocess '
        'suites excluded from the fast tier (see format.sh)')


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
