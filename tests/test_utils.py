"""Tests for cross-cutting utils: command runners, config, subprocess."""

import pytest

from skypilot_tpu import exceptions
from skypilot_tpu import skyt_config
from skypilot_tpu.utils import command_runner
from skypilot_tpu.utils import subprocess_utils


class TestLocalProcessRunner:

    def test_run_basic(self, tmp_path):
        r = command_runner.LocalProcessRunner(str(tmp_path / 'host0'))
        assert r.run('true') == 0
        assert r.run('false') == 1

    def test_home_remap(self, tmp_path):
        host = tmp_path / 'host0'
        r = command_runner.LocalProcessRunner(str(host))
        code, out, _ = r.run('echo $HOME', require_outputs=True)
        assert code == 0
        assert out.strip() == str(host)

    def test_env_and_cwd(self, tmp_path):
        host = tmp_path / 'h'
        sub = host / 'subdir'
        sub.mkdir(parents=True)
        r = command_runner.LocalProcessRunner(str(host))
        code, out, _ = r.run('echo $FOO-$(pwd)', env={'FOO': 'bar'},
                             cwd=str(sub), require_outputs=True)
        assert out.strip() == f'bar-{sub}'

    def test_log_path(self, tmp_path):
        r = command_runner.LocalProcessRunner(str(tmp_path / 'h'))
        log = tmp_path / 'out.log'
        assert r.run('echo hello', log_path=str(log)) == 0
        assert 'hello' in log.read_text()

    def test_rsync_up_down(self, tmp_path):
        src = tmp_path / 'src'
        src.mkdir()
        (src / 'a.txt').write_text('data')
        host = tmp_path / 'h'
        r = command_runner.LocalProcessRunner(str(host))
        r.rsync(str(src) + '/', str(host / 'dst'), up=True)
        assert (host / 'dst' / 'a.txt').read_text() == 'data'
        r.rsync(str(host / 'dst') + '/', str(tmp_path / 'back'), up=False)
        assert (tmp_path / 'back' / 'a.txt').read_text() == 'data'

    def test_run_or_raise(self, tmp_path):
        r = command_runner.LocalProcessRunner(str(tmp_path / 'h'))
        assert r.run_or_raise('echo ok', 'should not fail').strip() == 'ok'
        with pytest.raises(exceptions.CommandError):
            r.run_or_raise('exit 3', 'expected failure')


class TestSSHCommandBuild:

    def test_ssh_base_options(self, tmp_path):
        key = tmp_path / 'key'
        key.write_text('')
        r = command_runner.SSHCommandRunner('10.0.0.1', 'ubuntu', str(key),
                                            ssh_control_name='abc')
        base = r._ssh_base()
        assert 'ssh' == base[0]
        assert '-i' in base and str(key) in base
        joined = ' '.join(base)
        assert 'StrictHostKeyChecking=no' in joined
        assert 'ControlMaster=auto' in joined

    def test_proxy_command(self, tmp_path):
        key = tmp_path / 'key'
        key.write_text('')
        r = command_runner.SSHCommandRunner(
            '10.0.0.1', 'ubuntu', str(key),
            ssh_proxy_command='corkscrew proxy 8080 %h %p')
        assert any('ProxyCommand=corkscrew' in a for a in r._ssh_base())


class TestConfig:

    def test_missing_file(self, tmp_path, monkeypatch):
        monkeypatch.setenv('SKYT_CONFIG', str(tmp_path / 'nope.yaml'))
        skyt_config.reload_for_testing()
        assert not skyt_config.loaded()
        assert skyt_config.get_nested(('gcp', 'project_id'), 'dflt') == 'dflt'

    def test_nested_get_set(self, tmp_path, monkeypatch):
        cfg = tmp_path / 'config.yaml'
        cfg.write_text('gcp:\n  project_id: proj-1\n  zone: us-central2-b\n')
        monkeypatch.setenv('SKYT_CONFIG', str(cfg))
        skyt_config.reload_for_testing()
        assert skyt_config.loaded()
        assert skyt_config.get_nested(('gcp', 'project_id')) == 'proj-1'
        assert skyt_config.get_nested(('gcp', 'missing'), 42) == 42
        updated = skyt_config.set_nested(('jobs', 'controller', 'cpus'), 8)
        assert updated['jobs']['controller']['cpus'] == 8
        # set_nested must not mutate the loaded config.
        assert skyt_config.get_nested(('jobs',)) is None


class TestSubprocessUtils:

    def test_run_in_parallel(self):
        out = subprocess_utils.run_in_parallel(lambda x: x * 2, [1, 2, 3])
        assert out == [2, 4, 6]

    def test_run_raises(self):
        with pytest.raises(exceptions.CommandError):
            subprocess_utils.run('exit 7')

    def test_kill_process_tree(self):
        import subprocess
        import time
        proc = subprocess.Popen(['bash', '-c', 'sleep 100 & sleep 100'])
        time.sleep(0.2)
        subprocess_utils.kill_process_tree(proc.pid)
        time.sleep(0.2)
        assert proc.poll() is not None


class TestTimeline:

    def test_enabled_tracks_env(self, monkeypatch):
        """SKYT_DEBUG is re-read per event: toggling it mid-process
        (long-lived servers, tests) enables/disables tracing without a
        restart — the old first-call cache pinned the initial value."""
        from skypilot_tpu.utils import timeline
        timeline.reset()
        monkeypatch.delenv('SKYT_DEBUG', raising=False)
        with timeline.Event('off-event'):
            pass
        assert not timeline._events
        monkeypatch.setenv('SKYT_DEBUG', '1')
        with timeline.Event('on-event'):
            pass
        assert [e['name'] for e in timeline._events] == \
            ['on-event', 'on-event']        # B + E pair
        monkeypatch.delenv('SKYT_DEBUG', raising=False)
        with timeline.Event('off-again'):
            pass
        assert len(timeline._events) == 2   # no new events
        timeline.reset()
        assert not timeline._events


def test_time_limit_hook_fails_a_hung_test_and_goes_on(tmp_path):
    """tests/conftest.py's per-test limit: a body that sleeps past its
    `time_limit` fails with the limit in its message, and the test
    after it still runs. The inner pytest loads this suite's conftest
    as a plugin, in a process of its own."""
    import os
    import subprocess
    import sys
    (tmp_path / 'test_hang.py').write_text(
        'import time, pytest\n'
        '@pytest.mark.time_limit(1)\n'
        'def test_hangs(): time.sleep(60)\n'
        'def test_after(): pass\n')
    env = dict(os.environ, PYTHONPATH=os.path.dirname(__file__))
    res = subprocess.run(
        [sys.executable, '-m', 'pytest', '-p', 'conftest', '-p',
         'no:cacheprovider', '-q', 'test_hang.py'],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=50)
    assert res.returncode == 1, res.stdout + res.stderr
    assert '1 failed, 1 passed' in res.stdout, res.stdout
    assert ('test_hang.py::test_hangs: call exceeded its time limit of '
            '1 s') in res.stdout, res.stdout


def test_control_plane_imports_stay_light():
    """An agent or a controller imports neither JAX nor the schema
    validator nor networkx: every `skyt launch` starts several of these
    processes, and jsonschema's format checkers alone cost 1.5 s to
    import (skypilot_tpu/utils/schemas.py, skypilot_tpu/dag.py import
    them where they are used)."""
    import subprocess
    import sys
    code = ('import sys\n'
            'import skypilot_tpu.runtime.agent\n'
            'import skypilot_tpu.jobs.controller\n'
            'heavy = {"jax", "jsonschema", "networkx"} & set(sys.modules)\n'
            'assert not heavy, heavy\n')
    res = subprocess.run([sys.executable, '-c', code], capture_output=True,
                         text=True, timeout=60)
    assert res.returncode == 0, res.stderr
