"""End-to-end slice test (SURVEY.md §7.5): launch → queue → logs → exec →
stop/start → cancel → down, all on the local provider with real agents.

This is the fake-multi-host harness the reference lacks — its equivalent
coverage is cloud smoke tests (tests/test_smoke.py), which need real VMs.
"""
import time

import pytest
from click.testing import CliRunner

import skypilot_tpu as sky
from skypilot_tpu import core
from skypilot_tpu import execution
from skypilot_tpu import exceptions
from skypilot_tpu import resources as resources_lib
from skypilot_tpu import state
from skypilot_tpu.cli import cli

# Compile-heavy (JAX jit on the 1-core CPU host) or subprocess-driven:
pytestmark = pytest.mark.heavy


@pytest.fixture()
def local_env(tmp_path, monkeypatch):
    monkeypatch.setenv('SKYT_LOCAL_ROOT', str(tmp_path / 'local'))
    # SKYT_STATE_DIR is isolated by conftest already; reset the cached DB.
    state.reset_db_for_testing()
    yield
    for rec in state.get_clusters():
        try:
            core.down(rec['name'], purge=True)
        except Exception:  # pylint: disable=broad-except
            pass
    state.reset_db_for_testing()


def _local_task(name, run, num_nodes=1):
    t = sky.Task(name=name, run=run, num_nodes=num_nodes)
    t.set_resources(resources_lib.Resources(cloud='local'))
    return t


def _wait_terminal(cluster, jid, timeout=30):
    handle = state.get_cluster(cluster)['handle']
    return handle.head_client().wait_job(jid, timeout=timeout)


def test_launch_exec_queue_logs_down(local_env, capsys):
    t = _local_task('e2e', 'echo out rank=$SKYT_NODE_RANK '
                           'n=$SKYT_NUM_NODES', num_nodes=2)
    jid = execution.launch(t, cluster_name='c-e2e', detach_run=True)
    assert jid == 1
    job = _wait_terminal('c-e2e', jid)
    assert job['status'] == 'SUCCEEDED'
    assert len(job['gang']) == 2

    # num_nodes drove the host count.
    handle = state.get_cluster('c-e2e')['handle']
    assert handle.num_hosts == 2

    # queue
    jobs = core.queue('c-e2e')
    assert [j['job_id'] for j in jobs] == [1]

    # logs (rank-0 stream)
    rc = core.tail_logs('c-e2e', jid, follow=True)
    out = capsys.readouterr().out
    assert 'out rank=0 n=2' in out
    assert rc == 0

    # exec fast-path reuses the UP cluster
    jid2 = execution.exec(_local_task('e2', 'echo second'), 'c-e2e',
                          detach_run=True)
    assert _wait_terminal('c-e2e', jid2)['status'] == 'SUCCEEDED'

    # status
    recs = core.status(refresh=True)
    assert [(r['name'], r['status']) for r in recs] == [
        ('c-e2e', state.ClusterStatus.UP)]

    core.down('c-e2e')
    assert core.status() == []


def test_failed_job_reports_failed(local_env):
    t = _local_task('bad', 'exit 3')
    jid = execution.launch(t, cluster_name='c-bad', detach_run=True)
    job = _wait_terminal('c-bad', jid)
    assert job['status'] == 'FAILED'
    assert any(g['returncode'] == 3 for g in job['gang'])
    assert core.tail_logs('c-bad', jid, follow=True) == 1


def test_setup_runs_before_run(local_env):
    t = _local_task('with-setup', 'cat ~/marker.txt')
    t.setup = 'echo setup-was-here > ~/marker.txt'
    jid = execution.launch(t, cluster_name='c-setup', detach_run=True)
    job = _wait_terminal('c-setup', jid)
    assert job['status'] == 'SUCCEEDED'


def test_stop_start_cycle(local_env):
    t = _local_task('cyc', 'echo alive')
    execution.launch(t, cluster_name='c-cyc', detach_run=True)
    core.stop('c-cyc')
    assert state.get_cluster('c-cyc')['status'] == \
        state.ClusterStatus.STOPPED
    # exec on a stopped cluster fails
    with pytest.raises(exceptions.ClusterNotUpError):
        execution.exec(_local_task('x', 'echo x'), 'c-cyc',
                       detach_run=True)
    core.start('c-cyc')
    assert state.get_cluster('c-cyc')['status'] == state.ClusterStatus.UP
    jid = execution.exec(_local_task('x', 'echo back'), 'c-cyc',
                         detach_run=True)
    assert _wait_terminal('c-cyc', jid)['status'] == 'SUCCEEDED'


def test_cancel_running_job(local_env):
    t = _local_task('sleeper', 'sleep 60')
    jid = execution.launch(t, cluster_name='c-cxl', detach_run=True)
    handle = state.get_cluster('c-cxl')['handle']
    client = handle.head_client()
    deadline = time.time() + 20
    while time.time() < deadline:
        job = client.job(jid)
        if job['status'] == 'RUNNING':
            break
        time.sleep(0.3)
    assert core.cancel('c-cxl', [jid]) == [jid]
    job = client.job(jid)
    assert job['status'] == 'CANCELLED'


def test_autostop_roundtrip(local_env):
    execution.launch(_local_task('a', 'echo x'), cluster_name='c-as',
                     detach_run=True)
    core.autostop('c-as', 15, down=False)
    rec = state.get_cluster('c-as')
    assert rec['autostop'] == 15 and not rec['to_down']


def test_launch_reuses_up_cluster(local_env):
    t = _local_task('r1', 'echo one')
    execution.launch(t, cluster_name='c-reuse', detach_run=True)
    jid = execution.launch(_local_task('r2', 'echo two'),
                           cluster_name='c-reuse', detach_run=True)
    assert jid == 2  # same cluster, second job


def test_exec_missing_cluster_raises(local_env):
    with pytest.raises(exceptions.ClusterDoesNotExist):
        execution.exec(_local_task('x', 'echo'), 'nope', detach_run=True)


# ------------------------------------------------------------------- CLI
def test_cli_full_cycle(local_env):
    runner = CliRunner()
    res = runner.invoke(cli, ['launch', '-y', '-d', '-c', 'c-cli',
                              '--cloud', 'local', 'echo cli-ran'])
    assert res.exit_code == 0, res.output
    _wait_terminal('c-cli', 1)

    res = runner.invoke(cli, ['status'])
    assert 'c-cli' in res.output and 'UP' in res.output

    res = runner.invoke(cli, ['queue', 'c-cli'])
    assert 'SUCCEEDED' in res.output

    res = runner.invoke(cli, ['logs', 'c-cli', '1', '--no-follow'])
    assert 'cli-ran' in res.output

    res = runner.invoke(cli, ['exec', 'c-cli', '-d', 'echo more'])
    assert res.exit_code == 0, res.output

    res = runner.invoke(cli, ['autostop', 'c-cli', '-i', '5'])
    assert res.exit_code == 0, res.output

    res = runner.invoke(cli, ['down', '-y', 'c-cli'])
    assert res.exit_code == 0, res.output
    res = runner.invoke(cli, ['status'])
    assert 'No existing clusters' in res.output


@pytest.mark.integration
def test_docker_wrapped_task(local_env, tmp_path, monkeypatch, capsys):
    """`image_id: docker:<image>` runs setup AND run inside a
    container: the agent/backend bring the container up idempotently
    (pull + run -d) and exec the task scripts in it. A fake `docker`
    on PATH records the calls and executes the inner command on the
    host, so the full wrap is asserted without a docker daemon."""
    import os
    fake = tmp_path / 'bin'
    fake.mkdir()
    call_log = tmp_path / 'docker_calls.log'
    (fake / 'docker').write_text(
        '#!/usr/bin/env bash\n'
        f'echo "DOCKER $@" >> {call_log}\n'
        'cmd=$1; shift\n'
        'case "$cmd" in\n'
        '  image|container) exit 1;;\n'     # not present -> pull/run
        '  pull|run) exit 0;;\n'
        '  exec) shift; exec "$@";;\n'      # drop name; run on host
        'esac\n')
    (fake / 'docker').chmod(0o755)
    monkeypatch.setenv('PATH', f'{fake}:{os.environ["PATH"]}')

    t = sky.Task(name='dock', setup='echo setup-in-container',
                 run='echo run-in-container')
    t.set_resources(resources_lib.Resources(
        cloud='local', image_id='docker:ubuntu:22.04'))
    jid = execution.launch(t, cluster_name='c-dock', detach_run=True)
    job = _wait_terminal('c-dock', jid)
    assert job['status'] == 'SUCCEEDED', job

    calls = call_log.read_text()
    assert 'pull ubuntu:22.04' in calls
    assert 'run -d --name skyt-c-dock-r0 --network host' in calls
    assert 'exec skyt-c-dock-r0 bash' in calls
    core.tail_logs('c-dock', jid, follow=False)
    out = capsys.readouterr().out
    assert 'run-in-container' in out


def test_bare_image_id_still_gated():
    """A non-docker image_id still needs provisioner support: the
    local cloud lacks IMAGE_ID, so the feature gate reports it."""
    from skypilot_tpu import clouds
    local_cloud = clouds.Cloud.from_name('local')
    res = resources_lib.Resources(cloud='local',
                                  image_id='projects/x/images/y')
    assert clouds.CloudFeature.IMAGE_ID in \
        local_cloud.unsupported_features_for(res)
    res_docker = resources_lib.Resources(cloud='local',
                                         image_id='docker:img')
    assert clouds.CloudFeature.IMAGE_ID not in \
        local_cloud.unsupported_features_for(res_docker)


def test_cli_show_tpus():
    runner = CliRunner()
    res = runner.invoke(cli, ['show-tpus'])
    assert res.exit_code == 0, res.output
    assert 'tpu-v5e-16' in res.output.replace('v5litepod', 'tpu-v5e')
