"""Managed jobs whose controller is itself a job on a controller cluster
(reference: the jobs-controller VM): it outlives the client, recovers a
preempted job cluster, and translates a local workdir. The in-process
controller's tests are in tests/test_managed_jobs.py, whose fixture and
helper these use.
"""
import time

import pytest

import skypilot_tpu as sky
from skypilot_tpu import core
from skypilot_tpu import resources as resources_lib
from skypilot_tpu import state
from skypilot_tpu.jobs import core as jobs_core
from skypilot_tpu.jobs import state as jobs_state

from test_managed_jobs import _local_task
from test_managed_jobs import jobs_env  # noqa: unused-import (fixture)

pytestmark = pytest.mark.integration


@pytest.fixture()
def cluster_controller_env(jobs_env, tmp_path, monkeypatch):
    """Controller-on-cluster mode with local-provider controller
    resources (reference: jobs-controller VM)."""
    cfg = tmp_path / 'skyt_config.yaml'
    cfg.write_text(
        'jobs:\n  controller:\n    resources:\n      cloud: local\n')
    monkeypatch.setenv('SKYT_CONFIG', str(cfg))
    from skypilot_tpu import skyt_config
    skyt_config.reload_for_testing()
    yield
    skyt_config.reload_for_testing()


def test_managed_job_cluster_controller_survives_client(
        cluster_controller_env):
    """Controller runs as a job on the controller cluster: no client pid
    anywhere in the job row, so nothing dies with the client
    (reference: sky/jobs/core.py:30-137 controller-VM launch)."""
    t = _local_task('mj-vm', 'echo via-controller-cluster')
    jid = jobs_core.launch(t, retry_until_up=False,
                           controller='cluster')
    job = jobs_state.get_job(jid)
    assert job['controller_cluster'] == 'skyt-jobs-controller'
    assert not job.get('controller_pid')
    # queue() must not declare a pid-less cluster controller dead.
    assert all(r['status'] != jobs_state.ManagedJobStatus.FAILED_CONTROLLER
               for r in jobs_core.queue())
    job = jobs_core.wait(jid, timeout=150)
    assert job['status'] == jobs_state.ManagedJobStatus.SUCCEEDED
    # The controller cluster itself is alive and reusable.
    assert state.get_cluster('skyt-jobs-controller') is not None


def test_managed_job_cluster_controller_recovers_preemption(
        cluster_controller_env):
    """Full recovery semantics through the cluster-hosted controller:
    kill the job cluster mid-run; the controller (itself a cluster job,
    with the client idle) relaunches it."""
    t = _local_task('mj-vmrec', 'sleep 4 && echo done')
    jid = jobs_core.launch(t, retry_until_up=False,
                           controller='cluster')
    cluster = f'mj-vmrec-{jid}'
    deadline = time.time() + 60
    while time.time() < deadline:
        job = jobs_state.get_job(jid)
        if job['status'] == jobs_state.ManagedJobStatus.RUNNING and \
                state.get_cluster(cluster) is not None:
            break
        time.sleep(0.2)
    else:
        pytest.fail(f'job never RUNNING: {jobs_state.get_job(jid)}')
    core.down(cluster, purge=True)
    job = jobs_core.wait(jid, timeout=150)
    assert job['status'] == jobs_state.ManagedJobStatus.SUCCEEDED
    assert job['recovery_count'] >= 1


def test_cluster_controller_translates_workdir_and_recovers(
        cluster_controller_env, tmp_path):
    """The headline file-mount-translation scenario (reference:
    sky/utils/controller_utils.py:567 called from sky/jobs/core.py:78):
    a managed job with a client-local workdir is preempted AFTER the
    client's filesystem is gone; recovery must rebuild the workdir from
    the translated bucket, not the client path."""
    import shutil

    import yaml as yaml_lib

    workdir = tmp_path / 'client-workdir'
    workdir.mkdir()
    (workdir / 'marker.txt').write_text('from-client-workdir\n')
    t = sky.Task(name='mj-wd', run='sleep 8 && cat marker.txt',
                 workdir=str(workdir))
    t.set_resources(resources_lib.Resources(cloud='local'))
    jid = jobs_core.launch(t, retry_until_up=False, controller='cluster')

    # Submission already rewrote the persisted DAG: no client paths.
    job = jobs_state.get_job(jid)
    with open(job['dag_yaml'], encoding='utf-8') as f:
        cfgs = list(yaml_lib.safe_load_all(f))
    assert len(cfgs) == 1 and 'workdir' not in cfgs[0]
    assert str(workdir) not in str(cfgs[0])
    mounts = cfgs[0]['file_mounts']
    wd_spec = mounts['skyt_workdir']
    assert wd_spec['source'].startswith('local://skyt-workdir-')

    # The client filesystem leaves the picture entirely.
    shutil.rmtree(workdir)

    cluster = f'mj-wd-{jid}'
    # Generous: the controller + runtime agents are subprocesses that
    # may each pay cold XLA compiles on a cold cache (observed: the
    # whole scenario takes ~6 min cold vs ~30 s warm).
    deadline = time.time() + 240
    while time.time() < deadline:
        job = jobs_state.get_job(jid)
        if job['status'] == jobs_state.ManagedJobStatus.RUNNING and \
                state.get_cluster(cluster) is not None:
            break
        time.sleep(0.2)
    else:
        pytest.fail(f'job never RUNNING: {jobs_state.get_job(jid)}')
    core.down(cluster, purge=True)  # simulated preemption

    job = jobs_core.wait(jid, timeout=600)
    # `cat marker.txt` ran in ~/skyt_workdir rebuilt from the bucket —
    # with the client dir deleted, success is only possible via the
    # translated storage mount.
    assert job['status'] == jobs_state.ManagedJobStatus.SUCCEEDED
    assert job['recovery_count'] >= 1
    # Ephemeral translation bucket cleaned up with the job.
    assert state.get_storage(wd_spec['name']) is None
