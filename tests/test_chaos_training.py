"""Chaos suite, training: preemption-safe exits of sft, the PREEMPTED
status mapping, and the gang watchdog that recovers a hung rank
(docs/robustness.md).

The drills run the REAL LB -> server -> engine HTTP stack on the CPU;
a death is a SIGKILLed subprocess, not a mock. Shared helpers:
tests/chaos_helpers.py.
"""
import os
import signal
import subprocess
import sys
import threading
import time

import pytest

# Fixtures, used by name:
from chaos_helpers import _reset_faults  # noqa: unused-import

pytestmark = [pytest.mark.heavy,
              pytest.mark.usefixtures('one_device_children')]


# ================================================ preemption guard modes
def test_preemption_guard_immediate_exit_during_startup():
    """Startup phase (immediate=True): SIGTERM exits with
    EXIT_CODE_PREEMPTED on the spot — no step boundary is coming for
    minutes during weight streaming / first compile, and burning the
    preemption grace window there ends in SIGKILL + FAILED.
    cooperative() then hands the exit back to the step loop."""
    from skypilot_tpu.runtime.job_lib import EXIT_CODE_PREEMPTED
    from skypilot_tpu.train import checkpoint as ckpt_lib

    if threading.current_thread() is not threading.main_thread():
        pytest.skip('signal handlers need the main thread')
    guard = ckpt_lib.PreemptionGuard(immediate=True)
    try:
        with pytest.raises(SystemExit) as exc:
            os.kill(os.getpid(), signal.SIGTERM)
            deadline = time.time() + 10
            while time.time() < deadline:   # handler needs a bytecode
                time.sleep(0.001)           # boundary on this thread
            pytest.fail('immediate guard never fired')
        assert exc.value.code == EXIT_CODE_PREEMPTED
        assert guard.requested and guard.signum == signal.SIGTERM
    finally:
        guard.restore()

    guard = ckpt_lib.PreemptionGuard(immediate=True)
    try:
        guard.cooperative()   # step loop started: flag-only from here
        os.kill(os.getpid(), signal.SIGTERM)
        deadline = time.time() + 10
        while not guard.requested and time.time() < deadline:
            time.sleep(0.001)
        assert guard.requested
    finally:
        guard.restore()


# ========================================== preemption-safe training exit
# 17 s here: two sft processes, each 8 s to its first step.
# Measured on an idle 8-core box; the driver's is some three times slower.
@pytest.mark.time_limit(300)
@pytest.mark.integration
def test_sft_preemption_checkpoint_and_resume(tmp_path):
    """SIGTERM mid-run: sft checkpoints at the next step boundary,
    waits for the async save, and exits EXIT_CODE_PREEMPTED; a rerun
    resumes from that step instead of step 0."""
    from skypilot_tpu.runtime.job_lib import EXIT_CODE_PREEMPTED
    ckpt_dir = tmp_path / 'ckpt'
    env = dict(os.environ, JAX_PLATFORMS='cpu')
    args = [sys.executable, '-m', 'skypilot_tpu.train.sft',
            '--model', 'debug', '--steps', '100000',
            '--batch', '1', '--seq', '16',
            '--checkpoint-dir', str(ckpt_dir),
            '--checkpoint-every', '5', '--log-every', '5']
    proc = subprocess.Popen(args, env=env, stdout=subprocess.PIPE,
                            stderr=subprocess.STDOUT, text=True)
    try:
        # Wait until at least one periodic checkpoint landed.
        deadline = time.time() + 300
        while time.time() < deadline:
            if proc.poll() is not None:
                out = proc.stdout.read()
                raise AssertionError(
                    f'sft died early rc={proc.returncode}:\n{out[-2000:]}')
            steps = [int(p.name) for p in ckpt_dir.glob('[0-9]*')
                     if p.name.isdigit()]
            if steps:
                break
            time.sleep(0.5)
        else:
            raise AssertionError('no checkpoint appeared')
        proc.send_signal(signal.SIGTERM)
        out, _ = proc.communicate(timeout=120)
        assert proc.returncode == EXIT_CODE_PREEMPTED, out[-2000:]
        assert 'preemption requested' in out
        saved_steps = sorted(int(p.name) for p in ckpt_dir.glob('[0-9]*')
                             if p.name.isdigit())
        assert saved_steps, out[-2000:]
        resume_at = saved_steps[-1]

        # Resume run: must start from the preemption checkpoint.
        args2 = list(args)
        args2[args2.index('--steps') + 1] = str(resume_at + 3)
        out2 = subprocess.run(args2, env=env, stdout=subprocess.PIPE,
                              stderr=subprocess.STDOUT, text=True,
                              timeout=300, check=True).stdout
        assert f'resumed from step {resume_at}' in out2
    finally:
        if proc.poll() is None:
            proc.kill()


def test_preempted_exit_code_maps_to_preempted_status(tmp_path,
                                                      monkeypatch):
    """runtime layer: a gang rank exiting EXIT_CODE_PREEMPTED is not a
    failure — the job lands in PREEMPTED (which the managed-jobs
    controller recovers) instead of FAILED."""
    monkeypatch.setenv('SKYT_AGENT_HOME', str(tmp_path))
    from skypilot_tpu.runtime import job_lib
    jid = job_lib.add_job('prejob', {'num_nodes': 2})
    job_lib.gang_mark(jid, 0, 'DONE', 0)
    job_lib.gang_mark(jid, 1, 'DONE', job_lib.EXIT_CODE_PREEMPTED)
    assert not job_lib.gang_any_failed(jid)
    assert job_lib.gang_any_preempted(jid)
    assert job_lib.gang_all_done(jid)
    # A real nonzero exit still reads as failure.
    job_lib.gang_mark(jid, 0, 'DONE', 1)
    assert job_lib.gang_any_failed(jid)


def test_preempted_wins_over_collateral_rank_failure(tmp_path,
                                                     monkeypatch):
    """Report-ordering race: when a preemption SIGTERMs the gang, the
    non-signalled ranks' collectives abort with real nonzero codes and
    usually report FIRST. The later rc=75 must still flip the job to
    PREEMPTED (the recovery signal), whichever order reports land."""
    monkeypatch.setenv('SKYT_AGENT_HOME', str(tmp_path))
    from skypilot_tpu.runtime import job_lib
    from skypilot_tpu.runtime import server as rt_server
    head = rt_server.HeadState(rt_server.ClusterConfig(
        {'cluster_name': 'c', 'num_nodes': 2,
         'ips': ['127.0.0.1', '127.0.0.2']}))
    # Order A: collateral failure first, cooperative exit second.
    jid = head.submit({'name': 'j1', 'run': 'x', 'num_nodes': 2})
    head.report(jid, 1, 'done', 1)
    assert job_lib.get_job(jid)['status'] is job_lib.JobStatus.FAILED
    head.report(jid, 0, 'done', job_lib.EXIT_CODE_PREEMPTED)
    assert job_lib.get_job(jid)['status'] is \
        job_lib.JobStatus.PREEMPTED
    # Order B: cooperative exit first; a later collateral failure must
    # not downgrade PREEMPTED back to FAILED.
    jid2 = head.submit({'name': 'j2', 'run': 'x', 'num_nodes': 2})
    head.report(jid2, 0, 'done', job_lib.EXIT_CODE_PREEMPTED)
    head.report(jid2, 1, 'done', 1)
    assert job_lib.get_job(jid2)['status'] is \
        job_lib.JobStatus.PREEMPTED
    # No 75 anywhere: plain failure, no recovery.
    jid3 = head.submit({'name': 'j3', 'run': 'x', 'num_nodes': 2})
    head.report(jid3, 0, 'done', 1)
    head.report(jid3, 1, 'done', 0)
    assert job_lib.get_job(jid3)['status'] is job_lib.JobStatus.FAILED


# ===================================== gang hang watchdog recovery drill
# slow: 35 s in a six-worker run, and its timeline is the drill: two
# incarnations of a two-rank gang (four sft processes, 8 s each to their
# first step), 120 steps held to 0.1 s so that rank 0 is still running when
# the watchdog has confirmed rank 1's hang, then the relaunch.
@pytest.mark.slow
@pytest.mark.integration
def test_chaos_gang_hang_watchdog_recovery(tmp_path, tmp_state_dir,
                                           monkeypatch):
    """THE training-plane acceptance drill (docs/observability.md
    "Training plane"): one rank of a REAL 2-rank gang wedges via
    SKYT_FAULTS=train.step=hang -> the head agent's gang watchdog
    confirms the hang and escalates the cluster job to HUNG -> every
    rank has dumped a postmortem bundle (the hung rank via its
    sentinel, the survivor via the SIGTERM guard) -> the managed-jobs
    controller recovers (kill gang, relaunch) -> sft RESUMES from its
    preemption-era checkpoint -> SUCCEEDED, zero manual intervention.
    """
    import json
    import pathlib

    import skypilot_tpu as sky
    from skypilot_tpu import resources as resources_lib
    from skypilot_tpu import state
    from skypilot_tpu.jobs import core as jobs_core
    from skypilot_tpu.jobs import state as jobs_state
    from skypilot_tpu.train import postmortem as postmortem_lib

    drill = tmp_path / 'drill'
    drill.mkdir()
    pm_dir = tmp_path / 'postmortems'   # durable across the relaunch
    monkeypatch.setenv('SKYT_LOCAL_ROOT', str(tmp_path / 'local'))
    monkeypatch.setenv('SKYT_JOBS_CHECK_GAP', '0.3')
    monkeypatch.setenv('SKYT_JOBS_PREEMPTION_GRACE', '1')
    # Fast watchdog thresholds (agents inherit this env at provision).
    monkeypatch.setenv('SKYT_WATCHDOG_MIN_S', '3')
    monkeypatch.setenv('SKYT_WATCHDOG_FACTOR', '2')
    monkeypatch.setenv('SKYT_WATCHDOG_CONFIRM', '2')
    monkeypatch.setenv('SKYT_WATCHDOG_INTERVAL_S', '0.5')
    monkeypatch.setenv('SKYT_WATCHDOG_POLL_S', '0.3')
    monkeypatch.setenv('SKYT_HEARTBEAT_INTERVAL_S', '0.1')
    state.reset_db_for_testing()
    jobs_state.reset_db_for_testing()

    # Rank 1 arms the hang fault ONCE (marker-guarded, so the
    # recovered incarnation runs clean); a small latency fault on
    # every step keeps rank 0 running long enough to be SIGTERM'd by
    # the HUNG kill (exercising its preempt-bundle path). The JAX
    # coordinator triplet is cleared: on the CPU backend each rank is
    # its own single-process jax runtime (multiprocess CPU collectives
    # are unimplemented in jax 0.4.x — the watchdog plane is what is
    # under test).
    run_cmd = f'''
RANK="$SKYT_NODE_RANK"
if [ "$RANK" = "1" ] && [ ! -f "{drill}/armed" ]; then
  touch "{drill}/armed"
  export SKYT_FAULTS="$SKYT_FAULTS;train.step=hang,arg=600,after=4"
fi
env SKYT_NUM_NODES=1 JAX_COORDINATOR_ADDRESS= JAX_NUM_PROCESSES= \\
    JAX_PROCESS_ID= \\
  {sys.executable} -m skypilot_tpu.train.sft --model debug \\
  --steps 120 --batch 1 --seq 16 --prefetch 0 \\
  --checkpoint-dir "{drill}/ckpt/rank-$RANK" --checkpoint-every 2 \\
  --log-every 10 2>&1 | tee -a "{drill}/rank-$RANK.out"
exit "${{PIPESTATUS[0]}}"
'''
    t = sky.Task(name='hangdrill', run=run_cmd, num_nodes=2,
                 envs={'SKYT_POSTMORTEM_DIR': str(pm_dir),
                       'SKYT_FAULTS': 'train.step=latency,arg=0.1',
                       'JAX_PLATFORMS': 'cpu'})
    t.set_resources(resources_lib.Resources(cloud='local'))

    jid = jobs_core.launch(t, retry_until_up=False)
    saw_recovering = False
    deadline = time.time() + 900
    job = None
    try:
        while time.time() < deadline:
            job = jobs_state.get_job(jid)
            if job['status'] == jobs_state.ManagedJobStatus.RECOVERING:
                saw_recovering = True
            if job['status'].is_terminal():
                break
            time.sleep(0.1)
        else:
            pytest.fail(f'drill never finished: {job}')

        out1 = (drill / 'rank-1.out').read_text() \
            if (drill / 'rank-1.out').exists() else ''
        assert job['status'] == jobs_state.ManagedJobStatus.SUCCEEDED, \
            (job, out1[-2000:])
        assert job['recovery_count'] >= 1
        assert saw_recovering

        # Bundles from EVERY rank, durable across the relaunch: the
        # hung rank's sentinel bundle plus the survivor's SIGTERM
        # (preempt) bundle — each with stacks + spans + train state.
        bundles = postmortem_lib.list_bundles(root=str(pm_dir))
        reasons = {(b.get('rank'), b.get('reason')) for b in bundles}
        assert (1, 'hang') in reasons, bundles
        assert (0, 'preempt') in reasons, bundles
        for b in bundles:
            assert {'stacks.txt', 'spans.json', 'state.json'} <= \
                set(b['files']), b
        hang_state = json.loads(
            (pathlib.Path(next(
                b['path'] for b in bundles
                if (b.get('rank'), b.get('reason')) == (1, 'hang')))
             / 'state.json').read_text())
        assert hang_state['heartbeat']['stall']['stalled'] is True

        # The recovered rank resumed from its pre-hang checkpoint
        # (resume-from-step-k, not step 0).
        assert 'resumed from step' in out1, out1[-2000:]
    finally:
        for j in jobs_state.get_jobs():
            if not j['status'].is_terminal():
                try:
                    jobs_core.cancel([j['job_id']])
                except Exception:  # pylint: disable=broad-except
                    pass
        t_end = time.time() + 30
        while time.time() < t_end and any(
                not j['status'].is_terminal()
                for j in jobs_state.get_jobs()):
            time.sleep(0.5)
        for rec in state.get_clusters():
            try:
                from skypilot_tpu import core as sky_core
                sky_core.down(rec['name'], purge=True)
            except Exception:  # pylint: disable=broad-except
                pass
        state.reset_db_for_testing()
        jobs_state.reset_db_for_testing()
