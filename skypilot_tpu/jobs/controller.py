"""Managed-job controller: one daemon process per managed job.

Reference: sky/jobs/controller.py (550 LoC) — `JobsController` (:46),
`_run_one_task` (:103) with the watch loop distinguishing user failure
from preemption (:240-270) and triggering recovery (:315-325), signal-file
cancellation (:407), `_cleanup` (:435).

TPU-native change: the controller is a detached process on the client
machine sharing the client state DB ("consolidated controller") instead of
a dedicated controller VM — dropping Ray and the VM removes the need for
the reference's SSH-codegen query tunnel. The watch loop and recovery
semantics are the same; `jobs.core.launch` documents the trade-off.

Run:  python -m skypilot_tpu.jobs.controller --job-id N --dag-yaml PATH
"""
import argparse
import os
import time
from typing import Any, Optional

import requests

from skypilot_tpu import exceptions
from skypilot_tpu import state as cluster_state
from skypilot_tpu.jobs import constants
from skypilot_tpu.jobs import recovery_strategy
from skypilot_tpu.jobs import state as jobs_state
from skypilot_tpu.utils import log_utils

logger = log_utils.init_logger(__name__)

# Cluster-job statuses that mean "the user program failed on its own"
# (vs. infrastructure loss). Reference: sky/skylet/job_lib.py statuses.
_USER_FAILURE = ('FAILED', 'FAILED_SETUP')
_TERMINAL = ('SUCCEEDED', 'FAILED', 'FAILED_SETUP', 'CANCELLED')


def signal_path(job_id: int) -> str:
    d = os.path.join(cluster_state.state_dir(), constants.SIGNAL_DIR)
    os.makedirs(d, exist_ok=True)
    return os.path.join(d, str(job_id))


class JobsController:
    """Reference: sky/jobs/controller.py:46."""

    def __init__(self, job_id: int, dag_yaml: str) -> None:
        from skypilot_tpu import dag as dag_lib
        from skypilot_tpu import task as task_lib
        import yaml

        self.job_id = job_id
        with open(dag_yaml, 'r', encoding='utf-8') as f:
            configs = list(yaml.safe_load_all(f))
        self.dag = dag_lib.Dag()
        for cfg in configs:
            if cfg:
                self.dag.add(task_lib.Task.from_yaml_config(cfg))
        if not self.dag.tasks:
            raise exceptions.ManagedJobError('empty dag')
        self.job_name = (jobs_state.get_job(job_id) or {}).get('name') or \
            (self.dag.tasks[0].name or f'job-{job_id}')

    # --------------------------------------------------------------- run
    def run(self) -> None:
        """Walk the chain DAG task by task (reference :325 run)."""
        status = jobs_state.ManagedJobStatus.SUCCEEDED
        reason: Optional[str] = None
        try:
            for idx, task in enumerate(self.dag.tasks):
                jobs_state.set_task_index(self.job_id, idx)
                ok, reason = self._run_one_task(idx, task)
                if not ok:
                    status = jobs_state.ManagedJobStatus.FAILED
                    break
        except (_Cancelled, KeyboardInterrupt):
            # SIGINT is how jobs.core.cancel wakes the watch loop out of
            # its poll sleep; the signal file is the source of truth, but
            # an interrupt without a file is still operator intent.
            status = jobs_state.ManagedJobStatus.CANCELLED
            reason = 'cancelled by user'
        except exceptions.ManagedJobReachedMaxRetriesError as e:
            status = jobs_state.ManagedJobStatus.FAILED_NO_RESOURCE
            reason = str(e)
        except Exception as e:  # pylint: disable=broad-except
            logger.exception('controller crashed')
            status = jobs_state.ManagedJobStatus.FAILED_CONTROLLER
            reason = f'{type(e).__name__}: {e}'
        finally:
            self._cleanup()
            jobs_state.set_status(self.job_id, status, reason)
            logger.info('managed job %d finished: %s', self.job_id,
                        status.value)

    # --------------------------------------------------------- one task
    def _run_one_task(self, task_index: int, task: Any
                      ) -> 'tuple[bool, Optional[str]]':
        """Launch + watch + recover one task. Reference: :103.

        Returns (succeeded, failure_reason)."""
        cluster_name = constants.JOBS_CLUSTER_NAME_PREFIX.format(
            name=self.job_name, job_id=self.job_id)
        if len(self.dag.tasks) > 1:
            cluster_name = f'{cluster_name}-{task_index}'
        strategy = recovery_strategy.StrategyExecutor.make(
            cluster_name, task,
            retry_until_up=bool(
                (jobs_state.get_job(self.job_id) or {}).get(
                    'retry_until_up')))

        jobs_state.set_status(self.job_id,
                              jobs_state.ManagedJobStatus.STARTING)
        jobs_state.set_cluster_name(self.job_id, cluster_name)
        self._check_signal()
        cluster_job_id = strategy.launch()
        jobs_state.set_status(self.job_id,
                              jobs_state.ManagedJobStatus.RUNNING)

        gap = constants.status_check_gap_seconds()
        unreachable_since: Optional[float] = None
        while True:
            self._check_signal()
            time.sleep(gap)

            job_status = self._probe_job_status(cluster_name,
                                                cluster_job_id)
            if job_status == 'SUCCEEDED':
                recovery_strategy.terminate_cluster(cluster_name)
                jobs_state.set_cluster_name(self.job_id, None)
                return True, None
            if job_status in _USER_FAILURE:
                # The program itself failed — recovery cannot help
                # (reference :240: user failure => no recovery).
                recovery_strategy.terminate_cluster(cluster_name)
                return False, (f'task {task_index} failed '
                               f'({job_status.lower()})')
            if job_status == 'CANCELLED':
                # Cancelled out-of-band on the cluster; treat as user
                # cancellation of the whole managed job.
                raise _Cancelled()
            if job_status in ('PREEMPTED', 'HUNG'):
                # Cooperative preemption (EXIT_CODE_PREEMPTED): the
                # workload checkpointed at a step boundary and asked to
                # be rescheduled. HUNG: the gang watchdog confirmed a
                # rank stopped making step progress (train/watchdog.py)
                # and already killed the gang — every rank dumped a
                # postmortem bundle first. Both recover the same way:
                # relaunch resumes from the last checkpoint (step k,
                # not step 0) instead of declaring user failure.
                logger.info(
                    'task %d exited %s (%s); recovering', task_index,
                    job_status,
                    'cooperative checkpoint' if job_status == 'PREEMPTED'
                    else 'gang watchdog hang verdict')
                jobs_state.set_status(
                    self.job_id, jobs_state.ManagedJobStatus.RECOVERING)
                jobs_state.bump_recovery_count(self.job_id)
                cluster_job_id = strategy.recover()
                jobs_state.set_status(
                    self.job_id, jobs_state.ManagedJobStatus.RUNNING)
                unreachable_since = None
                continue
            if job_status is not None:
                unreachable_since = None
                continue

            # Probe failed: cluster unreachable or gone. Confirm against
            # the provider before declaring preemption (reference
            # :240-270 forces a cloud status refresh).
            now = time.time()
            if unreachable_since is None:
                unreachable_since = now
            cluster_status = self._refresh_cluster(cluster_name)
            if cluster_status == cluster_state.ClusterStatus.UP and \
                    now - unreachable_since < \
                    constants.preemption_grace_seconds():
                continue  # transient blip; keep watching

            logger.info('cluster %s lost (status=%s); recovering',
                        cluster_name, cluster_status)
            jobs_state.set_status(self.job_id,
                                  jobs_state.ManagedJobStatus.RECOVERING)
            jobs_state.bump_recovery_count(self.job_id)
            cluster_job_id = strategy.recover()
            jobs_state.set_status(self.job_id,
                                  jobs_state.ManagedJobStatus.RUNNING)
            unreachable_since = None

    # ----------------------------------------------------------- helpers
    def _probe_job_status(self, cluster_name: str,
                          cluster_job_id: int) -> Optional[str]:
        """Cluster-job status, or None if the cluster cannot answer."""
        record = cluster_state.get_cluster(cluster_name)
        if record is None:
            return None
        try:
            job = record['handle'].head_client().job(cluster_job_id)
        except (requests.RequestException, OSError):
            # Network/HTTP/timeout only: "unreachable" must mean the
            # CLUSTER is unreachable. A programming error (TypeError,
            # KeyError, ...) propagating here fails the controller loudly
            # instead of masquerading as a preemption and triggering a
            # spurious teardown+recovery.
            return None
        return job['status'] if job else None

    def _refresh_cluster(self, cluster_name: str):
        from skypilot_tpu.backends import backend_utils
        record = cluster_state.get_cluster(cluster_name)
        if record is None:
            return None
        try:
            return backend_utils.refresh_cluster_status(
                cluster_name, record['handle'])
        except exceptions.SkyTpuError:
            return None

    def _check_signal(self) -> None:
        """Reference: :407 _handle_signal — cancel via signal file."""
        path = signal_path(self.job_id)
        if not os.path.exists(path):
            return
        logger.info('cancel signal received for job %d', self.job_id)
        jobs_state.set_status(self.job_id,
                              jobs_state.ManagedJobStatus.CANCELLING)
        raise _Cancelled()

    def _cleanup(self) -> None:
        """Tear down any cluster this job still owns (reference :435)."""
        row = jobs_state.get_job(self.job_id)
        cluster_name = row.get('cluster_name') if row else None
        if cluster_name and \
                cluster_state.get_cluster(cluster_name) is not None:
            recovery_strategy.terminate_cluster(cluster_name)
        jobs_state.set_cluster_name(self.job_id, None)
        try:
            os.remove(signal_path(self.job_id))
        except OSError:
            pass
        # Non-persistent storages are cleaned up with the job (reference:
        # controller cleanup of ephemeral buckets). Translated
        # single-file mounts live in one staging bucket referenced by
        # URI string, not a storage-mount spec — clean those too.
        from skypilot_tpu.utils import controller_utils
        for task in self.dag.tasks:
            for spec in (task.storage_mounts or {}).values():
                self._maybe_delete_storage(spec)
            controller_utils.cleanup_translated_file_buckets(
                task.file_mounts or {})

    def _maybe_delete_storage(self, spec: Any) -> None:
        from skypilot_tpu.data import storage as storage_lib
        from skypilot_tpu.data import storage_mounting
        try:
            storage = storage_mounting.to_storage(spec)
            if storage.persistent:
                return
            # Rehydrate from the state DB: the in-memory object has no
            # attached stores (the backend's own instance did add_store).
            if cluster_state.get_storage(storage.name) is not None:
                storage_lib.Storage.delete_by_name(storage.name)
        except exceptions.SkyTpuError:
            pass


class _Cancelled(Exception):
    pass


def main(argv=None) -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument('--job-id', type=int, required=True)
    parser.add_argument('--dag-yaml', required=True)
    args = parser.parse_args(argv)
    jobs_state.set_controller_pid(args.job_id, os.getpid())
    JobsController(args.job_id, args.dag_yaml).run()


if __name__ == '__main__':
    main()
