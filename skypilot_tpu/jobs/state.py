"""Managed-jobs state: sqlite table + status enum.

Reference: sky/jobs/state.py (613 LoC) — `spot` table + `job_info`,
`ManagedJobStatus` enum (:129-169). The TPU-native controller runs as a
client-side daemon process sharing the client state dir, so this DB lives
next to the cluster DB (the reference keeps it on the controller VM and
tunnels queries over SSH codegen — one of the things dropping Ray + the
controller VM simplifies away).
"""
import enum
import os
import sqlite3
import threading
import time
from typing import Any, Dict, List, Optional

from skypilot_tpu import state as state_lib
from skypilot_tpu.utils import sqlite_utils


class ManagedJobStatus(enum.Enum):
    """Reference: sky/jobs/state.py:129-169."""
    PENDING = 'PENDING'
    SUBMITTED = 'SUBMITTED'
    STARTING = 'STARTING'
    RUNNING = 'RUNNING'
    RECOVERING = 'RECOVERING'
    SUCCEEDED = 'SUCCEEDED'
    CANCELLING = 'CANCELLING'
    CANCELLED = 'CANCELLED'
    FAILED = 'FAILED'
    FAILED_SETUP = 'FAILED_SETUP'
    FAILED_PRECHECKS = 'FAILED_PRECHECKS'
    FAILED_NO_RESOURCE = 'FAILED_NO_RESOURCE'
    FAILED_CONTROLLER = 'FAILED_CONTROLLER'

    def is_terminal(self) -> bool:
        return self in _TERMINAL

    def is_failed(self) -> bool:
        return self in (ManagedJobStatus.FAILED,
                        ManagedJobStatus.FAILED_SETUP,
                        ManagedJobStatus.FAILED_PRECHECKS,
                        ManagedJobStatus.FAILED_NO_RESOURCE,
                        ManagedJobStatus.FAILED_CONTROLLER)


_TERMINAL = {
    ManagedJobStatus.SUCCEEDED, ManagedJobStatus.CANCELLED,
    ManagedJobStatus.FAILED, ManagedJobStatus.FAILED_SETUP,
    ManagedJobStatus.FAILED_PRECHECKS,
    ManagedJobStatus.FAILED_NO_RESOURCE,
    ManagedJobStatus.FAILED_CONTROLLER,
}

_DB_LOCK = threading.RLock()
_DB: Optional[sqlite3.Connection] = None
_DB_PATH: Optional[str] = None


def _db_path() -> str:
    return os.path.join(state_lib.state_dir(), 'managed_jobs.db')


def _get_db() -> sqlite3.Connection:
    global _DB, _DB_PATH
    path = _db_path()
    with _DB_LOCK:
        if _DB is None or _DB_PATH != path:
            os.makedirs(os.path.dirname(path), exist_ok=True)
            _DB = sqlite_utils.connect(path)
            _DB.execute("""
                CREATE TABLE IF NOT EXISTS managed_jobs (
                    job_id INTEGER PRIMARY KEY AUTOINCREMENT,
                    name TEXT,
                    dag_yaml TEXT,
                    status TEXT,
                    submitted_at REAL,
                    started_at REAL,
                    ended_at REAL,
                    cluster_name TEXT,
                    task_index INTEGER DEFAULT 0,
                    num_tasks INTEGER DEFAULT 1,
                    recovery_count INTEGER DEFAULT 0,
                    failure_reason TEXT,
                    controller_pid INTEGER,
                    controller_cluster TEXT,
                    retry_until_up INTEGER DEFAULT 0)""")
            try:  # migrate pre-controller_cluster DBs
                _DB.execute('ALTER TABLE managed_jobs ADD COLUMN '
                            'controller_cluster TEXT')
            except sqlite3.OperationalError:
                pass  # column already exists
            _DB.commit()
            _DB_PATH = path
        return _DB


def reset_db_for_testing() -> None:
    global _DB, _DB_PATH
    with _DB_LOCK:
        if _DB is not None:
            _DB.close()
        _DB = None
        _DB_PATH = None


def create_job(name: str, dag_yaml: str, num_tasks: int,
               retry_until_up: bool = False) -> int:
    db = _get_db()
    with _DB_LOCK:
        cur = db.execute(
            """INSERT INTO managed_jobs
               (name, dag_yaml, status, submitted_at, num_tasks,
                retry_until_up)
               VALUES (?, ?, ?, ?, ?, ?)""",
            (name, dag_yaml, ManagedJobStatus.PENDING.value, time.time(),
             num_tasks, int(retry_until_up)))
        db.commit()
        return int(cur.lastrowid)


def _update(job_id: int, **fields: Any) -> None:
    db = _get_db()
    keys = ', '.join(f'{k}=?' for k in fields)
    with _DB_LOCK:
        db.execute(f'UPDATE managed_jobs SET {keys} WHERE job_id=?',
                   (*fields.values(), job_id))
        db.commit()


def set_status(job_id: int, status: ManagedJobStatus,
               failure_reason: Optional[str] = None) -> None:
    fields: Dict[str, Any] = {'status': status.value}
    if status is ManagedJobStatus.RUNNING:
        row = get_job(job_id)
        if row and row['started_at'] is None:
            fields['started_at'] = time.time()
    if status.is_terminal():
        fields['ended_at'] = time.time()
    if failure_reason is not None:
        fields['failure_reason'] = failure_reason
    _update(job_id, **fields)


def set_cluster_name(job_id: int, cluster_name: Optional[str]) -> None:
    _update(job_id, cluster_name=cluster_name)


def set_dag_yaml(job_id: int, dag_yaml: str) -> None:
    _update(job_id, dag_yaml=dag_yaml)


def set_task_index(job_id: int, task_index: int) -> None:
    _update(job_id, task_index=task_index)


def set_controller_pid(job_id: int, pid: int) -> None:
    _update(job_id, controller_pid=pid)


def set_controller_cluster(job_id: int, cluster: str) -> None:
    """Cluster-hosted controller (reference: the jobs-controller VM,
    sky/jobs/core.py:30-137)."""
    _update(job_id, controller_cluster=cluster)


def bump_recovery_count(job_id: int) -> None:
    db = _get_db()
    with _DB_LOCK:
        db.execute(
            'UPDATE managed_jobs SET recovery_count = recovery_count + 1 '
            'WHERE job_id=?', (job_id,))
        db.commit()


def get_job(job_id: int) -> Optional[Dict[str, Any]]:
    db = _get_db()
    with _DB_LOCK:
        row = db.execute('SELECT * FROM managed_jobs WHERE job_id=?',
                         (job_id,)).fetchone()
    return _row_to_dict(row) if row is not None else None


def get_jobs(skip_finished: bool = False) -> List[Dict[str, Any]]:
    db = _get_db()
    with _DB_LOCK:
        rows = db.execute(
            'SELECT * FROM managed_jobs ORDER BY job_id').fetchall()
    jobs = [_row_to_dict(r) for r in rows]
    if skip_finished:
        jobs = [j for j in jobs if not j['status'].is_terminal()]
    return jobs


def _row_to_dict(row: sqlite3.Row) -> Dict[str, Any]:
    d = dict(row)
    d['status'] = ManagedJobStatus(d['status'])
    return d
