"""Per-cluster job queue: sqlite job table + FIFO scheduler + gang state.

Mirrors the reference's sky/skylet/job_lib.py (JobStatus :86, FIFOScheduler
:199, add_job :273, update_job_status :512, is_cluster_idle :641) with one
structural change: the reference tracks only per-job status because Ray owns
the per-node fan-out; here the head agent owns the gang, so the job table
carries a companion `gang` table with one row per (job, rank) that workers
update as they start/finish.

Lives on the HEAD host under $SKYT_AGENT_HOME/.skyt/jobs.db. All writes go
through this module; worker hosts never touch the DB (they talk HTTP to the
head agent — runtime/server.py).
"""
import enum
import json
import os
import sqlite3
import threading
import time
from typing import Any, Dict, List, Optional

from skypilot_tpu.utils import sqlite_utils
from skypilot_tpu.utils import env


def agent_home() -> str:
    return os.path.expanduser(env.get('SKYT_AGENT_HOME', '~'))


def skyt_dir() -> str:
    d = os.path.join(agent_home(), '.skyt')
    os.makedirs(d, exist_ok=True)
    return d


def log_dir_for_job(job_id: int) -> str:
    return os.path.join(skyt_dir(), 'logs', str(job_id))


# Cooperative-preemption exit code (EX_TEMPFAIL): a workload that
# caught SIGTERM, checkpointed at a step boundary, and wants to be
# RESCHEDULED exits with this (train/checkpoint.PreemptionGuard). The
# head agent maps it to JobStatus.PREEMPTED instead of FAILED, and the
# managed-jobs controller recovers (resume from the checkpoint) rather
# than declaring user failure.
EXIT_CODE_PREEMPTED = 75


class JobStatus(enum.Enum):
    """Reference: sky/skylet/job_lib.py:86 (same lifecycle, plus
    PREEMPTED for cooperative-preemption exits — see
    EXIT_CODE_PREEMPTED — and HUNG for gang-watchdog hang verdicts,
    which the managed-jobs controller recovers like a preemption)."""
    INIT = 'INIT'
    PENDING = 'PENDING'
    SETTING_UP = 'SETTING_UP'
    RUNNING = 'RUNNING'
    SUCCEEDED = 'SUCCEEDED'
    FAILED = 'FAILED'
    FAILED_SETUP = 'FAILED_SETUP'
    CANCELLED = 'CANCELLED'
    PREEMPTED = 'PREEMPTED'
    # Gang watchdog verdict (train/watchdog.py): a rank stopped making
    # step progress while the process stayed alive — the failure mode
    # exit codes can never surface. Terminal: the gang is killed and
    # the managed-jobs controller resumes from the last checkpoint.
    HUNG = 'HUNG'

    def is_terminal(self) -> bool:
        return self in _TERMINAL

    @classmethod
    def nonterminal_statuses(cls) -> List['JobStatus']:
        return [s for s in cls if not s.is_terminal()]


_TERMINAL = {JobStatus.SUCCEEDED, JobStatus.FAILED, JobStatus.FAILED_SETUP,
             JobStatus.CANCELLED, JobStatus.PREEMPTED, JobStatus.HUNG}

_DB_LOCK = threading.RLock()
_DB: Optional[sqlite3.Connection] = None
_DB_HOME: Optional[str] = None


def _get_db() -> sqlite3.Connection:
    global _DB, _DB_HOME
    with _DB_LOCK:
        home = skyt_dir()
        if _DB is None or _DB_HOME != home:
            if _DB is not None:
                _DB.close()
            _DB = sqlite_utils.connect(os.path.join(home, 'jobs.db'))
            _DB.executescript("""
            CREATE TABLE IF NOT EXISTS jobs (
                job_id INTEGER PRIMARY KEY AUTOINCREMENT,
                name TEXT,
                username TEXT,
                submitted_at REAL,
                start_at REAL,
                end_at REAL,
                status TEXT,
                spec TEXT,            -- JSON JobSpec
                pid INTEGER DEFAULT -1);
            CREATE TABLE IF NOT EXISTS gang (
                job_id INTEGER,
                rank INTEGER,
                status TEXT,          -- PENDING/RUNNING/DONE
                returncode INTEGER,
                updated_at REAL,
                PRIMARY KEY (job_id, rank));
            CREATE TABLE IF NOT EXISTS kv (
                key TEXT PRIMARY KEY, value TEXT);
            """)
            _DB.commit()
            _DB_HOME = home
        return _DB


def reset_db_for_testing() -> None:
    global _DB, _DB_HOME
    with _DB_LOCK:
        if _DB is not None:
            _DB.close()
        _DB = None
        _DB_HOME = None


# ------------------------------------------------------------------ job CRUD
def add_job(name: Optional[str], spec: Dict[str, Any],
            username: str = '') -> int:
    """Insert a job in INIT and return its id (reference: job_lib.py:273)."""
    db = _get_db()
    with _DB_LOCK:
        cur = db.execute(
            'INSERT INTO jobs (name, username, submitted_at, status, spec) '
            'VALUES (?, ?, ?, ?, ?)',
            (name, username, time.time(), JobStatus.INIT.value,
             json.dumps(spec)))
        db.commit()
        job_id = cur.lastrowid
    num_nodes = int(spec.get('num_nodes', 1))
    with _DB_LOCK:
        for rank in range(num_nodes):
            db.execute(
                'INSERT OR REPLACE INTO gang '
                '(job_id, rank, status, returncode, updated_at) '
                'VALUES (?, ?, ?, NULL, ?)',
                (job_id, rank, 'PENDING', time.time()))
        db.execute('UPDATE jobs SET status=? WHERE job_id=?',
                   (JobStatus.PENDING.value, job_id))
        db.commit()
    return job_id


def set_status(job_id: int, status: JobStatus) -> None:
    db = _get_db()
    now = time.time()
    with _DB_LOCK:
        if status == JobStatus.RUNNING:
            db.execute(
                'UPDATE jobs SET status=?, start_at=COALESCE(start_at, ?) '
                'WHERE job_id=?', (status.value, now, job_id))
        elif status.is_terminal():
            db.execute(
                'UPDATE jobs SET status=?, end_at=COALESCE(end_at, ?) '
                'WHERE job_id=?', (status.value, now, job_id))
        else:
            db.execute('UPDATE jobs SET status=? WHERE job_id=?',
                       (status.value, job_id))
        db.commit()


def get_job(job_id: int) -> Optional[Dict[str, Any]]:
    db = _get_db()
    with _DB_LOCK:
        row = db.execute('SELECT * FROM jobs WHERE job_id=?',
                         (job_id,)).fetchone()
    return _row_to_job(row) if row else None


def get_latest_job_id() -> Optional[int]:
    db = _get_db()
    with _DB_LOCK:
        row = db.execute(
            'SELECT job_id FROM jobs ORDER BY job_id DESC LIMIT 1').fetchone()
    return row['job_id'] if row else None


def get_jobs(statuses: Optional[List[JobStatus]] = None) -> List[Dict[str,
                                                                      Any]]:
    db = _get_db()
    if statuses:
        marks = ','.join('?' * len(statuses))
        with _DB_LOCK:
            rows = db.execute(
                f'SELECT * FROM jobs WHERE status IN ({marks}) '
                'ORDER BY job_id DESC', [s.value for s in statuses]).fetchall()
    else:
        with _DB_LOCK:
            rows = db.execute(
                'SELECT * FROM jobs ORDER BY job_id DESC').fetchall()
    return [_row_to_job(r) for r in rows]


def _row_to_job(row: sqlite3.Row) -> Dict[str, Any]:
    return {
        'job_id': row['job_id'],
        'name': row['name'],
        'username': row['username'],
        'submitted_at': row['submitted_at'],
        'start_at': row['start_at'],
        'end_at': row['end_at'],
        'status': JobStatus(row['status']),
        'spec': json.loads(row['spec']) if row['spec'] else {},
        'pid': row['pid'],
    }


def set_job_started(job_id: int) -> None:
    set_status(job_id, JobStatus.RUNNING)


def is_cluster_idle(threshold_statuses=(JobStatus.INIT, JobStatus.PENDING,
                                        JobStatus.SETTING_UP,
                                        JobStatus.RUNNING)) -> bool:
    """No nonterminal jobs (reference: job_lib.py:641)."""
    db = _get_db()
    marks = ','.join('?' * len(threshold_statuses))
    with _DB_LOCK:
        row = db.execute(
            f'SELECT COUNT(*) AS n FROM jobs WHERE status IN ({marks})',
            [s.value for s in threshold_statuses]).fetchone()
    return row['n'] == 0


def last_activity_time() -> float:
    """Most recent job end/submit time; agent start if no jobs ever."""
    db = _get_db()
    with _DB_LOCK:
        row = db.execute('SELECT MAX(COALESCE(end_at, submitted_at)) AS t '
                         'FROM jobs').fetchone()
    if row['t'] is not None:
        return row['t']
    return float(get_kv('agent_start_time') or time.time())


# ----------------------------------------------------------------- gang state
def gang_records(job_id: int) -> List[Dict[str, Any]]:
    db = _get_db()
    with _DB_LOCK:
        rows = db.execute(
            'SELECT * FROM gang WHERE job_id=? ORDER BY rank',
            (job_id,)).fetchall()
    return [dict(r) for r in rows]


def gang_mark(job_id: int, rank: int, status: str,
              returncode: Optional[int] = None) -> None:
    db = _get_db()
    with _DB_LOCK:
        db.execute(
            'UPDATE gang SET status=?, returncode=?, updated_at=? '
            'WHERE job_id=? AND rank=?',
            (status, returncode, time.time(), job_id, rank))
        db.commit()


def gang_all_done(job_id: int) -> bool:
    return all(r['status'] == 'DONE' for r in gang_records(job_id))


def gang_any_failed(job_id: int) -> bool:
    """True if any rank exited with a REAL failure code — cooperative
    preemption exits (EXIT_CODE_PREEMPTED) are not failures."""
    return any(r['status'] == 'DONE' and
               (r['returncode'] or 0) not in (0, EXIT_CODE_PREEMPTED)
               for r in gang_records(job_id))


def gang_any_preempted(job_id: int) -> bool:
    return any(r['status'] == 'DONE' and
               (r['returncode'] or 0) == EXIT_CODE_PREEMPTED
               for r in gang_records(job_id))


def postmortem_trailer_lines(job_wire: Dict[str, Any]) -> List[str]:
    """Log-surface trailer for a finished job: the gang watchdog
    verdict (HUNG only) plus every rank's postmortem bundle paths
    (docs/observability.md "Training plane"). ONE formatter shared by
    both tail surfaces — the on-host rpc `tail` and the client
    backend's HTTP tail — so the two can't drift."""
    lines: List[str] = []
    if job_wire.get('status') == JobStatus.HUNG.value and \
            job_wire.get('watchdog'):
        lines.append(f'### gang watchdog verdict: '
                     f'{json.dumps(job_wire["watchdog"])} ###')
    bundles = job_wire.get('postmortems') or {}

    def _rank_key(r):
        try:
            return (0, int(r), '')
        except (TypeError, ValueError):
            return (1, 0, str(r))

    if any(bundles.values()):
        lines.append('### postmortem bundles:')
        for rank in sorted(bundles, key=_rank_key):
            for path in bundles[rank]:
                lines.append(f'###   rank {rank}: {path}')
    return lines


# ------------------------------------------------------------------ scheduler
class FIFOScheduler:
    """Pick the next runnable job (reference: job_lib.py:199 FIFOScheduler).

    TPU slices are exclusive: one accelerator job runs at a time. Jobs that
    request no accelerators may run concurrently (bounded).
    """

    MAX_CONCURRENT_CPU_JOBS = 8

    def schedule_step(self) -> Optional[int]:
        """Return a PENDING job_id to start now, or None."""
        active = get_jobs([JobStatus.SETTING_UP, JobStatus.RUNNING])
        acc_busy = any(j['spec'].get('accelerators') for j in active)
        pending = get_jobs([JobStatus.PENDING])
        if not pending:
            return None
        for job in reversed(pending):  # oldest first
            wants_acc = bool(job['spec'].get('accelerators'))
            if wants_acc:
                if not active:  # gang jobs also wait for CPU jobs to drain
                    return job['job_id']
            else:
                if not acc_busy and len(active) < \
                        self.MAX_CONCURRENT_CPU_JOBS:
                    return job['job_id']
        return None


# ------------------------------------------------------------------------ kv
def set_kv(key: str, value: str) -> None:
    db = _get_db()
    with _DB_LOCK:
        db.execute('INSERT INTO kv (key, value) VALUES (?, ?) '
                   'ON CONFLICT(key) DO UPDATE SET value=excluded.value',
                   (key, value))
        db.commit()


def get_kv(key: str) -> Optional[str]:
    db = _get_db()
    with _DB_LOCK:
        row = db.execute('SELECT value FROM kv WHERE key=?', (key,)).fetchone()
    return row['value'] if row else None
