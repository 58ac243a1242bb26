"""SkyServe-equivalent state: services + replicas in sqlite.

Reference: sky/serve/serve_state.py (536 LoC) — services table, replicas
table with pickled ReplicaInfo, status enums. Lives in the client state
dir because the TPU-native controller is a consolidated client-side
process (see serve/core.py), not a controller VM.

Durability contract (docs/robustness.md "Control plane"): serve.db is
the crash-recovery source of truth — the controller re-adopts replicas
from it after a restart, and a standby LB reads it concurrently with
the live controller. The connection recipe (utils/sqlite_utils.py)
gives WAL + busy-timeout for the multi-process access; this module
adds a schema-version stamp (PRAGMA user_version) and a fail-fast
integrity check at open: a corrupt or newer-schema DB raises a NAMED
error (exceptions.ServeStateCorruptError / ServeStateSchemaError)
instead of reading garbage rows and silently relaunching everything.
"""
import enum
import os
import pickle
import secrets
import sqlite3
import threading
import time
from typing import Any, Dict, List, Optional

from skypilot_tpu import exceptions
from skypilot_tpu import state as state_lib
from skypilot_tpu.utils import metrics as metrics_lib
from skypilot_tpu.utils import sqlite_utils

# Bumped whenever the schema changes shape in a way old readers could
# misread. v1: pre-stamp layout (implicit). v2: stamped; adds the
# liveness-identity fields rode by pickled ReplicaInfo (additive).
SCHEMA_VERSION = 2


class ServiceStatus(enum.Enum):
    """Reference: sky/serve/serve_state.py ServiceStatus."""
    CONTROLLER_INIT = 'CONTROLLER_INIT'
    REPLICA_INIT = 'REPLICA_INIT'
    READY = 'READY'
    NO_REPLICA = 'NO_REPLICA'
    SHUTTING_DOWN = 'SHUTTING_DOWN'
    FAILED = 'FAILED'
    FAILED_CLEANUP = 'FAILED_CLEANUP'

    def is_terminal(self) -> bool:
        return self in (ServiceStatus.FAILED, ServiceStatus.FAILED_CLEANUP)


class ReplicaStatus(enum.Enum):
    """Reference: sky/serve/serve_state.py ReplicaStatus."""
    PENDING = 'PENDING'
    PROVISIONING = 'PROVISIONING'
    STARTING = 'STARTING'
    READY = 'READY'
    NOT_READY = 'NOT_READY'
    SHUTTING_DOWN = 'SHUTTING_DOWN'
    PREEMPTED = 'PREEMPTED'
    FAILED = 'FAILED'

    def is_terminal(self) -> bool:
        return self is ReplicaStatus.FAILED


_DB_LOCK = threading.RLock()
_DB: Optional[sqlite3.Connection] = None
_DB_PATH: Optional[str] = None


def _open_checked(path: str) -> sqlite3.Connection:
    """Open serve.db with the WAL recipe, then fail FAST on damage:
    a controller restarting over a corrupt DB must die with a named
    error — the disaster mode is adopting/reaping from garbage rows
    (e.g. relaunching every replica a truncated page lost)."""
    db: Optional[sqlite3.Connection] = None
    try:
        db = sqlite_utils.connect(path)
        row = db.execute('PRAGMA quick_check').fetchone()
        if row is None or row[0] != 'ok':
            raise exceptions.ServeStateCorruptError(
                f'serve state DB {path} failed quick_check: '
                f'{row[0] if row else "no result"!r}. Refusing to '
                f'reconcile from it — restore the file or move it '
                f'aside and re-`serve up`.')
        version = db.execute('PRAGMA user_version').fetchone()[0]
    except exceptions.ServeStateCorruptError:
        # Close before raising: callers may retry in a poll loop, and
        # each retry would otherwise leak a connection + WAL handles.
        if db is not None:
            db.close()
        raise
    except sqlite3.DatabaseError as e:
        # "file is not a database" / "database disk image is
        # malformed" land here before any query succeeds.
        if db is not None:
            db.close()
        raise exceptions.ServeStateCorruptError(
            f'serve state DB {path} is unreadable: {e}. Refusing to '
            f'reconcile from it — restore the file or move it aside '
            f'and re-`serve up`.') from e
    if version > SCHEMA_VERSION:
        db.close()
        raise exceptions.ServeStateSchemaError(
            f'serve state DB {path} has schema v{version}; this build '
            f'understands up to v{SCHEMA_VERSION}. A newer controller '
            f'or standby LB owns it — upgrade this process instead of '
            f'letting it misread newer rows.')
    return db


def _get_db() -> sqlite3.Connection:
    global _DB, _DB_PATH
    path = os.path.join(state_lib.state_dir(), 'serve.db')
    with _DB_LOCK:
        if _DB is None or _DB_PATH != path:
            os.makedirs(os.path.dirname(path), exist_ok=True)
            _DB = _open_checked(path)
            _DB.execute("""
                CREATE TABLE IF NOT EXISTS services (
                    name TEXT PRIMARY KEY,
                    status TEXT,
                    spec BLOB,
                    task_yaml TEXT,
                    version INTEGER DEFAULT 1,
                    controller_port INTEGER,
                    lb_port INTEGER,
                    controller_pid INTEGER,
                    controller_mode TEXT DEFAULT 'process',
                    auth_token TEXT,
                    created_at REAL)""")
            try:  # migrate pre-controller_mode DBs
                _DB.execute("ALTER TABLE services ADD COLUMN "
                            "controller_mode TEXT DEFAULT 'process'")
            except sqlite3.OperationalError:
                pass  # column already exists
            try:  # migrate pre-auth DBs (pre-token services run open)
                _DB.execute(
                    'ALTER TABLE services ADD COLUMN auth_token TEXT')
            except sqlite3.OperationalError:
                pass  # column already exists
            try:  # migrate pre-rollout DBs (restart-safe rollouts)
                _DB.execute(
                    'ALTER TABLE services ADD COLUMN rollout TEXT')
            except sqlite3.OperationalError:
                pass  # column already exists
            _DB.execute("""
                CREATE TABLE IF NOT EXISTS replicas (
                    service_name TEXT,
                    replica_id INTEGER,
                    info BLOB,
                    PRIMARY KEY (service_name, replica_id))""")
            # Stamp AFTER the tables + migrations exist, so a crash
            # mid-setup re-runs the (idempotent) setup next open.
            _DB.execute(f'PRAGMA user_version={SCHEMA_VERSION}')
            _DB.commit()
            _DB_PATH = path
        return _DB


def lb_lease_path(service_name: str) -> str:
    """Lease file electing the one serving-port owner among a
    service's LB processes (docs/robustness.md "Control plane"). ONE
    definition, used by the LB runner (serve/service.py) and cleanup
    (serve/core.py) — it lives beside serve.db so every process of the
    service resolves the same file."""
    d = os.path.join(state_lib.state_dir(), 'serve')
    os.makedirs(d, exist_ok=True)
    return os.path.join(d, f'{service_name}.lb.lease')


def reset_db_for_testing() -> None:
    global _DB, _DB_PATH
    with _DB_LOCK:
        if _DB is not None:
            _DB.close()
        _DB = None
        _DB_PATH = None


# ---------------------------------------------------------------- services
def add_service(name: str, spec: Any, task_yaml: str,
                controller_port: int, lb_port: int,
                controller_mode: str = 'process') -> bool:
    """False if the service already exists.

    controller_mode ('process'|'cluster') is recorded at creation so
    later operations (serve update translation) branch on the recorded
    placement, not on an inference like pid-liveness.

    A per-service bearer token is minted here; the controller's admin
    API (/controller/*) requires it, so reaching the controller port is
    not enough to terminate or roll the service (the reference gets the
    same property from SSH-tunneled codegen).
    """
    db = _get_db()
    with _DB_LOCK:
        try:
            db.execute(
                """INSERT INTO services (name, status, spec, task_yaml,
                                         controller_port, lb_port,
                                         controller_mode, auth_token,
                                         created_at)
                   VALUES (?, ?, ?, ?, ?, ?, ?, ?, ?)""",
                (name, ServiceStatus.CONTROLLER_INIT.value,
                 pickle.dumps(spec), task_yaml, controller_port, lb_port,
                 controller_mode, secrets.token_hex(16), time.time()))
            db.commit()
            return True
        except sqlite3.IntegrityError:
            # Roll back the implicit transaction the failed INSERT
            # opened — without this the connection keeps the write lock
            # and every other process's writes hit 'database is locked'
            # until this process exits.
            db.rollback()
            return False


def set_service_status(name: str, status: ServiceStatus) -> None:
    db = _get_db()
    with _DB_LOCK:
        db.execute('UPDATE services SET status=? WHERE name=?',
                   (status.value, name))
        db.commit()


def set_service_controller_pid(name: str, pid: int) -> None:
    db = _get_db()
    with _DB_LOCK:
        db.execute('UPDATE services SET controller_pid=? WHERE name=?',
                   (pid, name))
        db.commit()


def set_service_spec(name: str, spec: Any, task_yaml: str,
                     version: int) -> None:
    db = _get_db()
    with _DB_LOCK:
        db.execute(
            'UPDATE services SET spec=?, task_yaml=?, version=? '
            'WHERE name=?',
            (pickle.dumps(spec), task_yaml, version, name))
        db.commit()


def set_rollout(name: str, state: Optional[Dict[str, Any]]) -> None:
    """Persist a rolling weight update's state machine (JSON) on the
    service row — the crash-recovery source of truth: a controller
    restarting mid-rollout resumes or rolls back from here instead of
    stranding the fleet half-updated (docs/robustness.md
    "Zero-downtime rollouts"). None clears it."""
    db = _get_db()
    import json
    with _DB_LOCK:
        db.execute('UPDATE services SET rollout=? WHERE name=?',
                   (json.dumps(state) if state is not None else None,
                    name))
        db.commit()


def get_rollout(name: str) -> Optional[Dict[str, Any]]:
    """The persisted rollout state, or None (no rollout recorded, or
    an unreadable blob — which is logged, not raised: a torn rollout
    row must not wedge a restarting controller)."""
    db = _get_db()
    with _DB_LOCK:
        row = db.execute('SELECT rollout FROM services WHERE name=?',
                         (name,)).fetchone()
    if row is None or row['rollout'] is None:
        return None
    import json
    try:
        state = json.loads(row['rollout'])
        return state if isinstance(state, dict) else None
    except ValueError:
        from skypilot_tpu.utils import log_utils
        log_utils.init_logger(__name__).warning(
            'rollout state for %s is unreadable; ignoring', name,
            exc_info=True)
        return None


def get_service(name: str) -> Optional[Dict[str, Any]]:
    db = _get_db()
    with _DB_LOCK:
        row = db.execute('SELECT * FROM services WHERE name=?',
                         (name,)).fetchone()
    return _service_row(row) if row else None


def get_services() -> List[Dict[str, Any]]:
    db = _get_db()
    with _DB_LOCK:
        rows = db.execute('SELECT * FROM services ORDER BY name').fetchall()
    return [_service_row(r) for r in rows]


def remove_service(name: str) -> None:
    db = _get_db()
    with _DB_LOCK:
        db.execute('DELETE FROM services WHERE name=?', (name,))
        db.execute('DELETE FROM replicas WHERE service_name=?', (name,))
        db.commit()


def _service_row(row: sqlite3.Row) -> Dict[str, Any]:
    d = dict(row)
    d['status'] = ServiceStatus(d['status'])
    d['spec'] = pickle.loads(d['spec'])
    return d


# ---------------------------------------------------------------- replicas
def upsert_replica(service_name: str, replica_id: int, info: Any) -> None:
    db = _get_db()
    with _DB_LOCK:
        db.execute(
            """INSERT INTO replicas (service_name, replica_id, info)
               VALUES (?, ?, ?)
               ON CONFLICT(service_name, replica_id)
               DO UPDATE SET info=excluded.info""",
            (service_name, replica_id, pickle.dumps(info)))
        db.commit()


def remove_replica(service_name: str, replica_id: int) -> None:
    db = _get_db()
    with _DB_LOCK:
        db.execute(
            'DELETE FROM replicas WHERE service_name=? AND replica_id=?',
            (service_name, replica_id))
        db.commit()


def get_replicas(service_name: str) -> List[Any]:
    """Replica rows for a service. A row whose blob no longer
    unpickles (torn write inside an intact page, or a class path that
    moved between builds — user_version can't see either) is SKIPPED
    with a warning, not raised: it can never be adopted, and crashing
    here would wedge both the restarting controller and `serve
    status` until someone hand-edits the DB. The controller's
    prune_terminal_replicas sweep deletes such rows."""
    db = _get_db()
    with _DB_LOCK:
        rows = db.execute(
            'SELECT replica_id, info FROM replicas WHERE service_name=? '
            'ORDER BY replica_id', (service_name,)).fetchall()
    out = []
    for r in rows:
        try:
            out.append(pickle.loads(r['info']))
        except Exception:  # pylint: disable=broad-except
            from skypilot_tpu.utils import log_utils
            log_utils.init_logger(__name__).warning(
                'replica row (%s, %s) is unreadable; skipping (the '
                'prune sweep will delete it)', service_name,
                r['replica_id'], exc_info=True)
    return out


# ------------------------------------------------------------ housekeeping
def _rows_gauge() -> 'metrics_lib.Gauge':
    return metrics_lib.REGISTRY.gauge(
        'skyt_serve_state_rows', 'Rows in serve.db by table', ('table',))


def update_row_gauges() -> Dict[str, int]:
    """Refresh skyt_serve_state_rows{table=...}; returns the counts."""
    db = _get_db()
    counts = {}
    for table in ('services', 'replicas'):
        with _DB_LOCK:
            counts[table] = db.execute(
                f'SELECT COUNT(*) FROM {table}').fetchone()[0]
        _rows_gauge().labels(table).set(counts[table])
    return counts


def prune_terminal_replicas(older_than_s: float,
                            service_name: Optional[str] = None) -> int:
    """Delete replica rows whose pickled info reached a terminal state
    (FAILED, or PREEMPTED with no cluster left to reconcile) more than
    `older_than_s` ago. Without this sweep the replicas table grows one
    row per relaunch/adopt cycle forever on long-lived spot services.
    Rows that unpickle to something unreadable are pruned too — they
    can never be adopted, only mislead. Returns rows deleted."""
    db = _get_db()
    cutoff = time.time() - max(older_than_s, 0.0)
    doomed: List[tuple] = []
    with _DB_LOCK:
        query = 'SELECT service_name, replica_id, info FROM replicas'
        args: tuple = ()
        if service_name is not None:
            query += ' WHERE service_name=?'
            args = (service_name,)
        for row in db.execute(query, args).fetchall():
            try:
                info = pickle.loads(row['info'])
                status = info.status
                if not (status.is_terminal() or
                        status is ReplicaStatus.PREEMPTED):
                    continue
                stamp = getattr(info, 'terminal_at', None) or \
                    getattr(info, 'launched_at', 0.0) or 0.0
                if stamp <= cutoff:
                    doomed.append((row['service_name'],
                                   row['replica_id']))
            except Exception:  # pylint: disable=broad-except
                doomed.append((row['service_name'], row['replica_id']))
        for svc, rid in doomed:
            db.execute(
                'DELETE FROM replicas WHERE service_name=? AND '
                'replica_id=?', (svc, rid))
        db.commit()
    update_row_gauges()
    return len(doomed)
