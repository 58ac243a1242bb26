"""Client-side persistent state (sqlite).

Mirrors the reference's sky/global_user_state.py: tables `clusters`,
`cluster_history`, `config`, `storage` in a per-user sqlite DB. Default
location ~/.skypilot_tpu/state.db; override with SKYT_STATE_DIR (tests).
"""
import enum
import json
import os
import pickle
import sqlite3
import threading
import time
import uuid
from typing import Any, Dict, List, Optional

from skypilot_tpu.utils import sqlite_utils
from skypilot_tpu.utils import env


def state_dir() -> str:
    d = env.get('SKYT_STATE_DIR',
                       os.path.expanduser('~/.skypilot_tpu'))
    os.makedirs(d, exist_ok=True)
    return d


class ClusterStatus(enum.Enum):
    """Reference: sky/global_user_state.py ClusterStatus (INIT/UP/STOPPED)."""
    INIT = 'INIT'
    UP = 'UP'
    STOPPED = 'STOPPED'

    def colored(self) -> str:
        return self.value


class StorageStatus(enum.Enum):
    INIT = 'INIT'
    UPLOAD_FAILED = 'UPLOAD_FAILED'
    READY = 'READY'


# RLock: helpers like _get_hash() call _get_db() while a public function
# already holds the lock (remove_cluster deadlocked with a plain Lock).
_DB_LOCK = threading.RLock()
_DB: Optional[sqlite3.Connection] = None


def _get_db() -> sqlite3.Connection:
    global _DB
    with _DB_LOCK:
        if _DB is None:
            path = os.path.join(state_dir(), 'state.db')
            _DB = sqlite_utils.connect(path)
            _create_tables(_DB)
        return _DB


def reset_db_for_testing() -> None:
    global _DB
    with _DB_LOCK:
        if _DB is not None:
            _DB.close()
        _DB = None


def _create_tables(db: sqlite3.Connection) -> None:
    db.executescript("""
    CREATE TABLE IF NOT EXISTS clusters (
        name TEXT PRIMARY KEY,
        launched_at INTEGER,
        handle BLOB,
        last_use TEXT,
        status TEXT,
        autostop INTEGER DEFAULT -1,
        to_down INTEGER DEFAULT 0,
        cluster_hash TEXT,
        requested_resources BLOB);
    CREATE TABLE IF NOT EXISTS cluster_history (
        cluster_hash TEXT PRIMARY KEY,
        name TEXT,
        num_nodes INTEGER,
        requested_resources BLOB,
        launched_resources BLOB,
        usage_intervals BLOB,
        hourly_cost REAL DEFAULT 0);
    CREATE TABLE IF NOT EXISTS config (
        key TEXT PRIMARY KEY,
        value TEXT);
    CREATE TABLE IF NOT EXISTS storage (
        name TEXT PRIMARY KEY,
        launched_at INTEGER,
        handle BLOB,
        last_use TEXT,
        status TEXT);
    """)
    # Migrations for DBs created before a column existed (CREATE IF NOT
    # EXISTS never alters an existing table).
    try:
        db.execute('ALTER TABLE cluster_history ADD COLUMN '
                   'hourly_cost REAL DEFAULT 0')
    except sqlite3.OperationalError:
        pass  # already present
    db.commit()


# ----------------------------------------------------------------- clusters
def add_or_update_cluster(name: str, handle: Any,
                          requested_resources: Optional[Any] = None,
                          is_launch: bool = True,
                          status: ClusterStatus = ClusterStatus.INIT) -> None:
    """Reference: sky/global_user_state.py:139 add_or_update_cluster."""
    db = _get_db()
    now = int(time.time())
    handle_blob = pickle.dumps(handle)
    req_blob = pickle.dumps(requested_resources)
    cluster_hash = _get_hash(name) or uuid.uuid4().hex
    with _DB_LOCK:
        db.execute(
            """INSERT INTO clusters
               (name, launched_at, handle, last_use, status, cluster_hash,
                requested_resources)
               VALUES (?, ?, ?, ?, ?, ?, ?)
               ON CONFLICT(name) DO UPDATE SET
                 handle=excluded.handle, status=excluded.status,
                 last_use=excluded.last_use,
                 requested_resources=excluded.requested_resources""" +
            (', launched_at=excluded.launched_at' if is_launch else ''),
            (name, now, handle_blob, _history_cmd(), status.value,
             cluster_hash, req_blob))
        db.commit()
        _record_history(db, name, cluster_hash, handle, requested_resources,
                        now if is_launch else None)


def _history_cmd() -> str:
    import sys
    return ' '.join(sys.argv[:4])


def _get_hash(name: str) -> Optional[str]:
    db = _get_db()
    with _DB_LOCK:
        row = db.execute('SELECT cluster_hash FROM clusters WHERE name=?',
                         (name,)).fetchone()
    return row['cluster_hash'] if row else None


def _record_history(db, name, cluster_hash, handle, requested_resources,
                    launched_at) -> None:
    num_nodes = getattr(handle, 'num_hosts', None)
    launched = getattr(handle, 'launched_resources', None)
    row = db.execute(
        'SELECT usage_intervals FROM cluster_history WHERE cluster_hash=?',
        (cluster_hash,)).fetchone()
    intervals = pickle.loads(row['usage_intervals']) if row else []
    # Only open a new interval if the previous one is closed — a relaunch
    # of a live cluster must not leave an un-closable open interval behind.
    if launched_at is not None and not (intervals and
                                        intervals[-1][1] is None):
        intervals.append((launched_at, None))
    hourly_cost = getattr(handle, 'hourly_cost', 0.0) or 0.0
    db.execute(
        """INSERT INTO cluster_history
           (cluster_hash, name, num_nodes, requested_resources,
            launched_resources, usage_intervals, hourly_cost)
           VALUES (?, ?, ?, ?, ?, ?, ?)
           ON CONFLICT(cluster_hash) DO UPDATE SET
             launched_resources=excluded.launched_resources,
             num_nodes=excluded.num_nodes,
             usage_intervals=excluded.usage_intervals,
             hourly_cost=excluded.hourly_cost""",
        (cluster_hash, name, num_nodes, pickle.dumps(requested_resources),
         pickle.dumps(launched), pickle.dumps(intervals), hourly_cost))
    db.commit()


def update_cluster_status(name: str, status: ClusterStatus) -> None:
    db = _get_db()
    with _DB_LOCK:
        db.execute('UPDATE clusters SET status=? WHERE name=?',
                   (status.value, name))
        db.commit()


def set_cluster_autostop(name: str, idle_minutes: int, to_down: bool) -> None:
    db = _get_db()
    with _DB_LOCK:
        db.execute('UPDATE clusters SET autostop=?, to_down=? WHERE name=?',
                   (idle_minutes, int(to_down), name))
        db.commit()


def get_cluster(name: str) -> Optional[Dict[str, Any]]:
    db = _get_db()
    with _DB_LOCK:
        row = db.execute('SELECT * FROM clusters WHERE name=?', (name,)).fetchone()
    return _cluster_row_to_dict(row) if row else None


def get_clusters() -> List[Dict[str, Any]]:
    db = _get_db()
    with _DB_LOCK:
        rows = db.execute(
            'SELECT * FROM clusters ORDER BY launched_at DESC').fetchall()
    return [_cluster_row_to_dict(r) for r in rows]


def _cluster_row_to_dict(row: sqlite3.Row) -> Dict[str, Any]:
    # On a closed interval the end timestamp is recorded at teardown; the
    # cost report integrates these (reference: cost_report sky/core.py:136).
    return {
        'name': row['name'],
        'launched_at': row['launched_at'],
        'handle': pickle.loads(row['handle']),
        'last_use': row['last_use'],
        'status': ClusterStatus(row['status']),
        'autostop': row['autostop'],
        'to_down': bool(row['to_down']),
        'cluster_hash': row['cluster_hash'],
        'requested_resources': pickle.loads(row['requested_resources'])
        if row['requested_resources'] else None,
    }


def remove_cluster(name: str) -> None:
    db = _get_db()
    with _DB_LOCK:
        ch = _get_hash(name)
        if ch is not None:
            row = db.execute(
                'SELECT usage_intervals FROM cluster_history '
                'WHERE cluster_hash=?', (ch,)).fetchone()
            if row:
                intervals = pickle.loads(row['usage_intervals'])
                if intervals and intervals[-1][1] is None:
                    intervals[-1] = (intervals[-1][0], int(time.time()))
                    db.execute(
                        'UPDATE cluster_history SET usage_intervals=? '
                        'WHERE cluster_hash=?',
                        (pickle.dumps(intervals), ch))
        db.execute('DELETE FROM clusters WHERE name=?', (name,))
        db.commit()


def get_cluster_history() -> List[Dict[str, Any]]:
    db = _get_db()
    with _DB_LOCK:
        rows = db.execute('SELECT * FROM cluster_history').fetchall()
    out = []
    for r in rows:
        out.append({
            'name': r['name'],
            'num_nodes': r['num_nodes'],
            'launched_resources': pickle.loads(r['launched_resources'])
            if r['launched_resources'] else None,
            'usage_intervals': pickle.loads(r['usage_intervals'])
            if r['usage_intervals'] else [],
            'hourly_cost': r['hourly_cost'],
        })
    return out


# ------------------------------------------------------------------- config
def set_config(key: str, value: Any) -> None:
    db = _get_db()
    with _DB_LOCK:
        db.execute(
            'INSERT INTO config (key, value) VALUES (?, ?) '
            'ON CONFLICT(key) DO UPDATE SET value=excluded.value',
            (key, json.dumps(value)))
        db.commit()


def get_config(key: str, default: Any = None) -> Any:
    db = _get_db()
    with _DB_LOCK:
        row = db.execute('SELECT value FROM config WHERE key=?', (key,)).fetchone()
    return json.loads(row['value']) if row else default


def set_enabled_clouds(clouds: List[str]) -> None:
    set_config('enabled_clouds', clouds)


def get_enabled_clouds() -> Optional[List[str]]:
    return get_config('enabled_clouds')


# ------------------------------------------------------------------ storage
def add_or_update_storage(name: str, handle: Any,
                          status: StorageStatus) -> None:
    db = _get_db()
    with _DB_LOCK:
        db.execute(
            """INSERT INTO storage (name, launched_at, handle, last_use,
                                    status)
               VALUES (?, ?, ?, ?, ?)
               ON CONFLICT(name) DO UPDATE SET handle=excluded.handle,
                 status=excluded.status, last_use=excluded.last_use""",
            (name, int(time.time()), pickle.dumps(handle), _history_cmd(),
             status.value))
        db.commit()


def get_storage(name: str) -> Optional[Dict[str, Any]]:
    db = _get_db()
    with _DB_LOCK:
        row = db.execute('SELECT * FROM storage WHERE name=?', (name,)).fetchone()
    if row is None:
        return None
    return {'name': row['name'], 'launched_at': row['launched_at'],
            'handle': pickle.loads(row['handle']),
            'status': StorageStatus(row['status'])}


def get_storages() -> List[Dict[str, Any]]:
    db = _get_db()
    with _DB_LOCK:
        rows = db.execute('SELECT name FROM storage').fetchall()
    return [get_storage(r['name']) for r in rows]


def remove_storage(name: str) -> None:
    db = _get_db()
    with _DB_LOCK:
        db.execute('DELETE FROM storage WHERE name=?', (name,))
        db.commit()
