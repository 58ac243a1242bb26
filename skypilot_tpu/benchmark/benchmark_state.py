"""Benchmark state: sqlite tables for benchmarks + per-cluster results.

Reference: sky/benchmark/benchmark_state.py.
"""
import enum
import os
import pickle
import sqlite3
import threading
import time
from typing import Any, Dict, List, Optional

from skypilot_tpu import state as state_lib
from skypilot_tpu.utils import sqlite_utils


class BenchmarkStatus(enum.Enum):
    INIT = 'INIT'
    RUNNING = 'RUNNING'
    FINISHED = 'FINISHED'
    TERMINATED = 'TERMINATED'


_DB_LOCK = threading.RLock()
_DB: Optional[sqlite3.Connection] = None
_DB_PATH: Optional[str] = None


def _get_db() -> sqlite3.Connection:
    global _DB, _DB_PATH
    path = os.path.join(state_lib.state_dir(), 'benchmark.db')
    with _DB_LOCK:
        if _DB is None or _DB_PATH != path:
            os.makedirs(os.path.dirname(path), exist_ok=True)
            _DB = sqlite_utils.connect(path)
            _DB.execute("""
                CREATE TABLE IF NOT EXISTS benchmarks (
                    name TEXT PRIMARY KEY,
                    task_yaml TEXT,
                    created_at REAL)""")
            _DB.execute("""
                CREATE TABLE IF NOT EXISTS benchmark_results (
                    benchmark TEXT,
                    cluster TEXT,
                    status TEXT,
                    resources BLOB,
                    hourly_cost REAL,
                    result BLOB,
                    PRIMARY KEY (benchmark, cluster))""")
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


def add_benchmark(name: str, task_yaml: str) -> None:
    db = _get_db()
    with _DB_LOCK:
        db.execute(
            'INSERT OR REPLACE INTO benchmarks VALUES (?, ?, ?)',
            (name, task_yaml, time.time()))
        db.commit()


def add_result(benchmark: str, cluster: str, resources: Any,
               hourly_cost: float) -> None:
    db = _get_db()
    with _DB_LOCK:
        db.execute(
            """INSERT OR REPLACE INTO benchmark_results
               (benchmark, cluster, status, resources, hourly_cost, result)
               VALUES (?, ?, ?, ?, ?, NULL)""",
            (benchmark, cluster, BenchmarkStatus.INIT.value,
             pickle.dumps(resources), hourly_cost))
        db.commit()


def update_result(benchmark: str, cluster: str,
                  status: BenchmarkStatus,
                  result: Optional[Dict[str, Any]]) -> None:
    db = _get_db()
    with _DB_LOCK:
        if result is not None:
            db.execute(
                'UPDATE benchmark_results SET status=?, result=? '
                'WHERE benchmark=? AND cluster=?',
                (status.value, pickle.dumps(result), benchmark, cluster))
        else:
            db.execute(
                'UPDATE benchmark_results SET status=? '
                'WHERE benchmark=? AND cluster=?',
                (status.value, benchmark, cluster))
        db.commit()


def get_benchmarks() -> List[Dict[str, Any]]:
    db = _get_db()
    with _DB_LOCK:
        rows = db.execute('SELECT * FROM benchmarks ORDER BY name').fetchall()
    return [dict(r) for r in rows]


def get_benchmark(name: str) -> Optional[Dict[str, Any]]:
    db = _get_db()
    with _DB_LOCK:
        row = db.execute('SELECT * FROM benchmarks WHERE name=?',
                         (name,)).fetchone()
    return dict(row) if row else None


def get_results(benchmark: str) -> List[Dict[str, Any]]:
    db = _get_db()
    with _DB_LOCK:
        rows = db.execute(
            'SELECT * FROM benchmark_results WHERE benchmark=? '
            'ORDER BY cluster', (benchmark,)).fetchall()
    out = []
    for r in rows:
        d = dict(r)
        d['status'] = BenchmarkStatus(d['status'])
        d['resources'] = pickle.loads(d['resources'])
        d['result'] = pickle.loads(d['result']) if d['result'] else None
        out.append(d)
    return out


def remove_benchmark(name: str) -> None:
    db = _get_db()
    with _DB_LOCK:
        db.execute('DELETE FROM benchmarks WHERE name=?', (name,))
        db.execute('DELETE FROM benchmark_results WHERE benchmark=?',
                   (name,))
        db.commit()
