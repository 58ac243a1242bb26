"""What every cell driver shares: paths, the child process, statistics.

Nothing here imports JAX: the parent of a cell never touches the chip,
its one child does. The process helpers are copies of chip_smoke.py's
(the benchmark imports nothing of the program's into the parent).
"""
import json
import math
import os
import signal
import subprocess
import sys
import time

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
# Git-ignored, inside the checkout: logs, seeded data files, traces.
OUT_DIR = os.path.join(ROOT, 'chiprun_out', 'chipbench')

T_PROCESS_START = time.monotonic()   # set-up is counted from here

class BenchFailure(Exception):
    """The run cannot give a result (no chip, child died, set-up that
    never stops compiling). The harness exits non-zero and prints no
    result line."""


def say(msg: str) -> None:
    print(msg, flush=True)


def load_json(path: str):
    with open(path, encoding='utf-8') as f:
        return json.load(f)


def bench_path(*parts: str) -> str:
    return os.path.join(BENCH_DIR, *parts)


def child_env(platform: str, extra: dict) -> dict:
    """The child's environment: the platform named explicitly, so that
    a missing chip is JAX's own start-up error and never a CPU run.
    BENCH_RUN is the driver's own and is not passed on. The compile
    cache is placed by the program's entry points
    (utils/compile_cache.configure): JAX_COMPILATION_CACHE_DIR if the
    caller set it, else <checkout>/.jax_cache."""
    env = dict(os.environ)
    env.pop('BENCH_RUN', None)
    env['JAX_PLATFORMS'] = platform
    env['PYTHONUNBUFFERED'] = '1'
    env['PYTHONPATH'] = ROOT + os.pathsep + env.get('PYTHONPATH', '')
    if platform == 'cpu':
        env.pop('XLA_FLAGS', None)
    env.update(extra)
    return env


def start_child(cmd: list, platform: str, extra_env: dict, log_path: str,
                pipe: bool = False) -> subprocess.Popen:
    """Start the cell's one child. Its output goes to log_path, or to a
    pipe the caller reads (and logs) line by line."""
    say(f'$ {" ".join(cmd[1:])}')
    out = subprocess.PIPE if pipe else open(log_path, 'wb')
    try:
        return subprocess.Popen(
            cmd, cwd=ROOT, stdout=out, stderr=subprocess.STDOUT,
            env=child_env(platform, extra_env), start_new_session=True)
    finally:
        if not pipe:
            out.close()


def stop_child(proc: subprocess.Popen, grace_s: float = 30.0) -> int:
    """SIGTERM, wait, SIGKILL the whole process group if it will not
    go; returns the exit code. Nothing is left running."""
    if proc.poll() is None:
        proc.send_signal(signal.SIGTERM)
        try:
            proc.wait(timeout=grace_s)
        except subprocess.TimeoutExpired:
            pass
    try:
        os.killpg(proc.pid, signal.SIGKILL)   # stragglers of the group
    except (ProcessLookupError, PermissionError):
        pass
    return proc.wait(timeout=30)


def tail(path: str, n: int = 30) -> str:
    try:
        with open(path, encoding='utf-8', errors='replace') as f:
            return ''.join(f.readlines()[-n:])
    except OSError:
        return ''


def python() -> str:
    return sys.executable or 'python3'


# ------------------------------------------------------------ statistics
def quantile(values, q: float) -> float:
    """Linear-interpolated quantile (numpy's default), q in [0, 1]."""
    xs = sorted(values)
    if not xs:
        raise ValueError('quantile of nothing')
    pos = q * (len(xs) - 1)
    lo = int(math.floor(pos))
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def median(values) -> float:
    return quantile(values, 0.5)
