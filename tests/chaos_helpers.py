"""What the chaos suite's files share (tests/test_chaos_*.py): ports,
in-process LBs and replicas, real replica / service / LB subprocesses,
and the waits on a rollout's phase. Not a test module."""
import os
import socket
import subprocess
import sys
import threading
import time

import pytest
import requests

from skypilot_tpu.utils import faults
from skypilot_tpu.utils import metrics as metrics_lib


@pytest.fixture(autouse=True)
def _reset_faults():
    faults.reset()
    yield
    faults.reset()


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(('127.0.0.1', 0))
        return s.getsockname()[1]


def _run_app_bg(app, port) -> None:
    from aiohttp import web
    threading.Thread(target=lambda: web.run_app(
        app, port=port, print=None, handle_signals=False),
        daemon=True).start()


def _wait_http(url: str, timeout: float = 60, proc=None) -> None:
    deadline = time.time() + timeout
    while time.time() < deadline:
        if proc is not None and proc.poll() is not None:
            raise AssertionError(
                f'server died rc={proc.returncode} before {url} was up')
        try:
            if requests.get(url, timeout=2).status_code == 200:
                return
        except requests.RequestException:
            pass
        time.sleep(0.2)
    raise AssertionError(f'{url} never became healthy')


# ============================================================ LB behavior
def _make_lb(replicas, monkeypatch=None, **env):
    """In-process LB with a private registry, controller sync parked."""
    from skypilot_tpu.serve import load_balancer as lb_lib
    os.environ.setdefault('SKYT_SERVE_LB_SYNC_INTERVAL', '3600')
    if monkeypatch is not None:
        monkeypatch.setenv('SKYT_SERVE_LB_SYNC_INTERVAL', '3600')
        for k, v in env.items():
            monkeypatch.setenv(k, str(v))
    reg = metrics_lib.MetricsRegistry()
    port = _free_port()
    lb = lb_lib.SkyServeLoadBalancer('http://127.0.0.1:9', port,
                                     metrics_registry=reg)
    lb.policy.set_ready_replicas(list(replicas))
    _run_app_bg(lb.make_app(), port)
    base = f'http://127.0.0.1:{port}'
    deadline = time.time() + 30
    while time.time() < deadline:
        try:
            requests.get(base + '/metrics', timeout=2)
            break
        except requests.RequestException:
            time.sleep(0.1)
    return lb, base, reg


def _ok_replica(name='ok'):
    """Tiny healthy replica app (no engine: LB behavior under test)."""
    from aiohttp import web

    async def handler(request):
        del request
        return web.Response(text=f'hello-{name}')

    app = web.Application()
    app.router.add_route('*', '/{p:.*}', handler)
    port = _free_port()
    _run_app_bg(app, port)
    url = f'http://127.0.0.1:{port}'
    _wait_http(url + '/x')
    return url


@pytest.fixture()
def control_plane_env(tmp_path, tmp_state_dir, monkeypatch):
    """Local-provider serve environment with fast control loops, for
    drills that run the real controller as a killable subprocess."""
    del tmp_state_dir
    from skypilot_tpu import state
    from skypilot_tpu.serve import serve_state
    monkeypatch.setenv('SKYT_LOCAL_ROOT', str(tmp_path / 'local'))
    monkeypatch.setenv('SKYT_DEFAULT_STORE', 'local')
    monkeypatch.setenv('SKYT_SERVE_CONTROLLER_INTERVAL', '0.3')
    monkeypatch.setenv('SKYT_SERVE_LB_SYNC_INTERVAL', '0.3')
    state.reset_db_for_testing()
    serve_state.reset_db_for_testing()
    yield tmp_path
    from skypilot_tpu import core as core_lib
    for rec in state.get_clusters():
        try:
            core_lib.down(rec['name'], purge=True)
        except Exception:  # pylint: disable=broad-except
            pass
    state.reset_db_for_testing()
    serve_state.reset_db_for_testing()


def _spawn_service(name, role):
    return subprocess.Popen(
        [sys.executable, '-m', 'skypilot_tpu.serve.service',
         '--service-name', name, '--role', role],
        env=dict(os.environ), stdout=subprocess.DEVNULL,
        stderr=subprocess.STDOUT)


def _wait_replicas_ready(name, want, timeout=120):
    from skypilot_tpu.serve import serve_state
    deadline = time.time() + timeout
    while time.time() < deadline:
        infos = serve_state.get_replicas(name)
        ready = [r for r in infos
                 if r.status is serve_state.ReplicaStatus.READY]
        if len(ready) >= want:
            return ready
        time.sleep(0.5)
    raise AssertionError(
        f'{want} replicas never READY: '
        f'{[(r.replica_id, r.status) for r in serve_state.get_replicas(name)]}')


def _wait_rollout_phase(cport, token, phases, timeout=180):
    headers = {'Authorization': f'Bearer {token}'}
    deadline = time.time() + timeout
    last = None
    while time.time() < deadline:
        try:
            last = requests.get(
                f'http://127.0.0.1:{cport}/controller/status',
                headers=headers, timeout=10).json()
            ro = last.get('rollout') or {}
            if ro.get('phase') in phases:
                return last
        except requests.RequestException:
            pass
        time.sleep(0.3)
    raise AssertionError(
        f'rollout never reached {phases}: '
        f'{(last or {}).get("rollout")}')


_ADMIN_FAKE_REPLICA = (
    "python -c \""
    "import http.server, json, os;\n"
    "class H(http.server.BaseHTTPRequestHandler):\n"
    "    def _ok(self, body=b'ok'):\n"
    "        self.send_response(200); self.end_headers();\n"
    "        self.wfile.write(body)\n"
    "    def do_GET(self):\n"
    "        self._ok()\n"
    "    def do_POST(self):\n"
    "        n = int(self.headers.get('Content-Length') or 0);\n"
    "        self.rfile.read(n);\n"
    "        self._ok(json.dumps({'ok': True}).encode())\n"
    "    def log_message(self, *a):\n"
    "        pass\n"
    "http.server.HTTPServer(('127.0.0.1', "
    "int(os.environ['SKYT_REPLICA_PORT'])), H).serve_forever()\"")
