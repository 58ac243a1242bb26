#!/usr/bin/env python3
"""Does the system still start on the chip? Serve a few requests and
train a few steps on the TPU through the entry points a user calls.

    python chip_smoke.py

This process never imports JAX: a chip belongs to one process, so each
leg is a child, one after another, started with JAX_PLATFORMS=tpu in its
environment (with `tpu` a missing chip is JAX's own start-up error, not
a quiet CPU backend). Legs:

  serve   `python -m skypilot_tpu.infer.server --model qwen2-1.5b` with
          the real SkyServeLoadBalancer in front of it in this process;
          mixed-length /generate requests through the LB (a concurrent
          burst, one streamed, one repeated). Every answer is a 200
          with exactly max_tokens tokens, the repeated greedy request
          is token-identical, and /stats names the TPU, says Pallas is
          compiled (not interpreted) and shows the Pallas rung for
          flash_attention and paged_attention.
  train   `python -m skypilot_tpu.train.sft --model qwen3-0.6b --steps 6`
          on synthetic data: exit 0, finite losses, the first near
          ln(vocab), flash forward and backward on the Pallas rung.
  tests   `python -m pytest tests_tpu/ -q`, the kernel gate: every
          Pallas kernel and engine path compiled by Mosaic and checked
          against its reference. No failure, and no skip but the two
          tests that need more devices than one chip.
  serve4, train4   only where four chips are visible: `--model qwen3-8b
          --tp 4` (16.4 GB in bf16: it exists only spread over the
          chips) and `--model qwen2-1.5b --mesh fsdp=2,tp=2`, plus
          per-device memory: four chips in use, evenly.

Weights are random, from a seed; widths are the published ones. Any leg
failing, timing out or reporting another platform than `tpu` makes the
exit code non-zero; nothing is caught and carried past. On success the
last line of stdout is one JSON object,
{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}.

`--rehearse-cpu` is for trying the script itself where there is no
chip: the same legs with the `debug` preset on four virtual CPU
devices, every output line labelled as a rehearsal. It says nothing
about the TPU. Logs go to chiprun_out/chip_smoke/.
"""
import argparse
import asyncio
import concurrent.futures
import json
import math
import os
import random
import re
import shutil
import signal
import socket
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request

ROOT = os.path.dirname(os.path.abspath(__file__))
OUT_DIR = os.path.join(ROOT, 'chiprun_out', 'chip_smoke')
PROBE = ('import json, jax; d = jax.devices(); print(json.dumps({'
         '"platform": d[0].platform, "kind": d[0].device_kind, '
         '"count": len(d)}))')
LEGS = ('serve', 'train', 'tests', 'serve4', 'train4')
# Prompt lengths in tokens. 'repeated' is shorter than one 64-token KV
# page, so both of its admissions take the same prefill path and the
# tokens can be compared; 'shared' spans pages, so its second admission
# goes through the prefix cache.
SIZES = {
    'tpu': {'single': 24, 'burst': (17, 40, 100, 300, 700, 220),
            'repeated': 40, 'shared': 200, 'max_tokens': 16},
    'cpu': {'single': 6, 'burst': (5, 9, 17, 30, 50, 22),
            'repeated': 12, 'shared': 80, 'max_tokens': 4},
}
# Each leg's (preset, tp) or (preset, mesh, batch, seq). The rehearsal's
# debug preset has 2 KV heads and a 128-token context.
PLAN = {
    'tpu': {'serve': ('qwen2-1.5b', 1), 'serve4': ('qwen3-8b', 4),
            'train': ('qwen3-0.6b', 'fsdp=1', 2, 2048),
            'train4': ('qwen2-1.5b', 'fsdp=2,tp=2', 4, 2048)},
    'cpu': {'serve': ('debug', 1), 'serve4': ('debug', 2),
            'train': ('debug', 'fsdp=1', 2, 64),
            'train4': ('debug', 'fsdp=2,tp=2', 4, 64)},
}
VOCAB = {'debug': 256, 'qwen2-1.5b': 151936, 'qwen3-0.6b': 151936,
         'qwen3-8b': 151936}

_label = ''


def say(msg: str) -> None:
    print(f'{_label}{msg}', flush=True)


class SmokeFailure(Exception):
    """A check of the smoke did not hold."""


def check(cond, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def child_env(platform: str, run_dir: str) -> dict:
    """The child's environment: the platform named explicitly (the
    ambient value here is `cpu`), and an empty home so that nothing a
    child compiles can depend on a file an earlier run left under ~
    (link profiles). The compile cache is placed by
    the entry points themselves: JAX_COMPILATION_CACHE_DIR if the
    caller set it, else <checkout>/.jax_cache."""
    env = dict(os.environ)
    env['JAX_PLATFORMS'] = platform
    env['PYTHONUNBUFFERED'] = '1'
    env['HOME'] = os.path.join(run_dir, 'home')
    os.makedirs(env['HOME'], exist_ok=True)
    if platform == 'cpu':
        env['XLA_FLAGS'] = '--xla_force_host_platform_device_count=4'
    return env


def tail(path: str, n: int = 40) -> str:
    with open(path, encoding='utf-8', errors='replace') as f:
        return ''.join(f.readlines()[-n:])


def stop(proc: subprocess.Popen) -> None:
    if proc.poll() is None:
        proc.send_signal(signal.SIGTERM)
        try:
            proc.wait(timeout=20)
        except subprocess.TimeoutExpired:
            proc.kill()
            proc.wait(timeout=20)


def run_to_end(name: str, cmd: list, platform: str, run_dir: str,
               deadline: float) -> str:
    """Run a child that is meant to finish by itself; its output goes
    to <run_dir>/<name>.log and comes back as text. A non-zero exit or
    an overrun deadline fails the leg."""
    log = os.path.join(run_dir, f'{name}.log')
    say(f'[{name}] $ {" ".join(cmd[1:])}')
    with open(log, 'wb') as logf:
        proc = subprocess.Popen(cmd, cwd=ROOT, stdout=logf,
                                stderr=subprocess.STDOUT,
                                env=child_env(platform, run_dir))
        try:
            rc = proc.wait(timeout=max(1.0, deadline - time.monotonic()))
        except subprocess.TimeoutExpired:
            raise SmokeFailure(f'{cmd[2]} did not finish in time:\n'
                               f'{tail(log)}')
        finally:
            stop(proc)
    check(rc == 0, f'{cmd[2]} exited with code {rc}:\n{tail(log, 60)}')
    with open(log, encoding='utf-8', errors='replace') as f:
        return f.read()


def free_port() -> int:
    with socket.socket() as s:
        s.bind(('127.0.0.1', 0))
        return s.getsockname()[1]


# ------------------------------------------------------------ serve leg
class FrontDoor:
    """The real load balancer, in this process, on its own event-loop
    thread — plus the one controller endpoint it syncs with, answering
    with the replica under test (so the LB learns its replica the way
    it does in a service, not by having its policy poked)."""

    def __init__(self, replica_url: str) -> None:
        from aiohttp import web

        from skypilot_tpu.serve import load_balancer as lb_lib
        self._web = web
        self.port = free_port()
        ctrl_port = free_port()
        self._replica = replica_url
        self._lb = lb_lib.SkyServeLoadBalancer(
            f'http://127.0.0.1:{ctrl_port}', self.port)
        self._ctrl_port = ctrl_port
        self._loop = asyncio.new_event_loop()
        self._runners = []
        self._thread = threading.Thread(target=self._loop.run_forever,
                                        daemon=True)
        self._thread.start()
        asyncio.run_coroutine_threadsafe(
            self._start(), self._loop).result(timeout=60)

    async def _sync(self, request):
        del request
        return self._web.json_response(
            {'ready_replica_urls': [self._replica]})

    async def _start(self) -> None:
        web = self._web
        ctrl = web.Application()
        ctrl.router.add_post('/controller/load_balancer_sync', self._sync)
        for app, port in ((ctrl, self._ctrl_port),
                          (self._lb.make_app(), self.port)):
            runner = web.AppRunner(app)
            await runner.setup()
            await web.TCPSite(runner, '127.0.0.1', port).start()
            self._runners.append(runner)

    @property
    def url(self) -> str:
        return f'http://127.0.0.1:{self.port}'

    def close(self) -> None:
        async def _stop():
            for runner in reversed(self._runners):
                await runner.cleanup()
        asyncio.run_coroutine_threadsafe(
            _stop(), self._loop).result(timeout=60)
        self._loop.call_soon_threadsafe(self._loop.stop)
        self._thread.join(timeout=30)


def http_json(url: str, payload=None, timeout: float = 600.0):
    """The parsed body of a 2xx answer (anything else raises). A
    streamed /generate answer is NDJSON, one {"token": t} per line; it
    comes back as {'tokens': [...]}."""
    data = json.dumps(payload).encode() if payload is not None else None
    req = urllib.request.Request(
        url, data=data, headers={'Content-Type': 'application/json'})
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        body = resp.read().decode()
    if payload and payload.get('stream'):
        return {'tokens': [json.loads(line)['token']
                           for line in body.splitlines() if line.strip()]}
    return json.loads(body)


def generate(base: str, prompt, max_tokens: int, stream: bool = False,
             retry_503_s: float = 0.0):
    give_up = time.monotonic() + retry_503_s
    while True:
        try:
            body = http_json(base + '/generate', {
                'tokens': prompt, 'max_tokens': max_tokens,
                'stream': stream})
            break
        except urllib.error.HTTPError as e:   # any answer but a 2xx
            check(e.code == 503 and time.monotonic() < give_up,
                  f'/generate answered {e.code}: {e.read()[:300]!r}')
            time.sleep(0.5)
    check(len(body['tokens']) == max_tokens,
          f'/generate returned {len(body["tokens"])} tokens for '
          f'max_tokens={max_tokens} (prompt of {len(prompt)})')
    return body['tokens']


def wait_ready(url: str, proc: subprocess.Popen, log: str,
               deadline: float) -> None:
    while True:
        check(proc.poll() is None,
              f'server exited with code {proc.returncode} before it '
              f'was ready:\n{tail(log)}')
        check(time.monotonic() < deadline,
              f'server not ready in time:\n{tail(log)}')
        try:
            http_json(url + '/health', timeout=5)
            return
        except (urllib.error.URLError, OSError, ValueError):
            time.sleep(1.0)


def serve_leg(name: str, preset: str, tp: int, platform: str,
              run_dir: str, deadline: float, sizes: dict) -> dict:
    """sizes: prompt lengths and max_tokens (SIZES: the rehearsal's
    debug preset has a 128-token context)."""
    t0 = time.monotonic()
    port = free_port()
    log = os.path.join(run_dir, f'{name}.log')
    cmd = [sys.executable, '-m', 'skypilot_tpu.infer.server',
           '--model', preset, '--port', str(port), '--num-slots', '8',
           '--max-seq-len', '2048']
    if tp > 1:
        cmd += ['--tp', str(tp)]
    say(f'[{name}] $ {" ".join(cmd[1:])}')
    rng = random.Random(0)
    vocab = VOCAB[preset]

    def prompt(n: int):
        return [rng.randrange(1, vocab) for _ in range(n)]

    new = sizes['max_tokens']
    with open(log, 'wb') as logf:
        proc = subprocess.Popen(cmd, cwd=ROOT, stdout=logf,
                                stderr=subprocess.STDOUT,
                                env=child_env(platform, run_dir))
    door = None
    try:
        replica = f'http://127.0.0.1:{port}'
        wait_ready(replica, proc, log, deadline)
        ready_s = time.monotonic() - t0
        door = FrontDoor(replica)
        # The LB has the replica after its first controller sync;
        # until then its answer is 503.
        generate(door.url, prompt(sizes['single']), new,
                 retry_503_s=60.0)
        # Mixed lengths at once: the engine packs what queues up behind
        # the first admission into one ragged prefill (the flash
        # kernel's path in serving). One of them streams.
        for _ in range(3):
            lens = sizes['burst']
            with concurrent.futures.ThreadPoolExecutor(len(lens)) as ex:
                futs = [ex.submit(generate, door.url, prompt(n),
                                  new + 4 * i, stream=(i == 2))
                        for i, n in enumerate(lens)]
                for f in futs:
                    f.result()
            stats = http_json(door.url + '/stats')
            if stats.get('ragged_dispatches', 0) >= 1:
                break
        check(stats.get('ragged_dispatches', 0) >= 1,
              'three concurrent bursts and no packed (ragged) prefill')
        # The same greedy request twice, alone, shorter than one KV
        # page so both take the same path: token-identical.
        again = prompt(sizes['repeated'])
        first = generate(door.url, again, new)
        check(generate(door.url, again, new) == first,
              'the repeated greedy request returned different tokens')
        # A longer prompt twice: the second admission shares the
        # first's pages through the prefix cache.
        shared = prompt(sizes['shared'])
        generate(door.url, shared, new)
        generate(door.url, shared, new)
        stats = http_json(door.url + '/stats')
        check(stats['prefix_cache']['hit_pages'] >= 1,
              f'no prefix-cache hit: {stats.get("prefix_cache")}')
    finally:
        if door is not None:
            door.close()
        stop(proc)
    dev = stats['device']
    paths = stats['kernel_paths']
    check(dev['platform'] == platform,
          f'server ran on {dev["platform"]}, not {platform}')
    check(dev['count'] >= tp, f'{dev["count"]} devices for --tp {tp}')
    check(dev['pallas_interpret'] == (platform != 'tpu'),
          f'pallas_interpret={dev["pallas_interpret"]} on {platform}')
    # Off the TPU prefill's attention is the XLA reference by design,
    # so only the chip run can ask for the flash rung.
    ops = ('paged_attention',) + \
        (('flash_attention',) if platform == 'tpu' else ())
    for op in ops:
        check(str(paths.get(op, '')).startswith('pallas'),
              f'{op} is not on a Pallas rung: kernel_paths={paths}')
    if tp > 1 and platform == 'tpu':
        check_spread(dev, tp, name)
    return {'leg': name, 'preset': preset, 'tp': tp, 'device': dev,
            'kernel_paths': paths, 'ready_s': round(ready_s, 1),
            'wall_s': round(time.monotonic() - t0, 1),
            'compile_cache': stats['compile_cache'],
            'ragged_dispatches': stats['ragged_dispatches'],
            'prefix_cache': stats.get('prefix_cache')}


def check_spread(dev: dict, n: int, name: str) -> None:
    """n chips hold the model, evenly (what each holds now; a chip's
    transient peak is reported, not judged)."""
    mem = dev.get('memory') or []
    check(len(mem) >= n, f'[{name}] memory of {len(mem)} devices, want {n}')
    held = [m['bytes_in_use'] for m in mem[:n]]
    check(min(held) > 2 ** 30,
          f'[{name}] a chip holds under 1 GiB: {held}')
    check(max(held) < 1.25 * min(held),
          f'[{name}] uneven spread over the chips: {held}')


# ------------------------------------------------------------ train leg
def train_leg(name: str, preset: str, mesh: str, batch: int, seq: int,
              platform: str, run_dir: str, deadline: float) -> dict:
    t0 = time.monotonic()
    text = run_to_end(name, [
        sys.executable, '-m', 'skypilot_tpu.train.sft',
        '--model', preset, '--steps', '6', '--batch', str(batch),
        '--seq', str(seq), '--log-every', '2', '--mesh', mesh],
        platform, run_dir, deadline)

    def logged(pattern: str):
        m = re.search(pattern, text)
        check(m is not None, f'sft logged no line matching {pattern!r}')
        return m

    dev = json.loads(logged(r'device at exit: (\{.*\})').group(1))
    cache = json.loads(logged(r'compile cache: (\{.*\})').group(1))
    m = logged(r'kernel dispatch paths: (\{.*?\}) '
               r'\(pallas (\w+), flash backward (\w+)\)')
    paths = json.loads(m.group(1).replace("'", '"'))
    losses = [float(x) for x in
              re.findall(r'step \d+/6 loss=(\S+) tokens/s', text)]
    check(dev['platform'] == platform,
          f'sft ran on {dev["platform"]}, not {platform}')
    check(len(losses) == 3, f'expected 3 logged losses, got {losses}')
    check(all(math.isfinite(x) for x in losses),
          f'non-finite loss: {losses}')
    check(abs(losses[0] - math.log(VOCAB[preset])) < 1.0,
          f'first loss {losses[0]} is not near ln(vocab) = '
          f'{math.log(VOCAB[preset]):.3f}')
    check('done: 6 steps' in text, 'sft did not log its last line')
    if platform == 'tpu':
        check(m.group(2) == 'compiled', f'Pallas ran {m.group(2)}')
        check(str(paths.get('flash_attention', '')).startswith('pallas')
              and m.group(3) == 'pallas',
              f'flash is not on the Pallas rung forward and backward: '
              f'{m.group(0)}')
        n = math.prod(int(axis.split('=')[1])
                      for axis in mesh.split(','))
        if n > 1:
            check_spread(dev, n, name)
    return {'leg': name, 'preset': preset, 'mesh': mesh, 'batch': batch,
            'seq': seq, 'device': dev, 'kernel_paths': paths,
            'flash_backward': m.group(3), 'losses': losses,
            'wall_s': round(time.monotonic() - t0, 1),
            'compile_cache': cache}


# ------------------------------------------------------------ tests leg
def tests_leg(name: str, platform: str, n_devices: int, run_dir: str,
              deadline: float) -> dict:
    t0 = time.monotonic()
    text = run_to_end(name, [
        sys.executable, '-m', 'pytest', 'tests_tpu/', '-q',
        '-p', 'no:cacheprovider'], platform, run_dir, deadline)
    counts = {word: int(n) for n, word in re.findall(
        r'(\d+) (passed|skipped|failed|error)', text.splitlines()[-1])}
    # Off the chip the gate skips itself; on it, only the multi-device
    # collectives test (one chip) and the multi-slice test may skip.
    if platform == 'tpu':
        allowed = 2 if n_devices < 2 else 1
        check(counts.get('passed', 0) > 0 and
              counts.get('skipped', 0) <= allowed,
              f'tests_tpu: {counts}, at most {allowed} skips allowed')
    return {'leg': name, 'counts': counts,
            'wall_s': round(time.monotonic() - t0, 1)}


# ----------------------------------------------------------------- main
def main(argv=None) -> int:
    global _label
    parser = argparse.ArgumentParser(description=__doc__.split('\n')[0])
    parser.add_argument('--rehearse-cpu', action='store_true',
                        help='try the script without a chip: debug '
                             'preset on virtual CPU devices, every '
                             'line labelled; proves nothing about '
                             'the TPU')
    parser.add_argument('--legs', default=None,
                        help='comma-separated subset of '
                             f'{",".join(LEGS)} (default: all the '
                             'visible chips allow)')
    args = parser.parse_args(argv)
    if not os.path.isdir(os.path.join(ROOT, 'skypilot_tpu')):
        print('chip_smoke.py: no skypilot_tpu package next to this '
              'script; run it from a checkout', file=sys.stderr)
        return 2
    platform = 'cpu' if args.rehearse_cpu else 'tpu'
    if args.rehearse_cpu:
        _label = '[CPU REHEARSAL, not a chip result] '
    t_start = time.monotonic()
    shutil.rmtree(OUT_DIR, ignore_errors=True)
    os.makedirs(OUT_DIR)

    # Which device? Asked in a child, which exits (and lets go of the
    # chip) before the first leg starts.
    probe = subprocess.run([sys.executable, '-c', PROBE], cwd=ROOT,
                           env=child_env(platform, OUT_DIR),
                           capture_output=True, text=True, timeout=300)
    if probe.returncode != 0:
        print(f'chip_smoke.py: JAX found no {platform} device:\n'
              f'{probe.stderr[-400:]}', file=sys.stderr)
        return 3
    device = json.loads(probe.stdout.strip().splitlines()[-1])
    if device['platform'] != platform:
        print(f'chip_smoke.py: JAX selected {device}', file=sys.stderr)
        return 3
    say(f'device: {json.dumps(device)}')

    four = device['count'] >= 4
    want = args.legs.split(',') if args.legs else \
        [leg for leg in LEGS if four or not leg.endswith('4')]
    unknown = set(want) - set(LEGS)
    if unknown or (not four and any(leg.endswith('4') for leg in want)):
        print(f'chip_smoke.py: cannot run legs {want} on {device}',
              file=sys.stderr)
        return 2
    plan = PLAN[platform]
    # The 1200 s limit is for the one-chip legs; a four-chip host runs
    # two more.
    give_up = t_start + (2300 if four else 1150)
    results = []
    try:
        for leg in want:
            deadline = min(time.monotonic() + 700, give_up)
            if leg.startswith('serve'):
                res = serve_leg(leg, *plan[leg], platform, OUT_DIR,
                                deadline, SIZES[platform])
            elif leg == 'tests':
                res = tests_leg(leg, platform, device['count'], OUT_DIR,
                                deadline)
            else:
                res = train_leg(leg, *plan[leg], platform, OUT_DIR,
                                deadline)
            results.append(res)
            say(f'[{leg}] ok: {json.dumps(res)}')
        check('jax' not in sys.modules, 'this process imported JAX')
    except SmokeFailure as e:
        print(f'{_label}chip_smoke.py: FAILED in leg {leg}: {e}',
              file=sys.stderr)
        return 1
    for res in results:
        if 'compile_cache' not in res:
            say(f'[{res["leg"]}] tests_tpu {res["counts"]}; wall '
                f'{res["wall_s"]}s')
            continue
        cc = res['compile_cache']
        v = res['device']['versions']
        say(f'[{res["leg"]}] {res["preset"]} on '
            f'{res["device"]["count"]}x {res["device"]["device_kind"]} '
            f'({res["device"]["platform"]}); jax {v.get("jax")} jaxlib '
            f'{v.get("jaxlib")} libtpu {v.get("libtpu")}; wall '
            f'{res["wall_s"]}s, compiling {cc["compile_seconds"]}s, '
            f'compile cache {cc["dir"]}: {cc["hits"]} hit(s), '
            f'{cc["misses"]} new entr(ies)')
    say(f'total wall {time.monotonic() - t_start:.0f}s; '
        f'logs in {OUT_DIR}')
    say(json.dumps({'ok': True, 'device': device}))
    return 0


if __name__ == '__main__':
    sys.exit(main())
