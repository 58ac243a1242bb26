#!/usr/bin/env python3
"""chipbench: one run of one cell of BENCHMARK.json on the chip.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

This process never imports JAX. It starts the cell's entry point — the
command a user types — as a child with JAX_PLATFORMS=tpu, waits until it
is ready and warm (all of that is set-up), measures for --seconds, stops
the child with SIGTERM and checks its exit code; the cell's driver then
holds the program to the configuration's plain reference. One process
holds the chip at a time. The report goes to earlier lines; the last
line of stdout is the one JSON object of the contract. A run that finds
no TPU, or too few chips, exits non-zero and prints no result.

Everything that belongs to one configuration, one traffic mix or one
metric is a file found by its name (README.md): configs/<config>.json,
traffic/<mix>.json, metrics/<metric>.json and the reader it names
under readers/.

    --rehearse-cpu   try the harness where there is no chip: the
                     configuration's `rehearsal` preset on the CPU,
                     every metric name prefixed `cpu_rehearsal.`.
                     Never a benchmark result.

A configuration's `kind` names its driver, <kind>_cell.py, whose
run(cfg, mix, platform, seed, seconds, trace, run_dir) returns what it
observed: `device` (the child's own report), `problems` (why the run is
not correct; empty if it is), `attempted`, `failed`, `profile_dir` and
`whole_steps` for the trace, and whatever its metrics' readers read.
"""
import argparse
import importlib
import json
import os
import shutil
import subprocess
import sys
import time

import common
from common import BenchFailure, say


def _args(argv):
    p = argparse.ArgumentParser(description=__doc__.split('\n')[0])
    p.add_argument('--workload', required=True)
    p.add_argument('--seed', type=int, default=0)
    p.add_argument('--seconds', type=float, default=None)
    p.add_argument('--trace', type=int, choices=(0, 1), default=0)
    p.add_argument('--rehearse-cpu', action='store_true')
    return p.parse_args(argv)


def _for_cell(metrics: list, cell: str) -> list:
    return [m for m in metrics if cell in m.get('workloads', [cell])]


def _merge(base: dict, over: dict) -> dict:
    out = dict(base)
    for k, v in over.items():
        out[k] = _merge(out[k], v) if isinstance(v, dict) and \
            isinstance(out.get(k), dict) else v
    return out


def _reduce_trace(profile_dir: str, whole_steps: bool):
    """The trace reducer runs in a child pinned to the CPU: it needs
    JAX to read the file, and this process stays off JAX."""
    import xplane
    path = xplane.find_trace(profile_dir) if profile_dir else None
    if path is None:
        say(f'no trace file under {profile_dir}')
        return None
    cmd = [common.python(), common.bench_path('xplane.py'), path] + \
        (['steps'] if whole_steps else [])
    res = subprocess.run(cmd, env=common.child_env('cpu', {}), cwd=common.ROOT,
                         capture_output=True, text=True, timeout=300)
    if res.returncode != 0:
        say(f'trace reducer failed: {res.stderr[-400:]}')
        return None
    red = json.loads(res.stdout.strip().splitlines()[-1])
    return red if red.get('chips') else None


def _device(obs: dict) -> dict:
    info = obs['device']
    if not info:
        raise BenchFailure(f'the child reported no device:\n'
                           f'{common.tail(obs["log"])}')
    peaks = [m.get('peak_bytes_in_use') or 0 for m in info.get('memory', [])]
    return {'platform': info['platform'], 'kind': info['device_kind'],
            'count': info['count'],
            'memory_peak_bytes': max(peaks, default=0)}


def main(argv=None) -> int:
    args = _args(argv)
    if not os.path.isdir(os.path.join(common.ROOT, 'skypilot_tpu')):
        print('chipbench: no skypilot_tpu package beside chipbench/; run '
              'it from a checkout', file=sys.stderr)
        return 2
    bench = common.load_json(os.path.join(common.ROOT, 'BENCHMARK.json'))
    cells = {w['name']: w for w in bench['workloads']}
    if args.workload not in cells:
        print(f'chipbench: no cell {args.workload!r}; BENCHMARK.json has '
              f'{sorted(cells)}', file=sys.stderr)
        return 2
    cell = cells[args.workload]
    entry = next(c for c in bench['configs'] if c['name'] == cell['config'])
    cfg = common.load_json(os.path.join(common.ROOT, entry['file']))
    mix = common.load_json(common.bench_path('traffic',
                                             cell['traffic'] + '.json'))
    rehearse = args.rehearse_cpu
    platform = 'cpu' if rehearse else 'tpu'
    if rehearse:
        cfg = _merge(cfg, cfg['rehearsal'])
        mix = _merge(mix, mix.get('rehearsal', {}))
    seconds = args.seconds if args.seconds is not None \
        else float(bench['run_seconds'])
    run_dir = os.path.join(common.OUT_DIR, args.workload,
                           f'seed{args.seed}_trace{args.trace}')
    shutil.rmtree(run_dir, ignore_errors=True)
    os.makedirs(run_dir)
    label = '[CPU REHEARSAL, not a chip result] ' if rehearse else ''
    say(f'{label}cell {cell["name"]}: config {cell["config"]}, traffic '
        f'{cell["traffic"]}, seed {args.seed}, {seconds:g}s, trace '
        f'{args.trace}')
    try:
        driver = importlib.import_module(cfg['kind'] + '_cell')
        obs = driver.run(cfg, mix, platform, args.seed, seconds, args.trace,
                         run_dir)
        dev = _device(obs)
        if dev['platform'] != platform or dev['count'] < cell['chips']:
            raise BenchFailure(f'the cell needs {cell["chips"]} {platform} '
                               f'chip(s); the child ran on {dev}')
    except BenchFailure as e:
        print(f'chipbench: {e}', file=sys.stderr)
        return 3
    say(f'device: {json.dumps(dev)}')
    peaks = common.load_json(common.bench_path('peaks.json'))
    if not rehearse and dev['kind'] not in peaks:
        print(f'chipbench: no peaks for device kind {dev["kind"]!r} in '
              f'peaks.json', file=sys.stderr)
        return 3
    obs.update(rehearsal=rehearse, peak=peaks.get(dev['kind']),
               chips=cell['chips'], model=cfg.get('model'), trace=None)
    if args.trace:
        obs['trace'] = _reduce_trace(obs.get('profile_dir'),
                                     obs.get('whole_steps', False))
    for p in obs['problems']:
        say(f'NOT CORRECT: {p}')
    metrics = {}
    wanted = _for_cell(bench['per_layer' if args.trace else 'end_to_end'],
                       cell['name'])
    for m in wanted:
        spec = common.load_json(common.bench_path('metrics',
                                                  m['name'] + '.json'))
        reader = importlib.import_module('readers.' + spec['reader'])
        value = reader.read(obs, spec.get('params', {}))
        if value is None:
            if not args.trace:
                print(f'chipbench: end-to-end metric {m["name"]} could not '
                      f'be taken', file=sys.stderr)
                return 3
            continue
        prefix = 'cpu_rehearsal.' if rehearse else ''
        metrics[prefix + m['name']] = {'value': value, 'unit': m['unit']}
    line = {'correct': not obs['problems'], 'attempted': obs['attempted'],
            'failed': obs['failed'], 'metrics': metrics, 'device': dev}
    tr = obs['trace']
    if args.trace and tr:
        import xplane
        dev.update(busy_s=tr['busy_s'], window_s=tr['window_s'])
        line['breakdown'] = {
            'device_ops': [[xplane.short_name(n)[:120], s]
                           for n, s in tr['ops_s'][:10]],
            'idle_gaps': [['unattributed', g] for g in tr['gaps_s'][:10]]}
        say(f'trace: busy {tr["busy_s"]:.4f}s of {tr["window_s"]:.4f}s on '
            f'{tr["chips"]} chip(s), {tr["steps"]} whole program runs; '
            f'programs {tr["programs"]}')
    elif args.trace and not rehearse:
        print('chipbench: the traced run brought back no device trace',
              file=sys.stderr)
        return 3
    say(f'wall {time.monotonic() - common.T_PROCESS_START:.1f}s; logs in '
        f'{run_dir}')
    with open(os.path.join(run_dir, 'result.json'), 'w') as f:
        json.dump(line, f)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == '__main__':
    sys.exit(main())
