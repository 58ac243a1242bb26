"""A training cell: the trainer as a user starts it, its step
boundaries read from its own log on this process's clock.

    python -m skypilot_tpu.train.sft --model <preset> <flags> \
        --steps <far beyond the window> --log-every 1 --data <seeded file>

(through children/entry_child.py, which adds the exit-time device line
sft does not print when it is stopped). The window opens at the first
step boundary after the warm steps and closes at the first boundary at
or after --seconds later; the rate is taken over all steps and all the
time between the two. SIGTERM then ends sft through its preemption
guard, whose exit code is the clean one here.

When sft has gone, a second child (children/check_child.py) takes the
chip and holds the program's loss and gradients to the configuration's
plain float32 reference on seeded weights and rows: outside set-up and
outside the window, and part of `correct`.
"""
import json
import math
import os
import re
import subprocess
import threading
import time

import common
import flops
import traffic_gen
from common import BenchFailure, say

STEP_RE = re.compile(r'step (\d+)/\d+ loss=(\S+) tokens/s')


def _write_data(path: str, rows) -> None:
    with open(path, 'w', encoding='utf-8') as f:
        for row in rows:
            f.write('{"tokens": ' + json.dumps(row.tolist()) + '}\n')


def _check(cfg: dict, mix: dict, platform: str, seed: int,
           run_dir: str) -> dict:
    """Run the correctness child; its report, or {'error': why}."""
    spec = {'preset': cfg['flags'][cfg['flags'].index('--model') + 1],
            'model': cfg['model'], 'reference': cfg['reference'],
            'seed': seed, 'rows': cfg['check']['rows'], 'seq': mix['seq']}
    cmd = [common.python(), common.bench_path('children', 'check_child.py'),
           json.dumps(spec)]
    say(f'$ {" ".join(cmd[1:2])} (loss and gradients against '
        f'references/{cfg["reference"]}.py)')
    log = os.path.join(run_dir, 'check.log')
    try:
        res = subprocess.run(cmd, cwd=common.ROOT, text=True, timeout=900,
                             env=common.child_env(platform, {}),
                             stdout=subprocess.PIPE, stderr=subprocess.STDOUT)
    except subprocess.TimeoutExpired:
        return {'error': 'the correctness child ran over 900 s'}
    with open(log, 'w', encoding='utf-8') as f:
        f.write(res.stdout)
    found = re.search(r'^chipbench-check: (\{.*\})$', res.stdout, re.M)
    if res.returncode != 0 or not found:
        return {'error': f'the correctness child exited with code '
                         f'{res.returncode}:\n{common.tail(log, 12)}'}
    return json.loads(found.group(1))


def _problems(obs: dict, cfg: dict, on_chip: bool) -> list:
    bad = []
    losses, chk, tol = obs['losses'], obs['check'], cfg['check']
    if obs['exit_code'] != cfg['sigterm_exit_code'] or obs['traceback']:
        bad.append(f'sft exit code {obs["exit_code"]} (clean is '
                   f'{cfg["sigterm_exit_code"]}), traceback: '
                   f'{obs["traceback"]}')
    if not losses or not all(math.isfinite(x) for x in losses):
        bad.append('a loss is missing or not finite')
    elif abs(losses[0] - obs['ln_vocab']) > cfg['first_loss_within']:
        bad.append(f'first loss {losses[0]} not within '
                   f'{cfg["first_loss_within"]} of ln(vocab) '
                   f'{obs["ln_vocab"]:.3f}')
    if obs['compiles_in_window']:
        bad.append(f'{obs["compiles_in_window"]} programs compiled inside '
                   f'the window')
    if on_chip and not (
            str(obs['kernel_paths'].get('flash_attention', '')
                ).startswith('pallas') and obs['pallas'] == 'compiled'
            and obs['flash_backward'] == 'pallas'):
        bad.append(f'flash is not on the compiled Pallas rung forward and '
                   f'backward: {obs["kernel_paths"]} {obs["pallas"]} '
                   f'{obs["flash_backward"]}')
    if 'error' in chk:
        return bad + [chk['error']]
    if not abs(chk['loss_program'] - chk['loss_reference']) <= \
            tol['loss_abs']:
        bad.append(f'loss {chk["loss_program"]} against the reference\'s '
                   f'{chk["loss_reference"]}: over {tol["loss_abs"]} apart')
    if not chk['grad_rel_err'] <= tol['grad_rel'] or \
            not chk['grad_rel_err_worst_leaf'] <= tol['grad_rel_leaf']:
        bad.append(f'gradients off the reference\'s by {chk["grad_rel_err"]} '
                   f'of their norm (allowed {tol["grad_rel"]}), worst leaf '
                   f'{chk["worst_leaf"]} by {chk["grad_rel_err_worst_leaf"]} '
                   f'(allowed {tol["grad_rel_leaf"]})')
    if chk['param_dtypes'] != [cfg['state_dtype']] or \
            chk['opt_state_dtypes'] != [cfg['state_dtype']]:
        bad.append(f'master weights {chk["param_dtypes"]} and optimizer '
                   f'state {chk["opt_state_dtypes"]} are not the '
                   f'configuration\'s {cfg["state_dtype"]}')
    if on_chip and (chk['pallas_interpret'] or not str(
            chk['kernel_paths'].get('flash_attention', '')
            ).startswith('pallas')):
        bad.append(f'the checked model did not run flash on the compiled '
                   f'Pallas rung: {chk["kernel_paths"]}')
    peak = max((m.get('peak_bytes_in_use') or 0 for m in
                (obs['device'] or {}).get('memory', [])), default=0)
    if on_chip and peak < obs['state_bytes']:
        bad.append(f'the device held {peak} bytes at most, less than the '
                   f'{obs["state_bytes"]} of the stated train state')
    return bad


def run(cfg: dict, mix: dict, platform: str, seed: int, seconds: float,
        trace: int, run_dir: str) -> dict:
    rows, seq = mix['rows'], mix['seq']
    warm = mix['warm_steps']
    vocab = cfg['vocab_size']
    data = os.path.join(run_dir, 'data.jsonl')
    _write_data(data, traffic_gen.train_rows(
        vocab, seed, rows * mix['distinct_steps'], seq))
    cmd = [common.python(), common.bench_path('children', 'entry_child.py'),
           cfg['entry']] + [str(x) for x in cfg['flags']] + [
        '--batch', str(rows), '--seq', str(seq), '--steps', '1000000',
        '--log-every', '1', '--data', data]
    extra = {}
    if trace:
        extra = {'SKYT_PROFILE_DIR': os.path.join(run_dir, 'profile'),
                 'SKYT_PROFILE_START_STEP': str(warm + 3),
                 'SKYT_PROFILE_NUM_STEPS': str(mix['traced_steps'])}
    log = os.path.join(run_dir, 'sft.log')
    proc = common.start_child(cmd, platform, extra, log, pipe=True)
    steps, compiles, lines, marks = [], [], [], {}
    closed = threading.Event()
    state = {'open': None, 'close': None}

    def reader() -> None:
        with open(log, 'w', encoding='utf-8') as logf:
            for raw in proc.stdout:
                now = time.monotonic()
                line = raw.decode('utf-8', 'replace')
                logf.write(line)
                lines.append(line)
                marks.setdefault('first line', now)
                if 'global devices' in line:
                    marks.setdefault('devices', now)
                m = STEP_RE.search(line)
                if m:
                    marks.setdefault('first step', now)
                    steps.append((int(m.group(1)), now, float(m.group(2))))
                    if len(steps) == warm:
                        state['open'] = now
                    elif state['open'] is not None and \
                            state['close'] is None and \
                            now - state['open'] >= seconds:
                        state['close'] = now
                        closed.set()
                elif line.startswith('chipbench-compile:'):
                    compiles.append((now, float(line.split()[1])))
        closed.set()

    thread = threading.Thread(target=reader, daemon=True)
    thread.start()
    try:
        if not closed.wait(timeout=1100 + seconds):
            raise BenchFailure(f'sft did not reach the end of the window:\n'
                               f'{common.tail(log)}')
        if state['close'] is None:
            raise BenchFailure(f'sft ended with code {proc.poll()} before '
                               f'the window closed:\n{common.tail(log)}')
    finally:
        exit_code = common.stop_child(proc, grace_s=60)
        thread.join(timeout=30)
        os.remove(data)     # megabytes a run; the seed makes it again
    text = ''.join(lines)
    inside = [s for s in steps if state['open'] <= s[1] <= state['close']]

    check = _check(cfg, mix, platform, seed, run_dir)
    say('check: ' + json.dumps(check))
    dev = re.search(r'chipbench-exit: (\{.*\})', text)
    paths = re.search(r'kernel dispatch paths: (\{.*?\}) '
                      r'\(pallas (\w+), flash backward (\w+)\)', text)
    obs = {
        'log': log, 'mix': mix, 'exit_code': exit_code,
        'boundaries': [t for _, t, _ in inside],
        'losses': [x for _, _, x in steps],
        'tokens_per_step': rows * seq,
        'setup_s': state['open'] - common.T_PROCESS_START,
        'compiles': compiles,
        'compiles_in_window': sum(
            1 for t, _ in compiles if state['open'] <= t <= state['close']),
        'device': json.loads(dev.group(1))['device'] if dev else None,
        'check': check, 'whole_steps': True,
        'attempted': max(0, len(inside) - 1), 'failed': 0,
        'state_bytes': cfg['state_bytes_per_param'] *
        flops.matmul_params(cfg['model']),
        'kernel_paths': json.loads(paths.group(1).replace("'", '"'))
        if paths else {},
        'pallas': paths.group(2) if paths else None,
        'flash_backward': paths.group(3) if paths else None,
        'traceback': 'Traceback (most recent call last)' in text,
        'ln_vocab': math.log(vocab),
        'profile_dir': extra.get('SKYT_PROFILE_DIR'),
    }
    # Where set-up went: the time before the child's first line and before
    # its devices are up is Python, imports and the TPU runtime's start.
    marks['window open'] = state['open']
    say('set-up: ' + ', '.join(
        f'{k} at {t - common.T_PROCESS_START:.1f}s' for k, t in marks.items()))
    say(f'steps in window: {len(inside) - 1}; window '
        f'{state["close"] - state["open"]:.4f}s; losses first/last '
        f'{obs["losses"][:1]}/{obs["losses"][-1:]}; compiles '
        f'{len(compiles)} ({sum(d for _, d in compiles):.1f}s), in window '
        f'{obs["compiles_in_window"]}; exit code {exit_code}')
    obs['problems'] = _problems(obs, cfg, platform != 'cpu')
    return obs
