"""A training cell of a decoder whose layers mix window and full
attention and are all routed experts, of which this chip holds a share
(`kind` `swa_moe_train`): train_cell's child, window and demands, and
moe_train_cell's routing demands, with three things of its own.

- The configuration's sizes are the published config.json keys at the
  top level of its file (SIZE_KEYS), with `router_outputs` and
  `experts_held` beside them; `swa_moe_flops.py` counts the parameters
  held and the step's operations from them.
- The check after the window is children/swa_moe_check_child.py
  (routing, loss and gradients against references/<name>.py, as
  children/moe_check_child.py compares them; no expert bias to draw).
- `correct` demands, beside everything train_cell demands (the full
  layers' flash on the compiled Pallas rung among it): the window
  layers' flash on that rung too, by the op of their own that the
  program records (`flash_window_attention`), in sft's run and in the
  checked model; zero dropped pairs on every step line of sft's log;
  and the routing within the configuration's `check` tolerances.

Files of this kind: `swa_moe_train_cell.py` (this driver),
`swa_moe_flops.py` (the count), `children/swa_moe_check_child.py` (the
check), `references/mellum_moe.py`, `readers/mfu_swa_moe.py`,
`readers/flash_window_roofline.py` and `readers/scope_ms_per_step.py`
with `xplane_scopes.py` (device time by the program's named scopes).
"""
import json
import os
import re
import subprocess
import types

import common
import moe_train_cell
import swa_moe_flops
import train_cell
from common import say

SIZE_KEYS = (
    'attention_bias', 'head_dim', 'hidden_act', 'hidden_size',
    'intermediate_size', 'layer_types', 'mlp_layer_types',
    'max_position_embeddings', 'max_window_layers', 'model_type',
    'moe_intermediate_size', 'norm_topk_prob', 'num_attention_heads',
    'num_experts', 'num_experts_per_tok', 'num_hidden_layers',
    'num_key_value_heads', 'rms_norm_eps', 'rope_parameters',
    'sliding_window', 'tie_word_embeddings', 'vocab_size',
    'use_sliding_window', 'router_outputs', 'experts_held')

WINDOW_OP = 'flash_window_attention'


def _check(cfg: dict, mix: dict, platform: str, seed: int,
           run_dir: str) -> dict:
    """Run the correctness child; its report, or {'error': why}."""
    spec = {'preset': cfg['flags'][cfg['flags'].index('--model') + 1],
            'sizes': cfg['model'], 'reference': cfg['reference'],
            'seed': seed, 'rows': cfg['check']['rows'], 'seq': mix['seq']}
    cmd = [common.python(),
           common.bench_path('children', 'swa_moe_check_child.py'),
           json.dumps(spec)]
    say(f'$ {" ".join(cmd[1:2])} (routing, loss and gradients against '
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


# What train_cell.run asks of `flops`: the parameters the train state's
# bytes are counted from.
_COUNT = types.SimpleNamespace(matmul_params=swa_moe_flops.held_params)


def _window_problems(obs: dict) -> list:
    """The window layers ran the compiled Pallas rung, in sft's run and
    in the checked model: read under their own op, never inferred from
    the full layers'."""
    bad = []
    ran = {'sft': obs['kernel_paths']}
    if 'error' not in obs['check']:
        ran['the checked model'] = obs['check']['kernel_paths']
    for who, paths in ran.items():
        if not str(paths.get(WINDOW_OP, '')).startswith('pallas'):
            bad.append(f'the window layers of {who} did not run flash on '
                       f'the Pallas rung: {paths}')
    return bad


def run(cfg: dict, mix: dict, platform: str, seed: int, seconds: float,
        trace: int, run_dir: str) -> dict:
    sizes = {k: cfg[k] for k in SIZE_KEYS}
    cfg = dict(cfg, model=sizes)
    with moe_train_cell._bound(train_cell, _check=_check, flops=_COUNT):
        obs = train_cell.run(cfg, mix, platform, seed, seconds, trace,
                             run_dir)
    with open(obs['log'], encoding='utf-8', errors='replace') as f:
        text = f.read()
    plan = re.search(r'moe routing plan: (.*)', text)
    tiles = re.search(r'flash tile plan: (.*)', text)
    obs.update(
        sizes=sizes, rows=mix['rows'],
        moe_plan=dict(kv.split('=') for kv in plan.group(1).split())
        if plan else {},
        flash_plan=tiles.group(1) if tiles else '',
        moe_steps=[{'held': int(a), 'pairs': int(b), 'fullest': float(c),
                    'dropped': int(d)}
                   for a, b, c, d in moe_train_cell.MOE_RE.findall(text)])
    held = [s['held'] for s in obs['moe_steps']]
    say(f'routing: plan {obs["moe_plan"]}; pairs to held experts a step '
        f'{min(held, default=0)}-{max(held, default=0)} of '
        f'{obs["moe_steps"][0]["pairs"] if held else 0}; fullest expert '
        f'over the mean at most '
        f'{max((s["fullest"] for s in obs["moe_steps"]), default=0)}')
    say(f'flash tile plan: {obs["flash_plan"]}')
    obs['problems'] += moe_train_cell._routing_problems(obs, cfg)
    if platform != 'cpu':
        obs['problems'] += _window_problems(obs)
    return obs
