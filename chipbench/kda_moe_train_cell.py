"""A training cell of a decoder of Kimi Delta Attention and latent
attention layers, a dense first layer and routed experts with a shared
one, of which this chip holds a share (`kind` `kda_moe_train`):
train_cell's child, window and demands, and moe_train_cell's routing
demands, with three things of its own.

- The configuration's sizes are the published config.json keys at the
  top level of its file (SIZE_KEYS), with `router_outputs` and
  `experts_held` beside them; `kda_moe_flops.py` counts the parameters
  held and the step's operations from them.
- The check after the window is children/kda_moe_check_child.py
  (routing, loss and gradients against references/<name>.py, as
  children/moe_check_child.py compares them, the expert bias drawn
  non-zero; and sft's own jitted step, whose change of the parameters
  and loss it holds to the same optimizer's on the reference's
  gradients).
- `correct` demands, beside everything train_cell demands (the latent
  layers' flash on the compiled Pallas rung among it, under the
  full-attention op `flash_attention`): zero dropped pairs on every step
  line of sft's log; the routing, the step's change of the parameters
  and its loss within the configuration's `check` tolerances; and sft's
  `kda plan` line as the sizes and the row fix it (the KDA layers, heads
  and widths, and chunks that cover the row; the chunk itself is the
  program's choice, which the roofline's count reads from the line).

Files of this kind: `kda_moe_train_cell.py` (this driver),
`kda_moe_flops.py` (the count), `children/kda_moe_check_child.py` (the
check), `references/kimi_linear.py`, `readers/mfu_kda_moe.py`,
`readers/kda_scan_roofline.py` and `readers/scope_ms_per_step.py` with
`xplane_scopes.py` (device time by the program's named scopes).
"""
import json
import os
import re
import subprocess
import types

import common
import kda_moe_flops
import moe_train_cell
import train_cell
from common import say

SIZE_KEYS = (
    'first_k_dense_replace', 'head_dim', 'hidden_act', 'hidden_size',
    'intermediate_size', 'kv_lora_rank', 'linear_attn_config',
    'mla_use_nope', 'model_max_length', 'model_type',
    'moe_intermediate_size', 'moe_layer_freq', 'moe_renormalize',
    'moe_router_activation_func', 'num_attention_heads',
    'num_expert_group', 'num_experts', 'num_experts_per_token',
    'num_hidden_layers', 'num_key_value_heads', 'num_nextn_predict_layers',
    'num_shared_experts', 'q_lora_rank', 'qk_nope_head_dim',
    'qk_rope_head_dim', 'rms_norm_eps', 'rope_scaling', 'rope_theta',
    'routed_scaling_factor', 'tie_word_embeddings', 'topk_group',
    'use_grouped_topk', 'v_head_dim', 'vocab_size', 'router_outputs',
    'experts_held')


def _flag(cfg: dict, name: str) -> str:
    return str(cfg['flags'][cfg['flags'].index(name) + 1])


def _check(cfg: dict, mix: dict, platform: str, seed: int,
           run_dir: str) -> dict:
    """Run the correctness child; its report, or {'error': why}."""
    spec = {'preset': _flag(cfg, '--model'),
            'sizes': cfg['model'], 'reference': cfg['reference'],
            'seed': seed, 'rows': cfg['check']['rows'], 'seq': mix['seq'],
            'bias_scale': cfg['check']['bias_scale'],
            # the trainer as train_cell starts sft
            'lr': float(_flag(cfg, '--lr')), 'mesh': _flag(cfg, '--mesh'),
            'steps': 1000000}
    cmd = [common.python(),
           common.bench_path('children', 'kda_moe_check_child.py'),
           json.dumps(spec)]
    say(f'$ {" ".join(cmd[1:2])} (routing, loss, gradients and the step '
        f'against references/{cfg["reference"]}.py)')
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
_COUNT = types.SimpleNamespace(matmul_params=kda_moe_flops.held_params)


def _kda_problems(obs: dict) -> list:
    la, seq = obs['sizes']['linear_attn_config'], obs['mix']['seq']
    want = {'layers': len(la['kda_layers']), 'heads': la['num_heads'],
            'dk': la['head_dim'], 'dv': la['head_dim']}
    try:
        got = {k: int(obs['kda_plan'][k]) for k in (*want, 'chunk', 'chunks')}
    except (KeyError, ValueError):
        return [f'sft printed no kda plan with the chunked form\'s numbers: '
                f'{obs["kda_plan"]}']
    if {k: got[k] for k in want} != want or got['chunk'] < 1 or \
            got['chunks'] * got['chunk'] < seq:
        return [f'sft\'s kda plan {obs["kda_plan"]} is not the chunked form '
                f'of the sizes ({want}, chunks covering {seq} positions)']
    return []


def _step_problems(obs: dict, cfg: dict) -> list:
    """The trainer's step against the reference's: its loss, and its
    change of the parameters, whole tree and worst leaf."""
    chk, tol = obs['check'], cfg['check']
    if 'error' in chk:
        return []
    bad = []
    if not abs(chk['loss_step'] - chk['loss_reference']) <= tol['loss_abs']:
        bad.append(f'the step\'s loss {chk["loss_step"]} against the '
                   f'reference\'s {chk["loss_reference"]}: over '
                   f'{tol["loss_abs"]} apart')
    if not chk['update_rel_err'] <= tol['update_rel'] or \
            not chk['update_rel_err_worst_leaf'] <= tol['update_rel_leaf']:
        bad.append(f'the step changed the parameters {chk["update_rel_err"]} '
                   f'of the reference\'s change off (allowed '
                   f'{tol["update_rel"]}), worst leaf '
                   f'{chk["worst_update_leaf"]} by '
                   f'{chk["update_rel_err_worst_leaf"]} (allowed '
                   f'{tol["update_rel_leaf"]})')
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
    kda = re.search(r'kda plan: (.*)', text)
    obs.update(
        sizes=sizes, rows=mix['rows'],
        moe_plan=dict(kv.split('=') for kv in plan.group(1).split())
        if plan else {},
        flash_plan=tiles.group(1) if tiles else '',
        kda_plan=dict(kv.split('=') for kv in kda.group(1).split())
        if kda else {},
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
    say(f'kda plan: {obs["kda_plan"]}')
    obs['problems'] += moe_train_cell._routing_problems(obs, cfg)
    obs['problems'] += _kda_problems(obs) + _step_problems(obs, cfg)
    return obs
