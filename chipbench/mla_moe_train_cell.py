"""A training cell of a decoder of latent attention layers (DeepSeek-V2's:
a decoupled key part rotated under YaRN, the softmax scale YaRN's), a
dense first layer and softmax-routed experts with shared ones and a
sequence-wise balance loss, of which this chip holds a share (`kind`
`mla_moe_train`): train_cell's child, window and demands, and
moe_train_cell's routing demands, with three things of its own.

- The configuration's sizes are the published config.json keys at the
  top level of its file (SIZE_KEYS), with `router_outputs`,
  `experts_held` and `aux_loss_alpha` beside them; `mla_moe_flops.py`
  counts the parameters held and the step's operations from them.
- The check after the window is children/mla_moe_check_child.py
  (routing, loss, the balance loss, gradients and sft's own jitted step
  against references/<name>.py, as children/kda_moe_check_child.py
  compares them).
- `correct` demands, beside everything train_cell demands (the latent
  layers' flash on the compiled Pallas rung among it, under the
  full-attention op `flash_attention`): zero dropped pairs and a finite
  balance loss (`moe_aux`) on every step line of sft's log; the
  routing, the balance loss, the step's change of the parameters and
  its loss within the configuration's `check` tolerances; and sft's
  `latent attention plan` line as the sizes fix it (layers, heads,
  widths, the YaRN ramp's pairs and the softmax scale, computed here
  from `rope_scaling`).

Files of this kind: `mla_moe_train_cell.py` (this driver),
`mla_moe_flops.py` (the count), `children/mla_moe_check_child.py` (the
check), `references/deepseek_v2.py`, `readers/mfu_mla_moe.py`,
`readers/flash_latent_roofline.py` and `readers/scope_ms_per_step.py`
with `xplane_scopes.py` (device time by the program's named scopes).
"""
import json
import math
import os
import re
import subprocess
import types

import common
import kda_moe_train_cell
import mla_moe_flops
import moe_train_cell
import train_cell
from common import say

SIZE_KEYS = (
    'attention_bias', 'first_k_dense_replace', 'hidden_act', 'hidden_size',
    'intermediate_size', 'kv_lora_rank', 'max_position_embeddings',
    'model_type', 'moe_intermediate_size', 'moe_layer_freq', 'n_group',
    'n_routed_experts', 'n_shared_experts', 'norm_topk_prob',
    'num_attention_heads', 'num_experts_per_tok', 'num_hidden_layers',
    'num_key_value_heads', 'q_lora_rank', 'qk_nope_head_dim',
    'qk_rope_head_dim', 'rms_norm_eps', 'rope_scaling', 'rope_theta',
    'routed_scaling_factor', 'scoring_func', 'seq_aux',
    'tie_word_embeddings', 'topk_group', 'topk_method', 'v_head_dim',
    'vocab_size', 'router_outputs', 'experts_held', 'aux_loss_alpha')

AUX_RE = re.compile(r'moe_dropped=\d+ moe_rows=\S+ moe_aux=(\S+)')


def _flag(cfg: dict, name: str) -> str:
    return str(cfg['flags'][cfg['flags'].index(name) + 1])


def _check(cfg: dict, mix: dict, platform: str, seed: int,
           run_dir: str) -> dict:
    """Run the correctness child; its report, or {'error': why}."""
    spec = {'preset': _flag(cfg, '--model'),
            'sizes': cfg['model'], 'reference': cfg['reference'],
            'seed': seed, 'rows': cfg['check']['rows'], 'seq': mix['seq'],
            # the trainer as train_cell starts sft
            'lr': float(_flag(cfg, '--lr')), 'mesh': _flag(cfg, '--mesh'),
            'steps': 1000000}
    cmd = [common.python(),
           common.bench_path('children', 'mla_moe_check_child.py'),
           json.dumps(spec)]
    say(f'$ {" ".join(cmd[1:2])} (routing, loss, balance loss, gradients '
        f'and the step against references/{cfg["reference"]}.py)')
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
_COUNT = types.SimpleNamespace(matmul_params=mla_moe_flops.held_params)


def expected_plan(m: dict) -> dict:
    """The `latent attention plan` the sizes fix: the rotation's ramp
    (the pairs that turn beta_fast and beta_slow times over the original
    length) and the softmax scale, 1/sqrt(qk width) times
    (0.1 mscale_all_dim ln factor + 1)^2, computed here from the
    published `rope_scaling`."""
    d, base = m['qk_rope_head_dim'], m['rope_theta']
    qk = m['qk_nope_head_dim'] + d
    scale, rs = qk ** -0.5, m['rope_scaling']

    def pair(turns):
        return d * math.log(rs['original_max_position_embeddings'] /
                            (turns * 2 * math.pi)) / (2 * math.log(base))
    low = max(math.floor(pair(rs['beta_fast'])), 0)
    high = min(math.ceil(pair(rs['beta_slow'])), d - 1)
    scale *= (0.1 * rs['mscale_all_dim'] * math.log(rs['factor']) + 1) ** 2
    return {'layers': str(m['num_hidden_layers']),
            'heads': str(m['num_attention_heads']),
            'qk': f'{m["qk_nope_head_dim"]}+{d}', 'v': str(m['v_head_dim']),
            'kv_rank': str(m['kv_lora_rank']),
            'rope': f'yarn({rs["factor"]:g},'
                    f'{rs["original_max_position_embeddings"]},{low}-{high})',
            'softmax_scale': scale}


def _mla_problems(obs: dict) -> list:
    want, got = expected_plan(obs['sizes']), dict(obs['mla_plan'])
    try:
        scale = float(got.pop('softmax_scale'))
    except (KeyError, ValueError):
        return [f'sft printed no latent attention plan with a softmax '
                f'scale: {obs["mla_plan"]}']
    bad = []
    if abs(scale - want.pop('softmax_scale')) > 1e-6:
        bad.append(f'sft\'s latent attention plan has the softmax scale '
                   f'{scale}; the sizes give {expected_plan(obs["sizes"])}')
    if got != want:
        bad.append(f'sft\'s latent attention plan {obs["mla_plan"]} is not '
                   f'the sizes\' {want}')
    return bad


def _aux_problems(obs: dict, cfg: dict) -> list:
    bad = []
    steps = obs['aux_steps']
    if not steps or len(steps) != len(obs['moe_steps']) or \
            not all(math.isfinite(x) for x in steps):
        bad.append(f'a step line of sft lacks a finite balance loss: '
                   f'{steps[:5]} on {len(obs["moe_steps"])} lines')
    chk, tol = obs['check'], cfg['check']
    if 'error' not in chk and not abs(
            chk['aux_program'] - chk['aux_reference']) <= tol['aux_abs']:
        bad.append(f'the balance loss {chk["aux_program"]} against the '
                   f'reference\'s {chk["aux_reference"]}: over '
                   f'{tol["aux_abs"]} apart')
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
    mla = re.search(r'latent attention plan: (.*)', text)
    obs.update(
        sizes=sizes, rows=mix['rows'],
        moe_plan=dict(kv.split('=') for kv in plan.group(1).split())
        if plan else {},
        flash_plan=tiles.group(1) if tiles else '',
        mla_plan=dict(kv.split('=') for kv in mla.group(1).split())
        if mla else {},
        moe_steps=[{'held': int(a), 'pairs': int(b), 'fullest': float(c),
                    'dropped': int(d)}
                   for a, b, c, d in moe_train_cell.MOE_RE.findall(text)],
        aux_steps=[float(x) for x in AUX_RE.findall(text)])
    held = [s['held'] for s in obs['moe_steps']]
    say(f'routing: plan {obs["moe_plan"]}; pairs to held experts a step '
        f'{min(held, default=0)}-{max(held, default=0)} of '
        f'{obs["moe_steps"][0]["pairs"] if held else 0}; fullest expert '
        f'over the mean at most '
        f'{max((s["fullest"] for s in obs["moe_steps"]), default=0)}; '
        f'balance loss {min(obs["aux_steps"], default=0)}-'
        f'{max(obs["aux_steps"], default=0)}')
    say(f'flash tile plan: {obs["flash_plan"]}')
    say(f'latent attention plan: {obs["mla_plan"]}')
    obs['problems'] += moe_train_cell._routing_problems(obs, cfg)
    obs['problems'] += _mla_problems(obs) + _aux_problems(obs, cfg) + \
        kda_moe_train_cell._step_problems(obs, cfg)
    return obs
