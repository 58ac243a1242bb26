"""A training cell of a decoder of full attention and routed experts in
every layer, of which this chip holds a share, trained by block
diffusion (`kind` `bd_moe_train`): train_cell's child, window and
demands, and moe_train_cell's routing demands, with these of its own.

- The configuration's sizes are the published config.json keys at the
  top level of its file (SIZE_KEYS), with `router_outputs`,
  `experts_held`, `block_length`, `mask_id` and `level_min` (the noise
  levels lie above it) beside them;
  `bd_moe_flops.py` counts the parameters held and the step's operations
  from them. `seq` of the traffic is L, the data's length: the program
  passes 2L positions, and the tokens a step counts are the data's.
- The data's ids lie under the mask's id (the last row of the slice
  held): the rows of the seeded file are drawn from `vocab_size` - 1.
- The check after the window is children/bd_moe_check_child.py
  (routing, loss and gradients against references/<name>.py under the
  same seeded noise, as children/swa_moe_check_child.py compares them).
- `correct` demands, beside what train_cell demands (its demand for the
  flash kernels on the compiled Pallas rung is read under this model's
  own op, `flash_block_diffusion_attention`: no causal flash call exists
  in it, so none is demanded): zero dropped pairs on every step line of
  sft's log; the routing within the configuration's `check` tolerances;
  the objective's counters on every step line, with the share of masked
  targets over the run within `masked_share_within` of a half and the
  mean weight over the run's masked targets within `weight_mean_within`
  of 2 / (1 + level_min), the inverse of the mean level; the noise the
  check drew, from (m, t) alone (both sides of the comparison are
  handed it, so the comparison cannot see a fault in it): one level a
  block, every level in [level_min, 1], the masked count within
  `masked_sd_within` standard deviations of the sum of the levels; and
  the plan line the program prints of its objective equal to the
  configuration's (block, data, positions, allowed pairs, mask id).

Files of this kind: `bd_moe_train_cell.py` (this driver),
`bd_moe_flops.py` (the count), `children/bd_moe_check_child.py` (the
check), `references/sdar_moe.py`, `readers/mfu_bd_moe.py`,
`readers/flash_bd_roofline.py` and `readers/scope_ms_per_step.py` with
`xplane_scopes.py` (device time by the program's named scopes).
"""
import json
import os
import re
import subprocess
import types

import bd_moe_flops
import common
import moe_train_cell
import traffic_gen
import train_cell
from common import say

SIZE_KEYS = (
    'attention_bias', 'decoder_sparse_step', 'head_dim', 'hidden_act',
    'hidden_size', 'intermediate_size', 'max_position_embeddings',
    'mlp_only_layers', 'model_type', 'moe_intermediate_size',
    'norm_topk_prob', 'num_attention_heads', 'num_experts',
    'num_experts_per_tok', 'num_hidden_layers', 'num_key_value_heads',
    'rms_norm_eps', 'rope_scaling', 'rope_theta', 'tie_word_embeddings',
    'vocab_size', 'router_outputs', 'experts_held', 'block_length',
    'mask_id', 'level_min')

BD_OP = 'flash_block_diffusion_attention'
BD_RE = re.compile(r'bd_masked=(\d+)/(\d+) bd_weight_mean=(\S+)')


def _check(cfg: dict, mix: dict, platform: str, seed: int,
           run_dir: str) -> dict:
    """Run the correctness child; its report, or {'error': why}."""
    spec = {'preset': cfg['flags'][cfg['flags'].index('--model') + 1],
            'sizes': cfg['model'], 'reference': cfg['reference'],
            'seed': seed, 'rows': cfg['check']['rows'], 'seq': mix['seq']}
    cmd = [common.python(),
           common.bench_path('children', 'bd_moe_check_child.py'),
           json.dumps(spec)]
    say(f'$ {" ".join(cmd[1:2])} (routing, loss and gradients against '
        f'references/{cfg["reference"]}.py under the same noise)')
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


# What train_cell.run asks of `flops` and of `traffic_gen`: the
# parameters the train state's bytes are counted from, and rows of ids,
# here from under the mask's id.
_COUNT = types.SimpleNamespace(matmul_params=bd_moe_flops.held_params)
_ROWS = types.SimpleNamespace(
    train_rows=lambda vocab, seed, n_rows, seq: traffic_gen.train_rows(
        vocab - 1, seed, n_rows, seq))
_TRAIN_PROBLEMS = train_cell._problems


def _as_flash(paths: dict) -> dict:
    return dict(paths, flash_attention=paths.get(BD_OP, ''))


def _problems(obs: dict, cfg: dict, on_chip: bool) -> list:
    """train_cell's demands, its flash rung read under this model's op."""
    obs = dict(obs, kernel_paths=_as_flash(obs['kernel_paths']))
    if 'error' not in obs['check']:
        obs['check'] = dict(obs['check'], kernel_paths=_as_flash(
            obs['check']['kernel_paths']))
    return _TRAIN_PROBLEMS(obs, cfg, on_chip)


def _objective_problems(obs: dict, cfg: dict, mix: dict) -> list:
    bad = []
    steps, sizes, tol = obs['bd_steps'], obs['sizes'], cfg['check']
    targets = mix['rows'] * mix['seq']
    if len(steps) != len(obs['moe_steps']) or not steps or any(
            s['targets'] != targets for s in steps):
        bad.append(f'sft\'s step lines do not all carry the objective\'s '
                   f'counters over {targets} targets: {steps[:3]}')
    else:
        masked = sum(s['masked'] for s in steps)
        share = masked / (targets * len(steps))
        if not abs(share - 0.5) <= tol['masked_share_within']:
            bad.append(f'{share:.4f} of the targets were masked over '
                       f'{len(steps)} steps; a half is expected, within '
                       f'{tol["masked_share_within"]}')
        weight = sum(s['weight'] * s['masked'] for s in steps) / max(
            masked, 1)
        want = 2.0 / (1.0 + sizes['level_min'])
        if not abs(weight - want) <= tol['weight_mean_within']:
            bad.append(f'the masked targets of {len(steps)} steps weigh '
                       f'{weight:.4f} in the mean; {want:.4f}, the inverse '
                       f'of the mean level, is expected, within '
                       f'{tol["weight_mean_within"]}')
    drawn = obs['check'].get('noise')
    if drawn is not None and not (
            drawn['one_level_a_block']
            and sizes['level_min'] <= drawn['level_min']
            and drawn['level_max'] <= 1.0
            and abs(drawn['masked'] - drawn['masked_expected']) <=
            tol['masked_sd_within'] * drawn['masked_sd']):
        bad.append(f'the noise the check drew is not the objective\'s (one '
                   f'level a block in [{sizes["level_min"]}, 1], masked '
                   f'with probability t within {tol["masked_sd_within"]} '
                   f'standard deviations): {drawn}')
    want = {'block': sizes['block_length'], 'data': mix['seq'],
            'positions': 2 * mix['seq'],
            'allowed_pairs': bd_moe_flops.allowed_pairs(
                mix['seq'], sizes['block_length']),
            'mask_id': sizes['mask_id']}
    if obs['bd_plan'] != {k: str(v) for k, v in want.items()}:
        bad.append(f'the program\'s block diffusion plan {obs["bd_plan"]} '
                   f'is not the configuration\'s {want}')
    return bad


def run(cfg: dict, mix: dict, platform: str, seed: int, seconds: float,
        trace: int, run_dir: str) -> dict:
    sizes = {k: cfg[k] for k in SIZE_KEYS}
    cfg = dict(cfg, model=sizes)
    with moe_train_cell._bound(train_cell, _check=_check, flops=_COUNT,
                               traffic_gen=_ROWS, _problems=_problems):
        obs = train_cell.run(cfg, mix, platform, seed, seconds, trace,
                             run_dir)
    with open(obs['log'], encoding='utf-8', errors='replace') as f:
        text = f.read()
    plan = re.search(r'moe routing plan: (.*)', text)
    tiles = re.search(r'flash tile plan: (.*)', text)
    objective = re.search(r'block diffusion plan: (.*)', text)
    obs.update(
        sizes=sizes, rows=mix['rows'],
        moe_plan=dict(kv.split('=') for kv in plan.group(1).split())
        if plan else {},
        bd_plan=dict(kv.split('=') for kv in objective.group(1).split())
        if objective else {},
        flash_plan=tiles.group(1) if tiles else '',
        moe_steps=[{'held': int(a), 'pairs': int(b), 'fullest': float(c),
                    'dropped': int(d)}
                   for a, b, c, d in moe_train_cell.MOE_RE.findall(text)],
        bd_steps=[{'masked': int(a), 'targets': int(b), 'weight': float(c)}
                  for a, b, c in BD_RE.findall(text)])
    held = [s['held'] for s in obs['moe_steps']]
    say(f'routing: plan {obs["moe_plan"]}; pairs to held experts a step '
        f'{min(held, default=0)}-{max(held, default=0)} of '
        f'{obs["moe_steps"][0]["pairs"] if held else 0}; fullest expert '
        f'over the mean at most '
        f'{max((s["fullest"] for s in obs["moe_steps"]), default=0)}')
    masked = [s['masked'] for s in obs['bd_steps']]
    say(f'objective: plan {obs["bd_plan"]}; masked targets a step '
        f'{min(masked, default=0)}-{max(masked, default=0)}; mean weight '
        f'{min((s["weight"] for s in obs["bd_steps"]), default=0)}-'
        f'{max((s["weight"] for s in obs["bd_steps"]), default=0)}')
    say(f'flash tile plan: {obs["flash_plan"]}')
    obs['problems'] += moe_train_cell._routing_problems(obs, cfg)
    obs['problems'] += _objective_problems(obs, cfg, mix)
    return obs
