"""The block-diffusion flash calls' share of their roofline, in %: the
allowed pairs' work a step (bd_moe_flops.py's `bd_scores`: 12 * heads *
head size for every (query, key) pair the mask allows, forward and
backward, nothing for recomputation, for masked area or for tiles
visited beyond the allowed pairs) over the device time a step of the
operations traced under params['scope'] (readers/scope_ms_per_step.py),
over chips times the bf16 peak of peaks.json. Compute bounds it: at head
size 128 a tile of scores is made from operands read once, hundreds of
operations a byte. A program without the scope, or a configuration
without the objective, gives None."""
import bd_moe_flops
from readers import scope_ms_per_step


def read(obs, params):
    ms = scope_ms_per_step.read(obs, params)
    sizes = obs.get('sizes') or {}
    if not ms or obs.get('peak') is None or 'block_length' not in sizes:
        return None
    work = bd_moe_flops.train_flops_per_step(
        sizes, obs['rows'], obs['mix']['seq'], 0)['bd_scores']
    return 100.0 * work / (ms / 1e3) / \
        (obs['peak']['bf16_flops_per_s'] * obs['chips'])
