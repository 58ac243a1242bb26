"""The window layers' flash calls' share of their roofline, in %: the
band's work a step (swa_moe_flops.py's `window_scores`: 12 * heads *
head size for every allowed (query, key) pair of the window layers,
forward and backward, nothing for recomputation or for masked area)
over the device time a step of the operations traced under
params['scope'] (readers/scope_ms_per_step.py), over chips times the
bf16 peak of peaks.json. Compute bounds it: at head size 128 a tile of
scores is made from operands read once, hundreds of operations a byte.
A program without the scope, or a configuration without window layers,
gives None."""
import swa_moe_flops
from readers import scope_ms_per_step


def read(obs, params):
    ms = scope_ms_per_step.read(obs, params)
    sizes = obs.get('sizes') or {}
    if not ms or obs.get('peak') is None or 'sliding_window' not in sizes:
        return None
    work = swa_moe_flops.train_flops_per_step(
        sizes, obs['rows'], obs['mix']['seq'], 0)['window_scores']
    return 100.0 * work / (ms / 1e3) / \
        (obs['peak']['bf16_flops_per_s'] * obs['chips'])
