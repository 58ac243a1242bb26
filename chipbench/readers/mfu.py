"""Model FLOP/s utilization: the benchmark's own operations per token
(flops.py) times the tokens of a step, over the median gap between step
boundaries (so that a stall of the profiler's in a traced run costs a
step and not the figure), over chips times the peak of peaks.json. Not
a kernel's roofline share, and blind to idle time."""
import flops
from readers import step_gap_ms


def read(obs, params):
    gap_ms = step_gap_ms.read(obs, params)
    if gap_ms is None or obs.get('peak') is None:
        return None
    per_step = flops.train_flops_per_token(obs['model'], obs['mix']['seq']) \
        * obs['tokens_per_step']
    return 100.0 * per_step / (gap_ms / 1e3) / \
        (obs['peak']['bf16_flops_per_s'] * obs['chips'])
