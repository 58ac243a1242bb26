"""Model FLOP/s utilization of a `bd_moe_train` cell: the whole step's
share of peak. The benchmark's own operations of a step by part
(bd_moe_flops.py: the 2L positions of the pass, the pairs the mask
allows, the head over the L noised positions), the experts' by the
median of the pairs routed to held experts that the run's step lines
reported, over the median gap between step boundaries, over chips times
the peak of peaks.json."""
import common
import bd_moe_flops
from readers import step_gap_ms


def read(obs, params):
    gap_ms = step_gap_ms.read(obs, params)
    steps = obs.get('moe_steps')
    if gap_ms is None or obs.get('peak') is None or not steps or \
            'block_length' not in (obs.get('sizes') or {}):
        return None
    per_step = bd_moe_flops.train_flops_per_step(
        obs['sizes'], obs['rows'], obs['mix']['seq'],
        common.median([s['held'] for s in steps]))['total']
    return 100.0 * per_step / (gap_ms / 1e3) / \
        (obs['peak']['bf16_flops_per_s'] * obs['chips'])
