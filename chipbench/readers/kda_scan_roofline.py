"""The KDA layers' chunked form's share of its roofline, in %: the time
the form would take at the chip's roofline over the device time a step
of the operations traced under params['scope'] (readers/
scope_ms_per_step.py), per chip. The roofline is the larger of two
bounds (kda_moe_flops.py): the form's matrix products forward and
backward (`scan_flops`, a function of the chunk sft's `kda plan` line
reports) over the bf16 peak of
peaks.json, and its bytes (`scan_bytes`: q, k, v, g and beta in, o out,
the chunk-start states) over the peak's HBM bandwidth. A program without
the scope or the plan, or a configuration without KDA layers, gives
None."""
import kda_moe_flops
from readers import scope_ms_per_step


def read(obs, params):
    ms = scope_ms_per_step.read(obs, params)
    sizes = obs.get('sizes') or {}
    peak = obs.get('peak')
    chunk = int((obs.get('kda_plan') or {}).get('chunk', 0))
    if not ms or peak is None or not chunk or \
            'linear_attn_config' not in sizes:
        return None
    rows, seq = obs['rows'], obs['mix']['seq']
    bound_s = max(
        kda_moe_flops.scan_flops(sizes, rows, seq, chunk) /
        peak['bf16_flops_per_s'],
        kda_moe_flops.scan_bytes(sizes, rows, seq, chunk) /
        peak['hbm_bytes_per_s'])
    return 100.0 * bound_s / (ms / 1e3) / obs['chips']
