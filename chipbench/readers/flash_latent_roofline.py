"""The latent layers' flash calls' share of their roofline, in %: the
time the causal latent attention would take at the chip's roofline over
the device time a step of the operations traced under params['scope']
(readers/scope_ms_per_step.py), per chip. The roofline is the larger of
two bounds (mla_moe_flops.py): the work, 6 * heads * (qk width + v
width) for every allowed (query, key) pair of the latent layers, forward
and backward, nothing for recomputation or for masked area
(`latent_flash_flops`, counted as readers/flash_window_roofline.py
counts its band), over the bf16 peak of peaks.json; and the bytes, each
operand read once (`latent_flash_bytes`), over the peak's HBM bandwidth.
Compute bounds it at the cells' lengths: a tile of scores is made from
operands read once, hundreds of operations a byte. A program without the
scope, or a configuration without latent layers, gives None."""
import mla_moe_flops
from readers import scope_ms_per_step


def read(obs, params):
    ms = scope_ms_per_step.read(obs, params)
    sizes = obs.get('sizes') or {}
    peak = obs.get('peak')
    if not ms or peak is None or 'kv_lora_rank' not in sizes:
        return None
    rows, seq = obs['rows'], obs['mix']['seq']
    bound_s = max(
        mla_moe_flops.latent_flash_flops(sizes, rows, seq) /
        peak['bf16_flops_per_s'],
        mla_moe_flops.latent_flash_bytes(sizes, rows, seq) /
        peak['hbm_bytes_per_s'])
    return 100.0 * bound_s / (ms / 1e3) / obs['chips']
