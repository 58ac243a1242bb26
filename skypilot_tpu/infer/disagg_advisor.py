"""Measured disaggregation advisor: should this replica's prefill
move off-box?

ROADMAP item 2 (prefill/decode disaggregation) needs a DECISION, and
the paper discipline (PR 15's ``placement='measured'``) is that such
decisions are computed from measurements, not argued. This module is
the pure decision function; every input is measured elsewhere:

  * interference — the tick plane's attributed ITL split
    (`infer/tickstats.py`): what fraction of observed ITL is prefill
    co-residency, i.e. the inflation erasable by moving prefill to a
    dedicated replica;
  * transfer cost — disaggregating means every request's prefilled KV
    pages cross the DCN from the prefill replica to a decode replica:
    bytes from PR 12's KV page math
    (`memory_plan.kv_bytes_per_token`, int8-aware), bandwidth from
    PR 15's measured comms profiles (census×profile DCN busbw), with
    an env fallback clearly marked ``assumed``.

The verdict weighs per-request benefit (interference seconds saved
across the request's decoded tokens) against per-request cost (KV
page transfer seconds). Served in ``GET /fleet/interference``.
Dependency-free and deterministic — the advisor goldens in
tests/test_tickstats.py pin it against hand-computed inputs.
"""
from typing import Any, Dict, Optional

from skypilot_tpu.utils import env


def advise(*,
           itl_p99_s: Optional[float],
           interference_frac: Optional[float],
           mixed_tick_frac: float = 0.0,
           kv_bytes_per_token: Optional[float],
           prompt_tokens_per_request: Optional[float],
           output_tokens_per_request: Optional[float],
           dcn_gbps: Optional[float] = None,
           dcn_source: str = 'assumed',
           min_inflation: Optional[float] = None) -> Dict[str, Any]:
    """Weigh measured interference against predicted KV transfer cost.

    Returns a structured verdict::

        {'recommendation': 'disaggregate' | 'keep_colocated'
                           | 'insufficient_data',
         'reason': <one sentence>,
         'measured': {itl_p99_s, interference_frac, mixed_tick_frac,
                      predicted_itl_improvement_s},
         'transfer': {kv_bytes_per_token, prompt_tokens_per_request,
                      bytes_per_request, dcn_gbps, dcn_source,
                      predicted_transfer_cost_s_per_request},
         'tradeoff': {benefit_s_per_request, cost_s_per_request},
         'inputs': {...}}                     # echo, for the record

    'disaggregate' requires BOTH (a) interference above the
    ``min_inflation`` floor (default SKYT_INTERFERENCE_MIN_INFLATION
    — below it the measurement is noise, not signal) and (b) the
    per-request benefit — interference seconds recovered across the
    request's decoded tokens — exceeding the per-request KV page
    transfer cost.
    """
    if min_inflation is None:
        min_inflation = env.get_float(
            'SKYT_INTERFERENCE_MIN_INFLATION', 0.1)
    if dcn_gbps is None:
        dcn_gbps = env.get_float('SKYT_INTERFERENCE_DCN_GBPS', 10.0)
        dcn_source = 'assumed'
    inputs = {
        'itl_p99_s': itl_p99_s,
        'interference_frac': interference_frac,
        'mixed_tick_frac': mixed_tick_frac,
        'kv_bytes_per_token': kv_bytes_per_token,
        'prompt_tokens_per_request': prompt_tokens_per_request,
        'output_tokens_per_request': output_tokens_per_request,
        'dcn_gbps': dcn_gbps,
        'dcn_source': dcn_source,
        'min_inflation': min_inflation,
    }

    def _verdict(rec: str, reason: str, *,
                 improvement_s: Optional[float] = None,
                 transfer_s: Optional[float] = None,
                 bytes_per_request: Optional[float] = None,
                 benefit_s: Optional[float] = None
                 ) -> Dict[str, Any]:
        return {
            'recommendation': rec,
            'reason': reason,
            'measured': {
                'itl_p99_s': itl_p99_s,
                'interference_frac': interference_frac,
                'mixed_tick_frac': mixed_tick_frac,
                'predicted_itl_improvement_s': improvement_s,
            },
            'transfer': {
                'kv_bytes_per_token': kv_bytes_per_token,
                'prompt_tokens_per_request': prompt_tokens_per_request,
                'bytes_per_request': bytes_per_request,
                'dcn_gbps': dcn_gbps,
                'dcn_source': dcn_source,
                'predicted_transfer_cost_s_per_request': transfer_s,
            },
            'tradeoff': {
                'benefit_s_per_request': benefit_s,
                'cost_s_per_request': transfer_s,
            },
            'inputs': inputs,
        }

    if itl_p99_s is None or interference_frac is None:
        return _verdict(
            'insufficient_data',
            'no attributed ITL measurement yet — the tick plane '
            'needs warm pure-decode baselines and finished requests')
    if kv_bytes_per_token is None or not prompt_tokens_per_request \
            or not output_tokens_per_request or not dcn_gbps:
        return _verdict(
            'insufficient_data',
            'transfer-cost inputs missing (KV bytes/token, request '
            'shape, or DCN bandwidth)')

    improvement_s = itl_p99_s * interference_frac
    bytes_per_request = kv_bytes_per_token * prompt_tokens_per_request
    transfer_s = bytes_per_request / (dcn_gbps * 1e9)
    # Benefit accrues once per decoded token (each inter-token gap
    # sheds its interference share); cost is paid once per request.
    benefit_s = improvement_s * output_tokens_per_request

    if interference_frac < min_inflation:
        return _verdict(
            'keep_colocated',
            f'measured interference '
            f'{interference_frac * 100.0:.1f}% of ITL is below the '
            f'{min_inflation * 100.0:.0f}% floor — not worth a '
            f'topology change',
            improvement_s=improvement_s, transfer_s=transfer_s,
            bytes_per_request=bytes_per_request, benefit_s=benefit_s)
    if benefit_s <= transfer_s:
        return _verdict(
            'keep_colocated',
            f'predicted per-request benefit {benefit_s * 1e3:.2f}ms '
            f'does not cover the KV page transfer cost '
            f'{transfer_s * 1e3:.2f}ms over {dcn_source} DCN at '
            f'{dcn_gbps:.1f} GB/s',
            improvement_s=improvement_s, transfer_s=transfer_s,
            bytes_per_request=bytes_per_request, benefit_s=benefit_s)
    return _verdict(
        'disaggregate',
        f'prefill co-residency inflates ITL p99 by '
        f'{interference_frac * 100.0:.1f}% '
        f'({improvement_s * 1e3:.2f}ms/token); moving prefill '
        f'off-replica recovers {benefit_s * 1e3:.2f}ms/request vs a '
        f'{transfer_s * 1e3:.2f}ms/request KV transfer over '
        f'{dcn_source} DCN',
        improvement_s=improvement_s, transfer_s=transfer_s,
        bytes_per_request=bytes_per_request, benefit_s=benefit_s)
