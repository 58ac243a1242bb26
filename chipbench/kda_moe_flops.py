"""Parameters held and operations a step needs for a decoder of Kimi
Delta Attention and latent attention layers with a dense first layer and
routed and shared experts after it, of which this chip holds a share
(configs of `kind` `kda_moe_train`): the benchmark's own count, by part,
from the configuration's sizes (the published config.json keys as the
file gives them, `router_outputs` and `experts_held`).

Forward and backward, no recomputation and nothing for masked area: 6
per weight of every matrix product a token passes through, so that a
part reads the same work whatever implements it. A KDA layer is its
projections (q, k, v and o at heads x head size; the decay's and the
output gate's two low-rank products each; beta's) and its chunked form
(`scan_flops`). A latent layer is its four projections and the score and
value products over the keys a query is allowed, 6 * heads * (qk width
+ v width) a (query, key) pair, (seq + 1) / 2 keys a query on average.
An expert layer is the router over all its outputs and the shared
expert for every token, and three expert matrices for every (token,
slot) pair routed to a held expert: the pairs are what the run's own
counters reported, and the check child holds them to the reference's
routing. The first layer's dense SwiGLU; the vocabulary is the output
head over the rows held; the embedding is a gather.
"""


def _kda_layers(m: dict) -> int:
    return len(m['linear_attn_config']['kda_layers'])


def _mla_layers(m: dict) -> int:
    return m['num_hidden_layers'] - _kda_layers(m)


def _kda_proj_params(m: dict) -> int:
    la, d = m['linear_attn_config'], m['hidden_size']
    hd, width = la['head_dim'], la['num_heads'] * la['head_dim']
    return 4 * d * width + 2 * (d * hd + hd * width) + d * la['num_heads']


def _kda_params(m: dict) -> int:
    """The projections, the three convolutions, dt_bias, the output
    gate's bias, A_log and the output norm."""
    la = m['linear_attn_config']
    width = la['num_heads'] * la['head_dim']
    return _kda_proj_params(m) + 3 * la['short_conv_kernel_size'] * width + \
        2 * width + la['num_heads'] + la['head_dim']


def _mla_proj_params(m: dict) -> int:
    d, h = m['hidden_size'], m['num_attention_heads']
    qk = m['qk_nope_head_dim'] + m['qk_rope_head_dim']
    rank = m['kv_lora_rank']
    return d * h * qk + d * (rank + m['qk_rope_head_dim']) + \
        rank * h * (m['qk_nope_head_dim'] + m['v_head_dim']) + \
        h * m['v_head_dim'] * d


def _expert_params(m: dict) -> int:
    return 3 * m['hidden_size'] * m['moe_intermediate_size']


def held_params(m: dict) -> int:
    """Every parameter the program holds, norms included: what the
    train state's 12 B a parameter counts."""
    d, (lo, hi) = m['hidden_size'], m['experts_held']
    dense = m['first_k_dense_replace']
    experts = (hi - lo + m['num_shared_experts']) * _expert_params(m) + \
        d * m['router_outputs'] + m['router_outputs']
    mixers = _kda_layers(m) * _kda_params(m) + \
        _mla_layers(m) * (_mla_proj_params(m) + m['kv_lora_rank'])
    ffns = dense * 3 * d * m['intermediate_size'] + \
        (m['num_hidden_layers'] - dense) * experts
    return mixers + ffns + m['num_hidden_layers'] * 2 * d + \
        2 * m['vocab_size'] * d + d


def scan_flops(m: dict, rows: int, seq: int, c: int) -> float:
    """The chunked form's matrix products, forward and backward (three
    times the forward), for chunks of c positions (the program's choice,
    from sft's `kda plan` line): a chunk and head has c (c - 1) / 2 key
    pairs and c (c + 1) / 2 query-key pairs of dk each, a triangular
    solve of c (c - 1) / 2 rows by (dk + dv), the three [c, dk] x [dk,
    dv] products with the state, and the query-key pairs' product with
    the new values."""
    la = m['linear_attn_config']
    dk = dv = la['head_dim']
    chunks = rows * -(-seq // c) * la['num_heads'] * _kda_layers(m)
    lower, strict = c * (c + 1) / 2, c * (c - 1) / 2
    forward = 2 * (strict * dk + lower * dk + strict * (dk + dv) +
                   3 * c * dk * dv + lower * dv)
    return 3 * forward * chunks


def scan_bytes(m: dict, rows: int, seq: int, c: int) -> float:
    """The chunked form's bytes, forward and backward, for chunks of c
    positions: q, k, g and v (float32) and beta in and o out, and the
    chunk-start states, written once; the backward reads the inputs and
    the output's gradient and writes the inputs' gradients (twice the
    forward's traffic) and reads the states."""
    la = m['linear_attn_config']
    h, dk = la['num_heads'], la['head_dim']
    dv = dk
    io = rows * seq * h * ((3 * dk + dv) * 4 + 4 + dv * 4)
    states = rows * -(-seq // c) * h * dk * dv * 4
    return _kda_layers(m) * (3 * io + 2 * states)


def train_flops_per_step(m: dict, rows: int, seq: int, pairs_held: float,
                         c: int) -> dict:
    """Operations of one step by part, and their `total`. pairs_held:
    (token, slot) pairs routed to held experts in the step, summed over
    the layers; c: the chunk of KDA's chunked form."""
    d, tokens = m['hidden_size'], rows * seq
    dense = m['first_k_dense_replace']
    expert_layers = m['num_hidden_layers'] - dense
    shared = m['num_shared_experts'] * _expert_params(m)
    by = {
        'kda_projections': 6 * tokens * _kda_layers(m) * _kda_proj_params(m),
        'kda_scan': scan_flops(m, rows, seq, c),
        'mla_projections': 6 * tokens * _mla_layers(m) * _mla_proj_params(m),
        'mla_scores': 6 * m['num_attention_heads'] * (
            m['qk_nope_head_dim'] + m['qk_rope_head_dim'] +
            m['v_head_dim']) * tokens * _mla_layers(m) * (seq + 1) / 2,
        'dense': 6 * tokens * dense * 3 * d * m['intermediate_size'],
        'router': 6 * tokens * expert_layers * d * m['router_outputs'],
        'shared_experts': 6 * tokens * expert_layers * shared,
        'experts': 6 * pairs_held * _expert_params(m),
        'vocabulary': 6 * tokens * d * m['vocab_size'],
    }
    by['total'] = sum(by.values())
    return by
