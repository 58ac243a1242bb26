"""Parameters held and operations a step needs for a decoder of latent
attention layers (DeepSeek-V2's) with a dense first layer and routed and
shared experts after it, of which this chip holds a share (configs of
`kind` `mla_moe_train`): the benchmark's own count, by part, from the
configuration's sizes (the published config.json keys as the file gives
them, `router_outputs` and `experts_held`).

Forward and backward, no recomputation and nothing for masked area: 6
per weight of every matrix product a token passes through, so that a
part reads the same work whatever implements it. A latent layer is its
four projections and the score and value products over the keys a query
is allowed, 6 * heads * (qk width + v width) a (query, key) pair, (seq +
1) / 2 keys a query on average (`latent_flash_flops`). An expert layer
is the router over all its outputs and the shared experts for every
token, and three expert matrices for every (token, slot) pair routed to
a held expert: the pairs are what the run's own counters reported, and
the check child holds them to the reference's routing. The first layer's
dense SwiGLU; the vocabulary is the output head over the rows held; the
embedding, the rotation, the norms and the balance loss are not matrix
products and count nothing.
"""


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
    layers, dense = m['num_hidden_layers'], m['first_k_dense_replace']
    experts = (hi - lo + m['n_shared_experts']) * _expert_params(m) + \
        d * m['router_outputs']
    return layers * (_mla_proj_params(m) + m['kv_lora_rank'] + 2 * d) + \
        dense * 3 * d * m['intermediate_size'] + \
        (layers - dense) * experts + 2 * m['vocab_size'] * d + d


def latent_flash_flops(m: dict, rows: int, seq: int) -> float:
    """The latent layers' causal attention, forward and backward: q k^T
    at the qk width and p v at the value width for every allowed (query,
    key) pair of every head, three times the forward."""
    return 6 * m['num_attention_heads'] * (
        m['qk_nope_head_dim'] + m['qk_rope_head_dim'] + m['v_head_dim']) * \
        rows * seq * (seq + 1) / 2 * m['num_hidden_layers']


def latent_flash_bytes(m: dict, rows: int, seq: int) -> float:
    """The latent layers' flash calls' bytes, forward and backward, each
    operand read once (bf16, the logsumexp float32): q, k and v in and o
    and the logsumexp out; the backward reads those and dO and writes
    dq, dk and dv."""
    h = m['num_attention_heads']
    qk = m['qk_nope_head_dim'] + m['qk_rope_head_dim']
    dv = m['v_head_dim']
    row = rows * seq * h
    forward = row * ((2 * qk + dv) * 2 + dv * 2 + 4)
    backward = row * ((2 * qk + 3 * dv) * 2 + 4 + (2 * qk + dv) * 2)
    return m['num_hidden_layers'] * (forward + backward)


def train_flops_per_step(m: dict, rows: int, seq: int,
                         pairs_held: float) -> dict:
    """Operations of one step by part, and their `total`. pairs_held:
    (token, slot) pairs routed to held experts in the step, summed over
    the layers."""
    d, tokens = m['hidden_size'], rows * seq
    layers, dense = m['num_hidden_layers'], m['first_k_dense_replace']
    shared = m['n_shared_experts'] * _expert_params(m)
    by = {
        'mla_projections': 6 * tokens * layers * _mla_proj_params(m),
        'mla_scores': latent_flash_flops(m, rows, seq),
        'dense': 6 * tokens * dense * 3 * d * m['intermediate_size'],
        'router': 6 * tokens * (layers - dense) * d * m['router_outputs'],
        'shared_experts': 6 * tokens * (layers - dense) * shared,
        'experts': 6 * pairs_held * _expert_params(m),
        'vocabulary': 6 * tokens * d * m['vocab_size'],
    }
    by['total'] = sum(by.values())
    return by
