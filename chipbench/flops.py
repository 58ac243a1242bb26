"""Operations a model needs per token, from its published sizes: the
benchmark's own count, so that no PR which claims a gain can move it.

Forward and backward, no recomputation: 6 per weight of every matrix
multiplication (the embedding lookup is not one; the output projection
is, tied or not), plus causal attention, whose score and value products
cost 2 * 2 * heads * head_dim per attended position, (seq + 1) / 2
positions on average, three times over for the backward pass.
"""


def matmul_params(m: dict) -> int:
    d, layers = m['hidden_size'], m['num_hidden_layers']
    hd = m.get('head_dim') or d // m['num_attention_heads']
    q = m['num_attention_heads'] * hd
    kv = m['num_key_value_heads'] * hd
    per_layer = d * q + 2 * d * kv + q * d + 3 * d * m['intermediate_size']
    return layers * per_layer + d * m['vocab_size']


def train_flops_per_token(m: dict, seq: int) -> float:
    hd = m.get('head_dim') or m['hidden_size'] // m['num_attention_heads']
    attn = 12 * m['num_hidden_layers'] * m['num_attention_heads'] * hd * \
        (seq + 1) / 2
    return 6 * matmul_params(m) + attn
