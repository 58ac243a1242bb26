"""Parameters held and operations a step needs for a decoder whose
layers mix window and full attention and are all routed experts, of
which this chip holds a share (configs of `kind` `swa_moe_train`): the
benchmark's own count, by part, from the configuration's sizes (the
published config.json keys as the file gives them, `router_outputs` and
`experts_held`).

Forward and backward, no recomputation and nothing for masked area: 6
per weight of every matrix product a token passes through, so that a
part reads the same work whatever implements it. Attention is its four
projections (q and o at heads x head size, k and v at KV heads x head
size) and the score and value products over the keys a query is
allowed: 12 * heads * head size a (query, key) pair. A full layer's
query sees (seq + 1) / 2 keys on average; a window layer's sees
`band_keys`: its own position and the window - 1 before it, fewer at
the row's start. An expert layer is the router over all its outputs
for every token, and three expert matrices for every (token, slot) pair
routed to a held expert: the pairs are what the run's own counters
reported, and the check child holds them to the reference's routing.
The vocabulary is the output head over the rows held; the embedding is
a gather.
"""


def _attention_params(m: dict) -> int:
    d, hd = m['hidden_size'], m['head_dim']
    return 2 * d * m['num_attention_heads'] * hd + \
        2 * d * m['num_key_value_heads'] * hd


def _expert_params(m: dict) -> int:
    return 3 * m['hidden_size'] * m['moe_intermediate_size']


def held_params(m: dict) -> int:
    """Every parameter the program holds, norms included: what the
    train state's 12 B a parameter counts."""
    d, (lo, hi) = m['hidden_size'], m['experts_held']
    layer = _attention_params(m) + 2 * m['head_dim'] + 2 * d + \
        (hi - lo) * _expert_params(m) + d * m['router_outputs']
    heads = 1 if m['tie_word_embeddings'] else 2
    return len(m['layer_types']) * layer + heads * m['vocab_size'] * d + d


def band_keys(seq: int, window: int) -> float:
    """Keys a query of a window layer is allowed, averaged over a row."""
    w = min(window, seq)
    return (w * (w + 1) / 2 + (seq - w) * w) / seq


def train_flops_per_step(m: dict, rows: int, seq: int,
                         pairs_held: float) -> dict:
    """Operations of one step by part, and their `total`. pairs_held:
    (token, slot) pairs routed to held experts in the step, summed over
    the layers."""
    d, tokens = m['hidden_size'], rows * seq
    kinds = m['layer_types']
    pair = 12 * m['num_attention_heads'] * m['head_dim']
    by = {
        'attention_projections': 6 * tokens * len(kinds) *
        _attention_params(m),
        'full_scores': pair * tokens * kinds.count('full_attention') *
        (seq + 1) / 2,
        'window_scores': pair * tokens * kinds.count('sliding_attention') *
        band_keys(seq, m['sliding_window']),
        'router': 6 * tokens * len(kinds) * d * m['router_outputs'],
        'experts': 6 * pairs_held * _expert_params(m),
        'vocabulary': 6 * tokens * d * m['vocab_size'],
    }
    by['total'] = sum(by.values())
    return by
