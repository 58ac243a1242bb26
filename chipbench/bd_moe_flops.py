"""Parameters held and operations a step needs for a decoder of full
attention and routed experts in every layer, of which this chip holds a
share, trained by block diffusion (configs of `kind` `bd_moe_train`):
the benchmark's own count, by part, from the configuration's sizes (the
published config.json keys as the file gives them, `router_outputs`,
`experts_held` and `block_length`).

A step reads `seq` = L data tokens a row and passes 2L positions
through every layer (the noised row and its clean copy); the head sees
the L noised ones. Forward and backward, no recomputation and nothing
for masked area: 6 per weight of every matrix product a position passes
through, so that a part reads the same work whatever implements it.
Attention is its four projections (q and o at heads x head size, k and
v at KV heads x head size) over the 2L positions and the score and
value products over the (query, key) pairs the block-diffusion mask
allows, 12 * heads * head size a pair: `allowed_pairs`, L^2 + L * B a
head and row (clean to clean L (L + B) / 2, noised to clean
L (L - B) / 2, a noised block to itself L * B). An expert layer is the
router over all its outputs for every one of the 2L positions, and
three expert matrices for every (position, slot) pair routed to a held
expert: the pairs are what the run's own counters reported, and the
check child holds them to the reference's routing. The vocabulary is
the output head over the rows held, for the L noised positions; the
embedding is a gather, and drawing the noise is elementwise.
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
    return m['num_hidden_layers'] * layer + heads * m['vocab_size'] * d + d


def allowed_pairs(seq: int, block: int) -> int:
    """(query, key) pairs of one head and row under the mask."""
    return seq * seq + seq * block


def train_flops_per_step(m: dict, rows: int, seq: int,
                         pairs_held: float) -> dict:
    """Operations of one step by part, and their `total`. seq: the
    data tokens of a row (L). pairs_held: (position, slot) pairs routed
    to held experts in the step, summed over the layers."""
    d, positions = m['hidden_size'], rows * 2 * seq
    layers = m['num_hidden_layers']
    by = {
        'attention_projections': 6 * positions * layers *
        _attention_params(m),
        'bd_scores': 12 * m['num_attention_heads'] * m['head_dim'] * rows *
        layers * allowed_pairs(seq, m['block_length']),
        'router': 6 * positions * layers * d * m['router_outputs'],
        'experts': 6 * pairs_held * _expert_params(m),
        'vocabulary': 6 * rows * seq * d * m['vocab_size'],
    }
    by['total'] = sum(by.values())
    return by
