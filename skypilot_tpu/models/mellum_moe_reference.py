"""Plain float32 reference of the Mellum2 decoder, as published for
Mellum2-12B-A2.5B-Instruct (huggingface.co/JetBrains/
Mellum2-12B-A2.5B-Instruct, config.json, `model_type` `mellum`).
RMSNorm with `rms_norm_eps` throughout, no biases, silu.

Layer i, of kind t = `layer_types[i]`: `h = x + Attn_t(norm(x))`,
`y = h + Experts(norm(h))`; a final RMSNorm and an output head of its
own (`tie_word_embeddings` false).

- `Attn_t`: q as `num_attention_heads` heads of `head_dim`, k and v as
  `num_key_value_heads` (a KV head serves heads / kv heads query
  heads); per-head RMSNorm on q and k over the head, weights
  [head_dim], before the rotation; the half-split rotation with the
  table of t; scores q k^T / sqrt(head_dim); key j is allowed for query
  i iff j <= i and, for `sliding_attention`, i - j < `sliding_window`
  (the window counts the query's own position); softmax in float32.
- The table of t, from `rope_parameters[t]` with theta `rope_theta`
  and d = head_dim. `default`: inv_freq_j = theta^(-2j/d), j = 0 ..
  d/2 - 1. `yarn` (factor f, original length L, `beta_fast`,
  `beta_slow`): c(r) = d ln(L / (2 pi r)) / (2 ln theta),
  low = max(floor(c(beta_fast)), 0), high = min(ceil(c(beta_slow)),
  d - 1), ramp_j = clip((j - low) / (high - low), 0, 1),
  inv_freq_j = (1 - ramp_j) theta^(-2j/d) + ramp_j theta^(-2j/d) / f;
  cos and sin are both multiplied by `attention_factor` (0.1 ln f + 1
  where the entry gives none), at every position.
- `Experts`: p = softmax(W_r n) over all the router's outputs; the
  `num_experts_per_tok` largest; with `norm_topk_prob`
  w = p[sel] / sum(p[sel]), else w = p[sel]; the output is
  sum_e w_e W_down,e (silu(W_gate,e n) * W_up,e n) over the selected
  experts that are held (`experts_held`, a range [lo, hi) of the
  router's outputs: one chip's share). A selected expert that is not
  held adds nothing; with every expert held this is the whole layer.

Straightforward jax.numpy: no kernel, no sort, no cache, no lower
precision (the caller sets jax.default_matmul_precision('highest')).
Every held expert is applied to every token and masked by the routing
weights. Sizes come from the configuration file (the published
config.json keys and `experts_held`), weights from the program's
parameter tree, whose layout this file reads: `tok_embed` [V, D];
`layer_<i>` {`op_norm`, `ffn_norm` {weight}; `attn` {wq, wk, wv, wo
{kernel}, q_norm, k_norm {weight}}; `experts` {router [D, E], w_gate,
w_up [held, D, W], w_down [held, W, D]}}; `final_norm` {weight};
`lm_head` {kernel [D, V]}.

Departures from the published description, none of which changes the
mathematics: attention is computed a block of queries at a time (a
head's scores at 16,384 tokens are 1 GB in float32), the loss a block
of rows at a time, the experts one after another into a running sum;
layers, heads, blocks and experts run under jax.checkpoint;
`selections` lets the caller fix which experts each token takes (for
gradients at the program's own routing) where the published model
always takes its own top-k. No shared expert and no multi-token
prediction module: config.json has no key of either.
"""
import math

import jax
import jax.numpy as jnp

BLOCK = 1024    # queries, or rows of the loss, worked on at a time


def _rms_norm(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * weight


def _block(seq):
    """The largest divisor of seq that is at most BLOCK."""
    return next(b for b in range(min(seq, BLOCK), 0, -1) if seq % b == 0)


def rotary_table(rp, hd, seq):
    """(cos, sin), each [seq, hd / 2], of one `rope_parameters` entry."""
    theta = rp['rope_theta']
    j = jnp.arange(hd // 2, dtype=jnp.float32)
    inv = theta ** (-2.0 * j / hd)
    scale = 1.0
    if rp['rope_type'] == 'yarn':
        f, length = rp['factor'], rp['original_max_position_embeddings']

        def c(r):
            return hd * math.log(length / (2 * math.pi * r)) / \
                (2 * math.log(theta))
        low = max(math.floor(c(rp['beta_fast'])), 0)
        high = min(math.ceil(c(rp['beta_slow'])), hd - 1)
        ramp = jnp.clip((j - low) / (high - low), 0.0, 1.0)
        inv = (1.0 - ramp) * inv + ramp * inv / f
        scale = rp.get('attention_factor') or 0.1 * math.log(f) + 1.0
    elif rp['rope_type'] != 'default':
        raise ValueError(f'unknown rope_type {rp["rope_type"]!r}')
    ang = jnp.arange(seq, dtype=jnp.float32)[:, None] * inv
    return jnp.cos(ang) * scale, jnp.sin(ang) * scale


def _rotate(x, cos, sin):
    """x: [seq, hd]; the half-split rotation."""
    half = x.shape[-1] // 2
    x1, x2 = x[:, :half], x[:, half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(a, p, m, kind):
    seq = a.shape[0]
    h, hk, hd = (m['num_attention_heads'], m['num_key_value_heads'],
                 m['head_dim'])
    eps = m['rms_norm_eps']
    window = m['sliding_window'] if kind == 'sliding_attention' else None
    cos, sin = rotary_table(m['rope_parameters'][kind], hd, seq)
    q = (a @ p['wq']['kernel']).reshape(seq, h, hd)
    k = (a @ p['wk']['kernel']).reshape(seq, hk, hd)
    v = (a @ p['wv']['kernel']).reshape(seq, hk, hd)
    q = _rms_norm(q, p['q_norm']['weight'], eps)
    k = _rms_norm(k, p['k_norm']['weight'], eps)
    bq = _block(seq)
    keys = jnp.arange(seq)[None, :]

    @jax.checkpoint
    def block(q1, start, k1, v1):
        """A block of one head's queries against all its keys."""
        queries = start + jnp.arange(bq)[:, None]
        allowed = keys <= queries
        if window is not None:
            allowed &= queries - keys < window
        scores = q1 @ k1.T / jnp.sqrt(jnp.float32(hd))
        return jax.nn.softmax(jnp.where(allowed, scores, -jnp.inf), -1) @ v1

    @jax.checkpoint
    def head(q1, k1, v1):
        q1, k1 = _rotate(q1, cos, sin), _rotate(k1, cos, sin)
        out = jax.lax.map(
            lambda qs: block(qs[0], qs[1], k1, v1),
            (q1.reshape(seq // bq, bq, hd), jnp.arange(0, seq, bq)))
        return out.reshape(seq, hd)

    def one(args):
        q1, i = args
        return head(q1, k[:, i // (h // hk)], v[:, i // (h // hk)])
    out = jax.lax.map(one, (q.transpose(1, 0, 2), jnp.arange(h)))
    return out.transpose(1, 0, 2).reshape(seq, h * hd) @ p['wo']['kernel']


def route(y, p, m):
    """Normed inputs [seq, D] -> (probabilities [seq, E], which the
    selection ranks, and the top-k of them [seq, k])."""
    probs = jax.nn.softmax(y @ p['router'], axis=-1)
    return probs, jax.lax.top_k(probs, m['num_experts_per_tok'])[1]


def _experts(y, p, m, sel=None):
    """-> (the layer's output, its own selection, the ranked scores)."""
    probs, own = route(y, p, m)
    sel = own if sel is None else sel
    w = jnp.take_along_axis(probs, sel, axis=-1)
    if m['norm_topk_prob']:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    lo, hi = m['experts_held']

    @jax.checkpoint
    def expert(w_gate, w_up, w_down, weight):
        return ((jax.nn.silu(y @ w_gate) * (y @ w_up)) @ w_down) * \
            weight[:, None]
    # Held expert e's weight for each token: w where selected, else 0.
    per = jnp.sum(jnp.where(sel[:, :, None] == jnp.arange(lo, hi),
                            w[..., None], 0.0), axis=1).T    # [held, seq]
    out, _ = jax.lax.scan(
        lambda acc, e: (acc + expert(*e), None), jnp.zeros_like(y),
        (p['w_gate'], p['w_up'], p['w_down'], per))
    return out, own, probs


def _layer(x, p, m, i, sel):
    """-> (y, the expert layer's (selection, ranked scores))."""
    eps = m['rms_norm_eps']
    a = _rms_norm(x, p['op_norm']['weight'], eps)
    x = x + _attention(a, p['attn'], m, m['layer_types'][i])
    y = _rms_norm(x, p['ffn_norm']['weight'], eps)
    out, own, ranked = _experts(y, p['experts'], m, sel)
    return x + out, (own, ranked)


def _decoder(params, tokens, m, selections=None):
    """tokens [seq] -> (final hidden states, {layer name: routing})."""
    x = params['tok_embed'][tokens]
    found = {}
    for i in range(len(m['layer_types'])):
        name = f'layer_{i}'
        x, found[name] = jax.checkpoint(
            lambda x, p, sel, i=i: _layer(x, p, m, i, sel))(
                x, params[name], (selections or {}).get(name))
    return _rms_norm(x, params['final_norm']['weight'],
                     m['rms_norm_eps']), found


def logits(params, tokens, m, selections=None):
    """tokens: [seq] int -> [seq, vocab] float32. selections: optional
    {layer name: [seq, k] int} fixing the experts each token takes."""
    return _decoder(params, tokens, m, selections)[0] @ \
        params['lm_head']['kernel']


def routing(params, tokens, m):
    """The reference's own routing of one sequence: {layer name:
    (selected [seq, k], ranked scores [seq, E])} for each layer."""
    return _decoder(params, tokens, m)[1]


def loss_with_routing(params, tokens, targets, m, selections=None):
    """Mean next-token cross-entropy over [rows, seq] tokens/targets, and
    {layer name: (selected [rows, seq, k], ranked scores [rows, seq, E])}:
    what each layer's router would itself select on the inputs it was
    given. selections: optional {layer name: [rows, seq, k] int}."""
    head = params['lm_head']['kernel']

    @jax.checkpoint
    def nll(args):
        hidden, tgt = args
        logp = jax.nn.log_softmax(hidden @ head, axis=-1)
        return -jnp.take_along_axis(logp, tgt[:, None], axis=-1)[:, 0]

    def row(args):
        tok, tgt, sel = args
        hidden, routed = _decoder(params, tok, m, sel)
        b = _block(tok.shape[0])
        return jax.lax.map(nll, (hidden.reshape(-1, b, hidden.shape[-1]),
                                 tgt.reshape(-1, b))).reshape(-1), routed
    losses, routed = jax.lax.map(row, (tokens, targets, selections))
    return jnp.mean(losses), routed


def loss(params, tokens, targets, m, selections=None):
    """The loss alone: the signature every reference has."""
    return loss_with_routing(params, tokens, targets, m, selections)[0]
