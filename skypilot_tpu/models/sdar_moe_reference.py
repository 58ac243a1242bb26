"""Plain float32 reference of the SDAR-MoE decoder under its training
objective, block diffusion, as published for SDAR-30B-A3B-Chat
(huggingface.co/JetLM/SDAR-30B-A3B-Chat, config.json, `model_type`
`sdar_moe`; SDAR, arXiv:2510.06303; the one-pass training and its mask
are BD3-LM's, arXiv:2503.09573 section 3). RMSNorm with `rms_norm_eps`
throughout, no biases, silu.

x_0 is a row of L token ids, B = `block_length`, the block of position
i is i // B. Given which positions are masked (m_i in {0, 1}) and the
noise level of each position's block (t_i, one value a block):

- x_t[i] = `mask_id` if m_i else x_0[i]. The model reads the 2L tokens
  `[x_t | x_0]`, the noised half first, at the position ids
  `[0..L-1 | 0..L-1]` (`position_ids`): both halves are rotated by the
  same table, inv_freq_j = theta^(-2j/d), theta `rope_theta`, d =
  `head_dim`, the half-split rotation.
- Query p may see key r (`allowed`), with n(p) = 1 for p < L (noised)
  and b(p) the block of p's position id, iff n(p) = n(r) = 1 and
  b(p) = b(r) (a noised block sees itself, both ways); or n(p) = 1,
  n(r) = 0 and b(r) < b(p) (and the clean blocks strictly before it);
  or n(p) = n(r) = 0 and b(r) <= b(p) (a clean block sees the clean
  blocks up to itself). A clean query never sees a noised key; a noised
  query never sees the clean copy of its own block.
- Layer: `h = x + Attn(norm(x))`, `y = h + Experts(norm(h))`. `Attn`: q
  as `num_attention_heads` heads of `head_dim`, k and v as
  `num_key_value_heads` (a KV head serves heads / kv heads query
  heads); per-head RMSNorm on q and k over the head, weights
  [head_dim], before the rotation; scores q k^T / sqrt(head_dim) under
  the mask; softmax in float32; the output projection. `Experts`:
  p = softmax(W_r n) over all the router's outputs; the
  `num_experts_per_tok` largest; with `norm_topk_prob`
  w = p[sel] / sum(p[sel]); the output is
  sum_e w_e W_down,e (silu(W_gate,e n) * W_up,e n) over the selected
  experts that are held (`experts_held`, a range [lo, hi) of the
  router's outputs: one chip's share). A selected expert that is not
  held adds nothing. All 2L positions are routed.
- A final RMSNorm and the untied output head on the L noised positions
  only. No shift: the logits at noised position i are for x_0[i]
  (`targets`). Loss = (1 / L) sum_i w_i (-log softmax(logits_i)[x_0[i]])
  with w_i = m_i / t_i (`token_weights`: the linear schedule's NELBO,
  alpha_t = 1 - t, weight -alpha'_t / (1 - alpha_t) = 1 / t).

Straightforward jax.numpy: no kernel, no sort, no cache, no lower
precision (the caller sets jax.default_matmul_precision('highest')).
Every held expert is applied to every position and masked by the
routing weights. Sizes come from the configuration file (the published
config.json keys, `experts_held`, `block_length`, `mask_id`), weights
from the program's parameter tree, whose layout this file reads:
`tok_embed` [V, D]; `layer_<i>` {`op_norm`, `ffn_norm` {weight}; `attn`
{wq, wk, wv, wo {kernel}, q_norm, k_norm {weight}}; `experts` {router
[D, E], w_gate, w_up [held, D, W], w_down [held, W, D]}}; `final_norm`
{weight}; `lm_head` {kernel [D, V]}.

Departures from the published description, none of which changes the
mathematics: attention is computed a block of queries at a time (a
head's scores at 16,384 positions are 1 GB in float32), the loss a
block of rows at a time, the experts one after another into a running
sum; layers, heads, blocks and experts run under jax.checkpoint;
`selections` lets the caller fix which experts each position takes (for
gradients at the program's own routing) where the published model
always takes its own top-k; the noise (m, t) is the caller's, so that
two implementations can be given the same.
"""
import jax
import jax.numpy as jnp

BLOCK = 1024    # queries, or rows of the loss, worked on at a time


def _rms_norm(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * weight


def _block(n):
    """The largest divisor of n that is at most BLOCK."""
    return next(b for b in range(min(n, BLOCK), 0, -1) if n % b == 0)


def allowed(p, r, length, block):
    """May query p see key r, both indices into `[x_t | x_0]`."""
    n_p, n_r = p < length, r < length
    b_p, b_r = (p % length) // block, (r % length) // block
    own = n_p & n_r & (b_p == b_r)
    before = n_p & ~n_r & (b_r < b_p)
    clean = ~n_p & ~n_r & (b_r <= b_p)
    return own | before | clean


def position_ids(length):
    ids = jnp.arange(length)
    return jnp.concatenate([ids, ids])


def token_weights(m, t):
    """What a position's cross-entropy is weighted by in the loss."""
    return jnp.where(m, 1.0 / t, 0.0)


def targets(x0):
    """The ids the logits of the noised positions are for."""
    return x0


def _rotate(x, cos, sin):
    """x: [n, hd]; the half-split rotation."""
    half = x.shape[-1] // 2
    x1, x2 = x[:, :half], x[:, half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(a, p, m):
    n = a.shape[0]                      # 2L positions
    h, hk, hd = (m['num_attention_heads'], m['num_key_value_heads'],
                 m['head_dim'])
    eps = m['rms_norm_eps']
    j = jnp.arange(hd // 2, dtype=jnp.float32)
    ang = position_ids(n // 2).astype(jnp.float32)[:, None] * \
        m['rope_theta'] ** (-2.0 * j / hd)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    q = (a @ p['wq']['kernel']).reshape(n, h, hd)
    k = (a @ p['wk']['kernel']).reshape(n, hk, hd)
    v = (a @ p['wv']['kernel']).reshape(n, hk, hd)
    q = _rms_norm(q, p['q_norm']['weight'], eps)
    k = _rms_norm(k, p['k_norm']['weight'], eps)
    bq = _block(n)
    keys = jnp.arange(n)[None, :]

    @jax.checkpoint
    def block(q1, start, k1, v1):
        """A block of one head's queries against all its keys."""
        queries = start + jnp.arange(bq)[:, None]
        scores = q1 @ k1.T / jnp.sqrt(jnp.float32(hd))
        scores = jnp.where(allowed(queries, keys, n // 2, m['block_length']),
                           scores, -jnp.inf)
        return jax.nn.softmax(scores, -1) @ v1

    @jax.checkpoint
    def head(q1, k1, v1):
        q1, k1 = _rotate(q1, cos, sin), _rotate(k1, cos, sin)
        out = jax.lax.map(
            lambda qs: block(qs[0], qs[1], k1, v1),
            (q1.reshape(n // bq, bq, hd), jnp.arange(0, n, bq)))
        return out.reshape(n, hd)

    def one(args):
        q1, i = args
        return head(q1, k[:, i // (h // hk)], v[:, i // (h // hk)])
    out = jax.lax.map(one, (q.transpose(1, 0, 2), jnp.arange(h)))
    return out.transpose(1, 0, 2).reshape(n, h * hd) @ p['wo']['kernel']


def route(y, p, m):
    """Normed inputs [n, D] -> (probabilities [n, E], which the
    selection ranks, and the top-k of them [n, k])."""
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
    # Held expert e's weight for each position: w where selected, else 0.
    per = jnp.sum(jnp.where(sel[:, :, None] == jnp.arange(lo, hi),
                            w[..., None], 0.0), axis=1).T      # [held, n]
    out, _ = jax.lax.scan(
        lambda acc, e: (acc + expert(*e), None), jnp.zeros_like(y),
        (p['w_gate'], p['w_up'], p['w_down'], per))
    return out, own, probs


def _layer(x, p, m, sel):
    """-> (y, the expert layer's (selection, ranked scores))."""
    eps = m['rms_norm_eps']
    x = x + _attention(_rms_norm(x, p['op_norm']['weight'], eps),
                       p['attn'], m)
    y = _rms_norm(x, p['ffn_norm']['weight'], eps)
    out, own, ranked = _experts(y, p['experts'], m, sel)
    return x + out, (own, ranked)


def _decoder(params, x0, masked, m, selections=None):
    """x0 [L] ids, masked [L] bool -> (final hidden states of the L
    noised positions, {layer name: routing of all 2L positions})."""
    x_t = jnp.where(masked, m['mask_id'], x0)
    x = params['tok_embed'][jnp.concatenate([x_t, x0])]
    found = {}
    for i in range(m['num_hidden_layers']):
        name = f'layer_{i}'
        x, found[name] = jax.checkpoint(
            lambda x, p, sel: _layer(x, p, m, sel))(
                x, params[name], (selections or {}).get(name))
    return _rms_norm(x[:x0.shape[0]], params['final_norm']['weight'],
                     m['rms_norm_eps']), found


def routing(params, x0, masked, m):
    """The reference's own routing of one noised row: {layer name:
    (selected [2L, k], ranked scores [2L, E])} for each layer."""
    return _decoder(params, x0, masked, m)[1]


def loss_with_routing(params, x0, masked, t, m, selections=None):
    """The block-diffusion loss over rows x0 [rows, L] given their noise
    (masked [rows, L] bool, t [rows, L] the level of each position's
    block), and {layer name: (selected [rows, 2L, k], ranked scores
    [rows, 2L, E])}: what each layer's router would itself select on the
    inputs it was given. selections: optional {layer name: [rows, 2L, k]
    int}."""
    head = params['lm_head']['kernel']

    @jax.checkpoint
    def nll(args):
        hidden, tgt = args
        logp = jax.nn.log_softmax(hidden @ head, axis=-1)
        return -jnp.take_along_axis(logp, tgt[:, None], axis=-1)[:, 0]

    def row(args):
        x, msk, level, sel = args
        hidden, routed = _decoder(params, x, msk, m, sel)
        b = _block(x.shape[0])
        each = jax.lax.map(nll, (hidden.reshape(-1, b, hidden.shape[-1]),
                                 targets(x).reshape(-1, b))).reshape(-1)
        return jnp.mean(token_weights(msk, level) * each), routed
    losses, routed = jax.lax.map(row, (x0, masked, t, selections))
    return jnp.mean(losses), routed


def loss(params, x0, masked, t, m, selections=None):
    """The loss alone: the signature every reference has."""
    return loss_with_routing(params, x0, masked, t, m, selections)[0]
