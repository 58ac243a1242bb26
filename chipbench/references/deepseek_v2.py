"""Plain float32 reference of the DeepSeek-V2 decoder, as published for
DeepSeek-V2-Lite (huggingface.co/deepseek-ai/DeepSeek-V2-Lite,
config.json, `model_type` `deepseek_v2`; the method is the DeepSeek-V2
report, arXiv:2405.04434). RMSNorm with `rms_norm_eps` throughout, silu.

Layer i (from 0): `h = x + MLA(norm(x))`, `y = h + FFN_i(norm(h))`; a
final RMSNorm and an output head of its own (`tie_word_embeddings`
false). FFN_i is a dense SwiGLU of `intermediate_size` for i <
`first_k_dense_replace`, experts after (`moe_layer_freq` 1).

- MLA, `num_attention_heads` heads, no q-LoRA (`q_lora_rank` null):
  q = W_q a as [q_nope (`qk_nope_head_dim`) | q_pe (`qk_rope_head_dim`)]
  a head; [c (`kv_lora_rank`) | k_pe] = W_kva a; [k_nope | v
  (`v_head_dim`)] = W_kvb rmsnorm(c) a head. q_pe and k_pe are rotated
  at position p: the adjacent pair (2j, 2j + 1) turns by p theta_j, in
  place, with theta_j = (1 - r_j) base^(-2j/d) + r_j base^(-2j/d) /
  factor, r_j = clip((j - low) / (high - low), 0, 1), low and high the
  pairs that turn `beta_fast` and `beta_slow` times over
  `original_max_position_embeddings` (floor and ceil, within 0..d - 1),
  d = `qk_rope_head_dim`, base `rope_theta`; cos and sin times
  mscale(factor, mscale) / mscale(factor, mscale_all_dim), where
  mscale(f, m) = 0.1 m ln f + 1. k = [k_nope | k_pe], k_pe shared by
  the heads; causal softmax(q k^T * s) v in float32, s = (nope +
  rope)^-1/2 mscale(factor, mscale_all_dim)^2; W_o.
- Experts: p = softmax(W_r y) over all `router_outputs`; sel = the
  `num_experts_per_tok` largest (`topk_method` greedy; `n_group` and
  `topk_group` 1, so no group limit); w = p[sel], divided by its sum
  only where `norm_topk_prob`, times `routed_scaling_factor`; the
  output is the shared experts' SwiGLU of width `n_shared_experts` x
  `moe_intermediate_size` plus sum_e w_e W_down,e (silu(W_gate,e y) *
  W_up,e y) over the selected experts that are held (`experts_held`, a
  range [lo, hi) of the router's outputs: one chip's share). A selected
  expert that is not held adds nothing; with every expert held this is
  the whole layer.
- The balance loss (`seq_aux`), per row of length L: aux_loss_alpha
  sum_i f_i P_i, f_i = E / (k L) times the slots of the row that chose
  expert i, P_i = the mean over the row of p_i; all E outputs count;
  no gradient reaches the counts. The loss is the mean next-token
  cross-entropy plus, for each expert layer, that mean over the rows.

Straightforward jax.numpy: no kernel, no chunking, no sort, no cache, no
lower precision (the caller sets jax.default_matmul_precision('highest')).
Every held expert is applied to every token and masked by the routing
weights. Sizes come from the configuration file (the published
config.json keys, `router_outputs`, `experts_held` and
`aux_loss_alpha`), weights from the program's parameter tree, whose
layout this file reads: `tok_embed` [V, D]; `layer_<i>` {`op_norm`,
`ffn_norm` {weight}; `mla` {q_proj, kv_a_proj, kv_b_proj, o_proj
{kernel}, kv_a_norm {weight}}; `mlp` {w_gate, w_up, w_down {kernel}} or
`experts` {router [D, E], w_gate, w_up [held, D, W], w_down [held, W,
D], shared_expert {w_gate, w_up, w_down {kernel}}}}; `final_norm`
{weight}; `lm_head` {kernel [D, V]}. The rotated columns of q_proj and
kv_a_proj are in the published order: pair j is columns 2j and 2j + 1.

Departures from HF's modeling_deepseek.py: attention runs a block of
queries at a time and a head at a time, the loss a block of rows at a
time, the experts one after another into a running sum, and layers,
heads, blocks and experts under jax.checkpoint, none of which changes
the mathematics. HF rotates the de-interleaved pairs and leaves q_pe and
k_pe in that order; this file rotates each pair in place: the dot
products are the same. HF's balance loss adds its gradient through
`AddAuxiliaryLoss` and leaves the reported loss the cross-entropy alone;
here the loss includes it (the gradient is the same). HF counts the
balance loss only in training mode and over each batch row; the rows
here are what the caller packs. `selections` lets the caller fix which
experts each token takes (for gradients at the program's own routing),
where the published model always takes its own top-k.

`fault`, where the sizes give it, makes this reference a wrong model on
purpose, for reading what the check's limits catch (PERF.md): one of
`no_rotation` (q_pe and k_pe not rotated, as Kimi Linear's `mla_use_nope`),
`half_split` (the rotation of the pairs (j, j + d/2), the Llama layout,
on the published columns), `plain_scale` (s = (nope + rope)^-1/2, no
mscale^2), `renormalized` (the selected weights divided by their sum),
`no_balance_loss` (the loss the cross-entropy alone).
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


def _mscale(factor, m):
    return 1.0 if factor <= 1 else 0.1 * m * math.log(factor) + 1.0


def rotary_frequencies(m):
    """theta_j for j = 0 .. d/2 - 1, under `rope_scaling` where given."""
    d, base = m['qk_rope_head_dim'], m['rope_theta']
    extra = base ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    rs = m.get('rope_scaling')
    if not rs:
        return extra

    def pair(turns):
        return d * math.log(rs['original_max_position_embeddings'] /
                            (turns * 2 * math.pi)) / (2 * math.log(base))
    low = max(math.floor(pair(rs['beta_fast'])), 0)
    high = min(math.ceil(pair(rs['beta_slow'])), d - 1)
    ramp = jnp.clip((jnp.arange(d // 2, dtype=jnp.float32) - low) /
                    (high - low if high != low else 0.001), 0.0, 1.0)
    return (1.0 - ramp) * extra + ramp * extra / rs['factor']


def softmax_scale(m):
    """s: 1/sqrt(nope + rope), times mscale(factor, mscale_all_dim)^2
    under `rope_scaling`."""
    s = (m['qk_nope_head_dim'] + m['qk_rope_head_dim']) ** -0.5
    rs = m.get('rope_scaling')
    if rs and m.get('fault') != 'plain_scale':
        s *= _mscale(rs['factor'], rs['mscale_all_dim']) ** 2
    return s


def _rotate(x, m):
    """x: [seq, heads, d] at positions 0..seq-1 -> each pair (2j, 2j + 1)
    turned in place (the published layout; `half_split`: pairs (j, j +
    d/2))."""
    seq, _, d = x.shape
    ang = jnp.arange(seq, dtype=jnp.float32)[:, None, None] * \
        rotary_frequencies(m)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    rs = m.get('rope_scaling')
    if rs:
        on_table = _mscale(rs['factor'], rs['mscale']) / \
            _mscale(rs['factor'], rs['mscale_all_dim'])
        cos, sin = cos * on_table, sin * on_table
    if m.get('fault') == 'half_split':
        x1, x2 = x[..., :d // 2], x[..., d // 2:]
        return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                               -1)
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, odd * cos + even * sin],
                     -1).reshape(x.shape)


def _mla(a, p, m):
    seq = a.shape[0]
    h, nope, rope, vd, rank = (m['num_attention_heads'],
                               m['qk_nope_head_dim'], m['qk_rope_head_dim'],
                               m['v_head_dim'], m['kv_lora_rank'])
    q = (a @ p['q_proj']['kernel']).reshape(seq, h, nope + rope)
    kv_a = a @ p['kv_a_proj']['kernel']
    c, k_pe = kv_a[:, :rank], kv_a[:, None, rank:]
    kv = (_rms_norm(c, p['kv_a_norm']['weight'], m['rms_norm_eps']) @
          p['kv_b_proj']['kernel']).reshape(seq, h, nope + vd)
    q_pe = q[..., nope:]
    if m.get('fault') != 'no_rotation':
        q_pe, k_pe = _rotate(q_pe, m), _rotate(k_pe, m)
    q = jnp.concatenate([q[..., :nope], q_pe], -1)
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_pe, (seq, h, rope))], -1)
    v = kv[..., nope:]
    scale = softmax_scale(m)
    bq = _block(seq)
    keys = jnp.arange(seq)[None, :]

    @jax.checkpoint
    def block(q1, start, k1, v1):
        """A block of one head's queries against all its keys."""
        allowed = keys <= start + jnp.arange(bq)[:, None]
        scores = q1 @ k1.T * scale
        return jax.nn.softmax(jnp.where(allowed, scores, -jnp.inf), -1) @ v1

    @jax.checkpoint
    def head(args):
        q1, k1, v1 = args
        out = jax.lax.map(
            lambda qs: block(qs[0], qs[1], k1, v1),
            (q1.reshape(seq // bq, bq, nope + rope), jnp.arange(0, seq, bq)))
        return out.reshape(seq, vd)
    out = jax.lax.map(head, (q.transpose(1, 0, 2), k.transpose(1, 0, 2),
                             v.transpose(1, 0, 2)))
    return out.transpose(1, 0, 2).reshape(seq, h * vd) @ \
        p['o_proj']['kernel']


def _swiglu(y, p):
    return (jax.nn.silu(y @ p['w_gate']['kernel']) *
            (y @ p['w_up']['kernel'])) @ p['w_down']['kernel']


def route(y, p, m):
    """Normed inputs [seq, D] -> (softmax scores [seq, E], the scores the
    selection ranks (the same) [seq, E], the top-k of those [seq, k])."""
    scores = jax.nn.softmax(y @ p['router'], axis=-1)
    return scores, scores, jax.lax.top_k(scores,
                                         m['num_experts_per_tok'])[1]


def balance_loss(scores, sel, m):
    """The sequence-wise balance loss of one row: scores [L, E], sel
    [L, k]."""
    if not m['seq_aux'] or m.get('fault') == 'no_balance_loss':
        return jnp.zeros((), jnp.float32)
    length, e = scores.shape
    k = m['num_experts_per_tok']
    counts = jax.lax.stop_gradient(jnp.sum(
        (sel[:, :, None] == jnp.arange(e)).astype(jnp.float32), (0, 1)))
    f = counts * e / (k * length)
    return m['aux_loss_alpha'] * jnp.sum(f * jnp.mean(scores, axis=0))


def _experts(y, p, m, sel=None):
    """-> (the layer's output, its own selection, the ranked scores, the
    balance loss)."""
    scores, ranked, own = route(y, p, m)
    sel = own if sel is None else sel
    w = jnp.take_along_axis(scores, sel, axis=-1)
    if m['norm_topk_prob'] or m.get('fault') == 'renormalized':
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    w = w * m['routed_scaling_factor']
    lo, hi = m['experts_held']

    @jax.checkpoint
    def expert(w_gate, w_up, w_down, weight):
        return ((jax.nn.silu(y @ w_gate) * (y @ w_up)) @ w_down) * \
            weight[:, None]
    # Held expert e's weight for each token: w where selected, else 0.
    per = jnp.sum(jnp.where(sel[:, :, None] == jnp.arange(lo, hi),
                            w[..., None], 0.0), axis=1).T    # [held, seq]
    out, _ = jax.lax.scan(
        lambda acc, e: (acc + expert(*e), None),
        jax.checkpoint(_swiglu)(y, p['shared_expert']),
        (p['w_gate'], p['w_up'], p['w_down'], per))
    return out, own, ranked, balance_loss(scores, sel, m)


def _layer(x, p, m, i, sel):
    """-> (y, the expert layer's (selection, ranked scores) or None, its
    balance loss)."""
    eps = m['rms_norm_eps']
    x = x + _mla(_rms_norm(x, p['op_norm']['weight'], eps), p['mla'], m)
    y = _rms_norm(x, p['ffn_norm']['weight'], eps)
    if i < m['first_k_dense_replace']:
        return x + _swiglu(y, p['mlp']), None, jnp.zeros((), jnp.float32)
    out, own, ranked, aux = _experts(y, p['experts'], m, sel)
    return x + out, (own, ranked), aux


def _decoder(params, tokens, m, selections=None):
    """tokens [seq] -> (final hidden states, {layer name: routing}, the
    balance loss summed over the layers)."""
    x = params['tok_embed'][tokens]
    found, aux = {}, jnp.zeros((), jnp.float32)
    for i in range(m['num_hidden_layers']):
        name = f'layer_{i}'
        x, routed, layer_aux = jax.checkpoint(
            lambda x, p, sel, i=i: _layer(x, p, m, i, sel))(
                x, params[name], (selections or {}).get(name))
        aux = aux + layer_aux
        if routed is not None:
            found[name] = routed
    return _rms_norm(x, params['final_norm']['weight'],
                     m['rms_norm_eps']), found, aux


def logits(params, tokens, m, selections=None):
    """tokens: [seq] int -> [seq, vocab] float32. selections: optional
    {layer name: [seq, k] int} fixing the experts each token takes."""
    return _decoder(params, tokens, m, selections)[0] @ \
        params['lm_head']['kernel']


def routing(params, tokens, m):
    """The reference's own routing of one sequence: {layer name:
    (selected [seq, k], ranked scores [seq, E])} for each expert layer."""
    return _decoder(params, tokens, m)[1]


def loss_parts(params, tokens, targets, m, selections=None):
    """(mean next-token cross-entropy over [rows, seq] tokens/targets,
    the balance loss summed over the layers and averaged over the rows,
    {layer name: (selected [rows, seq, k], ranked scores [rows, seq,
    E])}: what each expert layer's router would itself select on the
    inputs it was given). selections: optional {layer name: [rows, seq,
    k] int}."""
    head = params['lm_head']['kernel']

    @jax.checkpoint
    def nll(args):
        hidden, tgt = args
        logp = jax.nn.log_softmax(hidden @ head, axis=-1)
        return -jnp.take_along_axis(logp, tgt[:, None], axis=-1)[:, 0]

    def row(args):
        tok, tgt, sel = args
        hidden, routed, aux = _decoder(params, tok, m, sel)
        b = _block(tok.shape[0])
        return jax.lax.map(nll, (hidden.reshape(-1, b, hidden.shape[-1]),
                                 tgt.reshape(-1, b))).reshape(-1), \
            routed, aux
    losses, routed, aux = jax.lax.map(row, (tokens, targets, selections))
    return jnp.mean(losses), jnp.mean(aux), routed


def loss_with_routing(params, tokens, targets, m, selections=None):
    """The training loss (cross-entropy plus the balance loss) and the
    routing of `loss_parts`."""
    ce, aux, routed = loss_parts(params, tokens, targets, m, selections)
    return ce + aux, routed


def loss(params, tokens, targets, m, selections=None):
    """The loss alone: the signature every reference has."""
    return loss_with_routing(params, tokens, targets, m, selections)[0]
