"""Plain float32 reference of the Kimi Linear decoder, as published for
Kimi-Linear-48B-A3B-Instruct (huggingface.co/moonshotai/
Kimi-Linear-48B-A3B-Instruct, config.json, `model_type` `kimi_linear`;
the method is the Kimi Linear report, arXiv:2510.26692). RMSNorm with
`rms_norm_eps` throughout, silu, no rotation anywhere (`mla_use_nope`,
`rope_scaling` null).

Layer i (from 0): `h = x + Mix_i(norm(x))`, `y = h + FFN_i(norm(h))`;
a final RMSNorm and an output head of its own (`tie_word_embeddings`
false). Mix_i is KDA where i + 1 is in `linear_attn_config.kda_layers`,
MLA where it is in `full_attn_layers`; FFN_i is a dense SwiGLU of
`intermediate_size` for i < `first_k_dense_replace`, experts after.

- KDA, per head h of `linear_attn_config.num_heads`, dk = dv =
  `linear_attn_config.head_dim`, conv the causal depthwise convolution
  of `short_conv_kernel_size` taps (no bias), left-padded:
  q = l2norm(silu(conv(W_q a))), k = l2norm(silu(conv(W_k a))),
  v = silu(conv(W_v a)), l2norm(x) = x / sqrt(sum x^2 + 1e-6);
  g_t = -exp(A_log[h]) softplus(W_f2 W_f1 a_t + dt_bias) per channel;
  beta_t = sigmoid(W_b a_t)[h]; from S_{-1} = 0, one position at a time,
  S'_t = Diag(exp(g_t)) S_{t-1}, S_t = S'_t + beta_t k_t (v_t - S'_t^T k_t)^T,
  o_t = S_t^T q_t / sqrt(dk); the output W_o [rmsnorm_h(o) w sigmoid(W_g2
  W_g1 a + b_g)], the norm over a head's dv with weights [dv].
- MLA, `num_attention_heads` heads, no q-LoRA (`q_lora_rank` null):
  q = W_q a as [q_nope (`qk_nope_head_dim`) | q_rope (`qk_rope_head_dim`)]
  a head; [c (`kv_lora_rank`) | k_rope] = W_kva a; [k_nope | v
  (`v_head_dim`)] = W_kvb rmsnorm(c) a head; k = [k_nope | k_rope], k_rope
  shared by the heads; causal softmax(q k^T / sqrt(nope + rope)) v, in
  float32; W_o.
- Experts: s = sigmoid(W_r y) over all `router_outputs`; sel = the
  `num_experts_per_token` largest of s + b (b the selection bias, which
  selects and does not weigh; one expert group, so plain top-k);
  w = s[sel] / (sum(s[sel]) + 1e-20) (`moe_renormalize`) times
  `routed_scaling_factor`; the output is the shared expert's SwiGLU of
  width `num_shared_experts` x `moe_intermediate_size` plus
  sum_e w_e W_down,e (silu(W_gate,e y) * W_up,e y) over the selected
  experts that are held (`experts_held`, a range [lo, hi) of the
  router's outputs: one chip's share). A selected expert that is not
  held adds nothing; with every expert held this is the whole layer.

Straightforward jax.numpy: no kernel, no chunking, no sort, no cache, no
lower precision (the caller sets jax.default_matmul_precision('highest')).
The KDA recurrence is the sequential one above; every held expert is
applied to every token and masked by the routing weights. Sizes come
from the configuration file (the published config.json keys,
`router_outputs` and `experts_held`), weights from the program's
parameter tree, whose layout this file reads: `tok_embed` [V, D];
`layer_<i>` {`op_norm`, `ffn_norm` {weight}; `kda` {q_proj, k_proj,
v_proj, f_a_proj, f_b_proj, b_proj, g_a_proj, o_proj {kernel}, g_b_proj
{kernel, bias}, q_conv, k_conv, v_conv [taps, H dk], A_log [H], dt_bias
[H dk], o_norm [dv]} or `mla` {q_proj, kv_a_proj, kv_b_proj, o_proj
{kernel}, kv_a_norm {weight}}; `mlp` {w_gate, w_up, w_down {kernel}} or
`experts` {router [D, E], expert_bias [E], w_gate, w_up [held, D, W],
w_down [held, W, D], shared_expert {w_gate, w_up, w_down {kernel}}}};
`final_norm` {weight}; `lm_head` {kernel [D, V]}.

Departures from the published code, none of which changes the
mathematics: the recurrence runs under jax.checkpoint every `STEPS`
positions, attention a block of queries at a time and a head at a time,
the loss a block of rows at a time, the experts one after another into a
running sum; layers, heads, blocks and experts run under jax.checkpoint;
`selections` lets the caller fix which experts each token takes (for
gradients at the program's own routing) where the published model always
takes its own top-k.

`fault`, where the sizes give it, makes this reference a wrong model on
purpose, for reading what the check's limits catch (PERF.md): one of
`per_head_decay` (each head's channels decay alike, by the mean of its
channels' log-decays), `no_erase` (S_t = S'_t + beta_t k_t v_t^T), `no_l2norm`
(q and k not normed), `centered_conv` (the convolution sees one position
back and two ahead), `mla_rotary` (the half-split rotation of the
`qk_rope` parts at `rope_theta`).
"""
import jax
import jax.numpy as jnp

BLOCK = 1024    # queries, or rows of the loss, worked on at a time
STEPS = 64      # positions of the recurrence between checkpoints


def _rms_norm(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * weight


def _block(seq):
    """The largest divisor of seq that is at most BLOCK."""
    return next(b for b in range(min(seq, BLOCK), 0, -1) if seq % b == 0)


def kda_sequential(q, k, v, g, beta, erase=True):
    """The gated delta rule one position at a time: q, k, g [seq, H, dk],
    v [seq, H, dv], beta [seq, H] -> o [seq, H, dv], from a zero state.
    q arrives scaled. erase=False drops the delta rule's erase term (a
    fault, `no_erase`)."""
    seq, h, dk = q.shape
    dv = v.shape[-1]

    def step(state, x):
        q_t, k_t, v_t, g_t, b_t = x
        state = jnp.exp(g_t)[..., None] * state
        old = jnp.einsum('hkv,hk->hv', state, k_t) if erase else 0.0
        state = state + k_t[..., None] * (b_t[:, None] * (v_t - old))[
            :, None, :]
        return state, jnp.einsum('hkv,hk->hv', state, q_t)

    @jax.checkpoint
    def steps(state, xs):
        return jax.lax.scan(step, state, xs)
    n = -(-seq // STEPS)
    pad = n * STEPS - seq

    def split(x):
        x = jnp.pad(x, [(0, pad)] + [(0, 0)] * (x.ndim - 1))
        return x.reshape(n, STEPS, *x.shape[1:])
    _, o = jax.lax.scan(steps, jnp.zeros((h, dk, dv), jnp.float32),
                        tuple(map(split, (q, k, v, g, beta))))
    return o.reshape(n * STEPS, h, dv)[:seq]


def _conv(z, taps, centered=False):
    """Depthwise over the sequence: z [seq, C], taps [K, C]; left-padded
    by K - 1 (causal), or by 1 and right-padded by K - 2 (`centered`)."""
    n, seq = taps.shape[0], z.shape[0]
    left = 1 if centered else n - 1
    padded = jnp.pad(z, ((left, n - 1 - left), (0, 0)))
    return sum(taps[j] * padded[j:j + seq] for j in range(n))


def _kda(a, p, m):
    seq = a.shape[0]
    la = m['linear_attn_config']
    h, hd = la['num_heads'], la['head_dim']
    fault = m.get('fault')

    def conv_silu(name):
        z = a @ p[f'{name}_proj']['kernel']
        z = jax.nn.silu(_conv(z, p[f'{name}_conv'],
                              fault == 'centered_conv'))
        return z.reshape(seq, h, hd)

    def l2(x):
        if fault == 'no_l2norm':
            return x
        return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)
    q = l2(conv_silu('q')) / jnp.sqrt(jnp.float32(hd))
    k = l2(conv_silu('k'))
    v = conv_silu('v')
    f = (a @ p['f_a_proj']['kernel'] @ p['f_b_proj']['kernel'] +
         p['dt_bias']).reshape(seq, h, hd)
    g = -jnp.exp(p['A_log'])[:, None] * jax.nn.softplus(f)
    if fault == 'per_head_decay':
        g = jnp.broadcast_to(jnp.mean(g, -1, keepdims=True), g.shape)
    beta = jax.nn.sigmoid(a @ p['b_proj']['kernel'])
    o = kda_sequential(q, k, v, g, beta, erase=fault != 'no_erase')
    gate = (a @ p['g_a_proj']['kernel'] @ p['g_b_proj']['kernel'] +
            p['g_b_proj']['bias']).reshape(seq, h, hd)
    o = _rms_norm(o, p['o_norm'], m['rms_norm_eps']) * jax.nn.sigmoid(gate)
    return o.reshape(seq, h * hd) @ p['o_proj']['kernel']


def _rotate(x, theta):
    """x: [seq, heads, d]; the half-split rotation at positions 0..seq-1
    (only for the fault `mla_rotary`)."""
    seq, _, d = x.shape
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(seq, dtype=jnp.float32)[:, None, None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _mla(a, p, m):
    seq = a.shape[0]
    h, nope, rope, vd, rank = (m['num_attention_heads'],
                               m['qk_nope_head_dim'], m['qk_rope_head_dim'],
                               m['v_head_dim'], m['kv_lora_rank'])
    q = (a @ p['q_proj']['kernel']).reshape(seq, h, nope + rope)
    kv_a = a @ p['kv_a_proj']['kernel']
    c, k_rope = kv_a[:, :rank], kv_a[:, None, rank:]
    kv = (_rms_norm(c, p['kv_a_norm']['weight'], m['rms_norm_eps']) @
          p['kv_b_proj']['kernel']).reshape(seq, h, nope + vd)
    if m.get('fault') == 'mla_rotary':
        q = jnp.concatenate(
            [q[..., :nope], _rotate(q[..., nope:], m['rope_theta'])], -1)
        k_rope = _rotate(k_rope, m['rope_theta'])
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_rope, (seq, h, rope))], -1)
    v = kv[..., nope:]
    bq = _block(seq)
    keys = jnp.arange(seq)[None, :]

    @jax.checkpoint
    def block(q1, start, k1, v1):
        """A block of one head's queries against all its keys."""
        allowed = keys <= start + jnp.arange(bq)[:, None]
        scores = q1 @ k1.T / jnp.sqrt(jnp.float32(nope + rope))
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
    """Normed inputs [seq, D] -> (scores [seq, E], the scores the
    selection ranks [seq, E], the top-k of those [seq, k])."""
    scores = jax.nn.sigmoid(y @ p['router'])
    ranked = scores + p['expert_bias']
    return scores, ranked, jax.lax.top_k(ranked,
                                         m['num_experts_per_token'])[1]


def _experts(y, p, m, sel=None):
    """-> (the layer's output, its own selection, the ranked scores)."""
    scores, ranked, own = route(y, p, m)
    sel = own if sel is None else sel
    w = jnp.take_along_axis(scores, sel, axis=-1)
    if m['moe_renormalize']:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
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
    return out, own, ranked


def _layer(x, p, m, i, sel):
    """-> (y, the expert layer's (selection, ranked scores) or None)."""
    eps = m['rms_norm_eps']
    a = _rms_norm(x, p['op_norm']['weight'], eps)
    if i + 1 in m['linear_attn_config']['kda_layers']:
        x = x + _kda(a, p['kda'], m)
    else:
        x = x + _mla(a, p['mla'], m)
    y = _rms_norm(x, p['ffn_norm']['weight'], eps)
    if i < m['first_k_dense_replace']:
        return x + _swiglu(y, p['mlp']), None
    out, own, ranked = _experts(y, p['experts'], m, sel)
    return x + out, (own, ranked)


def _decoder(params, tokens, m, selections=None):
    """tokens [seq] -> (final hidden states, {layer name: routing})."""
    x = params['tok_embed'][tokens]
    found = {}
    for i in range(m['num_hidden_layers']):
        name = f'layer_{i}'
        x, routed = jax.checkpoint(
            lambda x, p, sel, i=i: _layer(x, p, m, i, sel))(
                x, params[name], (selections or {}).get(name))
        if routed is not None:
            found[name] = routed
    return _rms_norm(x, params['final_norm']['weight'],
                     m['rms_norm_eps']), found


def logits(params, tokens, m, selections=None):
    """tokens: [seq] int -> [seq, vocab] float32. selections: optional
    {layer name: [seq, k] int} fixing the experts each token takes."""
    return _decoder(params, tokens, m, selections)[0] @ \
        params['lm_head']['kernel']


def routing(params, tokens, m):
    """The reference's own routing of one sequence: {layer name:
    (selected [seq, k], ranked scores [seq, E])} for each expert layer."""
    return _decoder(params, tokens, m)[1]


def loss_with_routing(params, tokens, targets, m, selections=None):
    """Mean next-token cross-entropy over [rows, seq] tokens/targets, and
    {layer name: (selected [rows, seq, k], ranked scores [rows, seq, E])}:
    what each expert layer's router would itself select on the inputs it
    was given. selections: optional {layer name: [rows, seq, k] int}."""
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
