"""Plain float32 reference of the dense Llama-layout decoder, as
published for Qwen3 (huggingface.co/Qwen/Qwen3-0.6B, modeling_qwen3.py):
pre-norm blocks of grouped-query causal attention (per-head RMSNorm on q
and k before the rotary embedding where the weights have one; rotary in
the half-split convention) and a SwiGLU feed-forward, a final RMSNorm,
and the output projection tied to the embedding where the configuration
says so. Loss is the mean next-token cross-entropy.

Straightforward jax.numpy: no kernel, no cache, no lower precision (the
caller sets jax.default_matmul_precision('highest')). Sizes come from
the configuration file's `model` block (the published config.json keys),
weights from the program's parameter tree, whose layout this file reads:
`tok_embed` [V, D]; `layers`, every leaf stacked over the layers:
`attn_norm`, `mlp_norm` {weight}; `attn` {wq, wk, wv, wo {kernel},
q_norm, k_norm {weight}}; `mlp` {w_gate, w_up, w_down {kernel}};
`final_norm` {weight}. Departure from the published code: each block is
wrapped in jax.checkpoint, which changes memory and not the mathematics.
"""
import jax
import jax.numpy as jnp


def _rms_norm(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * weight


def _rotary(x, theta):
    """x: [seq, heads, head_dim], positions 0..seq-1."""
    seq, _, hd = x.shape
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(seq, dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _block(x, p, m):
    """One decoder block on one sequence. x: [seq, hidden]."""
    seq = x.shape[0]
    h, hk = m['num_attention_heads'], m['num_key_value_heads']
    hd = m.get('head_dim') or m['hidden_size'] // h
    eps, attn = m['rms_norm_eps'], p['attn']
    a = _rms_norm(x, p['attn_norm']['weight'], eps)
    q = (a @ attn['wq']['kernel']).reshape(seq, h, hd)
    k = (a @ attn['wk']['kernel']).reshape(seq, hk, hd)
    v = (a @ attn['wv']['kernel']).reshape(seq, hk, hd)
    if 'q_norm' in attn:
        q = _rms_norm(q, attn['q_norm']['weight'], eps)
        k = _rms_norm(k, attn['k_norm']['weight'], eps)
    q, k = _rotary(q, m['rope_theta']), _rotary(k, m['rope_theta'])
    k, v = jnp.repeat(k, h // hk, axis=1), jnp.repeat(v, h // hk, axis=1)
    scores = jnp.einsum('qhd,khd->hqk', q, k) / jnp.sqrt(jnp.float32(hd))
    causal = jnp.tril(jnp.ones((seq, seq), bool))
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    out = jnp.einsum('hqk,khd->qhd', probs, v).reshape(seq, h * hd)
    x = x + out @ attn['wo']['kernel']
    y = _rms_norm(x, p['mlp_norm']['weight'], eps)
    mlp = p['mlp']
    return x + (jax.nn.silu(y @ mlp['w_gate']['kernel']) *
                (y @ mlp['w_up']['kernel'])) @ mlp['w_down']['kernel']


def logits(params, tokens, m):
    """tokens: [seq] int -> [seq, vocab] float32."""
    x = params['tok_embed'][tokens]
    x, _ = jax.lax.scan(
        lambda x, p: (jax.checkpoint(lambda x, p: _block(x, p, m))(x, p),
                      None), x, params['layers'])
    x = _rms_norm(x, params['final_norm']['weight'], m['rms_norm_eps'])
    if m['tie_word_embeddings']:
        return x @ params['tok_embed'].T
    return x @ params['lm_head']['kernel']


def loss(params, tokens, targets, m):
    """Mean next-token cross-entropy over [rows, seq] tokens/targets."""
    def row(tok, tgt):
        logp = jax.nn.log_softmax(logits(params, tok, m), axis=-1)
        return -jnp.take_along_axis(logp, tgt[:, None], axis=-1)[:, 0]
    return jnp.mean(jax.lax.map(lambda tt: row(*tt), (tokens, targets)))
