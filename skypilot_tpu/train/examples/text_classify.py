"""LLM text-classification finetune — the GLUE/IMDB-shaped workload.

Reference analog: examples/huggingface_glue_imdb_app.yaml (HF Trainer
finetuning bert-base on IMDB sentiment). Rebuilt on this framework's
own stack, verbalizer-style: the classifier IS the language model —
training drives the LM head to emit a class token (POS/NEG) at the
last position of the review, which is exactly how one finetunes a
decoder-only model for classification (and with --checkpoint pointing
at real Llama weights, this same script is that finetune; without one
it trains the debug config from scratch). Data is synthetic but
learnable in a zero-egress environment: "reviews" are neutral tokens
salted with sentiment-bearing tokens from the positive or negative
lexicon, labels follow the majority lexicon.

    python -m skypilot_tpu.train.examples.text_classify --steps 80
"""
import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax

# Verbalizer token ids in the debug vocab (256): the LM head's logits
# at these two ids ARE the classifier.
POS_ID, NEG_ID = 250, 251
_POS_LEX = list(range(10, 30))      # sentiment-bearing token sets
_NEG_LEX = list(range(30, 50))


def synthetic_review(rng: np.random.Generator, seq: int):
    """Neutral filler + k tokens from one sentiment lexicon."""
    label = int(rng.integers(0, 2))
    lex = _POS_LEX if label == 1 else _NEG_LEX
    toks = rng.integers(60, 250, seq)
    salt = rng.choice(len(toks) - 1, size=max(3, seq // 4),
                      replace=False)
    toks[salt] = rng.choice(lex, size=len(salt))
    return toks.astype(np.int32), label


def synthetic_batch(rng, n: int, seq: int):
    xs, ys = zip(*(synthetic_review(rng, seq) for _ in range(n)))
    return np.stack(xs), np.asarray(ys, np.int32)


def main(argv=None) -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument('--steps', type=int, default=80)
    parser.add_argument('--batch', type=int, default=32)
    parser.add_argument('--seq', type=int, default=32)
    parser.add_argument('--lr', type=float, default=3e-3)
    parser.add_argument('--checkpoint', default=None,
                        help='HF Llama checkpoint dir for a REAL '
                             'finetune (default: train the debug '
                             'config from scratch)')
    args = parser.parse_args(argv)

    from skypilot_tpu.models import llama
    if args.checkpoint:
        from skypilot_tpu.models import weights as weights_lib
        cfg = weights_lib.load_config(args.checkpoint, remat=False)
        model = llama.LlamaModel(cfg)
        params = weights_lib.load_llama_params(cfg, args.checkpoint)
    else:
        cfg = dataclasses.replace(llama.CONFIGS['debug'],
                                  max_seq_len=max(64, args.seq))
        model = llama.LlamaModel(cfg)
        params = jax.jit(model.init)(
            jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))

    tx = optax.adam(args.lr)
    opt_state = jax.jit(tx.init)(params)
    last = jnp.full((args.batch, 1), args.seq - 1, jnp.int32)
    class_ids = jnp.asarray([NEG_ID, POS_ID])

    def loss_fn(params, toks, labels):
        # Logits only at the final position (the same lm-head slicing
        # serving prefill uses); restrict to the two verbalizer ids.
        logits = model.apply(params, toks, logit_positions=last)
        cls = logits[:, 0, class_ids]               # [B, 2]
        loss = optax.softmax_cross_entropy_with_integer_labels(
            cls, labels).mean()
        acc = (cls.argmax(-1) == labels).mean()
        return loss, acc

    @jax.jit
    def train_step(params, opt_state, toks, labels):
        (loss, acc), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params, toks, labels)
        updates, opt_state = tx.update(grads, opt_state)
        return optax.apply_updates(params, updates), opt_state, loss, acc

    rng = np.random.default_rng(7)
    t0 = time.time()
    loss = acc = None
    for step in range(args.steps):
        toks, labels = synthetic_batch(rng, args.batch, args.seq)
        params, opt_state, loss, acc = train_step(
            params, opt_state, jnp.asarray(toks), jnp.asarray(labels))
        if step % 10 == 0 or step == args.steps - 1:
            print(f'step {step:3d} loss {float(loss):.4f} '
                  f'acc {float(acc):.3f}', flush=True)
    # Held-out eval (fresh rng stream).
    ev = np.random.default_rng(999)
    toks, labels = synthetic_batch(ev, args.batch, args.seq)
    _, eval_acc = jax.jit(loss_fn)(params,
                                   jnp.asarray(toks),
                                   jnp.asarray(labels))
    print(f'FINAL loss={float(loss):.4f} train_acc={float(acc):.3f} '
          f'eval_acc={float(eval_acc):.3f} '
          f'({time.time() - t0:.1f}s)', flush=True)


if __name__ == '__main__':
    main()
