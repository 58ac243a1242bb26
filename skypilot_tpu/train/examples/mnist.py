"""Flax MNIST — the framework's `tpuvm_mnist` workload.

Reference analog: examples/tpu/tpuvm_mnist.yaml, which clones google/flax
and runs examples/mnist on a tpu-v2-8. Rebuilt self-contained: a small
convnet, pmap-free pjit data parallelism over all local devices, and a
synthetic-data fallback so it runs in zero-egress environments (the
baked-in torchvision/datasets download the reference relies on is a
network dependency).
"""
import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax
from flax import linen as nn
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


class CNN(nn.Module):
    @nn.compact
    def __call__(self, x):
        x = nn.Conv(32, (3, 3))(x)
        x = nn.relu(x)
        x = nn.avg_pool(x, (2, 2), strides=(2, 2))
        x = nn.Conv(64, (3, 3))(x)
        x = nn.relu(x)
        x = nn.avg_pool(x, (2, 2), strides=(2, 2))
        x = x.reshape((x.shape[0], -1))
        x = nn.Dense(256)(x)
        x = nn.relu(x)
        return nn.Dense(10)(x)


def load_data(n_train: int = 60000, n_test: int = 10000):
    """MNIST if torchvision has it cached locally; synthetic otherwise."""
    try:
        from torchvision import datasets  # type: ignore
        ds = datasets.MNIST('~/.cache/mnist', train=True, download=False)
        x = ds.data.numpy().astype(np.float32)[..., None] / 255.0
        y = ds.targets.numpy().astype(np.int32)
        return (x, y), (x[:n_test], y[:n_test])
    except Exception:  # pylint: disable=broad-except
        rng = np.random.default_rng(0)
        x = rng.normal(size=(n_train, 28, 28, 1)).astype(np.float32)
        y = rng.integers(0, 10, n_train, dtype=np.int32)
        return (x, y), (x[:n_test], y[:n_test])


def main(argv=None) -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument('--epochs', type=int, default=2)
    parser.add_argument('--batch', type=int, default=512)
    parser.add_argument('--lr', type=float, default=1e-3)
    args = parser.parse_args(argv)

    devices = jax.devices()
    mesh = Mesh(np.array(devices), ('data',))
    repl = NamedSharding(mesh, P())
    sharded = NamedSharding(mesh, P('data'))

    model = CNN()
    (train_x, train_y), _ = load_data()
    params = jax.jit(model.init, out_shardings=repl)(
        jax.random.PRNGKey(0), jnp.zeros((1, 28, 28, 1)))
    tx = optax.adam(args.lr)
    opt_state = tx.init(params)

    @jax.jit
    def step(params, opt_state, x, y):
        def loss_fn(p):
            logits = model.apply(p, x)
            onehot = jax.nn.one_hot(y, 10)
            loss = optax.softmax_cross_entropy(logits, onehot).mean()
            acc = (logits.argmax(-1) == y).mean()
            return loss, acc
        (loss, acc), grads = jax.value_and_grad(loss_fn,
                                                has_aux=True)(params)
        updates, opt_state = tx.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss, acc

    n = (len(train_x) // args.batch) * args.batch
    for epoch in range(args.epochs):
        t0 = time.perf_counter()
        perm = np.random.default_rng(epoch).permutation(n)
        losses, accs = [], []
        for i in range(0, n, args.batch):
            idx = perm[i:i + args.batch]
            x = jax.device_put(train_x[idx], sharded)
            y = jax.device_put(train_y[idx], sharded)
            params, opt_state, loss, acc = step(params, opt_state, x, y)
            losses.append(loss)
            accs.append(acc)
        dt = time.perf_counter() - t0
        print(f'epoch {epoch}: loss={np.mean(jax.device_get(losses)):.4f} '
              f'acc={np.mean(jax.device_get(accs)):.4f} '
              f'({n / dt:,.0f} img/s on {len(devices)} devices)')


if __name__ == '__main__':
    main()
