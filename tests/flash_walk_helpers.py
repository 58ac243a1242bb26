"""What the flash kernels' list of visited tiles (`flash_attention._walk`)
must be, told from a dense mask by enumeration: shared by the tests of
the causal, window and segment masks (test_ops_dispatch.py) and of the
block-diffusion mask (test_hybrid_sdar.py). And how many times a program
calls each flash kernel (`kernel_calls`): the tests of what a block
under remat runs again (test_hybrid_lfm2.py, test_model_train.py)."""
import collections

import numpy as np
from jax.extend import core as jax_core

from skypilot_tpu.ops import flash_attention


def kernel_calls(jaxpr, counts=None):
    """Pallas calls of a jaxpr by kernel function (`_fwd_kernel`,
    `_dq_kernel`, `_dkv_kernel`, ...), through every program inside it:
    jit, remat's recomputation, custom_vjp, scan, shard_map."""
    counts = collections.Counter() if counts is None else counts
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == 'pallas_call':
            counts[eqn.params['jaxpr'].debug_info.func_name] += 1
        for param in eqn.params.values():
            for sub in (param if isinstance(param, (tuple, list))
                        else (param,)):
                if isinstance(sub, jax_core.ClosedJaxpr):
                    kernel_calls(sub.jaxpr, counts)
                elif isinstance(sub, jax_core.Jaxpr):
                    kernel_calls(sub, counts)
    return counts


def check_walk(codes, dense, block_q, block_k, by_k=False,
               every_tile_masked=False):
    """`codes` holds each tile with an allowed entry of `dense` exactly
    once, in walk order (rows of tiles in turn: q tiles, or k tiles with
    `by_k`; a row's tiles rising), flagged first / last once a row and
    masked where some entry is not allowed; a row of tiles with no
    allowed entry holds one step that computes nothing. Returns the
    number of such empty rows."""
    qi, ki, first, last, plain, masked = (
        np.asarray(x) for x in flash_attention._decode(np.asarray(codes)))
    nq, nk = dense.shape[0] // block_q, dense.shape[1] // block_k
    tiles = np.asarray(dense).reshape(nq, block_q, nk, block_k)
    some, every = tiles.any(axis=(1, 3)), tiles.all(axis=(1, 3))
    if by_k:
        some, every = some.T, every.T
    want, empty = [], 0
    for row in range(some.shape[0]):
        cols = np.flatnonzero(some[row])
        if not len(cols):
            empty += 1
            want.append((row, 0, True, True, False, False))
        for n, col in enumerate(cols):
            edge = bool(every_tile_masked or not every[row, col])
            want.append((row, col, n == 0, n == len(cols) - 1, not edge,
                         edge))
    row, col = (ki, qi) if by_k else (qi, ki)
    got = list(zip(row, col, first, last, plain, masked))
    assert got == want, (got, want)
    # each visited tile once, and once a row each of the two edge flags
    computed = plain | masked
    assert len(set(zip(qi[computed], ki[computed]))) == int(some.sum())
    assert first.sum() == last.sum() == some.shape[0]
    return empty
