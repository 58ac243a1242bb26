"""What the flash kernels' list of visited tiles (`flash_attention._walk`)
must be, told from a dense mask by enumeration: shared by the tests of
the causal, window and segment masks (test_ops_dispatch.py) and of the
block-diffusion mask (test_hybrid_sdar.py)."""
import numpy as np

from skypilot_tpu.ops import flash_attention


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
