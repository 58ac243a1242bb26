"""The one general traffic generator. A mix is a data file under
chipbench/traffic/; this module turns it and --seed into the work of a
run, before the window, so that the generator does nothing inside it.
Every seed gets the same amount of work of the same sizes; the seed
draws the token ids.

Kinds of mix:
  steps   a training loop: `rows` sequences of `seq` tokens a step, no
          sequence repeated within `distinct_steps` steps.

An open-loop or closed-loop serving mix comes with the PR that proves
the first serving cell (PERF.md section 7 has what was learned about
it); the arithmetic to copy is skypilot_tpu/benchmark/workload.py's.
"""
import numpy as np


def train_rows(vocab: int, seed: int, n_rows: int, seq: int) -> np.ndarray:
    """n_rows packed training sequences of seq + 1 token ids."""
    rng = np.random.default_rng([seed, 0x5F7])
    return rng.integers(0, vocab, (n_rows, seq + 1), dtype=np.int32)
