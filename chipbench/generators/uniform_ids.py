"""The training generator: a fresh batch of uniform token ids every step.

    batches(traffic, seed, vocab) -> iterator of int32 [sequences, seq_len]

Every row of every batch differs. The amount of work does not depend on the
seed: only the ids do.
"""

from __future__ import annotations

import numpy as np


def batches(traffic: dict, seed: int, vocab: int):
    rng = np.random.Generator(np.random.PCG64([int(seed), 7]))
    shape = (int(traffic["sequences"]), int(traffic["seq_len"]))
    while True:
        yield rng.integers(0, vocab, shape).astype(np.int32)
