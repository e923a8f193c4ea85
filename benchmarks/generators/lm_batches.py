"""``lm_batches``: the token rows of a language-model training run.

A pure function of the traffic file and the seed.  Every row is
``seq_len + 1`` ids drawn uniformly below the vocabulary (inputs are the
first ``seq_len``, targets the row shifted by one), so all rows differ and
every seed gives rows of the same shape: the same work in another order.
"""

from __future__ import annotations

import numpy as np


def generate(traffic: dict, seed: int, *, vocab: int, global_batch: int) -> np.ndarray:
    """-> int32 ``[n_rows, seq_len + 1]``; ``steps_per_epoch`` whole global
    batches (the feed starts another epoch, reshuffled, when one runs out)."""
    n_rows = int(traffic["steps_per_epoch"]) * int(global_batch)
    rng = np.random.Generator(np.random.PCG64(int(seed)))
    return rng.integers(0, vocab, size=(n_rows, int(traffic["seq_len"]) + 1),
                        dtype=np.int32)
