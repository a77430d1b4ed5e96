"""Training batches: token ids drawn uniformly below the published
vocabulary, a fresh batch each step, every row different.

Traffic keys read: ``rows_per_chip``, ``seq_len``, ``chips``.
"""
from __future__ import annotations

from typing import Iterator, Tuple

import numpy as np


def batches(traffic: dict, vocab: int, seed: int
            ) -> Iterator[Tuple[np.ndarray, np.ndarray]]:
    """Yields (tokens, labels), int32 [rows, seq_len] each; labels are
    the tokens shifted by one (the next-token objective), so a batch is
    one draw of seq_len + 1 ids per row. The same seed gives the same
    batches in the same order."""
    rows = int(traffic["rows_per_chip"]) * int(traffic["chips"])
    seq = int(traffic["seq_len"])
    rng = np.random.default_rng([int(seed), 0x7A11])
    while True:
        ids = rng.integers(0, vocab, (rows, seq + 1), dtype=np.int32)
        yield ids[:, :-1], ids[:, 1:]
