"""Training data: seeded random token rows, made on the host by an
iterator that runs DURING the window (the wait for input is inside the
step time).  Every row differs; targets are the row shifted by one."""

from __future__ import annotations

import numpy as np


def batches(traffic: dict, seed: int, rows: int, vocab: int):
    """Yields ``{"tokens", "targets"}`` int32 arrays of ``rows`` x ``seq``
    for ever; batch ``n`` depends on ``(seed, n)`` only."""
    seq = int(traffic["seq"])
    n = 0
    while True:
        rng = np.random.default_rng([int(seed), n])
        tok = rng.integers(0, vocab, (rows, seq + 1), dtype=np.int32)
        yield {"tokens": tok[:, :-1], "targets": tok[:, 1:]}
        n += 1
