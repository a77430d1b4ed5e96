"""Serving requests for a closed loop of callers.

Every seed offers the SAME set of request sizes in another order: the
sizes are a fixed grid over the traffic file's distributions (so two
seeds do the same work and differ only in scheduling and token ids),
dealt to the callers from one stream the seed shuffles, cycle by cycle.
A cycle is ``size_grid`` requests (a power of two); the shorter it is
against the window, the less the seed's order changes the window's work.

Traffic keys read: ``prompt_tokens`` and ``new_tokens`` (each
``{"distribution": "log_uniform" | "fixed", "low", "high"}``),
``size_grid``, ``shared_prefix_tokens`` (ids every prompt opens with;
0 = unshared), ``temperature``.
"""
from __future__ import annotations

from typing import Iterator, List

import numpy as np

GRID = 64   # sizes per cycle where the traffic file gives no `size_grid`


def _quantile(spec: dict, q: float) -> int:
    low, high = int(spec["low"]), int(spec["high"])
    kind = spec.get("distribution", "log_uniform")
    if kind == "fixed" or low == high:
        return low
    if kind == "log_uniform":
        return int(round(low * (high / low) ** q))
    if kind == "uniform":
        return int(round(low + (high - low) * q))
    raise ValueError(f"unknown distribution {kind!r}")


def size_grid(traffic: dict) -> List[tuple]:
    """``size_grid`` (prompt_tokens, new_tokens) pairs: the i-th
    quantile of the prompt lengths beside the bit-reversed-i-th of the
    answer lengths, so the two are spread evenly and not tied to each
    other."""
    grid = int(traffic.get("size_grid", GRID))
    bits = grid.bit_length() - 1
    if grid < 2 or grid != 1 << bits:
        raise ValueError(f"size_grid must be a power of two; got {grid}")
    out = []
    for i in range(grid):
        j = int(format(i, f"0{bits}b")[::-1], 2)
        out.append((_quantile(traffic["prompt_tokens"], (i + 0.5) / grid),
                    _quantile(traffic["new_tokens"], (j + 0.5) / grid)))
    return out


def requests(traffic: dict, vocab: int, seed: int) -> Iterator[dict]:
    """Yields ``{"prompt": [ids], "max_new_tokens": n, "temperature": t}``
    without end; the caller that is free takes the next one."""
    rng = np.random.default_rng([int(seed), 0x5E21])
    grid = size_grid(traffic)
    shared = int(traffic.get("shared_prefix_tokens", 0))
    prefix = rng.integers(0, vocab, shared).tolist()
    temperature = float(traffic.get("temperature", 0.0))
    while True:
        for i in rng.permutation(len(grid)):
            n_prompt, n_new = grid[i]
            tail = rng.integers(0, vocab, max(n_prompt - shared, 1)).tolist()
            yield {"prompt": (prefix + tail)[:max(n_prompt, 1)],
                   "max_new_tokens": n_new, "temperature": temperature}
