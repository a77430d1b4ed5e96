"""Shared slope-timing harness for the benchmark scripts.

Methodology (docs/benchmarks.md): each timed run ends with a host
scalar readback as its completion fence, and per-step time is taken
from the SLOPE between two runs of different lengths, which cancels the
fixed dispatch + readback latency. bench.py keeps an inline copy of
this logic so it runs standalone — keep them in sync.
"""
import time


def slope_time(run_fenced, na: int, nb: int):
    """Time `run_fenced(n)` (which must execute n steps and end with a
    host readback) at two iteration counts; return (seconds_per_step,
    timing_tag) where tag is "slope" or "mean_fallback"."""
    if not (0 < na < nb):
        raise ValueError(f"need 0 < na < nb, got na={na} nb={nb}")
    t0 = time.perf_counter()
    run_fenced(na)
    dt_a = time.perf_counter() - t0
    t0 = time.perf_counter()
    run_fenced(nb)
    dt_b = time.perf_counter() - t0
    step = (dt_b - dt_a) / (nb - na)
    if step <= 0:  # noise on very fast runs: latency-biased mean, marked
        return dt_b / nb, "mean_fallback"
    return step, "slope"
