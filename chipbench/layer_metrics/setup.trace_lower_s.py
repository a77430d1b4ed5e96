"""Seconds of set-up in which jax traced or lowered a program: the union
of the ``jit_trace`` and ``jit_lower`` spans that ended before the
traced slice opened, a trace nested in another counted once
(chipbench/stall_spans.py)."""
from chipbench import stall_spans


def read(run):
    return stall_spans.setup_seconds(run, "trace_lower_s")
