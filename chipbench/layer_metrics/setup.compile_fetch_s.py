"""Seconds of set-up in which XLA compiled a program or the persistent
cache gave it back: the union of the ``jit_compile`` spans that ended
before the traced slice opened (chipbench/stall_spans.py; the info line
splits them into fetched and compiled)."""
from chipbench import stall_spans


def read(run):
    return stall_spans.setup_seconds(run, "compile_fetch_s")
