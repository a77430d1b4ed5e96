"""The least time the chip could take for a piece of work.

`work` is ``{"flops": operations, "bytes": bytes}`` as a family's work
counts give it (``chipbench/families/<family>.py``); `peak` is the
device's entry of ``peaks.json``. No architecture is known here.
"""
from __future__ import annotations


def roofline_seconds(work: dict, peak: dict) -> float:
    """The larger of operations over peak FLOP/s and bytes over peak
    bytes/s."""
    return max(work["flops"] / peak["bf16_flops_per_s"],
               work["bytes"] / peak["hbm_bytes_per_s"])
