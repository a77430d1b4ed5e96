"""Decode attention's share of its roofline: the least time the chip
could take to read the K and V the traced decode steps attended to (the
logical bytes of the cached keys, not the tile-padded ones) and do their
products, over the device time of the decode-attention kernel events.

The kernel is found by NAME (Mosaic custom calls named after
`paged_attention`; the trace carries no flax module path) and told from
the prefill calls of the same kernel by its query operand: one query per
row, ``[rows, heads, 1, head_dim]``. Nothing found, nothing printed."""
import re

from chipbench import flops, xplane
from chipbench.reference import Shape

PATTERN = r"paged_attention"


def read(run):
    steps = (run.traced or {}).get("steps")
    if run.trace is None or not steps:
        return None
    shape = Shape(run.config)
    rows = int(run.traffic["server"]["max_batch"])
    one_query = re.compile(
        rf"\[{rows},{shape.heads},1,{shape.head_dim}\]")
    events = xplane.kernel_events(run.trace, PATTERN,
                                  keep=lambda text: bool(
                                      one_query.search(text)))
    seconds = [sum(b - a for _, a, b in ev) for ev in events.values() if ev]
    if not seconds:
        return None
    context = sum(s["decode_context"] for s in steps)
    work = flops.paged_decode_work(shape, context)
    least = flops.roofline_seconds(work, run.peak) * shape.layers
    return 100.0 * least / (sum(seconds) / len(seconds))
