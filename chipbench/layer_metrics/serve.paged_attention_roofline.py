"""Decode attention's share of its roofline: the least time the chip
could take to read the K and V the traced decode steps attended to (the
logical bytes of the cached keys, not the tile-padded ones) and do their
products, over the device time of the decode-attention kernel events.

The kernel is found by NAME (Mosaic custom calls named after
`paged_attention`; the trace carries no flax module path) and told from
the prefill calls of the same kernel by its query operand, which the
run's family gives as a pattern (one query per row). The family also
says what all its layers together had to read and do over the traced
steps, so one whose layers differ (a window on some) can say so.
Nothing found, nothing printed."""
import re

from chipbench import roofline, xplane

PATTERN = r"paged_attention"


def read(run):
    steps = (run.traced or {}).get("steps")
    if run.trace is None or not steps:
        return None
    rows = int(run.traffic["server"]["max_batch"])
    one_query = re.compile(run.family.decode_query_pattern(run.shape, rows))
    events = xplane.kernel_events(run.trace, PATTERN,
                                  keep=lambda text: bool(
                                      one_query.search(text)))
    seconds = [sum(b - a for _, a, b in ev) for ev in events.values() if ev]
    if not seconds:
        return None
    work = run.family.decode_attention_work(run.shape, steps)
    least = roofline.roofline_seconds(work, run.peak)
    return 100.0 * least / (sum(seconds) / len(seconds))
