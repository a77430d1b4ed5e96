"""The flash-attention kernels' share of their roofline: the least time
the chip could take for the causal attention of all layers, forward and
backward, at this cell's shapes (counted by the run's family), over the
device time of the flash fwd / dq / dkv kernel events per step.

The kernels are found by NAME: Mosaic custom calls whose instruction is
named after `flash_attention` (the jitted entry point in
ops/pallas_attention.py). This trace carries no flax module path on its
events. A PR that renames or replaces the kernel makes this reader find
nothing, and the metric is then left out of the line, not printed as 0."""
from chipbench import roofline, xplane

PATTERN = r"flash_attention"


def read(run):
    if run.trace is None or not run.traced.get("steps"):
        return None
    events = xplane.kernel_events(run.trace, PATTERN)
    per_dev = [sum(b - a for _, a, b in ev) for ev in events.values() if ev]
    if not per_dev:
        return None
    work = run.family.attention_work(
        run.shape, int(run.traffic["rows_per_chip"]),
        int(run.traffic["seq_len"]))
    least = roofline.roofline_seconds(work, run.peak) * run.traced["steps"]
    return 100.0 * least / (sum(per_dev) / len(per_dev))
