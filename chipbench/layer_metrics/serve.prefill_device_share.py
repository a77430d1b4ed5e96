"""Share of the traced slice's device-busy time that ran under prefill
steps: the time in which an op ran on the (fullest) device inside the
``exec_step`` spans of kind ``prefill``, over all the time in which one
ran inside the window. What is left is decode. No ring or no prefill in
the slice, nothing printed."""
from chipbench import exec_steps, xplane


def read(run):
    prefill = exec_steps.of_kind(run, "prefill")
    if not prefill:
        return None
    trace = run.trace
    dev = exec_steps.busiest_device(trace)
    lo, hi = xplane.window(trace)
    busy = xplane.union(xplane.clip(
        ((a, b) for _, a, b in trace.ops[dev]), lo, hi))
    total = xplane.measure(busy)
    if not total:
        return None
    inside = sum(xplane.measure(xplane.clip(busy, a, b))
                 for a, b, _ in prefill)
    return 100.0 * inside / total
