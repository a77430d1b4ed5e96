"""Share of the traced slice's device-busy time spent in the
selective-scan decode kernel (Mosaic custom calls named ``ssm_decode``,
found by NAME on the fullest device): what the Mamba layers' two per-row
states cost a step, beside the matmuls' weights and the paged attention.
No kernel of that name, nothing printed."""
from chipbench import exec_steps, xplane

PATTERN = r"ssm_decode"


def read(run):
    trace = run.trace
    if trace is None or not trace.ops:
        return None
    dev = exec_steps.busiest_device(trace)
    events = xplane.kernel_events(trace, PATTERN)[dev]
    lo, hi = xplane.window(trace)
    total = xplane.measure(xplane.union(xplane.clip(
        ((a, b) for _, a, b in trace.ops[dev]), lo, hi)))
    if not events or not total:
        return None
    return 100.0 * sum(b - a for _, a, b in events) / total
