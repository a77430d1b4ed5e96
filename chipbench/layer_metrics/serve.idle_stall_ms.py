"""Device idle time of the traced slice, on the fullest device, that
overlaps a host stall of the program: any ``jit_trace``, ``jit_lower``,
``jit_compile`` or ``gc_pause`` span (chipbench/stall_spans.py). 0.0
where the slice anchors and holds no stall."""
from chipbench import stall_spans


def read(run):
    return stall_spans.idle_stall_ms(run)
