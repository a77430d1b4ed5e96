"""The program's ``exec_step`` spans laid on the device trace's clock.

Every `ShardedExecutor.step` ends in a readback that waits for the
device, so whatever ran on the device for a step ran inside its
``exec_step`` span. A reader that wants the device time of one KIND of
step (the experts' kernels in decode steps, the flash forward in prefill
steps, a prefill's share of the busy time) takes the steps from here,
each ``(t0, t1, attributes)`` on the trace's clock, and the events that
lie inside them. The anchoring is `program_spans`': the harness's
``chipbench/window`` span against `run.tracer.t_start`, refused (None)
when the two do not bracket the same stretch or the ring has lost spans
of the slice. A program without the ring gives None.
"""
from __future__ import annotations

import time
from typing import List, Optional, Tuple

from chipbench import program_spans, xplane

Step = Tuple[float, float, dict]
_CACHE = "_exec_steps"


def steps(run, recorder=None) -> Optional[List[Step]]:
    """The ``exec_step`` spans that lie wholly inside the traced slice,
    on the trace's clock, sorted; None where there is nothing to read.
    Reduced once a run (kept on `run`); `recorder` is for tests."""
    if hasattr(run, _CACHE):
        return getattr(run, _CACHE)
    out = None
    rec = recorder if recorder is not None else program_spans._recorder()
    tr, trace = run.tracer, run.trace
    window = [e for e in trace.host_spans if e[0] == xplane.WINDOW_SPAN] \
        if trace is not None and trace.ops else []
    if rec is not None and window and tr.t_start is not None \
            and tr.t_stop is not None:
        lo, hi = window[0][1], window[-1][2]
        delta = rec.now() - time.perf_counter()
        anchored = abs((hi - lo) - (tr.t_stop - tr.t_start)) \
            <= program_spans.ANCHOR_TOLERANCE_S
        if anchored and not program_spans._lost(rec, tr.t_start + delta):
            offset = lo - (tr.t_start + delta)
            out = sorted(
                (s.t0 + offset, s.t1 + offset, dict(s.extra or {}))
                for s in rec.between(tr.t_start + delta, tr.t_stop + delta)
                if s.name == "exec_step" and s.t0 >= tr.t_start + delta
                and s.t1 <= tr.t_stop + delta)
    setattr(run, _CACHE, out)
    return out


def of_kind(run, kind: str) -> Optional[List[Step]]:
    found = steps(run)
    if found is None:
        return None
    return [s for s in found if s[2].get("kind") == kind]


def seconds_inside(events, spans: List[Step]) -> float:
    """Summed length of the `events` ``(name, start, end)`` that lie
    inside one of the (disjoint, sorted) `spans`."""
    total, i = 0.0, 0
    for _, a, b in sorted(events, key=lambda e: e[1]):
        while i < len(spans) and spans[i][1] < a:
            i += 1
        if i < len(spans) and spans[i][0] <= a and b <= spans[i][1]:
            total += b - a
    return total


def busiest_device(trace) -> int:
    busy = xplane.busy_by_device(trace)
    return max(busy, key=busy.get)


def kernel_seconds(run, pattern: str, spans: List[Step]) -> float:
    """Device time (the fullest device's) of the Pallas kernels named
    `pattern` that ran inside `spans`."""
    events = xplane.kernel_events(run.trace, pattern)
    return seconds_inside(events[busiest_device(run.trace)], spans)
