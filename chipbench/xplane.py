"""From a profiler trace to numbers: the one reduction every PR shares.

`load` reads an ``.xplane.pb`` with `jax.profiler.ProfileData` (a copy
of the reader idea in ``benchmarks/xplane_profile.py``, without its
ResNet categories) into a plain :class:`Trace`; everything below that is
arithmetic on intervals and is checked against the recorded trace in
``chipbench/data``. Times are seconds on the trace's own clock.

Device planes are ``/device:TPU:<n>``; their ``XLA Ops`` line holds one
event per executed HLO instruction, named by the instruction's text
(``%flash_attention.3 = ... custom_call_target="tpu_custom_call"``), and
``Async XLA Ops`` the start..done spans of asynchronous copies and
collectives. Host spans are the harness's own `TraceAnnotation`s, named
``chipbench/<what>``; ``chipbench/window`` brackets the traced window.
"""
from __future__ import annotations

import glob
import gzip
import json
import os
import re
from dataclasses import dataclass, field
from typing import Callable, Dict, Iterable, List, Optional, Tuple

Interval = Tuple[float, float]            # (start_s, end_s)
Event = Tuple[str, float, float]          # (name, start_s, end_s)

WINDOW_SPAN = "chipbench/window"
SPAN_PREFIX = "chipbench/"
COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all")
_PALLAS = 'custom_call_target="tpu_custom_call"'


@dataclass
class Trace:
    """ops/async_ops: per device, events sorted by start."""
    ops: Dict[int, List[Event]] = field(default_factory=dict)
    async_ops: Dict[int, List[Event]] = field(default_factory=dict)
    host_spans: List[Event] = field(default_factory=list)

    def to_json(self) -> dict:
        return {"ops": {str(k): v for k, v in self.ops.items()},
                "async_ops": {str(k): v for k, v in self.async_ops.items()},
                "host_spans": self.host_spans}

    @classmethod
    def from_json(cls, obj: dict) -> "Trace":
        def events(rows):
            return [(str(n), float(a), float(b)) for n, a, b in rows]
        return cls({int(k): events(v) for k, v in obj["ops"].items()},
                   {int(k): events(v) for k, v in obj["async_ops"].items()},
                   events(obj["host_spans"]))


def load_json(path: str) -> Trace:
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rt") as f:
        return Trace.from_json(json.load(f))


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def load(path: str) -> Trace:
    """Read one ``.xplane.pb``. Only TPU device planes count as devices:
    a CPU rehearsal has none and yields an empty `ops`."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    trace = Trace()
    for plane in data.planes:
        m = re.match(r"/device:TPU:(\d+)$", plane.name)
        if m:
            dev = int(m.group(1))
            for line in plane.lines:
                if line.name == "XLA Ops":
                    trace.ops[dev] = _events(line)
                elif line.name == "Async XLA Ops":
                    trace.async_ops[dev] = _events(line)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                trace.host_spans.extend(
                    e for e in _events(line) if e[0].startswith(SPAN_PREFIX))
    trace.host_spans.sort(key=lambda e: e[1])
    return trace


def _events(line) -> List[Event]:
    out = [(e.name, e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9)
           for e in line.events]
    out.sort(key=lambda e: e[1])
    return out


# ---------------------------------------------------------------------------
# interval arithmetic
# ---------------------------------------------------------------------------

def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Disjoint, sorted cover of `intervals` (empty ones dropped)."""
    out: List[Interval] = []
    for a, b in sorted(i for i in intervals if i[1] > i[0]):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def measure(intervals: Iterable[Interval]) -> float:
    return sum(b - a for a, b in union(intervals))


def clip(intervals: Iterable[Interval], lo: float, hi: float
         ) -> List[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def subtract(cover: List[Interval], holes: List[Interval]) -> List[Interval]:
    """`cover` minus `holes`; both must be disjoint and sorted."""
    out, j = [], 0
    for a, b in cover:
        cur = a
        while j < len(holes) and holes[j][1] <= cur:
            j += 1
        k = j
        while k < len(holes) and holes[k][0] < b:
            if holes[k][0] > cur:
                out.append((cur, holes[k][0]))
            cur = max(cur, holes[k][1])
            k += 1
        if cur < b:
            out.append((cur, b))
    return out


# ---------------------------------------------------------------------------
# reductions
# ---------------------------------------------------------------------------

def window(trace: Trace) -> Interval:
    """The traced window: the harness's ``chipbench/window`` span where
    the trace has it, else first device op start to last op end."""
    spans = [e for e in trace.host_spans if e[0] == WINDOW_SPAN]
    if spans:
        return spans[0][1], spans[-1][2]
    starts = [ev[0][1] for ev in trace.ops.values() if ev]
    ends = [max(e[2] for e in ev) for ev in trace.ops.values() if ev]
    if not starts:
        raise ValueError("the trace holds no device operation")
    return min(starts), max(ends)


def busy_by_device(trace: Trace) -> Dict[int, float]:
    """Seconds inside the window in which an op ran, per device."""
    lo, hi = window(trace)
    return {dev: measure(clip(((a, b) for _, a, b in ev), lo, hi))
            for dev, ev in trace.ops.items()}


def idle_share(trace: Trace) -> Optional[float]:
    """Share of the window in which no op ran on the device (the
    fullest-loaded device where there are several); None for a trace
    with no device operation."""
    if not trace.ops:
        return None
    lo, hi = window(trace)
    return 1.0 - max(busy_by_device(trace).values()) / (hi - lo)


def is_collective(name: str) -> bool:
    return bool(COLLECTIVE.search(name.split(" = ")[0])) or bool(
        COLLECTIVE.search(_opcode(name)))


def _opcode(name: str) -> str:
    """The opcode of an instruction's text (``... = type opcode(...)``)."""
    m = re.search(r"\s([a-z][\w\-]*)\(", name.split(" = ", 1)[-1])
    return m.group(1) if m else ""


def exposed_collective_by_device(trace: Trace) -> Dict[int, float]:
    """Seconds inside the window in which a collective was in flight on
    a device (its sync op, or its async start..done span) while no other
    op ran there."""
    if not trace.ops:
        return {}
    lo, hi = window(trace)
    out = {}
    for dev, ev in trace.ops.items():
        both = list(ev) + list(trace.async_ops.get(dev, ()))
        coll = union(clip(((a, b) for n, a, b in both if is_collective(n)),
                          lo, hi))
        compute = union(clip(((a, b) for n, a, b in ev
                              if not is_collective(n)), lo, hi))
        out[dev] = measure(subtract(coll, compute))
    return out


def kernel_events(trace: Trace, pattern: str,
                  keep: Optional[Callable[[str], bool]] = None
                  ) -> Dict[int, List[Event]]:
    """Pallas (Mosaic) custom-call events inside the window whose
    instruction NAME (the text before `` = ``) matches `pattern`, per
    device. `keep` filters on the full text (operand shapes are in
    it). A trace with no device operation has none."""
    if not trace.ops:
        return {}
    lo, hi = window(trace)
    rx = re.compile(pattern)
    out = {}
    for dev, ev in trace.ops.items():
        out[dev] = [e for e in ev
                    if _PALLAS in e[0] and rx.search(e[0].split(" = ")[0])
                    and e[1] >= lo and e[2] <= hi
                    and (keep is None or keep(e[0]))]
    return out


def short_name(name: str) -> str:
    """``%convolution_add_fusion.23 = ...`` -> ``convolution_add_fusion``."""
    head = name.split(" = ")[0].strip().lstrip("%")
    return re.sub(r"[.\d]+$", "", head) or head


def top_device_ops(trace: Trace, n: int = 10) -> List[List]:
    """[[short name, seconds]] of the ops that took most device time in
    the window, averaged over devices."""
    lo, hi = window(trace)
    total: Dict[str, float] = {}
    for ev in trace.ops.values():
        for name, a, b in ev:
            a, b = max(a, lo), min(b, hi)
            if b > a:
                key = short_name(name)
                total[key] = total.get(key, 0.0) + (b - a)
    ndev = max(len(trace.ops), 1)
    rows = sorted(total.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v / ndev] for k, v in rows]


def idle_gaps_by_span(trace: Trace, n: int = 10,
                      device: Optional[int] = None) -> List[List]:
    """[[span name, seconds]]: the idle time of one device inside the
    window, attributed to the innermost harness span that covers each
    gap's midpoint (``(no span)`` where none does), largest first."""
    lo, hi = window(trace)
    if not trace.ops:
        return []
    dev = min(trace.ops) if device is None else device
    busy = union(clip(((a, b) for _, a, b in trace.ops[dev]), lo, hi))
    gaps = sorted(subtract([(lo, hi)], busy), key=lambda g: g[0] - g[1])
    spans = [e for e in trace.host_spans if e[0] != WINDOW_SPAN]
    total: Dict[str, float] = {}
    # the many sub-microsecond gaps between back-to-back ops are summed
    # unattributed; the longest ones carry the idle time worth a name
    short = sum(b - a for a, b in gaps[2000:])
    if short:
        total["(short gaps)"] = short
    for a, b in gaps[:2000]:
        mid = (a + b) / 2
        cover = [s for s in spans if s[1] <= mid <= s[2]]
        name = (min(cover, key=lambda s: s[2] - s[1])[0][len(SPAN_PREFIX):]
                if cover else "(no span)")
        total[name] = total.get(name, 0.0) + (b - a)
    rows = sorted(total.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v] for k, v in rows]
