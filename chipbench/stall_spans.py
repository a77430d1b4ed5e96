"""Host stalls, read from the program's span ring: the set-up metrics and
the traced slice's idle time under a stall.

The program records into its one span ring (`horovod_tpu.trace`,
docs/tracing.md) ``hvd_import`` (the package's import) and ``hvd_init``
(`hvd.init()`); from jax's own monitoring events every ``jit_trace``,
``jit_lower`` and ``jit_compile`` (attributes ``fun``, and on a compile
``fetched``: the persistent cache had the program); and ``gc_pause`` for
every generation-2 collection and any collection of 1 ms or more. Each
lies under the span that was open on its thread. A reader
(``chipbench/layer_metrics/setup.*.py``, ``serve.idle_stall_ms.py``)
calls

    s = stall_spans.analyse(run)      # None: nothing to read
    s.setup["compile_fetch_s"]        # s.setup None: set-up not held
    s.slice["idle_stall_s"]           # s.slice None: slice not anchored

and gets the same object as every other reader of the run (it is kept on
``run``); the first call prints one ``info stall_spans ...`` line on
standard error. A program that records none of these spans (an older
commit: its ``SPAN_LEGS`` lacks them) gives None.

**Set-up** (`s.setup`): seconds under ``hvd_import`` and ``hvd_init``;
the union of the ``jit_trace`` and ``jit_lower`` spans that ENDED before
the traced slice opened (a trace nested in another counts once); the
same for ``jit_compile``, also split into fetched and compiled programs.
None when the ring holds no ``hvd_import`` or has evicted anything: the
set-up spans are the ring's oldest, so any eviction took some of them.

**Slice** (`s.slice`): the device's idle time inside the traced slice,
on the fullest device, that overlaps any ``jit_*`` or ``gc_pause`` span.
The ring is laid on the trace by `program_spans`' anchor (the harness's
``chipbench/window`` span against `run.tracer`'s stamps, refused beyond
its 2 ms) and its test of a wrapped ring. 0.0 where the slice anchors
and holds no stall.
"""
from __future__ import annotations

import sys
import time
from types import SimpleNamespace
from typing import Dict, List, Optional

from chipbench import program_spans, xplane

JIT = ("jit_trace", "jit_lower", "jit_compile")
STALLS = JIT + ("gc_pause",)
SETUP = {"import_init_s": ("hvd_import", "hvd_init"),
         "trace_lower_s": ("jit_trace", "jit_lower"),
         "compile_fetch_s": ("jit_compile",)}
#: a stall of the slice at least this long is named on the info line
LISTED_S = 0.010
_CACHE = "_stall_spans"


def _records_stalls() -> bool:
    """Does the program under test declare the stall spans?"""
    try:
        from horovod_tpu.trace import spans
    except ImportError:
        return False
    return all(n in getattr(spans, "SPAN_LEGS", ()) for n in STALLS)


def analyse(run, recorder=None):
    """The run's stall spans, reduced once; None when there is nothing
    to read. `recorder` is for tests."""
    if hasattr(run, _CACHE):
        return getattr(run, _CACHE)
    out = None
    rec = recorder if recorder is not None else program_spans._recorder()
    tr = run.tracer
    if rec is not None and _records_stalls() and tr.t_start is not None \
            and tr.t_stop is not None:
        # recorder clock minus harness clock, sampled now
        delta = rec.now() - time.perf_counter()
        setup = _setup_side(rec, tr.t_start + delta)
        stalls = _slice_side(run, rec, delta)
        if setup is not None or stalls is not None:
            out = SimpleNamespace(setup=setup, slice=stalls, ring=(
                len(rec.between(float("-inf"), float("inf"))),
                rec.evicted))
            _report(out)
    setattr(run, _CACHE, out)
    return out


def setup_seconds(run, key: str) -> Optional[float]:
    a = analyse(run)
    return None if a is None or a.setup is None else a.setup[key]


def idle_stall_ms(run) -> Optional[float]:
    a = analyse(run)
    return None if a is None or a.slice is None \
        else 1e3 * a.slice["idle_stall_s"]


def _covered(spans) -> float:
    return xplane.measure((s.t0, s.t1) for s in spans)


def _setup_side(rec, t_slice: float) -> Optional[dict]:
    if rec.evicted:
        return None
    before = [s for s in rec.between(float("-inf"), t_slice)
              if s.t1 <= t_slice]
    if not any(s.name == "hvd_import" for s in before):
        return None
    out = {key: _covered(s for s in before if s.name in names)
           for key, names in SETUP.items()}
    compiles = [s for s in before if s.name == "jit_compile"]
    for key, fetched in (("fetched", True), ("compiled", False)):
        mine = [s for s in compiles if bool(s.extra.get("fetched")) == fetched]
        out[key] = (len(mine), _covered(mine))
    by_fun: Dict[str, float] = {}
    for s in before:
        if s.name in JIT:
            fun = str(s.extra.get("fun", ""))
            by_fun[fun] = by_fun.get(fun, 0.0) + (s.t1 - s.t0)
    out["costliest"] = sorted(by_fun.items(), key=lambda kv: -kv[1])[:5]
    return out


def _anchor(run) -> Optional[tuple]:
    """The traced window on the trace's clock, where the harness's
    ``chipbench/window`` span brackets the same stretch as its stamps."""
    trace, tr = run.trace, run.tracer
    if trace is None or not trace.ops:
        return None
    window = [e for e in trace.host_spans if e[0] == xplane.WINDOW_SPAN]
    if not window:
        return None
    lo, hi = window[0][1], window[-1][2]
    if abs((hi - lo) - (tr.t_stop - tr.t_start)) > \
            program_spans.ANCHOR_TOLERANCE_S:
        return None
    return lo, hi


def _above(by_id: dict, s, prefixes: tuple) -> Optional[object]:
    """The nearest span over `s` (not `s`) whose name starts with one of
    `prefixes`."""
    up = by_id.get(s.parent)
    while up is not None and not up.name.startswith(prefixes):
        up = by_id.get(up.parent)
    return up


def _slice_side(run, rec, delta: float) -> Optional[dict]:
    anchor = _anchor(run)
    tr = run.tracer
    if anchor is None or program_spans._lost(rec, tr.t_start + delta):
        return None
    lo, hi = anchor
    # recorder clock -> trace clock
    offset = lo - (tr.t_start + delta)
    spans = rec.between(tr.t_start + delta, tr.t_stop + delta)
    stalls = [s for s in spans if s.name in STALLS]
    busy = xplane.busy_by_device(run.trace)
    dev = max(busy, key=busy.get)       # the device idle_share reads
    gaps = xplane.subtract([(lo, hi)], xplane.union(xplane.clip(
        ((a, b) for _, a, b in run.trace.ops[dev]), lo, hi)))

    def idle_under(intervals) -> float:
        cover = xplane.union(xplane.clip(intervals, lo, hi))
        return xplane.measure(gaps) - xplane.measure(
            xplane.subtract(gaps, cover))

    by_id = {s.span: s for s in spans}
    listed: List[dict] = []
    for s in stalls:
        if s.t1 - s.t0 < LISTED_S:
            continue
        up = _above(by_id, s, program_spans._THREAD_SPANS)
        step = program_spans._above(by_id, up, "exec_step")
        listed.append({
            "name": s.name, "fun": s.extra.get("fun"),
            "under": up.name if up is not None else None,
            "kind": step.extra.get("kind") if step is not None else None,
            "s": s.t1 - s.t0,
            "idle_s": idle_under([(s.t0 + offset, s.t1 + offset)])})
    counts: Dict[str, int] = {}
    for s in stalls:
        counts[s.name] = counts.get(s.name, 0) + 1
    return {"idle_stall_s": idle_under(
                [(s.t0 + offset, s.t1 + offset) for s in stalls]),
            "idle_s": xplane.measure(gaps), "window_s": hi - lo,
            "counts": counts, "listed": listed,
            "jit_funs": sorted({str(s.extra.get("fun")) for s in stalls
                                if s.name in JIT})}


def _report(a) -> None:
    parts = []
    st, sl = a.setup, a.slice
    if st is not None:
        parts.append(
            "set-up " + " ".join(f"{k} {st[k]:.3f}" for k in SETUP)
            + " (compiled {} in {:.3f} s, fetched {} in {:.3f} s)".format(
                *st["compiled"], *st["fetched"]))
        parts.append("costliest " + ", ".join(
            f"{fun} {secs:.3f}" for fun, secs in st["costliest"]))
    if sl is not None:
        parts.append(
            f"slice idle_stall_s {sl['idle_stall_s']:.4f} of idle_s "
            f"{sl['idle_s']:.4f} in window {sl['window_s']:.4f}; spans "
            + (" ".join(f"{k} {n}" for k, n in sorted(sl["counts"].items()))
               or "none")
            + (f"; jit funs {' '.join(sl['jit_funs'])}" if sl["jit_funs"]
               else ""))
        for x in sl["listed"]:
            parts.append(
                f"stall {x['name']} fun {x['fun']} under {x['under']} kind "
                f"{x['kind']} {1e3 * x['s']:.1f} ms idle "
                f"{1e3 * x['idle_s']:.1f} ms")
    parts.append(f"ring {a.ring[0]} spans, {a.ring[1]} evicted")
    print("info stall_spans " + "; ".join(parts), file=sys.stderr)
