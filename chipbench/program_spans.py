"""The program's own spans, read for the serving cells' per-layer metrics.

The serve hot path records what it does into the process's span ring
(`horovod_tpu.trace.get_recorder()`, docs/tracing.md): one
``sched_iteration`` per `ContinuousBatcher.step` with its phases, one
``exec_step`` per `ShardedExecutor.step` with ``exec_upload`` /
``exec_dispatch`` / ``exec_readback``, and per request ``request``,
``queue_wait``, ``prefill``, ``decode`` (one stamp per token). A reader
(``chipbench/layer_metrics/serve.<metric>.py``) calls

    a = program_spans.analyse(run)        # None: nothing to read
    a.host["ttft_ms"]                     # lists, one entry per sample
    a.idle["upload_s"], a.idle["decode_iterations"]

and gets the same object as every other reader of the run (it is kept on
``run``); the first call prints one ``info program_spans ...`` line on
standard error. A program without the ring (an older commit) gives None.

**Clocks.** The ring's stamps are `recorder.now()` (monotonic), the
harness's are `time.perf_counter()`, the profile's are its own. The
difference of the first two is sampled here, at the read. The profile's
clock is reached through the harness's ``chipbench/window`` span: its
start on the trace clock is `run.tracer.t_start` on the harness's. The
idle side refuses (None) when that span's length and `t_stop - t_start`
differ by more than 2 ms (the two anchors do not bracket the same
stretch), when the ring has wrapped past the slice, or when the slice
holds no program span.

**Host side** (`a.host`), from the spans alone. The population is the
one ``serve.request_p90_ms`` takes, as nearly as the program's spans
tell it: requests that resolved ``ok``, were submitted after the first
``sched_iteration`` that began after the profiler stopped (its stop
stalls the loop for seconds), and resolved before the window closed
(`run.window_s` after `run.window_open`, which the runner stamps on the
harness's clock).

**Idle side** (`a.idle`), from the device trace and the spans: the time
inside the traced window in which no operation ran on the device, split
by the innermost ``sched_*`` / ``exec_*`` span open at each instant. (By
overlap, not by a gap's midpoint: between two decode programs the device
sees ONE gap of several milliseconds that begins under the readback of
one step and ends after the dispatch of the next; the midpoint would hand
all of it to whichever span the middle happens to fall in.)

    upload_s     exec_upload, exec_dispatch and exec_step's own time,
                 in decode (and verify) steps: before the program runs
    readback_s   exec_readback in those steps
    sched_s      any sched_* span, outside every exec_step
    prefill_s    anything inside a prefill's exec_step
    none_s       under no program span

which add up to the window's idle time on the fullest device.
"""
from __future__ import annotations

import statistics
import sys
import time
from types import SimpleNamespace
from typing import Dict, List, Optional

import numpy as np

from chipbench import xplane

ANCHOR_TOLERANCE_S = 0.002
_THREAD_SPANS = ("sched_", "exec_")
_CACHE = "_program_spans"


def _recorder():
    try:
        from horovod_tpu.trace import get_recorder
    except ImportError:
        return None
    rec = get_recorder()
    # an older program has the recorder but no ring to read
    return rec if hasattr(rec, "between") else None


def percentile(values: List[float], q: float) -> float:
    """The q-th percentile by linear interpolation (numpy's default)."""
    return float(np.percentile(np.asarray(values, np.float64), q))


def analyse(run, recorder=None):
    """The run's spans, reduced once; None when there is nothing to
    read (no traced slice, no ring). `recorder` is for tests."""
    if hasattr(run, _CACHE):
        return getattr(run, _CACHE)
    out = None
    rec = recorder if recorder is not None else _recorder()
    tr = run.tracer
    if rec is not None and tr.t_start is not None and tr.t_stop is not None:
        # recorder clock minus harness clock, sampled now
        delta = rec.now() - time.perf_counter()
        host = _host_side(run, rec, delta)
        idle = _idle_side(run, rec, delta)
        if host is not None or idle is not None:
            out = SimpleNamespace(host=host, idle=idle)
            _report(out)
    setattr(run, _CACHE, out)
    return out


def _above(by_id: dict, s, name: str):
    """`s` itself or the nearest span over it that is called `name`."""
    while s is not None and s.name != name:
        s = by_id.get(s.parent)
    return s


def _lost(rec, since: float) -> bool:
    """Has the ring evicted spans that ended after `since`?"""
    oldest = rec.oldest()
    return oldest is None or (rec.evicted > 0 and oldest > since)


# ---------------------------------------------------------------------------
# host side: requests, token gaps, occupancy
# ---------------------------------------------------------------------------

def _host_side(run, rec, delta: float) -> Optional[dict]:
    if run.window_open is None:
        return None
    t_stop = run.tracer.t_stop + delta
    t_open = run.window_open + delta
    if _lost(rec, t_open):
        return None
    t_close = t_open + run.window_s
    spans = rec.between(t_open, t_close)
    after = [s.t0 for s in spans
             if s.name == "sched_iteration" and s.t0 >= t_stop]
    if not after:
        return None
    t_quiet = min(after)
    # a request's legs share its trace id: `queue_wait` ends where its
    # admission wave starts prefill, `prefill` where its first token
    # exists, `decode` carries one stamp per token
    legs: Dict[tuple, object] = {
        (s.trace, s.name): s for s in spans
        if s.name in ("queue_wait", "prefill", "decode")}
    requests = [s for s in spans if s.name == "request"
                and s.extra.get("status") == "ok"
                and s.t0 >= t_quiet and s.t1 <= t_close
                and (s.trace, "prefill") in legs]
    if not requests:
        return None
    gaps: List[float] = []
    for s in requests:
        decode = legs.get((s.trace, "decode"))
        if decode is not None:
            gaps.extend(np.diff(decode.extra.get("token_times", ())) * 1e3)
    max_batch = int(run.traffic["server"]["max_batch"])
    decode_steps = [s for s in spans if s.name == "exec_step"
                    and s.extra.get("kind") == "decode"]
    quiet_steps = [s for s in decode_steps if s.t0 >= t_quiet
                   and s.t1 <= t_close]
    # the scheduler's own time, iteration by iteration, over the whole
    # window: for the cross-check against the harness's outside clocks
    by_id = {s.span: s for s in spans if s.name.startswith(_THREAD_SPANS)}
    inside: Dict[str, float] = {}
    for s in by_id.values():
        up = _above(by_id, s, "sched_iteration") \
            if s.name == "exec_step" else None
        if up is not None:
            inside[up.span] = inside.get(up.span, 0.0) + (s.t1 - s.t0)
    sched_self = [1e3 * ((s.t1 - s.t0) - inside.get(s.span, 0.0))
                  for s in by_id.values() if s.name == "sched_iteration"
                  and s.t0 >= t_open and s.t1 <= t_close]
    return {
        "requests": len(requests),
        "ttft_ms": [1e3 * (legs[s.trace, "prefill"].t1 - s.t0)
                    for s in requests],
        "queue_wait_ms": [1e3 * (q.t1 - q.t0) for q in (
            legs.get((s.trace, "queue_wait")) for s in requests)
            if q is not None],
        "request_ms": [1e3 * (s.t1 - s.t0) for s in requests],
        "token_gaps_ms": [float(g) for g in gaps],
        "occupancy": [s.extra.get("rows", 0) / max_batch
                      for s in quiet_steps],
        "exec_step_ms": [1e3 * (s.t1 - s.t0) for s in decode_steps
                         if s.t0 >= t_open and s.t1 <= t_close],
        "sched_self_ms": sched_self,
    }


# ---------------------------------------------------------------------------
# idle side: device gaps under program spans
# ---------------------------------------------------------------------------

def _leaves(spans) -> List[tuple]:
    """Disjoint ``(start, end, span)`` pieces, sorted: each stretch of
    time with the innermost of the spans over it. The spans are those
    of one thread's stack (the caller sees to it), so they nest."""
    out: List[tuple] = []
    stack: List = []        # the spans open at `cursor`, outermost first
    cursor = 0.0

    def close(until: float) -> None:
        nonlocal cursor
        while stack and stack[-1].t1 <= until:
            top = stack.pop()
            if top.t1 > cursor:
                out.append((cursor, top.t1, top))
                cursor = top.t1

    for s in sorted(spans, key=lambda s: (s.t0, -s.t1)):
        close(s.t0)
        if stack and s.t0 > cursor:
            out.append((cursor, s.t0, stack[-1]))
        stack.append(s)
        cursor = s.t0
    close(float("inf"))
    return out


def _idle_side(run, rec, delta: float) -> Optional[dict]:
    trace, tr = run.trace, run.tracer
    if trace is None or not trace.ops:
        return None
    window = [e for e in trace.host_spans if e[0] == xplane.WINDOW_SPAN]
    if not window:
        return None
    lo, hi = window[0][1], window[-1][2]
    if abs((hi - lo) - (tr.t_stop - tr.t_start)) > ANCHOR_TOLERANCE_S:
        return None
    if _lost(rec, tr.t_start + delta):
        return None
    # recorder clock -> trace clock
    offset = lo - (tr.t_start + delta)
    spans = [s for s in rec.between(tr.t_start + delta, tr.t_stop + delta)
             if s.name.startswith(_THREAD_SPANS)]
    by_id = {s.span: s for s in spans}
    # one scheduler thread's stack nests, and only what nests can be
    # split by innermost span: its iterations and what lies under them.
    # Two schedulers in the process (iterations that overlap) give
    # nothing to read; another thread's spans are left out
    roots = sorted((s.t0, s.t1) for s in spans
                   if s.name == "sched_iteration")
    if any(b[0] < a[1] for a, b in zip(roots, roots[1:])):
        return None
    spans = [s for s in spans
             if _above(by_id, s, "sched_iteration") is not None]
    if not spans:
        return None
    busy = xplane.busy_by_device(trace)
    dev = max(busy, key=busy.get)       # the device idle_share reads
    gaps = xplane.subtract([(lo, hi)], xplane.union(xplane.clip(
        ((a, b) for _, a, b in trace.ops[dev]), lo, hi)))
    total = {"upload_s": 0.0, "readback_s": 0.0, "sched_s": 0.0,
             "prefill_s": 0.0}
    by_leaf: Dict[str, float] = {}
    # both lists are disjoint and sorted: one pass gives each piece of
    # span time the idle seconds that overlap it
    g = 0
    for a, b, leaf in _leaves(spans):
        a, b = a + offset, b + offset
        while g < len(gaps) and gaps[g][1] <= a:
            g += 1
        idle, k = 0.0, g
        while k < len(gaps) and gaps[k][0] < b:
            idle += min(gaps[k][1], b) - max(gaps[k][0], a)
            k += 1
        if not idle:
            continue
        step = _above(by_id, leaf, "exec_step")
        name = leaf.name
        if step is None:
            key = "sched_s"
        elif step.extra.get("kind") == "prefill":
            key, name = "prefill_s", "prefill/" + leaf.name
        elif leaf.name == "exec_readback":
            key = "readback_s"
        else:
            key = "upload_s"
        total[key] += idle
        by_leaf[name] = by_leaf.get(name, 0.0) + idle
    total["idle_s"] = sum(b - a for a, b in gaps)
    # the pieces are disjoint, so this is never below 0 but by rounding
    total["none_s"] = total["idle_s"] - sum(by_leaf.values())
    if total["none_s"] > 1e-9:
        by_leaf["(no span)"] = total["none_s"]
    t_lo, t_hi = tr.t_start + delta, tr.t_stop + delta
    total["window_s"] = hi - lo
    total["decode_iterations"] = sum(
        1 for s in spans if s.name == "exec_step"
        and s.extra.get("kind") != "prefill"
        and s.t0 >= t_lo and s.t1 <= t_hi)
    total["by_leaf"] = by_leaf
    return total


def per_decode_iteration_ms(run, key: str) -> Optional[float]:
    """``a.idle[key]`` in milliseconds per decode iteration of the
    traced slice; None where there is no idle side or no iteration."""
    a = analyse(run)
    if a is None or a.idle is None or not a.idle["decode_iterations"]:
        return None
    return 1e3 * a.idle[key] / a.idle["decode_iterations"]


def host_samples(run, key: str) -> Optional[List[float]]:
    """``a.host[key]`` where it holds at least one sample, else None."""
    a = analyse(run)
    if a is None or a.host is None or not a.host[key]:
        return None
    return a.host[key]


def _report(a) -> None:
    parts = []
    h, idle = a.host, a.idle
    if h is not None:
        parts.append(f"requests {h['requests']}")
        # queue wait is no metric: its tail is a few requests that
        # wait for KV blocks, and no statistic of it repeats (PERF.md)
        for key, qs in (("ttft_ms", (50,)), ("queue_wait_ms", (50, 90)),
                        ("request_ms", (90,)),
                        ("token_gaps_ms", (50, 99))):
            if h[key]:
                parts.append(f"{key} n {len(h[key])} " + " ".join(
                    f"p{q} {percentile(h[key], q):.3f}" for q in qs))
            if h[key] and key == "queue_wait_ms":
                parts[-1] += (f" mean {statistics.fmean(h[key]):.3f}"
                              f" max {max(h[key]):.3f}")
        if h["occupancy"]:
            parts.append(f"occupancy {statistics.fmean(h['occupancy']):.4f}"
                         f" over {len(h['occupancy'])} decode steps")
        for key in ("exec_step_ms", "sched_self_ms"):
            if h[key]:
                parts.append(f"{key} median "
                             f"{statistics.median(h[key]):.3f} "
                             f"n {len(h[key])}")
    if idle is not None:
        parts.append(
            f"idle_s {idle['idle_s']:.4f} of window {idle['window_s']:.4f}"
            f" decode_iterations {idle['decode_iterations']} "
            + " ".join(f"{k} {idle[k]:.4f}" for k in (
                "upload_s", "readback_s", "sched_s", "prefill_s",
                "none_s")))
        parts.append("by leaf span " + " ".join(
            f"{k} {v:.4f}" for k, v in sorted(
                idle["by_leaf"].items(), key=lambda kv: -kv[1])))
    print("info program_spans " + "; ".join(parts), file=sys.stderr)
