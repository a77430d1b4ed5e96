"""chipbench/program_spans.py and the six readers over it, on planted
spans and a planted device trace (CPU; nothing here is a measurement)."""
import json
import os
import sys
import time

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import harness, program_spans, xplane  # noqa: E402
from chipbench import manifest as mf  # noqa: E402
from horovod_tpu.trace import SpanRecorder  # noqa: E402

MANIFEST = mf.load()
CELL = "gpt2-xl.serve-closed8"
HOST = ["serve.ttft_p50_ms", "serve.token_gap_p99_ms",
        "serve.batch_occupancy"]
IDLE = ["serve.idle_upload_ms", "serve.idle_readback_ms",
        "serve.idle_sched_ms"]
NEW = HOST + IDLE

# the harness's clock (perf_counter) at the traced slice's ends, and the
# same two instants on the profile's clock
P_START, P_STOP = 50.0, 54.0
X_LO = 100.0


class ShiftedRecorder(SpanRecorder):
    """A recorder whose clock runs `shift` seconds ahead of the
    harness's; spans are planted with `plant` at HARNESS times."""

    def __init__(self, shift=0.0, capacity=4096):
        super().__init__(capacity, ring=capacity)
        self.shift = shift
        self._n = 0

    def now(self):
        return time.perf_counter() + self.shift

    def plant(self, name, t0, t1, parent=None, **attrs):
        self._n += 1
        return self.record_local(
            name, t0 + self.shift, t1 + self.shift, span=f"s{self._n}",
            parent=parent.span if parent is not None else None, **attrs)


def _iteration(rec, t, kind, gaps):
    """One scheduler iteration of 0.5 s starting at harness time `t`
    with one executor step inside; appends the planted idle gaps (on the
    profile's clock) to `gaps` and returns the idle seconds by bucket."""
    x = t - P_START + X_LO
    phase = "sched_prefill" if kind == "prefill" else "sched_decode"
    # children are planted before their parents end, as the ring has it
    it_id = f"it{t}"
    rec.record_local("sched_retire", t + rec.shift, t + 0.01 + rec.shift,
                     span=f"r{t}", parent=it_id)
    rec.record_local("sched_admit", t + 0.01 + rec.shift,
                     t + 0.02 + rec.shift, span=f"a{t}", parent=it_id)
    for name, a, b in (("exec_upload", 0.03, 0.05),
                       ("exec_dispatch", 0.05, 0.06),
                       ("exec_readback", 0.06, 0.48)):
        rec.record_local(name, t + a + rec.shift, t + b + rec.shift,
                         span=f"{name}{t}", parent=f"x{t}")
    rec.record_local("exec_step", t + 0.03 + rec.shift,
                     t + 0.48 + rec.shift, span=f"x{t}", parent=f"p{t}",
                     kind=kind, rows=6)
    rec.record_local(phase, t + 0.02 + rec.shift, t + 0.49 + rec.shift,
                     span=f"p{t}", parent=it_id)
    rec.record_local("sched_retire", t + 0.49 + rec.shift,
                     t + 0.5 + rec.shift, span=f"q{t}", parent=it_id)
    rec.record_local("sched_iteration", t + rec.shift, t + 0.5 + rec.shift,
                     span=it_id)
    gaps += [(x + 0.031, x + 0.049),      # 18 ms under exec_upload
             (x + 0.052, x + 0.058),      # 6 ms under exec_dispatch
             (x + 0.40, x + 0.47),        # 70 ms under exec_readback
             (x + 0.4905, x + 0.4995),    # 9 ms under sched_retire
             (x + 0.021, x + 0.029)]      # 8 ms under the phase, before
    #                                       the executor step opens
    if kind == "prefill":
        return {"prefill_s": 0.094, "sched_s": 0.017}
    return {"upload_s": 0.024, "readback_s": 0.070, "sched_s": 0.017}


def _planted(shift=0.0, window_len=P_STOP - P_START, with_window=True,
             capacity=4096):
    """A run whose slice holds 6 decode iterations and 1 prefill."""
    cell = mf.Cell(MANIFEST, CELL)
    run = harness.Run(cell, 0, 45.0, True, False)
    run.tracer.t_start, run.tracer.t_stop = P_START, P_STOP
    run.window_open, run.window_s = P_START - 2.0, 45.0
    rec = ShiftedRecorder(shift, capacity)
    gaps, want = [], {}
    for i, kind in enumerate(["decode", "decode", "prefill", "decode",
                              "decode", "decode", "decode"]):
        for k, v in _iteration(rec, P_START + 0.2 + 0.5 * i, kind,
                               gaps).items():
            want[k] = want.get(k, 0.0) + v
    gaps.append((X_LO + 0.02, X_LO + 0.09))      # before any program span
    want["none_s"] = 0.07
    lo, hi = X_LO, X_LO + window_len
    ops = xplane.subtract([(lo - 1.0, hi + 1.0)], sorted(gaps))
    spans = [(xplane.WINDOW_SPAN, lo, hi)] if with_window else []
    run.trace = xplane.Trace(
        ops={0: [("%fusion.1 = f32[8]{0} fusion()", a, b) for a, b in ops]},
        host_spans=spans)
    return run, rec, want


@pytest.mark.parametrize("shift", [0.0, 1234.5, -77.25])
def test_idle_metrics_read_the_planted_milliseconds(shift):
    """Whatever the recorder's clock reads against the harness's, the
    offset undoes it: every planted gap lands under its span."""
    run, rec, want = _planted(shift)
    a = program_spans.analyse(run, recorder=rec)
    assert a.host is None                   # no request in the ring
    idle = a.idle
    assert idle["decode_iterations"] == 6
    for key, seconds in want.items():
        assert idle[key] == pytest.approx(seconds, abs=2e-6), key
    total = sum(want.values())
    assert idle["idle_s"] == pytest.approx(total, abs=1e-6)
    assert sum(idle[k] for k in ("upload_s", "readback_s", "sched_s",
                                 "prefill_s", "none_s")) == \
        pytest.approx(idle["idle_s"], abs=1e-9)
    share = xplane.idle_share(run.trace)
    assert share * idle["window_s"] == pytest.approx(total, abs=1e-6)
    # and through the readers, per decode iteration
    cell = run.cell
    got = {m: cell.reader(m).read(run) for m in IDLE}
    assert got["serve.idle_upload_ms"] == pytest.approx(24.0, abs=2e-3)
    assert got["serve.idle_readback_ms"] == pytest.approx(70.0, abs=2e-3)
    assert got["serve.idle_sched_ms"] == pytest.approx(
        1e3 * want["sched_s"] / 6, abs=2e-3)
    assert 6 * sum(got.values()) / 1e3 + idle["prefill_s"] \
        + idle["none_s"] == pytest.approx(total, abs=1e-5)
    assert idle["by_leaf"]["exec_readback"] == pytest.approx(6 * 0.07,
                                                             abs=1e-6)
    assert idle["by_leaf"]["prefill/exec_readback"] == pytest.approx(
        0.07, abs=1e-6)


def test_a_gap_across_spans_is_split_by_overlap():
    """Between two decode programs the device sees ONE gap: it opens
    under the readback of one step and closes after the dispatch of the
    next. Each span gets the part of it that it was open for."""
    cell = mf.Cell(MANIFEST, CELL)
    run = harness.Run(cell, 0, 45.0, True, False)
    run.tracer.t_start, run.tracer.t_stop = P_START, P_STOP
    rec = ShiftedRecorder(3.5)
    gaps = []
    for i in range(4):
        _iteration(rec, P_START + 0.2 + 0.5 * i, "decode", [])
        x = X_LO + 0.2 + 0.5 * i
        # device done 5 ms before the readback returns (x + 0.48); idle
        # through retire (x + 0.5), then 20 ms of the NEXT iteration's
        # retire + admit, 10 ms of its phase, 20 ms of upload and 4 ms
        # of dispatch
        gaps.append((x + 0.475, x + 0.5 + 0.054))
    lo, hi = X_LO, X_LO + 4.0
    run.trace = xplane.Trace(
        ops={0: [("%fusion.1 = f32[8]{0} fusion()", a, b) for a, b in
                 xplane.subtract([(lo, hi)], gaps)]},
        host_spans=[(xplane.WINDOW_SPAN, lo, hi)])
    idle = program_spans.analyse(run, recorder=rec).idle
    # (the two clocks are read a microsecond apart: hence the 20 us)
    # the last gap's tail lies past the fourth iteration: under no span
    assert idle["readback_s"] == pytest.approx(4 * 0.005, abs=2e-5)
    assert idle["upload_s"] == pytest.approx(3 * 0.024, abs=2e-5)
    assert idle["sched_s"] == pytest.approx(4 * 0.02 + 3 * 0.03, abs=2e-5)
    assert idle["none_s"] == pytest.approx(0.054, abs=2e-5)
    assert idle["by_leaf"]["exec_dispatch"] == pytest.approx(3 * 0.004,
                                                             abs=2e-5)
    assert idle["idle_s"] == pytest.approx(4 * 0.079, abs=2e-5)


def test_only_one_scheduler_threads_spans_are_split():
    """Spans nest within one thread's stack only. Another thread's span
    (no `sched_iteration` over it) is left out; a second scheduler
    (iterations that overlap) leaves nothing to read."""
    run, rec, want = _planted()
    t = P_START + 0.2
    rec.plant("exec_readback", t + 0.30, t + 0.52)    # a stray thread's
    idle = program_spans.analyse(run, recorder=rec).idle
    for key, seconds in want.items():
        assert idle[key] == pytest.approx(seconds, abs=2e-6), key
    run, rec, _ = _planted()
    rec.plant("sched_iteration", t + 0.25, t + 0.75)
    assert program_spans.analyse(run, recorder=rec) is None


@pytest.mark.parametrize("why, kwargs", [
    ("anchors_5ms_apart", {"window_len": 4.005}),
    ("no_window_span", {"with_window": False}),
    ("ring_wrapped_past_the_slice", {"capacity": 40}),
])
def test_idle_side_refuses(why, kwargs):
    run, rec, _ = _planted(**kwargs)
    if why == "ring_wrapped_past_the_slice":
        assert rec.evicted > 0
    assert program_spans.analyse(run, recorder=rec) is None
    for m in NEW:
        assert run.cell.reader(m).read(run) is None


def test_no_program_span_in_the_slice_is_nothing_to_read():
    run, _, _ = _planted()
    assert program_spans.analyse(run, recorder=ShiftedRecorder()) is None


def test_a_program_without_the_ring_is_nothing_to_read(monkeypatch):
    """The parent commit's recorder has no `between`: every new reader
    returns None and raises nothing."""
    import horovod_tpu.trace as trace_pkg

    class Old:
        def now(self):
            return time.time()
    monkeypatch.setattr(trace_pkg, "get_recorder", lambda: Old())
    run, _, _ = _planted()
    for m in NEW:
        assert run.cell.reader(m).read(run) is None


def test_two_anchors_2ms_apart_are_accepted():
    run, rec, _ = _planted(window_len=4.0015)
    assert program_spans.analyse(run, recorder=rec).idle is not None


def test_leaves_pick_the_innermost_span():
    rec = ShiftedRecorder()
    outer = rec.plant("sched_iteration", 0.0, 10.0)
    mid = rec.plant("sched_decode", 2.0, 8.0, parent=outer)
    inner = rec.plant("exec_step", 3.0, 5.0, parent=mid)
    late = rec.plant("sched_iteration", 12.0, 13.0)
    got = [(a, b, s.name) for a, b, s in program_spans._leaves(
        [late, inner, outer, mid])]
    assert got == [(0.0, 2.0, "sched_iteration"), (2.0, 3.0, "sched_decode"),
                   (3.0, 5.0, "exec_step"), (5.0, 8.0, "sched_decode"),
                   (8.0, 10.0, "sched_iteration"),
                   (12.0, 13.0, "sched_iteration")]


def test_host_side_takes_the_undisturbed_requests():
    """Requests submitted after the first iteration that began after
    the profiler's stop, resolved before the window's close; their
    token gaps pooled; decode occupancy over the same stretch."""
    run, rec, _ = _planted()
    run.seconds, run.window_s = 45.0, 45.0
    t_open = run.window_open
    quiet = P_STOP + 3.0            # the stop stalled the loop for 3 s
    rec.plant("sched_iteration", quiet, quiet + 0.03)
    rec.plant("sched_iteration", quiet + 0.03, quiet + 0.06)

    def request(rid, t0, stamps, status="ok"):
        ids = {"trace": f"rid{rid}", "parent": f"rid{rid}"}
        rec.record_local("queue_wait", t0 + rec.shift,
                         t0 + 0.002 + rec.shift, **ids)
        rec.record_local("prefill", t0 + 0.002 + rec.shift,
                         stamps[0] + rec.shift, rid=rid, **ids)
        rec.record_local("decode", stamps[0] + rec.shift,
                         stamps[-1] + rec.shift, trace=f"rid{rid}",
                         parent=f"rid{rid}", rid=rid, tokens=len(stamps),
                         token_times=[s + rec.shift for s in stamps])
        rec.record_local("request", t0 + rec.shift,
                         stamps[-1] + 0.001 + rec.shift, trace=f"rid{rid}",
                         span=f"rid{rid}", rid=rid, status=status)
    request(1, quiet - 0.5, [quiet + 1.0, quiet + 1.03])    # too early
    request(2, quiet + 0.1, [quiet + 0.2, quiet + 0.23, quiet + 0.43])
    request(3, quiet + 0.2, [quiet + 0.5, quiet + 0.53])
    request(4, quiet + 0.3, [quiet + 0.6], status="expired")
    request(5, t_open + 44.9, [t_open + 44.95, t_open + 45.2])  # too late
    for i, rows in enumerate([8, 8, 4, 8]):
        rec.plant("exec_step", quiet + 1 + i, quiet + 1.03 + i,
                  kind="decode", rows=rows)
    rec.plant("exec_step", quiet + 6, quiet + 6.1, kind="prefill", rows=1)
    a = program_spans.analyse(run, recorder=rec)
    h = a.host
    assert h["requests"] == 2
    assert h["ttft_ms"] == pytest.approx([100.0, 300.0], abs=1e-6)
    assert h["queue_wait_ms"] == pytest.approx([2.0, 2.0], abs=1e-6)
    assert sorted(h["token_gaps_ms"]) == pytest.approx(
        [30.0, 30.0, 200.0], abs=1e-6)
    cell = run.cell
    assert cell.reader("serve.ttft_p50_ms").read(run) == \
        pytest.approx(200.0, abs=1e-6)
    # queue wait is read (the info line prints it) but is no metric
    assert not any(m["name"].startswith("serve.queue_wait")
                   for m in MANIFEST["per_layer"])
    assert cell.reader("serve.token_gap_p99_ms").read(run) == \
        pytest.approx(np.percentile([30.0, 30.0, 200.0], 99), abs=1e-6)
    assert cell.reader("serve.batch_occupancy").read(run) == \
        pytest.approx(100.0 * (8 + 8 + 4 + 8) / (4 * 8), abs=1e-9)
    # the window's decode steps for the cross-check: the slice's six
    # and the four after it
    assert len(h["exec_step_ms"]) == 10


@pytest.mark.parametrize("metric", NEW)
def test_manifest_entry(metric):
    m = next(x for x in MANIFEST["per_layer"] if x["name"] == metric)
    assert m["workloads"] == [CELL] and m["moves"] == "serve_tokens_per_s"
    assert m["source"] in ("program_span", "program_counter")
    assert m["layer"] in ("serve_scheduler", "serve_executor")
    assert os.path.isfile(os.path.join(ROOT, mf.layer_metric_file(metric)))


def test_rehearsal_prints_the_host_side_and_leaves_the_idle_out(capsys):
    """`python -m chipbench --workload gpt2-xl.serve-closed8 --rehearse
    --trace 1`: the CPU profile has no device plane, so the three
    host-side metrics appear (as ``rehearsal.*``) and the idle ones do
    not; inside and outside agree on the requests they count."""
    import horovod_tpu as hvd
    try:
        rc = harness.main(["--workload", CELL, "--seed", str(2 ** 31 + 9),
                           "--seconds", "12", "--trace", "1", "--rehearse"])
    finally:
        hvd.shutdown()
    out, err = capsys.readouterr()
    assert rc == 0, err[-2000:]
    result = json.loads(out.strip().splitlines()[-1])
    assert result["correct"] is True, result["compared"]
    got = result["metrics"]
    for m in HOST:
        assert f"rehearsal.{m}" in got, (m, err[-3000:])
    for m in IDLE:
        assert f"rehearsal.{m}" not in got
    assert 0.0 < got["rehearsal.serve.batch_occupancy"]["value"] <= 100.0
    assert 0.0 < got["rehearsal.serve.ttft_p50_ms"]["value"] < \
        got["rehearsal.serve.request_p90_ms"]["value"]
    line = next(ln for ln in err.splitlines()
                if ln.startswith("info program_spans "))
    inside = int(line.split("requests ")[1].split(";")[0])
    outside = int(next(ln for ln in err.splitlines() if ln.startswith(
        "info latency of ")).split()[3])
    assert abs(inside - outside) <= 2
