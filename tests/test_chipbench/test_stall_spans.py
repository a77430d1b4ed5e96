"""chipbench/stall_spans.py and the four readers over it, on planted
spans and a planted device trace (CPU; nothing here is a measurement)."""
import json
import os
import sys
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import harness, stall_spans, xplane  # noqa: E402
from chipbench import manifest as mf  # noqa: E402
from horovod_tpu.trace import SpanRecorder  # noqa: E402

MANIFEST = mf.load()
SERVE = "gpt2-xl.serve-closed8"
TRAIN = "gpt2-medium.train-dp1"
SETUP = ["setup.import_init_s", "setup.trace_lower_s",
         "setup.compile_fetch_s"]
NEW = SETUP + ["serve.idle_stall_ms"]

# the harness's clock (perf_counter) at the traced slice's ends, and the
# first of them on the profile's clock
P_START, P_STOP = 50.0, 54.0
X_LO = 100.0


class ShiftedRecorder(SpanRecorder):
    """A recorder whose clock runs `shift` seconds ahead of the
    harness's; spans are planted at HARNESS times."""

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


def _setup(rec):
    """Set-up as a process records it, all before the slice opens:
    import 2 s, init 0.5 s; an outer trace of 2 s with a nested one,
    1 s of lowering, a fetched program (0.5 s) and a compiled one (2 s).
    The outer trace and its lowering are under an ``exec_step``."""
    rec.plant("hvd_import", 1.0, 3.0)
    rec.plant("hvd_init", 3.0, 3.5)
    rec.plant("jit_trace", 4.5, 5.0, fun="inner")       # nested: once
    step = rec.plant("exec_step", 3.8, 12.0, kind="prefill", rows=1)
    rec.plant("jit_trace", 4.0, 6.0, parent=step, fun="step")
    rec.plant("jit_lower", 6.0, 7.0, parent=step, fun="jit(step)")
    rec.plant("jit_compile", 7.0, 7.5, parent=step, fun="jit(step)",
              fetched=True)
    rec.plant("jit_compile", 8.0, 10.0, fun="jit(init)", fetched=False)
    # ends after the slice opened: not set-up
    rec.plant("jit_trace", P_START - 0.1, P_START + 0.1, fun="late")
    return {"import_init_s": 2.5, "trace_lower_s": 3.0,
            "compile_fetch_s": 2.5}


def _run(cell=SERVE, shift=0.0, window_len=P_STOP - P_START,
         with_window=True, capacity=4096, stalls=(), setup=True):
    """A traced run whose slice [P_START, P_STOP] holds the planted
    `stalls` ((name, t0, t1, attrs) at harness times) and one idle gap
    of 0.3 s at P_START + 1.0 (profile clock X_LO + 1.0)."""
    run = harness.Run(mf.Cell(MANIFEST, cell), 0, 45.0, True, False)
    run.tracer.t_start, run.tracer.t_stop = P_START, P_STOP
    rec = ShiftedRecorder(shift, capacity)
    want = _setup(rec) if setup else None
    it = rec.plant("sched_iteration", P_START + 0.5, P_START + 2.0)
    step = rec.plant("exec_step", P_START + 0.6, P_START + 1.9,
                     parent=it, kind="decode", rows=8)
    disp = rec.plant("exec_dispatch", P_START + 0.7, P_START + 1.8,
                     parent=step)
    for name, a, b, attrs in stalls:
        rec.plant(name, a, b, parent=disp, **attrs)
    lo, hi = X_LO, X_LO + window_len
    gaps = [(X_LO + 1.0, X_LO + 1.3)]
    run.trace = xplane.Trace(
        ops={0: [("%fusion.1 = f32[8]{0} fusion()", a, b) for a, b in
                 xplane.subtract([(lo - 1.0, hi + 1.0)], gaps)]},
        host_spans=[(xplane.WINDOW_SPAN, lo, hi)] if with_window else [])
    return run, rec, want


def _read(run, rec, metric):
    stall_spans.analyse(run, recorder=rec)
    return run.cell.reader(metric).read(run)


@pytest.mark.parametrize("shift", [0.0, 4321.5, -77.25])
def test_setup_reads_the_union_before_the_slice(shift):
    """Whatever the recorder's clock reads against the harness's: a
    trace nested in another counts once, and a span that ended after
    the slice opened is not set-up."""
    run, rec, want = _run(shift=shift)
    a = stall_spans.analyse(run, recorder=rec)
    for key, seconds in want.items():
        assert a.setup[key] == pytest.approx(seconds, abs=1e-9), key
    assert a.setup["fetched"] == (1, pytest.approx(0.5))
    assert a.setup["compiled"] == (1, pytest.approx(2.0))
    assert a.setup["costliest"][0] == ("step", pytest.approx(2.0))
    for m in SETUP:
        assert run.cell.reader(m).read(run) == pytest.approx(
            want[m.split(".")[1]], abs=1e-9)


def test_the_slice_boundary_decides_what_is_set_up():
    """A compile that ends a millisecond before the slice opens is
    set-up; one that ends a millisecond after it is not."""
    run, rec, want = _run()
    rec.plant("jit_compile", P_START - 1.0, P_START - 0.001, fun="edge")
    rec.plant("jit_compile", P_START - 1.0, P_START + 0.001, fun="after")
    a = stall_spans.analyse(run, recorder=rec)
    assert a.setup["compile_fetch_s"] == pytest.approx(
        want["compile_fetch_s"] + 0.999, abs=1e-6)


def test_idle_under_a_stall_is_its_overlap_with_the_gap():
    """The slice's one gap is 0.3 s (harness P_START + 1.0 .. + 1.3). A
    retrace over its first 0.1 s, a collection over the last 0.05 s and
    a compile that overlaps the retrace: 0.15 s under a stall."""
    run, rec, _ = _run(shift=12.5, stalls=[
        ("jit_trace", P_START + 0.95, P_START + 1.1, {"fun": "step"}),
        ("jit_compile", P_START + 1.05, P_START + 1.08,
         {"fun": "jit(step)", "fetched": False}),
        ("gc_pause", P_START + 1.25, P_START + 1.35,
         {"generation": 2, "collected": 10})])
    assert _read(run, rec, "serve.idle_stall_ms") == pytest.approx(
        150.0, abs=0.05)
    sl = stall_spans.analyse(run).slice
    # the set-up's late trace overlaps the slice's start
    assert sl["counts"] == {"jit_trace": 2, "jit_compile": 1, "gc_pause": 1}
    assert sl["idle_s"] == pytest.approx(0.3, abs=1e-9)
    listed = {x["name"]: x for x in sl["listed"] if x["fun"] != "late"}
    assert listed["jit_trace"]["under"] == "exec_dispatch"
    assert listed["jit_trace"]["kind"] == "decode"
    assert listed["jit_trace"]["idle_s"] == pytest.approx(0.1, abs=5e-5)
    assert listed["gc_pause"]["idle_s"] == pytest.approx(0.05, abs=5e-5)
    assert sl["jit_funs"] == ["jit(step)", "late", "step"]


def test_a_stall_where_the_device_is_busy_costs_no_idle_time():
    run, rec, _ = _run(stalls=[
        ("gc_pause", P_START + 2.0, P_START + 2.5,
         {"generation": 2, "collected": 0})])
    assert _read(run, rec, "serve.idle_stall_ms") == pytest.approx(
        0.0, abs=0.05)


def test_an_anchored_slice_without_a_stall_reads_zero_not_none():
    run, rec, _ = _run()
    assert _read(run, rec, "serve.idle_stall_ms") == 0.0


@pytest.mark.parametrize("why, kwargs", [
    ("anchors_5ms_apart", {"window_len": 4.005}),
    ("no_window_span", {"with_window": False}),
])
def test_an_unanchored_slice_reads_none_and_set_up_still_reads(why, kwargs):
    run, rec, want = _run(**kwargs)
    assert _read(run, rec, "serve.idle_stall_ms") is None
    assert run.cell.reader("setup.trace_lower_s").read(run) == \
        pytest.approx(want["trace_lower_s"])


def test_a_ring_that_evicted_anything_has_lost_set_up():
    run, rec, _ = _run(capacity=8)
    assert rec.evicted > 0
    for m in SETUP:
        assert _read(run, rec, m) is None
    assert _read(run, rec, "serve.idle_stall_ms") == 0.0    # slice held


def test_a_ring_without_the_import_span_reads_no_set_up():
    run, rec, _ = _run(setup=False)
    for m in SETUP:
        assert _read(run, rec, m) is None


def test_a_program_without_the_stall_spans_reads_nothing(monkeypatch):
    """The parent commit's registry lacks the new spans: every new
    reader returns None and raises nothing, however the ring looks."""
    from horovod_tpu.trace import spans
    legs = {k: v for k, v in spans.SPAN_LEGS.items()
            if not k.startswith(("jit_", "gc_", "hvd_"))}
    monkeypatch.setattr(spans, "SPAN_LEGS", legs)
    run, rec, _ = _run()
    for m in NEW:
        assert _read(run, rec, m) is None


def test_a_program_without_the_ring_reads_nothing(monkeypatch):
    import horovod_tpu.trace as trace_pkg

    class Old:
        def now(self):
            return time.time()
    monkeypatch.setattr(trace_pkg, "get_recorder", lambda: Old())
    run, _, _ = _run()
    for m in NEW:
        assert run.cell.reader(m).read(run) is None


def test_a_train_run_reads_set_up_from_the_same_spans():
    run, rec, want = _run(cell=TRAIN)
    assert _read(run, rec, "setup.compile_fetch_s") == pytest.approx(
        want["compile_fetch_s"])


def test_the_info_line_names_each_long_stall(capsys):
    run, rec, _ = _run(stalls=[
        ("jit_trace", P_START + 0.95, P_START + 1.1, {"fun": "step"}),
        ("gc_pause", P_START + 1.5, P_START + 1.505,
         {"generation": 0, "collected": 1})])
    stall_spans.analyse(run, recorder=rec)
    line = capsys.readouterr().err.strip()
    assert line.startswith("info stall_spans set-up ")
    assert "stall jit_trace fun step under exec_dispatch kind decode " \
        "150.0 ms idle 100.0 ms" in line
    assert "stall gc_pause" not in line          # 5 ms: counted, not named
    assert "spans gc_pause 1 jit_trace 2" in line
    assert "compiled 1 in 2.000 s, fetched 1 in 0.500 s" in line


@pytest.mark.parametrize("metric", NEW)
def test_manifest_entry(metric):
    m = next(x for x in MANIFEST["per_layer"] if x["name"] == metric)
    assert m["source"] == "program_span" and m["layer"] == "host_runtime"
    assert m["better"] == "lower"
    moved = next(e for e in MANIFEST["end_to_end"]
                 if e["name"] == m["moves"])
    # every cell but the Phi-4 one, whose own test holds its metrics
    # to the set it was added with (tests/test_chipbench/test_sambay.py)
    every = [w["name"] for w in MANIFEST["workloads"]]
    held = "phi4-mini-flash.serve-reason16"
    if metric.startswith("setup."):
        assert (m["moves"], m["unit"]) == ("setup_s", "s")
        assert m["workloads"] == [c for c in every if c != held]
    else:
        assert (m["moves"], m["unit"]) == ("serve_tokens_per_s", "ms")
        assert m["workloads"] == [c for c in moved["workloads"]
                                  if c != held]
    assert os.path.isfile(os.path.join(ROOT, mf.layer_metric_file(metric)))


def test_the_manifest_accepts_the_four_entries_and_refuses_a_bad_one():
    assert mf.validate(MANIFEST) == []
    bad = json.loads(json.dumps(MANIFEST))
    entry = next(x for x in bad["per_layer"]
                 if x["name"] == "serve.idle_stall_ms")
    entry["workloads"] = entry["workloads"] + [TRAIN]
    assert any("does not report serve_tokens_per_s" in e
               for e in mf.validate(bad))
