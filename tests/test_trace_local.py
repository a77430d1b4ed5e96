"""The span recorder's local, always-on half (docs/tracing.md, "The
local flight recorder"): nesting, the bounded ring, the clock, and the
one place the wall clock enters. The fleet half is tests/test_trace.py;
the serve stack's own spans are tests/test_serve_paged.py."""
import os
import subprocess
import sys
import textwrap
import threading
import time

import pytest

from horovod_tpu.trace import (SPAN_LEGS, SpanRecorder, TraceContext,
                               get_recorder)
from horovod_tpu.trace import spans as spans_mod
from horovod_tpu.trace.spans import RING_FACTOR, Span, to_wall, wall_base

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _names(spans):
    return [s.name for s in spans]


class TestThreadSpans:
    def test_nesting_gives_parent_ids(self):
        rec = SpanRecorder(64)
        with rec.span("sched_iteration", n=7) as it:
            with rec.span("sched_admit") as admit:
                pass
            with rec.span("sched_decode") as dec:
                with rec.span("exec_step", kind="decode") as step:
                    step.set(rows=3)
        got = {s.name: s for s in rec.between(0.0, float("inf"))}
        assert got["sched_iteration"].parent is None
        assert got["sched_admit"].parent == it.id
        assert got["sched_decode"].parent == it.id
        assert got["exec_step"].parent == dec.id
        assert got["exec_step"].extra == {"kind": "decode", "rows": 3}
        assert got["sched_iteration"].extra == {"n": 7}
        assert len({it.id, admit.id, dec.id, step.id}) == 4
        # spans enter the ring as they END: children before parents
        assert _names(rec.between(0.0, float("inf"))) == [
            "sched_admit", "exec_step", "sched_decode", "sched_iteration"]

    def test_a_span_that_raises_is_still_recorded_and_popped(self):
        rec = SpanRecorder(8)
        with pytest.raises(KeyError):
            with rec.span("sched_iteration"):
                with rec.span("sched_admit"):
                    raise KeyError("boom")
        with rec.span("sched_retire"):
            pass
        got = {s.name: s for s in rec.between(0.0, float("inf"))}
        assert set(got) == {"sched_iteration", "sched_admit",
                            "sched_retire"}
        assert got["sched_retire"].parent is None     # the stack emptied

    def test_each_thread_has_its_own_stack(self):
        rec = SpanRecorder(64)
        inner_parent = []

        def other():
            with rec.span("exec_step") as sp:
                inner_parent.append(sp.parent)

        with rec.span("sched_iteration"):
            t = threading.Thread(target=other)
            t.start()
            t.join(timeout=10)
            assert not t.is_alive()
        assert inner_parent == [None]

    def test_stamps_are_the_monotonic_clock(self):
        rec = SpanRecorder(8)
        before = time.monotonic()
        assert before <= rec.now() <= time.monotonic()
        with rec.span("exec_upload"):
            pass
        (sp,) = rec.between(0.0, float("inf"))
        assert before <= sp.t0 <= sp.t1 <= time.monotonic()
        assert to_wall(sp.t0) == pytest.approx(sp.t0 + wall_base(),
                                               abs=0.05)

    def test_open_span_is_a_profiler_annotation(self, monkeypatch):
        seen = []

        class Annotation:
            def __init__(self, name):
                self.name = name

            def __enter__(self):
                seen.append(("enter", self.name))

            def __exit__(self, *exc):
                seen.append(("exit", self.name))

        monkeypatch.setattr(spans_mod, "_annotation", lambda: Annotation)
        rec = SpanRecorder(8)
        with rec.span("exec_dispatch"):
            assert seen == [("enter", "hvd/exec_dispatch")]
        assert seen[-1] == ("exit", "hvd/exec_dispatch")

    def test_the_real_annotation_is_jax_profilers(self):
        import jax
        assert spans_mod._annotation() is jax.profiler.TraceAnnotation

    def test_every_local_name_is_declared(self):
        for name in ("sched_iteration", "sched_retire", "sched_admit",
                     "sched_prefill", "sched_decode", "exec_step",
                     "exec_upload", "exec_dispatch", "exec_readback"):
            assert SPAN_LEGS[name] is None


class TestRing:
    def test_bounded_and_reports_a_wrap(self):
        rec = SpanRecorder(4, ring=4)
        assert rec.oldest() is None and rec.evicted == 0
        for i in range(4):
            rec.record_local("exec_step", float(i), i + 0.5, n=i)
        assert rec.evicted == 0 and rec.oldest() == 0.5
        for i in range(4, 7):
            rec.record_local("exec_step", float(i), i + 0.5, n=i)
        held = rec.between(0.0, float("inf"))
        assert [s.extra["n"] for s in held] == [3, 4, 5, 6]
        assert rec.evicted == 3
        # a reader asking for [1, 5] can tell it lost spans: something
        # was evicted and the oldest survivor ended after its start
        assert rec.oldest() == 3.5 > 1.0

    def test_between_overlaps_and_does_not_drain(self):
        rec = SpanRecorder(16)
        rec.record_local("queue_wait", 1.0, 2.0)
        rec.record_local("prefill", 2.0, 3.0)
        rec.record_local("decode", 3.0, 9.0)
        assert _names(rec.between(2.5, 2.6)) == ["prefill"]
        assert _names(rec.between(2.0, 3.0)) == ["queue_wait", "prefill",
                                                 "decode"]
        assert _names(rec.between(10.0, 11.0)) == []
        assert len(rec.between(0.0, 100.0)) == 3      # still all there
        assert rec.pending() == 0                     # nothing to ship

    def test_local_ids_and_explicit_identity(self):
        rec = SpanRecorder(16)
        a = rec.record_local("request", 1.0, 2.0, trace="rid4",
                             span="rid4", rid=4)
        b = rec.record_local("decode", 1.5, 2.0, trace="rid4",
                             parent="rid4")
        assert (a.trace, a.span, a.parent) == ("rid4", "rid4", None)
        assert (b.trace, b.parent) == ("rid4", "rid4")
        assert b.span and b.span != a.span

    def test_default_ring_holds_three_windows_of_the_serve_cell(self):
        from horovod_tpu.core.config import Config
        # the knob still bounds what is held for a router, as it did
        assert Config().trace_ring == 4096
        rec = SpanRecorder(Config().trace_ring)
        assert rec.capacity == SpanRecorder().capacity == 4096
        # one 45 s window of gpt2-xl.serve-closed8 writes about 10,000
        # spans (PERF.md section 6)
        assert rec.ring == RING_FACTOR * 4096 >= 3 * 10_000

    def test_the_ring_and_the_by_trace_bound_are_separate(self):
        rec = SpanRecorder(2, ring=16)
        for i in range(5):
            rec.record_local("decode", float(i), i + 0.5,
                             ship=TraceContext.mint())
        assert rec.pending() == 2 and rec.dropped == 3
        assert len(rec.between(0.0, 9.0)) == 5 and rec.evicted == 0


class TestWallClockEntersOnce:
    def test_a_shipped_local_span_goes_onto_the_wall_clock(self):
        rec = SpanRecorder(16, pool="decode", replica=2)
        ctx = TraceContext.mint()
        t0 = rec.now()
        sp = rec.record_local("decode", t0, t0 + 0.25, ship=ctx, rid=1,
                              tokens=3, token_times=[t0, t0 + 0.1])
        # in the ring: monotonic, with the per-token stamps
        (held,) = rec.between(0.0, float("inf"))
        assert held is sp and held.t0 == t0
        assert held.extra["token_times"] == [t0, t0 + 0.1]
        assert (held.trace, held.parent) == (ctx.trace_id, ctx.span_id)
        # on the wire: wall clock, same length, no per-token stamps
        (wire,) = rec.drain(ctx.trace_id)
        assert wire["t0"] == pytest.approx(to_wall(t0), abs=0.05)
        assert wire["t1"] - wire["t0"] == pytest.approx(0.25, abs=1e-6)
        assert wire["extra"] == {"rid": 1, "tokens": 3}
        assert (wire["pool"], wire["replica"]) == ("decode", 2)
        # drained from the by-trace side only: the ring keeps it
        assert rec.drain(ctx.trace_id) == []
        assert len(rec.between(0.0, float("inf"))) == 1

    def test_one_base_per_drain(self, monkeypatch):
        bases = iter([1000.0, 2000.0])
        monkeypatch.setattr(spans_mod, "wall_base", lambda: next(bases))
        rec = SpanRecorder(16)
        ctx = TraceContext.mint()
        rec.record_local("queue_wait", 1.0, 2.0, ship=ctx)
        rec.record_local("prefill", 2.0, 3.0, ship=ctx.to_wire())
        rec.record_process("weight_fence", 2.5, 2.75, version=3)
        wire = rec.drain(ctx.trace_id)
        assert [(s["name"], s["t0"], s["t1"]) for s in wire] == [
            ("queue_wait", 1001.0, 1002.0), ("prefill", 1002.0, 1003.0),
            ("weight_fence", 1002.5, 1002.75)]

    def test_one_recording_path_and_a_router_span_passes_through(self):
        """The recorder takes monotonic stamps only; a span the router
        makes itself is on the wall clock already and goes to the wire
        as it is (no base)."""
        assert not hasattr(SpanRecorder, "record")
        wire = Span("t", "s", None, "dispatch", 5.0, 6.0).to_wire()
        assert (wire["t0"], wire["t1"]) == (5.0, 6.0)
        back = Span.from_wire(wire)
        assert (back.t0, back.t1, back.name) == (5.0, 6.0, "dispatch")

    def test_thread_spans_never_ship(self):
        rec = SpanRecorder(16)
        ctx = TraceContext.mint()
        with rec.span("sched_iteration"):
            with rec.span("exec_step"):
                rec.record_local("prefill", 1.0, 2.0, ship=ctx)
        assert [s["name"] for s in rec.drain(ctx.trace_id)] == ["prefill"]
        assert rec.drain(None) == []

    def test_a_garbage_context_records_locally_only(self):
        rec = SpanRecorder(16)
        sp = rec.record_local("prefill", 1.0, 2.0, trace="rid9",
                              ship={"bogus": 1})
        assert sp.trace == "rid9" and rec.pending() == 0
        assert _names(rec.between(0.0, 9.0)) == ["prefill"]

    def test_the_expression_lives_in_spans_py_only(self):
        hits = subprocess.run(
            ["grep", "-rln", "--include=*.py",
             "time.time() - time.monotonic()",
             os.path.join(_REPO, "horovod_tpu")],
            capture_output=True, text=True, timeout=60).stdout.split()
        assert [os.path.relpath(h, _REPO) for h in hits] == [
            "horovod_tpu/trace/spans.py"]


def test_trace_package_imports_and_records_without_jax(tmp_path):
    """`horovod_tpu/trace` loaded on its own, with jax unimportable:
    the recorder, a thread span and a drain all work."""
    code = textwrap.dedent("""\
        import sys, types
        class _NoJax:
            def find_spec(self, name, path=None, target=None):
                if name == "jax" or name.startswith("jax."):
                    raise AssertionError("trace pulled in jax: " + name)
                return None
        sys.meta_path.insert(0, _NoJax())
        pkg = types.ModuleType("horovod_tpu")
        pkg.__path__ = [%r]
        sys.modules["horovod_tpu"] = pkg
        from horovod_tpu.trace import SpanRecorder, TraceContext
        rec = SpanRecorder(8)
        ctx = TraceContext.mint()
        with rec.span("sched_iteration"):
            rec.record_local("prefill", 1.0, 2.0, ship=ctx)
        assert len(rec.between(0.0, float("inf"))) == 2
        assert [s["name"] for s in rec.drain(ctx.trace_id)] == ["prefill"]
        assert "jax" not in sys.modules
        print("ok")
        """) % os.path.join(_REPO, "horovod_tpu")
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=120, cwd=str(tmp_path))
    assert r.returncode == 0 and r.stdout.strip() == "ok", r.stderr


def test_the_global_recorder_is_one_object():
    assert get_recorder() is get_recorder()
    assert get_recorder().capacity >= 1
