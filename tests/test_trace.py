"""horovod_tpu.trace: tier-1 suite (distributed tracing plane).

Acceptance bars (docs/tracing.md):

* context propagation is structural back-compat: a malformed or
  missing ``"trace"`` field is simply untraced, never an error;
* the per-process span ring is bounded — overflow evicts the OLDEST
  trace whole, and drain pops a trace's spans exactly once (plus any
  pending process-level spans);
* the router's assembler tail-samples: an ok fast trace is attributed
  (leg histograms observed) and DROPPED; slow / errored / shed /
  failover-touched / flagged / head-sampled traces are retained in
  full, and retention is bounded;
* leg decomposition tiles the router-measured e2e exactly when clocks
  align — including across a deliberately skewed worker clock once a
  heartbeat sample lands (the NTP-style minimum-delay filter);
* artifacts are machine-readable while streaming: the merged Chrome
  trace is valid JSON with one named pid row per process, the
  incident dump leads with its header line;
* tools/trace_inspect.py runs jax-free (subprocess smoke with a
  meta-path hook that fails the import of jax);
* the exporter plane survives concurrency: /metrics scraped under
  heavy mutation stays parseable with monotone counters, and a
  TimelineEmitter interleaved with trace writes yields valid JSON;
* ``/metrics?fleet=1`` merges live worker snapshots over the ctrl
  socket into one exposition (2-worker loopback).
"""
import json
import os
import re
import subprocess
import sys
import textwrap
import threading
import time
import urllib.request
from types import SimpleNamespace
from unittest import mock

import pytest

from horovod_tpu.obs import metrics as obs_metrics
from horovod_tpu.obs.metrics import MetricsRegistry
from horovod_tpu.trace.clock import ClockOffsets
from horovod_tpu.trace.collect import (TraceAssembler, assembler_from_env,
                                       clock_key, leg_decompose)
from horovod_tpu.trace.context import TraceContext
from horovod_tpu.trace import spans as spans_mod
from horovod_tpu.trace.spans import (LEGS, SPAN_LEGS, SPAN_NAMES,
                                     SpanRecorder)
from horovod_tpu.trace.writer import (ROUTER_PID, ChromeTraceWriter,
                                      span_pid, span_row_name)

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# context propagation
# ---------------------------------------------------------------------------

class TestTraceContext:
    def test_mint_child_wire_round_trip(self):
        root = TraceContext.mint()
        assert root.parent_id is None
        kid = root.child()
        assert kid.trace_id == root.trace_id
        assert kid.parent_id == root.span_id
        assert kid.span_id != root.span_id
        back = TraceContext.from_wire(
            json.loads(json.dumps(kid.to_wire())))
        assert (back.trace_id, back.span_id, back.parent_id) == \
            (kid.trace_id, kid.span_id, kid.parent_id)

    def test_root_wire_omits_parent(self):
        d = TraceContext.mint().to_wire()
        assert set(d) == {"trace", "span"}

    @pytest.mark.parametrize("junk", [
        None, 7, "abc", [], {}, {"trace": "t"}, {"span": "s"},
        {"trace": "", "span": "s"}, {"trace": "t", "span": None}])
    def test_malformed_wire_is_untraced_not_an_error(self, junk):
        assert TraceContext.from_wire(junk) is None


# ---------------------------------------------------------------------------
# the span registry table
# ---------------------------------------------------------------------------

class TestSpanRegistry:
    def test_every_leg_reference_is_declared(self):
        assert all(leg is None or leg in LEGS
                   for leg in SPAN_LEGS.values())

    def test_names_follow_declaration_order(self):
        assert SPAN_NAMES == tuple(SPAN_LEGS)
        assert len(set(SPAN_NAMES)) == len(SPAN_NAMES)
        assert len(set(LEGS)) == len(LEGS)

    def test_every_leg_has_at_least_one_span(self):
        used = {leg for leg in SPAN_LEGS.values() if leg}
        assert used == set(LEGS)


# ---------------------------------------------------------------------------
# the per-process recorder
# ---------------------------------------------------------------------------

class TestSpanRecorder:
    def test_record_and_drain_pops_whole_trace(self):
        rec = SpanRecorder(64, pool="prefill", replica=3, gen=2)
        ctx = TraceContext.mint()
        rec.record_local("queue_wait", 1.0, 2.0, ship=ctx)
        rec.record_local("prefill", 2.0, 3.0, ship=ctx.to_wire(),
                         tokens=8)
        assert rec.pending() == 2
        spans = rec.drain(ctx.trace_id)
        assert [s["name"] for s in spans] == ["queue_wait", "prefill"]
        assert spans[0]["pool"] == "prefill"
        assert spans[0]["replica"] == 3 and spans[0]["gen"] == 2
        assert spans[1]["extra"] == {"tokens": 8}
        # the parent chain hangs off the carried context
        assert spans[0]["parent"] == ctx.span_id
        assert rec.pending() == 0 and rec.drain(ctx.trace_id) == []

    def test_untraced_and_garbage_are_single_branch_noops(self):
        rec = SpanRecorder(8)
        # nothing to ship under: the span stays in the local ring
        rec.record_local("prefill", 0.0, 1.0, ship=None)
        rec.record_local("prefill", 0.0, 1.0, ship={"bogus": 1})
        assert rec.pending() == 0
        assert rec.drain(None) == []

    def test_overflow_evicts_oldest_trace_whole(self):
        rec = SpanRecorder(4)
        a, b = TraceContext.mint(), TraceContext.mint()
        for i in range(3):
            rec.record_local("decode", i, i + 1, ship=a)
        for i in range(3):   # 6 > 4: trace a evicted WHOLE
            rec.record_local("decode", i, i + 1, ship=b)
        assert rec.dropped == 3
        assert rec.drain(a.trace_id) == []
        assert len(rec.drain(b.trace_id)) == 3

    def test_process_spans_ride_the_next_drain(self):
        rec = SpanRecorder(16)
        rec.record_process("weight_fence", 5.0, 6.0, gen=2)
        ctx = TraceContext.mint()
        rec.record_local("decode", 0.0, 1.0, ship=ctx)
        names = [s["name"] for s in rec.drain(ctx.trace_id)]
        assert names == ["decode", "weight_fence"]
        # drained exactly once
        assert all(s["name"] != "weight_fence"
                   for s in rec.drain(ctx.trace_id))

    def test_configure_stamps_identity(self):
        rec = SpanRecorder(8)
        rec.configure(pool="decode", replica=1, gen=4)
        ctx = TraceContext.mint()
        rec.record_local("decode", 0.0, 1.0, ship=ctx)
        sp = rec.drain(ctx.trace_id)[0]
        assert (sp["pool"], sp["replica"], sp["gen"]) == \
            ("decode", 1, 4)


# ---------------------------------------------------------------------------
# clock offsets (minimum-delay filter)
# ---------------------------------------------------------------------------

class TestClockOffsets:
    def test_unknown_process_aligns_identity(self):
        c = ClockOffsets()
        assert c.offset("nope") == 0.0
        assert c.align("nope", 42.0) == 42.0

    def test_tightest_round_trip_wins(self):
        c = ClockOffsets()
        # jittery read: 3 s window around a +10 s true offset
        c.note("w", remote_wall=100.0, local_before=108.5,
               local_after=111.5)
        # tight read: the true offset
        c.note("w", remote_wall=200.0, local_before=210.0,
               local_after=210.0)
        assert c.offset("w") == pytest.approx(10.0)
        assert c.align("w", 300.0) == pytest.approx(310.0)
        assert c.known() == {"w": pytest.approx(10.0)}

    def test_clock_key_shapes(self):
        assert clock_key("prefill", 3) == "prefill/r3"
        assert clock_key("", 0) == "pool/r0"
        assert clock_key("prefill", None) == "router"


# ---------------------------------------------------------------------------
# leg decomposition: boundaries tile e2e
# ---------------------------------------------------------------------------

def _span(name, t0, t1, *, pool="", replica=None, **extra):
    d = {"trace": "t", "span": "s", "name": name, "t0": t0, "t1": t1}
    if pool:
        d["pool"] = pool
    if replica is not None:
        d["replica"] = replica
    if extra:
        d["extra"] = extra
    return d


class TestLegDecompose:
    def test_colocated_trace_tiles_exactly(self):
        spans = [_span("queue_wait", 10.1, 10.3),
                 _span("prefill", 10.3, 10.5),
                 _span("decode", 10.5, 11.0)]
        legs = leg_decompose(spans, 10.0, 11.0)
        assert legs["queue"] == pytest.approx(300.0)
        assert legs["prefill"] == pytest.approx(200.0)
        assert legs["migrate"] == 0.0
        assert legs["decode"] == pytest.approx(500.0)
        assert sum(legs.values()) == pytest.approx(1000.0)

    def test_migrated_trace_has_four_legs(self):
        spans = [_span("prefill", 10.2, 10.4),
                 _span("park", 10.4, 10.5),
                 _span("migrate_push", 10.5, 10.6),
                 _span("migrate_install", 10.55, 10.65),
                 _span("decode", 10.65, 11.0)]
        legs = leg_decompose(spans, 10.0, 11.0)
        assert legs["queue"] == pytest.approx(200.0)
        assert legs["prefill"] == pytest.approx(200.0)
        # ... until the LAST migrate-family span END (nesting does not
        # double-count: boundaries, not span sums)
        assert legs["migrate"] == pytest.approx(250.0)
        assert legs["decode"] == pytest.approx(350.0)
        assert sum(legs.values()) == pytest.approx(1000.0)

    def test_no_spans_is_all_queue(self):
        legs = leg_decompose([], 5.0, 6.0)
        assert legs["queue"] == pytest.approx(1000.0)
        assert sum(legs.values()) == pytest.approx(1000.0)

    def test_misaligned_stamp_is_clamped_never_negative(self):
        # a worker clock 1000 s in the future cannot push a leg
        # negative or past the request window
        spans = [_span("prefill", 1010.0, 1010.5)]
        legs = leg_decompose(spans, 10.0, 11.0)
        assert all(v >= 0.0 for v in legs.values())
        assert sum(legs.values()) == pytest.approx(1000.0)


# ---------------------------------------------------------------------------
# the router-side assembler
# ---------------------------------------------------------------------------

def _mk_asm(**kw):
    kw.setdefault("registry", MetricsRegistry())
    kw.setdefault("pool", "testpool")
    return TraceAssembler(**kw)


def _worker_spans(ctx, base, *, skew=0.0, replica=0, migrate=False):
    """A plausible worker-side span set, stamped ``skew`` seconds off
    the router clock. The recorder's stamps are monotonic and `drain`
    adds the wall base: this worker's is 0, so the stamps below are
    its wall clock too."""
    rec = SpanRecorder(64, pool="prefill", replica=replica)
    b = base + skew
    rec.record_local("queue_wait", b + 0.01, b + 0.10, ship=ctx)
    rec.record_local("prefill", b + 0.10, b + 0.30, ship=ctx)
    if migrate:
        rec.record_local("park", b + 0.30, b + 0.35, ship=ctx)
        rec.record_local("migrate_push", b + 0.35, b + 0.45, ship=ctx)
    rec.record_local("decode", b + (0.45 if migrate else 0.30), b + 0.9,
                     ship=ctx)
    with mock.patch.object(spans_mod, "wall_base", return_value=0.0):
        return rec.drain(ctx.trace_id)


class TestTraceAssembler:
    def test_ok_fast_trace_attributed_then_dropped(self):
        R = MetricsRegistry()
        asm = _mk_asm(registry=R, slow_ms=5000.0)
        ctx = asm.start("r1")
        asm.add_spans(ctx, _worker_spans(ctx, time.time() - 1.0))
        assert asm.finish(ctx, "ok", e2e_ms=900.0, attempts=1) is None
        assert asm.finished == 1 and asm.retained() == []
        # ... but the legs WERE observed before the drop
        for leg in LEGS:
            h = R.get("hvd_trace_leg_ms",
                      {"leg": leg, "pool": "testpool"})
            assert h is not None and h.count == 1
        c = R.get("hvd_trace_retained_total", {"pool": "testpool"})
        assert c.value == 0

    @pytest.mark.parametrize("status", ["error", "expired", "rejected",
                                        "shed"])
    def test_bad_status_retained(self, status):
        asm = _mk_asm()
        ctx = asm.start("r1")
        rec = asm.finish(ctx, status, e2e_ms=10.0)
        assert rec is not None and rec["status"] == status
        assert [r["trace"] for r in asm.retained()] == [ctx.trace_id]

    def test_slow_failover_flagged_and_sampled_retained(self):
        asm = _mk_asm(slow_ms=100.0)
        slow = asm.start("slow")
        assert asm.finish(slow, "ok", e2e_ms=150.0) is not None
        multi = asm.start("multi")
        assert asm.finish(multi, "ok", e2e_ms=1.0,
                          attempts=2) is not None
        flagged = asm.start("flag")
        asm.mark(flagged, "chaos")
        rec = asm.finish(flagged, "ok", e2e_ms=1.0)
        assert rec is not None and rec["flags"] == ["chaos"]
        forced = asm.start("forced", forced=True)
        assert asm.finish(forced, "ok", e2e_ms=1.0) is not None
        assert len(asm.retained()) == 4

    def test_head_sampling_retains_everything_at_one(self):
        asm = _mk_asm(sample=1.0)
        for i in range(3):
            asm.finish(asm.start(i), "ok", e2e_ms=1.0)
        assert len(asm.retained()) == 3

    def test_retention_is_bounded(self):
        asm = _mk_asm(retain=2)
        for i in range(5):
            asm.finish(asm.start(i), "error", e2e_ms=1.0)
        kept = asm.retained()
        assert len(kept) == 2 and [r["rid"] for r in kept] == [3, 4]

    def test_unknown_or_finished_trace_is_noop(self):
        asm = _mk_asm()
        assert asm.finish("deadbeef", "ok") is None
        ctx = asm.start("r")
        asm.finish(ctx, "error", e2e_ms=1.0)
        asm.mark(ctx, "late")            # after finish: dropped
        asm.add_spans(ctx, [_span("decode", 0, 1)])
        assert asm.retained()[0]["flags"] == []
        assert asm.finish(ctx, "ok") is None   # double finish

    def test_legs_tile_e2e_across_a_skewed_worker_clock(self):
        asm = _mk_asm(slow_ms=0.0)   # retain all
        skew = 137.5                 # worker clock 137.5 s ahead
        t1 = time.time()
        t0 = t1 - 1.0
        # one tight heartbeat sample nails the offset exactly
        asm.note_heartbeat("prefill", 0, remote_wall=t0 + skew,
                           local_before=t0, local_after=t0)
        ctx = asm.start("rX")
        asm.add_spans(ctx, _worker_spans(ctx, t0, skew=skew,
                                         replica=0, migrate=True))
        rec = asm.finish(ctx, "ok", e2e_ms=1000.0)
        legs = rec["legs_ms"]
        assert all(legs[leg] > 0.0 for leg in LEGS)
        assert sum(legs.values()) == \
            pytest.approx(rec["e2e_ms"], rel=1e-6)

    def test_router_spans_pass_through_unaligned(self):
        asm = _mk_asm(slow_ms=0.0)
        asm.note_heartbeat("prefill", 0, remote_wall=0.0,
                           local_before=500.0)   # huge bogus offset
        ctx = asm.start("r")
        now = time.time()
        asm.span(ctx, "dispatch", now - 0.9, now - 0.8)
        rec = asm.finish(ctx, "ok", e2e_ms=1000.0)
        # the router-recorded span has replica None -> identity align
        assert sum(rec["legs_ms"].values()) == \
            pytest.approx(1000.0, rel=1e-6)

    def test_inflight_snapshot_shape(self):
        asm = _mk_asm()
        ctx = asm.start("r9")
        asm.mark(ctx, "failover")
        snap = asm.inflight_snapshot()
        assert len(snap) == 1
        assert snap[0]["rid"] == "r9"
        assert snap[0]["status"] == "inflight"
        assert snap[0]["flags"] == ["failover"]


# ---------------------------------------------------------------------------
# artifacts: jsonl, chrome trace, incident dump
# ---------------------------------------------------------------------------

def _retained_asm(n=2):
    asm = _mk_asm(slow_ms=0.0)
    base = time.time() - 2.0
    for i in range(n):
        ctx = asm.start(f"req{i}")
        asm.span(ctx, "dispatch", base + 0.0, base + 0.05)
        asm.add_spans(ctx, _worker_spans(ctx, base, replica=i))
        asm.finish(ctx, "ok", e2e_ms=950.0)
    return asm


class TestArtifacts:
    def test_write_jsonl_round_trips(self, tmp_path):
        asm = _retained_asm()
        path = str(tmp_path / "traces.jsonl")
        assert asm.write_jsonl(path) == 2
        recs = [json.loads(ln) for ln in open(path)]
        assert [r["rid"] for r in recs] == ["req0", "req1"]
        assert all(r["legs_ms"].keys() == set(LEGS) for r in recs)
        assert all(any(s["name"] == "request" for s in r["spans"])
                   for r in recs)

    def test_chrome_trace_has_named_pid_rows(self, tmp_path):
        asm = _retained_asm()
        path = str(tmp_path / "trace.json")
        assert asm.write_chrome(path) > 0
        doc = json.load(open(path))
        evs = doc["traceEvents"]
        names = {e["args"]["name"] for e in evs
                 if e.get("ph") == "M" and e["name"] == "process_name"}
        # the router row plus one row per worker process
        assert "router" in names
        assert {"prefill/r0", "prefill/r1"} <= names
        xs = [e for e in evs if e.get("ph") == "X"]
        assert xs and len({e["pid"] for e in xs}) >= 3
        assert all(e["dur"] >= 1 for e in xs)

    def test_chrome_trace_single_trace_filter(self, tmp_path):
        asm = _retained_asm()
        tid = asm.retained()[0]["trace"]
        path = str(tmp_path / "one.json")
        asm.write_chrome(path, trace_id=tid)
        evs = json.load(open(path))["traceEvents"]
        assert {e["args"]["trace"] for e in evs
                if e.get("ph") == "X"} == {tid}

    def test_pid_rows_are_stable_across_runs(self):
        sp = _span("decode", 0, 1, pool="decode", replica=2)
        sp["gen"] = 3
        assert span_row_name(sp) == "decode/r2/g3"
        assert span_pid(sp) == span_pid(dict(sp))
        assert span_pid(_span("request", 0, 1)) == ROUTER_PID

    def test_incident_dump_shape(self, tmp_path):
        asm = _retained_asm()
        asm.note_event({"kind": "health", "what": "eject", "rid": 0})
        open_ctx = asm.start("killed")     # still in flight
        path = str(tmp_path / "incident.jsonl")
        n = asm.dump_incident(path, reason="test_kill",
                              extra_events=[{"kind": "chaos",
                                             "fault": "kill"}])
        lines = [json.loads(ln) for ln in open(path)]
        assert lines[0]["kind"] == "incident"
        assert lines[0]["reason"] == "test_kill"
        assert "clock_offsets" in lines[0]
        kinds = [ln["kind"] for ln in lines[1:]]
        assert kinds.count("event") == 2
        assert kinds.count("trace") == n == 3   # 1 inflight + 2 kept
        inflight = [ln for ln in lines
                    if ln.get("status") == "inflight"]
        assert [r["trace"] for r in inflight] == [open_ctx.trace_id]


# ---------------------------------------------------------------------------
# env arming
# ---------------------------------------------------------------------------

class TestAssemblerFromEnv:
    def test_off_by_default(self, monkeypatch):
        monkeypatch.delenv("HOROVOD_TRACE", raising=False)
        assert assembler_from_env("disagg") is None

    def test_armed_with_knobs(self, monkeypatch):
        monkeypatch.setenv("HOROVOD_TRACE", "1")
        monkeypatch.setenv("HOROVOD_TRACE_SLOW_MS", "750")
        monkeypatch.setenv("HOROVOD_TRACE_SAMPLE", "0.25")
        monkeypatch.setenv("HOROVOD_TRACE_RETAIN", "17")
        asm = assembler_from_env("disagg")
        try:
            assert asm is not None and asm.pool == "disagg"
            assert asm.slow_ms == 750.0 and asm.sample == 0.25
            assert asm._retained.maxlen == 17
        finally:
            obs_metrics.get_registry().unregister("hvd_trace_leg_ms")
            obs_metrics.get_registry().unregister(
                "hvd_trace_retained_total")


# ---------------------------------------------------------------------------
# tools/trace_inspect.py: jax-free subprocess smoke
# ---------------------------------------------------------------------------

_NO_JAX_PRELUDE = textwrap.dedent("""\
    import sys
    class _NoJax:
        def find_spec(self, name, path=None, target=None):
            if name == "jax" or name.startswith("jax."):
                raise AssertionError(
                    "trace_inspect pulled in jax: " + name)
            return None
    sys.meta_path.insert(0, _NoJax())
    import runpy
    sys.argv = ["trace_inspect"] + sys.argv[1:]
    runpy.run_path(%r, run_name="__main__")
    """)


def _inspect(tmp_path, *argv):
    tool = os.path.join(_REPO, "tools", "trace_inspect.py")
    return subprocess.run(
        [sys.executable, "-c", _NO_JAX_PRELUDE % tool, *argv],
        capture_output=True, text=True, timeout=60, cwd=str(tmp_path))


class TestTraceInspectCLI:
    @pytest.fixture()
    def artifacts(self, tmp_path):
        asm = _retained_asm()
        asm.note_event({"kind": "chaos", "fault": "kill_replica"})
        asm.start("open")
        jl = str(tmp_path / "traces.jsonl")
        inc = str(tmp_path / "incident.jsonl")
        asm.write_jsonl(jl)
        asm.dump_incident(inc, reason="smoke")
        return SimpleNamespace(asm=asm, jsonl=jl, incident=inc)

    def test_list_is_jax_free(self, tmp_path, artifacts):
        r = _inspect(tmp_path, "list", artifacts.jsonl)
        assert r.returncode == 0, r.stderr
        assert "req0" in r.stdout and "req1" in r.stdout
        # SystemExit(0) would still print a traceback on assertion:
        assert "AssertionError" not in r.stderr

    def test_show_prints_span_tree(self, tmp_path, artifacts):
        tid = artifacts.asm.retained()[0]["trace"]
        r = _inspect(tmp_path, "show", artifacts.jsonl,
                     "--trace", tid[:8])
        assert r.returncode == 0, r.stderr
        for name in ("request", "prefill", "decode"):
            assert name in r.stdout
        assert "prefill/r0" in r.stdout

    def test_incident_events_and_filters(self, tmp_path, artifacts):
        r = _inspect(tmp_path, "events", artifacts.incident)
        assert r.returncode == 0, r.stderr
        assert "chaos" in r.stdout
        r = _inspect(tmp_path, "list", artifacts.incident, "--fault")
        assert r.returncode == 0, r.stderr
        assert "inflight" in r.stdout     # open trace is fault-ish
        r = _inspect(tmp_path, "list", artifacts.jsonl,
                     "--leg", "decode", "--min-ms", "100000")
        assert r.returncode == 0 and "req0" not in r.stdout

    def test_missing_file_is_a_clean_error(self, tmp_path):
        r = _inspect(tmp_path, "list", "no_such_file.jsonl")
        assert r.returncode == 1
        assert "error:" in r.stderr


# ---------------------------------------------------------------------------
# exporter concurrency
# ---------------------------------------------------------------------------

_METRIC_LINE = re.compile(
    r"^(?:# (?:HELP|TYPE) [a-zA-Z_:][a-zA-Z0-9_:]* .*"
    r"|[a-zA-Z_:][a-zA-Z0-9_:]*(?:\{[^}]*\})? -?[0-9e+.na-f]+)$",
    re.IGNORECASE)


class TestExporterConcurrency:
    def test_metrics_scrape_under_heavy_mutation(self):
        R = MetricsRegistry()
        tracked = R.counter("hvd_conc_tracked_total", "t")
        exp = obs_metrics and __import__(
            "horovod_tpu.obs.exporter", fromlist=["start_exporter"])
        exporter = exp.start_exporter(port=0, registry=R)
        stop = threading.Event()

        def mutate(i):
            n = 0
            while not stop.is_set():
                n += 1
                tracked.inc()
                R.counter("hvd_conc_churn_total", "c",
                          {"w": str(i), "k": str(n % 7)}).inc()
                R.histogram("hvd_conc_ms", "h",
                            {"w": str(i)}).observe(n % 50)
                R.gauge("hvd_conc_g", "g", {"w": str(i)}).set(n)

        threads = [threading.Thread(target=mutate, args=(i,),
                                    daemon=True) for i in range(4)]
        for t in threads:
            t.start()
        try:
            url = f"http://127.0.0.1:{exporter.port}/metrics"
            last = -1.0
            for _ in range(20):
                body = urllib.request.urlopen(url, timeout=5).read()
                text = body.decode()
                for ln in text.splitlines():
                    if ln:
                        assert _METRIC_LINE.match(ln), ln
                m = re.search(
                    r"^hvd_conc_tracked_total (\S+)$", text, re.M)
                assert m is not None
                v = float(m.group(1))
                assert v >= last    # counters stay monotone
                last = v
            assert last > 0
        finally:
            stop.set()
            for t in threads:
                t.join(timeout=5)
            exporter.stop()

    def test_timeline_emitter_interleaves_with_trace_writer(
            self, tmp_path):
        from horovod_tpu.obs.exporter import TimelineEmitter
        R = MetricsRegistry()
        R.counter("hvd_interleave_total", "t").inc(3)
        path = str(tmp_path / "merged.json")
        w = ChromeTraceWriter(path)
        em = TimelineEmitter(w, period_s=0.02, registry=R)
        try:
            deadline = time.monotonic() + 5.0
            wrote = 0
            while time.monotonic() < deadline:
                ctx = TraceContext.mint()
                sp = _span("decode", time.time() - 0.01, time.time(),
                           pool="decode", replica=wrote % 2)
                sp["trace"] = ctx.trace_id
                w.write_spans([sp])
                wrote += 1
                # the file is VALID JSON after every flush, with the
                # emitter racing us the whole time
                doc = json.load(open(path))
                if wrote >= 25 and any(
                        e["name"] == "METRICS"
                        for e in doc["traceEvents"]):
                    break
                time.sleep(0.01)
        finally:
            em.stop()
            w.close()
        doc = json.load(open(path))
        names = [e["name"] for e in doc["traceEvents"]]
        assert "decode" in names
        rows = [e for e in doc["traceEvents"] if e["name"] == "METRICS"]
        assert rows and \
            rows[0]["args"]["hvd_interleave_total"] == 3


# ---------------------------------------------------------------------------
# /metrics?fleet=1: 2-worker loopback merge
# ---------------------------------------------------------------------------

class TestFleetMetricsMerge:
    @pytest.fixture()
    def fleet(self):
        from horovod_tpu.serve.http import make_fleet_server
        from horovod_tpu.serve.proc_fleet import ProcessFleetRouter
        from horovod_tpu.serve.worker import ReplicaEndpoint
        R = obs_metrics.get_registry()
        R.unregister("hvd_fleetdemo_total")
        R.counter("hvd_fleetdemo_total", "demo").inc(5)
        # two REAL worker endpoints speaking the ctrl-socket metrics
        # op (the batcher is never touched by that op)
        eps = [ReplicaEndpoint(None, rid=i).start() for i in (0, 1)]

        class _Fleet:
            # the REAL scrape loop, bound to a minimal replica table
            metrics_snapshots = ProcessFleetRouter.metrics_snapshots
            replicas = {
                0: SimpleNamespace(state="up", addr=eps[0].address),
                1: SimpleNamespace(state="up", addr=eps[1].address),
                2: SimpleNamespace(state="respawning", addr=None),
                # a vanished worker: scrape must skip, not fail
                3: SimpleNamespace(state="up",
                                   addr=("127.0.0.1", 1)),
            }

            def healthz(self):
                return {"ok": True}

        srv = make_fleet_server(_Fleet())
        t = threading.Thread(target=srv.serve_forever, daemon=True)
        t.start()
        try:
            yield SimpleNamespace(port=srv.server_address[1])
        finally:
            srv.shutdown()
            srv.server_close()
            for ep in eps:
                ep.close()
            R.unregister("hvd_fleetdemo_total")

    def _get(self, port, path):
        return urllib.request.urlopen(
            f"http://127.0.0.1:{port}{path}", timeout=10).read().decode()

    def test_fleet_scrape_merges_worker_snapshots(self, fleet):
        body = self._get(fleet.port, "/metrics?fleet=1")
        # local registry + 2 worker snapshots of the same process
        # registry: the merged counter is exactly 3x the local value
        m = re.search(r"^hvd_fleetdemo_total (\S+)$", body, re.M)
        assert m is not None and float(m.group(1)) == 15.0
        assert "# TYPE hvd_fleetdemo_total counter" in body
        assert "# HELP hvd_fleetdemo_total demo" in body

    def test_plain_scrape_stays_local(self, fleet):
        body = self._get(fleet.port, "/metrics")
        m = re.search(r"^hvd_fleetdemo_total (\S+)$", body, re.M)
        assert m is not None and float(m.group(1)) == 5.0
