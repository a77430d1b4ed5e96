"""horovod_tpu.obs: the unified metrics plane (ISSUE 3).

Acceptance bars:

* registry semantics — labeled families, get-or-create identity, type
  conflicts fail fast, counters are monotonic;
* histogram bucket math — fixed log-spaced bounds, placement,
  interpolated percentiles, element-wise mergeability;
* concurrent increments stay exact (thread-safe plane);
* Prometheus text exposition matches the golden format;
* /metrics served over loopback (standalone exporter AND mounted on
  the serve front end, with engine wire-byte + serve latency series);
* cross-rank merge + straggler ranking (unit level here; the real
  4-process allgather path runs in tests/test_multiprocess.py);
* the streaming timeline writer never re-reads its own output file and
  uses rank-stable crc32 row ids.
"""
import builtins
import json
import re
import threading
import time
import urllib.request
import zlib

import numpy as np
import pytest

from horovod_tpu import obs
from horovod_tpu.obs.metrics import MetricsRegistry


# ---------------------------------------------------------------------------
# registry semantics
# ---------------------------------------------------------------------------

class TestRegistry:
    def test_get_or_create_identity_and_labels(self):
        R = MetricsRegistry()
        a = R.counter("reqs_total", "h", {"kind": "x"})
        b = R.counter("reqs_total", labels={"kind": "x"})
        c = R.counter("reqs_total", labels={"kind": "y"})
        assert a is b and a is not c
        a.inc(3)
        assert b.value == 3 and c.value == 0

    def test_type_conflict_and_bad_names_fail_fast(self):
        R = MetricsRegistry()
        R.counter("m")
        with pytest.raises(ValueError):
            R.gauge("m")
        with pytest.raises(ValueError):
            R.counter("0bad")
        with pytest.raises(ValueError):
            R.counter("ok", labels={"bad-label": "v"})

    def test_counter_monotonic(self):
        c = MetricsRegistry().counter("c")
        with pytest.raises(ValueError):
            c.inc(-1)

    def test_gauge_set_fn_and_dead_callback(self):
        R = MetricsRegistry()
        g = R.gauge("depth")
        g.set_fn(lambda: 7)
        assert g.value == 7

        def boom():
            raise RuntimeError("dead")
        g.set_fn(boom)
        assert g.value == 7  # last good sample, /metrics survives

    def test_unregister_claims_fresh_series(self):
        R = MetricsRegistry()
        R.counter("owned_total").inc(9)
        R.unregister("owned_total")
        assert R.counter("owned_total").value == 0

    def test_snapshot_is_json_serializable(self):
        R = MetricsRegistry()
        R.counter("c", labels={"k": "v"}).inc()
        R.gauge("g").set(1.5)
        R.histogram("h").observe(3.0)
        snap = json.loads(json.dumps(R.snapshot()))
        assert {e["name"] for e in snap["counters"]} == {"c"}
        (h,) = snap["histograms"]
        assert h["count"] == 1 and len(h["counts"]) == len(h["bounds"]) + 1


# ---------------------------------------------------------------------------
# histogram bucket math
# ---------------------------------------------------------------------------

class TestHistogram:
    def test_log_buckets_ladder(self):
        assert obs.log_buckets(0.1, 100) == (
            0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 25.0, 50.0, 100.0)

    def test_placement_and_overflow(self):
        h = obs.Histogram(bounds=(1.0, 10.0, 100.0))
        for v in (0.5, 1.0, 5.0, 100.0, 1e6):
            h.observe(v)
        assert h.counts == [2, 1, 1, 1]  # <=1, <=10, <=100, +Inf
        assert h.count == 5 and h.sum == pytest.approx(1000106.5)

    def test_percentile_interpolation(self):
        h = obs.Histogram(bounds=(1.0, 10.0, 100.0))
        for _ in range(10):
            h.observe(5.0)                 # all in the (1, 10] bucket
        # linear interpolation inside the landing bucket
        assert h.percentile(0.5) == pytest.approx(5.5)
        assert h.percentile(1.0) == pytest.approx(10.0)
        assert obs.Histogram(bounds=(1.0,)).percentile(0.5) is None

    def test_merge_is_elementwise(self):
        R1, R2 = MetricsRegistry(), MetricsRegistry()
        for R, n in ((R1, 2), (R2, 3)):
            h = R.histogram("lat_ms", bounds=(1.0, 10.0))
            for _ in range(n):
                h.observe(5.0)
            R.counter("c_total").inc(n)
            R.gauge("depth").set(n)
        m = obs.merge_snapshots([R1.snapshot(), R2.snapshot()])
        (h,) = m["histograms"]
        assert h["counts"] == [0, 5, 0] and h["count"] == 5
        assert m["counters"][0]["value"] == 5
        assert m["gauges"][0]["value"] == 5  # fleet-wide depth sums

    def test_merge_rejects_mismatched_bounds(self):
        R1, R2 = MetricsRegistry(), MetricsRegistry()
        R1.histogram("h", bounds=(1.0, 2.0)).observe(1)
        R2.histogram("h", bounds=(1.0, 3.0)).observe(1)
        with pytest.raises(ValueError):
            obs.merge_snapshots([R1.snapshot(), R2.snapshot()])


# ---------------------------------------------------------------------------
# concurrency
# ---------------------------------------------------------------------------

def test_concurrent_increments_exact():
    R = MetricsRegistry()
    c = R.counter("n_total")
    h = R.histogram("h_ms", bounds=(10.0, 1000.0))
    n_threads, per = 8, 500

    def work():
        for i in range(per):
            c.inc()
            h.observe(float(i % 8))

    ts = [threading.Thread(target=work) for _ in range(n_threads)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert c.value == n_threads * per
    assert h.count == n_threads * per
    assert h.counts[0] == n_threads * per  # every sample <= 10
    assert h.sum == pytest.approx(
        n_threads * sum(i % 8 for i in range(per)))


# ---------------------------------------------------------------------------
# Prometheus exposition
# ---------------------------------------------------------------------------

class TestPrometheus:
    def test_golden_format(self):
        R = MetricsRegistry()
        R.counter("app_requests_total", "requests seen",
                  {"kind": "read"}).inc(3)
        R.gauge("app_depth").set(2.5)
        h = R.histogram("app_latency_ms", "latency", bounds=(1.0, 10.0))
        h.observe(0.5)
        h.observe(5.0)
        h.observe(50.0)
        assert R.to_prometheus() == (
            '# TYPE app_depth gauge\n'
            'app_depth 2.5\n'
            '# HELP app_latency_ms latency\n'
            '# TYPE app_latency_ms histogram\n'
            'app_latency_ms_bucket{le="1"} 1\n'
            'app_latency_ms_bucket{le="10"} 2\n'
            'app_latency_ms_bucket{le="+Inf"} 3\n'
            'app_latency_ms_sum 55.5\n'
            'app_latency_ms_count 3\n'
            '# HELP app_requests_total requests seen\n'
            '# TYPE app_requests_total counter\n'
            'app_requests_total{kind="read"} 3\n')

    def test_every_sample_line_parses(self):
        R = MetricsRegistry()
        R.counter("a_total", labels={"k": 'v"q\n'}).inc()
        R.histogram("b_ms").observe(1.0)
        sample = re.compile(
            r'^[a-zA-Z_:][a-zA-Z0-9_:]*'                 # metric name
            r'(\{[a-zA-Z_]\w*="(?:[^"\\\n]|\\.)*"'       # first label
            r'(,[a-zA-Z_]\w*="(?:[^"\\\n]|\\.)*")*\})?'  # more labels
            r' -?[0-9.eE+-]+$')                          # sample value
        out = R.to_prometheus()
        assert out.endswith("\n")
        for line in out.splitlines():
            if line.startswith("#"):
                assert re.match(r"^# (HELP|TYPE) [a-zA-Z_:]", line)
            else:
                assert sample.match(line), line


# ---------------------------------------------------------------------------
# exporter over loopback
# ---------------------------------------------------------------------------

def test_exporter_metrics_and_healthz():
    R = MetricsRegistry()
    R.counter("exp_total").inc(4)
    exp = obs.start_exporter(port=0, registry=R)
    try:
        base = f"http://127.0.0.1:{exp.port}"
        with urllib.request.urlopen(base + "/metrics", timeout=10) as r:
            assert r.status == 200
            assert r.headers["Content-Type"].startswith("text/plain")
            body = r.read().decode()
        assert "exp_total 4" in body
        with urllib.request.urlopen(base + "/healthz", timeout=10) as r:
            assert json.loads(r.read())["ok"] is True
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(base + "/nope", timeout=10)
        assert ei.value.code == 404
    finally:
        exp.stop()


def test_config_metrics_knobs_fail_fast(monkeypatch):
    from horovod_tpu.core.config import Config
    for name, val in (("HOROVOD_METRICS_PORT", "abc"),
                      ("HOROVOD_METRICS_PORT", "70000"),
                      ("HOROVOD_METRICS_TIMELINE_PERIOD", "nope"),
                      ("HOROVOD_METRICS_TIMELINE_PERIOD", "-1")):
        monkeypatch.setenv(name, val)
        with pytest.raises(ValueError):
            Config.from_env()
        monkeypatch.delenv(name)


def test_init_starts_exporter_from_env(monkeypatch):
    import socket
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    monkeypatch.setenv("HOROVOD_METRICS_PORT", str(port))
    import horovod_tpu as hvd
    hvd.init()
    try:
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/metrics", timeout=10) as r:
            assert r.status == 200
    finally:
        hvd.shutdown()
    # exporter is torn down with the runtime
    with pytest.raises(Exception):
        urllib.request.urlopen(f"http://127.0.0.1:{port}/metrics",
                               timeout=2)


# ---------------------------------------------------------------------------
# cross-rank report (unit level; multiprocess path in test_multiprocess)
# ---------------------------------------------------------------------------

class TestReport:
    @staticmethod
    def _rank_snap(mean_ms, n=10):
        R = MetricsRegistry()
        h = R.histogram("hvd_step_time_ms")
        for _ in range(n):
            h.observe(mean_ms)
        R.counter("steps_total").inc(n)
        return R.snapshot()

    def test_straggler_ranking_and_skew(self):
        snaps = [self._rank_snap(m) for m in (4.0, 4.0, 80.0, 4.0)]
        rep = obs.build_report(snaps)
        assert rep["world_size"] == 4
        assert rep["step_metric"] == "hvd_step_time_ms"
        assert rep["stragglers"][0]["rank"] == 2
        assert rep["stragglers"][0]["skew"] > 5
        assert rep["skew"]["max_over_median"] == \
            rep["stragglers"][0]["skew"]
        assert set(rep["per_rank"]) == {0, 1, 2, 3}
        # merged counters sum across ranks
        merged = {e["name"]: e["value"]
                  for e in rep["merged"]["counters"]}
        assert merged["steps_total"] == 40
        # fleet p50/p99 come from the merged histogram
        assert rep["step_time"]["count"] == 40
        assert rep["step_time"]["p99_ms"] >= rep["step_time"]["p50_ms"]

    def test_no_step_metric(self):
        R = MetricsRegistry()
        R.counter("only_total").inc()
        rep = obs.build_report([R.snapshot()])
        assert rep["step_metric"] is None and rep["stragglers"] == []

    def test_step_timer_records(self):
        R = MetricsRegistry()
        with obs.step_timer(registry=R):
            time.sleep(0.01)
        h = R.get("hvd_step_time_ms")
        assert h.count == 1 and h.sum >= 10.0

    def test_single_process_metrics_report(self, hvd):
        # async -> engine-routed, so the wire-byte series exist
        out = hvd.synchronize(hvd.allreduce_async(
            np.ones((8, 2), np.float32), hvd.Sum, name="rep_ar"))
        np.testing.assert_allclose(np.asarray(out)[0], 8.0)
        with obs.step_timer():
            pass
        rep = hvd.metrics_report()
        assert rep["world_size"] == 1
        assert rep["stragglers"][0]["rank"] == 0
        names = {e["name"] for e in rep["merged"]["counters"]}
        assert "hvd_wire_bytes_total" in names


# ---------------------------------------------------------------------------
# re-routed legacy counters keep their instance views
# ---------------------------------------------------------------------------

class TestBackCompatViews:
    def test_engine_wire_bytes_views(self, hvd):
        h = hvd.allreduce_async(np.ones((8, 4), np.float32), hvd.Sum,
                                name="bc_ar")
        hvd.synchronize(h)
        eng = hvd.core.basics.get_engine()
        nb = 8 * 4 * 4
        assert eng.wire_bytes_logical == nb == eng.wire_bytes_actual
        c = obs.get_registry().get("hvd_wire_bytes_total",
                                   {"kind": "logical"})
        assert int(c.value) == eng.wire_bytes_logical

    def test_queue_counter_views(self):
        from horovod_tpu.serve import AdmissionQueue, Rejected
        q = AdmissionQueue(max_queue=1)
        q.submit([1, 2])
        with pytest.raises(Rejected):
            q.submit([3])
        assert q.admitted_count == 1 and q.shed_count == 1
        R = obs.get_registry()
        assert R.get("hvd_serve_shed_total").value == 1
        assert R.get("hvd_serve_queue_depth").value == 1
        # a fresh queue claims the series: views count from zero again
        q2 = AdmissionQueue(max_queue=4)
        assert q2.shed_count == 0
        assert R.get("hvd_serve_shed_total").value == 0


# ---------------------------------------------------------------------------
# serve loopback: /metrics mounted on the /generate server
# ---------------------------------------------------------------------------

def test_serve_http_metrics_endpoint(hvd):
    import jax
    import jax.numpy as jnp
    from horovod_tpu.models.gpt import GPT, GPTConfig
    from horovod_tpu.serve import (AdmissionQueue, ContinuousBatcher,
                                   ShardedExecutor)
    from horovod_tpu.serve.http import make_server

    # engine traffic first, so the scrape shows wire-byte series next to
    # the serve histograms (the ISSUE acceptance shape)
    hvd.synchronize(hvd.allreduce_async(
        np.ones((8, 4), np.float32), hvd.Sum, name="serve_m_ar"))

    kw = dict(vocab_size=64, num_layers=1, num_heads=2, head_dim=8,
              max_seq_len=32, dtype=jnp.float32,
              attention_impl="reference")
    model = GPT(GPTConfig(decode=True, **kw))
    # the cache is a collection of its own: the training-mode model
    # makes the same parameter tree
    params = GPT(GPTConfig(**kw)).init(
        jax.random.PRNGKey(0), jnp.zeros((2, 4), jnp.int32))["params"]
    ex = ShardedExecutor(model, params, max_batch=2, max_len=32)
    q = AdmissionQueue(max_queue=8)
    b = ContinuousBatcher(ex, q, buckets=(8, 16))
    srv = make_server(b)
    t = threading.Thread(target=srv.serve_forever, daemon=True)
    t.start()
    host, port = srv.server_address
    base = f"http://{host}:{port}"
    try:
        b.start()
        req = urllib.request.Request(
            base + "/generate",
            data=json.dumps({"tokens": [1, 2, 3],
                             "max_new_tokens": 2}).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=60) as resp:
            assert json.loads(resp.read())["status"] == "ok"
        with urllib.request.urlopen(base + "/metrics", timeout=10) as resp:
            assert resp.headers["Content-Type"].startswith("text/plain")
            body = resp.read().decode()
        # engine wire-bytes + serve latency-histogram series, in valid
        # Prometheus text
        assert 'hvd_wire_bytes_total{kind="logical"}' in body
        assert ('hvd_serve_step_ms_bucket{kernel="xla",kind="decode",'
                'le="+Inf"}') in body
        assert re.search(r"^hvd_serve_step_ms_count\{kernel=\"xla\","
                         r"kind=\"prefill\"\} [1-9]", body, re.M)
        assert re.search(r"^hvd_serve_ttft_ms_count [1-9]", body, re.M)
        assert re.search(r"^hvd_serve_admitted_total [1-9]", body, re.M)
    finally:
        srv.shutdown()
        b.stop()


# ---------------------------------------------------------------------------
# timeline satellites: streaming writer + stable tids
# ---------------------------------------------------------------------------

class TestTimelineStreaming:
    def test_long_run_never_rereads_its_output(self, tmp_path,
                                               monkeypatch):
        """Regression for the O(n^2) flush: the writer must open the
        trace exactly once for writing and NEVER re-open it to read the
        events back."""
        from horovod_tpu.timeline import Timeline
        monkeypatch.setenv("HOROVOD_TIMELINE_NATIVE", "0")
        path = str(tmp_path / "trace.json")
        opens = []
        real_open = builtins.open

        def spying_open(file, mode="r", *a, **kw):
            if isinstance(file, str) and file == path:
                opens.append(mode)
            return real_open(file, mode, *a, **kw)

        monkeypatch.setattr(builtins, "open", spying_open)
        tl = Timeline(path)
        tl.start()
        for i in range(10000):   # > 2 flush batches of 4096
            tl.instant("EV", {"i": i})
        tl.stop()
        assert opens == ["w"], opens
        doc = json.load(real_open(path))
        assert len(doc["traceEvents"]) == 10000
        assert doc["traceEvents"][0]["args"]["i"] == 0
        assert doc["traceEvents"][-1]["args"]["i"] == 9999

    def test_file_is_valid_json_between_flushes(self, tmp_path,
                                                monkeypatch):
        from horovod_tpu.timeline import Timeline
        monkeypatch.setenv("HOROVOD_TIMELINE_NATIVE", "0")
        path = str(tmp_path / "trace.json")
        tl = Timeline(path)
        tl.start()
        for i in range(5000):
            tl.begin(f"t{i % 3}", "QUEUED")
            tl.end(f"t{i % 3}", "QUEUED")
        deadline = time.monotonic() + 10
        n = 0
        while time.monotonic() < deadline:   # wait for a mid-run flush
            try:
                n = len(json.load(open(path))["traceEvents"])
            except (ValueError, FileNotFoundError):
                n = 0
            if n >= 4096:
                break
            time.sleep(0.05)
        assert n >= 4096   # valid JSON while the writer is still running
        tl.stop()
        assert len(json.load(open(path))["traceEvents"]) == 10000

    def test_restart_carries_forward_existing_trace(self, tmp_path,
                                                    monkeypatch):
        """A second writer on the same path (elastic restart, dynamic
        stop->start) appends after ONE read at open — the old
        merge-with-existing behavior without the per-flush re-read."""
        from horovod_tpu.timeline import Timeline
        monkeypatch.setenv("HOROVOD_TIMELINE_NATIVE", "0")
        path = str(tmp_path / "t.json")
        tl = Timeline(path)
        tl.start()
        tl.instant("A", {})
        tl.stop()
        tl2 = Timeline(path)
        tl2.start()
        tl2.instant("B", {})
        tl2.stop()
        names = [e["name"] for e in json.load(open(path))["traceEvents"]]
        assert names == ["A", "B"]

    def test_periodic_metrics_rows_on_timeline(self, tmp_path,
                                               monkeypatch):
        from horovod_tpu.timeline import Timeline
        monkeypatch.setenv("HOROVOD_TIMELINE_NATIVE", "0")
        R = MetricsRegistry()
        R.counter("emit_total").inc(3)
        R.histogram("emit_ms").observe(7.0)
        path = str(tmp_path / "t.json")
        tl = Timeline(path)
        tl.start()
        em = obs.TimelineEmitter(tl, period_s=0.05, registry=R)
        time.sleep(0.3)
        em.stop()
        tl.stop()
        rows = [e for e in json.load(open(path))["traceEvents"]
                if e["name"] == "METRICS"]
        assert rows
        assert rows[0]["args"]["emit_total"] == 3
        assert rows[0]["args"]["emit_ms"]["count"] == 1
        assert rows[0]["args"]["emit_ms"]["p50"] is not None

    def test_tids_are_crc32_stable(self, tmp_path, monkeypatch):
        from horovod_tpu.timeline import Timeline, _tid
        assert _tid("grad/layer0") == \
            zlib.crc32(b"grad/layer0") % (1 << 31)
        monkeypatch.setenv("HOROVOD_TIMELINE_NATIVE", "0")
        path = str(tmp_path / "t.json")
        tl = Timeline(path)
        tl.start()
        tl.begin("grad/layer0", "QUEUED")
        tl.end("grad/layer0", "QUEUED")
        tl.stop()
        evs = json.load(open(path))["traceEvents"]
        assert [e["tid"] for e in evs] == [_tid("grad/layer0")] * 2
