"""Chaos-hardened serving: fleet router, serve fault sites, KV crc.

The tier-1 bars of ISSUE 8 (docs/serving.md failover section):

* every new serve fault site is a byte-identical pass-through when
  chaos is disarmed;
* the fleet router ejects a replica that stops heartbeating within
  2 x suspect_s and re-enqueues its in-flight requests EXACTLY once
  (completion count == 1 per request — at-most-once, never silently
  dropped, never answered twice);
* a chaos serve.step crash kills only the replica's scheduler thread;
  the router fails over, auto-restarts it and re-admits it (on the
  newest streamed weights when a stream is attached);
* an injected serve.kv corruption flips REAL device cache bytes and the
  per-block crc catches it before any token reaches a client (re-prefill
  yields the same tokens a clean run produces; "error" mode fails
  cleanly);
* serve.admit drops and serve.route partitions are absorbed by
  re-dispatch;
* /healthz turns 503 once the batcher is stopped/dead; expired queued
  requests get a structured 504 deadline completion within one
  iteration;
* the serve-profile random_plan is seed-deterministic and fail-fast.
"""
import json
import threading
import time
import urllib.error
import urllib.request
from types import SimpleNamespace

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from horovod_tpu.chaos import inject
from horovod_tpu.chaos.detector import AccrualTracker
from horovod_tpu.chaos.plan import ChaosPlan, PlanError, random_plan
from horovod_tpu.models.gpt import GPT, GPTConfig
from horovod_tpu.serve import (AdmissionQueue, ContinuousBatcher,
                               FleetRouter, Rejected, Replica,
                               ShardedExecutor)

_KW = dict(vocab_size=64, num_layers=2, num_heads=2, head_dim=8,
           max_seq_len=48, dtype=jnp.float32, attention_impl="reference")


@pytest.fixture(autouse=True)
def _disarm():
    """Every test starts and ends with the injector disarmed."""
    inject.uninstall()
    yield
    inject.uninstall()


@pytest.fixture(scope="module")
def gpt():
    train = GPT(GPTConfig(**_KW))
    dec = GPT(GPTConfig(decode=True, **_KW))
    params = train.init(jax.random.PRNGKey(0),
                        jnp.zeros((2, 8), jnp.int32))["params"]
    return SimpleNamespace(dec=dec, params=params)


def _executor(gpt, rid=None, max_batch=4):
    return ShardedExecutor(gpt.dec, gpt.params, max_batch=max_batch,
                           max_len=_KW["max_seq_len"], replica_id=rid)


@pytest.fixture(scope="module")
def expool(gpt):
    """Executors are the expensive part (one jit compile each), and
    REUSING one across batchers is exactly the fleet-restart contract
    (stale cache blocks are validity-masked, the crc ledger resets on
    block alloc) — so the suite exercises it constantly by pooling."""
    cache = {}

    def get(rid=None, max_batch=4):
        key = (rid, max_batch)
        if key not in cache:
            cache[key] = _executor(gpt, rid=rid, max_batch=max_batch)
        return cache[key]

    return get


def _fleet(expool, n=2, *, interval_s=0.1, suspect_s=0.5, kv_crc=False,
           max_queue=32, subscribers=None, **router_kw):
    reps = [Replica(i, expool(rid=i), buckets=(8,),
                    max_queue=max_queue, kv_crc=kv_crc,
                    subscriber=(subscribers or {}).get(i))
            for i in range(n)]
    router = FleetRouter(reps, interval_s=interval_s,
                         suspect_s=suspect_s, **router_kw)
    return router, reps


def _prompts(n, seed=0, lo=2, hi=8):
    rng = np.random.RandomState(seed)
    return [list(rng.randint(1, 64, rng.randint(lo, hi)))
            for _ in range(n)]


# ---------------------------------------------------------------------------
# plan: new serve sites
# ---------------------------------------------------------------------------

class TestServePlan:
    def test_serve_sites_accept_their_kinds(self):
        ChaosPlan.from_dict({"faults": [
            {"rank": 0, "site": "serve.step", "kind": "crash",
             "peer": 1, "at": 5},
            {"rank": 0, "site": "serve.step", "kind": "slow_rank",
             "peer": 0, "at": 3, "seconds": 0.5},
            {"rank": 0, "site": "serve.kv", "kind": "corrupt",
             "peer": 2, "at": 9, "slot": 1},
            {"rank": 0, "site": "serve.route", "kind": "partition",
             "peer": 1, "at": 2, "seconds": 1.0},
            {"rank": 0, "site": "serve.admit", "kind": "drop",
             "peer": 0, "at": 4},
            {"rank": 0, "site": "serve.admit", "kind": "delay",
             "at": 1, "seconds": 0.01},
        ]})

    @pytest.mark.parametrize("fault", [
        # kind/site validation table: wrong pairings fail fast
        {"rank": 0, "site": "serve.kv", "kind": "drop", "at": 1},
        {"rank": 0, "site": "serve.route", "kind": "corrupt", "at": 1},
        {"rank": 0, "site": "serve.admit", "kind": "partition",
         "at": 1, "seconds": 1.0},
        {"rank": 0, "site": "serve.step", "kind": "torn_write", "at": 1},
        {"rank": 0, "site": "step", "kind": "slow_rank", "at": 1,
         "seconds": 0.5, "slot": 0},           # slot off-site
        {"rank": 0, "site": "serve.kv", "kind": "corrupt", "at": 1,
         "slot": -1},                          # negative slot
    ])
    def test_bad_serve_faults_fail_fast(self, fault):
        with pytest.raises(PlanError):
            ChaosPlan.from_dict({"faults": [fault]})

    def test_serve_profile_seed_deterministic(self):
        a = random_plan(11, 3, 240, profile="serve").to_json()
        b = random_plan(11, 3, 240, profile="serve").to_json()
        c = random_plan(12, 3, 240, profile="serve").to_json()
        assert a == b           # byte-identical per seed
        assert a != c
        plan = json.loads(a)
        kinds = {f["kind"] for f in plan["faults"]}
        assert {"crash", "partition", "corrupt", "slow_rank",
                "drop"} <= kinds
        sites = {f["site"] for f in plan["faults"]}
        assert sites <= {"serve.step", "serve.kv", "serve.route",
                         "serve.admit"}

    def test_serve_profile_fail_fast(self):
        with pytest.raises(PlanError):
            random_plan(0, 1, 240, profile="serve")   # nothing to fail to
        with pytest.raises(PlanError):
            random_plan(0, 3, 10, profile="serve")    # horizon too short
        with pytest.raises(PlanError):
            random_plan(0, 3, 240, profile="nope")


# ---------------------------------------------------------------------------
# accrual tracker (shared with the training detector)
# ---------------------------------------------------------------------------

class TestAccrualTracker:
    def test_suspect_recover_reset(self):
        tr = AccrualTracker([1], interval_s=0.01, suspect_s=0.05)
        # never-seen: age alone cannot suspect
        time.sleep(0.08)
        ev, _ = tr.observe(1, None)
        assert ev is None and tr.suspects() == {}
        # seen once, then silent past the threshold -> suspect
        assert tr.observe(1, 1)[0] is None
        time.sleep(0.08)
        ev, age = tr.observe(1, 1)
        assert ev == "suspect" and age > 0.05
        assert 1 in tr.suspects() and tr.phi(1) > 1.0
        # seq advances -> recovered
        assert tr.observe(1, 2)[0] == "recovered"
        assert tr.suspects() == {}
        # reset returns the peer to the never-seen state
        time.sleep(0.08)
        tr.reset(1)
        assert tr.observe(1, None)[0] is None
        assert tr.suspects() == {}


# ---------------------------------------------------------------------------
# per-block KV crc
# ---------------------------------------------------------------------------

class TestKVCrc:
    def test_corrupt_detected_and_reprefilled(self, expool):
        """An injected serve.kv corruption flips real cache bytes; the
        crc catches it at retirement and the re-prefilled generation
        produces EXACTLY the tokens a clean run produces — corruption
        never reaches the client."""
        prompt = list(range(2, 8))
        # clean reference
        ex = expool(max_batch=2)
        q = AdmissionQueue(max_queue=4)
        b = ContinuousBatcher(ex, q, buckets=(8,), kv_crc=True)
        h = q.submit(prompt, max_new_tokens=6)
        b.run()
        want = h.tokens
        assert h.status == "ok" and b.kv_corruptions_detected == 0

        plan = ChaosPlan.from_dict({"faults": [
            {"rank": 0, "site": "serve.kv", "kind": "corrupt",
             "at": 2}]})
        inject.install(plan, rank=0)
        ex = expool(max_batch=2)
        q = AdmissionQueue(max_queue=4)
        b = ContinuousBatcher(ex, q, buckets=(8,), kv_crc=True,
                              on_kv_corrupt="reprefill")
        h = q.submit(prompt, max_new_tokens=6)
        b.run()
        assert b.kv_corruptions_injected == 1
        assert b.kv_corruptions_detected >= 1
        assert b.kv_reprefills >= 1
        assert h.status == "ok" and h.tokens == want

    def test_corrupt_error_mode_fails_cleanly(self, expool):
        plan = ChaosPlan.from_dict({"faults": [
            {"rank": 0, "site": "serve.kv", "kind": "corrupt",
             "at": 2}]})
        inject.install(plan, rank=0)
        ex = expool(max_batch=2)
        q = AdmissionQueue(max_queue=4)
        b = ContinuousBatcher(ex, q, buckets=(8,), kv_crc=True,
                              on_kv_corrupt="error")
        h = q.submit(list(range(2, 8)), max_new_tokens=6)
        b.run()
        assert h.status == "error" and h.error == "kv_corrupt"
        assert h.tokens == []          # no garbage escapes
        assert b.kv.live() == 0        # the row went back, with its blocks

    def test_kv_crc_config_knob(self, monkeypatch):
        from horovod_tpu.core.config import Config
        monkeypatch.setenv("HOROVOD_SERVE_KV_CRC", "1")
        c = Config.from_env()
        assert c.serve_kv_crc is True
        c.validate()
        monkeypatch.delenv("HOROVOD_SERVE_KV_CRC")
        assert Config.from_env().serve_kv_crc is False


# ---------------------------------------------------------------------------
# disarmed pass-through
# ---------------------------------------------------------------------------

class TestPassThrough:
    def test_serve_path_byte_identical_disarmed_vs_empty_plan(self, expool):
        """The serve guards must not change behavior: tokens with no
        injector installed == tokens with an armed-but-empty plan ==
        tokens with kv_crc enabled (observe-only)."""
        prompts = _prompts(6, seed=3)

        def run(kv_crc=False):
            ex = expool(max_batch=2)
            q = AdmissionQueue(max_queue=8)
            b = ContinuousBatcher(ex, q, buckets=(8,), kv_crc=kv_crc)
            hs = [q.submit(p, max_new_tokens=5) for p in prompts]
            b.run()
            assert all(h.status == "ok" for h in hs)
            return [h.tokens for h in hs]

        base = run()
        inject.install(ChaosPlan.from_dict({"faults": []}), rank=0)
        assert run() == base
        inject.uninstall()
        assert run(kv_crc=True) == base


# ---------------------------------------------------------------------------
# fleet router
# ---------------------------------------------------------------------------

class TestFleetRouting:
    def test_fan_out_matches_single_replica(self, expool):
        """Identical params on every replica => the fleet answers
        exactly like one replica would, whatever the routing."""
        prompts = _prompts(8, seed=1)
        ex = expool(max_batch=4)
        q = AdmissionQueue(max_queue=16)
        b = ContinuousBatcher(ex, q, buckets=(8,))
        hs = [q.submit(p, max_new_tokens=5) for p in prompts]
        b.run()
        want = [h.tokens for h in hs]

        router, _ = _fleet(expool, 2)
        router.start()
        try:
            fhs = [router.submit(p, max_new_tokens=5) for p in prompts]
            for fh in fhs:
                assert fh.wait(60)
            assert [fh.tokens for fh in fhs] == want
            assert all(fh.status == "ok" and fh.resolutions == 1
                       for fh in fhs)
            used = {fh.replica for fh in fhs}
            assert used == {0, 1}      # least-loaded routing spreads
        finally:
            router.close()

    def test_drain_rejects_new_and_finishes_inflight(self, expool):
        router, _ = _fleet(expool, 2)
        router.start()
        fhs = [router.submit(p, max_new_tokens=4)
               for p in _prompts(4, seed=2)]
        # the draining flag flips synchronously: new submits shed with
        # a retry hint from that moment on
        router.draining = True
        with pytest.raises(Rejected) as ei:
            router.submit([1, 2, 3], max_new_tokens=2)
        assert ei.value.retry_after_ms and ei.value.retry_after_ms > 0
        router.drain(timeout_s=30)
        for fh in fhs:
            assert fh.wait(5)
            # finished normally or (rarely) shed by the drain cutoff —
            # but never silently dropped
            assert fh.status in ("ok", "rejected")
            if fh.status == "rejected":
                assert fh.retry_after_ms > 0

    def test_replica_requires_labeled_executor(self, expool):
        with pytest.raises(ValueError):
            Replica(0, expool(), buckets=(8,))


class TestDetectorUnderServe:
    def test_stalled_replica_ejected_and_request_requeued_once(self, expool):
        """ISSUE satellite: a 2-replica fleet where one replica stops
        heartbeating is ejected within 2 x suspect_s, and its in-flight
        request is re-enqueued exactly once (completion count == 1)."""
        suspect_s = 0.6
        router, reps = _fleet(expool, 2, interval_s=0.15,
                              suspect_s=suspect_s)
        events = []
        router.add_listener(lambda ev: events.append(ev))
        router.start()
        try:
            # wedge replica 0's executor: its batcher thread blocks
            # inside step(), so heartbeats stop — exactly what a stuck
            # host looks like from the router's seat
            ex0 = reps[0].executor
            orig = ex0.step
            gate = threading.Event()
            blocked = threading.Event()

            def blocking_step(*a, **k):
                if not gate.is_set():
                    blocked.set()
                    gate.wait(20)
                return orig(*a, **k)

            ex0.step = blocking_step
            # ties break to the lowest id: this lands on replica 0
            fh = router.submit(list(range(2, 7)), max_new_tokens=4)
            assert blocked.wait(10)
            t0 = time.monotonic()
            # ejected in O(heartbeat): within 2 x suspect_s of the stall
            while not any(e["event"] == "eject" and e["replica"] == 0
                          for e in events):
                assert time.monotonic() - t0 <= 2 * suspect_s, events
                time.sleep(0.02)
            # the in-flight request failed over to replica 1 and
            # completed EXACTLY once
            assert fh.wait(30)
            assert fh.status == "ok" and fh.replica == 1
            assert fh.resolutions == 1
            assert fh.attempts == 2            # original + one requeue
            assert router.stats()["requeued"] == 1
            # release the wedged replica: its ghost answer must be
            # suppressed, not delivered twice
            gate.set()
            deadline = time.monotonic() + 15
            while router.duplicates_suppressed < 1:
                assert time.monotonic() < deadline
                time.sleep(0.02)
            assert fh.resolutions == 1
            # and the recovered replica is re-admitted
            deadline = time.monotonic() + 20
            while reps[0].state != "up":
                assert time.monotonic() < deadline, reps[0].state
                time.sleep(0.05)
        finally:
            gate.set()
            ex0.step = orig        # un-wedge the pooled executor
            router.close()


class TestFleetChaos:
    def test_crash_failover_restart_readmit(self, expool):
        plan = ChaosPlan.from_dict({"seed": 5, "faults": [
            {"rank": 0, "site": "serve.step", "kind": "crash",
             "peer": 0, "at": 25}]})
        inject.install(plan, rank=0)
        router, reps = _fleet(expool, 2, interval_s=0.1, suspect_s=0.5)
        events = []
        router.add_listener(lambda ev: events.append(ev))
        router.start()
        try:
            handles = []
            deadline = time.monotonic() + 30
            while time.monotonic() < deadline:
                try:
                    handles.append(router.submit(
                        list(range(2, 7)), max_new_tokens=4))
                except Rejected:
                    pass
                time.sleep(0.02)
                if any(e["event"] == "readmit" and e["replica"] == 0
                       for e in events):
                    break
            for h in handles:
                assert h.wait(30)
            # the crash fired, the victim was ejected and came back
            assert any(e["event"] == "eject" and e["replica"] == 0
                       for e in events), events
            assert any(e["event"] == "readmit" and e["replica"] == 0
                       for e in events), events
            assert reps[0].restarts == 1
            # every request answered exactly once or rejected with a
            # retry hint — never dropped, never doubled
            for h in handles:
                assert h.resolutions <= 1
                assert h.status in ("ok", "rejected", "expired")
                if h.status == "rejected":
                    assert h.retry_after_ms > 0
            assert sum(1 for h in handles if h.status == "ok") > 0
        finally:
            router.close()

    def test_admit_drop_absorbed_by_redispatch(self, expool):
        """A serve.admit drop eats the request at one replica's door;
        the router retries it elsewhere — the client still gets its
        answer, exactly once."""
        plan = ChaosPlan.from_dict({"faults": [
            {"rank": 0, "site": "serve.admit", "kind": "drop",
             "peer": 0, "at": 0}]})
        inject.install(plan, rank=0)
        router, _ = _fleet(expool, 2)
        router.start()
        try:
            fhs = [router.submit(p, max_new_tokens=4)
                   for p in _prompts(4, seed=4)]
            for fh in fhs:
                assert fh.wait(30)
            assert all(fh.status == "ok" and fh.resolutions == 1
                       for fh in fhs)
            # the dropped admission was retried on the other replica
            inj = inject.injector()
            assert any(e["kind"] == "drop" and e["site"] == "serve.admit"
                       for e in inj.fired)
        finally:
            router.close()

    def test_route_partition_routed_around(self, expool):
        """While the router is partitioned from replica 0, dispatches
        land on replica 1; service continues uninterrupted."""
        plan = ChaosPlan.from_dict({"faults": [
            {"rank": 0, "site": "serve.route", "kind": "partition",
             "peer": 0, "at": 0, "seconds": 2.0}]})
        inject.install(plan, rank=0)
        router, _ = _fleet(expool, 2)
        router.start()
        try:
            fhs = [router.submit(p, max_new_tokens=4)
                   for p in _prompts(6, seed=5)]
            for fh in fhs:
                assert fh.wait(30)
            assert all(fh.status == "ok" for fh in fhs)
            # everything submitted during the window avoided replica 0
            assert {fh.replica for fh in fhs} == {1}
        finally:
            router.close()


class TestFleetWeightGate:
    def test_restarted_replica_readmits_on_newest_version(self, gpt, expool):
        """The re-admission gate: a crashed replica only takes traffic
        again after re-adopting the newest PUBLISHED weight version —
        even one published while it was down."""
        from horovod_tpu.native.store import StoreServer
        from horovod_tpu.redist.stream import (WeightPublisher,
                                               WeightSubscriber)
        plan = ChaosPlan.from_dict({"faults": [
            {"rank": 0, "site": "serve.step", "kind": "crash",
             "peer": 0, "at": 25}]})
        inject.install(plan, rank=0)
        with StoreServer() as srv:
            pub = WeightPublisher("gate", kv_addr="127.0.0.1",
                                  kv_port=srv.port, resume_timeout=0.05)
            pub.publish(gpt.params)           # v1
            subs = {i: WeightSubscriber("gate", kv_addr="127.0.0.1",
                                        kv_port=srv.port,
                                        template=gpt.params)
                    for i in range(2)}
            router, reps = _fleet(expool, 2, interval_s=0.1,
                                  suspect_s=0.5, subscribers=subs)
            events = []
            router.add_listener(lambda ev: events.append(ev))
            router.start()
            try:
                published = []

                def on_crash(ev):
                    # fires SYNCHRONOUSLY inside the injector, on the
                    # dying batcher thread, BEFORE the replica actually
                    # dies: v2 exists the moment the crash happens, so
                    # the re-admission gate must see it
                    if ev["kind"] == "crash":
                        published.append(pub.publish(gpt.params))  # v2

                inject.injector().add_listener(on_crash)
                deadline = time.monotonic() + 40
                while not any(e["event"] == "readmit"
                              and e["replica"] == 0 for e in events):
                    assert time.monotonic() < deadline, events
                    try:
                        router.submit(list(range(2, 6)),
                                      max_new_tokens=3).wait(10)
                    except Rejected:
                        pass
                    time.sleep(0.01)
                assert published == [2]
                # the victim came back ON v2, not its pre-crash params
                assert reps[0].executor.params_version == 2
                readmit = next(e for e in events
                               if e["event"] == "readmit"
                               and e["replica"] == 0)
                assert readmit["weights_version"] == 2
            finally:
                router.close()
                pub.close()
                for s in subs.values():
                    s.close()


# ---------------------------------------------------------------------------
# http satellites: /healthz liveness + structured 504 deadline
# ---------------------------------------------------------------------------

class TestHTTPSatellites:
    def _serve(self, batcher):
        from horovod_tpu.serve.http import make_server
        srv = make_server(batcher)
        t = threading.Thread(target=srv.serve_forever, daemon=True)
        t.start()
        host, port = srv.server_address
        return srv, f"http://{host}:{port}"

    def test_healthz_503_once_batcher_dead(self, expool):
        ex = expool(max_batch=2)
        q = AdmissionQueue(max_queue=4)
        b = ContinuousBatcher(ex, q, buckets=(8,))
        srv, base = self._serve(b)
        try:
            b.start()
            with urllib.request.urlopen(base + "/healthz",
                                        timeout=10) as resp:
                health = json.loads(resp.read())
            assert resp.status == 200
            assert health["replica_up"] is True
            assert health["draining"] is False
            # stop() ran: liveness goes 503 so an LB stops routing here
            b.stop()
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(base + "/healthz", timeout=10)
            assert ei.value.code == 503
            body = json.loads(ei.value.read())
            assert body["replica_up"] is False and body["ok"] is False
        finally:
            srv.shutdown()
            b.stop()

    def test_healthz_503_when_thread_dies(self, expool):
        """A batcher thread killed by a chaos crash (not a clean stop)
        must also flip /healthz to 503."""
        plan = ChaosPlan.from_dict({"faults": [
            {"rank": 0, "site": "serve.step", "kind": "crash",
             "at": 1}]})
        inject.install(plan, rank=0)
        ex = expool(max_batch=2)
        q = AdmissionQueue(max_queue=4)
        b = ContinuousBatcher(ex, q, buckets=(8,))
        srv, base = self._serve(b)
        try:
            b.start()
            deadline = time.monotonic() + 10
            while b.alive():
                assert time.monotonic() < deadline
                time.sleep(0.02)
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(base + "/healthz", timeout=10)
            assert ei.value.code == 503
        finally:
            srv.shutdown()
            b._thread = None   # thread is dead; skip the join wait

    def test_expired_queued_request_gets_504_within_one_iteration(
            self, expool):
        """ISSUE satellite: a request whose deadline passes while it
        WAITS (every slot busy) is answered 504 {"error": "deadline"}
        by the next scheduling iteration — not by client timeout."""
        ex = expool(max_batch=1)      # one slot: easy to fill
        q = AdmissionQueue(max_queue=8)
        b = ContinuousBatcher(ex, q, buckets=(8,))
        b.warmup()
        # pace the executor (~5 ms/step) so the occupying request
        # really holds the slot past the short deadline below
        orig_step = ex.step

        def paced_step(*a, **k):
            time.sleep(0.005)
            return orig_step(*a, **k)

        ex.step = paced_step
        srv, base = self._serve(b)
        try:
            # occupy the only slot with a long request
            q.submit(list(range(2, 7)), max_new_tokens=40,
                     deadline_ms=60000)
            b.start()

            req = urllib.request.Request(
                base + "/generate",
                data=json.dumps({"tokens": [1, 2, 3],
                                 "max_new_tokens": 4,
                                 "deadline_ms": 60.0}).encode(),
                headers={"Content-Type": "application/json"})
            t0 = time.monotonic()
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(req, timeout=30)
            elapsed = time.monotonic() - t0
            assert ei.value.code == 504
            body = json.loads(ei.value.read())
            assert body["error"] == "deadline"
            # one iteration after expiry, not a 30 s socket timeout;
            # generous bound for CI noise, far under the old behavior
            assert elapsed < 5.0, elapsed
        finally:
            ex.step = orig_step    # un-pace the pooled executor
            srv.shutdown()
            b.stop()

    def test_reap_expired_unit(self):
        q = AdmissionQueue(max_queue=8)
        h = q.submit([1, 2], max_new_tokens=4, deadline_ms=1.0)
        time.sleep(0.01)
        assert q.reap_expired() == 1
        assert h.status == "expired"
        assert q.depth() == 0 and q.expired_count == 1


# ---------------------------------------------------------------------------
# soak verdict core (pure, synthetic logs)
# ---------------------------------------------------------------------------

class TestServeSoakVerdict:
    def _plan(self):
        return random_plan(7, 3, 240, profile="serve")

    def _stats(self, up=3, inflight=0):
        return {"replicas_up": up, "inflight": inflight,
                "duplicates_suppressed": 0,
                "replicas": {str(i): {"weights_version": 2}
                             for i in range(3)}}

    def _happy(self, plan):
        victim = next(f.peer for f in plan.faults if f.kind == "crash")
        t = 1000.0
        events = [
            {"kind": "chaos", "fault": "crash", "site": "serve.step",
             "peer": victim, "t": t + 2.0},
            {"kind": "fleet", "event": "eject", "replica": victim,
             "t": t + 2.4},
            {"kind": "fleet", "event": "readmit", "replica": victim,
             "t": t + 4.0},
        ] + [{"kind": "chaos", "fault": f.kind, "site": f.site,
              "peer": f.peer, "t": t + 3.0}
             for f in plan.faults if f.kind != "crash"]
        records = [
            {"fid": i, "t0": t + 20.0 + i * 0.01,
             "t1": t + 20.5 + i * 0.01, "status": "ok",
             "latency_ms": 500.0, "retry_after_ms": None,
             "resolutions": 1} for i in range(40)]
        records.append(
            {"fid": 40, "t0": t + 2.1, "t1": t + 2.2,
             "status": "shed", "latency_ms": None,
             "retry_after_ms": 120.0, "resolutions": 0})
        return events, records

    def _eval(self, events, records, plan, stats, **kw):
        from horovod_tpu.serve.soak import evaluate_serve
        args = dict(replicas=3, suspect_s=1.0, slo_p99_ms=15000.0,
                    slo_error_rate=0.02, recovery_window_s=6.0,
                    newest_version=2, kv_injected=1, kv_detected=1)
        args.update(kw)
        return evaluate_serve(records, events, plan, stats, **args)

    def test_happy_path_green(self):
        plan = self._plan()
        events, records = self._happy(plan)
        v = self._eval(events, records, plan, self._stats())
        assert v["ok"], v
        assert v["failover_s"] == pytest.approx(0.4)
        assert v["p99_outside_ms"] == 500.0

    def test_red_on_silent_drop(self):
        plan = self._plan()
        events, records = self._happy(plan)
        records[3]["status"] = "pending"
        v = self._eval(events, records, plan, self._stats())
        assert v["no_silent_drops"] is False and not v["ok"]

    def test_red_on_double_answer(self):
        plan = self._plan()
        events, records = self._happy(plan)
        records[3]["resolutions"] = 2
        v = self._eval(events, records, plan, self._stats())
        assert v["answered_once"] is False and not v["ok"]

    def test_red_on_shed_without_retry_after(self):
        plan = self._plan()
        events, records = self._happy(plan)
        records[-1]["retry_after_ms"] = None
        v = self._eval(events, records, plan, self._stats())
        assert v["shed_carry_retry_after"] is False and not v["ok"]

    def test_red_when_corrupt_never_landed(self):
        plan = self._plan()
        events, records = self._happy(plan)
        v = self._eval(events, records, plan, self._stats(),
                       kv_injected=0, kv_detected=0)
        assert v["kv_containment"] is False and not v["ok"]

    def test_red_on_late_failover(self):
        plan = self._plan()
        events, records = self._happy(plan)
        for e in events:
            if e.get("event") == "eject":
                e["t"] += 5.0           # way past 2 x suspect_s
        v = self._eval(events, records, plan, self._stats())
        assert v["failover_bounded"] is False and not v["ok"]

    def test_red_on_capacity_not_restored(self):
        plan = self._plan()
        events, records = self._happy(plan)
        stats = self._stats(up=2)
        v = self._eval(events, records, plan, stats)
        assert v["capacity_restored"] is False and not v["ok"]

    def test_slo_windows_exclude_recovery(self):
        """Slow requests fully inside a recovery window do not count
        against the SLO; the same latencies outside it do."""
        plan = self._plan()
        events, records = self._happy(plan)
        # 30 s p99 but entirely within the crash recovery window
        records.append({"fid": 99, "t0": 1002.5, "t1": 1003.0,
                        "status": "ok", "latency_ms": 30000.0,
                        "retry_after_ms": None, "resolutions": 1})
        v = self._eval(events, records, plan, self._stats())
        assert v["ok"] and v["p99_outside_ms"] == 500.0
        # the same record outside every window breaks the SLO
        records[-1]["t0"] = 1100.0
        records[-1]["t1"] = 1130.0
        v = self._eval(events, records, plan, self._stats())
        assert v["slo_held"] is False and not v["ok"]
