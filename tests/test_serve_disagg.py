"""Disaggregated serving: KV-block migration, wire frames, verdicts.

Tier-1 coverage for serve/disagg.py + serve/kv_migrate.py (the
process-level soak acceptance lives in the slow tier,
tools/serve_soak.py --disagg):

* binary wire frames + the HOROVOD_SERVE_WIRE_MAX_FRAME knob;
* migrated-KV decode BIT-IDENTICAL to colocated prefill+decode across
  {GPT, Llama-GQA} x {greedy, speculative, sampled} x prefix-CoW
  blocks (pack -> install fully in-process — the plan/transport
  split makes the transport swappable);
* corrupt-in-flight caught by the per-block crc BEFORE any token,
  version fencing, reservation-gated install rejection, parked-row
  lifecycle (release + TTL reap);
* the endpoint ops (kv_install dedupe against ladder replays,
  migrate push under serve.migrate chaos);
* evaluate_disagg: green + one red per invariant;
* aggregate_healthz per-pool breakdown (503 only at zero ADMITTING
  capacity);
* the lifted fleet front door: sampled requests routed (no 400) and
  answered identically through a mid-request failover.
"""
import json
import socket
import threading
import time
from types import SimpleNamespace

import pytest

import jax
import jax.numpy as jnp

from horovod_tpu.chaos import inject
from horovod_tpu.chaos.plan import ChaosPlan, PlanError, random_plan
from horovod_tpu.models.gpt import GPT, GPTConfig
from horovod_tpu.models.llama import Llama, LlamaConfig
from horovod_tpu.serve import kv_migrate, wire
from horovod_tpu.serve.batcher import ContinuousBatcher
from horovod_tpu.serve.executor import ShardedExecutor
from horovod_tpu.serve.fleet import (FleetRouter, Replica,
                                     aggregate_healthz)
from horovod_tpu.serve.queue import AdmissionQueue
from horovod_tpu.serve.soak import evaluate_disagg
from horovod_tpu.serve.worker import ReplicaEndpoint

_GPT_KW = dict(vocab_size=64, num_layers=2, num_heads=2, head_dim=8,
               max_seq_len=48, dtype=jnp.float32,
               attention_impl="reference")
_PAGED = dict(kv_block_size=4, kv_pool_blocks=32)
_LLAMA_KW = dict(vocab_size=64, num_layers=2, num_heads=4,
                 num_kv_heads=2, head_dim=8, max_seq_len=48,
                 dtype=jnp.float32)


@pytest.fixture(autouse=True)
def _disarm():
    inject.uninstall()
    yield
    inject.uninstall()


@pytest.fixture(scope="module")
def expool():
    """Executor cache: jit caches are per executor, so the module
    shares one per (model, role, tag) and tests build fresh batchers
    over them (the Replica.build discipline)."""
    cache = {}

    def get(model: str, role: str = "target", tag: int = 0):
        key = (model, role, tag)
        if key in cache:
            return cache[key]
        if model == "gpt":
            dec = GPT(GPTConfig(decode=True, **_GPT_KW, **_PAGED))
            draft = GPT(GPTConfig(decode=True, **_GPT_KW))
            params = GPT(GPTConfig(**_GPT_KW)).init(
                jax.random.PRNGKey(0),
                jnp.zeros((2, 8), jnp.int32))["params"]
        else:
            dec = Llama(LlamaConfig(decode=True, **_LLAMA_KW,
                                    **_PAGED))
            draft = Llama(LlamaConfig(decode=True, **_LLAMA_KW))
            params = Llama(LlamaConfig(**_LLAMA_KW)).init(
                jax.random.PRNGKey(0),
                jnp.zeros((2, 8), jnp.int32))["params"]
        model_obj = draft if role == "draft" else dec
        cache[key] = ShardedExecutor(
            model_obj, params, max_batch=4, max_len=48,
            replica_id=tag, role=role)
        return cache[key]

    return get


def _batcher(expool, model="gpt", tag=0, *, spec=False, kv_crc=True,
             prefix=True, max_queue=16):
    q = AdmissionQueue(max_queue=max_queue,
                       default_deadline_ms=20000.0, replica_id=tag)
    b = ContinuousBatcher(
        expool(model, "target", tag), q, buckets=(8,),
        replica_id=tag, kv_crc=kv_crc,
        draft_executor=expool(model, "draft", tag) if spec else None,
        spec_k=3 if spec else 0, prefix_cache=prefix)
    b.warmup()
    return b


def _pack(b, handle, max_new, deadline_ms=20000.0, fid="d0"):
    return kv_migrate.pack_parked(b, handle.rid, fid=fid,
                                  max_new_tokens=max_new,
                                  deadline_ms=deadline_ms)


def _migrate_run(prefill_b, decode_b, prompt, max_new, **sampling):
    """Full in-process disagg leg: hold-prefill, pack, install, decode
    to completion; returns the token stream."""
    h1 = prefill_b.queue.submit(prompt, max_new_tokens=1,
                                hold_kv=True, **sampling)
    prefill_b.run()
    assert h1.status == "ok" and len(h1.tokens) == 1
    header, payload = _pack(prefill_b, h1, max_new,
                            fid=f"d{h1.rid}")
    decode_b.start()
    try:
        outcome, detail, h2 = kv_migrate.install(decode_b, header,
                                                 payload)
        assert outcome == "installed", (outcome, detail)
        assert h2.wait(timeout=30)
    finally:
        decode_b.stop()
    prefill_b.release_parked(h1.rid)
    prefill_b.run()
    assert h2.status == "ok"
    return h2.tokens


# ---------------------------------------------------------------------------
# binary wire frames + the max-frame knob
# ---------------------------------------------------------------------------

class TestWireBinary:
    def test_roundtrip_and_crc(self):
        a, b = socket.socketpair()
        try:
            payload = bytes(range(256)) * 17
            import zlib
            wire.send_bin(a, {"op": "kv_install", "x": 1,
                              "payload_crc": zlib.crc32(payload)},
                          payload)
            obj, got = wire.recv_any(b, timeout=5.0)
            assert obj["x"] == 1 and got == payload
            # plain JSON frames pass through recv_any with payload None
            wire.send_msg(a, {"op": "healthz"})
            obj, got = wire.recv_any(b, timeout=5.0)
            assert obj == {"op": "healthz"} and got is None
        finally:
            a.close()
            b.close()

    def test_frame_crc_catches_wire_corruption(self):
        a, b = socket.socketpair()
        try:
            payload = b"\x00" * 400
            wire.send_bin(a, {"payload_crc": 12345}, payload)
            with pytest.raises(wire.DispatchError, match="crc32"):
                wire.recv_any(b, timeout=5.0)
        finally:
            a.close()
            b.close()

    def test_oversize_names_the_knob(self, monkeypatch):
        monkeypatch.setenv("HOROVOD_SERVE_WIRE_MAX_FRAME",
                           str(1 << 16))
        wire._reset_max_frame_cache()
        try:
            a, b = socket.socketpair()
            try:
                with pytest.raises(wire.DispatchError,
                                   match="HOROVOD_SERVE_WIRE_MAX_FRAME"):
                    wire.send_bin(a, {}, b"\x00" * (1 << 17))
            finally:
                a.close()
                b.close()
        finally:
            monkeypatch.delenv("HOROVOD_SERVE_WIRE_MAX_FRAME")
            wire._reset_max_frame_cache()

    def test_knob_strict_parse_and_range(self, monkeypatch):
        from horovod_tpu.core.config import Config
        monkeypatch.setenv("HOROVOD_SERVE_WIRE_MAX_FRAME", "huge")
        with pytest.raises(ValueError, match="WIRE_MAX_FRAME"):
            Config.from_env()
        monkeypatch.setenv("HOROVOD_SERVE_WIRE_MAX_FRAME", "1024")
        with pytest.raises(ValueError, match="WIRE_MAX_FRAME"):
            Config.from_env()
        monkeypatch.setenv("HOROVOD_SERVE_WIRE_MAX_FRAME",
                           str(64 << 20))
        assert Config.from_env().serve_wire_max_frame == 64 << 20


# ---------------------------------------------------------------------------
# migrated-KV decode bit-identical to colocated prefill+decode
# ---------------------------------------------------------------------------

class TestMigrationParity:
    @pytest.mark.parametrize("model", ["gpt", "llama"])
    @pytest.mark.parametrize("mode", ["greedy", "spec", "sampled"])
    def test_bit_identical_with_prefix_cow(self, expool, model, mode):
        """Two requests per stack: A seeds the radix prefix cache, B
        shares A's prefix and diverges MID-BLOCK (a CoW block joins
        B's table). Both streams must match the colocated reference
        bit for bit — including B, whose migrated payload carries a
        copy-on-written block. ``spec`` runs greedy speculative
        decoding on the DECODE side only (the drafter re-syncs from
        the migrated prefix via forced feeds; greedy spec is
        bit-identical to target-only greedy by construction)."""
        spec = mode == "spec"
        sampling = ({"temperature": 0.8, "top_p": 0.9, "seed": 123}
                    if mode == "sampled" else {})
        prompt_a = [5, 9, 3, 17, 2, 11, 7]          # blocks: 4 + 3
        prompt_b = prompt_a[:5] + [40, 41]          # diverges mid-blk 2
        # colocated reference (prefill+decode in one batcher)
        ref = _batcher(expool, model, tag=0, spec=spec)
        ha = ref.queue.submit(prompt_a, max_new_tokens=8, **sampling)
        ref.run()
        hb = ref.queue.submit(prompt_b, max_new_tokens=8, **sampling)
        ref.run()
        assert ha.status == "ok" and hb.status == "ok"
        # disaggregated: prefill batcher (no drafter) -> migrate ->
        # decode batcher (drafter when spec)
        pre = _batcher(expool, model, tag=1, spec=False)
        dec = _batcher(expool, model, tag=2, spec=spec)
        toks_a = _migrate_run(pre, dec, prompt_a, 8, **sampling)
        assert toks_a == ha.tokens, (toks_a, ha.tokens)
        toks_b = _migrate_run(pre, dec, prompt_b, 8, **sampling)
        assert toks_b == hb.tokens, (toks_b, hb.tokens)
        # B's prefill really did hit the prefix cache (CoW exercised)
        assert pre.prefix is not None and pre.prefix.hits >= 1


# ---------------------------------------------------------------------------
# integrity: corrupt-in-flight, version fence, reservation gate, parking
# ---------------------------------------------------------------------------

class TestMigrationIntegrity:
    def _packet(self, expool, tag, max_new=10):
        pre = _batcher(expool, "gpt", tag=tag)
        h = pre.queue.submit([5, 9, 3, 17, 2], max_new_tokens=1,
                             hold_kv=True)
        pre.run()
        header, payload = _pack(pre, h, max_new, fid=f"t{tag}")
        return pre, h, header, payload

    def test_corrupt_in_flight_caught_before_any_token(self, expool):
        _, _, header, payload = self._packet(expool, 1)
        bad = bytearray(payload)
        bad[13] ^= 0x10
        dec = _batcher(expool, "gpt", tag=2)
        outcome, detail, handle = kv_migrate.install(
            dec, header, bytes(bad), timeout_s=1.0)
        assert outcome == "corrupt" and handle is None
        assert dec.migrate_corrupt_detected == 1
        assert dec.migrations_in == 0 and not dec._active

    def test_truncated_payload_is_corrupt(self, expool):
        _, _, header, payload = self._packet(expool, 1)
        dec = _batcher(expool, "gpt", tag=2)
        outcome, _, _ = kv_migrate.install(dec, header,
                                           payload[:-8], timeout_s=1.0)
        assert outcome == "corrupt"

    def test_version_fence_refuses_mismatch(self, expool):
        _, _, header, payload = self._packet(expool, 1)
        dec = _batcher(expool, "gpt", tag=2)
        stale = dict(header, weights_version=41)   # decode runs None
        ent = dec.submit_migrated(
            stale, kv_migrate.unpack_blocks(stale, payload))
        dec.run()
        assert ent["outcome"][0] == "version_mismatch"
        assert dec.migrations_in == 0 and not dec._active

    def test_packet_stamps_prefill_version_not_pack_version(
            self, expool):
        """A hot swap landing between prefill and pack must fence the
        packet OUT: the stamped version is the one the PREFILL ran
        under, not whatever the executor serves at pack time."""
        pre = _batcher(expool, "gpt", tag=1)
        h = pre.queue.submit([5, 9, 3], max_new_tokens=1, hold_kv=True)
        pre.run()
        ex = pre.executor
        ran_under = ex.last_step_version
        ex.params_version = 7          # a swap landed after the park
        try:
            header, _ = _pack(pre, h, 8)
        finally:
            ex.params_version = ran_under
        assert header["weights_version"] == ran_under != 7

    def test_reservation_gated_rejection(self, expool):
        """An install that would starve admitted sequences is refused
        with a structured retry hint — the same can_admit gate local
        newcomers pass through."""
        pre, h, header, payload = self._packet(expool, 1, max_new=40)
        dec = _batcher(expool, "gpt", tag=2)
        # ... with its pool mostly RESERVED by local admissions
        # (3 rows x ~10-block worst case against a 32-block pool)
        for _ in range(3):
            dec.queue.submit(list(range(1, 8)), max_new_tokens=30)
        dec.step()     # admit + reserve their worst-case growth
        assert dec.kv.reserved_total() > 0
        ent = dec.submit_migrated(
            header, kv_migrate.unpack_blocks(header, payload))
        dec.step()     # the install decision, on this thread
        outcome, detail = ent["outcome"]
        assert outcome == "rejected" and detail is not None
        assert dec.migrate_rejects == 1

    def test_every_replica_is_a_pool_to_hand_off_to(self, expool):
        """A decode replica whose config names no pool size is still a
        pool: the hand-off installs and continues bit-identically. The
        one layout answer left is a block size that differs."""
        def replica(tag, **kv):
            ex = ShardedExecutor(
                GPT(GPTConfig(decode=True, **_GPT_KW, **kv)),
                expool("gpt", "target", 1).params, max_batch=4,
                max_len=48, replica_id=tag)
            b = ContinuousBatcher(
                ex, AdmissionQueue(max_queue=16, replica_id=tag),
                buckets=(8,), replica_id=tag, kv_crc=True,
                prefix_cache=False)
            b.warmup()
            return b

        prompt = [5, 9, 3, 17, 2]
        plain = _batcher(expool, "gpt", tag=3)
        want = plain.queue.submit(prompt, max_new_tokens=8)
        plain.run()
        dec = replica(4, kv_block_size=4)      # 4 rows x 12 blocks
        assert dec.kv.pool.num_blocks == 48
        got = _migrate_run(_batcher(expool, "gpt", tag=1), dec,
                           prompt, 8)
        assert got == want.tokens and dec.migrations_in == 1

        _, _, header, payload = self._packet(expool, 1)
        other = replica(5)                     # the default block of 16
        ent = other.submit_migrated(
            header, kv_migrate.unpack_blocks(header, payload))
        other.run()
        outcome, detail = ent["outcome"]
        assert outcome == "incompatible" and "block size" in detail
        assert other.migrations_in == 0 and not other._active

    def test_release_and_ttl_reap(self, expool):
        pre = _batcher(expool, "gpt", tag=1)
        h1 = pre.queue.submit([1, 2, 3], max_new_tokens=1,
                              hold_kv=True)
        h2 = pre.queue.submit([4, 5, 6], max_new_tokens=1,
                              hold_kv=True, deadline_ms=50.0)
        pre.run()
        assert len(pre.parked) == 2
        in_use = pre.kv.pool.in_use()
        # explicit release frees the row on the next iteration
        pre.release_parked(h1.rid)
        pre.run()
        assert h1.rid not in pre.parked
        assert pre.kv.pool.in_use() < in_use
        assert kv_migrate.pack_parked(pre, h1.rid, fid="x",
                                      max_new_tokens=4,
                                      deadline_ms=100.0) is None
        # the TTL reaper frees an abandoned parked row past its
        # deadline + grace (the router died mid-orchestration)
        pre.parked_grace_s = 0.0
        time.sleep(0.08)
        pre.step()
        assert h2.rid not in pre.parked and pre.parked_reaped == 1

    def test_hold_kv_resolves_without_blocking_decode(self, expool):
        """A parked sequence must not hold a DECODE row hostage: the
        row leaves _active at park, so max_batch stays available."""
        pre = _batcher(expool, "gpt", tag=1)
        h = pre.queue.submit([1, 2, 3], max_new_tokens=1, hold_kv=True)
        pre.run()
        assert h.status == "ok" and not pre._active
        assert len(pre.parked) == 1


# ---------------------------------------------------------------------------
# endpoint ops: kv_install dedupe, migrate push under chaos
# ---------------------------------------------------------------------------

class TestEndpointMigration:
    def _endpoint(self, expool, tag):
        b = _batcher(expool, "gpt", tag=tag)
        b.start()
        ep = ReplicaEndpoint(b, rid=tag).start()
        return SimpleNamespace(b=b, ep=ep)

    def test_kv_install_replay_deduped(self, expool):
        pre = _batcher(expool, "gpt", tag=1)
        h = pre.queue.submit([5, 9, 3, 17, 2], max_new_tokens=1,
                             hold_kv=True)
        pre.run()
        header, payload = _pack(pre, h, 6, fid="dd1")
        dec = self._endpoint(expool, 2)
        try:
            for i in range(2):
                s = wire.connect(dec.ep.address, timeout=2.0)
                try:
                    wire.send_bin(s, header, payload)
                    ack = wire.recv_msg(s, timeout=20.0)
                finally:
                    s.close()
                assert ack["ack"] == "installed"
                if i == 1:
                    assert ack["dedupe"] is True
            assert dec.b.migrations_in == 1   # installed exactly once
            assert dec.ep.dedupe_hits == 1
            # the result op serves the finished stream (and replays
            # from the cache)
            for _ in range(2):
                s = wire.connect(dec.ep.address, timeout=2.0)
                try:
                    wire.send_msg(s, {"op": "result", "fid": "dd1",
                                      "deadline_ms": 10000.0})
                    ack = wire.recv_msg(s, timeout=5.0)
                    assert ack["ack"] == "accepted"
                    reply = wire.recv_msg(s, timeout=20.0)
                finally:
                    s.close()
                assert reply["status"] == "ok"
                assert len(reply["tokens"]) == 6
            # unknown fid is a structured miss, not a hang
            s = wire.connect(dec.ep.address, timeout=2.0)
            try:
                wire.send_msg(s, {"op": "result", "fid": "nope"})
                assert wire.recv_msg(s, timeout=5.0)["ack"] == \
                    "unknown_fid"
            finally:
                s.close()
        finally:
            dec.ep.close()
            dec.b.stop()

    def test_push_chaos_corrupt_and_conn_reset(self, expool):
        """serve.migrate chaos at the push: a corrupt is caught by the
        BLOCK crc on arrival (frame crc deliberately passes), a
        conn_reset after the frame lands is absorbed by the ladder
        with the replay served the deduped install ack."""
        pre = _batcher(expool, "gpt", tag=1)
        for fid, kind, at in (("c1", "corrupt", 0),
                              ("c2", "conn_reset", 0)):
            h = pre.queue.submit([5, 9, 3], max_new_tokens=1,
                                 hold_kv=True)
            pre.run()
            header, payload = _pack(pre, h, 6, fid=fid)
            if kind == "corrupt":
                plan = ChaosPlan.from_dict({"seed": 3, "faults": [
                    {"rank": 0, "site": "serve.migrate",
                     "kind": "corrupt", "at": at}]})
            else:
                plan = ChaosPlan.from_dict({"seed": 3, "faults": [
                    {"rank": 0, "site": "serve.migrate",
                     "kind": "conn_reset", "at": at}]})
            inject.install(plan, rank=0)
            dec = self._endpoint(expool, 2)
            try:
                ack = kv_migrate.push(dec.ep.address, header, payload)
                if kind == "corrupt":
                    assert ack["ack"] == "migrate_corrupt"
                    assert dec.b.migrate_corrupt_detected == 1
                    assert dec.b.migrations_in == 0
                else:
                    # the frame landed, the ack was severed: the
                    # ladder replay hits the install dedupe
                    assert ack["ack"] == "installed"
                    assert ack["dedupe"] is True
                    assert dec.b.migrations_in == 1
            finally:
                dec.ep.close()
                dec.b.stop()
                inject.uninstall()
                pre.release_parked(h.rid)
                pre.run()


# ---------------------------------------------------------------------------
# the disagg verdict: green + one red per invariant
# ---------------------------------------------------------------------------

def _disagg_fixture():
    plan = random_plan(7, 3, 240, profile="disagg", prefill=2)
    kill = next(f for f in plan.faults if f.kind == "crash")
    victim = kill.peer
    records = [{"fid": i, "t0": 1.0 + i, "t1": 1.05 + i,
                "status": "ok", "latency_ms": 50.0,
                "retry_after_ms": None, "resolutions": 1}
               for i in range(30)]
    events = [
        {"kind": "chaos", "fault": "crash", "site": "serve.proc",
         "peer": victim, "t": 100.0},
        {"kind": "fleet", "event": "eject", "replica": victim,
         "t": 101.0},
        {"kind": "fleet", "event": "readmit", "replica": victim,
         "weights_version": 2, "t": 108.0},
    ]
    stats = {
        "replicas_up": 3, "inflight": 0, "failovers": 1,
        "respawns": 1, "duplicates_suppressed": 0,
        "replicas": {r: {"weights_version": 2} for r in range(3)},
    }
    return plan, records, events, stats


def _eval_disagg(plan, records, events, stats, **kw):
    base = dict(replicas=3, suspect_s=1.0, slo_p99_ms=15000.0,
                slo_error_rate=0.02, recovery_window_s=6.0,
                newest_version=2, migrations_in=40,
                migrate_absorbed=1, migrate_corrupt_detected=2,
                reprefills=1)
    base.update(kw)
    return evaluate_disagg(records, events, plan, stats, **base)


class TestDisaggVerdict:
    def test_green(self):
        v = _eval_disagg(*_disagg_fixture())
        assert v["migrations_ok"] is True
        assert v["migrate_corrupt_caught"] is True
        assert v["migrate_blips_recovered"] is True
        assert v["failovers_only_kills"] is True
        assert v["respawned_on_newest"] is True
        assert v["ok"] is True, json.dumps(v, indent=2, default=str)

    def test_red_no_migrations(self):
        v = _eval_disagg(*_disagg_fixture(), migrations_in=0)
        assert v["migrations_ok"] is False and v["ok"] is False

    def test_red_corrupt_not_caught(self):
        v = _eval_disagg(*_disagg_fixture(),
                         migrate_corrupt_detected=0)
        assert v["migrate_corrupt_caught"] is False
        assert v["ok"] is False

    def test_red_blip_not_recovered(self):
        v = _eval_disagg(*_disagg_fixture(), migrate_absorbed=0,
                         reprefills=0)
        assert v["migrate_blips_recovered"] is False
        assert v["ok"] is False

    def test_red_migration_chaos_escalated_to_failover(self):
        plan, records, events, stats = _disagg_fixture()
        v = _eval_disagg(plan, records, events,
                         dict(stats, failovers=2))
        assert v["failovers_only_kills"] is False and v["ok"] is False

    def test_red_prefill_respawn_on_stale_weights(self):
        plan, records, events, stats = _disagg_fixture()
        events = [dict(e) for e in events]
        for e in events:
            if e.get("event") == "readmit":
                e["weights_version"] = 1
        v = _eval_disagg(plan, records, events, stats)
        assert v["respawned_on_newest"] is False and v["ok"] is False

    def test_red_unbounded_prefill_failover(self):
        plan, records, events, stats = _disagg_fixture()
        events = [dict(e) for e in events]
        for e in events:
            if e.get("event") == "eject":
                e["t"] = 103.5
        v = _eval_disagg(plan, records, events, stats)
        assert v["failover_bounded"] is False and v["ok"] is False


class TestDisaggPlan:
    def test_deterministic_and_composed(self):
        p1 = random_plan(9, 3, 120, profile="disagg", prefill=2)
        p2 = random_plan(9, 3, 120, profile="disagg", prefill=2)
        assert p1.to_json() == p2.to_json()
        sites = {(f.site, f.kind) for f in p1.faults}
        assert ("serve.proc", "crash") in sites
        assert ("serve.migrate", "conn_reset") in sites
        assert ("serve.migrate", "corrupt") in sites
        kill = next(f for f in p1.faults if f.kind == "crash")
        assert 0 <= kill.peer < 2          # a PREFILL replica
        for f in p1.faults:
            if f.site == "serve.migrate":
                assert f.peer == 2         # the decode replica

    def test_fail_fast(self):
        with pytest.raises(PlanError, match="prefill"):
            random_plan(9, 2, 120, profile="disagg", prefill=1)
        with pytest.raises(PlanError, match="decode"):
            random_plan(9, 2, 120, profile="disagg", prefill=2)
        with pytest.raises(PlanError, match="disagg"):
            random_plan(9, 3, 120, profile="serve", prefill=2)


# ---------------------------------------------------------------------------
# per-pool healthz: 503 only at zero ADMITTING capacity
# ---------------------------------------------------------------------------

class TestHealthzPools:
    def _infos(self, pre_free, dec_free):
        return {
            0: {"state": "up", "up": True, "draining": False,
                "queue_depth": 0, "weights_version": 1, "restarts": 0,
                "queue_free": pre_free, "kv_blocks_total": 32,
                "kv_blocks_in_use": 0},
            1: {"state": "up", "up": True, "draining": False,
                "queue_depth": 0, "weights_version": 1, "restarts": 0,
                "queue_free": dec_free, "kv_blocks_total": 32,
                "kv_blocks_in_use": 30},
        }

    def _pools(self):
        return {"prefill": {"replicas": [0], "admitting": True},
                "decode": {"replicas": [1], "admitting": False,
                           "migration_backlog": 3}}

    def test_decode_saturation_degrades_not_503(self):
        out = aggregate_healthz(self._infos(8, 0), draining=False,
                                retry_after_ms=250.0,
                                pools=self._pools())
        assert out["ok"] is True               # prefill still admits
        assert out["degraded"] == ["decode"]
        assert out["pools"]["decode"]["migration_backlog"] == 3
        assert out["pools"]["prefill"]["admitting"] is True

    def test_zero_prefill_capacity_is_503(self):
        out = aggregate_healthz(self._infos(0, 8), draining=False,
                                retry_after_ms=250.0,
                                pools=self._pools())
        assert out["ok"] is False              # admitting pool is full
        assert "prefill" in out["degraded"]

    def test_draining_is_503_and_poolless_unchanged(self):
        out = aggregate_healthz(self._infos(8, 8), draining=True,
                                retry_after_ms=250.0,
                                pools=self._pools())
        assert out["ok"] is False
        legacy = aggregate_healthz(self._infos(8, 8), draining=False,
                                   retry_after_ms=250.0)
        assert legacy["ok"] is True and "pools" not in legacy


# ---------------------------------------------------------------------------
# fleet front door: sampled requests routed, failover-identical
# ---------------------------------------------------------------------------

class TestSampledFleet:
    def _router(self, expool, tags):
        reps = [Replica(t, expool("gpt", "target", t), buckets=(8,),
                        max_queue=16, deadline_ms=20000.0,
                        kv_crc=False, spec_k=0, prefix_cache=False)
                for t in tags]
        return FleetRouter(reps, interval_s=0.05, suspect_s=0.2,
                           auto_restart=False)

    def test_sampled_identical_through_mid_request_failover(
            self, expool):
        """THE regression for the lifted greedy-only restriction: a
        sampled request re-dispatched by a mid-request failover
        answers exactly what the no-failover run answers — per-row
        seeded streams replay deterministically from counter 0."""
        sampling = dict(temperature=0.9, top_p=0.85, seed=77)
        prompt = [5, 9, 3, 17, 2]
        ref_router = self._router(expool, (0, 1)).start()
        try:
            href = ref_router.submit(prompt, max_new_tokens=12,
                                     **sampling)
            assert href.wait(timeout=30) and href.status == "ok"
        finally:
            ref_router.close()
        router = self._router(expool, (0, 1)).start()
        try:
            h = router.submit(prompt, max_new_tokens=12, **sampling)
            with router._lock:
                tr = router._inflight.get(h.fid)
            if tr is not None and tr.rid is not None:
                router._eject(tr.rid, "test: mid-request failover")
            assert h.wait(timeout=30)
            assert h.status == "ok"
            assert h.tokens == href.tokens, (h.tokens, href.tokens)
        finally:
            router.close()

    def test_fleet_front_door_serves_sampled(self, expool):
        """The structured 400 for temperature > 0 is GONE: the fleet
        HTTP face routes sampled requests (and still 400s malformed
        sampling values at the door)."""
        import http.client

        from horovod_tpu.serve.http import make_fleet_server
        router = self._router(expool, (0, 1)).start()
        srv = make_fleet_server(router)
        t = threading.Thread(target=srv.serve_forever, daemon=True)
        t.start()
        host, port = srv.server_address[:2]
        try:
            conn = http.client.HTTPConnection(host, port, timeout=30)
            body = json.dumps({"tokens": [5, 9, 3], "max_new_tokens": 6,
                               "temperature": 0.7, "seed": 5})
            conn.request("POST", "/generate", body,
                         {"Content-Type": "application/json"})
            resp = conn.getresponse()
            out = json.loads(resp.read())
            assert resp.status == 200, out
            assert len(out["tokens"]) == 6
            # direct submit with the same seed answers identically
            h = router.submit([5, 9, 3], max_new_tokens=6,
                              temperature=0.7, seed=5)
            assert h.wait(timeout=30) and h.tokens == out["tokens"]
            # malformed sampling stays a structured 400
            conn.request("POST", "/generate", json.dumps(
                {"tokens": [1], "temperature": -1.0}),
                {"Content-Type": "application/json"})
            resp = conn.getresponse()
            assert resp.status == 400
            resp.read()
            conn.close()
        finally:
            srv.shutdown()
            srv.server_close()
            router.close()
