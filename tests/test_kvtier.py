"""Fleet KV tier: eviction ladder, fleet radix index, spill format.

The ISSUE 19 bars (docs/serving.md, fleet-KV-tier section):

* the hvdkv-v1 spill format round-trips byte-identically (atomic
  write, crc32 ledger per leaf + payload crc) and every tier structure
  (HostRing LRU byte bound, DiskTier init scan + token re-verify)
  keeps its contract;
* the prefix cache's eviction hook emits a structured event BEFORE the
  decref (the block is still readable) and evicts LRU
  deepest-refcount-zero-first; a failing hook degrades to plain
  eviction, never an error;
* a demoted run promotes back HBM -> host -> disk bit-identically
  (same tokens as a cold prefill), crc-checked at every hop, with the
  weight-version fence refusing runs demoted under other weights;
* chaos ``kvtier.demote`` / ``kvtier.promote`` corrupt is caught by
  the crc gate before any device byte (re-prefill yields baseline
  tokens); drop degrades to re-prefill, never an error;
* the fleet radix index folds insert/demote/drop/flush events into
  contiguous-from-root lookups with version fencing, and
  ``prefer_holders`` orders candidates deepest-run-first; the
  in-process router builds the index from drained events and routes a
  returning conversation to its holder;
* a cross-replica pull round-trips over the kv_migrate wire shape and
  a corrupted payload is refused by ``unpack_blocks``;
* ``pack_parked`` on a prefix-shared (refcount-held) source stays
  byte-identical under a copy-on-write divergence by another request;
* ``aggregate_healthz`` rolls per-replica prefix-cache TOKEN counts
  into the fleet capacity payload;
* ``tools/kvtier_inspect.py`` lists/shows/verifies spill dirs with
  exit 1 on a crc mismatch, without ever importing jax.
"""
import json
import os
import subprocess
import sys
import time
import zlib
from types import SimpleNamespace

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from horovod_tpu.chaos import inject
from horovod_tpu.chaos.plan import ChaosPlan
from horovod_tpu.models.gpt import GPT, GPTConfig
from horovod_tpu.serve import (AdmissionQueue, ContinuousBatcher,
                               DiskTier, FleetRadixIndex, FleetRouter,
                               HostRing, Replica, ShardedExecutor,
                               TierEntry, kv_migrate, prefer_holders,
                               read_spill_file)
from horovod_tpu.serve.fleet import aggregate_healthz
from horovod_tpu.serve.kvtier.tier import (spill_file_name,
                                           write_spill_file)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_KW = dict(vocab_size=64, num_layers=2, num_heads=2, head_dim=8,
           max_seq_len=48, dtype=jnp.float32, attention_impl="reference")
_BS, _POOL = 4, 32
#: shared "system prompt": 17 tokens = 4 full blocks + 1 partial
_SYS = [3, 1, 4, 1, 5, 9, 2, 6, 5, 3, 5, 8, 9, 7, 9, 3, 2]


@pytest.fixture(autouse=True)
def _disarm():
    inject.uninstall()
    yield
    inject.uninstall()


@pytest.fixture(scope="module")
def gpt():
    train = GPT(GPTConfig(**_KW))
    paged = GPT(GPTConfig(decode=True, **_KW, kv_block_size=_BS,
                          kv_pool_blocks=_POOL))
    params = train.init(jax.random.PRNGKey(0),
                        jnp.zeros((2, 8), jnp.int32))["params"]
    return SimpleNamespace(paged=paged, params=params)


@pytest.fixture(scope="module")
def expool(gpt):
    """One PAGED executor per replica id, shared across batchers (the
    Replica.build discipline — jit caches are the expensive part)."""
    cache = {}

    def get(rid=None):
        if rid not in cache:
            cache[rid] = ShardedExecutor(
                gpt.paged, gpt.params, max_batch=4,
                max_len=_KW["max_seq_len"], replica_id=rid)
        return cache[rid]

    return get


def _batcher(expool, *, rid=None, kv_tier=False, host_mb=1,
             tier_dir=None, kv_crc=True, max_queue=16):
    q = AdmissionQueue(max_queue=max_queue,
                       default_deadline_ms=20000.0, replica_id=rid)
    b = ContinuousBatcher(
        expool(rid), q, buckets=(8, 40), replica_id=rid,
        kv_crc=kv_crc, prefix_cache=True, kv_tier=kv_tier,
        kvtier_host_mb=host_mb,
        kvtier_dir=None if tier_dir is None else str(tier_dir))
    b.warmup()
    return b


def _serve(b, prompt, max_new=4):
    h = b.queue.submit(list(prompt), max_new_tokens=max_new)
    b.run()
    assert h.done() and h.status == "ok", (h.status, h.error)
    return list(h.tokens)


def _evict_all(b):
    """Demote every refcount-zero prefix run down the ladder."""
    b.run()
    while b.prefix.evictable_blocks() > 0:
        assert b.prefix.evict(64) > 0


def _entry(tokens=(1, 2, 3, 4), version=3, fill=b"\x5a"):
    leaf_bytes = [bytes([i]) * 8 + fill * 8 for i in range(2)]
    return TierEntry(tokens, leaf_bytes,
                     [zlib.crc32(x) for x in leaf_bytes],
                     len(tokens), version)


# ---------------------------------------------------------------------------
# spill format + tier structures (jax-free plumbing)
# ---------------------------------------------------------------------------

class TestSpillFormat:
    def test_spill_file_roundtrip(self, tmp_path):
        e = _entry()
        path = str(tmp_path / spill_file_name(e.tokens))
        write_spill_file(path, e, _BS)
        header, payload = read_spill_file(path)
        assert header["format"] == "hvdkv-v1"
        assert header["tokens"] == list(e.tokens)
        assert header["block_size"] == _BS
        assert header["weights_version"] == e.version
        assert header["payload_crc"] == zlib.crc32(payload)
        leaves, off = [], 0
        for n in header["nbytes"]:
            leaves.append(payload[off:off + n])
            off += n
        assert leaves == e.leaf_bytes
        assert e.verify(leaves)
        assert not (tmp_path / (spill_file_name(e.tokens)
                                + ".tmp")).exists()

    def test_verify_catches_a_flip(self):
        e = _entry()
        bad = list(e.leaf_bytes)
        bad[1] = bytes([bad[1][0] ^ 0x01]) + bad[1][1:]
        assert e.verify() and not e.verify(bad)

    def test_disk_tier_scan_and_collision_reverify(self, tmp_path):
        d = DiskTier(str(tmp_path))
        e = _entry()
        assert d.put(e, _BS)
        # a NEW DiskTier over the same root rediscovers membership
        d2 = DiskTier(str(tmp_path))
        assert d2.count() == 1 and d2.contains(e.tokens)
        got = d2.get(e.tokens)
        assert got.leaf_bytes == e.leaf_bytes
        assert got.crcs == e.crcs and got.version == e.version
        # a file-name collision (same crc, different tokens) must be a
        # MISS: get() re-verifies the header token list against the key
        other = (9, 9, 9, 9)
        d2._files[other] = d2._files[e.tokens]
        assert d2.get(other) is None
        d2.pop(e.tokens)
        assert not d2.contains(e.tokens) and d2.count() == 1

    def test_disk_tier_skips_unreadable_files(self, tmp_path):
        (tmp_path / "junk.hvdkv").write_bytes(b"not a spill file")
        d = DiskTier(str(tmp_path))
        assert d.count() == 0

    def test_host_ring_lru_byte_bound(self):
        a = _entry((1,) * 4, fill=b"\xa0")
        b = _entry((2,) * 4, fill=b"\xb0")
        c = _entry((3,) * 4, fill=b"\xc0")
        ring = HostRing(2 * a.nbytes)
        assert ring.put(a) == [] and ring.put(b) == []
        # the bound pushes out the OLDEST entry
        assert ring.put(c) == [a]
        assert ring.get(a.tokens) is None
        # get() refreshes recency: b survives the next overflow
        assert ring.get(b.tokens) is b
        d = _entry((4,) * 4, fill=b"\xd0")
        assert ring.put(d) == [c]
        assert ring.count() == 2 and ring.bytes() == 2 * a.nbytes
        assert ring.pop(b.tokens) is b and ring.pop(b.tokens) is None


# ---------------------------------------------------------------------------
# prefix-cache eviction hook (satellite: structured events, LRU order)
# ---------------------------------------------------------------------------

class TestEvictionHook:
    def test_event_fields_and_pre_decref_ordering(self, expool):
        b = _batcher(expool)
        _serve(b, _SYS + [5, 6])
        captured = []

        def hook(ev):
            # fired BEFORE the decref: the tree still owns the block,
            # so a demotion subscriber can read its device bytes
            assert b.prefix.pool.refcount[ev["block"]] == 1
            captured.append(ev)

        b.prefix.on_evict = hook
        _evict_all(b)
        assert captured, "eviction emitted no events"
        for ev in captured:
            assert set(ev) == {"run", "tokens", "block", "blocks",
                               "token_len"}
            assert len(ev["run"]) == 8 and int(ev["run"], 16) >= 0
            assert ev["token_len"] == len(ev["tokens"])
            assert ev["blocks"] == ev["token_len"] // _BS

    def test_lru_deepest_refcount_zero_first(self, expool):
        b = _batcher(expool)
        t1 = _serve(b, _SYS + [5, 6])
        assert _serve(b, _SYS + [5, 6]) == t1  # shared-prefix reuse
        # a second conversation diverging at block 3 grows a branch
        _serve(b, _SYS[:12] + [9, 10, 11, 12, 13, 14, 15])
        captured = []
        b.prefix.on_evict = captured.append
        _evict_all(b)
        depths = [ev["blocks"] for ev in captured]
        # both branch leaves (depth 4) go before the shared chain,
        # which then cascades leaf-first: 3, 2, 1
        assert depths == [4, 4, 3, 2, 1], depths

    def test_failing_hook_degrades_to_plain_eviction(self, expool):
        b = _batcher(expool)
        _serve(b, _SYS + [5, 6])

        def hook(ev):
            raise RuntimeError("demotion subsystem on fire")

        b.prefix.on_evict = hook
        assert b.prefix.evictable_blocks() > 0
        _evict_all(b)
        assert b.prefix.evictable_blocks() == 0


# ---------------------------------------------------------------------------
# ladder round-trip: HBM -> host / disk -> HBM, bit-identical + fenced
# ---------------------------------------------------------------------------

class TestLadderRoundTrip:
    def _conversation(self, b):
        first = _serve(b, _SYS + [5, 6])
        return _SYS + [5, 6] + first + [7]

    def test_host_rung_bit_identical(self, expool, tmp_path):
        base = _batcher(expool)
        returning = self._conversation(base)
        base_tokens = _serve(base, returning)

        b = _batcher(expool, kv_tier=True, host_mb=1,
                     tier_dir=tmp_path)
        assert self._conversation(b) == returning
        _evict_all(b)
        st = b.kvtier.stats()
        assert st["demoted_blocks"] > 0 and st["host_runs"] > 0
        assert _serve(b, returning) == base_tokens
        st = b.kvtier.stats()
        assert st["promoted_blocks"] >= 4, st
        assert st["corrupt_detected"] == 0

    def test_disk_rung_spills_and_promotes(self, expool, tmp_path):
        base = _batcher(expool)
        returning = self._conversation(base)
        base_tokens = _serve(base, returning)

        # host_mb=0: every demotion overflows the ring straight to disk
        b = _batcher(expool, kv_tier=True, host_mb=0,
                     tier_dir=tmp_path)
        assert self._conversation(b) == returning
        _evict_all(b)
        st = b.kvtier.stats()
        assert st["host_runs"] == 0 and st["disk_runs"] > 0
        spills = [f for f in os.listdir(tmp_path)
                  if f.endswith(".hvdkv")]
        assert len(spills) == st["disk_runs"]
        assert _serve(b, returning) == base_tokens
        assert b.kvtier.stats()["promoted_blocks"] >= 4

    def test_version_fence_refuses_stale_runs(self, expool, tmp_path):
        b = _batcher(expool, rid=5, kv_tier=True, host_mb=1,
                     tier_dir=tmp_path)
        returning = self._conversation(b)
        _evict_all(b)
        held = b.kvtier.stats()["host_runs"]
        assert held > 0
        ex = b.executor
        v0 = ex.params_version
        try:
            ex.params_version = (v0 or 0) + 7
            # the run demoted under v0 must never install under v0+7 —
            # the request re-prefills (params are unchanged, so the
            # tokens still match; only the fence stamp moved)
            _serve(b, returning)
            st = b.kvtier.stats()
            assert st["promoted_blocks"] == 0, st
            assert st["host_runs"] < held  # fenced run was discarded
        finally:
            ex.params_version = v0

    def test_weight_flush_clears_host_tier(self, expool, tmp_path):
        b = _batcher(expool, kv_tier=True, host_mb=1,
                     tier_dir=tmp_path)
        self._conversation(b)
        _evict_all(b)
        assert b.kvtier.stats()["host_runs"] > 0
        b.kvtier.on_flush()
        assert b.kvtier.stats()["host_runs"] == 0
        evs = b.kvtier.drain_events()
        assert {"kind": "flush"} in evs


# ---------------------------------------------------------------------------
# chaos: corrupt caught by the crc gate, drops degrade to re-prefill
# ---------------------------------------------------------------------------

class TestChaos:
    def _baseline(self, expool):
        base = _batcher(expool)
        first = _serve(base, _SYS + [5, 6])
        returning = _SYS + [5, 6] + first + [7]
        return returning, _serve(base, returning)

    def _tiered(self, expool, tmp_path):
        return _batcher(expool, kv_tier=True, host_mb=1,
                        tier_dir=tmp_path)

    def _arm(self, site, kind):
        plan = ChaosPlan.from_dict({"faults": [
            {"rank": 0, "site": site, "kind": kind, "at": 0}]})
        inject.install(plan, rank=0)

    def test_promote_corrupt_caught_before_device(self, expool,
                                                  tmp_path):
        returning, base_tokens = self._baseline(expool)
        b = self._tiered(expool, tmp_path)
        _serve(b, _SYS + [5, 6])
        _evict_all(b)
        self._arm("kvtier.promote", "corrupt")
        assert _serve(b, returning) == base_tokens
        st = b.kvtier.stats()
        assert st["corrupt_detected"] >= 1, st

    def test_demote_corrupt_caught_at_promotion(self, expool,
                                                tmp_path):
        returning, base_tokens = self._baseline(expool)
        b = self._tiered(expool, tmp_path)
        _serve(b, _SYS + [5, 6])
        # the corrupt flips the DEMOTED copy after its crcs are
        # stamped over the clean bytes — only promotion can catch it
        self._arm("kvtier.demote", "corrupt")
        _evict_all(b)
        inject.uninstall()
        assert _serve(b, returning) == base_tokens
        st = b.kvtier.stats()
        assert st["corrupt_detected"] >= 1, st

    def test_drops_degrade_to_reprefill(self, expool, tmp_path):
        returning, base_tokens = self._baseline(expool)
        b = self._tiered(expool, tmp_path)
        _serve(b, _SYS + [5, 6])
        self._arm("kvtier.demote", "drop")
        _evict_all(b)
        inject.uninstall()
        assert b.kvtier.stats()["demote_drops"] == 1
        self._arm("kvtier.promote", "drop")
        assert _serve(b, returning) == base_tokens
        st = b.kvtier.stats()
        assert st["promote_drops"] >= 1, st
        assert st["corrupt_detected"] == 0


# ---------------------------------------------------------------------------
# fleet radix index + candidate ordering (router-side, jax-free)
# ---------------------------------------------------------------------------

class TestFleetIndex:
    def test_apply_events_and_contiguous_lookup(self):
        idx = FleetRadixIndex(_BS)
        run = list(range(1, 9))      # 2 full blocks
        n = idx.apply_events(0, [
            {"kind": "insert", "tokens": run, "version": 1},
            {"kind": "martian", "tokens": run},     # skipped
        ])
        assert n == 1
        assert idx.lookup(run + [77]) == {0: (2, "hbm")}
        # contiguity: a diverging SECOND block caps the match at 1
        assert idx.lookup(run[:4] + [50, 51, 52, 53]) == {0: (1, "hbm")}
        assert idx.lookup([40, 41, 42, 43]) == {}

    def test_demote_drop_flush(self):
        idx = FleetRadixIndex(_BS)
        run = list(range(1, 9))
        idx.apply_events(0, [{"kind": "insert", "tokens": run,
                              "version": 1}])
        idx.apply_events(0, [{"kind": "demote", "tokens": run,
                              "tier": "disk", "version": 1}])
        assert idx.lookup(run) == {0: (2, "disk")}
        idx.apply_events(0, [{"kind": "drop", "tokens": run}])
        assert idx.lookup(run) == {0: (1, "hbm")}
        idx.apply_events(0, [{"kind": "flush"}])
        assert idx.lookup(run) == {}
        assert idx.stats()["events_applied"] == 4

    def test_version_fence(self):
        idx = FleetRadixIndex(_BS)
        run = list(range(1, 9))
        idx.apply_events(0, [{"kind": "insert", "tokens": run,
                              "version": 1}])
        assert idx.lookup(run, versions={0: 1}) == {0: (2, "hbm")}
        assert idx.lookup(run, versions={0: 2}) == {}

    def test_prefer_holders_ordering(self):
        idx = FleetRadixIndex(_BS)
        run = list(range(1, 13))     # 3 full blocks
        idx.note_insert(1, run[:8], "hbm", None)   # shallow, resident
        idx.note_insert(2, run, "hbm", None)       # deep, demoted
        idx.note_tier(2, run, "disk", None)
        idx.note_tier(2, run[:8], "disk", None)
        cands = [SimpleNamespace(id=i) for i in (0, 1, 2)]
        # deepest-first beats tier: a disk holder of MORE blocks wins
        ordered, matched = prefer_holders(cands, run, idx)
        assert [c.id for c in ordered] == [2, 1, 0]
        assert matched == {1: 2, 2: 3}
        # at EQUAL depth the resident (hbm) holder wins the tiebreak
        ordered, _ = prefer_holders(cands, run[:8] + [50] * 4, idx)
        assert [c.id for c in ordered] == [1, 2, 0]
        # no index / no match: the load order is untouched
        assert prefer_holders(cands, run, None) == (cands, {})
        assert prefer_holders(cands, [40] * 8, idx) == (cands, {})
        # min_blocks filters shallow matches out entirely
        _, m = prefer_holders(cands, run, idx, min_blocks=3)
        assert m == {2: 3}


# ---------------------------------------------------------------------------
# cross-replica pull over the kv_migrate wire shape
# ---------------------------------------------------------------------------

class TestCrossReplicaPull:
    def test_export_graft_roundtrip_and_corrupt_refused(
            self, expool, tmp_path):
        src = _batcher(expool, rid=0, kv_tier=True, host_mb=1,
                       tier_dir=tmp_path / "src")
        first = _serve(src, _SYS + [5, 6])
        returning = _SYS + [5, 6] + first + [7]
        _evict_all(src)
        ver = src.executor.params_version
        packed = src.kvtier.export_run(returning, ver)
        assert packed is not None
        header, payload = packed
        assert header["op"] == "kvtier_pull"
        assert len(header["blocks"]) >= 4

        # a flipped payload byte is refused at the unpack gate — it
        # never reaches the destination's install queue
        bad = bytes([payload[0] ^ 0x40]) + payload[1:]
        with pytest.raises(kv_migrate.MigrateCorrupt):
            kv_migrate.unpack_blocks(header, bad)

        dst = _batcher(expool, rid=1, kv_tier=True, host_mb=1,
                       tier_dir=tmp_path / "dst")
        base_tokens = _serve(dst, returning)
        dst.prefix.flush()
        dst.kvtier.on_flush()
        dst.kvtier.submit_graft(header,
                                kv_migrate.unpack_blocks(header,
                                                         payload))
        assert dst.kvtier.has_grafts()
        assert _serve(dst, returning) == base_tokens
        assert dst.kvtier.pulls_in == 1
        assert dst.kvtier.stats()["corrupt_detected"] == 0


# ---------------------------------------------------------------------------
# in-process fleet: index built from heartbeats, returning turn routed
# ---------------------------------------------------------------------------

class TestRouterIntegration:
    def test_router_routes_returning_conversation(self, expool,
                                                  tmp_path):
        reps = [Replica(i, expool(rid=i), buckets=(8, 40),
                        max_queue=32, kv_crc=True, prefix_cache=True,
                        kv_tier=True, kvtier_host_mb=1,
                        kvtier_dir=str(tmp_path / str(i)))
                for i in range(2)]
        router = FleetRouter(reps, interval_s=0.05, suspect_s=5.0)
        router.start()
        try:
            assert router.kvtier_index is not None
            assert router.kvtier_index.block_size == _BS
            h = router.submit(_SYS + [5, 6], max_new_tokens=4)
            assert h.wait(timeout=30) and h.status == "ok"
            first = list(h.tokens)
            # the monitor sweep drains each replica's tier events into
            # the index within one heartbeat interval
            deadline = time.time() + 5
            while time.time() < deadline and \
                    router.kvtier_index.stats()["nodes"] == 0:
                time.sleep(0.05)
            assert router.kvtier_index.stats()["nodes"] > 0
            holders = router.kvtier_index.lookup(_SYS + [5, 6])
            assert holders and all(d >= 4 for d, _t in
                                   holders.values())
            routed0 = router._m_kvtier_routed.value
            h2 = router.submit(_SYS + [5, 6] + first + [7],
                               max_new_tokens=4)
            assert h2.wait(timeout=30) and h2.status == "ok"
            assert router._m_kvtier_routed.value > routed0
            # live healthz rolls the prefix-cache TOKEN counts up
            hz = router.healthz()
            assert hz["capacity"]["prefix_tokens_resident"] > 0
            held = [r for r in hz["replicas"].values()
                    if r.get("prefix_tokens_resident")]
            assert held, hz["replicas"]
        finally:
            router.close()


# ---------------------------------------------------------------------------
# pack_parked on a prefix-shared source under CoW divergence
# ---------------------------------------------------------------------------

class TestPackParkedPrefixCoW:
    def test_parked_source_untouched_by_cow(self, expool):
        b = _batcher(expool)
        P = list(range(1, 11))       # 10 tokens = 2 full blocks + 2
        _serve(b, P, max_new=2)      # P's full blocks enter the tree
        h = b.queue.submit(P, max_new_tokens=1, hold_kv=True)
        b.run()
        assert h.status == "ok"      # parked, blocks shared with tree
        hdr1, pay1 = kv_migrate.pack_parked(
            b, h.rid, fid="cow0", max_new_tokens=4,
            deadline_ms=20000.0)
        # a divergence INSIDE the parked row's shared block 1 must CoW
        # into a fresh block, never mutate the refcount-held source
        _serve(b, P[:6] + [60, 61, 62, 63], max_new=2)
        hdr2, pay2 = kv_migrate.pack_parked(
            b, h.rid, fid="cow1", max_new_tokens=4,
            deadline_ms=20000.0)
        assert pay1 == pay2
        assert [blk["crcs"] for blk in hdr1["blocks"]] == \
               [blk["crcs"] for blk in hdr2["blocks"]]
        b.release_parked(h.rid)
        b.run()


# ---------------------------------------------------------------------------
# healthz token rollup (satellite: fleet capacity payload)
# ---------------------------------------------------------------------------

class TestHealthzTokens:
    def test_aggregate_rolls_up_prefix_token_counts(self):
        info = {
            0: {"state": "up", "up": True, "draining": False,
                "queue_depth": 0, "weights_version": 1, "restarts": 0,
                "queue_free": 4, "kv_blocks_total": 32,
                "kv_blocks_in_use": 2,
                "prefix_tokens_resident": 40,
                "prefix_tokens_evictable": 24},
            1: {"state": "up", "up": True, "draining": False,
                "queue_depth": 0, "weights_version": 1, "restarts": 0,
                "queue_free": 4, "kv_blocks_total": 32,
                "kv_blocks_in_use": 0,
                "prefix_tokens_resident": 8,
                "prefix_tokens_evictable": 8},
            2: {"state": "up", "up": True, "draining": False,
                "queue_depth": 0, "weights_version": 1, "restarts": 0,
                "queue_free": 4},   # a replica with no prefix cache
        }
        out = aggregate_healthz(info, draining=False,
                                retry_after_ms=100.0)
        cap = out["capacity"]
        assert cap["prefix_tokens_resident"] == 48
        assert cap["prefix_tokens_evictable"] == 32
        assert out["replicas"]["0"]["prefix_tokens_resident"] == 40
        assert out["replicas"]["1"]["prefix_tokens_evictable"] == 8
        assert "prefix_tokens_resident" not in out["replicas"]["2"]


# ---------------------------------------------------------------------------
# inspect CLI (satellite: stdlib-only, crc exit code, never imports jax)
# ---------------------------------------------------------------------------

class TestInspectTool:
    TOOL = os.path.join(REPO, "tools", "kvtier_inspect.py")

    def _spill_dir(self, tmp_path):
        d = DiskTier(str(tmp_path))
        assert d.put(_entry((1, 2, 3, 4), fill=b"\xa1"), _BS)
        assert d.put(_entry((1, 2, 3, 4, 5, 6, 7, 8), fill=b"\xb2"),
                     _BS)
        return sorted(f for f in os.listdir(tmp_path)
                      if f.endswith(".hvdkv"))

    def _run(self, *args):
        return subprocess.run([sys.executable, self.TOOL, *args],
                              capture_output=True, text=True,
                              timeout=60)

    def test_list_show_verify_clean(self, tmp_path):
        names = self._spill_dir(tmp_path)
        out = self._run("list", str(tmp_path))
        assert out.returncode == 0, out.stderr
        assert "2 spill file(s)" in out.stdout
        out = self._run("show", str(tmp_path), names[0])
        assert out.returncode == 0 and "hvdkv-v1" in out.stdout
        out = self._run("verify", str(tmp_path))
        assert out.returncode == 0 and "OK" in out.stdout

    def test_verify_exits_1_on_crc_mismatch(self, tmp_path):
        names = self._spill_dir(tmp_path)
        p = tmp_path / names[0]
        raw = bytearray(p.read_bytes())
        raw[-1] ^= 0xAA              # flip one payload byte
        p.write_bytes(bytes(raw))
        out = self._run("verify", str(tmp_path))
        assert out.returncode == 1, out.stdout
        assert "CORRUPT" in out.stdout and "crc32" in out.stdout

    def test_tool_does_not_import_jax(self, tmp_path):
        """The inspect CLI must stay deployable on hosts without a jax
        install (the ckpt_inspect contract, applied to the tier)."""
        self._spill_dir(tmp_path)
        code = ("import sys; sys.modules['jax'] = None\n"
                "import runpy; sys.argv = ['kvtier_inspect', "
                f"'verify', {str(tmp_path)!r}]\n"
                f"runpy.run_path({self.TOOL!r}, "
                "run_name='__main__')\n")
        out = subprocess.run([sys.executable, "-c", code],
                             capture_output=True, text=True,
                             timeout=60)
        assert "OK" in out.stdout, (out.stdout, out.stderr)


# ---------------------------------------------------------------------------
# soak acceptance (slow tier)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_kvtier_soak_acceptance(tmp_path):
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "serve_soak.py"),
         "--kv-tier", "--seed", "7", "--out", str(tmp_path)],
        env=env, capture_output=True, text=True, timeout=300)
    assert out.stdout.strip(), out.stderr[-3000:]
    verdict = json.loads(out.stdout)
    detail = json.dumps(verdict, indent=2, sort_keys=True)[:3000]
    assert verdict["ok"] is True, detail
    assert out.returncode == 0
