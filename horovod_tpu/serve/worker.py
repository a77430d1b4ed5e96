"""Replica worker process: one OS process, one serving replica.

The unit the multi-process fleet (serve/proc_fleet.py) is made of.
Each worker hosts the full PR 8/10 serving stack — ``ShardedExecutor``
(+ optional draft executor), ``AdmissionQueue``, ``ContinuousBatcher``
with paged KV / prefix cache / speculative decoding — plus the three
things that make it a FLEET citizen across a process boundary:

* **A request endpoint** (:class:`ReplicaEndpoint`): a threading TCP
  server speaking the framed protocol of serve/wire.py. Every
  ``submit`` carries a router-generated request id (``fid``); the
  worker keeps a bounded resolution cache and an in-flight table keyed
  by it, so a REPLAYED dispatch — the retry ladder re-dialing after a
  ``conn_reset`` ate the reply — is served its cached (or still
  cooking) result instead of being executed twice. This mirrors the
  csrc/store.cc nonce dedupe and is what makes answered-exactly-once
  hold across the process boundary.
* **Heartbeats over the native KV** — ``serve.hb.<ns>.g<gen>.<rid>``
  posted by a chaos-exempt ``StoreClient`` on its own thread. The
  SEQUENCE only advances when the scheduler actually iterates (the
  batcher's heartbeat hook), so a wedged scheduler goes stale at the
  router's accrual sweep even while the poster thread lives — the same
  liveness-vs-reachability split the PR 5 detector enforces.
* **A weight gate at startup** — before taking traffic the worker
  adopts the NEWEST published version from the redist/stream.py
  channel (``WeightSubscriber.peek_version()`` names the target), so a
  respawned replica re-enters the fleet on the weights its siblings
  already serve, never the stale params it was built with.

Chaos: the worker installs the fleet's plan and fires ``serve.proc``
once per scheduler iteration — ``crash`` there is a REAL
``os.kill(getpid(), SIGKILL)`` (the injector's listener ledger is
flushed first), the genuine host-loss the soak's accrual-detection
bound is measured against. ``serve.step``/``serve.kv``/``serve.admit``
faults keep their PR 8 in-replica semantics, now per process.

Spawned via the runner machinery (runner/exec.py ``spawn_local``);
configuration travels as inline JSON in ``HOROVOD_SERVE_WORKER_CFG``
(see :func:`build_worker` for the schema).
"""
from __future__ import annotations

import json
import logging
import os
import signal
import socketserver
import sys
import threading
import time
from collections import OrderedDict
from typing import Any, Dict, Optional

from ..trace.spans import configure_recorder as _trace_configure
from ..trace.spans import get_recorder as _trace_recorder
from . import wire
from .queue import AdmitDropped, Rejected

logger = logging.getLogger("horovod_tpu")

#: resolved results retained for replay dedupe (the store.cc DoneRound
#: TTL cache analog, bounded by count instead of time)
DEDUPE_CAP = 4096

#: extra wait past a request's own deadline before the endpoint calls
#: it stalled — the batcher resolves expiry itself within one
#: iteration, so this only fires when the scheduler is wedged
REPLY_GRACE_S = 30.0


def tiny_gpt_builder(seed: int = 0,
                     vocab_size: int = 64, num_layers: int = 2,
                     num_heads: int = 2, head_dim: int = 8,
                     max_seq_len: int = 48, max_batch: int = 4,
                     kv_block_size: int = 4, kv_pool_blocks: int = 32,
                     draft: bool = False) -> Dict[str, Any]:
    """The built-in model builder the fleet soak and bench use: a tiny
    decode-mode GPT with params DETERMINISTIC per seed, so every
    replica process (and the soak's publisher) derives bit-identical
    weights without shipping arrays over the spawn boundary."""
    import jax
    import jax.numpy as jnp

    from ..models.gpt import GPT, GPTConfig

    kw = dict(vocab_size=vocab_size, num_layers=num_layers,
              num_heads=num_heads, head_dim=head_dim,
              max_seq_len=max_seq_len, dtype=jnp.float32,
              attention_impl="reference")
    model = GPT(GPTConfig(decode=True, **kw, kv_block_size=kv_block_size,
                          kv_pool_blocks=kv_pool_blocks))
    params = GPT(GPTConfig(**kw)).init(
        jax.random.PRNGKey(seed), jnp.zeros((2, 8), jnp.int32))["params"]
    draft_model = GPT(GPTConfig(decode=True, **kw)) if draft else None
    return {"model": model, "params": params,
            "draft_model": draft_model, "eos_id": None,
            "max_batch": max_batch, "max_len": max_seq_len}


def _resolve_builder(spec: str):
    """'module:function' -> callable, fail-fast."""
    import importlib
    mod, _, fn = spec.partition(":")
    if not mod or not fn:
        raise ValueError(
            f"worker builder must be 'module:function'; got {spec!r}")
    return getattr(importlib.import_module(mod), fn)


class ReplicaEndpoint:
    """The worker's request endpoint: framed submit/healthz over TCP
    with fid-keyed replay dedupe. Usable in-thread (tier-1 tests run it
    against a local batcher without any subprocess)."""

    def __init__(self, batcher, *, rid: int,
                 host: str = "127.0.0.1", port: int = 0,
                 dedupe_cap: int = DEDUPE_CAP):
        self.batcher = batcher
        self.rid = int(rid)
        self._lock = threading.Lock()
        self._inflight: Dict[str, Any] = {}
        self._done: "OrderedDict[str, dict]" = OrderedDict()
        self._dedupe_cap = int(dedupe_cap)
        #: replayed dispatches served from the cache or the in-flight
        #: table instead of being executed twice — the soak's evidence
        #: that a lost reply never becomes a duplicate execution
        self.dedupe_hits = 0
        self.submits = 0
        #: in-progress kv_install entries keyed by fid: a replayed
        #: install arriving while the original is still installing
        #: joins its outcome instead of double-installing
        self._installing: Dict[str, dict] = {}
        ep = self

        class Handler(socketserver.BaseRequestHandler):
            def handle(self):
                try:
                    msg, payload = wire.recv_any(self.request,
                                                 timeout=30.0)
                    ep._handle(self.request, msg, payload)
                except (wire.DispatchConnError, wire.DispatchError,
                        OSError):
                    # resilience: exempt (the client vanished or spoke
                    # garbage — the retry ladder lives ROUTER-side; any
                    # computed result is already in the dedupe cache
                    # for the replay)
                    pass

        class Server(socketserver.ThreadingTCPServer):
            allow_reuse_address = True
            daemon_threads = True

        self._server = Server((host, port), Handler)
        self.address = self._server.server_address[:2]
        self._thread = threading.Thread(
            target=self._server.serve_forever, daemon=True,
            name=f"hvd-replica-ep-{rid}")

    def start(self) -> "ReplicaEndpoint":
        self._thread.start()
        return self

    def close(self) -> None:
        self._server.shutdown()
        self._server.server_close()

    # -- request handling ---------------------------------------------------
    def _handle(self, sock, msg: dict,
                payload: Optional[bytes] = None) -> None:
        op = msg.get("op")
        if op == "healthz":
            wire.send_msg(sock, self.healthz())
            return
        if op == "metrics":
            # the fleet /metrics?fleet=1 scrape leg: the worker's whole
            # registry snapshot rides one JSON reply (serve/http.py
            # merges it with its siblings' via merge_snapshots)
            from ..obs import metrics as obs_metrics
            wire.send_msg(sock, {
                "ack": "metrics",
                "snapshot": obs_metrics.get_registry().snapshot()})
            return
        if op == "kv_install":
            self._handle_kv_install(sock, msg, payload or b"")
            return
        if op in ("migrate", "release", "result"):
            self._handle_disagg(sock, op, msg)
            return
        if op != "submit":
            wire.send_msg(sock, {"ack": "bad_request",
                                 "error": f"unknown op {op!r}"})
            return
        if msg.get("fid") in (None, ""):
            # a missing fid must not collapse onto one shared dedupe
            # key (str(None) == "None" would serve one caller another
            # request's cached tokens)
            wire.send_msg(sock, {"ack": "bad_request",
                                 "error": "submit requires a fid"})
            return
        fid = str(msg["fid"])
        with self._lock:
            self.submits += 1
            self._sweep_orphans_locked()
            cached = self._done.get(fid)
            handle = None if cached is not None \
                else self._inflight.get(fid)
            if cached is not None or handle is not None:
                # the replay-dedupe core: a re-dispatched request whose
                # reply was lost is served its existing result (or
                # joins the in-flight wait) — never executed twice
                self.dedupe_hits += 1
            elif self.batcher.draining:
                wire.send_msg(sock, {"ack": "rejected",
                                     "reason": "replica draining",
                                     "retry_after_ms": 1000.0})
                return
            else:
                try:
                    handle = self.batcher.queue.submit(
                        msg["prompt"],
                        max_new_tokens=int(msg.get("max_new_tokens", 16)),
                        deadline_ms=msg.get("deadline_ms"),
                        temperature=float(msg.get("temperature", 0.0)),
                        top_p=float(msg.get("top_p", 1.0)),
                        seed=int(msg.get("seed", 0)),
                        hold_kv=bool(msg.get("hold_kv", False)),
                        trace=msg.get("trace"))
                except AdmitDropped as e:
                    wire.send_msg(sock, {
                        "ack": "admit_dropped",
                        "retry_after_ms": e.retry_after_ms})
                    return
                except Rejected as e:
                    wire.send_msg(sock, {
                        "ack": "rejected", "reason": e.reason,
                        "retry_after_ms": e.retry_after_ms})
                    return
                except (KeyError, ValueError, TypeError) as e:
                    wire.send_msg(sock, {"ack": "bad_request",
                                         "error": str(e)})
                    return
                self._inflight[fid] = handle
        # accepted (fresh or replayed): ack now, result when it lands
        wire.send_msg(sock, {"ack": "accepted"})
        deadline_ms = msg.get("deadline_ms") \
            or self.batcher.queue.default_deadline_ms
        self._await_and_reply(sock, fid, handle, cached, deadline_ms,
                              trace=msg.get("trace"))

    def _record(self, handle) -> dict:
        """The cached (replay-servable) rendering of a resolved
        handle. ``rid`` rides along so the disagg ``migrate`` op can
        find the parked sequence a hold_kv prefill left behind."""
        return {"status": handle.status, "tokens": list(handle.tokens),
                "error": handle.error, "latency_ms": handle.latency_ms,
                "rid": handle.rid}

    def _sweep_orphans_locked(self) -> None:
        """Lazily migrate resolved orphans (a client that vanished
        before the ack leaves its entry here) into the bounded done
        cache, so the in-flight table cannot grow past the queue's own
        bounds. Caller holds ``self._lock``."""
        for k in [k for k, h in self._inflight.items() if h.done()]:
            h = self._inflight.pop(k)
            self._done[k] = self._record(h)
            while len(self._done) > self._dedupe_cap:
                self._done.popitem(last=False)

    def _await_and_reply(self, sock, fid: str, handle,
                         cached: Optional[dict],
                         deadline_ms: float,
                         trace: Optional[dict] = None) -> None:
        """The shared result tail of ``submit`` and ``result``: wait
        out the handle (unless a cached record already answers the
        replay), cache BEFORE sending — if the send dies with the
        reply, the replay finds the result here. When the request was
        traced, the recorder's completed spans for it piggyback on the
        reply as ``spans`` (drained at send time, NOT cached: a replay
        re-reads the result, not the telemetry)."""
        if cached is None:
            handle.wait(timeout=float(deadline_ms) / 1000.0
                        + REPLY_GRACE_S)
            if handle.done():
                cached = self._record(handle)
            else:
                # scheduler wedged past deadline + grace: a structured
                # error, not a dropped socket (NOT cached — a replay
                # after the replica recovers may still resolve it)
                wire.send_msg(sock, {"status": "error",
                                     "error": "replica stalled",
                                     "tokens": [], "latency_ms": None})
                return
            with self._lock:
                self._done[fid] = cached
                self._inflight.pop(fid, None)
                while len(self._done) > self._dedupe_cap:
                    self._done.popitem(last=False)
        reply = cached
        if isinstance(trace, dict) and trace.get("trace"):
            spans = _trace_recorder().drain(str(trace["trace"]))
            if spans:
                reply = dict(cached, spans=spans)
        wire.send_msg(sock, reply)

    # -- disaggregated serving ops (serve/disagg.py orchestration) ----------
    def _handle_disagg(self, sock, op: str, msg: dict) -> None:
        """``migrate`` / ``release`` / ``result``: the decode-pool and
        prefill-pool halves of KV-block migration, addressed by the
        SAME fid namespace (and dedupe discipline) as ``submit``."""
        from . import kv_migrate
        fid = str(msg.get("fid") or "")
        if not fid:
            wire.send_msg(sock, {"ack": "bad_request",
                                 "error": f"{op} requires a fid"})
            return
        with self._lock:
            self._sweep_orphans_locked()
            cached = self._done.get(fid)
            handle = self._inflight.get(fid)
            if op == "result" and cached is not None:
                self.dedupe_hits += 1
        if op == "result":
            # the decode-side completion wait: same contract as a
            # submit's reply leg (ack, block, cached-replay dedupe)
            if cached is None and handle is None:
                wire.send_msg(sock, {"ack": "unknown_fid"})
                return
            wire.send_msg(sock, {"ack": "accepted"})
            deadline_ms = msg.get("deadline_ms") \
                or self.batcher.queue.default_deadline_ms
            self._await_and_reply(sock, fid, handle, cached,
                                  deadline_ms,
                                  trace=msg.get("trace"))
            return
        rid = cached.get("rid") if cached is not None else \
            (handle.rid if handle is not None else None)
        if rid is None:
            wire.send_msg(sock, {"ack": "migrate_failed",
                                 "reason": "unknown_fid"})
            return
        if op == "release":
            self.batcher.release_parked(int(rid))
            wire.send_msg(sock, {"ack": "released"})
            return
        # op == "migrate": pack the parked sequence and PUSH it to the
        # decode endpoint the router chose (serve.migrate chaos +
        # retry ladder live inside kv_migrate.push)
        t0 = time.monotonic()
        try:
            packet = kv_migrate.pack_parked(
                self.batcher, int(rid), fid=str(msg["dfid"]),
                max_new_tokens=int(msg["max_new_tokens"]),
                deadline_ms=float(msg.get("deadline_ms") or 30000.0))
        except kv_migrate.MigrateCorrupt as e:
            # the SOURCE blocks are untrusted: release them so the
            # inevitable re-prefill runs on clean capacity
            self.batcher.release_parked(int(rid))
            wire.send_msg(sock, {"ack": "migrate_failed",
                                 "reason": "source_corrupt",
                                 "detail": str(e)[:200]})
            return
        except (KeyError, ValueError, TypeError) as e:
            wire.send_msg(sock, {"ack": "bad_request",
                                 "error": str(e)[:200]})
            return
        if packet is None:
            wire.send_msg(sock, {"ack": "migrate_failed",
                                 "reason": "not_parked"})
            return
        header, payload = packet
        try:
            target = (str(msg["target"][0]), int(msg["target"][1]))
            ack = kv_migrate.push(target, header, payload,
                                  peer=msg.get("peer"))
        except (wire.DispatchConnError, wire.DispatchError) as e:
            wire.send_msg(sock, {"ack": "migrate_failed",
                                 "reason": "unreachable",
                                 "detail": str(e)[:200]})
            return
        if ack.get("ack") == "installed":
            # the blocks live on the decode replica now — free the
            # parked row (scheduler-thread free, endpoint-safe)
            self.batcher.release_parked(int(rid))
            reply = {
                "ack": "migrated", "bytes": len(payload),
                "blocks": len(header["blocks"]),
                "ms": round((time.monotonic() - t0) * 1000.0, 3),
                "dedupe": bool(ack.get("dedupe", False))}
            tr = header.get("trace")
            if isinstance(tr, dict) and tr.get("trace"):
                _trace_recorder().record_local(
                    "migrate_push", t0, time.monotonic(), ship=tr,
                    fid=str(msg.get("dfid")), bytes=len(payload))
                spans = _trace_recorder().drain(str(tr["trace"]))
                if spans:
                    reply["spans"] = spans
            wire.send_msg(sock, reply)
            return
        wire.send_msg(sock, {
            "ack": "migrate_failed",
            "reason": str(ack.get("ack", "unknown")),
            "detail": ack.get("detail") or ack.get("error"),
            "retry_after_ms": ack.get("retry_after_ms")})

    def _handle_kv_install(self, sock, msg: dict,
                           payload: bytes) -> None:
        """Receive a migrated sequence (the decode-pool side): crc
        verification + reservation-gated install ride
        kv_migrate.install; the fid dedupe (done cache, in-flight
        table, in-progress installs) makes a ladder REPLAY of a
        severed push converge on one install and one ack."""
        from . import kv_migrate
        fid = str(msg.get("fid") or "")
        if not fid:
            wire.send_msg(sock, {"ack": "bad_request",
                                 "error": "kv_install requires a fid"})
            return
        mine = False
        with self._lock:
            self._sweep_orphans_locked()
            if fid in self._done or fid in self._inflight:
                self.dedupe_hits += 1
                ent = None
            else:
                ent = self._installing.get(fid)
                if ent is None:
                    mine = True
                    ent = {"evt": threading.Event(), "outcome": None,
                           "handle": None}
                    self._installing[fid] = ent
                else:
                    self.dedupe_hits += 1
        if ent is None:
            # already installed (or even resolved): the replay of a
            # severed push is served the same ack, never a second copy
            wire.send_msg(sock, {"ack": "installed", "dedupe": True})
            return
        if mine:
            t_i0 = time.monotonic()
            try:
                blocks = kv_migrate.unpack_blocks(msg, payload)
            except kv_migrate.MigrateCorrupt as e:
                self.batcher.note_migrate_corrupt()
                self._finalize_install(fid, ent, ("corrupt", str(e)),
                                       None)
            else:
                pending = self.batcher.submit_migrated(msg, blocks)
                if pending["evt"].wait(
                        kv_migrate.INSTALL_ACK_TIMEOUT_S):
                    out = pending["outcome"]
                    tr = msg.get("trace")
                    if isinstance(tr, dict) and tr.get("trace"):
                        # decode-side receive span; drained later with
                        # the result op's reply
                        _trace_recorder().record_local(
                            "migrate_install", t_i0, time.monotonic(),
                            ship=tr, fid=fid, outcome=str(out[0]))
                    self._finalize_install(
                        fid, ent, out,
                        pending["handle"] if out[0] == "installed"
                        else None)
                else:
                    # the decode scheduler has not picked the entry up
                    # yet: the install is still PENDING, not dead. The
                    # _installing entry stays registered so a ladder
                    # replay JOINS this install instead of starting a
                    # second one (the double-install the fid dedupe
                    # exists to prevent), and a finisher thread
                    # completes the bookkeeping — registering the
                    # handle for the result op — whenever it lands.
                    def finish():
                        pending["evt"].wait(REPLY_GRACE_S * 10)
                        out = pending["outcome"] or ("stalled", None)
                        self._finalize_install(
                            fid, ent, out,
                            pending["handle"] if out[0] == "installed"
                            else None)
                    threading.Thread(
                        target=finish, daemon=True,
                        name=f"hvd-install-finish-{self.rid}").start()
        else:
            ent["evt"].wait(kv_migrate.INSTALL_ACK_TIMEOUT_S + 5.0)
        outcome, detail = ent["outcome"] or ("stalled", None)
        if outcome == "installed":
            wire.send_msg(sock, {"ack": "installed",
                                 "dedupe": not mine})
        elif outcome == "corrupt":
            wire.send_msg(sock, {"ack": "migrate_corrupt",
                                 "detail": detail})
        elif outcome == "version_mismatch":
            wire.send_msg(sock, {"ack": "version_mismatch",
                                 "detail": detail})
        elif outcome == "rejected":
            wire.send_msg(sock, {"ack": "rejected",
                                 "retry_after_ms": detail})
        else:
            wire.send_msg(sock, {"ack": "bad_request",
                                 "error": f"{outcome}: {detail}"})

    def _finalize_install(self, fid: str, ent: dict, outcome: tuple,
                          handle) -> None:
        """Complete a kv_install's endpoint bookkeeping exactly once:
        record the outcome, register the handle for the result op,
        release the in-progress entry, wake every waiter (the original
        requester and any replays that joined it)."""
        with self._lock:
            ent["outcome"] = outcome
            ent["handle"] = handle
            if handle is not None:
                self._inflight[fid] = handle
            self._installing.pop(fid, None)
        ent["evt"].set()

    def healthz(self) -> dict:
        b = self.batcher
        info = {"replica": self.rid,
                "replica_up": b.alive(),
                "draining": bool(getattr(b, "draining", False)),
                "load": b.load(),
                "iterations": b.iterations,
                "weights_version": b.executor.params_version,
                "dedupe_hits": self.dedupe_hits,
                "kv_corruptions_injected": b.kv_corruptions_injected,
                "kv_corruptions_detected": b.kv_corruptions_detected,
                "kv_blocks_in_use": b.kv.pool.in_use(),
                "kv_blocks_total": b.kv.pool.num_blocks,
                "kv_block_size": b.kv.pool.block_size,
                # blocks held ONLY by the prefix cache (refcount-zero
                # runs): resident but reclaimable on demand — load
                # signals must not read cache residency as capacity
                # pressure
                "kv_blocks_evictable": (
                    b.prefix.evictable_blocks()
                    if getattr(b, "prefix", None) is not None else 0)}
        if getattr(b, "prefix", None) is not None:
            # TOKEN counts — the fleet-wide cacheable-capacity
            # definition the index and autoscale signals share
            info["prefix_tokens_resident"] = \
                b.prefix.resident_tokens()
            info["prefix_tokens_evictable"] = \
                b.prefix.evictable_tokens()
        if getattr(b, "kvtier", None) is not None:
            # fleet-index event feed piggybacks the healthz reply
            # (the heartbeat channel the router already polls)
            info["kvtier_events"] = b.kvtier.drain_events()
            info["kvtier"] = b.kvtier.stats()
        # disaggregated-serving evidence (serve/disagg.py healthz +
        # the disagg soak verdict read these per pool)
        info["migrations_in"] = b.migrations_in
        info["migrate_rejects"] = b.migrate_rejects
        info["migrate_corrupt_detected"] = b.migrate_corrupt_detected
        with b._parked_lock:
            info["parked"] = len(b.parked)
        from ..native.resilience import RETRIES_HELP
        from ..obs import metrics as obs_metrics
        info["migrate_absorbed"] = int(obs_metrics.get_registry().counter(
            "hvd_net_retries_total", RETRIES_HELP,
            {"site": "serve.migrate", "outcome": "absorbed"}).value)
        info.update(b.queue.counters())
        return info


class ReplicaWorker:
    """The whole worker process, assembled from a config dict (see
    :func:`build_worker`). In-process usable for tests; ``main()``
    wraps it for the real spawned process."""

    def __init__(self, cfg: dict):
        from .batcher import ContinuousBatcher
        from .executor import ShardedExecutor
        from .queue import AdmissionQueue

        self.cfg = dict(cfg)
        self.rid = int(cfg["rid"])
        self.gen = int(cfg.get("gen", 0))
        self.ns = str(cfg.get("ns", "fleet"))
        # stamp this process's span recorder with its fleet identity
        # (pool/replica/generation name the Chrome-trace pid row)
        _trace_configure(pool=str(cfg.get("pool") or self.ns),
                         replica=self.rid, gen=self.gen)
        self.hb_interval_s = float(cfg.get("hb_interval_s", 0.125))
        self._events_f = None
        events_path = cfg.get("events_path")
        if events_path:
            self._events_f = open(events_path, "a", buffering=1)
        self._install_chaos(cfg.get("chaos_plan"))

        built = _resolve_builder(
            cfg.get("builder",
                    "horovod_tpu.serve.worker:tiny_gpt_builder"))(
            **(cfg.get("builder_kwargs") or {}))
        self.executor = ShardedExecutor(
            built["model"], built["params"],
            max_batch=int(built.get("max_batch", 4)),
            max_len=int(built.get("max_len", 48)),
            replica_id=self.rid)
        if cfg.get("pool") in ("prefill", "decode"):
            # a disaggregated pool hands sequences over as KV blocks
            from .kv_migrate import refuse_per_row_state
            refuse_per_row_state(self.executor)
        draft = built.get("draft_model")
        self.draft_executor = None if draft is None else ShardedExecutor(
            draft, built["params"],
            max_batch=int(built.get("max_batch", 4)),
            max_len=int(built.get("max_len", 48)),
            replica_id=self.rid, role="draft")
        self.queue = AdmissionQueue(
            max_queue=int(cfg.get("max_queue", 64)),
            default_deadline_ms=float(cfg.get("deadline_ms", 30000.0)),
            replica_id=self.rid)
        self.batcher = ContinuousBatcher(
            self.executor, self.queue,
            buckets=tuple(cfg.get("buckets")
                          or built.get("buckets") or (8,)),
            eos_id=built.get("eos_id"), replica_id=self.rid,
            kv_crc=cfg.get("kv_crc"),
            draft_executor=self.draft_executor,
            spec_k=cfg.get("spec_k"),
            prefix_cache=cfg.get("prefix_cache"),
            kv_tier=cfg.get("kv_tier"),
            kvtier_host_mb=cfg.get("kvtier_host_mb"),
            # a shared spill root is partitioned per replica: two
            # workers scanning one directory would double-count runs
            kvtier_dir=(os.path.join(str(cfg["kvtier_dir"]),
                                     f"r{self.rid}")
                        if cfg.get("kvtier_dir") else None))
        # scheduler-iteration pulse: advances the heartbeat seq AND
        # crosses the serve.proc chaos gate (crash there = SIGKILL of
        # THIS process — the real host loss, see module docstring)
        self.seq = 0
        self.batcher.heartbeat = self._pulse
        # chaos-exempt KV client: the observer plane (heartbeats +
        # endpoint registration) must be neither faulted nor allowed to
        # skew site counters (the PR 5 detector's rule)
        self._kv = None
        kv_addr, kv_port = cfg.get("kv_addr"), cfg.get("kv_port")
        if kv_addr and kv_port:
            from ..native.store import StoreClient
            self._kv = StoreClient(str(kv_addr), int(kv_port),
                                   rank=self.rid, chaos_exempt=True)
        self.subscriber = None
        channel = cfg.get("channel")
        if channel and kv_addr and kv_port:
            from ..native.store import StoreClient
            from ..redist.stream import WeightSubscriber
            self.subscriber = WeightSubscriber(
                str(channel),
                client=StoreClient(str(kv_addr), int(kv_port),
                                   rank=self.rid, chaos_exempt=True),
                template=built["params"])
        self.endpoint = ReplicaEndpoint(
            self.batcher, rid=self.rid,
            host=str(cfg.get("host", "127.0.0.1")))
        self._hb_stop = threading.Event()
        self._hb_thread: Optional[threading.Thread] = None
        self.draining = False
        self._drained = threading.Event()

    # -- chaos wiring --------------------------------------------------------
    def _install_chaos(self, plan_obj) -> None:
        if not plan_obj:
            return
        from ..chaos import inject
        from ..chaos.plan import ChaosPlan
        plan = plan_obj if isinstance(plan_obj, ChaosPlan) \
            else ChaosPlan.from_dict(plan_obj)
        # epoch = the worker's GENERATION: a respawned worker's fresh
        # iteration/submit counters re-cross every exact-'at' address,
        # so epoch-pinned faults (the plan composer pins the kill to
        # epoch 0) fire in exactly one incarnation — the same rule the
        # elastic relaunch path uses (HOROVOD_CKPT_RESET_EPOCH)
        inj = inject.install(plan, rank=0, epoch=self.gen)
        if self._events_f is not None:
            f = self._events_f

            def log_event(ev: dict) -> None:
                f.write(json.dumps(ev, default=str) + "\n")
                f.flush()
                os.fsync(f.fileno())

            inj.add_listener(log_event)

    def _pulse(self) -> None:
        self.seq += 1
        from ..chaos import inject as _chaos
        if _chaos._INJ is None:
            return
        f = _chaos.fire("serve.proc", peer=self.rid,
                        step=self.batcher.iterations)
        if f is not None and f.kind == "crash":
            # the REAL host loss: no cleanup, no flushes beyond the
            # listener ledger (already fsync'd above), no goodbye on
            # the heartbeat key — exactly what a dead machine looks
            # like to the router's accrual sweep
            os.kill(os.getpid(), signal.SIGKILL)

    # -- lifecycle -----------------------------------------------------------
    def hb_key(self) -> str:
        return f"serve.hb.{self.ns}.g{self.gen}.{self.rid}"

    def ep_key(self) -> str:
        return f"serve.ep.{self.ns}.g{self.gen}.{self.rid}"

    def _hb_value(self) -> bytes:
        """``<seq>:<wall>`` — the sequence the accrual sweep reads plus
        this process's wall clock, the free round-trip clock sample the
        router's trace assembler estimates per-worker offsets from
        (trace/clock.py). Readers that predate the stamp parse the int
        prefix and ignore the rest."""
        return f"{self.seq}:{time.time():.6f}".encode()

    def _post_heartbeats(self) -> None:
        while not self._hb_stop.wait(self.hb_interval_s):
            try:
                self._kv.set(self.hb_key(), self._hb_value())
            except Exception as e:  # noqa: BLE001 — a KV blip must not
                logger.warning(     # kill the poster; stale age is the
                    "replica %d heartbeat post failed: %s",  # signal
                    self.rid, e)

    def _weight_gate(self, timeout_s: float = 30.0) -> None:
        """Adopt the channel's newest PUBLISHED version before taking
        traffic — the respawn re-admission gate, enforced where the
        weights actually land."""
        if self.subscriber is None:
            return
        target = self.subscriber.peek_version()
        if target is None:
            return                    # nothing published yet
        deadline = time.monotonic() + timeout_s
        while (self.executor.params_version or 0) < target:
            try:
                got = self.subscriber.poll()
                if got is not None:
                    self.executor.swap_params(got[1], version=got[0])
            except Exception as e:  # noqa: BLE001
                logger.warning("replica %d weight gate poll failed "
                               "(%s); retrying", self.rid, e)
            if (self.executor.params_version or 0) >= target:
                break
            if time.monotonic() > deadline:
                raise RuntimeError(
                    f"replica {self.rid} could not adopt weight "
                    f"version {target} within {timeout_s:.0f}s")
            time.sleep(0.05)

    def start(self) -> "ReplicaWorker":
        """Warm up, pass the weight gate, open the endpoint, start
        heartbeating, REGISTER (the registration key doubles as the
        ready signal the router waits on)."""
        self.batcher.warmup()
        self._weight_gate()
        if self.subscriber is not None:
            self.batcher.attach_weights(self.subscriber)
        self.endpoint.start()
        self.batcher.start()
        if self._kv is not None:
            self._kv.set(self.hb_key(), self._hb_value())
            self._hb_thread = threading.Thread(
                target=self._post_heartbeats, daemon=True,
                name=f"hvd-replica-hb-{self.rid}")
            self._hb_thread.start()
            self._kv.set(self.ep_key(), json.dumps({
                "host": self.endpoint.address[0],
                "port": self.endpoint.address[1],
                "pid": os.getpid(),
                "weights_version": self.executor.params_version,
                "t": time.time()}).encode())
        return self

    def drain(self, timeout_s: float = 10.0) -> None:
        """Stop admitting, finish the in-flight tail, stop. New submits
        are rejected with retry-after at the endpoint (never silently
        dropped) while the tail resolves."""
        self.draining = True
        self.batcher.draining = True
        deadline = time.monotonic() + timeout_s
        while time.monotonic() < deadline:
            if self.queue.depth() == 0 and not self.batcher._active:
                break
            time.sleep(0.05)
        self.close()
        self._drained.set()

    def close(self) -> None:
        self._hb_stop.set()
        self.batcher.stop()
        self.endpoint.close()
        if self.subscriber is not None:
            self.subscriber.close()
        if self._kv is not None:
            self._kv.close()
        if self._events_f is not None:
            self._events_f.close()

    def run_forever(self) -> int:
        """Block until the scheduler dies (rc 1 — the supervisor
        respawns) or a drain COMPLETES (rc 0 — exiting on the mere
        start of a drain would kill the in-flight tail the drain
        exists to finish)."""
        while True:
            if self._drained.is_set():
                return 0
            if self.draining:
                time.sleep(0.1)
                continue
            if not self.batcher.alive():
                logger.error("replica %d scheduler died — exiting so "
                             "the router can respawn a fresh process",
                             self.rid)
                return 1
            time.sleep(0.2)


def main(argv=None) -> int:
    cfg_raw = os.environ.get("HOROVOD_SERVE_WORKER_CFG")
    if not cfg_raw:
        print("serve worker: HOROVOD_SERVE_WORKER_CFG is not set",
              file=sys.stderr)
        return 2
    logging.basicConfig(level=logging.INFO)
    cfg = json.loads(cfg_raw)
    worker = ReplicaWorker(cfg)

    def _sigterm(signum, frame):
        logger.info("replica %d: SIGTERM — draining", worker.rid)
        threading.Thread(target=worker.drain, daemon=True).start()

    signal.signal(signal.SIGTERM, _sigterm)
    worker.start()
    logger.info("replica %d ready on %s:%d (gen %d, weights v%s)",
                worker.rid, worker.endpoint.address[0],
                worker.endpoint.address[1], worker.gen,
                worker.executor.params_version)
    return worker.run_forever()


if __name__ == "__main__":
    sys.exit(main())
