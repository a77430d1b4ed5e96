"""Continuous batcher: iteration-level scheduling over fixed shapes.

The Orca insight, TPU-flavored: requests join and leave the running
batch *between decode iterations*, never mid-program, and every program
the scheduler launches has one of a small closed set of shapes —
``[max_batch, 1]`` for decode, ``[max_batch, bucket]`` for each
configured prefill bucket (HOROVOD_SERVE_BUCKETS), and
``[max_batch, spec_k + 1]`` for the speculative verify step — so jit
compiles each exactly once and batch churn can never trigger a
recompile.

One `step()` is one scheduling iteration:

1. **retire** — finished (max_new_tokens / EOS / context-full) and
   deadline-expired sequences resolve their handles and free their KV
   capacity (row, block-table references + prefix refcounts) in
   the SAME iteration — a leaked block is capacity gone forever.
2. **admit** — pop queued requests into free capacity: a free row and
   free BLOCKS of the pool (serve/kv_cache.py `PagedKVCache`) —
   tokens, not rows — through `queue.pop_fitting`. With the radix
   prefix cache enabled
   (serve/prefix.py), each prompt is first matched against cached
   shared prefixes: matched blocks join the sequence's table by
   reference (copy-on-write at a mid-block divergence) and only the
   suffix is prefilled.
3. **decode** — one `[max_batch, 1]` step for every live sequence; or,
   with a draft executor attached, SPECULATIVE decoding: the drafter
   proposes up to `spec_k` tokens per row ([max_batch, 1] draft steps),
   the target scores all of them in ONE `[max_batch, spec_k+1]` verify
   step, and the greedy accept/rollback rule emits tokens BIT-IDENTICAL
   to target-only greedy decode — between 1 and spec_k+1 of them per
   target step.

Prefill counts as producing the first generated token (its last-logit
argmax), so a request admitted in iteration k has a token by k — no
separate prefill queue.
"""
from __future__ import annotations

import logging
import threading
import time
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Sequence

import numpy as np

from ..chaos import inject as _chaos
from ..obs import metrics as obs_metrics
from ..trace.spans import get_recorder as _trace_recorder
from .kv_cache import BlockPool, PagedKVCache
from .kvtier.tier import ReplicaKVTier
from .prefix import RadixPrefixCache
from .queue import AdmissionQueue, ServeRequest

logger = logging.getLogger("horovod_tpu")

#: acceptance-rate histogram bounds: fractions in (0, 1]
_ACCEPT_BOUNDS = (0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 0.75, 1.0)


class ReplicaDead(RuntimeError):
    """A chaos ``serve.step`` crash: this replica's scheduler thread
    dies here — the in-process analog of losing the replica's host.
    Its heartbeats stop, which is what the fleet router's accrual
    tracker detects (serve/fleet.py)."""


@dataclass
class _Active:
    req: ServeRequest
    slot: int
    #: generated tokens so far (first comes from the prefill step)
    out: List[int] = field(default_factory=list)
    #: tokens written into the KV cache (prompt + confirmed generations)
    cache_len: int = 0
    #: admission plan: prefix-matched blocks awaiting attachment
    plan: Optional[dict] = None
    #: prompt tokens served from the prefix cache instead of recompute
    prefix_tokens: int = 0
    #: tokens of this sequence VALIDLY ingested into the drafter cache
    draft_len: int = 0
    #: per-row random-draw counter: every sampling event (prefill,
    #: decode, each speculative position) consumes a fixed counter
    #: budget, so a request's token stream depends only on its own
    #: (seed, counter) history — deterministic across batch positions
    #: and restarts (a re-prefill replays from 0 and reproduces the
    #: original stream)
    rng_ctr: int = 0
    #: params version the PREFILL step actually ran under — what the
    #: migration packet stamps as its weight fence. Captured at the
    #: prefill (not at pack time): a hot swap landing between prefill
    #: and migration must fence the packet OUT, not relabel stale KV
    #: as current.
    params_version: Optional[int] = None
    #: monotonic stamp when this sequence's admission wave started
    #: its prefill (install time for a migrated sequence): the queue
    #: wait ends here
    t_admit: Optional[float] = None
    #: monotonic stamp of the first generated token (prefill-step end,
    #: or install time for a migrated sequence) — the decode span's
    #: start (docs/tracing.md)
    t_first: Optional[float] = None
    #: one monotonic stamp per entry of `out`: the end of the step that
    #: emitted it, shared by the rows of that step
    token_times: List[float] = field(default_factory=list)
    #: monotonic stamp when the sequence parked for migration — the
    #: traced park span's start (serve/kv_migrate.py records its end
    #: at pack time)
    parked_at: Optional[float] = None


class ContinuousBatcher:
    """Schedules an `AdmissionQueue` onto a `ShardedExecutor`."""

    def __init__(self, executor, queue: AdmissionQueue, *,
                 buckets: Sequence[int] = (32, 128, 512),
                 eos_id: Optional[int] = None,
                 replica_id: Optional[int] = None,
                 kv_crc: Optional[bool] = None,
                 on_kv_corrupt: str = "reprefill",
                 draft_executor=None,
                 spec_k: Optional[int] = None,
                 prefix_cache: Optional[bool] = None,
                 kv_tier: Optional[bool] = None,
                 kvtier_host_mb: Optional[int] = None,
                 kvtier_dir: Optional[str] = None):
        buckets = tuple(sorted(int(b) for b in buckets))
        if not buckets or buckets[0] < 1:
            raise ValueError(f"buckets must be positive ints; got {buckets}")
        if buckets[-1] > executor.max_len:
            raise ValueError(
                f"largest prefill bucket {buckets[-1]} exceeds the model "
                f"context {executor.max_len}")
        if on_kv_corrupt not in ("reprefill", "error"):
            raise ValueError(
                f"on_kv_corrupt must be 'reprefill' or 'error'; "
                f"got {on_kv_corrupt!r}")
        self.executor = executor
        self.queue = queue
        self.buckets = buckets
        # a model whose sequences hold per-row state beside their
        # blocks (models/sala_lm.py): what hands a row BLOCKS cannot
        # hand it the state that goes with them. Refused here, by
        # mechanism: a wrong answer is worse than a refusal
        stateful = bool(getattr(executor, "per_row_state", False))
        if stateful:
            name = type(executor.model).__name__
            if prefix_cache:
                raise ValueError(
                    f"prefix_cache=True with {name}: a radix hit hands a "
                    f"row its prefix's blocks, and the layers with "
                    f"recurrent state need the STATE at the hit's end, "
                    f"which nothing snapshots (serve/prefix.py)")
            if kv_tier:
                raise ValueError(
                    f"kv_tier=True with {name}: the tier demotes and "
                    f"promotes prefix BLOCKS; the per-row state at a "
                    f"prefix's end is not kept with them (serve/kvtier/)")
            if (spec_k or 0) > 0 or \
                    (draft_executor is not None and spec_k is None):
                raise ValueError(
                    f"speculative decoding with {name}: a rejected draft "
                    f"is rolled back by moving a row's length, and a "
                    f"recurrent state that has absorbed the draft cannot "
                    f"be rolled back (no snapshot to return to)")
            # the defaults that cannot apply are off, not an error
            prefix_cache, kv_tier = False, False
        #: rows a prefill step holds. None: the packed
        #: ``[max_batch, bucket]`` step, every admitted row at the
        #: longest one's bucket. A model whose config names
        #: ``prefill_rows`` (long prompts: models/routed_lm.py) is
        #: prefilled row-compact instead, ``[prefill_rows, bucket]`` a
        #: step with each step at its own rows' bucket, so a prefill's
        #: work follows the prompt tokens admitted and not
        #: ``max_batch x`` the longest: the rows of such a step are
        #: addressed through their block tables
        rows = getattr(executor.model.cfg, "prefill_rows", None)
        self.prefill_rows: Optional[int] = (
            min(int(rows), executor.max_batch) if rows else None)
        self.eos_id = eos_id
        #: fleet identity (None = standalone): labels the metric
        #: series and addresses chaos serve.step / serve.kv faults
        self.replica_id = replica_id
        cfg = None
        if kv_crc is None or spec_k is None or prefix_cache is None \
                or kv_tier is None:
            from ..core.config import Config
            cfg = Config.from_env()
        #: per-block crc-on-write / verify-on-read
        #: (HOROVOD_SERVE_KV_CRC or explicit): every cache write is
        #: folded into the crc ledger and every retiring request's
        #: valid prefix is re-read and verified BEFORE its tokens can
        #: reach a client — a corrupted cache either re-prefills from
        #: the prompt or fails cleanly ("error"/kv_corrupt), never
        #: returns garbage. Costs one device->host readback of the
        #: written slice per step plus one full-prefix readback per
        #: retiring request; an integrity option for chaos runs and
        #: paranoid deployments, off by default.
        self.kv_crc = bool(cfg.serve_kv_crc if kv_crc is None else kv_crc)
        self.on_kv_corrupt = on_kv_corrupt
        self.kv_corruptions_detected = 0
        self.kv_corruptions_injected = 0
        self.kv_reprefills = 0
        #: a fired serve.kv corrupt waiting for a written row, (row,)
        self._pending_corrupt = None
        # unservable prompts get shed at submit time, not discovered
        # holding a decode row
        if queue.max_prompt_len is None or \
                queue.max_prompt_len > buckets[-1]:
            queue.max_prompt_len = buckets[-1]

        # -- KV storage: the block pool, with the radix prefix cache
        # over it when enabled
        pool = BlockPool(executor.kv_pool_blocks, executor.kv_block_size)
        self.kv = PagedKVCache(executor.max_batch,
                               executor.blocks_per_seq, pool)
        if prefix_cache is None:
            prefix_cache = cfg.serve_prefix_cache
        self.prefix: Optional[RadixPrefixCache] = (
            RadixPrefixCache(pool, replica_id=replica_id)
            if prefix_cache else None)
        if self.prefix is not None:
            self.kv.evictable = self.prefix.evictable_blocks
            self.kv.evictor = self.prefix.evict
        #: params version the prefix cache's contents were computed
        #: under; a swap flushes the cache before any further lookup
        self._prefix_version = executor.params_version
        #: router-raised out-of-band flush (re-admission gate)
        self._prefix_flush = threading.Event()

        # -- fleet KV tier (serve/kvtier/): evicted prefix runs demote
        # down the HBM -> host -> disk ladder and promote back through
        # the verified install path. With the prefix cache off the
        # knob is inert.
        if kv_tier is None:
            kv_tier = cfg.serve_kvtier
        self.kvtier: Optional[ReplicaKVTier] = None
        if kv_tier and self.prefix is not None:
            if kvtier_host_mb is None or kvtier_dir is None:
                if cfg is None:
                    from ..core.config import Config
                    cfg = Config.from_env()
                if kvtier_host_mb is None:
                    kvtier_host_mb = cfg.serve_kvtier_host_mb
                if kvtier_dir is None:
                    kvtier_dir = cfg.serve_kvtier_dir
            self.kvtier = ReplicaKVTier(
                executor, self.kv.pool, self.prefix,
                replica_id=replica_id, kv_crc=self.kv_crc,
                host_bytes=int(kvtier_host_mb) * 1024 * 1024,
                spill_dir=kvtier_dir or None)
            self.prefix.on_evict = self.kvtier.on_evict

        # -- speculative decoding: a draft executor proposes spec_k
        # tokens per iteration; the target verifies them in one step
        if spec_k is None:
            spec_k = cfg.serve_spec_k
        self.spec_k = int(spec_k) if draft_executor is not None else 0
        self.draft = draft_executor if self.spec_k > 0 else None
        if self.draft is not None:
            if self.draft.max_batch != executor.max_batch:
                raise ValueError(
                    f"draft max_batch {self.draft.max_batch} must equal "
                    f"the target's {executor.max_batch} (rows pair 1:1)")
            if buckets[-1] > self.draft.max_len:
                raise ValueError(
                    f"largest prefill bucket {buckets[-1]} exceeds the "
                    f"draft model context {self.draft.max_len}")
            # the drafter's rows mirror the target's 1:1 and its state
            # is thrown away with its row, so its pool gets no block
            # accounting: row r owns blocks [r * n, (r + 1) * n) for
            # good, read through this one table
            n = self.draft.blocks_per_seq
            if self.draft.kv_pool_blocks < executor.max_batch * n:
                raise ValueError(
                    f"the draft pool of {self.draft.kv_pool_blocks} "
                    f"blocks cannot hold {executor.max_batch} rows of "
                    f"{n} (one fixed run a row)")
            self._draft_tables = np.arange(
                executor.max_batch * n, dtype=np.int32).reshape(
                executor.max_batch, n)
        #: (per-SEQUENCE target verify+decode step participations,
        #: tokens emitted by them) — the machine-independent
        #: speculative win tests/test_serve_paged.py asserts (< 0.7
        #: target steps per generated token). Row-granular on purpose: batched plain
        #: decode pegs at exactly 1.0 (each row pays one target step
        #: per token it emits), so only speculation can push the ratio
        #: below 1 — batching wins cannot masquerade as draft wins.
        self.gen_steps = 0
        self.gen_tokens = 0

        self._active: Dict[int, _Active] = {}   # row -> sequence
        self._reprefill: List[ServeRequest] = []
        # -- disaggregated serving (serve/disagg.py, serve/kv_migrate.py)
        #: PARKED sequences: cleanly retired hold_kv requests whose row
        #: + blocks stay allocated awaiting KV-block migration to a
        #: decode replica. Keyed by request rid; mutations under
        #: _parked_lock (a LEAF lock: nothing else is ever taken under
        #: it), reads from the endpoint thread are snapshot copies.
        self.parked: Dict[int, _Active] = {}
        self._parked_lock = threading.Lock()
        #: rids whose parked row the endpoint released (migration done
        #: or abandoned) — freed on the scheduler thread at step top
        self._parked_release: List[int] = []
        #: pin counts: a parked row being PACKED for migration must
        #: not be freed (released or TTL-reaped) mid-read — the pool
        #: could re-issue its blocks to a new owner and the pack would
        #: stamp self-consistent crcs over the wrong sequence's bytes
        self._parked_pins: Dict[int, int] = {}
        #: pending migrated-sequence installs (endpoint-submitted;
        #: installed on the scheduler thread through the same
        #: reservation-gated capacity check admission uses)
        self._migrate_in: List[dict] = []
        self._migrate_lock = threading.Lock()
        #: how long a parked row outlives its request deadline before
        #: the reaper frees it (the router died / abandoned it)
        self.parked_grace_s = 5.0
        self.migrations_in = 0
        self.migrate_rejects = 0
        self.parked_reaped = 0
        #: migration payloads whose per-block crc failed on arrival —
        #: incremented by the endpoint (note_migrate_corrupt), counted
        #: here so /healthz and the soak verdict see one number
        self.migrate_corrupt_detected = 0
        self.iterations = 0
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._dead = False
        #: fleet liveness hook: called once per scheduling iteration
        #: (busy or idle) on the batcher thread; a crashed/stuck
        #: replica stops calling it, which is the router's signal
        self.heartbeat: Optional[Callable[[], None]] = None
        #: router-visible drain flag (mirrored into /healthz)
        self.draining = False
        # -- metrics: time-to-first-token (admission wait + prefill),
        # live KV occupancy and the block-occupancy gauge.
        # Standalone batchers claim fresh; fleet replicas use labeled
        # children (same discipline as AdmissionQueue/ShardedExecutor).
        rl = {} if replica_id is None else {"replica": str(replica_id)}
        R = obs_metrics.get_registry()
        if replica_id is None:
            R.unregister("hvd_serve_ttft_ms")
            R.unregister("hvd_serve_kv_occupancy")
            R.unregister("hvd_serve_kv_blocks_in_use")
            R.unregister("hvd_serve_spec_accept_rate")
        self._m_ttft = R.histogram(
            "hvd_serve_ttft_ms",
            "time to first generated token (submit -> prefill), ms",
            rl or None)
        self._m_occupancy = R.gauge(
            "hvd_serve_kv_occupancy",
            "fraction of KV pool blocks in use (tokens resident, not "
            "sequences)", rl or None)
        self._m_blocks = R.gauge(
            "hvd_serve_kv_blocks_in_use",
            "KV pool blocks currently allocated",
            rl or None)
        #: per-row recurrent state the live rows hold (a model without
        #: any registers no such series)
        self._m_state = None
        if stateful:
            if replica_id is None:
                R.unregister("hvd_serve_state_bytes")
            self._m_state = R.gauge(
                "hvd_serve_state_bytes",
                "per-row recurrent state held by live rows, bytes (not "
                "addressed by block tables)", rl or None)
        self._m_accept = R.histogram(
            "hvd_serve_spec_accept_rate",
            "speculative decode: fraction of draft tokens accepted per "
            "verify step", rl or None, bounds=_ACCEPT_BOUNDS)
        self._m_kv_corrupt = R.counter(
            "hvd_serve_kv_corruptions_total",
            "KV rows whose verify-on-read crc failed (corruption "
            "caught before reaching a client)", rl or None)
        self._m_migrate_corrupt = R.counter(
            "hvd_serve_migrate_corrupt_total",
            "migrated-KV payloads whose per-block crc failed on "
            "arrival (corruption caught before install)", rl or None)
        #: optional weight-stream subscriber (redist/stream.py): polled
        #: between scheduling iterations, rate-limited so an idle or
        #: not-yet-published channel cannot stall the decode loop
        self._weights = None
        self._weights_interval = 0.25
        self._weights_next_poll = 0.0

    # -- hot weight streaming ------------------------------------------------
    def attach_weights(self, subscriber,
                       min_interval_s: float = 0.25) -> None:
        """Attach a ``WeightSubscriber``: the scheduler polls it
        between iterations — at most every ``min_interval_s`` seconds,
        because a poll against a channel with no published head blocks
        for the subscriber's KV timeout (~50 ms), which must not be
        paid per ~ms decode iteration — and adopts newer param
        versions via ``executor.swap_params`` (the executor's step
        lock is the no-mid-step fence). A transient stream failure
        logs and keeps serving on the current weights; it never takes
        the fleet down."""
        self._weights = subscriber
        self._weights_interval = float(min_interval_s)
        self._weights_next_poll = 0.0       # first step polls
        self._weights_thread = None

    def _maybe_swap_weights(self) -> None:
        """Kick (never join) a background adoption: the KV fetch, crc
        verify, assembly and device placement of a multi-GB tree must
        not run inline on the decode scheduling thread — only the final
        pointer swap is fenced, inside ``swap_params``'s step lock, so
        in-flight requests pay at most one step of swap latency, never
        the full adoption."""
        if self._weights is None:
            return
        now = time.monotonic()
        if now < self._weights_next_poll:
            return
        t = self._weights_thread
        if t is not None and t.is_alive():
            return                        # previous adoption in flight
        self._weights_next_poll = now + self._weights_interval

        def adopt():
            try:
                got = self._weights.poll()
                if got is not None:
                    version, tree = got
                    t_sw = time.monotonic()
                    self.executor.swap_params(tree, version=version)
                    _trace_recorder().record_process(
                        "weight_fence", t_sw, time.monotonic(),
                        version=version)
            except Exception as e:  # noqa: BLE001 — serve on stale
                import logging
                logging.getLogger("horovod_tpu").warning(
                    "weight stream poll failed (serving continues on "
                    "version %s): %s", self.executor.params_version, e)

        self._weights_thread = threading.Thread(
            target=adopt, daemon=True, name="hvd-serve-weights")
        self._weights_thread.start()

    # -- prefix-cache version fencing ---------------------------------------
    def request_prefix_flush(self) -> None:
        """Out-of-band invalidation (fleet re-admission gate): the
        flush itself runs on the scheduler thread at the top of the
        next iteration, BEFORE any admission can match — single-writer
        discipline, no lock needed."""
        self._prefix_flush.set()

    def _maybe_flush_prefix(self) -> None:
        if self.prefix is None:
            return
        v = self.executor.params_version
        if v != self._prefix_version or self._prefix_flush.is_set():
            dropped = self.prefix.flush()
            self._prefix_version = v
            self._prefix_flush.clear()
            if self.kvtier is not None:
                # ladder entries under the old version can never
                # promote (the fence refuses them): drop the host ring
                # and tell the fleet index this replica holds nothing
                self.kvtier.on_flush()
            if dropped:
                logger.info(
                    "serve replica %s: prefix cache flushed (%d runs) "
                    "on weight version change -> %s",
                    self.replica_id, dropped, v)

    # -- shape warmup --------------------------------------------------------
    def warmup(self) -> None:
        """Compile every shape the scheduler can launch — decode, one
        prefill per bucket, the speculative verify ([max_batch,
        spec_k+1]) and draft shapes, and the CoW block copy — with
        all-False masks (state untouched). Run once at startup so
        overload/churn never meets a compile; the draft/verify shapes
        joining this set is what keeps the jit cache flat when
        speculation is on."""
        B = self.executor.max_batch
        zero = np.zeros(B, np.int32)
        off = np.zeros(B, bool)
        tbl = np.full((B, self.executor.blocks_per_seq), -1, np.int32)
        R = self.prefill_rows or B      # a prefill step's rows
        for b in self.buckets:
            self.executor.step(
                np.zeros((R, b), np.int32), zero[:R], off[:R], zero[:R],
                kind="prefill", block_tables=tbl[:R],
                **self._slots_arg(zero[:R]))
        self.executor.step(np.zeros((B, 1), np.int32), zero, off, zero,
                           kind="decode", block_tables=tbl)
        self.executor.copy_kv_block(0, 0)   # compile the CoW copy
        if self.draft is not None:
            self.executor.step(
                np.zeros((B, self.spec_k + 1), np.int32), zero, off,
                zero, kind="verify", block_tables=tbl)
            for b in self.buckets:
                self.draft.step(np.zeros((B, b), np.int32), zero, off,
                                zero, kind="prefill",
                                block_tables=self._draft_tables)
            self.draft.step(np.zeros((B, 1), np.int32), zero, off, zero,
                            kind="decode",
                            block_tables=self._draft_tables)

    def _slots_arg(self, slots) -> dict:
        """``state_slots`` for a row-compact prefill step of a model
        with per-row state: which batch slot each row of the step
        stands for. Any other model's step takes no such argument."""
        if not getattr(self.executor, "per_row_state", False):
            return {}
        return {"state_slots": np.asarray(slots, np.int32)}

    # -- chaos guards (one attribute read when disarmed) ---------------------
    def _fire_step_chaos(self) -> None:
        """``serve.step`` site: crash kills THIS replica (the scheduler
        thread dies and heartbeats stop — the router's problem from
        here); delay/slow_rank sleep inside the injector, stalling the
        replica mid-decode exactly like an overloaded host."""
        if _chaos._INJ is None:
            return
        f = _chaos.fire("serve.step", peer=self.replica_id,
                        step=self.iterations)
        if f is not None and f.kind == "crash":
            raise ReplicaDead(
                f"chaos: replica {self.replica_id} crashed mid-decode "
                f"(iteration {self.iterations})")

    def _fire_kv_chaos(self) -> None:
        """``serve.kv`` site: corrupt flips a real bit inside a live
        sequence's device cache, in the row's newest BLOCK of the pool
        (detection must come from the per-block crc ledger, nothing
        else knows). A corrupt fired on an iteration
        with no written data is DEFERRED to the next one that has some,
        so an exact-``at`` address always lands exactly one flip."""
        if _chaos._INJ is None and self._pending_corrupt is None:
            return
        if _chaos._INJ is not None:
            f = _chaos.fire("serve.kv", peer=self.replica_id,
                            step=self.iterations)
            if f is not None and f.kind == "corrupt" \
                    and self._pending_corrupt is None:
                self._pending_corrupt = (f.slot,)
        if self._pending_corrupt is not None and self._active:
            want = self._pending_corrupt[0]
            slot = want if (want is not None and want in self._active) \
                else min(self._active)
            length = self._active[slot].cache_len
            if length > 0:
                self._pending_corrupt = None
                bs = self.kv.block_size
                bi = (int(length) - 1) // bs
                blk = self.kv.blocks[slot][bi]
                self.executor.corrupt_kv_block(
                    blk, ((int(length) - 1) % bs) + 1)
                self.kv_corruptions_injected += 1

    # -- one scheduling iteration -------------------------------------------
    def step(self) -> bool:
        """Run one retire/admit/prefill/decode iteration; returns True
        while there is (or may be) work in flight."""
        hb = self.heartbeat
        if hb is not None:
            hb()
        self._fire_step_chaos()
        rec = _trace_recorder()
        with rec.span("sched_iteration"):
            self._iterate(rec)
        self.iterations += 1
        return bool(self._active) or bool(self._reprefill) \
            or self.queue.depth() > 0 or bool(self._migrate_in) \
            or bool(self._parked_release) \
            or (self.kvtier is not None and self.kvtier.has_grafts())

    def _iterate(self, rec) -> None:
        """The body of one iteration, inside its ``sched_iteration``
        span; each phase is a child span in the process's span ring
        (docs/tracing.md, "The local flight recorder")."""
        self._maybe_swap_weights()
        # stale-weight KV must never serve a new version: any adopted
        # swap (or router-requested flush) invalidates the prefix cache
        # BEFORE this iteration can match against it
        self._maybe_flush_prefix()
        with rec.span("sched_retire"):
            # expired-but-still-queued requests get their structured
            # deadline completion NOW, even when every row is busy —
            # within one iteration, not at row-drain time
            self.queue.reap_expired()
            # migration plumbing (single-writer: all pool/row
            # bookkeeping happens HERE, on the scheduler thread — the
            # endpoint only enqueues): free rows the endpoint released,
            # reap abandoned parked rows, install migrated sequences
            # BEFORE admission so a mid-stream arrival is never starved
            # by local newcomers
            self._drain_parked_release()
            self._install_migrated()
            self._retire()
        with rec.span("sched_admit"):
            # KV tier (serve/kvtier/): install router-pulled runs, then
            # promote ladder-held prefixes of waiting prompts BEFORE
            # the admission wave matches — a promoted block is
            # indistinguishable from a locally cached one by the time
            # _plan walks the tree
            if self.kvtier is not None:
                if self.kvtier.has_grafts():
                    self.kvtier.install_grafts()
                if not self.kvtier.empty():
                    for p in self.queue.peek_prompts(
                            self.executor.max_batch):
                        self.kvtier.promote_for(p)
            admitted = self._admit()
        if admitted:
            with rec.span("sched_prefill"):
                self._prefill(admitted)
            with rec.span("sched_retire"):
                self._retire()  # a 1-token request finishes at prefill
        if self._active:
            with rec.span("sched_decode"):
                self._decode()
        # evaluated EVERY iteration, busy or idle: the iteration counter
        # ticks regardless, so an exact-'at' corrupt landing while
        # the replica is idle must still be captured (and deferred to
        # the next written row) — inside the busy branch the counter
        # would walk past the address without fire() ever seeing it
        self._fire_kv_chaos()
        if self._active:
            with rec.span("sched_retire"):
                self._retire()

    def run(self, max_iterations: Optional[int] = None) -> None:
        """Drive until drained (loopback/bench mode)."""
        it = 0
        while self.step():
            it += 1
            if max_iterations is not None and it >= max_iterations:
                break

    # -- background service mode (http front end / fleet replica) -----------
    def start(self) -> None:
        if self._thread is not None:
            return
        self._stop.clear()
        self._dead = False

        def loop():
            try:
                while not self._stop.is_set():
                    if not self.step():
                        # drained: sleep until a submit wakes us
                        self.queue.wait_for_work(timeout=0.05)
            except BaseException as e:  # noqa: BLE001 — replica death
                # The thread dying IS the failure signal: alive() goes
                # False, heartbeats stop, /healthz turns 503 and the
                # fleet router ejects this replica. Nothing here may
                # mask that by keeping the loop running.
                self._dead = True
                logger.error(
                    "serve replica %s batcher thread died: %s",
                    self.replica_id, e)

        self._thread = threading.Thread(target=loop, daemon=True,
                                        name="hvd-serve-batcher")
        self._thread.start()

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=10)
            self._thread = None

    def alive(self) -> bool:
        """The liveness signal /healthz and the fleet router consume:
        False once ``stop()`` ran or the scheduler thread died (chaos
        crash, unhandled error). A not-yet-started batcher (loopback
        ``run()`` mode) counts as alive — the caller drives it."""
        if self._stop.is_set() or self._dead:
            return False
        t = self._thread
        return t.is_alive() if t is not None else True

    def load(self) -> float:
        """The fleet router's capacity signal: waiting plus in-flight,
        with in-flight measured in the unit that actually limits this
        batcher — BLOCKS in use scaled to row-equivalents. Two replicas
        with the same sequence count can differ several-fold in memory
        pressure (one long context vs many short ones); routing on
        blocks sends the next long prompt to the replica that can
        actually hold it."""
        return self.queue.depth() + \
            self.kv.pool.in_use() / self.executor.blocks_per_seq

    # -- disaggregated serving: park / migrate-install ----------------------
    def parked_seq(self, rid: int) -> Optional[_Active]:
        """The parked sequence for request ``rid`` (None if unknown /
        already released). A point-in-time read; callers that go on
        to READ the row's blocks must hold a pin
        (:meth:`pin_parked`) or the TTL reaper could free — and the
        pool re-issue — those blocks mid-read."""
        with self._parked_lock:
            return self.parked.get(rid)

    def pin_parked(self, rid: int) -> Optional[_Active]:
        """Claim a read pin on ``rid``'s parked row (None if not
        parked): while any pin is held, neither release_parked nor
        the TTL reaper will free the row — the migration pack's
        device reads see stable blocks. Balance with
        :meth:`unpin_parked`."""
        with self._parked_lock:
            seq = self.parked.get(rid)
            if seq is not None:
                self._parked_pins[rid] = \
                    self._parked_pins.get(rid, 0) + 1
            return seq

    def unpin_parked(self, rid: int) -> None:
        with self._parked_lock:
            n = self._parked_pins.get(rid, 0) - 1
            if n > 0:
                self._parked_pins[rid] = n
            else:
                self._parked_pins.pop(rid, None)

    def release_parked(self, rid: int) -> None:
        """Ask the scheduler to free ``rid``'s parked row (migration
        landed or was abandoned). Endpoint-thread safe; idempotent."""
        with self._parked_lock:
            if rid in self.parked:
                self._parked_release.append(rid)
        self.queue._work.set()   # wake an idle scheduler to free it

    def _drain_parked_release(self) -> None:
        """Scheduler-thread half of release_parked, plus the TTL
        reaper: a parked row whose router died mid-orchestration must
        not hold pool blocks forever."""
        now = time.monotonic()
        with self._parked_lock:
            pinned = set(self._parked_pins)
            # a pinned row (mid-pack on the endpoint thread) is never
            # freed this iteration: releases defer to the next drain,
            # reaps re-qualify next time around
            rids = [r for r in self._parked_release
                    if r not in pinned]
            self._parked_release = [r for r in self._parked_release
                                    if r in pinned]
            reap = [rid for rid, seq in self.parked.items()
                    if now > seq.req.deadline + self.parked_grace_s
                    and rid not in rids and rid not in pinned]
            self.parked_reaped += len(reap)
            seqs = [self.parked.pop(rid) for rid in rids + reap
                    if rid in self.parked]
        for seq in seqs:
            self.kv.free_row(seq.slot)

    def submit_migrated(self, meta: dict,
                        blocks: List[dict]) -> dict:
        """Enqueue a migrated sequence for install (the decode-side
        receive path, serve/kv_migrate.py). ``meta`` carries the
        sequence state (prompt, emitted tokens, cache_len, sampling,
        rng_ctr, weights_version, deadline_ms); ``blocks`` is one dict
        per KV block — {"filled", "leaf_bytes", "crcs"} — already
        crc-VERIFIED by the caller. Returns the pending entry; the
        caller waits on ``entry["evt"]`` and reads
        ``entry["outcome"]``/``entry["handle"]`` — the actual install
        (capacity reservation, device writes, ledger seeding, version
        fence) runs on the scheduler thread at the top of the next
        iteration."""
        from .kv_migrate import refuse_per_row_state
        refuse_per_row_state(self.executor)
        from .queue import ServeHandle
        handle = ServeHandle(int(meta.get("rid", -1)))
        entry = {"meta": dict(meta), "blocks": blocks,
                 "handle": handle, "outcome": None,
                 "evt": threading.Event()}
        with self._migrate_lock:
            self._migrate_in.append(entry)
        self.queue._work.set()   # wake an idle scheduler to install
        return entry

    def note_migrate_corrupt(self) -> None:
        """Endpoint hook: a migration payload failed its per-block crc
        on arrival (counted before any install could happen)."""
        self.migrate_corrupt_detected += 1
        self._m_migrate_corrupt.inc()

    def _install_migrated(self) -> None:
        with self._migrate_lock:
            pending, self._migrate_in = self._migrate_in, []
        for ent in pending:
            try:
                outcome = self._install_one(ent)
            except Exception as e:  # noqa: BLE001 — a torn install must
                # surface as a structured reject, never kill the
                # scheduler thread (the sender re-prefills)
                logger.error(
                    "serve replica %s: migrated install failed: %s",
                    self.replica_id, e)
                outcome = ("error", str(e)[:200])
            if outcome[0] != "installed":
                self.migrate_rejects += 1
            ent["outcome"] = outcome
            ent["evt"].set()

    def _install_one(self, ent: dict) -> tuple:
        """Install one migrated sequence: weight-version fence,
        reservation-gated capacity, device block writes, crc-ledger
        seeding, batch enrollment. Returns ("installed", None) or a
        structured ("version_mismatch"|"rejected"|"incompatible",
        detail) the endpoint acks back to the sender."""
        meta, blocks = ent["meta"], ent["blocks"]
        # -- weight-version FENCE: migrated KV was computed under the
        # sender's version; installing it under any other version
        # would mix cache bytes across versions — the sender
        # re-prefills instead, never stale-KV tokens
        want = meta.get("weights_version")
        have = self.executor.params_version
        if want != have:
            return ("version_mismatch",
                    {"have": have, "want": want})
        cache_len = int(meta["cache_len"])
        out = [int(t) for t in meta.get("out", [])]
        max_new = int(meta["max_new_tokens"])
        remaining = max_new - len(out)
        if remaining <= 0 or cache_len >= self.executor.max_len:
            return ("incompatible", "sequence already complete")
        margin = self.spec_k + 1 if self.draft is not None else 0
        budget = min(cache_len + remaining + margin,
                     self.executor.max_len)
        bs = self.kv.block_size
        if int(meta.get("block_size", bs)) != bs:
            return ("incompatible",
                    f"block size {meta.get('block_size')} != {bs}")
        n_payload = -(-cache_len // bs)
        if len(blocks) != n_payload:
            return ("incompatible",
                    f"{len(blocks)} payload blocks for cache_len "
                    f"{cache_len} (need {n_payload})")
        need_total = self.kv.blocks_needed(budget)
        # the RESERVATION-GATED admission check local newcomers pass
        # through — a migrated install can never starve an admitted
        # sequence either
        if not self.kv.can_admit(need_total):
            return ("rejected", self.queue._retry_after_ms())
        row = self.kv.alloc_row(need_total)
        try:
            fresh = self.kv.ensure(row, cache_len)
            assert len(fresh) == n_payload
            self.executor.install_kv_blocks(
                fresh, [b["leaf_bytes"] for b in blocks],
                [int(b["filled"]) for b in blocks])
            if self.kv_crc:
                # seed the per-block ledger from the VERIFIED bytes
                # so verify-on-read covers the migrated prefix
                # exactly like locally written KV
                for blk, b in zip(fresh, blocks):
                    self.kv.pool.crc_reset(
                        blk, b["leaf_bytes"], int(b["filled"]))
        except ValueError as e:
            self.kv.free_row(row)
            return ("incompatible", str(e)[:200])
        # re-check the fence: a hot swap may have landed between the
        # check above and the last device write (swap_params only
        # fences individual steps/writes, not this whole span)
        if self.executor.params_version != want:
            self.kv.free_row(row)
            return ("version_mismatch",
                    {"have": self.executor.params_version,
                     "want": want})
        now = time.monotonic()
        req = ServeRequest(
            rid=int(meta.get("rid", -1)),
            prompt=[int(t) for t in meta["prompt"]],
            max_new_tokens=max_new,
            deadline=now + float(meta.get("deadline_ms", 30000.0))
            / 1000.0,
            submitted_at=now, handle=ent["handle"],
            temperature=float(meta.get("temperature", 0.0)),
            top_p=float(meta.get("top_p", 1.0)),
            seed=int(meta.get("seed", 0)),
            trace=meta.get("trace"))
        # the handle was made before its request: stamps start here
        # (the tokens the sender emitted all carry the install time)
        req.handle._request, req.handle.t_submit = req, now
        seq = _Active(req=req, slot=row, out=out,
                      cache_len=cache_len,
                      rng_ctr=int(meta.get("rng_ctr", 1)),
                      t_admit=now, t_first=now,
                      token_times=[now] * len(out))
        self.kv.lengths[row] = cache_len
        self._active[row] = seq
        self.migrations_in += 1
        return ("installed", None)

    # -- internals -----------------------------------------------------------
    def _stats(self) -> Optional[dict]:
        """Refresh the occupancy gauges; the SERVE timeline row's
        fields only where the executor has a timeline to write to."""
        occ = self.kv.occupancy()
        self._m_occupancy.set(occ)
        self._m_blocks.set(self.kv.pool.in_use())
        if self._m_state is not None:
            self._m_state.set(self.kv.live() * self.executor.state_row_bytes)
        if self.executor.timeline is None:
            return None
        return {"queue_depth": self.queue.depth(),
                "occupancy": round(occ, 3),
                "shed": self.queue.shed_count}

    def _resolve(self, seq: _Active, status: str, ms: float, *,
                 error: Optional[str] = None) -> None:
        """Hand the sequence's stamps to its handle and resolve it
        (an errored request delivers no tokens, so no token stamps)."""
        h = seq.req.handle
        h.t_admit, h.t_first = seq.t_admit, seq.t_first
        keep = error is None
        h.token_times = list(seq.token_times) if keep else []
        h._resolve(seq.out if keep else [], status, latency_ms=ms,
                   error=error)

    # -- on-device sampling row data -----------------------------------------
    def _sample_args(self, slots, ctr_offset: int = 0,
                     rows=None) -> dict:
        """Per-row sampling arrays for the jitted step: each active
        row's request temperature / top-p / seed plus its draw counter
        (``rng_ctr + ctr_offset``). Rows not listed stay at the greedy
        defaults (temperature 0) and are masked out anyway. `rows`
        (parallel to `slots`): where each slot's sequence sits in a
        row-compact prefill step of `prefill_rows` rows; None = in its
        own batch slot."""
        B = self.executor.max_batch if rows is None else self.prefill_rows
        s = {"temperature": np.zeros(B, np.float32),
             "top_p": np.ones(B, np.float32),
             "seed": np.zeros(B, np.uint32),
             "ctr": np.zeros(B, np.int32)}
        for at, slot in zip(slots if rows is None else rows, slots):
            seq = self._active[slot]
            req = seq.req
            s["temperature"][at] = getattr(req, "temperature", 0.0)
            s["top_p"][at] = getattr(req, "top_p", 1.0)
            s["seed"][at] = int(getattr(req, "seed", 0)) & 0xFFFFFFFF
            s["ctr"][at] = seq.rng_ctr + ctr_offset
        return s

    # -- crc plumbing (block-granular) ---------------------------------------
    def _crc_write(self, slot: int, lo: int, hi: int) -> None:
        """Fold cache positions ``[lo, hi)`` just written for row
        ``slot`` into the per-BLOCK crc ledger; an overwrite below a
        block's high-water mark (speculative rollback) recomputes that
        block's crc from a fresh readback — streaming crc32 cannot be
        truncated."""
        if not self.kv_crc or hi <= lo:
            return
        bs = self.kv.block_size
        pool = self.kv.pool
        blocks = self.kv.blocks[slot]
        for bi in range(lo // bs, (hi - 1) // bs + 1):
            blk = blocks[bi]
            blo = max(lo - bi * bs, 0)
            bhi = min(hi - bi * bs, bs)
            filled = pool.crc_filled(blk)
            if blo == filled:
                pool.crc_stream(
                    blk, self.executor.kv_block_bytes(blk, blo, bhi),
                    bhi)
            else:
                new_filled = max(filled, bhi)
                pool.crc_reset(
                    blk,
                    self.executor.kv_block_bytes(blk, 0, new_filled),
                    new_filled)

    def _kv_verify(self, seq: _Active) -> bool:
        """Verify-on-read: re-read the sequence's whole valid prefix
        and check it against the write-side crc ledger. Runs only at
        retirement (and only with kv_crc on), so a request's tokens are
        NEVER released to a client from cache bytes that changed behind
        the scheduler's back. Sequences verify per BLOCK — shared
        prefix blocks included, under the pool-wide ledger — and each
        block over exactly its covered prefix (the ledger's high-water
        mark can exceed cache_len: a verify step's rejected tail is
        written but not accepted)."""
        if not self.kv_crc or seq.cache_len <= 0:
            return True
        pool = self.kv.pool
        for blk in self.kv.blocks[seq.slot]:
            filled = pool.crc_filled(blk)
            if filled == 0:
                continue
            if not pool.crc_check(
                    blk, self.executor.kv_block_bytes(blk, 0, filled)):
                return False
        return True

    def _retire(self) -> None:
        now = time.monotonic()
        for slot in list(self._active):
            seq = self._active[slot]
            req = seq.req
            done_ok = (len(seq.out) >= req.max_new_tokens
                       or (self.eos_id is not None and seq.out
                           and seq.out[-1] == self.eos_id)
                       or seq.cache_len >= self.executor.max_len)
            expired = req.expired(now)
            if not (done_ok or expired):
                continue
            ms = (now - req.submitted_at) * 1000.0
            if not self._kv_verify(seq):
                # corrupted KV: the generated tokens are untrusted and
                # must not reach the client. Re-prefill from the prompt
                # (a fresh row, a clean generation) while the deadline
                # allows; otherwise fail cleanly.
                self.kv_corruptions_detected += 1
                self._m_kv_corrupt.inc()
                logger.warning(
                    "serve replica %s: KV row %d failed crc "
                    "verify-on-read (request %d) — %s",
                    self.replica_id, slot, req.rid,
                    "re-prefilling" if self.on_kv_corrupt == "reprefill"
                    and not expired else "failing the request")
                if self.prefix is not None:
                    # the corrupt block may BE a cached prefix run; a
                    # re-prefill matching it would corrupt again
                    self.prefix.flush()
                self.kv.free_row(slot)
                del self._active[slot]
                if self.on_kv_corrupt == "reprefill" and not expired:
                    self.kv_reprefills += 1
                    self._reprefill.append(req)
                else:
                    self._resolve(seq, "error", ms, error="kv_corrupt")
                continue
            if seq.t_first is not None and not req.hold_kv:
                trace, root = req.trace_ids()
                _trace_recorder().record_local(
                    "decode", seq.t_first, now, trace=trace,
                    parent=root, ship=req.trace, rid=req.rid,
                    tokens=len(seq.out), token_times=seq.token_times)
            if expired and not done_ok:
                self.queue.expired_count += 1
                self._resolve(seq, "expired", ms)
            elif req.hold_kv:
                # disaggregated prefill: PARK the verified sequence —
                # row and blocks stay allocated so the endpoint can
                # migrate them (serve/kv_migrate.py pack_parked).
                # Parked BEFORE the handle resolves: the endpoint's
                # migrate op keys off the resolution and must find the
                # entry already there.
                seq.parked_at = now
                with self._parked_lock:
                    self.parked[req.rid] = seq
                del self._active[slot]
                self._resolve(seq, "ok", ms)
                self.queue.note_service_ms(ms)
                continue
            else:
                self._resolve(seq, "ok", ms)
                self.queue.note_service_ms(ms)
            self.kv.free_row(slot)
            del self._active[slot]

    # -- admission -----------------------------------------------------------
    def _seq_token_budget(self, req: ServeRequest) -> int:
        """Worst-case cache positions this request can touch: prompt +
        generation budget + the speculative write-ahead margin."""
        margin = self.spec_k + 1 if self.draft is not None else 0
        return min(len(req.prompt) + req.max_new_tokens + margin,
                   self.executor.max_len)

    def _plan(self, req: ServeRequest) -> dict:
        """Admission plan: prefix match (references pinned) plus
        the fresh-block budget the admission gate charges."""
        if self.prefix is not None:
            full, partial, m = self.prefix.match(req.prompt)
        else:
            full, partial, m = [], None, 0
        total = self.kv.blocks_needed(self._seq_token_budget(req))
        # the partially matched block still costs a fresh block (its
        # copy-on-write copy), so only FULL shared blocks are free
        return {"full": full, "partial": partial, "m": m,
                "new_blocks": max(total - len(full), 0)}

    def _release_plan(self, plan: dict) -> None:
        if self.prefix is None:
            return
        self.prefix.release(plan["full"])
        if plan["partial"] is not None:
            self.prefix.release([plan["partial"][0]])

    def _admit(self) -> List[_Active]:
        free_rows = self.kv.num_rows - self.kv.live()
        if free_rows <= 0:
            return []
        admitted: List[_Active] = []
        # ONE evictable-tree walk per admission wave (the live hook is
        # O(cached blocks) and fits() runs under the queue lock); the
        # wave's own acceptances are charged against the snapshot:
        # `planned` for reservations that land at alloc_row, `pinned`
        # for matched prefix blocks whose new reference may have made
        # a previously-evictable run un-evictable. Each candidate is
        # charged for its OWN pins too, not just its predecessors' —
        # a request whose match pins the last evictable runs must not
        # be admitted against them (free + evictable - reserved would
        # go negative the moment the pins land, and a RESERVED append
        # of an already-running sequence would find the pool dry).
        # All three charges only ever UNDER-admit — the reservation
        # invariant cannot be pierced.
        ev0 = (self.prefix.evictable_blocks()
               if self.prefix is not None else 0)
        planned = 0
        pinned = 0

        def pins_of(plan: dict) -> int:
            return len(plan["full"]) + \
                (1 if plan["partial"] is not None else 0)

        def admit_one(req: ServeRequest, plan: dict) -> None:
            row = self.kv.alloc_row(plan["new_blocks"])
            a = _Active(req=req, slot=row, plan=plan)
            admitted.append(a)
            self._active[row] = a

        # corrupted-and-reset sequences re-enter ahead of the queue
        # (they already waited their turn once)
        while self._reprefill and len(admitted) < free_rows:
            plan = self._plan(self._reprefill[0])
            if not self.kv.can_admit(
                    plan["new_blocks"] + planned,
                    max(ev0 - pinned - pins_of(plan), 0)):
                self._release_plan(plan)
                # ahead-of-queue means AHEAD: admitting smaller queue
                # requests past a blocked reprefill would let them eat
                # the blocks it is waiting for (priority inversion —
                # it could starve to its deadline while parked here)
                return admitted
            # no `planned` charge here: admit_one's alloc_row reserves
            # immediately, so reserved_total already carries it
            pinned += pins_of(plan)
            admit_one(self._reprefill.pop(0), plan)

        plans: Dict[int, dict] = {}

        def fits(req: ServeRequest) -> bool:
            nonlocal planned, pinned
            plan = self._plan(req)
            if self.kv.can_admit(plan["new_blocks"] + planned,
                                 max(ev0 - pinned - pins_of(plan), 0)):
                plans[req.rid] = plan
                planned += plan["new_blocks"]
                pinned += pins_of(plan)
                return True
            self._release_plan(plan)
            return False

        for req in self.queue.pop_fitting(free_rows - len(admitted),
                                          fits):
            admit_one(req, plans[req.rid])
        return admitted

    def _bucket_for(self, length: int) -> int:
        for b in self.buckets:
            if length <= b:
                return b
        raise AssertionError(
            f"prompt of {length} passed admission but fits no bucket "
            f"{self.buckets}")  # queue.max_prompt_len makes this unreachable

    # -- prefill -------------------------------------------------------------
    def _prefill(self, admitted: List[_Active]) -> None:
        B = self.executor.max_batch
        t_p0 = time.monotonic()   # queue_wait ends / prefill begins
        hit_rows: List[_Active] = []
        # materialize each admission plan: shared full blocks join
        # the table by reference; a mid-block partial match is
        # copy-on-written into a fresh block the suffix then
        # overwrites from its divergence point
        for a in admitted:
            plan, row = a.plan, a.slot
            for blk in plan["full"]:
                self.kv.attach_shared(row, blk)
            if plan["partial"] is not None:
                src, _j = plan["partial"]
                dst = self.kv.append_block(row)
                self.executor.copy_kv_block(src, dst)
                self.kv.pool.crc_clone(src, dst)
                self.prefix.release([src])   # drop the CoW pin
            a.prefix_tokens = plan["m"]
            a.plan = None
            if self.prefix is not None:
                self.prefix.note_lookup(a.prefix_tokens)
            if a.prefix_tokens:
                hit_rows.append(a)
            self.kv.ensure(row, len(a.req.prompt))
        R = self.prefill_rows
        if R is None:
            # ONE packed prefill, row = the sequence's batch slot
            waves = [[(a.slot, a) for a in admitted]]
        else:
            # row-compact: R sequences a step, longest first so that a
            # step's rows share a bucket as nearly as they can; row =
            # the sequence's place in its step
            order = sorted(admitted, key=lambda a: a.prefix_tokens
                           - len(a.req.prompt))
            waves = [list(enumerate(order[i:i + R]))
                     for i in range(0, len(order), R)]
        rows = B if R is None else R
        expected_v = self._prefix_version
        #: slot -> (its first token, when its wave's step ended)
        first: Dict[int, tuple] = {}
        stale = False
        for wave in waves:
            # the smallest bucket fitting the wave's longest SUFFIX (the
            # unmatched prompt tail; the whole prompt when the prefix
            # cache missed or is off)
            bucket = self._bucket_for(max(
                len(a.req.prompt) - a.prefix_tokens for _, a in wave))
            tokens = np.zeros((rows, bucket), np.int32)
            positions = np.zeros(rows, np.int32)
            mask = np.zeros(rows, bool)
            last_idx = np.zeros(rows, np.int32)
            tables = self.kv.table()
            slots = {}
            if R is not None:
                # each row's own table, in the step's row order
                at = np.full(rows, -1)
                at[[r for r, _ in wave]] = [a.slot for _, a in wave]
                tables = np.where(at[:, None] >= 0, tables[at], -1)
                slots = self._slots_arg(np.maximum(at, 0))
            for r, a in wave:
                m = a.prefix_tokens
                suffix = a.req.prompt[m:]
                tokens[r, :len(suffix)] = suffix
                positions[r] = m
                mask[r] = True
                last_idx[r] = len(suffix) - 1
            nxt = self.executor.step(
                tokens, positions, mask, last_idx, kind="prefill",
                stats=self._stats(),
                sample=self._sample_args(
                    [a.slot for _, a in wave],
                    rows=None if R is None else [r for r, _ in wave]),
                block_tables=tables, **slots)
            stale = stale or \
                self.executor.last_step_version != expected_v
            t_wave = time.monotonic()   # one stamp for the wave's rows
            for r, a in wave:
                first[a.slot] = (int(nxt[r]), t_wave)
        if hit_rows and stale:
            # a weight swap landed between the prefix match and this
            # prefill: the hit rows mixed old-version cached KV with
            # new-version compute. Tear them down and re-prefill from
            # scratch (the flush at the next step top drops the stale
            # cache); miss rows ran entirely under one version and
            # stand.
            logger.warning(
                "serve replica %s: weight swap landed mid-prefill — "
                "re-prefilling %d prefix-hit sequences on version %s",
                self.replica_id, len(hit_rows),
                self.executor.params_version)
            self._prefix_flush.set()
            for a in hit_rows:
                self.kv.free_row(a.slot)
                del self._active[a.slot]
                self._reprefill.append(a.req)
            admitted = [a for a in admitted if a not in hit_rows]
        rec = _trace_recorder()
        for a in admitted:
            token, t_first = first[a.slot]
            self._m_ttft.observe(
                (t_first - a.req.submitted_at) * 1000.0)
            a.t_admit, a.t_first = t_p0, t_first
            a.token_times.append(t_first)
            trace, root = a.req.trace_ids()
            rec.record_local("queue_wait", a.req.submitted_at, t_p0,
                             trace=trace, parent=root, ship=a.req.trace)
            rec.record_local("prefill", t_p0, t_first, trace=trace,
                             parent=root, ship=a.req.trace,
                             rid=a.req.rid)
            n = len(a.req.prompt)
            a.cache_len = n
            a.params_version = self.executor.last_step_version
            a.rng_ctr = 1   # the prefill's first token consumed draw 0
            # the prompt is fully cached but only [0, n) is valid; the
            # first generated token is the prompt's last-logit argmax
            a.out.append(token)
            self.kv.lengths[a.slot] = n
            # crc-on-write covers exactly the written span [m, n) (pad
            # positions past n are unreachable and unverified; shared
            # prefix blocks carry their writer's ledger already)
            self._crc_write(a.slot, a.prefix_tokens, n)
            if self.prefix is not None:
                # publish this prompt's FULL blocks for future sharing
                self.prefix.insert(a.req.prompt,
                                   self.kv.blocks[a.slot])
                if self.kvtier is not None:
                    # fleet index event: this replica now holds the run
                    self.kvtier.note_insert(a.req.prompt,
                                            a.params_version)
        if self.draft is not None and admitted:
            self._draft_prefill(admitted)

    def _draft_prefill(self, admitted: List[_Active]) -> None:
        """Ingest each admitted prompt into the DRAFT model's cache
        (full prompt — the drafter has no prefix cache; it is small,
        that is the point). Its last-logit output is discarded: the
        first draft of the next iteration feeds the target's first
        emitted token."""
        B = self.draft.max_batch
        bucket = self._bucket_for(
            max(len(a.req.prompt) for a in admitted))
        tokens = np.zeros((B, bucket), np.int32)
        positions = np.zeros(B, np.int32)
        mask = np.zeros(B, bool)
        last_idx = np.zeros(B, np.int32)
        for a in admitted:
            n = len(a.req.prompt)
            tokens[a.slot, :n] = a.req.prompt
            mask[a.slot] = True
            last_idx[a.slot] = n - 1
        self.draft.step(tokens, positions, mask, last_idx,
                        kind="prefill", block_tables=self._draft_tables)
        for a in admitted:
            a.draft_len = len(a.req.prompt)

    # -- decode --------------------------------------------------------------
    def _decode(self) -> None:
        if self.draft is None:
            self._decode_plain(list(self._active))
            return
        spec_rows, plain_rows = [], []
        for slot, seq in self._active.items():
            # speculative write-ahead must stay inside both contexts;
            # boundary sequences fall back to plain decode
            if seq.cache_len + self.spec_k + 1 <= self.executor.max_len \
                    and seq.draft_len + self.spec_k <= self.draft.max_len:
                spec_rows.append(slot)
            else:
                plain_rows.append(slot)
        if spec_rows:
            self._decode_spec(spec_rows)
        if plain_rows:
            self._decode_plain(plain_rows)

    def _decode_plain(self, rows: List[int]) -> None:
        B = self.executor.max_batch
        tokens = np.zeros((B, 1), np.int32)
        positions = np.zeros(B, np.int32)
        mask = np.zeros(B, bool)
        last_idx = np.zeros(B, np.int32)
        for slot in rows:
            seq = self._active[slot]
            # the newest token is not yet in the cache: this step writes
            # it at position cache_len, attends, and samples the next
            tokens[slot, 0] = seq.out[-1]
            positions[slot] = seq.cache_len
            mask[slot] = True
            self.kv.ensure(slot, seq.cache_len + 1)
        nxt = self.executor.step(
            tokens, positions, mask, last_idx, kind="decode",
            stats=self._stats(), sample=self._sample_args(rows),
            block_tables=self.kv.table())
        t_tok = time.monotonic()   # one stamp for the step's rows
        self.gen_steps += len(rows)
        for slot in rows:
            seq = self._active[slot]
            # this step wrote one K/V entry at the old cache_len
            self._crc_write(slot, seq.cache_len, seq.cache_len + 1)
            seq.cache_len += 1
            self.kv.lengths[slot] = seq.cache_len
            seq.out.append(int(nxt[slot]))
            seq.token_times.append(t_tok)
            seq.rng_ctr += 1
            self.gen_tokens += 1

    def _decode_spec(self, rows: List[int]) -> None:
        """One speculative iteration: k draft proposals per row, ONE
        fused target verify step, on-device accept + rollback.

        The accept rule runs INSIDE the verify step
        (ops/pallas_paged.py speculative_accept): at temperature 0 it
        is the argmax rule — draft token i+1 is emitted iff it equals
        the target's argmax at position i, the first disagreement
        emits the target's own argmax — which keeps the emitted stream
        BIT-IDENTICAL to target-only greedy decode, just produced
        1..k+1 tokens per target step. Sampled rows instead apply
        rejection sampling against each proposal's draft distribution
        (kept on device from the draft steps), so the emitted stream
        is distribution-identical to target-only sampling. Rejected
        draft positions were written into the cache by the verify
        step; they sit beyond the new cache_len, unreachable by the
        positional validity mask, and are overwritten by the next
        iteration — rollback is bookkeeping, not data movement.
        """
        import jax.numpy as jnp

        k = self.spec_k
        B = self.executor.max_batch
        known = {slot: self._active[slot].req.prompt
                 + self._active[slot].out for slot in rows}
        # tokens the drafter has NOT validly ingested yet; feeding them
        # (forced) re-syncs its cache after a full-accept iteration
        # left it one token behind
        forced = {slot: known[slot][self._active[slot].draft_len:]
                  for slot in rows}
        #: proposals of row r start at draft step len(forced_r) - 1
        #: (the step that consumes the last forced token emits the
        #: first proposal) — what aligns each proposal with the step
        #: whose distribution it was drawn from
        first_prop = {slot: len(forced[slot]) - 1 for slot in rows}
        drafts: Dict[int, List[int]] = {slot: [] for slot in rows}
        fed: Dict[int, List[int]] = {slot: [] for slot in rows}
        prev: Dict[int, int] = {}
        step_probs = []
        for i in range(k):
            tokens = np.zeros((B, 1), np.int32)
            positions = np.zeros(B, np.int32)
            mask = np.zeros(B, bool)
            zero = np.zeros(B, np.int32)
            for slot in rows:
                seq = self._active[slot]
                if forced[slot]:
                    tok = forced[slot][0]
                else:
                    tok = prev[slot]
                tokens[slot, 0] = tok
                positions[slot] = seq.draft_len + i
                mask[slot] = True
            out, probs = self.draft.step(
                tokens, positions, mask, zero, kind="decode",
                sample=self._sample_args(rows, ctr_offset=i),
                block_tables=self._draft_tables)
            step_probs.append(probs)
            for slot in rows:
                o = int(out[slot])
                if forced[slot]:
                    fed[slot].append(forced[slot].pop(0))
                    if not forced[slot]:
                        drafts[slot].append(o)   # drafted past known
                else:
                    fed[slot].append(prev[slot])
                    drafts[slot].append(o)
                prev[slot] = o
        # ONE batched verify: token 0 is each row's last emitted token
        # (its K/V enters the cache here, same as plain decode), tokens
        # 1..n_d are the drafts; the target scores every position and
        # applies the accept rule on device against each proposal's
        # draft distribution (gathered per row: proposal j of row r
        # came from draft step first_prop[r] + j)
        tokens = np.zeros((B, k + 1), np.int32)
        positions = np.zeros(B, np.int32)
        mask = np.zeros(B, bool)
        zero = np.zeros(B, np.int32)
        n_draft = np.zeros(B, np.int32)
        offs = np.zeros(B, np.int32)
        for slot in rows:
            seq = self._active[slot]
            row_toks = [known[slot][-1]] + drafts[slot][:k]
            tokens[slot, :len(row_toks)] = row_toks
            positions[slot] = seq.cache_len
            mask[slot] = True
            n_draft[slot] = len(drafts[slot])
            offs[slot] = max(first_prop[slot], 0)
            self.kv.ensure(slot, seq.cache_len + k + 1)
        stacked = jnp.stack(step_probs)                    # [k, B, V]
        idx = np.clip(offs[:, None] + np.arange(k)[None, :], 0, k - 1)
        dprobs = stacked[jnp.asarray(idx),
                         jnp.arange(B)[:, None]]           # [B, k, V]
        emitted_all, n_acc = self.executor.step(
            tokens, positions, mask, zero, kind="verify",
            stats=self._stats(), sample=self._sample_args(rows),
            draft_probs=dprobs, n_draft=n_draft,
            block_tables=self.kv.table())
        t_tok = time.monotonic()   # one stamp for all the step emitted
        self.gen_steps += len(rows)
        for slot in rows:
            seq = self._active[slot]
            n_d = len(drafts[slot])
            a = int(n_acc[slot])
            if n_d:
                self._m_accept.observe(a / n_d)
            emitted = [int(t) for t in emitted_all[slot, :a + 1]]
            remaining = seq.req.max_new_tokens - len(seq.out)
            emitted = emitted[:remaining]
            if self.eos_id is not None and self.eos_id in emitted:
                emitted = emitted[:emitted.index(self.eos_id) + 1]
            # the verify step wrote k+1 cache positions regardless;
            # crc them all — a later overwrite of the rejected tail
            # recomputes those blocks' ledgers
            self._crc_write(slot, seq.cache_len, seq.cache_len + k + 1)
            seq.out.extend(emitted)
            seq.token_times.extend([t_tok] * len(emitted))
            seq.cache_len += len(emitted)
            self.kv.lengths[slot] = seq.cache_len
            self.gen_tokens += len(emitted)
            # every speculative iteration consumes a FIXED draw budget
            # (k proposal draws + the verify's per-position draws), so
            # the stream stays deterministic however many were accepted
            seq.rng_ctr += k + 1
            # drafter rollback: its valid prefix is however far the fed
            # token stream still agrees with the true sequence
            nk = known[slot] + emitted
            base = seq.draft_len
            p = 0
            while p < len(fed[slot]) and base + p < len(nk) \
                    and fed[slot][p] == nk[base + p]:
                p += 1
            seq.draft_len = base + p
