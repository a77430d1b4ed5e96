"""Sharded model executor: the single jitted entry point of the server.

Drives a decode-mode model (models/gpt.py or models/llama.py with
``cfg.decode=True``) at **fixed shapes**: every call is
``[max_batch, T]`` tokens with per-row positions, an update mask and a
per-row last-token index, where T is 1 (decode) or one of the configured
prefill buckets (a prefill may hold fewer rows: the batcher runs
``[prefill_rows, bucket]``). Because batch membership is carried in
*data* (mask, positions) rather than *shape*, sequences can join and
leave at iteration granularity without ever invalidating the jit cache
— the no-recompile contract the continuous batcher (serve/batcher.py)
is built on.

Three decisions are frozen at build time so the jit cache stays flat:

* **Kernel**: the decode-attention kernel is resolved ONCE
  (``HOROVOD_SERVE_KERNEL`` via `ops.pallas_paged.resolve_kernel` —
  fused Pallas on TPU by default, the XLA gather oracle as CPU
  fallback) and stamp it into the model config before the first trace.
  The resolved path is named by a one-shot **KERNEL** timeline instant
  and the ``kernel`` label on ``hvd_serve_step_ms``, so a silent
  fallback to XLA on TPU is visible in the trace and in /metrics.
* **Sampling**: token selection runs ON DEVICE inside the jitted step
  — temperature / top-p with per-request seeds threaded as row data
  (``sample=`` arrays), greedy being the ``temperature == 0`` special
  case (an all-greedy batch takes a sort-free `lax.cond` branch of the
  same program). Only the per-row EMITTING position's logits are
  computed (``logits_idx`` gathers before the lm_head), and the
  speculative verify step applies the rejection-sampling accept rule
  on device (`ops.pallas_paged.speculative_accept`), returning the
  emitted tokens instead of raw argmaxes.

* **Resident dtypes**: each parameter is held in the dtype the step
  multiplies it in — where the model's own trace shows a leaf only
  ever converted to one narrower float dtype (float32 `Dense` kernels
  and biases under bfloat16 compute), the constructor and
  `swap_params` make that conversion once, in one program, and the
  step reads half the bytes; every other leaf (norms, embeddings, a
  float32 head, a checkpoint already in its compute dtype) is held as
  given. The caller's tree is not consumed.

**One transfer a step.** Everything a step receives as host data
crosses to the device as ONE ``int32 [rows, T + words]`` buffer
(`pack_step`): a row's tokens, its position, mask, emitting index (or
draft count), sampling data as bit patterns, block table and, for a
model with per-row state, state slot. The jitted step takes it apart
with static slices and bitcasts (`unpack_step`), so the model and the
samplers receive the values and dtypes they always did; the buffer's
shape carries ``rows`` and ``T``, so it is the program's key as the
token array's shape was (docs/serving.md has the table).

Sharding rides the training stack unchanged: pass `mesh` plus the
model's `PartitionRules` (parallel/tp.py) and parameters are placed with
`shard_params`; jit/GSPMD then emits the same ICI collectives the
training step uses. The KV cache and token buffers default to
replicated, which is correct for TP (activations replicated, weights
sharded) — the Megatron serving layout.

Observability: each step lands on the timeline's **SERVE** row
(`timeline.instant("SERVE", {...})`) with step latency, step kind,
queue depth / batch occupancy / shed count (supplied by the batcher) and
a rolling tokens/s, next to the engine's WIRE_BYTES row in the same
trace.
"""
from __future__ import annotations

import copy
import logging
import threading
import time
from collections import deque
from typing import Any, Dict, Optional, Set, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..obs import metrics as obs_metrics
from ..trace.spans import get_recorder as _trace_recorder

logger = logging.getLogger("horovod_tpu")


def _named_leaves(tree) -> list:
    """``(name, leaf)`` of a flax collection in flatten order, the name
    being the variable's own (the last dict key of its path)."""
    return [(next(k.key for k in reversed(path) if hasattr(k, "key")), leaf)
            for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]]


def _stat_totals(stats) -> Dict[str, Any]:
    """A ``stats`` collection (one sown value a layer and counter) ->
    each counter's sum over the layers, by the counter's name."""
    totals: Dict[str, Any] = {}
    for name, leaf in _named_leaves(stats):
        totals[name] = totals.get(name, 0) + jnp.sum(leaf)
    return totals


#: the kinds of leaf a model's ``cache`` collection may hold
#: (docs/serving.md): a K or V pool; a per-BLOCK leaf of another shape,
#: which follows the block tables; a per-ROW leaf, which follows the
#: batch slot. A model config names the leaves that are no K/V pool
#: (``cache_leaves``: leaf name -> kind)
KV_POOL, PER_BLOCK, PER_ROW = "kv", "block", "row"


def _resident_dtypes(jaxpr, given: list) -> list:
    """The dtype each parameter leaf is HELD in, read off the traced
    forward: a leaf whose every use is a ``convert_element_type`` to one
    narrower floating dtype is held in that dtype (the cast moves from
    every step to once; each later operation receives the same rounded
    number), every other leaf as given. ``jaxpr.invars`` starts with the
    parameter leaves in flatten order. A leaf consumed by anything else
    (a nested call included, whatever that call does with it) or
    returned as is keeps its dtype: the rule errs towards not casting."""
    uses = {id(v): set() for v in jaxpr.invars[:len(given)]}
    for v in jaxpr.outvars:
        if id(v) in uses:
            uses[id(v)].add(None)
    for eqn in jaxpr.eqns:
        to = eqn.params.get("new_dtype") \
            if eqn.primitive is jax.lax.convert_element_type_p else None
        for v in eqn.invars:
            if id(v) in uses:
                uses[id(v)].add(to)
    held = []
    for v, dt in zip(jaxpr.invars, given):
        (to,) = uses[id(v)] if len(uses[id(v)]) == 1 else (None,)
        narrower = to is not None and \
            jnp.issubdtype(dt, jnp.floating) and \
            jnp.issubdtype(to, jnp.floating) and \
            jnp.dtype(to).itemsize < jnp.dtype(dt).itemsize
        held.append(jnp.dtype(to) if narrower else dt)
    return held


#: the words of a packed step row between its tokens and its block
#: table, one int32 each: position, update mask (0/1), emitting index
#: (a verify step: draft count), temperature and top_p (float32 bits),
#: seed (uint32 bits), draw counter
ROW_WORDS = 7


def pack_step(tokens, positions, mask, idx, sample, tables,
              blocks_per_seq: int, slots=None) -> np.ndarray:
    """A step's host inputs as ONE ``int32 [rows, T + ROW_WORDS +
    blocks_per_seq (+ 1)]`` array, row by row in the order
    `unpack_step` reads: tokens, position, mask, ``idx`` (``last_idx``
    of a token step, ``n_draft`` of a verify step), temperature, top_p,
    seed, ctr, the block table, and the state slot where ``slots`` is
    given. Float32 and uint32 fields travel as their bit patterns. An
    array of another shape than the step's rows and the executor's
    table width ask for is refused: numpy would broadcast it, and a
    narrower table would shift what the step reads as ``T``."""
    tokens = np.asarray(tokens)
    tables = np.asarray(tables)
    B, T = tokens.shape
    cols = [np.asarray(positions), np.asarray(mask, bool), np.asarray(idx),
            np.asarray(sample["temperature"], np.float32).view(np.int32),
            np.asarray(sample["top_p"], np.float32).view(np.int32),
            np.asarray(sample["seed"], np.uint32).view(np.int32),
            np.asarray(sample["ctr"])]
    per_row = cols if slots is None else cols + [np.asarray(slots)]
    if tables.shape != (B, blocks_per_seq) or \
            any(c.shape != (B,) for c in per_row):
        raise ValueError(
            f"a step of {B} rows needs [{B}] per-row arrays and "
            f"[{B}, {blocks_per_seq}] block tables; got "
            f"{[c.shape for c in per_row]} and {tables.shape}")
    buf = np.empty((B, T + len(per_row) + blocks_per_seq), np.int32)
    buf[:, :T] = tokens
    for i, c in enumerate(cols):
        buf[:, T + i] = c
    buf[:, T + ROW_WORDS:T + ROW_WORDS + blocks_per_seq] = tables
    if slots is not None:
        buf[:, -1] = slots
    return buf


def unpack_step(packed, blocks_per_seq: int, slots: bool):
    """`pack_step`'s array, inside the jitted step -> ``(tokens [B, T]
    int32, positions, mask bool, idx, temperature float32, top_p
    float32, seed uint32, ctr, tables [B, blocks_per_seq], slots or
    None)``: static slices and bitcasts, every value as the host held
    it. ``T`` is what the row's width leaves."""
    T = packed.shape[1] - ROW_WORDS - blocks_per_seq - int(slots)
    col = [packed[:, T + i] for i in range(ROW_WORDS)]
    bits = jax.lax.bitcast_convert_type
    return (packed[:, :T], col[0], col[1] != 0, col[2],
            bits(col[3], jnp.float32), bits(col[4], jnp.float32),
            bits(col[5], jnp.uint32), col[6],
            packed[:, T + ROW_WORDS:T + ROW_WORDS + blocks_per_seq],
            packed[:, -1] if slots else None)


class ShardedExecutor:
    """Owns the params, the device KV cache and the one jitted step."""

    def __init__(self, model: Any, params: Any, *, max_batch: int,
                 max_len: int, mesh=None, partition_rules=None,
                 timeline=None, replica_id: Optional[int] = None,
                 role: str = "target"):
        if max_batch < 1:
            raise ValueError(f"max_batch must be >= 1; got {max_batch}")
        if role not in ("target", "draft"):
            raise ValueError(f"role must be 'target'|'draft'; got {role!r}")
        if max_len > model.cfg.max_seq_len:
            # the position tables are shaped by the model's
            # max_seq_len; a larger executor max_len would silently
            # clamp position lookups instead of erroring
            raise ValueError(
                f"max_len {max_len} exceeds the model's max_seq_len "
                f"{model.cfg.max_seq_len}")
        # the executor's own copy of the model config: the pool size
        # and the kernel resolved below are stamped on it before the
        # first trace (the model reads both at trace time), and the
        # caller's model stays as given, free to build an executor of
        # another shape
        cfg = copy.copy(model.cfg)
        self.model = model = model.clone(cfg=cfg)
        self.max_batch = max_batch
        self.max_len = max_len
        self.timeline = timeline
        #: "draft" executors (speculative decoding proposers) share the
        #: process with a target executor: they must neither reclaim
        #: the serve metric families nor blend into the target's series
        self.role = role
        # -- the KV storage (model-config driven): the device cache is
        # a block pool and every step takes per-row block tables
        self.kv_block_size = int(cfg.kv_block_size)
        #: fixed block-table width: enough entries to address max_len
        self.blocks_per_seq = -(-max_len // self.kv_block_size)
        if not cfg.kv_pool_blocks:
            # a config that names no pool size gets the worst case,
            # every row at max_len: a table that never changes can
            # address it, and admission by blocks never waits where
            # rows are free
            cfg.kv_pool_blocks = max_batch * self.blocks_per_seq
        self.kv_pool_blocks = int(cfg.kv_pool_blocks)
        #: does a sequence of this model hold state that the block
        #: tables do not address (a per-row leaf of the cache, below)?
        #: Sized here as the pool is: a slot a batch row
        self.per_row_state = bool(getattr(cfg, "per_row_state", False))
        if self.per_row_state:
            cfg.state_rows = max_batch
        #: layers whose decode step attends to blocks it selects
        self._select_layers = int(getattr(cfg, "block_select_layers", 0))
        #: bytes of cached VALUES a token of context and a row cost, for
        #: a model that says so (one whose cache does not grow by every
        #: attending layer's K and V a token: models/sambay_lm.py); its
        #: decode steps carry what their live rows hold
        self._cache_cost = (
            (int(cfg.cache_token_bytes), int(cfg.cache_row_bytes))
            if hasattr(cfg, "cache_token_bytes") else None)
        if self.kv_pool_blocks < self.blocks_per_seq:
            raise ValueError(
                f"kv_pool_blocks {self.kv_pool_blocks} cannot cover one "
                f"max_len sequence ({self.blocks_per_seq} blocks of "
                f"{self.kv_block_size})")
        #: vocab width — the verify step's draft-probs row shape
        self.vocab_size = int(cfg.vocab_size)
        # -- decode kernel, resolved ONCE: stamping the resolution here
        # keeps every compiled program on one path and the jit cache
        # flat
        from ..ops.pallas_paged import resolve_kernel
        self.kernel = cfg.decode_kernel = resolve_kernel(cfg.decode_kernel)
        # kept for hot weight swaps (redist/stream.py): replacement
        # params are placed exactly like the originals
        self._mesh = mesh
        self._rules = partition_rules
        placed = mesh is not None and partition_rules is not None
        if placed:
            from ..parallel.tp import shard_params
            params = shard_params(params, mesh, partition_rules)
        # the swap/version fence: step() holds this lock for the whole
        # forward, swap_params() takes it to replace self.params — a
        # swap can therefore land only BETWEEN decode iterations, never
        # mid-step, and no step ever mixes two param versions
        self._swap_lock = threading.Lock()
        self.params_version: Optional[int] = None
        self.swaps = 0
        # -- metrics --
        self.steps = 0
        self.tokens_out = 0
        self._tok_window: "deque[Tuple[float, int]]" = deque(maxlen=1024)
        #: distinct (kind, T) entry points actually executed — the
        #: jit-signature ledger the no-recompile tests assert on
        self.signatures: Set[Tuple[str, int]] = set()
        # registry series: per-kind step latency histogram + generated
        # tokens. Claimed fresh per executor when standalone (one
        # serving stack per process); a FLEET replica instead passes
        # replica_id and gets get-or-create labeled children, so one
        # replica's (re)construction never clobbers its siblings'
        # series and a restarted replica keeps counting where it left
        # off (serve/fleet.py).
        self.replica_id = replica_id
        rl = {} if replica_id is None else {"replica": str(replica_id)}
        if role == "draft":
            rl = dict(rl, role="draft")
        R = obs_metrics.get_registry()
        if replica_id is None and role == "target":
            # only the TARGET standalone executor claims the families
            # fresh: a draft executor is constructed NEXT TO a target in
            # the same process and must not clobber its series
            R.unregister("hvd_serve_step_ms")
            R.unregister("hvd_serve_tokens_total")
        # get-or-create, NOT claimed fresh: a multi-replica fleet runs
        # several executors in one process and the swap series is
        # fleet-shared (redist/stream.py)
        self._m_swap_ms = R.histogram(
            "hvd_weight_swap_ms",
            "hot weight swap: new params placed + adopted (ms)")
        self._m_step_ms = {
            k: R.histogram("hvd_serve_step_ms",
                           "executor step latency by kind (ms)",
                           dict(rl, kind=k, kernel=self.kernel))
            for k in ("prefill", "decode", "verify")}
        self._m_tokens = R.counter(
            "hvd_serve_tokens_total", "tokens generated", rl or None)

        # -- the jitted steps. Token selection runs ON DEVICE
        # (ops/pallas_paged.py sampling): per-row temperature / top-p /
        # seed / draw-counter ride as data through the fixed shapes.
        #
        #   _fwd_token   prefill + decode: only the per-row EMITTING
        #                position's logits are computed (logits_idx
        #                gathers hidden states before the lm_head — the
        #                step's largest GEMM runs [B, 1, V], never
        #                [B, bucket, V]); returns the sampled token
        #                [B], plus the filtered sampling distribution
        #                [B, V] on DRAFT executors (what the verify
        #                step consumes as q).
        #   _fwd_verify  the fused speculative verify: full [B, T, V]
        #                logits (every draft position emits), the
        #                rejection-sampling accept rule applied on
        #                device -> (emitted [B, T], n_accept [B]).
        from ..ops.pallas_paged import (STREAM_DRAFT, STREAM_SAMPLE,
                                        sample_with_probs,
                                        speculative_accept)
        stream = STREAM_DRAFT if role == "draft" else STREAM_SAMPLE
        emit_probs = role == "draft"

        def apply_model(params, cache, tokens, positions, mask, tables,
                        logits_idx, slots=()):
            # `slots` (a model with per-row state only): which batch
            # slot's state each row of the step reads and writes
            kw = {"state_slots": slots[0]} if slots else {}
            return self.model.apply(
                {"params": params, "cache": cache}, tokens,
                positions=positions, update_mask=mask,
                logits_idx=logits_idx, mutable=["cache", "stats"],
                block_tables=tables, **kw)

        def with_stats(per_row, vout):
            """A model that sows step counters (``stats`` collection,
            per layer: experts that received a token; blocks a sparse
            layer attended) gets each counter's sum over the layers
            appended to the step's per-row int32 result, in the order
            of `_stat_names`, so the one readback carries them; any
            other model's program is as it was."""
            if "stats" not in vout:
                return per_row
            totals = _stat_totals(vout["stats"])
            return jnp.concatenate(
                [per_row] + [totals[n].astype(per_row.dtype)[None]
                             for n in sorted(totals)])

        def unpack(packed):
            return unpack_step(packed, self.blocks_per_seq,
                               self.per_row_state)

        def fwd_token(params, cache, packed):
            (tokens, positions, mask, last_idx, temp, top_p, seed, ctr,
             tables, slots) = unpack(packed)
            logits, vout = apply_model(
                params, cache, tokens, positions, mask, tables, last_idx,
                () if slots is None else (slots,))
            tok, probs = sample_with_probs(
                logits[:, 0], temp, top_p, seed, ctr, stream=stream)
            tok = with_stats(tok, vout)
            if emit_probs:
                return tok, probs, vout["cache"]
            return tok, vout["cache"]

        def fwd_verify(params, cache, packed, dprobs):
            (tokens, positions, mask, n_draft, temp, top_p, seed, ctr,
             tables, _) = unpack(packed)
            logits, vout = apply_model(params, cache, tokens, positions,
                                       mask, tables, None)
            emitted, n_acc = speculative_accept(
                tokens, dprobs, logits, n_draft, temp, top_p, seed, ctr)
            return emitted, with_stats(n_acc, vout), vout["cache"]

        # donating the cache lets XLA update it in place on TPU; CPU
        # does not support donation and would only warn
        donate = () if jax.default_backend() == "cpu" else (1,)
        self._fwd_token = jax.jit(fwd_token, donate_argnums=donate)
        self._fwd_verify = jax.jit(fwd_verify, donate_argnums=donate)

        # -- the resident state, from ONE trace of the model and no
        # program that holds its forward: the trace says which dtype
        # each parameter is multiplied in (`_resident_dtypes`) and what
        # the cache collection looks like; every model creates that
        # collection as zeros, so shapes and dtypes are all of it
        def make_cache(params, tokens, positions, mask, tables):
            _, v = self.model.apply(
                {"params": params}, tokens, positions=positions,
                update_mask=mask, mutable=["cache", "stats"],
                block_tables=tables)
            return v

        S = jax.ShapeDtypeStruct
        traced, collections = jax.make_jaxpr(make_cache, return_shape=True)(
            params, S((max_batch, 1), jnp.int32), S((max_batch,), jnp.int32),
            S((max_batch,), bool),
            S((max_batch, self.blocks_per_seq), jnp.int32))
        #: the step counters the model sows (see `with_stats`), by
        #: name; the collection is there or it is not
        self._stat_names = sorted(
            {n for n, _ in _named_leaves(collections.get("stats", {}))})
        #: the kind of each cache leaf, in flatten order
        named = getattr(cfg, "cache_leaves", None) or {}
        cache_leaves = _named_leaves(collections["cache"])
        self._leaf_kinds = [named.get(n, KV_POOL) for n, _ in cache_leaves]
        #: bytes of per-row state one batch slot holds on the device (0:
        #: the model's sequences are their blocks and nothing else)
        self.state_row_bytes = sum(
            int(np.prod(leaf.shape[1:])) * jnp.dtype(leaf.dtype).itemsize
            for kind, (_, leaf) in zip(self._leaf_kinds, cache_leaves)
            if kind == PER_ROW)
        for kind, (name, leaf) in zip(self._leaf_kinds, cache_leaves):
            lead = {KV_POOL: (self.kv_pool_blocks, self.kv_block_size),
                    PER_BLOCK: (self.kv_pool_blocks,),
                    PER_ROW: (max_batch,)}.get(kind)
            if lead is None or leaf.shape[:len(lead)] != lead or \
                    (kind == KV_POOL and len(leaf.shape) != 4):
                raise ValueError(
                    f"cache leaf {name!r} {leaf.shape} is no {kind!r} "
                    f"leaf of a {self.kv_pool_blocks}x"
                    f"{self.kv_block_size} pool and {max_batch} rows: a "
                    f"model names its leaves that are no K/V pool in "
                    f"cfg.cache_leaves (docs/serving.md)")
        leaves, self._treedef = jax.tree_util.tree_flatten(params)
        # the zeros go where the parameters are: replicated over a mesh
        # (the Megatron serving layout), else COMMITTED to the device
        # the caller committed its tree to, as the cache every step
        # hands back will be (committed or not is in jit's signature)
        where = jax.sharding.NamedSharding(
            mesh, jax.sharding.PartitionSpec()) if placed else next(
            (x.sharding for x in leaves if getattr(x, "committed", False)),
            None)
        self.cache = jax.tree_util.tree_map(
            lambda s: jnp.zeros(s.shape, s.dtype, device=where),
            collections["cache"])

        # parameters are HELD in the dtype the step multiplies them in:
        # cast here and in `swap_params` by one program over the leaves
        # that change (the others ARE the caller's arrays), never inside
        # the step. The caller's arrays are not donated: a fleet builds
        # several replicas from one tree.
        self._given_dtypes = [jnp.dtype(x.dtype) for x in leaves]
        self._resident_dtypes = _resident_dtypes(
            traced.jaxpr, self._given_dtypes)
        self._cast_idx = [i for i, (g, r) in enumerate(zip(
            self._given_dtypes, self._resident_dtypes)) if g != r]
        to = [self._resident_dtypes[i] for i in self._cast_idx]

        def resident_cast(xs):
            return [x.astype(d) for x, d in zip(xs, to)]

        # a `shard_params` placement survives the cast
        self._cast = jax.jit(
            resident_cast, out_shardings=[
                leaves[i].sharding for i in self._cast_idx]
            if placed else None)
        self.params = self._to_resident(params)

        # CoW block copy, jitted once (shapes are static): donation
        # makes it an in-place pool write on TPU instead of a full
        # pool copy per CoW
        def copy_block(cache, src, dst):
            # every leaf that follows the block tables; a per-row leaf
            # is no block's
            leaves, treedef = jax.tree_util.tree_flatten(cache)
            return jax.tree_util.tree_unflatten(treedef, [
                leaf if kind == PER_ROW else leaf.at[dst].set(leaf[src])
                for kind, leaf in zip(self._leaf_kinds, leaves)])

        self._copy_block = jax.jit(
            copy_block, donate_argnums=() if
            jax.default_backend() == "cpu" else (0,))
        #: params_version the most recent step actually ran under (set
        #: inside the step lock) — what lets the batcher detect a swap
        #: landing between its prefix-cache lookup and the prefill
        self.last_step_version: Optional[int] = None
        # the bytes given and held are on the log line, so a silent
        # fall-back to "nothing cast" shows in a run's log
        nbytes = [sum(int(np.prod(x.shape)) * d.itemsize
                      for x, d in zip(leaves, dts))
                  for dts in (self._given_dtypes, self._resident_dtypes)]
        # and so are the shape and the layout the pools are HELD in and
        # their bytes, as values and on the device: a pool the device
        # lays out another way than row-major (`kv_cache.write_kv_pools`)
        # is copied whole by every layer of every step, and pays in
        # padding there
        pools = self._cache_leaves()
        logger.info(
            "serve executor (replica=%s role=%s): decode kernel=%s "
            "pool=%dx%d backend=%s; resident bytes %d -> %d, %d of %d "
            "leaves cast; %d pools %s held %s, pool bytes %d -> %d on the "
            "device", replica_id, role, self.kernel,
            self.kv_pool_blocks, self.kv_block_size,
            jax.default_backend(), *nbytes, len(self._cast_idx),
            len(leaves), len(pools),
            "/".join(sorted({f"{x.dtype}{list(x.shape)}" for x in pools})),
            "/".join(sorted({str(x.format.layout.major_to_minor)
                             for x in pools})),
            sum(x.nbytes for x in pools),
            sum(x.addressable_shards[0].data.on_device_size_in_bytes()
                for x in pools))
        # one-shot KERNEL instant: names the RESOLVED decode kernel so
        # a silent fallback to XLA on TPU is visible in the trace
        if self.timeline is not None:
            self.timeline.instant("KERNEL", {
                "kernel": self.kernel, "role": role,
                "backend": jax.default_backend()})

    # -- the one step --------------------------------------------------------
    def _default_sample(self, B: int) -> Dict[str, np.ndarray]:
        """Greedy row data: temperature 0 everywhere (the all-greedy
        `lax.cond` fast path inside the jitted step)."""
        return {"temperature": np.zeros(B, np.float32),
                "top_p": np.ones(B, np.float32),
                "seed": np.zeros(B, np.uint32),
                "ctr": np.zeros(B, np.int32)}

    def step(self, tokens: np.ndarray, positions: np.ndarray,
             mask: np.ndarray, last_idx: np.ndarray, *,
             kind: str = "decode",
             stats: Optional[Dict[str, Any]] = None,
             block_tables: Optional[np.ndarray] = None,
             sample: Optional[Dict[str, np.ndarray]] = None,
             draft_probs=None, n_draft: Optional[np.ndarray] = None,
             state_slots: Optional[np.ndarray] = None):
        """Run one fixed-shape forward step.

        tokens [max_batch, T] int32; positions/last_idx [max_batch]
        int32; mask [max_batch] bool; block_tables
        [max_batch, blocks_per_seq] int32, -1 for unassigned entries.
        ``sample`` carries the per-row sampling data (temperature /
        top_p / seed / ctr arrays, [max_batch] each); None is greedy.
        A PREFILL may be row-compact: ``[rows, T]`` tokens with
        ``rows`` < max_batch and every per-row array (the block tables
        too) of that many rows: the pool is addressed through the
        tables alone, so a step's rows need not be the batch's (the
        batcher prefills ``prefill_rows`` prompts a step, by default
        one; each ``(rows, T)`` shape
        is one compiled program, to be warmed like any other). For a
        model with per-row state ``state_slots`` [rows] says which
        batch slot each row of such a step stands for (a masked-out
        row's is ignored); a full-batch step's rows are the slots.
        `stats` (queue depth, occupancy, shed count — batcher-supplied)
        is folded into the SERVE event.

        Returns, valid where `mask` is set:

        * ``kind="prefill"`` / ``"decode"``: the sampled next token per
          row, ``[max_batch]`` int32 (the emitting position is
          ``last_idx`` — its logits are the only ones computed). A
          DRAFT executor returns ``(tokens, probs)`` where ``probs``
          [max_batch, V] is the on-device filtered distribution each
          token was drawn from.
        * ``kind="verify"``: ``(emitted [max_batch, T] int32,
          n_accept [max_batch] int32)`` — the rejection-sampling (or,
          at temperature 0, bit-identical greedy) accept rule applied
          on device against ``draft_probs`` [max_batch, T-1, V] with
          per-row real proposal counts ``n_draft``.
        """
        t0 = time.perf_counter()
        T = int(tokens.shape[1])
        self.signatures.add((kind, T))
        if block_tables is None:
            raise ValueError("an executor step needs block_tables")
        B = int(tokens.shape[0])
        if B != self.max_batch and kind != "prefill":
            raise ValueError(
                f"{kind} step of {B} rows on an executor of "
                f"{self.max_batch}: only a prefill may be row-compact")
        if self.per_row_state and state_slots is None:
            if B != self.max_batch:
                raise ValueError(
                    f"a row-compact step of {B} rows of a model with "
                    f"per-row state needs `state_slots`: which batch "
                    f"slot's state each row reads and writes")
            state_slots = np.arange(B)
        n_tok = int(np.sum(mask))
        rec = _trace_recorder()
        probs = None
        attrs = {"kind": kind, "rows": n_tok}
        if kind == "prefill":
            # prompt tokens this step ingests (each row's up to its
            # emitting position; the rest of the bucket is padding) and
            # the token positions it computes, padding and masked rows
            # included: tokens / token_slots is the step's useful share
            on = np.asarray(mask, bool)
            attrs["tokens"] = int(np.sum((np.asarray(last_idx) + 1)[on]))
            attrs["token_slots"] = B * T
            # prompt tokens the cache already held (a radix hit's
            # prefix): a row starts at its first fresh position
            attrs["cached_tokens"] = int(np.sum(np.asarray(positions)[on]))
        # the step and its three legs land in the process's span ring
        # (docs/tracing.md): which of them the device waits for is what
        # the serve cell's idle metrics read
        with rec.span("exec_step", **attrs) as step_span:
            with rec.span("exec_upload") as upload:
                # ONE transfer: every host array of the step in one
                # buffer, placed uncommitted as each of them used to be
                # (a mesh-placed executor's program replicates it)
                idx = last_idx
                if kind == "verify":
                    idx = n_draft if n_draft is not None \
                        else np.zeros(B, np.int32)
                host = pack_step(
                    tokens, positions, mask, idx,
                    sample if sample is not None
                    else self._default_sample(B), block_tables,
                    self.blocks_per_seq,
                    state_slots if self.per_row_state else None)
                args = [jax.device_put(host)]
                if kind == "verify":
                    # the drafter's distribution is on the device
                    # already and stays there
                    args.append(
                        draft_probs if draft_probs is not None
                        else jnp.zeros((B, T - 1, self.vocab_size),
                                       jnp.float32))
                upload.set(
                    transfers=1 + (draft_probs is not None and not
                                   isinstance(draft_probs, jax.Array)),
                    bytes=host.nbytes)
            with self._swap_lock:   # the weight-swap version fence
                self.last_step_version = self.params_version
                fwd = self._fwd_verify if kind == "verify" \
                    else self._fwd_token
                with rec.span("exec_dispatch"):
                    *out, self.cache = fwd(self.params, self.cache,
                                           *args)
                # host readback doubles as completion fence — inside
                # the lock so a swap never lands mid-step
                with rec.span("exec_readback"):
                    if kind == "verify":
                        nxt = (np.asarray(out[0]), np.asarray(out[1]))
                    else:
                        nxt = np.asarray(out[0])
                        if self.role == "draft":
                            probs = out[1]
            if self._stat_names:
                # the model's step counters rode in on the readback as
                # further entries of the per-row result
                n = len(self._stat_names)
                if kind == "verify":
                    nxt, sown = (nxt[0], nxt[1][:-n]), nxt[1][-n:]
                else:
                    nxt, sown = nxt[:-n], nxt[-n:]
                step_span.set(**{name: int(v) for name, v in
                                 zip(self._stat_names, sown)})
            if kind == "decode" and self._select_layers:
                # what the selecting layers COULD have read: each live
                # row's cached blocks, the one being written included
                live = np.asarray(positions)[np.asarray(mask, bool)]
                step_span.set(blocks_cached=int(
                    self._select_layers
                    * np.sum(live // self.kv_block_size + 1)))
            if kind == "decode" and self.per_row_state:
                step_span.set(state_rows=n_tok)
            if kind == "decode" and self._cache_cost:
                # known without a readback: the contexts the live rows
                # have after this step, the pool blocks their tables
                # name, and what a row holds whatever its context
                on = np.asarray(mask, bool)
                per_token, per_row = self._cache_cost
                held = int(np.sum(np.asarray(block_tables)[on] >= 0))
                step_span.set(
                    context_tokens=int(np.sum(np.asarray(positions)[on] + 1)),
                    cache_bytes_held=held * self.kv_block_size * per_token
                    + n_tok * per_row)
        dt_ms = (time.perf_counter() - t0) * 1000.0
        self.steps += 1
        self._m_step_ms.get(kind, self._m_step_ms["decode"]).observe(dt_ms)
        self.tokens_out += n_tok
        self._m_tokens.inc(n_tok)
        self._tok_window.append((time.perf_counter(), n_tok))
        if self.timeline is not None:
            ev = {"kind": kind, "step_ms": round(dt_ms, 3),
                  "tokens": n_tok, "tokens_per_s": round(self.tokens_per_s(), 1)}
            if stats:
                ev.update(stats)
            self.timeline.instant("SERVE", ev)
        if self.role == "draft" and kind != "verify":
            # the filtered proposal distribution stays ON DEVICE — the
            # batcher hands it straight to the target's verify step
            return nxt, probs
        return nxt

    def _to_resident(self, params: Any) -> Any:
        """``params`` in the given dtypes -> the tree the step consumes:
        the leaves `_resident_dtypes` narrows go through the one cast
        program, the others are passed on as they are."""
        if not self._cast_idx:
            return params
        leaves = jax.tree_util.tree_leaves(params)
        cast = self._cast([leaves[i] for i in self._cast_idx])
        for i, x in zip(self._cast_idx, cast):
            leaves[i] = x
        return jax.tree_util.tree_unflatten(self._treedef, leaves)

    # -- hot weight swap (redist/stream.py consumer) -------------------------
    def swap_params(self, new_params: Any, *,
                    version: Optional[int] = None) -> bool:
        """Adopt ``new_params`` between decode iterations.

        The version fence: the step lock guarantees no swap lands while
        a forward is in flight (no torn step — every launched program
        sees exactly one param version), and adoption is MONOTONE —
        a ``version`` at or below the current one is refused (returns
        False) so out-of-order polls across replicas can never roll
        weights backwards. The structure must match the serving params
        exactly (same treedef/shapes), in the dtypes the constructor
        was GIVEN (a publisher of float32 master weights need not know
        the serving dtype: the executor casts, and does not consume the
        caller's arrays) or in the RESIDENT dtypes; placement (mesh +
        partition rules) mirrors the constructor.

        Returns True on adoption; observes ``hvd_weight_swap_ms`` and
        emits a SWAP timeline instant."""
        t0 = time.perf_counter()
        if version is not None and self.params_version is not None \
                and version <= self.params_version:
            return False
        old_leaves = jax.tree_util.tree_leaves(self.params)
        new_leaves, new_def = jax.tree_util.tree_flatten(new_params)
        # .dtype without np.asarray: materializing device arrays to
        # host just to read their dtype would cost an O(model) transfer
        # per swap (and raise on multi-host GSPMD leaves)
        dtypes = [getattr(x, "dtype", None) for x in new_leaves]
        if new_def != self._treedef or any(
                np.shape(a) != np.shape(b)
                for a, b in zip(old_leaves, new_leaves)) \
                or dtypes not in (self._given_dtypes,
                                  self._resident_dtypes):
            # dtype is part of the jitted step's signature: adopting a
            # tree in any other dtype would not error — it would
            # recompile EVERY bucket mid-traffic. Fail fast instead.
            raise ValueError(
                "swap_params: replacement tree does not match the "
                "serving params (treedef/shape/dtype mismatch) — "
                "refusing a structurally torn swap (a dtype change "
                "would recompile every serving bucket mid-traffic)")
        if self._mesh is not None and self._rules is not None:
            from ..parallel.tp import shard_params
            new_params = shard_params(new_params, self._mesh,
                                      self._rules)
        else:
            new_params = jax.tree_util.tree_map(jnp.asarray, new_params)
        if dtypes != self._resident_dtypes:
            # the constructor's program, outside the step lock; waited
            # for, so the swap's time holds the cast and the given tree
            # can be let go when this returns
            new_params = jax.block_until_ready(
                self._to_resident(new_params))
        with self._swap_lock:
            # re-check under the lock: another subscriber thread may
            # have adopted a newer version while we placed this one
            if version is not None and self.params_version is not None \
                    and version <= self.params_version:
                return False
            self.params = new_params
            self.params_version = version if version is not None else \
                (self.params_version or 0) + 1
            self.swaps += 1
        dt_ms = (time.perf_counter() - t0) * 1000.0
        self._m_swap_ms.observe(dt_ms)
        if self.timeline is not None:
            self.timeline.instant("SWAP", {
                "version": self.params_version,
                "swap_ms": round(dt_ms, 3)})
        return True

    # -- KV integrity hooks (serve.kv chaos + crc option) --------------------
    def _cache_leaves(self, kind: str = KV_POOL) -> list:
        """The leaves of one kind inside the flax cache collection, in
        flatten order; by default the device KV pools (cache_k and
        cache_v of each layer). The integrity ledger and migration
        cover the K/V pools only."""
        return [l for k, l in zip(self._leaf_kinds,
                                  jax.tree_util.tree_leaves(self.cache))
                if k == kind]

    def kv_block_bytes(self, block: int, start: int,
                       stop: int) -> list:
        """Host bytes of positions ``[start, stop)`` of pool block
        ``block`` in each cache leaf (leaf order) — what the per-BLOCK
        crc ledger (BlockPool.crc_stream/crc_check) runs over. Decode
        reads one position; the verify-on-read pass re-reads each
        block's written prefix once per retiring request. Reads under
        the step lock: off CPU the step DONATES the cache, so a reader
        on another thread (the migration endpoint) must never hold
        leaves across a step."""
        with self._swap_lock:
            return [np.asarray(l[block, start:stop]).tobytes()
                    for l in self._cache_leaves()]

    def copy_kv_block(self, src: int, dst: int) -> None:
        """Device-side copy of pool block ``src`` onto ``dst`` in every
        cache leaf — the copy-on-write body behind partial prefix-block
        sharing (serve/prefix.py). One precompiled program; call once
        from warmup so the first divergent prompt never meets a
        compile."""
        with self._swap_lock:   # never tear a step in flight
            self.cache = self._copy_block(
                self.cache, jnp.asarray(src, jnp.int32),
                jnp.asarray(dst, jnp.int32))

    def install_kv_blocks(self, blocks: "list[int]",
                          block_leaf_bytes: "list[list[bytes]]",
                          lengths: "list[int]") -> None:
        """Write migrated KV bytes into pool blocks ``blocks``:
        ``block_leaf_bytes[j]`` carries one bytes object per cache
        leaf (leaf order, the order :meth:`kv_block_bytes` reads) for
        block ``blocks[j]``, covering positions ``[0, lengths[j])`` —
        the receive half of paged KV-block migration
        (serve/kv_migrate.py). BATCHED: one scatter per cache leaf
        for the whole sequence (positions past ``lengths[j]`` land as
        zeros — unreachable by the positional mask, and overwritten
        by the first decode write that needs them), not a full-pool
        functional update per (block, leaf). Byte counts are
        validated against the leaf dtype/shape before anything lands,
        and the write runs under the swap lock so it can never tear a
        step in flight."""
        if not blocks:
            return
        bs = self.kv_block_size
        if set(self._leaf_kinds) != {KV_POOL}:
            raise ValueError(
                "install_kv_blocks: this model's cache holds leaves that "
                "are no K/V pool (per-block leaves of another shape, "
                "per-row state); a migrated sequence would arrive "
                "without them. Migration of such state is not "
                "implemented (docs/serving.md)")
        with self._swap_lock:
            leaves, treedef = jax.tree_util.tree_flatten(self.cache)
            idxs = [i for i, k in enumerate(self._leaf_kinds)
                    if k == KV_POOL]
            if any(len(lb) != len(idxs) for lb in block_leaf_bytes):
                raise ValueError(
                    f"install_kv_blocks: payload leaf counts "
                    f"{[len(lb) for lb in block_leaf_bytes]} do not "
                    f"match the {len(idxs)} cache leaves — the "
                    f"sender's model layout does not match")
            ids = jnp.asarray(blocks, jnp.int32)
            for li, i in enumerate(idxs):
                leaf = leaves[i]
                tail = leaf.shape[2:]
                row = int(np.prod(tail)) * leaf.dtype.itemsize
                stacked = np.zeros((len(blocks), bs) + tail,
                                   leaf.dtype)
                for j, (lb, length) in enumerate(
                        zip(block_leaf_bytes, lengths)):
                    raw = lb[li]
                    if len(raw) != int(length) * row:
                        raise ValueError(
                            f"install_kv_blocks: leaf payload of "
                            f"{len(raw)} bytes != expected "
                            f"{int(length) * row} for {length} "
                            f"positions of {tail} {leaf.dtype} — "
                            f"incompatible pool layouts")
                    stacked[j, :int(length)] = np.frombuffer(
                        raw, dtype=leaf.dtype).reshape(
                        (int(length),) + tail)
                leaves[i] = leaf.at[ids].set(jnp.asarray(stacked))
            self.cache = jax.tree_util.tree_unflatten(treedef, leaves)

    def corrupt_kv_block(self, block: int, length: int) -> None:
        """The chaos ``serve.kv`` fault body: flip one deterministically
        chosen bit inside the first ``length`` positions of pool block
        ``block`` — real device bytes change, so detection must come
        from the per-block crc ledger, not from bookkeeping."""
        from ..chaos import inject as _chaos
        with self._swap_lock:
            leaves, treedef = jax.tree_util.tree_flatten(self.cache)
            idx = self._leaf_kinds.index(KV_POOL)
            row = np.array(leaves[idx][block, :length])
            flipped = np.frombuffer(
                _chaos.corrupt_copy(row.tobytes()),
                dtype=row.dtype).reshape(row.shape)
            leaves[idx] = leaves[idx].at[block, :length].set(
                jnp.asarray(flipped))
            self.cache = jax.tree_util.tree_unflatten(treedef, leaves)

    # -- metrics -------------------------------------------------------------
    def tokens_per_s(self) -> float:
        """Rolling throughput over the retained step window."""
        if len(self._tok_window) < 2:
            return 0.0
        t_first = self._tok_window[0][0]
        t_last = self._tok_window[-1][0]
        if t_last <= t_first:
            return 0.0
        toks = sum(n for _, n in self._tok_window) - self._tok_window[0][1]
        return toks / (t_last - t_first)

    def lowered_decode_text(self) -> str:
        """The decode step ([max_batch, 1]) lowered for this backend,
        as text — what a caller greps to see which attention path the
        resolved ``kernel`` actually put in the program (the fused
        kernel is a ``tpu_custom_call`` on TPU). Lowering only: nothing
        is compiled, run or donated."""
        B = self.max_batch
        zi = np.zeros(B, np.int32)
        packed = pack_step(
            np.zeros((B, 1), np.int32), zi, np.zeros(B, bool), zi,
            self._default_sample(B),
            np.full((B, self.blocks_per_seq), -1, np.int32),
            self.blocks_per_seq,
            np.arange(B) if self.per_row_state else None)
        return self._fwd_token.lower(
            self.params, self.cache, packed).as_text()

    def jit_cache_size(self) -> int:
        """Compiled-program count across the step functions — the churn
        tests assert this is flat."""
        return int(self._fwd_token._cache_size()
                   + self._fwd_verify._cache_size())
