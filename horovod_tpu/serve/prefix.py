"""Radix prefix cache: shared system prompts are computed once.

A host-side radix tree over prompt TOKEN IDS at block granularity —
each node is one full KV block (``block_size`` consecutive tokens) plus
the pool index holding that block's K/V. A prefill whose prompt walks
down an existing path COPIES BLOCK REFERENCES instead of recomputing
attention: the matched run joins the new sequence's block table with a
refcount each (serve/kv_cache.py ``BlockPool``), and only the suffix
past the match is fed to the model. Correctness rests on causality —
a block's K/V depends only on the tokens at and before it, and both
model families cache position-absolute values (learned positions /
post-RoPE keys), so a shared block is valid verbatim for every sequence
sharing that token prefix.

Three policies the serving contract needs:

* **Copy-on-write at the divergence block.** When the match ends
  MID-block (the prompt diverges inside a cached block, or simply ends
  there), the partially matching block is CoW'd: a fresh block is
  allocated, the cached one is device-copied onto it
  (``executor.copy_kv_block``), and the sequence writes its divergent
  tokens into the copy. The cached original is never written by a
  non-owner — a refcount > 1 block is read-only by construction.
* **LRU eviction of refcount-zero runs.** The tree holds one refcount
  per node; a node whose block's ONLY reference is the tree itself
  (pool refcount == 1), and all of whose descendants' likewise, is
  evictable, leaves first, least-recently matched first.
  `evictable_blocks()` feeds the paged admission gate, so
  cached-but-unreferenced runs count as free capacity.
* **Version fencing.** Cached K/V is only valid for the params that
  computed it: the batcher flushes this cache whenever
  ``swap_params`` adopts a new version (and the fleet router flushes a
  recovering replica before re-admission) — stale-weight KV can never
  serve a new model version (docs/serving.md).

The eviction bookkeeping is kept as the tree and the refcounts change,
never recomputed by a walk: the pool reports each tree block whose
refcount crosses 1 <-> 2 (``BlockPool.on_shared``), each node counts
the shared blocks in and below it, and the evictable leaves wait in a
heap keyed by ``last_used``. A match, a release and an eviction cost
the blocks they touch, whatever the size of the tree; the evictable
count is exact at every call.

Single-threaded by design: every method runs on the batcher's
scheduling thread, the same one-writer discipline as the block
allocator.
"""
from __future__ import annotations

import heapq
import logging
import zlib
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

from ..obs import metrics as obs_metrics
from .kv_cache import BlockPool

logger = logging.getLogger("horovod_tpu")


def _key(prompt, lo: int, hi: int) -> Tuple[int, ...]:
    """``prompt[lo:hi]`` as a tuple of Python ints: a tree key."""
    seg = prompt[lo:hi]
    if isinstance(seg, np.ndarray):
        return tuple(seg.tolist())
    return tuple(map(int, seg))


class _Node:
    __slots__ = ("tokens", "block", "children", "lead", "parent",
                 "last_used", "holds", "queued")

    def __init__(self, tokens: Tuple[int, ...], block: int,
                 parent: Optional["_Node"]):
        self.tokens = tokens
        self.block = block
        self.children: Dict[Tuple[int, ...], "_Node"] = {}
        #: the same children by their segment's first token, in
        #: insertion order: the only ones a partial match can hit
        self.lead: Dict[int, List["_Node"]] = {}
        self.parent = parent
        self.last_used = 0
        #: 1 while this node's block is shared (refcount > 1), plus
        #: one per child whose own ``holds`` is nonzero: the subtree
        #: holds a referenced block exactly when this is nonzero
        self.holds = 0
        #: ``last_used`` the node's current eviction-heap entry was
        #: pushed under (0: none)
        self.queued = 0


class RadixPrefixCache:
    """Block-granularity radix tree over prompt token ids."""

    def __init__(self, pool: BlockPool,
                 replica_id: Optional[int] = None):
        self.pool = pool
        self.block_size = pool.block_size
        self._children: Dict[Tuple[int, ...], _Node] = {}   # root level
        self._lead: Dict[int, List[_Node]] = {}
        self._nodes = 0
        self._tick = 0
        #: pool index -> the node that owns it (a block is in the tree
        #: at most once: insert only adds a sequence's own blocks at
        #: their own token path)
        self._by_block: Dict[int, _Node] = {}
        #: nodes whose ``holds`` is nonzero: evictable = nodes - held
        self._held = 0
        #: min-heap of (last_used, node) over the evictable leaves;
        #: entries whose tick is not the node's ``queued`` are stale
        self._lru: List[Tuple[int, _Node]] = []
        pool.on_shared = self._on_shared
        #: eviction hook (the KV tier's demotion trigger,
        #: serve/kvtier/): called with a structured event dict — run id,
        #: block index, block count (depth of the run), token length and
        #: the run's root->node token path — BEFORE the tree reference
        #: is dropped, so the subscriber can still read the block's
        #: device bytes and crc ledger. Runs on the scheduler thread
        #: (eviction is an admission-wave step); a raising hook is
        #: logged and dropped, never the scheduler's problem.
        self.on_evict: Optional[Callable[[dict], None]] = None
        # -- counters (obs): standalone stacks claim fresh, fleet
        # replicas get labeled children (the serve-wide discipline)
        rl = {} if replica_id is None else {"replica": str(replica_id)}
        R = obs_metrics.get_registry()
        if replica_id is None:
            for fam in ("hvd_serve_prefix_hits_total",
                        "hvd_serve_prefix_misses_total",
                        "hvd_serve_prefix_tokens_saved_total",
                        "hvd_serve_prefix_evictions_total"):
                R.unregister(fam)
        self._m_hits = R.counter(
            "hvd_serve_prefix_hits_total",
            "prefills that reused at least one cached prefix block",
            rl or None)
        self._m_misses = R.counter(
            "hvd_serve_prefix_misses_total",
            "prefills that matched no cached prefix", rl or None)
        self._m_saved = R.counter(
            "hvd_serve_prefix_tokens_saved_total",
            "prompt tokens served from cached KV instead of recompute",
            rl or None)
        self._m_evict = R.counter(
            "hvd_serve_prefix_evictions_total",
            "prefix blocks evicted (LRU, refcount-zero runs)", rl or None)

    # -- introspection -------------------------------------------------------
    def __len__(self) -> int:
        return self._nodes

    @property
    def hits(self) -> int:
        return int(self._m_hits.value)

    @property
    def misses(self) -> int:
        return int(self._m_misses.value)

    @property
    def tokens_saved(self) -> int:
        return int(self._m_saved.value)

    def _touch(self, node: _Node) -> None:
        self._tick += 1
        node.last_used = self._tick
        self._queue(node)

    # -- incremental bookkeeping ---------------------------------------------
    def _queue(self, node: _Node) -> None:
        """Give an evictable leaf its heap entry under its current
        ``last_used`` (no-op for any other node, or when it has one)."""
        if node.holds == 0 and not node.children \
                and node.queued != node.last_used:
            node.queued = node.last_used
            heapq.heappush(self._lru, (node.last_used, node))
            if len(self._lru) > 2 * self._nodes + 64:
                # stale entries (re-touched or re-held nodes) outgrew
                # the live ones: drop them, amortised O(1) a push
                self._lru = [e for e in self._lru
                             if e[1].queued == e[0]]
                heapq.heapify(self._lru)

    def _hold(self, node: _Node, d: int) -> None:
        """A hold (+1) or its release (-1) at ``node``: the node's own
        block became shared or unshared, or a child's subtree became
        held or clear. Walks up only while a count crosses zero."""
        while node is not None:
            node.holds += d
            if node.holds != (1 if d > 0 else 0):
                return
            self._held += d
            if d < 0:
                self._queue(node)
            node = node.parent

    def _on_shared(self, blk: int, d: int) -> None:
        """``BlockPool.on_shared``: ``blk``'s refcount crossed 1 <-> 2."""
        node = self._by_block.get(blk)
        if node is not None:
            self._hold(node, d)

    # -- capacity in TOKENS (the fleet index / autoscale definition) ---------
    def resident_tokens(self) -> int:
        """Prompt tokens whose KV is resident in the tree — every node
        is one full block, so this is nodes x block_size. The
        fleet-wide definition of cacheable capacity (``aggregate_
        healthz`` reports it per replica; docs/serving.md)."""
        return self._nodes * self.block_size

    def evictable_tokens(self) -> int:
        """Tokens releasable on demand (the token-granular view of
        :meth:`evictable_blocks`, same refcount rule)."""
        return self.evictable_blocks() * self.block_size

    def run_tokens(self, node: _Node) -> Tuple[int, ...]:
        """The root->node token path — the run identity the eviction
        event and the fleet KV tier key on."""
        segs: List[Tuple[int, ...]] = []
        cur: Optional[_Node] = node
        while cur is not None:
            segs.append(cur.tokens)
            cur = cur.parent
        out: List[int] = []
        for seg in reversed(segs):
            out.extend(seg)
        return tuple(out)

    # -- lookup --------------------------------------------------------------
    def match(self, prompt) -> Tuple[List[int], Optional[Tuple[int, int]],
                                     int]:
        """Longest cached prefix of ``prompt``, capped at
        ``len(prompt) - 1`` tokens (at least one prompt token must be
        prefilled so the request has a last-logit to sample from).

        Returns ``(full_blocks, partial, matched_tokens)`` where
        ``full_blocks`` are pool indices whose refcount was BUMPED for
        the caller (they become the sequence's references), and
        ``partial`` is ``(block, tokens_matched_in_block)`` for a
        mid-block match — also bumped, but as a TEMPORARY pin the
        caller must drop after the copy-on-write copy (the pin
        guarantees eviction cannot free the source mid-wave).

        Hit/miss accounting is the caller's (`note_lookup`): a match
        whose admission falls through must not count as a hit.
        """
        bs = self.block_size
        cap = len(prompt) - 1
        full: List[int] = []
        children, lead = self._children, self._lead
        pos = 0
        while pos + bs <= cap:
            child = children.get(_key(prompt, pos, pos + bs))
            if child is None:
                break
            self.pool.incref(child.block)
            self._touch(child)
            full.append(child.block)
            children, lead = child.children, child.lead
            pos += bs
        # partial (copy-on-write) match inside the next block: only a
        # child whose segment starts with the next token can match any
        # of it, and none can match more than one block
        partial: Optional[Tuple[int, int]] = None
        if pos < cap:
            want = _key(prompt, pos, min(cap, pos + bs))
            best, best_j = None, 0
            for child in lead.get(want[0], ()):
                j = 0
                for a, b in zip(child.tokens, want):
                    if a != b:
                        break
                    j += 1
                if j > best_j:
                    best, best_j = child, j
            if best is not None:
                self.pool.incref(best.block)      # temp pin, see above
                self._touch(best)
                partial = (best.block, best_j)
                pos += best_j
        return full, partial, pos

    def note_lookup(self, matched_tokens: int) -> None:
        """Fold one ADMITTED prefill into the hit/miss/tokens-saved
        counters (docs/metrics.md)."""
        if matched_tokens > 0:
            self._m_hits.inc()
            self._m_saved.inc(matched_tokens)
        else:
            self._m_misses.inc()

    def release(self, blocks) -> None:
        """Drop references handed out by :meth:`match` (an admission
        that fell through, or the CoW temp pin after the copy)."""
        for blk in blocks:
            self.pool.decref(blk)

    # -- insertion -----------------------------------------------------------
    def insert(self, prompt, seq_blocks: List[int]) -> int:
        """Record ``prompt``'s full blocks (computed KV now resident in
        ``seq_blocks``, the sequence's table) into the tree; each newly
        created node takes its own refcount on the block. Existing
        nodes win (first writer of a prefix keeps it — contents are
        identical by construction). Returns nodes created."""
        bs = self.block_size
        children = self._children
        parent: Optional[_Node] = None
        created = 0
        pos = 0
        while pos + bs <= len(prompt) and (pos // bs) < len(seq_blocks):
            seg = _key(prompt, pos, pos + bs)
            child = children.get(seg)
            if child is None:
                child = self._add(parent, seg, seq_blocks[pos // bs])
                created += 1
            self._touch(child)
            parent = child
            children = child.children
            pos += bs
        return created

    def _add(self, parent: Optional[_Node], seg: Tuple[int, ...],
             block: int) -> _Node:
        """A new leaf under ``parent`` (None: the root) holding
        ``block``, with the tree's own refcount on it. The block's
        other holder (the caller) makes it shared at once."""
        self.pool.incref(block)
        node = _Node(seg, block, parent)
        children, lead = (self._children, self._lead) if parent is None \
            else (parent.children, parent.lead)
        children[seg] = node
        lead.setdefault(seg[0], []).append(node)
        self._by_block[block] = node
        self._nodes += 1
        if self.pool.refcount[block] > 1:
            self._hold(node, 1)
        return node

    def attach(self, tokens, block: int) -> bool:
        """Graft ONE block back onto the tree (the KV tier's promotion
        path, serve/kvtier/): ``tokens`` is the full root->node token
        path (a multiple of ``block_size``; the last ``block_size``
        tokens are the new node's segment) and ``block`` a pool index
        whose bytes already hold that segment's KV (installed through
        the verified path). Takes its OWN refcount on success — the
        caller keeps/releases whatever reference it held. Returns False
        without touching anything when the parent path is missing (the
        caller promotes shallower blocks first) or the node already
        exists (someone recomputed it; the existing node wins, exactly
        like :meth:`insert`)."""
        bs = self.block_size
        toks = tuple(int(t) for t in tokens)
        if not toks or len(toks) % bs != 0:
            return False
        children = self._children
        parent: Optional[_Node] = None
        for pos in range(0, len(toks) - bs, bs):
            parent = children.get(toks[pos:pos + bs])
            if parent is None:
                return False
            children = parent.children
        seg = toks[-bs:]
        if seg in children:
            return False
        self._touch(self._add(parent, seg, block))
        return True

    # -- eviction ------------------------------------------------------------
    def evictable_blocks(self) -> int:
        """Blocks releasable on demand: nodes whose subtree holds no
        externally referenced block (pool refcount > 1 anywhere below
        pins the whole path — leaf-first eviction cannot reach it).
        Kept as the tree and the refcounts change: O(1)."""
        return self._nodes - self._held

    def _lru_leaf(self) -> Optional[_Node]:
        """The least recently used evictable leaf, off the heap."""
        while self._lru:
            tick, node = heapq.heappop(self._lru)
            if node.queued != tick:
                continue                      # superseded entry
            node.queued = 0
            if node.holds == 0 and not node.children:
                return node
        return None

    def evict(self, n_blocks: int) -> int:
        """Release at least ``n_blocks`` back to the pool if possible:
        LRU leaves first, cascading up as parents become leaves.
        Returns blocks actually freed."""
        freed = 0
        while freed < n_blocks:
            victim = self._lru_leaf()
            if victim is None:
                break
            hook = self.on_evict
            if hook is not None:
                # structured eviction event, emitted BEFORE the decref:
                # the run's block is still owned by the tree here, so a
                # demotion subscriber (serve/kvtier/) can read its
                # device bytes and crc ledger. "run" is a stable id of
                # the root->node token path; "blocks" its depth.
                tokens = self.run_tokens(victim)
                depth = len(tokens) // self.block_size
                ev = {"run": "%08x" % zlib.crc32(
                          b"".join(int(t).to_bytes(4, "little")
                                   for t in tokens)),
                      "tokens": tokens,
                      "block": victim.block,
                      "blocks": depth,
                      "token_len": len(tokens)}
                try:
                    hook(ev)
                except Exception as e:  # noqa: BLE001 — a demotion
                    # failure must degrade to plain eviction (the run
                    # re-prefills later), never kill the scheduler
                    logger.warning(
                        "prefix eviction hook failed (run dropped, "
                        "will re-prefill on next use): %s", e)
            self._remove(victim)
            freed += 1
            self._m_evict.inc()
        return freed

    def _remove(self, node: _Node) -> None:
        """Drop an evictable leaf; its parent may become one."""
        parent = node.parent
        children, lead = (self._children, self._lead) if parent is None \
            else (parent.children, parent.lead)
        del children[node.tokens]
        same = lead[node.tokens[0]]
        same.remove(node)
        if not same:
            del lead[node.tokens[0]]
        del self._by_block[node.block]
        self._nodes -= 1
        self.pool.decref(node.block)
        if parent is not None:
            self._queue(parent)

    def flush(self) -> int:
        """Drop EVERY cached run (weight-swap invalidation): all tree
        references return to the pool; blocks still shared by live
        sequences survive under their owners' refcounts and die with
        them. Returns nodes dropped."""
        dropped = 0
        stack = list(self._children.values())
        self._by_block = {}
        while stack:
            n = stack.pop()
            stack.extend(n.children.values())
            self.pool.decref(n.block)
            dropped += 1
        self._children, self._lead = {}, {}
        self._nodes = self._held = 0
        self._lru = []
        return dropped
