"""The radix prefix cache's kept bookkeeping against the walks it
replaced (tier-1, CPU).

`RadixPrefixCache` keeps its evictable count and its least-recently-used
evictable leaves as the tree and the pool's refcounts change
(serve/prefix.py). The oracle is the cache as it was before that: a
whole-tree post-order walk for ``evictable_blocks``, ``min(last_used)``
over a fresh list of leaves for each eviction, and a match that read
the whole remaining prompt. It is copied here, whole, and driven beside
the cache under test through the same seeded sequences of operations
over two identical pools; after every operation the counts, the match
results, the eviction victims and their events, the refcounts, the free
lists and the trees must be identical.

The cost tests count the tree NODES an admission wave and an eviction
read (a node class that records every attribute read), not time: a wave
whose head is refused reads the head's matched path and a constant
more, whatever the size of the tree, and ``evict(k)`` reads O(k).
"""
import zlib
from types import SimpleNamespace
from typing import Dict, List, Optional, Tuple

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from horovod_tpu.models.gpt import GPT, GPTConfig
from horovod_tpu.serve import (AdmissionQueue, BlockPool, ContinuousBatcher,
                               PagedKVCache, RadixPrefixCache,
                               ShardedExecutor)
from horovod_tpu.serve import prefix as prefix_mod
from horovod_tpu.trace import SpanRecorder
from horovod_tpu.trace import spans as spans_mod


# ---------------------------------------------------------------------------
# the oracle: the cache as it was, walks and all
# ---------------------------------------------------------------------------

class _ONode:
    __slots__ = ("tokens", "block", "children", "parent", "last_used")

    def __init__(self, tokens, block, parent):
        self.tokens = tokens
        self.block = block
        self.children = {}
        self.parent = parent
        self.last_used = 0


class OracleCache:
    """The whole-walk radix cache (no metrics): the reference."""

    def __init__(self, pool: BlockPool):
        self.pool = pool
        self.block_size = pool.block_size
        self._children: Dict[Tuple[int, ...], _ONode] = {}
        self._nodes = 0
        self._tick = 0
        self.on_evict = None
        self.evictions = 0

    def __len__(self):
        return self._nodes

    def _touch(self, node):
        self._tick += 1
        node.last_used = self._tick

    def resident_tokens(self):
        return self._nodes * self.block_size

    def evictable_tokens(self):
        return self.evictable_blocks() * self.block_size

    def run_tokens(self, node):
        segs = []
        cur = node
        while cur is not None:
            segs.append(cur.tokens)
            cur = cur.parent
        out = []
        for seg in reversed(segs):
            out.extend(seg)
        return tuple(out)

    def match(self, prompt):
        bs = self.block_size
        cap = len(prompt) - 1
        full = []
        children = self._children
        pos = 0
        while pos + bs <= cap:
            seg = tuple(int(t) for t in prompt[pos:pos + bs])
            child = children.get(seg)
            if child is None:
                break
            self.pool.incref(child.block)
            self._touch(child)
            full.append(child.block)
            children = child.children
            pos += bs
        partial = None
        want = [int(t) for t in prompt[pos:cap]]
        if want:
            best, best_j = None, 0
            for child in children.values():
                j = 0
                for a, b in zip(child.tokens, want):
                    if a != b:
                        break
                    j += 1
                if j > best_j:
                    best, best_j = child, j
            if best is not None:
                self.pool.incref(best.block)
                self._touch(best)
                partial = (best.block, best_j)
                pos += best_j
        return full, partial, pos

    def note_lookup(self, matched_tokens):
        pass

    def release(self, blocks):
        for blk in blocks:
            self.pool.decref(blk)

    def insert(self, prompt, seq_blocks):
        bs = self.block_size
        children = self._children
        parent = None
        created = 0
        pos = 0
        while pos + bs <= len(prompt) and (pos // bs) < len(seq_blocks):
            seg = tuple(int(t) for t in prompt[pos:pos + bs])
            child = children.get(seg)
            if child is None:
                blk = seq_blocks[pos // bs]
                self.pool.incref(blk)
                child = _ONode(seg, blk, parent)
                children[seg] = child
                self._nodes += 1
                created += 1
            self._touch(child)
            parent = child
            children = child.children
            pos += bs
        return created

    def attach(self, tokens, block):
        bs = self.block_size
        toks = tuple(int(t) for t in tokens)
        if not toks or len(toks) % bs != 0:
            return False
        children = self._children
        parent = None
        for pos in range(0, len(toks) - bs, bs):
            parent = children.get(toks[pos:pos + bs])
            if parent is None:
                return False
            children = parent.children
        seg = toks[-bs:]
        if seg in children:
            return False
        self.pool.incref(block)
        node = _ONode(seg, block, parent)
        children[seg] = node
        self._nodes += 1
        self._touch(node)
        return True

    def _leaves(self):
        out = []
        stack = list(self._children.values())
        while stack:
            n = stack.pop()
            if n.children:
                stack.extend(n.children.values())
            else:
                out.append(n)
        return out

    def evictable_blocks(self):
        return walk_evictable(self)

    def evict(self, n_blocks):
        freed = 0
        while freed < n_blocks:
            cands = [lf for lf in self._leaves()
                     if self.pool.refcount[lf.block] == 1]
            if not cands:
                break
            victim = min(cands, key=lambda lf: lf.last_used)
            if self.on_evict is not None:
                tokens = self.run_tokens(victim)
                self.on_evict({
                    "run": "%08x" % zlib.crc32(
                        b"".join(int(t).to_bytes(4, "little")
                                 for t in tokens)),
                    "tokens": tokens, "block": victim.block,
                    "blocks": len(tokens) // self.block_size,
                    "token_len": len(tokens)})
            self._remove(victim)
            freed += 1
            self.evictions += 1
        return freed

    def _remove(self, node):
        siblings = (node.parent.children if node.parent is not None
                    else self._children)
        siblings.pop(node.tokens, None)
        self.pool.decref(node.block)
        self._nodes -= 1

    def flush(self):
        dropped = 0
        stack = list(self._children.values())
        while stack:
            n = stack.pop()
            stack.extend(n.children.values())
            self.pool.decref(n.block)
            dropped += 1
        self._children = {}
        self._nodes = 0
        return dropped


def walk_evictable(cache) -> int:
    """The old whole-tree count, over either cache's tree: nodes whose
    subtree holds no block with a pool refcount above 1."""
    count = 0
    ok = {}
    stack = [(n, False) for n in cache._children.values()]
    while stack:
        node, visited = stack.pop()
        if not visited:
            stack.append((node, True))
            stack.extend((ch, False) for ch in node.children.values())
            continue
        good = cache.pool.refcount[node.block] == 1 and all(
            ok[id(ch)] for ch in node.children.values())
        ok[id(node)] = good
        if good:
            count += 1
    return count


def tree_dump(cache) -> List[tuple]:
    """Every node as (token path, block, last_used), sorted."""
    out = []
    stack = [((), n) for n in cache._children.values()]
    while stack:
        path, n = stack.pop()
        p = path + n.tokens
        out.append((p, n.block, n.last_used))
        stack.extend((p, c) for c in n.children.values())
    return sorted(out)


# ---------------------------------------------------------------------------
# seeded operation sequences, run on the cache and on the oracle
# ---------------------------------------------------------------------------

class World:
    """A pool, a paged row table and a prefix cache over them, driven
    the way the batcher drives them."""

    def __init__(self, cache_cls, blocks: int, bs: int, rows: int,
                 blocks_per_seq: int):
        self.pool = BlockPool(blocks, bs)
        self.cache = cache_cls(self.pool)
        self.kv = PagedKVCache(rows, blocks_per_seq, self.pool)
        self.kv.evictor = self.cache.evict
        self.kv.evictable = self.cache.evictable_blocks
        self.events: List[dict] = []
        self.cache.on_evict = self.events.append
        #: row -> token budget it may grow to
        self.budget: Dict[int, int] = {}

    def admit(self, prompt, max_new):
        """Plan (match, pins charged against the wave's snapshot) and
        admit or refuse, as `ContinuousBatcher._admit` does."""
        ev0 = self.cache.evictable_blocks()
        full, partial, m = self.cache.match(prompt)
        budget = len(prompt) + max_new
        new_blocks = max(self.kv.blocks_needed(budget) - len(full), 0)
        pins = len(full) + (partial is not None)
        ok = self.kv.can_admit(new_blocks, max(ev0 - pins, 0))
        if not ok:
            self.cache.release(full + ([partial[0]] if partial else []))
            return (list(full), partial, m, False)
        row = self.kv.alloc_row(new_blocks)
        for blk in full:
            self.kv.attach_shared(row, blk)
        if partial is not None:
            self.kv.append_block(row)          # the CoW copy's block
            self.cache.release([partial[0]])   # drop the CoW pin
        self.kv.ensure(row, len(prompt))
        self.cache.insert(prompt, self.kv.blocks[row])
        self.budget[row] = budget
        return (list(full), partial, m, row)

    def grow(self, row, k):
        self.kv.ensure(row, min(
            len(self.kv.blocks[row]) * self.pool.block_size + k,
            self.budget[row]))
        return len(self.kv.blocks[row])

    def free(self, row):
        self.kv.free_row(row)
        del self.budget[row]

    def attach(self, tokens):
        blk = self.pool.alloc()
        if blk is None:
            return None
        ok = self.cache.attach(tokens, blk)
        self.pool.decref(blk)       # the tree's refcount is the owner
        return ok

    def match_release(self, prompt):
        full, partial, m = self.cache.match(prompt)
        self.cache.release(full + ([partial[0]] if partial else []))
        return (list(full), partial, m)


def _prompts(rng, bs: int, shared: bool) -> List[List[int]]:
    """Shared: one system prompt that ends mid-block under suffixes
    that share stems of their own; unshared: distinct prompts plus
    exact repeats. A small vocabulary makes segments that share first
    tokens and diverge mid-block."""
    if shared:
        system = list(rng.randint(0, 6, 3 * bs + bs // 2))
        stems = [list(rng.randint(0, 6, rng.randint(1, 2 * bs)))
                 for _ in range(4)]
        out = []
        for _ in range(12):
            stem = stems[rng.randint(len(stems))]
            cut = rng.randint(0, len(stem) + 1)
            tail = list(rng.randint(0, 6, rng.randint(0, bs + 2)))
            out.append(system + stem[:cut] + tail)
        return out
    out = [list(rng.randint(0, 50, rng.randint(1, 5 * bs)))
           for _ in range(8)]
    return out + out[:3]


def _run_sequence(seed: int, bs: int, shared: bool, steps: int = 300):
    rng = np.random.RandomState(seed)
    prompts = _prompts(rng, bs, shared)
    longest = max(len(p) for p in prompts) + 2 * bs
    per_seq = -(-longest // bs) + 1
    blocks = 3 * per_seq
    worlds = [World(RadixPrefixCache, blocks, bs, 5, per_seq),
              World(OracleCache, blocks, bs, 5, per_seq)]
    new, old = worlds
    kinds = ("admit", "admit", "admit", "grow", "grow", "free", "free",
             "evict", "match", "attach", "flush")
    weights = np.array([3, 3, 3, 2, 2, 3, 2, 1.5, 1.5, 1.5, 0.15])
    seen = set()
    for step in range(steps):
        kind = kinds[rng.choice(len(kinds), p=weights / weights.sum())]
        live = sorted(new.budget)
        if kind == "admit":
            p = prompts[rng.randint(len(prompts))]
            args = (p, int(rng.randint(1, 2 * bs + 1)))
        elif kind in ("grow", "free"):
            if not live:
                continue
            args = (live[rng.randint(len(live))],) + \
                ((int(rng.randint(1, bs + 2)),) if kind == "grow" else ())
        elif kind == "evict":
            args = (int(rng.randint(1, 5)),)
        elif kind == "match":
            args = (prompts[rng.randint(len(prompts))],)
        elif kind == "attach":
            p = prompts[rng.randint(len(prompts))]
            depth = int(rng.randint(1, len(p) // bs + 1)) \
                if len(p) >= bs else 1
            args = (p[:depth * bs],)
        else:
            args = ()
        fn = {"admit": World.admit, "grow": World.grow,
              "free": World.free, "match": World.match_release,
              "attach": World.attach,
              "evict": lambda w, k: w.cache.evict(k),
              "flush": lambda w: w.cache.flush()}[kind]
        got = [fn(w, *args) for w in worlds]
        where = f"step {step} ({kind})"
        assert got[0] == got[1], where
        assert new.events == old.events, where
        new.events.clear()
        old.events.clear()
        assert new.cache.evictable_blocks() == walk_evictable(new.cache) \
            == old.cache.evictable_blocks(), where
        assert new.cache.evictable_tokens() == \
            old.cache.evictable_tokens(), where
        assert len(new.cache) == len(old.cache), where
        assert np.array_equal(new.pool.refcount, old.pool.refcount), where
        assert new.pool._free == old.pool._free, where
        assert tree_dump(new.cache) == tree_dump(old.cache), where
        seen.add(kind)
        if kind == "admit" and got[0][1] is not None:
            seen.add("partial")
        if kind == "admit" and got[0][3] is False:
            seen.add("refused")
    return new, old, seen


@pytest.mark.parametrize("shared", [True, False],
                         ids=["shared", "unshared"])
@pytest.mark.parametrize("bs", [4, 16])
@pytest.mark.parametrize("seed", [0, 1, 2, 3, 4])
def test_kept_bookkeeping_equals_the_walks(seed, bs, shared):
    """Counts, matches, victims and their order, on_evict events,
    refcounts, free lists and trees: identical to the oracle after
    every operation of a seeded sequence."""
    new, old, seen = _run_sequence(seed, bs, shared)
    # the sequence reached what it is there to reach
    assert {"admit", "grow", "free", "evict", "match",
            "attach"} <= seen
    assert "refused" in seen
    if shared:
        assert "partial" in seen
    assert new.cache._m_evict.value > 0


@pytest.mark.parametrize("bs", [4, 16])
def test_heap_stays_bounded_without_evictions(bs):
    """Re-touching evictable leaves leaves stale heap entries; they
    are dropped once they outnumber the tree, so a server that never
    evicts does not grow the heap."""
    pool = BlockPool(64, bs)
    cache = RadixPrefixCache(pool)
    prompt = list(range(3 * bs))
    blks = [pool.alloc() for _ in range(3)]
    cache.insert(prompt, blks)
    for b in blks:
        pool.decref(b)
    for _ in range(2000):
        cache.insert(prompt, blks)        # touches the path: a new tick
    assert len(cache._lru) <= 2 * len(cache) + 64 + 1
    assert cache.evictable_blocks() == walk_evictable(cache) == 3
    assert cache.evict(3) == 3 and len(cache) == 0
    assert pool.free_count() == 64


# ---------------------------------------------------------------------------
# cost: nodes read, not time
# ---------------------------------------------------------------------------

_READS: List[Optional[set]] = [None]


class _CountingNode(prefix_mod._Node):
    """A tree node that records which nodes have had any attribute
    read while ``_READS[0]`` is a set."""
    __slots__ = ()

    def __getattribute__(self, name):
        seen = _READS[0]
        if seen is not None:
            seen.add(id(self))
        return object.__getattribute__(self, name)


class _Reads:
    def __enter__(self):
        _READS[0] = set()
        return self

    def __exit__(self, *exc):
        self.n = len(_READS[0])
        _READS[0] = None


_DOC_BS = 64
_SYSTEM = 2048                         # 32 blocks, shared by every prompt
_GRID = (3653, 5166, 7306, 10333)      # the doc16 cell's prompt lengths
_MAX_LEN = 12288


@pytest.fixture(scope="module")
def doc_model():
    cfg = dict(vocab_size=512, num_layers=1, num_heads=1, head_dim=8,
               max_seq_len=_MAX_LEN, dtype=jnp.float32,
               attention_impl="reference")
    model = GPT(GPTConfig(decode=True, **cfg, kv_block_size=_DOC_BS,
                          kv_pool_blocks=1536))
    params = GPT(GPTConfig(**cfg)).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]
    return SimpleNamespace(model=model, params=params)


def _doc_batcher(doc_model, n_docs: int, live: int, monkeypatch):
    """A doc16-shaped batcher state built on the host alone: a 32-block
    system prompt under ``n_docs`` documents, the first ``live`` of
    them held by live rows that reserve blocks for their answers, the
    rest cached and evictable. Nodes are `_CountingNode`s."""
    monkeypatch.setattr(prefix_mod, "_Node", _CountingNode)
    ex = ShardedExecutor(doc_model.model, doc_model.params, max_batch=16,
                         max_len=_MAX_LEN)
    q = AdmissionQueue(max_queue=8, default_deadline_ms=600000.0)
    b = ContinuousBatcher(ex, q, buckets=(_MAX_LEN,), prefix_cache=True,
                          kv_crc=False, spec_k=0, kv_tier=False)
    rng = np.random.RandomState(n_docs)
    system = list(rng.randint(0, 512, _SYSTEM))
    for i in range(n_docs):
        n = _GRID[i % len(_GRID)]
        prompt = system + list(rng.randint(0, 512, n - _SYSTEM))
        full, partial, _ = b.prefix.match(prompt)
        if partial is not None:
            b.prefix.release([partial[0]])
        row = b.kv.alloc_row(0)
        for blk in full:
            b.kv.attach_shared(row, blk)
        b.kv.ensure(row, n)
        b.prefix.insert(prompt, b.kv.blocks[row])
        if i >= live:
            b.kv.free_row(row)
        else:
            b.kv.reserved[row] = 48      # its answer's worst case
    assert b.prefix.evictable_blocks() == walk_evictable(b.prefix) > 0
    return b, system, rng


def _blocked_head(b, system, rng):
    """A 10,333-token head that must wait: more fresh blocks than
    free + evictable - reserved."""
    head = system + list(rng.randint(0, 512, _GRID[-1] - _SYSTEM))
    need = b.kv.blocks_needed(len(head) + 1722) - _SYSTEM // _DOC_BS
    row = next(iter(b.kv.reserved))
    b.kv.reserved[row] += max(b.kv.available_blocks() - need + 1, 0)
    assert 0 < b.kv.available_blocks() < need
    return b.queue.submit(head, max_new_tokens=1722)


@pytest.mark.parametrize("n_docs", [5, 17])
def test_refused_wave_reads_its_path_not_the_tree(doc_model, n_docs,
                                                  monkeypatch):
    """One admission wave whose head is refused reads the head's 32
    matched nodes and a constant more, with the tree at ~400 or
    ~1,200 nodes; the nodes every other wave leaves held read alike."""
    b, system, rng = _doc_batcher(doc_model, n_docs, n_docs * 3 // 4,
                                  monkeypatch)
    assert len(b.prefix) > 60 * n_docs
    h = _blocked_head(b, system, rng)
    rec = SpanRecorder()
    with rec.span("sched_admit") as span, _Reads() as reads:
        admitted = b._admit(span)
    assert admitted == [] and b.queue.depth() == 1
    assert h.status == "pending"
    assert reads.n <= _SYSTEM // _DOC_BS + 4, reads.n
    assert span.attrs == {"planned": 1, "admitted": 0}
    # the refused plan's pins are all released
    assert b.prefix.evictable_blocks() == walk_evictable(b.prefix)


@pytest.mark.parametrize("n_docs", [5, 17])
def test_evict_reads_what_it_frees(doc_model, n_docs, monkeypatch):
    """``evict(k)`` reads O(k) nodes (each victim and its parent), not
    the tree, and frees the oracle's victims in the oracle's order."""
    b, _, _ = _doc_batcher(doc_model, n_docs, n_docs // 2, monkeypatch)
    cache = b.prefix
    assert cache.evictable_blocks() >= 68
    for k in (1, 7, 40):
        before = cache.evictable_blocks()
        want = _oracle_victims(cache, k)
        with _Reads() as reads:
            got = cache.evict(k)
        assert got == k and len(want) == k
        assert all(cache.pool.refcount[blk] == 0 for blk in want)
        assert reads.n <= 2 * k + 4, (k, reads.n)
        assert cache.evictable_blocks() == before - k \
            == walk_evictable(cache)
    # the order, through the events (whose token paths are the
    # subscriber's cost, not counted above)
    want = _oracle_victims(cache, 20)
    freed = []
    cache.on_evict = lambda ev: freed.append(ev["block"])
    assert cache.evict(20) == 20 and freed == want


def _oracle_victims(cache, k: int) -> List[int]:
    """The blocks the old ``min(last_used)`` loop would free, in order,
    computed on a shadow of the tree (the real one is not touched)."""
    shadow = {id(n): (n, set(id(c) for c in n.children.values()))
              for n in _walk_all(cache)}
    out = []
    for _ in range(k):
        cands = [n for n, kids in shadow.values()
                 if not kids and cache.pool.refcount[n.block] == 1]
        if not cands:
            break
        v = min(cands, key=lambda n: n.last_used)
        out.append(v.block)
        del shadow[id(v)]
        if v.parent is not None and id(v.parent) in shadow:
            shadow[id(v.parent)][1].discard(id(v))
    return out


def _walk_all(cache):
    out = []
    stack = list(cache._children.values())
    while stack:
        n = stack.pop()
        out.append(n)
        stack.extend(n.children.values())
    return out


# ---------------------------------------------------------------------------
# the sched_admit counter, on a served model
# ---------------------------------------------------------------------------

_KW = dict(vocab_size=64, num_layers=2, num_heads=2, head_dim=8,
           max_seq_len=48, dtype=jnp.float32, attention_impl="reference")


@pytest.fixture(scope="module")
def tiny():
    train = GPT(GPTConfig(**_KW))
    params = train.init(jax.random.PRNGKey(0),
                        jnp.zeros((2, 8), jnp.int32))["params"]

    @jax.jit
    def onext(p, padded, last):
        return jnp.argmax(jnp.take(
            train.apply({"params": p}, padded)[0], last, axis=0))

    def oracle(prompt, max_new):
        seq, out = list(prompt), []
        for _ in range(max_new):
            padded = np.zeros((1, _KW["max_seq_len"]), np.int32)
            padded[0, :len(seq)] = seq
            nxt = int(onext(params, jnp.asarray(padded),
                            jnp.asarray(len(seq) - 1)))
            out.append(nxt)
            seq.append(nxt)
        return out

    return SimpleNamespace(params=params, oracle=oracle)


def _served(tiny, pool_blocks: int, oracle_cache: bool):
    """A 4-row batcher over a pool of ``pool_blocks`` 4-token blocks
    (the worst case is 4 x 12 = 48), its waves recorded: the
    admission order and each wave's ``sched_admit`` attributes."""
    model = GPT(GPTConfig(decode=True, **_KW, kv_block_size=4,
                          kv_pool_blocks=pool_blocks))
    ex = ShardedExecutor(model, tiny.params, max_batch=4,
                         max_len=_KW["max_seq_len"])
    q = AdmissionQueue(max_queue=64, default_deadline_ms=600000.0)
    b = ContinuousBatcher(ex, q, buckets=(16, 32), prefix_cache=True,
                          kv_crc=False, spec_k=0, kv_tier=False)
    if oracle_cache:
        b.kv.pool.on_shared = None
        b.prefix = OracleCache(b.kv.pool)
        b.kv.evictable = b.prefix.evictable_blocks
        b.kv.evictor = b.prefix.evict
    b.warmup()
    waves: List[List[int]] = []
    real = b._prefill

    def prefill(admitted):
        waves.append([a.req.rid for a in admitted])
        real(admitted)

    b._prefill = prefill
    return ex, q, b, waves


def _shared_traffic(seed=7, n=14):
    rng = np.random.RandomState(seed)
    system = list(rng.randint(0, 64, 10))       # 2.5 blocks: ends mid-block
    return [(system + list(rng.randint(0, 64, rng.randint(1, 12))),
             int(rng.randint(6, 20))) for _ in range(n)]


def test_token_streams_and_admission_order_unchanged(tiny):
    """A shared prefix served from a pool of half the worst case:
    greedy tokens equal the oracle's, and the waves admit the same
    requests in the same order as the cache with the walks did, with
    evictions on the way."""
    traffic = _shared_traffic()
    out = []
    for oracle_cache in (False, True):
        rec, old = SpanRecorder(), spans_mod._recorder
        spans_mod._recorder = rec
        try:
            ex, q, b, waves = _served(tiny, 24, oracle_cache)
            handles = [q.submit(p, max_new_tokens=k) for p, k in traffic]
            b.run()
        finally:
            spans_mod._recorder = old
        out.append(SimpleNamespace(b=b, waves=waves, handles=handles,
                                   spans=rec.between(0.0, float("inf"))))
    new, ref = out
    for (p, k), h in zip(traffic, new.handles):
        assert h.status == "ok" and h.tokens == tiny.oracle(p, k)
    assert [h.tokens for h in new.handles] == \
        [h.tokens for h in ref.handles]
    assert new.waves == ref.waves
    assert len(new.waves) > 3                  # blocks, not rows, gate it
    assert new.b.prefix._m_evict.value > 0 and ref.b.prefix.evictions > 0
    assert new.b.prefix._m_evict.value == ref.b.prefix.evictions
    assert new.b.prefix.hits > 0
    # the counter: planned - admitted is the refused head, and both
    # the blocked and the admitting iterations occur
    admits = [s.extra for s in new.spans
              if s.name == "sched_admit" and s.extra]
    assert all(set(e) == {"planned", "admitted"} for e in admits)
    assert sum(e["admitted"] for e in admits) == len(traffic)
    assert any(e["planned"] == e["admitted"] + 1 and e["admitted"] == 0
               for e in admits)
    assert any(e["admitted"] > 0 for e in admits)
    assert all(e["planned"] - e["admitted"] in (0, 1) for e in admits)


def test_sched_admit_attrs_only_with_the_prefix_cache(tiny):
    """With the prefix cache off the span carries no attributes."""
    rec, old = SpanRecorder(), spans_mod._recorder
    spans_mod._recorder = rec
    try:
        ex, q, b, _ = _served(tiny, 48, False)
        b.prefix = None
        b.kv.evictable = b.kv.evictor = None
        q.submit([1, 2, 3], max_new_tokens=2)
        b.run()
    finally:
        spans_mod._recorder = old
    admits = [s for s in rec.between(0.0, float("inf"))
              if s.name == "sched_admit"]
    assert admits and all(not s.extra for s in admits)
