"""Paged KV, radix prefix sharing, speculative decoding (tier-1, CPU).

The ISSUE 10 bars (docs/serving.md):

* the block allocator never hands out an in-use block — alloc/free/
  refcount/eviction are airtight under reuse and sharing;
* decode over the block pool and block tables emits EXACTLY the tokens
  of the straight-line greedy oracle, for GPT and Llama-GQA,
  across admission waves that recycle rows and blocks;
* prefix-shared prefills (full-block reuse AND a copy-on-write
  divergence mid-block) stay bit-identical, and the shared source
  block is never mutated by a non-owner;
* speculative decoding — including a drafter whose proposals get
  REJECTED and rolled back — emits the target's greedy stream
  bit-identically (same tokens, same stop positions) and wins
  < 0.7 target steps per token when the drafter agrees;
* deadline-expired and shed requests release every block reference and
  prefix refcount in the same iteration: zero leaked blocks after an
  overload burst;
* a chaos ``serve.kv`` corrupt flips a bit in a real pool BLOCK and
  the per-block crc catches it before tokens reach a client;
* the new config knobs parse strictly; the fleet flushes a recovered
  replica's prefix cache before re-admission (stale-weight KV can
  never serve a new version).
"""
import time
from types import SimpleNamespace

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from horovod_tpu.chaos import inject
from horovod_tpu.chaos.plan import ChaosPlan
from horovod_tpu.core.config import Config
from horovod_tpu.models.gpt import GPT, GPTConfig
from horovod_tpu.models.llama import Llama, LlamaConfig
from horovod_tpu.serve import (AdmissionQueue, BlockPool, ContinuousBatcher,
                               PagedKVCache, RadixPrefixCache, Rejected,
                               ShardedExecutor)
from horovod_tpu.trace import SpanRecorder, TraceContext, get_recorder
from horovod_tpu.trace import spans as spans_mod
from horovod_tpu.trace.spans import to_wall

_KW = dict(vocab_size=64, num_layers=2, num_heads=2, head_dim=8,
           max_seq_len=48, dtype=jnp.float32, attention_impl="reference")
_BS, _POOL = 4, 32


@pytest.fixture(autouse=True)
def _disarm():
    inject.uninstall()
    yield
    inject.uninstall()


@pytest.fixture(scope="module")
def gpt():
    """Tiny GPT over ONE param set: training-mode oracle and decode
    over an explicit pool."""
    train = GPT(GPTConfig(**_KW))
    paged = GPT(GPTConfig(decode=True, **_KW, kv_block_size=_BS,
                          kv_pool_blocks=_POOL))
    params = train.init(jax.random.PRNGKey(0),
                        jnp.zeros((2, 8), jnp.int32))["params"]
    # a DIFFERENT drafter (disagrees with the target almost always —
    # the rejection/rollback path) and a PERFECT drafter (same params)
    draft_params = train.init(jax.random.PRNGKey(9),
                              jnp.zeros((2, 8), jnp.int32))["params"]

    @jax.jit
    def onext(p, padded, last):
        return jnp.argmax(jnp.take(
            train.apply({"params": p}, padded)[0], last, axis=0))

    def oracle(prompt, max_new, eos_id=None):
        seq, out = list(prompt), []
        for _ in range(max_new):
            padded = np.zeros((1, _KW["max_seq_len"]), np.int32)
            padded[0, :len(seq)] = seq
            nxt = int(onext(params, jnp.asarray(padded),
                            jnp.asarray(len(seq) - 1)))
            out.append(nxt)
            seq.append(nxt)
            if eos_id is not None and nxt == eos_id:
                break
        return out

    return SimpleNamespace(paged=paged, params=params,
                           draft_params=draft_params, oracle=oracle)


def _stack(gpt, *, max_batch=4, max_queue=32, buckets=(16,),
           deadline_ms=30000.0, prefix=True, kv_crc=False,
           draft=None, spec_k=3, eos_id=None, warmup=True):
    ex = ShardedExecutor(gpt.paged, gpt.params, max_batch=max_batch,
                         max_len=_KW["max_seq_len"])
    q = AdmissionQueue(max_queue=max_queue,
                       default_deadline_ms=deadline_ms)
    b = ContinuousBatcher(ex, q, buckets=buckets, prefix_cache=prefix,
                          kv_crc=kv_crc, draft_executor=draft,
                          spec_k=spec_k, eos_id=eos_id)
    if warmup:
        b.warmup()
    return ex, q, b


def _draft_ex(gpt, params, max_batch=4, max_len=_KW["max_seq_len"]):
    """An ordinary executor on the default pool (sized by the executor
    for its own max_batch, hence a model of its own)."""
    return ShardedExecutor(GPT(GPTConfig(decode=True, **_KW)), params,
                           max_batch=max_batch, max_len=max_len,
                           role="draft")


# ---------------------------------------------------------------------------
# block allocator
# ---------------------------------------------------------------------------

class TestBlockPool:
    def test_alloc_free_refcount_never_hand_out_in_use(self):
        pool = BlockPool(4, 8)
        blocks = [pool.alloc() for _ in range(4)]
        assert sorted(blocks) == [0, 1, 2, 3]
        assert pool.alloc() is None            # exhausted
        assert pool.in_use() == 4 and pool.occupancy() == 1.0
        # a shared block survives its first owner's release
        pool.incref(blocks[0])
        assert not pool.decref(blocks[0])      # still referenced
        assert pool.alloc() is None            # NOT handed out again
        assert pool.decref(blocks[0])          # last ref -> freed
        got = pool.alloc()
        assert got == blocks[0]                # LIFO reuse
        pool2 = BlockPool(2, 4)
        a = pool2.alloc()
        pool2.decref(a)
        with pytest.raises(ValueError):        # double free
            pool2.decref(a)
        with pytest.raises(ValueError):        # sharing a dead block
            pool2.incref(a)

    def test_every_alloc_is_refcount_zero(self):
        """Randomized churn: the free list never yields a block whose
        refcount is nonzero (the alloc() assertion is the real gate;
        this drives it through interleaved share/release)."""
        rng = np.random.RandomState(3)
        pool = BlockPool(8, 4)
        live = []
        for _ in range(500):
            op = rng.randint(3)
            if op == 0:
                blk = pool.alloc()
                if blk is not None:
                    live.append(blk)
            elif op == 1 and live:
                blk = live[rng.randint(len(live))]
                pool.incref(blk)
                live.append(blk)               # one extra release due
            elif op == 2 and live:
                blk = live.pop(rng.randint(len(live)))
                pool.decref(blk)
        assert pool.in_use() + pool.free_count() == 8

    def test_block_crc_ledger_stream_reset_clone(self):
        pool = BlockPool(4, 8)
        a, b = pool.alloc(), pool.alloc()
        pool.crc_stream(a, [b"ab", b"12"], 2)
        pool.crc_stream(a, [b"cd", b"34"], 4)
        assert pool.crc_filled(a) == 4
        assert pool.crc_check(a, [b"abcd", b"1234"])
        assert not pool.crc_check(a, [b"abcX", b"1234"])
        pool.crc_clone(a, b)                   # CoW bookkeeping
        assert pool.crc_check(b, [b"abcd", b"1234"])
        pool.crc_reset(a, [b"zz", b"99"], 2)   # rollback recompute
        assert pool.crc_check(a, [b"zz", b"99"])
        pool.decref(a)
        assert pool.crc_filled(a) == 0         # ledger dies with block

    def test_paged_cache_reservation_gate(self):
        pool = BlockPool(8, 4)
        kv = PagedKVCache(2, 4, pool)
        assert kv.blocks_needed(1) == 1 and kv.blocks_needed(9) == 3
        assert kv.can_admit(5)
        r0 = kv.alloc_row(5)                   # reserve 5 of 8
        assert kv.available_blocks() == 3
        assert not kv.can_admit(4)             # would starve row 0
        assert kv.can_admit(3)
        kv.ensure(r0, 9)                       # 3 blocks materialize
        assert pool.in_use() == 3 and kv.reserved[r0] == 2
        kv.free_row(r0)
        assert pool.in_use() == 0 and kv.reserved_total() == 0
        with pytest.raises(ValueError):
            kv.free_row(r0)

    def test_reserved_append_never_starves(self):
        """The admission invariant: growth the gate admitted always
        finds a block, even when the free list momentarily drains."""
        pool = BlockPool(2, 4)
        kv = PagedKVCache(2, 2, pool)
        r0 = kv.alloc_row(2)
        assert not kv.can_admit(1)             # both blocks spoken for
        assert [pool.refcount[b] for b in kv.ensure(r0, 8)] == [1, 1]
        with pytest.raises(RuntimeError):      # UNreserved growth trips
            kv.append_block(r0)


# ---------------------------------------------------------------------------
# paged decode correctness
# ---------------------------------------------------------------------------

class TestPagedDecode:
    def test_row_and_block_reuse_matches_oracle(self, gpt):
        """8 requests over 4 rows: the second wave recycles rows AND
        pool blocks still holding the first wave's bytes."""
        ex, q, b = _stack(gpt, prefix=False)
        rng = np.random.RandomState(1)
        prompts = [list(rng.randint(0, 64, rng.randint(2, 9)))
                   for _ in range(8)]
        handles = [q.submit(p, max_new_tokens=6) for p in prompts]
        b.run()
        assert b.kv.generation.sum() >= 5      # rows actually recycled
        assert b.kv.pool.frees > 0             # blocks returned + reused
        for p, h in zip(prompts, handles):
            assert h.status == "ok"
            assert h.tokens == gpt.oracle(p, 6)
        assert b.kv.pool.in_use() == 0         # nothing leaked

    def test_llama_gqa_paged_matches_oracle(self):
        kw = dict(vocab_size=64, num_layers=2, num_heads=4,
                  num_kv_heads=2, head_dim=8, max_seq_len=32,
                  dtype=jnp.float32, attention_impl="reference")
        train = Llama(LlamaConfig(**kw))
        dec = Llama(LlamaConfig(decode=True, **kw, kv_block_size=4,
                                kv_pool_blocks=24))
        params = train.init(jax.random.PRNGKey(0),
                            jnp.zeros((2, 8), jnp.int32))["params"]
        ex = ShardedExecutor(dec, params, max_batch=2, max_len=32)
        q = AdmissionQueue(max_queue=8)
        b = ContinuousBatcher(ex, q, buckets=(8,), prefix_cache=True)
        rng = np.random.RandomState(2)
        prompts = [list(rng.randint(0, 64, 5)) for _ in range(3)]
        handles = [q.submit(p, max_new_tokens=4) for p in prompts]
        b.run()

        @jax.jit
        def onext(p, padded, last):
            return jnp.argmax(jnp.take(
                train.apply({"params": p}, padded)[0], last, axis=0))

        for p, h in zip(prompts, handles):
            seq, want = list(p), []
            for _ in range(4):
                padded = np.zeros((1, 32), np.int32)
                padded[0, :len(seq)] = seq
                nxt = int(onext(params, jnp.asarray(padded),
                                jnp.asarray(len(seq) - 1)))
                want.append(nxt)
                seq.append(nxt)
            assert h.status == "ok" and h.tokens == want

    def test_jit_cache_flat_across_paged_churn(self, gpt):
        """Paged + speculative: post-warmup churn (mixed lengths
        joining mid-flight, rows and blocks recycling, CoW copies)
        adds zero compiled programs."""
        draft = _draft_ex(gpt, gpt.params, max_batch=3)
        ex, q, b = _stack(gpt, max_batch=3, draft=draft, spec_k=2)
        baseline = ex.jit_cache_size()
        dbase = draft.jit_cache_size()
        rng = np.random.RandomState(4)
        handles = [q.submit(list(rng.randint(0, 64, n)), max_new_tokens=m)
                   for n, m in ((2, 9), (7, 3), (5, 5))]
        for i in range(40):
            alive = b.step()
            if i in (2, 5, 9):
                handles.append(q.submit(
                    list(rng.randint(0, 64, rng.randint(2, 16))),
                    max_new_tokens=int(rng.randint(1, 8))))
            if not alive and q.depth() == 0:
                break
        b.run()
        assert all(h.status == "ok" for h in handles)
        assert ex.jit_cache_size() == baseline
        assert draft.jit_cache_size() == dbase


# ---------------------------------------------------------------------------
# radix prefix cache
# ---------------------------------------------------------------------------

class TestPrefixCacheUnit:
    def _cache(self, blocks=16, bs=4):
        pool = BlockPool(blocks, bs)
        return pool, RadixPrefixCache(pool)

    def _publish(self, pool, cache, prompt):
        """Simulate a prefill owner: allocate that prompt's full
        blocks, insert, then retire the owner (tree keeps its refs)."""
        n_full = len(prompt) // pool.block_size
        blks = [pool.alloc() for _ in range(n_full)]
        cache.insert(prompt, blks)
        for b in blks:
            pool.decref(b)
        return blks

    def test_match_refcounts_and_release(self):
        pool, cache = self._cache()
        blks = self._publish(pool, cache, list(range(12)))
        assert len(cache) == 3
        full, partial, m = cache.match(list(range(12)) + [50])
        assert m == 12 and partial is None and full == blks
        assert all(pool.refcount[b] == 2 for b in full)  # tree + caller
        cache.release(full)
        assert all(pool.refcount[b] == 1 for b in full)
        # a mid-block divergence pins the partial source temporarily
        full, partial, m = cache.match(list(range(10)) + [50, 51])
        assert len(full) == 2 and partial == (blks[2], 2) and m == 10
        cache.release(full + [partial[0]])

    def test_match_caps_at_prompt_minus_one(self):
        """At least one prompt token must be prefilled (the request
        needs a last-logit to sample from)."""
        pool, cache = self._cache()
        blks = self._publish(pool, cache, list(range(8)))
        # the prompt IS the cached run: a full match would leave zero
        # tokens to prefill, so the 2nd block may only match partially
        full, partial, m = cache.match(list(range(8)))
        assert m == 7 and full == [blks[0]]
        assert partial == (blks[1], 3)
        cache.release(full + [partial[0]])

    def test_lru_eviction_leaves_first_and_pinned_paths_survive(self):
        pool, cache = self._cache(blocks=8)
        a = self._publish(pool, cache, [1, 2, 3, 4, 5, 6, 7, 8])
        b = self._publish(pool, cache, [9, 10, 11, 12])
        # touch the [1..8] path so [9..12] is LRU
        full, partial, _ = cache.match([1, 2, 3, 4, 5, 6, 7, 8, 0])
        cache.release(full + ([partial[0]] if partial else []))
        assert cache.evictable_blocks() == 3
        assert cache.evict(1) == 1
        assert pool.refcount[b[0]] == 0        # the LRU leaf died
        # pin [1..8]'s leaf: its whole path becomes unevictable
        pool.incref(a[1])
        assert cache.evictable_blocks() == 0
        assert cache.evict(4) == 0
        pool.decref(a[1])
        assert cache.evict(4) == 2             # cascades up the path
        assert len(cache) == 0

    def test_flush_returns_tree_refs_only(self):
        pool, cache = self._cache()
        blks = self._publish(pool, cache, list(range(8)))
        pool.incref(blks[0])                   # a live sequence shares
        assert cache.flush() == 2
        assert len(cache) == 0
        assert pool.refcount[blks[0]] == 1     # survives under owner
        assert pool.refcount[blks[1]] == 0


class TestPrefixSharing:
    def test_shared_system_prompt_bit_identical_and_counted(self, gpt):
        """Wave 1 publishes the system prompt's blocks; wave 2 reuses
        them — same tokens as the oracle, tokens_saved > 0, and the
        pool holds ONE copy of the shared run."""
        ex, q, b = _stack(gpt, max_batch=4, buckets=(16,))
        rng = np.random.RandomState(5)
        system = list(rng.randint(0, 64, 8))   # 2 full blocks
        h0 = q.submit(system + [1, 2], max_new_tokens=5)
        b.run()                                # publish
        assert b.prefix.misses >= 1
        prompts = [system + list(rng.randint(0, 64, k)) for k in (2, 3)]
        handles = [q.submit(p, max_new_tokens=5) for p in prompts]
        b.run()
        assert h0.tokens == gpt.oracle(system + [1, 2], 5)
        for p, h in zip(prompts, handles):
            assert h.status == "ok" and h.tokens == gpt.oracle(p, 5)
        assert b.prefix.hits == 2
        assert b.prefix.tokens_saved == 16     # 2 blocks x 2 requests
        # the tree holds one copy of the shared run, still resident
        assert b.kv.pool.in_use() == len(b.prefix)

    def test_cow_divergence_mid_block_never_mutates_source(self, gpt):
        """A prompt diverging INSIDE a cached block copies it (CoW) and
        overwrites only its own copy: the original owner's prompt still
        matches byte-identically afterwards."""
        ex, q, b = _stack(gpt, max_batch=4, buckets=(16,))
        base = [3, 1, 4, 1, 5, 9, 2, 6, 5, 3, 5, 8]   # 3 full blocks
        h0 = q.submit(base, max_new_tokens=4)
        b.run()
        saved0 = b.prefix.tokens_saved
        # diverges at position 10 — inside the 3rd block
        fork = base[:10] + [60, 61]
        h1 = q.submit(fork, max_new_tokens=4)
        b.run()
        assert b.prefix.tokens_saved - saved0 == 10   # 8 full + 2 CoW
        # the source block was copied, not written: re-serving the
        # ORIGINAL prompt from cache still matches the oracle
        h2 = q.submit(base + [7], max_new_tokens=4)
        b.run()
        assert h0.tokens == gpt.oracle(base, 4)
        assert h1.tokens == gpt.oracle(fork, 4)
        assert h2.tokens == gpt.oracle(base + [7], 4)

    def test_weight_swap_flushes_prefix_cache(self, gpt):
        ex, q, b = _stack(gpt, max_batch=2, buckets=(16,))
        q.submit([1, 2, 3, 4, 5, 6, 7, 8, 9], max_new_tokens=2)
        b.run()
        assert len(b.prefix) > 0
        ex.swap_params(gpt.params, version=2)  # same values, new version
        q.submit([1, 2, 3], max_new_tokens=1)
        b.run()
        # flushed BEFORE the new admission could match, then the new
        # prompt re-published under v2
        assert b._prefix_version == 2
        assert b.prefix.hits == 0

    def test_router_requested_flush_runs_before_admission(self, gpt):
        ex, q, b = _stack(gpt, max_batch=2, buckets=(16,))
        q.submit(list(range(1, 10)), max_new_tokens=1)
        b.run()
        assert len(b.prefix) > 0
        b.request_prefix_flush()
        q.submit(list(range(1, 10)), max_new_tokens=1)
        b.run()
        assert b.prefix.hits == 0              # the re-walk was a miss


# ---------------------------------------------------------------------------
# speculative decoding
# ---------------------------------------------------------------------------

class TestSpeculative:
    def test_perfect_drafter_bit_identical_and_step_win(self, gpt):
        """Drafter == target: every proposal accepted; emitted stream
        identical to plain greedy; < 0.7 target steps per token (the
        machine-independent win the bench gate asserts)."""
        draft = _draft_ex(gpt, gpt.params)
        ex, q, b = _stack(gpt, draft=draft, spec_k=3, prefix=False)
        rng = np.random.RandomState(6)
        prompts = [list(rng.randint(0, 64, rng.randint(2, 9)))
                   for _ in range(6)]
        handles = [q.submit(p, max_new_tokens=8) for p in prompts]
        b.run()
        for p, h in zip(prompts, handles):
            assert h.status == "ok" and h.tokens == gpt.oracle(p, 8)
        assert b.gen_tokens > 0
        assert b.gen_steps / b.gen_tokens < 0.7

    def test_rejecting_drafter_rolls_back_bit_identically(self, gpt):
        """A drafter with DIFFERENT params disagrees with the target
        almost everywhere: nearly every draft is rejected, the write-
        ahead is rolled back, and the emitted stream is still exactly
        the target's greedy stream."""
        draft = _draft_ex(gpt, gpt.draft_params)
        ex, q, b = _stack(gpt, draft=draft, spec_k=3, kv_crc=True)
        rng = np.random.RandomState(7)
        prompts = [list(rng.randint(0, 64, rng.randint(2, 9)))
                   for _ in range(6)]
        handles = [q.submit(p, max_new_tokens=7) for p in prompts]
        b.run()
        for p, h in zip(prompts, handles):
            assert h.status == "ok" and h.tokens == gpt.oracle(p, 7)
        # rollback actually happened: more target steps than a
        # full-accept run would need (7 tokens needs >= 2 verify steps
        # even at full accept; rejection pushes it near 1 step/token)
        assert b.gen_steps / b.gen_tokens > 0.5

    def test_eos_stop_positions_identical(self, gpt):
        """EOS inside an ACCEPTED draft run must stop the stream at
        exactly the position plain greedy decode stops."""
        rng = np.random.RandomState(8)
        prompts = [list(rng.randint(0, 64, 5)) for _ in range(4)]
        # pick an eos that actually occurs mid-stream for some prompt
        eos = gpt.oracle(prompts[0], 8)[2]
        want = [gpt.oracle(p, 8, eos_id=eos) for p in prompts]
        draft = _draft_ex(gpt, gpt.params)
        ex, q, b = _stack(gpt, draft=draft, spec_k=3, prefix=False,
                          eos_id=eos)
        handles = [q.submit(p, max_new_tokens=8) for p in prompts]
        b.run()
        for w, h in zip(want, handles):
            assert h.status == "ok" and h.tokens == w

    def test_drafter_reads_its_pool_through_the_identity_table(self, gpt):
        """The drafter is an ordinary executor: the batcher hands it one
        constant table, row r owning blocks [r * n, (r + 1) * n). A
        drafter context of 44 leaves its last block of 16 partial;
        sequences grow past it (those rows fall back to plain decode)
        and the stream stays the target's greedy one."""
        draft = _draft_ex(gpt, gpt.params, max_len=44)
        assert (draft.blocks_per_seq, draft.kv_pool_blocks) == (3, 12)
        ex, q, b = _stack(gpt, draft=draft, spec_k=3, prefix=False)
        assert b._draft_tables.tolist() == [
            [0, 1, 2], [3, 4, 5], [6, 7, 8], [9, 10, 11]]
        rng = np.random.RandomState(12)
        prompts = [list(rng.randint(0, 64, rng.randint(4, 12)))
                   for _ in range(6)]
        handles = [q.submit(p, max_new_tokens=36) for p in prompts]
        b.run()
        for p, h in zip(prompts, handles):
            assert h.status == "ok" and h.tokens == gpt.oracle(p, 36)
        assert b.gen_steps < b.gen_tokens      # speculation did run

    def test_spec_with_prefix_and_llama_gqa_target(self):
        """The ISSUE pairing: GPT drafter proposing, Llama-GQA target
        verifying — paged + prefix-shared + speculative all on, output
        bit-identical to the Llama-only greedy oracle."""
        kw = dict(vocab_size=64, num_layers=2, num_heads=4,
                  num_kv_heads=2, head_dim=8, max_seq_len=48,
                  dtype=jnp.float32, attention_impl="reference")
        train = Llama(LlamaConfig(**kw))
        dec = Llama(LlamaConfig(decode=True, **kw, kv_block_size=4,
                                kv_pool_blocks=32))
        params = train.init(jax.random.PRNGKey(0),
                            jnp.zeros((2, 8), jnp.int32))["params"]
        gkw = dict(vocab_size=64, num_layers=1, num_heads=2, head_dim=8,
                   max_seq_len=48, dtype=jnp.float32,
                   attention_impl="reference")
        gdraft = GPT(GPTConfig(decode=True, **gkw))
        gparams = GPT(GPTConfig(**gkw)).init(
            jax.random.PRNGKey(3), jnp.zeros((2, 8), jnp.int32))["params"]
        ex = ShardedExecutor(dec, params, max_batch=2, max_len=48)
        draft = ShardedExecutor(gdraft, gparams, max_batch=2,
                                max_len=48, role="draft")
        q = AdmissionQueue(max_queue=8)
        b = ContinuousBatcher(ex, q, buckets=(16,), prefix_cache=True,
                              draft_executor=draft, spec_k=2,
                              kv_crc=True)
        rng = np.random.RandomState(11)
        system = list(rng.randint(0, 64, 8))
        prompts = [system + list(rng.randint(0, 64, 3))
                   for _ in range(4)]
        handles = [q.submit(p, max_new_tokens=5) for p in prompts]
        b.run()

        @jax.jit
        def onext(p, padded, last):
            return jnp.argmax(jnp.take(
                train.apply({"params": p}, padded)[0], last, axis=0))

        for p, h in zip(prompts, handles):
            seq, want = list(p), []
            for _ in range(5):
                padded = np.zeros((1, 48), np.int32)
                padded[0, :len(seq)] = seq
                nxt = int(onext(params, jnp.asarray(padded),
                                jnp.asarray(len(seq) - 1)))
                want.append(nxt)
                seq.append(nxt)
            assert h.status == "ok" and h.tokens == want
        assert b.prefix.hits >= 1              # sharing + spec compose


# ---------------------------------------------------------------------------
# block release discipline (expiry / shed / overload)
# ---------------------------------------------------------------------------

class TestBlockRelease:
    def test_zero_leaked_blocks_after_overload_burst(self, gpt):
        """The PR 2 slot-free-on-expiry bar re-targeted at blocks: a
        burst that triggers shed + deadline expiry mid-decode leaves
        ZERO blocks allocated once drained (prefix cache off so any
        resident block would be a leak)."""
        ex, q, b = _stack(gpt, max_batch=2, max_queue=4, prefix=False,
                          deadline_ms=5.0)
        rng = np.random.RandomState(12)
        handles, shed = [], 0
        for _ in range(12):
            try:
                handles.append(q.submit(list(rng.randint(0, 64, 6)),
                                        max_new_tokens=40))
            except Rejected:
                shed += 1
        b.run()
        assert shed > 0
        assert any(h.status == "expired" for h in handles)
        assert b.kv.live() == 0
        assert b.kv.pool.in_use() == 0         # zero leaked blocks
        assert b.kv.reserved_total() == 0
        # capacity actually restored: a fresh request completes
        h2 = q.submit(list(range(4)), max_new_tokens=2,
                      deadline_ms=30000.0)
        b.run()
        assert h2.status == "ok" and len(h2.tokens) == 2

    def test_expiry_decrements_prefix_refcounts_same_iteration(self, gpt):
        """An expired sequence holding SHARED prefix blocks returns its
        references; the tree's own refcount keeps the run cached."""
        ex, q, b = _stack(gpt, max_batch=2, buckets=(16,))
        system = list(range(1, 9))             # 2 full blocks
        q.submit(system + [9], max_new_tokens=2)
        b.run()                                # publish
        resident = b.kv.pool.in_use()
        h = q.submit(system + [10], max_new_tokens=40, deadline_ms=5.0)
        b.run()
        assert h.status == "expired"
        assert b.kv.live() == 0
        # only the tree's references remain — the expired sequence's
        # shares and private blocks all came back
        assert b.kv.pool.in_use() == resident == len(b.prefix)

    def test_blocked_reprefill_is_not_queue_jumped(self, gpt):
        """A corrupted-and-reset request waiting in the reprefill lane
        is AHEAD of the queue: while its block budget doesn't fit, no
        queued request may admit past it and eat the blocks it waits
        for (it would starve to its deadline parked there)."""
        ex, q, b = _stack(gpt, max_batch=4, buckets=(16,), prefix=False)
        hogs = [q.submit(list(np.random.RandomState(s).randint(0, 64, 12)),
                         max_new_tokens=30) for s in (30, 31)]
        for _ in range(2):
            b.step()
        # park a big request in the reprefill lane (what a detected KV
        # corruption does), too big for the blocks currently free
        big = q.submit(list(np.random.RandomState(32).randint(0, 64, 12)),
                       max_new_tokens=30)
        b._reprefill.append(q.pop(1)[0])
        small = q.submit([1, 2, 3], max_new_tokens=1)
        b.step()
        assert b._reprefill                     # still blocked...
        assert q.depth() == 1                   # ...and small NOT past it
        b.run()                                 # hogs retire -> both go
        assert big.status == "ok" and small.status == "ok"
        assert all(h.status == "ok" for h in hogs)
        assert b.kv.pool.in_use() == 0

    def test_failed_admission_releases_matched_plan(self, gpt):
        """A prefix match whose admission falls through (no free
        blocks) must drop its pinned references — the queue-head
        request admits later instead of deadlocking the pool."""
        ex, q, b = _stack(gpt, max_batch=4, buckets=(16,))
        system = list(range(1, 13))            # 3 full blocks
        q.submit(system, max_new_tokens=1)
        b.run()
        # occupy nearly the whole pool with held rows (don't drain)
        hogs = [q.submit(list(np.random.RandomState(s).randint(0, 64, 12)),
                         max_new_tokens=30) for s in (20, 21, 22)]
        for _ in range(3):
            b.step()
        refc0 = int(b.kv.pool.refcount.sum())
        h = q.submit(system + [5], max_new_tokens=30)
        b.step()                               # match pinned + released
        assert int(b.kv.pool.refcount.sum()) >= refc0  # hog growth ok
        b.run()                                # hogs finish, h admits
        assert h.status == "ok"
        assert all(x.status == "ok" for x in hogs)
        assert b.kv.live() == 0


# ---------------------------------------------------------------------------
# chaos: serve.kv corrupt on a pool BLOCK
# ---------------------------------------------------------------------------

class TestPagedKVChaos:
    def test_block_corrupt_caught_by_per_block_crc(self, gpt):
        """The serve.kv fault flips a real bit inside a pool block; the
        per-block crc catches it at verify-on-read, the sequence
        re-prefills, and the client still gets oracle tokens."""
        plan = ChaosPlan.from_dict({"faults": [
            {"rank": 0, "site": "serve.kv", "kind": "corrupt",
             "at": 3}]})
        inject.install(plan, rank=0)
        ex, q, b = _stack(gpt, max_batch=2, kv_crc=True, prefix=True)
        rng = np.random.RandomState(13)
        prompts = [list(rng.randint(0, 64, 6)) for _ in range(2)]
        handles = [q.submit(p, max_new_tokens=8) for p in prompts]
        b.run()
        assert b.kv_corruptions_injected == 1
        assert b.kv_corruptions_detected >= 1
        assert b.kv_reprefills >= 1
        for p, h in zip(prompts, handles):
            assert h.status == "ok" and h.tokens == gpt.oracle(p, 8)

    def test_shared_prefix_block_corrupt_flushes_cache(self, gpt):
        """Corruption landing in a SHARED prefix block must not be
        re-matched by the re-prefill: detection flushes the tree."""
        plan = ChaosPlan.from_dict({"faults": [
            {"rank": 0, "site": "serve.kv", "kind": "corrupt",
             "at": 6, "slot": 0}]})
        inject.install(plan, rank=0)
        ex, q, b = _stack(gpt, max_batch=2, kv_crc=True, prefix=True,
                          buckets=(16,))
        system = list(range(1, 10))
        h0 = q.submit(system, max_new_tokens=2)
        b.run()
        h1 = q.submit(system + [3], max_new_tokens=8)
        b.run()
        assert b.kv_corruptions_injected == 1
        assert b.kv_corruptions_detected >= 1
        assert h0.status == "ok" and h1.status == "ok"
        assert h1.tokens == gpt.oracle(system + [3], 8)


# ---------------------------------------------------------------------------
# fleet re-admission: the KV side of the weight gate
# ---------------------------------------------------------------------------

class TestFleetReadmissionFlush:
    def _paged_fleet(self, gpt, subscribers=None):
        from horovod_tpu.serve import FleetRouter, Replica
        reps = [
            Replica(i,
                    ShardedExecutor(gpt.paged, gpt.params, max_batch=4,
                                    max_len=_KW["max_seq_len"],
                                    replica_id=i),
                    buckets=(16,), max_queue=32, prefix_cache=True,
                    subscriber=(subscribers or {}).get(i))
            for i in range(2)]
        router = FleetRouter(reps, interval_s=0.1, suspect_s=0.5)
        return router, reps

    def _eject_and_recover(self, router, reps, events, mid_eject=None):
        """Populate replica 0's prefix cache, freeze its heartbeat so
        the router ejects it (slow path — the batcher and its prefix
        cache SURVIVE), run ``mid_eject``, unfreeze, wait for
        re-admission."""
        system = list(range(1, 10))
        deadline = time.monotonic() + 30
        if reps[0].subscriber is not None:
            # let the initial v1 adoption (and its version-fence flush)
            # land first, or it would wipe the tree we populate below
            while any(r.batcher._prefix_version is None
                      or r.batcher._prefix_version
                      != r.executor.params_version for r in reps):
                assert time.monotonic() < deadline
                time.sleep(0.02)
        while len(reps[0].batcher.prefix) == 0:
            assert time.monotonic() < deadline
            router.submit(system + [int(time.monotonic() * 997) % 60],
                          max_new_tokens=2).wait(10)
        reps[0].batcher.heartbeat = lambda: None   # wedge heartbeats
        while not any(e["event"] == "eject" and e["replica"] == 0
                      for e in events):
            assert time.monotonic() < deadline, events
            time.sleep(0.02)
        assert len(reps[0].batcher.prefix) > 0     # survived ejection
        if mid_eject is not None:
            mid_eject()
        reps[0].batcher.heartbeat = reps[0]._heartbeat
        while not any(e["event"] == "readmit" and e["replica"] == 0
                      for e in events):
            assert time.monotonic() < deadline, events
            time.sleep(0.02)
        # the flush lands on the scheduler thread's next iteration
        while len(reps[0].batcher.prefix) > 0:
            assert time.monotonic() < deadline, \
                "recovered replica rejoined with its stale prefix cache"
            time.sleep(0.02)

    def test_readmitted_replica_prefix_cache_flushed(self, gpt):
        """A slow-but-alive replica keeps its batcher across ejection;
        re-admission must flush its prefix cache even when NO weight
        version changed while it was out (it cannot know what it
        missed — conservative gate)."""
        router, reps = self._paged_fleet(gpt)
        events = []
        router.add_listener(lambda ev: events.append(ev))
        router.start()
        try:
            self._eject_and_recover(router, reps, events)
            h = router.submit(list(range(1, 10)), max_new_tokens=2)
            assert h.wait(20) and h.status == "ok"
        finally:
            router.close()

    def test_v2_published_mid_eject_never_serves_v1_prefix(self, gpt):
        """The ISSUE regression: weights move to v2 while the replica
        is ejected; on re-admission its v1 prefix runs are flushed
        BEFORE any prompt can match them, and it serves v2."""
        from horovod_tpu.native.store import StoreServer
        from horovod_tpu.redist.stream import (WeightPublisher,
                                               WeightSubscriber)
        with StoreServer() as srv:
            pub = WeightPublisher("kvgate", kv_addr="127.0.0.1",
                                  kv_port=srv.port, resume_timeout=0.05)
            pub.publish(gpt.params)                    # v1
            subs = {i: WeightSubscriber("kvgate", kv_addr="127.0.0.1",
                                        kv_port=srv.port,
                                        template=gpt.params)
                    for i in range(2)}
            router, reps = self._paged_fleet(gpt, subscribers=subs)
            events = []
            router.add_listener(lambda ev: events.append(ev))
            router.start()
            try:
                self._eject_and_recover(
                    router, reps, events,
                    mid_eject=lambda: pub.publish(gpt.params))  # v2
                assert reps[0].executor.params_version == 2
                # same values under v2, so service stays bit-identical
                h = router.submit(list(range(1, 10)), max_new_tokens=3)
                assert h.wait(20) and h.status == "ok"
                assert h.tokens == gpt.oracle(list(range(1, 10)), 3)
            finally:
                router.close()
                pub.close()
                for s in subs.values():
                    s.close()


# ---------------------------------------------------------------------------
# config knobs
# ---------------------------------------------------------------------------

class TestPagedConfigKnobs:
    def test_defaults(self):
        c = Config()
        c.validate()
        assert not hasattr(c, "serve_kv_block")   # the model config's
        assert GPTConfig(decode=True, **_KW).kv_block_size == 16
        assert c.serve_prefix_cache is True
        assert c.serve_spec_k == 3

    def test_env_parsing(self, monkeypatch):
        monkeypatch.setenv("HOROVOD_SERVE_PREFIX_CACHE", "0")
        monkeypatch.setenv("HOROVOD_SERVE_SPEC_K", "5")
        c = Config.from_env()
        assert c.serve_prefix_cache is False
        assert c.serve_spec_k == 5

    @pytest.mark.parametrize("name,val", [
        ("HOROVOD_SERVE_SPEC_K", "-1"),
        ("HOROVOD_SERVE_SPEC_K", "k"),
        ("HOROVOD_SERVE_SPEC_K", "100"),
    ])
    def test_bad_env_fails_fast(self, monkeypatch, name, val):
        monkeypatch.setenv(name, val)
        with pytest.raises(ValueError):
            Config.from_env()

    def test_model_config_validation(self):
        with pytest.raises(ValueError):        # a pool is decode-only
            GPTConfig(kv_block_size=4, kv_pool_blocks=8, **_KW)
        with pytest.raises(ValueError):        # a block holds a token
            GPTConfig(decode=True, kv_block_size=0, **_KW)
        with pytest.raises(ValueError):
            LlamaConfig(decode=True, kv_pool_blocks=-1,
                        vocab_size=64, num_layers=1, num_heads=2,
                        head_dim=8, max_seq_len=32)
        # no pool size is no longer another layout: the executor's call
        assert GPTConfig(decode=True, kv_block_size=4,
                         **_KW).kv_pool_blocks == 0

    def test_executor_rejects_undersized_pool(self, gpt):
        small = GPT(GPTConfig(decode=True, **_KW, kv_block_size=4,
                              kv_pool_blocks=4))
        with pytest.raises(ValueError):        # can't hold one max_len seq
            ShardedExecutor(small, gpt.params, max_batch=2, max_len=48)

    def test_draft_executor_must_hold_its_rows_and_match(self, gpt):
        ex = ShardedExecutor(gpt.paged, gpt.params, max_batch=2,
                             max_len=48)
        q = AdmissionQueue(max_queue=4)

        def refused(draft):
            with pytest.raises(ValueError):
                ContinuousBatcher(ex, q, buckets=(8,),
                                  draft_executor=draft, spec_k=2,
                                  prefix_cache=False)

        # a pool too small for one fixed run a row (2 x 12 blocks of 4)
        refused(ShardedExecutor(
            GPT(GPTConfig(decode=True, **_KW, kv_block_size=4,
                          kv_pool_blocks=16)),
            gpt.params, max_batch=2, max_len=48, role="draft"))
        refused(_draft_ex(gpt, gpt.params, max_batch=3))   # rows pair 1:1
        refused(_draft_ex(gpt, gpt.params, max_batch=2,
                          max_len=6))              # context < the bucket
        ContinuousBatcher(ex, q, buckets=(8,), spec_k=2,
                          draft_executor=_draft_ex(gpt, gpt.params, 2),
                          prefix_cache=False)


# ---------------------------------------------------------------------------
# the local flight recorder: spans and token stamps where they happen
# ---------------------------------------------------------------------------

def _span_scenario(gpt, scenario):
    """(executor, queue, batcher, draft executor or None, submit kwargs)
    for one way of generating tokens."""
    draft, kw, sub = None, {}, {}
    if scenario == "speculative":
        draft = _draft_ex(gpt, gpt.params)
        kw = dict(draft=draft, spec_k=3, prefix=False)
    elif scenario == "speculative_rejecting":
        draft = _draft_ex(gpt, gpt.draft_params)
        kw = dict(draft=draft, spec_k=3, kv_crc=True)
    elif scenario == "reprefill_after_kv_corruption":
        inject.install(ChaosPlan.from_dict({"faults": [
            {"rank": 0, "site": "serve.kv", "kind": "corrupt",
             "at": 3}]}), rank=0)
        kw = dict(max_batch=2, kv_crc=True)
    elif scenario == "router_traced":
        sub = {"trace": True}
    ex, q, b = _stack(gpt, **kw)
    return ex, q, b, draft, sub


def _span_run(gpt, scenario, n=5, new=7):
    """Serve `n` requests one way; the handles and every span the
    process recorded meanwhile (warm-up included), in a recorder of the
    run's own so that no other test's spans are among them."""
    rec, old = SpanRecorder(), spans_mod._recorder
    spans_mod._recorder = rec
    try:
        ex, q, b, draft, sub = _span_scenario(gpt, scenario)
        rng = np.random.RandomState(21)
        prompts = [list(rng.randint(0, 64, rng.randint(2, 9)))
                   for _ in range(n)]
        handles = [q.submit(
            p, max_new_tokens=new,
            trace=TraceContext.mint().to_wire() if sub else None)
            for p in prompts]
        b.run()
    finally:
        inject.uninstall()
        spans_mod._recorder = old
    assert get_recorder() is not rec
    for p, h in zip(prompts, handles):
        assert h.status == "ok" and h.tokens == gpt.oracle(p, new)
    return SimpleNamespace(
        ex=ex, q=q, b=b, draft=draft, handles=handles, scenario=scenario,
        rec=rec, spans=rec.between(0.0, float("inf")))


class TestLocalSpans:
    @pytest.fixture(scope="class", params=[
        "plain", "speculative", "speculative_rejecting",
        "reprefill_after_kv_corruption", "router_traced"])
    def r(self, request, gpt):
        return _span_run(gpt, request.param)

    def test_every_finished_request_has_ordered_stamps(self, r):
        if r.scenario == "reprefill_after_kv_corruption":
            assert r.b.kv_reprefills >= 1
        roots = {s.extra["rid"]: s for s in r.spans
                 if s.name == "request"}
        for h in r.handles:
            assert len(h.token_times) == len(h.tokens)
            stamps = [h.t_submit, h.t_admit, h.t_first,
                      *h.token_times, h.t_done]
            assert all(a <= b for a, b in zip(stamps, stamps[1:]))
            assert h.t_first == h.token_times[0]
            root = roots[h.rid]
            assert (root.t0, root.t1) == (h.t_submit, h.t_done)
            # each stamp once: the root carries none of its legs'
            assert root.extra == {"rid": h.rid, "status": "ok"}
            assert h._request is None       # no cycle left behind
            mine = [s for s in r.spans if s.trace == root.trace]
            decode = [s for s in mine if s.name == "decode"][-1]
            assert decode.extra["token_times"] == h.token_times
            assert (decode.t0, decode.extra["tokens"]) == \
                (h.t_first, len(h.tokens))
            wait = [s for s in mine if s.name == "queue_wait"][-1]
            assert (wait.t0, wait.t1) == (h.t_submit, h.t_admit)
            pre = [s for s in mine if s.name == "prefill"][-1]
            assert (pre.t0, pre.t1) == (h.t_admit, h.t_first)

    def test_exec_steps_are_all_recorded(self, r):
        """One `exec_step` span per executor step (warm-up included),
        whose rows add up to the tokens the executor counted."""
        steps = [s for s in r.spans if s.name == "exec_step"]
        executors = [ex for ex in (r.ex, r.draft) if ex is not None]
        assert len(steps) == sum(ex.steps for ex in executors)
        assert sum(s.extra["rows"] for s in steps) == \
            sum(ex.tokens_out for ex in executors)
        legs = {"exec_upload", "exec_dispatch", "exec_readback"}
        by_parent = {}
        for s in r.spans:
            if s.name in legs:
                by_parent.setdefault(s.parent, set()).add(s.name)
        assert all(by_parent[s.span] == legs for s in steps)
        iterations = [s for s in r.spans if s.name == "sched_iteration"]
        assert len(iterations) == r.b.iterations

    def test_children_lie_inside_their_parents(self, r):
        by_id = {s.span: s for s in r.spans}
        children = [s for s in r.spans if s.parent in by_id]
        assert len(children) > len(r.spans) // 2
        for s in children:
            up = by_id[s.parent]
            assert up.t0 <= s.t0 <= s.t1 <= up.t1, (s.name, up.name)
        # the scheduler's phases hang off its iteration, the executor's
        # step off the phase that launched it
        for s in r.spans:
            if s.name in ("sched_retire", "sched_admit", "sched_prefill",
                          "sched_decode"):
                assert by_id[s.parent].name == "sched_iteration"
            if s.name == "exec_step" and s.parent is not None:
                assert by_id[s.parent].name in ("sched_prefill",
                                                "sched_decode")

    def test_router_traced_request_ships_what_it_shipped(self, gpt):
        """`drain` hands the router queue_wait / prefill / decode on
        the WALL clock, without the per-token stamps and without any
        scheduler or executor span."""
        r = _span_run(gpt, "router_traced", n=2)
        roots = {s.extra["rid"]: s for s in r.spans
                 if s.name == "request"}
        for h in r.handles:
            root = roots[h.rid]     # under the router's ids
            assert not root.trace.startswith("rid")
            wire = r.rec.drain(root.trace)
            assert [s["name"] for s in wire] == ["queue_wait", "prefill",
                                                 "decode"]
            assert all(s["parent"] == root.span for s in wire)
            assert wire[2]["extra"] == {"rid": h.rid,
                                        "tokens": len(h.tokens)}
            assert wire[1]["extra"] == {"rid": h.rid}
            assert wire[0]["t0"] == pytest.approx(
                to_wall(h.t_submit), abs=0.05)
            assert abs(wire[2]["t1"] - time.time()) < 60.0
            # one base for the drain: the spans still tile exactly
            assert wire[0]["t1"] == wire[1]["t0"]
            assert wire[1]["t1"] == wire[2]["t0"]

    def test_a_request_that_expires_in_the_queue_is_recorded(self, gpt):
        rec = get_recorder()
        t_rec = rec.now()
        ex, q, b = _stack(gpt, max_batch=2)
        h = q.submit([1, 2, 3], max_new_tokens=4, deadline_ms=1e-3)
        b.run()
        assert h.status == "expired"
        assert (h.t_admit, h.t_first, h.token_times) == (None, None, [])
        assert h.t_submit <= h.t_done
        root = [s for s in rec.between(t_rec, float("inf"))
                if s.name == "request"]
        assert len(root) == 1 and root[0].extra["status"] == "expired"
