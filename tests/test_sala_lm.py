"""`models/sala_lm.py` on the serving path against the plain reference
(`chipbench/families/sala.py`, which imports nothing of the program):
prefill then decode through the cache, LOGITS compared with the
reference's one full forward pass, at tiny widths on the CPU (float32
compute, so a tolerance is rounding of one arithmetic in another order:
2e-4). Contexts cross ``dense_len`` (32 here) into the sparse phase;
the lightning layers' state follows the batch slot."""
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import harness  # noqa: E402
from chipbench import manifest as mf  # noqa: E402
from horovod_tpu.ops import block_sparse, lightning  # noqa: E402
from horovod_tpu.serve import (AdmissionQueue, ContinuousBatcher,  # noqa: E402
                               ShardedExecutor, kv_cache, pool_blocks_for)

FAMILY = mf.load_module("chipbench/families/sala.py")
CONFIG = harness._merge(
    mf.load_json("chipbench/configs/minicpm-sala-l12.json"),
    harness._merge(FAMILY.REHEARSE_CONFIG,
                   {"assumed": {"compute_dtype": "float32"}}))
SHAPE = FAMILY.Shape(CONFIG)      # block 4, top-k 4, window 8, dense_len 32
BLOCK, MAX_LEN, ROWS = 4, 64, 3
TOL = dict(rtol=2e-4, atol=2e-4)


def _serving(kernel, seed=0, rows=ROWS):
    model = FAMILY.serve_model(
        SHAPE, CONFIG, kv_block=BLOCK,
        # every row's whole table at once: the tests deal blocks by hand
        kv_pool_blocks=pool_blocks_for(rows, MAX_LEN, BLOCK, fraction=1.0),
        decode_kernel=kernel)
    key = FAMILY.seed_key(seed)
    params = jax.jit(lambda k: FAMILY.program_params(SHAPE, k))(key)
    ref_w = jax.jit(lambda k: FAMILY.reference_weights(SHAPE, k))(key)
    return model, params, ref_w


_LOGITS_AT = jax.jit(FAMILY.logits_at, static_argnums=(1,))


def _reference_logits(ref_w, seq, where):
    """The reference's logits of `seq` at `where`, the sequence padded
    to one length (causal: the padding is never seen)."""
    tokens = np.zeros((1, MAX_LEN), np.int32)
    tokens[0, :len(seq)] = seq
    return np.asarray(_LOGITS_AT(ref_w, SHAPE, jnp.asarray(tokens),
                                 jnp.asarray(where, jnp.int32)))


class Driver:
    """`model.apply` on an executor's cache with hand-dealt tables:
    logits out, where the executor would only hand back tokens."""

    def __init__(self, kernel, rows=ROWS, seed=0):
        self.model, params, self.ref_w = _serving(kernel, seed, rows)
        self.ex = ShardedExecutor(self.model, params, max_batch=rows,
                                  max_len=MAX_LEN)
        self.model = self.ex.model          # state_rows stamped
        nblk = self.ex.blocks_per_seq
        self.tables = np.arange(rows * nblk, dtype=np.int32).reshape(
            rows, nblk)
        self._apply = jax.jit(self._call)

    def _call(self, cache, tokens, positions, mask, last_idx, tables, slots):
        return self.model.apply(
            {"params": self.ex.params, "cache": cache}, tokens,
            positions=positions, update_mask=mask, logits_idx=last_idx,
            block_tables=tables, state_slots=slots,
            mutable=["cache", "stats"])

    def step(self, tokens, positions, mask, last_idx, slots=None):
        tokens = np.asarray(tokens, np.int32)
        slots = np.arange(len(tokens)) if slots is None else np.asarray(slots)
        logits, vout = self._apply(
            self.ex.cache, jnp.asarray(tokens),
            jnp.asarray(positions, jnp.int32), jnp.asarray(mask, bool),
            jnp.asarray(last_idx, jnp.int32),
            jnp.asarray(self.tables[slots]), jnp.asarray(slots, jnp.int32))
        self.ex.cache = vout["cache"]
        attended = sum(int(x) for x in
                       jax.tree_util.tree_leaves(vout["stats"]))
        return np.asarray(logits[:, 0]), attended


def _prompts(lengths, seed=1):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, SHAPE.vocab, n).tolist() for n in lengths]


def test_shape_reads_the_run_of_layers_from_first_layer():
    assert SHAPE.mixers == ("minicpm4", "lightning-attn", "lightning-attn")
    assert (SHAPE.block, SHAPE.topk, SHAPE.window, SHAPE.dense_len) == \
        (4, 4, 8, 32)
    assert SHAPE.published_layers == 32 and SHAPE.first_layer == 9


@pytest.mark.parametrize("kernel", ["pallas", "xla"])
def test_prefill_then_decode_matches_the_reference(kernel):
    """Row 0 a prompt of 26 (dense), row 1 of 41 (its later queries
    already sparse in the prefill), row 2 idle; then nine decode steps,
    row 0 crossing ``dense_len`` on the way: dense and sparse rows in
    one step. Logits at every emitting position against the reference's
    full forward pass; a padded bucket's tail (48 - 26 tokens) must
    leave no trace in a state."""
    d = Driver(kernel)
    seqs = _prompts((26, 41))
    bucket = 48
    tokens = np.zeros((ROWS, bucket), np.int32)
    for r, p in enumerate(seqs):
        tokens[r, :len(p)] = p
    mask = np.array([True, True, False])
    got, _ = d.step(tokens, np.zeros(ROWS), mask,
                    [len(seqs[0]) - 1, len(seqs[1]) - 1, 0])
    for r in range(2):
        want = _reference_logits(d.ref_w, seqs[r], [len(seqs[r]) - 1])[0]
        np.testing.assert_allclose(got[r], want, **TOL)
    crossed = []
    for _ in range(9):
        nxt = got.argmax(-1)
        positions = [len(seqs[0]), len(seqs[1]), 0]
        for r in range(2):
            seqs[r].append(int(nxt[r]))
        got, attended = d.step(
            [[seqs[0][-1]], [seqs[1][-1]], [0]], positions, mask,
            np.zeros(ROWS))
        # row 1 is sparse (4 blocks); row 0 attends all its blocks until
        # its context passes 32, then 4
        dense0 = positions[0] + 1 <= SHAPE.dense_len
        assert attended == (positions[0] // BLOCK + 1 if dense0 else 4) + 4
        crossed.append(dense0)
        for r in range(2):
            want = _reference_logits(d.ref_w, seqs[r], [len(seqs[r]) - 1])[0]
            np.testing.assert_allclose(got[r], want, **TOL)
    assert crossed[0] and not crossed[-1]


def test_a_slot_reused_by_a_second_sequence_starts_from_zero():
    """Slot 0 serves one sequence, then another from position 0: the
    second one's logits are those of a fresh model."""
    d = Driver("xla", rows=2)
    first, second = _prompts((30, 19), seed=2)
    for seq in (first, second):
        tokens = np.zeros((2, 32), np.int32)
        tokens[0, :len(seq)] = seq
        got, _ = d.step(tokens, [0, 0], [True, False], [len(seq) - 1, 0])
        got, _ = d.step([[int(got[0].argmax())], [0]], [len(seq), 0],
                        [True, False], [0, 0])
        full = seq + [int(_reference_logits(
            d.ref_w, seq, [len(seq) - 1])[0].argmax())]
        want = _reference_logits(d.ref_w, full, [len(full) - 1])[0]
        np.testing.assert_allclose(got[0], want, **TOL)
    # a one-token decode at position 0 resets too (the decode kernel)
    got, _ = d.step([[second[0]], [0]], [0, 0], [True, False], [0, 0])
    want = _reference_logits(d.ref_w, second[:1], [0])[0]
    np.testing.assert_allclose(got[0], want, **TOL)


@pytest.mark.parametrize("kernel", ["pallas", "xla"])
def test_a_masked_row_leaves_its_state_untouched(kernel):
    d = Driver(kernel, rows=2)
    seq = _prompts((12,), seed=3)[0]
    tokens = np.zeros((2, 32), np.int32)
    tokens[1, :len(seq)] = seq
    d.step(tokens, [0, 0], [False, True], [0, len(seq) - 1])
    states = lambda: [np.asarray(x) for x in d.ex._cache_leaves("row")]  # noqa: E731
    before = states()
    assert any(s[1].any() for s in before) and not any(
        s[0].any() for s in before)
    # row 1 masked out of a decode step and of a prefill step
    d.step([[5], [7]], [0, len(seq)], [True, False], [0, 0])
    d.step(tokens, [1, len(seq)], [True, False], [3, 3])
    for a, b in zip(before, states()):
        np.testing.assert_array_equal(a[1], b[1])


def test_row_compact_prefill_addresses_the_slots_state():
    """A one-row prefill step that stands for slot 2: its state lands in
    slot 2, the other slots' stay, and the slot's decode continues it."""
    d = Driver("xla")
    other, seq = _prompts((9, 37), seed=4)
    tokens = np.zeros((ROWS, 16), np.int32)
    tokens[0, :len(other)] = other
    d.step(tokens, np.zeros(ROWS), [True, False, False],
           [len(other) - 1, 0, 0])
    kept = [np.asarray(x[0]) for x in d.ex._cache_leaves("row")]
    one = np.zeros((1, 48), np.int32)
    one[0, :len(seq)] = seq
    got, _ = d.step(one, [0], [True], [len(seq) - 1], slots=[2])
    want = _reference_logits(d.ref_w, seq, [len(seq) - 1])[0]
    np.testing.assert_allclose(got[0], want, **TOL)
    for a, leaf in zip(kept, d.ex._cache_leaves("row")):
        np.testing.assert_array_equal(a, np.asarray(leaf[0]))
        assert np.asarray(leaf[2]).any() and not np.asarray(leaf[1]).any()
    seq = seq + [int(got[0].argmax())]
    got, _ = d.step([[0], [0], [seq[-1]]], [0, 0, len(seq) - 1],
                    [False, False, True], np.zeros(ROWS))
    want = _reference_logits(d.ref_w, seq, [len(seq) - 1])[0]
    np.testing.assert_allclose(got[2], want, **TOL)


def test_through_the_executor_and_the_batcher():
    """`AdmissionQueue.submit` -> `ContinuousBatcher.step` ->
    `ShardedExecutor.step`, row-compact prefill, slots reused by later
    requests: every served token is the reference's greedy token, or
    lies within the tolerance of its best."""
    from horovod_tpu.trace.spans import get_recorder
    rec = get_recorder()
    t_begin = rec.now()         # the ring is the process's, not the test's
    model, params, ref_w = _serving("xla", seed=5)
    ex = ShardedExecutor(model, params, max_batch=2, max_len=MAX_LEN)
    queue = AdmissionQueue(max_queue=8, default_deadline_ms=600000.0)
    batcher = ContinuousBatcher(ex, queue, buckets=(24, 48),
                                prefix_cache=False, kv_crc=False,
                                kv_tier=False, spec_k=0)
    batcher.warmup()
    assert batcher.prefill_rows == 1
    prompts = _prompts((30, 11, 42, 25), seed=6)
    handles = [queue.submit(p, max_new_tokens=6, temperature=0.0)
               for p in prompts]
    for _ in range(200):
        batcher.step()
        if all(h.done() for h in handles):
            break
    for p, h in zip(prompts, handles):
        assert h.status == "ok" and len(h.tokens) == 6
        seq = p + list(h.tokens)
        lg = _reference_logits(ref_w, seq,
                               np.arange(len(p) - 1, len(seq) - 1))
        served = lg[np.arange(6), h.tokens]
        assert (lg.max(-1) - served).max() <= 2e-4
    # the decode steps of this model say what they attended and updated
    steps = [s for s in rec.between(t_begin, rec.now())
             if s.name == "exec_step"]
    decode = [s.extra for s in steps if s.extra["kind"] == "decode"
              and s.extra["rows"]]          # the warm-up's has none
    assert decode and all(
        0 < e["blocks_attended"] <= e["blocks_cached"]
        and e["state_rows"] == e["rows"]
        for e in decode)
    assert any(e["blocks_attended"] < e["blocks_cached"] for e in decode)


# -- the pieces ---------------------------------------------------------------

@pytest.mark.parametrize("chunk", [8, 16, 37, 5, 256])
def test_chunked_scan_matches_the_recurrence(chunk):
    """Chunks that divide the 37 tokens (37), that do not (8, 16, 5) and
    one longer than them; a row cut at 20 tokens, a row out of the step."""
    rng = np.random.default_rng(0)
    B, T, H, D = 3, 37, 4, 16
    q, k, v = (jnp.asarray(rng.normal(size=(B, T, H, D)), jnp.float32)
               for _ in range(3))
    s0 = jnp.asarray(rng.normal(size=(B, H, D, D)), jnp.float32)
    n = jnp.asarray([37, 20, 0])
    want_o, want_s = lightning.lightning_recurrence(s0, q, k, v, n)
    o, s = lightning.lightning_chunked(s0, q, k, v, n, chunk=chunk)
    np.testing.assert_allclose(s, want_s, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(o[0], want_o[0], rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(o[1, :20], want_o[1, :20], rtol=1e-5,
                               atol=1e-5)
    np.testing.assert_array_equal(s[2], s0[2])


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_decode_kernel_is_bit_exact_against_its_oracle(dtype):
    """q, k, v arrive in the compute dtype: bfloat16 values, whose
    products are exact in float32, so the kernel (interpret mode) and
    its oracle agree bit for bit whatever the compiler fuses. With
    float32 operands (the CPU tests' compute dtype) a fused
    multiply-add may round once where the other rounds twice: 1e-6."""
    rng = np.random.default_rng(1)
    B, H, D = 4, 4, 16
    q, k, v = (jnp.asarray(rng.normal(size=(B, H, D)), dtype)
               for _ in range(3))
    s0 = jnp.asarray(rng.normal(size=(B, H, D, D)), jnp.float32)
    pos = jnp.asarray([5, 0, 7, 0])
    live = jnp.asarray([True, True, False, False])
    o, s = lightning.lightning_decode(s0, q, k, v, pos, live, interpret=True)
    want_o, want_s = jax.jit(lightning.lightning_decode_reference)(
        s0, q, k, v, pos, live)
    same = np.testing.assert_array_equal if dtype == jnp.bfloat16 else \
        (lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-6))
    same(o, want_o)
    same(s, want_s)
    # position 0 starts from zero; a row out of the step keeps its state
    np.testing.assert_array_equal(
        s[1], np.asarray(k[1], np.float32)[:, :, None]
        * np.asarray(v[1], np.float32)[:, None, :])
    np.testing.assert_array_equal(s[2:], s0[2:])


SIZES = block_sparse.SparseSizes(block=4, stride=1, init_blocks=1,
                                 window_blocks=2, topk=4, dense_len=32)


def _pool_with(keys, vals, table):
    """Pools holding one row's keys/vals [L, KV, D] through `table`."""
    L, KV, D = keys.shape
    nb = int(table.max()) + 2
    pk = jnp.zeros((nb, 4, KV, D), keys.dtype).at[table].set(
        keys.reshape(-1, 4, KV, D))
    pv = jnp.zeros((nb, 4, KV, D), vals.dtype).at[table].set(
        vals.reshape(-1, 4, KV, D))
    return pk, pv


def _masked_dense(q, keys, vals, sel, q_pos):
    """Softmax attention of q [Q, KV, G, D] over all keys with the
    unselected (query, block) pairs and the future masked."""
    s = jnp.einsum("qkgd,skd->kgqs", q, keys) / np.sqrt(q.shape[-1])
    ok = jnp.repeat(sel, 4, axis=2) & \
        (jnp.arange(keys.shape[0])[None, :] <= q_pos[:, None])[None]
    p = jax.nn.softmax(jnp.where(ok[:, None], s, -1e30), axis=-1)
    return jnp.einsum("kgqs,skd->qkgd", p, vals)


def test_selected_table_attention_matches_masked_dense_attention():
    """A decode query at position 50 (13 blocks cached): the attended
    table holds 4 blocks a KV group, block 0, the newest two and one
    chosen, and attention over that table is attention over all keys
    with the other blocks masked. The same position through the
    prefill's tiles gives the same."""
    rng = np.random.default_rng(2)
    L, KV, G, D = 52, 2, 2, 16
    keys, vals = (jnp.asarray(rng.normal(size=(L, KV, D)), jnp.float32)
                  for _ in range(2))
    q = jnp.asarray(rng.normal(size=(1, 1, KV * G, D)), jnp.float32)
    table = jnp.asarray(rng.permutation(16)[:13], jnp.int32)
    pk, pv = _pool_with(keys, vals, table)
    tables = jnp.full((1, 16), -1, jnp.int32).at[0, :13].set(table)
    pos = jnp.asarray([50])
    cpool = jnp.zeros((pk.shape[0], 4, KV, D), jnp.float32)
    # the compressed keys as a prefill of all 52 tokens writes them
    cpool = block_sparse.write_compressed_keys(
        cpool, pk, tables, jnp.asarray([0]), jnp.asarray([True]), L, SIZES)
    c = block_sparse.gather_compressed(cpool, tables[0])
    np.testing.assert_allclose(c[7], (keys[7] + keys[8]) / 2, rtol=1e-6)
    att, lengths, n_att = block_sparse.attended_tables(
        q[:, 0], cpool, tables, pos, SIZES)
    assert att.shape == (1, KV, 8) and int(n_att[0]) == 4
    assert int(lengths[0]) == 3 * 4 + 50 % 4
    picked = np.asarray(att[0, :, :4])
    assert (np.asarray(att[0, :, 4:]) == -1).all()
    for g in range(KV):
        logical = [int(np.flatnonzero(np.asarray(table) == b)[0])
                   for b in picked[g]]
        assert logical == sorted(logical) and logical[0] == 0
        assert logical[-2:] == [11, 12]
    got = kv_cache.paged_attention(
        jnp.repeat(q, KV, axis=0), pk, pv, att.reshape(KV, 8),
        jnp.repeat(lengths, KV))                        # [KV, 1, H, D]
    got = jnp.stack([got[g, 0].reshape(KV, G, D)[g] for g in range(KV)])
    qr = q.reshape(1, KV, G, D)
    sc = block_sparse.block_scores(qr, c, pos, SIZES)
    idx = block_sparse.select_blocks(sc, pos, SIZES)
    sel = jnp.any(idx[..., None] == jnp.arange(13), axis=-2)
    want = _masked_dense(qr, keys, vals, sel, pos)[0]
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    # the prefill's path: 20 queries from position 31, the last at 50
    qs = jnp.asarray(rng.normal(size=(1, 20, KV * G, D)), jnp.float32)
    qs = qs.at[0, 19].set(q[0, 0])
    out = block_sparse.prefill_attention(
        qs, pk, pv, cpool, tables, jnp.asarray([31]), SIZES)
    np.testing.assert_allclose(out[0, 19].reshape(KV, G, D), want,
                               rtol=1e-5, atol=1e-5)
    # and a query still dense (position 31: context 32) sees everything
    dense = _masked_dense(qs[0, :1].reshape(1, KV, G, D), keys, vals,
                          jnp.ones((KV, 1, 13), bool), jnp.asarray([31]))
    np.testing.assert_allclose(out[0, 0].reshape(KV, G, D), dense[0],
                               rtol=1e-5, atol=1e-5)


def test_compressed_keys_are_written_as_their_window_completes():
    """A decode token at position p completes window (p - 1) / 1 here
    (kernel 2, stride 1): written then, from the pool's own keys, into
    the block that holds the window's first key."""
    rng = np.random.default_rng(3)
    KV, D = 2, 16
    keys = jnp.asarray(rng.normal(size=(12, KV, D)), jnp.float32)
    table = jnp.asarray([3, 0, 2], jnp.int32)
    pk, _ = _pool_with(keys, keys, table)
    tables = jnp.full((1, 4), -1, jnp.int32).at[0, :3].set(table)
    cpool = jnp.zeros((pk.shape[0], 4, KV, D), jnp.float32)
    for p in (0, 4, 9):
        cpool = block_sparse.write_compressed_keys(
            cpool, pk, tables, jnp.asarray([p]), jnp.asarray([True]), 1,
            SIZES)
    c = np.asarray(block_sparse.gather_compressed(cpool, tables[0]))
    # window j = p - 1 covers keys [j, j + 2): window 3 (block 0's last)
    # ends in block 1; position 0 completes none
    for j in (3, 8):
        np.testing.assert_allclose(c[j], (keys[j] + keys[j + 1]) / 2,
                                   rtol=1e-6)
    assert not c[[0, 1, 2, 4, 5, 6, 7, 9, 10, 11]].any()
    # a masked row writes nothing
    again = block_sparse.write_compressed_keys(
        cpool, pk, tables, jnp.asarray([6]), jnp.asarray([False]), 1, SIZES)
    np.testing.assert_array_equal(again, cpool)


# -- what cannot run yet refuses by name --------------------------------------

def _executor(rows=2):
    model, params, _ = _serving("xla", rows=rows)
    return ShardedExecutor(model, params, max_batch=rows, max_len=MAX_LEN)


@pytest.mark.parametrize("kw, what", [
    (dict(prefix_cache=True), "prefix_cache"),
    (dict(kv_tier=True), "kv_tier"),
    (dict(spec_k=2), "speculative"),
])
def test_the_batcher_refuses_what_would_lose_the_state(kw, what):
    ex = _executor()
    args = dict(prefix_cache=False, kv_crc=False, kv_tier=False, spec_k=0)
    args.update(kw)
    with pytest.raises(ValueError, match=what) as err:
        ContinuousBatcher(ex, AdmissionQueue(max_queue=4), buckets=(32,),
                          **args)
    assert "SalaLM" in str(err.value)


def test_migration_and_disaggregated_pools_refuse_per_row_state():
    from horovod_tpu.serve import kv_migrate
    from horovod_tpu.serve.worker import ReplicaWorker
    ex = _executor()
    batcher = ContinuousBatcher(ex, AdmissionQueue(max_queue=4),
                                buckets=(32,), kv_crc=False, spec_k=0)
    # the defaults that cannot apply are off
    assert batcher.prefix is None and batcher.kvtier is None
    with pytest.raises(ValueError, match="migration of SalaLM"):
        kv_migrate.pack_parked(batcher, 0, fid="f", max_new_tokens=4,
                               deadline_ms=1000.0)
    with pytest.raises(ValueError, match="migration of SalaLM"):
        batcher.submit_migrated({}, [])
    with pytest.raises(ValueError, match="no K/V pool"):
        ex.install_kv_blocks([0], [[b""]], [0])

    def builder():
        model, params, _ = _serving("xla", rows=2)
        return {"model": model, "params": params, "max_batch": 2,
                "max_len": MAX_LEN, "buckets": (32,)}
    sys.modules[__name__].sala_builder = builder
    with pytest.raises(ValueError, match="migration of SalaLM"):
        ReplicaWorker({"rid": 0, "pool": "decode",
                       "builder": f"{__name__}:sala_builder"})


def test_the_three_kinds_of_cache_leaf():
    """K/V pools, the per-block compressed keys, the per-row state: a
    block copy moves the first two and leaves the third; the integrity
    ledger reads the pools alone; the gauge counts live rows' state."""
    ex = _executor(rows=8)
    kinds = ex._leaf_kinds
    assert sorted(set(kinds)) == ["block", "kv", "row"]
    assert kinds.count("kv") == 2 and kinds.count("block") == 1
    assert kinds.count("row") == 2
    H, D = SHAPE.lightning_heads, SHAPE.lightning_head_dim
    assert ex.state_row_bytes == 2 * H * D * D * 4
    leaves, treedef = jax.tree_util.tree_flatten(ex.cache)
    ex.cache = jax.tree_util.tree_unflatten(treedef, [
        x.at[1].set(1.0) for x in leaves])
    ex.copy_kv_block(1, 5)
    for kind, leaf in zip(kinds, jax.tree_util.tree_leaves(ex.cache)):
        assert bool(np.asarray(leaf[5]).all()) is (kind != "row")
    assert len(ex.kv_block_bytes(1, 0, 2)) == 2
    queue = AdmissionQueue(max_queue=4)
    batcher = ContinuousBatcher(ex, queue, buckets=(32,), kv_crc=False,
                                spec_k=0)
    queue.submit([1, 2, 3], max_new_tokens=3, temperature=0.0)
    batcher.step()
    from horovod_tpu.obs import metrics as obs_metrics
    gauge = obs_metrics.get_registry().get("hvd_serve_state_bytes")
    assert gauge.value == ex.state_row_bytes        # one live row
    assert "hvd_serve_state_bytes" in \
        obs_metrics.get_registry().to_prometheus()
