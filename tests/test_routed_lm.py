"""`models/routed_lm.py` on the serving path against the plain reference
(`chipbench/families/smallthinker.py`, which imports nothing of the
program): prefill then paged decode, LOGITS compared, on prompts shorter
and longer than the (tiny) window, with both decode kernels; through
`ShardedExecutor` + `ContinuousBatcher` with row-compact prefill; and a
row's logits unchanged when its batch-mates change."""
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
from horovod_tpu.serve import (AdmissionQueue, ContinuousBatcher,  # noqa: E402
                               ShardedExecutor, pool_blocks_for)

FAMILY = mf.load_module("chipbench/families/smallthinker.py")
CONFIG = harness._merge(
    mf.load_json("chipbench/configs/smallthinker-21b-l8.json"),
    dict(FAMILY.REHEARSE_CONFIG,
         assumed={"compute_dtype": "float32"}))
SHAPE = FAMILY.Shape(CONFIG)          # window 8, 4 layers: one period
BLOCK, MAX_LEN, ROWS = 4, 48, 3


def _serving(kernel, seed=0, max_len=MAX_LEN, rows=ROWS, shape=SHAPE):
    model = FAMILY.serve_model(
        shape, CONFIG, kv_block=BLOCK,
        # every row's whole table at once: the tests below deal the
        # blocks out by hand
        kv_pool_blocks=pool_blocks_for(rows, max_len, BLOCK, fraction=1.0),
        decode_kernel=kernel)
    key = FAMILY.seed_key(seed)
    params = jax.jit(lambda k: FAMILY.program_params(shape, k))(key)
    ref_w = jax.jit(lambda k: FAMILY.reference_weights(shape, k))(key)
    return model, params, ref_w


_LOGITS_AT = jax.jit(FAMILY.logits_at, static_argnums=(1,))


def _reference_logits(ref_w, seq, where, shape=SHAPE):
    """The reference's logits of `seq` at `where`; the sequence is
    padded to a fixed length (causal: the padding is never seen), so
    that every call is one compiled program."""
    pad = -(-len(seq) // MAX_LEN) * MAX_LEN
    tokens = np.zeros((1, pad), np.int32)
    tokens[0, :len(seq)] = seq
    return np.asarray(_LOGITS_AT(ref_w, shape, jnp.asarray(tokens),
                                 jnp.asarray(where, jnp.int32)))


def test_shape_reads_the_first_layers_of_the_layouts():
    assert SHAPE.rope_layout == (0, 1, 1, 1)
    assert SHAPE.window_layout == (0, 1, 1, 1)
    assert SHAPE.window == 8 and SHAPE.published_layers == 52


@pytest.mark.parametrize("kernel", ["pallas", "xla"])
def test_prefill_then_decode_matches_the_reference(kernel):
    """Row 0 a prompt below the window, row 1 above it (through the
    flash prefill at 24 tokens), row 2 idle; then eight decode steps,
    the short row crossing the window on the way. Logits at every
    emitting position against the reference's full forward pass."""
    model, params, ref_w = _serving(kernel)
    ex = ShardedExecutor(model, params, max_batch=ROWS, max_len=MAX_LEN)
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, SHAPE.vocab, n).tolist() for n in (5, 21)]
    nblk = ex.blocks_per_seq
    tables = np.full((ROWS, nblk), -1, np.int32)
    tables[0], tables[1] = np.arange(nblk), nblk + np.arange(nblk)

    @jax.jit
    def apply(cache, tokens, positions, mask, last_idx):
        return model.apply(
            {"params": ex.params, "cache": cache}, tokens,
            positions=positions, update_mask=mask, logits_idx=last_idx,
            block_tables=jnp.asarray(tables), mutable=["cache", "stats"])

    def step(tokens, positions, mask, last_idx):
        logits, vout = apply(
            ex.cache, jnp.asarray(tokens, jnp.int32),
            jnp.asarray(positions, jnp.int32), jnp.asarray(mask),
            jnp.asarray(last_idx, jnp.int32))
        ex.cache = vout["cache"]
        hit = sum(int(x) for x in jax.tree_util.tree_leaves(vout["stats"]))
        return np.asarray(logits[:, 0]), hit

    bucket = 24
    tokens = np.zeros((ROWS, bucket), np.int32)
    for r, p in enumerate(prompts):
        tokens[r, :len(p)] = p
    mask = np.array([True, True, False])
    lengths = np.array([len(p) for p in prompts] + [1])
    got, hit = step(tokens, np.zeros(ROWS, np.int32), mask, lengths - 1)
    # 26 real tokens x 2 experts over 8 experts, 4 layers
    assert 4 <= hit <= 4 * SHAPE.experts
    seqs = [list(p) for p in prompts]
    for r in range(2):
        want = _reference_logits(ref_w, seqs[r], [len(seqs[r]) - 1])[0]
        np.testing.assert_allclose(got[r], want, rtol=2e-4, atol=2e-4)
    for _ in range(8):
        nxt = got.argmax(-1)
        positions = np.array([len(s) for s in seqs] + [0])
        for r in range(2):
            seqs[r].append(int(nxt[r]))
        tok = np.array([[seqs[0][-1]], [seqs[1][-1]], [0]])
        got, hit = step(tok, positions, mask, np.zeros(ROWS, np.int32))
        assert hit <= 4 * 2 * SHAPE.top_k          # two live rows
        for r in range(2):
            want = _reference_logits(ref_w, seqs[r], [len(seqs[r]) - 1])[0]
            np.testing.assert_allclose(got[r], want, rtol=2e-4, atol=2e-4)
    assert len(seqs[0]) > SHAPE.window          # crossed it while decoding


def _serve(kernel, prompts, new_tokens, *, buckets=(8, 24), max_len=MAX_LEN,
           rows=ROWS, seed=0, shape=SHAPE):
    model, params, ref_w = _serving(kernel, seed, max_len, rows, shape)
    ex = ShardedExecutor(model, params, max_batch=rows, max_len=max_len)
    queue = AdmissionQueue(max_queue=16, default_deadline_ms=600000.0)
    batcher = ContinuousBatcher(ex, queue, buckets=buckets,
                                prefix_cache=True, kv_crc=False,
                                kv_tier=False, spec_k=0)
    batcher.warmup()
    handles = [queue.submit(p, max_new_tokens=n, temperature=0.0)
               for p, n in zip(prompts, new_tokens)]
    for _ in range(500):
        if all(h.done() for h in handles):
            break
        batcher.step()
    assert all(h.done() and h.status == "ok" for h in handles), \
        [h.status for h in handles]
    return ex, batcher, ref_w, [list(h.tokens) for h in handles]


def _served_gap(ref_w, prompt, served, shape=SHAPE):
    """At every served position, how far the served token's reference
    logit lies below the reference's best (0 for exact greedy)."""
    seq = list(prompt) + list(served)
    where = len(prompt) - 1 + np.arange(len(served))
    lg = _reference_logits(ref_w, seq, where, shape)
    return float(np.max(lg.max(-1) - lg[np.arange(len(served)), served]))


@pytest.mark.parametrize("kernel", ["pallas", "xla"])
def test_through_the_executor_and_the_batcher(kernel):
    """Five requests over three rows, prompts on both sides of the
    window and of the flash threshold, the second wave admitted while
    the first decodes; one prompt shares a prefix with an earlier one."""
    rng = np.random.default_rng(2)
    lens = (3, 22, 9, 17, 24)
    prompts = [rng.integers(0, SHAPE.vocab, n).tolist() for n in lens]
    prompts[3][:8] = prompts[1][:8]         # two whole shared blocks
    new = (6, 4, 9, 5, 3)
    ex, batcher, ref_w, served = _serve(kernel, prompts, new)
    assert batcher.prefill_rows == 1
    # row-compact prefill: one row a step, each at its own bucket
    assert ("prefill", 8) in ex.signatures and ("prefill", 24) in ex.signatures
    assert ex.jit_cache_size() == 3             # two buckets and decode
    for p, n, got in zip(prompts, new, served):
        assert len(got) == n
        assert _served_gap(ref_w, p, got) < 1e-3


def test_a_prompt_longer_than_the_old_last_bucket_is_accepted():
    """The default buckets end at 512 and the queue refused what they
    could not hold; with a bucket for it a 600-token prompt is served,
    every layer kind through the long prefill."""
    rng = np.random.default_rng(3)
    prompt = rng.integers(0, SHAPE.vocab, 600).tolist()
    long = FAMILY.Shape(dict(CONFIG, max_position_embeddings=1024))
    _, batcher, ref_w, (served,) = _serve(
        "xla", [prompt], [3], buckets=(32, 128, 512, 640), max_len=640,
        rows=2, shape=long)
    assert batcher.queue.max_prompt_len == 640
    assert len(served) == 3
    assert _served_gap(ref_w, prompt, served, long) < 1e-3


def test_prompt_past_the_last_bucket_is_refused_at_submit():
    model, params, _ = _serving("xla")
    ex = ShardedExecutor(model, params, max_batch=ROWS, max_len=MAX_LEN)
    queue = AdmissionQueue(max_queue=4, default_deadline_ms=1000.0)
    ContinuousBatcher(ex, queue, buckets=(8, 24), prefix_cache=False,
                      kv_crc=False, kv_tier=False, spec_k=0)
    from horovod_tpu.serve.queue import Rejected
    with pytest.raises(Rejected, match="prompt length 25"):
        queue.submit(list(range(25)), max_new_tokens=1)


@pytest.mark.parametrize("kernel", ["pallas", "xla"])
def test_a_rows_logits_do_not_depend_on_its_batch_mates(kernel):
    """No capacity anywhere: row 0's logits in a decode step are the same
    whether the other rows are idle or crowd its experts."""
    model, params, _ = _serving(kernel)
    ex = ShardedExecutor(model, params, max_batch=ROWS, max_len=MAX_LEN)
    nblk = ex.blocks_per_seq
    tables = np.stack([r * nblk + np.arange(nblk) for r in range(ROWS)])
    rng = np.random.default_rng(4)
    prompt = rng.integers(0, SHAPE.vocab, (ROWS, 24))

    @jax.jit
    def forward(cache, tokens, positions, mask, last_idx):
        return model.apply(
            {"params": params, "cache": cache}, tokens, positions=positions,
            update_mask=mask, logits_idx=last_idx,
            block_tables=jnp.asarray(tables, jnp.int32),
            mutable=["cache", "stats"])

    def apply(cache, tokens, positions, mask, last_idx):
        logits, vout = forward(
            cache, jnp.asarray(tokens, jnp.int32),
            jnp.asarray(positions, jnp.int32), jnp.asarray(mask),
            jnp.asarray(last_idx, jnp.int32))
        return np.asarray(logits[:, 0]), vout["cache"]

    _, cache = apply(ex.cache, prompt, np.zeros(ROWS), np.ones(ROWS, bool),
                     np.full(ROWS, 23))
    tok = np.array([[7], [7], [7]])
    alone, _ = apply(cache, tok, np.full(ROWS, 24),
                     np.array([True, False, False]), np.zeros(ROWS))
    crowd, _ = apply(cache, tok, np.full(ROWS, 24), np.ones(ROWS, bool),
                     np.zeros(ROWS))
    other, _ = apply(cache, np.array([[7], [99], [300]]), np.full(ROWS, 24),
                     np.ones(ROWS, bool), np.zeros(ROWS))
    np.testing.assert_allclose(alone[0], crowd[0], rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(alone[0], other[0], rtol=1e-6, atol=1e-6)


def test_the_control_precision_fails_the_comparison():
    """The reference in fp8, put in the program's place, is further from
    the reference than any limit a bfloat16 program needs."""
    rng = np.random.default_rng(5)
    _, _, ref_w = _serving("xla")
    seq = jnp.asarray([rng.integers(0, SHAPE.vocab, 40)], jnp.int32)
    where = jnp.arange(20, 40)
    logits_at = jax.jit(FAMILY.logits_at, static_argnums=(1, 4))
    exact = np.asarray(logits_at(ref_w, SHAPE, seq, where, "float32"))
    gaps = {}
    for precision in ("bfloat16", FAMILY.CONTROL):
        lg = np.asarray(logits_at(ref_w, SHAPE, seq, where, precision))
        first = lg.argmax(-1)
        gaps[precision] = float(np.max(
            exact.max(-1) - exact[np.arange(len(first)), first]))
    assert gaps[FAMILY.CONTROL] > gaps["bfloat16"]


def test_config_requires_the_paged_serving_layout():
    from horovod_tpu.models.routed_lm import RoutedLMConfig
    with pytest.raises(ValueError, match="paged"):
        RoutedLMConfig(kv_block_size=0)
    with pytest.raises(ValueError, match="one entry per layer"):
        RoutedLMConfig(num_layers=4, rope_layout=[0, 1], kv_block_size=4,
                       kv_pool_blocks=8)
    cfg = RoutedLMConfig(num_layers=8, kv_block_size=4, kv_pool_blocks=8)
    assert cfg.rope_layout == (0, 1, 1, 1, 0, 1, 1, 1)
    assert [cfg.layer_window(i) for i in range(4)] == [None, 64, 64, 64]


def test_reference_imports_nothing_of_the_program():
    with open(os.path.join(ROOT, "chipbench/families/smallthinker.py")) as f:
        source = f.read()
    imports = [l for l in source.splitlines()
               if l.lstrip().startswith(("import ", "from "))]
    program = [l for l in imports if "horovod_tpu" in l]
    # only `serve_model`, which builds the PROGRAM's model, names it
    assert program == ["    from horovod_tpu.models.routed_lm import "
                       "RoutedLM, RoutedLMConfig"]
