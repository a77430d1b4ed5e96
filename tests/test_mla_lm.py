"""`models/mla_lm.py` on the serving path against the plain reference
(`chipbench/families/joyai.py`, the PUBLISHED non-absorbed form, which
imports nothing of the program): prefill then decode through the latent
pool, a second request whose prefix a radix hit supplies, LOGITS
compared with the reference's one full forward pass at tiny widths on
the CPU; the latent-decode kernel and its oracle against the published
definition; `ep.py`'s sigmoid routing and its softmax and ReGLU
arithmetic as before; `ops/bf16_split.py`. The model runs as the
configuration has it, bfloat16 weights and float32 activations
multiplied as bfloat16 terms, so a tolerance is the rounding of one
float32 arithmetic in another order (the absorption's reassociation,
the online softmax, the flash forward's blocks): 2e-4, where logits
spread over about 0.5; activations rounded once to bfloat16 miss it,
operands through fp8 miss it by orders (the last tests)."""
import functools
import math
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
from horovod_tpu.ops import pallas_latent  # noqa: E402
from horovod_tpu.parallel import ep  # noqa: E402
from horovod_tpu.serve import (AdmissionQueue, ContinuousBatcher,  # noqa: E402
                               ShardedExecutor, pool_blocks_for)

FAMILY = mf.load_module("chipbench/families/joyai.py")
CONFIG = harness._merge(
    mf.load_json("chipbench/configs/joyai-flash-l5.json"),
    FAMILY.REHEARSE_CONFIG)
SHAPE = FAMILY.Shape(CONFIG)   # 1 dense + 2 routed layers, 16 experts top 4
BLOCK, MAX_LEN, ROWS = 4, 64, 3
TOL = dict(rtol=2e-4, atol=2e-4)


@functools.lru_cache(maxsize=None)
def _weights(seed):
    """The seed's weights both ways, made once a process."""
    key = FAMILY.seed_key(seed)
    return (jax.jit(lambda k: FAMILY.program_params(SHAPE, k))(key),
            jax.jit(lambda k: FAMILY.reference_weights(SHAPE, k))(key))


def _serving(kernel, seed=0, rows=ROWS):
    model = FAMILY.serve_model(
        SHAPE, CONFIG, kv_block=BLOCK,
        kv_pool_blocks=pool_blocks_for(rows, MAX_LEN, BLOCK, fraction=1.0),
        decode_kernel=kernel)
    return (model, *_weights(seed))


_LOGITS_AT = jax.jit(FAMILY.logits_at, static_argnums=(1, 4))


def _reference_logits(ref_w, seq, where, precision="float32"):
    tokens = np.zeros((1, MAX_LEN), np.int32)
    tokens[0, :len(seq)] = seq
    return np.asarray(_LOGITS_AT(ref_w, SHAPE, jnp.asarray(tokens),
                                 jnp.asarray(where, jnp.int32), precision))


class Stepper:
    """`model.apply` on an executor's cache with hand-dealt tables:
    logits out, where the executor would only hand back tokens."""

    def __init__(self, kernel, rows=ROWS, seed=0):
        self.model, params, self.ref_w = _serving(kernel, seed, rows)
        self.ex = ShardedExecutor(self.model, params, max_batch=rows,
                                  max_len=MAX_LEN)
        self.model = self.ex.model
        nblk = self.ex.blocks_per_seq
        self.tables = np.arange(rows * nblk, dtype=np.int32).reshape(
            rows, nblk)
        self._apply = jax.jit(self._call)

    def _call(self, cache, tokens, positions, mask, last_idx, tables):
        return self.model.apply(
            {"params": self.ex.params, "cache": cache}, tokens,
            positions=positions, update_mask=mask, logits_idx=last_idx,
            block_tables=tables, mutable=["cache", "stats"])

    def step(self, tokens, positions, mask, last_idx, tables=None):
        tokens = np.asarray(tokens, np.int32)
        tables = self.tables[:len(tokens)] if tables is None else tables
        logits, vout = self._apply(
            self.ex.cache, jnp.asarray(tokens),
            jnp.asarray(positions, jnp.int32), jnp.asarray(mask, bool),
            jnp.asarray(last_idx, jnp.int32), jnp.asarray(tables))
        self.ex.cache = vout["cache"]
        return np.asarray(logits[:, 0])

    def prefill(self, prompt, row, bucket, start=0, table=None):
        """One row, row-compact, from position `start` (the tokens before
        it are in the row's blocks already)."""
        tokens = np.zeros((1, bucket), np.int32)
        tokens[0, :len(prompt) - start] = prompt[start:]
        table = self.tables[row] if table is None else table
        return self.step(tokens, [start], [True], [len(prompt) - start - 1],
                         np.asarray(table)[None])[0]

    def decode(self, last, positions, mask):
        return self.step([[t] for t in last], positions, mask,
                         np.zeros(len(last)))


def _prompts(lengths, seed=1):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, SHAPE.vocab, n).tolist() for n in lengths]


@pytest.mark.parametrize("kernel", ["pallas", "xla"])
def test_prefill_then_decode_matches_the_reference(kernel):
    """Row 0 a prompt of 21, row 1 of 30 (contexts that cross blocks of
    4, neither a multiple of them), row 2 padding (masked); then 8
    decode steps through the latent pool. Logits at every emitting
    position against the reference's full forward pass."""
    d = Stepper(kernel)
    seqs = _prompts((21, 30))
    bucket = 32
    tokens = np.zeros((ROWS, bucket), np.int32)
    for r, p in enumerate(seqs):
        tokens[r, :len(p)] = p
    mask = np.array([True, True, False])
    got = d.step(tokens, np.zeros(ROWS), mask,
                 [len(seqs[0]) - 1, len(seqs[1]) - 1, 0])
    for r in range(2):
        want = _reference_logits(d.ref_w, seqs[r], [len(seqs[r]) - 1])[0]
        np.testing.assert_allclose(got[r], want, **TOL)
    for _ in range(8):
        nxt = got.argmax(-1)
        positions = [len(seqs[0]), len(seqs[1]), 0]
        for r in range(2):
            seqs[r].append(int(nxt[r]))
        got = d.decode([seqs[0][-1], seqs[1][-1], 0], positions, mask)
        for r in range(2):
            want = _reference_logits(d.ref_w, seqs[r], [len(seqs[r]) - 1])[0]
            np.testing.assert_allclose(got[r], want, **TOL)
    # the masked row wrote nothing: its blocks are as they were made
    nblk = d.ex.blocks_per_seq
    for leaf in jax.tree_util.tree_leaves(d.ex.cache):
        assert not np.asarray(leaf[2 * nblk:3 * nblk]).any()


@pytest.mark.parametrize("kernel", ["pallas", "xla"])
def test_a_prefix_hit_gives_the_logits_of_a_whole_prefill(kernel):
    """Row 0 prefills a prompt whole; row 1's prompt shares its first 12
    tokens (three blocks), which its table takes from row 0's blocks,
    and prefills only its suffix from position 12. Its logits, and a
    decode step's after them, are the reference's (and so a whole
    prefill's)."""
    d = Stepper(kernel)
    first, tail = _prompts((19, 14), seed=3)
    second = first[:12] + tail
    d.prefill(first, 0, 32)
    table = d.tables[1].copy()
    table[:3] = d.tables[0][:3]
    got = d.prefill(second, 1, 16, start=12, table=table)
    np.testing.assert_allclose(
        got, _reference_logits(d.ref_w, second, [len(second) - 1])[0], **TOL)
    second.append(int(got.argmax()))
    d.tables[1] = table
    got = d.decode([0, second[-1], 0], [0, len(second) - 1, 0],
                   [False, True, False])[1]
    np.testing.assert_allclose(
        got, _reference_logits(d.ref_w, second, [len(second) - 1])[0], **TOL)


def test_through_the_batcher_with_prefix_reuse():
    """`AdmissionQueue.submit` -> `ContinuousBatcher.step` ->
    `ShardedExecutor.step` with the radix prefix cache on: five requests
    that open with one 8-token system prompt. Every served token is the
    reference's greedy choice within a margin no rounding reaches, the
    requests after the first prefill only what follows the shared prefix
    (``tokens`` on their prefill spans, ``cached_tokens`` the prefix;
    0 without the cache) and answer what they answer
    without the cache; decode spans carry the cache counters at
    the latent's size and the experts the routed layers counted."""
    from horovod_tpu.trace.spans import get_recorder
    shared = _prompts((8,), seed=5)[0]
    prompts = [shared + t for t in _prompts((13, 21, 6, 17, 10), seed=7)]

    def serve(prefix_cache):
        model, params, ref_w = _serving("pallas", rows=2)
        ex = ShardedExecutor(model, params, max_batch=2, max_len=MAX_LEN)
        queue = AdmissionQueue(max_queue=8)
        batcher = ContinuousBatcher(ex, queue, buckets=(16, 32),
                                    kv_crc=False, spec_k=0,
                                    prefix_cache=prefix_cache)
        batcher.warmup()
        t0 = get_recorder().now()
        handles = []
        for p in prompts:     # one at a time: a later one finds the prefix
            handles.append(queue.submit(p, max_new_tokens=4,
                                        temperature=0.0))
            for _ in range(40):
                batcher.step()
                if handles[-1].done():
                    break
        spans = [s for s in get_recorder().between(t0, get_recorder().now())
                 if s.name == "exec_step"]
        return ex, ref_w, handles, spans

    ex, ref_w, handles, spans = serve(True)
    for p, h in zip(prompts, handles):
        assert h.status == "ok" and len(h.tokens) == 4
        lg = _reference_logits(ref_w, p + list(h.tokens),
                               len(p) - 1 + np.arange(4))
        gap = lg.max(-1) - lg[np.arange(4), h.tokens]
        assert gap.max() <= 2e-4, gap
    prefill = [s.extra for s in spans if s.extra["kind"] == "prefill"]
    assert [e["tokens"] for e in prefill] == \
        [len(prompts[0])] + [len(p) - 8 for p in prompts[1:]]
    # what the radix hits supplied, from the host's own bookkeeping
    assert [e["cached_tokens"] for e in prefill] == [0] + [8] * 4
    decode = [s.extra for s in spans if s.extra["kind"] == "decode"]
    per_token = ex.model.cfg.cache_token_bytes
    assert per_token == 3 * (32 + 8) * 4 and ex.model.cfg.cache_row_bytes == 0
    for e in decode:
        held = e["cache_bytes_held"] // per_token
        assert e["context_tokens"] <= held \
            <= e["context_tokens"] + BLOCK * e["rows"]
        # two routed layers, each hit by at least top_k experts
        assert 2 * SHAPE.top_k <= e["experts_hit"] <= 2 * SHAPE.experts
    _, _, cold, cold_spans = serve(False)
    assert [list(h.tokens) for h in cold] == [list(h.tokens)
                                              for h in handles]
    assert [(s.extra["tokens"], s.extra["cached_tokens"]) for s in cold_spans
            if s.extra["kind"] == "prefill"] == [(len(p), 0) for p in prompts]


def test_the_latent_is_one_per_block_leaf_a_layer():
    model, params, _ = _serving("xla")
    ex = ShardedExecutor(model, params, max_batch=ROWS, max_len=MAX_LEN)
    leaves = jax.tree_util.tree_leaves(ex.cache)
    assert len(leaves) == SHAPE.layers
    assert all(x.shape == (ex.kv_pool_blocks, BLOCK, 128)
               and x.dtype == jnp.float32 for x in leaves)
    assert set(ex._leaf_kinds) == {"block"}
    # copy-on-write copies the latent with its block
    ex.cache = jax.tree_util.tree_map(
        lambda x: x.at[5].set(1.0), ex.cache)
    ex.copy_kv_block(5, 9)
    assert all(np.asarray(x[9]).all() for x in
               jax.tree_util.tree_leaves(ex.cache))
    with pytest.raises(ValueError, match="no K/V pool"):
        ex.install_kv_blocks([0], [[b""]], [0])


# -- the latent-decode kernel, its oracle and the published form ---------------

def _latent_case(seed=0, T=1, R=32, P=8, H=4, N=16, V=16, BS=4, nblk=6):
    """A pool of latents, tables with -1 tails, a row at position 0, a
    context not a multiple of the block; and the published form's
    per-head keys and values of the same latents."""
    rng = np.random.default_rng(seed)
    NB, lanes = 24, 128
    lat = rng.normal(size=(NB, BS, R + P)).astype(np.float32)
    pool = np.zeros((NB, BS, lanes), np.float32)
    pool[..., :R + P] = lat
    w_uk = rng.normal(size=(R, H, N)).astype(np.float32) / math.sqrt(R)
    w_uv = rng.normal(size=(R, H, V)).astype(np.float32) / math.sqrt(R)
    q_n = rng.normal(size=(3, T, H, N)).astype(np.float32)
    q_r = rng.normal(size=(3, T, H, P)).astype(np.float32)
    tbl = np.full((3, nblk), -1, np.int32)
    tbl[0, :4] = [7, 2, 11, 3]       # 15 keys: 3 blocks and 3 of a fourth
    tbl[1, :1] = [5]                 # position 0
    tbl[2, :6] = [1, 4, 6, 8, 9, 10]
    pos = np.array([14, 0, 22], np.int32) - (T - 1) * np.array([1, 0, 1])
    return dict(pool=pool, w_uk=w_uk, w_uv=w_uv, q_n=q_n, q_r=q_r, tbl=tbl,
                pos=pos, R=R, P=P, scale=1.0 / math.sqrt(N + P))


def _published(c):
    """Per-head keys ``[W_UK,i c ; k_r]`` and values ``W_UV,i c``, each
    row's own context, float64: the definition."""
    out = []
    B, T, H, _ = c["q_n"].shape
    R = c["R"]
    for b in range(B):
        keys = c["pool"][c["tbl"][b][c["tbl"][b] >= 0]].reshape(
            -1, c["pool"].shape[-1]).astype(np.float64)
        lat, k_r = keys[:, :R], keys[:, R:R + c["P"]]
        rows = []
        for t in range(T):
            n = c["pos"][b] + t + 1
            heads = []
            for h in range(H):
                k = np.concatenate([lat[:n] @ c["w_uk"][:, h], k_r[:n]], 1)
                v = lat[:n] @ c["w_uv"][:, h]
                q = np.concatenate([c["q_n"][b, t, h], c["q_r"][b, t, h]])
                s = k @ q * c["scale"]
                p = np.exp(s - s.max())
                heads.append(p / p.sum() @ v)
            rows.append(heads)
        out.append(rows)
    return np.asarray(out)


@pytest.mark.parametrize("T", [1, 3])
@pytest.mark.parametrize("attend", ["oracle", "kernel"])
def test_absorbed_latent_attention_is_the_published_form(T, attend):
    c = _latent_case(T=T)
    q_lat = np.einsum("bthn,rhn->bthr", c["q_n"], c["w_uk"])
    qt = np.zeros(c["q_n"].shape[:3] + (128,), np.float32)
    qt[..., :c["R"]] = q_lat
    qt[..., c["R"]:c["R"] + c["P"]] = c["q_r"]
    fn = pallas_latent.latent_attention if attend == "oracle" else \
        functools.partial(pallas_latent.latent_attention_fused,
                          interpret=True)
    o_lat = fn(jnp.asarray(qt), jnp.asarray(c["pool"]), c["tbl"], c["pos"],
               scale=c["scale"], value_lanes=c["R"])
    got = np.einsum("bthr,rhv->bthv", np.asarray(o_lat, np.float64),
                    c["w_uv"])
    np.testing.assert_allclose(got, _published(c), rtol=2e-4, atol=2e-5)


def test_the_kernel_matches_its_oracle_in_bfloat16():
    """The pool's dtype: both products take bfloat16 operands; the
    kernel rounds unnormalised probabilities, so it is held to one
    bfloat16 rounding of the output."""
    c = _latent_case(seed=4)
    rng = np.random.default_rng(9)
    q = jnp.asarray(rng.normal(size=(3, 1, 4, 128)), jnp.bfloat16)
    pool = jnp.asarray(c["pool"], jnp.bfloat16)
    args = (q, pool, c["tbl"], c["pos"])
    kw = dict(scale=c["scale"], value_lanes=c["R"])
    want = np.asarray(pallas_latent.latent_attention(*args, **kw), np.float32)
    got = np.asarray(pallas_latent.latent_attention_fused(
        *args, interpret=True, **kw), np.float32)
    np.testing.assert_allclose(got, want, rtol=1 / 64, atol=1 / 64)


def test_a_row_without_keys_reads_zeros():
    c = _latent_case()
    tbl = np.full_like(c["tbl"], -1)
    got = pallas_latent.latent_attention_fused(
        jnp.ones((3, 1, 4, 128)), jnp.asarray(c["pool"]), tbl, c["pos"],
        scale=0.5, value_lanes=32, interpret=True)
    assert not np.asarray(got).any()


# -- ep.py: the sigmoid routing, and the softmax and ReGLU as they were --------

def test_sigmoid_routing_selects_by_the_bias_and_weighs_without_it():
    """Four experts, top 2: by the scores alone experts 0 and 1 win; the
    bias lifts expert 3 past expert 1. The weights are the chosen
    SCORES (not score + bias), normalised, times the scale."""
    s = np.array([0.9, 0.7, 0.2, 0.6])
    logits = jnp.asarray(np.log(s / (1 - s))[None], jnp.float32)
    bias = jnp.asarray([0.0, 0.0, 0.0, 0.15])
    experts, weights = ep.topk_dropless(logits, 2, scoring="sigmoid",
                                        bias=bias, scale=2.5)
    assert experts.tolist() == [[0, 3]]
    np.testing.assert_allclose(weights, [[2.5 * 0.9 / 1.5, 2.5 * 0.6 / 1.5]],
                               rtol=1e-6)
    experts, weights = ep.topk_dropless(logits, 2, scoring="sigmoid")
    assert experts.tolist() == [[0, 1]]
    np.testing.assert_allclose(weights, [[0.9 / 1.6, 0.7 / 1.6]], rtol=1e-6)
    with pytest.raises(ValueError, match="scoring"):
        ep.topk_dropless(logits, 2, scoring="tanh")


def _old_topk_dropless(logits, k):
    """The softmax routing as it was before the scoring argument."""
    top, experts = jax.lax.top_k(logits.astype(jnp.float32), k)
    return experts.astype(jnp.int32), jax.nn.softmax(top, axis=-1)


@jax.jit
def _old_routed_experts(x, experts, weights, valid, w_in, w_out):
    """The ReGLU experts as they were before the activation argument,
    over `lax.ragged_dot`, jitted as they were."""
    T, d = x.shape
    k = experts.shape[1]
    E, f = w_out.shape[0], w_out.shape[1]
    M = T * k
    tm = 512 if M >= 512 else -(-M // 128) * 128
    Mp = -(-M // tm) * tm
    flat = jnp.where(valid[:, None], experts, E).reshape(M)
    flat = jnp.pad(flat, (0, Mp - M), constant_values=E)
    order = jnp.argsort(flat, stable=True)
    sizes = jnp.sum(flat[:, None] == jnp.arange(E)[None, :], axis=0,
                    dtype=jnp.int32)
    rows = x[jnp.minimum(order // k, T - 1)]
    h = jax.lax.ragged_dot(rows, w_in, sizes,
                           preferred_element_type=jnp.float32)
    act = (jax.nn.relu(h[:, :f]) * h[:, f:]).astype(x.dtype)
    out = jax.lax.ragged_dot(act, w_out, sizes,
                             preferred_element_type=jnp.float32)
    back = jnp.argsort(order)[:M]
    pairs = jnp.where(valid[:, None, None], out[back].reshape(T, k, d), 0.0)
    y = jnp.sum(pairs * weights[..., None], axis=1)
    return y.astype(jnp.float32), jnp.sum(sizes > 0).astype(jnp.int32)


def test_softmax_routing_and_reglu_experts_are_bit_identical_to_before():
    rng = np.random.default_rng(11)
    T, d, E, f, k = 37, 64, 8, 32, 3
    logits = jnp.asarray(rng.normal(size=(T, E)), jnp.float32)
    x = jnp.asarray(rng.normal(size=(T, d)), jnp.bfloat16)
    w_in = jnp.asarray(0.1 * rng.normal(size=(E, d, 2 * f)), jnp.bfloat16)
    w_out = jnp.asarray(0.1 * rng.normal(size=(E, f, d)), jnp.bfloat16)
    valid = jnp.asarray(rng.random(T) > 0.2)
    want = _old_topk_dropless(logits, k)
    got = ep.topk_dropless(logits, k)
    for a, b in zip(got, want):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    y, hit = ep.routed_experts(x, *got, valid, w_in, w_out,
                               impl="ragged_dot", out_dtype=jnp.float32)
    y0, hit0 = _old_routed_experts(x, *want, valid, w_in, w_out)
    np.testing.assert_array_equal(np.asarray(y), np.asarray(y0))
    assert int(hit) == int(hit0)
    silu, _ = ep.routed_experts(x, *got, valid, w_in, w_out,
                                impl="ragged_dot", out_dtype=jnp.float32,
                                activation="silu")
    assert not np.array_equal(np.asarray(silu), np.asarray(y))
    with pytest.raises(ValueError, match="activation"):
        ep.routed_experts(x, *got, valid, w_in, w_out, activation="gelu")


# -- the tolerance refuses a lower precision ---------------------------------------

def test_fp8_operands_miss_the_tolerance():
    _, _, ref_w = _serving("xla")
    seq = _prompts((40,), seed=17)[0]
    where = np.arange(30, 40)
    exact = _reference_logits(ref_w, seq, where)
    fp8 = _reference_logits(ref_w, seq, where, "fp8")
    assert np.abs(fp8 - exact).max() > 100 * TOL["atol"]
