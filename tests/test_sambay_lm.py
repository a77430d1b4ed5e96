"""`models/sambay_lm.py` on the serving path against the plain reference
(`chipbench/families/sambay.py`, which imports nothing of the program):
prefill then decode through ring, pool and both Mamba states, LOGITS
compared with the reference's one full forward pass, at tiny widths on
the CPU. Compute is float32 here, so a tolerance is the rounding of one
arithmetic in another order (the kernels' chunked scan, the pair-head
queries' sqrt(2), the last-token shortcut): 2e-4, where logits spread
over about 0.6; operands through fp8 miss it by three orders (the last
test). Contexts pass the window (8 here) and wrap the ring several
times; the states follow the batch slot."""
import functools
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
from horovod_tpu.models import sambay_lm  # noqa: E402
from horovod_tpu.ops import selective_scan as ss  # noqa: E402
from horovod_tpu.serve import (AdmissionQueue, ContinuousBatcher,  # noqa: E402
                               ShardedExecutor, kv_cache, pool_blocks_for)
from horovod_tpu.serve.executor import _named_leaves  # noqa: E402

FAMILY = mf.load_module("chipbench/families/sambay.py")
CONFIG = harness._merge(
    mf.load_json("chipbench/configs/phi4-mini-flash.json"),
    harness._merge(FAMILY.REHEARSE_CONFIG,
                   {"assumed": {"compute_dtype": "float32"}}))
SHAPE = FAMILY.Shape(CONFIG)      # 8 layers, window 8, d_state 4, d_conv 4
BLOCK, MAX_LEN, ROWS = 4, 64, 3
TOL = dict(rtol=2e-4, atol=2e-4)


@functools.lru_cache(maxsize=None)
def _weights(seed):
    """The seed's weights both ways, made once a process (an executor
    does not consume the tree it is given)."""
    key = FAMILY.seed_key(seed)
    return (jax.jit(lambda k: FAMILY.program_params(SHAPE, k))(key),
            jax.jit(lambda k: FAMILY.reference_weights(SHAPE, k))(key))


def _serving(kernel, seed=0, rows=ROWS):
    model = FAMILY.serve_model(
        SHAPE, CONFIG, kv_block=BLOCK,
        # every row's whole table at once: the tests deal blocks by hand
        kv_pool_blocks=pool_blocks_for(rows, MAX_LEN, BLOCK, fraction=1.0),
        decode_kernel=kernel)
    return (model, *_weights(seed))


_LOGITS_AT = jax.jit(FAMILY.logits_at, static_argnums=(1, 4))


def _reference_logits(ref_w, seq, where, precision="float32"):
    """The reference's logits of `seq` at `where`, the sequence padded
    to one length (causal: the padding is never seen)."""
    tokens = np.zeros((1, MAX_LEN), np.int32)
    tokens[0, :len(seq)] = seq
    return np.asarray(_LOGITS_AT(ref_w, SHAPE, jnp.asarray(tokens),
                                 jnp.asarray(where, jnp.int32), precision))


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
            block_tables=tables, state_slots=slots, mutable=["cache"])

    def step(self, tokens, positions, mask, last_idx, slots=None):
        tokens = np.asarray(tokens, np.int32)
        slots = np.arange(len(tokens)) if slots is None else np.asarray(slots)
        logits, vout = self._apply(
            self.ex.cache, jnp.asarray(tokens),
            jnp.asarray(positions, jnp.int32), jnp.asarray(mask, bool),
            jnp.asarray(last_idx, jnp.int32),
            jnp.asarray(self.tables[slots]), jnp.asarray(slots, jnp.int32))
        self.ex.cache = vout["cache"]
        return np.asarray(logits[:, 0])

    def prefill(self, prompt, slot, bucket):
        """One row, row-compact, into `slot`."""
        tokens = np.zeros((1, bucket), np.int32)
        tokens[0, :len(prompt)] = prompt
        return self.step(tokens, [0], [True], [len(prompt) - 1],
                         slots=[slot])[0]

    def decode(self, last, positions, mask):
        return self.step([[t] for t in last], positions, mask,
                         np.zeros(len(last)))


def _prompts(lengths, seed=1):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, SHAPE.vocab, n).tolist() for n in lengths]


def test_layer_kinds_from_the_halves():
    assert SHAPE.kinds == ("mamba", "window", "mamba", "window", "mamba",
                           "full", "gmu", "cross")
    assert SHAPE.kinds == sambay_lm.layer_kinds(8)
    full = sambay_lm.layer_kinds(32)
    assert [full.count(k) for k in ("mamba", "window", "full", "gmu",
                                    "cross")] == [9, 8, 1, 7, 7]
    assert full[16] == "mamba" and full[17] == "full" and full[15] == "window"
    with pytest.raises(ValueError, match="multiple of 4"):
        sambay_lm.layer_kinds(6)


@pytest.mark.parametrize("kernel", ["pallas", "xla"])
def test_prefill_then_decode_matches_the_reference(kernel):
    """Row 0 a prompt of 26, row 1 of 41 (both past the window of 8:
    the prefill fills each ring with the prompt's LAST 8 tokens), row 2
    idle; then 12 decode steps, the ring wrapped once more and a half.
    Logits at every emitting position against the reference's full
    forward pass; a padded bucket's tail (48 - 26 tokens) must leave no
    trace in a state or a ring."""
    d = Driver(kernel)
    seqs = _prompts((26, 41))
    bucket = 48
    tokens = np.zeros((ROWS, bucket), np.int32)
    for r, p in enumerate(seqs):
        tokens[r, :len(p)] = p
    mask = np.array([True, True, False])
    got = d.step(tokens, np.zeros(ROWS), mask,
                 [len(seqs[0]) - 1, len(seqs[1]) - 1, 0])
    for r in range(2):
        want = _reference_logits(d.ref_w, seqs[r], [len(seqs[r]) - 1])[0]
        np.testing.assert_allclose(got[r], want, **TOL)
    for _ in range(12):
        nxt = got.argmax(-1)
        positions = [len(seqs[0]), len(seqs[1]), 0]
        for r in range(2):
            seqs[r].append(int(nxt[r]))
        got = d.decode([seqs[0][-1], seqs[1][-1], 0], positions, mask)
        for r in range(2):
            want = _reference_logits(d.ref_w, seqs[r], [len(seqs[r]) - 1])[0]
            np.testing.assert_allclose(got[r], want, **TOL)


@pytest.mark.parametrize("first", [5, 30])
def test_a_slot_reused_by_a_second_sequence_starts_clean(first):
    """A sequence leaves its ring, conv and SSM state in slot 1; the
    next one there, prefilled (position 0) and decoded, answers as if
    the slot were new: shorter than the window (the ring's stale slots
    are never read) and longer."""
    d = Driver("pallas")
    old, new = _prompts((37, first), seed=3)
    d.prefill(old, 1, 48)
    for i in range(3):
        d.decode([0, 7 + i, 0], [0, len(old) + i, 0], [False, True, False])
    got = d.prefill(new, 1, 32)
    np.testing.assert_allclose(
        got, _reference_logits(d.ref_w, new, [len(new) - 1])[0], **TOL)
    for _ in range(4):
        new.append(int(got.argmax()))
        got = d.decode([0, new[-1], 0], [0, len(new) - 1, 0],
                       [False, True, False])[1]
        np.testing.assert_allclose(
            got, _reference_logits(d.ref_w, new, [len(new) - 1])[0], **TOL)


def test_a_one_token_sequence_starts_a_slot_from_zero_in_a_decode_step():
    """A row at position 0 in a DECODE step (the kernel's own reset, not
    the prefill's) ignores what the slot held."""
    d = Driver("pallas")
    d.prefill(_prompts((29,), seed=5)[0], 0, 32)
    got = d.decode([11, 0, 0], [0, 0, 0], [True, False, False])[0]
    np.testing.assert_allclose(
        got, _reference_logits(d.ref_w, [11], [0])[0], **TOL)


@pytest.mark.parametrize("kernel", ["pallas", "xla"])
def test_a_masked_row_leaves_its_leaves_untouched(kernel):
    """Row 1 sits out three decode steps (its token, position and table
    are whatever the batcher left there): ring, conv and SSM state come
    through bit for bit, and its next step answers as the reference."""
    d = Driver(kernel)
    seqs = _prompts((19, 23), seed=7)
    for r in (0, 1):
        got = d.prefill(seqs[r], r, 32)
        seqs[r].append(int(got.argmax()))
    rows = [np.asarray(x[1]) for x in jax.tree_util.tree_leaves(d.ex.cache)
            if x.shape[0] == ROWS]
    for i in range(3):
        got = d.decode([seqs[0][-1], 99, 0], [len(seqs[0]) - 1, 5 + i, 0],
                       [True, False, False])
        seqs[0].append(int(got[0].argmax()))
    after = [np.asarray(x[1]) for x in jax.tree_util.tree_leaves(d.ex.cache)
             if x.shape[0] == ROWS]
    assert len(rows) == 3 * 2 + 2 * 2       # 3 Mamba and 2 window layers
    for a, b in zip(rows, after):
        np.testing.assert_array_equal(a, b)
    got = d.decode([0, seqs[1][-1], 0], [0, len(seqs[1]) - 1, 0],
                   [False, True, False])[1]
    np.testing.assert_allclose(
        got, _reference_logits(d.ref_w, seqs[1], [len(seqs[1]) - 1])[0],
        **TOL)


def test_row_compact_prefill_addresses_the_slots_leaves():
    """One row a prefill step, into slot 2 and then slot 0: the step's
    row 0 is not batch row 0, and the other slots' leaves stay zero."""
    d = Driver("pallas")
    seqs = _prompts((33, 21), seed=9)
    for slot, seq in zip((2, 0), seqs):
        got = d.prefill(seq, slot, 48)
        np.testing.assert_allclose(
            got, _reference_logits(d.ref_w, seq, [len(seq) - 1])[0], **TOL)
    for leaf in jax.tree_util.tree_leaves(d.ex.cache):
        if leaf.shape[0] == ROWS:
            assert not np.asarray(leaf[1]).any()
            assert np.asarray(leaf[2]).any()
    got = d.decode([5, 0, 6], [len(seqs[1]), 0, len(seqs[0])],
                   [True, False, True])
    for r, seq, tok in ((0, seqs[1], 5), (2, seqs[0], 6)):
        np.testing.assert_allclose(
            got[r], _reference_logits(d.ref_w, seq + [tok], [len(seq)])[0],
            **TOL)


def test_the_cross_decoder_on_the_last_token_is_every_layer_on_every_token():
    """The prefill runs layers above the full one at `logits_idx` only.
    Prefilling EVERY prefix of a prompt, each into a fresh slot, gives
    at each length the logits the reference has there from one pass that
    ran every layer at every position."""
    d = Driver("xla", rows=1)
    prompt = _prompts((13,), seed=11)[0]
    want = _reference_logits(d.ref_w, prompt, np.arange(len(prompt)))
    for n in range(1, len(prompt) + 1):
        np.testing.assert_allclose(d.prefill(prompt[:n], 0, 16),
                                   want[n - 1], **TOL)


def test_through_the_executor_and_the_batcher():
    """`AdmissionQueue.submit` -> `ContinuousBatcher.step` ->
    `ShardedExecutor.step`: five requests over three slots (two slots
    are reused), each served token the reference's own greedy choice at
    its position within a margin no rounding reaches; decode spans carry
    the two counters, at the values the sizes give."""
    from horovod_tpu.trace.spans import get_recorder
    model, params, ref_w = _serving("pallas")
    ex = ShardedExecutor(model, params, max_batch=ROWS, max_len=MAX_LEN)
    queue = AdmissionQueue(max_queue=8)
    batcher = ContinuousBatcher(ex, queue, buckets=(32, 48), kv_crc=False,
                                spec_k=0)
    batcher.warmup()
    prompts = _prompts((22, 40, 9, 31, 17), seed=13)
    handles = [queue.submit(p, max_new_tokens=6, temperature=0.0)
               for p in prompts]
    t0 = get_recorder().now()
    for _ in range(80):
        batcher.step()
        if all(h.done() for h in handles):
            break
    for p, h in zip(prompts, handles):
        assert h.status == "ok" and len(h.tokens) == 6
        seq = p + list(h.tokens)
        lg = _reference_logits(ref_w, seq, len(p) - 1 + np.arange(6))
        gap = lg.max(-1) - lg[np.arange(6), h.tokens]
        assert gap.max() <= 2e-4, gap
    decode = [s for s in get_recorder().between(t0, get_recorder().now())
              if s.name == "exec_step" and s.extra.get("kind") == "decode"]
    assert decode
    cfg = ex.model.cfg
    per_token, row = cfg.cache_token_bytes, cfg.cache_row_bytes
    assert per_token == 2 * SHAPE.kv_heads * SHAPE.head_dim * 4
    # three Mamba layers' two states, two window layers' rings of 8
    assert row == 3 * 4 * 128 * (4 + 4) + 2 * 8 * per_token
    for s in decode:
        e = s.extra
        assert e["state_rows"] == e["rows"]
        # whole blocks of BLOCK tokens cover the live rows' contexts
        held = (e["cache_bytes_held"] - e["rows"] * row) // per_token
        assert e["context_tokens"] <= held \
            <= e["context_tokens"] + 2 * BLOCK * e["rows"]


# -- the scan: kernel, chunks, definition --------------------------------------

def _scan_inputs(B=2, T=37, d=256, N=4, seed=0):
    rng = np.random.default_rng(seed)
    f = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)  # noqa: E731
    return dict(H0=f(B, N, d), dt=jax.nn.softplus(f(B, T, d)), c=f(B, T, d),
                Bm=f(B, T, N), Cm=f(B, T, N), A=-jnp.exp(0.3 * f(N, d)),
                D=f(d))


@pytest.mark.parametrize("chunk, lanes", [(8, 128), (16, 256), (24, None),
                                          (64, None), (5, 128)])
@pytest.mark.parametrize("start", ["zero", "state"])
def test_prefill_scan_matches_the_recurrence(chunk, lanes, start):
    """Chunks that divide 37 tokens and chunks that do not (5 is rounded
    up to 8, a whole tile of tokens), from zero and from a state a row
    brought along; row 1's last 17 tokens are padding and enter
    nothing."""
    x = _scan_inputs()
    H0 = x["H0"] if start == "state" else jnp.zeros_like(x["H0"])
    nv = jnp.asarray([37, 20])
    args = (H0, x["dt"], x["c"], x["Bm"], x["Cm"], x["A"], x["D"], nv)
    want_y, want_s = ss.ssm_recurrence(*args)
    y, s = ss.ssm_prefill(*args, chunk=chunk, lanes=lanes, interpret=True)
    np.testing.assert_allclose(y[0], want_y[0], rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(y[1, :20], want_y[1, :20], rtol=1e-5,
                               atol=1e-5)
    np.testing.assert_allclose(s, want_s, rtol=1e-5, atol=1e-5)
    # the state after 20 real tokens is the state a 20-token scan leaves
    _, s20 = ss.ssm_recurrence(H0[1:], *(v[1:, :20] for v in (
        x["dt"], x["c"], x["Bm"], x["Cm"])), x["A"], x["D"],
        jnp.asarray([20]))
    np.testing.assert_allclose(s[1], s20[0], rtol=1e-5, atol=1e-5)


def test_a_row_out_of_the_prefill_keeps_its_state():
    x = _scan_inputs(seed=1)
    y, s = ss.ssm_prefill(x["H0"], x["dt"], x["c"], x["Bm"], x["Cm"],
                          x["A"], x["D"], jnp.asarray([0, 37]),
                          interpret=True)
    np.testing.assert_array_equal(s[0], x["H0"][0])


@pytest.mark.parametrize("live", [(True, True, True), (True, False, True)])
def test_decode_kernel_is_bit_exact_against_its_oracle(live):
    """`ssm_decode` in interpret mode against `ssm_decode_reference`,
    both under jit: y, the conv state and the SSM state bit for bit; row
    0 at position 0 (reset), a masked row handed back as it came."""
    x = _scan_inputs(B=3, T=1, seed=2)
    rng = np.random.default_rng(3)
    conv = jnp.asarray(rng.normal(size=(3, 4, 256)), jnp.float32)
    a = jnp.asarray(rng.normal(size=(3, 256)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(4, 256)), jnp.float32)
    pos = jnp.asarray([0, 5, 9])
    c = ss.conv_step(conv, a, w, x["D"], pos)
    args = (conv, x["H0"], a, c, x["dt"][:, 0], x["Bm"][:, 0], x["Cm"][:, 0],
            x["A"], x["D"], pos, jnp.asarray(live))
    got = ss.ssm_decode(*args, interpret=True)
    want = jax.jit(ss.ssm_decode_reference)(*args)
    for g, w_ in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w_))
    if not live[1]:
        np.testing.assert_array_equal(got[1][1], conv[1])
        np.testing.assert_array_equal(got[2][1], x["H0"][1])
    # the reset row: nothing of the old states in the new ones
    np.testing.assert_array_equal(got[1][0, :3], jnp.zeros((3, 256)))
    np.testing.assert_array_equal(got[1][0, 3], a[0])


def test_conv_step_continues_the_causal_convolution():
    """Token by token from a zero state, `conv_step` gives what
    `causal_conv` gives over the sequence, and the state it is handed is
    the last four inputs."""
    rng = np.random.default_rng(4)
    a = jnp.asarray(rng.normal(size=(2, 11, 32)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(4, 32)), jnp.float32)
    b = jnp.asarray(rng.normal(size=(32,)), jnp.float32)
    nv = jnp.asarray([11, 6])
    c, new = ss.causal_conv(jnp.zeros((2, 4, 32)), a, w, b, nv)
    state = jnp.zeros((2, 4, 32))
    for t in range(11):
        ct = ss.conv_step(state, a[:, t], w, b, jnp.asarray([t, t]))
        np.testing.assert_allclose(ct, c[:, t], rtol=1e-6, atol=1e-6)
        state = jnp.concatenate([state[:, 1:], a[:, t:t + 1]], axis=1)
    np.testing.assert_array_equal(new[0], a[0, 7:11])
    np.testing.assert_array_equal(new[1], a[1, 2:6])


# -- pair-heads ------------------------------------------------------------------

def test_pair_head_attention_is_the_two_softmaxes_written_out():
    """K and V held as pair-heads and queries as ``[q1 | 0]``, ``[0 |
    q2]`` times sqrt(2), through the paged kernel's oracle AND the
    kernel: each output is the softmax of ONE head's scores over the
    pair's V."""
    rng = np.random.default_rng(5)
    L, H, KV, D, BS = 21, 8, 4, 16, 4
    q = jnp.asarray(rng.normal(size=(1, 1, H, D)), jnp.float32)
    k, v = (jnp.asarray(rng.normal(size=(L, KV, D)), jnp.float32)
            for _ in range(2))
    nblk = -(-L // BS)
    pad = lambda x: jnp.pad(x, ((0, nblk * BS - L), (0, 0), (0, 0)))  # noqa: E731
    pool_k = pad(k).reshape(nblk, BS, KV // 2, 2 * D)
    pool_v = pad(v).reshape(nblk, BS, KV // 2, 2 * D)
    tables = jnp.arange(nblk, dtype=jnp.int32)[None]
    pos = jnp.asarray([L - 1])
    qp = sambay_lm.pair_queries(q, jnp.float32)
    want = np.zeros((H, 2 * D), np.float32)
    for h in range(H):
        # query head h is of pair h // 2, which reads KV pair h // 4:
        # its first head against k_{2j}, its second against k_{2j+1}
        j = h // 4
        kh = k[:, 2 * j + h % 2]
        p = jax.nn.softmax(q[0, 0, h] @ kh.T / np.sqrt(D))
        want[h] = p @ jnp.concatenate([v[:, 2 * j], v[:, 2 * j + 1]], -1)
    oracle = kv_cache.paged_attention(qp, pool_k, pool_v, tables, pos)
    np.testing.assert_allclose(oracle[0, 0], want, rtol=1e-5, atol=1e-5)
    from horovod_tpu.ops.pallas_paged import paged_attention_fused
    fused = paged_attention_fused(qp, pool_k, pool_v, tables, pos,
                                  interpret=True)
    np.testing.assert_allclose(fused[0, 0], want, rtol=1e-5, atol=1e-5)


# -- the cache's leaves -----------------------------------------------------------

def _executor(rows=2):
    model, params, _ = _serving("xla", rows=rows)
    return ShardedExecutor(model, params, max_batch=rows, max_len=MAX_LEN)


def test_the_kinds_of_cache_leaf():
    """ONE K/V pool pair (the full layer's; the cross layer owns no
    leaf), and per-row leaves: conv and SSM state a Mamba layer, a ring
    pair a window layer, no ring longer than the window. A block copy
    moves the pool and leaves every per-row leaf; the gauge counts
    states and rings of the live rows."""
    ex = _executor(rows=8)
    names = [n for n, _ in _named_leaves(ex.cache)]
    assert sorted(names) == sorted(
        ["conv", "ssm"] * 3 + ["ring_k", "ring_v"] * 2 + ["k", "v"])
    assert ex._leaf_kinds.count("kv") == 2
    assert ex._leaf_kinds.count("row") == 10
    # the memory unit and the cross layer own nothing in the cache
    assert sorted(ex.cache) == [f"layers_{i}" for i in range(6)]
    assert sorted(ex.cache["layers_5"]["mixer"]) == ["k", "v"]
    for name, leaf in zip(names, jax.tree_util.tree_leaves(ex.cache)):
        if name.startswith("ring"):
            assert leaf.shape[1] * leaf.shape[2] == SHAPE.window
    # held as the device lays them out: 2 pair-heads of 32 here, unpadded
    d, N, K, W = SHAPE.d_inner, SHAPE.d_state, SHAPE.d_conv, SHAPE.window
    assert ex.state_row_bytes == 3 * 4 * d * (N + K) \
        + 2 * W * 2 * SHAPE.kv_heads * SHAPE.head_dim * 4
    leaves, treedef = jax.tree_util.tree_flatten(ex.cache)
    ex.cache = jax.tree_util.tree_unflatten(treedef, [
        x.at[1].set(1.0) for x in leaves])
    ex.copy_kv_block(1, 5)
    for kind, leaf in zip(ex._leaf_kinds,
                          jax.tree_util.tree_leaves(ex.cache)):
        assert bool(np.asarray(leaf[5]).all()) is (kind == "kv")
    assert len(ex.kv_block_bytes(1, 0, 2)) == 2
    queue = AdmissionQueue(max_queue=4)
    batcher = ContinuousBatcher(ex, queue, buckets=(32,), kv_crc=False,
                                spec_k=0)
    queue.submit([1, 2, 3], max_new_tokens=3, temperature=0.0)
    batcher.step()
    from horovod_tpu.obs import metrics as obs_metrics
    gauge = obs_metrics.get_registry().get("hvd_serve_state_bytes")
    assert gauge.value == ex.state_row_bytes        # one live row


def test_no_other_models_decode_step_carries_the_cache_counters():
    from horovod_tpu.models.gpt import GPT, GPTConfig
    from horovod_tpu.trace.spans import get_recorder
    kw = dict(vocab_size=64, num_layers=1, num_heads=2, head_dim=8,
              max_seq_len=32)
    params = GPT(GPTConfig(**kw)).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32))["params"]
    ex = ShardedExecutor(GPT(GPTConfig(decode=True, **kw)), params,
                         max_batch=2, max_len=32)
    t0 = get_recorder().now()
    ex.step(np.zeros((2, 1), np.int32), np.zeros(2, np.int32),
            np.ones(2, bool), np.zeros(2, np.int32), kind="decode",
            block_tables=np.zeros((2, ex.blocks_per_seq), np.int32))
    (span,) = [s for s in get_recorder().between(t0, get_recorder().now())
               if s.name == "exec_step"]
    assert not {"context_tokens", "cache_bytes_held", "state_rows"} \
        & set(span.extra)


# -- what cannot run yet refuses by name ------------------------------------------

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
    assert "SambaYLM" in str(err.value)


def test_migration_refuses_per_row_state():
    from horovod_tpu.serve import kv_migrate
    ex = _executor()
    batcher = ContinuousBatcher(ex, AdmissionQueue(max_queue=4),
                                buckets=(32,), kv_crc=False, spec_k=0)
    assert batcher.prefix is None and batcher.kvtier is None
    with pytest.raises(ValueError, match="migration of SambaYLM"):
        kv_migrate.pack_parked(batcher, 0, fid="f", max_new_tokens=4,
                               deadline_ms=1000.0)
    with pytest.raises(ValueError, match="migration of SambaYLM"):
        batcher.submit_migrated({}, [])
    with pytest.raises(ValueError, match="no K/V pool"):
        ex.install_kv_blocks([0], [[b""]], [0])


# -- the tolerance refuses a lower precision ---------------------------------------

def test_fp8_operands_miss_the_tolerance():
    """The reference itself with matmul operands through fp8 misses the
    tolerance the program is held to by orders of magnitude."""
    _, _, ref_w = _serving("xla")
    seq = _prompts((40,), seed=17)[0]
    where = np.arange(30, 40)
    exact = _reference_logits(ref_w, seq, where)
    fp8 = _reference_logits(ref_w, seq, where, "fp8")
    assert np.abs(fp8 - exact).max() > 100 * TOL["atol"]
