"""`ShardedExecutor` holds each parameter in the dtype the step
multiplies it in (serve/executor.py, "Resident dtypes"): what is cast
and what is not, that the numbers are the ones the float32 tree gives,
`swap_params`' dtype contract, and that the constructor builds its
state from one trace of the model and no program that holds its
forward. Single process, CPU; Pallas in interpret mode."""
import logging
import time
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.models.gpt import GPT, GPTConfig
from horovod_tpu.models.routed_lm import RoutedLM, RoutedLMConfig
from horovod_tpu.serve import ShardedExecutor
from horovod_tpu.trace.spans import get_recorder

_KW = dict(vocab_size=64, num_layers=2, num_heads=2, head_dim=8,
           max_seq_len=48, attention_impl="reference")
_PAGED = dict(decode=True, kv_block_size=4, kv_pool_blocks=24)
_ROWS, _MAX_LEN, _BUCKET, _DECODES = 2, 48, 8, 6
_PROMPTS = [[5, 9, 2, 41, 7], [11, 3, 60, 8, 1, 33, 2]]


def _gpt_params(**kw):
    return GPT(GPTConfig(**dict(_KW, **kw))).init(
        jax.random.PRNGKey(0), jnp.zeros((2, 8), jnp.int32))["params"]


def _gpt_executor(params, kernel="xla", **kw):
    model = GPT(GPTConfig(decode_kernel=kernel, **_PAGED,
                          **dict(_KW, **kw)))
    return ShardedExecutor(model, params, max_batch=_ROWS,
                           max_len=_MAX_LEN)


def _routed():
    cfg = RoutedLMConfig(vocab_size=64, num_layers=2, embed_dim=32,
                         num_heads=2, head_dim=16, num_experts=4,
                         experts_per_token=2, expert_dim=16, window=8,
                         max_seq_len=_MAX_LEN, decode_kernel="xla",
                         kv_block_size=4, kv_pool_blocks=24)
    model = RoutedLM(cfg)
    z = jnp.zeros((_ROWS,), jnp.int32)
    params = model.init(
        jax.random.PRNGKey(0), jnp.zeros((_ROWS, 1), jnp.int32),
        positions=z, update_mask=jnp.zeros((_ROWS,), bool),
        block_tables=jnp.full((_ROWS, 12), -1, jnp.int32))["params"]
    # a checkpoint published in bfloat16: every leaf, the norms' too
    return model, jax.tree_util.tree_map(
        lambda x: x.astype(jnp.bfloat16), params)


def _tables(ex):
    n = ex.blocks_per_seq
    return np.arange(_ROWS * n, dtype=np.int32).reshape(_ROWS, n)


def _prompt_batch():
    """`_PROMPTS` padded to the bucket, and each row's length."""
    tokens = np.zeros((_ROWS, _BUCKET), np.int32)
    for r, p in enumerate(_PROMPTS):
        tokens[r, :len(p)] = p
    return tokens, np.array([len(p) for p in _PROMPTS], np.int32)


def _first_step(ex):
    """One prefill of `_PROMPTS` through `ex.step`: the greedy tokens."""
    tokens, lengths = _prompt_batch()
    return ex.step(tokens, np.zeros(_ROWS, np.int32),
                   np.ones(_ROWS, bool), lengths - 1, kind="prefill",
                   block_tables=_tables(ex))


# -- (a) the numbers are the float32 tree's -----------------------------------

def _drive(ex, params):
    """A prefill and `_DECODES` greedy decode steps of `ex.model` over
    `params`, by `model.apply` (float32 logits out): what an executor
    that kept the tree as given computes. -> {kind: (logits, tokens)}"""
    tables = jnp.asarray(_tables(ex))

    @jax.jit
    def apply(cache, tokens, positions, last_idx):
        logits, v = ex.model.apply(
            {"params": params, "cache": cache}, tokens,
            positions=positions, update_mask=jnp.ones((_ROWS,), bool),
            logits_idx=last_idx, block_tables=tables, mutable=["cache"])
        return logits[:, 0], v["cache"]

    tokens, lengths = _prompt_batch()
    cache = jax.tree_util.tree_map(jnp.zeros_like, ex.cache)
    logits, cache = apply(cache, jnp.asarray(tokens),
                          jnp.zeros((_ROWS,), jnp.int32),
                          jnp.asarray(lengths - 1))
    out = {"prefill": ([np.asarray(logits)],
                       [np.asarray(logits).argmax(-1)])}
    dl, dt = [], []
    for i in range(_DECODES):
        nxt = np.asarray(logits).argmax(-1).astype(np.int32)
        logits, cache = apply(cache, jnp.asarray(nxt[:, None]),
                              jnp.asarray(lengths + i),
                              jnp.zeros((_ROWS,), jnp.int32))
        dl.append(np.asarray(logits))
        dt.append(np.asarray(logits).argmax(-1))
    out["decode"] = (dl, dt)
    return out


def _serve(ex):
    """The same prefill and decode steps through `ex.step`."""
    nxt = _first_step(ex)
    out = {"prefill": [nxt], "decode": []}
    _, lengths = _prompt_batch()
    for i in range(_DECODES):
        nxt = ex.step(nxt[:, None].astype(np.int32), lengths + i,
                      np.ones(_ROWS, bool), np.zeros(_ROWS, np.int32),
                      kind="decode", block_tables=_tables(ex))
        out["decode"].append(nxt)
    return out


@pytest.fixture(scope="module")
def driven():
    """Per kernel: the given float32 tree and the resident tree through
    `model.apply`, and the executor's own steps."""
    given = _gpt_params()
    out = {}
    for kernel in ("xla", "pallas"):
        ex = _gpt_executor(given, kernel)
        assert ex._cast_idx, "nothing was cast: the test compares a " \
            "tree with itself"
        out[kernel] = SimpleNamespace(given=_drive(ex, given),
                                      resident=_drive(ex, ex.params),
                                      served=_serve(ex))
    return out


@pytest.mark.parametrize("kernel", ["xla", "pallas"])
@pytest.mark.parametrize("kind", ["prefill", "decode"])
def test_resident_tree_gives_the_float32_trees_numbers(driven, kind, kernel):
    d = driven[kernel]
    for got, want in zip(d.resident[kind][0], d.given[kind][0]):
        assert got.dtype == np.float32
        np.testing.assert_array_equal(got, want)       # bit for bit
    for served, want in zip(d.served[kind], d.given[kind][1]):
        np.testing.assert_array_equal(served, want)


# -- (b) which leaves are held in which dtype ---------------------------------

_GROUPS = {
    "dense_kernels": (lambda p: p[-1] == "kernel" and p[-2] != "lm_head",
                      jnp.bfloat16),
    "dense_biases": (lambda p: p[-1] == "bias" and not p[-2].startswith("ln"),
                     jnp.bfloat16),
    "layernorm": (lambda p: p[-2].startswith("ln"), jnp.float32),
    "embed": (lambda p: p[0] == "embed", jnp.float32),
    "pos_embed": (lambda p: p[0] == "pos_embed", jnp.float32),
    "lm_head": (lambda p: p[0] == "lm_head", jnp.float32),
}


def _by_path(tree):
    return {tuple(k.key for k in path): leaf for path, leaf in
            jax.tree_util.tree_flatten_with_path(tree)[0]}


@pytest.fixture(scope="module")
def gpt_bf16():
    given = _gpt_params()
    return given, _gpt_executor(given)


@pytest.mark.parametrize("group", list(_GROUPS))
def test_gpt_resident_dtype_by_leaf_group(gpt_bf16, group):
    given, ex = gpt_bf16
    member, dtype = _GROUPS[group]
    held, was = _by_path(ex.params), _by_path(given)
    paths = [p for p in held if member(p)]
    assert paths
    for p in paths:
        assert was[p].dtype == jnp.float32
        assert held[p].dtype == dtype, p
        if dtype == jnp.float32:
            assert held[p] is was[p]            # passed on, not copied
        else:
            np.testing.assert_array_equal(
                np.asarray(held[p]), np.asarray(was[p].astype(dtype)))
    assert set().union(*(
        {p for p in held if m(p)} for m, _ in _GROUPS.values())) == set(held)


def _same_arrays(a, b):
    return all(x is y for x, y in zip(jax.tree_util.tree_leaves(a),
                                      jax.tree_util.tree_leaves(b)))


def test_float32_compute_casts_nothing():
    given = _gpt_params(dtype=jnp.float32)
    ex = _gpt_executor(given, dtype=jnp.float32)
    assert ex._cast_idx == [] and _same_arrays(ex.params, given)


def test_bfloat16_checkpoint_is_held_as_given():
    """Every `RoutedLM` leaf arrives in the compute dtype, and the
    router and the head are WIDENED at use: nothing to cast."""
    model, given = _routed()
    assert {x.dtype for x in jax.tree_util.tree_leaves(given)} == \
        {jnp.dtype(jnp.bfloat16)}
    ex = ShardedExecutor(model, given, max_batch=_ROWS, max_len=_MAX_LEN)
    assert ex._cast_idx == [] and _same_arrays(ex.params, given)


# -- (c) swap_params' dtype contract ------------------------------------------

def _other(tree):
    return jax.tree_util.tree_map(lambda x: x + 0.1 * jnp.sign(x + 0.5), tree)


@pytest.fixture()
def swapping():
    given = _gpt_params()
    ex = _gpt_executor(given)
    _first_step(ex)
    return given, ex


def _swap_given(given, ex):
    assert ex.swap_params(_other(given), version=2) is True
    want = _gpt_executor(_other(given))
    for a, b in zip(jax.tree_util.tree_leaves(ex.params),
                    jax.tree_util.tree_leaves(want.params)):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def _swap_resident(given, ex):
    tree = _gpt_executor(_other(given)).params
    assert ex.swap_params(tree, version=2) is True
    assert _same_arrays(ex.params, tree)


def _swap_wrong_shape(given, ex):
    bad = jax.tree_util.tree_map(lambda x: x, given)
    bad["lm_head"]["kernel"] = jnp.zeros((16, 65), jnp.float32)
    with pytest.raises(ValueError, match="shape"):
        ex.swap_params(bad, version=2)


def _swap_foreign_dtype(given, ex):
    f16 = jax.tree_util.tree_map(lambda x: x.astype(jnp.float16), given)
    with pytest.raises(ValueError, match="dtype"):
        ex.swap_params(f16, version=2)
    mixed = jax.tree_util.tree_map(lambda x: x, given)     # half-cast
    mixed["lm_head"]["kernel"] = given["lm_head"]["kernel"].astype(
        jnp.bfloat16)
    with pytest.raises(ValueError, match="dtype"):
        ex.swap_params(mixed, version=2)


def _swap_stale_version(given, ex):
    assert ex.swap_params(given, version=5) is True
    assert ex.swap_params(_other(given), version=5) is False
    assert ex.swap_params(_other(given), version=4) is False
    assert ex.params_version == 5 and ex.swaps == 1


def _swap_keeps_the_jit_cache_flat(given, ex):
    before = ex.jit_cache_size()
    first = _first_step(ex)
    ex.swap_params(_other(given), version=2)           # given dtypes
    ex.swap_params(_gpt_executor(given).params, version=3)   # resident
    assert ex.jit_cache_size() == before
    np.testing.assert_array_equal(_first_step(ex), first)
    assert ex.jit_cache_size() == before
    assert ex._cast._cache_size() == 1      # the constructor's program


@pytest.mark.parametrize("case", [
    _swap_given, _swap_resident, _swap_wrong_shape, _swap_foreign_dtype,
    _swap_stale_version, _swap_keeps_the_jit_cache_flat],
    ids=lambda f: f.__name__.lstrip("_"))
def test_swap_params(swapping, case):
    case(*swapping)


# -- (d) the constructor: one trace, no forward compiled ----------------------

@pytest.fixture()
def compiled_names(caplog):
    """-> a function: the names of the programs compiled since it was
    last called (jax logs each at WARNING under `jax_log_compiles`)."""
    seen = [0]

    def since():
        new, seen[0] = caplog.records[seen[0]:], len(caplog.records)
        return [r.getMessage().split()[1] for r in new
                if r.getMessage().startswith("Compiling ")]
    with jax.log_compiles(True), caplog.at_level(logging.WARNING, "jax"):
        yield since


def test_constructor_compiles_no_forward(compiled_names, monkeypatch):
    given = _gpt_params()
    calls = []
    inner = GPT.__call__
    monkeypatch.setattr(
        GPT, "__call__",
        lambda self, *a, **k: (calls.append(1), inner(self, *a, **k))[1])
    compiled_names()
    ex = _gpt_executor(given, "pallas")
    names = compiled_names()
    assert len(calls) == 1                  # the model is traced ONCE
    # the one cast, and eager `jnp.zeros` (a broadcast) where this
    # process has not made such zeros before: nothing that could hold a
    # matmul, and no cache-making forward
    assert "jit(resident_cast)" in names, names
    assert set(names) <= {"jit(resident_cast)", "jit(broadcast_in_dim)"}, \
        names
    assert ex.jit_cache_size() == 0


@pytest.mark.parametrize("arch", ["gpt", "routed"])
def test_cache_is_the_zeros_a_forward_would_make(arch):
    if arch == "gpt":
        params = _gpt_params()
        ex = _gpt_executor(params)
        model = ex.model
    else:
        model, params = _routed()
        ex = ShardedExecutor(model, params, max_batch=_ROWS,
                             max_len=_MAX_LEN)
    z = jnp.zeros((_ROWS,), jnp.int32)
    _, made = jax.jit(lambda p: model.apply(
        {"params": p}, jnp.zeros((_ROWS, 1), jnp.int32), positions=z,
        update_mask=jnp.zeros((_ROWS,), bool),
        block_tables=jnp.full((_ROWS, ex.blocks_per_seq), -1, jnp.int32),
        mutable=["cache"]))(params)
    want = jax.tree_util.tree_flatten_with_path(made["cache"])[0]
    got = jax.tree_util.tree_flatten_with_path(ex.cache)[0]
    assert [p for p, _ in got] == [p for p, _ in want] and got
    for (_, a), (_, b) in zip(got, want):
        assert a.shape == b.shape and a.dtype == b.dtype
        assert not np.asarray(a).any() and not np.asarray(b).any()
    assert bool(ex._stat_names) is (arch == "routed")


# -- (e) the caller's tree is not consumed ------------------------------------

def test_two_executors_from_one_given_tree_both_step():
    given = _gpt_params()
    a, b = _gpt_executor(given), _gpt_executor(given)
    np.testing.assert_array_equal(_first_step(a), _first_step(b))
    assert not any(x.is_deleted()
                   for x in jax.tree_util.tree_leaves(given))
    assert _gpt_executor(given)._cast_idx       # and a third can be built


def test_a_committed_tree_gets_a_committed_cache():
    """Committed or not is in jit's signature: the zero cache of a tree
    the caller put on a device must be committed there like the cache
    every step hands back, or the second step is a second program."""
    given = jax.device_put(_gpt_params(), jax.devices()[0])
    ex = _gpt_executor(given)
    assert all(x.committed for x in jax.tree_util.tree_leaves(ex.cache))
    _first_step(ex)
    once = ex.jit_cache_size()
    _first_step(ex)
    assert ex.jit_cache_size() == once == 1


# -- (f) the pools are held in the shape the device lays out row-major --------
# (`kv_cache.write_kv_pools`), and whoever writes `executor.cache` hands
# every pool back as it was held, so the next step neither compiles
# again nor re-lays a pool out.

@pytest.mark.parametrize("logical,held", [
    ((160, 16, 25, 64), (160, 16, 32, 128)),        # GPT-2 XL
    ((96, 16, 16, 64), (96, 16, 16, 128)),          # GPT-2 medium
    ((96, 16, 12, 64), (96, 16, 16, 128)),          # GPT-2
    ((64, 16, 4, 96), (64, 16, 4, 128)),
    ((800, 128, 4, 128), (800, 128, 4, 128)),       # whole tiles: as it is
    ((64, 16, 8, 256), (64, 16, 8, 256)),
    ((64, 16, 5, 128), (64, 16, 8, 128)),
    ((24, 4, 2, 8), (24, 4, 2, 8)),                 # the tests' heads
    ((24, 4, 3, 16), (24, 4, 3, 16)),
], ids=str)
def test_held_pool_shape(logical, held):
    from horovod_tpu.serve.kv_cache import held_pool_shape
    assert held_pool_shape(*logical) == held


def test_padded_pools_serve_the_tokens_of_the_whole_sequence():
    """3 heads of 64 are held as 8 of 128 (the tests' other models are
    too narrow to be padded): the executor's greedy tokens, prefill and
    decode, are those of the model run over the whole sequence with no
    cache at all."""
    kw = dict(_KW, num_heads=3, head_dim=64)
    params = _gpt_params(num_heads=3, head_dim=64)
    ex = _gpt_executor(params, num_heads=3, head_dim=64)
    assert {x.shape for x in ex._cache_leaves()} == {(24, 4, 8, 128)}
    whole = jax.jit(GPT(GPTConfig(**kw)).apply)
    seqs = [list(p) for p in _PROMPTS]
    served = _serve(ex)
    for step in served["prefill"] + served["decode"]:
        for seq, tok in zip(seqs, step):
            padded = np.zeros((1, _MAX_LEN), np.int32)
            padded[0, :len(seq)] = seq
            logits = whole({"params": params}, jnp.asarray(padded))
            assert int(tok) == int(jnp.argmax(logits[0, len(seq) - 1]))
            seq.append(int(tok))


_SPARE = _ROWS * 12 - 1         # the last block of `_tables`: never reached


def _idle_step(kind, T):
    def writer(ex):             # every row masked: the pools pass through
        ex.step(np.zeros((_ROWS, T), np.int32), np.zeros(_ROWS, np.int32),
                np.zeros(_ROWS, bool), np.zeros(_ROWS, np.int32),
                kind=kind, block_tables=_tables(ex))
    return writer


def _reinstall_first_blocks(ex):
    firsts = [int(t[0]) for t in _tables(ex)]
    size = ex.kv_block_size     # both prompts fill their first block
    ex.install_kv_blocks(
        firsts, [ex.kv_block_bytes(b, 0, size) for b in firsts],
        [size] * len(firsts))


_CACHE_WRITERS = {
    "prefill": _idle_step("prefill", _BUCKET),
    "decode": _idle_step("decode", 1),
    "verify": _idle_step("verify", 3),
    "copy_kv_block": lambda ex: ex.copy_kv_block(0, _SPARE),
    "install_kv_blocks": _reinstall_first_blocks,
    "corrupt_kv_block": lambda ex: ex.corrupt_kv_block(
        _SPARE, ex.kv_block_size),
}


def _pools_held_row_major(ex):
    pools = ex._cache_leaves()
    return bool(pools) and all(
        x.shape == (24, 4, 2, 8) and
        x.format.layout.major_to_minor == (0, 1, 2, 3) for x in pools)


@pytest.mark.parametrize("writer", list(_CACHE_WRITERS))
def test_cache_writer_hands_the_pools_back_as_held(writer, monkeypatch):
    from horovod_tpu.chaos import inject
    # no chaos plan is armed here: the fault body flips the first bit
    monkeypatch.setattr(inject, "corrupt_copy",
                        lambda raw: bytes([raw[0] ^ 1]) + bytes(raw[1:]))
    given = _gpt_params()
    ex, twin = _gpt_executor(given), _gpt_executor(given)
    assert _pools_held_row_major(ex)        # as the constructor made them
    _, lengths = _prompt_batch()

    def decode(e, nxt, i):
        return e.step(nxt[:, None].astype(np.int32), lengths + i,
                      np.ones(_ROWS, bool), np.zeros(_ROWS, np.int32),
                      kind="decode", block_tables=_tables(e))
    a, b = decode(ex, _first_step(ex), 0), decode(twin, _first_step(twin), 0)
    before = ex.kv_block_bytes(_SPARE, 0, ex.kv_block_size)
    _CACHE_WRITERS[writer](ex)              # the detour `twin` never sees
    assert _pools_held_row_major(ex)
    if writer == "corrupt_kv_block":        # and it did write
        assert ex.kv_block_bytes(_SPARE, 0, ex.kv_block_size) != before
    programs = ex.jit_cache_size()
    for i in range(1, 4):
        a, b = decode(ex, a, i), decode(twin, b, i)
        np.testing.assert_array_equal(a, b)
    assert ex.jit_cache_size() == programs
    assert _pools_held_row_major(ex)


def test_constructor_logs_the_pools_layout_and_bytes(caplog):
    with caplog.at_level(logging.INFO, "horovod_tpu"):
        ex = _gpt_executor(_gpt_params())
    (line,) = [r.getMessage() for r in caplog.records
               if r.getMessage().startswith("serve executor")]
    logical = sum(x.nbytes for x in ex._cache_leaves())
    # the CPU pads nothing: both counts are the values' bytes
    assert logical == 4 * 24 * 4 * 2 * 8 * 2        # 4 pools of bfloat16
    assert f"4 pools bfloat16[24, 4, 2, 8] held (0, 1, 2, 3), pool " \
        f"bytes {logical} -> {logical} on the device" in line, line


# -- (g) a step's host inputs cross in ONE transfer ---------------------------
# (`executor.pack_step` / `unpack_step`): what the model and the
# samplers receive inside the jitted step is what the host held, bit for
# bit; the buffer's shape carries rows and T, so it keys the program.

_B = 4      # rows of the packed-step executors
#: per-row sampling data with awkward bit patterns
_SAMPLE = {"temperature": np.array([0.7, 1e-6, -0.0, 0.0], np.float32),
           "top_p": np.array([0.9, 1.0, 1e-6, 0.3], np.float32),
           "seed": np.array([2 ** 31, 2 ** 32 - 1, 0, 7], np.uint32),
           "ctr": np.array([0, 5, 2 ** 31 - 1, 3], np.int32)}
_MASK = np.array([False, True, True, False])


def _executor_of(arch):
    """An executor of `_B` rows: GPT, or the model with per-row state."""
    if arch == "gpt":
        model = GPT(GPTConfig(decode_kernel="xla", **_PAGED, **_KW))
        return ShardedExecutor(model, _gpt_params(), max_batch=_B,
                               max_len=_MAX_LEN)
    from horovod_tpu.models.sala_lm import SalaLM, SalaLMConfig
    model = SalaLM(SalaLMConfig(
        vocab_size=64, max_seq_len=_MAX_LEN, kv_block_size=4,
        kv_pool_blocks=48, state_rows=_B, dtype=jnp.float32,
        param_dtype=jnp.float32, decode_kernel="xla"))
    params = model.init(
        jax.random.PRNGKey(0), jnp.zeros((_B, 1), jnp.int32),
        positions=jnp.zeros((_B,), jnp.int32),
        update_mask=jnp.zeros((_B,), bool),
        block_tables=jnp.full((_B, 12), -1, jnp.int32))["params"]
    return ShardedExecutor(model, params, max_batch=_B, max_len=_MAX_LEN)


class _SpyModel:
    """Stands in for `ex.model` when a step is traced: hands each input
    of `apply` to the host, then calls the model."""

    def __init__(self, model, seen):
        self.model, self.seen = model, seen

    def apply(self, variables, tokens, **kw):
        named = {"tokens": tokens, "positions": kw["positions"],
                 "mask": kw["update_mask"], "tables": kw["block_tables"]}
        for opt in ("logits_idx", "state_slots"):
            if kw.get(opt) is not None:
                named[opt] = kw[opt]
        jax.debug.callback(lambda **v: self.seen.update(v), **named)
        return self.model.apply(variables, tokens, **kw)


def _spied_executor(monkeypatch, arch, seen):
    """An executor of `_B` rows whose traced steps report what reaches
    `model.apply` and the two samplers."""
    from horovod_tpu.ops import pallas_paged
    sample, accept = (pallas_paged.sample_with_probs,
                      pallas_paged.speculative_accept)

    def spy_sample(logits, temp, top_p, seed, ctr, **kw):
        jax.debug.callback(lambda **v: seen.update(v), temperature=temp,
                           top_p=top_p, seed=seed, ctr=ctr)
        return sample(logits, temp, top_p, seed, ctr, **kw)

    def spy_accept(tokens, dprobs, logits, n_draft, temp, top_p, seed, ctr):
        jax.debug.callback(lambda **v: seen.update(v), draft_tokens=tokens,
                           n_draft=n_draft, temperature=temp, top_p=top_p,
                           seed=seed, ctr=ctr)
        return accept(tokens, dprobs, logits, n_draft, temp, top_p, seed,
                      ctr)

    monkeypatch.setattr(pallas_paged, "sample_with_probs", spy_sample)
    monkeypatch.setattr(pallas_paged, "speculative_accept", spy_accept)
    ex = _executor_of(arch)
    ex.model = _SpyModel(ex.model, seen)
    return ex


def _same_bits(got, want, dtype):
    got = np.asarray(got)
    assert got.dtype == dtype and got.shape == np.shape(want)
    want = np.asarray(want, dtype)
    if dtype != bool:       # float fields by their bits: -0.0 is not 0.0
        got, want = (x.view("u%d" % x.itemsize) for x in (got, want))
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("kind", ["decode", "row_compact_prefill", "verify"])
def test_the_step_receives_what_the_host_held(kind, monkeypatch):
    seen = {}
    ex = _spied_executor(
        monkeypatch, "sala" if kind == "row_compact_prefill" else "gpt",
        seen)
    n = ex.blocks_per_seq
    tables = np.arange(_B * n, dtype=np.int32).reshape(_B, n)
    tables[:, 5:] = -1
    tables[0] = -1
    positions = np.array([0, 7, 3, 0], np.int32)
    rng = np.random.RandomState(0)
    if kind == "decode":
        want = dict(tokens=rng.randint(0, 64, (_B, 1)), positions=positions,
                    mask=_MASK, logits_idx=np.zeros(_B, np.int32),
                    tables=tables, **_SAMPLE)
        ex.step(want["tokens"], positions, _MASK, want["logits_idx"],
                kind="decode", block_tables=tables, sample=_SAMPLE)
    elif kind == "row_compact_prefill":
        rows = [1, 2]           # two rows of the batch's four, out of order
        sample = {k: v[rows] for k, v in _SAMPLE.items()}
        want = dict(tokens=rng.randint(0, 64, (2, _BUCKET)),
                    positions=np.zeros(2, np.int32), mask=_MASK[rows],
                    logits_idx=np.array([4, 6], np.int32),
                    tables=tables[rows], state_slots=np.array([3, 1]),
                    **sample)
        ex.step(want["tokens"], want["positions"], want["mask"],
                want["logits_idx"], kind="prefill", sample=sample,
                block_tables=want["tables"],
                state_slots=want["state_slots"])
    else:
        tokens = rng.randint(0, 64, (_B, 3))
        n_draft = np.array([2, 0, 1, 2], np.int32)
        want = dict(tokens=tokens, draft_tokens=tokens, positions=positions,
                    mask=_MASK, tables=tables, n_draft=n_draft, **_SAMPLE)
        ex.step(tokens, positions, _MASK, None, kind="verify",
                block_tables=tables, sample=_SAMPLE, n_draft=n_draft,
                draft_probs=jnp.full((_B, 2, 64), 1 / 64, jnp.float32))
    jax.effects_barrier()
    assert sorted(seen) == sorted(want)
    dtypes = {"mask": bool, "temperature": np.float32,
              "top_p": np.float32, "seed": np.uint32}
    for name, value in want.items():
        _same_bits(seen[name], value, dtypes.get(name, np.int32))


def _nine_argument_step(ex):
    """The step as it was before the packed buffer, kept here as the
    reference: nine device arrays in, the sampled token out."""
    from horovod_tpu.ops.pallas_paged import sample_with_probs

    @jax.jit
    def fwd(params, cache, tokens, positions, mask, last_idx, temp, top_p,
            seed, ctr, tables):
        logits, vout = ex.model.apply(
            {"params": params, "cache": cache}, tokens,
            positions=positions, update_mask=mask, logits_idx=last_idx,
            mutable=["cache", "stats"], block_tables=tables)
        tok, _ = sample_with_probs(logits[:, 0], temp, top_p, seed, ctr)
        return tok, vout["cache"]
    return fwd


def test_sampled_token_streams_are_the_nine_argument_steps():
    ex = _gpt_executor(_gpt_params())
    ref, cache = _nine_argument_step(ex), jax.tree_util.tree_map(
        jnp.zeros_like, ex.cache)
    sample = {"temperature": np.array([0.8, 1.3], np.float32),
              "top_p": np.array([0.95, 0.6], np.float32),
              "seed": np.array([2 ** 31 + 11, 2 ** 32 - 1], np.uint32)}
    tokens, lengths = _prompt_batch()
    positions, last = np.zeros(_ROWS, np.int32), lengths - 1
    mask, tables, kind = np.ones(_ROWS, bool), _tables(ex), "prefill"
    streams = []
    for i in range(1 + 2 * _DECODES):
        s = dict(sample, ctr=np.full(_ROWS, i, np.int32))
        got = ex.step(tokens, positions, mask, last, kind=kind,
                      block_tables=tables, sample=s)
        want, cache = ref(
            ex.params, cache, jnp.asarray(tokens, jnp.int32),
            jnp.asarray(positions, jnp.int32), jnp.asarray(mask, bool),
            jnp.asarray(last, jnp.int32),
            jnp.asarray(s["temperature"], jnp.float32),
            jnp.asarray(s["top_p"], jnp.float32),
            jnp.asarray(s["seed"], jnp.uint32),
            jnp.asarray(s["ctr"], jnp.int32),
            jnp.asarray(tables, jnp.int32))
        np.testing.assert_array_equal(got, np.asarray(want))
        streams.append(got)
        tokens, positions, last, kind = (
            got[:, None].astype(np.int32), lengths + i,
            np.zeros(_ROWS, np.int32), "decode")
    # sampled, not greedy: the rows' streams are no argmax run
    assert len({tuple(np.stack(streams)[:, r]) for r in range(_ROWS)}) == 2


def _upload_spans(since):
    return [s for s in get_recorder().between(since, float("inf"))
            if s.name == "exec_upload"]


@pytest.mark.parametrize("arch", ["gpt", "sala"])
def test_a_decode_step_makes_one_transfer(arch, monkeypatch):
    ex = _executor_of(arch)
    n = ex.blocks_per_seq
    tables = np.arange(_B * n, dtype=np.int32).reshape(_B, n)
    calls, put = [], jax.device_put
    monkeypatch.setattr(jax, "device_put",
                        lambda x, *a, **kw: calls.append(x) or put(x, *a, **kw))
    t0 = time.monotonic()
    with jax.transfer_guard_host_to_device("disallow"):
        ex.step(np.zeros((_B, 1), np.int32), np.zeros(_B, np.int32),
                _MASK, np.zeros(_B, np.int32), kind="decode",
                block_tables=tables, sample=_SAMPLE)
    (span,) = _upload_spans(t0)
    words = _B * 1 + 7 * _B + _B * n + (_B if arch == "sala" else 0)
    assert span.extra == {"transfers": 1, "bytes": 4 * words}
    assert len(calls) == 1 and calls[0].nbytes == 4 * words


def test_the_packed_shape_keys_the_program():
    """A ``[1, 35]`` and a ``[2, 8]`` prefill pack into the same number
    of words (12 table entries and 7 more a row) and are two programs;
    a churn of values through one shape is one."""
    ex = _gpt_executor(_gpt_params())
    assert ex.blocks_per_seq == 12
    t0 = time.monotonic()
    for rows, T in ((1, 35), (2, 8)):
        ex.step(np.zeros((rows, T), np.int32), np.zeros(rows, np.int32),
                np.ones(rows, bool), np.full(rows, 4, np.int32),
                kind="prefill", block_tables=_tables(ex)[:rows])
    assert [s.extra["bytes"] for s in _upload_spans(t0)] == [4 * 54] * 2
    assert ex.jit_cache_size() == 2
    rng = np.random.RandomState(1)
    for i in range(6):          # admissions come and go: values, not shapes
        ex.step(rng.randint(0, 64, (_ROWS, 1)), rng.randint(0, 40, _ROWS),
                rng.rand(_ROWS) < 0.5, np.zeros(_ROWS, np.int32),
                kind="decode", block_tables=rng.randint(-1, 24, (_ROWS, 12)),
                sample={"temperature": rng.rand(_ROWS).astype(np.float32),
                        "top_p": np.ones(_ROWS, np.float32),
                        "seed": rng.randint(0, 2 ** 32, _ROWS, np.uint32),
                        "ctr": np.full(_ROWS, i, np.int32)})
    assert ex.jit_cache_size() == 3


def test_an_array_of_another_shape_is_refused():
    ex = _gpt_executor(_gpt_params())
    step = dict(tokens=np.zeros((_ROWS, 1), np.int32),
                positions=np.zeros(_ROWS, np.int32),
                mask=np.ones(_ROWS, bool),
                last_idx=np.zeros(_ROWS, np.int32), kind="decode",
                block_tables=_tables(ex))
    for wrong in (dict(positions=np.zeros(1, np.int32)),
                  # one entry short: T would be read one token longer
                  dict(block_tables=_tables(ex)[:, :-1])):
        with pytest.raises(ValueError, match=r"a step of 2 rows"):
            ex.step(**dict(step, **wrong))
