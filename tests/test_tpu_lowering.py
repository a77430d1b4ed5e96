"""Every Pallas entry point, lowered for TPU from the CPU sandbox.

`jax.export` with ``platforms=["tpu"]`` runs the Pallas TPU lowering
without a chip, and that lowering is where a kernel is first refused:
the paged decode kernel's old ``(1, T, G, D)`` q/out blocks failed here
at every shape ("the last two dimensions of your block shape [must be]
divisible by 8 and 128 ... or equal to the respective dimensions of the
overall array"), and a Pallas call inside a GSPMD-partitioned train step
fails here too ("Mosaic kernels cannot be automatically partitioned").
Shapes are the smoke's GPT-2-small ones plus one GQA shape.

This tier checks the Pallas lowering only. The second tier goes one
step further where the installed libtpu can describe a v5e topology
without hardware (it is skipped where it cannot): an ahead-of-time
compile, which runs Mosaic and its scoped-VMEM accounting. What the
kernels compute on the MXU, and whether the programs fit next to
everything else in HBM, is the chip's to say (`chip_smoke.py`).
"""
import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

from horovod_tpu.ops import pallas_paged
from horovod_tpu.ops.pallas_attention import flash_attention
from horovod_tpu.ops.pallas_ce import fused_softmax_cross_entropy
from horovod_tpu.serve.executor import ROW_WORDS
from horovod_tpu.serve.kv_cache import held_pool_shape, pool_blocks_for

#: (heads, kv_heads, head_dim): GPT-2-small, then the GQA shape
_HEADS = [(12, 12, 64), (32, 8, 128)]
_B, _S, _BLOCK = 8, 1024, 16


def _sds(shape, dtype, sharding=None):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _attention_args(H, KV, D, sharding=None):
    q = _sds((_B, H, _S, D), jnp.bfloat16, sharding)
    kv = _sds((_B, KV, _S, D), jnp.bfloat16, sharding)
    return q, kv, kv


def _flash_fwd(q, k, v):
    return flash_attention(q, k, v, causal=True)


def _flash_grad(q, k, v):
    return jax.grad(lambda *a: _flash_fwd(*a).astype(jnp.float32).sum(),
                    argnums=(0, 1, 2))(q, k, v)


def _ce_args(dtype, sharding=None):
    return (_sds((8192, 50304), dtype, sharding),
            _sds((8192,), jnp.int32, sharding))


def _ce_grad(logits, labels):
    return jax.grad(fused_softmax_cross_entropy)(logits, labels)


def _paged_args(T, H, KV, D, sharding=None):
    """The pools as a model holds them (`kv_cache.held_pool_shape`)."""
    nblk = _S // _BLOCK
    pool = _sds(held_pool_shape(pool_blocks_for(_B, _S, _BLOCK), _BLOCK,
                                KV, D), jnp.bfloat16, sharding)
    return (_sds((_B, T, H, D), jnp.bfloat16, sharding), pool, pool,
            _sds((_B, nblk), jnp.int32, sharding),
            _sds((_B,), jnp.int32, sharding))


def _paged(q, pool_k, pool_v, tables, positions, *, kv_heads):
    return pallas_paged._paged_attention_call(
        q, pool_k, pool_v, tables, positions, interpret=False,
        kv_heads=kv_heads)


def _entry_points():
    for H, KV, D in _HEADS:
        tag = f"h{H}kv{KV}d{D}"
        yield f"flash_fwd-{tag}", _flash_fwd, \
            functools.partial(_attention_args, H, KV, D)
        yield f"flash_grad-{tag}", _flash_grad, \
            functools.partial(_attention_args, H, KV, D)
        for T in (1, 4, 128):      # the walk twice, the assembly once
            yield f"paged_T{T}-{tag}", \
                functools.partial(_paged, kv_heads=KV), \
                functools.partial(_paged_args, T, H, KV, D)
    for dtype in (jnp.float32, jnp.bfloat16):
        tag = jnp.dtype(dtype).name
        yield f"ce_fwd-{tag}", fused_softmax_cross_entropy, \
            functools.partial(_ce_args, dtype)
        yield f"ce_grad-{tag}", _ce_grad, functools.partial(_ce_args, dtype)


_ENTRY_POINTS = list(_entry_points())
_IDS = [name for name, _, _ in _ENTRY_POINTS]


@pytest.mark.parametrize("name,fn,make_args", _ENTRY_POINTS, ids=_IDS)
def test_kernel_lowers_for_tpu(name, fn, make_args):
    exported = jax.export.export(jax.jit(fn), platforms=["tpu"])(*make_args())
    assert "tpu_custom_call" in exported.mlir_module()


def _train_step(mesh):
    """The smoke's train step at GPT-2-small width, two layers deep,
    with the Pallas attention forced (on CPU the platform dispatch would
    pick the reference and there would be nothing to lower)."""
    import optax

    from horovod_tpu.models.gpt import GPT, GPTConfig
    from horovod_tpu.parallel.tp import gpt_partition_rules
    from horovod_tpu.training import make_gspmd_train_step

    n = mesh.size
    model = GPT(GPTConfig(vocab_size=50304, num_layers=2, num_heads=12,
                          head_dim=64, max_seq_len=_S, mesh=mesh,
                          attention_impl="pallas"))
    repl = NamedSharding(mesh, P())
    params = jax.eval_shape(
        lambda k, t: model.init(k, t)["params"], jax.random.PRNGKey(0),
        _sds((n, _S), jnp.int32))
    tx = optax.adamw(1e-3)
    opt = jax.eval_shape(tx.init, params)
    params, opt = jax.tree.map(
        lambda s: _sds(s.shape, s.dtype, repl), (params, opt))
    tokens = _sds((_B * n, _S), jnp.int32,
                  NamedSharding(mesh, P("dp", None)))
    step = make_gspmd_train_step(model.apply, tx, mesh, gpt_partition_rules())
    return step, (params, opt, tokens, tokens)


def test_train_step_on_a_dp_mesh_lowers_for_tpu():
    """GSPMD cannot partition a Mosaic kernel, so on a mesh the model
    must run the attention kernel per shard (ops/pallas_attention
    fused_attention's `mesh=`); a bare pallas_call raises right here."""
    from horovod_tpu.parallel.mesh_utils import make_mesh
    step, args = _train_step(make_mesh(dp=len(jax.devices())))
    exported = jax.export.export(step, platforms=["tpu"])(*args)
    assert exported.mlir_module().count("tpu_custom_call") >= 3  # fwd, dq, dkv


# -- second tier: Mosaic, ahead of time ---------------------------------------

@pytest.fixture(scope="module")
def v5e():
    """Four described (not attached) v5e devices, or skip."""
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(topology_name="v5e:2x2",
                                            platform="tpu")
    except Exception as e:  # noqa: BLE001 — no libtpu / no AOT support here
        pytest.skip(f"no compile-only TPU topology: {e}")
    return topo.devices


def _aot_compile(fn, args):
    return fn.trace(*args).lower(lowering_platforms=("tpu",)).compile()


@pytest.mark.parametrize("name,fn,make_args", _ENTRY_POINTS, ids=_IDS)
def test_mosaic_accepts_kernel(v5e, name, fn, make_args):
    _aot_compile(jax.jit(fn), make_args(SingleDeviceSharding(v5e[0])))


def test_mosaic_accepts_float32_paged_pool(v5e):
    """float32 K/V at D=64 needs more than the default 16 MiB of scoped
    VMEM: the kernel's own request (`_vmem_limit_bytes`) must cover it."""
    sh = SingleDeviceSharding(v5e[0])
    args = [_sds(a.shape, jnp.float32 if a.dtype == jnp.bfloat16 else a.dtype,
                 sh) for a in _paged_args(1, 12, 12, 64)]
    _aot_compile(jax.jit(functools.partial(_paged, kv_heads=12)), args)


def test_four_chip_train_step_compiles(v5e):
    from jax.sharding import Mesh
    mesh = Mesh(np.array(v5e, dtype=object), ("dp",))
    step, args = _train_step(mesh)
    compiled = _aot_compile(step, args)
    assert "all-reduce" in compiled.as_text()      # the dp gradient sum


# -- the long-context serving shapes: window and full layers side by side -----
# 16 rows, 28 query heads over 4 kv heads of 128, a pool of 800 blocks of
# 128 tokens, 100 table entries a row (12,800 positions); prompts of up to
# 12,288 tokens prefilled one row a step. A full layer's paged kernel asks
# for about 55 MB of scoped VMEM at these sizes; a Mosaic refusal (VMEM,
# tiling, the dynamic loop bounds of the window) is found here.

_LONG = dict(rows=16, H=28, KV=4, D=128, block=128, entries=100, pool=800,
             window=4096, prompt=12288)


def _long_paged_args(sharding=None):
    c = _LONG
    pool = _sds((c["pool"], c["block"], c["KV"], c["D"]), jnp.bfloat16,
                sharding)
    return (_sds((c["rows"], 1, c["H"], c["D"]), jnp.bfloat16, sharding),
            pool, pool, _sds((c["rows"], c["entries"]), jnp.int32, sharding),
            _sds((c["rows"],), jnp.int32, sharding))


def _long_prefill_args(sharding=None):
    c = _LONG
    keys = _sds((1, c["KV"], c["entries"] * c["block"], c["D"]),
                jnp.bfloat16, sharding)
    return (_sds((1, c["H"], c["prompt"], c["D"]), jnp.bfloat16, sharding),
            keys, keys, _sds((1,), jnp.int32, sharding))


def _long_paged(window):
    return lambda *a: pallas_paged._paged_attention_call(
        *a, interpret=False, window=window)


def _long_prefill(window):
    from horovod_tpu.ops.pallas_attention import flash_prefill
    return lambda *a: flash_prefill(*a, window=window)


_LONG_ENTRY_POINTS = [
    (f"{name}-{'full' if w is None else 'window'}", fn(w), args)
    for name, fn, args in (("paged", _long_paged, _long_paged_args),
                           ("flash_prefill", _long_prefill,
                            _long_prefill_args))
    for w in (None, _LONG["window"])]
_LONG_IDS = [name for name, _, _ in _LONG_ENTRY_POINTS]


@pytest.mark.parametrize("name,fn,make_args", _LONG_ENTRY_POINTS,
                         ids=_LONG_IDS)
def test_long_context_kernel_lowers_for_tpu(name, fn, make_args):
    exported = jax.export.export(jax.jit(fn), platforms=["tpu"])(*make_args())
    assert "tpu_custom_call" in exported.mlir_module()


@pytest.mark.parametrize("name,fn,make_args", _LONG_ENTRY_POINTS,
                         ids=_LONG_IDS)
def test_mosaic_accepts_long_context_kernel(v5e, name, fn, make_args):
    _aot_compile(jax.jit(fn), make_args(SingleDeviceSharding(v5e[0])))


def test_window_kernel_asks_for_less_vmem_than_the_full_one():
    c = _LONG
    group = c["H"] // c["KV"]
    full = pallas_paged._vmem_limit_bytes(
        group, c["KV"], c["D"], c["block"], c["entries"], 2)
    entries = pallas_paged.window_entries(c["window"], 1, c["block"],
                                          c["entries"])
    windowed = pallas_paged._vmem_limit_bytes(
        group, c["KV"], c["D"], c["block"], entries, 2)
    assert entries == 33 and windowed < full / 2
    assert 40 << 20 < full < 100 << 20      # the chip has 128 MiB


@pytest.mark.parametrize("tokens", [16, 2048])
def test_mosaic_accepts_the_grouped_expert_matmul(v5e, tokens):
    """Dropless top-6 of 64 gated experts of width 768 over a 2560-wide
    stream (`parallel/ep.py routed_experts` over jax's Pallas grouped
    matmul), at a decode step's 16 tokens and a short prefill's 2,048."""
    from horovod_tpu.parallel import ep
    sh = SingleDeviceSharding(v5e[0])
    E, K, D, F = 64, 6, 2560, 768
    args = (_sds((tokens, D), jnp.bfloat16, sh),
            _sds((tokens, K), jnp.int32, sh),
            _sds((tokens, K), jnp.float32, sh), _sds((tokens,), bool, sh),
            _sds((E, D, 2 * F), jnp.bfloat16, sh),
            _sds((E, F, D), jnp.bfloat16, sh))
    compiled = _aot_compile(
        jax.jit(lambda *a: ep.routed_experts(*a, impl="gmm")), args)
    assert compiled.as_text().count('custom_call_target="tpu_custom_call"') \
        == 2


# -- the serve executor's own step programs, at GPT-2 XL widths ---------------
# 2 layers, 8 rows, 25 heads of 64, blocks of 16, the Pallas kernel: the
# programs `ShardedExecutor` jits, compiled for a described chip as it
# would run them there (the cache donated).

_XL_ROWS, _XL_BLOCK, _XL_LEN = 8, 16, 640


@pytest.fixture
def xl_executor(v5e, monkeypatch):
    from horovod_tpu.models.gpt import GPT, GPTConfig
    from horovod_tpu.serve import ShardedExecutor
    kw = dict(vocab_size=256, num_layers=2, num_heads=25, head_dim=64,
              max_seq_len=_XL_LEN)
    params = jax.jit(lambda k: GPT(GPTConfig(**kw)).init(
        k, jnp.zeros((1, 8), jnp.int32))["params"])(jax.random.PRNGKey(0))
    # the model asks the default backend (the CPU, here) whether to
    # interpret the kernel: steer it from the test
    monkeypatch.setattr(
        pallas_paged, "paged_attention_fused", functools.partial(
            pallas_paged.paged_attention_fused, interpret=False))
    ex = ShardedExecutor(
        GPT(GPTConfig(decode=True, kv_block_size=_XL_BLOCK,
                      kv_pool_blocks=pool_blocks_for(_XL_ROWS, _XL_LEN,
                                                     _XL_BLOCK),
                      decode_kernel="pallas", **kw)),
        params, max_batch=_XL_ROWS, max_len=_XL_LEN)
    # the same step function, donated as off the CPU
    ex._fwd_token = jax.jit(ex._fwd_token.__wrapped__, donate_argnums=(1,))
    ex.chip = SingleDeviceSharding(v5e[0])
    return ex


def _compile_step(ex, tokens):
    """`ex._fwd_token` at ``[rows, tokens]``, compiled for the chip."""
    sh, rows = ex.chip, ex.max_batch

    def on_chip(tree):
        return jax.tree_util.tree_map(
            lambda x: _sds(x.shape, x.dtype, sh), tree)
    # the step's one packed input (`executor.pack_step`)
    return _aot_compile(ex._fwd_token, [
        on_chip(ex.params), on_chip(ex.cache),
        _sds((rows, tokens + ROW_WORDS + ex.blocks_per_seq), jnp.int32,
             sh)])


def _entry_layout(compiled) -> str:
    return next(line for line in compiled.as_text().splitlines()
                if "entry_computation_layout" in line)


def test_decode_step_reads_no_float32_matmul_kernel(xl_executor):
    """The decode step of `ShardedExecutor` at GPT-2 XL widths (2
    layers, 8 rows, the Pallas kernel), compiled with the tree the
    executor HOLDS: the four `Dense` kernels of a block enter the
    program in the compute dtype, so no step reads them in float32
    (serve/executor.py, "Resident dtypes")."""
    compiled = _compile_step(xl_executor, 1)
    entry = _entry_layout(compiled)
    for shape in ("[1600,4800]", "[1600,1600]", "[1600,6400]",
                  "[6400,1600]"):
        assert "f32" + shape not in entry, shape
        assert entry.count("bf16" + shape) == 2, shape       # one a layer
    assert 'custom_call_target="tpu_custom_call"' in compiled.as_text()


@pytest.mark.parametrize("tokens", [1, 128], ids=["decode", "prefill128"])
def test_step_copies_no_pool(xl_executor, tokens):
    """The pools are held in a shape the chip lays out row-major by
    itself (`kv_cache.write_kv_pools`), the order the scatter and the
    paged kernel read, so the step re-lays none of them out: no
    ``copy`` of a pool, held or as the attention sees it (the chip's
    layout for ``[160,16,25,64]`` costs six a layer), at most one
    staged through another memory space (``copy-start``), every pool
    row-major at the program's entry and the donated cache aliased
    whole."""
    ex = xl_executor
    pools = ex._cache_leaves()
    assert pools[0].shape == (160, 16, 32, 128)
    compiled = _compile_step(ex, tokens)

    def count(op, shape):   # instructions `op` whose (first) result is it
        return sum(1 for line in compiled.as_text().splitlines()
                   if f" {op}(" in line and
                   re.search(r" = \(?" + re.escape(shape) + r"\{", line))
    held = "bf16[160,16,32,128]"
    assert count("copy", held) == 0
    assert count("copy", "bf16[160,16,25,64]") == 0
    assert count("copy-start", held) <= 1
    layouts = re.findall(re.escape(held) + r"\{([\d,]+)",
                         _entry_layout(compiled))
    assert len(layouts) == 2 * len(pools)       # arguments and results
    assert set(layouts) == {"3,2,1,0"}, layouts
    assert compiled.memory_analysis().alias_size_in_bytes >= sum(
        x.nbytes for x in pools)


# the two serving cells' pools: GPT-2 XL's (8 rows, 25 heads of 64, blocks
# of 16) and the long-context cell's (`_LONG`), full and window layers
_POOLS = {
    "gpt2-xl": dict(rows=8, H=25, KV=25, D=64, block=16, entries=40,
                    pool=160, window=None),
    "long-full": dict(_LONG, window=None),
    "long-window": _LONG,
}


@pytest.mark.parametrize("case", list(_POOLS))
def test_chip_lays_a_held_pool_out_row_major(v5e, case):
    """Scatter + paged kernel over one layer's pools in the shape they
    are HELD in (`kv_cache.held_pool_shape`), donated, at both cells'
    pool shapes: with no layout named the pools enter and leave
    row-major and none is copied, and with the choice left to the
    compiler (`Layout.AUTO`) it is row-major too. A compiler that
    changes its mind fails here, not in a benchmark."""
    from jax.experimental.layout import Format, Layout
    from horovod_tpu.serve.kv_cache import held_pool_shape, write_kv_paged
    c = _POOLS[case]
    sh = SingleDeviceSharding(v5e[0])
    KV, D = c["KV"], c["D"]
    held = held_pool_shape(c["pool"], c["block"], KV, D)
    # the long-context cell's pools are held as they are
    assert held == ((160, 16, 32, 128) if case == "gpt2-xl"
                    else (c["pool"], c["block"], KV, D))

    def layer(pool_k, pool_v, q, k_new, v_new, positions, mask, tables):
        pool_k, pool_v = write_kv_paged(pool_k, pool_v, k_new, v_new,
                                        positions, mask, tables)
        out = pallas_paged._paged_attention_call(
            q, pool_k, pool_v, tables, positions, interpret=False,
            window=c["window"], kv_heads=KV)
        return out, pool_k, pool_v

    new = _sds((c["rows"], 1) + held[2:], jnp.bfloat16, sh)
    rest = [_sds((c["rows"], 1, c["H"], D), jnp.bfloat16, sh), new, new,
            _sds((c["rows"],), jnp.int32, sh), _sds((c["rows"],), bool, sh),
            _sds((c["rows"], c["entries"]), jnp.int32, sh)]
    pool = _sds(held, jnp.bfloat16, sh)
    compiled = _aot_compile(jax.jit(layer, donate_argnums=(0, 1)),
                            [pool, pool] + rest)
    shape = "bf16[%s]" % ",".join(map(str, held))
    text = compiled.as_text()
    assert set(re.findall(re.escape(shape) + r"\{([\d,]+)",
                          _entry_layout(compiled))) == {"3,2,1,0"}
    corner = "bf16[%s]" % ",".join(map(str, held[:2] + (KV, D)))
    for pool in (shape, corner):    # neither copied nor padded back
        assert not re.search(r" = " + re.escape(pool) + r"\{[^ ]* (copy|pad)\(",
                             text), pool
    auto = Format(Layout.AUTO, sh)
    chosen = _aot_compile(
        jax.jit(layer, donate_argnums=(0, 1),
                in_shardings=(auto, auto) + (None,) * 6,
                out_shardings=(None, auto, auto)),
        [_sds(held, jnp.bfloat16)] * 2 + rest)
    formats = list(chosen.input_formats[0][:2]) + \
        list(chosen.output_formats[1:])
    assert [f.layout.major_to_minor for f in formats] == [(0, 1, 2, 3)] * 4


# -- the sparse + linear hybrid's decode step ---------------------------------
# One lightning and one block-sparse layer at the published widths (4096
# wide, 32 heads of 128, 2 KV heads, a feed-forward of 16,384; 16 rows, the
# cell's pool of 4,608 blocks of 64 and tables of 576 entries), the
# vocabulary cut to keep the shapes' bookkeeping light. Nothing is held:
# the executor is built from the parameters' SHAPES.

@pytest.fixture
def sala_executor(v5e, monkeypatch):
    from chipbench import manifest as mf
    from horovod_tpu.ops import lightning
    from horovod_tpu.serve import ShardedExecutor
    family = mf.load_module("chipbench/families/sala.py")
    config = mf.load_json("chipbench/configs/minicpm-sala-l12.json")
    config.update(num_hidden_layers=2, vocab_size=512)
    config["assumed"]["first_layer"] = 8        # lightning, then sparse
    shape = family.Shape(config)
    assert shape.mixers == ("lightning-attn", "minicpm4")
    rows, max_len = 16, 36864
    model = family.serve_model(
        shape, config, kv_block=64,
        kv_pool_blocks=pool_blocks_for(rows, max_len, 64),
        decode_kernel="pallas")
    params = jax.eval_shape(lambda k: family.program_params(shape, k),
                            family.seed_key(0))
    # the model asks the default backend (the CPU, here) whether to
    # interpret its kernels: steer it from the test
    monkeypatch.setattr(
        pallas_paged, "paged_attention_fused", functools.partial(
            pallas_paged.paged_attention_fused, interpret=False))
    decode = lightning.lightning_decode
    monkeypatch.setattr(
        lightning, "lightning_decode",
        lambda *a, interpret: decode(*a, interpret=False))
    ex = ShardedExecutor(model, params, max_batch=rows, max_len=max_len)
    ex._fwd_token = jax.jit(ex._fwd_token.__wrapped__, donate_argnums=(1,))
    ex.chip = SingleDeviceSharding(v5e[0])
    return ex


def test_hybrid_decode_step_aliases_the_state_and_the_pools(sala_executor):
    """The decode step compiles for the chip with both named kernels in
    it, the donated cache aliased whole (pools, compressed keys, the
    lightning state) and no copy of a pool, of the per-block leaf or of
    the state: the selection reads the compressed keys through a gather,
    the attended tables go to the paged kernel, the state is updated in
    place."""
    ex = sala_executor
    sh, rows = ex.chip, ex.max_batch

    def on_chip(tree):
        return jax.tree_util.tree_map(
            lambda x: _sds(x.shape, x.dtype, sh), tree)
    # the step's one packed input, a state slot at each row's end
    compiled = _aot_compile(ex._fwd_token, [
        on_chip(ex.params), on_chip(ex.cache),
        _sds((rows, 1 + ROW_WORDS + ex.blocks_per_seq + 1), jnp.int32,
             sh)])
    text = compiled.as_text()
    for kernel in ("lightning_decode", "_paged_attention_call"):
        assert re.search(r"%" + kernel + r"[.\d]* = .*custom-call", text), \
            kernel
    leaves = jax.tree_util.tree_leaves(ex.cache)
    assert sorted(ex._leaf_kinds) == ["block", "kv", "kv", "row"]
    for leaf in leaves:
        shape = "%s[%s]" % ({"bfloat16": "bf16", "float32": "f32"}[
            str(leaf.dtype)], ",".join(map(str, leaf.shape)))
        assert not re.search(
            r" = " + re.escape(shape) + r"\{[^ ]* copy\(", text), shape
    assert compiled.memory_analysis().alias_size_in_bytes >= sum(
        x.nbytes for x in leaves)
    # a (row, KV group) pair is a row to the paged kernel: 32 of them,
    # over the 128-entry attended table and not the 576-entry one
    call = next(line for line in text.splitlines()
                if re.search(r"%_paged_attention_call[.\d]* = ", line))
    assert "s32[32,128]" in call and "s32[32,576]" not in call


# One period of each half of the SambaY stack at the published widths
# (2,560 wide, 40 query and 20 KV heads of 64, a feed-forward of 10,240,
# Mamba states of 16 x 5,120 and 4 x 5,120, a window of 512; 16 rows, the
# cell's pool of 1,024 blocks of 64 and tables of 128 entries): 8 layers
# hold three Mamba and two window layers, the full layer, a memory unit
# and a cross layer; the vocabulary cut to keep the bookkeeping light.

@pytest.fixture
def sambay_executor(v5e, monkeypatch):
    from chipbench import manifest as mf
    from horovod_tpu.ops import selective_scan
    from horovod_tpu.serve import ShardedExecutor
    family = mf.load_module("chipbench/families/sambay.py")
    config = mf.load_json("chipbench/configs/phi4-mini-flash.json")
    config.update(num_hidden_layers=8, vocab_size=512)
    shape = family.Shape(config)
    rows, max_len = 16, 8192
    model = family.serve_model(
        shape, config, kv_block=64,
        kv_pool_blocks=pool_blocks_for(rows, max_len, 64),
        decode_kernel="pallas")
    params = jax.eval_shape(lambda k: family.program_params(shape, k),
                            family.seed_key(0))
    # the model asks the default backend (the CPU, here) whether to
    # interpret its kernels: steer it from the test
    monkeypatch.setattr(
        pallas_paged, "paged_attention_fused", functools.partial(
            pallas_paged.paged_attention_fused, interpret=False))
    decode = selective_scan.ssm_decode
    monkeypatch.setattr(
        selective_scan, "ssm_decode",
        lambda *a, interpret: decode(*a, interpret=False))
    ex = ShardedExecutor(model, params, max_batch=rows, max_len=max_len)
    ex._fwd_token = jax.jit(ex._fwd_token.__wrapped__, donate_argnums=(1,))
    ex.chip = SingleDeviceSharding(v5e[0])
    return ex


def test_sambay_decode_step_aliases_states_rings_and_the_one_pool(
        sambay_executor):
    """The decode step compiles for the chip with both named kernels in
    it, the donated cache aliased whole and no copy of a conv state, an
    SSM state, a ring or the pool; the cross layer reads the full layer's
    pool (one pool pair in the cache, four paged calls: two rings, the
    pool twice); and the 128-entry call asks for the VMEM the sizes
    give."""
    ex = sambay_executor
    sh, rows = ex.chip, ex.max_batch

    def on_chip(tree):
        return jax.tree_util.tree_map(
            lambda x: _sds(x.shape, x.dtype, sh), tree)
    compiled = _aot_compile(ex._fwd_token, [
        on_chip(ex.params), on_chip(ex.cache),
        _sds((rows, 1 + ROW_WORDS + ex.blocks_per_seq + 1), jnp.int32,
             sh)])
    text = compiled.as_text()
    for kernel in ("ssm_decode", "_paged_attention_call"):
        assert re.search(r"%" + kernel + r"[.\d]* = .*custom-call", text), \
            kernel
    leaves = jax.tree_util.tree_leaves(ex.cache)
    assert sorted(ex._leaf_kinds) == ["kv"] * 2 + ["row"] * 10
    assert sorted({(str(x.dtype), x.shape) for x in leaves}) == [
        ("bfloat16", (16, 8, 64, 16, 128)),        # a ring: 512 tokens
        ("bfloat16", (1024, 64, 16, 128)),         # the pool, pair-heads
        ("float32", (16, 4, 5120)), ("float32", (16, 16, 5120))]
    for leaf in leaves:
        shape = "%s[%s]" % ({"bfloat16": "bf16", "float32": "f32"}[
            str(leaf.dtype)], ",".join(map(str, leaf.shape)))
        assert not re.search(
            r" = " + re.escape(shape) + r"\{[^ ]* copy\(", text), shape
    assert compiled.memory_analysis().alias_size_in_bytes >= sum(
        x.nbytes for x in leaves)
    calls = [line for line in text.splitlines()
             if re.search(r"%_paged_attention_call[.\d]* = ", line)]
    assert sorted("s32[16,128]" in c for c in calls) == [False, False,
                                                         True, True]
    assert all("s32[16,8]" in c for c in calls if "s32[16,128]" not in c)
    # 10 pair-heads' whole table assembled in VMEM, as the issue reckons:
    # 42 MB of K and V, 66 MB asked for
    assert pallas_paged._vmem_limit_bytes(4, 10, 128, 64, 128, 2) \
        == 66_396_160


def test_sambay_prefill_step_compiles_with_its_three_kernels(
        sambay_executor, monkeypatch):
    """The longest bucket, one row: the scan kernel, the flash forward
    of the window layers and the last token's paged reads."""
    from horovod_tpu.ops import pallas_attention, selective_scan
    prefill, flash = selective_scan.ssm_prefill, \
        pallas_attention.flash_prefill
    monkeypatch.setattr(
        selective_scan, "ssm_prefill",
        lambda *a, interpret: prefill(*a, interpret=False))
    monkeypatch.setattr(
        pallas_attention, "flash_prefill",
        lambda *a, interpret, **kw: flash(*a, interpret=False, **kw))
    ex = sambay_executor
    sh = ex.chip

    def on_chip(tree):
        return jax.tree_util.tree_map(
            lambda x: _sds(x.shape, x.dtype, sh), tree)
    compiled = _aot_compile(ex._fwd_token, [
        on_chip(ex.params), on_chip(ex.cache),
        _sds((1, 4928 + ROW_WORDS + ex.blocks_per_seq + 1), jnp.int32, sh)])
    text = compiled.as_text()
    for kernel in ("ssm_prefill", "flash_prefill", "_paged_attention_call"):
        assert re.search(r"%" + kernel + r"[.\d]* = .*custom-call", text), \
            kernel
    assert compiled.memory_analysis().temp_size_in_bytes < 2 << 30


# -- the decode walk at the four serve cells' decode shapes -------------------
# (family, configuration, rows the harness counts, kernel rows, query
# heads, kv heads, head_dim, pool as HELD, table width, window, q dtype):
# GPT-2 XL's held pool, SmallThinker's full and window layers, the sparse
# cell's (row, KV group) rows over 128-entry attended tables, SambaY's
# 128-entry pool and 8-entry ring read with float32 pair-head queries

_WALK_CELLS = {
    "gpt2-xl": ("gpt2", "gpt2-xl", 8, 8, 25, 25, 64, (160, 16, 32, 128),
                40, None, jnp.bfloat16),
    "smallthinker-full": ("smallthinker", "smallthinker-21b-l8", 16, 16, 28,
                          4, 128, (800, 128, 4, 128), 100, None,
                          jnp.bfloat16),
    "smallthinker-window": ("smallthinker", "smallthinker-21b-l8", 16, 16,
                            28, 4, 128, (800, 128, 4, 128), 100, 4096,
                            jnp.bfloat16),
    "minicpm-sala": ("sala", "minicpm-sala-l12", 16, 32, 32, 2, 128,
                     (4608, 64, 2, 128), 128, None, jnp.bfloat16),
    "phi4-pool": ("sambay", "phi4-mini-flash", 16, 16, 40, 10, 128,
                  (1024, 64, 16, 128), 128, None, jnp.float32),
    "phi4-ring": ("sambay", "phi4-mini-flash", 16, 16, 40, 10, 128,
                  (128, 64, 16, 128), 8, None, jnp.float32),
}


def _scoped_vmem(line: str) -> str:
    """The scoped-VMEM request a Mosaic call carries in its backend
    config (empty: the compiler's default)."""
    return re.search(r'"scoped_memory_configs":\[([^\]]*)\]', line).group(1)


@pytest.mark.parametrize("cell", list(_WALK_CELLS))
def test_decode_walk_compiles_at_the_serve_cells_shapes(v5e, cell):
    """One query a row (the walk): the instruction is still
    `_paged_attention_call` with the query operand the family's
    `decode_query_pattern` finds, the pools go in as held (no pool- or
    corner-shaped copy or pad), and the walk asks for no scoped VMEM
    beyond the compiler's default where the assembly of the same call
    asks for its whole table's worth."""
    from chipbench import manifest as mf
    family, config, rows, B, H, KV, D, held, nblk, window, qdt = \
        _WALK_CELLS[cell]
    fam = mf.load_module(f"chipbench/families/{family}.py")
    shape = fam.Shape(mf.load_json(f"chipbench/configs/{config}.json"))
    sh = SingleDeviceSharding(v5e[0])
    pool = _sds(held, jnp.bfloat16, sh)
    args = (_sds((B, 1, H, D), qdt, sh), pool, pool,
            _sds((B, nblk), jnp.int32, sh), _sds((B,), jnp.int32, sh))
    compiled = _aot_compile(jax.jit(functools.partial(
        pallas_paged._paged_attention_call, interpret=False, window=window,
        kv_heads=KV)), args)
    text = compiled.as_text()
    (call,) = [line for line in text.splitlines()
               if re.search(r"%_paged_attention_call[.\d]* = .*custom-call",
                            line)]
    assert re.search(fam.decode_query_pattern(shape, rows),
                     call.replace(" ", ""))
    for pool_shape in (held, held[:2] + (KV, D)):
        text_shape = "bf16[%s]" % ",".join(map(str, pool_shape))
        assert not re.search(re.escape(text_shape) + r"\{[^ ]* (copy|pad)\(",
                             text), text_shape
    assert _scoped_vmem(call) == ""
    # what the walk holds in VMEM (K and V, two slots of whole held
    # blocks) against what the assembly of the same call asks for
    BS = held[1]
    walk = 2 * 2 * pallas_paged.walk_chunk_blocks(BS, nblk) * BS \
        * held[2] * held[3] * 2
    entries = pallas_paged.window_entries(window, 1, BS, nblk)
    assert 4 * walk <= pallas_paged._vmem_limit_bytes(
        H // KV, KV, D, BS, entries, 2)


# -- latent attention at the JoyAI-LLM-Flash cell's shapes --------------------
# (16 rows, 32 heads, the 576-value latent held at 640 lanes, 1,536 pool
# blocks of 64, tables of 192 entries; the prefill's published form at
# the largest bucket over the whole table). The model runs both in
# float32 (`models/mla_lm.py`); the kernels take bfloat16 too.

@pytest.mark.parametrize("dtype", ["bf16", "f32"])
@pytest.mark.parametrize("T", [1, 4])
def test_latent_decode_compiles_and_reads_the_pool_once(v5e, T, dtype):
    """The absorbed walk: ONE `_latent_attention_call` with the pool as
    its operand and no copy or pad of it (the block is the key and the
    value), within the compiler's default scoped VMEM; a verify's `T`
    queries a row share the walk."""
    from horovod_tpu.ops import pallas_latent
    dt = {"bf16": jnp.bfloat16, "f32": jnp.float32}[dtype]
    sh = SingleDeviceSharding(v5e[0])
    pool = _sds((1536, 64, 640), dt, sh)
    compiled = _aot_compile(jax.jit(functools.partial(
        pallas_latent._latent_attention_call, scale=192 ** -0.5,
        value_lanes=512, interpret=False)), (
            _sds((16, T, 32, 640), dt, sh), pool,
            _sds((16, 192), jnp.int32, sh), _sds((16,), jnp.int32, sh)))
    text = compiled.as_text()
    (call,) = [line for line in text.splitlines() if re.search(
        r"%_latent_attention_call[.\d]* = .*custom-call", line)]
    assert f"{dtype}[1536,64,640]" in call.replace(" ", "")
    assert not re.search(dtype + r"\[1536,64,640\]\{[^ ]* (copy|pad)\(",
                         text)
    assert _scoped_vmem(call) == ""


@pytest.mark.parametrize("dtype", ["bf16", "f32"])
def test_flash_prefill_takes_a_scale_and_narrower_values(v5e, dtype):
    """Keys of 192 (128 + 64) and values of 128 over a 12,288-key table,
    the largest bucket's queries: the forward compiles, asks for the
    VMEM its two widths need, and writes values' width."""
    from horovod_tpu.ops.pallas_attention import flash_prefill
    dt = {"bf16": jnp.bfloat16, "f32": jnp.float32}[dtype]
    sh = SingleDeviceSharding(v5e[0])
    compiled = _aot_compile(jax.jit(functools.partial(
        flash_prefill, interpret=False, scale=192 ** -0.5)), (
            _sds((1, 32, 10368, 192), dt, sh),
            _sds((1, 32, 12288, 192), dt, sh),
            _sds((1, 32, 12288, 128), dt, sh),
            _sds((1,), jnp.int32, sh)))
    (call,) = [line for line in compiled.as_text().splitlines()
               if re.search(r"%flash_prefill[.\d]* = .*custom-call", line)]
    assert call.replace(" ", "").startswith("%flash_prefill") and \
        f"{dtype}[1,32,10752,128]" in call.replace(" ", "")
    assert _scoped_vmem(call) != ""
