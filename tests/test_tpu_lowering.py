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

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

from horovod_tpu.ops import pallas_paged
from horovod_tpu.ops.pallas_attention import flash_attention
from horovod_tpu.ops.pallas_ce import fused_softmax_cross_entropy
from horovod_tpu.serve.kv_cache import pool_blocks_for

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
    nblk = _S // _BLOCK
    pool = _sds((pool_blocks_for(_B, _S, _BLOCK), _BLOCK, KV, D),
                jnp.bfloat16, sharding)
    return (_sds((_B, T, H, D), jnp.bfloat16, sharding), pool, pool,
            _sds((_B, nblk), jnp.int32, sharding),
            _sds((_B,), jnp.int32, sharding))


def _paged(q, pool_k, pool_v, tables, positions):
    return pallas_paged._paged_attention_call(
        q, pool_k, pool_v, tables, positions, interpret=False)


def _entry_points():
    for H, KV, D in _HEADS:
        tag = f"h{H}kv{KV}d{D}"
        yield f"flash_fwd-{tag}", _flash_fwd, \
            functools.partial(_attention_args, H, KV, D)
        yield f"flash_grad-{tag}", _flash_grad, \
            functools.partial(_attention_args, H, KV, D)
        for T in (1, 4):
            yield f"paged_T{T}-{tag}", _paged, \
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
    _aot_compile(jax.jit(_paged), args)


def test_four_chip_train_step_compiles(v5e):
    from jax.sharding import Mesh
    mesh = Mesh(np.array(v5e, dtype=object), ("dp",))
    step, args = _train_step(mesh)
    compiled = _aot_compile(step, args)
    assert "all-reduce" in compiled.as_text()      # the dp gradient sum
