"""Llama-family decoder LM: RMSNorm, rotary embeddings, SwiGLU, GQA.

Extends the model zoo beyond GPT with the architecture that dominates
current open-weight LMs. The reference framework is model-agnostic (its
examples stop at ResNet/transformer encoders); this family exists so
the TPU framework's parallelism stack (TP partition rules, ring/Ulysses
sequence parallelism, DP/PP composition) is demonstrated on a modern
pretraining target, the same way models/gpt.py does for GPT-2.

TPU-first design notes:
* RoPE is computed in f32 and applied with rotate-half (two multiplies
  + one add — XLA fuses it into the surrounding matmuls' epilogue).
* GQA stores num_kv_heads K/V projections and keeps them at kv width
  everywhere: the Pallas flash kernels read kv head h // G via block
  index maps (never expanding K/V in HBM, forward or backward), and on
  the sequence-parallel path the kv-width tensors go through the
  ring/Ulysses collectives with heads broadcast locally — ICI traffic
  shrinks by H/H_kv, which is the point of GQA at long context.
* Attention runs through ops/pallas_attention.fused_attention (flash
  kernel on TPU) or parallel/sp ring/Ulysses under shard_map when a
  sequence axis is configured — identical plumbing to models/gpt.py.
* All matmuls are bf16 with f32 params (MXU-native); norms in f32.
"""
from functools import partial
from typing import Any, Optional

import jax
import jax.numpy as jnp
from flax import linen as nn
from jax.sharding import Mesh, PartitionSpec as P

from ..parallel import sp as sp_lib
from ..serve import kv_cache as kvc


class LlamaConfig:
    def __init__(self, vocab_size=256, num_layers=2, num_heads=4,
                 num_kv_heads: Optional[int] = None, head_dim=16,
                 mlp_dim: Optional[int] = None, max_seq_len=512,
                 rope_theta: float = 10000.0,
                 attention: str = "dense", mesh: Optional[Mesh] = None,
                 sp_axis: str = "sp", dp_axis: str = "dp",
                 tp_axis: str = "tp", dtype=jnp.bfloat16,
                 attention_impl: Optional[str] = None,
                 remat: bool = False,
                 logits_dtype=jnp.float32,
                 decode: bool = False,
                 kv_block_size: int = kvc.KV_BLOCK_SIZE,
                 kv_pool_blocks: int = 0,
                 decode_kernel: Optional[str] = None):
        if decode_kernel not in (None, "pallas", "xla"):
            raise ValueError(
                f"decode_kernel must be None (resolve from "
                f"HOROVOD_SERVE_KERNEL at executor build), 'pallas' or "
                f"'xla'; got {decode_kernel!r}")
        if decode and attention != "dense":
            raise ValueError(
                f"decode mode supports attention='dense' only (got "
                f"{attention!r}); sequence parallelism shards the axis "
                "the KV cache grows along")
        if kv_pool_blocks and not decode:
            raise ValueError("a KV pool is a decode-mode cache")
        if decode and (kv_block_size < 1 or kv_pool_blocks < 0):
            raise ValueError(
                f"decode mode needs kv_block_size >= 1 and "
                f"kv_pool_blocks >= 0 (0: the executor sizes the pool); "
                f"got {kv_block_size}, {kv_pool_blocks}")
        self.vocab_size = vocab_size
        self.num_layers = num_layers
        self.num_heads = num_heads
        self.num_kv_heads = num_kv_heads or num_heads
        if num_heads % self.num_kv_heads:
            raise ValueError(
                f"num_heads={num_heads} must be a multiple of "
                f"num_kv_heads={self.num_kv_heads}")
        self.head_dim = head_dim
        self.embed_dim = num_heads * head_dim
        # Llama uses ~8/3 * d, rounded; keep it lane-aligned
        self.mlp_dim = mlp_dim or _round_up(8 * self.embed_dim // 3, 128)
        self.max_seq_len = max_seq_len
        self.rope_theta = rope_theta
        #: dense | ring | ulysses | zigzag (causally load-balanced ring;
        #: the residual stream runs zigzag-permuted between embed and
        #: final norm — user-invisible, logits return in natural order)
        self.attention = attention
        self.mesh = mesh
        self.sp_axis = sp_axis
        self.dp_axis = dp_axis
        self.tp_axis = tp_axis
        self.dtype = dtype
        self.attention_impl = attention_impl
        #: per-block activation checkpointing (see GPTConfig.remat)
        self.remat = remat
        #: lm_head compute dtype (see GPTConfig.logits_dtype): float32
        #: is the conservative default; bfloat16 halves the [B, S, V]
        #: logits/dlogits HBM traffic — the fused CE kernel computes in
        #: f32 internally either way
        self.logits_dtype = logits_dtype
        #: inference mode (horovod_tpu/serve): attention threads a KV
        #: block pool at kv width (GQA's H/KV HBM saving carries
        #: straight into the cache) and __call__ takes per-row
        #: `positions`, `update_mask` and `block_tables` at fixed
        #: [rows, T] shapes
        self.decode = decode
        #: the decode cache's block size and pool size (see
        #: GPTConfig.kv_block_size): GQA's HBM saving compounds with
        #: token-bounded occupancy
        self.kv_block_size = kv_block_size
        self.kv_pool_blocks = kv_pool_blocks
        #: decode attention implementation (see
        #: GPTConfig.decode_kernel): "pallas" | "xla" | None = resolve
        #: from HOROVOD_SERVE_KERNEL at executor build
        self.decode_kernel = decode_kernel


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def rope_frequencies(head_dim: int, max_seq_len: int,
                     theta: float) -> jax.Array:
    """[max_seq_len, head_dim/2] rotation angles, f32."""
    inv = 1.0 / (theta ** (jnp.arange(0, head_dim, 2,
                                      dtype=jnp.float32) / head_dim))
    return jnp.outer(jnp.arange(max_seq_len, dtype=jnp.float32), inv)


def apply_rope(x: jax.Array, angles: jax.Array) -> jax.Array:
    """Rotate-half RoPE. x [B, H, S, D]; angles [S, D/2] or, for
    per-row windows (decode: each cache slot sits at its own absolute
    position), [B, S, D/2] (f32).

    Positions are absolute over the given angle slice, so sequence-
    parallel shards pass their own angle window (see Attention)."""
    B, H, S, D = x.shape
    xf = x.astype(jnp.float32).reshape(B, H, S, D // 2, 2)
    x1, x2 = xf[..., 0], xf[..., 1]
    if angles.ndim == 3:     # [B, S, D/2] -> broadcast over heads
        cos = jnp.cos(angles)[:, None]
        sin = jnp.sin(angles)[:, None]
    else:                    # [S, D/2] -> broadcast over batch + heads
        cos = jnp.cos(angles)[None, None]
        sin = jnp.sin(angles)[None, None]
    out = jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.reshape(B, H, S, D).astype(x.dtype)


class RMSNorm(nn.Module):
    eps: float = 1e-6

    @nn.compact
    def __call__(self, x):
        xf = x.astype(jnp.float32)
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],),
                           jnp.float32)
        norm = xf * jax.lax.rsqrt(
            jnp.mean(xf * xf, axis=-1, keepdims=True) + self.eps)
        return (norm * scale).astype(x.dtype)


class LlamaAttention(nn.Module):
    """Causal GQA attention with RoPE; dense / ring / ulysses dispatch
    mirrors models/gpt.py Attention."""
    cfg: Any

    @nn.compact
    def __call__(self, x, positions=None, update_mask=None,
                 block_tables=None):
        cfg = self.cfg
        B, S, _ = x.shape
        H, KV, D = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
        dense = partial(nn.Dense, use_bias=False, dtype=cfg.dtype,
                        param_dtype=jnp.float32)
        q = dense(H * D, name="wq")(x).reshape(B, S, H, D)
        k = dense(KV * D, name="wk")(x).reshape(B, S, KV, D)
        v = dense(KV * D, name="wv")(x).reshape(B, S, KV, D)

        if cfg.decode:
            # serving path: rotate the S new tokens by each row's
            # absolute positions, write K/V (kv width — GQA) into this
            # layer's block pool, attend over each row's blocks
            # (horovod_tpu/serve/kv_cache.py). Keys are cached
            # post-RoPE, the standard absolute-rotation layout (which
            # is also what makes a cached shared-prefix block reusable
            # verbatim across sequences: the rotation is absolute).
            table = rope_frequencies(D, cfg.max_seq_len, cfg.rope_theta)
            win = table[positions[:, None] + jnp.arange(S)[None, :]]
            q = apply_rope(q.transpose(0, 2, 1, 3), win)
            k = apply_rope(k.transpose(0, 2, 1, 3), win)
            q, k = q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3)
            o = kvc.pool_attention(self, cfg, q, k, v, positions,
                                   update_mask, block_tables)
            return dense(cfg.embed_dim, name="wo")(
                o.reshape(B, S, H * D))

        q, k, v = (t.transpose(0, 2, 1, 3) for t in (q, k, v))

        sp = (cfg.attention in ("ring", "ulysses", "zigzag")
              and cfg.mesh is not None
              and cfg.sp_axis in cfg.mesh.axis_names)
        angles = rope_frequencies(D, cfg.max_seq_len, cfg.rope_theta)
        if sp:
            mesh_axes = cfg.mesh.axis_names
            b_ax = cfg.dp_axis if cfg.dp_axis in mesh_axes else None
            h_ax = cfg.tp_axis if cfg.tp_axis in mesh_axes else None
            spec = P(b_ax, h_ax, cfg.sp_axis, None)
            attn = {"ring": sp_lib.ring_attention,
                    "ulysses": sp_lib.ulysses_attention,
                    "zigzag": sp_lib.zigzag_ring_attention}[cfg.attention]
            sp_impl, vma = sp_lib.sp_impl_for(cfg.attention_impl)

            def sharded(q, k, v):
                # each sp shard rotates by its absolute position window;
                # k/v stay kv-width — ring/ulysses broadcast heads
                # locally, so ICI traffic is H/KV times smaller
                idx = jax.lax.axis_index(cfg.sp_axis)
                s_loc = q.shape[2]
                if cfg.attention == "zigzag":
                    # local rows are chunks (idx, 2n-1-idx) of 2n — the
                    # RoPE window follows the true zigzag positions
                    n_sp = jax.lax.psum(1, cfg.sp_axis)
                    c = s_loc // 2
                    win = jnp.concatenate([
                        jax.lax.dynamic_slice_in_dim(
                            angles, idx * c, c, axis=0),
                        jax.lax.dynamic_slice_in_dim(
                            angles, (2 * n_sp - 1 - idx) * c, c, axis=0),
                    ])
                else:
                    win = jax.lax.dynamic_slice_in_dim(
                        angles, idx * s_loc, s_loc, axis=0)
                qr = apply_rope(q, win)
                kr = apply_rope(k, win)
                return attn(qr, kr, v, axis_name=cfg.sp_axis, causal=True,
                            impl=sp_impl)

            o = jax.shard_map(sharded, mesh=cfg.mesh,
                              in_specs=(spec, spec, spec), out_specs=spec,
                              check_vma=vma)(q, k, v)
        else:
            q = apply_rope(q, angles[:S])
            k = apply_rope(k, angles[:S])
            # kv-width k/v go straight in: the pallas kernels are
            # GQA-aware (the reference fallback expands internally)
            from ..ops.pallas_attention import fused_attention
            o = fused_attention(q, k, v, causal=True,
                                force=cfg.attention_impl, mesh=cfg.mesh,
                                batch_axis=cfg.dp_axis,
                                head_axis=cfg.tp_axis)

        o = o.transpose(0, 2, 1, 3).reshape(B, S, H * D)
        return dense(cfg.embed_dim, name="wo")(o)


class SwiGLU(nn.Module):
    cfg: Any

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        dense = partial(nn.Dense, use_bias=False, dtype=cfg.dtype,
                        param_dtype=jnp.float32)
        g = dense(cfg.mlp_dim, name="gate")(x)
        u = dense(cfg.mlp_dim, name="up")(x)
        return dense(cfg.embed_dim, name="down")(nn.silu(g) * u)


class LlamaBlock(nn.Module):
    cfg: Any

    @nn.compact
    def __call__(self, x, positions=None, update_mask=None,
                 block_tables=None):
        x = x + LlamaAttention(self.cfg, name="attn")(
            RMSNorm(name="attn_norm")(x), positions=positions,
            update_mask=update_mask, block_tables=block_tables)
        return x + SwiGLU(self.cfg, name="mlp")(
            RMSNorm(name="mlp_norm")(x))


class Llama(nn.Module):
    cfg: Any

    @nn.compact
    def __call__(self, tokens, positions=None, update_mask=None,
                 block_tables=None, logits_idx=None):
        cfg = self.cfg
        if cfg.decode and (positions is None or update_mask is None):
            raise ValueError(
                "decode mode needs per-row `positions` and `update_mask` "
                "(see horovod_tpu/serve/executor.py)")
        if tokens.shape[1] > cfg.max_seq_len:
            # fail loudly: the sp path would otherwise silently clamp
            # RoPE windows past the angle table (duplicated positions)
            raise ValueError(
                f"sequence length {tokens.shape[1]} exceeds "
                f"max_seq_len={cfg.max_seq_len}")
        x = nn.Embed(cfg.vocab_size, cfg.embed_dim,
                     param_dtype=jnp.float32, name="embed")(tokens)
        x = x.astype(cfg.dtype)
        zig = (cfg.attention == "zigzag" and cfg.mesh is not None
               and cfg.sp_axis in cfg.mesh.axis_names)
        if zig:
            # the residual stream runs in the zigzag order between the
            # embedding and the final norm: one gather each way for the
            # whole model, RMSNorm/SwiGLU are position-independent, and
            # attention masks/RoPE use the true positions
            n_sp = cfg.mesh.shape[cfg.sp_axis]
            if tokens.shape[1] % (2 * n_sp):
                raise ValueError(
                    f"zigzag needs seq {tokens.shape[1]} divisible by "
                    f"2*sp={2 * n_sp}")
            x = sp_lib.zigzag_shard(x, n_sp, seq_axis=1)
        block_cls = nn.remat(LlamaBlock) if cfg.remat else LlamaBlock
        for i in range(cfg.num_layers):
            x = block_cls(cfg, name=f"layers_{i}")(
                x, positions=positions, update_mask=update_mask,
                block_tables=block_tables)
        if zig:
            x = sp_lib.zigzag_unshard(x, n_sp, seq_axis=1)
        x = RMSNorm(name="norm_f")(x)
        if logits_idx is not None:
            # serving: gather each row's emitting position BEFORE the
            # lm_head so the step's largest GEMM runs at [B, 1, V]
            # (see models/gpt.py)
            x = jnp.take_along_axis(
                x, logits_idx.astype(jnp.int32)[:, None, None], axis=1)
        return nn.Dense(cfg.vocab_size, use_bias=False,
                        dtype=cfg.logits_dtype,
                        param_dtype=jnp.float32, name="lm_head")(x)


def llama_partition_rules(tp_axis: str = "tp"):
    """Megatron-style TP rules for the Llama family.

    Column-parallel: wq/wk/wv and gate/up (output features over tp);
    row-parallel: wo/down (input features over tp; XLA inserts the
    psum). With GQA, num_kv_heads must be divisible by the tp degree
    or XLA falls back to a halo exchange — keep kv_heads % tp == 0.
    """
    from ..parallel.tp import PartitionRules
    return PartitionRules([
        (r"attn/w[qkv]/kernel", P(None, tp_axis)),
        (r"attn/wo/kernel", P(tp_axis, None)),
        (r"mlp/(gate|up)/kernel", P(None, tp_axis)),
        (r"mlp/down/kernel", P(tp_axis, None)),
        (r"embed/embedding", P(None, tp_axis)),
        (r"lm_head/kernel", P(None, tp_axis)),
    ])


#: ~1.1B-param pretraining shape (TinyLlama-class), for benchmarks
Llama_1B = partial(LlamaConfig, num_layers=22, num_heads=32,
                   num_kv_heads=4, head_dim=64, vocab_size=32000,
                   max_seq_len=2048)
