"""GPT-style decoder transformer, built for hybrid dp/tp/sp meshes.

The long-context / distributed flagship: parameters follow Megatron-style
tensor-parallel partition rules (parallel/tp.py:gpt_partition_rules), the
batch shards over 'dp', and attention can run as ring attention or Ulysses
over an 'sp' axis (parallel/sp.py) for sequences longer than one device's
memory. Everything is standard flax under jit+GSPMD; the sp attention drops
into shard_map over the same mesh.

bfloat16 compute, float32 params; pre-LN blocks; learned positions.
"""
from __future__ import annotations

from dataclasses import field
from functools import partial
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from ..parallel import sp as sp_lib
from ..serve import kv_cache as kvc


class GPTConfig:
    def __init__(self, vocab_size=256, num_layers=2, num_heads=4,
                 head_dim=16, mlp_ratio=4, max_seq_len=512,
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
        if decode_kernel not in (None, "pallas", "xla"):
            raise ValueError(
                f"decode_kernel must be None (resolve from "
                f"HOROVOD_SERVE_KERNEL at executor build), 'pallas' or "
                f"'xla'; got {decode_kernel!r}")
        self.vocab_size = vocab_size
        self.num_layers = num_layers
        self.num_heads = num_heads
        self.head_dim = head_dim
        self.embed_dim = num_heads * head_dim
        self.mlp_dim = self.embed_dim * mlp_ratio
        self.max_seq_len = max_seq_len
        self.attention = attention   # dense | ring | ulysses | zigzag
        self.mesh = mesh
        self.sp_axis = sp_axis
        self.dp_axis = dp_axis
        self.tp_axis = tp_axis
        self.dtype = dtype
        # None = auto (pallas on TPU, reference elsewhere);
        # "pallas" | "reference" | "interpret" to force
        self.attention_impl = attention_impl
        #: rematerialize each block on the backward pass (activation
        #: checkpointing, jax.checkpoint) — trades ~1/3 more FLOPs for
        #: O(layers) less activation HBM; essential at long context
        self.remat = remat
        #: lm_head compute dtype. float32 is the conservative default;
        #: bfloat16 runs the head matmul (the largest GEMM in the step)
        #: at MXU bf16 rate and halves the [B, S, V] logits/dlogits HBM
        #: traffic — the fused CE kernel upcasts to f32 INTERNALLY
        #: either way (ops/pallas_ce.py), so only the stored logit
        #: values lose precision (standard TPU LM recipe)
        self.logits_dtype = logits_dtype
        #: inference mode (horovod_tpu/serve): attention threads a KV
        #: block pool (flax "cache" collection) and __call__ takes
        #: per-row `positions`, `update_mask` and `block_tables` at
        #: fixed [rows, T] shapes — the serving executor's
        #: no-recompile contract
        self.decode = decode
        #: the decode cache: blocks of this many tokens in a pool of
        #: kv_pool_blocks (serve/kv_cache.py), addressed by per-row
        #: block tables passed to __call__ — occupancy is bounded by
        #: tokens resident, not rows x max_seq_len. kv_pool_blocks 0:
        #: the model's ShardedExecutor sizes the pool for its worst
        #: case, max_batch x ceil(max_len / kv_block_size).
        self.kv_block_size = kv_block_size
        self.kv_pool_blocks = kv_pool_blocks
        #: decode attention implementation: "pallas" (the fused
        #: block-table-aware kernel, ops/pallas_paged.py — interpret
        #: mode off TPU), "xla" (the gather+masked-einsum oracle), or
        #: None — resolve from HOROVOD_SERVE_KERNEL once at executor
        #: build (serve/executor.py)
        self.decode_kernel = decode_kernel


class Attention(nn.Module):
    """Multi-head attention; `causal=False` makes it the encoder flavor
    (shared with models/vit.py)."""
    cfg: Any
    causal: bool = True

    @nn.compact
    def __call__(self, x, positions=None, update_mask=None,
                 block_tables=None):
        cfg = self.cfg
        B, S, _ = x.shape
        qkv = nn.Dense(3 * cfg.embed_dim, dtype=cfg.dtype,
                       param_dtype=jnp.float32, name="qkv")(x)
        qkv = qkv.reshape(B, S, 3, cfg.num_heads, cfg.head_dim)

        # getattr: this Attention is shared by ViT/MoE whose configs
        # predate the decode flag
        if getattr(cfg, "decode", False):
            # serving path: write the S new tokens' K/V into this
            # layer's block pool through each row's table, then attend
            # over the row's blocks (serve/kv_cache.py). Same qkv/out
            # params as training — the pool lives in the separate
            # "cache" collection.
            o = kvc.pool_attention(
                self, cfg, qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2],
                positions, update_mask, block_tables)
            o = o.reshape(B, S, cfg.embed_dim)
            return nn.Dense(cfg.embed_dim, dtype=cfg.dtype,
                            param_dtype=jnp.float32, name="out")(o)

        q, k, v = [qkv[:, :, i].transpose(0, 2, 1, 3) for i in range(3)]

        if cfg.attention in ("ring", "ulysses", "zigzag") \
                and cfg.mesh is not None:
            attn = {"ring": sp_lib.ring_attention,
                    "ulysses": sp_lib.ulysses_attention,
                    "zigzag": sp_lib.zigzag_ring_attention}[cfg.attention]
            sp_impl, vma = sp_lib.sp_impl_for(cfg.attention_impl)
            mesh_axes = cfg.mesh.axis_names
            b_ax = cfg.dp_axis if cfg.dp_axis in mesh_axes else None
            h_ax = cfg.tp_axis if cfg.tp_axis in mesh_axes else None
            spec = P(b_ax, h_ax, cfg.sp_axis, None)
            o = jax.shard_map(
                partial(attn, axis_name=cfg.sp_axis, causal=self.causal,
                        impl=sp_impl),
                mesh=cfg.mesh,
                in_specs=(spec, spec, spec), out_specs=spec,
                check_vma=vma,
            )(q, k, v)
        else:
            # fused pallas kernel on TPU (per shard of the mesh, if
            # any), dense reference elsewhere
            from ..ops.pallas_attention import fused_attention
            o = fused_attention(q, k, v, causal=self.causal,
                                force=cfg.attention_impl, mesh=cfg.mesh,
                                batch_axis=cfg.dp_axis,
                                head_axis=cfg.tp_axis)

        o = o.transpose(0, 2, 1, 3).reshape(B, S, cfg.embed_dim)
        return nn.Dense(cfg.embed_dim, dtype=cfg.dtype,
                        param_dtype=jnp.float32, name="out")(o)


class MLP(nn.Module):
    cfg: Any

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        h = nn.Dense(cfg.mlp_dim, dtype=cfg.dtype,
                     param_dtype=jnp.float32, name="up")(x)
        h = nn.gelu(h)
        return nn.Dense(cfg.embed_dim, dtype=cfg.dtype,
                        param_dtype=jnp.float32, name="down")(h)


class Block(nn.Module):
    cfg: Any

    @nn.compact
    def __call__(self, x, positions=None, update_mask=None,
                 block_tables=None):
        cfg = self.cfg
        h = nn.LayerNorm(dtype=jnp.float32, name="ln1")(x)
        x = x + Attention(cfg, name="attn")(h, positions=positions,
                                            update_mask=update_mask,
                                            block_tables=block_tables)
        h = nn.LayerNorm(dtype=jnp.float32, name="ln2")(x)
        return x + MLP(cfg, name="mlp")(h)


class GPT(nn.Module):
    cfg: Any

    @nn.compact
    def __call__(self, tokens, positions=None, update_mask=None,
                 block_tables=None, logits_idx=None):
        cfg = self.cfg
        B, S = tokens.shape
        if cfg.decode and (positions is None or update_mask is None):
            raise ValueError(
                "decode mode needs per-row `positions` and `update_mask` "
                "(see horovod_tpu/serve/executor.py)")
        x = nn.Embed(cfg.vocab_size, cfg.embed_dim,
                     param_dtype=jnp.float32, name="embed")(tokens)
        # decode: row i's S tokens sit at absolute positions
        # positions[i] + [0, S) of that row's sequence
        pos_idx = jnp.arange(S)[None] if positions is None \
            else positions[:, None] + jnp.arange(S)[None, :]
        pos = nn.Embed(cfg.max_seq_len, cfg.embed_dim,
                       param_dtype=jnp.float32, name="pos_embed")(pos_idx)
        x = (x + pos).astype(cfg.dtype)
        zig = (cfg.attention == "zigzag" and cfg.mesh is not None
               and cfg.sp_axis in cfg.mesh.axis_names)
        if zig:
            # residual stream in zigzag order between embed (positions
            # already added in natural order) and the final norm — see
            # models/llama.py; causal masks use true positions
            n_sp = cfg.mesh.shape[cfg.sp_axis]
            if S % (2 * n_sp):
                raise ValueError(f"zigzag needs seq {S} divisible by "
                                 f"2*sp={2 * n_sp}")
            x = sp_lib.zigzag_shard(x, n_sp, seq_axis=1)
        block_cls = nn.remat(Block) if cfg.remat else Block
        for i in range(cfg.num_layers):
            x = block_cls(cfg, name=f"layers_{i}")(
                x, positions=positions, update_mask=update_mask,
                block_tables=block_tables)
        if zig:
            x = sp_lib.zigzag_unshard(x, n_sp, seq_axis=1)
        x = nn.LayerNorm(dtype=jnp.float32, name="ln_f")(x)
        if logits_idx is not None:
            # decode/prefill serving: only the per-row emitting
            # position's logits are ever consumed — gather it BEFORE
            # the lm_head so the largest GEMM of the step (and the
            # sampling work downstream) runs at [B, 1, V], not
            # [B, bucket, V] (serve/executor.py)
            x = jnp.take_along_axis(
                x, logits_idx.astype(jnp.int32)[:, None, None], axis=1)
        logits = nn.Dense(cfg.vocab_size, use_bias=False,
                          dtype=cfg.logits_dtype,
                          param_dtype=jnp.float32, name="lm_head")(x)
        return logits
