"""Decoder LM whose every layer routes to experts, with per-layer
attention kinds: the serving model of the sparse long-context family.

What sets it apart from `models/llama.py` (whose `RMSNorm`, RoPE and
GQA plumbing it reuses) and `models/moe.py` (Switch-style GELU experts
under a capacity, training only):

* **Per-layer attention kinds over one KV pool.** ``rope_layout[l]``
  says whether layer ``l`` rotates q and k (RoPE) or carries no position
  encoding at all; ``window_layout[l]`` whether its queries see only
  the newest ``window`` keys or the whole context. Every layer writes
  the same paged pool layout through the same block tables
  (`serve/kv_cache.py`); a window layer simply never reads behind its
  window (`ops/pallas_paged.py` ``window=``).
* **Dropless routed experts, routed before attention.** The router
  reads the layer's normed INPUT, so its logits are taken before the
  attention residual; the gated (ReGLU) experts then run on the normed
  post-attention stream over dropless top-k routes
  (`parallel/ep.py routed_experts`): no capacity, no token dropped, a
  row's answer independent of its batch-mates. Padding positions and
  idle rows are routed nowhere.
* **Parameters in the dtype the config names** (``param_dtype``): a
  checkpoint published in bfloat16 is held in bfloat16.
* **A float32 residual stream.** ``dtype`` (bfloat16) is what the
  matmuls' operands are rounded to; the stream they add into, the
  norms and the router's logits are float32, and every projection back
  into the stream hands over its float32 accumulator unrounded. The
  top-k of 64 router logits is a near-tie somewhere in every long
  request, and a flipped expert moves the logits by more than all the
  other rounding together (PERF.md, PR 30): the router must not see
  rounding the matmuls do not need.
* **Long prompts.** A step of `FLASH_MIN_TOKENS` or more tokens a row
  (a prefill) attends with the flash forward over the row's gathered
  blocks (`ops/pallas_attention.py flash_prefill`: the cached prefix,
  then the fresh tokens; blocks behind a window skipped), never with a
  dense ``[tokens, context]`` score tensor. ``prefill_rows`` tells the
  batcher to prefill that many rows a step instead of packing
  ``[max_batch, bucket]`` (`serve/batcher.py`).

Serving only (``decode=True``, paged): the model is driven by
`serve.ShardedExecutor` like `GPT` and `Llama`. Each layer sows the
number of experts that received a token into the ``stats`` collection;
the executor returns the sum with the step's tokens.
"""
from functools import partial
from typing import Any, Optional, Sequence

import jax
import jax.numpy as jnp
from flax import linen as nn

from ..parallel import ep
from .llama import RMSNorm, apply_rope


#: steps of at least this many tokens a row attend with the flash forward;
#: shorter ones (decode, speculative verify) with the paged kernel
FLASH_MIN_TOKENS = 16


class RoutedLMConfig:
    def __init__(self, vocab_size=256, num_layers=4, embed_dim=64,
                 num_heads=4, num_kv_heads: Optional[int] = None,
                 head_dim=16, num_experts=8, experts_per_token=2,
                 expert_dim=32,
                 rope_layout: Optional[Sequence[int]] = None,
                 window_layout: Optional[Sequence[int]] = None,
                 window: int = 64, rope_theta: float = 10000.0,
                 rms_eps: float = 1e-6, max_seq_len=512,
                 dtype=jnp.bfloat16, param_dtype=jnp.bfloat16,
                 logits_dtype=jnp.float32, decode: bool = True,
                 kv_block_size: int = 0, kv_pool_blocks: int = 0,
                 decode_kernel: Optional[str] = None,
                 prefill_rows: int = 1):
        if decode_kernel not in (None, "pallas", "xla"):
            raise ValueError(
                f"decode_kernel must be None (resolve from "
                f"HOROVOD_SERVE_KERNEL at executor build), 'pallas' or "
                f"'xla'; got {decode_kernel!r}")
        if not decode or not kv_block_size or kv_pool_blocks < 1:
            raise ValueError(
                "RoutedLM is a serving model over the paged KV pool: "
                "decode=True, kv_block_size > 0 and kv_pool_blocks >= 1")
        self.num_kv_heads = num_kv_heads or num_heads
        if num_heads % self.num_kv_heads:
            raise ValueError(
                f"num_heads={num_heads} must be a multiple of "
                f"num_kv_heads={self.num_kv_heads}")
        if not 1 <= experts_per_token <= num_experts:
            raise ValueError(
                f"experts_per_token={experts_per_token} must be in "
                f"[1, num_experts={num_experts}]")
        # one period of 4 where nothing is said: a full layer without
        # position encoding, then three window layers with RoPE
        if rope_layout is None:
            rope_layout = [int(i % 4 != 0) for i in range(num_layers)]
        if window_layout is None:
            window_layout = [int(i % 4 != 0) for i in range(num_layers)]
        if len(rope_layout) != num_layers or \
                len(window_layout) != num_layers:
            raise ValueError(
                f"rope_layout ({len(rope_layout)}) and window_layout "
                f"({len(window_layout)}) need one entry per layer "
                f"({num_layers})")
        if window < 1:
            raise ValueError(f"window must be >= 1; got {window}")
        self.vocab_size = vocab_size
        self.num_layers = num_layers
        #: the residual width; NOT tied to num_heads * head_dim
        self.embed_dim = embed_dim
        self.num_heads = num_heads
        self.head_dim = head_dim
        self.num_experts = num_experts
        self.experts_per_token = experts_per_token
        #: each expert's hidden width
        self.expert_dim = expert_dim
        self.rope_layout = tuple(int(bool(r)) for r in rope_layout)
        self.window_layout = tuple(int(bool(w)) for w in window_layout)
        self.window = int(window)
        self.rope_theta = rope_theta
        self.rms_eps = rms_eps
        self.max_seq_len = max_seq_len
        self.dtype = dtype
        self.param_dtype = param_dtype
        self.logits_dtype = logits_dtype
        self.decode = decode
        self.kv_block_size = kv_block_size
        self.kv_pool_blocks = kv_pool_blocks
        #: "pallas" | "xla" | None = resolve at executor build. Names
        #: the whole attention path: the fused paged kernel and the
        #: flash prefill, or their XLA oracle for both
        self.decode_kernel = decode_kernel
        #: rows the batcher prefills in one step (`serve/batcher.py`):
        #: a long prompt is prefilled alone at its own bucket
        self.prefill_rows = int(prefill_rows)

    def layer_window(self, layer: int) -> Optional[int]:
        return self.window if self.window_layout[layer] else None


class Proj(nn.Module):
    """A bias-free projection back into the residual stream: operands in
    the compute dtype, the float32 accumulator handed over unrounded."""
    features: int
    dtype: Any
    param_dtype: Any

    @nn.compact
    def __call__(self, x):
        kernel = self.param("kernel", nn.initializers.lecun_normal(),
                            (x.shape[-1], self.features), self.param_dtype)
        return jnp.dot(x.astype(self.dtype), kernel.astype(self.dtype),
                       preferred_element_type=jnp.float32)


class RoutedAttention(nn.Module):
    """GQA over the paged pool; RoPE and the window per layer."""
    cfg: Any
    rope: bool
    window: Optional[int]

    @nn.compact
    def __call__(self, h, positions, update_mask, block_tables):
        from ..serve import kv_cache as kvc
        cfg = self.cfg
        B, T, _ = h.shape
        H, KV, D = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
        dense = partial(nn.Dense, use_bias=False, dtype=cfg.dtype,
                        param_dtype=cfg.param_dtype)
        q = dense(H * D, name="wq")(h).reshape(B, T, H, D)
        k = dense(KV * D, name="wk")(h).reshape(B, T, KV, D)
        v = dense(KV * D, name="wv")(h).reshape(B, T, KV, D)
        if self.rope:
            # each row's tokens at their absolute positions; keys are
            # cached rotated (a shared prefix block stays reusable)
            inv = 1.0 / (cfg.rope_theta ** (
                jnp.arange(0, D, 2, dtype=jnp.float32) / D))
            pos = positions[:, None] + jnp.arange(T)[None, :]
            angles = pos.astype(jnp.float32)[..., None] * inv
            q = apply_rope(q.transpose(0, 2, 1, 3), angles).transpose(
                0, 2, 1, 3)
            k = apply_rope(k.transpose(0, 2, 1, 3), angles).transpose(
                0, 2, 1, 3)
        pool_k, pool_v = kvc.write_kv_pools(
            self, cfg, k, v, positions, update_mask, block_tables)
        if cfg.decode_kernel != "pallas":
            o = kvc.paged_attention(q, pool_k, pool_v, block_tables,
                                    positions, window=self.window)
        elif T >= FLASH_MIN_TOKENS:
            # a prefill: the row's blocks gathered once ([B, L, KV, D],
            # a cached prefix first), then the flash forward from each
            # row's first fresh position
            from ..ops.pallas_attention import flash_prefill
            tbl = jnp.maximum(block_tables, 0)
            L = tbl.shape[1] * cfg.kv_block_size
            keys = pool_k[tbl].reshape(B, L, KV, D).transpose(0, 2, 1, 3)
            vals = pool_v[tbl].reshape(B, L, KV, D).transpose(0, 2, 1, 3)
            o = flash_prefill(
                q.transpose(0, 2, 1, 3), keys, vals, positions,
                window=self.window,
                interpret=jax.default_backend() != "tpu").transpose(
                    0, 2, 1, 3)
        else:
            from ..ops.pallas_paged import paged_attention_fused
            o = paged_attention_fused(q, pool_k, pool_v, block_tables,
                                      positions, window=self.window)
        return Proj(cfg.embed_dim, cfg.dtype, cfg.param_dtype, name="wo")(
            o.reshape(B, T, H * D))


class RoutedExperts(nn.Module):
    """The expert weights and their dropless top-k application."""
    cfg: Any

    @nn.compact
    def __call__(self, u, router_logits, valid):
        cfg = self.cfg
        B, T, d = u.shape
        E, f = cfg.num_experts, cfg.expert_dim
        init = nn.initializers.normal(0.02)
        w_in = self.param("w_in", init, (E, d, 2 * f), cfg.param_dtype)
        w_out = self.param("w_out", init, (E, f, d), cfg.param_dtype)
        experts, weights = ep.topk_dropless(
            router_logits.reshape(B * T, E), cfg.experts_per_token)
        y, hit = ep.routed_experts(
            u.reshape(B * T, d).astype(cfg.dtype), experts, weights,
            valid.reshape(B * T), w_in, w_out, out_dtype=jnp.float32)
        self.sow("stats", "experts_hit", hit,
                 reduce_fn=lambda a, b: a + b,
                 init_fn=lambda: jnp.zeros((), jnp.int32))
        return y.reshape(B, T, d)


class RoutedBlock(nn.Module):
    cfg: Any
    layer: int

    @nn.compact
    def __call__(self, x, positions, update_mask, block_tables, valid):
        cfg = self.cfg
        h = RMSNorm(eps=cfg.rms_eps, name="attn_norm")(x)      # float32
        # the router reads the layer's normed input, BEFORE attention,
        # unrounded; its logits are float32 at full precision (64
        # columns: the cost is nothing beside the experts')
        router = self.param("router", nn.initializers.normal(0.02),
                            (cfg.embed_dim, cfg.num_experts),
                            cfg.param_dtype)
        logits = jnp.dot(h, router.astype(jnp.float32),
                         precision=jax.lax.Precision.HIGHEST)
        x = x + RoutedAttention(
            cfg, rope=bool(cfg.rope_layout[self.layer]),
            window=cfg.layer_window(self.layer), name="attn")(
                h, positions, update_mask, block_tables)
        u = RMSNorm(eps=cfg.rms_eps, name="mlp_norm")(x)
        return x + RoutedExperts(cfg, name="experts")(u, logits, valid)


class RoutedLM(nn.Module):
    cfg: Any

    @nn.compact
    def __call__(self, tokens, positions=None, update_mask=None,
                 block_tables=None, logits_idx=None):
        cfg = self.cfg
        if positions is None or update_mask is None \
                or block_tables is None:
            raise ValueError(
                "RoutedLM needs per-row `positions`, `update_mask` and "
                "`block_tables` (see horovod_tpu/serve/executor.py)")
        B, T = tokens.shape
        if T > cfg.max_seq_len:
            raise ValueError(f"sequence length {T} exceeds "
                             f"max_seq_len={cfg.max_seq_len}")
        # tokens the experts owe an answer: live rows, up to the
        # emitting position (a prefill's tail is bucket padding)
        valid = jnp.broadcast_to(update_mask[:, None], (B, T))
        if logits_idx is not None:
            valid = valid & (jnp.arange(T)[None, :]
                             <= logits_idx.astype(jnp.int32)[:, None])
        x = nn.Embed(cfg.vocab_size, cfg.embed_dim,
                     param_dtype=cfg.param_dtype, name="embed")(tokens)
        x = x.astype(jnp.float32)       # the residual stream
        for i in range(cfg.num_layers):
            x = RoutedBlock(cfg, layer=i, name=f"layers_{i}")(
                x, positions, update_mask, block_tables, valid)
        x = RMSNorm(eps=cfg.rms_eps, name="norm_f")(x)
        if logits_idx is not None:
            # only each row's emitting position reaches the head
            x = jnp.take_along_axis(
                x, logits_idx.astype(jnp.int32)[:, None, None], axis=1)
        return nn.Dense(cfg.vocab_size, use_bias=False,
                        dtype=cfg.logits_dtype,
                        param_dtype=cfg.param_dtype, name="lm_head")(x)
