"""Decoder LM with multi-head latent attention (MLA) and DeepSeek-style
routed experts: the serving model of the DeepSeek-V3 layout
(JoyAI-LLM-Flash).

``x`` is the float32 residual stream, ``x0 = embed[token]``. Layer
``l``: ``x += MLA(rms(x))``, then ``x += FFN_l(rms'(x))``; RMSNorm with a
gain; logits ``rms_f(x) . W_head`` (untied, no biases anywhere).

* **MLA.** Queries ``q = W_qb rms_q(W_qa h)``, ``H`` heads of ``N + P``
  (a part without position ``q_n`` and a rotated part ``q_r``). One
  down-projection ``[c ; k_r] = W_kva h`` gives the latent ``c`` (``R``,
  normed) and ONE rope key ``k_r`` (``P``) that every head shares; each
  head's key and value are up-projections of the latent, ``[k_n,i ; v_i]
  = W_kvb,i c``. RoPE over interleaved pairs at absolute positions on
  ``q_r`` and ``k_r``. Scores ``(q_n . k_n + q_r . k_r) / sqrt(N + P)``,
  causal softmax, output ``W_o [sum p_i v_i]_i``.
* **The cache is the latent.** One leaf a layer, ``latent`` ``[pool
  blocks, block, lanes]``: ``[c ; k_r]`` after the norm and the
  rotation, ``R + P`` values a token padded with zeros to whole 128-lane
  tiles, written through the block tables like every pool and copied,
  shared and released with its blocks (a per-BLOCK leaf,
  docs/serving.md). Nothing per head is cached.
* **Decode is absorbed** (`ops/pallas_latent.py`): ``q~_i = [W_UK,i^T
  q_n,i ; q_r,i]`` scores against the cached ``[c ; k_r]`` itself, the
  attention's output is taken in the latent and ``W_UV,i`` applied after
  it: the same numbers by associativity, with the pool read once a step.
* **Prefill is the published form.** A step of `FLASH_MIN_TOKENS` or more
  tokens a row gathers the row's latent blocks (a cached prefix, then
  the fresh tokens, just written), up-projects them per head to ``k_n``
  and ``v``, appends ``k_r`` and runs the flash forward from the row's
  first fresh position (`ops/pallas_attention.py flash_prefill`, keys of
  ``N + P``, values of ``V``). A radix hit therefore costs the prefix's
  up-projection and nothing else.
* **FFN.** The first ``dense_layers`` layers are one SwiGLU of
  ``dense_dim``; the others route in float32: ``s = sigmoid(u . W_r)``,
  the top ``k`` of ``s + b`` (``b`` a selection bias), weights
  ``scale * s_e / sum s`` (`parallel/ep.py topk_dropless`, ``sigmoid``),
  SwiGLU experts over dropless routes (`ep.routed_experts`, ``silu``),
  plus an always-on shared SwiGLU of ``shared_experts * expert_dim``.
* **No activation is rounded.** The weights are bfloat16 (``dtype``);
  every activation, the cached latent included, is float32, and each
  product with a weight takes the activation as the three bfloat16 terms
  that sum to it (`ops/bf16_split.py`), the kernels' products at
  `Precision.HIGHEST`. The router picks 8 of 256 sigmoid scores that lie
  thousandths apart, and one expert chosen for another moves a token's
  logits by up to about 1: a bfloat16 operand anywhere upstream of the
  router flips one in a good share of (token, layer) pairs, float32
  arithmetic in another order about once in tens of thousands.

Serving only (``decode=True``, paged): driven by `serve.ShardedExecutor`
like `models/routed_lm.py`, whose `Proj`, `FLASH_MIN_TOKENS` and float32
stream conventions it shares. Each routed layer sows the experts that
received a token; ``cache_token_bytes`` tells the executor what a token
of context costs, so its decode steps carry what their rows hold.
"""
import math
from typing import Any, Optional

import jax
import jax.numpy as jnp
from flax import linen as nn

from ..ops.bf16_split import split_einsum
from ..parallel import ep
from .llama import RMSNorm, apply_rope
from .routed_lm import FLASH_MIN_TOKENS, Proj


class MLAConfig:
    #: the cache leaves that are no K/V pool, by kind (serve/executor.py)
    cache_leaves = {"latent": "block"}
    #: what a row holds whatever its context (serve/executor.py)
    cache_row_bytes = 0
    #: the batcher prefills one row a step at the row's own bucket
    prefill_rows = 1

    def __init__(self, vocab_size=256, num_layers=3, embed_dim=64,
                 num_heads=4, q_lora_rank=48, kv_lora_rank=32,
                 qk_nope_dim=16, qk_rope_dim=8, v_head_dim=16,
                 dense_layers=1, dense_dim=128, num_experts=8,
                 experts_per_token=2, expert_dim=32, shared_experts=1,
                 routed_scaling=2.5, rope_theta=10000.0, rms_eps=1e-6,
                 max_seq_len=512, dtype=jnp.bfloat16,
                 param_dtype=jnp.bfloat16,
                 decode: bool = True, kv_block_size: int = 0,
                 kv_pool_blocks: int = 0,
                 decode_kernel: Optional[str] = None):
        if decode_kernel not in (None, "pallas", "xla"):
            raise ValueError(
                f"decode_kernel must be None (resolve from "
                f"HOROVOD_SERVE_KERNEL at executor build), 'pallas' or "
                f"'xla'; got {decode_kernel!r}")
        if not decode or not kv_block_size or kv_pool_blocks < 1:
            raise ValueError(
                "MLALM is a serving model over the paged latent pool: "
                "decode=True, kv_block_size > 0 and kv_pool_blocks >= 1")
        if not 1 <= experts_per_token <= num_experts:
            raise ValueError(
                f"experts_per_token={experts_per_token} must be in "
                f"[1, num_experts={num_experts}]")
        if qk_rope_dim % 2 or not 0 <= dense_layers <= num_layers:
            raise ValueError(
                f"qk_rope_dim={qk_rope_dim} must be even (pairs rotate) "
                f"and dense_layers={dense_layers} within the depth")
        self.vocab_size = vocab_size
        self.num_layers = num_layers
        self.embed_dim = embed_dim
        self.num_heads = num_heads
        self.q_lora_rank = q_lora_rank
        self.kv_lora_rank = kv_lora_rank
        self.qk_nope_dim = qk_nope_dim
        self.qk_rope_dim = qk_rope_dim
        self.v_head_dim = v_head_dim
        self.dense_layers = dense_layers
        self.dense_dim = dense_dim
        self.num_experts = num_experts
        self.experts_per_token = experts_per_token
        self.expert_dim = expert_dim
        self.shared_experts = shared_experts
        self.routed_scaling = float(routed_scaling)
        self.rope_theta = rope_theta
        self.rms_eps = rms_eps
        self.max_seq_len = max_seq_len
        self.dtype = dtype
        self.param_dtype = param_dtype
        self.decode = decode
        self.kv_block_size = kv_block_size
        self.kv_pool_blocks = kv_pool_blocks
        #: "pallas" | "xla" | None = resolve at executor build: the
        #: latent walk and the flash prefill, or the absorbed XLA oracle
        #: for both
        self.decode_kernel = decode_kernel

    @property
    def latent_dim(self) -> int:
        """Values a token and layer caches: the latent and the rope key."""
        return self.kv_lora_rank + self.qk_rope_dim

    @property
    def latent_lanes(self) -> int:
        """The latent as held: padded to whole 128-lane tiles."""
        return -(-self.latent_dim // 128) * 128

    @property
    def cache_token_bytes(self) -> int:
        """Bytes of cached VALUES a token of context costs: every
        layer's latent and rope key in float32 (the pool pads them to
        `latent_lanes`)."""
        return self.num_layers * self.latent_dim * 4


def write_latent_pool(module, cfg, latent, positions, update_mask,
                      block_tables):
    """The layer's latent pool (the ``"cache"`` collection of the flax
    ``module`` that calls this) with the ``T`` new tokens' ``[c ; k_r]``
    ([B, T, latent_dim]) written through the tables, zero-padded to
    `latent_lanes` -> the pool ``[pool_blocks, block, lanes]``."""
    from ..serve import kv_cache as kvc
    shape = (cfg.kv_pool_blocks, cfg.kv_block_size, cfg.latent_lanes)
    pool = module.variable("cache", "latent", jnp.zeros, shape, jnp.float32)
    latent = jnp.pad(latent, ((0, 0), (0, 0),
                              (0, cfg.latent_lanes - cfg.latent_dim)))
    pool.value = kvc.write_paged(pool.value, latent, positions, update_mask,
                                 block_tables)
    return pool.value


class MLAttention(nn.Module):
    """Latent attention over the paged latent pool."""
    cfg: Any

    @nn.compact
    def __call__(self, h, positions, update_mask, block_tables):
        cfg = self.cfg
        B, T, _ = h.shape
        H, R, N, P, V = (cfg.num_heads, cfg.kv_lora_rank, cfg.qk_nope_dim,
                         cfg.qk_rope_dim, cfg.v_head_dim)
        dt = cfg.dtype
        proj = lambda n, name: Proj(n, dt, cfg.param_dtype,  # noqa: E731
                                    exact=True, name=name)
        cq = RMSNorm(eps=cfg.rms_eps, name="q_norm")(proj(
            cfg.q_lora_rank, "wq_a")(h))
        q = proj(H * (N + P), "wq_b")(cq).reshape(B, T, H, N + P)
        ckr = proj(R + P, "wkv_a")(h)                         # [B, T, R+P]
        c = RMSNorm(eps=cfg.rms_eps, name="kv_norm")(ckr[..., :R])
        # each row's tokens at their absolute positions; the rope key is
        # cached rotated (a shared prefix block stays reusable)
        inv = 1.0 / (cfg.rope_theta ** (
            jnp.arange(0, P, 2, dtype=jnp.float32) / P))
        pos = positions[:, None] + jnp.arange(T)[None, :]
        angles = pos.astype(jnp.float32)[..., None] * inv     # [B, T, P/2]
        q_n = q[..., :N]
        q_r = apply_rope(q[..., N:].transpose(0, 2, 1, 3), angles).transpose(
            0, 2, 1, 3)
        k_r = apply_rope(ckr[:, None, :, R:], angles)[:, 0]   # [B, T, P]
        pool = write_latent_pool(
            self, cfg, jnp.concatenate([c, k_r], axis=-1), positions,
            update_mask, block_tables)
        # per head: the key's part without position, then the value
        wkv_b = self.param("wkv_b", nn.initializers.lecun_normal(),
                           (R, H * (N + V)), cfg.param_dtype).astype(dt)
        w_uk = wkv_b.reshape(R, H, N + V)[..., :N]
        w_uv = wkv_b.reshape(R, H, N + V)[..., N:]
        scale = 1.0 / math.sqrt(N + P)
        if cfg.decode_kernel == "pallas" and T >= FLASH_MIN_TOKENS:
            # a prefill: the row's latent blocks gathered once (a cached
            # prefix first), each head's keys and values up-projected
            # from them, then the flash forward from each row's first
            # fresh position
            from ..ops.pallas_attention import flash_prefill
            L = block_tables.shape[1] * cfg.kv_block_size
            lat = pool[jnp.maximum(block_tables, 0)].reshape(B, L, -1)
            k_n = split_einsum("blr,rhn->bhln", lat[..., :R], w_uk)
            v = split_einsum("blr,rhv->bhlv", lat[..., :R], w_uv)
            k = jnp.concatenate([k_n, jnp.broadcast_to(
                lat[:, None, :, R:R + P], (B, H, L, P))], axis=-1)
            qh = jnp.concatenate([q_n, q_r], axis=-1)
            o = flash_prefill(
                qh.transpose(0, 2, 1, 3), k, v, positions, scale=scale,
                interpret=jax.default_backend() != "tpu").transpose(
                    0, 2, 1, 3)                               # [B, T, H, V]
        else:
            # absorbed: W_UK into the query, the attention taken in the
            # latent, W_UV after it
            from ..ops import pallas_latent
            q_lat = split_einsum("bthn,rhn->bthr", q_n, w_uk)
            qt = jnp.pad(jnp.concatenate([q_lat, q_r], axis=-1),
                         ((0, 0), (0, 0), (0, 0),
                          (0, cfg.latent_lanes - cfg.latent_dim)))
            attend = pallas_latent.latent_attention_fused \
                if cfg.decode_kernel == "pallas" \
                else pallas_latent.latent_attention
            o_lat = attend(qt, pool, block_tables, positions, scale=scale,
                           value_lanes=R)                     # [B, T, H, R]
            o = split_einsum("bthr,rhv->bthv", o_lat, w_uv)
        return proj(cfg.embed_dim, "wo")(o.reshape(B, T, H * V))


class SwiGLU(nn.Module):
    """``W_down(silu(W_gate u) * W_up u)``, every operand float32."""
    cfg: Any
    width: int

    @nn.compact
    def __call__(self, u):
        cfg = self.cfg
        proj = lambda n, name: Proj(n, cfg.dtype, cfg.param_dtype,  # noqa: E731
                                    exact=True, name=name)
        return proj(cfg.embed_dim, "w_down")(
            jax.nn.silu(proj(self.width, "w_gate")(u))
            * proj(self.width, "w_up")(u))


class RoutedSwiGLU(nn.Module):
    """Sigmoid-routed SwiGLU experts with a selection bias, and the
    shared expert beside them."""
    cfg: Any

    @nn.compact
    def __call__(self, u, valid):
        cfg = self.cfg
        B, T, d = u.shape
        E, f = cfg.num_experts, cfg.expert_dim
        init = nn.initializers.normal(0.02)
        # the router in float32 at full precision on the unrounded
        # stream (its cost is nothing beside the experts')
        router = self.param("router", init, (d, E), cfg.param_dtype)
        bias = self.param("bias", nn.initializers.zeros, (E,), jnp.float32)
        logits = jnp.dot(u.reshape(B * T, d), router.astype(jnp.float32),
                         precision=jax.lax.Precision.HIGHEST)
        experts, weights = ep.topk_dropless(
            logits, cfg.experts_per_token, scoring="sigmoid", bias=bias,
            scale=cfg.routed_scaling)
        w_in = self.param("w_in", init, (E, d, 2 * f), cfg.param_dtype)
        w_out = self.param("w_out", init, (E, f, d), cfg.param_dtype)
        exact = jnp.dtype(cfg.dtype) == jnp.bfloat16
        y, hit = ep.routed_experts(
            u.reshape(B * T, d).astype(jnp.float32 if exact else cfg.dtype),
            experts, weights, valid.reshape(B * T), w_in, w_out,
            out_dtype=jnp.float32, activation="silu", exact=exact)
        self.sow("stats", "experts_hit", hit,
                 reduce_fn=lambda a, b: a + b,
                 init_fn=lambda: jnp.zeros((), jnp.int32))
        return y.reshape(B, T, d) + SwiGLU(
            cfg, cfg.shared_experts * f, name="shared")(u)


class MLABlock(nn.Module):
    cfg: Any
    layer: int

    @nn.compact
    def __call__(self, x, positions, update_mask, block_tables, valid):
        cfg = self.cfg
        h = RMSNorm(eps=cfg.rms_eps, name="attn_norm")(x)     # float32
        x = x + MLAttention(cfg, name="attn")(h, positions, update_mask,
                                              block_tables)
        u = RMSNorm(eps=cfg.rms_eps, name="mlp_norm")(x)
        if self.layer < cfg.dense_layers:
            return x + SwiGLU(cfg, cfg.dense_dim, name="mlp")(u)
        return x + RoutedSwiGLU(cfg, name="moe")(u, valid)


class MLALM(nn.Module):
    cfg: Any

    @nn.compact
    def __call__(self, tokens, positions=None, update_mask=None,
                 block_tables=None, logits_idx=None):
        cfg = self.cfg
        if positions is None or update_mask is None \
                or block_tables is None:
            raise ValueError(
                "MLALM needs per-row `positions`, `update_mask` and "
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
            x = MLABlock(cfg, layer=i, name=f"layers_{i}")(
                x, positions, update_mask, block_tables, valid)
        x = RMSNorm(eps=cfg.rms_eps, name="norm_f")(x)
        if logits_idx is not None:
            # only each row's emitting position reaches the head
            x = jnp.take_along_axis(
                x, logits_idx.astype(jnp.int32)[:, None, None], axis=1)
        return Proj(cfg.vocab_size, cfg.dtype, cfg.param_dtype, exact=True,
                    name="lm_head")(x)
