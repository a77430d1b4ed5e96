"""Decoder-hybrid-decoder LM (SambaY): a self-decoder of Mamba and
window-attention layers, ONE full-attention layer, and a cross-decoder
of gated memory units and cross-attention layers that own no K or V.
The serving model of Phi-4-mini-flash-reasoning.

Layer ``l`` of ``n`` (``mb_per_layer = 2``, ``h = n / 2``) is, by
`layer_kinds`: even ``l <= h`` a **Mamba** layer (`ops/selective_scan.py`);
odd ``l < h`` **window attention** over the newest ``window`` keys;
``l = h + 1`` the **full attention** layer; even ``l > h + 1`` a **gated
memory unit**, ``W_out(silu(W_in h_t) * M_t)`` with ``M_t`` the SSM output
of layer ``h`` (before its gate) at the same token; odd ``l > h + 1``
**cross-attention**, queries of its own over the full layer's K and V.
Every attention is differential: heads pair up, each pair runs two
softmaxes over one V and subtracts, ``a1 - lam * a2``, under an RMS norm
of the pair's width. No position encoding; LayerNorm with gain and bias;
the head is the embedding, transposed.

What sets it apart in the ``cache`` collection (docs/serving.md):

* **Two per-ROW leaves a Mamba layer**, ``conv`` ``[state_rows, d_conv,
  d_inner]`` and ``ssm`` ``[state_rows, d_state, d_inner]`` float32
  (channels on the lanes), addressed by ``state_slots``; a row at
  position 0 starts from zero, a masked row and a bucket's padding leave
  both untouched.
* **A window layer keeps a RING a row, not the context:** ``ring_k`` /
  ``ring_v`` ``[state_rows, window / block, block, pairs, 2 D]``, per-ROW
  leaves too. Token ``p`` lies at slot ``p mod window`` (no position
  encoding: a ring needs no order); a decode step reads the slot's
  ``window / block`` blocks with the paged kernel through a FIXED table;
  a prefill attends inside the prompt with the flash forward on the
  fresh K and V and then writes the row's last ``min(n, window)`` real
  tokens into the ring. Block tables never address a window layer.
* **One K/V pool, written by the full layer, read by it and by every
  cross-attention layer.** The pool holds PAIR-heads, ``[pool_blocks,
  block, pairs, 2 D]``: ``K_j = [k_2j | k_2j+1]``, ``V_j = [v_2j |
  v_2j+1]``. The kernels get ``2 * pairs * group`` query heads of ``2 D``,
  ``[q1 | 0]`` and ``[0 | q2]``, scaled by ``sqrt(2)`` (they divide by
  ``sqrt(2 D)``, the model by ``sqrt(D)``): ordinary GQA computes both
  softmaxes exactly. The cross-attention layers own no cache leaf.
* **A prefill runs the cross-decoder on ONE token a row.** Nothing
  behind the full layer's K and V depends on the upper layers at other
  positions, so a bucket goes whole through the self-decoder and the
  full layer's norm and K, V projection; the full layer's attention and
  feed-forward and every layer above run at ``logits_idx`` only.

A prefill step starts its rows at position 0 (a prompt is prefilled
whole: the serving plane has no other prefill for a model with per-row
state); `selective_scan.ssm_prefill` itself takes a start state, a
window layer's prefill does not read the ring.

Serving only (``decode=True``, paged), driven by `serve.ShardedExecutor`.
``per_row_state`` makes the serving plane refuse prefix reuse,
speculation, the KV tier and migration by name.
"""
import math
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
from flax import linen as nn

from ..ops import selective_scan as ss
from .routed_lm import Proj

MAMBA, WINDOW, FULL, GMU, CROSS = "mamba", "window", "full", "gmu", "cross"


def layer_kinds(num_layers: int) -> Tuple[str, ...]:
    """The kind of each layer: a self-decoder of ``n / 2 + 1`` layers
    (Mamba at the even, window attention at the odd), the full layer,
    then gated memory units (even) and cross-attention (odd)."""
    if num_layers < 4 or num_layers % 4:
        raise ValueError(
            f"num_layers={num_layers}: a SambaY stack is whole pairs in "
            f"both halves (a multiple of 4)")
    half = num_layers // 2
    return tuple(
        (MAMBA if l % 2 == 0 else WINDOW) if l <= half else
        FULL if l == half + 1 else (GMU if l % 2 == 0 else CROSS)
        for l in range(num_layers))


class SambaYConfig:
    #: a sequence holds state that block tables do not address (conv
    #: and SSM state, the window layers' rings): the serving plane asks
    per_row_state = True
    #: the cache leaves that are no K/V pool, by kind (serve/executor.py)
    cache_leaves = {"conv": "row", "ssm": "row",
                    "ring_k": "row", "ring_v": "row"}
    #: the batcher prefills one row a step at the row's own bucket
    #: (`serve/batcher.py`): prompts are long and the scan is per row
    prefill_rows = 1

    def __init__(self, vocab_size=256, num_layers=8, embed_dim=64,
                 num_heads=8, num_kv_heads=4, ffn_dim=128, window=8,
                 d_state=4, d_conv=4, expand=2,
                 dt_rank: Optional[int] = None, norm_eps=1e-5,
                 max_seq_len=512, dtype=jnp.bfloat16,
                 param_dtype=jnp.bfloat16, logits_dtype=jnp.float32,
                 decode: bool = True, kv_block_size: int = 0,
                 kv_pool_blocks: int = 0, state_rows: int = 0,
                 decode_kernel: Optional[str] = None):
        if decode_kernel not in (None, "pallas", "xla"):
            raise ValueError(
                f"decode_kernel must be None (resolve from "
                f"HOROVOD_SERVE_KERNEL at executor build), 'pallas' or "
                f"'xla'; got {decode_kernel!r}")
        if not decode or not kv_block_size or kv_pool_blocks < 1:
            raise ValueError(
                "SambaYLM is a serving model over the paged KV pool: "
                "decode=True, kv_block_size > 0 and kv_pool_blocks >= 1")
        if embed_dim % num_heads or num_heads % num_kv_heads \
                or num_kv_heads % 2 or (num_heads // num_kv_heads) % 2:
            raise ValueError(
                f"{num_heads} query and {num_kv_heads} KV heads over "
                f"{embed_dim}: differential attention pairs neighbouring "
                f"heads, and a query pair reads ONE KV pair")
        if window % kv_block_size:
            raise ValueError(
                f"window {window} must be whole blocks of {kv_block_size}")
        self.vocab_size = vocab_size
        self.kinds = layer_kinds(num_layers)
        self.num_layers = num_layers
        self.embed_dim = embed_dim
        self.num_heads = num_heads
        self.num_kv_heads = num_kv_heads
        self.head_dim = embed_dim // num_heads
        self.ffn_dim = ffn_dim
        self.window = int(window)
        self.d_state = int(d_state)
        self.d_conv = int(d_conv)
        self.d_inner = int(expand) * embed_dim
        self.dt_rank = int(dt_rank or -(-embed_dim // 16))
        self.norm_eps = norm_eps
        self.max_seq_len = max_seq_len
        self.dtype = dtype
        self.param_dtype = param_dtype
        self.logits_dtype = logits_dtype
        self.decode = decode
        self.kv_block_size = kv_block_size
        self.kv_pool_blocks = kv_pool_blocks
        #: rows of the per-row leaves (the executor's max_batch; stamped
        #: by the executor where the config names none, as the pool is)
        self.state_rows = int(state_rows)
        self.decode_kernel = decode_kernel

    @property
    def kv_pairs(self) -> int:
        """Pair-heads a pool or ring holds, each ``2 * head_dim`` wide."""
        return self.num_kv_heads // 2

    @property
    def cache_token_bytes(self) -> int:
        """Bytes of K and V VALUES a token of context costs: the full
        layer's, once (on the device a pool pads the pair-heads to whole
        sublane tiles, `serve.kv_cache.held_pool_shape`)."""
        return 2 * self.num_kv_heads * self.head_dim \
            * jnp.dtype(self.dtype).itemsize

    @property
    def cache_row_bytes(self) -> int:
        """Bytes of values a row holds whatever its context: the Mamba
        layers' conv and SSM state, the window layers' rings."""
        mamba = self.kinds.count(MAMBA) * 4 * self.d_inner \
            * (self.d_conv + self.d_state)
        return mamba + self.kinds.count(WINDOW) * self.window \
            * self.cache_token_bytes


def lambda_init(layer: int) -> float:
    return 0.8 - 0.6 * math.exp(-0.3 * layer)


def _need_state_rows(cfg):
    if cfg.state_rows < 1:
        raise ValueError(
            "state_rows is not set: name it in the model config, or "
            "build the model's ShardedExecutor first (it sizes the "
            "per-row leaves for max_batch)")


def _slot_rows(cfg, update_mask, state_slots):
    """``(read, write)`` slot of each row of a row-compact step: a
    masked row reads any slot and writes none (``mode="drop"``)."""
    write = jnp.where(update_mask, state_slots, cfg.state_rows)
    return jnp.minimum(write, cfg.state_rows - 1), write


class Mamba(nn.Module):
    """The Mamba-1 mixer on the slot's conv and SSM state; also hands
    back ``M``, the scan's output before the gate."""
    cfg: Any

    @nn.compact
    def __call__(self, h, positions, update_mask, state_slots, n_valid):
        cfg = self.cfg
        B, T, _ = h.shape
        d, N, K, R = cfg.d_inner, cfg.d_state, cfg.d_conv, cfg.dt_rank
        f32 = jnp.float32
        proj = lambda n, name: Proj(n, cfg.dtype, cfg.param_dtype,  # noqa: E731
                                    name=name)
        az = proj(2 * d, "in_proj")(h)
        a, z = az[..., :d], az[..., d:]
        conv_w = self.param("conv_w", nn.initializers.normal(0.02), (K, d),
                            f32)
        conv_b = self.param("conv_b", nn.initializers.zeros, (d,), f32)
        A = -jnp.exp(self.param(
            "A_log", lambda *_: jnp.log(jnp.broadcast_to(
                jnp.arange(1, N + 1, dtype=f32)[:, None], (N, d))), (N, d),
            f32))
        D = self.param("D", nn.initializers.ones, (d,), f32)
        dt_bias = self.param("dt_bias", nn.initializers.zeros, (d,), f32)
        _need_state_rows(cfg)
        conv = self.variable("cache", "conv", jnp.zeros,
                             (cfg.state_rows, K, d), f32)
        ssm = self.variable("cache", "ssm", jnp.zeros,
                            (cfg.state_rows, N, d), f32)

        def step_inputs(c):
            """The conv output -> ``(dt, B, C)``, all float32."""
            x = proj(R + 2 * N, "x_proj")(c)
            dt = jax.nn.softplus(
                proj(d, "dt_proj")(x[..., :R]) + dt_bias)
            return dt, x[..., R:R + N], x[..., R + N:]

        pallas = cfg.decode_kernel == "pallas"
        interpret = jax.default_backend() != "tpu"
        if T > 1 or B != cfg.state_rows:
            # the rows' slots' states out, the scan, and back: a prefill
            # holds a row or a few. A slot's next sequence starts from
            # zero; a row out of the step (n_valid 0) keeps its states
            read, write = _slot_rows(cfg, update_mask, state_slots)
            fresh = (positions == 0)[:, None, None]
            c, conv1 = ss.causal_conv(
                jnp.where(fresh, 0.0, conv.value[read]), a, conv_w, conv_b,
                n_valid)
            dt, Bm, Cm = step_inputs(c)
            args = (jnp.where(fresh, 0.0, ssm.value[read]), dt, c, Bm, Cm,
                    A, D, n_valid)
            if pallas:
                y, ssm1 = ss.ssm_prefill(*args, interpret=interpret)
            else:
                y, ssm1 = ss.ssm_recurrence(*args)
            conv.value = conv.value.at[write].set(conv1, mode="drop")
            ssm.value = ssm.value.at[write].set(ssm1, mode="drop")
        else:
            # a decode step's rows ARE the slots: updated in place
            a1 = a[:, 0]
            c = ss.conv_step(conv.value, a1, conv_w, conv_b, positions)
            dt, Bm, Cm = step_inputs(c)
            args = (conv.value, ssm.value, a1, c, dt, Bm, Cm, A, D,
                    positions, update_mask)
            if pallas:
                y, conv.value, ssm.value = ss.ssm_decode(
                    *args, interpret=interpret)
            else:
                y, conv.value, ssm.value = ss.ssm_decode_reference(*args)
            y = y[:, None]
        return proj(cfg.embed_dim, "out_proj")(y * jax.nn.silu(z)), y


class BiasProj(nn.Module):
    """`Proj` with a float32 bias added to the accumulator."""
    features: int
    cfg: Any

    @nn.compact
    def __call__(self, x):
        bias = self.param("bias", nn.initializers.zeros, (self.features,),
                          jnp.float32)
        return Proj(self.features, self.cfg.dtype, self.cfg.param_dtype,
                    name="proj")(x) + bias


def pair_queries(q, dtype):
    """q [B, T, H, D] -> the kernels' ``[B, T, H, 2 D]``: an even head
    (the pair's first) in the left half, an odd head in the right, the
    other half zero, times ``sqrt(2)`` (the kernels divide by
    ``sqrt(2 D)``)."""
    left = (jnp.arange(q.shape[2]) % 2 == 0)[:, None]
    q = q * math.sqrt(2.0)
    return jnp.concatenate([jnp.where(left, q, 0.0),
                            jnp.where(left, 0.0, q)], axis=-1).astype(dtype)


class DiffAttention(nn.Module):
    """Differential attention of one layer. ``kind`` says where K and V
    live: a ring (`WINDOW`), the pool this layer writes (`FULL`), or the
    pool handed in (`CROSS`). Returns ``(out, pools)``."""
    cfg: Any
    kind: str
    layer: int

    @nn.compact
    def __call__(self, h_kv, h_q, positions, q_positions, update_mask,
                 block_tables, state_slots, n_valid, pools=None):
        from ..serve import kv_cache as kvc
        cfg = self.cfg
        B, Tq, _ = h_q.shape
        H, KV, D = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
        P = cfg.kv_pairs
        f32 = jnp.float32
        pallas = cfg.decode_kernel == "pallas"

        def paged(q, pool_k, pool_v, tables, pos):
            """One query a row over blocks: float32 queries in, float32
            out (the kernel upcasts its operands anyway), so the two
            softmaxes reach the subtraction unrounded."""
            qp = pair_queries(q, f32)
            if pallas:
                from ..ops.pallas_paged import paged_attention_fused
                return paged_attention_fused(qp, pool_k, pool_v, tables, pos)
            return kvc.paged_attention(qp, pool_k, pool_v, tables, pos)

        if self.kind == CROSS:
            q = BiasProj(H * D, cfg, name="wq")(h_q).reshape(B, Tq, H, D)
            a = paged(q, *pools, block_tables, q_positions)
        else:
            # one matrix, q first: a prefill of the full layer projects
            # K and V of the whole bucket and q of ONE token a row; any
            # other step multiplies by the matrix whole, unsliced
            w = self.param("wqkv", nn.initializers.normal(0.02),
                           (cfg.embed_dim, (H + 2 * KV) * D),
                           cfg.param_dtype)
            b = self.param("bqkv", nn.initializers.zeros,
                           ((H + 2 * KV) * D,), f32)

            def part(x, lo, hi):
                return jnp.dot(x.astype(cfg.dtype),
                               w[:, lo:hi].astype(cfg.dtype),
                               preferred_element_type=f32) + b[lo:hi]

            T = h_kv.shape[1]
            if h_kv is h_q:
                qkv = part(h_q, 0, (H + 2 * KV) * D)
                q, kv = qkv[..., :H * D], qkv[..., H * D:]
            else:
                q = part(h_q, 0, H * D)
                kv = part(h_kv, H * D, (H + 2 * KV) * D)
            q, kv = q.reshape(B, Tq, H, D), kv.astype(cfg.dtype)
            # neighbouring heads side by side ARE the pair-heads
            k = kv[..., :KV * D].reshape(B, T, P, 2 * D)
            v = kv[..., KV * D:].reshape(B, T, P, 2 * D)
            if self.kind == FULL:
                pools = kvc.write_kv_pools(
                    self, cfg, k, v, positions, update_mask, block_tables)
                a = paged(q, *pools, block_tables, q_positions)
            else:
                a = self._window(q, k, v, positions, update_mask,
                                 state_slots, n_valid, paged)
        # pair i: a[2i] - lam * a[2i + 1], normed over the pair's width
        lam = [self.param(f"lambda_{n}", nn.initializers.normal(0.1), (D,),
                          f32) for n in ("q1", "k1", "q2", "k2")]
        init = lambda_init(self.layer)
        lam = jnp.exp(jnp.sum(lam[0] * lam[1])) \
            - jnp.exp(jnp.sum(lam[2] * lam[3])) + init
        a = a.astype(f32).reshape(B, Tq, H // 2, 2, 2 * D)
        o = a[..., 0, :] - lam * a[..., 1, :]
        g = self.param("subln", nn.initializers.ones, (2 * D,), f32)
        o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True)
                              + cfg.norm_eps) * g * (1.0 - init)
        return BiasProj(cfg.embed_dim, cfg, name="wo")(
            o.reshape(B, Tq, H * D)), pools

    def _window(self, q, k, v, positions, update_mask, state_slots,
                n_valid, paged):
        """A window layer: the row's ring written and, in a decode
        step, read; a prefill attends inside the prompt."""
        from ..serve import kv_cache as kvc
        cfg = self.cfg
        B, T, P, D2 = k.shape
        W, BS = cfg.window, cfg.kv_block_size
        nb = W // BS
        _need_state_rows(cfg)
        R = cfg.state_rows
        held = kvc.held_pool_shape(R * nb, BS, P, D2)
        ring_k = self.variable("cache", "ring_k", jnp.zeros,
                               (R, nb) + held[1:], cfg.dtype)
        ring_v = self.variable("cache", "ring_v", jnp.zeros,
                               (R, nb) + held[1:], cfg.dtype)
        if held[2:] != (P, D2):
            tiles = ((0, 0), (0, 0), (0, held[2] - P), (0, held[3] - D2))
            k_held, v_held = jnp.pad(k, tiles), jnp.pad(v, tiles)
        else:
            k_held, v_held = k, v
        rk, rv = ring_k.value.reshape(held), ring_v.value.reshape(held)
        if T == 1:
            # token p at slot p mod W of the row's own blocks; then the
            # min(p + 1, W) keys the ring holds, in whatever order
            read, _ = _slot_rows(cfg, update_mask, state_slots)
            tables = read[:, None] * nb + jnp.arange(nb)[None, :]
            rk, rv = kvc.write_kv_paged(rk, rv, k_held, v_held,
                                        positions % W, update_mask, tables)
            a = paged(q, rk[:, :, :P, :D2], rv[:, :, :P, :D2], tables,
                      jnp.minimum(positions, W - 1))
        else:
            qp = pair_queries(q, cfg.dtype)
            zero = jnp.zeros_like(positions)
            if cfg.decode_kernel == "pallas":
                from ..ops.pallas_attention import flash_prefill
                a = flash_prefill(
                    qp.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
                    v.transpose(0, 2, 1, 3), zero, window=W,
                    out_dtype=jnp.float32,
                    interpret=jax.default_backend() != "tpu").transpose(
                        0, 2, 1, 3)
            else:
                a = kvc.masked_attention(qp.astype(jnp.float32), k, v,
                                         zero, window=W)
            # the last min(n, W) real tokens into the ring; the bucket's
            # padding would overwrite keys the row still needs
            # (a row out of the step has n_valid 0 and writes nothing)
            idx = n_valid[:, None] - W + jnp.arange(W)[None, :]   # [B, W]
            take = jnp.clip(idx, 0, T - 1)
            slot = (positions[:, None] + idx) % W
            flat = jnp.where(idx >= 0, state_slots[:, None] * W + slot,
                             R * W).reshape(-1)

            def fill(ring, new):
                new = jnp.take_along_axis(
                    new, take[:, :, None, None], axis=1)
                return ring.reshape(R * W, *held[2:]).at[flat].set(
                    new.reshape(B * W, *held[2:]), mode="drop")

            rk, rv = fill(rk, k_held), fill(rv, v_held)
        ring_k.value = rk.reshape(ring_k.value.shape)
        ring_v.value = rv.reshape(ring_v.value.shape)
        return a


class SambaYBlock(nn.Module):
    cfg: Any
    layer: int

    def _norm(self, name):
        return nn.LayerNorm(epsilon=self.cfg.norm_eps, dtype=jnp.float32,
                            param_dtype=jnp.float32,
                            use_fast_variance=False, name=name)

    @nn.compact
    def __call__(self, x, step, memory=None, pools=None, x_all=None):
        """``x`` the stream this layer's mixer and feed-forward run on;
        ``memory`` and ``pools`` what the stack hands down (the SSM
        output of the self-decoder's last layer, the full layer's K and
        V pools) and this layer hands on; ``x_all`` the whole bucket,
        for the full layer's K and V; ``step`` the step's per-row
        arrays. Returns ``(x, memory, pools)``."""
        cfg, kind = self.cfg, self.cfg.kinds[self.layer]
        norm = self._norm("norm1")
        h = norm(x)
        if kind == MAMBA:
            a, memory = Mamba(cfg, name="mixer")(
                h, step["positions"], step["update_mask"],
                step["state_slots"], step["n_valid"])
        elif kind == GMU:
            gate = Proj(cfg.d_inner, cfg.dtype, cfg.param_dtype,
                        name="in_proj")(h)
            a = Proj(cfg.embed_dim, cfg.dtype, cfg.param_dtype,
                     name="out_proj")(jax.nn.silu(gate) * memory)
        else:
            a, pools = DiffAttention(
                cfg, kind=kind, layer=self.layer, name="mixer")(
                    # a bucket's K and V come from all of it; a one-token
                    # step's from the token the queries come from
                    norm(x_all) if kind == FULL and x_all.shape[1] > 1
                    else h, h,
                    step["positions"], step["q_positions"],
                    step["update_mask"], step["block_tables"],
                    step["state_slots"], step["n_valid"], pools)
        x = x + a
        u = self._norm("norm2")(x)
        dense = lambda name: nn.Dense(                          # noqa: E731
            cfg.ffn_dim, use_bias=False, dtype=cfg.dtype,
            param_dtype=cfg.param_dtype, name=name)(u)
        y = Proj(cfg.embed_dim, cfg.dtype, cfg.param_dtype, name="w_down")(
            jax.nn.silu(dense("w_gate")) * dense("w_up"))
        return x + y, memory, pools


class SambaYLM(nn.Module):
    cfg: Any

    @nn.compact
    def __call__(self, tokens, positions=None, update_mask=None,
                 block_tables=None, logits_idx=None, state_slots=None):
        cfg = self.cfg
        if positions is None or update_mask is None \
                or block_tables is None:
            raise ValueError(
                "SambaYLM needs per-row `positions`, `update_mask` and "
                "`block_tables` (see horovod_tpu/serve/executor.py)")
        B, T = tokens.shape
        if T > cfg.max_seq_len:
            raise ValueError(f"sequence length {T} exceeds "
                             f"max_seq_len={cfg.max_seq_len}")
        if state_slots is None:
            if cfg.state_rows and B != cfg.state_rows:
                raise ValueError(
                    f"a step of {B} rows on a state of {cfg.state_rows} "
                    f"slots needs `state_slots`: which slot each row is")
            state_slots = jnp.arange(B, dtype=jnp.int32)
        # the emitting token of each row: the cross-decoder runs on it
        # alone; tokens behind it are bucket padding
        last = jnp.full((B,), T - 1, jnp.int32) if logits_idx is None \
            else logits_idx.astype(jnp.int32)
        step = {"positions": positions, "update_mask": update_mask,
                "block_tables": block_tables, "state_slots": state_slots,
                "n_valid": jnp.where(update_mask, last + 1, 0),
                "q_positions": positions + last}
        embed = nn.Embed(cfg.vocab_size, cfg.embed_dim,
                         param_dtype=cfg.param_dtype, name="embed")
        x = embed(tokens).astype(jnp.float32)       # the residual stream
        memory = pools = x_all = None
        for i, kind in enumerate(cfg.kinds):
            if kind == FULL:
                # from here on one token a row: the emitting one, and
                # the self-decoder's memory at it
                pick = lambda y: jnp.take_along_axis(       # noqa: E731
                    y, last[:, None, None], axis=1)
                x_all, x, memory = x, pick(x), pick(memory)
            x, memory, pools = SambaYBlock(
                cfg, layer=i, name=f"layers_{i}")(
                    x, step, memory, pools, x_all if kind == FULL else None)
        x = nn.LayerNorm(epsilon=cfg.norm_eps, dtype=jnp.float32,
                         param_dtype=jnp.float32, use_fast_variance=False,
                         name="norm_f")(x)
        # the head is the embedding: operands in the compute dtype, the
        # accumulator in the logits' dtype
        return jnp.einsum("btd,vd->btv", x.astype(cfg.dtype),
                          embed.embedding.astype(cfg.dtype),
                          preferred_element_type=cfg.logits_dtype)
