"""The MiniCPM-SALA family (openbmb, 2026-02): everything the benchmark
knows of this architecture, in one file.

A decoder whose layers mix tokens in one of two ways, named per layer by
``mixer_types``: ``minicpm4`` (block-sparse softmax attention, InfLLM-v2)
and ``lightning-attn`` (linear attention with a per-head decay). ``x`` is
the float32 residual stream, ``h = rmsnorm(x)``. muP, from the config's
own keys: ``x0 = scale_emb * embed[token]``; each branch enters as
``x + (scale_depth / sqrt(32)) * branch`` (the PUBLISHED depth); the head
reads ``rmsnorm(x) / (hidden_size / dim_model_base)``. Feed-forward:
``W_down(silu(W_gate u) * W_up u)``, ``u = rmsnorm(x)``. Both mixers:
``q = Wq h``, ``k``, ``v`` per head, q and k each through an RMS norm over
the head with one gain shared by the heads, the output gated,
``o * sigmoid(Wg h)``, before ``Wo``.

``lightning-attn``: RoPE (interleaved pairs, theta) on q and k at the
token's position; per head ``S_t = lam_h S_{t-1} + k_t^T v_t``,
``lam_h = exp(-2^(-8(h+1)/H))``, ``o_t = (q_t / sqrt(D)) S_t``; an RMS
norm with a gain over the concatenated heads, the gate, ``Wo``.

``minicpm4`` (no position encoding; blocks of ``block`` tokens, a
compressed key ``c_j = mean(k[stride*j : stride*j + 2*stride])`` every
``stride = block / 4`` keys): a query at position ``p`` with
``p + 1 <= dense_len`` attends causally to every key; else per query
head ``r_j = softmax_j(q . c_j / sqrt(D))`` over the ``j`` it can see
(``stride*j + 2*stride - 1 <= p``), summed over the heads of a KV group;
block ``b`` scores the largest of windows ``4b-1 .. 4b+3``; the query
attends to block 0 (``init_blocks``), the newest ``window / block``
blocks up to its own and the best-scored of the rest, ``topk`` in all
(ties to the lower block), one set a KV group, causally.

What the source's config.json leaves open is listed under ``assumed`` in
the configuration file. One departure from the source, noted there: the
dense/sparse switch is per QUERY, not per sequence.

**The plain reference** (`logits_at`) is these equations in `jax.numpy`,
float32 at `Precision.HIGHEST`: no kernel, no cache, no chunks, nothing
of the program imported; the recurrence is a loop over tokens, the
selection is made per query exactly as written above. Forced by 16 GB:
the weights are held as the program's bfloat16 VALUES (3.93 B parameters
in float32 are 15.7 GB) and upcast a layer at a time; rows go through
the projections and the feed-forward `ROWS` at a time and attention
scores are held for `QUERY_BLOCK` queries at a time; and, the sequence
being padded far past its end (causal: the padding is never seen),
nothing past the last position read is computed.

**Work counts** are what the algorithm requires: the keys of the blocks
a sparse query ATTENDS (all of them while dense), the state read and
written once a row and lightning layer.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.reference import HIGHEST, _round, seed_key  # noqa: F401

#: the nearest precision below the configurations' (bfloat16 compute)
CONTROL = "fp8"

SPARSE, LIGHTNING = "minicpm4", "lightning-attn"

#: `--rehearse` widths (CPU, interpret-mode kernels): one sparse and two
#: lightning layers, contexts that cross `dense_len` into the sparse phase
REHEARSE_CONFIG = {
    "hidden_size": 64, "num_hidden_layers": 3, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "intermediate_size": 128,
    "lightning_nh": 4, "lightning_nkv": 4, "lightning_head_dim": 16,
    "dim_model_base": 16, "max_position_embeddings": 128, "vocab_size": 512,
    "assumed": {"sparse_block_size": 4, "sparse_kernel_size": 2,
                "sparse_kernel_stride": 1, "sparse_window_size": 8,
                "sparse_topk": 4, "sparse_dense_len": 32}}

PUBLISHED_WIDTHS = ("d", "heads", "kv_heads", "head_dim", "ffn",
                    "lightning_heads", "lightning_kv_heads",
                    "lightning_head_dim", "vocab", "positions", "theta",
                    "eps", "scale_emb", "scale_depth", "dim_model_base",
                    "published_layers")


class Shape:
    """The sizes, read from a configuration file's own keys."""

    def __init__(self, config: dict):
        a = config.get("assumed", {})
        self.d = int(config["hidden_size"])
        self.layers = int(config["num_hidden_layers"])
        self.heads = int(config["num_attention_heads"])
        self.kv_heads = int(config["num_key_value_heads"])
        self.head_dim = int(config["head_dim"])
        self.ffn = int(config["intermediate_size"])
        self.lightning_heads = int(config["lightning_nh"])
        self.lightning_kv_heads = int(config["lightning_nkv"])
        self.lightning_head_dim = int(config["lightning_head_dim"])
        self.vocab = int(config["vocab_size"])
        #: ids below this are what a check admits as a token; the head is
        #: NOT padded (73,448 columns as published: the model cannot emit
        #: past them), the bound is the next whole lane tile
        self.padded_vocab = -(-self.vocab // 128) * 128
        self.positions = int(config["max_position_embeddings"])
        self.theta = float(config["rope_theta"])
        self.eps = float(config["rms_norm_eps"])
        self.scale_emb = float(config["scale_emb"])
        self.scale_depth = float(config["scale_depth"])
        self.dim_model_base = int(config["dim_model_base"])
        #: the source's depth: `mixer_types` is kept whole in the file
        #: and `layers` entries from `first_layer` on are the layers run
        self.published_layers = len(config["mixer_types"])
        self.first_layer = int(a.get("first_layer", 0))
        self.mixers = tuple(config["mixer_types"][
            self.first_layer:self.first_layer + self.layers])
        if len(self.mixers) != self.layers or \
                set(self.mixers) - {SPARSE, LIGHTNING}:
            raise ValueError("mixer_types does not cover the layers run")
        if self.lightning_kv_heads != self.lightning_heads:
            raise ValueError("lightning layers have a KV head a query head")
        self.block = int(a.get("sparse_block_size", 64))
        self.kernel = int(a.get("sparse_kernel_size", 32))
        self.stride = int(a.get("sparse_kernel_stride", 16))
        self.init_blocks = int(a.get("sparse_init_blocks", 1))
        self.window = int(a.get("sparse_window_size", 2048))
        self.topk = int(a.get("sparse_topk", 64))
        self.dense_len = int(a.get("sparse_dense_len", 8192))
        if self.kernel != 2 * self.stride or self.block != 4 * self.stride:
            raise ValueError("a block holds four compressed keys, each "
                             "over two strides")
        self.init_std = float(a.get("initializer_range", 0.02))

    @property
    def sparse_layers(self) -> int:
        return sum(m == SPARSE for m in self.mixers)

    @property
    def lightning_layers(self) -> int:
        return self.layers - self.sparse_layers


# ---------------------------------------------------------------------------
# weights: the reference's own tree, and the same values as the program's
# ---------------------------------------------------------------------------

def _weight_shapes(s: Shape) -> dict:
    """Leaves stacked over ALL layers, over the sparse ones (``s_``) and
    over the lightning ones (``l_``)."""
    L, S, N, d, f = s.layers, s.sparse_layers, s.lightning_layers, s.d, s.ffn
    q, kv = s.heads * s.head_dim, s.kv_heads * s.head_dim
    lq = s.lightning_heads * s.lightning_head_dim
    return {"embed": (s.vocab, d), "norm_f_g": (d,), "head": (d, s.vocab),
            "attn_norm_g": (L, d), "mlp_norm_g": (L, d),
            "w_gate": (L, d, f), "w_up": (L, d, f), "w_down": (L, f, d),
            "s_wq": (S, d, q), "s_wk": (S, d, kv), "s_wv": (S, d, kv),
            "s_wg": (S, d, q), "s_wo": (S, q, d),
            "s_q_norm_g": (S, s.head_dim), "s_k_norm_g": (S, s.head_dim),
            "l_wq": (N, d, lq), "l_wk": (N, d, lq), "l_wv": (N, d, lq),
            "l_wg": (N, d, lq), "l_wo": (N, lq, d),
            "l_q_norm_g": (N, s.lightning_head_dim),
            "l_k_norm_g": (N, s.lightning_head_dim), "l_o_norm_g": (N, lq)}


def reference_weights(shape: Shape, key) -> dict:
    """The seed's weights: normal(0, 0.02) for every matrix and the
    embedding, held as the bfloat16 values the program holds; norm gains
    1 + that noise, float32. Traceable: call under `jit`. Made a layer
    at a time, so that no more than one leaf of one layer exists in
    float32."""
    out = {}
    for i, (name, shp) in enumerate(sorted(_weight_shapes(shape).items())):
        k = jax.random.fold_in(key, i)

        def draw(kk, dims, gain=name.endswith("_g")):
            x = shape.init_std * jax.random.normal(kk, dims, jnp.float32)
            return 1.0 + x if gain else x.astype(jnp.bfloat16)

        if name in ("embed", "head", "norm_f_g"):
            out[name] = draw(k, shp)
        else:
            out[name] = jnp.stack([draw(jax.random.fold_in(k, l), shp[1:])
                                   for l in range(shp[0])])
    return out


def _layer_weights(w: dict, kind: str, i, nth) -> dict:
    """The leaves of layer ``i``, the ``nth`` of its kind, under its
    kind's names with the prefix dropped (``i``, ``nth`` may be traced:
    one layer's leaves are taken out of the stacks at a time)."""
    pre = "s_" if kind == SPARSE else "l_"
    lw = {n: w[n][i] for n in ("attn_norm_g", "mlp_norm_g", "w_gate",
                               "w_up", "w_down")}
    lw.update({n[2:]: v[nth] for n, v in w.items() if n.startswith(pre)})
    return lw


def _nth_of_kind(shape: Shape, i: int) -> int:
    return sum(m == shape.mixers[i] for m in shape.mixers[:i])


def program_params(shape: Shape, key) -> dict:
    """The same values as `models/sala_lm.py`'s parameter tree."""
    w = reference_weights(shape, key)
    tree = {"embed": {"embedding": w["embed"]},
            "norm_f": {"scale": w["norm_f_g"]},
            "lm_head": {"kernel": w["head"]}}
    for i in range(shape.layers):
        lw = _layer_weights(w, shape.mixers[i], i, _nth_of_kind(shape, i))
        attn = {n: {"kernel": lw[n]} for n in ("wq", "wk", "wv", "wg", "wo")}
        attn.update({n: {"scale": lw[n + "_g"]} for n in
                     ("q_norm", "k_norm", "o_norm") if n + "_g" in lw})
        tree[f"layers_{i}"] = {
            "attn_norm": {"scale": lw["attn_norm_g"]},
            "mlp_norm": {"scale": lw["mlp_norm_g"]},
            "attn": attn,
            **{n: {"kernel": lw[n]} for n in ("w_gate", "w_up", "w_down")}}
    return tree


def serve_model(shape: Shape, config: dict, *, kv_block: int,
                kv_pool_blocks: int, decode_kernel):
    """The model object a `ShardedExecutor` gets."""
    from horovod_tpu.models.sala_lm import SalaLM, SalaLMConfig
    if kv_block != shape.block:
        raise ValueError(f"the pool block ({kv_block}) is the selection "
                         f"block ({shape.block})")
    assumed = config.get("assumed", {})
    return SalaLM(SalaLMConfig(
        vocab_size=shape.vocab, embed_dim=shape.d, num_heads=shape.heads,
        num_kv_heads=shape.kv_heads, head_dim=shape.head_dim,
        ffn_dim=shape.ffn, mixer_types=shape.mixers,
        lightning_heads=shape.lightning_heads,
        lightning_head_dim=shape.lightning_head_dim,
        sparse_stride=shape.stride, sparse_init_blocks=shape.init_blocks,
        sparse_window=shape.window, sparse_topk=shape.topk,
        dense_len=shape.dense_len, scale_emb=shape.scale_emb,
        scale_depth=shape.scale_depth, mup_depth=shape.published_layers,
        dim_model_base=shape.dim_model_base, rope_theta=shape.theta,
        rms_eps=shape.eps, max_seq_len=shape.positions,
        dtype=jnp.dtype(assumed.get("compute_dtype", "bfloat16")),
        param_dtype=jnp.dtype(assumed.get("param_dtype", "bfloat16")),
        logits_dtype=jnp.dtype(assumed.get("logits_dtype", "float32")),
        decode=True, kv_block_size=kv_block, kv_pool_blocks=kv_pool_blocks,
        decode_kernel=decode_kernel))


# ---------------------------------------------------------------------------
# the plain reference
# ---------------------------------------------------------------------------

#: rows that go through a projection or the feed-forward at once, and
#: queries whose scores are held at once
ROWS, QUERY_BLOCK = 2048, 128


def _mm(a, b, precision: str):
    return jnp.matmul(_round(a, precision),
                      _round(b.astype(jnp.float32), precision),
                      precision=HIGHEST)


def _rmsnorm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * g


def _rope(x, theta):
    """x [S, n, D] rotated over interleaved pairs (2i, 2i+1) by
    ``position * theta^(-2i/D)``."""
    S, n, D = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D))
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv       # [S, D/2]
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                     axis=-1).reshape(S, n, D)


def _blocks(f, x, size: int, n_read, width: int):
    """``f`` over `size` rows of ``x [S, ...]`` at a time ->
    ``[S, width]``; blocks that start at or past row ``n_read`` (the
    padding behind the last position read) are left at zero."""
    S = x.shape[0]
    size = min(size, S)
    pad = (-S) % size
    xb = jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1)).reshape(
        -1, size, *x.shape[1:])

    def one(args):
        xi, start = args
        return jax.lax.cond(start < n_read, lambda: f(xi, start),
                            lambda: jnp.zeros((size, width), jnp.float32))

    out = jax.lax.map(one, (xb, jnp.arange(xb.shape[0]) * size))
    return out.reshape(-1, width)[:S]


def _selected(q, c, q_pos, shape: Shape):
    """Which blocks each query attends: q [Q, KV, G, D], compressed keys
    c [n, KV, D], positions q_pos [Q] -> [KV, Q, n_blocks] bool (all of
    them for a query still dense; causality is the caller's)."""
    D, n = q.shape[-1], c.shape[0]
    nblk = n // 4
    s = jnp.einsum("qkgd,nkd->kgqn", q, c, precision=HIGHEST) / math.sqrt(D)
    seen = (shape.stride * jnp.arange(n) + shape.kernel - 1)[None, :] \
        <= q_pos[:, None]
    r = jax.nn.softmax(jnp.where(seen[None, None], s, -1e30), axis=-1)
    r = jnp.sum(jnp.where(seen[None, None], r, 0.0), axis=1)    # [KV, Q, n]
    # block b: the largest of windows 4b-1 .. 4b+3
    j = 4 * jnp.arange(nblk)[:, None] + jnp.arange(-1, 4)[None, :]
    score = jnp.max(jnp.where(j >= 0, r[..., jnp.clip(j, 0, n - 1)], 0.0),
                    axis=-1)                                    # [KV,Q,nblk]
    b = jnp.arange(nblk)[None, :]
    cur = (q_pos // shape.block)[:, None]
    forced = (b < shape.init_blocks) | \
        ((b > cur - shape.window // shape.block) & (b <= cur))
    score = jnp.where(forced[None], 1e30, score)
    score = jnp.where((b > cur)[None], -1.0, score)
    _, idx = jax.lax.top_k(score, shape.topk)       # ties: the lower block
    hit = jnp.any(idx[..., None] == jnp.arange(nblk), axis=-2)
    return hit | (q_pos + 1 <= shape.dense_len)[None, :, None]


def _sparse_attention(q, k, v, n_read, shape: Shape, precision: str):
    """q [S, H, D], k/v [S, KV, D] -> [S, H*D]."""
    S, H, D = q.shape
    KV = k.shape[1]
    pad = (-S) % shape.block
    kp = jnp.pad(k, ((0, pad + shape.block), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, pad), (0, 0), (0, 0)))
    L = S + pad
    # c_j = mean(k[stride*j : stride*j + kernel]), four a block
    starts = shape.stride * jnp.arange(L // shape.stride)
    c = jnp.mean(kp[starts[:, None] + jnp.arange(shape.kernel)[None, :]],
                 axis=1)                                        # [n, KV, D]
    kr, vr = _round(kp[:L], precision), _round(vp, precision)
    key_pos = jnp.arange(L)

    def block(qi, start):
        q_pos = start + jnp.arange(qi.shape[0])
        qi = qi.reshape(-1, KV, H // KV, D)
        s = jnp.einsum("qkgd,skd->kgqs", _round(qi, precision), kr,
                       precision=HIGHEST) / math.sqrt(D)
        sel = jnp.repeat(_selected(qi, c, q_pos, shape), shape.block, axis=2)
        ok = sel & (key_pos[None, :] <= q_pos[:, None])[None]
        p = jax.nn.softmax(jnp.where(ok[:, None], s, -1e30), axis=-1)
        return jnp.einsum("kgqs,skd->qkgd", _round(p, precision), vr,
                          precision=HIGHEST).reshape(-1, H * D)

    return _blocks(block, q, QUERY_BLOCK, n_read, H * D)


def _lightning(q, k, v, n_read, shape: Shape, precision: str):
    """q/k/v [S, H, D] -> [S, H*D]: the recurrence, a token at a time."""
    S, H, D = q.shape
    lam = jnp.exp(-(2.0 ** (-8.0 * jnp.arange(1, H + 1, dtype=jnp.float32)
                            / H)))[:, None, None]
    q, k, v = (_round(x, precision) for x in (q, k, v))

    def token(t, carry):
        state, out = carry
        state = lam * state + k[t][:, :, None] * v[t][:, None, :]
        o = jnp.einsum("hd,hde->he", q[t], state, precision=HIGHEST)
        return state, jax.lax.dynamic_update_slice(
            out, (o / math.sqrt(D)).reshape(1, H * D), (t, 0))

    _, out = jax.lax.fori_loop(
        0, jnp.minimum(n_read, S), token,
        (jnp.zeros((H, D, D), jnp.float32),
         jnp.zeros((S, H * D), jnp.float32)))
    return out


def _layer(x, lw, kind: str, n_read, shape: Shape, precision: str):
    S, d = x.shape
    sparse = kind == SPARSE
    H = shape.heads if sparse else shape.lightning_heads
    KV = shape.kv_heads if sparse else shape.lightning_kv_heads
    D = shape.head_dim if sparse else shape.lightning_head_dim
    h = _rmsnorm(x, lw["attn_norm_g"], shape.eps)

    def proj(name, width):
        return _blocks(lambda hi, _: _mm(hi, lw[name], precision), h, ROWS,
                       n_read, width)

    q = _rmsnorm(proj("wq", H * D).reshape(S, H, D), lw["q_norm_g"],
                 shape.eps)
    k = _rmsnorm(proj("wk", KV * D).reshape(S, KV, D), lw["k_norm_g"],
                 shape.eps)
    v = proj("wv", KV * D).reshape(S, KV, D)
    if sparse:
        o = _sparse_attention(q, k, v, n_read, shape, precision)
    else:
        o = _lightning(_rope(q, shape.theta), _rope(k, shape.theta), v,
                       n_read, shape, precision)
        o = _rmsnorm(o, lw["o_norm_g"], shape.eps)
    gate = jax.nn.sigmoid(proj("wg", H * D))
    scale = shape.scale_depth / math.sqrt(shape.published_layers)
    x = x + scale * _blocks(
        lambda oi, _: _mm(oi, lw["wo"], precision), o * gate, ROWS, n_read, d)
    u = _rmsnorm(x, lw["mlp_norm_g"], shape.eps)

    def ffn(ui, _):
        hid = jax.nn.silu(_mm(ui, lw["w_gate"], precision)) \
            * _mm(ui, lw["w_up"], precision)
        return _mm(hid, lw["w_down"], precision)

    return x + scale * _blocks(ffn, u, ROWS, n_read, d)


def hidden(w, shape: Shape, tokens, n_read, precision: str = "float32"):
    """Final-norm hidden states [S, d] of ONE sequence, tokens [1, S];
    rows at and past ``n_read`` mean nothing."""
    x = shape.scale_emb * w["embed"][tokens[0]].astype(jnp.float32)
    # a run of layers of one kind goes under one `scan` (one compiled
    # body), which takes one layer's leaves out of the stacks at a time
    i = 0
    while i < shape.layers:
        kind = shape.mixers[i]
        n = next((j for j in range(i, shape.layers)
                  if shape.mixers[j] != kind), shape.layers) - i
        x, _ = jax.lax.scan(
            lambda x, at, kind=kind: (_layer(
                x, _layer_weights(w, kind, *at), kind, n_read, shape,
                precision), None),
            x, (i + jnp.arange(n), _nth_of_kind(shape, i) + jnp.arange(n)))
        i += n
    return _rmsnorm(x, w["norm_f_g"], shape.eps)


def logits_at(w, shape: Shape, tokens, where, precision: str = "float32"):
    """[n, vocab] float32 logits of ONE sequence (tokens [1, S]) at the
    positions `where` [n]."""
    n_read = jnp.max(where) + 1
    x = hidden(w, shape, tokens, n_read, precision)[where]
    return _mm(x / (shape.d / shape.dim_model_base), w["head"], precision)


# ---------------------------------------------------------------------------
# required work, from the sizes alone
# ---------------------------------------------------------------------------

def _matmul_params(s: Shape, kind: str) -> int:
    """One layer's matrices: q, gate and output at query width, k and v
    at KV width, the three of the feed-forward."""
    if kind == SPARSE:
        q, kv = s.heads * s.head_dim, s.kv_heads * s.head_dim
    else:
        q = kv = s.lightning_heads * s.lightning_head_dim
    return 3 * s.d * q + 2 * s.d * kv + 3 * s.d * s.ffn


def layer_params(s: Shape, kind: str) -> int:
    """Matrices and gains: two layer norms, the q and k norms and, in a
    lightning layer, the output norm."""
    D = s.head_dim if kind == SPARSE else s.lightning_head_dim
    gains = 2 * s.d + 2 * D + (
        0 if kind == SPARSE else s.lightning_heads * s.lightning_head_dim)
    return _matmul_params(s, kind) + gains


def param_count(s: Shape) -> int:
    return sum(layer_params(s, m) for m in s.mixers) \
        + 2 * s.vocab * s.d + s.d


def matmul_flops_per_token(s: Shape) -> int:
    """Forward operations of one token through every layer's matrices
    and the head, without its attention or state."""
    return 2 * sum(_matmul_params(s, m) for m in s.mixers) \
        + 2 * s.d * s.vocab


def attended_keys(s: Shape, start, n) -> int:
    """Keys a sparse layer's queries attend: `n` consecutive queries from
    position `start` (arrays, one entry a row): the whole context up to
    `dense_len`, then `topk` blocks, the query's own partly filled."""
    total = 0
    for a, m in zip(np.asarray(start, np.int64).ravel(),
                    np.asarray(n, np.int64).ravel()):
        p = a + np.arange(m, dtype=np.int64)
        total += int(np.where(p + 1 <= s.dense_len, p + 1,
                              (s.topk - 1) * s.block + p % s.block + 1).sum())
    return total


def _windows_scored(s: Shape, start, n) -> int:
    """Compressed keys the sparse queries among them score."""
    total = 0
    for a, m in zip(np.asarray(start, np.int64).ravel(),
                    np.asarray(n, np.int64).ravel()):
        p = a + np.arange(m, dtype=np.int64)
        seen = np.maximum(p - s.kernel + 1, -1) // s.stride + 1
        total += int(np.where(p + 1 > s.dense_len, seen, 0).sum())
    return total


def _step_rows(step: dict):
    """(start, tokens) per row of a recorded step."""
    start = np.asarray(step["rows_start"], np.int64)
    n = np.asarray(step["rows_tokens"], np.int64) \
        if step["kind"] == "prefill" else np.ones_like(start)
    return start, n


def state_work(s: Shape, rows: int, itemsize: int = 4) -> dict:
    """The lightning layers' decode update of `rows` rows: each head's
    state read and written once, ``4 D^2`` operations a head (the outer
    product, the decay and sum, the query's product with the state)."""
    H, D = s.lightning_heads, s.lightning_head_dim
    return {"flops": s.lightning_layers * rows * H * 4 * D * D,
            "bytes": s.lightning_layers * rows * 2 * H * D * D * itemsize}


def serve_flops(s: Shape, steps) -> float:
    """Required forward operations of the recorded executor steps: every
    prompt and generated token through the matrices, its sparse layers'
    attention over the keys it attends and their selection scores, its
    lightning layers' state update, one head product an emitted token."""
    tokens = sum(x["prompt_tokens"] + x["decode_tokens"] for x in steps)
    emitted = sum(x["emitted"] for x in steps)
    head = 2 * s.d * s.vocab
    keys = sum(attended_keys(s, *_step_rows(x)) for x in steps)
    scored = sum(_windows_scored(s, *_step_rows(x)) for x in steps)
    qd = s.heads * s.head_dim
    return ((matmul_flops_per_token(s) - head) * tokens
            + s.sparse_layers * (4 * qd * keys + 2 * qd * scored)
            + state_work(s, tokens)["flops"] + head * emitted)


def paged_decode_work(s: Shape, keys: int, itemsize: int = 2) -> dict:
    """One sparse layer's decode attention over `keys` attended keys
    (summed over rows): K and V read once at KV width, a dot product and
    a weighted sum per key and query head."""
    return {"flops": 4 * keys * s.heads * s.head_dim,
            "bytes": 2 * keys * s.kv_heads * s.head_dim * itemsize}


def decode_attention_work(s: Shape, steps) -> dict:
    """`paged_decode_work` of the sparse layers over the recorded decode
    steps: the bytes of the blocks ATTENDED."""
    keys = sum(attended_keys(s, *_step_rows(st)) for st in steps
               if st["kind"] == "decode")
    return paged_decode_work(s, s.sparse_layers * keys)


def decode_query_pattern(s: Shape, rows: int) -> str:
    """The decode kernel's query operand in a trace event's text: a
    (row, KV group) pair is a row to it, ``[rows * kv_heads, kv_heads,
    group, head_dim]``."""
    return (rf"\[{rows * s.kv_heads},{s.kv_heads},"
            rf"{s.heads // s.kv_heads},{s.head_dim}\]")


def _prefill_work(s: Shape, tokens: int, keys: int,
                  itemsize: int = 2) -> dict:
    qo = 2 * tokens * s.heads * s.head_dim * itemsize
    kv = 2 * tokens * s.kv_heads * s.head_dim * itemsize
    return {"flops": s.sparse_layers * 4 * s.heads * s.head_dim * keys,
            "bytes": s.sparse_layers * (qo + kv)}


def attention_work(s: Shape, batch: int, seq: int) -> dict:
    """The sparse layers' prefill attention (forward only: the family is
    served) of `batch` prompts of `seq` tokens: QK^T and PV over the
    keys each query attends; q read and o written at query width, K and
    V of the tokens read once."""
    return _prefill_work(s, batch * seq,
                         attended_keys(s, [0] * batch, [seq] * batch))


def prefill_attention_work(s: Shape, steps) -> dict:
    """`attention_work` of the recorded prefill steps, each row from its
    own cached prefix."""
    pre = [x for x in steps if x["kind"] == "prefill"]
    return _prefill_work(
        s, sum(x["prompt_tokens"] for x in pre),
        sum(attended_keys(s, *_step_rows(x)) for x in pre))


WORK_COUNTS = {
    "param_count": param_count,
    "matmul_flops_per_decode_token": matmul_flops_per_token,
    "sparse_layer_row_at_16000":
        lambda s: paged_decode_work(s, attended_keys(s, [15999], [1])),
    "dense_walk_row_at_16000": lambda s: paged_decode_work(s, 16000),
    "state_update_one_row": lambda s: state_work(s, 1),
    "sparse_prefill_16384": lambda s: attention_work(s, 1, 16384),
}
