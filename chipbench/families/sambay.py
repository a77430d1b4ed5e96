"""The SambaY family (Phi-4-mini-flash-reasoning, microsoft, 2025-07;
arXiv:2507.06607): everything the benchmark knows of this architecture,
in one file.

``x`` is the float32 residual stream, ``x0 = embed[token]``. Layer ``l``:
``x = x + mixer_l(LN(x))``, then ``x = x + W_down(silu(W_gate u) * W_up u)``
with ``u = LN'(x)``; LN is LayerNorm with gain and bias; logits =
``LN_f(x) . embed^T`` (tied). Kind of layer ``l`` of ``n``, ``h = n / 2``
(`kinds`): even ``l <= h`` Mamba; odd ``l < h`` window attention; ``l =
h + 1`` full attention; even ``l > h + 1`` a gated memory unit; odd ``l >
h + 1`` cross-attention.

*Mamba* (Mamba-1): ``(a, z) = W_in h``; ``c_t = silu(b_c + sum_j w_c[:, j]
* a_{t-K+1+j})`` (causal, zeros before the first token); ``(delta_t, B_t,
C_t) = W_x c_t``; ``dt_t = softplus(W_dt delta_t + b_dt)``; ``A =
-exp(A_log)``; ``H_t = exp(dt_t (x) 1 * A) * H_{t-1} + (dt_t * c_t) (x)
B_t``, ``H_{-1} = 0``; ``y_t = H_t C_t + D * c_t``; output ``W_out(y_t *
silu(z_t))``. Layer ``h`` also hands ``M_t = y_t`` down the stack.

*Gated memory unit:* ``W_out'(silu(W_in' h_t) * M_t)``.

*Differential attention:* ``(q, k, v) = W_qkv h + b``, no position
encoding. Query pair ``i`` is ``(q_2i, q_2i+1)``, KV pair ``j = i // 2`` is
``(k_2j, k_2j+1)`` with ``V_j = [v_2j | v_2j+1]``; ``a1_i = softmax(q_2i
k_2j^T / sqrt(D) + mask) V_j``, ``a2_i`` the same of ``q_2i+1, k_2j+1``;
``lam = exp(lq1 . lk1) - exp(lq2 . lk2) + lam_init``, ``lam_init = 0.8 -
0.6 exp(-0.3 l)``; ``o_i = (1 - lam_init) * rmsnorm(a1_i - lam * a2_i;
g)``; output ``W_o [o_0 | ...] + b_o``. Causal; a window layer's query at
``p`` sees keys ``(p - window, p]``. *Cross-attention:* the same with a
``W_q`` of its own and the FULL layer's ``k``, ``v``.

What the source's config.json leaves open is under ``assumed`` in the
configuration file.

**The plain reference** (`logits_at`) is these equations in `jax.numpy`,
float32 at `Precision.HIGHEST`: no kernel, no cache, no ring, no
pair-heads, no last-token shortcut, nothing of the program imported.
Every layer runs at every position up to the last one read; the
recurrence is a loop over tokens; the two softmaxes of each pair are
written out. Forced by 16 GB: the weights are held as the program's
bfloat16 VALUES and upcast a layer at a time; rows go through the
projections `ROWS` at a time and attention scores are held for
`QUERY_BLOCK` queries at a time.

**Work counts** are what the algorithm requires: a prefill's
cross-decoder once a row, the keys a query sees (all of the full
layer's, ``window`` of a window layer's), both Mamba states read and
written once a row and layer.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.reference import (HIGHEST, _layer_norm, _round,  # noqa: F401
                                 seed_key)

#: the nearest precision below the configurations' (bfloat16 compute)
CONTROL = "fp8"

MAMBA, WINDOW, FULL, GMU, CROSS = "mamba", "window", "full", "gmu", "cross"

#: `--rehearse` widths (CPU, interpret-mode kernels): 8 layers hold all
#: five kinds and both halves; contexts pass the window of 8 and wrap
#: the ring more than once
REHEARSE_CONFIG = {
    "hidden_size": 64, "num_hidden_layers": 8, "num_attention_heads": 8,
    "num_key_value_heads": 4, "intermediate_size": 128, "sliding_window": 8,
    "max_position_embeddings": 128, "vocab_size": 512,
    "assumed": {"d_state": 4, "d_conv": 4, "expand": 2, "dt_rank": 4}}

PUBLISHED_WIDTHS = ("d", "layers", "heads", "kv_heads", "ffn", "vocab",
                    "window", "positions", "eps", "mb_per_layer")


def kinds(layers: int):
    half = layers // 2
    return tuple(
        (MAMBA if l % 2 == 0 else WINDOW) if l <= half else
        FULL if l == half + 1 else (GMU if l % 2 == 0 else CROSS)
        for l in range(layers))


class Shape:
    """The sizes, read from a configuration file's own keys."""

    def __init__(self, config: dict):
        a = config.get("assumed", {})
        self.d = int(config["hidden_size"])
        self.layers = int(config["num_hidden_layers"])
        self.heads = int(config["num_attention_heads"])
        self.kv_heads = int(config["num_key_value_heads"])
        self.head_dim = self.d // self.heads
        self.ffn = int(config["intermediate_size"])
        self.vocab = int(config["vocab_size"])
        #: ids below this are what a check admits as a token: the head is
        #: the embedding, no row is padding
        self.padded_vocab = self.vocab
        self.window = int(config["sliding_window"])
        self.positions = int(config["max_position_embeddings"])
        self.eps = float(config["layer_norm_eps"])
        self.mb_per_layer = int(config["mb_per_layer"])
        if self.mb_per_layer != 2 or self.layers % 4 \
                or not config["tie_word_embeddings"]:
            raise ValueError("a SambaY stack: a Mamba layer every second "
                             "layer, whole pairs in both halves, a tied head")
        self.d_state = int(a.get("d_state", 16))
        self.d_conv = int(a.get("d_conv", 4))
        self.d_inner = int(a.get("expand", 2)) * self.d
        self.dt_rank = int(a.get("dt_rank", -(-self.d // 16)))
        self.init_std = float(a.get("initializer_range", 0.02))
        self.kinds = kinds(self.layers)

    def count(self, kind: str) -> int:
        return self.kinds.count(kind)


# ---------------------------------------------------------------------------
# weights: the reference's own tree, and the same values as the program's
# ---------------------------------------------------------------------------

def _weight_shapes(s: Shape) -> dict:
    """Leaves stacked over ALL layers, over the Mamba ones (``m_``), the
    attention layers with K and V (``a_``: the window layers, then the
    full one), the memory units (``g_``) and the cross layers (``c_``)."""
    L, d, f, di = s.layers, s.d, s.ffn, s.d_inner
    M, A, G, C = s.count(MAMBA), s.count(WINDOW) + 1, s.count(GMU), \
        s.count(CROSS)
    D, qw, kvw = s.head_dim, s.heads * s.head_dim, \
        2 * s.kv_heads * s.head_dim
    return {"embed": (s.vocab, d), "norm_f_g": (d,), "norm_f_b": (d,),
            "norm1_g": (L, d), "norm1_b": (L, d), "norm2_g": (L, d),
            "norm2_b": (L, d), "w_gate": (L, d, f), "w_up": (L, d, f),
            "w_down": (L, f, d),
            "m_in": (M, d, 2 * di), "m_conv_w": (M, di, s.d_conv),
            "m_conv_b": (M, di), "m_x": (M, di, s.dt_rank + 2 * s.d_state),
            "m_dt_w": (M, s.dt_rank, di), "m_dt_b": (M, di),
            "m_A_log": (M, di, s.d_state), "m_D": (M, di),
            "m_out": (M, di, d),
            "a_wqkv": (A, d, qw + kvw), "a_bqkv": (A, qw + kvw),
            "a_wo": (A, qw, d), "a_bo": (A, d), "a_lam": (A, 4, D),
            "a_subln_g": (A, 2 * D),
            "g_in": (G, d, di), "g_out": (G, di, d),
            "c_wq": (C, d, qw), "c_bq": (C, qw), "c_wo": (C, qw, d),
            "c_bo": (C, d), "c_lam": (C, 4, D), "c_subln_g": (C, 2 * D)}


def _draw(name: str, key, dims, s: Shape):
    """One layer's leaf. Matrices and the embedding: normal(0, 0.02) as
    bfloat16 values; biases that noise in float32, gains 1 + it; the
    lambda vectors normal(0, 0.1); the conv taps normal(0, 1/sqrt(K));
    ``A_log = log(1..N)`` a channel; ``D = 1``; ``b_dt`` so that
    ``softplus(b_dt)`` is log-uniform in [0.001, 0.1]."""
    f32 = jnp.float32
    if name == "m_A_log":
        return jnp.log(jnp.broadcast_to(
            jnp.arange(1, dims[1] + 1, dtype=f32), dims))
    if name == "m_D":
        return jnp.ones(dims, f32)
    if name == "m_dt_b":
        dt = jnp.exp(jax.random.uniform(key, dims, f32)
                     * (math.log(0.1) - math.log(0.001)) + math.log(0.001))
        return dt + jnp.log(-jnp.expm1(-dt))
    noise = jax.random.normal(key, dims, f32)
    if name.endswith("_lam"):
        return 0.1 * noise
    if name == "m_conv_w":
        return noise / math.sqrt(dims[-1])
    x = s.init_std * noise
    if name.endswith("_g"):
        return 1.0 + x
    return x if len(dims) == 1 else x.astype(jnp.bfloat16)


def reference_weights(shape: Shape, key) -> dict:
    """The seed's weights. Traceable: call under `jit`. Made a layer at
    a time, so that no more than one leaf of one layer exists in
    float32."""
    out = {}
    for i, (name, shp) in enumerate(sorted(_weight_shapes(shape).items())):
        k = jax.random.fold_in(key, i)
        if name in ("embed", "norm_f_g", "norm_f_b"):
            out[name] = _draw(name, k, shp, shape)
        else:
            out[name] = jnp.stack([
                _draw(name, jax.random.fold_in(k, l), shp[1:], shape)
                for l in range(shp[0])])
    return out


def _nth(shape: Shape, l: int) -> int:
    """Layer ``l``'s place among the stacked leaves of its kind (the
    full layer is the last of the ``a_`` leaves)."""
    kind = shape.kinds[l]
    if kind == FULL:
        return shape.count(WINDOW)
    return sum(k == kind for k in shape.kinds[:l])


_PREFIX = {MAMBA: "m_", WINDOW: "a_", FULL: "a_", GMU: "g_", CROSS: "c_"}
_COMMON = ("norm1_g", "norm1_b", "norm2_g", "norm2_b", "w_gate", "w_up",
           "w_down")


def _layer_weights(w: dict, kind: str, l, nth) -> dict:
    """The leaves of layer ``l``, the ``nth`` of its kind, under its
    kind's names with the prefix dropped (``l``, ``nth`` may be traced)."""
    lw = {n: w[n][l] for n in _COMMON}
    lw.update({n[2:]: v[nth] for n, v in w.items()
               if n.startswith(_PREFIX[kind])})
    return lw


def program_params(shape: Shape, key) -> dict:
    """The same values as `models/sambay_lm.py`'s parameter tree (which
    holds the conv taps and ``A_log`` channels-last)."""
    w = reference_weights(shape, key)
    kern = lambda x: {"kernel": x}                          # noqa: E731
    biased = lambda x, b: {"bias": b, "proj": kern(x)}      # noqa: E731
    tree = {"embed": {"embedding": w["embed"]},
            "norm_f": {"scale": w["norm_f_g"], "bias": w["norm_f_b"]}}
    for l, kind in enumerate(shape.kinds):
        lw = _layer_weights(w, kind, l, _nth(shape, l))
        layer = {"norm1": {"scale": lw["norm1_g"], "bias": lw["norm1_b"]},
                 "norm2": {"scale": lw["norm2_g"], "bias": lw["norm2_b"]},
                 **{n: kern(lw[n]) for n in ("w_gate", "w_up", "w_down")}}
        lam = lambda: {f"lambda_{n}": lw["lam"][i] for i, n in  # noqa: E731
                       enumerate(("q1", "k1", "q2", "k2"))}
        if kind == MAMBA:
            layer["mixer"] = {
                "in_proj": kern(lw["in"]), "conv_w": lw["conv_w"].T,
                "conv_b": lw["conv_b"], "x_proj": kern(lw["x"]),
                "dt_proj": kern(lw["dt_w"]), "dt_bias": lw["dt_b"],
                "A_log": lw["A_log"].T, "D": lw["D"],
                "out_proj": kern(lw["out"])}
        elif kind == GMU:
            layer.update(in_proj=kern(lw["in"]), out_proj=kern(lw["out"]))
        elif kind == CROSS:
            layer["mixer"] = {"wq": biased(lw["wq"], lw["bq"]),
                              "wo": biased(lw["wo"], lw["bo"]),
                              "subln": lw["subln_g"], **lam()}
        else:
            layer["mixer"] = {"wqkv": lw["wqkv"], "bqkv": lw["bqkv"],
                              "wo": biased(lw["wo"], lw["bo"]),
                              "subln": lw["subln_g"], **lam()}
        tree[f"layers_{l}"] = layer
    return tree


def serve_model(shape: Shape, config: dict, *, kv_block: int,
                kv_pool_blocks: int, decode_kernel):
    """The model object a `ShardedExecutor` gets."""
    from horovod_tpu.models.sambay_lm import SambaYConfig, SambaYLM
    assumed = config.get("assumed", {})
    return SambaYLM(SambaYConfig(
        vocab_size=shape.vocab, num_layers=shape.layers, embed_dim=shape.d,
        num_heads=shape.heads, num_kv_heads=shape.kv_heads,
        ffn_dim=shape.ffn, window=shape.window, d_state=shape.d_state,
        d_conv=shape.d_conv, expand=shape.d_inner // shape.d,
        dt_rank=shape.dt_rank, norm_eps=shape.eps,
        max_seq_len=shape.positions,
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


def _proj(h, w, n_read, precision, bias=None):
    out = _blocks(lambda hi, _: _mm(hi, w, precision), h, ROWS, n_read,
                  w.shape[1])
    return out if bias is None else out + bias


def _mamba(h, lw, n_read, s: Shape, precision: str):
    """h [S, d] -> ``(W_out(y * silu(z)), y)``: the recurrence, a token
    at a time."""
    S, di, N, K, R = h.shape[0], s.d_inner, s.d_state, s.d_conv, s.dt_rank
    az = _proj(h, lw["in"], n_read, precision)
    a, z = az[:, :di], az[:, di:]
    ap = jnp.pad(a, ((K - 1, 0), (0, 0)))
    c = jax.nn.silu(lw["conv_b"] + sum(
        lw["conv_w"][:, j] * ap[j:j + S] for j in range(K)))
    x = _proj(c, lw["x"], n_read, precision)
    dt = jax.nn.softplus(
        _proj(x[:, :R], lw["dt_w"], n_read, precision, lw["dt_b"]))
    Bm, Cm = x[:, R:R + N], x[:, R + N:]
    A = -jnp.exp(lw["A_log"])                                   # [di, N]

    def token(t, carry):
        H, out = carry
        H = jnp.exp(dt[t][:, None] * A) * H \
            + (dt[t] * c[t])[:, None] * Bm[t][None, :]
        y = jnp.sum(H * Cm[t][None, :], axis=1) + lw["D"] * c[t]
        return H, jax.lax.dynamic_update_slice(out, y[None], (t, 0))

    _, y = jax.lax.fori_loop(
        0, jnp.minimum(n_read, S), token,
        (jnp.zeros((di, N), jnp.float32), jnp.zeros((S, di), jnp.float32)))
    return _proj(y * jax.nn.silu(z), lw["out"], n_read, precision), y


def _diff_attention(q, k, v, lam, g, layer, window, n_read, s: Shape,
                    precision: str):
    """q [S, H, D], k/v [S, KV, D] -> [S, H*D]: both softmaxes of every
    pair, the subtraction, the norm over the pair's width."""
    S, H, D = q.shape
    P = s.kv_heads // 2
    k1, k2 = (_round(k[:, i::2], precision) for i in (0, 1))    # [S, P, D]
    V = _round(jnp.concatenate([v[:, 0::2], v[:, 1::2]], axis=-1),
               precision)                                       # [S, P, 2D]
    init = 0.8 - 0.6 * jnp.exp(-0.3 * jnp.asarray(layer, jnp.float32))
    lam = jnp.exp(jnp.sum(lam[0] * lam[1])) \
        - jnp.exp(jnp.sum(lam[2] * lam[3])) + init
    key_pos = jnp.arange(S)

    def block(qi, start):
        q_pos = start + jnp.arange(qi.shape[0])
        ok = key_pos[None, :] <= q_pos[:, None]
        if window is not None:
            ok &= key_pos[None, :] > q_pos[:, None] - window

        def softmax_v(qh, kh):
            # query pair i reads KV pair i // 2
            qh = _round(qh.reshape(-1, P, H // 2 // P, D), precision)
            sc = jnp.einsum("qjgd,sjd->jgqs", qh, kh,
                            precision=HIGHEST) / math.sqrt(D)
            p = jax.nn.softmax(jnp.where(ok[None, None], sc, -1e30), axis=-1)
            return jnp.einsum("jgqs,sje->qjge", _round(p, precision), V,
                              precision=HIGHEST)

        o = softmax_v(qi[:, 0::2], k1) - lam * softmax_v(qi[:, 1::2], k2)
        o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True)
                              + s.eps) * g * (1.0 - init)
        return o.reshape(-1, H * D)

    return _blocks(block, q, QUERY_BLOCK, n_read, H * D)


def _layer(x, lw, kind: str, layer, n_read, s: Shape, precision: str,
           memory=None, kv=None):
    """One layer -> ``(x, memory, kv)``: ``memory`` the Mamba layer's
    ``y``, ``kv`` the K and V an attention layer computed."""
    S, H, KV, D = x.shape[0], s.heads, s.kv_heads, s.head_dim
    h = _layer_norm(x, lw["norm1_g"], lw["norm1_b"], s.eps)
    if kind == MAMBA:
        a, memory = _mamba(h, lw, n_read, s, precision)
    elif kind == GMU:
        a = _proj(jax.nn.silu(_proj(h, lw["in"], n_read, precision))
                  * memory, lw["out"], n_read, precision)
    else:
        if kind == CROSS:
            q = _proj(h, lw["wq"], n_read, precision, lw["bq"])
        else:
            qkv = _proj(h, lw["wqkv"], n_read, precision, lw["bqkv"])
            q = qkv[:, :H * D]
            kv = (qkv[:, H * D:(H + KV) * D].reshape(S, KV, D),
                  qkv[:, (H + KV) * D:].reshape(S, KV, D))
        o = _diff_attention(
            q.reshape(S, H, D), *kv, lw["lam"], lw["subln_g"], layer,
            s.window if kind == WINDOW else None, n_read, s, precision)
        a = _proj(o, lw["wo"], n_read, precision, lw["bo"])
    x = x + a
    u = _layer_norm(x, lw["norm2_g"], lw["norm2_b"], s.eps)

    def ffn(ui, _):
        hid = jax.nn.silu(_mm(ui, lw["w_gate"], precision)) \
            * _mm(ui, lw["w_up"], precision)
        return _mm(hid, lw["w_down"], precision)

    return x + _blocks(ffn, u, ROWS, n_read, s.d), memory, kv


def hidden(w, shape: Shape, tokens, n_read, precision: str = "float32"):
    """Final-norm hidden states [S, d] of ONE sequence, tokens [1, S];
    rows at and past ``n_read`` mean nothing. EVERY layer at every
    position."""
    x = w["embed"][tokens[0]].astype(jnp.float32)
    half = shape.layers // 2

    def one(x, kind, l, nth, memory=None, kv=None):
        return _layer(x, _layer_weights(w, kind, l, nth), kind, l, n_read,
                      shape, precision, memory, kv)

    # the alternating layers go pair by pair under one `scan` (one
    # compiled body a half), which takes a layer's leaves out of the
    # stacks at a time
    def lower(x, i):
        x, _, _ = one(x, MAMBA, 2 * i, i)
        x, _, _ = one(x, WINDOW, 2 * i + 1, i)
        return x, None

    x, _ = jax.lax.scan(lower, x, jnp.arange(half // 2))
    x, memory, _ = one(x, MAMBA, half, half // 2)
    x, _, kv = one(x, FULL, half + 1, half // 2)

    def upper(x, i):
        x, _, _ = one(x, GMU, half + 2 + 2 * i, i, memory=memory)
        x, _, _ = one(x, CROSS, half + 3 + 2 * i, i, kv=kv)
        return x, None

    x, _ = jax.lax.scan(upper, x, jnp.arange(half // 2 - 1))
    return _layer_norm(x, w["norm_f_g"], w["norm_f_b"], shape.eps)


def logits_at(w, shape: Shape, tokens, where, precision: str = "float32"):
    """[n, vocab] float32 logits of ONE sequence (tokens [1, S]) at the
    positions `where` [n]."""
    n_read = jnp.max(where) + 1
    x = hidden(w, shape, tokens, n_read, precision)[where]
    return _mm(x, w["embed"].T, precision)


# ---------------------------------------------------------------------------
# required work, from the sizes alone
# ---------------------------------------------------------------------------

def _mixer_params(s: Shape, kind: str) -> int:
    """One layer's mixer matrices."""
    d, di, qw = s.d, s.d_inner, s.heads * s.head_dim
    return {MAMBA: d * 2 * di + di * (s.dt_rank + 2 * s.d_state)
            + s.dt_rank * di + di * d,
            WINDOW: d * (qw + 2 * s.kv_heads * s.head_dim) + qw * d,
            GMU: 2 * d * di, CROSS: 2 * d * qw}[
                WINDOW if kind == FULL else kind]


def _small_params(s: Shape, kind: str) -> int:
    """Biases, gains and vectors: two LayerNorms, and the mixer's."""
    di, qw, D = s.d_inner, s.heads * s.head_dim, s.head_dim
    lam = 4 * D + 2 * D
    return 4 * s.d + {
        MAMBA: di * s.d_conv + di + di + di * s.d_state + di,
        WINDOW: qw + 2 * s.kv_heads * D + s.d + lam,
        GMU: 0, CROSS: qw + s.d + lam}[WINDOW if kind == FULL else kind]


def param_count(s: Shape) -> int:
    return sum(_mixer_params(s, k) + 3 * s.d * s.ffn + _small_params(s, k)
               for k in s.kinds) + s.vocab * s.d + 2 * s.d


def matmul_flops_per_decode_token(s: Shape) -> int:
    """Forward operations of one decode token: every layer's matrices
    and the head, without attention or state."""
    return 2 * sum(_mixer_params(s, k) + 3 * s.d * s.ffn for k in s.kinds) \
        + 2 * s.d * s.vocab


def matmul_flops_per_prefill_token(s: Shape) -> int:
    """... of one prompt token that emits nothing: the self-decoder's
    layers and the full layer's K and V projection."""
    below = [k for k in s.kinds if k in (MAMBA, WINDOW)]
    return 2 * sum(_mixer_params(s, k) + 3 * s.d * s.ffn for k in below) \
        + 2 * s.d * 2 * s.kv_heads * s.head_dim


def _attn_flops_per_key(s: Shape) -> int:
    """A query head's product with a key of ``D`` and its weight on a
    pair's V of ``2 D``."""
    return s.heads * (2 * s.head_dim + 4 * s.head_dim)


def _keys_seen(s: Shape, start, n, window=None) -> int:
    """Keys `n` consecutive queries from position `start` see (arrays,
    one entry a row), all of the context or its newest `window`."""
    total = 0
    for a, m in zip(np.asarray(start, np.int64).ravel(),
                    np.asarray(n, np.int64).ravel()):
        seen = a + np.arange(m, dtype=np.int64) + 1
        total += int((seen if window is None
                      else np.minimum(seen, window)).sum())
    return total


def _step_rows(step: dict):
    """(start, tokens) per row of a recorded step."""
    start = np.asarray(step["rows_start"], np.int64)
    n = np.asarray(step["rows_tokens"], np.int64) \
        if step["kind"] == "prefill" else np.ones_like(start)
    return start, n


def state_work(s: Shape, rows: int, itemsize: int = 4) -> dict:
    """The Mamba layers' decode update of `rows` rows: the conv state
    (``d_conv`` inputs a channel) and the SSM state read and written
    once; seven operations a state element (the decay's product,
    exponential and product, the input's two, the output's two) and
    two a conv tap."""
    per_row = s.d_inner * (s.d_state + s.d_conv)
    return {"flops": s.count(MAMBA) * rows * s.d_inner
            * (7 * s.d_state + 2 * s.d_conv),
            "bytes": s.count(MAMBA) * rows * 2 * per_row * itemsize}


def serve_flops(s: Shape, steps) -> float:
    """Required forward operations of the recorded executor steps: a
    prompt token through the self-decoder and the full layer's K and V;
    an emitting token (a decode token, a prefill row's last) through
    everything else and the head; each query's attention over the keys
    it sees; the Mamba update a token."""
    tokens = sum(x["prompt_tokens"] + x["decode_tokens"] for x in steps)
    emitted = sum(x["emitted"] for x in steps)
    low = matmul_flops_per_prefill_token(s)
    windowed = sum(_keys_seen(s, *_step_rows(x), s.window) for x in steps)
    # the full layer and the cross layers attend at emitting tokens only
    full = sum(_keys_seen(s, start + n - 1, np.ones_like(n))
               for start, n in map(_step_rows, steps))
    return (low * tokens
            + (matmul_flops_per_decode_token(s) - low) * emitted
            + _attn_flops_per_key(s) * (
                s.count(WINDOW) * windowed + (1 + s.count(CROSS)) * full)
            + state_work(s, tokens)["flops"])


def kv_token_bytes(s: Shape, itemsize: int = 2) -> int:
    """K and V of one token in one layer that has them."""
    return 2 * s.kv_heads * s.head_dim * itemsize


def decode_row_work(s: Shape, context: int) -> dict:
    """One decode row at `context` (its own token included): the pool
    read by the full layer and every cross layer, a ring by each window
    layer."""
    keys = (1 + s.count(CROSS)) * context \
        + s.count(WINDOW) * min(context, s.window)
    return {"flops": _attn_flops_per_key(s) * keys,
            "bytes": kv_token_bytes(s) * keys}


def decode_attention_work(s: Shape, steps) -> dict:
    """`decode_row_work` over the recorded decode steps' rows."""
    total = {"flops": 0, "bytes": 0}
    for st in steps:
        if st["kind"] != "decode":
            continue
        for p in np.asarray(st["rows_start"], np.int64).ravel():
            work = decode_row_work(s, int(p) + 1)
            total = {k: total[k] + work[k] for k in total}
    return total


def decode_query_pattern(s: Shape, rows: int) -> str:
    """The decode kernel's query operand in a trace event's text: pool
    and ring reads alike, ``[rows, pairs, 4, 2 D]`` (a prefill row's
    last token is one row)."""
    return (rf"\[{rows},{s.kv_heads // 2},"
            rf"{2 * s.heads // s.kv_heads},{2 * s.head_dim}\]")


def _prefill_work(s: Shape, tokens: int, keys: int,
                  itemsize: int = 2) -> dict:
    qo = tokens * s.heads * s.head_dim * (itemsize + 2 * 4)
    return {"flops": s.count(WINDOW) * _attn_flops_per_key(s) * keys,
            "bytes": s.count(WINDOW) * (qo + kv_token_bytes(s) * tokens)}


def attention_work(s: Shape, batch: int, seq: int) -> dict:
    """The window layers' prefill attention of `batch` prompts of `seq`
    tokens: q read at query width, both softmaxes' outputs written in
    float32 at twice it, K and V of the tokens read once."""
    return _prefill_work(s, batch * seq,
                         _keys_seen(s, [0] * batch, [seq] * batch, s.window))


def prefill_attention_work(s: Shape, steps) -> dict:
    pre = [x for x in steps if x["kind"] == "prefill"]
    return _prefill_work(
        s, sum(x["prompt_tokens"] for x in pre),
        sum(_keys_seen(s, *_step_rows(x), s.window) for x in pre))


def cache_row_bytes(s: Shape) -> int:
    """What a row holds whatever its context: both Mamba states, the
    window layers' rings."""
    return s.count(MAMBA) * 4 * s.d_inner * (s.d_state + s.d_conv) \
        + s.count(WINDOW) * s.window * kv_token_bytes(s)


def cache_bytes(s: Shape, context: int) -> int:
    """Bytes of values a row at `context` holds in the cache."""
    return kv_token_bytes(s) * context + cache_row_bytes(s)


WORK_COUNTS = {
    "param_count": param_count,
    "matmul_flops_per_decode_token": matmul_flops_per_decode_token,
    "matmul_flops_per_prefill_token": matmul_flops_per_prefill_token,
    "decode_row_at_4096": lambda s: decode_row_work(s, 4096),
    "state_update_one_row": lambda s: state_work(s, 1),
    "cache_bytes_row_at_4096": lambda s: cache_bytes(s, 4096),
    "window_prefill_4928": lambda s: attention_work(s, 1, 4928),
}
