"""The DeepSeek-V3 layout as JoyAI-LLM-Flash publishes it (jdopensource,
2026): multi-head latent attention, a leading dense layer, then layers of
256 sigmoid-routed SwiGLU experts with a shared expert. Everything the
benchmark knows of this architecture, in one file.

``x`` is the float32 residual stream, ``x0 = embed[token]``. Layer ``l``:
``h = rms(x)``, ``x += MLA(h)``, ``u = rms'(x)``, ``x += FFN_l(u)``;
RMSNorm with eps and a gain; logits ``rms_f(x) . W_head`` (untied, no
biases anywhere).

*MLA:* ``q = W_qb rms_q(W_qa h)``, ``H`` heads of ``N + P`` split ``q_n``
(``N``) and ``q_r`` (``P``); ``[c ; k_r] = W_kva h`` (``R + P``), ``c =
rms_kv(c)``; ``[k_n,i ; v_i] = W_kvb,i c`` (``N + V`` a head). RoPE over
interleaved pairs ``(2j, 2j+1)`` at absolute positions on ``q_r`` and on
the one ``k_r`` all heads share. ``s_i(t, tau) = (q_n,i . k_n,i(tau) +
q_r,i . k_r(tau)) / sqrt(N + P)``, causal softmax, ``W_o [sum_tau p_i
v_i(tau)]_i``.

*FFN:* the first ``first_k_dense_replace`` layers ``W_d(silu(W_g u) * W_u
u)``; the others ``sigma = sigmoid(u . W_r)`` (float32), ``E = top_k(sigma
+ b)``, ``w_e = scale * sigma_e / (sum_E sigma + 1e-20)``, ``y = S(u) +
sum_E w_e SwiGLU_e(u)`` with ``S`` the shared SwiGLU.

What the source's config.json leaves open is under ``assumed`` in the
configuration file.

**The plain reference** (`logits_at`) is these equations in `jax.numpy`,
float32 at `Precision.HIGHEST`, in the PUBLISHED form: per-head keys and
values up-projected from the latent, no absorption, no cache, no kernel,
nothing of the program imported; the experts a loop over all of them
with a mask. Forced by 16 GB: the weights are held as the program's
bfloat16 VALUES and upcast a layer, and an expert, at a time; rows go
through the experts `ROWS` at a time (blocks past the last position read
skipped) and attention scores are held for `QUERY_BLOCK` queries at a
time.

**Work counts** are what the algorithm requires: every token through
every matrix once (the decode's absorbed products cost what the
up-projection they replace costs), a prefill's attention in the
published form from each row's own cached prefix, a decode's over the
latent in the absorbed form, ``top_k`` routed and the shared SwiGLU a
token, the experts' weight bytes from the COUNTED experts that received
a token; weights at bfloat16's 2 bytes, activations and the cached
latent at float32's 4 (the program rounds none of them).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.reference import HIGHEST, _round, seed_key  # noqa: F401

#: the nearest precision below the configuration's (bfloat16 weights)
CONTROL = "fp8"

#: `--rehearse` widths (CPU, interpret-mode kernels): the dense layer and
#: two routed ones, 16 experts top 4, a latent of rank 32
REHEARSE_CONFIG = {
    "hidden_size": 64, "num_hidden_layers": 3, "num_attention_heads": 4,
    "num_key_value_heads": 4, "q_lora_rank": 48, "kv_lora_rank": 32,
    "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "qk_head_dim": 24,
    "v_head_dim": 16, "head_dim": 8, "intermediate_size": 128,
    "moe_intermediate_size": 32, "n_routed_experts": 16,
    "num_experts_per_tok": 4, "max_position_embeddings": 128,
    "vocab_size": 512}

PUBLISHED_WIDTHS = ("d", "heads", "q_rank", "kv_rank", "nope", "rope",
                    "v_dim", "dense_layers", "dense_ffn", "experts", "top_k",
                    "ffn", "shared", "routed_scale", "theta", "eps", "vocab",
                    "positions")


class Shape:
    """The sizes, read from a configuration file's own keys."""

    def __init__(self, config: dict):
        self.d = int(config["hidden_size"])
        self.layers = int(config["num_hidden_layers"])
        self.heads = int(config["num_attention_heads"])
        self.q_rank = int(config["q_lora_rank"])
        self.kv_rank = int(config["kv_lora_rank"])
        self.nope = int(config["qk_nope_head_dim"])
        self.rope = int(config["qk_rope_head_dim"])
        self.v_dim = int(config["v_head_dim"])
        self.dense_layers = int(config["first_k_dense_replace"])
        self.dense_ffn = int(config["intermediate_size"])
        self.experts = int(config["n_routed_experts"])
        self.top_k = int(config["num_experts_per_tok"])
        self.ffn = int(config["moe_intermediate_size"])
        self.shared = int(config["n_shared_experts"])
        self.routed_scale = float(config["routed_scaling_factor"])
        self.theta = float(config["rope_theta"])
        self.eps = float(config["rms_norm_eps"])
        self.vocab = int(config["vocab_size"])
        self.padded_vocab = self.vocab
        self.positions = int(config["max_position_embeddings"])
        self.mtp_layers = int(config["num_nextn_predict_layers"])
        self.init_std = float(config.get("assumed", {}).get(
            "initializer_range", 0.02))
        if (config["scoring_func"], config["topk_method"], config["n_group"],
                config["topk_group"], config["norm_topk_prob"],
                config["rope_interleave"], config["rope_scaling"],
                config["moe_layer_freq"], config["tie_word_embeddings"],
                self.mtp_layers) != ("sigmoid", "noaux_tc", 1, 1, True, True,
                                     None, 1, False, 0):
            raise ValueError(
                "the family's layer: sigmoid noaux_tc routing in one group, "
                "normalised; interleaved RoPE without scaling; every layer "
                "after the dense ones routed; an untied head; no "
                "multi-token-prediction module")
        if self.nope + self.rope != int(config["qk_head_dim"]):
            raise ValueError("qk_head_dim is qk_nope + qk_rope")

    @property
    def moe_layers(self) -> int:
        return self.layers - self.dense_layers

    @property
    def latent(self) -> int:
        """Values a token and layer caches: ``[c ; k_r]``."""
        return self.kv_rank + self.rope


# ---------------------------------------------------------------------------
# weights: the reference's own tree, and the same values as the program's
# ---------------------------------------------------------------------------

#: leaves of every layer, of the dense layers, of the routed layers, and
#: of the top; the experts' (gate then up side by side in ``w_in``, as
#: the program holds them) are drawn an expert at a time
ATTN_LEAVES = ("attn_norm_g", "mlp_norm_g", "q_norm_g", "kv_norm_g", "wq_a",
               "wq_b", "wkv_a", "wkv_b", "wo")
DENSE_LEAVES = ("d_gate", "d_up", "d_down")
MOE_LEAVES = ("router", "bias", "s_gate", "s_up", "s_down", "w_in", "w_out")
EXPERT_LEAVES = ("w_in", "w_out")
_LEAF_NAMES = ("embed", "norm_f_g", "head") + ATTN_LEAVES + DENSE_LEAVES \
    + MOE_LEAVES


def _dims(s: Shape) -> dict:
    """One layer's (or the top's) shape of each leaf."""
    d, H, R, N, P, V = s.d, s.heads, s.kv_rank, s.nope, s.rope, s.v_dim
    f, E, fs = s.ffn, s.experts, s.shared * s.ffn
    return {"embed": (s.vocab, d), "norm_f_g": (d,), "head": (d, s.vocab),
            "attn_norm_g": (d,), "mlp_norm_g": (d,), "q_norm_g": (s.q_rank,),
            "kv_norm_g": (R,), "wq_a": (d, s.q_rank),
            "wq_b": (s.q_rank, H * (N + P)), "wkv_a": (d, R + P),
            "wkv_b": (R, H * (N + V)), "wo": (H * V, d),
            "d_gate": (d, s.dense_ffn), "d_up": (d, s.dense_ffn),
            "d_down": (s.dense_ffn, d), "router": (d, E), "bias": (E,),
            "s_gate": (d, fs), "s_up": (d, fs), "s_down": (fs, d),
            "w_in": (E, d, 2 * f), "w_out": (E, f, d)}


def _draw(name: str, key, dims, s: Shape):
    """Matrices and the embedding normal(0, 0.02) as bfloat16 values;
    norm gains 1 + that noise and the selection bias that noise, float32."""
    x = s.init_std * jax.random.normal(key, dims, jnp.float32)
    if name.endswith("_g"):
        return 1.0 + x
    return x if name == "bias" else x.astype(jnp.bfloat16)


def _key(name: str, key):
    return jax.random.fold_in(key, sorted(_LEAF_NAMES).index(name))


def layer_leaf(name: str, key, layer, s: Shape):
    """Layer ``layer``'s leaf `name` (``layer`` may be traced); an
    expert leaf is drawn an expert at a time, gate and up apart."""
    k = jax.random.fold_in(_key(name, key), layer)
    dims = _dims(s)[name]
    if name not in EXPERT_LEAVES:
        return _draw(name, k, dims, s)

    def expert(e):
        ke = jax.random.fold_in(k, e)
        if name == "w_out":
            return _draw(name, ke, dims[1:], s)
        half = (dims[1], dims[2] // 2)
        return jnp.concatenate([_draw(name, jax.random.fold_in(ke, i), half,
                                      s) for i in (0, 1)], axis=-1)
    return jax.lax.map(expert, jnp.arange(dims[0]))



def reference_weights(shape: Shape, key) -> dict:
    """The seed's weights: the top's, then each leaf stacked over the
    layers that have it (every layer's attention; the dense layers'; the
    routed layers'). Traceable: call under `jit`; an expert's float32
    draw is the largest temporary."""
    out = {n: _draw(n, _key(n, key), _dims(shape)[n], shape)
           for n in ("embed", "norm_f_g", "head")}
    spans = [(ATTN_LEAVES, 0, shape.layers),
             (DENSE_LEAVES, 0, shape.dense_layers),
             (MOE_LEAVES, shape.dense_layers, shape.layers)]
    for names, lo, hi in spans:
        for n in names:
            out[n] = jax.lax.map(lambda l, n=n: layer_leaf(n, key, l, shape),
                                 jnp.arange(lo, hi))
    return out


def program_params(shape: Shape, key) -> dict:
    """The same values as `models/mla_lm.py`'s parameter tree, drawn a
    layer at a time (never stacked, so never copied out of a stack)."""
    top = {n: _draw(n, _key(n, key), _dims(shape)[n], shape)
           for n in ("embed", "norm_f_g", "head")}
    kern = lambda x: {"kernel": x}                          # noqa: E731
    tree = {"embed": {"embedding": top["embed"]},
            "norm_f": {"scale": top["norm_f_g"]},
            "lm_head": kern(top["head"])}
    for l in range(shape.layers):
        w = lambda n: layer_leaf(n, key, l, shape)          # noqa: E731
        layer = {"attn_norm": {"scale": w("attn_norm_g")},
                 "mlp_norm": {"scale": w("mlp_norm_g")},
                 "attn": {"wq_a": kern(w("wq_a")),
                          "q_norm": {"scale": w("q_norm_g")},
                          "wq_b": kern(w("wq_b")), "wkv_a": kern(w("wkv_a")),
                          "kv_norm": {"scale": w("kv_norm_g")},
                          "wkv_b": w("wkv_b"), "wo": kern(w("wo"))}}
        if l < shape.dense_layers:
            layer["mlp"] = {"w_gate": kern(w("d_gate")),
                            "w_up": kern(w("d_up")),
                            "w_down": kern(w("d_down"))}
        else:
            layer["moe"] = {"router": w("router"), "bias": w("bias"),
                            "w_in": w("w_in"), "w_out": w("w_out"),
                            "shared": {"w_gate": kern(w("s_gate")),
                                       "w_up": kern(w("s_up")),
                                       "w_down": kern(w("s_down"))}}
        tree[f"layers_{l}"] = layer
    return tree


def serve_model(shape: Shape, config: dict, *, kv_block: int,
                kv_pool_blocks: int, decode_kernel):
    """The model object a `ShardedExecutor` gets."""
    from horovod_tpu.models.mla_lm import MLALM, MLAConfig
    assumed = config.get("assumed", {})
    return MLALM(MLAConfig(
        vocab_size=shape.vocab, num_layers=shape.layers, embed_dim=shape.d,
        num_heads=shape.heads, q_lora_rank=shape.q_rank,
        kv_lora_rank=shape.kv_rank, qk_nope_dim=shape.nope,
        qk_rope_dim=shape.rope, v_head_dim=shape.v_dim,
        dense_layers=shape.dense_layers, dense_dim=shape.dense_ffn,
        num_experts=shape.experts, experts_per_token=shape.top_k,
        expert_dim=shape.ffn, shared_experts=shape.shared,
        routed_scaling=shape.routed_scale, rope_theta=shape.theta,
        rms_eps=shape.eps, max_seq_len=shape.positions,
        dtype=jnp.dtype(assumed.get("compute_dtype", "bfloat16")),
        param_dtype=jnp.dtype(assumed.get("param_dtype", "bfloat16")),
        decode=True, kv_block_size=kv_block, kv_pool_blocks=kv_pool_blocks,
        decode_kernel=decode_kernel))


# ---------------------------------------------------------------------------
# the plain reference
# ---------------------------------------------------------------------------

#: rows that go through the experts at once, and queries whose scores are
#: held at once
ROWS, QUERY_BLOCK = 2048, 128


def _mm(a, b, precision: str):
    return jnp.matmul(_round(a, precision),
                      _round(b.astype(jnp.float32), precision),
                      precision=HIGHEST)


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * g


def _rope(x, positions, theta):
    """x [S, n, D] rotated over interleaved pairs (2j, 2j+1) by
    ``position * theta^(-2j/D)``."""
    S, n, D = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D))
    ang = positions.astype(jnp.float32)[:, None] * inv          # [S, D/2]
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                     axis=-1).reshape(S, n, D)


def _blocks(f, x, size: int, n_read, width: int):
    """``f`` over `size` rows of ``x [S, ...]`` at a time -> ``[S,
    width]``; blocks that start at or past row ``n_read`` are zero."""
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


def _attention(h, lw, n_read, s: Shape, precision: str):
    """The published MLA: every head's key and value up-projected from
    the latent, both softmax products written out."""
    S, H, R, N, P, V = h.shape[0], s.heads, s.kv_rank, s.nope, s.rope, \
        s.v_dim
    positions = jnp.arange(S)
    q = _mm(_rms(_mm(h, lw["wq_a"], precision), lw["q_norm_g"], s.eps),
            lw["wq_b"], precision).reshape(S, H, N + P)
    ckr = _mm(h, lw["wkv_a"], precision)
    c = _rms(ckr[:, :R], lw["kv_norm_g"], s.eps)
    k_r = _rope(ckr[:, None, R:], positions, s.theta)            # [S, 1, P]
    kv = _mm(c, lw["wkv_b"], precision).reshape(S, H, N + V)
    k = _round(jnp.concatenate(
        [kv[..., :N], jnp.broadcast_to(k_r, (S, H, P))], axis=-1), precision)
    v = _round(kv[..., N:], precision)
    q = jnp.concatenate([q[..., :N], _rope(q[..., N:], positions, s.theta)],
                        axis=-1)
    key_pos = jnp.arange(S)

    def block(qi, start):
        q_pos = start + jnp.arange(qi.shape[0])
        sc = jnp.einsum("qhd,khd->hqk", _round(qi, precision), k,
                        precision=HIGHEST) / math.sqrt(N + P)
        ok = key_pos[None, :] <= q_pos[:, None]
        p = jax.nn.softmax(jnp.where(ok[None], sc, -1e30), axis=-1)
        return jnp.einsum("hqk,khv->qhv", _round(p, precision), v,
                          precision=HIGHEST).reshape(-1, H * V)

    o = _blocks(block, q, QUERY_BLOCK, n_read, H * V)
    return _mm(o, lw["wo"], precision)


def _swiglu(u, wg, wu, wd, precision):
    return _mm(jax.nn.silu(_mm(u, wg, precision)) * _mm(u, wu, precision),
               wd, precision)


def _routed(u, lw, n_read, s: Shape, precision: str):
    """Sigmoid routing with the selection bias, then every expert over
    every row of a block, kept where the row chose it; the shared
    expert on every row."""
    f = s.ffn

    def rows(ui, _):
        sig = jax.nn.sigmoid(_mm(ui, lw["router"], precision))  # [rows, E]
        _, chosen = jax.lax.top_k(sig + lw["bias"], s.top_k)
        w = jnp.take_along_axis(sig, chosen, axis=-1)
        w = s.routed_scale * w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
        gate = jnp.zeros_like(sig).at[
            jnp.arange(sig.shape[0])[:, None], chosen].set(w)   # [rows, E]

        def one(y, e):
            w_in, w_out, g = e
            return y + g[:, None] * _swiglu(ui, w_in[:, :f], w_in[:, f:],
                                            w_out, precision), None

        y, _ = jax.lax.scan(one, _swiglu(ui, lw["s_gate"], lw["s_up"],
                                         lw["s_down"], precision),
                            (lw["w_in"], lw["w_out"], gate.T))
        return y

    return _blocks(rows, u, ROWS, n_read, s.d)


def _layer(x, lw, dense: bool, n_read, s: Shape, precision: str):
    x = x + _attention(_rms(x, lw["attn_norm_g"], s.eps), lw, n_read, s,
                       precision)
    u = _rms(x, lw["mlp_norm_g"], s.eps)
    if dense:
        return x + _blocks(lambda ui, _: _swiglu(
            ui, lw["d_gate"], lw["d_up"], lw["d_down"], precision), u, ROWS,
            n_read, s.d)
    return x + _routed(u, lw, n_read, s, precision)


def hidden(w, shape: Shape, tokens, n_read, precision: str = "float32"):
    """Final-norm hidden states [S, d] of ONE sequence, tokens [1, S];
    rows at and past ``n_read`` mean nothing."""
    x = w["embed"][tokens[0]].astype(jnp.float32)
    Ld = shape.dense_layers

    def layers(names, idx):
        return {n: w[n][idx] for n in names}

    for l in range(Ld):
        x = _layer(x, layers(ATTN_LEAVES + DENSE_LEAVES, l), True, n_read,
                   shape, precision)

    def routed(x, lw):
        return _layer(x, lw, False, n_read, shape, precision), None

    # the routed layers under one `scan`, which takes a layer's leaves
    # out of the stacks at a time
    x, _ = jax.lax.scan(routed, x, dict(
        {n: w[n][Ld:] for n in ATTN_LEAVES}, **{n: w[n] for n in MOE_LEAVES}))
    return _rms(x, w["norm_f_g"], shape.eps)


def logits_at(w, shape: Shape, tokens, where, precision: str = "float32"):
    """[n, vocab] float32 logits of ONE sequence (tokens [1, S]) at the
    positions `where` [n]."""
    n_read = jnp.max(where) + 1
    return _mm(hidden(w, shape, tokens, n_read, precision)[where], w["head"],
               precision)


# ---------------------------------------------------------------------------
# required work, from the sizes alone
# ---------------------------------------------------------------------------

def _mla_params(s: Shape) -> int:
    """One layer's latent-attention matrices."""
    H, R, N, P, V = s.heads, s.kv_rank, s.nope, s.rope, s.v_dim
    return s.d * s.q_rank + s.q_rank * H * (N + P) + s.d * (R + P) \
        + R * H * (N + V) + H * V * s.d


def expert_bytes(s: Shape, itemsize: int = 2) -> int:
    """One expert's three matrices."""
    return 3 * s.d * s.ffn * itemsize


def param_count(s: Shape) -> int:
    attn = _mla_params(s) + s.q_rank + s.kv_rank + 2 * s.d
    dense = 3 * s.d * s.dense_ffn
    routed = s.d * s.experts + s.experts \
        + (s.experts + s.shared) * 3 * s.d * s.ffn
    return s.layers * attn + s.dense_layers * dense + s.moe_layers * routed \
        + 2 * s.vocab * s.d + s.d


def matmul_flops_per_decode_token(s: Shape) -> int:
    """Forward operations of one token through every layer's matrices
    and the head, without attention over the context: the latent
    attention's (the absorbed decode's two products cost what the
    up-projection they replace costs), the dense layers' SwiGLU, the
    router and ``top_k`` + shared SwiGLUs of a routed layer."""
    swiglu = 3 * s.d * s.ffn
    return 2 * (s.layers * _mla_params(s)
                + s.dense_layers * 3 * s.d * s.dense_ffn
                + s.moe_layers * (s.d * s.experts
                                  + (s.top_k + s.shared) * swiglu)
                + s.d * s.vocab)


def _pair_flops(s: Shape) -> int:
    """A (query, key) pair of the published form: ``q . k`` over ``N +
    P`` and the weight on ``v`` of ``V``, every head."""
    return 2 * s.heads * (s.nope + s.rope + s.v_dim)


#: bytes of an activation and of a cached value: the program rounds
#: neither (`models/mla_lm.py`); only the weights are bfloat16
ACT_BYTES = 4


def latent_decode_work(s: Shape, keys: int, layers: int = 1) -> dict:
    """The absorbed decode over `keys` cached latents (summed over rows)
    in `layers` layers: each key's ``R + P`` float32 values read ONCE, a
    dot product over them and a weighted sum over its first ``R`` a key
    and query head."""
    return {"flops": layers * keys * 2 * s.heads * (s.latent + s.kv_rank),
            "bytes": layers * keys * s.latent * ACT_BYTES}


def _step_rows(step: dict):
    """(start, tokens) per row of a recorded step."""
    start = np.asarray(step["rows_start"], np.int64)
    n = np.asarray(step["rows_tokens"], np.int64) \
        if step["kind"] == "prefill" else np.ones_like(start)
    return start, n


def _pairs(steps, kind: str) -> int:
    """Causal (query, key) pairs of the recorded steps of a kind."""
    total = 0
    for st in steps:
        if st["kind"] == kind:
            start, n = _step_rows(st)
            total += int((n * start + n * (n + 1) // 2).sum())
    return total


def serve_flops(s: Shape, steps) -> float:
    """Required forward operations of the recorded executor steps: every
    prompt and generated token through the matrices, a prefill's
    attention in the published form, a decode's over the latent, and one
    head product per emitted token."""
    tokens = sum(x["prompt_tokens"] + x["decode_tokens"] for x in steps)
    emitted = sum(x["emitted"] for x in steps)
    head = 2 * s.d * s.vocab
    return ((matmul_flops_per_decode_token(s) - head) * tokens
            + s.layers * _pair_flops(s) * _pairs(steps, "prefill")
            + latent_decode_work(s, _pairs(steps, "decode"),
                                 s.layers)["flops"]
            + head * emitted)


def _prefill_work(s: Shape, queries: int, keys: int, pairs: int) -> dict:
    """Flash forward of `queries` queries over `keys` keys (summed over
    rows) seeing `pairs` (query, key) pairs, every layer: q read and o
    written, every key's K and V read once, at every head's width, in
    float32."""
    width = s.heads * (s.nope + s.rope + s.v_dim) * ACT_BYTES
    return {"flops": s.layers * _pair_flops(s) * pairs,
            "bytes": s.layers * (queries + keys) * width}


def attention_work(s: Shape, batch: int, seq: int) -> dict:
    """The prefill attention of `batch` prompts of `seq` tokens from 0."""
    return _prefill_work(s, batch * seq, batch * seq,
                         batch * seq * (seq + 1) // 2)


def prefill_attention_work(s: Shape, steps) -> dict:
    """`attention_work` of the recorded prefill steps, each row from its
    own cached prefix (whose keys it reads, and does not compute)."""
    queries = keys = 0
    for st in steps:
        if st["kind"] == "prefill":
            start, n = _step_rows(st)
            queries += int(n.sum())
            keys += int((start + n).sum())
    return _prefill_work(s, queries, keys, _pairs(steps, "prefill"))


def expert_work(s: Shape, tokens: int, experts_hit: int) -> dict:
    """The routed experts' work for `tokens` tokens in steps whose layers
    together counted `experts_hit` experts that received a token: three
    products a (token, expert) pair; each counted expert's bfloat16
    matrices read once, each pair's float32 input read and output
    written (the shared expert is a dense product, not the experts'
    kernel)."""
    pairs = tokens * s.top_k
    return {"flops": pairs * 6 * s.d * s.ffn,
            "bytes": experts_hit * expert_bytes(s)
            + pairs * 2 * s.d * ACT_BYTES}


def cache_bytes(s: Shape, context: int) -> int:
    """Bytes of values a row at `context` holds in the cache: every
    layer's float32 latent and rope key a token, nothing else."""
    return s.layers * s.latent * ACT_BYTES * context


WORK_COUNTS = {
    "param_count": param_count,
    "matmul_flops_per_decode_token": matmul_flops_per_decode_token,
    "latent_decode_layer_8000": lambda s: latent_decode_work(s, 8000),
    "flash_prefill_8192": lambda s: attention_work(s, 1, 8192),
    "expert_bytes": expert_bytes,
}
