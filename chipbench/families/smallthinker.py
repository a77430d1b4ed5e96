"""The SmallThinker family (PowerInfer, 2025-07): everything the benchmark
knows of this architecture, in one file.

A decoder in which EVERY layer routes to experts and the layers differ in
their attention: per period of four, one layer attends to the whole
context and carries no position encoding (NoPE), three rotate q and k
(RoPE) and see a sliding window. The router reads the layer's normed
input, before attention. One layer ``l``, input ``x [T, d]``:

    h  = rmsnorm(x, g1)
    r  = h @ Wr                          # [T, E] router logits, float32
    q, k, v = h@Wq, h@Wk, h@Wv           # [T,H,D], [T,KV,D], [T,KV,D]
    if rope_layout[l]:   q, k = rope(q, k, position)
    key j visible to query i:  j <= i                   (window_layout[l] == 0)
                               i - window < j <= i      (window_layout[l] == 1)
    a  = softmax(q k^T / sqrt(D)) v ,  query head n reads kv head n // (H/KV)
    x1 = x + a @ Wo
    u  = rmsnorm(x1, g2)
    S  = the top_k largest of r ;  w = softmax(r[S])
    y  = sum_{e in S} w_e * (relu(u @ Wgate_e) * (u @ Wup_e)) @ Wdown_e
    out = x1 + y

Embedding unscaled; after the last layer ``rmsnorm`` and an untied head.
What the source's config.json leaves open is listed under ``assumed`` in
the configuration file: the router reads the NORMED input, no biases and
no q/k norm, RoPE over INTERLEAVED pairs ``(2i, 2i+1)`` (as
`models/llama.py apply_rope` rotates; with seeded weights the half-split
pairing is the same model under a permutation of Wq's and Wk's columns),
initializer 0.02. The family's description mentions secondary experts;
the 21B config has none, and the config is trusted.

**The plain reference** (`logits_at`) is these equations in `jax.numpy`,
float32 arithmetic at `Precision.HIGHEST`, no kernel, no cache, nothing
of the program imported; the experts are a loop over all of them with a
mask. Two departures, both forced by 16 GB: (1) the weights are held as
the program's bfloat16 VALUES (3.97 B parameters in float32 are 15.9 GB)
and upcast a layer, and an expert, at a time: the arithmetic on them is
float32; (2) attention scores are computed for a block of queries at a
time (12,800^2 x 28 heads x 4 B does not fit).

**Work counts** are what the algorithm requires: 6 active experts a
token, the keys a window layer can see clipped at the window (from each
row's own cached length, ``rows_start``), the experts' weight bytes from
the COUNTED experts that received a token.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.reference import HIGHEST, _round, seed_key  # noqa: F401

#: the nearest precision below the configurations' (bfloat16 compute)
CONTROL = "fp8"

#: `--rehearse` widths (CPU, interpret-mode kernels): one period, prompts
#: on both sides of the window
REHEARSE_CONFIG = {
    "hidden_size": 64, "num_hidden_layers": 4, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "moe_ffn_hidden_size": 32,
    "moe_num_primary_experts": 8, "moe_num_active_primary_experts": 2,
    "sliding_window_size": 8, "max_position_embeddings": 64,
    "vocab_size": 512}

PUBLISHED_WIDTHS = ("d", "heads", "kv_heads", "head_dim", "experts", "top_k",
                    "ffn", "window", "theta", "eps", "vocab", "positions",
                    "published_layers")


class Shape:
    """The sizes, read from a configuration file's own keys."""

    def __init__(self, config: dict):
        self.d = int(config["hidden_size"])
        self.layers = int(config["num_hidden_layers"])
        self.heads = int(config["num_attention_heads"])
        self.kv_heads = int(config["num_key_value_heads"])
        self.head_dim = int(config["head_dim"])
        self.experts = int(config["moe_num_primary_experts"])
        self.top_k = int(config["moe_num_active_primary_experts"])
        self.ffn = int(config["moe_ffn_hidden_size"])
        self.window = int(config["sliding_window_size"])
        self.theta = float(config["rope_theta"])
        self.eps = float(config["rms_norm_eps"])
        self.vocab = int(config["vocab_size"])
        self.padded_vocab = self.vocab
        self.positions = int(config["max_position_embeddings"])
        #: the source's depth: the two layouts are kept whole in the file
        #: and the first `layers` entries of each are the layers run
        self.published_layers = len(config["rope_layout"])
        self.init_std = float(config.get("assumed", {}).get(
            "initializer_range", 0.02))
        # tuples, not numbers: the per-layer kinds
        self.rope_layout = tuple(
            int(r) for r in config["rope_layout"][:self.layers])
        self.window_layout = tuple(
            int(w) for w in config["sliding_window_layout"][:self.layers])
        if len(self.rope_layout) != self.layers or \
                len(self.window_layout) != self.layers:
            raise ValueError("the layouts are shorter than the depth")

    @property
    def window_layers(self) -> int:
        return sum(self.window_layout)

    @property
    def full_layers(self) -> int:
        return self.layers - self.window_layers


# ---------------------------------------------------------------------------
# weights: the reference's own tree, and the same values as the program's
# ---------------------------------------------------------------------------

def _weight_shapes(s: Shape) -> dict:
    L, d, E, f = s.layers, s.d, s.experts, s.ffn
    q, kv = s.heads * s.head_dim, s.kv_heads * s.head_dim
    return {"embed": (s.vocab, d), "norm_f_g": (d,), "head": (d, s.vocab),
            "attn_norm_g": (L, d), "mlp_norm_g": (L, d),
            "wq": (L, d, q), "wk": (L, d, kv), "wv": (L, d, kv),
            "wo": (L, q, d), "router": (L, d, E),
            "w_gate": (L, E, d, f), "w_up": (L, E, d, f),
            "w_down": (L, E, f, d)}


LAYER_LEAVES = ("attn_norm_g", "mlp_norm_g", "wq", "wk", "wv", "wo", "router",
                "w_gate", "w_up", "w_down")


def reference_weights(shape: Shape, key) -> dict:
    """The seed's weights, stacked over layers: normal(0, 0.02) for every
    matrix and the embedding, held as the bfloat16 values the program
    holds; norm gains 1 + that noise, float32 (as the program's are).
    Traceable: call under `jit`. Made a layer at a time, so that no more
    than one leaf of one layer exists in float32."""
    out = {}
    for i, (name, shp) in enumerate(sorted(_weight_shapes(shape).items())):
        k = jax.random.fold_in(key, i)
        if name.endswith("_g"):
            out[name] = 1.0 + shape.init_std * jax.random.normal(
                k, shp, jnp.float32)
        elif name in LAYER_LEAVES:
            out[name] = jnp.stack([
                (shape.init_std * jax.random.normal(
                    jax.random.fold_in(k, l), shp[1:], jnp.float32)
                 ).astype(jnp.bfloat16) for l in range(shp[0])])
        else:
            out[name] = (shape.init_std * jax.random.normal(
                k, shp, jnp.float32)).astype(jnp.bfloat16)
    return out


def program_params(shape: Shape, key) -> dict:
    """The same values as `models/routed_lm.py`'s parameter tree (each
    expert's gate and up projection side by side in ``w_in``)."""
    w = reference_weights(shape, key)
    tree = {"embed": {"embedding": w["embed"]},
            "norm_f": {"scale": w["norm_f_g"]},
            "lm_head": {"kernel": w["head"]}}
    for i in range(shape.layers):
        tree[f"layers_{i}"] = {
            "attn_norm": {"scale": w["attn_norm_g"][i]},
            "mlp_norm": {"scale": w["mlp_norm_g"][i]},
            "router": w["router"][i],
            "attn": {n: {"kernel": w[n][i]}
                     for n in ("wq", "wk", "wv", "wo")},
            "experts": {
                "w_in": jnp.concatenate([w["w_gate"][i], w["w_up"][i]],
                                        axis=-1),
                "w_out": w["w_down"][i]}}
    return tree


def serve_model(shape: Shape, config: dict, *, kv_block: int,
                kv_pool_blocks: int, decode_kernel):
    """The model object a `ShardedExecutor` gets."""
    from horovod_tpu.models.routed_lm import RoutedLM, RoutedLMConfig
    assumed = config.get("assumed", {})
    return RoutedLM(RoutedLMConfig(
        vocab_size=shape.vocab, num_layers=shape.layers, embed_dim=shape.d,
        num_heads=shape.heads, num_kv_heads=shape.kv_heads,
        head_dim=shape.head_dim, num_experts=shape.experts,
        experts_per_token=shape.top_k, expert_dim=shape.ffn,
        rope_layout=shape.rope_layout, window_layout=shape.window_layout,
        window=shape.window, rope_theta=shape.theta, rms_eps=shape.eps,
        max_seq_len=shape.positions,
        dtype=jnp.dtype(assumed.get("compute_dtype", "bfloat16")),
        param_dtype=jnp.dtype(assumed.get("param_dtype", "bfloat16")),
        logits_dtype=jnp.dtype(assumed.get("logits_dtype", "float32")),
        decode=True, kv_block_size=kv_block, kv_pool_blocks=kv_pool_blocks,
        decode_kernel=decode_kernel))


# ---------------------------------------------------------------------------
# the plain reference
# ---------------------------------------------------------------------------

#: queries whose scores are held at once
QUERY_BLOCK = 512


def _mm(a, b, precision: str):
    return jnp.matmul(_round(a, precision),
                      _round(b.astype(jnp.float32), precision),
                      precision=HIGHEST)


def _rmsnorm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * g


def _rope(x, positions, theta):
    """x [S, n, D] rotated over interleaved pairs (2i, 2i+1) by
    ``position * theta^(-2i/D)``."""
    S, n, D = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D))
    ang = positions.astype(jnp.float32)[:, None] * inv          # [S, D/2]
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                     axis=-1).reshape(S, n, D)


def _attention(q, k, v, window, precision: str):
    """q [S, H, D], k/v [S, KV, D] -> [S, H*D]; `window` is a traced
    number of visible keys (S + 1: the whole context)."""
    S, H, D = q.shape
    KV = k.shape[1]
    G = H // KV
    pad = (-S) % QUERY_BLOCK
    qb = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(
        -1, QUERY_BLOCK, KV, G, D)
    kr, vr = _round(k, precision), _round(v, precision)
    key_pos = jnp.arange(S)

    def block(args):
        qi, start = args
        s = jnp.einsum("qkgd,skd->kgqs", _round(qi, precision), kr,
                       precision=HIGHEST) / math.sqrt(D)
        q_pos = start + jnp.arange(QUERY_BLOCK)
        ok = (key_pos[None, :] <= q_pos[:, None]) & \
            (key_pos[None, :] > q_pos[:, None] - window)
        p = jax.nn.softmax(jnp.where(ok[None, None], s, -1e30), axis=-1)
        return jnp.einsum("kgqs,skd->qkgd", _round(p, precision), vr,
                          precision=HIGHEST)

    starts = jnp.arange(qb.shape[0]) * QUERY_BLOCK
    out = jax.lax.map(block, (qb, starts))
    return out.reshape(-1, H * D)[:S]


def _experts(u, r, lw, shape: Shape, precision: str):
    """Every expert over every token, kept where the token chose it."""
    top, chosen = jax.lax.top_k(r, shape.top_k)
    w = jax.nn.softmax(top, axis=-1)
    gate = jnp.zeros_like(r).at[
        jnp.arange(r.shape[0])[:, None], chosen].set(w)         # [S, E]

    def one(y, e):
        wg, wu, wd, g = e
        hid = jax.nn.relu(_mm(u, wg, precision)) * _mm(u, wu, precision)
        return y + g[:, None] * _mm(hid, wd, precision), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(u),
                        (lw["w_gate"], lw["w_up"], lw["w_down"], gate.T))
    return y


def _layer(x, lw, rope, windowed, positions, shape: Shape, precision: str):
    S = x.shape[0]
    H, KV, D = shape.heads, shape.kv_heads, shape.head_dim
    h = _rmsnorm(x, lw["attn_norm_g"], shape.eps)
    r = _mm(h, lw["router"], precision)          # BEFORE attention
    q = _mm(h, lw["wq"], precision).reshape(S, H, D)
    k = _mm(h, lw["wk"], precision).reshape(S, KV, D)
    v = _mm(h, lw["wv"], precision).reshape(S, KV, D)
    q = jnp.where(rope, _rope(q, positions, shape.theta), q)
    k = jnp.where(rope, _rope(k, positions, shape.theta), k)
    window = jnp.where(windowed, shape.window, S + 1)
    x = x + _mm(_attention(q, k, v, window, precision), lw["wo"], precision)
    u = _rmsnorm(x, lw["mlp_norm_g"], shape.eps)
    return x + _experts(u, r, lw, shape, precision)


def hidden(w, shape: Shape, tokens, precision: str = "float32"):
    """Final-norm hidden states [S, d] of ONE sequence, tokens [1, S]."""
    x = w["embed"][tokens[0]].astype(jnp.float32)
    positions = jnp.arange(x.shape[0])
    layers = ({k: w[k] for k in LAYER_LEAVES},
              jnp.asarray(shape.rope_layout, bool),
              jnp.asarray(shape.window_layout, bool))

    def body(x, layer):
        lw, rope, windowed = layer
        return _layer(x, lw, rope, windowed, positions, shape,
                      precision), None

    x, _ = jax.lax.scan(body, x, layers)
    return _rmsnorm(x, w["norm_f_g"], shape.eps)


def logits_at(w, shape: Shape, tokens, where, precision: str = "float32"):
    """[n, vocab] float32 logits of ONE sequence (tokens [1, S]) at the
    positions `where` [n]."""
    return _mm(hidden(w, shape, tokens, precision)[where], w["head"],
               precision)


# ---------------------------------------------------------------------------
# required work, from the sizes alone
# ---------------------------------------------------------------------------

def layer_params(s: Shape) -> int:
    """One layer: attention, router, two norm gains, every expert."""
    attn = 2 * s.d * s.heads * s.head_dim + 2 * s.d * s.kv_heads * s.head_dim
    return attn + s.d * s.experts + 2 * s.d + s.experts * 3 * s.d * s.ffn


def param_count(s: Shape) -> int:
    return s.layers * layer_params(s) + 2 * s.vocab * s.d + s.d


def expert_bytes(s: Shape, itemsize: int = 2) -> int:
    """One expert's three matrices."""
    return 3 * s.d * s.ffn * itemsize


def active_flops_per_token(s: Shape) -> int:
    """Forward operations of one token through all layers and the head,
    without its attention over the context: the attention and router
    matrices and `top_k` experts a layer."""
    attn = 2 * s.d * s.heads * s.head_dim + 2 * s.d * s.kv_heads * s.head_dim
    layer = attn + s.d * s.experts + s.top_k * 3 * s.d * s.ffn
    return 2 * layer * s.layers + 2 * s.d * s.vocab


def _visible(s: Shape, start, n):
    """Sums, over `n` consecutive queries from position `start` (arrays,
    one entry a row), of the keys each sees: (whole context, window)."""
    start, n = np.asarray(start, np.int64), np.asarray(n, np.int64)
    full = n * start + n * (n + 1) // 2
    # query i sees min(start + i + 1, window) keys
    under = np.clip(s.window - start, 0, n)     # queries not yet clipped
    windowed = (under * start + under * (under + 1) // 2
                + (n - under) * s.window)
    return int(full.sum()), int(windowed.sum())


def _step_rows(step: dict):
    """(start, tokens) per row of a recorded step."""
    start = np.asarray(step["rows_start"], np.int64)
    n = np.asarray(step["rows_tokens"], np.int64) \
        if step["kind"] == "prefill" else np.ones_like(start)
    return start, n


def _context(s: Shape, steps, kinds) -> int:
    """Keys attended to, summed over the tokens of `steps` of the given
    kinds and over all layers."""
    total = 0
    for st in steps:
        if st["kind"] in kinds:
            full, windowed = _visible(s, *_step_rows(st))
            total += s.full_layers * full + s.window_layers * windowed
    return total


def serve_flops(s: Shape, steps) -> float:
    """Required forward operations of the recorded executor steps: every
    prompt and generated token through the active experts, its attention
    over the keys its layers let it see, and one head product per
    emitted token."""
    tokens = sum(x["prompt_tokens"] + x["decode_tokens"] for x in steps)
    emitted = sum(x["emitted"] for x in steps)
    head = 2 * s.d * s.vocab
    return ((active_flops_per_token(s) - head) * tokens
            + 4 * s.heads * s.head_dim * _context(
                s, steps, ("prefill", "decode", "verify"))
            + head * emitted)


def paged_decode_work(s: Shape, keys: int, itemsize: int = 2) -> dict:
    """One layer's decode attention over `keys` visible cached keys
    (summed over rows): K and V read once at kv width, a dot product and
    a weighted sum per key and query head."""
    return {"flops": 4 * keys * s.heads * s.head_dim,
            "bytes": 2 * keys * s.kv_heads * s.head_dim * itemsize}


def decode_attention_work(s: Shape, steps) -> dict:
    """`paged_decode_work` of every layer over the recorded decode steps,
    a window layer's keys clipped at the window."""
    full = windowed = 0
    for st in steps:
        if st["kind"] == "decode":
            f, w = _visible(s, *_step_rows(st))
            full, windowed = full + f, windowed + w
    return paged_decode_work(
        s, s.full_layers * full + s.window_layers * windowed)


def decode_query_pattern(s: Shape, rows: int) -> str:
    """The decode kernel's query operand in a trace event's text, one
    query per row: ``[rows, kv_heads, group, head_dim]``."""
    return rf"\[{rows},{s.kv_heads},{s.heads // s.kv_heads},{s.head_dim}\]"


def _flash_work(s: Shape, tokens: int, keys: int,
                itemsize: int = 2) -> dict:
    """Forward attention of `tokens` queries that see `keys` keys in
    all, summed over layers: QK^T and PV; q read and o written at query
    width, K and V of the tokens read once at kv width, in every
    layer."""
    qo = 2 * tokens * s.heads * s.head_dim * itemsize
    kv = 2 * tokens * s.kv_heads * s.head_dim * itemsize
    return {"flops": 4 * s.heads * s.head_dim * keys,
            "bytes": s.layers * (qo + kv)}


def attention_work(s: Shape, batch: int, seq: int) -> dict:
    """The prefill attention (forward only: the family is served) of
    `batch` prompts of `seq` tokens, all layers, window layers clipped."""
    full, windowed = _visible(s, [0] * batch, [seq] * batch)
    return _flash_work(s, batch * seq,
                       s.full_layers * full + s.window_layers * windowed)


def prefill_attention_work(s: Shape, steps) -> dict:
    """`attention_work` of the recorded prefill steps, each row from its
    own cached prefix."""
    tokens = sum(x["prompt_tokens"] for x in steps if x["kind"] == "prefill")
    return _flash_work(s, tokens, _context(s, steps, ("prefill",)))


def expert_work(s: Shape, tokens: int, experts_hit: int,
                itemsize: int = 2) -> dict:
    """The experts' work for `tokens` tokens in steps whose layers
    together counted `experts_hit` experts that received a token: three
    products a (token, expert) pair; each counted expert's matrices read
    once, each pair's input read and output written."""
    pairs = tokens * s.top_k
    return {"flops": pairs * 6 * s.d * s.ffn,
            "bytes": experts_hit * expert_bytes(s, itemsize)
            + pairs * 2 * s.d * itemsize}


WORK_COUNTS = {
    "param_count": param_count,
    "active_flops_per_decode_token": active_flops_per_token,
    "paged_full_layer_8000": lambda s: paged_decode_work(s, 8000),
    "paged_window_layer_8000":
        lambda s: paged_decode_work(s, min(8000, s.window)),
    "flash_prefill_8192": lambda s: attention_work(s, 1, 8192),
    "expert_bytes": expert_bytes,
}
