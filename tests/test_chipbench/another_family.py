"""A family module of ANOTHER architecture, for the files-alone test.

`test_chipbench.py` copies this file to ``chipbench/families/toygated.py``
in a temporary checkout, beside a configuration with that family's own
keys (``hidden_size``, ``num_hidden_layers``, ``num_key_value_heads``,
``intermediate_size`` ...), and runs the `serve` runner, the
`closed_loop_requests` generator and the serve readers on it as they
stand. It is what a later PR's ``chipbench/families/<family>.py`` looks
like, at toy widths: a pre-RMSNorm decoder with rotary positions,
grouped-query attention and a gated MLP, which the program builds as
`models/llama.py`. Everything a family module has is here in one file;
`chipbench/README.md` lists the names.
"""
import math

import jax
import jax.numpy as jnp

from chipbench.reference import seed_key  # noqa: F401  (any seed -> a key)

HIGHEST = jax.lax.Precision.HIGHEST

#: float32 compute as configured; the nearest precision below it
CONTROL = "bfloat16"

REHEARSE_CONFIG = {"hidden_size": 32, "num_hidden_layers": 2,
                   "num_attention_heads": 4, "num_key_value_heads": 2,
                   "intermediate_size": 48, "max_position_embeddings": 64,
                   "vocab_size": 300,
                   "assumed": {"padded_vocab_size": 384}}

PUBLISHED_WIDTHS = ("d", "layers", "heads", "kv_heads", "head_dim", "ffn",
                    "positions", "vocab")


class Shape:
    def __init__(self, config: dict):
        self.d = int(config["hidden_size"])
        self.layers = int(config["num_hidden_layers"])
        self.heads = int(config["num_attention_heads"])
        self.kv_heads = int(config["num_key_value_heads"])
        self.head_dim = self.d // self.heads
        self.ffn = int(config["intermediate_size"])
        self.positions = int(config["max_position_embeddings"])
        self.vocab = int(config["vocab_size"])
        assumed = config.get("assumed", {})
        self.padded_vocab = int(assumed.get("padded_vocab_size", self.vocab))
        self.eps = float(config["rms_norm_eps"])
        self.theta = float(config["rope_theta"])
        self.init_std = float(config.get("initializer_range", 0.02))


# -- weights: the reference's own, and the same values as the program's tree

def _weight_shapes(s: Shape) -> dict:
    L, d, f = s.layers, s.d, s.ffn
    kv = s.kv_heads * s.head_dim
    return {"embed": (s.padded_vocab, d), "norm_f": (d,),
            "lm_head": (d, s.padded_vocab),
            "attn_norm": (L, d), "wq": (L, d, d), "wk": (L, d, kv),
            "wv": (L, d, kv), "wo": (L, d, d), "mlp_norm": (L, d),
            "gate": (L, d, f), "up": (L, d, f), "down": (L, f, d)}


def reference_weights(shape: Shape, key) -> dict:
    out = {}
    for i, (name, shp) in enumerate(sorted(_weight_shapes(shape).items())):
        noise = shape.init_std * jax.random.normal(
            jax.random.fold_in(key, i), shp, jnp.float32)
        out[name] = 1.0 + noise if "norm" in name else noise
    return out


def program_params(shape: Shape, key) -> dict:
    w = reference_weights(shape, key)
    tree = {"embed": {"embedding": w["embed"]},
            "norm_f": {"scale": w["norm_f"]},
            "lm_head": {"kernel": w["lm_head"]}}
    for i in range(shape.layers):
        tree[f"layers_{i}"] = {
            "attn_norm": {"scale": w["attn_norm"][i]},
            "attn": {n: {"kernel": w[n][i]} for n in ("wq", "wk", "wv",
                                                      "wo")},
            "mlp_norm": {"scale": w["mlp_norm"][i]},
            "mlp": {n: {"kernel": w[n][i]} for n in ("gate", "up", "down")}}
    return tree


def serve_model(shape: Shape, config: dict, *, kv_block: int,
                kv_pool_blocks: int, decode_kernel):
    from horovod_tpu.models.llama import Llama, LlamaConfig
    assumed = config.get("assumed", {})
    return Llama(LlamaConfig(
        vocab_size=shape.padded_vocab, num_layers=shape.layers,
        num_heads=shape.heads, num_kv_heads=shape.kv_heads,
        head_dim=shape.head_dim, mlp_dim=shape.ffn,
        max_seq_len=shape.positions, rope_theta=shape.theta,
        dtype=jnp.dtype(assumed.get("compute_dtype", "float32")),
        decode=True, kv_block_size=kv_block, kv_pool_blocks=kv_pool_blocks,
        decode_kernel=decode_kernel))


# -- the plain reference: float32 jax.numpy, nothing of the program

def _mm(a, b, precision: str):
    if precision == "bfloat16":
        a, b = (x.astype(jnp.bfloat16).astype(jnp.float32) for x in (a, b))
    elif precision != "float32":
        raise ValueError(f"precision {precision!r}")
    return jnp.matmul(a, b, precision=HIGHEST)


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def _rope(x, theta):
    """x [S, heads, D]: adjacent pairs rotated by position x frequency."""
    S, _, D = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D))
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv[None]
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                     axis=-1).reshape(x.shape)


def logits_at(w, shape: Shape, tokens, where, precision: str = "float32"):
    """[n, padded_vocab] logits of ONE sequence (tokens [1, S]) at the
    positions `where` [n]."""
    H, KV, D = shape.heads, shape.kv_heads, shape.head_dim
    x = w["embed"][tokens[0]]
    S = x.shape[0]
    causal = jnp.tril(jnp.ones((S, S), bool))
    for i in range(shape.layers):
        h = _rms(x, w["attn_norm"][i], shape.eps)
        q = _rope(_mm(h, w["wq"][i], precision).reshape(S, H, D),
                  shape.theta)
        k = _rope(_mm(h, w["wk"][i], precision).reshape(S, KV, D),
                  shape.theta)
        v = _mm(h, w["wv"][i], precision).reshape(S, KV, D)
        # query head h reads key-value head h // (H / KV)
        k, v = (jnp.repeat(t, H // KV, axis=1) for t in (k, v))
        scores = jnp.einsum("qhd,khd->hqk", q, k,
                            precision=HIGHEST) / math.sqrt(D)
        probs = jax.nn.softmax(jnp.where(causal, scores, -1e30), axis=-1)
        att = jnp.einsum("hqk,khd->qhd", probs, v, precision=HIGHEST)
        x = x + _mm(att.reshape(S, H * D), w["wo"][i], precision)
        h = _rms(x, w["mlp_norm"][i], shape.eps)
        gated = jax.nn.silu(_mm(h, w["gate"][i], precision)) * _mm(
            h, w["up"][i], precision)
        x = x + _mm(gated, w["down"][i], precision)
    x = _rms(x, w["norm_f"], shape.eps)
    return _mm(x[where], w["lm_head"], precision)


# -- required work, from the sizes alone (a multiply-add is two operations)

def layer_matmul_params(s: Shape) -> int:
    """wq, wo; wk, wv at key-value width; gate, up, down."""
    return (2 * s.d * s.d + 2 * s.d * s.kv_heads * s.head_dim
            + 3 * s.d * s.ffn)


def param_count(s: Shape) -> int:
    return (2 * s.padded_vocab * s.d + s.d
            + s.layers * (layer_matmul_params(s) + 2 * s.d))


def serve_flops(s: Shape, steps) -> float:
    """Forward operations of the recorded executor steps: the block
    matrices for every token, QK^T and PV against every key attended to
    (all query heads), one head product per emitted token."""
    tokens = sum(x["prompt_tokens"] + x["decode_tokens"] for x in steps)
    context = sum(x["prompt_context"] + x["decode_context"] for x in steps)
    emitted = sum(x["emitted"] for x in steps)
    return (2 * layer_matmul_params(s) * s.layers * tokens
            + 4 * s.heads * s.head_dim * s.layers * context
            + 2 * s.d * s.padded_vocab * emitted)


def decode_attention_work(s: Shape, steps) -> dict:
    """All layers' decode attention over the recorded decode steps: K
    and V (float32, as configured) are read once at KEY-VALUE width, the
    products run over all query heads."""
    context = sum(x["decode_context"] for x in steps)
    return {"flops": 4 * context * s.heads * s.head_dim * s.layers,
            "bytes": 2 * context * s.kv_heads * s.head_dim * 4 * s.layers}


def decode_query_pattern(s: Shape, rows: int) -> str:
    return rf"\[{rows},{s.heads},1,{s.head_dim}\]"


WORK_COUNTS = {
    "layer_matmul_params": layer_matmul_params,
    "param_count": param_count,
    "serve_flops_10_prompt_2_decode": lambda s: serve_flops(s, [
        {"prompt_tokens": 10, "prompt_context": 55, "decode_tokens": 0,
         "decode_context": 0, "emitted": 1, "kind": "prefill"},
        {"prompt_tokens": 0, "prompt_context": 0, "decode_tokens": 2,
         "decode_context": 23, "emitted": 2, "kind": "decode"}]),
    "decode_attention_100": lambda s: decode_attention_work(
        s, [{"decode_context": 100}]),
}
