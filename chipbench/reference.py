"""The plain GPT-2 reference: float32 `jax.numpy`, no kernels, no cache.

This file is the yardstick `correct` is decided against, so it imports
nothing from `horovod_tpu` and takes nothing the program has made. The
weights come from `--seed` through :func:`make_weights`; the runners
arrange the same values into the program's parameter tree.

The model is the published GPT-2 block (pre-LN, learned positions,
tanh-approximated GELU, causal softmax attention) with the departures
the configuration files list under ``assumed``: the output head is a
separate matrix (untied), the vocabulary is padded to ``padded_vocab``
rows, and LayerNorm's epsilon is the configuration's. Weights are held
STACKED over layers (``[L, ...]`` leaves) and the layers run under
`lax.scan`, so the reference compiles in seconds at any depth.

`precision` selects how matmul operands are rounded before each product
(the products themselves accumulate in float32 at `highest`):

* ``float32``  no rounding: the reference proper;
* ``bfloat16`` operands through bfloat16: what the program computes in;
* ``fp8``      operands through float8_e4m3fn with a per-tensor scale,
  straight-through gradients: the precision a later PR would be tempted
  by, which the comparison has to refuse (the control).
"""
from __future__ import annotations

import functools
import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
PRECISIONS = ("float32", "bfloat16", "fp8")

#: stacked per-layer leaves -> shape as a function of (d, 4d)
_LAYER_LEAVES = {
    "ln1_g": lambda d, f: (d,), "ln1_b": lambda d, f: (d,),
    "qkv_w": lambda d, f: (d, 3 * d), "qkv_b": lambda d, f: (3 * d,),
    "proj_w": lambda d, f: (d, d), "proj_b": lambda d, f: (d,),
    "ln2_g": lambda d, f: (d,), "ln2_b": lambda d, f: (d,),
    "fc_w": lambda d, f: (d, f), "fc_b": lambda d, f: (f,),
    "out_w": lambda d, f: (f, d), "out_b": lambda d, f: (d,),
}
LAYER_LEAVES = tuple(_LAYER_LEAVES)
TOP_LEAVES = ("wte", "wpe", "lnf_g", "lnf_b", "head_w")


class Shape:
    """The sizes the reference needs, read from a configuration file."""

    def __init__(self, config: dict):
        self.d = int(config["n_embd"])
        self.layers = int(config["n_layer"])
        self.heads = int(config["n_head"])
        self.head_dim = self.d // self.heads
        self.positions = int(config["n_positions"])
        self.ffn = int(config.get("n_inner") or 4 * self.d)
        self.vocab = int(config["vocab_size"])
        assumed = config.get("assumed", {})
        self.padded_vocab = int(assumed.get("padded_vocab_size", self.vocab))
        self.eps = float(config["layer_norm_epsilon"])
        self.init_std = float(config.get("initializer_range", 0.02))


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any non-negative whole number (the driver's seeds
    pass 2**31): the low 32 bits seed the key, the rest are folded in."""
    seed = int(seed)
    key = jax.random.PRNGKey(np.uint32(seed & 0xFFFFFFFF))
    return jax.random.fold_in(key, np.uint32((seed >> 32) & 0xFFFFFFFF))


def weight_shapes(shape: Shape) -> Dict[str, Tuple[int, ...]]:
    d, f, L = shape.d, shape.ffn, shape.layers
    out = {"wte": (shape.padded_vocab, d), "wpe": (shape.positions, d),
           "lnf_g": (d,), "lnf_b": (d,), "head_w": (d, shape.padded_vocab)}
    for name, fn in _LAYER_LEAVES.items():
        out[name] = (L,) + fn(d, f)
    return out


def make_weights(shape: Shape, key: jax.Array) -> Dict[str, jax.Array]:
    """Float32 weights from the seed's key, stacked over layers: normal
    with the configuration's ``initializer_range`` for every matrix,
    embedding AND bias (a zero bias would hide a bias left out), one
    plus that noise for LayerNorm gains. Traceable: call under `jit`."""
    out = {}
    for i, (name, shp) in enumerate(sorted(weight_shapes(shape).items())):
        noise = shape.init_std * jax.random.normal(
            jax.random.fold_in(key, i), shp, jnp.float32)
        out[name] = 1.0 + noise if name.endswith("_g") else noise
    return out


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _round(x, precision: str):
    if precision == "float32":
        return x
    if precision == "bfloat16":
        r = x.astype(jnp.bfloat16).astype(jnp.float32)
    elif precision == "fp8":
        scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
        r = (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale
    else:
        raise ValueError(f"precision must be one of {PRECISIONS}; "
                         f"got {precision!r}")
    # straight-through: the backward pass sees the identity
    return x + jax.lax.stop_gradient(r - x)


def _mm(a, b, precision: str):
    return jnp.matmul(_round(a, precision), _round(b, precision),
                      precision=HIGHEST)


def _layer_norm(x, g, b, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * g + b


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def _block(x, w, shape: Shape, precision: str):
    B, S, d = x.shape
    H, D = shape.heads, shape.head_dim
    h = _layer_norm(x, w["ln1_g"], w["ln1_b"], shape.eps)
    qkv = (_mm(h, w["qkv_w"], precision) + w["qkv_b"]).reshape(B, S, 3, H, D)
    q, k, v = (qkv[:, :, i].transpose(0, 2, 1, 3) for i in range(3))
    scores = jnp.einsum("bhqd,bhkd->bhqk", _round(q, precision),
                        _round(k, precision), precision=HIGHEST)
    scores = scores / math.sqrt(D)
    causal = jnp.tril(jnp.ones((S, S), bool))
    scores = jnp.where(causal, scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    att = jnp.einsum("bhqk,bhkd->bhqd", _round(probs, precision),
                     _round(v, precision), precision=HIGHEST)
    att = att.transpose(0, 2, 1, 3).reshape(B, S, d)
    x = x + _mm(att, w["proj_w"], precision) + w["proj_b"]
    h = _layer_norm(x, w["ln2_g"], w["ln2_b"], shape.eps)
    h = _gelu_tanh(_mm(h, w["fc_w"], precision) + w["fc_b"])
    return x + _mm(h, w["out_w"], precision) + w["out_b"]


def hidden(w, shape: Shape, tokens, precision: str = "float32",
           remat: bool = False):
    """Final-LayerNorm hidden states [B, S, d] for tokens [B, S]."""
    S = tokens.shape[1]
    x = w["wte"][tokens] + w["wpe"][:S][None]
    layers = {k: w[k] for k in LAYER_LEAVES}
    body = functools.partial(_block, shape=shape, precision=precision)
    if remat:
        body = jax.checkpoint(body)
    x, _ = jax.lax.scan(lambda c, lw: (body(c, lw), None), x, layers)
    return _layer_norm(x, w["lnf_g"], w["lnf_b"], shape.eps)


def logits(w, shape: Shape, tokens, precision: str = "float32",
           remat: bool = False):
    """[B, S, padded_vocab] float32 logits."""
    return _mm(hidden(w, shape, tokens, precision, remat), w["head_w"],
               precision)


def logits_at(w, shape: Shape, tokens, where, precision: str = "float32"):
    """[n, padded_vocab] logits of ONE sequence (tokens [1, S]) at the
    positions `where` [n]: what a serving check reads, without the head
    product at the positions nobody asked for."""
    return _mm(hidden(w, shape, tokens, precision)[0][where], w["head_w"],
               precision)


def loss(w, shape: Shape, tokens, labels, precision: str = "float32"):
    """Mean token cross entropy over the padded vocabulary (the padded
    rows are real rows of the model that runs)."""
    lg = logits(w, shape, tokens, precision, remat=True)
    lse = jax.nn.logsumexp(lg, axis=-1)
    picked = jnp.take_along_axis(lg, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - picked)


# ---------------------------------------------------------------------------
# training: gradients in blocks of rows, AdamW written out
# ---------------------------------------------------------------------------

class Trainer:
    """Follows the program's first steps: full-batch gradients summed
    over blocks of `rows_per_block` rows (so float32 activations fit),
    then AdamW as optax composes it (scale_by_adam, decayed weights,
    learning rate)."""

    def __init__(self, shape: Shape, optimizer: dict,
                 precision: str = "float32", rows_per_block: int = 1):
        self.shape = shape
        self.opt = optimizer
        self.rows = rows_per_block

        def block_grads(w, acc, tokens, labels, share):
            l, g = jax.value_and_grad(loss)(w, shape, tokens, labels,
                                            precision)
            acc_l, acc_g = acc
            return (acc_l + share * l,
                    jax.tree.map(lambda a, b: a + share * b, acc_g, g))

        self._block_grads = jax.jit(block_grads, donate_argnums=(1,))
        #: the seed's weights, and a tree's distance from them
        self.weights = jax.jit(lambda key: make_weights(shape, key))
        self.moved = jax.jit(lambda w, key: jax.tree.map(
            lambda a, b: a - b, w, make_weights(shape, key)))
        self._zeros = jax.jit(lambda w: (jnp.zeros((), jnp.float32),
                                         jax.tree.map(jnp.zeros_like, w)))
        self._adamw = jax.jit(self._adamw_impl, donate_argnums=(0, 1, 2))

    def grads(self, w, tokens: np.ndarray, labels: np.ndarray):
        """(mean loss, mean gradient) over all rows of the batch."""
        n = tokens.shape[0]
        if n % self.rows:
            raise ValueError(f"{n} rows do not split into blocks of "
                             f"{self.rows}")
        acc = self._zeros(w)
        for i in range(0, n, self.rows):
            acc = self._block_grads(
                w, acc, jnp.asarray(tokens[i:i + self.rows]),
                jnp.asarray(labels[i:i + self.rows]), self.rows / n)
        return acc

    def _adamw_impl(self, w, m, v, g, t):
        o = self.opt
        b1, b2 = o["b1"], o["b2"]
        m = jax.tree.map(lambda m_, g_: b1 * m_ + (1 - b1) * g_, m, g)
        v = jax.tree.map(lambda v_, g_: b2 * v_ + (1 - b2) * g_ * g_, v, g)
        c1, c2 = 1 - b1 ** t, 1 - b2 ** t

        def upd(p, m_, v_):
            step = (m_ / c1) / (jnp.sqrt(v_ / c2) + o["eps"])
            return p - o["learning_rate"] * (step + o["weight_decay"] * p)
        return jax.tree.map(upd, w, m, v), m, v

    def init(self, w):
        return self._zeros(w)[1], self._zeros(w)[1]

    def step(self, w, m, v, g, t: int):
        return self._adamw(w, m, v, g, jnp.float32(t))


QKV_PARTS = ("q", "k", "v")


def split_qkv(tree: dict) -> dict:
    """The fused qkv matrix and bias as their q, k and v parts (the
    last axis is laid out [3][heads][head_dim]), named ``qkv_w.q`` ...:
    the key's bias has no gradient under softmax, and only as a part of
    its own can the comparison set it aside."""
    out = {k: x for k, x in tree.items() if k not in ("qkv_w", "qkv_b")}
    for name in ("qkv_w", "qkv_b"):
        x = tree[name]
        parts = x.reshape(x.shape[:-1] + (3, x.shape[-1] // 3))
        for j, part in enumerate(QKV_PARTS):
            out[f"{name}.{part}"] = parts[..., j, :]
    return out


@jax.jit
def _norms(tree):
    return {k: (jnp.sqrt(jnp.sum(x * x)) if k in TOP_LEAVES else
                jnp.sqrt(jnp.sum(jnp.square(x),
                                 axis=tuple(range(1, x.ndim)))))
            for k, x in split_qkv(tree).items()}


def leaf_norms(tree: Dict[str, jax.Array]) -> Dict[str, float]:
    """L2 norm of every leaf (qkv split into its parts); stacked leaves
    give one norm per layer. Keys: ``name`` for top leaves, ``name/i``
    for layer i's."""
    out = {}
    for k, val in _norms(tree).items():
        val = np.asarray(val)
        if k not in TOP_LEAVES:
            for i, n in enumerate(val):
                out[f"{k}/{i}"] = float(n)
        else:
            out[k] = float(val)
    return out
