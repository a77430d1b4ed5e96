"""What the harness knows of the program's parameter layout.

The plain reference holds its weights stacked over layers under its own
names; `models/gpt.py` holds a flax tree. These functions carry the same
values across, read per-leaf norms back in the reference's names, and
build the model object a serving executor or a train step gets from a
configuration. The reference itself knows none of this.
"""
from __future__ import annotations

from typing import Dict

from chipbench import reference as ref


def flax_tree(stacked: dict, shape: ref.Shape) -> dict:
    """The reference's stacked weights as `models/gpt.py`'s tree."""
    tree = {"embed": {"embedding": stacked["wte"]},
            "pos_embed": {"embedding": stacked["wpe"]},
            "ln_f": {"scale": stacked["lnf_g"], "bias": stacked["lnf_b"]},
            "lm_head": {"kernel": stacked["head_w"]}}
    for i in range(shape.layers):
        w = {k: stacked[k][i] for k in ref.LAYER_LEAVES}
        tree[f"layers_{i}"] = {
            "ln1": {"scale": w["ln1_g"], "bias": w["ln1_b"]},
            "attn": {"qkv": {"kernel": w["qkv_w"], "bias": w["qkv_b"]},
                     "out": {"kernel": w["proj_w"], "bias": w["proj_b"]}},
            "ln2": {"scale": w["ln2_g"], "bias": w["ln2_b"]},
            "mlp": {"up": {"kernel": w["fc_w"], "bias": w["fc_b"]},
                    "down": {"kernel": w["out_w"], "bias": w["out_b"]}}}
    return tree


def program_params(shape: ref.Shape, key) -> dict:
    """The seed's weights as the program's parameter tree. Traceable:
    call under `jit`, so they are made on the device in one call."""
    return flax_tree(ref.make_weights(shape, key), shape)


def _model(shape: ref.Shape, config: dict, **kw):
    import jax.numpy as jnp
    from horovod_tpu.models.gpt import GPT, GPTConfig
    assumed = config.get("assumed", {})
    return GPT(GPTConfig(
        vocab_size=shape.padded_vocab, num_layers=shape.layers,
        num_heads=shape.heads, head_dim=shape.head_dim,
        max_seq_len=shape.positions,
        dtype=jnp.dtype(assumed.get("compute_dtype", "bfloat16")),
        logits_dtype=jnp.dtype(assumed.get("logits_dtype", "float32")),
        **kw))


def serve_model(shape: ref.Shape, config: dict, *, kv_block: int,
                kv_pool_blocks: int, decode_kernel):
    """The model object a `ShardedExecutor` gets: decode mode over a
    paged KV pool of `kv_pool_blocks` blocks of `kv_block` tokens."""
    return _model(shape, config, decode=True, kv_block_size=kv_block,
                  kv_pool_blocks=kv_pool_blocks, decode_kernel=decode_kernel)


def train_model(shape: ref.Shape, config: dict, *, kernels):
    """The model whose `apply` a train step gets; `kernels` is the
    attention implementation (None: the platform's; ``"interpret"``
    off the TPU)."""
    return _model(shape, config, attention_impl=kernels)


def _qkv_parts(name: str, x):
    """The program's fused qkv leaf as its q, k, v parts (its last axis
    is reshaped [3, heads, head_dim] in models/gpt.py)."""
    parts = x.reshape(x.shape[:-1] + (3, x.shape[-1] // 3))
    return [(f"{name}.{p}", parts[..., j, :])
            for j, p in enumerate(ref.QKV_PARTS)]


def leaf_norms(tree: dict, shape: ref.Shape) -> Dict[str, "jax.Array"]:
    """Per-leaf L2 norms of a program-side tree, keyed like
    `reference.leaf_norms` (``name`` / ``name/i``). Traceable."""
    import jax.numpy as jnp

    def n(x):
        return jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
    out = {"wte": n(tree["embed"]["embedding"]),
           "wpe": n(tree["pos_embed"]["embedding"]),
           "lnf_g": n(tree["ln_f"]["scale"]),
           "lnf_b": n(tree["ln_f"]["bias"]),
           "head_w": n(tree["lm_head"]["kernel"])}
    for i in range(shape.layers):
        t = tree[f"layers_{i}"]
        for key, leaf in (
                ("ln1_g", t["ln1"]["scale"]), ("ln1_b", t["ln1"]["bias"]),
                *_qkv_parts("qkv_w", t["attn"]["qkv"]["kernel"]),
                *_qkv_parts("qkv_b", t["attn"]["qkv"]["bias"]),
                ("proj_w", t["attn"]["out"]["kernel"]),
                ("proj_b", t["attn"]["out"]["bias"]),
                ("ln2_g", t["ln2"]["scale"]), ("ln2_b", t["ln2"]["bias"]),
                ("fc_w", t["mlp"]["up"]["kernel"]),
                ("fc_b", t["mlp"]["up"]["bias"]),
                ("out_w", t["mlp"]["down"]["kernel"]),
                ("out_b", t["mlp"]["down"]["bias"])):
            out[f"{key}/{i}"] = n(leaf)
    return out
