"""Decoder LM with two kinds of token mixer chosen per layer: block-sparse
attention over the paged KV pool, and lightning (linear) attention over
a per-row recurrent state. The serving model of the sparse + linear
hybrid family (MiniCPM-SALA).

What sets it apart from `models/routed_lm.py` (whose `Proj` and float32
residual stream it reuses) and `models/llama.py` (`RMSNorm`,
`apply_rope`):

* **``mixer_types[l]``** names layer ``l``'s mixer. ``"minicpm4"``: GQA
  without position encoding over the block pool; a query whose context
  has passed ``dense_len`` attends only to the blocks it SELECTS
  (`ops/block_sparse.py`: the first, the newest window, the best-scored
  by the block's compressed keys, ``sparse_topk`` in all, one set a KV
  group). ``"lightning-attn"``: RoPE, then the decayed linear recurrence
  of `ops/lightning.py` on a ``[heads, D, D]`` float32 state a row, an
  RMS norm over the concatenated heads. Both norm q and k per head and
  gate their output with ``sigmoid(Wg h)`` before ``Wo``.
* **Three kinds of leaf in the ``cache`` collection**
  (docs/serving.md): the K and V pools through `write_kv_pools`, as
  every served model's; a per-BLOCK leaf of another shape, the
  compressed keys ``[pool_blocks, 4, H_kv, D]`` float32, which follows
  the block tables; and a per-ROW leaf, the lightning state
  ``[state_rows, H, D, D]`` float32, which follows the batch slot
  (``state_slots``: which slot each row of the step stands for; a
  row-compact prefill's rows are not the batch's). A row at position 0
  starts from a zero state; a row the mask leaves out, and the bucket
  padding behind ``logits_idx``, leave it untouched.
* **muP scaling** from the config's own keys: the embedding times
  ``scale_emb``, every branch into the stream times
  ``scale_depth / sqrt(mup_depth)`` (the PUBLISHED depth, whatever part
  of it is run), the head reads the final norm over
  ``embed_dim / dim_model_base``.
* **Dense and sparse rows in one decode step.** A decode step hands the
  paged kernel (or its XLA oracle) one table a (row, KV group): the
  row's own blocks while dense, the selected ones once sparse, with the
  matching length. The kernel reads whole ``[block, H_kv, D]`` pool
  blocks, so a (row, group) pair is a row of its own to it, its query
  heads of the other groups zero. No step reads a sparse row's whole K
  and V. A prefill (more than one token a row) applies the same rule
  per query (`block_sparse.prefill_attention`) and runs the recurrence
  by chunks (`lightning.lightning_chunked`).

Serving only (``decode=True``, paged), driven by `serve.ShardedExecutor`.
``per_row_state`` tells the serving plane that block tables are not all
of a sequence's state: prefix reuse, speculation, the KV tier and
migration refuse such a model by name (serve/batcher.py,
serve/kv_migrate.py, serve/worker.py). Each sparse layer sows the blocks
its live rows attended into the ``stats`` collection
(``blocks_attended``); the executor returns the sum with the step's
tokens.
"""
import math
from typing import Any, Optional, Sequence

import jax
import jax.numpy as jnp
from flax import linen as nn

from ..ops import block_sparse, lightning
from .llama import RMSNorm, apply_rope
from .routed_lm import Proj

SPARSE, LIGHTNING = "minicpm4", "lightning-attn"


class SalaLMConfig:
    #: a sequence of this model holds state that block tables do not
    #: address (the lightning layers'): the serving plane asks
    per_row_state = True
    #: the cache leaves that are no K/V pool, by kind
    #: (serve/executor.py): the compressed keys follow the block
    #: tables, the lightning state the batch slot
    cache_leaves = {"ckeys": "block", "state": "row"}

    def __init__(self, vocab_size=256, embed_dim=64, num_heads=4,
                 num_kv_heads=2, head_dim=16, ffn_dim=128,
                 mixer_types: Sequence[str] = (SPARSE, LIGHTNING),
                 lightning_heads: Optional[int] = None,
                 lightning_head_dim: Optional[int] = None,
                 sparse_stride: int = 1, sparse_init_blocks: int = 1,
                 sparse_window: int = 8, sparse_topk: int = 4,
                 dense_len: int = 32,
                 scale_emb: float = 1.0, scale_depth: float = 1.0,
                 mup_depth: Optional[int] = None,
                 dim_model_base: Optional[int] = None,
                 rope_theta: float = 10000.0, rms_eps: float = 1e-6,
                 max_seq_len=512, dtype=jnp.bfloat16,
                 param_dtype=jnp.bfloat16, logits_dtype=jnp.float32,
                 decode: bool = True, kv_block_size: int = 0,
                 kv_pool_blocks: int = 0, state_rows: int = 0,
                 decode_kernel: Optional[str] = None,
                 prefill_rows: int = 1):
        if decode_kernel not in (None, "pallas", "xla"):
            raise ValueError(
                f"decode_kernel must be None (resolve from "
                f"HOROVOD_SERVE_KERNEL at executor build), 'pallas' or "
                f"'xla'; got {decode_kernel!r}")
        if not decode or not kv_block_size or kv_pool_blocks < 1:
            raise ValueError(
                "SalaLM is a serving model over the paged KV pool: "
                "decode=True, kv_block_size > 0 and kv_pool_blocks >= 1")
        if num_heads % num_kv_heads:
            raise ValueError(
                f"num_heads={num_heads} must be a multiple of "
                f"num_kv_heads={num_kv_heads}")
        unknown = set(mixer_types) - {SPARSE, LIGHTNING}
        if unknown or not mixer_types:
            raise ValueError(
                f"mixer_types holds {SPARSE!r} and {LIGHTNING!r}; got "
                f"{sorted(unknown) or 'nothing'}")
        if sparse_window % kv_block_size:
            raise ValueError(
                f"sparse_window {sparse_window} must be whole blocks of "
                f"{kv_block_size}")
        self.vocab_size = vocab_size
        self.mixer_types = tuple(mixer_types)
        self.num_layers = len(self.mixer_types)
        self.embed_dim = embed_dim
        self.num_heads = num_heads
        self.num_kv_heads = num_kv_heads
        self.head_dim = head_dim
        self.ffn_dim = ffn_dim
        self.lightning_heads = lightning_heads or num_heads
        self.lightning_head_dim = lightning_head_dim or head_dim
        #: the selection block IS the pool block
        self.sparse = block_sparse.SparseSizes(
            block=int(kv_block_size), stride=int(sparse_stride),
            init_blocks=int(sparse_init_blocks),
            window_blocks=int(sparse_window) // int(kv_block_size),
            topk=int(sparse_topk), dense_len=int(dense_len)).validate()
        self.scale_emb = float(scale_emb)
        #: every branch enters the stream times this
        self.branch_scale = float(scale_depth) / math.sqrt(
            mup_depth or self.num_layers)
        self.head_divisor = embed_dim / dim_model_base \
            if dim_model_base else 1.0
        self.rope_theta = rope_theta
        self.rms_eps = rms_eps
        self.max_seq_len = max_seq_len
        self.dtype = dtype
        self.param_dtype = param_dtype
        self.logits_dtype = logits_dtype
        self.decode = decode
        self.kv_block_size = kv_block_size
        self.kv_pool_blocks = kv_pool_blocks
        #: rows of the per-row state (the executor's max_batch; stamped
        #: by the executor where the config names none, as the pool is)
        self.state_rows = int(state_rows)
        self.decode_kernel = decode_kernel
        self.prefill_rows = int(prefill_rows)

    @property
    def block_select_layers(self) -> int:
        """Layers whose decode step attends to selected blocks."""
        return sum(m == SPARSE for m in self.mixer_types)


def _projections(cfg, h, heads, kv_heads, D):
    """q, k, v per head with the q/k norm (one gain over ``D``, shared
    by the heads), and the output gate; all float32."""
    B, T, _ = h.shape
    proj = lambda n, name: Proj(n * D, cfg.dtype, cfg.param_dtype,  # noqa: E731
                                name=name)(h)
    q = RMSNorm(eps=cfg.rms_eps, name="q_norm")(
        proj(heads, "wq").reshape(B, T, heads, D))
    k = RMSNorm(eps=cfg.rms_eps, name="k_norm")(
        proj(kv_heads, "wk").reshape(B, T, kv_heads, D))
    v = proj(kv_heads, "wv").reshape(B, T, kv_heads, D)
    gate = jax.nn.sigmoid(proj(heads, "wg"))
    return q, k, v, gate


class SparseAttention(nn.Module):
    """``minicpm4``: GQA without position encoding; dense up to
    ``dense_len``, then over the selected blocks."""
    cfg: Any

    @nn.compact
    def __call__(self, h, positions, update_mask, block_tables):
        from ..serve import kv_cache as kvc
        cfg, sizes = self.cfg, self.cfg.sparse
        B, T, _ = h.shape
        H, KV, D = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
        q, k, v, gate = _projections(cfg, h, H, KV, D)
        q, k, v = (x.astype(cfg.dtype) for x in (q, k, v))
        pool_k, pool_v = kvc.write_kv_pools(
            self, cfg, k, v, positions, update_mask, block_tables)
        ck = self.variable(
            "cache", "ckeys", jnp.zeros,
            (cfg.kv_pool_blocks, 4, KV, D), jnp.float32)
        ck.value = block_sparse.write_compressed_keys(
            ck.value, pool_k, block_tables, positions, update_mask, T,
            sizes)
        if T > 1:
            o = block_sparse.prefill_attention(
                q, pool_k, pool_v, ck.value, block_tables, positions, sizes)
            attended = jnp.zeros((), jnp.int32)
        else:
            tables, lengths, n_att = block_sparse.attended_tables(
                q[:, 0], ck.value, block_tables, positions, sizes)
            # a (row, KV group) pair is a row of its own to the kernel,
            # which reads whole pool blocks: its queries of the other
            # groups are zero and their outputs dropped
            own = jnp.eye(KV, dtype=q.dtype)
            q2 = (q.reshape(B, 1, 1, KV, H // KV, D)
                  * own[None, :, None, :, None, None]).reshape(
                      B * KV, 1, H, D)
            args = (q2, pool_k, pool_v, tables.reshape(B * KV, -1),
                    jnp.repeat(lengths, KV))
            if cfg.decode_kernel == "pallas":
                from ..ops.pallas_paged import paged_attention_fused
                o2 = paged_attention_fused(*args)
            else:
                o2 = kvc.paged_attention(*args)
            o = jnp.sum(o2.reshape(B, KV, 1, KV, H // KV, D)
                        * own[None, :, None, :, None, None],
                        axis=1).reshape(B, 1, H, D)
            attended = jnp.sum(jnp.where(update_mask, n_att, 0))
        self.sow("stats", "blocks_attended", attended.astype(jnp.int32),
                 reduce_fn=lambda a, b: a + b,
                 init_fn=lambda: jnp.zeros((), jnp.int32))
        o = o.reshape(B, T, H * D).astype(jnp.float32) * gate
        return Proj(cfg.embed_dim, cfg.dtype, cfg.param_dtype, name="wo")(o)


class LightningAttention(nn.Module):
    """``lightning-attn``: RoPE, the decayed linear recurrence on the
    slot's state, an RMS norm over the concatenated heads."""
    cfg: Any

    @nn.compact
    def __call__(self, h, positions, update_mask, state_slots, n_valid):
        cfg = self.cfg
        B, T, _ = h.shape
        H, D = cfg.lightning_heads, cfg.lightning_head_dim
        q, k, v, gate = _projections(cfg, h, H, H, D)
        inv = 1.0 / (cfg.rope_theta ** (
            jnp.arange(0, D, 2, dtype=jnp.float32) / D))
        pos = positions[:, None] + jnp.arange(T)[None, :]
        angles = pos.astype(jnp.float32)[..., None] * inv
        q = apply_rope(q.transpose(0, 2, 1, 3), angles).transpose(0, 2, 1, 3)
        k = apply_rope(k.transpose(0, 2, 1, 3), angles).transpose(0, 2, 1, 3)
        q, k, v = (x.astype(cfg.dtype) for x in (q, k, v))
        if cfg.state_rows < 1:
            raise ValueError(
                "state_rows is not set: name it in the model config, or "
                "build the model's ShardedExecutor first (it sizes the "
                "per-row state for max_batch)")
        st = self.variable("cache", "state", jnp.zeros,
                           (cfg.state_rows, H, D, D), jnp.float32)
        if T > 1 or B != cfg.state_rows:
            # the rows' slots' states out, the scan, and back: a prefill
            # holds a row or a few. A slot's next sequence starts from
            # zero; a row out of the step (n_valid 0) keeps its state
            slots = jnp.where(update_mask, state_slots, cfg.state_rows)
            S = st.value[jnp.minimum(slots, cfg.state_rows - 1)]
            S = jnp.where((positions == 0)[:, None, None, None], 0.0, S)
            o, S = lightning.lightning_chunked(S, q, k, v, n_valid)
            st.value = st.value.at[slots].set(S, mode="drop")
        else:
            # a decode step's rows ARE the slots: updated in place
            args = (st.value, q[:, 0], k[:, 0], v[:, 0], positions,
                    update_mask)
            if cfg.decode_kernel == "pallas":
                o, st.value = lightning.lightning_decode(
                    *args, interpret=jax.default_backend() != "tpu")
            else:
                o, st.value = lightning.lightning_decode_reference(*args)
            o = o[:, None]
        o = RMSNorm(eps=cfg.rms_eps, name="o_norm")(
            o.reshape(B, T, H * D)) * gate
        return Proj(cfg.embed_dim, cfg.dtype, cfg.param_dtype, name="wo")(o)


class SalaBlock(nn.Module):
    cfg: Any
    mixer: str

    @nn.compact
    def __call__(self, x, positions, update_mask, block_tables,
                 state_slots, n_valid):
        cfg = self.cfg
        h = RMSNorm(eps=cfg.rms_eps, name="attn_norm")(x)      # float32
        if self.mixer == SPARSE:
            a = SparseAttention(cfg, name="attn")(
                h, positions, update_mask, block_tables)
        else:
            a = LightningAttention(cfg, name="attn")(
                h, positions, update_mask, state_slots, n_valid)
        x = x + cfg.branch_scale * a
        u = RMSNorm(eps=cfg.rms_eps, name="mlp_norm")(x)
        dense = lambda name: nn.Dense(                          # noqa: E731
            cfg.ffn_dim, use_bias=False, dtype=cfg.dtype,
            param_dtype=cfg.param_dtype, name=name)(u)
        y = Proj(cfg.embed_dim, cfg.dtype, cfg.param_dtype, name="w_down")(
            jax.nn.silu(dense("w_gate")) * dense("w_up"))
        return x + cfg.branch_scale * y


class SalaLM(nn.Module):
    cfg: Any

    @nn.compact
    def __call__(self, tokens, positions=None, update_mask=None,
                 block_tables=None, logits_idx=None, state_slots=None):
        cfg = self.cfg
        if positions is None or update_mask is None \
                or block_tables is None:
            raise ValueError(
                "SalaLM needs per-row `positions`, `update_mask` and "
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
        # tokens that enter a state: live rows, up to the emitting
        # position (a prefill's tail is bucket padding)
        n_valid = jnp.full((B,), T, jnp.int32) if logits_idx is None \
            else logits_idx.astype(jnp.int32) + 1
        n_valid = jnp.where(update_mask, n_valid, 0)
        x = nn.Embed(cfg.vocab_size, cfg.embed_dim,
                     param_dtype=cfg.param_dtype, name="embed")(tokens)
        x = x.astype(jnp.float32) * cfg.scale_emb   # the residual stream
        for i, mixer in enumerate(cfg.mixer_types):
            x = SalaBlock(cfg, mixer=mixer, name=f"layers_{i}")(
                x, positions, update_mask, block_tables, state_slots,
                n_valid)
        x = RMSNorm(eps=cfg.rms_eps, name="norm_f")(x)
        if logits_idx is not None:
            # only each row's emitting position reaches the head
            x = jnp.take_along_axis(
                x, logits_idx.astype(jnp.int32)[:, None, None], axis=1)
        return nn.Dense(cfg.vocab_size, use_bias=False,
                        dtype=cfg.logits_dtype,
                        param_dtype=cfg.param_dtype, name="lm_head")(
                            x / cfg.head_divisor)
