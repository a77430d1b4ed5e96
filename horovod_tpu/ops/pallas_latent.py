"""Paged decode attention over a LATENT pool: multi-head latent attention
(MLA, DeepSeek-V2/V3) read once a step.

A latent-attention layer caches ONE vector a token, ``[c ; k_r]``: the
normed down-projected latent ``c`` (``R`` lanes) and the rotated rope key
``k_r`` that every head shares (``P`` lanes), held in the pool ``[blocks,
block_size, lanes]`` with ``lanes`` the latent padded to whole 128-lane
tiles (``models/mla_lm.py``; the padding is zero). Decode absorbs each
head's key up-projection into its query (``q~_i = [W_UK,i^T q_nope_i ;
q_rope_i]``), so every query head scores against the SAME 576-wide key,
and the attention's output is taken in the latent (``sum_tau p_i c``),
whose first ``R`` lanes are the value: the per-head values are ``W_UV,i``
of it, applied by the model after this call.

`_latent_attention_call` walks each row's live table entries as the
paged walk does (`ops/pallas_paged.py _walk_kernel`): ``C`` whole blocks a
chunk by hand-started DMAs into a two-slot VMEM buffer, the next chunk
(at a row's end, the next row's first) in flight while the current one
folds into a float32 running max, sum and accumulator. Each block is
copied ONCE and used twice, as the key (all lanes) and as the value (its
first ``R`` lanes); the ``T * H`` queries of a row are one ``[T * H,
lanes]`` operand. Scores are scaled by the caller's ``scale`` (MLA's
``1/sqrt(qk_nope + qk_rope)``, not ``1/sqrt(lanes)``); both products
take their operands in the pool's dtype with float32 accumulation (a
bfloat16 pool rounds the probabilities once; a float32 pool is
multiplied at `Precision.HIGHEST`, so nothing is rounded to bfloat16),
and the softmax is float32.

`latent_attention` is the XLA oracle: the same roundings over the
gathered table, the definition the kernel is held to (to a tolerance:
the online softmax sums in another order and rounds unnormalised
probabilities; tests/test_mla_lm.py). A ``-1`` table entry is never
fetched and its keys count for nothing; a row with no key reads zeros.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .pallas_paged import MASK_VALUE

#: keys one update of the walk covers at least: a chunk is this many
#: keys' worth of whole blocks (8 blocks of 64); a latent block is 80 KB
#: (160 KB in float32) and 32 heads share it, so chunks are larger than
#: the paged walk's
CHUNK_KEYS = 512


def chunk_blocks(BS: int, nblk: int) -> int:
    """Blocks one update covers: never more than a table, nor more than
    the 32 bits that say which of a chunk's blocks landed."""
    return max(1, min(nblk, CHUNK_KEYS // BS, 32))


def _precision(dtype):
    """The products' precision over a pool of `dtype`: a float32 pool's
    at HIGHEST (the MXU's default would round both operands to
    bfloat16)."""
    return jax.lax.Precision.HIGHEST if dtype == jnp.float32 else None


def latent_attention(q: jax.Array, pool: jax.Array, block_tables: jax.Array,
                     positions: jax.Array, *, scale: float,
                     value_lanes: int) -> jax.Array:
    """The XLA oracle. q ``[B, T, H, lanes]`` absorbed queries (zero past
    the latent's width), pool ``[num_blocks, block_size, lanes]``,
    block_tables ``[B, blocks_per_seq]`` (-1 unassigned), positions
    ``[B]``: query ``t`` of row ``b`` sees the keys at positions ``<=
    positions[b] + t``. Returns ``[B, T, H, value_lanes]`` in q's dtype:
    the probabilities (in the pool's dtype, as the kernel's) times each
    key's first ``value_lanes`` lanes."""
    B, T, H, lanes = q.shape
    _NB, BS, _ = pool.shape
    nblk = block_tables.shape[1]
    L = nblk * BS
    precision = _precision(pool.dtype)
    keys = pool[jnp.maximum(block_tables, 0)].reshape(B, L, lanes)
    s = jnp.einsum("bthl,bkl->bthk", q.astype(pool.dtype), keys,
                   precision=precision,
                   preferred_element_type=jnp.float32) * np.float32(scale)
    seen = positions[:, None, None, None] + \
        jnp.arange(T)[None, :, None, None]
    s = jnp.where(jnp.arange(L)[None, None, None, :] <= seen, s, MASK_VALUE)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bthk,bkv->bthv", p.astype(pool.dtype),
                   keys[..., :value_lanes], precision=precision,
                   preferred_element_type=jnp.float32)
    return o.astype(q.dtype)


def _latent_kernel(tbl_ref, pos_ref, q_ref, pool, o_ref, buf, sem, slot_ref,
                   m_ref, l_ref, acc_ref, *, H: int, T: int, BS: int, C: int,
                   nblk: int, R: int, scale: float, precision):
    """One row's walk over its live table entries ``0 .. last`` (the
    entry of its newest key), ``C`` blocks a chunk."""
    b = pl.program_id(0)
    TH = q_ref.shape[1]
    N = C * BS

    def last_entry(row):
        return jnp.minimum(pl.cdiv(pos_ref[row] + T, BS), nblk) - 1

    def entry(row, chunk, c):
        """(live, pool block) of block ``c`` of a row's chunk."""
        e = chunk * C + c
        blk = tbl_ref[row, jnp.minimum(e, nblk - 1)]
        return (e <= last_entry(row)) & (blk >= 0), jnp.maximum(blk, 0)

    def copy(blk, slot, c):
        return pltpu.make_async_copy(pool.at[blk], buf.at[slot, c],
                                     sem.at[slot])

    def start(row, chunk, slot):
        def block(c, carry):
            live, blk = entry(row, chunk, c)

            @pl.when(live)
            def _():
                copy(blk, slot, c).start()

            @pl.when(jnp.logical_not(live))
            def _():
                # the block is the value too: stale VMEM bytes (NaN bit
                # patterns included) must not meet a zero probability
                buf[slot, c] = jnp.zeros(buf.shape[2:], buf.dtype)
            return carry
        jax.lax.fori_loop(0, C, block, 0)

    def wait(row, chunk, slot):
        """Wait for a chunk's copies -> a bit a block, set if it landed."""
        def block(c, bits):
            live, blk = entry(row, chunk, c)

            @pl.when(live)
            def _():
                copy(blk, slot, c).wait()
            return bits | (live.astype(jnp.int32) << c)
        return jax.lax.fori_loop(0, C, block, jnp.int32(0))

    @pl.when(b == 0)
    def _():
        slot_ref[0] = 0
        start(0, 0, 0)

    pos = pos_ref[b]
    n = last_entry(b) // C + 1
    s0 = slot_ref[0]
    m_ref[...] = jnp.full(m_ref.shape, -jnp.inf, jnp.float32)
    l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
    acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)
    q = q_ref[0]                                          # [TH, lanes]

    def update(i, slot, landed):
        kidx = jax.lax.broadcasted_iota(jnp.int32, (TH, N), 1)
        r_of = jax.lax.broadcasted_iota(jnp.int32, (TH, N), 0)
        # query row r = t * H + h sees key j iff j <= pos + t, i.e.
        # (j - pos) * H <= r
        rel = kidx + (i * N - pos)
        valid = (rel * H <= r_of) & (((landed >> (kidx // BS)) & 1) == 1)
        k = buf[slot].reshape(N, -1)                      # [N, lanes]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), precision=precision,
            preferred_element_type=jnp.float32) * scale
        s = jnp.where(valid, s, MASK_VALUE)
        m_prev = m_ref[...]                               # [TH, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = alpha * acc_ref[...] + jax.lax.dot_general(
            p.astype(k.dtype), k[:, :R], (((1,), (0,)), ((), ())),
            precision=precision, preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    def chunk_body(i, carry):
        slot = (s0 + i) % 2
        more = i + 1 < n

        # the next chunk of this row, or after its last the next row's
        # first, into the other slot
        @pl.when(more | (b + 1 < pl.num_programs(0)))
        def _():
            start(jnp.where(more, b, b + 1), jnp.where(more, i + 1, 0),
                  1 - slot)

        update(i, slot, wait(b, i, slot))
        return carry

    jax.lax.fori_loop(0, n, chunk_body, 0)
    slot_ref[0] = (s0 + n) % 2
    l = l_ref[...]
    # a row with no key at all (every entry unassigned) reads zeros
    out = jnp.where(l > 0, acc_ref[...] / jnp.where(l > 0, l, 1.0), 0.0)
    o_ref[0] = out.astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("scale", "value_lanes", "interpret"))
def _latent_attention_call(q, pool, block_tables, positions, *, scale: float,
                           value_lanes: int, interpret: bool):
    B, T, H, lanes = q.shape
    _NB, BS, _ = pool.shape
    nblk = block_tables.shape[1]
    C = chunk_blocks(BS, nblk)
    R = value_lanes
    # [B, T, H, lanes] -> [B, T * H, lanes]: row t * H + h
    qf = q.reshape(B, T * H, lanes)
    f32 = jnp.float32
    kern = functools.partial(_latent_kernel, H=H, T=T, BS=BS, C=C,
                             nblk=nblk, R=R, scale=float(scale),
                             precision=_precision(pool.dtype))
    out = pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,            # block tables, positions
            grid=(B,),
            in_specs=[pl.BlockSpec((1, T * H, lanes),
                                   lambda b, tbl, pos: (b, 0, 0)),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((1, T * H, R),
                                   lambda b, tbl, pos: (b, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((2, C, BS, lanes), pool.dtype),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SMEM((1,), jnp.int32),   # slot of the row's first chunk
                pltpu.VMEM((T * H, 1), f32), pltpu.VMEM((T * H, 1), f32),
                pltpu.VMEM((T * H, R), f32)]),
        out_shape=jax.ShapeDtypeStruct((B, T * H, R), q.dtype),
        compiler_params=pltpu.CompilerParams(
            # a row's last chunk starts the next row's first: rows run
            # in order
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(block_tables, positions, qf, pool)
    return out.reshape(B, T, H, R)


def latent_attention_fused(q: jax.Array, pool: jax.Array,
                           block_tables: jax.Array, positions: jax.Array, *,
                           scale: float, value_lanes: int,
                           interpret: Optional[bool] = None) -> jax.Array:
    """`latent_attention` by the Pallas walk (module docstring): q
    ``[B, T, H, lanes]`` in the pool's dtype, pool ``[num_blocks,
    block_size, lanes]`` with ``lanes`` whole 128-lane tiles. ``T`` is 1
    in decode, a verify's ``spec_k + 1`` or a short bucket's length.
    ``interpret=None``: compiled on TPU, interpret mode elsewhere."""
    lanes = pool.shape[-1]
    if q.shape[-1] != lanes or lanes % 128 or value_lanes > lanes:
        raise ValueError(
            f"queries of {q.shape[-1]} lanes and values of {value_lanes} "
            f"against a pool of {lanes} (whole 128-lane tiles)")
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    return _latent_attention_call(
        q.astype(pool.dtype), pool, jnp.asarray(block_tables, jnp.int32),
        jnp.asarray(positions, jnp.int32), scale=float(scale),
        value_lanes=int(value_lanes), interpret=bool(interpret))
