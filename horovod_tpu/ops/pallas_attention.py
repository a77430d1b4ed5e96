"""Fused flash attention as Pallas TPU kernels — forward AND backward.

The hot op of the GPT model family (models/gpt.py). The pure-lax reference
implementation (parallel/sp.py attention_reference) materializes the full
[Sq, Skv] score matrix in HBM; these kernels stream K/V blocks through VMEM
with the online-softmax recurrence, so HBM traffic is O(S*D) instead of
O(S^2) and the matmuls hit the MXU at block size.

Training support: `flash_attention` carries a custom VJP. The forward
kernel additionally emits the per-row log-sum-exp; the backward pass is
the standard recompute scheme as two Pallas kernels — one gridded over
query blocks producing dQ, one over key blocks producing dK/dV — so the
backward also never materializes [Sq, Skv] (classic FlashAttention-2
structure; all accumulation in fp32).

Design (pallas_guide.md patterns):
* grid = (batch, heads, S/block); each program owns one row block.
* K/V (resp. Q/dO) for the (batch, head) live in VMEM whole; the inner
  fori_loop walks them in blocks, trip count trimmed for causal.
* GQA: K/V may carry fewer heads; the K/V block index maps read kv head
  h // G, and the dK/dV kernel's innermost grid axis walks the group,
  accumulating into the same (f32) output block — grouped K/V are never
  expanded in HBM, forward or backward.
* padding to block multiples is masked by real-position bounds inside the
  kernels (both padded keys and padded queries).
* On non-TPU platforms the same kernels run in interpret mode (tests), or
  fall back to the dense reference via `fused_attention(..., force=...)`.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.sharding import PartitionSpec as P

NEG_INF = -1e30


def _sds(ref_array, shape, dtype):
    """ShapeDtypeStruct carrying the reference array's varying-mesh-axes
    annotation, so the kernels also work inside shard_map (check_vma)."""
    return jax.ShapeDtypeStruct(shape, dtype, vma=jax.typeof(ref_array).vma)


def _pos_mask(qi_base, kb_base, bq, bk, *, causal: bool,
              seq_q: int, seq_q_p: int, seq_k: int, seq_k_p: int,
              window: Optional[int] = None):
    """[bq, bk] validity mask for a (query-block, key-block) tile:
    causal lower-triangle plus real (unpadded) position bounds; with a
    ``window``, also only the newest ``window`` keys of each query
    (``q_pos - window < k_pos``). `qi_base` is the block's first
    query's position among the KEYS (a prefill's queries start past a
    cached prefix)."""
    q_pos = qi_base + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    k_pos = kb_base + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = jnp.full((bq, bk), True)
    if causal:
        mask = q_pos >= k_pos
    if window is not None:
        mask = mask & (q_pos - window < k_pos)
    if seq_k != seq_k_p:
        mask = mask & (k_pos < seq_k)
    if seq_q != seq_q_p:
        mask = mask & (q_pos < seq_q)
    return mask


# ---------------------------------------------------------------------------
# forward kernel
# ---------------------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, *maybe_lse_ref,
                scale: float, causal: bool, block_q: int, block_k: int,
                seq_q: int, seq_q_p: int, seq_k: int, seq_k_p: int,
                window: Optional[int] = None, q_start=None,
                mxu_dtype=jnp.float32, precision=None):
    """`window`, `q_start`, `mxu_dtype` and `precision` (the products')
    are the serving prefill's (:func:`flash_prefill`); left at their
    defaults the kernel is the training forward, operation for
    operation."""
    qi = pl.program_id(2)
    q = q_ref[0, 0].astype(jnp.float32) * scale       # [bq, D]
    bq = q.shape[0]
    q = q.astype(mxu_dtype)
    # position among the keys of this block's first query
    q_base = qi * block_q if q_start is None else q_start + qi * block_q

    num_kb = seq_k_p // block_k
    if causal:
        # last key position this query block can see
        last = q_base + block_q - 1
        nkb = jnp.minimum(num_kb, (last // block_k) + 1)
    else:
        nkb = num_kb
    # first key block that holds a key the block's first query can see:
    # the blocks wholly behind the window are skipped, not masked
    kb0 = 0 if window is None else \
        jnp.maximum(q_base - window + 1, 0) // block_k

    def body(kb, carry):
        o, m, l = carry
        k = k_ref[0, 0, pl.dslice(kb * block_k, block_k), :].astype(
            mxu_dtype)                                # [bk, D]
        v = v_ref[0, 0, pl.dslice(kb * block_k, block_k), :].astype(
            mxu_dtype)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), precision=precision,
            preferred_element_type=jnp.float32)       # [bq, bk]
        mask = _pos_mask(q_base, kb * block_k, bq, block_k,
                         causal=causal, seq_q=seq_q, seq_q_p=seq_q_p,
                         seq_k=seq_k, seq_k_p=seq_k_p, window=window)
        s = jnp.where(mask, s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        safe_m = jnp.where(m_new <= NEG_INF / 2, 0.0, m_new)
        p = jnp.exp(s - safe_m[:, None])
        p = jnp.where(s <= NEG_INF / 2, 0.0, p)
        corr = jnp.exp(jnp.minimum(m - safe_m, 0.0))
        corr = jnp.where(m <= NEG_INF / 2, 0.0, corr)
        l_new = l * corr + p.sum(axis=-1)
        o_new = o * corr[:, None] + jax.lax.dot_general(
            p.astype(mxu_dtype), v, (((1,), (0,)), ((), ())),
            precision=precision, preferred_element_type=jnp.float32)
        return o_new, m_new, l_new

    # the values' width (a serving prefill's may be narrower than q's)
    o0 = jnp.zeros((bq, v_ref.shape[-1]), jnp.float32)
    m0 = jnp.full((bq,), NEG_INF, jnp.float32)
    l0 = jnp.zeros((bq,), jnp.float32)
    o, m, l = jax.lax.fori_loop(kb0, nkb, body, (o0, m0, l0))
    o = o / jnp.maximum(l, 1e-20)[:, None]
    o_ref[0, 0] = o.astype(o_ref.dtype)
    if maybe_lse_ref:   # training: emit per-row log-sum-exp for the VJP
        safe_m = jnp.where(m <= NEG_INF / 2, 0.0, m)
        # [bq, 1] column: TPU pallas requires the last two block dims to
        # obey the (8, 128) tiling rule, which [1, block_q] violates
        maybe_lse_ref[0][0, 0] = \
            (safe_m + jnp.log(jnp.maximum(l, 1e-20)))[:, None]


def _fwd_impl(q, k, v, causal, scale, block_q, block_k,
              seq_q, seq_k, interpret, emit_lse=True):
    B, H, Sq_p, D = q.shape
    KV, Skv_p = k.shape[1], k.shape[2]
    G = H // KV  # GQA: q head h reads kv head h // G
    kernel = functools.partial(
        _fwd_kernel, scale=scale, causal=causal,
        block_q=block_q, block_k=block_k,
        seq_q=seq_q, seq_q_p=Sq_p, seq_k=seq_k, seq_k_p=Skv_p)
    out_specs = [pl.BlockSpec((1, 1, block_q, D),
                              lambda b, h, qi: (b, h, qi, 0))]
    out_shape = [_sds(q, (B, H, Sq_p, D), q.dtype)]
    if emit_lse:
        out_specs.append(
            pl.BlockSpec((1, 1, block_q, 1), lambda b, h, qi: (b, h, qi, 0)))
        out_shape.append(_sds(q, (B, H, Sq_p, 1), jnp.float32))
    out = pl.pallas_call(
        kernel,
        grid=(B, H, Sq_p // block_q),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D), lambda b, h, qi: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, Skv_p, D),
                         lambda b, h, qi: (b, h // G, 0, 0)),
            pl.BlockSpec((1, 1, Skv_p, D),
                         lambda b, h, qi: (b, h // G, 0, 0)),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
    )(q, k, v)
    return out if emit_lse else (out[0], None)


def _prefill_kernel(start_ref, q_ref, k_ref, v_ref, o_ref, **kw):
    """The forward kernel with each row's first query position read
    from the scalar-prefetched ``start_ref``."""
    _fwd_kernel(q_ref, k_ref, v_ref, o_ref,
                q_start=start_ref[pl.program_id(0)], **kw)


@functools.partial(jax.jit, static_argnames=(
    "window", "block_q", "block_k", "out_dtype", "interpret", "scale"))
def flash_prefill(q: jax.Array, k: jax.Array, v: jax.Array,
                  q_start: jax.Array, *, window: Optional[int] = None,
                  block_q: int = 512, block_k: int = 512,
                  out_dtype=None, interpret: bool = False,
                  scale: Optional[float] = None) -> jax.Array:
    """Causal attention of a serving prefill: row ``b``'s ``Sq`` queries
    ``[B, H, Sq, D]`` sit at key positions ``q_start[b] + i`` of its
    ``[B, H_kv, Skv, D]`` keys (a cached prefix first, then the fresh
    tokens), query ``i`` sees keys ``<= q_start[b] + i`` and, with a
    ``window``, only the newest ``window`` of them; key blocks wholly
    behind the window or past the diagonal are skipped. Forward only,
    operands to the MXU in the inputs' dtype with float32 accumulation
    and softmax (float32 inputs multiplied at `Precision.HIGHEST`, so
    nothing is rounded to bfloat16); the output ``[B, H, Sq, Dv]`` in
    ``out_dtype`` (the
    queries' where none is named: differential attention subtracts two
    outputs and wants them unrounded). The values ``[B, H_kv, Skv, Dv]``
    may be narrower than the keys (latent attention's 128 beside keys of
    192); ``scale`` multiplies the scores (``1/sqrt(D)`` where None).
    Keys past a row's real length must be finite (they are masked, not
    skipped)."""
    from jax.experimental.pallas import tpu as pltpu
    qq, kk, vv, scale, block_q, block_k, Sq, Skv, pad_q = _prepare(
        q, k, v, scale, block_q, block_k)
    B, H, Sq_p, D = qq.shape
    KV, Skv_p = kk.shape[1], kk.shape[2]
    Dv = vv.shape[3]
    G = H // KV
    kernel = functools.partial(
        _prefill_kernel, scale=scale, causal=True, block_q=block_q,
        # padded query rows are cut off below, not masked: a query's
        # position is not its index here
        block_k=block_k, seq_q=Sq_p, seq_q_p=Sq_p, seq_k=Skv,
        seq_k_p=Skv_p, window=window, mxu_dtype=q.dtype,
        precision=jax.lax.Precision.HIGHEST if q.dtype == jnp.float32
        else None)
    # K and V of one kv head stay whole in VMEM (double-buffered), as in
    # the training forward; at a 12,800-key context that is past the
    # compiler's default scoped limit
    tile, vtile = (-(-n // 128) * 128 for n in (D, Dv))
    need = 2 * Skv_p * (tile + vtile) * k.dtype.itemsize \
        + 6 * block_q * block_k * 4 + 4 * block_q * (tile + vtile) * 4
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, H, Sq_p // block_q),
            in_specs=[
                pl.BlockSpec((1, 1, block_q, D),
                             lambda b, h, qi, st: (b, h, qi, 0)),
                pl.BlockSpec((1, 1, Skv_p, D),
                             lambda b, h, qi, st: (b, h // G, 0, 0)),
                pl.BlockSpec((1, 1, Skv_p, Dv),
                             lambda b, h, qi, st: (b, h // G, 0, 0)),
            ],
            out_specs=pl.BlockSpec((1, 1, block_q, Dv),
                                   lambda b, h, qi, st: (b, h, qi, 0))),
        out_shape=_sds(q, (B, H, Sq_p, Dv), out_dtype or q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel"),
            vmem_limit_bytes=max(16 << 20, need + need // 4)),
        interpret=interpret,
    )(jnp.asarray(q_start, jnp.int32), qq, kk, vv)
    return out[:, :, :Sq] if pad_q else out


# ---------------------------------------------------------------------------
# backward kernels (FlashAttention-2 recompute scheme)
# ---------------------------------------------------------------------------

def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
                   *, scale: float, causal: bool, block_q: int,
                   block_k: int, seq_q: int, seq_q_p: int, seq_k: int,
                   seq_k_p: int):
    qi = pl.program_id(2)
    q = q_ref[0, 0].astype(jnp.float32) * scale       # [bq, D]
    do = do_ref[0, 0].astype(jnp.float32)             # [bq, D]
    lse = lse_ref[0, 0]                               # [bq, 1]
    delta = delta_ref[0, 0]                           # [bq, 1]
    bq, d = q.shape

    num_kb = seq_k_p // block_k
    if causal:
        last = (qi + 1) * block_q - 1
        nkb = jnp.minimum(num_kb, (last // block_k) + 1)
    else:
        nkb = num_kb

    def body(kb, dq):
        k = k_ref[0, 0, pl.dslice(kb * block_k, block_k), :].astype(
            jnp.float32)
        v = v_ref[0, 0, pl.dslice(kb * block_k, block_k), :].astype(
            jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)       # [bq, bk]
        mask = _pos_mask(qi * block_q, kb * block_k, bq, block_k,
                         causal=causal, seq_q=seq_q, seq_q_p=seq_q_p,
                         seq_k=seq_k, seq_k_p=seq_k_p)
        p = jnp.where(mask, jnp.exp(s - lse), 0.0)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)       # [bq, bk]
        ds = p * (dp - delta)
        return dq + jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    dq = jax.lax.fori_loop(0, nkb, body, jnp.zeros((bq, d), jnp.float32))
    dq_ref[0, 0] = (dq * scale).astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, *, scale: float, causal: bool,
                    block_q: int, block_k: int, seq_q: int, seq_q_p: int,
                    seq_k: int, seq_k_p: int):
    # grid (B, KV, kb, g): g (innermost) walks the GQA group sharing this
    # kv head; the dk/dv output block index ignores g, so Pallas keeps it
    # in VMEM across the consecutive g steps and we accumulate into it.
    kb = pl.program_id(2)
    g = pl.program_id(3)
    k = k_ref[0, 0].astype(jnp.float32)               # [bk, D]
    v = v_ref[0, 0].astype(jnp.float32)               # [bk, D]
    bk, d = k.shape

    num_qb = seq_q_p // block_q
    if causal:
        # first query block that can see this key block
        qb0 = (kb * block_k) // block_q
    else:
        qb0 = 0

    def body(qi, carry):
        dk, dv = carry
        q = q_ref[0, 0, pl.dslice(qi * block_q, block_q), :].astype(
            jnp.float32) * scale                      # [bq, D]
        do = do_ref[0, 0, pl.dslice(qi * block_q, block_q), :].astype(
            jnp.float32)
        lse = lse_ref[0, 0, pl.dslice(qi * block_q, block_q), :]
        delta = delta_ref[0, 0, pl.dslice(qi * block_q, block_q), :]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)       # [bq, bk]
        mask = _pos_mask(qi * block_q, kb * block_k, block_q, bk,
                         causal=causal, seq_q=seq_q, seq_q_p=seq_q_p,
                         seq_k=seq_k, seq_k_p=seq_k_p)
        p = jnp.where(mask, jnp.exp(s - lse), 0.0)            # [bq, bk]
        dv_new = dv + jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)       # [bk, D]
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)       # [bq, bk]
        ds = p * (dp - delta)
        dk_new = dk + jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)       # [bk, D]
        return dk_new, dv_new

    dk0 = jnp.zeros((bk, d), jnp.float32)
    dv0 = jnp.zeros((bk, d), jnp.float32)
    dk, dv = jax.lax.fori_loop(qb0, num_qb, body, (dk0, dv0))

    # q was pre-scaled, so dk already carries one factor of `scale`
    @pl.when(g == 0)
    def _init():
        dk_ref[0, 0] = dk.astype(dk_ref.dtype)
        dv_ref[0, 0] = dv.astype(dv_ref.dtype)

    @pl.when(g != 0)
    def _accum():
        dk_ref[0, 0] += dk.astype(dk_ref.dtype)
        dv_ref[0, 0] += dv.astype(dv_ref.dtype)


# ---------------------------------------------------------------------------
# custom-VJP wrapper (operates on padded [B, H, S_p, D] / [B, KV, S_p, D])
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8, 9))
def _flash(q, k, v, causal, scale, block_q, block_k, seq_q, seq_k,
           interpret):
    # primal (inference) path: skip the LSE output entirely
    o, _ = _fwd_impl(q, k, v, causal, scale, block_q, block_k,
                     seq_q, seq_k, interpret, emit_lse=False)
    return o


def _flash_fwd(q, k, v, causal, scale, block_q, block_k, seq_q, seq_k,
               interpret):
    o, lse = _fwd_impl(q, k, v, causal, scale, block_q, block_k,
                       seq_q, seq_k, interpret)
    return o, (q, k, v, o, lse)


def _flash_bwd(causal, scale, block_q, block_k, seq_q, seq_k, interpret,
               res, do):
    return _flash_bwd_delta(causal, scale, block_q, block_k, seq_q,
                            seq_k, interpret, res, do, None)


_flash.defvjp(_flash_fwd, _flash_bwd)


# ---------------------------------------------------------------------------
# public entry points
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=(
    "causal", "scale", "block_q", "block_k", "interpret"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, scale: Optional[float] = None,
                    block_q: int = 512, block_k: int = 512,
                    interpret: bool = False) -> jax.Array:
    """[B, H, Sq, D] x [B, H_kv, Skv, D] -> [B, H, Sq, D] fused attention.

    GQA-aware: k/v may carry H_kv < H heads (H divisible by H_kv); q head
    h reads kv head h // (H // H_kv) directly via the kernels' block index
    maps, so grouped K/V are never expanded in HBM — forward reads and
    the dK/dV gradients stay at kv width (the backward accumulates the
    group's contributions inside the kernel).
    Differentiable (custom VJP with Pallas backward kernels)."""
    qq, kk, vv, scale_, block_q, block_k, Sq, Skv, pad_q = _prepare(
        q, k, v, scale, block_q, block_k)
    out = _flash(qq, kk, vv, causal, scale_, block_q, block_k,
                 Sq, Skv, interpret)
    return out[:, :, :Sq] if pad_q else out


def _prepare(q, k, v, scale, block_q, block_k):
    """Shared entry prologue: GQA validation, scale default, block
    clamping, and padding sequences to block multiples (padded
    positions are masked by real-position bounds inside the kernels)."""
    B, H, Sq, D = q.shape
    KV, Skv = k.shape[1], k.shape[2]
    if H % KV:
        raise ValueError(f"q heads {H} must be a multiple of kv heads {KV}")
    scale_ = float(scale) if scale is not None else 1.0 / (D ** 0.5)
    # clamp to the sequence, then round UP to a sublane multiple (8) so
    # odd lengths (e.g. S=50 -> block 56) still satisfy TPU (8,128)
    # tiling — the sequence pads up to the block and the kernels mask
    # padded rows by real-position bounds
    block_q = -(-min(block_q, Sq) // 8) * 8
    block_k = -(-min(block_k, Skv) // 8) * 8
    pad_q = (-Sq) % block_q
    pad_k = (-Skv) % block_k
    qq = jnp.pad(q, ((0, 0), (0, 0), (0, pad_q), (0, 0))) if pad_q else q
    kk = jnp.pad(k, ((0, 0), (0, 0), (0, pad_k), (0, 0))) if pad_k else k
    vv = jnp.pad(v, ((0, 0), (0, 0), (0, pad_k), (0, 0))) if pad_k else v
    return qq, kk, vv, scale_, block_q, block_k, Sq, Skv, pad_q


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8, 9))
def _flash_lse(q, k, v, causal, scale, block_q, block_k, seq_q, seq_k,
               interpret):
    return _fwd_impl(q, k, v, causal, scale, block_q, block_k,
                     seq_q, seq_k, interpret, emit_lse=True)


def _flash_lse_fwd(q, k, v, causal, scale, block_q, block_k, seq_q, seq_k,
                   interpret):
    o, lse = _fwd_impl(q, k, v, causal, scale, block_q, block_k,
                       seq_q, seq_k, interpret, emit_lse=True)
    return (o, lse), (q, k, v, o, lse)


def _flash_lse_bwd(causal, scale, block_q, block_k, seq_q, seq_k,
                   interpret, res, cts):
    """VJP with a live LSE cotangent.

    With L = f(o, lse): ds = p * (dp - delta) from the o path plus
    p * dlse from the lse path (d lse / d s_qk = p_qk), i.e.
    ds = p * (dp - (delta - dlse)) — so the existing dq/dkv kernels are
    reused verbatim with delta' = delta - dlse. dv = p^T do is
    unaffected by lse."""
    do, dlse = cts
    return _flash_bwd_delta(causal, scale, block_q, block_k, seq_q,
                            seq_k, interpret, res, do, dlse)


def _flash_bwd_delta(causal, scale, block_q, block_k, seq_q, seq_k,
                     interpret, res, do, dlse):
    q, k, v, o, lse = res
    B, H, Sq_p, D = q.shape
    KV, Skv_p = k.shape[1], k.shape[2]
    G = H // KV
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1, keepdims=True)
    if dlse is not None:
        delta = delta - dlse.astype(jnp.float32)

    common = dict(scale=scale, causal=causal, block_q=block_q,
                  block_k=block_k, seq_q=seq_q, seq_q_p=Sq_p,
                  seq_k=seq_k, seq_k_p=Skv_p)
    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, **common),
        grid=(B, H, Sq_p // block_q),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D), lambda b, h, qi: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, Skv_p, D),
                         lambda b, h, qi: (b, h // G, 0, 0)),
            pl.BlockSpec((1, 1, Skv_p, D),
                         lambda b, h, qi: (b, h // G, 0, 0)),
            pl.BlockSpec((1, 1, block_q, D), lambda b, h, qi: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, block_q, 1), lambda b, h, qi: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, block_q, 1), lambda b, h, qi: (b, h, qi, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, D),
                               lambda b, h, qi: (b, h, qi, 0)),
        out_shape=_sds(q, (B, H, Sq_p, D), q.dtype),
        interpret=interpret,
    )(q, k, v, do, lse, delta)

    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, **common),
        grid=(B, KV, Skv_p // block_k, G),
        in_specs=[
            pl.BlockSpec((1, 1, Sq_p, D),
                         lambda b, kv, kb, g: (b, kv * G + g, 0, 0)),
            pl.BlockSpec((1, 1, block_k, D),
                         lambda b, kv, kb, g: (b, kv, kb, 0)),
            pl.BlockSpec((1, 1, block_k, D),
                         lambda b, kv, kb, g: (b, kv, kb, 0)),
            pl.BlockSpec((1, 1, Sq_p, D),
                         lambda b, kv, kb, g: (b, kv * G + g, 0, 0)),
            pl.BlockSpec((1, 1, Sq_p, 1),
                         lambda b, kv, kb, g: (b, kv * G + g, 0, 0)),
            pl.BlockSpec((1, 1, Sq_p, 1),
                         lambda b, kv, kb, g: (b, kv * G + g, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_k, D),
                         lambda b, kv, kb, g: (b, kv, kb, 0)),
            pl.BlockSpec((1, 1, block_k, D),
                         lambda b, kv, kb, g: (b, kv, kb, 0)),
        ],
        out_shape=[
            _sds(k, (B, KV, Skv_p, D),
                 k.dtype if G == 1 else jnp.float32),
            _sds(v, (B, KV, Skv_p, D),
                 v.dtype if G == 1 else jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v, do, lse, delta)
    return dq, dk.astype(k.dtype), dv.astype(v.dtype)


_flash_lse.defvjp(_flash_lse_fwd, _flash_lse_bwd)


def flash_attention_lse(q: jax.Array, k: jax.Array, v: jax.Array, *,
                        causal: bool = True,
                        scale: Optional[float] = None,
                        block_q: int = 512, block_k: int = 512,
                        interpret: bool = False):
    """Like flash_attention but also returns the per-row log-sum-exp
    [B, H, Sq] — the combination weight for blockwise/ring attention
    (flash-decoding-style merging). Differentiable in BOTH outputs:
    the VJP folds the lse cotangent into the same backward kernels
    (delta' = delta - dlse). GQA-aware like flash_attention."""
    qq, kk, vv, scale_, block_q, block_k, Sq, Skv, pad_q = _prepare(
        q, k, v, scale, block_q, block_k)
    o, lse = _flash_lse(qq, kk, vv, causal, scale_, block_q, block_k,
                        Sq, Skv, interpret)
    if pad_q:
        o, lse = o[:, :, :Sq], lse[:, :, :Sq]
    return o, lse[..., 0]


def fused_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, scale: Optional[float] = None,
                    force: Optional[str] = None, mesh=None,
                    batch_axis: str = "dp",
                    head_axis: str = "tp") -> jax.Array:
    """Dispatch: pallas kernel on TPU, dense reference elsewhere.

    force: "pallas" | "reference" | "interpret" overrides the platform
    check (tests use "interpret" to run the kernel on CPU).

    mesh: the mesh the caller's jit is partitioned over, if any. GSPMD
    cannot split a Mosaic kernel ("Mosaic kernels cannot be
    automatically partitioned. Please wrap the call in a shard_map"),
    so with a mesh the kernel runs per shard — batch over `batch_axis`,
    heads over `head_axis`, whichever of the two the mesh has. The
    reference path is plain XLA ops and stays with the partitioner.
    """
    if q.shape[1] % k.shape[1]:
        raise ValueError(f"q heads {q.shape[1]} must be a multiple of "
                         f"kv heads {k.shape[1]}")
    mode = force
    if mode is None:
        mode = "pallas" if jax.devices()[0].platform == "tpu" \
            else "reference"
    if mode not in ("pallas", "interpret"):
        from ..parallel.sp import attention_reference, expand_kv_heads
        k, v = expand_kv_heads(k, v, q.shape[1] // k.shape[1])
        return attention_reference(q, k, v, causal=causal, scale=scale)
    kernel = functools.partial(flash_attention, causal=causal, scale=scale,
                               interpret=mode == "interpret")
    if mesh is None:
        return kernel(q, k, v)
    axes = mesh.axis_names
    spec = P(batch_axis if batch_axis in axes else None,
             head_axis if head_axis in axes else None, None, None)
    # interpret mode: jax's HLO interpreter cannot propagate vma through
    # pallas calls yet (same rule as parallel/sp.sp_impl_for)
    return jax.shard_map(kernel, mesh=mesh, in_specs=(spec, spec, spec),
                         out_specs=spec,
                         check_vma=mode != "interpret")(q, k, v)
