"""Selective state-space scan (Mamba-1): the recurrence a serving step
runs on a fixed-size state whose decay depends on the INPUT.

Per channel ``d`` (of ``d_inner``) and state index ``n`` (of ``N``):

    H_t[n, d] = exp(dt_t[d] * A[n, d]) * H_{t-1}[n, d]
                + (dt_t[d] * c_t[d]) * B_t[n]                 H_{-1} = 0
    y_t[d]    = sum_n H_t[n, d] * C_t[n] + D[d] * c_t[d]

``c_t`` is the causal convolution's output (`causal_conv`), ``dt_t`` the
softplus'd step, ``B_t``/``C_t`` the token's input and output vectors,
``A = -exp(A_log)``. The state lies ``[N, d_inner]``, CHANNELS ON THE
LANES: ``[d_inner, 16]`` would pad 16 to 128 in every tile. What a
sequence keeps between steps is that state, ``[rows, N, d_inner]``
float32, and the convolution's last ``K`` inputs, ``[rows, K, d_inner]``
float32 (the newest last; a step reads the ``K - 1`` newest, and the chip
lays four rows of 5,120 out as the kernel reads them where three cost a
copy in and a copy out), whatever its context.

Entry points, one arithmetic:

* :func:`ssm_decode` — one token a row, ONE Pallas kernel over the rows
  (named ``ssm_decode``: the trace finds it by that name) that aliases
  BOTH states in and out, so a step reads and writes each live row's
  states once and copies nothing. A row at position 0 starts from zero
  whatever the slot held; a masked row's states are handed back
  untouched (the three rules `ops/lightning.py` keeps).
  :func:`ssm_decode_reference` is the same arithmetic in `jax.numpy`,
  the kernel's bit-exactness oracle in interpret mode. The convolution's
  OUTPUT is the caller's (`conv_step`: two projections lie between it
  and the scan); the kernel shifts the token into the state.
* :func:`ssm_prefill` — a bucket: one Pallas kernel (``ssm_prefill``)
  over (row, channel block, chunk of tokens), the state of a channel
  block carried in registers from token to token and in VMEM from chunk
  to chunk. Tokens at and past ``n_valid`` (a bucket's padding behind a
  row's last token) leave the state as it was.
* :func:`ssm_recurrence` — the definition, token by token under
  `lax.scan`; what the tests hold the other two to, and the XLA path.

Every operand is float32.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


# ---------------------------------------------------------------------------
# the causal convolution (XLA: a handful of shifted sums)
# ---------------------------------------------------------------------------

def causal_conv(conv_state: jax.Array, a: jax.Array, w: jax.Array,
                b: jax.Array, n_valid: jax.Array
                ) -> Tuple[jax.Array, jax.Array]:
    """``c_t = silu(b + sum_j w[j] * a_{t-K+1+j})`` over ``a`` [B, T, d]
    with the inputs before the first token in ``conv_state`` [B, K, d]
    (zeros where a row starts a sequence); w [K, d], b [d] ->
    ``(c [B, T, d], new state)``: the last ``K`` inputs up to each row's
    ``n_valid``-th token (0: the state as it came)."""
    K, T = w.shape[0], a.shape[1]
    full = jnp.concatenate([conv_state, a], axis=1)          # [B, K+T, d]
    c = b + sum(w[j] * full[:, j + 1:j + 1 + T] for j in range(K))
    new = jax.vmap(lambda x, n: jax.lax.dynamic_slice_in_dim(
        x, n, K, axis=0))(full, n_valid.astype(jnp.int32))
    return jax.nn.silu(c), new


def conv_step(conv_state: jax.Array, a: jax.Array, w: jax.Array,
              b: jax.Array, positions: jax.Array) -> jax.Array:
    """One decode token's convolution output: ``a`` [B, d] after the
    state's ``K - 1`` newest inputs (zero for a row at position 0) ->
    [B, d]."""
    K = w.shape[0]
    keep = jnp.where(positions == 0, 0.0, 1.0).astype(
        jnp.float32)[:, None]
    c = b + w[K - 1] * a
    for j in range(K - 1):
        c = c + w[j] * (conv_state[:, j + 1] * keep)
    return jax.nn.silu(c)


# ---------------------------------------------------------------------------
# decode: one token a row, both states updated in place
# ---------------------------------------------------------------------------

def _decode_kernel(pos_ref, live_ref, a_ref, c_ref, dt_ref, b_ref, cm_ref,
                   A_ref, D_ref, conv_ref, ssm_ref,
                   y_ref, conv_out_ref, ssm_out_ref):
    """One row: a, c, dt ``[1, d]``, B and C columns ``[N, 1]``, A
    ``[N, d]``, D ``[1, d]``, the conv state ``[K, d]``, the SSM state
    ``[N, d]``."""
    r = pl.program_id(0)

    @pl.when(live_ref[r] != 0)
    def _():
        # a slot's next sequence never sees the last one's state
        keep = jnp.where(pos_ref[r] == 0, 0.0, 1.0).astype(jnp.float32)
        dt, c = dt_ref[0], c_ref[0]
        H = jnp.exp(dt * A_ref[...]) * (ssm_ref[0] * keep) \
            + (dt * c) * b_ref[0]
        ssm_out_ref[0] = H
        y_ref[0] = jnp.sum(H * cm_ref[0], axis=0, keepdims=True) \
            + D_ref[...] * c
        conv_out_ref[0] = jnp.concatenate(
            [conv_ref[0, 1:] * keep, a_ref[0]], axis=0)

    @pl.when(live_ref[r] == 0)
    def _():
        ssm_out_ref[...] = ssm_ref[...]
        conv_out_ref[...] = conv_ref[...]
        y_ref[...] = jnp.zeros(y_ref.shape, y_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def ssm_decode(conv_state: jax.Array, ssm_state: jax.Array, a: jax.Array,
               c: jax.Array, dt: jax.Array, Bm: jax.Array, Cm: jax.Array,
               A: jax.Array, D: jax.Array, positions: jax.Array,
               live: jax.Array, *, interpret: bool = False
               ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """One decode token a row: ``conv_state`` [B, K, d] and
    ``ssm_state`` [B, N, d] float32; the token's conv input ``a``, conv
    output ``c`` and step ``dt`` [B, d]; ``Bm``/``Cm`` [B, N]; ``A``
    [N, d]; ``D`` [d]; positions [B] int32, live [B] bool ->
    ``(y [B, d], new conv state, new SSM state)``. Both states are
    aliased in and out of the kernel."""
    B, N, d = ssm_state.shape
    K = conv_state.shape[1]

    def row(r, pos, live):
        return r, 0, 0

    def whole(r, pos, live):
        return 0, 0

    vec = pl.BlockSpec((1, 1, d), row)
    col = pl.BlockSpec((1, N, 1), row)
    y, conv_new, ssm_new = pl.pallas_call(
        _decode_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,              # positions, live
            grid=(B,),
            in_specs=[vec, vec, vec, col, col,
                      pl.BlockSpec((N, d), whole),
                      pl.BlockSpec((1, d), whole),
                      pl.BlockSpec((1, K, d), row),
                      pl.BlockSpec((1, N, d), row)],
            out_specs=[vec, pl.BlockSpec((1, K, d), row),
                       pl.BlockSpec((1, N, d), row)]),
        out_shape=[jax.ShapeDtypeStruct((B, 1, d), jnp.float32),
                   jax.ShapeDtypeStruct(conv_state.shape, conv_state.dtype),
                   jax.ShapeDtypeStruct(ssm_state.shape, ssm_state.dtype)],
        # operands 9 and 10 (after the two prefetched scalars and a, c,
        # dt, B, C, A, D) are the states; results 1 and 2 are the states
        input_output_aliases={9: 1, 10: 2},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            # a row's states in and out, double-buffered, A, and the
            # update's temporaries
            vmem_limit_bytes=max(16 << 20, 16 * N * d * 4)),
        name="ssm_decode",
        interpret=interpret,
    )(jnp.asarray(positions, jnp.int32), jnp.asarray(live, jnp.int32),
      a[:, None], c[:, None], dt[:, None], Bm[..., None], Cm[..., None],
      A, D[None], conv_state, ssm_state)
    return y[:, 0], conv_new, ssm_new


def ssm_decode_reference(conv_state, ssm_state, a, c, dt, Bm, Cm, A, D,
                         positions, live):
    """`ssm_decode` in `jax.numpy`, operation for operation."""
    keep = jnp.where(positions == 0, 0.0, 1.0).astype(
        jnp.float32)[:, None, None]
    H = jnp.exp(dt[:, None] * A[None]) * (ssm_state * keep) \
        + (dt * c)[:, None] * Bm[..., None]
    y = jnp.sum(H * Cm[..., None], axis=1) + D[None] * c
    conv = jnp.concatenate([conv_state[:, 1:] * keep, a[:, None]], axis=1)
    on = jnp.asarray(live, bool)
    return (jnp.where(on[:, None], y, 0.0),
            jnp.where(on[:, None, None], conv, conv_state),
            jnp.where(on[:, None, None], H, ssm_state))


# ---------------------------------------------------------------------------
# prefill: a bucket of tokens, chunk by chunk
# ---------------------------------------------------------------------------

#: tokens a chunk of the prefill scan holds in VMEM (a multiple of
#: `GROUP`), channels a program's state block holds (8 or 16 vector
#: registers of state carried from token to token), and tokens read and
#: written as one aligned tile
CHUNK, LANES, GROUP = 64, 1024, 8


def _prefill_kernel(nv_ref, dt_ref, c_ref, b_ref, cm_ref, A_ref, D_ref,
                    h0_ref, y_ref, h_ref, h_scr, *, chunk: int):
    """One (row, channel block, chunk). dt, c, y ``[chunk, lanes]``; B
    and C ``[chunk, N, 1]`` (a token's vector is a column, so its outer
    product with the channels' row needs no transpose); the state
    ``[N, lanes]`` in ``h_scr`` between chunks."""
    r, k = pl.program_id(0), pl.program_id(2)

    @pl.when(k == 0)
    def _():
        h_scr[...] = h0_ref[0]

    A, D = A_ref[...], D_ref[...]
    n_left = nv_ref[r] - k * chunk      # real tokens from this chunk on

    def group(g, H):
        at = pl.ds(pl.multiple_of(g * GROUP, GROUP), GROUP)
        dt, c = dt_ref[0, at, :], c_ref[0, at, :]            # [GROUP, lanes]
        ys = []
        for i in range(GROUP):
            t = g * GROUP + i
            dti, ci = dt[i:i + 1], c[i:i + 1]
            new = jnp.exp(dti * A) * H + (dti * ci) * b_ref[0, t]
            ys.append(jnp.sum(new * cm_ref[0, t], axis=0, keepdims=True)
                      + D * ci)
            # padding behind the row's last token never enters the state
            H = jnp.where(t < n_left, new, H)
        y_ref[0, at, :] = jnp.concatenate(ys, axis=0)
        return H

    H = jax.lax.fori_loop(0, chunk // GROUP, group, h_scr[...])
    h_scr[...] = H

    @pl.when(k == pl.num_programs(2) - 1)
    def _():
        h_ref[0] = H


@functools.partial(jax.jit, static_argnames=("chunk", "lanes", "interpret"))
def ssm_prefill(ssm_state: jax.Array, dt: jax.Array, c: jax.Array,
                Bm: jax.Array, Cm: jax.Array, A: jax.Array, D: jax.Array,
                n_valid: jax.Array, *, chunk: Optional[int] = None,
                lanes: Optional[int] = None, interpret: bool = False
                ) -> Tuple[jax.Array, jax.Array]:
    """``T`` tokens a row from ``ssm_state`` [B, N, d] (already zeroed
    where a row starts a sequence): dt/c [B, T, d], Bm/Cm [B, T, N], A
    [N, d], D [d], n_valid [B] the tokens of each row that are real (0:
    the state comes back as it went in) -> ``(y [B, T, d], new state)``.
    Outputs at positions past ``n_valid`` mean nothing."""
    B, T, d = dt.shape
    N = ssm_state.shape[1]
    C = -(-min(int(chunk or CHUNK), T) // GROUP) * GROUP
    pad = (-T) % C
    if pad:
        dt, c, Bm, Cm = (jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
                         for x in (dt, c, Bm, Cm))
    W = min(int(lanes or LANES), d)
    if d % W:
        raise ValueError(f"{d} channels are no whole blocks of {W}")

    def tok(r, j, k, nv):
        return r, k, j

    def vec(r, j, k, nv):
        return r, k, 0, 0

    def chan(r, j, k, nv):
        return 0, j

    def state(r, j, k, nv):
        return r, 0, j

    y, new = pl.pallas_call(
        functools.partial(_prefill_kernel, chunk=C),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,              # n_valid
            grid=(B, d // W, (T + pad) // C),
            in_specs=[pl.BlockSpec((1, C, W), tok),
                      pl.BlockSpec((1, C, W), tok),
                      pl.BlockSpec((1, C, N, 1), vec),
                      pl.BlockSpec((1, C, N, 1), vec),
                      pl.BlockSpec((N, W), chan),
                      pl.BlockSpec((1, W), chan),
                      pl.BlockSpec((1, N, W), state)],
            out_specs=[pl.BlockSpec((1, C, W), tok),
                       pl.BlockSpec((1, N, W), state)],
            scratch_shapes=[pltpu.VMEM((N, W), jnp.float32)]),
        out_shape=[jax.ShapeDtypeStruct((B, T + pad, d), jnp.float32),
                   jax.ShapeDtypeStruct(ssm_state.shape, ssm_state.dtype)],
        compiler_params=pltpu.CompilerParams(
            # rows and channel blocks are independent; a block's chunks
            # carry its state in order
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=32 << 20),
        name="ssm_prefill",
        interpret=interpret,
    )(jnp.asarray(n_valid, jnp.int32), dt, c, Bm[..., None], Cm[..., None],
      A, D[None], ssm_state)
    return (y[:, :T] if pad else y), new


def ssm_recurrence(ssm_state, dt, c, Bm, Cm, A, D, n_valid):
    """The definition: one token at a time over dt/c [B, T, d] and
    Bm/Cm [B, T, N], tokens past ``n_valid`` [B] skipped."""
    def body(H, xs):
        dti, ci, bi, cmi, t = xs
        new = jnp.exp(dti[:, None] * A[None]) * H \
            + (dti * ci)[:, None] * bi[..., None]
        y = jnp.sum(new * cmi[..., None], axis=1) + D[None] * ci
        return jnp.where((t < n_valid)[:, None, None], new, H), y

    sw = lambda x: jnp.swapaxes(x, 0, 1)                     # noqa: E731
    state, y = jax.lax.scan(
        body, ssm_state,
        (sw(dt), sw(c), sw(Bm), sw(Cm), jnp.arange(dt.shape[1])))
    return sw(y), state
