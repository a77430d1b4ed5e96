"""Lightning (linear) attention with a per-head decay: the recurrence a
serving step runs on a fixed-size state instead of a KV cache.

Per head ``h`` with decay ``lam_h = exp(-s_h)``, ``s_h = 2^(-8(h+1)/H)``:

    S_t = lam_h * S_{t-1} + k_t^T v_t        S is [D, D] float32, S_{-1} = 0
    o_t = (q_t / sqrt(D)) S_t

The state is what a sequence keeps between steps: ``[rows, H, D, D]``
float32, whatever its context. Three entry points, one arithmetic:

* :func:`lightning_decode` — one token a row, ONE Pallas kernel over the
  rows (named ``lightning_decode``: the trace finds it by that name)
  that aliases the state in and out, so a step reads and writes each
  live row's state once and copies nothing. A row at position 0 starts
  from zero whatever the slot held; a masked row's state is handed back
  untouched. :func:`lightning_decode_reference` is the same arithmetic
  in `jax.numpy`, the kernel's bit-exactness oracle in interpret mode.
* :func:`lightning_chunked` — a prefill: `lax.scan` over chunks of ``C``
  tokens; inside a chunk ``O = ((Q K^T) * M) V`` with
  ``M[t, s] = lam^(t-s)`` for ``s <= t``, across chunks
  ``O_t += lam^(t+1) q_t S_prev`` and
  ``S = lam^n S_prev + sum_s lam^(n-1-s) k_s^T v_s`` over the chunk's
  ``n`` VALID tokens: a bucket's padding behind a row's last token
  neither enters the sum nor decays the state (padding keys are
  harmless in a pool and poison in a sum).
* :func:`lightning_recurrence` — the definition, token by token; what
  the tests hold the other two to.

q, k, v arrive in the compute dtype (bfloat16 values); every product
with the state is float32 at full precision.
"""
from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

HIGHEST = jax.lax.Precision.HIGHEST


def decay_slopes(num_heads: int) -> np.ndarray:
    """``s_h = 2^(-8 (h + 1) / H)``: the fastest head forgets within a
    few tokens, the slowest keeps 1/e of a token for 256."""
    h = np.arange(1, num_heads + 1, dtype=np.float64)
    return 2.0 ** (-8.0 * h / num_heads)


def decays(num_heads: int) -> np.ndarray:
    """``lam_h = exp(-s_h)`` as the float32 values every path uses."""
    return np.exp(-decay_slopes(num_heads)).astype(np.float32)


# ---------------------------------------------------------------------------
# decode: one token a row, the state updated in place
# ---------------------------------------------------------------------------

def _decode_kernel(pos_ref, live_ref, q_ref, k_ref, v_ref, s_ref,
                   o_ref, s_out_ref, *, lam: Tuple[float, ...],
                   scale: float):
    """One row. q and k are ``[D, H]`` (a head's vector is a column, so
    its outer product with v's row and its product with the state need
    no transpose), v is ``[H, D]``, the state ``[H, D, D]``."""
    b = pl.program_id(0)

    @pl.when(live_ref[b] != 0)
    def _():
        # a slot's next sequence never sees the last one's state
        keep = jnp.where(pos_ref[b] == 0, 0.0, 1.0).astype(jnp.float32)
        for h, lam_h in enumerate(lam):
            kc = k_ref[0, :, h:h + 1].astype(jnp.float32)      # [D, 1]
            qc = q_ref[0, :, h:h + 1].astype(jnp.float32)
            vr = v_ref[0, h:h + 1, :].astype(jnp.float32)      # [1, D]
            s = s_ref[0, h] * (lam_h * keep) + kc * vr
            s_out_ref[0, h] = s
            o_ref[0, h:h + 1, :] = jnp.sum(qc * s, axis=0,
                                           keepdims=True) * scale

    @pl.when(live_ref[b] == 0)
    def _():
        s_out_ref[...] = s_ref[...]
        o_ref[...] = jnp.zeros(o_ref.shape, o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def lightning_decode(state: jax.Array, q: jax.Array, k: jax.Array,
                     v: jax.Array, positions: jax.Array, live: jax.Array,
                     *, interpret: bool = False
                     ) -> Tuple[jax.Array, jax.Array]:
    """One decode token a row: ``state`` [B, H, D, D] float32, q/k/v
    [B, H, D], positions [B] int32, live [B] bool ->
    ``(o [B, H, D] float32, new state)``. The state is aliased in and
    out of the kernel."""
    B, H, D, _ = state.shape
    qt, kt = q.transpose(0, 2, 1), k.transpose(0, 2, 1)        # [B, D, H]
    kern = functools.partial(
        _decode_kernel, lam=tuple(float(x) for x in decays(H)),
        scale=1.0 / math.sqrt(D))

    def row3(b, pos, live):
        return b, 0, 0

    def row4(b, pos, live):
        return b, 0, 0, 0

    o, new = pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,              # positions, live
            grid=(B,),
            in_specs=[pl.BlockSpec((1, D, H), row3),
                      pl.BlockSpec((1, D, H), row3),
                      pl.BlockSpec((1, H, D), row3),
                      pl.BlockSpec((1, H, D, D), row4)],
            out_specs=[pl.BlockSpec((1, H, D), row3),
                       pl.BlockSpec((1, H, D, D), row4)]),
        out_shape=[jax.ShapeDtypeStruct((B, H, D), jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        # operand 5 (after the two prefetched scalars and q, k, v) is
        # the state; result 1 is the state
        input_output_aliases={5: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            # a row's state in and out, double-buffered, and a head's
            # temporaries
            vmem_limit_bytes=max(16 << 20, 6 * H * D * D * 4)),
        name="lightning_decode",
        interpret=interpret,
    )(jnp.asarray(positions, jnp.int32),
      jnp.asarray(live, jnp.int32), qt, kt, v, state)
    return o, new


def lightning_decode_reference(state, q, k, v, positions, live):
    """`lightning_decode` in `jax.numpy`, operation for operation."""
    H, D = state.shape[1], state.shape[2]
    lam = jnp.asarray(decays(H))[None, :, None, None]
    keep = jnp.where(positions == 0, 0.0, 1.0).astype(
        jnp.float32)[:, None, None, None]
    qf, kf, vf = (x.astype(jnp.float32) for x in (q, k, v))
    s = state * (lam * keep) + kf[..., :, None] * vf[..., None, :]
    o = jnp.sum(qf[..., :, None] * s, axis=2) * (1.0 / math.sqrt(D))
    on = jnp.asarray(live, bool)
    return (jnp.where(on[:, None, None], o, 0.0),
            jnp.where(on[:, None, None, None], s, state))


# ---------------------------------------------------------------------------
# prefill: chunks of C tokens
# ---------------------------------------------------------------------------

#: tokens a chunk of the prefill scan holds
CHUNK = 256


def lightning_chunked(state: jax.Array, q: jax.Array, k: jax.Array,
                      v: jax.Array, n_valid: jax.Array, *,
                      chunk: Optional[int] = None
                      ) -> Tuple[jax.Array, jax.Array]:
    """``T`` tokens a row from ``state`` [B, H, D, D] (already zeroed
    where a row starts a sequence): q/k/v [B, T, H, D], n_valid [B]
    the tokens of each row that are real (0: the row is not in the
    step, its state comes back as it went in) ->
    ``(o [B, T, H, D] float32, new state)``. Outputs at positions past
    ``n_valid`` mean nothing."""
    B, T, H, D = q.shape
    C = min(int(chunk or CHUNK), T)
    pad = (-T) % C
    if pad:
        q, k, v = (jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
                   for x in (q, k, v))
    n_chunks = (T + pad) // C
    slope = jnp.asarray(decay_slopes(H), jnp.float32)           # [H]
    t = jnp.arange(C, dtype=jnp.float32)
    # M[h, t, s] = lam_h^(t - s) for s <= t
    gap = t[:, None] - t[None, :]
    M = jnp.where(gap >= 0, jnp.exp(-slope[:, None, None]
                                    * jnp.maximum(gap, 0.0)), 0.0)
    carry_in = jnp.exp(-slope[None, :] * (t[:, None] + 1.0))   # [C, H]
    scale = 1.0 / math.sqrt(D)

    def split(x):       # [B, T, H, D] -> [n_chunks, B, C, H, D]
        return x.reshape(B, n_chunks, C, H, D).transpose(1, 0, 2, 3, 4)

    def body(S, xs):
        qc, kc, vc, c = xs
        n = jnp.clip(n_valid - c * C, 0, C).astype(jnp.float32)  # [B]
        real = t[None, :] < n[:, None]                           # [B, C]
        kc = jnp.where(real[..., None, None], kc, jnp.zeros_like(kc))
        qf, kf, vf = (x.astype(jnp.float32) for x in (qc, kc, vc))
        # operands as they came (bfloat16 values: one exact pass)
        scores = jnp.einsum("bthd,bshd->bhts", qc, kc, precision=HIGHEST,
                            preferred_element_type=jnp.float32)
        o = jnp.einsum("bhts,bshd->bthd", scores * M[None], vf,
                       precision=HIGHEST)
        o = o + jnp.einsum("bthd,bhde->bthe",
                           qf * carry_in[None, :, :, None], S,
                           precision=HIGHEST)
        # the state after the chunk's n real tokens
        left = n[:, None, None] - 1.0 - t[None, :, None]          # [B, C, 1]
        w = jnp.where(real[..., None],
                      jnp.exp(-slope[None, None, :]
                              * jnp.maximum(left, 0.0)), 0.0)    # [B, C, H]
        S = S * jnp.exp(-slope[None, :] * n[:, None])[..., None, None] \
            + jnp.einsum("bshd,bshe->bhde", kf * w[..., None], vf,
                         precision=HIGHEST)
        return S, o * scale

    state, o = jax.lax.scan(
        body, state, (split(q), split(k), split(v), jnp.arange(n_chunks)))
    o = o.transpose(1, 0, 2, 3, 4).reshape(B, T + pad, H, D)
    return (o[:, :T] if pad else o), state


def lightning_recurrence(state, q, k, v, n_valid):
    """The definition: one token at a time over q/k/v [B, T, H, D],
    tokens past ``n_valid`` [B] skipped."""
    H, D = q.shape[2], q.shape[3]
    lam = jnp.asarray(decays(H))[None, :, None, None]

    def body(S, xs):
        qt, kt, vt, t = xs
        kf, vf = kt.astype(jnp.float32), vt.astype(jnp.float32)
        new = lam * S + kf[..., :, None] * vf[..., None, :]
        S = jnp.where((t < n_valid)[:, None, None, None], new, S)
        o = jnp.einsum("bhd,bhde->bhe", qt.astype(jnp.float32), S,
                       precision=HIGHEST) / math.sqrt(D)
        return S, o

    state, o = jax.lax.scan(
        body, state, (q.transpose(1, 0, 2, 3), k.transpose(1, 0, 2, 3),
                      v.transpose(1, 0, 2, 3), jnp.arange(q.shape[1])))
    return o.transpose(1, 0, 2, 3), state
