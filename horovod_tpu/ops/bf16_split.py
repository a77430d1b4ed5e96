"""Float32 activations against bfloat16 weights at float32 precision, on
the MXU's bfloat16 path.

A float32 ``x`` is the exact sum of three bfloat16 terms: the leading 8
significant bits, the next 8 of what is left, the last 8 of that
(`bf16_parts`). A bfloat16 weight ``w`` is exact as it is, so ``x @ w``
is ``sum_i part_i @ w``: bfloat16 products accumulated in float32, with
nothing of ``x`` rounded away. `split_einsum` stacks the terms on a new
leading axis of ONE product, so the weight is read once and the product
has three times the rows: in a decode step, whose few rows leave the MXU
idle behind the weight's bytes, that costs nearly nothing; in a prefill
it is three passes where a rounded operand takes one.

A model whose decisions are discontinuous in the stream (a router's top
k of many close scores) uses this to keep every activation unrounded.

The terms are cut by masking the low 16 bits of a float32, not by
rounding to bfloat16 and subtracting: each term is then exactly a
bfloat16 value whatever a compiler does with a float32 -> bfloat16 ->
float32 round trip (XLA may drop one as excess precision, and the
remainder would read zero).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

#: terms a float32 operand is cut into: 3 x 8 significant bits hold all 24
PARTS = 3


def bf16_parts(x: jax.Array, parts: int = PARTS) -> jax.Array:
    """``[parts, *x.shape]`` bfloat16 terms whose float32 sum is ``x``
    (all of it for ``parts`` 3; the last term truncated, not rounded)."""
    rest = x.astype(jnp.float32)
    out = []
    for _ in range(parts):
        bits = jax.lax.bitcast_convert_type(rest, jnp.uint32)
        lead = jax.lax.bitcast_convert_type(bits & jnp.uint32(0xFFFF0000),
                                            jnp.float32)
        out.append(lead.astype(jnp.bfloat16))
        rest = rest - lead
    return jnp.stack(out)


def split_einsum(spec: str, x: jax.Array, w: jax.Array,
                 parts: int = PARTS) -> jax.Array:
    """``jnp.einsum(spec, x, w)`` in float32 for a float32 ``x`` and a
    bfloat16 ``w``: the terms of ``x`` as one product's leading axis,
    summed smallest first. A ``w`` of another dtype is multiplied as it
    is, at `Precision.HIGHEST`."""
    if w.dtype != jnp.bfloat16:
        return jnp.einsum(spec, x.astype(w.dtype), w,
                          precision=jax.lax.Precision.HIGHEST,
                          preferred_element_type=jnp.float32)
    ins, out = spec.split("->")
    a, b = ins.split(",")
    p = next(c for c in "pzyxwvu" if c not in spec)
    y = jnp.einsum(f"{p}{a},{b}->{p}{out}", bf16_parts(x, parts), w,
                   preferred_element_type=jnp.float32)
    return functools.reduce(lambda acc, i: acc + y[i],
                            range(parts - 2, -1, -1), y[parts - 1])
