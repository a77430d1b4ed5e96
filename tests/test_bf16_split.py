"""`ops/bf16_split.py`: float32 activations against bfloat16 weights as
bfloat16 terms, and `ep.routed_experts(exact=True)` built on it (CPU)."""
import numpy as np
import pytest

import jax.numpy as jnp

from horovod_tpu.ops import bf16_split
from horovod_tpu.parallel import ep


def _floats(seed, shape):
    """Normal values over many binades, signs both ways."""
    rng = np.random.default_rng(seed)
    return (rng.normal(size=shape)
            * 2.0 ** rng.integers(-20, 20, size=shape)).astype(np.float32)


def test_three_terms_sum_to_the_float32():
    x = _floats(0, (4096,))
    parts = bf16_split.bf16_parts(jnp.asarray(x))
    assert parts.shape == (3, 4096) and parts.dtype == jnp.bfloat16
    p = np.asarray(parts, np.float64)
    np.testing.assert_array_equal(p.sum(0), x.astype(np.float64))
    # each term is the leading bits of what the terms before it left
    assert (np.abs(p[1]) <= np.abs(p[0]) * 2.0 ** -7).all()
    assert (np.abs(p[2]) <= np.abs(p[1]) * 2.0 ** -7).all()


@pytest.mark.parametrize("parts,rel", [(1, 2.0 ** -7), (2, 2.0 ** -15)])
def test_fewer_terms_keep_fewer_bits(parts, rel):
    x = _floats(1, (4096,)).astype(np.float64)
    got = np.asarray(bf16_split.bf16_parts(jnp.asarray(x, jnp.float32),
                                           parts), np.float64).sum(0)
    assert (np.abs(got - x) <= rel * np.abs(x)).all()
    assert np.abs(got - x).max() > 0


def test_the_split_product_is_the_float32_product():
    """A product over 512 against bfloat16 weights: within float32
    rounding of the float64 product, where rounding the activation to
    bfloat16 once misses by about 2^-9 of the terms' size."""
    rng = np.random.default_rng(2)
    x = rng.normal(size=(16, 512)).astype(np.float32)
    w = jnp.asarray(rng.normal(size=(512, 96)), jnp.bfloat16)
    exact = x.astype(np.float64) @ np.asarray(w, np.float64)
    scale = np.abs(x).astype(np.float64) @ np.abs(np.asarray(w, np.float64))
    got = np.asarray(bf16_split.split_einsum("mk,kn->mn", jnp.asarray(x), w))
    assert got.dtype == np.float32
    assert (np.abs(got - exact) <= 2e-6 * scale).all()
    rounded = np.asarray(jnp.dot(jnp.asarray(x, jnp.bfloat16), w,
                                 preferred_element_type=jnp.float32))
    assert np.abs(rounded - exact).max() > 100 * np.abs(got - exact).max()
    # a weight of another dtype is multiplied as it is
    w32 = jnp.asarray(w, jnp.float32)
    np.testing.assert_allclose(
        np.asarray(bf16_split.split_einsum("mk,kn->mn", jnp.asarray(x), w32)),
        exact, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("impl", ["ragged_dot", "gmm_interpret"])
def test_exact_experts_round_nothing(impl):
    """SwiGLU experts over float32 rows: the float64 computation of the
    same bfloat16 weights, to float32 rounding; the rounded path (x and
    the activation in bfloat16) is far off it."""
    rng = np.random.default_rng(3)
    T, d, E, f, k = 19, 128, 8, 128, 2
    x = rng.normal(size=(T, d)).astype(np.float32)
    w_in = jnp.asarray(0.1 * rng.normal(size=(E, d, 2 * f)), jnp.bfloat16)
    w_out = jnp.asarray(0.1 * rng.normal(size=(E, f, d)), jnp.bfloat16)
    experts = jnp.asarray(np.stack([rng.choice(E, k, replace=False)
                                    for _ in range(T)]), jnp.int32)
    weights = jnp.asarray(rng.random((T, k)), jnp.float32)
    valid = jnp.asarray(rng.random(T) > 0.2)
    y, hit = ep.routed_experts(jnp.asarray(x), experts, weights, valid,
                               w_in, w_out, impl=impl, out_dtype=jnp.float32,
                               activation="silu", exact=True)
    wi, wo = (np.asarray(w, np.float64) for w in (w_in, w_out))
    want = np.zeros((T, d))
    for t in range(T):
        if not valid[t]:
            continue
        for j in range(k):
            e = int(experts[t, j])
            h = x[t].astype(np.float64) @ wi[e]
            g = h[:f] / (1 + np.exp(-h[:f])) * h[f:]
            want[t] += float(weights[t, j]) * (g @ wo[e])
    err = np.abs(np.asarray(y, np.float64) - want).max()
    assert err <= 1e-5 * np.abs(want).max()
    assert int(hit) == len(np.unique(np.asarray(experts)[np.asarray(valid)]))
    rounded, _ = ep.routed_experts(jnp.asarray(x, jnp.bfloat16), experts,
                                   weights, valid, w_in, w_out, impl=impl,
                                   out_dtype=jnp.float32, activation="silu")
    assert np.abs(np.asarray(rounded, np.float64) - want).max() > 30 * err


def test_exact_experts_in_passes_of_rows(monkeypatch):
    """A prefill's many rows go through in passes of `EXACT_ROWS` sorted
    rows, each pass's groups clipped to it: every row's answer is the
    one a single pass gives."""
    rng = np.random.default_rng(4)
    T, d, E, f, k = 300, 64, 8, 32, 2
    x = jnp.asarray(rng.normal(size=(T, d)), jnp.float32)
    w_in = jnp.asarray(0.1 * rng.normal(size=(E, d, 2 * f)), jnp.bfloat16)
    w_out = jnp.asarray(0.1 * rng.normal(size=(E, f, d)), jnp.bfloat16)
    experts = jnp.asarray(np.stack([rng.choice(E, k, replace=False)
                                    for _ in range(T)]), jnp.int32)
    weights = jnp.asarray(rng.random((T, k)), jnp.float32)
    valid = jnp.asarray(rng.random(T) > 0.1)
    args = (x, experts, weights, valid, w_in, w_out)
    kw = dict(impl="ragged_dot", out_dtype=jnp.float32, activation="silu",
              exact=True)
    whole, hit = ep.routed_experts(*args, **kw)
    monkeypatch.setattr(ep, "EXACT_ROWS", 128)  # 1,024 sorted rows: 8 passes
    ep._routed_experts.clear_cache()
    passes, hit2 = ep.routed_experts(*args, **kw)
    ep._routed_experts.clear_cache()
    np.testing.assert_array_equal(np.asarray(passes), np.asarray(whole))
    assert int(hit) == int(hit2) == E
