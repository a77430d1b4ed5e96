"""Dropless top-k routing over gated experts (`parallel/ep.py`):
against a loop over the experts with a mask, under skewed routing, and
row by row independent of what else is in the batch."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from horovod_tpu.parallel import ep

E, K, D, F = 8, 3, 128, 128


def _weights(seed, dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    w_in = jnp.asarray(rng.normal(size=(E, D, 2 * F)) / np.sqrt(D), dtype)
    w_out = jnp.asarray(rng.normal(size=(E, F, D)) / np.sqrt(F), dtype)
    return w_in, w_out


def _loop(x, experts, weights, valid, w_in, w_out):
    """Every expert over every token, kept where the token chose it."""
    x = x.astype(jnp.float32)
    y = jnp.zeros_like(x)
    for e in range(E):
        h = jnp.matmul(x, w_in[e].astype(jnp.float32), precision="highest")
        out = jnp.matmul(jax.nn.relu(h[:, :F]) * h[:, F:],
                         w_out[e].astype(jnp.float32), precision="highest")
        gate = jnp.sum(jnp.where(experts == e, weights, 0.0), axis=1)
        y = y + gate[:, None] * out
    return jnp.where(valid[:, None], y, 0.0)


def _route(logits):
    return ep.topk_dropless(jnp.asarray(logits, jnp.float32), K)


def _cases():
    rng = np.random.default_rng(0)
    T = 20
    even = rng.normal(size=(T, E))
    one = even.copy()
    one[:, 5] += 100.0                 # expert 5 gets every token
    few = even.copy()
    few[:, :3] += 100.0                # experts 0-2 get all; 5 get none
    return {"even": even, "one_expert_gets_every_token": one,
            "most_experts_get_none": few}


@pytest.mark.parametrize("impl", ["ragged_dot", "gmm_interpret"])
@pytest.mark.parametrize("case", list(_cases()))
def test_matches_the_loop_over_experts(case, impl):
    logits = _cases()[case]
    T = logits.shape[0]
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.normal(size=(T, D)), jnp.float32)
    valid = jnp.asarray(rng.random(T) > 0.2)
    w_in, w_out = _weights(2)
    experts, weights = _route(logits)
    np.testing.assert_allclose(np.asarray(weights.sum(-1)), 1.0, rtol=1e-6)
    y, hit = ep.routed_experts(x, experts, weights, valid, w_in, w_out,
                               impl=impl)
    want = _loop(x, experts, weights, valid, w_in, w_out)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want),
                               rtol=2e-4, atol=2e-4)
    chosen = np.unique(np.asarray(experts)[np.asarray(valid)])
    assert int(hit) == len(chosen)
    if case == "one_expert_gets_every_token":
        assert 5 in chosen and np.all(np.asarray(experts)[:, 0] == 5)
    if case == "most_experts_get_none":
        assert int(hit) == 3


def test_top_k_is_the_k_largest_and_softmax_over_them():
    logits = np.array([[0.0, 3.0, 1.0, 2.0, -1.0, 2.5, 0.5, 0.1]])
    experts, weights = _route(logits)
    assert experts.tolist() == [[1, 5, 3]]
    want = np.exp([3.0, 2.5, 2.0]) / np.exp([3.0, 2.5, 2.0]).sum()
    np.testing.assert_allclose(np.asarray(weights)[0], want, rtol=1e-6)


def test_a_row_does_not_depend_on_its_batch_mates():
    """No capacity, nothing dropped: a token's output is the same
    whatever else is routed with it, crowded experts included."""
    rng = np.random.default_rng(3)
    w_in, w_out = _weights(4)
    x = jnp.asarray(rng.normal(size=(24, D)), jnp.float32)
    logits = rng.normal(size=(24, E))
    logits[1:, 2] += 50.0              # everyone else crowds expert 2
    logits[0, 2] += 50.0               # ... which token 0 uses too
    experts, weights = _route(logits)
    ones = jnp.ones(24, bool)
    together, _ = ep.routed_experts(x, experts, weights, ones, w_in, w_out)
    alone, _ = ep.routed_experts(x[:1], experts[:1], weights[:1], ones[:1],
                                 w_in, w_out)
    np.testing.assert_allclose(np.asarray(together)[0], np.asarray(alone)[0],
                               rtol=1e-5, atol=1e-5)
    # the capacity-bound router drops under the same pressure
    dispatch, _ = ep.topk_route(jnp.asarray(logits), E, capacity=4, k=K)
    assert float(dispatch.sum()) < 24 * K


def test_invalid_tokens_cost_no_expert():
    rng = np.random.default_rng(5)
    w_in, w_out = _weights(6, jnp.bfloat16)
    x = jnp.asarray(rng.normal(size=(6, D)), jnp.bfloat16)
    logits = np.full((6, E), -10.0)
    logits[:3, [0, 1, 2]] = 1.0
    logits[3:, [5, 6, 7]] = 1.0        # only the invalid rows want 5-7
    experts, weights = _route(logits)
    valid = jnp.asarray([True] * 3 + [False] * 3)
    y, hit = ep.routed_experts(x, experts, weights, valid, w_in, w_out)
    assert int(hit) == 3 and y.dtype == jnp.bfloat16
    assert not np.any(np.asarray(y, np.float32)[3:])
    assert np.all(np.isfinite(np.asarray(y, np.float32)))
