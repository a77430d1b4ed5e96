"""A window on the serving attention: the paged decode kernel and its
XLA oracle, and the flash forward a long-prompt prefill runs.

`window=None` is the program without the argument (bit-exact against
the oracle, as tests/test_serve_kernels.py holds it); with a window the
kernel walks only the table entries that can hold a visible key, so it
sums fewer (exactly zero) terms than the oracle and matches to float32
rounding, not bit for bit.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from horovod_tpu.ops import pallas_paged
from horovod_tpu.ops.pallas_attention import flash_prefill
from horovod_tpu.serve import kv_cache as kvc

BS, KV, G, D = 4, 2, 3, 16
NBLK, NB = 10, 64          # table entries a row, pool blocks


def _pool(seed):
    rng = np.random.default_rng(seed)
    pool_k = jnp.asarray(rng.normal(size=(NB, BS, KV, D)), jnp.float32)
    pool_v = jnp.asarray(rng.normal(size=(NB, BS, KV, D)), jnp.float32)
    return pool_k, pool_v


def _tables(positions, T, rng):
    """Each row gets distinct pool blocks for the positions it has
    written (pos + T), -1 beyond."""
    tables = np.full((len(positions), NBLK), -1, np.int32)
    free = list(rng.permutation(NB))
    for b, p in enumerate(positions):
        for j in range(-(-(p + T) // BS)):
            tables[b, j] = free.pop()
    return tables


# pos below the window, at its edge, one past it, far past it, and with
# the window's oldest key first / last in a block
@pytest.mark.parametrize("window", [5, 8, 9])
@pytest.mark.parametrize("T", [1, 3])
def test_window_kernel_matches_oracle(window, T):
    rng = np.random.default_rng(window * 10 + T)
    positions = np.array([0, 2, window - 1, window, window + 1,
                          3 * BS - 1, 3 * BS, 29, 36], np.int32)
    positions = np.minimum(positions, NBLK * BS - T)
    B = len(positions)
    pool_k, pool_v = _pool(1)
    tables = _tables(positions, T, rng)
    q = jnp.asarray(rng.normal(size=(B, T, KV * G, D)), jnp.float32)
    want = kvc.paged_attention(q, pool_k, pool_v, jnp.asarray(tables),
                               jnp.asarray(positions), window=window)
    got = pallas_paged.paged_attention_fused(
        q, pool_k, pool_v, tables, positions, interpret=True, window=window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-6)
    # and the oracle's window is the dense definition
    full = kvc.paged_attention(q, pool_k, pool_v, jnp.asarray(tables),
                               jnp.asarray(positions))
    inside = positions + T <= window        # every key is in the window
    np.testing.assert_array_equal(np.asarray(want)[inside],
                                  np.asarray(full)[inside])
    assert not np.allclose(np.asarray(want)[~inside],
                           np.asarray(full)[~inside])


def test_window_walks_fewer_entries():
    assert pallas_paged.window_entries(None, 1, 128, 100) == 100
    assert pallas_paged.window_entries(4096, 1, 128, 100) == 33
    assert pallas_paged.window_entries(4096, 1, 128, 20) == 20
    assert pallas_paged.window_entries(8, 3, 4, 10) == 4


@pytest.mark.parametrize("T", [1, 4])
def test_no_window_is_bit_exact_as_before(T):
    rng = np.random.default_rng(7)
    positions = np.array([0, 5, 17, NBLK * BS - T], np.int32)
    pool_k, pool_v = _pool(2)
    tables = _tables(positions, T, rng)
    q = jnp.asarray(rng.normal(size=(4, T, KV * G, D)), jnp.float32)
    want = kvc.paged_attention(q, pool_k, pool_v, jnp.asarray(tables),
                               jnp.asarray(positions))
    for kw in ({}, {"window": None}):
        got = pallas_paged.paged_attention_fused(
            q, pool_k, pool_v, tables, positions, interpret=True, **kw)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def _dense(q, k, v, q_start, window):
    """Dense masked attention, float32: query i of row b at key
    position q_start[b] + i."""
    B, H, Sq, Dh = q.shape
    g = H // k.shape[1]
    k, v = jnp.repeat(k, g, axis=1), jnp.repeat(v, g, axis=1)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                   precision="highest") / np.sqrt(Dh)
    qp = q_start[:, None, None] + jnp.arange(Sq)[None, :, None]
    kp = jnp.arange(k.shape[2])[None, None, :]
    ok = kp <= qp
    if window is not None:
        ok &= kp > qp - window
    s = jnp.where(ok[:, None], s, -1e30)
    return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), v,
                      precision="highest")


@pytest.mark.parametrize("window", [None, 5, 16, 40])
@pytest.mark.parametrize("blocks", [(8, 8), (16, 8), (24, 64)])
def test_flash_prefill_matches_dense(window, blocks):
    rng = np.random.default_rng(3)
    B, H, Sq, Skv = 3, KV * G, 24, 64
    q = jnp.asarray(rng.normal(size=(B, H, Sq, D)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, KV, Skv, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, KV, Skv, D)), jnp.float32)
    q_start = jnp.asarray([0, 7, 40], jnp.int32)     # cached prefixes
    got = flash_prefill(q, k, v, q_start, window=window,
                        block_q=blocks[0], block_k=blocks[1],
                        interpret=True)
    want = _dense(q, k, v, q_start, window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_flash_prefill_in_bfloat16():
    rng = np.random.default_rng(4)
    q = jnp.asarray(rng.normal(size=(1, KV * G, 32, D)), jnp.bfloat16)
    k = jnp.asarray(rng.normal(size=(1, KV, 48, D)), jnp.bfloat16)
    v = jnp.asarray(rng.normal(size=(1, KV, 48, D)), jnp.bfloat16)
    q_start = jnp.asarray([16], jnp.int32)
    got = flash_prefill(q, k, v, q_start, window=12, block_q=16,
                        block_k=16, interpret=True)
    assert got.dtype == jnp.bfloat16
    want = _dense(*(t.astype(jnp.float32) for t in (q, k, v)), q_start, 12)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want), atol=0.03)


def test_training_forward_is_unchanged_by_the_new_arguments():
    """The kernel's defaults are the training forward: same output as
    the dense reference it has always been held to, and the window
    variant at a window wider than the sequence equals it bit for bit."""
    from horovod_tpu.ops.pallas_attention import flash_attention
    rng = np.random.default_rng(5)
    q = jnp.asarray(rng.normal(size=(2, 4, 32, D)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(2, 4, 32, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(2, 4, 32, D)), jnp.float32)
    base = flash_attention(q, k, v, causal=True, block_q=8, block_k=8,
                           interpret=True)
    wide = flash_prefill(q, k, v, jnp.zeros(2, jnp.int32), window=1000,
                         block_q=8, block_k=8, interpret=True)
    np.testing.assert_array_equal(np.asarray(base), np.asarray(wide))
