"""Expert parallelism: switch-style MoE dispatch over an 'ep' mesh axis.

The reference's accounting (SURVEY §2.6): "EP — absent; alltoall + process
sets are the primitives an MoE implementation would use." This module is that
implementation, TPU-native: priority-ordered top-k routing (k=1 Switch,
k=2 GShard/Mixtral) with fixed expert capacity (static shapes for XLA),
dispatch/combine as einsums against a one-hot dispatch mask,
and `lax.all_to_all` moving token buffers between expert shards — the same
primitive the reference exposes as hvd.alltoall (torch/mpi_ops.py:960).
"""
from __future__ import annotations

from functools import partial
from typing import Callable, Tuple

import jax
import jax.numpy as jnp
from jax import lax


def topk_route(logits: jax.Array, num_experts: int, capacity: int,
               k: int = 1, normalize: bool = True
               ) -> Tuple[jax.Array, jax.Array]:
    """Top-k router with capacity dropping.

    k=1 (normalize=False) is Switch-Transformer routing; k=2 with
    normalized gates is the GShard/Mixtral scheme. Choices are placed in
    priority order: every token's 1st choice claims buffer slots before
    any 2nd choice does, so under capacity pressure second choices drop
    first (GShard semantics).

    logits: [T, E]. Returns (dispatch [T, E, C] one-hot, combine
    [T, E, C] gate-weighted), both zero for dropped tokens.
    """
    if not 1 <= k <= num_experts:
        raise ValueError(f"top-k k={k} must be in [1, num_experts="
                         f"{num_experts}]")
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    occupancy = jnp.zeros((num_experts,), jnp.float32)  # slots used so far
    masked = probs
    dispatches, gates = [], []
    for _ in range(k):
        expert = jnp.argmax(masked, axis=-1)                  # [T]
        gate = jnp.take_along_axis(probs, expert[:, None], axis=-1)[:, 0]
        onehot = jax.nn.one_hot(expert, num_experts, dtype=jnp.float32)
        # position within the expert buffer, offset by earlier choices
        pos = (jnp.cumsum(onehot, axis=0) + occupancy[None, :]) \
            * onehot - 1.0                                     # [T, E]
        in_cap = (pos < capacity) & (pos >= 0)
        pos_cap = jnp.where(in_cap, pos, 0).astype(jnp.int32)
        dispatches.append((onehot * in_cap)[..., None] * jax.nn.one_hot(
            pos_cap, capacity, dtype=jnp.float32))             # [T, E, C]
        gates.append(gate)
        occupancy = occupancy + onehot.sum(axis=0)
        masked = jnp.where(onehot > 0, -jnp.inf, masked)
    dispatch = sum(dispatches)
    if normalize and k > 1:   # Mixtral-style: chosen gates sum to 1
        denom = jnp.maximum(sum(gates), 1e-9)
        gates = [g / denom for g in gates]
    combine = sum(d * g[:, None, None] for d, g in zip(dispatches, gates))
    return dispatch, combine


def top1_route(logits: jax.Array, num_experts: int, capacity: int
               ) -> Tuple[jax.Array, jax.Array]:
    """Top-1 router with capacity dropping (Switch Transformer style)."""
    return topk_route(logits, num_experts, capacity, k=1, normalize=False)


def moe_layer(x: jax.Array, router_w: jax.Array, expert_fn: Callable,
              expert_params, *, axis_name: str = "ep",
              capacity_factor: float = 1.25,
              logits: jax.Array = None, top_k: int = 1) -> jax.Array:
    """Expert-parallel MoE for use inside shard_map.

    x: local tokens [T_local, D]. `expert_params` are the LOCAL experts'
    parameters, stacked on a leading axis [E_local, ...]. Global expert
    count = E_local * ep_size. Dispatch crosses the 'ep' axis via
    all_to_all; combine returns by the reverse all_to_all.

    Pass precomputed fp32 `logits` [T_local, E] to route on exactly the
    values a caller also uses for the load-balancing aux loss (avoids a
    second router matmul and bf16/fp32 divergence on near-tie tokens);
    `router_w` is ignored then and may be None.
    """
    n = lax.psum(1, axis_name)
    T, D = x.shape
    e_local = jax.tree_util.tree_leaves(expert_params)[0].shape[0]
    E = e_local * n
    capacity = max(1, int(capacity_factor * T / E))

    capacity = capacity * top_k  # k choices share the buffer
    if logits is None:
        logits = x @ router_w                                   # [T, E]
    dispatch, combine = topk_route(logits, E, capacity, k=top_k)

    # token buffers per global expert: [E, C, D]
    buffers = jnp.einsum("tec,td->ecd", dispatch, x.astype(jnp.float32))
    # reshape to [n, E_local, C, D] and all_to_all so shard j receives the
    # buffers for ITS experts from every shard: result [n, E_local, C, D]
    # with axis 0 = source shard
    send = buffers.reshape(n, e_local, capacity, D)
    recv = lax.all_to_all(send, axis_name, split_axis=0, concat_axis=0,
                          tiled=True)
    # merge the per-source buffers: experts process all n*C slots
    expert_in = recv.transpose(1, 0, 2, 3).reshape(e_local, n * capacity, D)
    expert_out = jax.vmap(expert_fn)(expert_params,
                                     expert_in.astype(x.dtype))
    expert_out = expert_out.astype(jnp.float32).reshape(
        e_local, n, capacity, D).transpose(1, 0, 2, 3)          # [n,EL,C,D]
    # return results to the source shards
    back = lax.all_to_all(expert_out, axis_name, split_axis=0,
                          concat_axis=0, tiled=True)            # [n,EL,C,D]
    out_buffers = back.reshape(E, capacity, D)
    y = jnp.einsum("tec,ecd->td", combine, out_buffers)
    return y.astype(x.dtype)


def moe_reference(x, router_w, expert_fn, all_expert_params,
                  capacity_factor: float = 1.25, logits=None,
                  top_k: int = 1):
    """Single-device oracle: same routing/capacity, all experts local."""
    T, D = x.shape
    E = jax.tree_util.tree_leaves(all_expert_params)[0].shape[0]
    capacity = max(1, int(capacity_factor * T / E)) * top_k
    if logits is None:
        logits = x @ router_w
    dispatch, combine = topk_route(logits, E, capacity, k=top_k)
    buffers = jnp.einsum("tec,td->ecd", dispatch, x.astype(jnp.float32))
    out = jax.vmap(expert_fn)(all_expert_params, buffers.astype(x.dtype))
    y = jnp.einsum("tec,ecd->td", combine, out.astype(jnp.float32))
    return y.astype(x.dtype)


# ---------------------------------------------------------------------------
# dropless routing: no capacity, no token dropped, rows independent
# ---------------------------------------------------------------------------
# `topk_route` above bounds every expert's buffer and drops what does not
# fit, so what a token gets depends on which other tokens share its batch:
# fine for training at a capacity factor, wrong for serving, where a row's
# answer must not depend on its batch-mates. Here every (token, expert)
# pair is computed: the pairs are sorted by expert and the experts run as
# one grouped matmul over the sorted rows, so the work is tokens x k and an
# expert that received no token is never read.

def topk_dropless(logits: jax.Array, k: int) -> Tuple[jax.Array, jax.Array]:
    """The ``k`` largest router logits of each token and their softmax
    OVER THE CHOSEN (float32): ``(experts [T, k] int32, weights [T, k])``.
    Ties go to the lower expert id (`lax.top_k`)."""
    top, experts = lax.top_k(logits.astype(jnp.float32), k)
    return experts.astype(jnp.int32), jax.nn.softmax(top, axis=-1)


def _tile(n: int, cap: int) -> int:
    """The largest multiple of 128 that divides `n` and is at most `cap`
    (`n` itself where it is no larger)."""
    if n <= cap:
        return n
    for t in range(cap - cap % 128, 127, -128):
        if n % t == 0:
            return t
    raise ValueError(f"no 128-multiple tile <= {cap} divides {n}")


def grouped_matmul(lhs: jax.Array, rhs: jax.Array, group_sizes: jax.Array,
                   *, impl: str, out_dtype) -> jax.Array:
    """``lhs[rows of group g] @ rhs[g]`` for every group: lhs ``[M, K]``
    sorted by group, rhs ``[G, K, N]``, group_sizes ``[G]`` int32. Rows
    past the groups' total are undefined. `impl`: ``"gmm"`` (jax's
    Pallas grouped matmul for TPU, which visits only the (row tile,
    group) pairs that exist: an empty group costs nothing),
    ``"gmm_interpret"`` (the same kernel interpreted, for tests) or
    ``"ragged_dot"`` (`lax.ragged_dot`, any backend)."""
    if impl == "ragged_dot":
        return lax.ragged_dot(lhs, rhs, group_sizes,
                              preferred_element_type=out_dtype)
    if impl not in ("gmm", "gmm_interpret"):
        raise ValueError(f"impl must be gmm | gmm_interpret | ragged_dot; "
                         f"got {impl!r}")
    from jax.experimental.pallas.ops.tpu.megablox import gmm
    M, K = lhs.shape
    tiling = (min(M, 512), _tile(K, 1280), _tile(rhs.shape[2], 512))
    return gmm(lhs, rhs, group_sizes, preferred_element_type=out_dtype,
               tiling=tiling, interpret=impl == "gmm_interpret")


def routed_experts(x: jax.Array, experts: jax.Array, weights: jax.Array,
                   valid: jax.Array, w_in: jax.Array, w_out: jax.Array, *,
                   impl: str = None, out_dtype=None
                   ) -> Tuple[jax.Array, jax.Array]:
    """Gated experts over dropless routes.

    x ``[T, d]``; experts / weights ``[T, k]`` (:func:`topk_dropless`);
    valid ``[T]`` bool (padding and idle rows are routed nowhere: they
    cost no work and read no expert); w_in ``[E, d, 2f]`` (each
    expert's gate then up projection, side by side) and w_out
    ``[E, f, d]``. Returns ``(y [T, d], hit)``: ``y[t] = sum_j
    weights[t, j] * (relu(x[t] @ gate_e) * (x[t] @ up_e)) @ down_e`` for
    ``e = experts[t, j]``, zero for invalid tokens, accumulated in
    float32 and returned in `out_dtype` (x's where None); ``hit`` the
    number of experts that received a token (int32 scalar)."""
    if impl is None:
        impl = "gmm" if jax.default_backend() == "tpu" else "ragged_dot"
    return _routed_experts(x, experts, weights, valid, w_in, w_out,
                           impl=impl, out_dtype=jnp.dtype(out_dtype or
                                                          x.dtype))


# the two grouped matmuls are the kernels named ``gmm`` in a device trace
# (the profiler's events carry the kernel's name and no module path): the
# benchmark finds the experts' device time by that name
@partial(jax.jit, static_argnames=("impl", "out_dtype"))
def _routed_experts(x, experts, weights, valid, w_in, w_out, *, impl,
                    out_dtype):
    T, d = x.shape
    k = experts.shape[1]
    E, f = w_out.shape[0], w_out.shape[1]
    M = T * k
    tm = 512 if M >= 512 else -(-M // 128) * 128
    Mp = -(-M // tm) * tm
    # invalid pairs sort past the last expert, into no group
    flat = jnp.where(valid[:, None], experts, E).reshape(M)
    flat = jnp.pad(flat, (0, Mp - M), constant_values=E)
    order = jnp.argsort(flat, stable=True)                       # [Mp]
    # a compare-and-sum, not a scatter-add (`bincount`): E is small
    sizes = jnp.sum(flat[:, None] == jnp.arange(E)[None, :], axis=0,
                    dtype=jnp.int32)
    rows = x[jnp.minimum(order // k, T - 1)]                     # [Mp, d]
    # float32 between the two products: the gate's product is rounded
    # once, as the operand of the second matmul
    h = grouped_matmul(rows, w_in, sizes, impl=impl, out_dtype=jnp.float32)
    act = (jax.nn.relu(h[:, :f]) * h[:, f:]).astype(x.dtype)
    out = grouped_matmul(act, w_out, sizes, impl=impl,
                         out_dtype=jnp.float32)                  # [Mp, d]
    back = jnp.argsort(order)[:M]                # pair -> its sorted row
    pairs = out[back].reshape(T, k, d)
    # `where`, not a product: rows no group owns are never written
    pairs = jnp.where(valid[:, None, None], pairs, 0.0)
    y = jnp.sum(pairs * weights[..., None], axis=1)
    return y.astype(out_dtype), jnp.sum(sizes > 0).astype(jnp.int32)
