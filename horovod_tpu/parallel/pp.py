"""Pipeline parallelism: SPMD GPipe + 1F1B over a 'pp' mesh axis.

The reference has no pipeline parallelism (SURVEY §2.6 "PP — absent"). The
TPU-native design runs all stages as ONE SPMD program: every device holds its
stage's parameters; activations advance stage-to-stage with `lax.ppermute`
(neighbor ICI transfers) inside a `lax.scan` over clock ticks — the
collective-permute pipeline pattern. Two schedules:

* `gpipe` — forward fill-drain (M + S - 1 ticks); training via jax autodiff
  through the scan (holds all M microbatch activations).
* `pipeline_1f1b` — explicit one-forward-one-backward training step: live
  activations bounded at 2S-1 per stage, parameter grads accumulate online,
  with hooks for non-uniform first/last stages (embedding input grads,
  head/loss parameters) so real LMs pipeline end to end.
"""
from __future__ import annotations

from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
from jax import lax


def _pvary(x, axes):
    """Mark `x` device-varying over `axes`."""
    if isinstance(axes, str):
        axes = (axes,)
    for ax in axes:
        x = lax.pcast(x, ax, to="varying")
    return x


def _masked_add(acc, new, valid):
    """acc + new where `valid`, leafwise over a pytree."""
    return jax.tree_util.tree_map(
        lambda a, g: a + jnp.where(valid, g, jnp.zeros_like(g)),
        acc, new)


def gpipe(stage_fn: Callable[[Any, jax.Array], jax.Array],
          stage_params: Any,
          microbatches: jax.Array,
          axis_name: str = "pp") -> jax.Array:
    """Run a GPipe forward pass inside shard_map.

    stage_fn(params, x) -> y: one stage's computation (same shape in/out).
    stage_params: this device's stage parameters.
    microbatches: [M, mb, ...] — the full input on stage 0 (other stages
    ignore their copy).
    Returns [M, mb, ...]: the pipeline output, valid on the LAST stage
    (zeros elsewhere); callers typically ppermute/psum it home.
    """
    n = lax.psum(1, axis_name)
    idx = lax.axis_index(axis_name)
    M = microbatches.shape[0]
    mb_shape = microbatches.shape[1:]
    fwd_perm = [(i, (i + 1) % n) for i in range(n)]

    def tick(carry, t):
        state, outputs = carry            # state: [mb, ...] in-flight act
        # stage 0 injects microbatch t (when one remains); others use the
        # activation received from their left neighbor
        inject = lax.dynamic_index_in_dim(
            microbatches, jnp.minimum(t, M - 1), axis=0, keepdims=False)
        x = jnp.where(idx == 0, inject, state)
        y = stage_fn(stage_params, x)
        # last stage records finished microbatch t - (n - 1); a negative
        # slot matches no index, masked update keeps vma types uniform
        out_slot = t - (n - 1)
        sel = (jnp.arange(M) == out_slot) & (idx == n - 1)
        bcast = sel.reshape((M,) + (1,) * len(mb_shape))
        outputs = jnp.where(bcast, y[None], outputs)
        # advance activations around the ring
        state = lax.ppermute(y, axis_name, fwd_perm)
        return (state, outputs), None

    # mark as device-varying along the pp axis so scan carry types are
    # stable (see jax shard_map scan-vma docs)
    state0 = _pvary(jnp.zeros(mb_shape, microbatches.dtype), axis_name)
    out0 = _pvary(jnp.zeros((M,) + mb_shape, microbatches.dtype),
                  axis_name)
    (_, outputs), _ = lax.scan(tick, (state0, out0),
                               jnp.arange(M + n - 1))
    return outputs


def gpipe_and_return(stage_fn, stage_params, microbatches,
                     axis_name: str = "pp") -> jax.Array:
    """gpipe + broadcast of the final output from the last stage to all
    stages (masked psum), so every device returns the result."""
    n = lax.psum(1, axis_name)
    idx = lax.axis_index(axis_name)
    out = gpipe(stage_fn, stage_params, microbatches, axis_name)
    masked = jnp.where(idx == n - 1, out, jnp.zeros_like(out))
    return lax.psum(masked, axis_name)


def pipeline_1f1b(stage_fn: Callable[[Any, jax.Array], jax.Array],
                  stage_params: Any,
                  microbatches: jax.Array,
                  targets: jax.Array,
                  loss_fn: Callable[..., jax.Array],
                  axis_name: str = "pp",
                  *,
                  head_params: Optional[Any] = None,
                  return_input_grads: bool = False,
                  vary_axes: tuple = ()):
    """One-forward-one-backward pipeline training step inside shard_map.

    The memory-bound schedule (beyond the reference; GPipe + jax.grad
    holds all M microbatch activations, 1F1B holds at most 2S-1 per
    stage): each clock tick every stage runs one forward (microbatch
    ``t - s``) and one backward (microbatch ``t - (2S-1-s)``), forward
    activations ppermute right while cotangents ppermute left, and
    parameter gradients accumulate online. Backward recomputes the stage
    forward from the saved input (rematerialization — FLOPs for HBM, the
    TPU trade).

    stage_fn(params, x) -> y: one stage, same shape in/out.
    microbatches: [M, mb, ...] (read on stage 0); targets: [M, ...]
    (read on the last stage). The step optimizes the MEAN over
    microbatches of ``loss_fn(y, target)`` — or, with `head_params`
    given, ``loss_fn(head_params, y, target)``, so an LM head / final
    projection lives inside the loss and its parameter grads come back
    too (they conceptually belong to the last stage; returned replicated
    via psum).

    `return_input_grads=True` additionally returns dL/d(microbatches)
    ([M, mb, ...], replicated) — the hook for a pre-pipeline embedding
    computed outside: embed tokens, pipeline the blocks, backprop the
    returned input grads into the embedding table.

    `vary_axes`: further mesh axes the inputs are device-varying over
    (e.g. a dp axis whose shards carry different microbatches) — the
    scan carries are initialized varying over them too. The caller owns
    any reduction over those axes (e.g. pmean the grads over dp).

    Returns ``(loss, grads)`` — or ``(loss, grads, aux)`` when
    `head_params` or `return_input_grads` is set, with
    ``aux = {"head_grads": ..., "input_grads": ...}`` (absent hooks are
    None). `loss` is the scalar mean loss, identical on every stage;
    `grads` is this stage's parameter-gradient pytree.
    """
    n = lax.psum(1, axis_name)
    idx = lax.axis_index(axis_name)
    is_last = idx == n - 1
    M = microbatches.shape[0]
    mb_shape = microbatches.shape[1:]
    B = 2 * n - 1                     # ring-buffer depth = max live acts
    right = [(i, (i + 1) % n) for i in range(n)]
    left = [(i, (i - 1) % n) for i in range(n)]
    inv_m = 1.0 / M
    with_head = head_params is not None
    all_axes = (axis_name,) + tuple(vary_axes)
    # _vary_pp: pp only (for values already varying over vary_axes);
    # _varying: fresh zero-init carries, varying over pp + extra axes
    _vary_pp = lambda x: _pvary(x, axis_name)        # noqa: E731
    _varying = lambda x: _pvary(x, all_axes)         # noqa: E731

    def tick(carry, t):
        (fwd_in, bwd_in, buf, gseed, gacc, hacc, dxs, loss_acc) = carry
        # read the backward half's saved input FIRST: at stage 0 the
        # live-activation window equals the ring depth, so this tick's
        # forward write lands in the same slot
        # (written at tick t_f = t - (2(S-s) - 1))
        bwd_slot = jnp.mod(t - (2 * (n - idx) - 1), B)
        x_saved = lax.dynamic_index_in_dim(buf, bwd_slot, axis=0,
                                           keepdims=False)
        # ---- forward: microbatch t - s -------------------------------
        m_f = t - idx
        f_valid = (m_f >= 0) & (m_f < M)
        inject = lax.dynamic_index_in_dim(
            microbatches, jnp.clip(m_f, 0, M - 1), axis=0, keepdims=False)
        x = jnp.where(idx == 0, inject, fwd_in)
        # zero invalid lanes BEFORE compute so junk can't make NaNs that
        # survive multiplicative masking
        x = jnp.where(f_valid, x, jnp.zeros_like(x))
        y = stage_fn(stage_params, x)
        buf = lax.dynamic_update_index_in_dim(buf, x, jnp.mod(t, B),
                                              axis=0)
        # last stage: per-microbatch loss + the backward seed dL/dy,
        # consumed by the backward half exactly one tick later
        tgt = lax.dynamic_index_in_dim(
            targets, jnp.clip(m_f, 0, M - 1), axis=0, keepdims=False)
        lmask = f_valid & is_last
        if with_head:
            # pvary the head first: a replicated (unvarying) primal
            # makes vma-aware AD insert an implicit psum inside the vjp,
            # folding OTHER stages' mid-pipeline activations into dhead
            hp = jax.tree_util.tree_map(_vary_pp, head_params)
            lval, loss_vjp = jax.vjp(loss_fn, hp, y, tgt)
            # seed inherits lval's device-varying type via zeros_like
            dhead, gy, _ = loss_vjp(jnp.zeros_like(lval)
                                    + jnp.asarray(inv_m, lval.dtype))
            hacc = _masked_add(hacc, dhead, lmask)
        else:
            lval, loss_vjp = jax.vjp(loss_fn, y, tgt)
            gy = loss_vjp(jnp.zeros_like(lval)
                          + jnp.asarray(inv_m, lval.dtype))[0]
        loss_acc = loss_acc + jnp.where(lmask, lval * inv_m, 0.0)
        new_gseed = jnp.where(lmask, gy, jnp.zeros_like(gy))
        # ---- backward: microbatch t - (2S-1-s) -----------------------
        m_b = t - (2 * n - 1 - idx)
        b_valid = (m_b >= 0) & (m_b < M)
        g_in = jnp.where(is_last, gseed, bwd_in)
        g_in = jnp.where(b_valid, g_in, jnp.zeros_like(g_in))
        _, stage_vjp = jax.vjp(stage_fn, stage_params, x_saved)
        dparams, dx = stage_vjp(g_in)
        gacc = _masked_add(gacc, dparams, b_valid)
        if return_input_grads:
            # stage 0's dx IS dL/d(microbatch m_b)
            written = lax.dynamic_update_index_in_dim(
                dxs, dx, jnp.clip(m_b, 0, M - 1), axis=0)
            dxs = jnp.where(b_valid & (idx == 0), written, dxs)
        # ---- advance the rings ---------------------------------------
        fwd_in = lax.ppermute(y, axis_name, right)
        bwd_in = lax.ppermute(dx, axis_name, left)
        return (fwd_in, bwd_in, buf, new_gseed, gacc, hacc, dxs,
                loss_acc), None

    dt = microbatches.dtype
    zero_act = lambda: _varying(jnp.zeros(mb_shape, dt))  # noqa: E731
    zero_tree = lambda tree: jax.tree_util.tree_map(      # noqa: E731
        lambda p: _varying(jnp.zeros(p.shape, p.dtype)), tree)
    carry0 = (zero_act(),                                # fwd ring
              zero_act(),                                # bwd ring
              _varying(jnp.zeros((B,) + mb_shape, dt)),  # act buffer
              zero_act(),                                # loss seed
              zero_tree(stage_params),
              zero_tree(head_params) if with_head else (),
              _varying(jnp.zeros((M,) + mb_shape, dt))
              if return_input_grads else (),
              _varying(jnp.zeros((), jnp.float32)))
    (_, _, _, _, grads, hacc, dxs, loss_acc), _ = lax.scan(
        tick, carry0, jnp.arange(M + 2 * n - 1))
    # only the last stage accumulated loss; share it with every stage
    loss = lax.psum(loss_acc, axis_name)
    if not with_head and not return_input_grads:
        return loss, grads
    aux = {"head_grads": None, "input_grads": None}
    if with_head:
        # accumulated on the last stage only; replicate
        aux["head_grads"] = jax.tree_util.tree_map(
            lambda g: lax.psum(g, axis_name), hacc)
    if return_input_grads:
        aux["input_grads"] = lax.psum(dxs, axis_name)  # stage 0's writes
    return loss, grads, aux


def pipeline_interleaved_1f1b(
        stage_fn: Callable[[Any, jax.Array], jax.Array],
        stage_params: Any,
        microbatches: jax.Array,
        targets: jax.Array,
        loss_fn: Callable[..., jax.Array],
        axis_name: str = "pp",
        *,
        head_params: Optional[Any] = None,
        return_input_grads: bool = False,
        vary_axes: tuple = ()):
    """Interleaved (virtual-stage) 1F1B: Megatron-style bubble shrink.

    `stage_params` is stacked [V, ...]: this device owns V virtual
    stages — global stage i + j·n for chunk j on device i — so the
    pipeline has S·V stages on S devices and the fill/drain bubble per
    microbatch group shrinks by V (activations just flow around the
    same ppermute ring V times; stage n·j's input arrives from device
    n-1's chunk j-1 via the ordinary wrap). Schedules forward of
    microbatch m on global stage s at tick m+s and backward at tick
    m+2nV−s; each device still runs at most one forward and one
    backward per tick.

    Constraint: M ≤ n (one microbatch group — the Megatron group size).
    For more microbatches, run waves of n and combine (losses average,
    gradients add).

    Same hooks and return convention as pipeline_1f1b; grads come back
    stacked [V, ...] matching `stage_params`.
    """
    n = lax.psum(1, axis_name)
    idx = lax.axis_index(axis_name)
    M = microbatches.shape[0]
    if M > n:
        raise ValueError(
            f"interleaved schedule takes one microbatch group at a time "
            f"(M={M} > stages={n}); run waves of {n} and combine")
    V = jax.tree_util.tree_leaves(stage_params)[0].shape[0]
    mb_shape = microbatches.shape[1:]
    B = 2 * n * V                     # ring-buffer depth (window max)
    right = [(i, (i + 1) % n) for i in range(n)]
    left = [(i, (i - 1) % n) for i in range(n)]
    inv_m = 1.0 / M
    with_head = head_params is not None
    all_axes = (axis_name,) + tuple(vary_axes)
    _vary_pp = lambda x: _pvary(x, axis_name)        # noqa: E731
    _varying = lambda x: _pvary(x, all_axes)         # noqa: E731

    def _chunk_params(j):
        return jax.tree_util.tree_map(
            lambda a: lax.dynamic_index_in_dim(
                a, jnp.clip(j, 0, V - 1), axis=0, keepdims=False),
            stage_params)

    def tick(carry, t):
        (fwd_in, bwd_in, buf, gseed, gacc, hacc, dxs, loss_acc) = carry
        # ---- backward indices + saved-input read (before the write:
        # the (i=0, j=0) window equals the ring depth) ----------------
        # bwd of (m, stage s=i+jn) runs at t = m + 2nV - 1 - i - jn,
        # so w := t - (2nV - 1) + i = m - jn
        w = t - 2 * n * V + 1 + idx
        m_b = jnp.mod(w, n)
        j_b = (m_b - w) // n
        b_valid = (w <= m_b) & (j_b < V) & (m_b < M)
        slot_r = jnp.mod(m_b + idx + j_b * n, B)
        x_saved = lax.dynamic_index_in_dim(buf, slot_r, axis=0,
                                           keepdims=False)
        # ---- forward: device i, tick t -> (m, chunk) ----------------
        r = t - idx
        m_f = jnp.mod(r, n)
        j_f = r // n
        f_valid = (r >= 0) & (m_f < M) & (j_f < V)
        inject = lax.dynamic_index_in_dim(
            microbatches, jnp.clip(m_f, 0, M - 1), axis=0,
            keepdims=False)
        # global stage 0 == device 0 chunk 0 injects; every other
        # (device, chunk) takes the ring value (device 0's chunks j>0
        # receive device n-1 chunk j-1 through the ordinary wrap)
        x = jnp.where((idx == 0) & (j_f == 0), inject, fwd_in)
        x = jnp.where(f_valid, x, jnp.zeros_like(x))
        y = stage_fn(_chunk_params(j_f), x)
        buf = lax.dynamic_update_index_in_dim(buf, x, jnp.mod(t, B),
                                              axis=0)
        tgt = lax.dynamic_index_in_dim(
            targets, jnp.clip(m_f, 0, M - 1), axis=0, keepdims=False)
        lmask = f_valid & (idx == n - 1) & (j_f == V - 1)
        if with_head:
            hp = jax.tree_util.tree_map(_vary_pp, head_params)
            lval, loss_vjp = jax.vjp(loss_fn, hp, y, tgt)
            dhead, gy, _ = loss_vjp(jnp.zeros_like(lval)
                                    + jnp.asarray(inv_m, lval.dtype))
            hacc = _masked_add(hacc, dhead, lmask)
        else:
            lval, loss_vjp = jax.vjp(loss_fn, y, tgt)
            gy = loss_vjp(jnp.zeros_like(lval)
                          + jnp.asarray(inv_m, lval.dtype))[0]
        loss_acc = loss_acc + jnp.where(lmask, lval * inv_m, 0.0)
        new_gseed = jnp.where(lmask, gy, jnp.zeros_like(gy))
        # ---- backward ------------------------------------------------
        g_in = jnp.where((idx == n - 1) & (j_b == V - 1), gseed, bwd_in)
        g_in = jnp.where(b_valid, g_in, jnp.zeros_like(g_in))
        _, stage_vjp = jax.vjp(stage_fn, _chunk_params(j_b), x_saved)
        dparams, dx = stage_vjp(g_in)
        gacc = jax.tree_util.tree_map(
            lambda acc, g: lax.dynamic_update_index_in_dim(
                acc,
                lax.dynamic_index_in_dim(
                    acc, jnp.clip(j_b, 0, V - 1), axis=0,
                    keepdims=False)
                + jnp.where(b_valid, g, jnp.zeros_like(g)),
                jnp.clip(j_b, 0, V - 1), axis=0),
            gacc, dparams)
        if return_input_grads:
            written = lax.dynamic_update_index_in_dim(
                dxs, dx, jnp.clip(m_b, 0, M - 1), axis=0)
            dxs = jnp.where(b_valid & (idx == 0) & (j_b == 0),
                            written, dxs)
        # ---- rings ---------------------------------------------------
        fwd_in = lax.ppermute(y, axis_name, right)
        bwd_in = lax.ppermute(dx, axis_name, left)
        return (fwd_in, bwd_in, buf, new_gseed, gacc, hacc, dxs,
                loss_acc), None

    dt = microbatches.dtype
    zero_act = lambda: _varying(jnp.zeros(mb_shape, dt))  # noqa: E731
    zero_tree = lambda tree: jax.tree_util.tree_map(      # noqa: E731
        lambda p: _varying(jnp.zeros(p.shape, p.dtype)), tree)
    carry0 = (zero_act(),                                # fwd ring
              zero_act(),                                # bwd ring
              _varying(jnp.zeros((B,) + mb_shape, dt)),  # act buffer
              zero_act(),                                # loss seed
              zero_tree(stage_params),                   # [V, ...] gacc
              zero_tree(head_params) if with_head else (),
              _varying(jnp.zeros((M,) + mb_shape, dt))
              if return_input_grads else (),
              _varying(jnp.zeros((), jnp.float32)))
    (_, _, _, _, grads, hacc, dxs, loss_acc), _ = lax.scan(
        tick, carry0, jnp.arange(M + 2 * n * V - 1))
    loss = lax.psum(loss_acc, axis_name)
    if not with_head and not return_input_grads:
        return loss, grads
    aux = {"head_grads": None, "input_grads": None}
    if with_head:
        aux["head_grads"] = jax.tree_util.tree_map(
            lambda g: lax.psum(g, axis_name), hacc)
    if return_input_grads:
        aux["input_grads"] = lax.psum(dxs, axis_name)
    return loss, grads, aux


def pipeline_interleaved_waves(stage_fn, stage_params, microbatches,
                               targets, loss_fn, axis_name: str = "pp",
                               *, head_params: Optional[Any] = None,
                               return_input_grads: bool = False,
                               vary_axes: tuple = ()):
    """Interleaved 1F1B over M > S microbatches: waves of S groups.

    Scans pipeline_interleaved_1f1b over ⌈M/S⌉ groups of S microbatches
    (M must divide by S), averaging losses and every gradient family —
    the exact mean-over-M objective of pipeline_1f1b. Same return
    convention; with `return_input_grads` the per-wave input grads
    reassemble to [M, mb, ...].
    """
    n = lax.psum(1, axis_name)
    M = microbatches.shape[0]
    if M <= n:
        return pipeline_interleaved_1f1b(
            stage_fn, stage_params, microbatches, targets, loss_fn,
            axis_name, head_params=head_params,
            return_input_grads=return_input_grads, vary_axes=vary_axes)
    if M % n:
        raise ValueError(f"microbatch count {M} must divide by the "
                         f"stage count {n} for wave scheduling")
    W = M // n
    xs_w = microbatches.reshape((W, n) + microbatches.shape[1:])
    ts_w = targets.reshape((W, n) + targets.shape[1:])
    with_head = head_params is not None

    def wave(carry, inputs):
        gsum, hsum, lsum = carry
        xw, tw = inputs
        out = pipeline_interleaved_1f1b(
            stage_fn, stage_params, xw, tw, loss_fn, axis_name,
            head_params=head_params,
            return_input_grads=return_input_grads,
            vary_axes=vary_axes)
        if with_head or return_input_grads:
            loss, grads, aux = out
        else:
            loss, grads = out
            aux = {"head_grads": None, "input_grads": None}
        gsum = jax.tree_util.tree_map(jnp.add, gsum, grads)
        if with_head:
            hsum = jax.tree_util.tree_map(jnp.add, hsum,
                                          aux["head_grads"])
        return (gsum, hsum, lsum + loss), aux["input_grads"]

    # zero carries derived from the params/inputs so they inherit the
    # same device-varying axes as the per-wave outputs
    zero_g = jax.tree_util.tree_map(lambda p: p * 0, stage_params)
    zero_h = jax.tree_util.tree_map(lambda p: p * 0, head_params) \
        if with_head else ()

    (gsum, hsum, lsum), dxs_w = lax.scan(
        wave, (zero_g, zero_h,
               _pvary(jnp.zeros((), jnp.float32), vary_axes)),
        (xs_w, ts_w))
    inv_w = 1.0 / W
    loss = lsum * inv_w
    grads = jax.tree_util.tree_map(lambda g: g * inv_w, gsum)
    if not with_head and not return_input_grads:
        return loss, grads
    aux = {"head_grads": None, "input_grads": None}
    if with_head:
        aux["head_grads"] = jax.tree_util.tree_map(
            lambda g: g * inv_w, hsum)
    if return_input_grads:
        # [W, n, mb...] -> [M, mb...]; each wave's grads are d(wave
        # mean)/dx — rescale to the global mean
        aux["input_grads"] = dxs_w.reshape(
            (M,) + dxs_w.shape[2:]) * inv_w
    return loss, grads, aux
