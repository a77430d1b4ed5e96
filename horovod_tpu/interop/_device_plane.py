"""Device data plane for the foreign-framework bindings.

The binding host plane (interop/_plane.py: shm on-host, TCP ring/store
across hosts) is the analog of the reference's Gloo CPU ops — correct
everywhere, but it never touches the accelerators. The reference's real
data plane on GPU machines is NCCL (horovod/common/ops/
nccl_operations.cc:185): tensor payloads reduce over NVLink/IB while the
Gloo controller (gloo/gloo_controller.cc) carries only control traffic.

This module is that split for TPU pods. When every binding worker owns
TPU chips, large tensors stage into jax device buffers and reduce as
XLA collectives over ICI/DCN (`jax.distributed` + shard_map psum); the
host plane keeps small/control traffic (objects, barriers, negotiation,
ragged shapes). The size cutover is HOROVOD_DEVICE_PLANE_THRESHOLD
bytes, the role the reference's NCCL-vs-Gloo build split plays
statically and its fusion thresholds play dynamically.

Activation (HOROVOD_DEVICE_PLANE):
  * ``auto`` (default) — on only when TPU hardware is attached
    (``/dev/accel*`` / ``/dev/vfio``): CPU-only binding jobs stay on the
    host plane and never pay a jax backend init.
  * ``1``/``jax``/``on`` — force on (tests use this with JAX_PLATFORMS=cpu
    and jax's gloo cross-process CPU collectives).
  * ``0``/``off`` — force off.

Consistency contract: routing must be identical on every rank for the
k-th collective, so eligibility depends only on rank-invariant facts
(shape, dtype, op, process set, the shared threshold). Per-rank state
(load, timing) must never influence the route.
"""
from __future__ import annotations

import functools
import glob
import logging
import os
from typing import Optional

import numpy as np

from ..core.config import (DEVICE_ALLTOALL_MIN_FILL_DEFAULT,
                           DEVICE_PLANE_THRESHOLD_DEFAULT)

logger = logging.getLogger("horovod_tpu")

AXIS = "proc"

_state = {
    "active": False,
    "mesh": None,          # jax Mesh over one device per binding rank
    "device": None,        # this rank's staging device
    "n": 0,
    "me": -1,
    "threshold": 65536,
    "alltoall_min_fill": 0.25,
    "owns_distributed": False,
}

# per-kind counters: tests assert the route actually taken
stats = {"allreduce": 0, "allgather": 0, "broadcast": 0,
         "reducescatter": 0, "alltoall": 0}


def _mode() -> str:
    # knob: exempt (binding plane boots pre-Config; declared +
    # validated in core/config.py as device_plane)
    return os.environ.get("HOROVOD_DEVICE_PLANE", "auto").strip().lower()


def tpu_attached() -> bool:
    """TPU chips visible to this host (device nodes + libtpu, not jax —
    probing jax here would pay a backend init on every CPU-only binding
    job). A bare vfio node is NOT enough: any KVM/GPU-passthrough host
    has /dev/vfio, so device nodes only count when libtpu is installed
    alongside them."""
    if os.environ.get("TPU_NAME") or os.environ.get("TPU_WORKER_ID"):
        return True
    if not (glob.glob("/dev/accel*") or glob.glob("/dev/vfio/[0-9]*")):
        return False
    import importlib.util
    return any(importlib.util.find_spec(m) is not None
               for m in ("libtpu", "libtpu_nightly"))


def is_active() -> bool:
    return _state["active"]


def threshold() -> int:
    return _state["threshold"]


def maybe_init(rank: int, size: int) -> bool:
    """Join the device plane if configured; returns active state.

    Collective: when enabled, EVERY rank must call this (init blocks in
    jax.distributed.initialize until all processes connect — the same
    all-or-nothing contract as the native coordinator)."""
    mode = _mode()
    if mode in ("0", "off", "false", "no"):
        return False
    forced = mode in ("1", "jax", "on", "true", "yes")
    if not forced and not tpu_attached():
        return False
    if size <= 1:
        return False
    coord = os.environ.get("HOROVOD_COORDINATOR_ADDR")
    if not coord:
        msg = ("device plane needs HOROVOD_COORDINATOR_ADDR from the "
               "launcher (hvdrun exports it)")
        if forced:
            raise RuntimeError(msg)
        logger.warning("%s; staying on the host plane", msg)
        return False
    import jax
    # CPU backend: cross-process collectives need gloo (no-op on TPU,
    # where collectives ride ICI/DCN natively)
    jax.config.update("jax_cpu_collectives_implementation", "gloo")
    if not jax.distributed.is_initialized():
        jax.distributed.initialize(coordinator_address=coord,
                                   num_processes=size, process_id=rank)
        _state["owns_distributed"] = True
    if jax.process_count() != size or jax.process_index() != rank:
        msg = (f"jax.distributed topology ({jax.process_index()}/"
               f"{jax.process_count()}) does not match the binding job "
               f"({rank}/{size})")
        if forced:
            raise RuntimeError(msg)
        logger.warning("%s; staying on the host plane", msg)
        return False
    _finish_init(rank, size)
    return True


def _finish_init(rank: int, size: int) -> None:
    import jax
    from jax.sharding import Mesh
    per_proc = {}
    for d in jax.devices():
        cur = per_proc.get(d.process_index)
        if cur is None or d.id < cur.id:
            per_proc[d.process_index] = d
    devs = [per_proc[p] for p in range(size)]
    _state.update(
        active=True,
        mesh=Mesh(np.asarray(devs, dtype=object), (AXIS,)),
        device=per_proc[rank],
        n=size,
        me=rank,
        # knob: exempt (binding plane boots pre-Config; both knobs are
        # declared + validated in core/config.py, defaults shared)
        threshold=int(os.environ.get(
            "HOROVOD_DEVICE_PLANE_THRESHOLD",
            str(DEVICE_PLANE_THRESHOLD_DEFAULT))),
        alltoall_min_fill=float(os.environ.get(  # knob: exempt (see above)
            "HOROVOD_DEVICE_ALLTOALL_MIN_FILL",
            str(DEVICE_ALLTOALL_MIN_FILL_DEFAULT))),
    )
    logger.debug("device plane up: %d ranks over %s, threshold=%dB",
                 size, devs[0].platform, _state["threshold"])


def init_local(n: int) -> None:
    """Single-controller test/dryrun mode: n local devices stand in for
    n binding ranks so the very same jitted collective programs can be
    compile-checked and oracle-tested without n real processes (the
    driver's dryrun contract). Data flows through :func:`run_stacked`."""
    import jax
    from jax.sharding import Mesh
    devs = jax.devices()[:n]
    if len(devs) < n:
        raise RuntimeError(f"init_local({n}): only {len(devs)} devices")
    _state.update(active=True, mesh=Mesh(np.asarray(devs, dtype=object),
                                         (AXIS,)),
                  device=devs[0], n=n, me=0,
                  # knob: exempt (dryrun leg, same contract as maybe_init)
                  threshold=int(os.environ.get(
                      "HOROVOD_DEVICE_PLANE_THRESHOLD",
                      str(DEVICE_PLANE_THRESHOLD_DEFAULT))),
                  alltoall_min_fill=float(os.environ.get(  # knob: exempt (see above)
                      "HOROVOD_DEVICE_ALLTOALL_MIN_FILL",
                      str(DEVICE_ALLTOALL_MIN_FILL_DEFAULT))))


def shutdown() -> None:
    if not _state["active"]:
        return
    _state.update(active=False, mesh=None, device=None, n=0, me=-1)
    _program.cache_clear()
    if _state["owns_distributed"]:
        _state["owns_distributed"] = False
        try:
            import jax
            jax.distributed.shutdown()
        except Exception:  # noqa: BLE001 — already torn down
            pass


# -- eligibility --------------------------------------------------------------

def _dtype_ok(dt: np.dtype) -> bool:
    import jax
    if dt.kind not in "fiu" or dt.itemsize > 8:
        return False
    if dt.itemsize == 8 and not jax.config.jax_enable_x64:
        # f64/i64 would silently downcast on a default-config jax
        return False
    return True


def eligible(kind: str, arr: np.ndarray, op: Optional[str] = None,
             is_global_comm: bool = True) -> bool:
    """Rank-invariant routing decision (see module docstring)."""
    if not _state["active"] or not is_global_comm:
        return False
    if arr.nbytes < _state["threshold"]:
        return False
    if not _dtype_ok(arr.dtype):
        return False
    if op is not None and op not in ("sum", "min", "max", "prod"):
        return False
    if kind == "reducescatter" and (
            arr.ndim < 1 or arr.shape[0] % _state["n"]):
        return False
    return True


# -- compiled collective programs ---------------------------------------------

@functools.lru_cache(maxsize=512)
def _program(kind: str, op: Optional[str], root: Optional[int]):
    """One jitted shard_map program per (kind, op, root) over the plane
    mesh; shapes/dtypes re-specialize inside jax.jit's own cache."""
    import jax
    from jax import lax
    from jax import numpy as jnp
    from jax.sharding import PartitionSpec as P
    shard_map = jax.shard_map

    mesh = _state["mesh"]
    n = _state["n"]

    if kind == "allreduce":
        def blk(x):                      # [1, ...] per shard
            if op == "sum":
                r = lax.psum(x, AXIS)
            elif op == "min":
                r = lax.pmin(x, AXIS)
            elif op == "max":
                r = lax.pmax(x, AXIS)
            else:                        # prod: gather-and-multiply
                g = lax.all_gather(x, AXIS)          # [n, 1, ...]
                r = jnp.prod(g, axis=0)
            return r
        out_specs = P(AXIS)
    elif kind == "allgather":
        def blk(x):                      # [1, ...] -> [n, ...] replicated
            return lax.all_gather(x, AXIS, axis=0, tiled=True)
        out_specs = P()
    elif kind == "broadcast":
        def blk(x):                      # masked psum: one collective
            idx = lax.axis_index(AXIS)
            r = lax.psum(jnp.where(idx == root, x, jnp.zeros_like(x)),
                         AXIS)
            return r[0]                  # [1, ...] -> [...] replicated
        out_specs = P()
    elif kind == "alltoall":
        def blk(x):                      # [1, n, M, ...] per shard
            # split the dst axis, concat received rows on a new src
            # axis, then restore the [1, n, M, ...] shard convention
            # (axis 1 = src on the way out)
            r = lax.all_to_all(x, AXIS, split_axis=1, concat_axis=0)
            return jnp.swapaxes(r, 0, 1)  # [n, 1, ...] -> [1, n, ...]
        out_specs = P(AXIS)
    elif kind == "reducescatter":
        def blk(x):                      # [1, d0, ...]; n | d0
            if op == "sum":
                r = lax.psum(x, AXIS)[0]
            elif op == "min":
                r = lax.pmin(x, AXIS)[0]
            elif op == "max":
                r = lax.pmax(x, AXIS)[0]
            else:
                g = lax.all_gather(x, AXIS)
                r = jnp.prod(g, axis=0)[0]
            chunk = r.shape[0] // n
            idx = lax.axis_index(AXIS)
            return lax.dynamic_slice_in_dim(r, idx * chunk, chunk,
                                            axis=0)[None]
        out_specs = P(AXIS)
    else:  # pragma: no cover — internal misuse
        raise ValueError(kind)

    # check_vma off: the replicated-output programs (allgather/broadcast)
    # return collective results jax still tracks as axis-varying
    return jax.jit(shard_map(blk, mesh=mesh, in_specs=P(AXIS),
                             out_specs=out_specs, check_vma=False))


def _stage_in(arr: np.ndarray):
    """This rank's array -> one row of a global [n, ...] device array."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    local = jax.device_put(arr[None], _state["device"])
    return jax.make_array_from_single_device_arrays(
        (_state["n"],) + arr.shape,
        NamedSharding(_state["mesh"], P(AXIS)), [local])


def _my_shard(out) -> np.ndarray:
    """Local row of a P(AXIS)-sharded result."""
    return np.asarray(out.addressable_shards[0].data)[0]


def _replicated(out) -> np.ndarray:
    return np.asarray(out.addressable_shards[0].data)


# -- public collectives (numpy in, numpy out; blocking) -----------------------

def allreduce(arr: np.ndarray, op: str = "sum") -> np.ndarray:
    stats["allreduce"] += 1
    out = _program("allreduce", op, None)(_stage_in(arr))
    return _my_shard(out)


def allgather(arr: np.ndarray) -> np.ndarray:
    """[d, ...] -> [n, d, ...] (the host comm's stacked convention)."""
    stats["allgather"] += 1
    out = _program("allgather", None, None)(_stage_in(arr))
    return _replicated(out)


def broadcast(arr: np.ndarray, root: int) -> np.ndarray:
    stats["broadcast"] += 1
    out = _program("broadcast", None, int(root))(_stage_in(arr))
    return _replicated(out)


def reducescatter(arr: np.ndarray, op: str = "sum") -> np.ndarray:
    stats["reducescatter"] += 1
    out = _program("reducescatter", op, None)(_stage_in(arr))
    return _my_shard(out)


def alltoall_eligible(S: np.ndarray, dtype: np.dtype, row_bytes: int,
                      is_global_comm: bool = True) -> bool:
    """Rank-invariant routing for the ragged alltoall: S is the
    NEGOTIATED (P, P) row-count matrix (identical on every rank after
    the host-plane meta allgather), so total bytes, max chunk and the
    pad fill ratio are global facts. Pad-to-max inflates device traffic
    to P²·M rows, so heavily skewed payloads (fill below
    HOROVOD_DEVICE_ALLTOALL_MIN_FILL, default 0.25) stay on the
    wire-exact host ring."""
    if not _state["active"] or not is_global_comm:
        return False
    if not _dtype_ok(np.dtype(dtype)):
        return False
    n = _state["n"]
    if S.shape != (n, n):
        return False
    # threshold keeps ONE meaning across collectives: this-rank tensor
    # bytes (eligible() uses arr.nbytes). The rank-invariant analog here
    # is the max per-rank send total — every rank computes the same
    # number from the negotiated S, and the cutover doesn't silently
    # shrink as P grows the global sum.
    per_rank_bytes = int(S.sum(axis=1).max()) * row_bytes
    if per_rank_bytes < _state["threshold"]:
        return False
    m = int(S.max())
    if m == 0:
        return False
    fill = float(S.sum()) / float(n * n * m)
    return fill >= _state["alltoall_min_fill"]


def alltoall(chunks, S: np.ndarray, dtype, trail) -> list:
    """Ragged alltoall via pad-to-max + one XLA all_to_all over the
    plane mesh (the reference's NCCLAlltoall role, nccl_operations.cc).
    chunks[d] = this rank's rows for dst d; S[src, dst] = negotiated
    row counts. Returns the received chunk list indexed by src."""
    stats["alltoall"] += 1
    me, n = _state["me"], _state["n"]
    m = int(S.max())
    local = np.zeros((n, m) + tuple(trail), dtype)
    for d, c in enumerate(chunks):
        if c.shape[0]:
            local[d, :c.shape[0]] = c
    out = _program("alltoall", None, None)(_stage_in(local))
    mine = _my_shard(out)                # [n(src), m, ...]
    return [np.ascontiguousarray(mine[s, :int(S[s, me])])
            for s in range(n)]


def run_stacked_alltoall(stacked: np.ndarray) -> np.ndarray:
    """Oracle hook (init_local mode): stacked[src, dst] = padded chunk
    rows; returns global [rank, src, M, ...] result."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    x = jax.device_put(np.ascontiguousarray(stacked),
                       NamedSharding(_state["mesh"], P(AXIS)))
    return np.asarray(_program("alltoall", None, None)(x))


# -- single-controller oracle hook (init_local mode) --------------------------

def run_stacked(kind: str, stacked: np.ndarray, op: str = "sum",
                root: int = 0):
    """Run the SAME compiled program over host-provided per-rank rows
    (stacked[i] = rank i's input) on the local mesh; returns the global
    result array. Used by the driver dryrun to oracle-test the plane
    programs without multiple processes."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    x = jax.device_put(np.ascontiguousarray(stacked),
                       NamedSharding(_state["mesh"], P(AXIS)))
    if kind in ("allreduce", "reducescatter"):
        return np.asarray(_program(kind, op, None)(x))
    if kind == "allgather":
        return np.asarray(_program(kind, None, None)(x))
    if kind == "broadcast":
        return np.asarray(_program(kind, None, int(root))(x))
    raise ValueError(kind)
