"""Process/global-state bootstrap: init, shutdown, rank/size queries.

TPU-native re-design of the reference's HorovodBasics
(horovod/common/basics.py:29-471) and InitializeHorovodOnce
(horovod/common/operations.cc:856-906).

Two execution modes:

* **SPMD single-controller** (the TPU-idiomatic default): one Python process
  drives every chip through XLA. `size()` is the number of devices — each
  device is a logical "rank" (worker) for data parallelism, exactly the
  granularity at which the reference counts workers. Per-rank values live as
  rows of "stacked" arrays sharded over the global mesh.
* **Multi-process** (one controller per host, `jax.distributed`): when the
  launcher exports HOROVOD_RANK/SIZE/... (contract identical to
  runner/gloo_run.py:66-78 in the reference) and a coordinator address,
  `init()` calls `jax.distributed.initialize` so all hosts join one global
  mesh spanning ICI+DCN.
"""
from __future__ import annotations

import atexit
import logging
import os
import threading
from typing import List, Optional, Sequence

import jax

from ..trace import stalls
from ..trace.spans import get_recorder
from .config import Config
from .mesh import build_global_mesh, build_hierarchical_mesh, global_devices
from .process_sets import ProcessSet, ProcessSetTable, global_process_set

logger = logging.getLogger("horovod_tpu")


class _GlobalState:
    """Analog of HorovodGlobalState (horovod/common/global_state.h)."""

    def __init__(self):
        self.lock = threading.Lock()
        self.initialized = False
        self.config: Optional[Config] = None
        self.devices: List[jax.Device] = []
        self.mesh = None
        self.hier_mesh = None
        self.process_set_table = ProcessSetTable()
        self.engine = None            # ops.engine.Engine, lazily started
        self.timeline = None          # timeline.Timeline
        self.parameter_manager = None # autotune.ParameterManager
        self.coordinator = None       # native.store.Coordinator (multi-proc)
        self.detector = None          # chaos.detector.HeartbeatDetector
        self.metrics_exporter = None  # obs.exporter.Exporter (/metrics)
        self.metrics_emitter = None   # obs.exporter.TimelineEmitter
        self.joined_ranks = set()
        self.last_joined_rank = -1
        self.shutdown_requested = False


_state = _GlobalState()


def _maybe_init_distributed(cfg: Config) -> None:
    """Join a multi-host job when the launcher provided coordinates."""
    coord = os.environ.get("HOROVOD_COORDINATOR_ADDR")
    # NB: must not touch jax.process_count()/jax.devices() here — any backend
    # query initializes XLA and makes jax.distributed.initialize impossible.
    if coord and cfg.size_env and cfg.size_env > 1 \
            and not jax.distributed.is_initialized():
        # Process identity is the host-level (cross) numbering, not the
        # per-device global rank; fall back explicitly (a '0' value is valid).
        def _first(*vals):
            for v in vals:
                if v is not None:
                    return int(v)
            return None

        num_processes = _first(os.environ.get("HOROVOD_NUM_PROCESSES"),
                               cfg.cross_size_env)
        process_id = _first(os.environ.get("HOROVOD_PROCESS_ID"),
                            cfg.cross_rank_env)
        if num_processes is None or process_id is None:
            raise RuntimeError(
                "Multi-process init needs HOROVOD_NUM_PROCESSES/"
                "HOROVOD_PROCESS_ID (or HOROVOD_CROSS_SIZE/HOROVOD_CROSS_RANK)"
                " alongside HOROVOD_COORDINATOR_ADDR")
        # CPU backend: cross-process collectives need an explicit
        # implementation (the reference's Gloo CPU data plane,
        # ops/gloo_operations.cc — jax ships the same gloo transport).
        # No-op for TPU, where collectives ride ICI/DCN natively.
        jax.config.update("jax_cpu_collectives_implementation", "gloo")
        try:
            jax.distributed.initialize(
                coordinator_address=coord,
                num_processes=num_processes,
                process_id=process_id,
            )
        except Exception as e:  # pragma: no cover - env dependent
            raise RuntimeError(f"jax.distributed.initialize failed: {e}") from e


def _maybe_create_coordinator(cfg: Optional[Config] = None):
    """Connect the native host-level Coordinator (csrc/store.cc) when the
    launcher exported a native KV address — the role the reference's
    controller transport plays over Gloo (gloo/gloo_controller.cc): barrier,
    blob allgather/bcast and cache-bitvector AND/OR across processes."""
    addr = os.environ.get("HOROVOD_NATIVE_KV_ADDR")
    port = os.environ.get("HOROVOD_NATIVE_KV_PORT")
    if not addr or not port:
        return None
    rank_ = int(os.environ.get("HOROVOD_PROCESS_ID",
                               os.environ.get("HOROVOD_CROSS_RANK", "0")))
    size_ = int(os.environ.get("HOROVOD_NUM_PROCESSES",
                               os.environ.get("HOROVOD_CROSS_SIZE", "1")))
    try:
        import socket
        from ..native.store import Coordinator
        # The launcher exports a hostname; resolve worker-side so remote
        # workers get a routable address (the launcher's own /etc/hosts may
        # map its name to loopback).
        ip = socket.gethostbyname(addr)
        # reference HOROVOD_GLOO_TIMEOUT_SECONDS: control-plane op timeout
        timeout = (cfg or Config.from_env()).gloo_timeout_seconds
        return Coordinator(ip, int(port), rank_, size_, timeout=timeout)
    except Exception as e:  # noqa: BLE001
        if size_ > 1:
            # The coordinator protocol is collective: one process silently
            # running without it would leave the others blocked in every
            # barrier/allgather until timeout. Fail fast instead.
            raise RuntimeError(
                f"native coordinator connect failed ({addr}:{port}): {e}; "
                "all processes must join the control plane") from e
        logger.warning("native coordinator unavailable: %s", e)
        return None


def _maybe_start_detector(cfg: Config):
    """Start the heartbeat failure detector (chaos/detector.py) when
    enabled (HOROVOD_HEARTBEAT_INTERVAL_S > 0) and a native KV store is
    reachable. Runs on its own thread + connection, fully off the
    engine cycle. Under the elastic launcher (HOROVOD_ELASTIC) a
    confirmed suspicion escalates by exiting, so the driver resets in
    O(heartbeat interval) instead of O(collective timeout)."""
    if cfg.heartbeat_interval_s <= 0:
        return None
    addr = os.environ.get("HOROVOD_NATIVE_KV_ADDR")
    port = os.environ.get("HOROVOD_NATIVE_KV_PORT")
    if not addr or not port:
        logger.debug("heartbeat detector enabled but no native KV store "
                     "(HOROVOD_NATIVE_KV_ADDR/PORT unset); skipping")
        return None
    try:
        import socket
        from ..chaos import detector as chaos_detector
        from ..chaos import process_identity
        rank_, world = process_identity()
        if world < 2:
            return None
        return chaos_detector.start_detector(
            socket.gethostbyname(addr), int(port), rank_, world,
            interval_s=cfg.heartbeat_interval_s,
            suspect_s=cfg.heartbeat_suspect_s,
            gen=os.environ.get("HOROVOD_SHM_GEN", "1"),
            escalate="exit" if cfg.elastic_enabled else None)
    except Exception as e:  # noqa: BLE001 — detection must not take
        logger.warning("heartbeat detector unavailable: %s", e)  # init down
        return None


def init(comm: Optional[Sequence[int]] = None,
         process_sets: Optional[Sequence[ProcessSet]] = None) -> None:
    """Initialize the framework (reference: hvd.init, basics.py:51).

    `comm` may be a list of global ranks to restrict the job to a device
    subset (the reference accepts an mpi4py comm or rank list). `process_sets`
    pre-registers subgroup sets, like hvd.init(process_sets=[...]).
    """
    rec = get_recorder()
    t0 = rec.now()
    with _state.lock:
        if _state.initialized:
            return
        stalls.install()
        cfg = Config.from_env()
        _state.config = cfg
        _maybe_init_distributed(cfg)
        _state.coordinator = _maybe_create_coordinator(cfg)
        # chaos plane: arm the fault injector (HOROVOD_CHAOS_PLAN) and
        # start the heartbeat failure detector. Arming is idempotent
        # across in-process resets so site counters / once-fired faults
        # are never replayed.
        if cfg.chaos_plan:
            from ..chaos import inject as chaos_inject
            chaos_inject.install_from_env()
        _state.detector = _maybe_start_detector(cfg)

        devices = global_devices()
        if comm is not None and not hasattr(comm, "Get_rank"):
            ranks = sorted(int(r) for r in comm)
            devices = [devices[r] for r in ranks]
        _state.devices = devices
        _state.mesh = build_global_mesh(devices)
        # launcher-provided local size (HOROVOD_LOCAL_SIZE) pins the
        # ICI-local axis; otherwise inferred from per-process device counts
        _state.hier_mesh = build_hierarchical_mesh(
            devices, local_size=cfg.local_size_env)
        _state.process_set_table.initialize_global(devices)
        _state.joined_ranks = set()
        _state.shutdown_requested = False

        _configure_logging(cfg)
        # rank 0 records, like the reference's coordinator-written
        # timeline (timeline.cc; multi-rank writers would race on the
        # same HOROVOD_TIMELINE path)
        if cfg.timeline_filename and jax.process_index() == 0:
            from .. import timeline as timeline_mod
            _state.timeline = timeline_mod.Timeline(cfg.timeline_filename)
            _state.timeline.start()

        # /metrics exporter (HOROVOD_METRICS_PORT): every process
        # exposes its own registry on port + process_index, so
        # co-located controllers don't fight over one socket and a
        # scraper sees one target per rank.
        if cfg.metrics_port:
            from ..obs import exporter as obs_exporter
            try:
                port = cfg.metrics_port + jax.process_index()
                if port > 65535:
                    raise ValueError(
                        f"metrics port {port} (base + process_index) "
                        f"exceeds 65535")
                _state.metrics_exporter = obs_exporter.start_exporter(
                    port=port)
            except (OSError, ValueError) as e:
                # observability must not take init down: a busy port /
                # out-of-range offset degrades to a warning
                logger.warning("metrics exporter unavailable: %s", e)
        # periodic METRICS rows on the timeline
        if cfg.metrics_timeline_period_s > 0 and _state.timeline is not None:
            from ..obs import exporter as obs_exporter
            _state.metrics_emitter = obs_exporter.TimelineEmitter(
                _state.timeline, cfg.metrics_timeline_period_s)

        _state.initialized = True

    if process_sets:
        for ps in process_sets:
            add_process_set(ps)

    logger.debug("horovod_tpu initialized: %d devices, platform=%s",
                 len(_state.devices), _state.devices[0].platform)
    rec.record_local("hvd_init", t0, rec.now())


def _configure_logging(cfg: Config) -> None:
    level = getattr(logging, cfg.log_level, logging.WARNING)
    logger.setLevel(level)
    if cfg.log_with_timestamp and not logger.handlers:
        # reference --log-with-timestamp (launch.py:527)
        h = logging.StreamHandler()
        h.setFormatter(logging.Formatter(
            "[%(asctime)s] %(levelname)s %(message)s"))
        logger.addHandler(h)
        logger.propagate = False


def shutdown() -> None:
    """Tear down (reference: hvd.shutdown, basics.py:141)."""
    with _state.lock:
        if not _state.initialized:
            return
        _state.shutdown_requested = True
    if _state.engine is not None:
        _state.engine.stop()
        _state.engine = None
    if _state.metrics_emitter is not None:
        _state.metrics_emitter.stop()
        _state.metrics_emitter = None
    if _state.metrics_exporter is not None:
        _state.metrics_exporter.stop()
        _state.metrics_exporter = None
    if _state.timeline is not None:
        _state.timeline.stop()
        _state.timeline = None
    if _state.detector is not None:
        from ..chaos import detector as chaos_detector
        chaos_detector.stop_detector()
        _state.detector = None
    if _state.coordinator is not None:
        _state.coordinator.close()
        _state.coordinator = None
    with _state.lock:
        _state.process_set_table.clear()
        _state.initialized = False
        _state.mesh = None
        _state.hier_mesh = None
        _state.devices = []
        _state.joined_ranks = set()


atexit.register(shutdown)


def is_initialized() -> bool:
    """reference: basics.py:198 (horovod_is_initialized)."""
    return _state.initialized


def _require_init() -> None:
    if not _state.initialized:
        raise ValueError(
            "horovod_tpu has not been initialized; run hvd.init() first.")


def size() -> int:
    """Total number of workers = devices in the job (hvd.size)."""
    _require_init()
    return len(_state.devices)


def rank() -> int:
    """This controller's lowest global rank (hvd.rank).

    In multi-process mode each process controls `local_size()` consecutive
    devices and `rank()` is the first of them; in single-controller mode this
    is 0 and per-device ranks appear as the leading axis of stacked arrays.

    NOTE for reference-script ports: a script that branches on
    ``rank() == 0`` for per-WORKER behavior (e.g. "only rank 0 logs")
    keeps its meaning — one controller, one log. But per-DEVICE rank
    semantics (e.g. "each rank seeds with its rank") must move to the
    data level: use :func:`stacked_rank` to get each device-rank's index
    as a stacked array row.
    """
    _require_init()
    return jax.process_index() * local_size()


def stacked_rank():
    """Per-device global ranks as a stacked [size] int32 array — row i is
    rank i's value of "my rank". The stacked-data counterpart of the
    reference's per-process ``hvd.rank()`` for scripts that need a
    per-rank value (seeding, sharding offsets) under the
    single-controller SPMD model."""
    import numpy as np
    _require_init()
    return np.arange(size(), dtype=np.int32)


def local_size() -> int:
    """Devices managed by this process (hvd.local_size)."""
    _require_init()
    n_local = len([d for d in _state.devices
                   if d.process_index == jax.process_index()])
    return n_local if n_local else len(_state.devices)


def local_rank() -> int:
    """hvd.local_rank — 0 for the single-controller (it owns all chips)."""
    _require_init()
    return 0


def cross_size() -> int:
    """Number of processes/hosts (hvd.cross_size)."""
    _require_init()
    return jax.process_count()


def cross_rank() -> int:
    """hvd.cross_rank."""
    _require_init()
    return jax.process_index()


def is_homogeneous() -> bool:
    """True when every process has the same local size (basics.py:239)."""
    _require_init()
    counts = {}
    for d in _state.devices:
        counts[d.process_index] = counts.get(d.process_index, 0) + 1
    return len(set(counts.values())) <= 1


# --- capability queries (reference: *_built/*_enabled, basics.py:250-330) ---

def mpi_threads_supported() -> bool:
    return False


def mpi_built() -> bool:
    return False


def mpi_enabled() -> bool:
    return False


def gloo_built() -> bool:
    # The DCN controller plays gloo's role; report True for script parity.
    return True


def gloo_enabled() -> bool:
    return True


def nccl_built() -> bool:
    return False


def ddl_built() -> bool:
    return False


def ccl_built() -> bool:
    return False


def cuda_built() -> bool:
    return False


def rocm_built() -> bool:
    return False


def tpu_built() -> bool:
    """New capability query: XLA/TPU data plane is always compiled in."""
    return True


def tpu_enabled() -> bool:
    _require_init()
    return _state.devices[0].platform == "tpu"


# --- process-set management (reference: process_sets.py:123-163) -----------

def add_process_set(process_set) -> ProcessSet:
    _require_init()
    if not isinstance(process_set, ProcessSet):
        process_set = ProcessSet(process_set)
    _state.process_set_table.add(process_set, _state.devices)
    return process_set


def remove_process_set(process_set: ProcessSet) -> None:
    _require_init()
    if process_set.process_set_id is None:
        raise ValueError("Process set was never added")
    _state.process_set_table.remove(process_set.process_set_id)


def get_process_set_ids_and_ranks():
    _require_init()
    t = _state.process_set_table
    return {i: list(t.get(i).ranks) for i in t.ids()}


def process_set_included(process_set_id: int = 0) -> bool:
    _require_init()
    ps = _state.process_set_table.get(process_set_id)
    first = jax.process_index() * local_size()
    return any(first <= r < first + local_size() for r in ps.ranks)


# --- accessors used by the rest of the framework ---------------------------

def get_state() -> _GlobalState:
    return _state


def get_mesh():
    _require_init()
    return _state.mesh


def get_hier_mesh():
    _require_init()
    return _state.hier_mesh


def get_config() -> Config:
    _require_init()
    return _state.config


def get_coordinator():
    """The native host-level Coordinator, or None in single-process mode."""
    _require_init()
    return _state.coordinator


def get_failure_detector():
    """The running heartbeat failure detector (chaos/detector.py), or
    None when disabled (HOROVOD_HEARTBEAT_INTERVAL_S=0, the default) or
    single-process."""
    _require_init()
    return _state.detector


def get_process_set(process_set: Optional[ProcessSet] = None) -> ProcessSet:
    """Resolve the default (global) set, mirroring process_set= kwargs."""
    _require_init()
    if process_set is None or process_set is global_process_set:
        return _state.process_set_table.get(0)
    if process_set.process_set_id is None:
        raise ValueError(
            "Process set must be added via hvd.add_process_set() before use")
    return _state.process_set_table.get(process_set.process_set_id)


def get_engine():
    """The lazily-started async engine (background dispatcher)."""
    _require_init()
    if _state.engine is None:
        from ..ops.engine import Engine
        _state.engine = Engine(_state)
        _state.engine.start()
    return _state.engine


def start_timeline(file_path: str, mark_cycles: bool = False) -> None:
    """reference: basics.py:159 (dynamic timeline start)."""
    _require_init()
    from .. import timeline as timeline_mod
    if _state.timeline is not None:
        raise ValueError("Timeline already active; stop it first")
    _state.timeline = timeline_mod.Timeline(file_path, mark_cycles=mark_cycles)
    _state.timeline.start()


def stop_timeline() -> None:
    """reference: basics.py:185."""
    _require_init()
    if _state.timeline is not None:
        _state.timeline.stop()
        _state.timeline = None
