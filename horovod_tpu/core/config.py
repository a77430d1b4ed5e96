"""Typed runtime configuration with HOROVOD_* env-var compatibility.

The reference scatters ~30 knobs across env parsing in
horovod/common/operations.cc:455-650 and horovod/common/utils/env_parser.cc.
Here they collapse into one dataclass (SURVEY §5.6 direction) while keeping
the same env names so reference users' scripts keep working.
"""
from __future__ import annotations

import os
from dataclasses import dataclass, field, fields
from typing import Optional


def _env_bool(name: str, default: bool) -> bool:
    v = os.environ.get(name)
    if v is None:
        return default
    return v.strip().lower() in ("1", "true", "yes", "on")


def _env_int(name: str, default: int) -> int:
    v = os.environ.get(name)
    if v is None:
        return default
    try:
        return int(v)
    except ValueError:
        return default


def _env_float(name: str, default: float) -> float:
    v = os.environ.get(name)
    if v is None:
        return default
    try:
        return float(v)
    except ValueError:
        return default


def _env_int_strict(name: str, default: int) -> int:
    """Like _env_int but a malformed value raises instead of silently
    falling back — the serve knobs' fail-fast contract."""
    v = os.environ.get(name)
    if v is None:
        return default
    try:
        return int(v)
    except ValueError:
        raise ValueError(f"{name} must be an integer; got {v!r}")


def _env_float_strict(name: str, default: float) -> float:
    v = os.environ.get(name)
    if v is None:
        return default
    try:
        return float(v)
    except ValueError:
        raise ValueError(f"{name} must be a number; got {v!r}")


#: pre-Config read-site defaults, single-sourced: these knobs are also
#: read directly (annotated) on paths where no Config exists yet — the
#: binding plane (interop/_device_plane.py) and the elastic driver —
#: and the default must not fork between the dataclass and those sites.
DEVICE_PLANE_THRESHOLD_DEFAULT = 65536
DEVICE_ALLTOALL_MIN_FILL_DEFAULT = 0.25
ELASTIC_POLL_INTERVAL_S_DEFAULT = 1.0


@dataclass
class Config:
    """All runtime knobs. Defaults mirror the reference where one exists."""

    # Fusion: reference default 64MB via HOROVOD_FUSION_THRESHOLD
    # (operations.cc:519-524; parameter_manager default 64MB).
    fusion_threshold_bytes: int = 64 * 1024 * 1024
    # Background dispatch cycle in ms (reference default 1ms,
    # operations.cc:525-534 HOROVOD_CYCLE_TIME).
    cycle_time_ms: float = 1.0
    # Response/jit cache capacity (reference HOROVOD_CACHE_CAPACITY,
    # operations.cc:544).
    cache_capacity: int = 1024
    # Two-level algorithms (reference HOROVOD_HIERARCHICAL_ALLREDUCE,
    # HOROVOD_TORUS_ALLREDUCE — operations.cc:548-606).
    hierarchical_allreduce: bool = False
    hierarchical_allgather: bool = False
    torus_allreduce: bool = False
    # Autotune (operations.cc:628-637).
    autotune: bool = False
    autotune_log: Optional[str] = None
    autotune_warmup_samples: int = 3
    autotune_steps_per_sample: int = 10
    # reference --autotune-bayes-opt-max-samples / ...-gaussian-process-noise
    # (launch.py:431-437)
    autotune_bayes_opt_max_samples: int = 20
    autotune_gaussian_process_noise: Optional[float] = None
    # True when HOROVOD_HIERARCHICAL_ALLREDUCE was set explicitly (either
    # value) — the reference's --no-hierarchical-allreduce contract: an
    # explicit setting freezes the knob against autotuning (launch.py:380)
    hierarchical_allreduce_set: bool = False
    # Native control-plane op timeout (reference HOROVOD_GLOO_TIMEOUT_SECONDS)
    gloo_timeout_seconds: float = 300.0
    # Timestamps in log lines (reference --log-with-timestamp)
    log_with_timestamp: bool = False
    # Timeline (operations.cc:495-510).
    timeline_filename: Optional[str] = None
    timeline_mark_cycles: bool = False
    # Stall inspector (env_parser.cc:121-133).
    stall_check_disable: bool = False
    stall_warning_time_seconds: float = 60.0
    stall_shutdown_time_seconds: float = 0.0
    # Elastic (operations.cc:501).
    elastic_enabled: bool = False
    # Adasum tuning (HOROVOD_ADASUM_MPI_CHUNK_SIZE analog).
    adasum_chunk_bytes: int = 1 << 26
    # Two-level Adasum (AdasumGpuAllreduceOp::NcclHierarchical analog,
    # adasum_gpu_operations.cc:66): local sum reduce-scatter, cross-node
    # Adasum, local allgather. Off by default: the flat device-rank tree
    # is the reference's AdasumMPI semantic.
    adasum_hierarchical: bool = False
    # Wire-format compression for fused collectives (HOROVOD_COMPRESSION):
    # "none" | "bf16" (cast the fused buffer) | "int8" (block-scaled
    # quantization with error feedback, optim/compression.py).
    compression: str = "none"
    # Elements per int8 quantization block (HOROVOD_COMPRESSION_BLOCK_SIZE).
    # One fp32 scale travels per block; 128 keeps the sidecar under 4%.
    compression_block_size: int = 128
    # Restrict compression to the DCN hop of the hierarchical allreduce
    # (HOROVOD_COMPRESSION_DCN_ONLY): ICI stays full precision; only the
    # cross-slice hop — where bytes are expensive — is quantized. Without
    # hierarchical/torus allreduce this means no compression at all.
    compression_dcn_only: bool = False
    # True when HOROVOD_COMPRESSION was set explicitly — freezes the knob
    # against autotuning (same contract as hierarchical_allreduce_set).
    compression_set: bool = False
    # Collective algorithm plane (HOROVOD_COLLECTIVE_ALGO, ops/algo.py):
    # "auto" resolves per bucket from the autotuner's learned per-regime
    # choices / the alpha-beta cost model; an explicit algorithm
    # ("direct" | "rs_ag" | "rhd" | "two_level") forces every eligible
    # allreduce bucket onto that strategy and freezes autotuning.
    collective_algo: str = "auto"
    # True when HOROVOD_COLLECTIVE_ALGO was set explicitly.
    collective_algo_set: bool = False
    # Autotuner-learned per-regime algorithms ("" = not learned yet):
    # buckets below/at-or-above collective_algo_threshold_bytes resolve
    # to small/large respectively. Written by the engine when the tuner
    # samples/pins the algo dims; round-synchronized from rank 0 like
    # every other tunable.
    collective_algo_small: str = ""
    collective_algo_large: str = ""
    # Small/large bucket split for the per-regime choices
    # (HOROVOD_COLLECTIVE_ALGO_THRESHOLD, bytes); 0 uses the analytic
    # alpha-beta crossover (ops/algo.py crossover_bytes).
    collective_algo_threshold_bytes: int = 0
    # Convergence harness (horovod_tpu/converge): the short-real-
    # optimization matrix run that gates every wire-format/algorithm
    # change. Steps per cell (HOROVOD_CONVERGE_STEPS).
    converge_steps: int = 30
    # Per-rank batch size (HOROVOD_CONVERGE_BATCH).
    converge_batch: int = 4
    # Data/init seed (HOROVOD_CONVERGE_SEED) — the whole run is a pure
    # function of this seed, so two runs with the same seed must
    # produce identical curves (the determinism invariant the tests pin).
    converge_seed: int = 0
    # SGD learning rate (HOROVOD_CONVERGE_LR). 0 (the default) uses the
    # per-model calibrated rate from bench_zoo.CONVERGE_LRS — a single
    # global rate cannot serve both gpt_tiny (needs ~0.2 to clear the
    # converge gate in 30 steps) and resnet18 (needs <=0.1 to keep the
    # short-run trajectory out of its chaotic regime, where ulp-level
    # wire noise amplifies into large final-loss scatter). A positive
    # value overrides every row (measured in docs/benchmarks.md).
    converge_lr: float = 0.0
    # Comma-separated bench_zoo.CONVERGE_MODELS rows the matrix trains
    # (HOROVOD_CONVERGE_MODELS).
    converge_models: str = "resnet18,gpt_tiny"
    # Global multiplier on every per-cell tolerance
    # (HOROVOD_CONVERGE_TOL_SCALE): >1 loosens a flaky CI box, <1
    # tightens a nightly sweep; 1.0 is the documented table as-is.
    converge_tol_scale: float = 1.0
    # Serving (horovod_tpu/serve): continuous-batching inference knobs.
    # Decode slots the executor batches per iteration (the fixed jit
    # batch shape — HOROVOD_SERVE_MAX_BATCH).
    serve_max_batch: int = 8
    # Admission-queue bound past which submits are load-shed with a
    # structured retry-after rejection (HOROVOD_SERVE_MAX_QUEUE).
    serve_max_queue: int = 64
    # Default per-request deadline (HOROVOD_SERVE_DEADLINE_MS); expired
    # requests resolve "expired" and free their KV slot.
    serve_deadline_ms: float = 30000.0
    # Prefill length buckets (HOROVOD_SERVE_BUCKETS, csv): prompts are
    # right-padded to the smallest fitting bucket so jit compiles one
    # prefill program per bucket and nothing else, ever.
    serve_buckets: tuple = (32, 128, 512)
    # Per-slot KV integrity: crc-on-write / verify-on-read of every
    # retiring sequence's cache prefix (HOROVOD_SERVE_KV_CRC). Catches
    # silent cache corruption before tokens reach a client (the chaos
    # serve.kv fault's detection path) at the cost of one small
    # device->host readback per step plus one prefix readback per
    # retiring request. Off by default; the serving soak forces it on.
    serve_kv_crc: bool = False
    # Radix prefix cache over prompt token ids (HOROVOD_SERVE_PREFIX_
    # CACHE): shared system prompts map to refcounted read-only block
    # runs, so a cached prefix copies block references instead of
    # recomputing attention. Flushed on every weight-version swap.
    serve_prefix_cache: bool = True
    # Paged decode attention kernel (HOROVOD_SERVE_KERNEL): "pallas"
    # runs the fused block-table-aware Pallas kernels
    # (ops/pallas_paged.py — interpret mode off TPU, the parity/CI
    # tier), "xla" the gather+masked-einsum oracle, "auto" (default)
    # pallas on TPU and xla elsewhere. Resolved ONCE at executor build
    # (serve/executor.py) so the jit cache stays flat; the resolved
    # path is named by a one-shot KERNEL timeline instant and the
    # hvd_serve_step_ms {kernel=...} label, so a silent fallback to
    # XLA on TPU is visible.
    serve_kernel: str = "auto"
    # Serve wire frame ceiling in bytes (HOROVOD_SERVE_WIRE_MAX_FRAME):
    # the largest frame serve/wire.py will send or accept. Dispatch
    # frames (token ids, acks) never approach it; KV-block MIGRATION
    # frames (serve/kv_migrate.py) carry a whole sequence's paged
    # blocks as binary payload and scale with model size x context, so
    # disaggregated deployments with big pools raise this. Oversize is
    # always a loud DispatchError naming the knob, never a truncation.
    serve_wire_max_frame: int = 4 * 1024 * 1024
    # Speculative decoding draft depth (HOROVOD_SERVE_SPEC_K): with a
    # draft executor attached, the drafter proposes up to this many
    # tokens per iteration and the target verifies them in ONE
    # [max_batch, spec_k+1] step — emitted tokens stay bit-identical
    # to target-only greedy decode. 0 disables speculation even when a
    # drafter is wired up.
    serve_spec_k: int = 3
    # Fleet KV tier (HOROVOD_SERVE_KVTIER): promote the radix prefix
    # cache to a fleet resource (serve/kvtier/) — evicted refcount-zero
    # runs demote HBM -> host-RAM -> disk instead of dying, returning
    # conversations promote them back through the crc-gated
    # version-fenced install path, and the fleet routers steer
    # prefix-heavy requests to the replica holding the longest cached
    # run. With the prefix cache off the knob is inert; off by default.
    serve_kvtier: bool = False
    # Host-RAM ring bound for demoted KV blocks, in MiB per replica
    # (HOROVOD_SERVE_KVTIER_HOST_MB). Overflow spills to the disk tier
    # when HOROVOD_SERVE_KVTIER_DIR is set, else the oldest run drops
    # (re-prefill on next use — the miss path, never an error).
    serve_kvtier_host_mb: int = 64
    # Disk spill directory for the KV tier (HOROVOD_SERVE_KVTIER_DIR):
    # one hvdkv-v1 file per demoted block (per-leaf bytes + crc table +
    # weight version; tools/kvtier_inspect.py audits them offline).
    # Empty (default) disables the disk rung of the ladder.
    serve_kvtier_dir: str = ""
    # Autoscale plane (horovod_tpu/autoscale): master enable — the
    # soak/bench harnesses attach an Autoscaler to the serve router
    # when set (HOROVOD_AUTOSCALE). Library callers construct
    # Autoscaler directly; this knob is how the CLI surfaces opt in.
    autoscale: bool = False
    # Seconds between load-snapshot samples on the autoscaler's poll
    # thread (HOROVOD_AUTOSCALE_INTERVAL_S).
    autoscale_interval_s: float = 1.0
    # Pool-utilization band (max of queue occupancy and paged-KV
    # occupancy): at/above the high bar the policy grows the pool
    # (HOROVOD_AUTOSCALE_UP_UTIL), at/below the low bar it shrinks
    # (HOROVOD_AUTOSCALE_DOWN_UTIL); between the two it HOLDS — the
    # hysteresis band that stops thrash.
    autoscale_up_util: float = 0.75
    autoscale_down_util: float = 0.25
    # Cooldowns: minimum seconds between scale-ups of one pool
    # (HOROVOD_AUTOSCALE_COOLDOWN_UP_S) and quiet seconds — no scale
    # action on the pool — before a scale-down
    # (HOROVOD_AUTOSCALE_COOLDOWN_DOWN_S; down > up so capacity is
    # quick to arrive and slow to leave).
    autoscale_cooldown_up_s: float = 5.0
    autoscale_cooldown_down_s: float = 20.0
    # Per-pool replica-count floor/ceiling the policy clamps targets
    # to (HOROVOD_AUTOSCALE_MIN_REPLICAS /
    # HOROVOD_AUTOSCALE_MAX_REPLICAS).
    autoscale_min_replicas: int = 1
    autoscale_max_replicas: int = 4
    # Prompt-length mix: prompts at/above this many tokens count as
    # LONG (HOROVOD_AUTOSCALE_LONG_PROMPT_TOKENS); when the long
    # fraction of the recent-prompt window crosses
    # HOROVOD_AUTOSCALE_LONG_PROMPT_FRAC and TTFT is over SLO, the
    # policy grows the PREFILL pool specifically.
    autoscale_long_prompt_tokens: int = 64
    autoscale_long_prompt_frac: float = 0.5
    # p99 time-to-first-token the policy defends, in ms
    # (HOROVOD_AUTOSCALE_TTFT_SLO_MS).
    autoscale_ttft_slo_ms: float = 5000.0
    # Checkpoint plane (horovod_tpu/ckpt): max in-flight async host
    # snapshots — save() backpressures beyond this bound
    # (HOROVOD_CKPT_SNAPSHOT_DEPTH; 2 = classic double buffering).
    ckpt_snapshot_depth: int = 2
    # Buddy-rank shard mirroring over the p2p ring so one lost host's
    # shard is recoverable from its ring successor
    # (HOROVOD_CKPT_REPLICATE).
    ckpt_replicate: bool = False
    # Committed checkpoints retained per directory; 0 keeps everything
    # (HOROVOD_CKPT_MAX_TO_KEEP).
    ckpt_max_to_keep: int = 3
    # Elastic auto-restore: @hvd.elastic.run loads the state's last
    # on-disk commit on (re)entry — through the reshard plan when the
    # world size changed (HOROVOD_CKPT_AUTO_RESTORE).
    ckpt_auto_restore: bool = False
    # Redistribution plane (horovod_tpu/redist): elastic (re)entries
    # first try the IN-MEMORY restore — surviving holders redistribute
    # committed state over the wire, falling back to the checkpoint
    # only when state was actually lost (HOROVOD_REDIST_ELASTIC).
    redist_elastic: bool = True
    # Bounded-memory transfer granularity: per-rank send/receive bytes
    # per redistribution round (HOROVOD_REDIST_CHUNK_BYTES).
    redist_chunk_bytes: int = 16 * 1024 * 1024
    # Chaos plane (horovod_tpu/chaos): declarative seeded fault plan —
    # inline JSON or a path to a JSON file (HOROVOD_CHAOS_PLAN). None
    # leaves every injection shim a byte-identical pass-through.
    chaos_plan: Optional[str] = None
    # Failure-detector heartbeat period over the native KV store
    # (HOROVOD_HEARTBEAT_INTERVAL_S; 0 disables the detector). Each
    # process posts + sweeps off the engine cycle on its own thread.
    heartbeat_interval_s: float = 0.0
    # Heartbeat age past which a peer is suspected dead, named in
    # logs/metrics/timeline and escalated (HOROVOD_HEARTBEAT_SUSPECT_S).
    heartbeat_suspect_s: float = 5.0
    # Transient-fault absorption (native/resilience.py): max retries a
    # wire request survives before its connection fault escalates
    # (HOROVOD_NET_RETRIES; 0 disables the ladder — every blip is
    # fatal, the pre-PR 9 behavior).
    net_retries: int = 4
    # First backoff delay in ms; delay k doubles with seeded jitter
    # (HOROVOD_NET_BACKOFF_BASE_MS).
    net_backoff_base_ms: float = 25.0
    # Total retry time budget per logical request, seconds
    # (HOROVOD_NET_RETRY_BUDGET_S). MUST stay below the collective
    # timeout: retries may delay an escalation, never mask one. When
    # unset, from_env derives min(10, gloo_timeout/2) so a shortened
    # stall bound never invalidates the default.
    net_retry_budget_s: float = 10.0
    # Observability (horovod_tpu/obs): port for the stdlib /metrics +
    # /healthz exporter (HOROVOD_METRICS_PORT; 0 disables). In
    # multi-process mode each controller binds port + process_index so
    # co-located processes don't fight over one socket.
    metrics_port: int = 0
    # Seconds between periodic METRICS instant rows on the timeline
    # (HOROVOD_METRICS_TIMELINE_PERIOD; 0 disables). Only meaningful
    # while a timeline is active.
    metrics_timeline_period_s: float = 0.0
    # Native timeline writer (HOROVOD_TIMELINE_NATIVE): the csrc
    # stream-append writer behind Timeline; 0 falls back to the pure-
    # python writer. Read at timeline start (timeline.py) — declared
    # here so the knob registry + docs stay the single source.
    timeline_native: bool = True
    # Cross-host transport for the interop binding plane
    # (HOROVOD_PLANE_P2P): 1 (default) forms the wire-optimal p2p ring,
    # 0 falls back to the star-topology store comm (unroutable-peer
    # networks). Env-driven ONLY and must match on every rank — a
    # per-rank fallback would split one communicator across two
    # transports and deadlock it (native/store_comm.py).
    plane_p2p: bool = True
    # Device plane for the torch/tf/keras bindings
    # (HOROVOD_DEVICE_PLANE): "auto" activates only with TPU hardware
    # attached; "1"/"jax"/"on" force it; "0"/"off" disable.
    device_plane: str = "auto"
    # Payload bytes past which binding-plane collectives stage onto the
    # device mesh (HOROVOD_DEVICE_PLANE_THRESHOLD).
    device_plane_threshold: int = DEVICE_PLANE_THRESHOLD_DEFAULT
    # Global fill ratio the ragged alltoall must clear before riding
    # the device mesh (HOROVOD_DEVICE_ALLTOALL_MIN_FILL) — pad-to-max
    # inflates device traffic on skewed payloads.
    device_alltoall_min_fill: float = DEVICE_ALLTOALL_MIN_FILL_DEFAULT
    # Elastic driver discovery/worker poll period, seconds
    # (HOROVOD_ELASTIC_POLL_INTERVAL_S). The chaos soak raises it so
    # surviving workers get a full detection window before the reset.
    elastic_poll_interval_s: float = ELASTIC_POLL_INTERVAL_S_DEFAULT
    # Runtime lock-order witness (HOROVOD_ANALYSIS_WITNESS): 1
    # instruments threading.Lock/RLock creation in horovod_tpu and
    # fails tier-1 on a witnessed acquisition cycle
    # (horovod_tpu/analysis/witness.py, docs/analysis.md).
    analysis_witness: bool = False
    # Distributed request tracing over the serve fleet (HOROVOD_TRACE):
    # 1 arms the router-side TraceAssembler — span contexts minted at
    # admission, piggyback collection, leg attribution, tail sampling,
    # flight recorder (horovod_tpu/trace, docs/tracing.md). Workers
    # need no knob: they record for any message carrying a context.
    trace: bool = False
    # Head-sample rate in [0, 1] (HOROVOD_TRACE_SAMPLE): fraction of
    # requests whose FULL trace is retained even when nothing
    # interesting happened; tail sampling keeps the interesting ones
    # regardless.
    trace_sample: float = 0.0
    # Per-process span-ring capacity, total spans (HOROVOD_TRACE_RING):
    # a worker whose router never collects evicts oldest-trace-first
    # past this bound. The always-on local ring (trace/spans.py) holds
    # eight times as many and evicts its oldest span.
    trace_ring: int = 4096
    # Retained-trace ring on the router (HOROVOD_TRACE_RETAIN): the
    # last N tail-sampled traces kept for the flight recorder.
    trace_retain: int = 256
    # e2e milliseconds at/above which a request counts as SLOW and its
    # trace is retained (HOROVOD_TRACE_SLOW_MS).
    trace_slow_ms: float = 2000.0
    # Profiler trace annotations around collectives
    # (HOROVOD_DISABLE_NVTX_RANGES, mirroring the reference's NVTX
    # switch; read lazily in ops/collective_ops.py profiler_range).
    disable_nvtx_ranges: bool = False
    # Process sets (operations.cc:649 HOROVOD_DYNAMIC_PROCESS_SETS).
    dynamic_process_sets: bool = False
    # Grouped-op fusion (operations.cc:616 HOROVOD_DISABLE_GROUP_FUSION).
    disable_group_fusion: bool = False
    # Logging.
    log_level: str = "WARNING"
    # Launcher-provided identity (gloo_run.py:66-78 env contract).
    rank_env: Optional[int] = None
    size_env: Optional[int] = None
    local_rank_env: Optional[int] = None
    local_size_env: Optional[int] = None
    cross_rank_env: Optional[int] = None
    cross_size_env: Optional[int] = None

    @staticmethod
    def from_env() -> "Config":
        c = Config()
        mb = _env_float(  # knob: exempt (lenient by reference contract — horovod's env_parser falls back on malformed values for this legacy knob)
            "HOROVOD_FUSION_THRESHOLD", -1.0)
        if mb >= 0:
            c.fusion_threshold_bytes = int(mb)
        c.cycle_time_ms = _env_float(  # knob: exempt (lenient by reference contract — horovod's env_parser falls back on malformed values for this legacy knob)
            "HOROVOD_CYCLE_TIME", c.cycle_time_ms)
        c.cache_capacity = _env_int(  # knob: exempt (lenient by reference contract — horovod's env_parser falls back on malformed values for this legacy knob)
            "HOROVOD_CACHE_CAPACITY", c.cache_capacity)
        c.hierarchical_allreduce = _env_bool(
            "HOROVOD_HIERARCHICAL_ALLREDUCE", c.hierarchical_allreduce)
        c.hierarchical_allreduce_set = \
            "HOROVOD_HIERARCHICAL_ALLREDUCE" in os.environ
        c.hierarchical_allgather = _env_bool(
            "HOROVOD_HIERARCHICAL_ALLGATHER", c.hierarchical_allgather)
        c.torus_allreduce = _env_bool("HOROVOD_TORUS_ALLREDUCE", c.torus_allreduce)
        c.adasum_hierarchical = _env_bool(
            "HOROVOD_ADASUM_HIERARCHICAL", c.adasum_hierarchical)
        c.autotune = _env_bool("HOROVOD_AUTOTUNE", c.autotune)
        c.autotune_log = os.environ.get("HOROVOD_AUTOTUNE_LOG", c.autotune_log)
        c.autotune_warmup_samples = _env_int(  # knob: exempt (lenient by reference contract — horovod's env_parser falls back on malformed values for this legacy knob)
            "HOROVOD_AUTOTUNE_WARMUP_SAMPLES", c.autotune_warmup_samples)
        c.autotune_steps_per_sample = _env_int(  # knob: exempt (lenient by reference contract — horovod's env_parser falls back on malformed values for this legacy knob)
            "HOROVOD_AUTOTUNE_STEPS_PER_SAMPLE", c.autotune_steps_per_sample)
        c.autotune_bayes_opt_max_samples = _env_int(  # knob: exempt (lenient by reference contract — horovod's env_parser falls back on malformed values for this legacy knob)
            "HOROVOD_AUTOTUNE_BAYES_OPT_MAX_SAMPLES",
            c.autotune_bayes_opt_max_samples)
        noise = _env_float(  # knob: exempt (lenient by reference contract — horovod's env_parser falls back on malformed values for this legacy knob)
            "HOROVOD_AUTOTUNE_GAUSSIAN_PROCESS_NOISE", -1.0)
        if noise >= 0:
            c.autotune_gaussian_process_noise = noise
        c.gloo_timeout_seconds = _env_float(  # knob: exempt (lenient by reference contract — horovod's env_parser falls back on malformed values for this legacy knob)
            "HOROVOD_GLOO_TIMEOUT_SECONDS", c.gloo_timeout_seconds)
        c.log_with_timestamp = _env_bool(
            "HOROVOD_LOG_WITH_TIMESTAMP", c.log_with_timestamp)
        c.timeline_filename = os.environ.get("HOROVOD_TIMELINE", c.timeline_filename)
        c.timeline_mark_cycles = _env_bool(
            "HOROVOD_TIMELINE_MARK_CYCLES", c.timeline_mark_cycles)
        c.stall_check_disable = _env_bool(
            "HOROVOD_STALL_CHECK_DISABLE", c.stall_check_disable)
        c.stall_warning_time_seconds = _env_float(  # knob: exempt (lenient by reference contract — horovod's env_parser falls back on malformed values for this legacy knob)
            "HOROVOD_STALL_CHECK_TIME_SECONDS", c.stall_warning_time_seconds)
        c.stall_shutdown_time_seconds = _env_float(  # knob: exempt (lenient by reference contract — horovod's env_parser falls back on malformed values for this legacy knob)
            "HOROVOD_STALL_SHUTDOWN_TIME_SECONDS", c.stall_shutdown_time_seconds)
        c.compression = os.environ.get(
            "HOROVOD_COMPRESSION", c.compression).strip().lower()
        c.compression_set = "HOROVOD_COMPRESSION" in os.environ
        # strict since the analysis plane landed: PR 1 documented this
        # knob as fail-fast, but the parse was silently lenient — a
        # typo'd block size fell back to 128 and changed every wire
        # payload without a word (knob-registry lint finding)
        c.compression_block_size = _env_int_strict(
            "HOROVOD_COMPRESSION_BLOCK_SIZE", c.compression_block_size)
        c.compression_dcn_only = _env_bool(
            "HOROVOD_COMPRESSION_DCN_ONLY", c.compression_dcn_only)
        # Collective-algorithm knobs parse strictly (fail-fast contract):
        # a typo'd algorithm must fail at startup, not silently fall back
        # to "auto" and change which XLA programs a job launches.
        c.collective_algo = os.environ.get(
            "HOROVOD_COLLECTIVE_ALGO", c.collective_algo).strip().lower()
        c.collective_algo_set = "HOROVOD_COLLECTIVE_ALGO" in os.environ
        c.collective_algo_threshold_bytes = _env_int_strict(
            "HOROVOD_COLLECTIVE_ALGO_THRESHOLD",
            c.collective_algo_threshold_bytes)
        # Convergence-harness knobs parse strictly: a typo'd step count
        # or tolerance scale silently falling back would change what the
        # matrix gate actually proved.
        c.converge_steps = _env_int_strict(
            "HOROVOD_CONVERGE_STEPS", c.converge_steps)
        c.converge_batch = _env_int_strict(
            "HOROVOD_CONVERGE_BATCH", c.converge_batch)
        c.converge_seed = _env_int_strict(
            "HOROVOD_CONVERGE_SEED", c.converge_seed)
        c.converge_lr = _env_float_strict(
            "HOROVOD_CONVERGE_LR", c.converge_lr)
        c.converge_models = os.environ.get(
            "HOROVOD_CONVERGE_MODELS", c.converge_models).strip()
        c.converge_tol_scale = _env_float_strict(
            "HOROVOD_CONVERGE_TOL_SCALE", c.converge_tol_scale)
        # Serve knobs parse strictly (no silent default fallback): a
        # typo'd shape knob must fail at startup, not surface as a
        # recompile storm mid-traffic.
        c.serve_max_batch = _env_int_strict(
            "HOROVOD_SERVE_MAX_BATCH", c.serve_max_batch)
        c.serve_max_queue = _env_int_strict(
            "HOROVOD_SERVE_MAX_QUEUE", c.serve_max_queue)
        c.serve_deadline_ms = _env_float_strict(
            "HOROVOD_SERVE_DEADLINE_MS", c.serve_deadline_ms)
        raw_buckets = os.environ.get("HOROVOD_SERVE_BUCKETS")
        if raw_buckets is not None:
            try:
                c.serve_buckets = tuple(
                    int(x) for x in raw_buckets.split(",") if x.strip())
            except ValueError:
                raise ValueError(
                    f"HOROVOD_SERVE_BUCKETS must be a comma-separated "
                    f"list of ints; got {raw_buckets!r}")
        c.serve_kv_crc = _env_bool("HOROVOD_SERVE_KV_CRC",
                                   c.serve_kv_crc)
        c.serve_prefix_cache = _env_bool(
            "HOROVOD_SERVE_PREFIX_CACHE", c.serve_prefix_cache)
        c.serve_spec_k = _env_int_strict(
            "HOROVOD_SERVE_SPEC_K", c.serve_spec_k)
        c.serve_wire_max_frame = _env_int_strict(
            "HOROVOD_SERVE_WIRE_MAX_FRAME", c.serve_wire_max_frame)
        raw = os.environ.get("HOROVOD_SERVE_KERNEL")
        if raw is not None:
            c.serve_kernel = raw.strip().lower()
        c.serve_kvtier = _env_bool("HOROVOD_SERVE_KVTIER",
                                   c.serve_kvtier)
        c.serve_kvtier_host_mb = _env_int_strict(
            "HOROVOD_SERVE_KVTIER_HOST_MB", c.serve_kvtier_host_mb)
        raw = os.environ.get("HOROVOD_SERVE_KVTIER_DIR")
        if raw is not None:
            c.serve_kvtier_dir = raw.strip()
        # Autoscale knobs parse strictly (same contract): a typo'd
        # threshold must fail at startup — a policy silently running
        # with a default band would scale on bars nobody chose.
        c.autoscale = _env_bool("HOROVOD_AUTOSCALE", c.autoscale)
        c.autoscale_interval_s = _env_float_strict(
            "HOROVOD_AUTOSCALE_INTERVAL_S", c.autoscale_interval_s)
        c.autoscale_up_util = _env_float_strict(
            "HOROVOD_AUTOSCALE_UP_UTIL", c.autoscale_up_util)
        c.autoscale_down_util = _env_float_strict(
            "HOROVOD_AUTOSCALE_DOWN_UTIL", c.autoscale_down_util)
        c.autoscale_cooldown_up_s = _env_float_strict(
            "HOROVOD_AUTOSCALE_COOLDOWN_UP_S",
            c.autoscale_cooldown_up_s)
        c.autoscale_cooldown_down_s = _env_float_strict(
            "HOROVOD_AUTOSCALE_COOLDOWN_DOWN_S",
            c.autoscale_cooldown_down_s)
        c.autoscale_min_replicas = _env_int_strict(
            "HOROVOD_AUTOSCALE_MIN_REPLICAS",
            c.autoscale_min_replicas)
        c.autoscale_max_replicas = _env_int_strict(
            "HOROVOD_AUTOSCALE_MAX_REPLICAS",
            c.autoscale_max_replicas)
        c.autoscale_long_prompt_tokens = _env_int_strict(
            "HOROVOD_AUTOSCALE_LONG_PROMPT_TOKENS",
            c.autoscale_long_prompt_tokens)
        c.autoscale_long_prompt_frac = _env_float_strict(
            "HOROVOD_AUTOSCALE_LONG_PROMPT_FRAC",
            c.autoscale_long_prompt_frac)
        c.autoscale_ttft_slo_ms = _env_float_strict(
            "HOROVOD_AUTOSCALE_TTFT_SLO_MS", c.autoscale_ttft_slo_ms)
        # Ckpt knobs parse strictly (the PR 1-3 convention): a typo'd
        # depth/retention must fail at startup, not silently fall back
        # and change durability semantics mid-job.
        c.ckpt_snapshot_depth = _env_int_strict(
            "HOROVOD_CKPT_SNAPSHOT_DEPTH", c.ckpt_snapshot_depth)
        c.ckpt_max_to_keep = _env_int_strict(
            "HOROVOD_CKPT_MAX_TO_KEEP", c.ckpt_max_to_keep)
        c.ckpt_replicate = _env_bool(
            "HOROVOD_CKPT_REPLICATE", c.ckpt_replicate)
        c.ckpt_auto_restore = _env_bool(
            "HOROVOD_CKPT_AUTO_RESTORE", c.ckpt_auto_restore)
        c.redist_elastic = _env_bool(
            "HOROVOD_REDIST_ELASTIC", c.redist_elastic)
        c.redist_chunk_bytes = _env_int_strict(
            "HOROVOD_REDIST_CHUNK_BYTES", c.redist_chunk_bytes)
        # Chaos knobs parse strictly (same contract): a typo'd plan or
        # heartbeat period must fail at startup — a soak run that
        # silently injected nothing would "prove" recovery it never
        # exercised.
        c.chaos_plan = os.environ.get("HOROVOD_CHAOS_PLAN") or None
        c.heartbeat_interval_s = _env_float_strict(
            "HOROVOD_HEARTBEAT_INTERVAL_S", c.heartbeat_interval_s)
        c.heartbeat_suspect_s = _env_float_strict(
            "HOROVOD_HEARTBEAT_SUSPECT_S", c.heartbeat_suspect_s)
        # Net-resilience knobs parse strictly too: a typo'd retry count
        # must fail at startup — a job that silently ran without the
        # ladder would turn every blip back into a 17 s elastic reset.
        c.net_retries = _env_int_strict(
            "HOROVOD_NET_RETRIES", c.net_retries)
        c.net_backoff_base_ms = _env_float_strict(
            "HOROVOD_NET_BACKOFF_BASE_MS", c.net_backoff_base_ms)
        # the unset-budget default derives from the collective timeout
        # (min(10, timeout/2), native/resilience.py default_budget_s)
        # so shortening the stall bound never trips the budget-below-
        # timeout validation on a knob the deployment never set
        from ..native.resilience import default_budget_s
        c.net_retry_budget_s = _env_float_strict(
            "HOROVOD_NET_RETRY_BUDGET_S",
            default_budget_s(c.gloo_timeout_seconds))
        # Metrics knobs parse strictly too: a typo'd port must fail at
        # startup, not silently leave the fleet unobservable.
        c.metrics_port = _env_int_strict(
            "HOROVOD_METRICS_PORT", c.metrics_port)
        c.metrics_timeline_period_s = _env_float_strict(
            "HOROVOD_METRICS_TIMELINE_PERIOD", c.metrics_timeline_period_s)
        c.elastic_enabled = _env_bool("HOROVOD_ELASTIC", c.elastic_enabled)
        c.timeline_native = _env_bool(
            "HOROVOD_TIMELINE_NATIVE", c.timeline_native)
        c.plane_p2p = _env_bool("HOROVOD_PLANE_P2P", c.plane_p2p)
        c.device_plane = os.environ.get(
            "HOROVOD_DEVICE_PLANE", c.device_plane).strip().lower()
        c.device_plane_threshold = _env_int_strict(
            "HOROVOD_DEVICE_PLANE_THRESHOLD", c.device_plane_threshold)
        c.device_alltoall_min_fill = _env_float_strict(
            "HOROVOD_DEVICE_ALLTOALL_MIN_FILL",
            c.device_alltoall_min_fill)
        c.elastic_poll_interval_s = _env_float_strict(
            "HOROVOD_ELASTIC_POLL_INTERVAL_S", c.elastic_poll_interval_s)
        c.analysis_witness = _env_bool(
            "HOROVOD_ANALYSIS_WITNESS", c.analysis_witness)
        c.trace = _env_bool("HOROVOD_TRACE", c.trace)
        c.trace_sample = _env_float_strict(
            "HOROVOD_TRACE_SAMPLE", c.trace_sample)
        c.trace_ring = _env_int_strict(
            "HOROVOD_TRACE_RING", c.trace_ring)
        c.trace_retain = _env_int_strict(
            "HOROVOD_TRACE_RETAIN", c.trace_retain)
        c.trace_slow_ms = _env_float_strict(
            "HOROVOD_TRACE_SLOW_MS", c.trace_slow_ms)
        c.disable_nvtx_ranges = _env_bool(
            "HOROVOD_DISABLE_NVTX_RANGES", c.disable_nvtx_ranges)
        c.dynamic_process_sets = _env_bool(
            "HOROVOD_DYNAMIC_PROCESS_SETS", c.dynamic_process_sets)
        c.disable_group_fusion = _env_bool(
            "HOROVOD_DISABLE_GROUP_FUSION", c.disable_group_fusion)
        c.log_level = os.environ.get("HOROVOD_LOG_LEVEL", c.log_level).upper()

        def _opt_int(name):
            v = os.environ.get(name)
            return int(v) if v is not None and v != "" else None

        c.rank_env = _opt_int("HOROVOD_RANK")
        c.size_env = _opt_int("HOROVOD_SIZE")
        c.local_rank_env = _opt_int("HOROVOD_LOCAL_RANK")
        c.local_size_env = _opt_int("HOROVOD_LOCAL_SIZE")
        c.cross_rank_env = _opt_int("HOROVOD_CROSS_RANK")
        c.cross_size_env = _opt_int("HOROVOD_CROSS_SIZE")
        c.validate()
        return c

    def validate(self) -> None:
        """Fail fast with actionable messages instead of deep inside the
        engine (a bad fusion threshold used to surface as a bucketization
        TypeError cycles later)."""
        if self.compression not in ("none", "bf16", "int8"):
            raise ValueError(
                f"HOROVOD_COMPRESSION must be one of 'none'|'bf16'|'int8'; "
                f"got {self.compression!r}")
        bs = self.compression_block_size
        if not isinstance(bs, int) or not (8 <= bs <= 1 << 20):
            raise ValueError(
                f"HOROVOD_COMPRESSION_BLOCK_SIZE must be an int in "
                f"[8, {1 << 20}] (one fp32 scale travels per block); "
                f"got {bs!r}")
        from ..ops.algo import ALGO_CHOICES, ALGORITHMS
        if self.collective_algo not in ALGO_CHOICES:
            raise ValueError(
                f"HOROVOD_COLLECTIVE_ALGO must be one of "
                f"{'|'.join(ALGO_CHOICES)}; got {self.collective_algo!r}")
        for knob in ("collective_algo_small", "collective_algo_large"):
            v = getattr(self, knob)
            if v and v not in ALGORITHMS:
                raise ValueError(
                    f"{knob} must be empty or one of "
                    f"{'|'.join(ALGORITHMS)}; got {v!r}")
        at = self.collective_algo_threshold_bytes
        if not isinstance(at, int) or at < 0:
            raise ValueError(
                f"HOROVOD_COLLECTIVE_ALGO_THRESHOLD must be a "
                f"non-negative byte count (0 uses the analytic "
                f"crossover); got {at!r}")
        ft = self.fusion_threshold_bytes
        if not isinstance(ft, int) or ft < 0:
            raise ValueError(
                f"HOROVOD_FUSION_THRESHOLD must be a non-negative byte "
                f"count (0 disables fusion); got {ft!r}")
        ct = self.cycle_time_ms
        if not isinstance(ct, (int, float)) or not (0 <= ct < 60_000):
            raise ValueError(
                f"HOROVOD_CYCLE_TIME must be milliseconds in [0, 60000); "
                f"got {ct!r}")
        if not isinstance(self.cache_capacity, int) or \
                self.cache_capacity < 0:
            raise ValueError(
                f"HOROVOD_CACHE_CAPACITY must be a non-negative int; got "
                f"{self.cache_capacity!r}")
        if not isinstance(self.converge_steps, int) or \
                not (1 <= self.converge_steps <= 100_000):
            raise ValueError(
                f"HOROVOD_CONVERGE_STEPS must be an int in [1, 100000]; "
                f"got {self.converge_steps!r}")
        if not isinstance(self.converge_batch, int) or \
                not (1 <= self.converge_batch <= 4096):
            raise ValueError(
                f"HOROVOD_CONVERGE_BATCH must be an int in [1, 4096]; "
                f"got {self.converge_batch!r}")
        if not isinstance(self.converge_seed, int) or \
                self.converge_seed < 0:
            raise ValueError(
                f"HOROVOD_CONVERGE_SEED must be a non-negative int; got "
                f"{self.converge_seed!r}")
        lr = self.converge_lr
        if not isinstance(lr, (int, float)) or not (0 <= lr <= 100):
            raise ValueError(
                f"HOROVOD_CONVERGE_LR must be a learning rate in "
                f"[0, 100] (0 = per-model calibrated rate); got {lr!r}")
        if not isinstance(self.converge_models, str) or \
                not self.converge_models.strip():
            raise ValueError(
                f"HOROVOD_CONVERGE_MODELS must be a non-empty "
                f"comma-separated list of models/bench_zoo.py "
                f"CONVERGE_MODELS rows; got {self.converge_models!r}")
        ts = self.converge_tol_scale
        if not isinstance(ts, (int, float)) or not (0 < ts <= 100):
            raise ValueError(
                f"HOROVOD_CONVERGE_TOL_SCALE must be a tolerance "
                f"multiplier in (0, 100]; got {ts!r}")
        if not isinstance(self.serve_max_batch, int) or \
                not (1 <= self.serve_max_batch <= 4096):
            raise ValueError(
                f"HOROVOD_SERVE_MAX_BATCH must be an int in [1, 4096] "
                f"(the fixed decode batch shape); got "
                f"{self.serve_max_batch!r}")
        if not isinstance(self.serve_max_queue, int) or \
                self.serve_max_queue < 1:
            raise ValueError(
                f"HOROVOD_SERVE_MAX_QUEUE must be a positive int; got "
                f"{self.serve_max_queue!r}")
        dl = self.serve_deadline_ms
        if not isinstance(dl, (int, float)) or not (0 < dl <= 86_400_000):
            raise ValueError(
                f"HOROVOD_SERVE_DEADLINE_MS must be milliseconds in "
                f"(0, 86400000]; got {dl!r}")
        if not isinstance(self.serve_kv_crc, bool):
            raise ValueError(
                f"HOROVOD_SERVE_KV_CRC must be a boolean; got "
                f"{self.serve_kv_crc!r}")
        if not isinstance(self.serve_prefix_cache, bool):
            raise ValueError(
                f"HOROVOD_SERVE_PREFIX_CACHE must be a boolean; got "
                f"{self.serve_prefix_cache!r}")
        sk = self.serve_spec_k
        if not isinstance(sk, int) or not (0 <= sk <= 64):
            raise ValueError(
                f"HOROVOD_SERVE_SPEC_K must be an int in [0, 64] (the "
                f"verify step's shape is [max_batch, spec_k+1] — it "
                f"joins the precompiled bucket set); got {sk!r}")
        wf = self.serve_wire_max_frame
        if not isinstance(wf, int) or \
                not (1 << 16 <= wf <= (1 << 31) - 1):
            raise ValueError(
                f"HOROVOD_SERVE_WIRE_MAX_FRAME must be bytes in "
                f"[{1 << 16}, {(1 << 31) - 1}] (the serve wire frame "
                f"ceiling; bit 31 of the length word is the binary-"
                f"frame flag, so a full 2 GiB frame cannot be "
                f"represented); got {wf!r}")
        if self.serve_kernel not in ("auto", "pallas", "xla"):
            raise ValueError(
                f"HOROVOD_SERVE_KERNEL must be 'auto', 'pallas' or "
                f"'xla' (the paged decode attention kernel — resolved "
                f"once at executor build); got {self.serve_kernel!r}")
        if not isinstance(self.serve_kvtier, bool):
            raise ValueError(
                f"HOROVOD_SERVE_KVTIER must be a boolean; got "
                f"{self.serve_kvtier!r}")
        hm = self.serve_kvtier_host_mb
        if not isinstance(hm, int) or not (0 <= hm <= 1_048_576):
            raise ValueError(
                f"HOROVOD_SERVE_KVTIER_HOST_MB must be MiB in "
                f"[0, 1048576] (the host-RAM ring bound for demoted KV "
                f"blocks; 0 spills every demotion straight to disk or "
                f"drops it); got {hm!r}")
        if not isinstance(self.serve_kvtier_dir, str):
            raise ValueError(
                f"HOROVOD_SERVE_KVTIER_DIR must be a directory path "
                f"string ('' disables the disk tier); got "
                f"{self.serve_kvtier_dir!r}")
        if not isinstance(self.autoscale, bool):
            raise ValueError(
                f"HOROVOD_AUTOSCALE must be a boolean; got "
                f"{self.autoscale!r}")
        ai = self.autoscale_interval_s
        if not isinstance(ai, (int, float)) or not (0 < ai <= 3600):
            raise ValueError(
                f"HOROVOD_AUTOSCALE_INTERVAL_S must be seconds in "
                f"(0, 3600]; got {ai!r}")
        au, ad = self.autoscale_up_util, self.autoscale_down_util
        if not isinstance(au, (int, float)) or not (0 < au <= 1):
            raise ValueError(
                f"HOROVOD_AUTOSCALE_UP_UTIL must be a utilization in "
                f"(0, 1]; got {au!r}")
        if not isinstance(ad, (int, float)) or not (0 <= ad < 1):
            raise ValueError(
                f"HOROVOD_AUTOSCALE_DOWN_UTIL must be a utilization "
                f"in [0, 1); got {ad!r}")
        if ad >= au:
            raise ValueError(
                f"HOROVOD_AUTOSCALE_DOWN_UTIL ({ad!r}) must be below "
                f"HOROVOD_AUTOSCALE_UP_UTIL ({au!r}) — the gap is the "
                f"hysteresis band; an empty band thrashes")
        for name, v in (("HOROVOD_AUTOSCALE_COOLDOWN_UP_S",
                         self.autoscale_cooldown_up_s),
                        ("HOROVOD_AUTOSCALE_COOLDOWN_DOWN_S",
                         self.autoscale_cooldown_down_s)):
            if not isinstance(v, (int, float)) or not (0 <= v <= 86_400):
                raise ValueError(
                    f"{name} must be seconds in [0, 86400]; got {v!r}")
        amin = self.autoscale_min_replicas
        amax = self.autoscale_max_replicas
        if not isinstance(amin, int) or not (1 <= amin <= 4096):
            raise ValueError(
                f"HOROVOD_AUTOSCALE_MIN_REPLICAS must be an int in "
                f"[1, 4096]; got {amin!r}")
        if not isinstance(amax, int) or not (amin <= amax <= 4096):
            raise ValueError(
                f"HOROVOD_AUTOSCALE_MAX_REPLICAS must be an int in "
                f"[{amin}, 4096] (>= the replica floor); got {amax!r}")
        lt = self.autoscale_long_prompt_tokens
        if not isinstance(lt, int) or not (1 <= lt <= 1_000_000):
            raise ValueError(
                f"HOROVOD_AUTOSCALE_LONG_PROMPT_TOKENS must be an int "
                f"in [1, 1000000]; got {lt!r}")
        lf = self.autoscale_long_prompt_frac
        if not isinstance(lf, (int, float)) or not (0 < lf <= 1):
            raise ValueError(
                f"HOROVOD_AUTOSCALE_LONG_PROMPT_FRAC must be a "
                f"fraction in (0, 1]; got {lf!r}")
        ts = self.autoscale_ttft_slo_ms
        if not isinstance(ts, (int, float)) or not (0 < ts <= 86_400_000):
            raise ValueError(
                f"HOROVOD_AUTOSCALE_TTFT_SLO_MS must be milliseconds "
                f"in (0, 86400000]; got {ts!r}")
        mp = self.metrics_port
        if not isinstance(mp, int) or not (0 <= mp <= 65535):
            raise ValueError(
                f"HOROVOD_METRICS_PORT must be an int in [0, 65535] "
                f"(0 disables the exporter); got {mp!r}")
        mtp = self.metrics_timeline_period_s
        if not isinstance(mtp, (int, float)) or not (0 <= mtp <= 86_400):
            raise ValueError(
                f"HOROVOD_METRICS_TIMELINE_PERIOD must be seconds in "
                f"[0, 86400] (0 disables); got {mtp!r}")
        tsr = self.trace_sample
        if not isinstance(tsr, (int, float)) or not (0 <= tsr <= 1):
            raise ValueError(
                f"HOROVOD_TRACE_SAMPLE must be a fraction in [0, 1]; "
                f"got {tsr!r}")
        tring = self.trace_ring
        if not isinstance(tring, int) or not (1 <= tring <= 10_000_000):
            raise ValueError(
                f"HOROVOD_TRACE_RING must be an int in [1, 10000000] "
                f"(total spans buffered per process); got {tring!r}")
        tret = self.trace_retain
        if not isinstance(tret, int) or not (1 <= tret <= 1_000_000):
            raise ValueError(
                f"HOROVOD_TRACE_RETAIN must be an int in [1, 1000000] "
                f"(tail-sampled traces kept); got {tret!r}")
        tslow = self.trace_slow_ms
        if not isinstance(tslow, (int, float)) \
                or not (0 < tslow <= 86_400_000):
            raise ValueError(
                f"HOROVOD_TRACE_SLOW_MS must be milliseconds in "
                f"(0, 86400000]; got {tslow!r}")
        sd = self.ckpt_snapshot_depth
        if not isinstance(sd, int) or not (1 <= sd <= 64):
            raise ValueError(
                f"HOROVOD_CKPT_SNAPSHOT_DEPTH must be an int in [1, 64] "
                f"(in-flight host snapshots, each a full tree copy); "
                f"got {sd!r}")
        mk = self.ckpt_max_to_keep
        if not isinstance(mk, int) or not (0 <= mk <= 1_000_000):
            raise ValueError(
                f"HOROVOD_CKPT_MAX_TO_KEEP must be an int in "
                f"[0, 1000000] (0 keeps every checkpoint); got {mk!r}")
        rc = self.redist_chunk_bytes
        if not isinstance(rc, int) or not (4096 <= rc <= 1 << 31):
            raise ValueError(
                f"HOROVOD_REDIST_CHUNK_BYTES must be an int in "
                f"[4096, {1 << 31}] (per-rank bytes per "
                f"redistribution round); got {rc!r}")
        hi = self.heartbeat_interval_s
        if not isinstance(hi, (int, float)) or not (0 <= hi <= 3600):
            raise ValueError(
                f"HOROVOD_HEARTBEAT_INTERVAL_S must be seconds in "
                f"[0, 3600] (0 disables the failure detector); got {hi!r}")
        hs = self.heartbeat_suspect_s
        if not isinstance(hs, (int, float)) or not (0 < hs <= 86_400):
            raise ValueError(
                f"HOROVOD_HEARTBEAT_SUSPECT_S must be seconds in "
                f"(0, 86400]; got {hs!r}")
        if hi > 0 and hs <= hi:
            raise ValueError(
                f"HOROVOD_HEARTBEAT_SUSPECT_S ({hs!r}) must exceed "
                f"HOROVOD_HEARTBEAT_INTERVAL_S ({hi!r}) — a suspect "
                f"threshold at or under one heartbeat period flags "
                f"every healthy peer")
        nr = self.net_retries
        if not isinstance(nr, int) or not (0 <= nr <= 100):
            raise ValueError(
                f"HOROVOD_NET_RETRIES must be an int in [0, 100] "
                f"(0 disables the retry ladder); got {nr!r}")
        nb = self.net_backoff_base_ms
        if not isinstance(nb, (int, float)) or not (0 < nb <= 60_000):
            raise ValueError(
                f"HOROVOD_NET_BACKOFF_BASE_MS must be milliseconds in "
                f"(0, 60000]; got {nb!r}")
        nbd = self.net_retry_budget_s
        if not isinstance(nbd, (int, float)) or not (0 < nbd <= 86_400):
            raise ValueError(
                f"HOROVOD_NET_RETRY_BUDGET_S must be seconds in "
                f"(0, 86400]; got {nbd!r}")
        if nr > 0 and nbd >= self.gloo_timeout_seconds:
            raise ValueError(
                f"HOROVOD_NET_RETRY_BUDGET_S ({nbd!r}) must stay BELOW "
                f"the collective timeout "
                f"HOROVOD_GLOO_TIMEOUT_SECONDS "
                f"({self.gloo_timeout_seconds!r}) — the retry ladder "
                f"may delay an escalation, never mask one")
        if self.device_plane not in ("auto", "0", "off", "false", "no",
                                     "1", "jax", "on", "true", "yes"):
            raise ValueError(
                f"HOROVOD_DEVICE_PLANE must be 'auto', an off value "
                f"('0'|'off'|'false'|'no') or a force value "
                f"('1'|'jax'|'on'|'true'|'yes'); got "
                f"{self.device_plane!r}")
        dpt = self.device_plane_threshold
        if not isinstance(dpt, int) or dpt < 0:
            raise ValueError(
                f"HOROVOD_DEVICE_PLANE_THRESHOLD must be a non-negative "
                f"byte count; got {dpt!r}")
        mf = self.device_alltoall_min_fill
        if not isinstance(mf, (int, float)) or not (0 <= mf <= 1):
            raise ValueError(
                f"HOROVOD_DEVICE_ALLTOALL_MIN_FILL must be a fill "
                f"ratio in [0, 1]; got {mf!r}")
        ep = self.elastic_poll_interval_s
        if not isinstance(ep, (int, float)) or not (0 < ep <= 3600):
            raise ValueError(
                f"HOROVOD_ELASTIC_POLL_INTERVAL_S must be seconds in "
                f"(0, 3600]; got {ep!r}")
        if self.chaos_plan is not None:
            # full fail-fast parse (schema + kind/site/schedule
            # validation) — chaos.plan is stdlib-only, no cycle
            from ..chaos.plan import ChaosPlan, PlanError
            try:
                ChaosPlan.parse(self.chaos_plan)
            except PlanError as e:
                raise ValueError(f"HOROVOD_CHAOS_PLAN invalid: {e}") \
                    from None
        bk = self.serve_buckets
        if (not isinstance(bk, (tuple, list)) or not bk
                or not all(isinstance(b, int) and b > 0 for b in bk)
                or list(bk) != sorted(set(bk))):
            raise ValueError(
                f"HOROVOD_SERVE_BUCKETS must be strictly ascending "
                f"positive ints (one prefill program compiles per "
                f"bucket); got {bk!r}")
