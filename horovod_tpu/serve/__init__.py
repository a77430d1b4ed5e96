"""horovod_tpu.serve: TPU-native continuous-batching inference.

The first request-path subsystem of the tree: an Orca/vLLM-style
continuous batcher over the pjit-sharded decoder models, reusing the
training stack's mesh/TP machinery for the forward path and the
timeline for observability. See docs/serving.md for the architecture
and the bucket/no-recompile contract.

    queue.py     admission control: bounded queue, deadlines, load shed
    kv_cache.py  KV storage: one vLLM-style block pool read through
                 per-row tables (BlockPool free-list allocator,
                 per-block crc ledger)
    prefix.py    radix prefix cache: shared system prompts computed
                 once, refcounted block runs, CoW at divergence, LRU
                 eviction, weight-version flush
    batcher.py   iteration-level scheduler over fixed bucket shapes,
                 with optional speculative decoding (draft proposes k,
                 target verifies in one fused step; greedy accept is
                 bit-identical, sampled accept is rejection-sampling
                 distribution-correct)
    executor.py  the one jitted step, sharded via parallel/tp rules;
                 decode kernel (HOROVOD_SERVE_KERNEL: fused Pallas vs
                 XLA oracle, ops/pallas_paged.py) and on-device
                 sampling (temperature/top-p, per-request seeds as
                 row data) resolved/fused at build
    http.py      optional stdlib front end (/generate, /healthz)
    fleet.py     health-aware router over N replicas: accrual-driven
                 ejection, at-most-once failover, drain-on-SIGTERM,
                 re-admission on fresh streamed weights
    wire.py      framed dispatch protocol + retryable-vs-fatal
                 classification for the multi-process fleet
    worker.py    one replica as one OS process: endpoint with replay
                 dedupe, KV heartbeats, startup weight gate
    proc_fleet.py multi-process fleet router: accrual sweep over real
                 heartbeat keys, dispatch over the resilience ladder,
                 SIGKILL-survivable respawn gated on fresh weights
    disagg.py    prefill/decode DISAGGREGATED serving: two dedicated
                 worker-process pools, prompt KV computed in the
                 prefill pool and MIGRATED block-by-block to a decode
                 replica (bit-identical continuation, bounded
                 re-prefill on any failure, per-pool healthz)
    kv_migrate.py live paged-KV block migration: pack/verify/install
                 with per-block crc32 ledgers, binary wire frames and
                 weight-version fencing (plan/transport split)
    kvtier/      fleet-wide KV tier: router-side radix index over
                 cached prefix runs (cross-replica prefix routing +
                 run pulls) and the per-replica HBM -> host-RAM ->
                 disk eviction ladder with crc-verified promotion
                 and weight-version fencing
    soak.py      serving SLO soaks under seeded chaos plans — in-
                 process, multi-process and disaggregated
                 (tools/serve_soak.py CLI; docs/serving.md)
"""
from .batcher import ContinuousBatcher, ReplicaDead            # noqa: F401
from .disagg import DisaggRouter                               # noqa: F401
from .executor import ShardedExecutor                          # noqa: F401
from .fleet import FleetHandle, FleetRouter, Replica           # noqa: F401
from .http import (                                            # noqa: F401
    make_fleet_server, make_server, retry_after_seconds, serve_http,
)
from .proc_fleet import ProcessFleetRouter, ProcessReplica     # noqa: F401
from .kv_cache import (                                        # noqa: F401
    BlockPool, PagedKVCache, masked_attention, paged_attention,
    pool_blocks_for, write_kv_paged,
)
from .kvtier import (                                          # noqa: F401
    DiskTier, FleetRadixIndex, HostRing, ReplicaKVTier, TierEntry,
    prefer_holders, read_spill_file,
)
from .prefix import RadixPrefixCache                           # noqa: F401
from .queue import (                                           # noqa: F401
    AdmissionQueue, AdmitDropped, Rejected, ServeHandle, ServeRequest,
)
