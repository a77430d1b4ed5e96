"""horovod_tpu: a TPU-native distributed training framework.

A ground-up re-design of Horovod's capabilities (reference: ERerGB/horovod)
for TPUs: the data plane is XLA collectives over the ICI mesh emitted from
shard_map/pjit programs; process sets are device sub-meshes; the async engine
buckets requests into fused jitted collectives; elastic/launcher/timeline/
autotune subsystems mirror the reference's behavior with TPU-idiomatic
internals.

Public API mirrors `import horovod.torch as hvd`:

    import horovod_tpu as hvd
    hvd.init()
    out = hvd.allreduce(stacked_grads)        # sync
    h = hvd.allreduce_async(stacked_grads)    # async (fused by the engine)
    out = hvd.synchronize(h)
"""
import time as _time
_T_IMPORT = _time.monotonic()       # where the `hvd_import` span starts

# The runtime lock-order witness must arm BEFORE any horovod_tpu
# module creates a lock, so it comes first (no-op unless
# HOROVOD_ANALYSIS_WITNESS=1; stdlib-only import — docs/analysis.md).
from .analysis import witness as _witness                      # noqa: F401
_witness.maybe_install()

from .core.types import (                                      # noqa: F401
    ReduceOp, Average, Sum, Adasum, Min, Max, Product,
    Status, StatusType, HorovodInternalError, HostsUpdatedInterrupt,
    DuplicateNameError,
)
from .core.basics import (                                     # noqa: F401
    init, shutdown, is_initialized,
    size, rank, stacked_rank, local_size, local_rank, cross_size,
    cross_rank, is_homogeneous,
    mpi_threads_supported, mpi_built, mpi_enabled, gloo_built, gloo_enabled,
    nccl_built, ddl_built, ccl_built, cuda_built, rocm_built,
    tpu_built, tpu_enabled,
    add_process_set, remove_process_set, get_process_set_ids_and_ranks,
    process_set_included, start_timeline, stop_timeline,
)
from .core.process_sets import ProcessSet, global_process_set  # noqa: F401
from .core.mesh import (                                       # noqa: F401
    GLOBAL_AXIS, CROSS_AXIS, LOCAL_AXIS, shard_stacked,
)
from .ops.collective_ops import (                              # noqa: F401
    allreduce, allgather, broadcast, alltoall, reducescatter, barrier, join,
    local_rows, quantized_allgather, quantized_reducescatter,
    quantized_alltoall,
)
from .ops.sparse import (                                      # noqa: F401
    sparse_allreduce, sparse_allreduce_async)
from .ops import inside                                        # noqa: F401
from .ops.engine import (                                      # noqa: F401
    allreduce_async, allgather_async, broadcast_async, alltoall_async,
    reducescatter_async, grouped_allreduce, grouped_allreduce_async,
    grouped_allgather, grouped_allgather_async, grouped_reducescatter,
    grouped_reducescatter_async, synchronize, poll, wait,
)
from .optim.compression import Compression                     # noqa: F401
from .optim.optimizer import (                                 # noqa: F401
    DistributedOptimizer, DistributedGradientTape, distributed_grad,
    allreduce_gradients, PartialDistributedGradientTape,
)
from .optim.functions import (                                 # noqa: F401
    broadcast_parameters, broadcast_object, allgather_object,
    broadcast_optimizer_state, broadcast_variables,
)

from . import chaos                                            # noqa: F401
from . import elastic                                          # noqa: F401
from . import obs                                              # noqa: F401
from .obs import metrics_report                                # noqa: F401
from . import serve                                            # noqa: F401
from .runner.api import run                                    # noqa: F401
from . import checkpoint                                       # noqa: F401
from .checkpoint import (                                      # noqa: F401
    Checkpointer, save_checkpoint, restore_checkpoint,
)
from . import ckpt                                             # noqa: F401
from .ckpt import ShardedCheckpointer                          # noqa: F401
from . import redist                                           # noqa: F401
from .redist import redistribute                               # noqa: F401

__version__ = "0.2.0"

# the import itself, into the process's span ring (docs/tracing.md)
from .trace.spans import get_recorder as _get_recorder          # noqa: E402
_get_recorder().record_local("hvd_import", _T_IMPORT, _time.monotonic())
