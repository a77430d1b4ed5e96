"""Test configuration: force an 8-device CPU platform so every collective
test exercises a real multi-device mesh without TPU hardware (the analog of
the reference running parallel tests under mpirun -np N,
.buildkite/gen-pipeline.sh:140).

The platform is pinned through jax.config as well as the environment, so a
bare `python -m pytest` (no JAX_PLATFORMS) still lands on the CPU mesh.
"""
import os

os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "")
    + " --xla_force_host_platform_device_count=8").strip()

# Collective-op stall bound for the binding plane (reference
# HOROVOD_GLOO_TIMEOUT_SECONDS). The product default (60 s shm / 300 s
# store) is right for real jobs, but a full-suite run oversubscribes
# this 1-core container so badly that a worker can be starved past 60 s
# INSIDE a barrier — the one observed suite flake
# (test_keras_estimator_multiprocess). Children of every multiprocess
# test inherit this.
os.environ.setdefault("HOROVOD_GLOO_TIMEOUT_SECONDS", "600")

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import pytest  # noqa: E402


@pytest.fixture()
def hvd():
    import horovod_tpu as hvd
    hvd.init()
    yield hvd
    hvd.shutdown()


# --------------------------------------------------------------------------
# runtime lock-order witness (docs/analysis.md): opt-in via
#   HOROVOD_ANALYSIS_WITNESS=1 python -m pytest tests/... -q
# Locks created by horovod_tpu modules are instrumented for the whole
# session (armed at horovod_tpu import, above); the teardown assertion
# fails the run on any witnessed acquisition cycle.
# --------------------------------------------------------------------------
from horovod_tpu.core.config import _env_bool as _hvd_env_bool  # noqa: E402

if _hvd_env_bool("HOROVOD_ANALYSIS_WITNESS", False):
    from horovod_tpu.analysis import witness as _witness
    _witness.install()

    @pytest.fixture(scope="session", autouse=True)
    def _lock_order_witness():
        yield
        _witness.check()   # raises WitnessCycleError on a cycle
