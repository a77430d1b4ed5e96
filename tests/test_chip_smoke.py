"""chip_smoke.py, checked where no chip is: the script refuses to run
without a TPU (and without the repo around it), and its phase functions
pass at tiny widths with interpret-mode kernels on the 8-device CPU mesh
— so a chip call is never spent on a Python error. The compile-cache
helper's placement rule is pinned here too.
"""
import os
import shutil
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _ROOT)
import chip_smoke  # noqa: E402

_TINY = dict(vocab_size=128, num_layers=2, num_heads=4, head_dim=8,
             max_seq_len=64)
_TINY_SERVE = dict(max_batch=4, kv_block=4, buckets=(8, 32), new_tokens=4,
                   shared_prefix=12,
                   waves=((20, 5, 30, 9), (14, 18, 16, 7)),
                   sharers=((0,), (0, 1, 2)))


def _run_smoke(cwd, script):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    return subprocess.run([sys.executable, script], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=120)


def test_refuses_without_tpu_naming_the_platform():
    out = _run_smoke(_ROOT, "chip_smoke.py")
    assert out.returncode != 0
    assert "'cpu'" in out.stderr
    assert out.stdout == ""          # no result line, no phase line


def test_refuses_outside_the_checkout(tmp_path):
    shutil.copy(os.path.join(_ROOT, "chip_smoke.py"), tmp_path)
    out = _run_smoke(tmp_path, "chip_smoke.py")
    assert out.returncode != 0
    assert out.stdout == ""


@pytest.fixture()
def log():
    lg = chip_smoke.CompileLog()
    yield lg
    lg.close()


def test_compile_log_counts_backend_compiles(log):
    jax.jit(lambda x: x * 3 + 1)(jnp.arange(7))
    assert log.programs >= 1 and log.seconds > 0
    mark = log.mark()
    assert log.since(mark)["programs_compiled"] == 0


def test_collectives_phase(hvd, log):
    rep = chip_smoke.collectives_phase(log)
    assert rep["ranks"] == 8


@pytest.fixture(scope="module")
def trained():
    """(report, params) of one tiny train phase, shared by the tests
    that need trained weights."""
    import horovod_tpu as hvd
    hvd.init()
    lg = chip_smoke.CompileLog()
    try:
        return chip_smoke.train_phase(
            lg, _TINY, per_chip_batch=1, steps=3, learning_rate=1e-3,
            attention_impl="interpret", custom_call=None)
    finally:
        lg.close()
        hvd.shutdown()


def test_train_phase_on_the_cpu_mesh(trained):
    rep, _ = trained
    assert rep["mesh"] == {"dp": 8} and rep["global_batch"] == 8
    assert rep["loss"][-1] < rep["loss"][0]
    assert rep["devices"]["params"] == list(range(8))
    assert rep["devices"]["batch"] == list(range(8))


def test_train_phase_demands_the_custom_call(hvd, log):
    # with the reference attention there is no Pallas call to find: the
    # check that guards "the flash kernel is what ran" must fire
    with pytest.raises(AssertionError, match="tpu_custom_call"):
        chip_smoke.train_phase(log, _TINY, per_chip_batch=1, steps=1,
                               learning_rate=1e-3, attention_impl="reference")


def test_ce_kernel_phase(log):
    rep = chip_smoke.ce_kernel_phase(log, 64, 128, interpret=True)
    assert rep["grad_err_rel"] <= rep["tolerance_rel"]


def test_serve_phase_with_the_pallas_kernel(trained, log):
    _, params = trained
    params = jax.device_put(params, jax.devices()[0])
    rep = chip_smoke.serve_phase(log, _TINY, params, _TINY_SERVE,
                                 decode_kernel="pallas")
    assert rep["kernel"] == "pallas" and rep["requests"] == 8
    assert rep["prefix_hits"] >= 1
    assert rep["devices"]["kv_pool"] == [0]
    assert {"prefill:8", "prefill:32", "decode:1"} <= set(rep["steps"])


def test_paged_kernel_phase(log):
    rep = chip_smoke.paged_kernel_phase(
        log, num_heads=4, num_kv_heads=2, head_dim=8, max_len=32,
        max_batch=3, kv_block=4)
    assert rep["err_rel"] == {"T1": 0.0, "T4": 0.0}   # interpret: bit-exact


# -- the compile-cache helper ------------------------------------------------

@pytest.fixture()
def cache_config():
    """Restore the three cache settings so the rest of the suite does
    not start writing a persistent cache."""
    names = ("jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs",
             "jax_persistent_cache_min_entry_size_bytes")
    saved = {n: getattr(jax.config, n) for n in names}
    yield
    for n, v in saved.items():
        jax.config.update(n, v)


def test_cache_dir_from_the_environment_is_left_alone(monkeypatch,
                                                      cache_config):
    from horovod_tpu.compile_cache import enable_compile_cache
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/outside")
    assert enable_compile_cache() == "/somewhere/outside"
    assert jax.config.jax_compilation_cache_dir == before


def test_cache_dir_defaults_to_the_checkout(monkeypatch, cache_config):
    from horovod_tpu.compile_cache import enable_compile_cache
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    want = os.path.join(_ROOT, ".jax_cache")
    assert enable_compile_cache() == want
    assert jax.config.jax_compilation_cache_dir == want
