#!/usr/bin/env python
"""chip_smoke.py — the train -> serve main path, once, on the TPU.

The quickest proof that the system still starts on the chip. ONE process
(a parent that has touched JAX holds the chip, so nothing is spawned)
drives every local chip through the entry points a user calls, at the
full width of the GPT-2-small shape (163M parameters, bf16 compute,
random weights from a seed):

* **collectives**  `hvd.allreduce` and `hvd.allreduce_async` +
  `hvd.synchronize` (the engine's fused pack/unpack programs) against
  the numpy sum — trivial on one chip, real on four.
* **train**  `make_mesh(dp=n)` -> `shard_params` ->
  `make_gspmd_train_step`, 5 steps of 8 x 1024 tokens per chip on one
  fixed batch: loss finite and falling, the flash-attention Pallas call
  present in the lowered step, nothing compiled after step 2; plus the
  fused cross-entropy kernel fwd+bwd at [8192, 50304] against optax.
* **serve**  `ShardedExecutor` + `AdmissionQueue` + `ContinuousBatcher`
  with paged KV and the radix prefix cache: 8 seeded requests x 16
  tokens, every request answered in-vocabulary, a prefix hit, nothing
  compiled after warm-up, a repeat run token-identical, the resolved
  decode kernel present in the lowered step; plus `paged_attention_fused`
  against the XLA oracle at T=1 and T=4.

Every phase prints one JSON line (wall time, compile seconds and
persistent-cache hits, step times as information only, peak device
memory, which devices hold what). Any failed check or exception is a
traceback and a nonzero exit; no later phase runs after a failed one.
Without a TPU it exits nonzero, naming the platform it found, and
prints no result. The last line of a passing run is

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": n}}

It sets neither JAX_PLATFORMS nor XLA_FLAGS, computes no utilization and
assumes no peak rate. Run it twice in one command to see cold vs warm
compile times (the cache is `JAX_COMPILATION_CACHE_DIR` when set, else
`<checkout>/.jax_cache`).
"""
from __future__ import annotations

import importlib.metadata
import json
import os
import statistics
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import NamedSharding, PartitionSpec as P

import horovod_tpu as hvd
from horovod_tpu import native
from horovod_tpu.compile_cache import enable_compile_cache
from horovod_tpu.models.gpt import GPT, GPTConfig
from horovod_tpu.ops.pallas_ce import fused_softmax_cross_entropy
from horovod_tpu.ops.pallas_paged import paged_attention_fused
from horovod_tpu.parallel.mesh_utils import make_mesh
from horovod_tpu.parallel.tp import gpt_partition_rules, shard_params
from horovod_tpu.serve import (AdmissionQueue, ContinuousBatcher,
                               ShardedExecutor, kv_cache, pool_blocks_for)
from horovod_tpu.trace import get_recorder
from horovod_tpu.training import make_gspmd_train_step

#: the model of the builders' captures, at full width and depth
GPT2_SMALL = dict(vocab_size=50304, num_layers=12, num_heads=12,
                  head_dim=64, max_seq_len=1024)

#: train phase: per-chip batch x full context, and the fused-CE check.
#: AdamW at 1e-4, below the benchmark builders' 1e-3: with no warm-up
#: every weight moves ~lr per step, and on this batch the loss overshoots
#: at step 5 — at 1e-3 on one chip (9.32 -> 14.45, with the Pallas and the
#: reference attention alike) and at 3e-4 on four (10.56 -> 11.41; chip
#: runs, PR 21). That says nothing about a kernel and would make "lower
#: at step 5 than at step 1" a coin toss; at 1e-4 it falls steadily.
TRAIN = dict(per_chip_batch=8, steps=5, learning_rate=1e-4, ce_rows=8192)

#: serve phase: two prefill buckets keep warm-up compiles few; four of
#: the eight prompts open with the same `shared_prefix` tokens, and the
#: second wave is submitted after the first has published it
SERVE = dict(max_batch=8, kv_block=16, buckets=(64, 256), new_tokens=16,
             shared_prefix=96,
             waves=((120, 24, 200, 57), (104, 150, 136, 60)),
             sharers=((0,), (0, 1, 2)))

#: a second kernel shape (GQA, D=128) for the kernel-vs-oracle check
GQA_KERNEL = dict(num_heads=32, num_kv_heads=8, head_dim=128)

_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"


def emit(obj: dict) -> None:
    print(json.dumps(obj), flush=True)


def check(cond, what: str) -> None:
    """A smoke check: raises (never `assert`, which -O strips)."""
    if not cond:
        raise AssertionError(what)


def tolerance(dtype) -> float:
    """The comparison tolerance, fixed from the dtype alone: half the
    significand (sqrt(eps)) relative to the reference's largest
    magnitude — 3.5e-4 in float32, 8.8e-2 in bfloat16. Bit-exactness is
    the interpret-mode contract (tests/test_serve_kernels.py); on the
    MXU, where XLA and Mosaic round f32 matmul operands differently, it
    is a tolerance."""
    return float(jnp.finfo(dtype).eps) ** 0.5


class CompileLog:
    """Every backend compile request of this process, persistent-cache
    hits included, counted through jax.monitoring — stricter than one
    function's jit cache: an eager op that compiles shows up too."""

    def __init__(self):
        self.programs = 0
        self.seconds = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_secs)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_secs(self, event: str, secs: float, **_kw) -> None:
        if event == _BACKEND_COMPILE:
            self.programs += 1
            self.seconds += secs

    def _on_event(self, event: str, **_kw) -> None:
        if event == _CACHE_HIT:
            self.cache_hits += 1

    def close(self) -> None:
        jax.monitoring.unregister_event_duration_listener(self._on_secs)
        jax.monitoring.unregister_event_listener(self._on_event)

    def mark(self) -> tuple:
        return self.programs, self.seconds, self.cache_hits

    def since(self, mark: tuple) -> dict:
        p, s, h = mark
        return {"programs_compiled": self.programs - p,
                "compile_s": round(self.seconds - s, 3),
                "cache_hits": self.cache_hits - h}


def device_ids(tree) -> list:
    """Ids of the devices holding any leaf of `tree`."""
    return sorted({d.id for leaf in jax.tree.leaves(tree)
                   for d in leaf.sharding.device_set})


def memory_per_device() -> list:
    """[{id, bytes_in_use, peak_bytes_in_use}] as each device reports
    it (None where the backend keeps no statistics)."""
    out = []
    for d in jax.local_devices():
        st = d.memory_stats() or {}
        out.append({"id": d.id, "bytes_in_use": st.get("bytes_in_use"),
                    "peak_bytes_in_use": st.get("peak_bytes_in_use")})
    return out


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

def collectives_phase(log: CompileLog) -> dict:
    t0, mark = time.perf_counter(), log.mark()
    n = hvd.size()
    # distinct integer-valued rows: every sum is exact in float32
    rows = (np.arange(n, dtype=np.float32)[:, None] * 1000.0
            + np.arange(4096, dtype=np.float32)[None, :])
    want = np.tile(rows.sum(0), (n, 1))
    got = np.asarray(hvd.allreduce(rows, hvd.Sum))
    check(np.array_equal(got, want), "allreduce != numpy sum")
    # two requests in flight so the engine fuses them into one bucket
    h1 = hvd.allreduce_async(rows, hvd.Sum, name="chip_smoke.a")
    h2 = hvd.allreduce_async(2.0 * rows, hvd.Sum, name="chip_smoke.b")
    a = np.asarray(hvd.synchronize(h1))
    b = np.asarray(hvd.synchronize(h2))
    check(np.array_equal(a, want), "allreduce_async != numpy sum")
    check(np.array_equal(b, 2.0 * want), "fused allreduce_async != numpy sum")
    return {"phase": "collectives", "ranks": n,
            "wall_s": round(time.perf_counter() - t0, 3),
            **log.since(mark)}


def train_phase(log: CompileLog, widths: dict, *, per_chip_batch: int,
                steps: int, learning_rate: float, attention_impl=None,
                custom_call="tpu_custom_call") -> tuple:
    """Returns (report, trained params). `attention_impl=None` leaves
    the platform dispatch of ops/pallas_attention.fused_attention in
    charge; `custom_call` is the text the lowered step must contain
    (None: not checked — interpret-mode kernels lower to plain HLO)."""
    t0, mark = time.perf_counter(), log.mark()
    n = hvd.size()
    mesh = make_mesh(dp=n)
    model = GPT(GPTConfig(**widths, mesh=mesh, attention_impl=attention_impl))
    seq, vocab = widths["max_seq_len"], widths["vocab_size"]
    host_tokens = np.random.RandomState(0).randint(
        0, vocab, (per_chip_batch * n, seq)).astype(np.int32)
    batch_sh = NamedSharding(mesh, P("dp", None))
    tokens = jax.device_put(host_tokens, batch_sh)
    targets = jax.device_put(np.roll(host_tokens, -1, axis=1), batch_sh)

    rules = gpt_partition_rules()
    params = jax.jit(lambda k, t: model.init(k, t)["params"])(
        jax.random.PRNGKey(0), host_tokens[:n])
    n_params = sum(x.size for x in jax.tree.leaves(params))
    params = shard_params(params, mesh, rules)
    tx = optax.adamw(learning_rate)
    opt = tx.init(params)
    step = make_gspmd_train_step(model.apply, tx, mesh, rules)

    if custom_call is not None:
        check(custom_call in step.lower(params, opt, tokens, targets).as_text(),
              f"no {custom_call} in the lowered train step: the flash "
              f"kernel is not what would run")
    placed = {"params": device_ids(params), "opt_state": device_ids(opt),
              "batch": device_ids((tokens, targets))}
    for name, ids in placed.items():
        check(len(ids) == n, f"{name} on devices {ids}, expected all {n}")

    losses, step_s, compiled = [], [], []
    for i in range(steps):
        t_step = time.perf_counter()
        params, opt, loss = jax.block_until_ready(
            step(params, opt, tokens, targets))
        step_s.append(time.perf_counter() - t_step)
        losses.append(float(loss))
        compiled.append(log.programs)
        check(np.isfinite(losses[-1]), f"loss at step {i + 1}: {losses[-1]}")
    check(losses[-1] < losses[0],
          f"loss did not fall over {steps} steps: {losses}")
    check(compiled[-1] == compiled[min(1, steps - 1)],
          f"compilations after step 2 (programs by step: {compiled})")
    mem = memory_per_device()
    if jax.devices()[0].platform == "tpu":
        idle = [m["id"] for m in mem if not m["bytes_in_use"]]
        check(not idle, f"devices {idle} hold no bytes after training")
    report = {"phase": "train", "n_params": int(n_params),
              "mesh": dict(mesh.shape), "global_batch": per_chip_batch * n,
              "seq": seq, "loss": [round(x, 4) for x in losses],
              "info_first_step_s": round(step_s[0], 3),
              "info_steady_step_s": round(float(np.median(step_s[2:])), 4),
              "devices": placed, "memory": mem,
              "wall_s": round(time.perf_counter() - t0, 3),
              **log.since(mark)}
    return report, params


def ce_kernel_phase(log: CompileLog, rows: int, vocab: int, *,
                    interpret: bool = False) -> dict:
    """The fused cross-entropy kernel (the default loss of
    `make_train_step`), fwd+bwd on float32 logits, against the optax
    composition."""
    t0, mark = time.perf_counter(), log.mark()
    tol = tolerance(jnp.float32)

    def reference(logits, labels):
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, labels).mean()

    def kernel(logits, labels):
        return fused_softmax_cross_entropy(logits, labels,
                                           interpret=interpret)

    @jax.jit
    def compare(key):
        k1, k2 = jax.random.split(key)
        logits = jax.random.normal(k1, (rows, vocab), jnp.float32)
        labels = jax.random.randint(k2, (rows,), 0, vocab)
        lk, gk = jax.value_and_grad(kernel)(logits, labels)
        lr, gr = jax.value_and_grad(reference)(logits, labels)
        return lk, lr, jnp.max(jnp.abs(gk - gr)), jnp.max(jnp.abs(gr))

    lk, lr, gerr, gmax = (float(x) for x in compare(jax.random.PRNGKey(1)))
    check(np.isfinite(lk) and abs(lk - lr) <= tol * abs(lr),
          f"fused CE loss {lk} vs optax {lr} (tol {tol:.1e} relative)")
    check(gerr <= tol * gmax,
          f"fused CE grad off by {gerr:.3e}; largest reference entry "
          f"{gmax:.3e}, tol {tol:.1e} relative")
    return {"phase": "ce_kernel", "shape": [rows, vocab], "dtype": "float32",
            "tolerance_rel": tol, "loss_kernel": lk, "loss_reference": lr,
            "grad_err_rel": gerr / gmax,
            "wall_s": round(time.perf_counter() - t0, 3), **log.since(mark)}


def smoke_prompts(vocab: int, shape: dict) -> list:
    """The seeded request waves: lists of token lists."""
    rng = np.random.RandomState(0)
    shared = list(rng.randint(0, vocab, shape["shared_prefix"]))
    waves = []
    for lengths, sharers in zip(shape["waves"], shape["sharers"]):
        wave = []
        for i, n in enumerate(lengths):
            head = shared if i in sharers else []
            wave.append(head + list(rng.randint(0, vocab, n - len(head))))
        waves.append(wave)
    return waves


def serve_phase(log: CompileLog, widths: dict, params, shape: dict, *,
                decode_kernel=None) -> dict:
    """One replica on the first device (`mesh=None`). `decode_kernel=
    None` leaves HOROVOD_SERVE_KERNEL / the platform in charge."""
    t0, mark = time.perf_counter(), log.mark()
    t_rec = get_recorder().now()    # this phase's spans start here
    max_len, vocab = widths["max_seq_len"], widths["vocab_size"]
    B, block, new = shape["max_batch"], shape["kv_block"], shape["new_tokens"]
    cfg = GPTConfig(**widths, decode=True, kv_block_size=block,
                    kv_pool_blocks=pool_blocks_for(B, max_len, block),
                    decode_kernel=decode_kernel)
    ex = ShardedExecutor(GPT(cfg), params, max_batch=B, max_len=max_len)
    queue = AdmissionQueue(max_queue=4 * B, default_deadline_ms=300000.0)
    batcher = ContinuousBatcher(ex, queue, buckets=shape["buckets"],
                                prefix_cache=True, kv_crc=False,
                                kv_tier=False, spec_k=0)

    on_tpu = jax.devices()[0].platform == "tpu"
    check(("tpu_custom_call" in ex.lowered_decode_text())
          == (ex.kernel == "pallas" and on_tpu),
          f"executor reports kernel={ex.kernel} but the lowered decode "
          f"step disagrees")
    t_warm = time.perf_counter()
    batcher.warmup()
    warm_s = time.perf_counter() - t_warm
    jit0, programs0 = ex.jit_cache_size(), log.programs
    waves = smoke_prompts(vocab, shape)

    def run_once():
        out = []
        for wave in waves:
            handles = [queue.submit(p, max_new_tokens=new) for p in wave]
            batcher.run()
            for h in handles:
                check(h.status == "ok", f"request {h.rid}: {h.status}")
                check(len(h.tokens) == new and
                      all(0 <= t < vocab for t in h.tokens),
                      f"request {h.rid}: tokens {h.tokens}")
                out.append(list(h.tokens))
        return out

    t_run = time.perf_counter()
    first = run_once()
    run_s = time.perf_counter() - t_run
    hits = batcher.prefix.hits
    check(hits >= 1, "no prefix hit among the prompts sharing a prefix")
    # the repeat starts from the same (empty) prefix cache, so it is
    # scheduled identically and must emit identical tokens
    batcher.request_prefix_flush()
    check(run_once() == first, "repeat run produced different tokens")
    check(ex.jit_cache_size() == jit0 and log.programs == programs0,
          f"compiled after warm-up: jit cache {jit0} -> "
          f"{ex.jit_cache_size()}, programs +{log.programs - programs0}")
    return {"phase": "serve", "kernel": ex.kernel, "replicas": 1,
            "requests": len(first), "new_tokens": new,
            "prefix_hits": hits, "prefix_tokens_saved":
                batcher.prefix.tokens_saved,
            "steps": sorted(f"{k}:{t}" for k, t in ex.signatures),
            "info_warmup_s": round(warm_s, 3),
            "info_first_run_s": round(run_s, 3),
            "info_step_ms_p50": round(statistics.median(
                s.duration_ms for s in get_recorder().between(t_rec, 1e18)
                if s.name == "exec_step"), 3),
            "devices": {"params": device_ids(ex.params),
                        "kv_pool": device_ids(ex.cache)},
            "local_devices": jax.local_device_count(),
            "memory": memory_per_device(),
            "wall_s": round(time.perf_counter() - t0, 3), **log.since(mark)}


def paged_kernel_phase(log: CompileLog, *, num_heads: int, num_kv_heads: int,
                       head_dim: int, max_len: int, max_batch: int,
                       kv_block: int, dtype="bfloat16", interpret=None) -> dict:
    """`paged_attention_fused` at T=1 (decode) and T=4 (the fused
    verify) against the oracle `serve.kv_cache.paged_attention`, on a
    seeded pool with ragged block tables."""
    t0, mark = time.perf_counter(), log.mark()
    dtype = jnp.dtype(dtype)
    tol = tolerance(dtype)
    rng = np.random.RandomState(2)
    B, BS = max_batch, kv_block
    nblk = -(-max_len // BS)
    NB = pool_blocks_for(B, max_len, BS)
    pool_k = jnp.asarray(rng.randn(NB, BS, num_kv_heads, head_dim), dtype)
    pool_v = jnp.asarray(rng.randn(NB, BS, num_kv_heads, head_dim), dtype)
    tables = np.full((B, nblk), -1, np.int32)
    positions = np.zeros(B, np.int32)
    for b in range(B):
        used = rng.randint(1, nblk + 1)
        tables[b, :used] = rng.choice(NB, used, replace=False)
        positions[b] = rng.randint(0, max(used * BS - 4, 1))
    oracle = jax.jit(kv_cache.paged_attention)
    errs = {}
    for T in (1, 4):
        q = jnp.asarray(rng.randn(B, T, num_heads, head_dim), dtype)
        want = np.asarray(oracle(q, pool_k, pool_v, jnp.asarray(tables),
                                 jnp.asarray(positions)), np.float32)
        got = np.asarray(paged_attention_fused(
            q, pool_k, pool_v, tables, positions, interpret=interpret),
            np.float32)
        check(np.isfinite(got).all(), f"T={T}: kernel output not finite")
        err = float(np.abs(got - want).max() / np.abs(want).max())
        check(err <= tol, f"T={T}: kernel off the oracle by {err:.3e} of "
              f"the largest entry (tol {tol:.1e})")
        errs[f"T{T}"] = err
    return {"phase": "paged_kernel", "heads": num_heads,
            "kv_heads": num_kv_heads, "head_dim": head_dim,
            "dtype": dtype.name, "kv_block": BS,
            "blocks_per_seq": nblk, "tolerance_rel": tol, "err_rel": errs,
            "wall_s": round(time.perf_counter() - t0, 3), **log.since(mark)}


# ---------------------------------------------------------------------------

def main() -> int:
    cache_dir = enable_compile_cache()
    cached = len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, but jax.devices()[0].platform is "
              f"{dev.platform!r} ({dev.device_kind}, {len(jax.devices())} "
              f"devices); nothing was run", file=sys.stderr)
        return 1
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    t0 = time.perf_counter()
    hvd.init()
    log = CompileLog()
    start = log.mark()
    try:
        emit({"phase": "init", **device, "hvd_size": hvd.size(),
              "jax": jax.__version__,
              "jaxlib": importlib.metadata.version("jaxlib"),
              "libtpu": importlib.metadata.version("libtpu"),
              "compile_cache_dir": cache_dir,
              "cache_entries_at_start": cached,
              # make_mesh / global_devices order devices by id, not by
              # ICI coordinates
              "device_order": [d.id for d in hvd.core.mesh.global_devices()]})
        check(hvd.size() == device["count"],
              f"hvd.size() {hvd.size()} != device count {device['count']}")
        emit(collectives_phase(log))
        report, params = train_phase(
            log, GPT2_SMALL, per_chip_batch=TRAIN["per_chip_batch"],
            steps=TRAIN["steps"], learning_rate=TRAIN["learning_rate"])
        emit(report)
        # the serve replica is one chip: hand it the trained weights there
        params = jax.device_put(params, dev)
        emit(ce_kernel_phase(log, TRAIN["ce_rows"],
                             GPT2_SMALL["vocab_size"]))
        emit(serve_phase(log, GPT2_SMALL, params, SERVE))
        kshape = dict(max_len=GPT2_SMALL["max_seq_len"],
                      max_batch=SERVE["max_batch"],
                      kv_block=SERVE["kv_block"])
        emit(paged_kernel_phase(
            log, num_heads=GPT2_SMALL["num_heads"],
            num_kv_heads=GPT2_SMALL["num_heads"],
            head_dim=GPT2_SMALL["head_dim"], **kshape))
        emit(paged_kernel_phase(log, **GQA_KERNEL, **kshape))
        emit({"phase": "done", "wall_s": round(time.perf_counter() - t0, 3),
              "native_lib_loaded": native.loaded(),
              **log.since(start)})
    finally:
        log.close()
        hvd.shutdown()
    emit({"ok": True, "device": device})
    return 0


if __name__ == "__main__":
    sys.exit(main())
