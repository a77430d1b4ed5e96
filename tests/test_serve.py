"""horovod_tpu.serve: continuous-batching inference (tier-1, CPU).

The acceptance bars of the serving subsystem (docs/serving.md):

* KV row and block reuse decodes EXACTLY like a straight-line
  full-forward oracle (greedy), across admission waves that recycle
  rows and their blocks;
* batch churn (iteration-level join/leave) never grows the jit cache —
  the fixed-bucket no-recompile contract;
* overload sheds load with a structured retry-after rejection while
  admitted requests keep being served;
* deadlines expire mid-generation, resolve with partial output and
  free their slot;
* the continuous batcher sustains >= 2x the tokens/s of a serial
  one-request-at-a-time baseline on the same model (ISSUE 2 bar);
* per-step latency lands on the SERVE timeline row.
"""
import json
import threading
import urllib.request
from types import SimpleNamespace

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from horovod_tpu.core.config import Config
from horovod_tpu.models.gpt import GPT, GPTConfig
from horovod_tpu.models.llama import Llama, LlamaConfig
from horovod_tpu.serve import (AdmissionQueue, BlockPool,
                               ContinuousBatcher, PagedKVCache, Rejected,
                               ShardedExecutor)
from horovod_tpu.trace import get_recorder

_KW = dict(vocab_size=64, num_layers=2, num_heads=2, head_dim=8,
           max_seq_len=48, dtype=jnp.float32, attention_impl="reference")


@pytest.fixture(scope="module")
def gpt():
    """Tiny GPT: one param set shared by the training-mode oracle and
    the decode-mode serving path (the cache is a separate collection,
    so the trees are identical by construction)."""
    train = GPT(GPTConfig(**_KW))
    dec = GPT(GPTConfig(decode=True, **_KW))
    toks = jnp.zeros((2, 8), jnp.int32)
    params = train.init(jax.random.PRNGKey(0), toks)["params"]

    @jax.jit
    def oracle_next(p, padded, last):
        logits = train.apply({"params": p}, padded)
        return jnp.argmax(jnp.take(logits[0], last, axis=0))

    def oracle(prompt, max_new):
        seq = list(prompt)
        out = []
        for _ in range(max_new):
            padded = np.zeros((1, _KW["max_seq_len"]), np.int32)
            padded[0, :len(seq)] = seq
            nxt = int(oracle_next(params, jnp.asarray(padded),
                                  jnp.asarray(len(seq) - 1)))
            out.append(nxt)
            seq.append(nxt)
        return out

    return SimpleNamespace(train=train, dec=dec, params=params,
                           oracle=oracle)


def _stack(gpt, max_batch=4, max_queue=16, buckets=(8, 16),
           deadline_ms=30000.0, timeline=None, warmup=True):
    ex = ShardedExecutor(gpt.dec, gpt.params, max_batch=max_batch,
                         max_len=_KW["max_seq_len"], timeline=timeline)
    q = AdmissionQueue(max_queue=max_queue, default_deadline_ms=deadline_ms)
    b = ContinuousBatcher(ex, q, buckets=buckets)
    if warmup:
        b.warmup()
    return ex, q, b


class TestRowManager:
    def test_alloc_free_reuse_accounting(self):
        kv = PagedKVCache(2, 4, BlockPool(8, 4))
        a, b = kv.alloc_row(1), kv.alloc_row(1)
        assert {a, b} == {0, 1}
        assert kv.alloc_row(1) is None     # every row live
        assert kv.live() == 2 and not kv.can_admit(0)
        held = kv.ensure(b, 5)             # two blocks of four
        assert len(held) == 2 and kv.pool.in_use() == 2
        kv.free_row(b)
        assert kv.pool.in_use() == 0       # its blocks went with it
        assert kv.alloc_row(1) == b        # LIFO reuse
        assert kv.generation[b] == 2       # the reuse ledger
        assert kv.allocs == 3 and kv.frees == 1
        kv.free_row(a)
        with pytest.raises(ValueError):    # double free
            kv.free_row(a)

    def test_lengths_reset_on_alloc(self):
        kv = PagedKVCache(1, 4, BlockPool(4, 4))
        r = kv.alloc_row(1)
        kv.lengths[r] = 9
        kv.free_row(r)
        r = kv.alloc_row(1)
        assert kv.lengths[r] == 0 and kv.blocks[r] == []


class TestDecodeCorrectness:
    def test_slot_reuse_matches_straight_line_oracle(self, gpt):
        """Two admission waves over 4 rows: the second wave reuses
        rows, and pool blocks still holding the first wave's stale KV
        bytes; every request must still decode exactly like the
        full-forward oracle."""
        ex, q, b = _stack(gpt)
        rng = np.random.RandomState(1)
        prompts = [list(rng.randint(0, 64, rng.randint(2, 9)))
                   for _ in range(8)]  # 8 requests > 4 rows => reuse
        handles = [q.submit(p, max_new_tokens=6) for p in prompts]
        b.run()
        assert b.kv.generation.sum() >= 5  # rows actually recycled
        # and blocks: more handed out than were ever in use at once
        assert b.kv.pool.allocs > b.kv.pool.peak_in_use
        for p, h in zip(prompts, handles):
            assert h.status == "ok"
            assert h.tokens == gpt.oracle(p, 6)

    def test_llama_gqa_decode_matches_oracle(self):
        """Same bar for the Llama path: GQA kv-width cache + per-row
        RoPE windows."""
        kw = dict(vocab_size=64, num_layers=2, num_heads=4,
                  num_kv_heads=2, head_dim=8, max_seq_len=32,
                  dtype=jnp.float32, attention_impl="reference")
        train = Llama(LlamaConfig(**kw))
        dec = Llama(LlamaConfig(decode=True, **kw))
        params = train.init(jax.random.PRNGKey(0),
                            jnp.zeros((2, 8), jnp.int32))["params"]
        ex = ShardedExecutor(dec, params, max_batch=2, max_len=32)
        q = AdmissionQueue(max_queue=8)
        b = ContinuousBatcher(ex, q, buckets=(8,))
        rng = np.random.RandomState(2)
        prompts = [list(rng.randint(0, 64, 5)) for _ in range(3)]
        handles = [q.submit(p, max_new_tokens=4) for p in prompts]
        b.run()

        @jax.jit
        def onext(p, padded, last):
            return jnp.argmax(jnp.take(
                train.apply({"params": p}, padded)[0], last, axis=0))

        for p, h in zip(prompts, handles):
            seq, want = list(p), []
            for _ in range(4):
                padded = np.zeros((1, 32), np.int32)
                padded[0, :len(seq)] = seq
                nxt = int(onext(params, jnp.asarray(padded),
                                jnp.asarray(len(seq) - 1)))
                want.append(nxt)
                seq.append(nxt)
            assert h.status == "ok" and h.tokens == want

    @pytest.mark.parametrize("family", ["gpt", "llama"])
    def test_decode_true_alone_means_a_worst_case_pool(self, family):
        """`decode=True` without pool sizes is no other layout: blocks
        of the one default size, and the executor works the pool out as
        every row at max_len (40 here: a partial last block). Rows,
        blocks and all, the stream is the straight-line oracle's."""
        kw = dict(vocab_size=64, num_layers=2, num_heads=4, head_dim=8,
                  max_seq_len=40, dtype=jnp.float32,
                  attention_impl="reference")
        if family == "llama":
            kw["num_kv_heads"] = 2
        Model, Cfg = {"gpt": (GPT, GPTConfig),
                      "llama": (Llama, LlamaConfig)}[family]
        train, dec = Model(Cfg(**kw)), Model(Cfg(decode=True, **kw))
        assert (dec.cfg.kv_block_size, dec.cfg.kv_pool_blocks) == (16, 0)
        params = train.init(jax.random.PRNGKey(0),
                            jnp.zeros((2, 8), jnp.int32))["params"]
        ex = ShardedExecutor(dec, params, max_batch=3, max_len=40)
        assert ex.blocks_per_seq == 3
        assert ex.kv_pool_blocks == ex.max_batch * ex.blocks_per_seq == 9
        assert dec.cfg.kv_pool_blocks == 0     # the caller's, as given
        q = AdmissionQueue(max_queue=8)
        b = ContinuousBatcher(ex, q, buckets=(8, 16))
        assert b.kv.pool.num_blocks == 9 and b.kv.num_rows == 3
        rng = np.random.RandomState(5)
        prompts = [list(rng.randint(0, 64, rng.randint(3, 14)))
                   for _ in range(5)]          # 5 requests > 3 rows
        handles = [q.submit(p, max_new_tokens=24) for p in prompts]
        b.run()
        assert b.kv.pool.peak_in_use <= 9 and b.kv.pool.in_use() == 0

        @jax.jit
        def onext(p, padded, last):
            return jnp.argmax(jnp.take(
                train.apply({"params": p}, padded)[0], last, axis=0))

        for p, h in zip(prompts, handles):
            seq, want = list(p), []
            for _ in range(24):
                padded = np.zeros((1, 40), np.int32)
                padded[0, :len(seq)] = seq
                nxt = int(onext(params, jnp.asarray(padded),
                                jnp.asarray(len(seq) - 1)))
                want.append(nxt)
                seq.append(nxt)
            assert h.status == "ok" and h.tokens == want

    def test_tp_mesh_executor_matches_unsharded(self, gpt):
        """The executor under a dp x tp mesh (parallel/tp partition
        rules, GSPMD collectives) decodes the same tokens as the
        unsharded run."""
        from horovod_tpu.parallel.mesh_utils import make_mesh
        from horovod_tpu.parallel.tp import gpt_partition_rules
        mesh = make_mesh(dp=jax.device_count() // 2, tp=2)
        ex = ShardedExecutor(gpt.dec, gpt.params, max_batch=2,
                             max_len=_KW["max_seq_len"], mesh=mesh,
                             partition_rules=gpt_partition_rules())
        q = AdmissionQueue(max_queue=4)
        b = ContinuousBatcher(ex, q, buckets=(8,))
        prompt = list(np.random.RandomState(3).randint(0, 64, 6))
        h = q.submit(prompt, max_new_tokens=5)
        b.run()
        assert h.status == "ok"
        assert h.tokens == gpt.oracle(prompt, 5)


class TestNoRecompileAcrossChurn:
    def test_jit_cache_stable_under_join_leave(self, gpt):
        """After warmup, arbitrary batch churn — requests of mixed
        lengths joining mid-flight while others retire — must add zero
        jit entries (the fixed-shape contract)."""
        ex, q, b = _stack(gpt, max_batch=3)
        baseline = ex.jit_cache_size()
        sigs = set(ex.signatures)
        rng = np.random.RandomState(4)
        handles = [q.submit(list(rng.randint(0, 64, n)), max_new_tokens=m)
                   for n, m in ((2, 9), (7, 3), (5, 5))]
        # join mid-flight: drip new requests in while the batch drains
        for i in range(30):
            alive = b.step()
            if i in (2, 5, 9):
                handles.append(q.submit(
                    list(rng.randint(0, 64, rng.randint(2, 16))),
                    max_new_tokens=int(rng.randint(1, 8))))
            if not alive and q.depth() == 0:
                break
        b.run()
        assert all(h.status == "ok" for h in handles)
        assert ex.jit_cache_size() == baseline
        assert set(ex.signatures) == sigs


class TestAdmissionControl:
    def test_overload_sheds_with_retry_after_and_keeps_serving(self, gpt):
        """Queue-full submits get a structured Rejected (retry-after
        hint, shed counter); the admitted requests all complete and no
        recompilation happens — the no-crash overload bar."""
        ex, q, b = _stack(gpt, max_batch=2, max_queue=3)
        baseline = ex.jit_cache_size()
        rng = np.random.RandomState(5)
        admitted, rejected = [], []
        for _ in range(10):
            try:
                admitted.append(q.submit(list(rng.randint(0, 64, 4)),
                                         max_new_tokens=4))
            except Rejected as e:
                rejected.append(e)
        assert len(admitted) == 3 and len(rejected) == 7
        assert q.shed_count == 7
        assert all(e.retry_after_ms and e.retry_after_ms > 0
                   for e in rejected)
        b.run()
        assert all(h.status == "ok" for h in admitted)
        assert ex.jit_cache_size() == baseline
        # the retry-after estimate sharpens once service times exist
        assert q._service_ms_ewma is not None

    def test_unservable_prompt_rejected_at_the_door(self, gpt):
        ex, q, b = _stack(gpt, warmup=False)  # buckets (8, 16)
        with pytest.raises(Rejected) as ei:
            q.submit(list(range(17)), max_new_tokens=1)
        assert ei.value.retry_after_ms is None  # retrying cannot help
        with pytest.raises(Rejected):
            q.submit([], max_new_tokens=1)

    def test_deadline_expires_mid_generation_and_frees_slot(self, gpt):
        ex, q, b = _stack(gpt, max_batch=2, deadline_ms=2.0)
        h = q.submit(list(range(4)), max_new_tokens=40)
        b.run()
        assert h.status == "expired"
        assert len(h.tokens) < 40          # partial output returned
        assert b.kv.live() == 0            # slot went back to the pool
        assert q.expired_count >= 1
        # the server is still healthy: a fresh request completes
        h2 = q.submit(list(range(4)), max_new_tokens=2,
                      deadline_ms=30000.0)
        b.run()
        assert h2.status == "ok" and len(h2.tokens) == 2


@pytest.mark.filterwarnings("ignore::pytest.PytestUnraisableExceptionWarning")
class TestThroughput:
    def test_continuous_batching_at_least_2x_serial(self, gpt):
        """ISSUE 2 acceptance bar: on the same tiny model, the
        continuous batcher (8 slots) sustains >= 2x the tokens/s of a
        one-request-at-a-time baseline (same executor code, 1 slot) —
        iteration cost is dispatch-bound, so batching amortizes it."""
        import time
        n_req, max_new = 8, 12
        rng = np.random.RandomState(6)
        prompts = [list(rng.randint(0, 64, 4)) for _ in range(n_req)]

        def tokens_per_s(max_batch):
            ex, q, b = _stack(gpt, max_batch=max_batch,
                              max_queue=n_req, buckets=(8,))
            handles = [q.submit(p, max_new_tokens=max_new)
                       for p in prompts]
            t0 = time.perf_counter()
            b.run()
            dt = time.perf_counter() - t0
            assert all(h.status == "ok" for h in handles)
            return sum(len(h.tokens) for h in handles) / dt

        continuous = tokens_per_s(8)
        serial = tokens_per_s(1)
        assert continuous >= 2.0 * serial, \
            f"continuous {continuous:.1f} tok/s vs serial {serial:.1f}"


class TestObservability:
    def test_serve_timeline_row(self, gpt, tmp_path, monkeypatch):
        """Every executor step lands a SERVE instant with latency and
        the batcher's queue/occupancy/shed counters."""
        monkeypatch.setenv("HOROVOD_TIMELINE_NATIVE", "0")
        from horovod_tpu.timeline import Timeline
        path = str(tmp_path / "serve_trace.json")
        tl = Timeline(path)
        tl.start()
        ex, q, b = _stack(gpt, max_batch=2, timeline=tl, warmup=False)
        h = q.submit(list(range(4)), max_new_tokens=3)
        b.run()
        tl.stop()
        assert h.status == "ok"
        with open(path) as f:
            events = [e for e in json.load(f)["traceEvents"]
                      if e["name"] == "SERVE"]
        assert len(events) >= 3  # 1 prefill + >= 2 decode steps
        kinds = {e["args"]["kind"] for e in events}
        assert {"prefill", "decode"} <= kinds
        for e in events:
            assert {"step_ms", "tokens_per_s", "queue_depth",
                    "occupancy", "shed"} <= set(e["args"])

    def test_executor_metrics(self, gpt):
        t_rec = get_recorder().now()
        ex, q, b = _stack(gpt, max_batch=2, warmup=False)
        q.submit(list(range(4)), max_new_tokens=4)
        b.run()
        assert ex.steps >= 4
        steps = [s for s in get_recorder().between(t_rec, 1e18)
                 if s.name == "exec_step"]
        assert len(steps) == ex.steps
        assert all(s.duration_ms > 0 for s in steps)
        assert ex.tokens_out >= 4


class TestConfigKnobs:
    def test_defaults_validate(self):
        c = Config()
        c.validate()
        assert c.serve_max_batch == 8 and c.serve_buckets == (32, 128, 512)

    def test_env_parsing(self, monkeypatch):
        monkeypatch.setenv("HOROVOD_SERVE_MAX_BATCH", "16")
        monkeypatch.setenv("HOROVOD_SERVE_MAX_QUEUE", "128")
        monkeypatch.setenv("HOROVOD_SERVE_DEADLINE_MS", "1500")
        monkeypatch.setenv("HOROVOD_SERVE_BUCKETS", "16,64,256")
        c = Config.from_env()
        assert c.serve_max_batch == 16
        assert c.serve_max_queue == 128
        assert c.serve_deadline_ms == 1500.0
        assert c.serve_buckets == (16, 64, 256)

    @pytest.mark.parametrize("name,val", [
        ("HOROVOD_SERVE_MAX_BATCH", "zero"),
        ("HOROVOD_SERVE_MAX_BATCH", "0"),
        ("HOROVOD_SERVE_MAX_QUEUE", "-1"),
        ("HOROVOD_SERVE_DEADLINE_MS", "0"),
        ("HOROVOD_SERVE_DEADLINE_MS", "soon"),
        ("HOROVOD_SERVE_BUCKETS", "64,16"),      # not ascending
        ("HOROVOD_SERVE_BUCKETS", "16,x"),       # not ints
        ("HOROVOD_SERVE_BUCKETS", ""),           # empty
    ])
    def test_bad_env_fails_fast(self, monkeypatch, name, val):
        monkeypatch.setenv(name, val)
        with pytest.raises(ValueError):
            Config.from_env()


class TestHTTPFrontEnd:
    def test_generate_healthz_and_429(self, gpt):
        from horovod_tpu.serve.http import make_server
        ex, q, b = _stack(gpt, max_batch=2, max_queue=1, warmup=False)
        srv = make_server(b)
        t = threading.Thread(target=srv.serve_forever, daemon=True)
        t.start()
        host, port = srv.server_address
        base = f"http://{host}:{port}"
        try:
            # batcher NOT running yet: fill the queue, then overload
            q.submit(list(range(4)), max_new_tokens=2)
            req = urllib.request.Request(
                base + "/generate",
                data=json.dumps({"tokens": [1, 2, 3],
                                 "max_new_tokens": 2}).encode(),
                headers={"Content-Type": "application/json"})
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(req, timeout=10)
            assert ei.value.code == 429
            body = json.loads(ei.value.read())
            assert body["error"] == "rejected"
            assert body["retry_after_ms"] > 0
            assert ei.value.headers.get("Retry-After") is not None
            # now serve for real
            b.start()
            with urllib.request.urlopen(req, timeout=60) as resp:
                out = json.loads(resp.read())
            assert out["status"] == "ok" and len(out["tokens"]) == 2
            with urllib.request.urlopen(base + "/healthz",
                                        timeout=10) as resp:
                health = json.loads(resp.read())
            assert health["ok"] and health["shed"] >= 1
            assert "occupancy" in health and "tokens_per_s" in health
            # malformed bodies are a structured 400, never a dropped
            # socket (including submit's own validation errors)
            for bad in ({"max_new_tokens": 2},          # no tokens
                        {"tokens": ["x"]},              # non-int tokens
                        {"tokens": [1], "max_new_tokens": 0},
                        {"tokens": [1], "deadline_ms": "5s"}):
                breq = urllib.request.Request(
                    base + "/generate", data=json.dumps(bad).encode(),
                    headers={"Content-Type": "application/json"})
                with pytest.raises(urllib.error.HTTPError) as bei:
                    urllib.request.urlopen(breq, timeout=10)
                assert bei.value.code == 400, bad
        finally:
            srv.shutdown()
            b.stop()
