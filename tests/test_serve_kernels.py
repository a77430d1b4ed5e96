"""Fused Pallas serving kernels + on-device sampling (ISSUE 12).

Tier-1 (CPU) coverage for the serve plane's compute half:

* interpret-mode BIT-EXACT parity of the fused paged-attention /
  fused-verify kernel against the single masked-attention oracle
  (serve/kv_cache.py), across GQA widths, dtypes, -1 block tables, and
  pool states shaped like block reuse, CoW divergence and speculative
  rollback overwrites;
* end-to-end token-stream identity between `kernel="pallas"` and
  `kernel="xla"` serving stacks (GPT and Llama-GQA, prefix-cache CoW,
  rejecting-drafter rollback), with greedy speculative output
  bit-identical to target-only decode under BOTH kernels;
* on-device sampling semantics: per-request seed determinism across
  batch positions and restarts, temperature=0 == greedy, top-p edge
  cases, and the rejection-sampling accept rule's distribution
  correctness against an analytic toy distribution;
* the HOROVOD_SERVE_KERNEL knob's fail-fast parsing, one-shot KERNEL
  timeline instant, kernel-labeled step metrics, and jit-cache
  flatness across kernel warmup and churn.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from horovod_tpu.models.gpt import GPT, GPTConfig
from horovod_tpu.models.llama import Llama, LlamaConfig
from horovod_tpu.ops import pallas_paged as pp
from horovod_tpu.serve import (AdmissionQueue, ContinuousBatcher,
                               ShardedExecutor)
from horovod_tpu.serve import kv_cache as kvc

_KW = dict(vocab_size=64, num_layers=2, num_heads=4, head_dim=8,
           max_seq_len=48, dtype=jnp.float32, attention_impl="reference")
_BLOCK, _POOL = 4, 40


@pytest.fixture(scope="module")
def gpt_params():
    return GPT(GPTConfig(**_KW)).init(
        jax.random.PRNGKey(0), jnp.zeros((2, 8), jnp.int32))["params"]


def _stack(params, kernel, *, spec=False, draft_params=None,
           prefix=False, max_batch=4, buckets=(8, 16), timeline=None,
           num_layers=None):
    kw = dict(_KW)
    if num_layers is not None:
        kw["num_layers"] = num_layers
    mcfg = GPTConfig(decode=True, **kw, kv_block_size=_BLOCK,
                     kv_pool_blocks=_POOL, decode_kernel=kernel)
    ex = ShardedExecutor(GPT(mcfg), params, max_batch=max_batch,
                         max_len=_KW["max_seq_len"], timeline=timeline)
    draft = None
    if spec:
        draft = ShardedExecutor(
            GPT(GPTConfig(decode=True, **kw)),
            draft_params if draft_params is not None else params,
            max_batch=max_batch, max_len=_KW["max_seq_len"],
            role="draft")
    q = AdmissionQueue(max_queue=64)
    b = ContinuousBatcher(ex, q, buckets=buckets, prefix_cache=prefix,
                          draft_executor=draft, spec_k=3)
    b.warmup()
    return ex, q, b


def _drive(params, kernel, prompts, max_new=6, sampling=None, **kw):
    ex, q, b = _stack(params, kernel, **kw)
    j0 = ex.jit_cache_size()
    hs = [q.submit(p, max_new_tokens=max_new, **(sampling or {}))
          for p in prompts]
    b.run()
    assert all(h.status == "ok" for h in hs), [h.status for h in hs]
    assert ex.jit_cache_size() == j0   # churn never recompiles
    return [h.tokens for h in hs]


# ---------------------------------------------------------------------------
# kernel-level parity: bit-exact vs the masked-attention oracle
# ---------------------------------------------------------------------------

class TestKernelParity:
    def _check(self, q, pk, pv, tbl, pos):
        ref = np.asarray(jax.jit(kvc.paged_attention)(
            q, pk, pv, jnp.asarray(tbl), jnp.asarray(pos)), np.float32)
        got = np.asarray(pp.paged_attention_fused(q, pk, pv, tbl, pos),
                         np.float32)
        assert np.array_equal(ref, got), \
            f"kernel diverged from oracle by {np.abs(ref - got).max()}"

    @pytest.mark.parametrize("dtype", [np.float32, jnp.bfloat16])
    @pytest.mark.parametrize("T,H,KV", [(1, 4, 2), (4, 4, 4), (3, 8, 2),
                                        (1, 4, 1)])
    def test_bit_exact_decode_and_verify(self, dtype, T, H, KV):
        """T=1 is the decode step, T>1 the fused speculative verify;
        GQA group widths 1/2/4; unassigned -1 entries predicated."""
        rng = np.random.RandomState(7)
        B, D, NB, BS, nblk = 3, 16, 10, 8, 4
        q = jnp.asarray(rng.randn(B, T, H, D), dtype)
        pk = jnp.asarray(rng.randn(NB, BS, KV, D), dtype)
        pv = jnp.asarray(rng.randn(NB, BS, KV, D), dtype)
        tbl = np.full((B, nblk), -1, np.int32)
        for b in range(B):
            n = rng.randint(1, nblk + 1)
            tbl[b, :n] = rng.choice(NB, n, replace=False)
        pos = np.array(
            [rng.randint(0, max(int((tbl[b] >= 0).sum()) * BS - T, 1))
             for b in range(B)], np.int32)
        self._check(q, pk, pv, tbl, pos)

    def test_shared_reused_and_rollback_pool_states(self):
        """Pool states the serve plane actually produces: the same
        block referenced by several rows (radix prefix sharing), a
        CoW-divergent pair (shared prefix run + private tails), and a
        rollback overwrite (position mid-block, bytes past it stale
        from a rejected speculative tail)."""
        rng = np.random.RandomState(3)
        B, D, KV, NB, BS, nblk = 4, 16, 2, 8, 4, 6
        pk = jnp.asarray(rng.randn(NB, BS, KV, D).astype(np.float32))
        pv = jnp.asarray(rng.randn(NB, BS, KV, D).astype(np.float32))
        tbl = np.full((B, nblk), -1, np.int32)
        tbl[0, :3] = [2, 5, 1]          # rows 0/1 share blocks 2,5
        tbl[1, :4] = [2, 5, 3, 0]       # ...then diverge (CoW copy: 3)
        tbl[2, :2] = [2, 4]             # partial share + private tail
        tbl[3, :1] = [7]
        # positions mid-block: bytes past them are stale (rollback) and
        # must be unreachable in BOTH implementations; the batcher
        # invariant pos + T <= assigned-block coverage holds (kv.ensure
        # grows the table BEFORE every step)
        pos = np.array([8, 11, 4, 0], np.int32)
        for T in (1, 4):
            q = jnp.asarray(rng.randn(B, T, 4, D).astype(np.float32))
            self._check(q, pk, pv, tbl, pos)

    def test_fused_head_mismatch_fails_fast(self):
        q = jnp.zeros((1, 1, 3, 8))
        pool = jnp.zeros((2, 4, 2, 8))
        with pytest.raises(ValueError, match="multiple of kv heads"):
            pp.paged_attention_fused(q, pool, pool,
                                     np.zeros((1, 1), np.int32),
                                     np.zeros(1, np.int32))

    def test_masked_attention_is_the_single_oracle(self):
        """The dedupe contract: the pool read and the models' decode
        attention all route through ONE reference implementation."""
        import inspect
        assert "masked_attention(" in inspect.getsource(
            kvc.paged_attention)
        assert "paged_attention(" in inspect.getsource(
            kvc.pool_attention)
        # the models delegate to kv_cache for every decode read
        import horovod_tpu.models.gpt as gpt_mod
        import horovod_tpu.models.llama as llama_mod
        for mod in (gpt_mod, llama_mod):
            src = inspect.getsource(mod)
            assert "kvc.pool_attention(" in src
            assert "self.variable(" not in src   # the pool is kvc's

    def test_the_block_pool_is_the_one_storage_format(self):
        """The source contract: no second KV layout, selector or knob
        under horovod_tpu/ (PR 32 took them out)."""
        import pathlib
        import re
        import horovod_tpu
        gone = re.compile(
            r"SlotKVCache|cached_attention|_admit_slotted|"
            r"paged_model_kwargs|serve_kv_block\b|\.paged\b")
        root = pathlib.Path(horovod_tpu.__file__).parent
        hits = [f"{f.relative_to(root)}:{i}: {line.strip()}"
                for f in sorted(root.rglob("*.py"))
                for i, line in enumerate(
                    f.read_text().splitlines(), 1)
                if gone.search(line)]
        assert not hits, hits


# ---------------------------------------------------------------------------
# end-to-end: pallas and xla stacks emit identical token streams
# ---------------------------------------------------------------------------

class TestServeKernelParityE2E:
    def test_greedy_paged_streams_identical_across_reuse(self,
                                                         gpt_params):
        """8 requests over 4 rows: the second wave recycles rows and
        pool blocks — both kernels must emit identical streams."""
        rng = np.random.RandomState(1)
        prompts = [list(rng.randint(0, 64, rng.randint(2, 9)))
                   for _ in range(8)]
        assert _drive(gpt_params, "xla", prompts) == \
            _drive(gpt_params, "pallas", prompts)

    def test_prefix_cow_divergence_identical(self, gpt_params):
        """Shared system prompt + tails diverging mid-block: the radix
        cache CoW path under the pallas kernel matches xla exactly."""
        rng = np.random.RandomState(2)
        system = list(rng.randint(0, 64, 10))    # mid-block divergence
        prompts = [system + list(rng.randint(0, 64, 3))
                   for _ in range(6)]
        kw = dict(prefix=True, num_layers=1)
        assert _drive(gpt_params, "xla", prompts, **kw) == \
            _drive(gpt_params, "pallas", prompts, **kw)

    def test_greedy_spec_bit_identical_to_target_only(self, gpt_params):
        """Speculative greedy (fused verify + on-device argmax accept)
        emits the target-only greedy stream under BOTH kernels, with a
        rejecting drafter (different params -> rollback overwrites)."""
        rng = np.random.RandomState(3)
        prompts = [list(rng.randint(0, 64, rng.randint(2, 8)))
                   for _ in range(6)]
        kw1 = dict(_KW, num_layers=1)
        other = GPT(GPTConfig(**kw1)).init(
            jax.random.PRNGKey(9), jnp.zeros((2, 8), jnp.int32))["params"]
        params = GPT(GPTConfig(**kw1)).init(
            jax.random.PRNGKey(0), jnp.zeros((2, 8), jnp.int32))["params"]
        base = _drive(params, "xla", prompts, num_layers=1)
        for kernel in ("xla", "pallas"):
            for dp in (params, other):       # perfect + rejecting
                got = _drive(params, kernel, prompts, spec=True,
                             draft_params=dp, num_layers=1)
                assert got == base, (kernel,
                                     "perfect" if dp is params
                                     else "rejecting")

    def test_llama_gqa_paged_pallas_matches_xla(self):
        kw = dict(vocab_size=64, num_layers=1, num_heads=4,
                  num_kv_heads=2, head_dim=8, max_seq_len=32,
                  dtype=jnp.float32, attention_impl="reference")
        params = Llama(LlamaConfig(**kw)).init(
            jax.random.PRNGKey(0), jnp.zeros((2, 8), jnp.int32))["params"]
        rng = np.random.RandomState(4)
        prompts = [list(rng.randint(0, 64, rng.randint(2, 8)))
                   for _ in range(4)]

        def drive(kernel):
            mcfg = LlamaConfig(decode=True, **kw, kv_block_size=4,
                               kv_pool_blocks=24, decode_kernel=kernel)
            ex = ShardedExecutor(Llama(mcfg), params, max_batch=2,
                                 max_len=32)
            q = AdmissionQueue(max_queue=16)
            b = ContinuousBatcher(ex, q, buckets=(8,),
                                  prefix_cache=False)
            b.warmup()
            hs = [q.submit(p, max_new_tokens=4) for p in prompts]
            b.run()
            assert all(h.status == "ok" for h in hs)
            return [h.tokens for h in hs]

        assert drive("xla") == drive("pallas")

    def test_kernel_observability(self, gpt_params):
        """One-shot KERNEL timeline instant names the resolved path;
        hvd_serve_step_ms carries the kernel label."""
        events = []

        class Cap:
            def instant(self, name, args=None, **kw):
                events.append((name, args))

        ex, q, b = _stack(gpt_params, "pallas", timeline=Cap(),
                          num_layers=1)
        kern = [a for n, a in events if n == "KERNEL"]
        assert len(kern) == 1 and kern[0]["kernel"] == "pallas"
        assert ex.kernel == "pallas"
        from horovod_tpu.obs import metrics as obs_metrics
        fam = obs_metrics.get_registry().get(
            "hvd_serve_step_ms", {"kind": "decode", "kernel": "pallas"})
        assert fam is not None
        # a config that names no kernel resolves from the environment:
        # auto, off the TPU, is the XLA oracle
        ex2, _, _ = _stack(gpt_params, None, num_layers=1)
        assert ex2.kernel == ex2.model.cfg.decode_kernel == "xla"


# ---------------------------------------------------------------------------
# knob resolution
# ---------------------------------------------------------------------------

class TestKernelKnob:
    def test_env_fail_fast(self, monkeypatch):
        from horovod_tpu.core.config import Config
        monkeypatch.setenv("HOROVOD_SERVE_KERNEL", "bogus")
        with pytest.raises(ValueError, match="HOROVOD_SERVE_KERNEL"):
            Config.from_env()

    def test_env_resolution(self, monkeypatch):
        from horovod_tpu.core.config import Config
        monkeypatch.setenv("HOROVOD_SERVE_KERNEL", "PALLAS")
        assert Config.from_env().serve_kernel == "pallas"
        assert pp.resolve_kernel() == "pallas"
        monkeypatch.setenv("HOROVOD_SERVE_KERNEL", "auto")
        # auto off-TPU is the XLA oracle (CPU fallback)
        assert pp.resolve_kernel() == "xla"
        assert pp.resolve_kernel("pallas") == "pallas"  # explicit wins
        with pytest.raises(ValueError, match="serve kernel"):
            pp.resolve_kernel("bogus")

    def test_unknown_kernel_is_refused(self):
        # every decode config is a pool, so the fused kernel has no
        # layout left to refuse
        assert GPTConfig(decode=True, decode_kernel="pallas",
                         **_KW).decode_kernel == "pallas"
        with pytest.raises(ValueError, match="decode_kernel"):
            GPTConfig(decode=True, decode_kernel="triton", **_KW)


# ---------------------------------------------------------------------------
# on-device sampling semantics
# ---------------------------------------------------------------------------

class TestSamplingSemantics:
    def test_temperature_zero_is_greedy(self, gpt_params):
        rng = np.random.RandomState(5)
        prompts = [list(rng.randint(0, 64, 5)) for _ in range(4)]
        greedy = _drive(gpt_params, "xla", prompts)
        explicit = _drive(gpt_params, "xla", prompts,
                          sampling=dict(temperature=0.0, top_p=1.0,
                                        seed=123))
        assert explicit == greedy

    def test_seed_determinism_across_positions_and_restarts(
            self, gpt_params):
        """The same (prompt, seed) emits the same stream whether it
        runs alone, in a full batch at a different row, or on a fresh
        stack (restart)."""
        rng = np.random.RandomState(6)
        target = list(rng.randint(0, 64, 5))
        others = [list(rng.randint(0, 64, 5)) for _ in range(3)]
        s = dict(temperature=0.9, top_p=0.8, seed=777)
        alone = _drive(gpt_params, "xla", [target], sampling=s)
        # batched: other requests occupy lower rows, pushing the
        # target to a different batch position
        batched = _drive(gpt_params, "xla", others + [target],
                         sampling=s)
        assert batched[-1] == alone[0]
        restart = _drive(gpt_params, "xla", [target], sampling=s)
        assert restart[0] == alone[0]
        # a different seed must (for this workload) change the stream
        other_seed = _drive(gpt_params, "xla", [target],
                            sampling=dict(s, seed=778))
        assert other_seed[0] != alone[0]

    def test_top_p_one_is_plain_sampling(self, gpt_params):
        rng = np.random.RandomState(8)
        prompts = [list(rng.randint(0, 64, 5)) for _ in range(3)]
        a = _drive(gpt_params, "xla", prompts,
                   sampling=dict(temperature=1.1, top_p=1.0, seed=5))
        b = _drive(gpt_params, "xla", prompts,
                   sampling=dict(temperature=1.1, top_p=0.999999,
                                 seed=5))
        # p=1.0 keeps the full distribution; 1-eps drops at most
        # zero-probability tails — streams agree on this tiny model
        assert a == b

    def test_filtered_probs_edge_cases(self):
        logits = jnp.asarray([[2.0, 1.0, 0.5, -1.0]])
        one = jnp.ones(1)
        # top_p = 1.0 keeps everything
        f = pp.filtered_probs(logits, one, jnp.asarray([1.0]))
        assert np.all(np.asarray(f) > 0)
        assert np.isclose(float(f.sum()), 1.0, atol=1e-6)
        # single-token nucleus: tiny top_p keeps exactly the argmax
        f = pp.filtered_probs(logits, one, jnp.asarray([1e-6]))
        assert np.count_nonzero(np.asarray(f)) == 1
        assert int(np.argmax(np.asarray(f))) == 0
        # probability ties: stable sort keeps the LOWER token id when
        # the nucleus splits a tie
        tied = jnp.asarray([[1.0, 1.0, 1.0, 1.0]])
        f = np.asarray(pp.filtered_probs(tied, one,
                                         jnp.asarray([0.6])))
        assert np.count_nonzero(f) == 3 and f[0, 3] == 0.0
        # temperature <= 0 collapses to the one-hot argmax
        f = np.asarray(pp.filtered_probs(logits, jnp.zeros(1),
                                         jnp.asarray([0.3])))
        assert np.array_equal(f, [[1.0, 0.0, 0.0, 0.0]])

    def test_rejection_sampling_matches_target_distribution(self):
        """The acceptance-distribution law on an analytic toy pair
        (p, q): spec-emitted first tokens must be distributed as p,
        and the accept rate must match sum_i min(p_i, q_i)."""
        rng = np.random.RandomState(0)
        V, N, k = 8, 4000, 1
        p_log = jnp.asarray(rng.randn(V).astype(np.float32))
        q_log = jnp.asarray(rng.randn(V).astype(np.float32))
        temps, topps = jnp.ones(N), jnp.ones(N)
        seeds = jnp.arange(N, dtype=jnp.uint32)
        ctrs = jnp.zeros(N, jnp.int32)
        dq = pp.filtered_probs(jnp.broadcast_to(q_log, (N, V)), temps,
                               topps)
        dtok = pp._categorical(
            pp._row_keys(seeds, pp.STREAM_DRAFT, ctrs), dq)
        tokens = jnp.stack([jnp.zeros(N, jnp.int32), dtok], 1)
        tgt = jnp.broadcast_to(p_log, (N, k + 1, V))
        em, na = jax.jit(pp.speculative_accept)(
            tokens, dq[:, None], tgt, jnp.ones(N, jnp.int32), temps,
            topps, seeds, ctrs)
        first = np.asarray(em)[np.arange(N), 0]
        emp = np.bincount(first, minlength=V) / N
        want = np.asarray(jax.nn.softmax(p_log))
        tv = 0.5 * np.abs(emp - want).sum()
        assert tv < 0.05, f"TV distance {tv}"
        # analytic accept rate: sum_i min(p_i, q_i)
        qn = np.asarray(jax.nn.softmax(q_log))
        expect = float(np.minimum(want, qn).sum())
        got = float(np.asarray(na).mean())
        assert abs(got - expect) < 0.05, (got, expect)

    def test_spec_sampled_deterministic_and_accept_exported(
            self, gpt_params):
        """Sampled speculative serving: seed-deterministic end to end,
        accept-rate histogram exported."""
        rng = np.random.RandomState(9)
        prompts = [list(rng.randint(0, 64, 5)) for _ in range(3)]
        s = dict(temperature=0.8, top_p=0.9, seed=321)
        kw = dict(spec=True, num_layers=1)
        a = _drive(gpt_params, "xla", prompts, sampling=s, **kw)
        b = _drive(gpt_params, "xla", prompts, sampling=s, **kw)
        assert a == b
        from horovod_tpu.obs import metrics as obs_metrics
        fam = obs_metrics.get_registry().get(
            "hvd_serve_spec_accept_rate")
        assert fam is not None and fam.count > 0

    def test_submit_validation_fail_fast(self, gpt_params):
        _, q, _ = _stack(gpt_params, None, num_layers=1)
        with pytest.raises(ValueError, match="temperature"):
            q.submit([1, 2], temperature=-0.5)
        with pytest.raises(ValueError, match="top_p"):
            q.submit([1, 2], top_p=0.0)
        with pytest.raises(ValueError, match="top_p"):
            q.submit([1, 2], top_p=1.5)
