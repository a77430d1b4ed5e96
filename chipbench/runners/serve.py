"""Serving cells: `AdmissionQueue.submit` -> `ContinuousBatcher.step`.

One replica on the first chip: `ShardedExecutor` (paged KV, the decode
kernel left to the platform unless the traffic file says otherwise),
`AdmissionQueue`, `ContinuousBatcher` with the settings of the traffic
file's ``server`` group. The harness drives `batcher.step()` itself on
the main thread (no second thread competes for the interpreter) and
plays the callers between iterations: a closed loop of ``callers``, each
submitting its next request when its last resolves.

What is timed from outside, by the harness's own clock: submit ->
`ServeHandle` resolved per request (stamped in the handle's
`on_resolve` hook), each `batcher.step()` and, inside it, each
`executor.step()` (wrapped here, with the tokens and cached lengths it
was given, so the FLOP and byte functions know the work, and with the
tokens it emitted: the window's rate is all of them over its length).
Past the close nothing is submitted and the eight requests in flight
run to their end outside the measured time, so that each token counted
is seen delivered and each answer is judged.

After the window the program is dropped and the plain reference reads a
sample of the finished requests, the longest among them: one forward
pass over each prompt with its served tokens, and at every served
position the gap by which the served token's reference logit lies below
the reference's best. Greedy decoding in exact arithmetic gives 0; the
widest gap is what `check` compares.

No model is named here. The configuration's family (`run.family`,
``chipbench/families/<family>.py``) gives the model object and the
parameters the executor gets, and the reference's weights and logits.
"""
from __future__ import annotations

import contextlib
import sys
import time
from typing import List

import numpy as np


#: what this runner asks of the configuration's family
FAMILY_NEEDS = ("seed_key", "program_params", "serve_model",
                "reference_weights", "logits_at", "CONTROL")


class _Caller:
    __slots__ = ("handle", "request", "t_submit", "t_done")

    def __init__(self):
        self.handle = None
        self.request = None
        self.t_submit = 0.0
        self.t_done = None


def percentile(values: List[float], q: float) -> float:
    """The q-th percentile by linear interpolation (numpy's default)."""
    return float(np.percentile(np.asarray(values, np.float64), q))


class Runner:
    def __init__(self, run):
        self.run = run
        self.shape = run.shape
        self.executor = self.batcher = self.queue = None
        self.finished: List[dict] = []
        self.emitted = (0, 0)
        self.steps: List[dict] = []       # one record per executor step
        self.iterations: List[tuple] = []  # (t0, t1, executor seconds)

    # -- set-up -----------------------------------------------------------
    def setup(self) -> None:
        import jax

        import horovod_tpu as hvd
        from horovod_tpu.serve import (AdmissionQueue, ContinuousBatcher,
                                       ShardedExecutor, pool_blocks_for)

        run, shape, tr = self.run, self.shape, self.run.traffic
        family, sv = run.family, tr["server"]
        with run.phase("init"):
            hvd.init()
        B, max_len, block = sv["max_batch"], sv["max_len"], sv["kv_block"]
        kernel = sv.get("decode_kernel")
        if run.rehearse and kernel is None:
            kernel = "pallas"           # interpret mode off the TPU
        model = family.serve_model(
            shape, run.config, kv_block=block,
            kv_pool_blocks=pool_blocks_for(B, max_len, block),
            decode_kernel=kernel)
        # one replica on the default (first) device, as a caller of the
        # serving API gets it; no `default_device` scope here: it is part
        # of jit's cache key, and the window runs outside any
        with run.phase("weights"):
            params = jax.block_until_ready(jax.jit(
                lambda k: family.program_params(shape, k))(
                    family.seed_key(run.seed)))
        with run.phase("executor"):
            self.executor = ShardedExecutor(model, params, max_batch=B,
                                            max_len=max_len)
        del params
        self.queue = AdmissionQueue(
            max_queue=sv["max_queue"],
            default_deadline_ms=sv["deadline_ms"])
        self.batcher = ContinuousBatcher(
            self.executor, self.queue,
            buckets=tuple(sv["prefill_buckets"]),
            prefix_cache=sv["prefix_cache"], kv_crc=sv["kv_crc"],
            kv_tier=sv["kv_tier"], spec_k=sv["spec_k"])
        with run.phase("warmup"):
            self.batcher.warmup()
        self._wrap_executor()
        self._requests = run.cell.generator().requests(
            tr, shape.vocab, run.seed)

    def _wrap_executor(self) -> None:
        """Time every `executor.step` from outside and note the work it
        was handed: rows, tokens and the keys each token attends to (the
        sums, and each row's own cached length ``rows_start`` and, in a
        prefill, ``rows_tokens``; a decode step adds one token a row: for
        a family whose layers do not all read the whole context)."""
        ex, inner, run = self.executor, self.executor.step, self.run

        def step(tokens, positions, mask, last_idx, *, kind="decode", **kw):
            t0 = time.perf_counter()
            with run.tracer.span(f"executor_{kind}"):
                out = inner(tokens, positions, mask, last_idx, kind=kind,
                            **kw)
            t1 = time.perf_counter()
            rows = np.flatnonzero(mask)
            start = np.asarray(positions)[rows].astype(np.int64)
            if kind == "prefill":
                n = np.asarray(last_idx)[rows].astype(np.int64) + 1
                rec = {"prompt_tokens": int(n.sum()),
                       "prompt_context": int((n * start
                                              + n * (n + 1) // 2).sum()),
                       "decode_tokens": 0, "decode_context": 0,
                       "rows_start": start, "rows_tokens": n}
            else:
                rec = {"prompt_tokens": 0, "prompt_context": 0,
                       "decode_tokens": int(rows.size),
                       "decode_context": int((start + 1).sum()),
                       "rows_start": start}
            rec.update(kind=kind, t0=t0, t1=t1, emitted=int(rows.size),
                       width=int(np.asarray(tokens).shape[1]))
            self.steps.append(rec)
            return out
        ex.step = step

    # -- the measured window ----------------------------------------------
    def _submit(self, caller: _Caller) -> None:
        req = next(self._requests)
        caller.request, caller.t_done = req, None

        def resolved(_handle, c=caller):
            c.t_done = time.perf_counter()
        caller.t_submit = time.perf_counter()
        with self.run.tracer.span("submit"):
            caller.handle = self.queue.submit(
                req["prompt"], max_new_tokens=req["max_new_tokens"],
                temperature=req["temperature"], on_resolve=resolved)

    def window(self) -> None:
        run, tr = self.run, self.run.traffic
        trace_seconds = float(tr.get("trace_seconds", 4.0))
        callers = [_Caller() for _ in range(int(tr["callers"]))]
        t_start = run.window_open = time.perf_counter()
        t_end = t_start + run.seconds
        t_trace = t_start + min(2.0, run.seconds / 4)
        t_undisturbed = t_start    # requests submitted before it are
        # left out of the latencies: the profiler's stop stalls the loop
        for c in callers:
            self._submit(c)
        while True:
            now = time.perf_counter()
            if now >= t_end:
                break
            if run.tracer.enabled and not run.tracer.done:
                if not run.tracer.active and now >= t_trace:
                    run.tracer.start()
                elif run.tracer.active and \
                        now - run.tracer.t_start >= trace_seconds:
                    run.tracer.stop()       # blocks for seconds
                    t_undisturbed = time.perf_counter()
            n_steps = len(self.steps)
            t0 = time.perf_counter()
            with run.tracer.span("schedule"):
                self.batcher.step()
            t1 = time.perf_counter()
            inside = sum(s["t1"] - s["t0"] for s in self.steps[n_steps:])
            self.iterations.append((t0, t1, inside))
            self._collect(callers, t1, resubmit=True)
        run.tracer.stop()
        run.window_s = time.perf_counter() - t_start
        in_window = len(self.finished)
        steps_in_window = len(self.steps)
        # past the close nothing is submitted, and the requests in flight
        # run to their end: their tokens made inside the window were work
        # of the window, and an answer that comes late is late, not wrong
        t_give_up = time.perf_counter() + 60.0
        while any(c.handle is not None for c in callers) \
                and time.perf_counter() < t_give_up:
            self.batcher.step()
            self._collect(callers, time.perf_counter(), resubmit=False)
        never = sum(1 for c in callers if c.handle is not None)
        ok = [f for f in self.finished[:in_window] if f["status"] == "ok"]
        run.attempted = len(self.finished) + never
        run.failed = never + sum(1 for f in self.finished
                                 if f["status"] != "ok")
        #: tokens the steps emitted, inside the window and after it
        self.emitted = (
            sum(st["emitted"] for st in self.steps[:steps_in_window]),
            sum(st["emitted"] for st in self.steps[steps_in_window:]))
        del self.steps[steps_in_window:]
        run.spans["executor_decode"] = [
            s["t1"] - s["t0"] for s in self.steps if s["kind"] == "decode"]
        run.spans["schedule_self"] = [
            (t1 - t0) - inside for t0, t1, inside in self.iterations]
        kinds: dict = {}
        for st in self.steps:
            key = f"{st['kind']}{st['width']}"
            n, secs = kinds.get(key, (0, 0.0))
            kinds[key] = (n + 1, secs + st["t1"] - st["t0"])
        print("info window " + " ".join(
            f"{k} {n} steps {secs:.2f} s" for k, (n, secs) in
            sorted(kinds.items())) + f"; scheduler "
            f"{sum(run.spans['schedule_self']):.2f} s; finished "
            f"{in_window} in the window, {len(self.finished) - in_window} "
            f"after its close", file=sys.stderr)
        if run.tracer.done:
            lo, hi = run.tracer.t_start, run.tracer.t_stop
            inside = [s for s in self.steps if lo <= s["t0"] and s["t1"] <= hi]
            run.traced = {"seconds": hi - lo, "steps": inside}
        run.spans["request_latency_ms"] = [
            f["latency_ms"] for f in ok if f["t_submit"] >= t_undisturbed]
        if ok:
            # every token an executor step of the window emitted (the
            # harness's own count at the call; each is delivered when its
            # request resolves, inside the window or in the drain above)
            run.end_to_end["serve_tokens_per_s"] = (
                self.emitted[0] / run.window_s)
        lat = run.spans["request_latency_ms"]
        if lat:
            print(f"info latency of {len(lat)} requests resolved in the "
                  f"window: p50 {percentile(lat, 50):.1f} ms p90 "
                  f"{percentile(lat, 90):.1f} ms", file=sys.stderr)

    def _collect(self, callers, now: float, *, resubmit: bool) -> None:
        """Move resolved handles to `finished`; the caller submits its
        next request, or (past the close) leaves."""
        for c in callers:
            if c.handle is None or not c.handle.done():
                continue
            h = c.handle
            self.finished.append({
                "prompt": c.request["prompt"],
                "max_new_tokens": c.request["max_new_tokens"],
                "tokens": list(h.tokens), "status": h.status,
                "t_submit": c.t_submit,
                "latency_ms": 1e3 * ((c.t_done or now) - c.t_submit)})
            c.handle = None
            if resubmit:
                self._submit(c)

    # -- the comparison ---------------------------------------------------
    def sample(self) -> List[dict]:
        """The finished requests the reference reads: `checked_requests`
        drawn from the seed, and the longest of all."""
        ok = [f for f in self.finished if f["status"] == "ok" and f["tokens"]]
        if not ok:
            return []
        rng = np.random.default_rng([int(self.run.seed), 0xC4EC])
        k = min(int(self.run.traffic["checked_requests"]), len(ok))
        picked = [ok[i] for i in rng.choice(len(ok), k, replace=False)]
        longest = max(ok, key=lambda f: len(f["prompt"]) + len(f["tokens"]))
        if all(longest is not p for p in picked):
            picked.append(longest)
        return picked

    def _drop_program(self) -> None:
        """Free the weights, the KV pool and the compiled steps. The
        wrapper on `executor.step` closes a reference cycle, so collect:
        the reference's own weights need the room."""
        import gc
        self.executor = self.batcher = self.queue = None
        gc.collect()

    def check(self) -> List[dict]:
        run, shape = self.run, self.shape
        picked = self.sample()
        bad = sum(1 for f in self.finished
                  if f["status"] != "ok"
                  or len(f["tokens"]) != f["max_new_tokens"]
                  or not all(0 <= t < shape.padded_vocab
                             for t in f["tokens"]))
        # the rate counts tokens where the steps emit them: every one of
        # them has to have been delivered, and no other
        uncounted = abs(sum(self.emitted)
                        - sum(len(f["tokens"]) for f in self.finished))
        self._drop_program()
        limits = run.cell.limits(run.rehearse)
        gaps = served_gaps(run.family, shape, run.seed, picked,
                           pad_to=int(run.traffic["server"]["max_len"]),
                           device=run.devices[0])
        widest = max((g["served"].max() for g in gaps), default=float("nan"))
        return [
            {"name": "served_logit_gap", "value": float(widest),
             "limit": limits["served_logit_gap"]},
            {"name": "bad_answers", "value": float(bad), "limit": 0.0},
            {"name": "uncounted_tokens", "value": float(uncounted),
             "limit": 0.0},
        ]

    def calibrate(self, seeds, control_seeds) -> List[dict]:
        """Readings for setting the limit, in one process: per seed a
        short window at the cell's own load, then the reference over the
        sample; on `control_seeds` also the token the fp8 reference puts
        first at each of the same positions (the control)."""
        run = self.run
        rows = []
        family, control = run.family, run.family.CONTROL
        first = True
        for seed in seeds:
            if not first:
                run.seed = seed
                self.finished, self.steps, self.iterations = [], [], []
                self.setup()
            first = False
            self.window()
            picked = self.sample()
            self._drop_program()
            precisions = (control,) if seed in control_seeds else ()
            gaps = served_gaps(
                family, self.shape, seed, picked,
                pad_to=int(run.traffic["server"]["max_len"]),
                device=run.devices[0], controls=precisions)
            row = {"seed": seed, "finished": len(self.finished),
                   "checked_tokens": sum(len(g["served"]) for g in gaps),
                   "served_logit_gap":
                       float(max(g["served"].max() for g in gaps)),
                   "top2_margin_median": float(np.median(
                       np.concatenate([g["margin"] for g in gaps])))}
            for p in precisions:
                row[f"control_{p}_gap"] = float(
                    max(g[p].max() for g in gaps))
            rows.append(row)
        return rows

    def close(self) -> None:
        self._drop_program()


def served_gaps(family, shape, seed: int, picked: List[dict], *,
                pad_to: int, device=None, controls=()) -> List[dict]:
    """For each picked request: ``served`` [n] the gap, at every served
    position, between the reference's best logit and its logit of the
    served token; ``margin`` [n] the reference's own top-1 to top-2
    margin there; and per control precision the gap of the token that
    precision puts first. The reference is the family's: it makes its own
    weights from the seed and reads one request at a time, padded to
    `pad_to` (causal, so the padding is never seen)."""
    import jax
    import jax.numpy as jnp

    if not picked:
        return []
    n_max = max(len(f["tokens"]) for f in picked)

    def read(w, tokens, where, served):
        lg = family.logits_at(w, shape, tokens, where)      # [n_max, V]
        top2 = jax.lax.top_k(lg, 2)[0]
        mine = jnp.take_along_axis(lg, served[:, None], axis=1)[:, 0]
        out = {"served": top2[:, 0] - mine, "margin": top2[:, 0] - top2[:, 1]}
        for p in controls:
            first = jnp.argmax(family.logits_at(w, shape, tokens, where,
                                                precision=p), axis=-1)
            out[p] = top2[:, 0] - jnp.take_along_axis(
                lg, first[:, None], axis=1)[:, 0]
        return out

    ctx = jax.default_device(device) if device is not None \
        else contextlib.nullcontext()
    with ctx:
        w = jax.jit(lambda k: family.reference_weights(shape, k))(
            family.seed_key(seed))
        read = jax.jit(read)
        out = []
        for f in picked:
            n, p = len(f["tokens"]), len(f["prompt"])
            seq = (f["prompt"] + f["tokens"])[:pad_to]
            tokens = np.zeros((1, pad_to), np.int32)
            tokens[0, :len(seq)] = seq
            # served token i was chosen from the logits at position p-1+i
            where = np.minimum(p - 1 + np.arange(n_max), pad_to - 1)
            served = np.zeros(n_max, np.int32)
            served[:n] = f["tokens"]
            got = read(w, jnp.asarray(tokens), jnp.asarray(where, jnp.int32),
                       jnp.asarray(served))
            out.append({k: np.asarray(v)[:n] for k, v in got.items()})
    return out
