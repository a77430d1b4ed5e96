"""Training cells: `training.make_train_step` over `make_mesh(dp=chips)`.

The product's data-parallel path: one jitted shard_map step (forward,
backward with the flash-attention kernels, the fused cross-entropy
kernel on the flattened local logits, gradients reduced in-graph by
`DistributedOptimizer`, the optax update), parameters and optimizer
state replicated, the batch split over ``dp``.

Set-up builds ONE step object with its state, drives it through the
`checked_steps` first steps on the window's own call and feed (that is
also the warm-up: the first call compiles), and hands the same object to
the window. After the window the state is freed and the plain reference
follows the same first steps from the same seed; `check` compares each
step's loss, the first gradient's norm as the optimizer got it (Adam's first moment after one step is
``(1 - b1) * g``) and the norm of the parameters' change, by the worst
leaf. See PERF.md section 2 for the readings behind each limit.

No model is named here. The configuration's family (`run.family`,
``chipbench/families/<family>.py``) gives the model whose `apply` the
step gets, the parameters from the seed and their per-leaf norms, and the
reference's trainer and norms.
"""
from __future__ import annotations

import collections
import contextlib
import sys
import time
from typing import Dict, List

import numpy as np


#: what this runner asks of the configuration's family
FAMILY_NEEDS = ("seed_key", "program_params", "train_model",
                "program_leaf_norms", "Trainer", "reference_leaf_norms",
                "CONTROL")


def _first_moment(opt_state):
    """Adam's `mu` tree inside whatever the optimizer wraps it in."""
    import jax
    found = [x for x in jax.tree.leaves(
        opt_state, is_leaf=lambda x: hasattr(x, "mu") and hasattr(x, "nu"))
        if hasattr(x, "mu")]
    if len(found) != 1:
        raise RuntimeError(f"expected one Adam state in the optimizer "
                           f"state, found {len(found)}")
    return found[0].mu


def worst_leaf_gap(prog: Dict[str, float], want: Dict[str, float],
                   skip=()) -> float:
    """max over leaves of |prog - want| / max(want, median(want)): the
    gap between the two NORMS of a leaf, against the reference's norm of
    that leaf or of the median leaf, whichever is larger."""
    med = float(np.median(list(want.values())))
    return max(abs(prog[k] - want[k]) / max(want[k], med)
               for k in want if k not in skip)


def compare_steps(prog: dict, want: dict, limits: dict) -> List[dict]:
    """The compared numbers of a training cell. `prog` / `want`:
    ``{"loss": [..], "grad": {leaf: norm}, "change": {leaf: norm}}``.
    Leaves whose reference gradient is under a thousandth of the median
    leaf's move under Adam by round-off alone and are left out of the
    change (a rule on the reference's gradient, not a list of names)."""
    med_g = float(np.median(list(want["grad"].values())))
    dead = {k for k, g in want["grad"].items() if g < 1e-3 * med_g}
    loss_gap = max(abs(a - b) / abs(b)
                   for a, b in zip(prog["loss"], want["loss"]))
    return [
        {"name": "loss_gap", "value": float(loss_gap),
         "limit": limits["loss_gap"]},
        {"name": "grad_norm_gap", "value":
            float(worst_leaf_gap(prog["grad"], want["grad"])),
         "limit": limits["grad_norm_gap"]},
        {"name": "change_norm_gap", "value":
            float(worst_leaf_gap(prog["change"], want["change"], dead)),
         "limit": limits["change_norm_gap"]},
    ]


class Runner:
    #: never more steps in flight than this, whatever the traffic asks:
    #: the wait at the window's close is that many steps long
    MAX_AHEAD = 64

    def __init__(self, run):
        self.run = run
        self.shape = run.shape
        self.state = None
        self.readings: dict = {}

    # -- set-up -----------------------------------------------------------
    def setup(self) -> None:
        import jax
        import optax
        from jax.sharding import NamedSharding, PartitionSpec as P

        import horovod_tpu as hvd
        from horovod_tpu.ops.pallas_ce import fused_cross_entropy
        from horovod_tpu.parallel.mesh_utils import make_mesh
        from horovod_tpu.training import make_train_step

        run, shape, tr = self.run, self.shape, self.run.traffic
        family = run.family
        with run.phase("init"):
            hvd.init()
            self.mesh = make_mesh(dp=run.chips, devices=run.devices)
        kernels = "interpret" if run.rehearse else None
        model = family.train_model(shape, run.config, kernels=kernels)

        def loss_fn(logits, labels):
            # the fused CE kernel on this chip's flattened logits
            return fused_cross_entropy(
                logits.reshape(-1, logits.shape[-1]), labels.reshape(-1),
                force=kernels)

        o = tr["optimizer"]
        if o["name"] != "adamw":
            raise ValueError(f"optimizer {o['name']!r}: the reference "
                             f"writes out adamw only")
        tx = optax.adamw(o["learning_rate"], b1=o["b1"], b2=o["b2"],
                         eps=o["eps"], weight_decay=o["weight_decay"])
        self.step = make_train_step(model.apply, tx, self.mesh,
                                    axis_name="dp", loss_fn=loss_fn)
        repl = NamedSharding(self.mesh, P())
        self._batch_sh = NamedSharding(self.mesh, P("dp"))
        self._init = jax.jit(lambda k: family.program_params(shape, k),
                             out_shardings=repl)
        self._init_opt = jax.jit(self.step.init_opt_state,
                                 out_shardings=repl)

        self.tokens_per_step = (int(tr["rows_per_chip"]) * run.chips
                                * int(tr["seq_len"]))
        self._norms = jax.jit(lambda t: family.program_leaf_norms(t, shape))
        self._change = jax.jit(lambda p, k: family.program_leaf_norms(
            jax.tree.map(lambda a, b: a - b, p, self._init(k)), shape))
        self.readings = self.first_steps(run.seed)

    def first_steps(self, seed: int) -> dict:
        """Fresh state from `seed`, then the checked first steps through
        the window's own call and feed. Returns the program's readings;
        leaves `self.state` and `self._feed` ready for the window."""
        import jax
        tr, shape = self.run.traffic, self.shape
        key = self.run.family.seed_key(seed)
        self.state = None
        phase = self.run.phase
        with phase("weights"):
            params = jax.block_until_ready(self._init(key))
            self.state = (params, jax.block_until_ready(
                self._init_opt(params)), {})
        self._feed = self.run.cell.generator().batches(tr, shape.vocab, seed)
        self._checked = []
        losses, grad, took = [], None, []
        for i in range(int(tr["checked_steps"])):
            with phase("first_step" if i == 0 else "next_steps"):
                t = time.perf_counter()
                host = next(self._feed)
                self._checked.append(host)
                losses.append(float(self._dispatch(self._put(host))))
                took.append(time.perf_counter() - t)
            if i == 0:
                with phase("norms"):
                    b1 = tr["optimizer"]["b1"]
                    grad = {k: float(v) / (1 - b1)
                            for k, v in jax.device_get(self._norms(
                                _first_moment(self.state[1]))).items()}
        with phase("norms"):
            moved = {k: float(v) for k, v in jax.device_get(
                self._change(self.state[0], key)).items()}
        # a fenced step after the first, which compiles or fetches: what
        # the window sizes its dispatch-ahead by
        self._step_s = min(took[1:] or took)
        return {"loss": losses, "grad": grad, "change": moved}

    def _put(self, host):
        import jax
        return tuple(jax.device_put(x, self._batch_sh) for x in host)

    def _dispatch(self, batch):
        """One step through the compiled object; the state is donated
        and replaced. Returns the (not yet fetched) loss."""
        params, opt_state, stats = self.state
        params, opt_state, stats, loss = self.step(
            params, opt_state, stats, *batch)
        self.state = (params, opt_state, stats)
        return loss

    # -- the measured window ----------------------------------------------
    def window(self) -> None:
        """Steps are dispatched `dispatch_ahead_s` seconds ahead of the one
        the host waits for, so the chip stays fed while the host stands
        still. When the time is up nothing more is sent, every step that
        was sent is waited for, and the clock is read after that wait:
        all of that work counts, over all of that time."""
        import jax
        run, tr = self.run, self.run.traffic
        trace_steps = int(tr.get("trace_steps", 8))
        ahead = max(1, min(self.MAX_AHEAD, int(np.ceil(
            float(tr["dispatch_ahead_s"]) / self._step_s))))
        pending: collections.deque = collections.deque()
        done_at: List[float] = []

        def wait(n_left: int) -> None:
            # the fence: a step counts once its loss is ready
            with run.tracer.span("fence"):
                while len(pending) > n_left:
                    jax.block_until_ready(pending.popleft())
                    done_at.append(time.perf_counter())

        nxt = self._put(next(self._feed))
        steps = 0
        traced_from = None
        t_start = time.perf_counter()
        t_end = t_start + run.seconds
        while time.perf_counter() < t_end:
            if steps == 2 and run.tracer.enabled and traced_from is None:
                wait(0)         # the slice starts and ends on an idle chip
                run.tracer.start()
                traced_from = steps
            with run.tracer.span("step"):
                loss = self._dispatch(nxt)
                pending.append(loss)
                # the next batch is made and uploaded while the chip works
                with run.tracer.span("feed"):
                    nxt = self._put(next(self._feed))
            steps += 1
            if run.tracer.active and steps - traced_from >= trace_steps:
                wait(0)
                run.tracer.stop()
                run.traced = {"steps": steps - traced_from,
                              "tokens": (steps - traced_from)
                              * self.tokens_per_step,
                              "seconds": run.tracer.t_stop
                              - run.tracer.t_start}
            wait(ahead)
        t_sent = time.perf_counter()
        wait(0)
        run.window_s = time.perf_counter() - t_start
        run.tracer.stop()
        run.attempted = steps
        run.failed = 0 if np.isfinite(float(loss)) else steps
        gaps = np.diff([t_start] + done_at)
        run.spans["step"] = [float(g) for g in gaps]
        # a stall shows as one long gap between two completions; the chip
        # ran dry only if the gap was longer than the steps sent ahead
        med = float(np.median(gaps))
        print(f"info window {steps} steps, {ahead} sent ahead: median gap "
              f"between completions {1e3 * med:.2f} ms, longest "
              f"{1e3 * float(gaps.max()):.2f} ms, "
              f"{int((gaps > 1.5 * med).sum())} over 1.5 x the median; "
              f"{run.window_s - (t_sent - t_start):.2f} s waited after the "
              f"last was sent", file=sys.stderr)
        run.end_to_end["train_tokens_per_s"] = (
            steps * self.tokens_per_step / run.window_s)

    # -- the comparison ---------------------------------------------------
    def check(self) -> List[dict]:
        import jax
        run, shape, tr = self.run, self.shape, self.run.traffic
        self.state = None            # free the program's state first
        want = reference_steps(
            run.family, shape, tr["optimizer"], run.seed, self._checked,
            rows_per_block=int(tr.get("reference_rows_per_block", 1)),
            device=run.devices[0])
        return compare_steps(self.readings, want,
                             run.cell.limits(run.rehearse))

    def calibrate(self, seeds, control_seeds) -> List[dict]:
        """Readings for setting limits, in one process: the program
        against the reference on every seed; on `control_seeds` also the
        reference in the family's control precision (fp8 below bfloat16)
        put in the program's place (the control) and
        the reference with half of the batch, or all but the first
        chip's rows, left out (the faults a training cell can have)."""
        run, shape, tr = self.run, self.shape, self.run.traffic
        family, control = run.family, run.family.CONTROL
        prog = {}
        for seed in seeds:
            prog[seed] = (self.first_steps(seed), self._checked)
        self.state = None
        limits = run.cell.limits(run.rehearse)
        kw = dict(rows_per_block=int(tr.get("reference_rows_per_block", 1)),
                  device=run.devices[0])
        n = int(tr["rows_per_chip"]) * run.chips
        rows = []
        for seed in seeds:
            readings, batches = prog[seed]
            want = reference_steps(family, shape, tr["optimizer"], seed,
                                   batches, **kw)
            sides = {"program": readings}
            if seed in control_seeds:
                sides[f"control_{control}"] = reference_steps(
                    family, shape, tr["optimizer"], seed, batches,
                    precision=control, **kw)
                sides["fault_half_batch"] = reference_steps(
                    family, shape, tr["optimizer"], seed, batches,
                    rows=slice(0, n // 2), **kw)
                if run.chips > 1:
                    sides["fault_no_exchange"] = reference_steps(
                        family, shape, tr["optimizer"], seed, batches,
                        rows=slice(0, int(tr["rows_per_chip"])), **kw)
            for side, got in sides.items():
                rows.append({"seed": seed, "side": side, **{
                    c["name"]: c["value"]
                    for c in compare_steps(got, want, limits)}})
        return rows

    def close(self) -> None:
        self.state = None


_TRAINERS: Dict[tuple, object] = {}


def _trainer(family, shape, optimizer: dict, precision: str,
             rows_per_block: int):
    """One of the family's `Trainer`s (and so one compilation) per
    shape, optimizer and precision, however many seeds follow it."""
    key = (family.__name__, tuple(sorted(vars(shape).items())),
           tuple(sorted(optimizer.items())), precision, rows_per_block)
    if key not in _TRAINERS:
        _TRAINERS[key] = family.Trainer(shape, optimizer, precision,
                                        rows_per_block)
    return _TRAINERS[key]


def reference_steps(family, shape, optimizer: dict, seed: int,
                    batches, *, rows_per_block: int = 1, device=None,
                    precision: str = "float32", rows=None) -> dict:
    """The reference's readings over the checked steps: losses, first
    gradient norms, change norms. `rows` (a slice) restricts every batch
    to some of its rows: how the left-out-half and left-out-exchange
    faults are planted in the reference for calibration."""
    import jax
    trainer = _trainer(family, shape, optimizer, precision, rows_per_block)
    with jax.default_device(device) if device is not None \
            else contextlib.nullcontext():
        key = family.seed_key(seed)
        w = trainer.weights(key)
        m, v = trainer.init(w)
        losses, grad = [], None
        for t, (tokens, labels) in enumerate(batches, start=1):
            if rows is not None:
                tokens, labels = tokens[rows], labels[rows]
            loss, g = trainer.grads(w, tokens, labels)
            losses.append(float(loss))
            if t == 1:
                grad = family.reference_leaf_norms(g)
            w, m, v = trainer.step(w, m, v, g, t)
        change = family.reference_leaf_norms(trainer.moved(w, key))
    return {"loss": losses, "grad": grad, "change": change}
