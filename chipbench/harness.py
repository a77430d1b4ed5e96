"""One cell, once, in one process: set-up, window, numbers, check.

    python -m chipbench --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The harness knows the order of a run and the shape of its last line; it
knows no cell, configuration, architecture or metric by name. The
configuration's file names its family (``chipbench/families/<name>.py``:
shape, reference, work counts, model objects, rehearsal widths); the
cell's traffic file names a runner (``chipbench/runners/<name>.py``)
which drives the system under test; with ``--trace 1`` each per-layer metric of the cell is read
by its own file (``chipbench/layer_metrics/<metric>.py``).

Order of a run: set-up (import, `hvd.init`, weights from the seed on the
device, warm-up of this cell's shapes, the checked first steps) ->
measured window -> peak memory read -> trace reduced -> program state
freed -> plain reference run and compared. Every number compared is
printed beside its limit, last on standard error and last in the result
line. Exit code 0 only with a result line printed.
"""
from __future__ import annotations

import argparse
import contextlib
import copy
import json
import shutil
import sys
import tempfile
import time
from typing import Any, Dict, List, Optional

from . import manifest as mf
from . import xplane

class Refused(Exception):
    """The run cannot be a measurement (no TPU, too few chips, unknown
    device kind): exit nonzero, print no result."""


def _merge(base: dict, over: dict) -> dict:
    out = copy.deepcopy(base)
    for k, v in over.items():
        out[k] = _merge(out[k], v) if isinstance(v, dict) and \
            isinstance(out.get(k), dict) else copy.deepcopy(v)
    return out


class Tracer:
    """The profiler around a slice of the window, and host spans.

    `span(name)` is a `jax.profiler.TraceAnnotation` named
    ``chipbench/<name>`` while the profiler runs (so device gaps can be
    laid against what the host was doing) and nothing otherwise."""

    def __init__(self, enabled: bool):
        self.enabled = enabled
        self.active = False
        self.done = False
        self.dir: Optional[str] = None
        self.t_start = self.t_stop = None
        self._window = None

    def start(self) -> None:
        if not self.enabled or self.active or self.done:
            return
        import jax
        self.dir = tempfile.mkdtemp(prefix="chipbench_trace_")
        jax.profiler.start_trace(self.dir)
        self.active = True
        self._window = jax.profiler.TraceAnnotation(xplane.WINDOW_SPAN)
        self._window.__enter__()
        self.t_start = time.perf_counter()

    def stop(self) -> None:
        if not self.active:
            return
        import jax
        self.t_stop = time.perf_counter()
        self._window.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self.active, self.done = False, True

    def span(self, name: str):
        if not self.active:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(xplane.SPAN_PREFIX + name)

    def load(self) -> Optional[xplane.Trace]:
        if not self.done:
            return None
        try:
            return xplane.load(xplane.find_xplane(self.dir))
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


class Run:
    """What a runner and the metric readers share about one run."""

    def __init__(self, cell: mf.Cell, seed: int, seconds: float,
                 trace: bool, rehearse: bool):
        self.cell = cell
        self.seed = seed
        self.seconds = seconds
        self.rehearse = rehearse
        self.config = cell.config
        self.traffic = cell.traffic
        #: whatever depends on the architecture is asked of this module
        self.family = cell.family()
        if rehearse:
            self.config = _merge(cell.config, self.family.REHEARSE_CONFIG)
            self.traffic = _merge(cell.traffic,
                                  cell.traffic.get("rehearse", {}))
        #: the family's reading of the configuration's sizes
        self.shape = self.family.Shape(self.config)
        self.chips = cell.chips
        self.tracer = Tracer(trace)
        self.devices: list = []
        self.peak: Dict[str, float] = {}
        #: filled by the runner: the window's length, and where a reader
        #: lays the program's own spans against it, its opening on
        #: `time.perf_counter()`
        self.window_s: float = 0.0
        self.window_open: Optional[float] = None
        self.attempted = 0
        self.failed = 0
        self.end_to_end: Dict[str, float] = {}
        #: what happened INSIDE the traced slice (counts the readers
        #: divide by trace times), filled by the runner
        self.traced: Dict[str, Any] = {}
        #: host-clock spans of the whole window: name -> [seconds]
        self.spans: Dict[str, List[float]] = {}
        self.trace: Optional[xplane.Trace] = None
        self.compiles_in_window = 0
        #: seconds of set-up by phase, for the info line on stderr
        self.phases: Dict[str, float] = {}

    @contextlib.contextmanager
    def phase(self, name: str):
        """Adds the block's wall time to set-up phase `name`."""
        t = time.perf_counter()
        try:
            yield
        finally:
            self.phases[name] = (self.phases.get(name, 0.0)
                                 + time.perf_counter() - t)


class CompileCount:
    """Backend compile requests of this process (persistent-cache hits
    included), through jax.monitoring: the recipe of `chip_smoke.py`."""
    _EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.programs = 0
        self.seconds = 0.0
        self._jax = jax
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, secs: float, **_kw) -> None:
        if event == self._EVENT:
            self.programs += 1
            self.seconds += secs

    def close(self) -> None:
        try:
            self._jax.monitoring.unregister_event_duration_listener(self._on)
        except (ValueError, AttributeError):
            pass


def device_gate(run: Run) -> dict:
    import jax
    devs = sorted(jax.devices(), key=lambda d: d.id)
    dev = devs[0]
    if run.rehearse:
        if len(devs) < run.chips:
            raise Refused(f"rehearsal of a {run.chips}-chip cell needs "
                          f"{run.chips} CPU devices, found {len(devs)}")
    else:
        if dev.platform != "tpu":
            raise Refused(
                f"chipbench measures a TPU, but jax.devices()[0].platform "
                f"is {dev.platform!r} ({dev.device_kind}, {len(devs)} "
                f"devices); nothing was run")
        if len(devs) < run.chips:
            raise Refused(f"cell {run.cell.name} needs {run.chips} chips, "
                          f"JAX found {len(devs)}")
    peaks = mf.load_json("chipbench/peaks.json", run.cell.root)
    kinds = peaks["device_kinds"]
    if run.rehearse:
        run.peak = dict(next(iter(kinds.values())))
    elif dev.device_kind not in kinds:
        raise Refused(f"device kind {dev.device_kind!r} is not in "
                      f"chipbench/peaks.json ({sorted(kinds)}): add its "
                      f"published peaks with their source")
    else:
        run.peak = dict(kinds[dev.device_kind])
    run.devices = devs[:run.chips]
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devs)}


def enable_cache(run: Run) -> None:
    """`JAX_COMPILATION_CACHE_DIR` if set, else ``<checkout>/.jax_cache``
    (the program's one helper decides; the path is fixed, so a cell's
    second run fetches what its first compiled). A rehearsal compiles
    for the CPU and keeps nothing."""
    if run.rehearse:
        return
    from horovod_tpu.compile_cache import enable_compile_cache
    enable_compile_cache()


def _memory_peak(devices) -> int:
    peak = 0
    for d in devices:
        st = d.memory_stats() or {}
        peak = max(peak, int(st.get("peak_bytes_in_use") or 0))
    return peak


def _read_layers(run: Run) -> Dict[str, dict]:
    out = {}
    for m in run.cell.per_layer:
        value = run.cell.reader(m["name"]).read(run)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def compare(compared: List[dict]) -> bool:
    """`correct`: every compared number is finite and within its limit
    (``value <= limit``; an exact comparison has the limit 0)."""
    ok = bool(compared)
    for c in compared:
        v = c["value"]
        if not (v == v and abs(v) != float("inf") and v <= c["limit"]):
            ok = False
    return ok


def main(argv: Optional[List[str]] = None, t0: Optional[float] = None,
         root: str = mf.ROOT) -> int:
    t0 = time.perf_counter() if t0 is None else t0
    ap = argparse.ArgumentParser(prog="python -m chipbench")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny widths on the CPU with interpret-mode "
                         "kernels: for tests and the sandbox; prints "
                         "under no device metric's name")
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed is a non-negative whole number")

    try:
        manifest = mf.load(root)
        cell = mf.Cell(manifest, args.workload, root)
        run = Run(cell, args.seed, args.seconds, bool(args.trace),
                  args.rehearse)
        device = device_gate(run)
        enable_cache(run)
        runner = cell.runner().Runner(run)
    except (Refused, mf.ManifestError, FileNotFoundError,
            ImportError) as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 1

    compiles = CompileCount()
    try:
        t_built = time.perf_counter()
        runner.setup()
        setup_s = time.perf_counter() - t0
        setup_compiles = (compiles.programs, compiles.seconds)
        runner.window()
        run.compiles_in_window = compiles.programs - setup_compiles[0]
        run.tracer.stop()       # a runner that forgot must not leak it
        memory_peak = _memory_peak(run.devices)
        metrics: Dict[str, dict] = {}
        breakdown = None
        if args.trace:
            run.trace = run.tracer.load()
            metrics = _read_layers(run)
            if run.trace is not None and run.trace.ops:
                lo, hi = xplane.window(run.trace)
                busy = xplane.busy_by_device(run.trace)
                device["busy_s"] = sum(busy.values()) / len(busy)
                device["window_s"] = hi - lo
                breakdown = {
                    "device_ops": xplane.top_device_ops(run.trace),
                    "idle_gaps": xplane.idle_gaps_by_span(run.trace)}
            run.trace = None
        else:
            values = dict(run.end_to_end, setup_s=setup_s)
            for m in cell.end_to_end:
                if m["name"] in values:
                    metrics[m["name"]] = {"value": float(values[m["name"]]),
                                          "unit": m["unit"]}
        device["memory_peak_bytes"] = memory_peak
        t_check = time.perf_counter()
        compared = runner.check()
        phases = " ".join(f"{k} {v:.2f}" for k, v in run.phases.items())
        print(f"info setup_s {setup_s:.3f} (to the runner {t_built - t0:.3f}; "
              f"{phases}; {setup_compiles[0]} programs compiled or fetched "
              f"in {setup_compiles[1]:.2f} s) window_s {run.window_s:.3f} "
              f"check_s {time.perf_counter() - t_check:.3f}",
              file=sys.stderr)
    finally:
        run.tracer.stop()
        runner.close()
        compiles.close()

    correct = compare(compared) and run.compiles_in_window == 0
    compared.append({"name": "compiles_in_window",
                     "value": float(run.compiles_in_window), "limit": 0.0})
    result = {"correct": correct, "attempted": run.attempted,
              "failed": run.failed, "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    if args.rehearse:
        # a CPU number is never printed under a device metric's name
        result["metrics"] = {f"rehearsal.{k}": v for k, v in metrics.items()}
        result["rehearsal"] = True
    result["compared"] = {c["name"]: {"value": c["value"],
                                      "limit": c["limit"]}
                          for c in compared}
    sys.stdout.flush()
    for c in compared:
        print(f"compared {c['name']} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0
