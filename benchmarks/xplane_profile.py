#!/usr/bin/env python
"""Xplane profile capture + measured-vs-modeled roofline validation.

VERDICT r4 items 1/weak-2: the 38.8%-MFU "HBM-bound ceiling" claimed by
benchmarks/resnet_roofline.py was an analytic model no profile had
validated. This harness captures a real `jax.profiler.trace` over timed
ResNet-50 steps on the chip, parses the xplane with
`jax.profiler.ProfileData` (jaxlib's own xspace reader), and reports:

  - per-step device time (from the XLA Modules line, one event per
    executed module) vs the roofline's serial/overlap floors
  - per-category device self-time (conv / BN-ish elementwise fusions /
    copies / optimizer / other) from the XLA Ops line
  - achieved HBM GB/s from per-op `bytes accessed` stats where the
    profile carries them, vs the modeled 819 GB/s bound

The reference's analog evidence is its Tensor Fusion + timeline docs
(/root/reference/docs/timeline.rst) — profiling is how it argues its
overheads away; here it is how we validate (or refute) the roofline.

Usage (on the chip, machine otherwise quiet):
    python benchmarks/xplane_profile.py            # capture + parse
    python benchmarks/xplane_profile.py --parse-only DIR  # re-parse

Emits one JSON line and writes the parsed op table to
benchmarks/xplane_op_table.json for the docs.
"""
import argparse
import glob
import json
import os
import re
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def _hlo_path(model: str) -> str:
    suffix = "" if model == "resnet50" else f"_{model}"
    return os.path.join(REPO, "benchmarks", f"xplane_hlo{suffix}.txt")


def _op_table_path(model: str) -> str:
    suffix = "" if model == "resnet50" else f"_{model}"
    return os.path.join(REPO, "benchmarks",
                        f"xplane_op_table{suffix}.json")


def _category(name, stats):
    """Map one XLA-Ops event to a coarse roofline category.

    The TPU xplane sometimes carries an hlo_category stat; fall back to
    HLO-text regexes on the event name (the full instruction text).
    """
    cat = None
    for k in ("hlo_category", "category"):
        v = stats.get(k)
        if isinstance(v, str) and v:
            cat = v.lower()
            break
    text = (cat or "") + " " + name.lower()
    if "%convolution" in text or "convolution(" in text:
        return "conv"
    if "select-and-scatter" in text or "reduce-window" in text:
        return "pool"
    if "all-reduce" in text or "all-gather" in text or \
            "reduce-scatter" in text or "collective" in text:
        return "collective"
    # %convert_reduce_fusion.* = the per-channel f32 stats reductions the
    # roofline's bn term models (mean/var fwd, dgamma/dbeta bwd)
    if "convert_reduce_fusion" in text or re.match(r"%reduce", name):
        return "reduce(bn-stats)"
    # SGD+momentum fp32 parameter updates fuse as (multiply|copy)_add
    # over f32 weight-shaped tuples
    if re.search(r"%(copy|multiply)_add_fusion", name):
        return "param-update"
    if "%copy" in text or "copy-start" in text or "copy-done" in text:
        return "copy(dma)"
    if "transpose" in text:
        return "transpose"
    if "%dot" in text or "matmul" in text:
        return "matmul"
    if "fusion" in text:
        return "elementwise-fusion"
    return "other"


def _load_hlo_categories(hlo_path):
    """instruction name -> category, from the optimized HLO's fusion
    bodies (exact, unlike root-text regexes). Returns {} when absent."""
    if not os.path.exists(hlo_path):
        return {}
    comp_ops = {}        # computation name -> set of interior opcodes
    inst_info = {}       # instruction name -> (opcode, calls, result_type)
    cur = None
    # instruction line: "%name = <type> opcode(...)". The type may be a
    # tuple "(f32[64]{...}, bf16[...]{...})" with internal spaces, so the
    # opcode is found as the first lowercase token followed by "(" after
    # the "=" (tiling suffixes like T(8,128)/S(1) are uppercase).
    line_re = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*)$")
    opcode_re = re.compile(r"(?:^|\s)([a-z][a-zA-Z0-9_\-]*)\(")
    calls_re = re.compile(r"(?:calls|to_apply)=%?([\w.\-]+)")
    with open(hlo_path) as f:
        for line in f:
            stripped = line.rstrip()
            # computation header: "%name (params...) -> type {" — the
            # params/types carry nested parens (tilings like T(8,128)),
            # so key off the trailing "{" + an "->" before any "="
            if stripped.endswith("{") and "->" in stripped and \
                    "=" not in stripped.split("->", 1)[0]:
                head = stripped.lstrip()
                if head.startswith("ENTRY "):
                    head = head[6:].lstrip()
                cur = head.split("(")[0].strip().lstrip("%")
                comp_ops.setdefault(cur, set())
                continue
            m = line_re.match(line)
            if m and cur:
                name, rest = m.groups()
                om = opcode_re.search(rest)
                if not om:
                    continue
                opcode = om.group(1)
                rtype = rest[:om.start()].strip()
                comp_ops[cur].add(opcode)
                calls = calls_re.search(line)
                inst_info[name] = (opcode, calls.group(1) if calls else None,
                                   rtype)
    def ops_of(inst):
        info = inst_info.get(inst)
        if not info:
            return set(), ""
        opcode, calls, rtype = info
        ops = {opcode}
        if calls and calls in comp_ops:
            ops |= comp_ops[calls]
        return ops, rtype

    cats = {}
    for inst in inst_info:
        ops, rtype = ops_of(inst)
        if "convolution" in ops:
            cats[inst] = "conv"
        elif "select-and-scatter" in ops or "reduce-window" in ops:
            cats[inst] = "pool"
        elif "all-reduce" in ops or "all-gather" in ops or \
                "reduce-scatter" in ops:
            cats[inst] = "collective"
        elif "dot" in ops:
            cats[inst] = "matmul"
        elif "reduce" in ops:
            cats[inst] = "reduce(bn-stats)"
        elif "custom-call" in ops:
            # Mosaic kernels (flash attention / fused CE) lower to
            # tpu custom-calls
            cats[inst] = "pallas(custom-call)"
        elif ops & {"copy", "copy-start", "copy-done", "transpose"}:
            cats[inst] = "copy/transpose"
        elif "fusion" in ops or ops & {"add", "multiply", "subtract",
                                       "maximum", "divide", "select"}:
            # elementwise passes: f32 roots are the optimizer/bn-param
            # updates, bf16 roots the activation traffic (bn-apply/relu/
            # residual)
            cats[inst] = "elementwise-f32(update)" \
                if rtype.startswith(("(f32", "f32")) \
                else "elementwise-bf16(act)"
    return cats


def capture_gpt(trace_dir, steps, warmup, batch):
    """GPT-2-small step — the SAME program gpt_bench.py benchmarks
    (shared builder, benchmarks/_gpt_step.py) — profiles where the
    non-MFU 36% of the 64%-MFU step goes."""
    import jax

    import horovod_tpu as hvd
    from benchmarks._gpt_step import build_gpt_train_step
    from horovod_tpu.compile_cache import enable_compile_cache

    enable_compile_cache()
    hvd.init()
    platform = jax.devices()[0].platform
    seq = 1024 if platform == "tpu" else 128
    vocab = 50304 if platform == "tpu" else 512
    step, params, opt, tokens, targets, _n, _mesh = build_gpt_train_step(
        seq=seq, vocab=vocab, batch=batch)
    for _ in range(warmup):
        params, opt, loss = step(params, opt, tokens, targets)
        float(loss)
    with jax.profiler.trace(trace_dir):
        for _ in range(steps):
            params, opt, loss = step(params, opt, tokens, targets)
        float(loss)
    try:
        hlo = step.lower(params, opt, tokens, targets).compile().as_text()
        with open(_hlo_path("gpt"), "w") as f:
            f.write(hlo)
    except Exception as e:
        sys.stderr.write(f"hlo dump failed: {e!r}\n")
    return platform


def capture(trace_dir, steps, warmup, batch):
    import jax
    import numpy as np
    import optax

    import horovod_tpu as hvd
    from horovod_tpu.compile_cache import enable_compile_cache
    from horovod_tpu.models.bench_zoo import (build_benchmark_model,
                                              default_image_size)
    from horovod_tpu.training import (init_replicated, make_train_step,
                                      shard_batch)

    enable_compile_cache()
    hvd.init()
    mesh = hvd.core.basics.get_mesh()
    platform = jax.devices()[0].platform
    image_size = default_image_size("resnet50", platform == "tpu")
    apply_fn, params, batch_stats, has_bn = build_benchmark_model(
        "resnet50", image_size)
    tx = optax.sgd(0.01, momentum=0.9)
    params = init_replicated(params, mesh)
    batch_stats = init_replicated(batch_stats, mesh)
    step = make_train_step(apply_fn, tx, mesh, has_batch_stats=has_bn)
    opt_state = init_replicated(step.init_opt_state(params), mesh)
    images = shard_batch(
        np.random.rand(batch, image_size, image_size, 3).astype(np.float32),
        mesh)
    labels = shard_batch(
        np.random.randint(0, 1000, size=(batch,)).astype(np.int32), mesh)

    for _ in range(warmup):
        params, opt_state, batch_stats, loss = step(
            params, opt_state, batch_stats, images, labels)
    float(loss)

    with jax.profiler.trace(trace_dir):
        for _ in range(steps):
            params, opt_state, batch_stats, loss = step(
                params, opt_state, batch_stats, images, labels)
        float(loss)  # readback inside the trace: fence device completion

    # Ground-truth categorization source: the OPTIMIZED HLO of the very
    # executable the trace ran (cache-hit compile). Trace event names on
    # TPU are fusion roots ("%fusion.123 = ..."), which hide whether a
    # convolution/reduce/update lives inside — the HLO text holds the
    # fusion bodies.
    try:
        lowered = step.lower(params, opt_state, batch_stats, images,
                             labels)
        hlo = lowered.compile().as_text()
        with open(_hlo_path("resnet50"), "w") as f:
            f.write(hlo)
    except Exception as e:  # profiling still useful without it
        sys.stderr.write(f"hlo dump failed: {e!r}\n")
    return platform


def parse(trace_dir, batch, steps, model="resnet50"):
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(
        os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(paths[-1])

    device_plane = None
    for p in pd.planes:
        if "/device:TPU" in p.name or "/device:CPU:" in p.name:
            device_plane = p
            break
    if device_plane is None:
        raise RuntimeError(
            f"no device plane; planes={[p.name for p in pd.planes]}")

    hlo_cats = _load_hlo_categories(_hlo_path(model))
    module_durs = []      # per-executed-module wall on device
    op_table = {}         # name -> [total_ns, count, category, bytes]
    stat_keys = set()
    for line in device_plane.lines:
        if line.name == "XLA Modules":
            for e in line.events:
                if "jit_" in e.name:
                    module_durs.append((e.name.split("(")[0],
                                        e.duration_ns))
        elif line.name == "XLA Ops":
            for e in line.events:
                stats = dict(e.stats)
                stat_keys.update(stats.keys())
                short = e.name.split(" = ")[0]
                cat = hlo_cats.get(short.lstrip("%")) or \
                    _category(e.name, stats)
                byt = 0
                for k, v in stats.items():
                    if "bytes" in str(k).lower() and \
                            isinstance(v, (int, float)):
                        byt = max(byt, int(v))
                ent = op_table.setdefault(short, [0, 0, cat, 0, e.name[:160]])
                ent[0] += int(e.duration_ns)
                ent[1] += 1
                ent[3] += byt

    # the dominant module is the train step; group module durations by name
    by_mod = {}
    for name, d in module_durs:
        by_mod.setdefault(name, []).append(d)
    train_key = max(by_mod, key=lambda k: sum(by_mod[k])) if by_mod else None
    step_ns = sorted(by_mod[train_key])[len(by_mod[train_key]) // 2] \
        if train_key else None

    cats = {}
    total_op_ns = 0
    total_bytes = 0
    for name, (ns, n, cat, byt, _full) in op_table.items():
        c = cats.setdefault(cat, [0, 0])
        c[0] += ns
        c[1] += byt
        total_op_ns += ns
        total_bytes += byt

    top = sorted(op_table.items(), key=lambda kv: -kv[1][0])[:40]
    result = {
        "metric": f"{model}_xplane_profile",
        "trace_dir": trace_dir,
        "batch": batch,
        "profiled_steps": steps,
        "device_plane": device_plane.name,
        "train_module": train_key,
        "median_step_ms": round(step_ns / 1e6, 3) if step_ns else None,
        "img_s_from_profile": round(batch / (step_ns / 1e9), 1)
        if step_ns else None,
        "steps_seen": len(by_mod.get(train_key, [])) if train_key else 0,
        "op_self_time_ms_per_step": round(
            total_op_ns / 1e6 / max(steps, 1), 3),
        "per_category_ms_per_step": {
            k: round(v[0] / 1e6 / max(steps, 1), 3)
            for k, v in sorted(cats.items(), key=lambda kv: -kv[1][0])},
        "per_category_gb": {
            k: round(v[1] / 1e9, 3)
            for k, v in cats.items() if v[1]},
        "hlo_categorized": bool(hlo_cats),
        "bytes_stat_available": total_bytes > 0,
        "achieved_hbm_gb_s": round(
            (total_bytes / max(steps, 1)) / (step_ns / 1e9) / 1e9, 1)
        if (total_bytes and step_ns) else None,
        "stat_keys_seen": sorted(str(k) for k in stat_keys)[:30],
    }
    table = [{"op": k, "ms_total": round(v[0] / 1e6, 3), "count": v[1],
              "category": v[2], "gb": round(v[3] / 1e9, 4),
              "hlo": v[4]} for k, v in top]
    with open(_op_table_path(model), "w") as f:
        json.dump(table, f, indent=1)
    return result


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default="resnet50",
                    choices=["resnet50", "gpt"])
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--warmup", type=int, default=4)
    ap.add_argument("--batch", type=int, default=None)
    ap.add_argument("--trace-dir", default=None)
    ap.add_argument("--parse-only", metavar="DIR", default=None)
    args = ap.parse_args()
    if args.batch is None:
        args.batch = 32 if args.model == "resnet50" else 8
    if args.trace_dir is None:
        args.trace_dir = os.path.join(
            REPO, "benchmarks",
            "xplane_trace" if args.model == "resnet50"
            else "xplane_trace_gpt")

    if args.parse_only:
        result = parse(args.parse_only, args.batch, args.steps,
                       model=args.model)
    else:
        cap = capture if args.model == "resnet50" else capture_gpt
        platform = cap(args.trace_dir, args.steps, args.warmup,
                       args.batch)
        result = parse(args.trace_dir, args.batch, args.steps,
                       model=args.model)
        result["platform"] = platform

    # measured-vs-modeled: pull the roofline's floors for the same batch
    # (resnet only — no analytic model exists for the gpt step)
    if args.model != "resnet50":
        print(json.dumps(result), flush=True)
        return 0
    try:
        roof = json.loads(subprocess.run(
            [sys.executable,
             os.path.join(REPO, "benchmarks", "resnet_roofline.py"),
             "--batch", str(args.batch)],
            capture_output=True, text=True, timeout=120).stdout.strip()
            .splitlines()[-1])
        result["modeled"] = {
            "mem_floor_ms": roof["mem_floor_ms"],
            "compute_floor_ms": roof["compute_floor_ms"],
            "serial_floor_ms": roof["serial_floor_ms"],
            "overlap_ceiling_img_s": roof["overlap_ceiling_img_s"],
            "bn_ms": roof["bn_ms"],
        }
        if result.get("median_step_ms"):
            result["measured_vs_overlap_floor"] = round(
                result["median_step_ms"] /
                max(roof["mem_floor_ms"], roof["compute_floor_ms"]), 2)
    except Exception as e:  # roofline comparison is best-effort
        result["modeled_error"] = repr(e)

    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
