"""The JoyAI-LLM-Flash family's cell, work counts and the two readers the
cell adds, on planted spans and a planted device trace (CPU; nothing here
is a measurement). The configuration's `published` group and the cell's
rehearsal are also checked by `test_chipbench.py`, which is parametrised
over `BENCHMARK.json`."""
import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import exec_steps, harness, roofline, xplane  # noqa: E402
from chipbench import manifest as mf  # noqa: E402
from test_smallthinker import (PEAK, P_START, P_STOP, X_LO,  # noqa: E402
                               Recorder, _custom_call)

MANIFEST = mf.load()
CELL = "joyai-flash-l5.serve-doc16"
CONFIG = mf.load_json("chipbench/configs/joyai-flash-l5.json")
FAMILY = mf.load_module("chipbench/families/joyai.py")
SHAPE = FAMILY.Shape(CONFIG)
#: the public-architecture catalog (``architectures.jsonl``) where one is
#: at hand: the configuration is checked against its row
CATALOG = os.environ.get("MODEL_CATALOG", "")


def test_the_cell_is_as_specified():
    cell = mf.Cell(MANIFEST, CELL)
    tr, sv = cell.traffic, cell.traffic["server"]
    assert cell.chips == 1 and tr["callers"] == sv["max_batch"] == 16
    assert (tr["prompt_tokens"]["low"], tr["prompt_tokens"]["high"]) == \
        (3072, 12288)
    assert (tr["new_tokens"]["low"], tr["new_tokens"]["high"]) == (512, 2048)
    assert tr["size_grid"] == 4 and tr["shared_prefix_tokens"] == 2048
    assert tr["temperature"] == 0.0 and tr["checked_requests"] == 6
    assert tr["trace_seconds"] == 4.0
    assert sv["max_len"] == 12288 and sv["kv_block"] == 64
    assert (sv["prefix_cache"], sv["kv_crc"], sv["kv_tier"], sv["spec_k"],
            sv["decode_kernel"]) == (True, False, False, 0, None)
    # a SUBSET: later metrics may list the cell too
    names = {m["name"] for m in cell.per_layer}
    assert {"serve.latent_attention_roofline", "serve.prefix_hit_share",
            "serve.moe_expert_roofline", "serve.cache_bytes_per_token",
            "serve.exec_step_ms",
            "serve.sched_self_ms", "serve.device_idle_share",
            "serve.step_mfu", "setup.import_init_s", "setup.trace_lower_s",
            "setup.compile_fetch_s", "serve.idle_stall_ms"} <= names
    # the traced slice opens after the first iteration, which prefills
    # the first wave, and closes before its first answer ends: it holds
    # no prefill step, so no prefill kernel's reader is listed
    assert "serve.prefill_attention_roofline" not in names
    assert {m["name"] for m in cell.end_to_end} == \
        {"serve_tokens_per_s", "setup_s"}
    sizes = cell.generator().size_grid(tr)
    assert sizes == [(3653, 609), (5166, 1218), (7306, 861), (10333, 1722)]
    buckets = sv["prefill_buckets"]
    # admission sheds a WHOLE prompt past the largest bucket: the first,
    # unshared prefill of the longest prompt has to fit
    assert buckets[-1] >= 10333 and max(p + n for p, n in sizes) <= \
        sv["max_len"]
    # after the first, a prefill is the suffix past the shared 2,048
    fresh = [p - 2048 for p, _ in sizes]
    assert fresh == [1605, 3118, 5258, 8285]
    padded = [min(b for b in buckets if b >= n) for n in fresh]
    assert sum(padded) < 1.05 * sum(fresh)
    assert all(b % 128 == 0 for b in buckets)


def test_cut_in_depth_and_mtp_only():
    entry = next(c for c in MANIFEST["configs"]
                 if c["name"] == "joyai-flash-l5")
    assert CONFIG["reduced"] == entry["reduced"] == \
        ["num_hidden_layers", "num_nextn_predict_layers"]
    assert set(CONFIG["reduced_why"]) == set(CONFIG["reduced"])
    assert (CONFIG["num_hidden_layers"], CONFIG["num_nextn_predict_layers"]) \
        == (5, 0)
    assert (SHAPE.d, SHAPE.heads, SHAPE.q_rank, SHAPE.kv_rank, SHAPE.nope,
            SHAPE.rope, SHAPE.v_dim, SHAPE.experts, SHAPE.ffn, SHAPE.top_k,
            SHAPE.shared, SHAPE.dense_ffn, SHAPE.vocab) == \
        (2048, 32, 1536, 512, 128, 64, 128, 256, 768, 8, 1, 7168, 129280)
    assert (SHAPE.dense_layers, SHAPE.moe_layers) == (1, 4)


def test_every_source_key_is_kept():
    if not os.path.isfile(CATALOG):
        pytest.skip("no catalog named by MODEL_CATALOG")
    row = next(r for r in map(json.loads, open(CATALOG))
               if r["name"] == "JoyAI-LLM-Flash")
    assert [k for k, v in row["config"].items()
            if CONFIG.get(k) != v] == CONFIG["reduced"]
    assert CONFIG["source"] == row["source_url"]


@pytest.mark.parametrize("what", sorted(CONFIG["hand_worked"]))
def test_hand_worked(what):
    assert FAMILY.WORK_COUNTS[what](SHAPE) == \
        CONFIG["hand_worked"][what]["value"]


def test_the_deployment_fits_one_chip():
    """Weights in bfloat16 (11.12 GB) and the cell's float32 latent
    pool, held at 640 lanes, under 75% of the chip's 16 GiB; the values
    a token costs are 5 x 2,304 B."""
    from horovod_tpu.serve import pool_blocks_for
    weights = FAMILY.param_count(SHAPE) * 2
    blocks = pool_blocks_for(16, 12288, 64)
    pool = blocks * 64 * 640 * 4 * SHAPE.layers
    assert (weights, blocks, pool) == (11_116_283_904, 1536, 1_258_291_200)
    assert weights + pool < 0.75 * 16 * 2 ** 30
    assert FAMILY.cache_bytes(SHAPE, 1) == 5 * 2304
    # the grid's four sizes a cycle, four cycles over 16 rows, with the
    # shared prefix's 32 blocks held once
    sizes = [(3653, 609), (5166, 1218), (7306, 861), (10333, 1722)]
    rows = 4 * sum(-(-(p + n) // 64) - 32 for p, n in sizes) + 32
    assert rows == 1456 <= blocks


def _decode(starts):
    return {"kind": "decode", "prompt_tokens": 0, "decode_tokens": len(starts),
            "emitted": len(starts), "rows_start": np.asarray(starts)}


def _prefill(start, n):
    return {"kind": "prefill", "prompt_tokens": n, "decode_tokens": 0,
            "emitted": 1, "rows_start": np.asarray([start]),
            "rows_tokens": np.asarray([n])}


def test_prefill_work_counts_a_cached_prefix():
    # 100 tokens from the shared 2,048: query i sees 2,049 + i keys; the
    # prefix's keys are read, not computed
    pairs = sum(2049 + i for i in range(100))
    work = FAMILY.prefill_attention_work(SHAPE, [_prefill(2048, 100),
                                                 _decode([5])])
    assert work == {"flops": 5 * 2 * 32 * 320 * pairs,
                    "bytes": 5 * (100 + 2148) * 32 * 320 * 4}


def test_serve_flops_by_hand():
    steps = [_prefill(0, 10), _decode([10, 5000])]
    per_token = 2 * 612_499_456 - 2 * 2048 * 129280
    want = (per_token * 12 + 5 * 20_480 * 55
            + 5 * 69_632 * (11 + 5001) + 2 * 2048 * 129280 * 3)
    assert FAMILY.serve_flops(SHAPE, steps) == want


def test_expert_work_leaves_the_shared_expert_out():
    work = FAMILY.expert_work(SHAPE, tokens=16, experts_hit=4 * 102)
    assert work["flops"] == 16 * 8 * 6 * 2048 * 768
    assert work["bytes"] == 4 * 102 * 9_437_184 + 16 * 8 * 2 * 2048 * 4


def _planted(counters=True):
    """A 4 s slice: a prefill step (1.0 s) and two decode steps (0.1 s
    each: 5 latent kernels of 1 ms in each); a fourth step straddles the
    slice's end. Prefills of the window outside the slice too."""
    rec = Recorder()
    run = harness.Run(mf.Cell(MANIFEST, CELL), 0, 1.0, True, False)
    run.tracer.t_start, run.tracer.t_stop = P_START, P_STOP
    run.window_open, run.window_s = P_START - 10.0, 20.0
    run.peak = PEAK

    def cached(n):
        return {"cached_tokens": n} if counters else {}

    def ctx(n):
        return {"context_tokens": n, "cache_bytes_held": 11520 * n} \
            if counters else {}
    rec.plant(P_START - 15.0, P_START - 14.0, kind="prefill", rows=1,
              tokens=9999, **cached(0))      # before the window opened
    rec.plant(P_START - 9.0, P_START - 8.0, kind="prefill", rows=1,
              tokens=10333, **cached(0))
    rec.plant(P_START - 5.0, P_START - 4.0, kind="prefill", rows=1,
              tokens=1605, **cached(2048))
    rec.plant(P_START + 0.5, P_START + 1.5, kind="prefill", rows=1,
              tokens=5258, **cached(2048))
    rec.plant(P_START + 2.0, P_START + 2.1, kind="decode", rows=16,
              **ctx(120_000))
    rec.plant(P_START + 3.0, P_START + 3.1, kind="decode", rows=12,
              **ctx(80_000))
    rec.plant(P_STOP - 0.05, P_STOP + 0.05, kind="decode", rows=16,
              **ctx(1))
    x = X_LO
    ops = [("%fusion.1 = f32[8]{0} fusion(%p)", x + 0.6, x + 1.4),
           (_custom_call("flash_prefill.3", "f32[1,32,5376,128]"),
            x + 1.0, x + 1.2)]
    for t in (2.0, 3.0):
        ops.append(("%fusion.2 = f32[8]{0} fusion(%p)", x + t + 0.001,
                    x + t + 0.031))
        for i in range(5):
            a = x + t + 0.04 + 0.002 * i
            ops.append((_custom_call(f"_latent_attention_call.{i + 1}",
                                     "f32[16,32,512]"), a, a + 0.001))
    run.trace = xplane.Trace(
        ops={0: ops}, host_spans=[(xplane.WINDOW_SPAN, x, x + 4.0)])
    run.traced = {"seconds": 4.0, "steps": []}
    exec_steps.steps(run, recorder=rec)
    return run, rec


def test_latent_decode_work_reads_each_key_once_a_layer():
    work = FAMILY.latent_decode_work(SHAPE, 200_000, 5)
    assert work == {"flops": 5 * 200_000 * 69_632,
                    "bytes": 5 * 200_000 * 2304}


def test_latent_roofline_reads_the_named_kernel_in_decode_steps():
    run, _ = _planted()
    work = FAMILY.latent_decode_work(SHAPE, 200_000, 5)
    reader = mf.Cell(MANIFEST, CELL).reader("serve.latent_attention_roofline")
    assert reader.read(run) == pytest.approx(
        100 * roofline.roofline_seconds(work, PEAK) / 0.010)
    # bandwidth-bound: a million latents a layer is 2.30 GB, 2.81 ms
    assert roofline.roofline_seconds(work, PEAK) == pytest.approx(
        2_304_000_000 / 819e9)
    # a program that counts no context (the parent, another model)
    assert reader.read(_planted(counters=False)[0]) is None
    # a trace without the kernel
    run.trace = xplane.Trace(
        ops={0: [o for o in run.trace.ops[0] if "latent" not in o[0]]},
        host_spans=run.trace.host_spans)
    assert reader.read(run) is None


def test_prefix_hit_share_reads_the_windows_prefills(monkeypatch):
    from chipbench import program_spans
    run, rec = _planted()
    monkeypatch.setattr(program_spans, "_recorder", lambda: rec)
    reader = mf.Cell(MANIFEST, CELL).reader("serve.prefix_hit_share")
    # the three prefills inside the window, not the one before it
    cached, fresh = 2 * 2048, 10333 + 1605 + 5258
    assert reader.read(run) == pytest.approx(100 * cached / (cached + fresh))
    run, rec = _planted(counters=False)
    monkeypatch.setattr(program_spans, "_recorder", lambda: rec)
    assert reader.read(run) is None
    run.window_open = None
    assert reader.read(run) is None
