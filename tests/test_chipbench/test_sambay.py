"""The SambaY family's cell, work counts and the three readers the cell
adds, on planted spans and a planted device trace (CPU; nothing here is
a measurement). The configuration's `published` and `hand_worked` groups
and the cell's rehearsal are checked by `test_chipbench.py`, which is
parametrised over `BENCHMARK.json`."""
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
CELL = "phi4-mini-flash.serve-reason16"
CONFIG = mf.load_json("chipbench/configs/phi4-mini-flash.json")
FAMILY = mf.load_module("chipbench/families/sambay.py")
SHAPE = FAMILY.Shape(CONFIG)
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def test_the_cell_is_as_the_issue_names_it():
    cell = mf.Cell(MANIFEST, CELL)
    tr, sv = cell.traffic, cell.traffic["server"]
    assert cell.chips == 1 and tr["callers"] == sv["max_batch"] == 16
    assert (tr["prompt_tokens"]["low"], tr["prompt_tokens"]["high"]) == \
        (1024, 6144)
    assert (tr["new_tokens"]["low"], tr["new_tokens"]["high"]) == (384, 1536)
    assert tr["size_grid"] == 4 and tr["shared_prefix_tokens"] == 0
    assert tr["temperature"] == 0.0 and tr["checked_requests"] == 6
    assert tr["trace_seconds"] == 4.0
    assert sv["max_len"] == 8192 and sv["kv_block"] == 64
    assert sv["prefill_buckets"] == [1344, 2048, 3200, 4928]
    assert (sv["prefix_cache"], sv["kv_crc"], sv["kv_tier"], sv["spec_k"],
            sv["decode_kernel"]) == (False, False, False, 0, None)
    names = {m["name"] for m in cell.per_layer}
    assert names == {
        "serve.ssm_state_roofline", "serve.ssm_update_share",
        "serve.cache_bytes_per_token", "serve.paged_attention_roofline",
        "serve.exec_step_ms", "serve.sched_self_ms",
        "serve.device_idle_share", "serve.step_mfu"}
    assert {m["name"] for m in cell.end_to_end} == \
        {"serve_tokens_per_s", "setup_s"}
    sizes = cell.generator().size_grid(tr)
    assert sizes == [(1281, 457), (2005, 913), (3138, 646), (4911, 1292)]
    # padding under 5% of the prompt tokens
    buckets = [min(b for b in sv["prefill_buckets"] if b >= p)
               for p, _ in sizes]
    assert sum(buckets) < 1.05 * sum(p for p, _ in sizes)
    # the pool, half the worst case, holds four whole cycles of the grid
    # at their longest (58.6k tokens; NOT sixteen of the longest request:
    # PERF.md has what that costs), and the window is whole blocks
    from horovod_tpu.serve import pool_blocks_for
    assert pool_blocks_for(16, 8192, 64) == 1024 \
        >= 4 * sum(-(-(p + n) // 64) for p, n in sizes) == 924
    assert SHAPE.window % sv["kv_block"] == 0
    # the 128-entry table's assembly is what holds max_len at 8,192
    from horovod_tpu.ops.pallas_paged import _vmem_limit_bytes
    assert 60 << 20 < _vmem_limit_bytes(4, 10, 128, 64, 128, 2) < 72 << 20


def test_nothing_is_cut_and_published_is_the_files_own_keys():
    assert CONFIG["reduced"] == [] and CONFIG["family"] == "sambay"
    entry = next(c for c in MANIFEST["configs"]
                 if c["name"] == "phi4-mini-flash")
    assert entry["reduced"] == [] and entry["source"] == CONFIG["source"]
    assert CONFIG["published"] == {
        "d": CONFIG["hidden_size"], "layers": CONFIG["num_hidden_layers"],
        "heads": CONFIG["num_attention_heads"],
        "kv_heads": CONFIG["num_key_value_heads"],
        "ffn": CONFIG["intermediate_size"], "vocab": CONFIG["vocab_size"],
        "window": CONFIG["sliding_window"],
        "positions": CONFIG["max_position_embeddings"],
        "eps": CONFIG["layer_norm_eps"],
        "mb_per_layer": CONFIG["mb_per_layer"]}
    assert (SHAPE.d, SHAPE.layers, SHAPE.heads, SHAPE.kv_heads,
            SHAPE.head_dim, SHAPE.ffn, SHAPE.vocab, SHAPE.window) == \
        (2560, 32, 40, 20, 64, 10240, 200064, 512)
    assert (SHAPE.d_state, SHAPE.d_conv, SHAPE.d_inner, SHAPE.dt_rank) == \
        (16, 4, 5120, 160)
    assert [SHAPE.count(k) for k in ("mamba", "window", "full", "gmu",
                                     "cross")] == [9, 8, 1, 7, 7]


def test_every_source_key_is_kept():
    if not os.path.exists(CATALOG):
        pytest.skip("the catalog is not on this machine")
    row = next(r for r in map(json.loads, open(CATALOG))
               if r["name"] == "Phi-4-mini-flash-reasoning")
    assert [k for k, v in row["config"].items() if CONFIG.get(k) != v] == []
    assert CONFIG["source"] == row["source_url"]


def test_the_deployment_fits_one_chip_whole():
    """Resident by the sizes: weights in bfloat16, the pool, 16 rows'
    rings and states, as VALUES and as the device holds them (10
    pair-heads padded to 16 sublanes)."""
    weights = FAMILY.param_count(SHAPE) * 2
    pool = 1024 * 64 * FAMILY.kv_token_bytes(SHAPE)
    rows = 16 * FAMILY.cache_row_bytes(SHAPE)
    assert (weights, pool, rows) == (7_705_125_888, 335_544_320,
                                     394_526_720)
    held = (pool + 16 * 8 * 512 * 5120) * 16 // 10 + 16 * 9 * 409_600
    assert weights + held < 0.6 * 16 * 2 ** 30


def _decode(starts):
    return {"kind": "decode", "prompt_tokens": 0, "decode_tokens": len(starts),
            "emitted": len(starts), "rows_start": np.asarray(starts)}


def _prefill(start, n):
    return {"kind": "prefill", "prompt_tokens": n, "decode_tokens": 0,
            "emitted": 1, "rows_start": np.asarray([start]),
            "rows_tokens": np.asarray([n])}


def test_decode_attention_work_is_the_pool_eight_times_and_eight_rings():
    # a row at context 100 (inside the window) and one at 4,096
    work = FAMILY.decode_attention_work(
        SHAPE, [_decode([99, 4095]), _prefill(0, 50)])
    keys = 16 * 100 + 8 * 4096 + 8 * 512
    assert work == {"flops": 40 * (2 * 64 + 4 * 64) * keys,
                    "bytes": 5120 * keys}
    assert FAMILY.decode_query_pattern(SHAPE, 16) == r"\[16,10,4,128\]"
    assert FAMILY.cache_bytes(SHAPE, 4096) == 4096 * 5120 + 24_657_920


def test_serve_flops_count_the_cross_decoder_once_a_row():
    steps = [_prefill(0, 1000), _decode([1000, 50])]
    low = FAMILY.matmul_flops_per_prefill_token(SHAPE)
    every = FAMILY.matmul_flops_per_decode_token(SHAPE)
    windowed = (512 * 513 // 2 + 488 * 512) + 512 + 51
    full = 1000 + 1001 + 51
    want = (low * 1002 + (every - low) * 3
            + 15_360 * (8 * windowed + 8 * full)
            + 1002 * 9 * 5120 * (7 * 16 + 2 * 4))
    assert FAMILY.serve_flops(SHAPE, steps) == want
    # 14 of 32 layers and the head cost a prefill token nothing
    assert low < 0.5 * every


def test_state_work_counts_both_states_read_and_written():
    work = FAMILY.state_work(SHAPE, 16)
    assert work == {"flops": 9 * 16 * 5120 * 120,
                    "bytes": 9 * 16 * 2 * 5120 * (16 + 4) * 4}
    # 16 rows: 118 MB a step, 0.144 ms at the HBM peak
    assert roofline.roofline_seconds(work, PEAK) == pytest.approx(
        117_964_800 / 819e9)


def _planted(counters=True):
    """A 4 s slice: a prefill step (1.0 s) and two decode steps (0.1 s
    each: 9 `ssm_decode` kernels of 1 ms in each), one of 12 rows; a
    fourth step straddles the slice's end."""
    rec = Recorder()
    run = harness.Run(mf.Cell(MANIFEST, CELL), 0, 1.0, True, False)
    run.tracer.t_start, run.tracer.t_stop = P_START, P_STOP
    run.peak = PEAK

    def extra(rows, tokens, held):
        return dict(state_rows=rows, context_tokens=tokens,
                    cache_bytes_held=held) if counters else {}
    rec.plant(P_START + 0.5, P_START + 1.5, kind="prefill", rows=1,
              tokens=4911)
    rec.plant(P_START + 2.0, P_START + 2.1, kind="decode", rows=16,
              **extra(16, 50_000, 700_000_000))
    rec.plant(P_START + 3.0, P_START + 3.1, kind="decode", rows=12,
              **extra(12, 30_000, 500_000_000))
    rec.plant(P_STOP - 0.05, P_STOP + 0.05, kind="decode", rows=16,
              **extra(16, 1, 1))
    x = X_LO
    ops = [("%fusion.1 = f32[8]{0} fusion(%p)", x + 0.6, x + 1.4)]
    for t in (2.0, 3.0):
        ops.append(("%fusion.2 = f32[8]{0} fusion(%p)", x + t + 0.001,
                    x + t + 0.031))
        for i in range(9):
            a = x + t + 0.04 + 0.002 * i
            ops.append((_custom_call(f"ssm_decode.{i + 1}",
                                     "(f32[16,1,5120], f32[16,16,5120])"),
                        a, a + 0.001))
    run.trace = xplane.Trace(
        ops={0: ops}, host_spans=[(xplane.WINDOW_SPAN, x, x + 4.0)])
    run.traced = {"seconds": 4.0, "steps": []}
    exec_steps.steps(run, recorder=rec)
    return run


def _read(run, metric):
    return mf.Cell(MANIFEST, CELL).reader(metric).read(run)


def test_ssm_state_roofline_reads_the_named_kernel_in_decode_steps():
    work = FAMILY.state_work(SHAPE, 16 + 12)
    assert _read(_planted(), "serve.ssm_state_roofline") == pytest.approx(
        100 * roofline.roofline_seconds(work, PEAK) / 0.018)
    # a program that counts no rows (another model's) reports nothing
    assert _read(_planted(counters=False),
                 "serve.ssm_state_roofline") is None


def test_ssm_update_share_is_the_kernels_time_over_busy_time():
    # busy: 0.8 s of the prefill, 2 x (30 ms + 9 ms)
    assert _read(_planted(), "serve.ssm_update_share") == pytest.approx(
        100 * 0.018 / (0.8 + 0.078))
    run = _planted()
    run.trace = xplane.Trace(
        ops={0: [o for o in run.trace.ops[0] if "ssm_decode" not in o[0]]},
        host_spans=run.trace.host_spans)
    assert _read(run, "serve.ssm_update_share") is None
    assert _read(run, "serve.ssm_state_roofline") is None


def test_cache_bytes_per_token_is_held_over_context():
    assert _read(_planted(), "serve.cache_bytes_per_token") == \
        pytest.approx(1_200_000_000 / 80_000)
    # a program that sets neither counter (the parent, another model)
    assert _read(_planted(counters=False),
                 "serve.cache_bytes_per_token") is None
