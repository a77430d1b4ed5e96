"""The SmallThinker family's work counts and the three readers its cell
adds, on planted spans and a planted device trace (CPU; nothing here is
a measurement). The configuration's `published` and `hand_worked`
groups and the cell's rehearsal are checked by `test_chipbench.py`,
which is parametrised over `BENCHMARK.json`."""
import os
import sys
import time

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import exec_steps, harness, roofline, xplane  # noqa: E402
from chipbench import manifest as mf  # noqa: E402
from horovod_tpu.trace import SpanRecorder  # noqa: E402

MANIFEST = mf.load()
CELL = "smallthinker-21b-l8.serve-mixed16"
FAMILY = mf.load_module("chipbench/families/smallthinker.py")
SHAPE = FAMILY.Shape(mf.load_json("chipbench/configs/smallthinker-21b-l8.json"))
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}

# the harness's clock at the traced slice's ends; the slice on the trace
P_START, P_STOP, X_LO = 50.0, 54.0, 100.0


def test_the_cell_is_as_the_issue_names_it():
    cell = mf.Cell(MANIFEST, CELL)
    tr, sv = cell.traffic, cell.traffic["server"]
    assert cell.chips == 1 and tr["callers"] == sv["max_batch"] == 16
    assert (tr["prompt_tokens"]["low"], tr["prompt_tokens"]["high"]) == \
        (1024, 12288)
    assert (tr["new_tokens"]["low"], tr["new_tokens"]["high"]) == (64, 512)
    assert tr["size_grid"] == 16 and tr["shared_prefix_tokens"] == 0
    assert tr["temperature"] == 0.0 and tr["checked_requests"] == 6
    assert sv["max_len"] == 12800 and sv["kv_block"] == 128
    assert sv["prefill_buckets"] == [2048, 4096, 8192, 12288]
    assert (sv["prefix_cache"], sv["kv_crc"], sv["kv_tier"], sv["spec_k"],
            sv["max_queue"], sv["deadline_ms"]) == \
        (True, False, False, 0, 32, 300000.0)
    names = {m["name"] for m in cell.per_layer}
    assert {"serve.moe_expert_roofline", "serve.prefill_attention_roofline",
            "serve.prefill_device_share", "serve.paged_attention_roofline",
            "serve.step_mfu"} <= names
    # 44% of the grid's prompts are longer than the window
    gen = cell.generator()
    sizes = [p for p, _ in gen.size_grid(tr)]
    assert sum(p > SHAPE.window for p in sizes) == 7


def test_shape_is_two_periods_at_the_published_widths():
    assert SHAPE.layers == 8 and SHAPE.published_layers == 52
    assert SHAPE.rope_layout == SHAPE.window_layout == (0, 1, 1, 1) * 2
    assert (SHAPE.full_layers, SHAPE.window_layers) == (2, 6)
    # resident: weights in bfloat16 and the cell's pool
    pool = 800 * 128 * 2 * SHAPE.kv_heads * SHAPE.head_dim * 2 * SHAPE.layers
    assert FAMILY.param_count(SHAPE) * 2 + pool == 7_933_875_200 + 1_677_721_600


def _decode(starts):
    return {"kind": "decode", "prompt_tokens": 0, "decode_tokens": len(starts),
            "emitted": len(starts), "rows_start": np.asarray(starts)}


def _prefill(start, n):
    return {"kind": "prefill", "prompt_tokens": n, "decode_tokens": 0,
            "emitted": 1, "rows_start": np.asarray([start]),
            "rows_tokens": np.asarray([n])}


def test_window_layers_are_clipped_row_by_row():
    # rows at 999 and 7,999 cached tokens: after the write 1,000 and
    # 8,000 keys; the 6 window layers see min(keys, 4096)
    work = FAMILY.decode_attention_work(SHAPE, [_decode([999, 7999])])
    keys = 2 * (1000 + 8000) + 6 * (1000 + 4096)
    assert work == {"flops": 4 * keys * 28 * 128,
                    "bytes": 2 * keys * 4 * 128 * 2}
    # the sums alone (9,000 keys) would not have said which row to clip
    assert keys < 8 * 9000
    assert FAMILY.decode_query_pattern(SHAPE, 16) == r"\[16,4,7,128\]"


def test_prefill_work_counts_a_cached_prefix():
    # 100 tokens from position 4,090: query i sees 4,091 + i keys in a
    # full layer, at most 4,096 in a window layer
    full = sum(4091 + i for i in range(100))
    windowed = sum(min(4091 + i, 4096) for i in range(100))
    assert FAMILY._visible(SHAPE, [4090], [100]) == (full, windowed)
    work = FAMILY.prefill_attention_work(SHAPE, [_prefill(4090, 100)])
    assert work["flops"] == 4 * 28 * 128 * (2 * full + 6 * windowed)
    assert work["bytes"] == 8 * (2 * 100 * 3584 * 2 + 2 * 100 * 512 * 2)


def test_serve_flops_by_hand():
    steps = [_prefill(0, 10), _decode([10, 5000])]
    layer = 20_971_520 + 163_840 + 6 * 5_898_240      # multiply-adds
    tokens, emitted = 12, 3
    context = (8 * 55                                  # the prompt
               + 8 * 11 + 2 * 5001 + 6 * 4096)         # the two decodes
    want = (2 * layer * 8 * tokens + 4 * 3584 * context
            + 2 * 2560 * 151936 * emitted)
    assert FAMILY.serve_flops(SHAPE, steps) == want


def test_expert_work_takes_the_counted_experts():
    work = FAMILY.expert_work(SHAPE, tokens=16, experts_hit=51 * 8)
    assert work["flops"] == 16 * 6 * 6 * 2560 * 768
    assert work["bytes"] == 51 * 8 * 11_796_480 + 16 * 6 * 2 * 2560 * 2
    # all 64 experts a layer would be 25% more bytes than were read
    assert 64 * 8 * 11_796_480 > 1.25 * (work["bytes"] - 491_520)


class Recorder(SpanRecorder):
    """A recorder on the harness's clock; spans planted at given times."""

    def __init__(self):
        super().__init__(256, ring=256)
        self._n = 0

    def now(self):
        return time.perf_counter()

    def plant(self, t0, t1, **attrs):
        self._n += 1
        self.record_local("exec_step", t0, t1, span=f"s{self._n}", **attrs)


def _custom_call(name, shape="bf16[128,1536]"):
    return (f"%{name} = {shape}{{1,0}} custom-call(%a, %b), "
            'custom_call_target="tpu_custom_call"')


def _planted(experts_hit=True):
    """A 4 s slice: a prefill step (1.0 s, 0.8 s of it busy, 0.3 s in the
    flash forward) and two decode steps (0.1 s each, 30 ms in the two
    grouped matmuls each), one of them a decode of 12 rows."""
    rec = Recorder()
    run = harness.Run(mf.Cell(MANIFEST, CELL), 0, 1.0, True, False)
    run.tracer.t_start, run.tracer.t_stop = P_START, P_STOP
    run.peak = PEAK
    hit = {"experts_hit": 400} if experts_hit else {}
    rec.plant(P_START + 0.5, P_START + 1.5, kind="prefill", rows=1,
              tokens=5000, **hit)
    rec.plant(P_START + 2.0, P_START + 2.1, kind="decode", rows=16, **hit)
    rec.plant(P_START + 3.0, P_START + 3.1, kind="decode", rows=12, **hit)
    # a step that straddles the slice's end is left out
    rec.plant(P_STOP - 0.05, P_STOP + 0.05, kind="decode", rows=16, **hit)
    x = X_LO
    ops = [("%fusion.1 = f32[8]{0} fusion(%p)", x + 0.501, x + 1.0),
           (_custom_call("flash_prefill.7", "bf16[1,28,8192,128]"),
            x + 1.0, x + 1.3),
           (_custom_call("gmm.2"), x + 1.3, x + 1.32),   # a prefill's
           ("%fusion.2 = f32[8]{0} fusion(%p)", x + 2.001, x + 2.02),
           (_custom_call("gmm.2"), x + 2.02, x + 2.04),
           (_custom_call("gmm.3", "f32[128,2560]"), x + 2.04, x + 2.05),
           (_custom_call("gmm.2"), x + 3.001, x + 3.021),
           (_custom_call("gmm.3", "f32[128,2560]"), x + 3.021, x + 3.031),
           ("%fusion.3 = f32[8]{0} fusion(%p)", x + 3.5, x + 3.6)]
    run.trace = xplane.Trace(
        ops={0: ops}, host_spans=[(xplane.WINDOW_SPAN, x, x + 4.0)])
    run.traced = {"seconds": 4.0, "steps": [_prefill(0, 5000),
                                            _decode([100] * 16),
                                            _decode([100] * 12)]}
    exec_steps.steps(run, recorder=rec)
    return run


def _read(run, metric):
    return mf.Cell(MANIFEST, CELL).reader(metric).read(run)


def test_exec_steps_on_the_trace_clock():
    run = _planted()
    got = exec_steps.steps(run)
    assert [(round(a - X_LO, 4), round(b - X_LO, 4), e["kind"])
            for a, b, e in got] == [(0.5, 1.5, "prefill"),
                                    (2.0, 2.1, "decode"),
                                    (3.0, 3.1, "decode")]
    assert [e["rows"] for _, _, e in exec_steps.of_kind(run, "decode")] == \
        [16, 12]


def test_expert_roofline_reads_decode_steps_and_counted_experts():
    run = _planted()
    work = FAMILY.expert_work(SHAPE, tokens=28, experts_hit=800)
    want = 100 * roofline.roofline_seconds(work, PEAK) / 0.06
    assert _read(run, "serve.moe_expert_roofline") == pytest.approx(want)
    # a program that counts nothing (an older one) reports nothing
    assert _read(_planted(experts_hit=False),
                 "serve.moe_expert_roofline") is None


def test_prefill_attention_roofline_and_device_share():
    run = _planted()
    work = FAMILY.prefill_attention_work(SHAPE, [_prefill(0, 5000)])
    assert _read(run, "serve.prefill_attention_roofline") == pytest.approx(
        100 * roofline.roofline_seconds(work, PEAK) / 0.3)
    # busy 0.819 s under the prefill step, of 0.819 + 0.049 + 0.03 + 0.1
    assert _read(run, "serve.prefill_device_share") == pytest.approx(
        100 * 0.819 / 0.998)


def test_loose_anchors_give_nothing():
    run = _planted()
    delattr(run, "_exec_steps")
    run.tracer.t_stop = P_STOP + 0.5      # the two windows disagree
    assert exec_steps.steps(run, recorder=Recorder()) is None
    for metric in ("serve.moe_expert_roofline",
                   "serve.prefill_attention_roofline",
                   "serve.prefill_device_share"):
        assert _read(run, metric) is None
