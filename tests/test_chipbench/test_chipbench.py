"""The chip benchmark's own tests (CPU only; nothing here describes a
TPU topology or loads libtpu, at import or later).

What is checked: `BENCHMARK.json` against the driver's published rules
entry by entry (the PR 22 refusal as a test), the files every name leads
to, the FLOP/byte functions against hand-worked values, the trace
reduction on the recorded trace under ``chipbench/data`` and on synthetic
ones, the traffic generators, the plain reference against
``models/gpt.py`` at a tiny size, the control (the reference in fp8 put
in the program's place) failing the comparison, a rehearsal of every
cell end to end, the faults a cell can have each turning `correct`
false, refusal off a TPU, and a throw-away cell added from files alone.
"""
import copy
import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import flops, harness, xplane  # noqa: E402
from chipbench import manifest as mf  # noqa: E402
from chipbench import reference as ref  # noqa: E402

MANIFEST = mf.load()
CONFIGS = MANIFEST["configs"]
CELLS = MANIFEST["workloads"]
END_TO_END = MANIFEST["end_to_end"]
PER_LAYER = MANIFEST["per_layer"]
METRICS = END_TO_END + PER_LAYER


def _ids(entries):
    return [e["name"] for e in entries]


def _shape(config_name):
    entry = next(c for c in CONFIGS if c["name"] == config_name)
    return ref.Shape(mf.load_json(entry["file"]))


# ---------------------------------------------------------------------------
# the manifest, entry by entry
# ---------------------------------------------------------------------------

def test_manifest_passes_the_drivers_rules():
    assert mf.validate(MANIFEST) == []


@pytest.mark.parametrize("entry", CONFIGS + CELLS + METRICS,
                         ids=_ids(CONFIGS + CELLS + METRICS))
def test_name_is_one_token(entry):
    assert mf.NAME.match(entry["name"]), entry["name"]
    assert not set(entry["name"]) & set(" ,/")


@pytest.mark.parametrize("metric", METRICS, ids=_ids(METRICS))
def test_unit_better_and_source(metric):
    assert mf.UNIT.match(metric["unit"]) and len(metric["unit"]) <= 16
    assert metric["better"] in ("lower", "higher")
    assert metric["source"] in mf.SOURCES


@pytest.mark.parametrize("metric", PER_LAYER, ids=_ids(PER_LAYER))
def test_layer_is_one_token(metric):
    # PR 22 was refused for a `layer` with a space in it
    assert mf.NAME.match(metric["layer"]), metric["layer"]


@pytest.mark.parametrize("metric", PER_LAYER, ids=_ids(PER_LAYER))
def test_moves_is_reported_wherever_the_metric_is(metric):
    moved = next(m for m in END_TO_END if m["name"] == metric["moves"])
    every = _ids(CELLS)
    reporting = set(moved.get("workloads", every))
    assert set(metric.get("workloads", every)) <= reporting
    assert os.path.isfile(os.path.join(
        ROOT, mf.layer_metric_file(metric["name"])))


@pytest.mark.parametrize("metric", END_TO_END, ids=_ids(END_TO_END))
def test_bound_is_within_the_contract(metric):
    assert 0.01 <= metric["bound"] <= 0.1
    assert metric["source"] in ("host_clock", "device_trace")


@pytest.mark.parametrize("cell", CELLS, ids=_ids(CELLS))
def test_cell_files_are_found_by_name(cell):
    c = mf.Cell(MANIFEST, cell["name"])
    assert hasattr(c.runner(), "Runner")
    assert c.generator() is not None
    assert all(v > 0 for v in c.limits().values())
    assert {m["name"] for m in c.end_to_end} >= {"setup_s"}
    assert len(c.end_to_end) >= 2 and c.per_layer
    assert any("mfu" in m["name"] for m in c.per_layer)
    for m in c.per_layer:
        assert callable(c.reader(m["name"]).read)
    assert len(cell["why"]) <= 200 and "\n" not in cell["why"]


PUBLISHED = {   # config.json of the two checkpoints, by hand
    "gpt2-medium": dict(d=1024, layers=24, heads=16, positions=1024),
    "gpt2-xl": dict(d=1600, layers=48, heads=25, positions=1024),
}


@pytest.mark.parametrize("config", CONFIGS, ids=_ids(CONFIGS))
def test_config_keeps_the_published_widths(config):
    data = mf.load_json(config["file"])
    shape = ref.Shape(data)
    want = PUBLISHED[config["name"]]
    assert (shape.d, shape.layers, shape.heads, shape.positions) == (
        want["d"], want["layers"], want["heads"], want["positions"])
    assert shape.head_dim == 64 and shape.ffn == 4 * shape.d
    assert shape.vocab == 50257 and shape.padded_vocab % 128 == 0
    assert sorted(data["reduced"]) == sorted(config["reduced"])
    assert not any(mf._WIDTH.search(k) for k in config["reduced"])
    assert config["source"] == data["source"]


def _breach(name):
    m = copy.deepcopy(MANIFEST)
    if name == "layer_with_a_space":
        m["per_layer"][0]["layer"] = "train step"
    elif name == "unit_too_long":
        m["end_to_end"][0]["unit"] = "tokens_per_second"
    elif name == "unit_with_a_space":
        m["end_to_end"][0]["unit"] = "tokens per s"
    elif name == "moves_unknown":
        m["per_layer"][0]["moves"] = "nothing"
    elif name == "moves_not_reported_in_cell":
        m["per_layer"][0]["moves"] = "serve_tokens_per_s"
    elif name == "bound_too_wide":
        m["end_to_end"][0]["bound"] = 0.2
    elif name == "no_setup_s":
        m["end_to_end"] = [e for e in m["end_to_end"]
                           if e["name"] != "setup_s"]
    elif name == "reduced_names_a_width":
        m["configs"][0]["reduced"] = ["n_embd"]
    elif name == "second_four_chip_cell":
        for w in m["workloads"]:
            w["chips"] = 4
    elif name == "extra_key_on_a_metric":
        m["per_layer"][0]["why"] = "because"
    elif name == "name_with_a_slash":
        m["per_layer"][0]["name"] = "train/step_mfu"
    elif name == "absolute_path_in_command":
        m["command"] = ["python3", "/root/repo/chipbench/run.py"]
    elif name == "run_seconds_too_long":
        m["run_seconds"] = 52
    elif name == "config_file_outside_paths":
        m["configs"][0]["file"] = "benchmarks/gpt2.json"
    elif name == "extra_top_level_key":
        m["notes"] = "x"
    return m


@pytest.mark.parametrize("breach", [
    "layer_with_a_space", "unit_too_long", "unit_with_a_space",
    "moves_unknown", "moves_not_reported_in_cell", "bound_too_wide",
    "no_setup_s", "reduced_names_a_width", "second_four_chip_cell",
    "extra_key_on_a_metric", "name_with_a_slash",
    "absolute_path_in_command", "run_seconds_too_long",
    "config_file_outside_paths", "extra_top_level_key"])
def test_validate_refuses(breach):
    assert mf.validate(_breach(breach)) != []


# ---------------------------------------------------------------------------
# operations and bytes, against values worked by hand
# ---------------------------------------------------------------------------

HAND = {
    "gpt2-medium": {
        "layer_matmul_params": 12_582_912,      # 4*1024^2 + 2*1024*4096
        "param_count": 406_382_592,
        # 2*12,582,912*24 + 4*512.5*1024*24 + 2*1024*50304, times 3
        "train_flops_per_token_1024": 2_272_149_504,
        # 7 * (2*1024^2*64*16*8/2); 12 * (8*16*1024*64*2 B)
        "flash_8x1024": {"flops": 60_129_542_144, "bytes": 201_326_592},
        # 2000 cached tokens: K and V, 16 heads * 64 * 2 B each
        "paged_2000": {"flops": 8_192_000, "bytes": 8_192_000},
    },
    "gpt2-xl": {
        "layer_matmul_params": 30_720_000,      # 4*1600^2 + 2*1600*6400
        "param_count": 1_638_172_800,
        "train_flops_per_token_1024": 3 * (
            2 * 30_720_000 * 48 + 4 * 512.5 * 1600 * 48
            + 2 * 1600 * 50304),
        "flash_8x1024": {"flops": 7 * 2 * 1024 * 1024 * 64 * 25 * 8 // 2,
                         "bytes": 12 * 8 * 25 * 1024 * 64 * 2},
        "paged_2000": {"flops": 12_800_000, "bytes": 12_800_000},
    },
}


@pytest.mark.parametrize("what", ["layer_matmul_params", "param_count",
                                  "train_flops_per_token_1024",
                                  "flash_8x1024", "paged_2000"])
@pytest.mark.parametrize("config", _ids(CONFIGS))
def test_flop_and_byte_functions(config, what):
    shape = _shape(config)
    got = {
        "layer_matmul_params": lambda: flops.layer_matmul_params(shape),
        "param_count": lambda: flops.param_count(shape),
        "train_flops_per_token_1024":
            lambda: flops.train_flops_per_token(shape, 1024),
        "flash_8x1024": lambda: flops.flash_attention_work(shape, 8, 1024),
        "paged_2000": lambda: flops.paged_decode_work(shape, 2000),
    }[what]()
    assert got == HAND[config][what]


def test_serve_flops_by_hand():
    shape = _shape("gpt2-xl")
    # dense 2,949,120,000 a token; 307,200 a key; head 160,972,800
    assert flops.serve_flops(shape, 100, 5050, 8, 800) == (
        2_949_120_000 * 108 + 307_200 * 5850 + 160_972_800 * 8)
    assert flops.serve_flops(shape, 100, 5050, 8, 800) == 321_589_862_400


def test_roofline_takes_the_larger_bound():
    peak = mf.load_json("chipbench/peaks.json")["device_kinds"]["TPU v5 lite"]
    assert (peak["bf16_flops_per_s"], peak["hbm_bytes_per_s"]) == (
        197e12, 819e9)
    work = flops.flash_attention_work(_shape("gpt2-medium"), 8, 1024)
    assert flops.roofline_seconds(work, peak) == pytest.approx(
        60_129_542_144 / 197e12)              # compute-bound
    work = flops.paged_decode_work(_shape("gpt2-xl"), 2000)
    assert flops.roofline_seconds(work, peak) == pytest.approx(
        12_800_000 / 819e9)                   # bandwidth-bound


# ---------------------------------------------------------------------------
# the trace reduction
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("intervals, want", [
    ([], 0.0),
    ([(0, 1), (2, 3)], 2.0),
    ([(0, 2), (1, 3)], 3.0),
    ([(0, 5), (1, 2), (5, 6)], 6.0),
    ([(1, 1), (3, 2)], 0.0),
])
def test_union_measure(intervals, want):
    assert xplane.measure(intervals) == pytest.approx(want)


@pytest.mark.parametrize("cover, holes, want", [
    ([(0, 10)], [], [(0, 10)]),
    ([(0, 10)], [(2, 3), (5, 7)], [(0, 2), (3, 5), (7, 10)]),
    ([(0, 4), (6, 10)], [(3, 7)], [(0, 3), (7, 10)]),
    ([(0, 4)], [(0, 4)], []),
])
def test_subtract(cover, holes, want):
    assert xplane.subtract(cover, holes) == want


def _synthetic():
    """Two devices, a 10 s window; device 0 runs compute 0-4 and 6-9, an
    all-reduce 4-6 (sync) and an async collective 8.5-9.5 whose last
    half second no compute covers."""
    ar = "%all-reduce.1 = f32[8]{0} all-reduce(f32[8]{0} %x), to_apply=%add"
    start = ("%all-reduce-start.2 = f32[8]{0} all-reduce-start(f32[8]{0} "
             "%y), to_apply=%add")
    fa = ('%flash_attention.3 = bf16[8,16,1024,64]{3,2,1,0} custom-call('
          '%q), custom_call_target="tpu_custom_call"')
    fu = "%fusion.7 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop"
    return xplane.Trace(
        ops={0: [(fu, 0.0, 3.0), (fa, 3.0, 4.0), (ar, 4.0, 6.0),
                 (fu, 6.0, 9.0)],
             1: [(fu, 0.0, 5.0)]},
        async_ops={0: [(start, 8.5, 9.5)]},
        host_spans=[(xplane.WINDOW_SPAN, 0.0, 10.0),
                    ("chipbench/step", 0.0, 9.6),
                    ("chipbench/fence", 9.1, 9.6)])


def test_busy_and_idle_on_a_synthetic_trace():
    t = _synthetic()
    assert xplane.window(t) == (0.0, 10.0)
    assert xplane.busy_by_device(t) == {0: pytest.approx(9.0),
                                        1: pytest.approx(5.0)}


def test_exposed_collective_time_on_a_synthetic_trace():
    exposed = xplane.exposed_collective_by_device(_synthetic())
    # the sync all-reduce (2 s, nothing beside it) + the async tail
    assert exposed[0] == pytest.approx(2.5)
    assert exposed[1] == 0.0


def test_kernel_events_and_breakdown_on_a_synthetic_trace():
    t = _synthetic()
    ev = xplane.kernel_events(t, "flash_attention")
    assert [(a, b) for _, a, b in ev[0]] == [(3.0, 4.0)] and ev[1] == []
    assert xplane.kernel_events(t, "paged_attention")[0] == []
    top = dict(xplane.top_device_ops(t))
    assert top["fusion"] == pytest.approx((6.0 + 5.0) / 2)
    gaps = dict(xplane.idle_gaps_by_span(t))
    # 9.0-10.0 idle on device 0: its midpoint lies in the fence span
    assert gaps == {"fence": pytest.approx(1.0)}


@pytest.mark.parametrize("name, want", [
    ("%all-reduce.74 = (f32[1024,4096]{1,0}) all-reduce(%a)", True),
    ("%all-gather-start.1 = f32[8] all-gather-start(%a)", True),
    ("%fusion.3 = f32[8] fusion(%all-reduce.74)", False),
    ("%flash_attention.3 = bf16[8] custom-call(%q)", False),
])
def test_is_collective(name, want):
    assert xplane.is_collective(name) is want


@pytest.fixture(scope="module")
def recorded():
    return xplane.load_json(os.path.join(
        ROOT, "chipbench/data/trace_train_2steps.json.gz"))


def test_recorded_trace_window_and_busy(recorded):
    """Two traced steps of gpt2-medium.train-dp1 on one v5e chip."""
    lo, hi = xplane.window(recorded)
    assert hi - lo == pytest.approx(0.389425, abs=1e-5)
    busy = xplane.busy_by_device(recorded)
    assert list(busy) == [0]
    assert busy[0] == pytest.approx(0.383682, abs=1e-5)
    assert 0.0 < 1 - busy[0] / (hi - lo) < 0.03


def test_recorded_trace_kernel_time(recorded):
    ev = xplane.kernel_events(recorded, "flash_attention")[0]
    # forward, dq and dkv kernels of 24 layers in each of 2 steps
    assert len(ev) == 2 * 24 * 3
    assert sum(b - a for _, a, b in ev) == pytest.approx(0.0952186,
                                                         abs=1e-6)
    assert xplane.exposed_collective_by_device(recorded) == {0: 0.0}


def test_recorded_trace_breakdown(recorded):
    top = xplane.top_device_ops(recorded)
    assert top[0][0] == "fusion" and top[1][0] == "flash_attention"
    assert len(top) <= 10
    gaps = xplane.idle_gaps_by_span(recorded)
    assert gaps[0][0] == "fence" and len(gaps) <= 10


def test_flash_roofline_reader_on_the_recorded_trace(recorded):
    """The reader's arithmetic, not a measurement: 2 steps x 24 layers
    x 305.2 us least, over the 95.2 ms the kernels took."""
    cell = mf.Cell(MANIFEST, "gpt2-medium.train-dp1")
    run = harness.Run(cell, 0, 1.0, True, False)
    run.peak = mf.load_json(
        "chipbench/peaks.json")["device_kinds"]["TPU v5 lite"]
    run.trace, run.traced = recorded, {"steps": 2}
    got = cell.reader("train.flash_attention_roofline").read(run)
    assert got == pytest.approx(
        100 * 2 * 24 * (60_129_542_144 / 197e12) / 0.0952186, rel=1e-4)
    run.trace = xplane.Trace()
    assert cell.reader("train.flash_attention_roofline").read(run) is None


@pytest.mark.parametrize("metric", PER_LAYER, ids=_ids(PER_LAYER))
def test_reader_with_nothing_to_read_returns_nothing(metric):
    cell_name = metric.get("workloads", _ids(CELLS))[0]
    cell = mf.Cell(MANIFEST, cell_name)
    run = harness.Run(cell, 0, 1.0, True, False)
    assert cell.reader(metric["name"]).read(run) is None


# ---------------------------------------------------------------------------
# traffic from the seed
# ---------------------------------------------------------------------------

def _take(it, n):
    return [next(it) for _ in range(n)]


@pytest.mark.parametrize("seed", [0, 7, 2 ** 31 + 11, 2 ** 32 + 15])
def test_token_batches_from_the_seed(seed):
    cell = mf.Cell(MANIFEST, "gpt2-medium.train-dp4")
    gen = cell.generator().batches
    a = _take(gen(cell.traffic, 50257, seed), 2)
    b = _take(gen(cell.traffic, 50257, seed), 2)
    c = _take(gen(cell.traffic, 50257, seed + 1), 1)
    for (ta, la), (tb, lb) in zip(a, b):
        assert np.array_equal(ta, tb) and np.array_equal(la, lb)
    tokens, labels = a[0]
    assert tokens.shape == labels.shape == (32, 1024)
    assert tokens.dtype == np.int32 and 0 <= tokens.min()
    assert tokens.max() < 50257
    assert np.array_equal(tokens[:, 1:], labels[:, :-1])
    assert not np.array_equal(tokens, c[0][0])
    assert not np.array_equal(a[0][0], a[1][0])        # fresh each step
    assert len({row.tobytes() for row in tokens}) == 32  # rows all differ


@pytest.mark.parametrize("seed", [0, 7, 2 ** 32 + 15])
def test_closed_loop_requests_from_the_seed(seed):
    cell = mf.Cell(MANIFEST, "gpt2-xl.serve-closed8")
    gen = cell.generator()
    grid = cell.traffic["size_grid"]
    a = _take(gen.requests(cell.traffic, 50257, seed), grid)
    b = _take(gen.requests(cell.traffic, 50257, seed), grid)
    other = _take(gen.requests(cell.traffic, 50257, seed + 1), grid)
    assert a == b and a != other

    def sizes(reqs):
        return sorted((len(r["prompt"]), r["max_new_tokens"]) for r in reqs)
    # every seed offers the same sizes, in another order
    assert sizes(a) == sizes(other) == sorted(gen.size_grid(cell.traffic))
    assert min(s[0] for s in sizes(a)) >= 32
    assert max(s[0] for s in sizes(a)) <= 512
    assert min(s[1] for s in sizes(a)) >= 32
    assert max(s[1] for s in sizes(a)) <= 128
    longest = max(p + n for p, n in sizes(a))
    assert longest <= cell.traffic["server"]["max_len"]
    assert all(r["temperature"] == 0.0 for r in a)
    assert all(0 <= t < 50257 for r in a[:4] for t in r["prompt"])


def test_shared_prefix_is_shared():
    cell = mf.Cell(MANIFEST, "gpt2-xl.serve-closed8")
    traffic = dict(cell.traffic, shared_prefix_tokens=16)
    reqs = _take(cell.generator().requests(traffic, 50257, 3), 8)
    assert len({tuple(r["prompt"][:16]) for r in reqs}) == 1
    assert len({tuple(r["prompt"][16:24]) for r in reqs}) == 8


# ---------------------------------------------------------------------------
# the plain reference, the control, the comparison
# ---------------------------------------------------------------------------

TINY = ref.Shape({"n_embd": 64, "n_layer": 2, "n_head": 4,
                  "n_positions": 32, "vocab_size": 500,
                  "layer_norm_epsilon": 1e-6,
                  "assumed": {"padded_vocab_size": 512}})
ADAMW = {"name": "adamw", "learning_rate": 1e-4, "b1": 0.9, "b2": 0.999,
         "eps": 1e-8, "weight_decay": 1e-4}


def test_reference_imports_nothing_of_the_program():
    src = open(os.path.join(ROOT, "chipbench/reference.py")).read()
    assert "horovod_tpu" not in src.replace(
        "imports\nnothing from `horovod_tpu`", "")


def test_reference_agrees_with_models_gpt_in_float32():
    import jax
    import jax.numpy as jnp
    from chipbench.gpt_layout import flax_tree
    from horovod_tpu.models.gpt import GPT, GPTConfig
    w = ref.make_weights(TINY, ref.seed_key(2 ** 32 + 5))
    tokens = np.random.default_rng(0).integers(0, 500, (2, 32),
                                               dtype=np.int32)
    model = GPT(GPTConfig(vocab_size=512, num_layers=2, num_heads=4,
                          head_dim=16, max_seq_len=32, dtype=jnp.float32))
    with jax.default_matmul_precision("highest"):
        got = model.apply({"params": flax_tree(w, TINY)}, tokens)
    want = ref.logits(w, TINY, tokens)
    assert got.shape == want.shape == (2, 32, 512)
    assert float(jnp.max(jnp.abs(got - want))) < 2e-5


def test_reference_adamw_is_optax_adamw():
    import jax
    import optax
    w = ref.make_weights(TINY, ref.seed_key(1))
    g = ref.make_weights(TINY, ref.seed_key(2))
    trainer = ref.Trainer(TINY, ADAMW)
    tx = optax.adamw(1e-4, b1=0.9, b2=0.999, eps=1e-8, weight_decay=1e-4)
    state, p = tx.init(w), w
    m, v = trainer.init(w)
    mine = jax.tree.map(lambda x: x + 0, w)
    for t in (1, 2):
        updates, state = tx.update(g, state, p)
        p = optax.apply_updates(p, updates)
        mine, m, v = trainer.step(mine, m, v, jax.tree.map(
            lambda x: x + 0, g), t)
    for k in p:
        assert np.allclose(p[k], mine[k], rtol=0, atol=1e-7), k


def _train_module():
    return mf.load_module("chipbench/runners/train.py")


@pytest.fixture(scope="module")
def tiny_batches():
    cell = mf.Cell(MANIFEST, "gpt2-medium.train-dp1")
    traffic = dict(cell.traffic, rows_per_chip=2, seq_len=32)
    return lambda seed: _take(
        cell.generator().batches(traffic, 500, seed), 3)


@pytest.mark.parametrize("seed", [11, 12, 2 ** 32 + 13])
def test_the_control_fails_the_comparison(seed, tiny_batches):
    """The reference in fp8, put in the program's place, is not correct
    by the rehearsal's limits; in bfloat16 (what the program computes
    in) it is."""
    train = _train_module()
    limits = mf.Cell(MANIFEST, "gpt2-medium.train-dp1").limits(True)
    batches = tiny_batches(seed)
    want = train.reference_steps(TINY, ADAMW, seed, batches)
    for precision, correct in (("bfloat16", True), ("fp8", False)):
        got = train.reference_steps(TINY, ADAMW, seed, batches,
                                    precision=precision)
        compared = train.compare_steps(got, want, limits)
        assert harness.compare(compared) is correct, (precision, compared)


def test_worst_leaf_gap_by_hand():
    train = _train_module()
    want = {"a": 1.0, "b": 2.0, "c": 4.0}
    # a is measured against the median leaf (2.0), c against itself
    assert train.worst_leaf_gap({"a": 1.5, "b": 2.0, "c": 4.0},
                                want) == pytest.approx(0.25)
    assert train.worst_leaf_gap({"a": 1.0, "b": 2.0, "c": 6.0},
                                want) == pytest.approx(0.5)
    assert train.worst_leaf_gap({"a": 1.0, "b": 2.0, "c": 6.0},
                                want, skip={"c"}) == 0.0


def test_leaves_with_no_gradient_are_left_out_of_the_change():
    train = _train_module()
    want = {"loss": [1.0], "grad": {"a": 1.0, "b": 1.0, "k": 1e-6},
            "change": {"a": 1.0, "b": 1.0, "k": 1.0}}
    prog = {"loss": [1.0], "grad": dict(want["grad"]),
            "change": {"a": 1.0, "b": 1.0, "k": 2.0}}
    limits = {"loss_gap": 1e-3, "grad_norm_gap": 1e-3,
              "change_norm_gap": 1e-3}
    assert harness.compare(train.compare_steps(prog, want, limits))
    prog["change"]["a"] = 2.0
    assert not harness.compare(train.compare_steps(prog, want, limits))


@pytest.mark.parametrize("compared, want", [
    ([], False),
    ([{"name": "x", "value": 0.0, "limit": 0.0}], True),
    ([{"name": "x", "value": 1e-9, "limit": 0.0}], False),
    ([{"name": "x", "value": float("nan"), "limit": 1.0}], False),
    ([{"name": "x", "value": float("inf"), "limit": 1.0}], False),
    ([{"name": "x", "value": 0.5, "limit": 1.0},
      {"name": "y", "value": 2.0, "limit": 1.0}], False),
])
def test_compare(compared, want):
    assert harness.compare(compared) is want


def test_p90_by_hand():
    serve = mf.load_module("chipbench/runners/serve.py")
    assert serve.percentile(list(range(1, 12)), 90) == pytest.approx(10.0)
    assert serve.percentile([5.0], 90) == 5.0


# ---------------------------------------------------------------------------
# a run end to end: rehearsals, faults, refusals
# ---------------------------------------------------------------------------

@pytest.fixture()
def hvd_off():
    yield
    import horovod_tpu as hvd
    hvd.shutdown()


def _rehearse(cell, capsys, seed=3, trace=0, seconds=1.0):
    rc = harness.main(["--workload", cell, "--seed", str(seed),
                       "--seconds", str(seconds), "--trace", str(trace),
                       "--rehearse"])
    out, err = capsys.readouterr()
    assert rc == 0, err[-2000:]
    return json.loads(out.strip().splitlines()[-1]), err


@pytest.mark.parametrize("cell", CELLS, ids=_ids(CELLS))
def test_rehearsal_ends_in_one_well_formed_line(cell, capsys, hvd_off):
    result, err = _rehearse(cell["name"], capsys, seed=2 ** 32 + 21,
                            trace=1)
    assert list(result)[:5] == ["correct", "attempted", "failed",
                                "metrics", "device"]
    assert list(result)[-1] == "compared"
    assert result["correct"] is True, result["compared"]
    assert result["attempted"] > 0 and result["failed"] == 0
    assert result["device"]["platform"] == "cpu"
    assert result["device"]["count"] >= cell["chips"]
    assert "memory_peak_bytes" in result["device"]
    # a CPU number is never printed under a device metric's name
    assert result["rehearsal"] is True
    assert result["metrics"]
    assert all(k.startswith("rehearsal.") for k in result["metrics"])
    assert result["compared"]["compiles_in_window"] == {"value": 0.0,
                                                        "limit": 0.0}
    if "serve_tokens_per_s" in [m["name"] for m in mf.Cell(
            MANIFEST, cell["name"]).end_to_end]:
        # every token the rate counts was delivered, and no other
        assert result["compared"]["uncounted_tokens"] == {"value": 0.0,
                                                          "limit": 0.0}
    for name, c in result["compared"].items():
        assert f"compared {name} " in err
    assert err.strip().splitlines()[-1].startswith("compared ")


def _train_fault(monkeypatch, fault):
    import jax
    import jax.numpy as jnp
    Runner = _train_module().Runner
    if fault == "state_unchanged":
        def dispatch(self, batch):
            params, opt_state, stats = jax.tree.map(jnp.copy, self.state)
            return self.step(params, opt_state, stats, *batch)[-1]
        monkeypatch.setattr(Runner, "_dispatch", dispatch)
        return
    put = Runner._put
    if fault == "half_batch_left_out":
        def rows(x, chips):
            half = x.shape[0] // 2
            return np.concatenate([x[:half], x[:half]])
    elif fault == "exchange_left_out":
        def rows(x, chips):     # every chip sees the first chip's rows
            return np.tile(x[:x.shape[0] // chips], (chips, 1))
    monkeypatch.setattr(Runner, "_put", lambda self, host: put(
        self, tuple(rows(x, self.run.chips) for x in host)))


@pytest.mark.parametrize("cell, fault", [
    ("gpt2-medium.train-dp1", "state_unchanged"),
    ("gpt2-medium.train-dp1", "half_batch_left_out"),
    ("gpt2-medium.train-dp4", "exchange_left_out"),
])
def test_a_broken_train_step_is_not_correct(cell, fault, monkeypatch,
                                            capsys, hvd_off):
    _train_fault(monkeypatch, fault)
    result, _ = _rehearse(cell, capsys)
    assert result["correct"] is False
    over = [k for k, c in result["compared"].items()
            if c["value"] > c["limit"]]
    assert over, result["compared"]


def test_an_altered_token_is_not_correct(monkeypatch, capsys, hvd_off):
    Runner = mf.load_module("chipbench/runners/serve.py").Runner
    wrap = Runner._wrap_executor

    def altered(self):
        inner = self.executor.step

        def step(tokens, positions, mask, last_idx, *, kind="decode", **kw):
            out = np.array(inner(tokens, positions, mask, last_idx,
                                 kind=kind, **kw))
            if kind == "decode":        # every row's token, off by one
                out = (out + 1) % self.shape.vocab
            return out
        self.executor.step = step
        wrap(self)
    monkeypatch.setattr(Runner, "_wrap_executor", altered)
    result, _ = _rehearse("gpt2-xl.serve-closed8", capsys, seconds=2.0)
    c = result["compared"]["served_logit_gap"]
    assert result["correct"] is False and c["value"] > c["limit"]


@pytest.mark.parametrize("cell", CELLS, ids=_ids(CELLS))
def test_no_measurement_off_a_tpu(cell, capsys):
    rc = harness.main(["--workload", cell["name"], "--seed", "1",
                       "--seconds", "1", "--trace", "0"])
    out, err = capsys.readouterr()
    assert rc != 0 and out == ""
    assert "measures a TPU" in err


def test_the_command_refuses_off_a_tpu():
    proc = subprocess.run(
        MANIFEST["command"] + ["--workload", CELLS[0]["name"], "--seed",
                               str(2 ** 31 + 5), "--seconds", "1",
                               "--trace", "0"],
        cwd=ROOT, capture_output=True, text=True, timeout=120,
        env=dict(os.environ, JAX_PLATFORMS="cpu", BENCH_RUN="7"))
    assert proc.returncode != 0 and proc.stdout == ""
    assert "measures a TPU" in proc.stderr


class _Chip:
    platform = "tpu"

    def __init__(self, kind, i=0):
        self.device_kind, self.id = kind, i


@pytest.mark.parametrize("kind, chips, cell, refused", [
    ("TPU v5 lite", 1, "gpt2-medium.train-dp1", False),
    ("TPU v5 lite", 4, "gpt2-medium.train-dp4", False),
    ("TPU v5 lite", 1, "gpt2-medium.train-dp4", True),   # too few chips
    ("TPU v9 imagined", 1, "gpt2-medium.train-dp1", True),
])
def testdevice_gate(kind, chips, cell, refused, monkeypatch):
    import jax
    monkeypatch.setattr(jax, "devices",
                        lambda: [_Chip(kind, i) for i in range(chips)])
    run = harness.Run(mf.Cell(MANIFEST, cell), 0, 1.0, False, False)
    if refused:
        with pytest.raises(harness.Refused):
            harness.device_gate(run)
    else:
        device = harness.device_gate(run)
        assert device == {"platform": "tpu", "kind": kind, "count": chips}
        assert run.peak["bf16_flops_per_s"] == 197e12
        assert len(run.devices) == run.chips


def test_unknown_cell_prints_no_result(capsys):
    rc = harness.main(["--workload", "no-such-cell", "--seed", "1",
                       "--seconds", "1", "--rehearse"])
    out, err = capsys.readouterr()
    assert rc != 0 and out == "" and "no cell" in err


# ---------------------------------------------------------------------------
# a later PR adds files and manifest entries, and edits nothing
# ---------------------------------------------------------------------------

def test_a_cell_a_config_and_a_metric_are_added_by_files_alone(tmp_path):
    root = str(tmp_path)
    shutil.copytree(os.path.join(ROOT, "chipbench"),
                    os.path.join(root, "chipbench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {os.path.join(dp, p): open(os.path.join(dp, p), "rb").read()
              for dp, _, fs in os.walk(root) for p in fs}
    config = mf.load_json("chipbench/configs/gpt2-medium.json")
    config.update(source="https://huggingface.co/openai-community/gpt2",
                  n_embd=768, n_layer=12, n_head=12)
    traffic = mf.load_json("chipbench/traffic/train-dp1.json")
    traffic["rows_per_chip"] = 16
    limits = mf.load_json("chipbench/limits/gpt2-medium.train-dp1.json")
    added = {"chipbench/configs/gpt2-small.json": json.dumps(config),
             "chipbench/traffic/train-b16.json": json.dumps(traffic),
             "chipbench/limits/gpt2-small.train-b16.json":
                 json.dumps(limits),
             "chipbench/layer_metrics/train.steps_traced.py":
                 "def read(run):\n"
                 "    return run.traced.get('steps') or None\n"}
    for rel, text in added.items():
        with open(os.path.join(root, rel), "w") as f:
            f.write(text)
    m = copy.deepcopy(MANIFEST)
    m["configs"].append({
        "name": "gpt2-small", "file": "chipbench/configs/gpt2-small.json",
        "source": config["source"], "reduced": list(config["reduced"]),
        "why": "throw-away"})
    m["workloads"].append({"name": "gpt2-small.train-b16", "chips": 1,
                           "config": "gpt2-small", "traffic": "train-b16",
                           "why": "throw-away"})
    m["workloads"].append({"name": "gpt2-medium.train-b16", "chips": 1,
                           "config": "gpt2-medium", "traffic": "train-b16",
                           "why": "throw-away"})
    with open(os.path.join(root, "chipbench/limits/"
                                 "gpt2-medium.train-b16.json"), "w") as f:
        json.dump(limits, f)
    new = ["gpt2-small.train-b16", "gpt2-medium.train-b16"]
    for metric in m["end_to_end"] + m["per_layer"]:
        if "gpt2-medium.train-dp1" in metric.get("workloads", ()):
            metric["workloads"] += new
    m["per_layer"].append({
        "name": "train.steps_traced", "unit": "steps", "better": "higher",
        "source": "program_counter", "layer": "train_step",
        "moves": "train_tokens_per_s", "workloads": new})
    assert mf.validate(m, root) == []
    cell = mf.Cell(m, "gpt2-small.train-b16", root)
    assert ref.Shape(cell.config).d == 768
    assert cell.traffic["rows_per_chip"] == 16
    assert "train.steps_traced" in [x["name"] for x in cell.per_layer]
    run = harness.Run(cell, 0, 1.0, True, False)
    run.traced = {"steps": 8}
    assert cell.reader("train.steps_traced").read(run) == 8
    assert cell.runner().Runner(run).shape.layers == 12
    # nothing that was there has been edited
    for path, data in before.items():
        assert open(path, "rb").read() == data, path


def test_readme_says_how_to_add_each_kind_of_file():
    text = open(os.path.join(ROOT, "chipbench/README.md")).read()
    for word in ("chipbench/configs/", "chipbench/traffic/",
                 "chipbench/generators/", "chipbench/layer_metrics/",
                 "chipbench/runners/", "chipbench/limits/"):
        assert word in text, word
