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
false, refusal off a TPU, and throw-away cells added from files alone:
one of the family that is here, one of ANOTHER architecture.

Whatever depends on an architecture is found through the configuration's
family (``chipbench/families/<family>.py``) and the configuration's own
file; no table here is keyed by a configuration's name. The checks behind
the tests parametrised over configurations and cells are functions
(``check_*``), which the files-alone tests call on their throw-away
entries too. The tests of `chipbench/reference.py`, `flops.py` and
`gpt_layout.py` further down are the GPT-2 family's own.
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

from chipbench import flops, harness, roofline, xplane  # noqa: E402
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


def _family(config, root=ROOT):
    """A `configs` entry's family module, by its file's ``family``."""
    return mf.load_module(mf.family_file(
        mf.load_json(config["file"], root), config["name"]), root)


def _shape(config_name):
    entry = next(c for c in CONFIGS if c["name"] == config_name)
    return _family(entry).Shape(mf.load_json(entry["file"]))


# ---------------------------------------------------------------------------
# the manifest, entry by entry
# ---------------------------------------------------------------------------

def test_manifest_passes_the_drivers_rules():
    assert mf.validate(MANIFEST) == []


def check_name_is_one_token(entry):
    assert mf.NAME.match(entry["name"]), entry["name"]
    assert not set(entry["name"]) & set(" ,/")


@pytest.mark.parametrize("entry", CONFIGS + CELLS + METRICS,
                         ids=_ids(CONFIGS + CELLS + METRICS))
def test_name_is_one_token(entry):
    check_name_is_one_token(entry)


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


def check_cell_files_are_found_by_name(manifest, cell, root=ROOT):
    c = mf.Cell(manifest, cell["name"], root)
    assert hasattr(c.runner(), "Runner")
    # the family brings what the harness and this cell's runner ask of it
    missing = [n for n in ("Shape", "REHEARSE_CONFIG", "PUBLISHED_WIDTHS",
                           "WORK_COUNTS") + tuple(c.runner().FAMILY_NEEDS)
               if not hasattr(c.family(), n)]
    assert not missing, (c.config["family"], missing)
    assert c.generator() is not None
    assert all(v > 0 for v in c.limits().values())
    assert {m["name"] for m in c.end_to_end} >= {"setup_s"}
    assert len(c.end_to_end) >= 2 and c.per_layer
    assert any("mfu" in m["name"] for m in c.per_layer)
    for m in c.per_layer:
        assert callable(c.reader(m["name"]).read)
    assert len(cell["why"]) <= 200 and "\n" not in cell["why"]


@pytest.mark.parametrize("cell", CELLS, ids=_ids(CELLS))
def test_cell_files_are_found_by_name(cell):
    check_cell_files_are_found_by_name(MANIFEST, cell)


def check_config_keeps_the_published_widths(config, root=ROOT):
    """The sizes the family's `Shape` reads from the file's own keys are
    the source's, which the file states a second time, by hand, under
    ``published`` (every width the family names)."""
    data = mf.load_json(config["file"], root)
    family = _family(config, root)
    shape = family.Shape(data)
    published = data.get("published") or {}
    assert set(published) == set(family.PUBLISHED_WIDTHS), config["name"]
    assert {k: getattr(shape, k) for k in published} == published
    assert shape.padded_vocab >= shape.vocab
    assert shape.padded_vocab % 128 == 0
    assert sorted(data["reduced"]) == sorted(config["reduced"])
    assert not any(mf._WIDTH.search(k) for k in config["reduced"])
    assert config["source"] == data["source"]


@pytest.mark.parametrize("config", CONFIGS, ids=_ids(CONFIGS))
def test_config_keeps_the_published_widths(config):
    check_config_keeps_the_published_widths(config)


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

def hand_worked_cases(configs, root=ROOT):
    """(configs entry, work count) for every hand-worked value the
    configurations' files hold. One that brings none still gets a case,
    which fails: the next configuration must bring its own."""
    cases = []
    for c in configs:
        names = list(mf.load_json(c["file"], root).get("hand_worked") or {})
        cases += [(c, n) for n in names or ["none_brought"]]
    return cases


def check_flop_and_byte_functions(config, what, root=ROOT):
    """A work count of the configuration's family, at the configuration's
    sizes, against the value its file gives, worked by hand (``how``)."""
    data = mf.load_json(config["file"], root)
    family = _family(config, root)
    hand = data.get("hand_worked") or {}
    assert hand, f"{config['name']} brings no hand-worked values"
    # every work count the family checks this way has a value here
    assert set(hand) == set(family.WORK_COUNTS), config["name"]
    got = family.WORK_COUNTS[what](family.Shape(data))
    assert got == hand[what]["value"], hand[what]["how"]


_HAND = hand_worked_cases(CONFIGS)


@pytest.mark.parametrize(
    "config, what", _HAND, ids=[f"{c['name']}-{w}" for c, w in _HAND])
def test_flop_and_byte_functions(config, what):
    check_flop_and_byte_functions(config, what)


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
    assert roofline.roofline_seconds(work, peak) == pytest.approx(
        60_129_542_144 / 197e12)              # compute-bound
    work = flops.paged_decode_work(_shape("gpt2-xl"), 2000)
    assert roofline.roofline_seconds(work, peak) == pytest.approx(
        12_800_000 / 819e9)                   # bandwidth-bound


def test_what_the_readers_ask_the_gpt2_family_for():
    """The whole model's work over recorded steps is the per-layer,
    per-call counts above, summed: same arithmetic as before the seam."""
    shape = _shape("gpt2-xl")
    family = mf.load_module("chipbench/families/gpt2.py")
    steps = [{"kind": "prefill", "prompt_tokens": 100,
              "prompt_context": 5050, "decode_tokens": 0,
              "decode_context": 0, "emitted": 1},
             {"kind": "decode", "prompt_tokens": 0, "prompt_context": 0,
              "decode_tokens": 8, "decode_context": 800, "emitted": 8}]
    # the prefill's one emitted token costs a head product too
    assert family.serve_flops(shape, steps) == (
        321_589_862_400 + 160_972_800)
    assert family.decode_attention_work(shape, steps) == {
        "flops": 48 * 4 * 800 * 1600, "bytes": 48 * 2 * 800 * 1600 * 2}
    assert family.attention_work(shape, 8, 1024) == {
        "flops": 48 * 93_952_409_600, "bytes": 48 * 314_572_800}
    assert family.decode_query_pattern(shape, 8) == r"\[8,25,1,64\]"
    assert family.train_flops_per_token(shape, 1024) == 9_802_598_400
    assert family.param_count(shape) == 1_638_172_800


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


def check_reader_with_nothing_to_read(manifest, metric, cell_name,
                                      root=ROOT):
    cell = mf.Cell(manifest, cell_name, root)
    run = harness.Run(cell, 0, 1.0, True, False)
    assert cell.reader(metric["name"]).read(run) is None


@pytest.mark.parametrize("metric", PER_LAYER, ids=_ids(PER_LAYER))
def test_reader_with_nothing_to_read_returns_nothing(metric):
    check_reader_with_nothing_to_read(
        MANIFEST, metric, metric.get("workloads", _ids(CELLS))[0])


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

# the GPT-2 family's own parts: its reference, its control, its layout
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
    family = mf.load_module("chipbench/families/gpt2.py")
    assert family.CONTROL == "fp8"
    want = train.reference_steps(family, TINY, ADAMW, seed, batches)
    for precision, correct in (("bfloat16", True), ("fp8", False)):
        got = train.reference_steps(family, TINY, ADAMW, seed, batches,
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


def _rehearse(cell, capsys, seed=3, trace=0, seconds=1.0, root=ROOT):
    rc = harness.main(["--workload", cell, "--seed", str(seed),
                       "--seconds", str(seconds), "--trace", str(trace),
                       "--rehearse"], root=root)
    out, err = capsys.readouterr()
    assert rc == 0, err[-2000:]
    return json.loads(out.strip().splitlines()[-1]), err


def check_rehearsal_ends_in_one_well_formed_line(manifest, cell, capsys,
                                                 root=ROOT):
    result, err = _rehearse(cell["name"], capsys, seed=2 ** 32 + 21,
                            trace=1, root=root)
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
            manifest, cell["name"], root).end_to_end]:
        # every token the rate counts was delivered, and no other
        assert result["compared"]["uncounted_tokens"] == {"value": 0.0,
                                                          "limit": 0.0}
    for name, c in result["compared"].items():
        assert f"compared {name} " in err
    assert err.strip().splitlines()[-1].startswith("compared ")
    return result


@pytest.mark.parametrize("cell", CELLS, ids=_ids(CELLS))
def test_rehearsal_ends_in_one_well_formed_line(cell, capsys, hvd_off):
    check_rehearsal_ends_in_one_well_formed_line(MANIFEST, cell, capsys)


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


def check_no_measurement_off_a_tpu(cell, capsys, root=ROOT):
    rc = harness.main(["--workload", cell["name"], "--seed", "1",
                       "--seconds", "1", "--trace", "0"], root=root)
    out, err = capsys.readouterr()
    assert rc != 0 and out == ""
    assert "measures a TPU" in err


@pytest.mark.parametrize("cell", CELLS, ids=_ids(CELLS))
def test_no_measurement_off_a_tpu(cell, capsys):
    check_no_measurement_off_a_tpu(cell, capsys)


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

def _checkout(tmp_path):
    """A copy of ``chipbench/`` in a temporary root, and every file's
    bytes as copied: what a later PR starts from and may not edit."""
    root = str(tmp_path)
    shutil.copytree(os.path.join(ROOT, "chipbench"),
                    os.path.join(root, "chipbench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {os.path.join(dp, p): open(os.path.join(dp, p), "rb").read()
              for dp, _, fs in os.walk(root) for p in fs}
    return root, before


def _add(root, files):
    for rel, text in files.items():
        assert not os.path.exists(os.path.join(root, rel)), rel
        with open(os.path.join(root, rel), "w") as f:
            f.write(text if isinstance(text, str) else json.dumps(text))


def _list_cells(manifest, beside, new):
    """Every metric that lists the cell `beside` lists `new` too."""
    for metric in manifest["end_to_end"] + manifest["per_layer"]:
        if beside in metric.get("workloads", ()):
            metric["workloads"] += new


def test_a_cell_a_config_and_a_metric_are_added_by_files_alone(tmp_path):
    """Another configuration of the family that is here needs a file of
    its own (its keys, its published widths, its hand-worked values) and
    no family module."""
    root, before = _checkout(tmp_path)
    config = mf.load_json("chipbench/configs/gpt2-medium.json")
    config.update(
        source="https://huggingface.co/openai-community/gpt2",
        n_embd=768, n_layer=12, n_head=12,
        published=dict(d=768, layers=12, heads=12, head_dim=64, ffn=3072,
                       positions=1024, vocab=50257),
        hand_worked={
            "layer_matmul_params": {
                "value": 4 * 768 * 768 + 2 * 768 * 3072, "how": "as said"},
            "param_count": {
                "value": 2 * 50304 * 768 + 1024 * 768 + 2 * 768 + 12 * (
                    7_077_888 + 3 * 768 + 768 + 3072 + 768 + 4 * 768),
                "how": "as said"},
            "train_flops_per_token_1024": {
                "value": 3 * (2 * 7_077_888 * 12 + 4 * 512.5 * 768 * 12
                              + 2 * 768 * 50304), "how": "as said"},
            "flash_8x1024": {"value": {
                "flops": 7 * 2 * 1024 * 1024 * 64 * 12 * 8 // 2,
                "bytes": 12 * 8 * 12 * 1024 * 64 * 2}, "how": "as said"},
            "paged_2000": {"value": {"flops": 4 * 2000 * 768,
                                     "bytes": 2 * 2000 * 768 * 2},
                           "how": "as said"}})
    traffic = mf.load_json("chipbench/traffic/train-dp1.json")
    traffic["rows_per_chip"] = 16
    limits = mf.load_json("chipbench/limits/gpt2-medium.train-dp1.json")
    _add(root, {
        "chipbench/configs/gpt2-small.json": config,
        "chipbench/traffic/train-b16.json": traffic,
        "chipbench/limits/gpt2-small.train-b16.json": limits,
        "chipbench/limits/gpt2-medium.train-b16.json": limits,
        "chipbench/layer_metrics/train.steps_traced.py":
            "def read(run):\n"
            "    return run.traced.get('steps') or None\n"})
    m = copy.deepcopy(MANIFEST)
    small = {"name": "gpt2-small", "source": config["source"],
             "file": "chipbench/configs/gpt2-small.json",
             "reduced": list(config["reduced"]), "why": "throw-away"}
    m["configs"].append(small)
    new = [{"name": "gpt2-small.train-b16", "chips": 1,
            "config": "gpt2-small", "traffic": "train-b16",
            "why": "throw-away"},
           {"name": "gpt2-medium.train-b16", "chips": 1,
            "config": "gpt2-medium", "traffic": "train-b16",
            "why": "throw-away"}]
    m["workloads"] += new
    _list_cells(m, "gpt2-medium.train-dp1", _ids(new))
    m["per_layer"].append({
        "name": "train.steps_traced", "unit": "steps", "better": "higher",
        "source": "program_counter", "layer": "train_step",
        "moves": "train_tokens_per_s", "workloads": _ids(new)})
    assert mf.validate(m, root) == []
    check_config_keeps_the_published_widths(small, root)
    cases = hand_worked_cases([small], root)
    assert len(cases) == 5
    for _, what in cases:
        check_flop_and_byte_functions(small, what, root)
    for entry in new:
        check_name_is_one_token(entry)
        check_cell_files_are_found_by_name(m, entry, root)
    cell = mf.Cell(m, "gpt2-small.train-b16", root)
    assert cell.traffic["rows_per_chip"] == 16
    assert "train.steps_traced" in [x["name"] for x in cell.per_layer]
    run = harness.Run(cell, 0, 1.0, True, False)
    assert run.shape.d == 768
    run.traced = {"steps": 8}
    assert cell.reader("train.steps_traced").read(run) == 8
    assert cell.runner().Runner(run).shape.layers == 12
    # a configuration that brings no hand-worked values fails its case
    del config["hand_worked"]
    with open(os.path.join(root, small["file"]), "w") as f:
        json.dump(config, f)
    (_, what), = hand_worked_cases([small], root)
    with pytest.raises(AssertionError, match="no hand-worked values"):
        check_flop_and_byte_functions(small, what, root)
    # nothing that was there has been edited
    for path, data in before.items():
        assert open(path, "rb").read() == data, path


TOYGATED = {     # another architecture's keys; the family reads them
    "source": "tests/test_chipbench/another_family.py",
    "family": "toygated", "model_type": "toygated",
    "hidden_size": 64, "num_hidden_layers": 4, "num_attention_heads": 8,
    "num_key_value_heads": 2, "intermediate_size": 176,
    "max_position_embeddings": 128, "vocab_size": 1000,
    "rms_norm_eps": 1e-6, "rope_theta": 10000.0, "initializer_range": 0.02,
    "reduced": [],
    "assumed": {"padded_vocab_size": 1024, "compute_dtype": "float32"},
    "published": dict(d=64, layers=4, heads=8, kv_heads=2, head_dim=8,
                      ffn=176, positions=128, vocab=1000),
    "hand_worked": {
        "layer_matmul_params": {
            "value": 44_032, "how": "2*64*64 + 2*64*(2*8) + 3*64*176"},
        "param_count": {
            "value": 307_776,
            "how": "2*1024*64 + 64 + 4*(44032 + 2*64)"},
        "serve_flops_10_prompt_2_decode": {
            "value": 4_700_160,
            "how": "2*44032*4*12 + 4*8*8*4*78 + 2*64*1024*3"},
        "decode_attention_100": {
            "value": {"flops": 102_400, "bytes": 51_200},
            "how": "4*100*(8*8)*4; 2*100*(2*8)*4 B*4 layers"}},
}


def test_another_architecture_is_added_by_files_alone(tmp_path, capsys,
                                                      hvd_off):
    """A configuration whose keys, model, reference and work counts are
    not GPT-2's comes as a configuration file, a family module, a limits
    file and manifest entries. The `serve` runner, the
    `closed_loop_requests` generator, the traffic file and the readers
    are used as they stand, and every check the tests above run on the
    manifest's configurations and cells passes for it."""
    root, before = _checkout(tmp_path)
    with open(os.path.join(os.path.dirname(__file__),
                           "another_family.py")) as f:
        family_source = f.read()
    _add(root, {
        "chipbench/configs/toygated.json": TOYGATED,
        "chipbench/families/toygated.py": family_source,
        "chipbench/limits/toygated.serve-closed8.json":
            {"limits": {"served_logit_gap": 0.01}}})
    m = copy.deepcopy(MANIFEST)
    config = {"name": "toygated", "source": TOYGATED["source"],
              "file": "chipbench/configs/toygated.json", "reduced": [],
              "why": "throw-away"}
    cell = {"name": "toygated.serve-closed8", "config": "toygated",
            "traffic": "serve-closed8", "chips": 1, "why": "throw-away"}
    m["configs"].append(config)
    m["workloads"].append(cell)
    _list_cells(m, "gpt2-xl.serve-closed8", [cell["name"]])
    # the harness reads BENCHMARK.json from the root it is given
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(m, f)
    assert mf.validate(m, root) == []

    for entry in (config, cell):
        check_name_is_one_token(entry)
    check_config_keeps_the_published_widths(config, root)
    cases = hand_worked_cases([config], root)
    assert [w for _, w in cases] == list(TOYGATED["hand_worked"])
    for _, what in cases:
        check_flop_and_byte_functions(config, what, root)
    check_cell_files_are_found_by_name(m, cell, root)
    for metric in m["per_layer"]:
        if cell["name"] in metric.get("workloads", ()):
            check_reader_with_nothing_to_read(m, metric, cell["name"], root)
    check_no_measurement_off_a_tpu(cell, capsys, root)
    result = check_rehearsal_ends_in_one_well_formed_line(m, cell, capsys,
                                                          root)
    assert result["compared"]["served_logit_gap"]["limit"] == 0.01
    assert "rehearsal.serve.step_mfu" in result["metrics"]

    # the readers read THIS family's work, at this configuration's sizes
    c = mf.Cell(m, cell["name"], root)
    run = harness.Run(c, 0, 1.0, True, False)
    family = run.family
    assert family.__file__.startswith(root) and run.shape.kv_heads == 2
    run.peak = {"bf16_flops_per_s": 1e9, "hbm_bytes_per_s": 1e6}
    steps = [{"kind": "prefill", "prompt_tokens": 10, "prompt_context": 55,
              "decode_tokens": 0, "decode_context": 0, "emitted": 1},
             {"kind": "decode", "prompt_tokens": 0, "prompt_context": 0,
              "decode_tokens": 2, "decode_context": 23, "emitted": 2}]
    run.traced = {"seconds": 2.0, "steps": steps}
    assert c.reader("serve.step_mfu").read(run) == pytest.approx(
        100 * 4_700_160 / (2.0 * 1e9))
    decode = ('%_paged_attention_call.4 = f32[8,8,1,8]{3,2,1,0} custom-call('
              'f32[8,8,1,8]{3,2,1,0} %q), custom_call_target='
              '"tpu_custom_call"')
    prefill = decode.replace("[8,8,1,8]", "[8,8,24,8]")
    run.trace = xplane.Trace(
        ops={0: [(prefill, 0.0, 1.0), (decode, 1.0, 1.5)]},
        host_spans=[(xplane.WINDOW_SPAN, 0.0, 2.0)])
    # 23 cached tokens x 4 layers: K and V at key-value width, float32
    assert c.reader("serve.paged_attention_roofline").read(run) == \
        pytest.approx(100 * (2 * 23 * 2 * 8 * 4 * 4 / 1e6) / 0.5)
    # nothing that was there has been edited
    for path, data in before.items():
        assert open(path, "rb").read() == data, path


def test_readme_says_how_to_add_each_kind_of_file():
    text = open(os.path.join(ROOT, "chipbench/README.md")).read()
    for word in ("chipbench/configs/", "chipbench/families/",
                 "chipbench/traffic/", "chipbench/generators/",
                 "chipbench/layer_metrics/", "chipbench/runners/",
                 "chipbench/limits/"):
        assert word in text, word
