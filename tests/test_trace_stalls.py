"""Host stalls in the span ring (horovod_tpu/trace/stalls.py): jax's
compile path, long garbage collections, and the package's own import
and `hvd.init()` (CPU)."""
import gc
import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.trace import spans as spans_mod
from horovod_tpu.trace import stalls
from horovod_tpu.trace.spans import (SPAN_LEGS, SpanRecorder, get_recorder,
                                     wall_span_ended_now)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
STALLS = ("jit_trace", "jit_lower", "jit_compile", "gc_pause")


@pytest.fixture
def rec():
    """A recorder of the test's own in the global slot (the hooks write
    to whichever recorder is there), so no other test's spans are read."""
    get_recorder()                      # the hooks are installed
    stalls.install()                    # jax is imported: both hooks
    mine, old = SpanRecorder(), spans_mod._recorder
    spans_mod._recorder = mine
    try:
        yield mine
    finally:
        spans_mod._recorder = old


def _named(rec, names=STALLS):
    return [s for s in rec.between(float("-inf"), float("inf"))
            if s.name in names]


def test_compile_path_spans_carry_fun_and_parent(rec):
    """A first call inside an open span: its trace, lowering and compile
    are three spans under it, named by jax's name for the function, and
    lie inside it."""
    def stall_probe_first(x):
        return jnp.tanh(x) * 3.0

    f = jax.jit(stall_probe_first)
    with rec.span("exec_dispatch") as dispatch:
        f(jnp.ones(5)).block_until_ready()
    (outer,) = [s for s in rec.between(0.0, float("inf"))
                if s.name == "exec_dispatch"]
    mine = [s for s in _named(rec)
            if "stall_probe_first" in s.extra.get("fun", "")]
    assert sorted(s.name for s in mine) == ["jit_compile", "jit_lower",
                                            "jit_trace"]
    for s in mine:
        assert s.parent == dispatch.id
        assert outer.t0 <= s.t0 <= s.t1 <= outer.t1
    (compile_,) = [s for s in mine if s.name == "jit_compile"]
    assert compile_.extra["fetched"] is False


def test_a_numpy_argument_retraces_under_the_open_step_without_a_compile(rec):
    """A warmed function handed a numpy array is traced again (jax's
    `jaxpr_trace_duration`) and compiles nothing: the retrace lands
    under the `exec_dispatch` of the `exec_step` that paid for it."""
    def stall_probe_retrace(x):
        return x * 2.0 + 1.0

    f = jax.jit(stall_probe_retrace)
    f(jnp.ones(4, jnp.float32)).block_until_ready()
    t0 = rec.now()
    with rec.span("exec_step", kind="decode", rows=4) as step:
        with rec.span("exec_dispatch") as dispatch:
            f(np.ones(4, np.float32)).block_until_ready()
    got = [s for s in rec.between(t0, float("inf")) if s.name in STALLS
           and s.name != "gc_pause"]
    assert [s.name for s in got] == ["jit_trace"]
    assert got[0].extra["fun"] == "stall_probe_retrace"
    assert got[0].parent == dispatch.id
    (d,) = [s for s in rec.between(t0, float("inf"))
            if s.name == "exec_dispatch"]
    assert d.parent == step.id


def test_a_trace_inside_another_is_covered_by_it_and_not_recorded(rec):
    """A jitted function traced while another is traced (and the helpers
    jax traces while it lowers) lie inside the outer span: one span per
    outermost trace. A trace that fails leaves nothing open, so the
    next one is recorded."""
    @jax.jit
    def stall_probe_inner(x):
        return jnp.cos(x) * 2.0

    def stall_probe_outer(x):
        return stall_probe_inner(x) + 1.0

    t0 = rec.now()
    jax.jit(stall_probe_outer)(jnp.ones(7)).block_until_ready()
    traces = [s for s in rec.between(t0, float("inf"))
              if s.name == "jit_trace"]
    assert "stall_probe_outer" in [s.extra["fun"] for s in traces]
    assert not [s for s in traces if s.extra["fun"] in (
        "stall_probe_inner", "cos", "multiply")]

    def stall_probe_bad(x):
        return x @ jnp.ones((3, 3))            # (7,) @ (3, 3): refused
    with pytest.raises(TypeError):
        jax.jit(stall_probe_bad)(jnp.ones(7))
    t1 = rec.now()
    jax.jit(lambda x: x - 1.0)(jnp.ones(9)).block_until_ready()
    assert [s for s in rec.between(t1, float("inf"))
            if s.name == "jit_trace"]


def test_a_hundred_warmed_calls_record_nothing(rec):
    f = jax.jit(lambda x: jnp.sin(x) + x)
    x = jnp.ones(16)
    f(x).block_until_ready()
    t0 = rec.now()
    for _ in range(100):
        y = f(x)
    y.block_until_ready()
    assert not [s for s in rec.between(t0, float("inf"))
                if s.name.startswith("jit_")]


def test_a_cache_hit_inside_a_compile_says_fetched(rec):
    """jax's `cache_hits` event fired inside a compile's time span marks
    that compile fetched; one outside it does not."""
    import time
    from jax import monitoring
    event = "/jax/core/compile/backend_compile_duration"
    t = time.time()
    monitoring.record_event("/jax/compilation_cache/cache_hits")
    monitoring.record_event_time_span(event, t - 0.5, t + 0.01,
                                      fun_name="jit(hit)")
    monitoring.record_event_time_span(event, t + 0.02, t + 0.03,
                                      fun_name="jit(miss)")
    now = rec.now()
    got = {s.extra["fun"]: s for s in _named(rec, ("jit_compile",))}
    assert got["jit(hit)"].extra["fetched"] is True
    assert got["jit(miss)"].extra["fetched"] is False
    # on the ring's clock: each ends as its event reaches the listener
    # and lasts what jax's wall stamps say
    for fun, length in (("jit(hit)", 0.51), ("jit(miss)", 0.01)):
        s = got[fun]
        assert s.t1 - s.t0 == pytest.approx(length)
        assert now - 0.5 < s.t1 <= now


def test_other_jax_events_are_not_spans(rec):
    from jax import monitoring
    monitoring.record_event_time_span("/jax/something/else", 1.0, 2.0,
                                      fun_name="x")
    assert not _named(rec, set(SPAN_LEGS))


def test_a_full_collection_is_a_gc_pause_and_a_short_young_one_is_not(rec):
    gc.collect(2)
    full = [s for s in _named(rec, ("gc_pause",))
            if s.extra["generation"] == 2]
    assert full and all(s.extra["collected"] >= 0 and s.t1 >= s.t0
                        for s in full)
    t0 = rec.now()
    gc.collect(0)               # the young generation is empty: fast
    young = [s for s in rec.between(t0, float("inf"))
             if s.name == "gc_pause"]
    assert all(s.t1 - s.t0 >= stalls.GC_PAUSE_MIN_S for s in young)


def test_a_collection_under_an_open_span_is_its_child(rec):
    with rec.span("exec_step", kind="prefill") as step:
        gc.collect(2)
    (pause,) = [s for s in _named(rec, ("gc_pause",))
                if s.parent == step.id and s.extra["generation"] == 2]
    (outer,) = [s for s in rec.between(0.0, float("inf"))
                if s.name == "exec_step"]
    assert pause.parent == step.id
    assert outer.t0 <= pause.t0 <= pause.t1 <= outer.t1


def test_a_full_collection_is_a_profiler_annotation(rec, monkeypatch):
    opened = []

    class Ann:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            opened.append(("enter", self.name))

        def __exit__(self, *exc):
            opened.append(("exit", self.name))
    monkeypatch.setattr(spans_mod, "_annotation", lambda: Ann)
    gc.collect(2)
    assert ("enter", "hvd/gc_pause") in opened
    assert opened.count(("enter", "hvd/gc_pause")) == \
        opened.count(("exit", "hvd/gc_pause"))


def test_a_collection_inside_the_recorders_lock_waits_for_it(rec):
    """A `gc.callbacks` hook can run while its own thread holds the
    recorder's lock (a collection set off by `between`'s list): the span
    waits lock-free and joins the ring with the next call that takes
    the lock."""
    with rec._lock:
        rec.record_reentrant("gc_pause", 1.0, 2.0, generation=2,
                             collected=0)
        assert not rec._ring
    (got,) = rec.between(0.0, 3.0)
    assert got.name == "gc_pause" and got.extra["generation"] == 2
    # with the lock free it goes straight in
    rec.record_reentrant("gc_pause", 4.0, 5.0, generation=0, collected=0)
    assert [s.t0 for s in rec._ring if s.t1 <= 6.0] == [1.0, 4.0]


def test_a_collection_that_starts_inside_the_recorders_lock_is_kept(rec):
    """The hook itself, not only `record_reentrant`: a full collection
    run by a thread that holds the recorder's lock finishes (a blocking
    record would wait on that thread forever) and lands in the ring."""
    import threading
    done = threading.Event()

    def collect_inside():
        with rec._lock:
            gc.collect(2)
        done.set()
    t = threading.Thread(target=collect_inside, daemon=True)
    t.start()
    t.join(timeout=60)
    assert done.is_set()
    assert any(s.name == "gc_pause" and s.extra["generation"] == 2
               for s in rec.between(float("-inf"), float("inf")))


def test_installing_twice_registers_one_of_each_hook():
    from jax._src import monitoring
    stalls.install()
    stalls.install()
    assert gc.callbacks.count(stalls._on_gc) == 1
    assert monitoring.get_event_time_span_listeners().count(
        stalls._on_jax_span) == 1
    assert monitoring.get_scalar_listeners().count(
        stalls._on_jax_start) == 1
    assert monitoring.get_event_listeners().count(stalls._on_jax_event) == 1


def test_a_wall_span_that_just_ended_ends_now_on_the_ring_clock():
    import time
    before = time.monotonic()
    t0, t1 = wall_span_ended_now(1000.0, 1002.5)
    assert before <= t1 <= time.monotonic()
    assert t1 - t0 == pytest.approx(2.5)


def test_stall_spans_are_declared_local_and_outside_the_hot_path_names():
    for name in STALLS + ("hvd_import", "hvd_init"):
        assert SPAN_LEGS[name] is None
        assert not name.startswith(("sched_", "exec_"))


def test_a_fresh_process_records_its_import_init_and_fetches(tmp_path):
    """In a process of its own: `import horovod_tpu` and `hvd.init()`
    are in the ring, once each; a program compiled, then dropped from
    memory and asked for again comes from the persistent cache."""
    script = textwrap.dedent(f"""
        import json
        import jax, jax.numpy as jnp
        import horovod_tpu as hvd
        from horovod_tpu.trace import get_recorder
        jax.config.update("jax_compilation_cache_dir", {str(tmp_path)!r})
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
        hvd.init()
        hvd.init()
        def probe(x):
            return jnp.cos(x) + 1
        jax.jit(probe)(jnp.ones(3)).block_until_ready()
        jax.clear_caches()
        jax.jit(probe)(jnp.ones(3)).block_until_ready()
        print(json.dumps([[s.name, s.t1 - s.t0, s.extra.get("fun"),
                           s.extra.get("fetched")]
                          for s in get_recorder().between(0, 1e18)]))
    """)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=ROOT + os.pathsep + os.environ.get("PYTHONPATH", ""))
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    got = json.loads(out.stdout.strip().splitlines()[-1])
    names = [g[0] for g in got]
    assert names.count("hvd_import") == 1 and names.count("hvd_init") == 1
    assert names.index("hvd_import") < names.index("hvd_init")
    assert all(g[1] > 0 for g in got if g[0] in ("hvd_import", "hvd_init"))
    probes = [g[3] for g in got
              if g[0] == "jit_compile" and g[2] == "jit(probe)"]
    assert probes == [False, True]


def test_collections_on_many_threads_nest_under_their_own_threads_span(rec):
    """Four threads open spans, collect and read the ring at once, with
    the interpreter switching threads every 10 us: nothing deadlocks
    (a collection can start inside the recorder's lock), and every
    `gc_pause` lies inside the span its own thread had open."""
    import threading
    ids = {}

    def work(k):
        for i in range(40):
            with rec.span("exec_step", kind="decode", rows=k) as sp:
                ids[sp.id] = k
                gc.collect(2 if i % 8 == 0 else 0)
                rec.between(0.0, float("inf"))

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=work, args=(k,))
                   for k in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(old)
    held = rec.between(float("-inf"), float("inf"))
    assert not rec._parked
    steps = {s.span: s for s in held if s.name == "exec_step"}
    assert len(steps) == 160
    pauses = [s for s in held if s.name == "gc_pause"
              and s.parent in steps]
    assert pauses
    for p in pauses:
        up = steps[p.parent]
        assert up.t0 <= p.t0 <= p.t1 <= up.t1
