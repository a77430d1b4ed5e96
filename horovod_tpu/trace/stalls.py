"""Host stalls in the span ring: host work that is not the hot path's
own but holds it up.

Two hooks, installed once per process by :func:`install` (which
`get_recorder()` calls when it creates the recorder, and `hvd.init()`
again), record into the process's one span ring (trace/spans.py), each
span under the span open on the thread that paid for the stall:

* **jax's compile path**, from jax's own monitoring events through one
  ``jax.monitoring.register_event_time_span_listener``: ``jit_trace``
  (a function traced to a jaxpr), ``jit_lower`` (the jaxpr lowered to
  an MLIR module) and ``jit_compile`` (XLA compile or persistent-cache
  fetch: ``fetched`` says which), each with jax's ``fun`` name; a trace
  or lowering inside another is covered by the outer one and not
  recorded (a scalar listener sees each start). jax
  stamps them with ``time.time()`` and calls the listener as they end;
  `spans.wall_span_ended_now` puts them on the ring's clock. A warmed
  jitted call fires none of these events, so the hook costs a warmed
  path nothing.
* **the garbage collector** (``gc.callbacks``): ``gc_pause`` for every
  generation-2 collection and for any collection of at least
  :data:`GC_PAUSE_MIN_S` (``generation``, ``collected``); shorter
  young collections would flood the ring. A generation-2 collection is
  also an ``hvd/gc_pause`` profiler annotation while it runs.

The tracing plane never imports jax: the jax hook is installed once the
process has.
"""
from __future__ import annotations

import gc
import sys
import threading
import time

from . import spans

__all__ = ["JAX_EVENTS", "GC_PAUSE_MIN_S", "install"]

#: jax's monitoring event -> the span it becomes
JAX_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "jit_trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "jit_lower",
    "/jax/core/compile/backend_compile_duration": "jit_compile",
}
#: fired inside ``compile_or_get_cached`` when the persistent cache has
#: the program
_CACHE_HIT = "/jax/compilation_cache/cache_hits"
#: a trace or lowering that runs inside another one on its thread (a
#: jitted function called while another is traced, the helpers jax
#: traces while it lowers) lies inside that span and is not recorded:
#: at GPT-2 XL's size they are some 20,000 spans, nearly all under a
#: millisecond, which would fill the ring before a window opened
_NESTING = frozenset(e for e, n in JAX_EVENTS.items() if n != "jit_compile")

#: a generation-0 or -1 collection shorter than this is not recorded
GC_PAUSE_MIN_S = 0.001

_install_lock = threading.Lock()
_jax_installed = False
#: per thread: .hit, wall stamp of the last cache hit; .open, traces
#: and lowerings under way
_tls = threading.local()


def _on_jax_start(event: str, _value: float, **_kw) -> None:
    # jax marks the start of each timed block with a scalar of its start
    if event in _NESTING:
        _tls.open = getattr(_tls, "open", 0) + 1


def _on_jax_span(event: str, start_time: float, end_time: float,
                 **kw) -> None:
    name = JAX_EVENTS.get(event)
    if name is None:
        return
    if event in _NESTING:
        _tls.open = max(getattr(_tls, "open", 0) - 1, 0)
        if _tls.open:
            return          # inside another trace or lowering: covered
    rec = spans._recorder
    if rec is None:
        return
    attrs = {"fun": str(kw.get("fun_name", ""))}
    if name == "jit_compile":
        hit = getattr(_tls, "hit", None)
        attrs["fetched"] = hit is not None and start_time <= hit <= end_time
    t0, t1 = spans.wall_span_ended_now(start_time, end_time)
    rec.record_local(name, t0, t1, parent=rec.current(), **attrs)


def _on_jax_event(event: str, **_kw) -> None:
    if event == _CACHE_HIT:
        _tls.hit = time.time()


class _Collection:
    """The collection under way (collections never overlap: the
    interpreter runs one at a time and none inside a callback)."""
    t0 = 0.0
    annotation = None


def _on_gc(phase: str, info: dict) -> None:
    if phase == "start":
        _Collection.annotation = None
        if info["generation"] == 2:
            ann = spans._annotation()
            if ann is not None:
                _Collection.annotation = ann("hvd/gc_pause")
                _Collection.annotation.__enter__()
        _Collection.t0 = time.monotonic()
        return
    t1 = time.monotonic()
    if _Collection.annotation is not None:
        _Collection.annotation.__exit__(None, None, None)
        _Collection.annotation = None
    gen, t0 = info["generation"], _Collection.t0
    rec = spans._recorder
    if rec is not None and (gen == 2 or t1 - t0 >= GC_PAUSE_MIN_S):
        # the collection may have begun inside the recorder's own lock
        rec.record_reentrant("gc_pause", t0, t1, parent=rec.current(),
                             generation=gen, collected=info["collected"])


def install() -> None:
    """Install both hooks, each once per process; the jax hook only once
    the process has imported jax (call again after that: `hvd.init`
    does)."""
    global _jax_installed
    with _install_lock:
        if _on_gc not in gc.callbacks:
            gc.callbacks.append(_on_gc)
        if _jax_installed:
            return
        monitoring = getattr(sys.modules.get("jax"), "monitoring", None)
        if monitoring is None:      # no jax yet, or a partial import
            return
        monitoring.register_scalar_listener(_on_jax_start)
        monitoring.register_event_time_span_listener(_on_jax_span)
        monitoring.register_event_listener(_on_jax_event)
        _jax_installed = True
