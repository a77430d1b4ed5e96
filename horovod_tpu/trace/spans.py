"""The span registry + the per-process span recorder.

**The one constants table** for the tracing plane: every span name a
process may record and every leg label the router's
``hvd_trace_leg_ms{leg,pool}`` histograms attribute to is declared
HERE, in :data:`SPAN_LEGS` — and machine-checked against the
docs/tracing.md registry tables by the ``trace-registry`` pass of the
static-analysis plane (``python tools/check.py --pass trace-registry``,
docs/analysis.md), in both directions, exactly like the knob and
metric registries. A span name recorded anywhere in the codebase that
is not declared here is a finding; so is a declared name without a
docs row, and a docs row without a declaration.

The recorder has two jobs (docs/tracing.md):

* **the local flight recorder, always on** — every process records
  what its serve hot path does (scheduler iterations, executor steps,
  every request's queue wait / prefill / decode with one stamp per
  token) into one bounded ring, on the MONOTONIC clock the scheduler's
  own stamps use. Nothing is shipped and nothing drains it: a reader
  in the process (the benchmark's per-layer metrics, a debugger) asks
  for the spans of a time range (:meth:`SpanRecorder.between`). While
  a span opened by :meth:`SpanRecorder.span` is open it is also a
  ``jax.profiler.TraceAnnotation("hvd/<name>")``, so a profile shows
  the program's spans beside the device ops.
* **the worker-side half of fleet span collection** — for a request
  that carries a router-minted context, the same spans are also filed
  under its trace id; the wire layer piggybacks them on the next reply
  frame that trace produces (serve/worker.py) — no new sockets, no
  background flusher. The wire's clock is the WALL clock
  (``time.time()``), which the router aligns at merge (trace/clock.py):
  the wall base is added where a span leaves the process
  (:meth:`Span.to_wire` from :meth:`SpanRecorder.drain`), and nowhere
  else.
"""
from __future__ import annotations

import itertools
import sys
import threading
import time
from collections import OrderedDict, deque
from typing import Dict, List, Optional

__all__ = ["SPAN_LEGS", "SPAN_NAMES", "LEGS", "Span", "SpanRecorder",
           "get_recorder", "configure_recorder", "wall_base", "to_wall",
           "wall_span_ended_now"]


def wall_base() -> float:
    """Seconds to add to a ``time.monotonic()`` stamp to put it on the
    wall clock, now. THE one place the two clocks meet: spans are
    stamped monotonic (a clock that cannot step) and only become wall
    time when they leave the process."""
    return time.time() - time.monotonic()


def to_wall(t_mono: float) -> float:
    """A monotonic stamp on the wall clock (for a caller that hands a
    stamp to a wall-clock API, e.g. ``TraceAssembler.span``)."""
    return t_mono + wall_base()


def wall_span_ended_now(t0_wall: float, t1_wall: float) -> tuple:
    """``(t0, t1)`` on the monotonic clock of a span stamped on the wall
    clock that has just ended (jax's compile events reach a listener as
    they end): it ends now and lasts what the wall clock says. No base
    is sampled, so a thread switched out between two clock reads cannot
    shift it out of the span it lies in."""
    t1 = time.monotonic()
    return t1 - (t1_wall - t0_wall), t1

#: span name -> the latency leg it attributes to (None = overhead /
#: bookkeeping spans that are merged into the timeline but excluded
#: from the leg decomposition). THE declaration table the
#: trace-registry analysis pass checks against docs/tracing.md.
SPAN_LEGS: "OrderedDict[str, Optional[str]]" = OrderedDict([
    ("request",         None),        # root: admission -> resolution
    ("dispatch",        "queue"),     # router pick + enqueue -> ack
    ("queue_wait",      "queue"),     # worker admission -> prefill start
    ("prefill",         "prefill"),   # wave's prefill start -> first token
    ("park",            "migrate"),   # parked (hold_kv) -> migrate pack
    ("migrate_push",    "migrate"),   # pack + push + install ack (sender)
    ("migrate_install", "migrate"),   # arrival crc -> device install
    ("decode",          "decode"),    # first token -> retirement
    ("failover",        None),        # eject -> victims re-dispatched
    ("re_prefill",      None),        # a migration leg fell back
    ("weight_fence",    None),        # hot-swap adoption fence
    ("kvtier_promote",  None),        # ladder -> HBM verified install
    ("kvtier_pull",     None),        # cross-replica run pull (router)
    # -- local, never shipped: the serve hot path's own work ---------------
    ("sched_iteration", None),        # one ContinuousBatcher.step
    ("sched_retire",    None),        # resolve + free finished rows
    ("sched_admit",     None),        # queue pop + block planning
    ("sched_prefill",   None),        # one prefill wave
    ("sched_decode",    None),        # one decode (or speculative) wave
    ("exec_step",       None),        # one ShardedExecutor.step
    ("exec_upload",     None),        # host -> device input arrays
    ("exec_dispatch",   None),        # the jitted call returning
    ("exec_readback",   None),        # device -> host result (the fence)
    # -- local, never shipped: host work that stalls whatever is open ------
    ("jit_trace",       None),        # jax traced a function to a jaxpr
    ("jit_lower",       None),        # jaxpr -> MLIR module
    ("jit_compile",     None),        # XLA compile or persistent-cache fetch
    ("gc_pause",        None),        # a gen-2 or >= 1 ms collection
    ("hvd_import",      None),        # import horovod_tpu
    ("hvd_init",        None),        # hvd.init()
])

#: every declared span name, in declaration order
SPAN_NAMES = tuple(SPAN_LEGS)

#: every leg label ``hvd_trace_leg_ms`` may carry, in timeline order
LEGS = ("queue", "prefill", "migrate", "decode")


#: the local ring holds this many times ``HOROVOD_TRACE_RING`` spans
#: (the knob bounds what a worker holds for a router that never
#: collects; it scales both): 65,536 at the default, 20 MB when full; a
#: traced run of the benchmark's serve cells holds 10,000-24,000 spans
#: when its readers run, the latent-attention cell's decode of answers
#: to 2,048 tokens about 30,000-40,000, and its readers need the ring
#: unwrapped since the import (PERF.md sections 6 and 7)
RING_FACTOR = 16

#: attributes that stay in the process: `to_wire` leaves them out, so a
#: reply frame never grows by a stamp per token
_LOCAL_ATTRS = frozenset({"token_times"})


class Span:
    """One completed span: ``[t0, t1]`` seconds on the clock of whoever
    made it (a recorder's spans: monotonic; the router's own: wall)
    plus the identity of the process that recorded it. Plain dict on
    the wire (:meth:`to_wire`) — spans ride reply frames as JSON,
    always on the wall clock."""

    __slots__ = ("trace", "span", "parent", "name", "pool", "replica",
                 "gen", "t0", "t1", "extra")

    def __init__(self, trace: str, span: str, parent: Optional[str],
                 name: str, t0: float, t1: float, *,
                 pool: str = "", replica: Optional[int] = None,
                 gen: Optional[int] = None,
                 extra: Optional[dict] = None):
        self.trace = trace
        self.span = span
        self.parent = parent
        self.name = name
        self.t0 = float(t0)
        self.t1 = float(t1)
        self.pool = pool
        self.replica = replica
        self.gen = gen
        self.extra = extra or {}

    @property
    def duration_ms(self) -> float:
        return max(self.t1 - self.t0, 0.0) * 1000.0

    def to_wire(self, base: float = 0.0) -> dict:
        """The span as the wire carries it. ``base`` puts monotonic
        stamps on the wall clock: one :func:`wall_base` shared by the
        spans of a drain, so they still tile exactly."""
        d = {"trace": self.trace, "span": self.span, "name": self.name,
             "t0": self.t0 + base, "t1": self.t1 + base}
        if self.parent is not None:
            d["parent"] = self.parent
        if self.pool:
            d["pool"] = self.pool
        if self.replica is not None:
            d["replica"] = self.replica
        if self.gen is not None:
            d["gen"] = self.gen
        extra = {k: v for k, v in self.extra.items()
                 if k not in _LOCAL_ATTRS}
        if extra:
            d["extra"] = extra
        return d

    @classmethod
    def from_wire(cls, d: dict) -> "Span":
        return cls(str(d.get("trace", "")), str(d.get("span", "")),
                   d.get("parent"), str(d.get("name", "")),
                   float(d.get("t0", 0.0)), float(d.get("t1", 0.0)),
                   pool=str(d.get("pool", "")),
                   replica=d.get("replica"), gen=d.get("gen"),
                   extra=d.get("extra") or {})


def _annotation():
    """``jax.profiler.TraceAnnotation`` where this process has already
    imported jax (a serving process has; a router may not, and the
    tracing plane never imports it for them)."""
    jax = sys.modules.get("jax")
    if jax is None:
        return None
    try:
        return jax.profiler.TraceAnnotation
    except AttributeError:      # a partially imported jax
        return None


class _OpenSpan:
    """The context manager behind :meth:`SpanRecorder.span`."""

    __slots__ = ("_rec", "name", "attrs", "id", "parent", "t0", "_ann")

    def __init__(self, rec: "SpanRecorder", name: str, attrs: dict):
        self._rec = rec
        self.name = name
        self.attrs = attrs
        self._ann = None

    def set(self, **attrs) -> None:
        """Attributes known only once the work is under way."""
        self.attrs.update(attrs)

    def __enter__(self) -> "_OpenSpan":
        rec = self._rec
        stack = rec._stack()
        self.parent = stack[-1].id if stack else None
        self.id = rec._next_id()
        ann = _annotation()
        if ann is not None:
            self._ann = ann("hvd/" + self.name)
            self._ann.__enter__()
        self.t0 = time.monotonic()
        # on the stack only inside [t0, t1]: whatever is recorded under
        # it meanwhile (a collection, a compile) lies inside it
        stack.append(self)
        return self

    def __exit__(self, *exc) -> None:
        rec = self._rec
        rec._stack().pop()
        t1 = time.monotonic()
        if self._ann is not None:
            self._ann.__exit__(*exc)
        rec._keep(Span("", self.id, self.parent, self.name, self.t0, t1,
                       pool=rec.pool, replica=rec.replica, gen=rec.gen,
                       extra=self.attrs))


class SpanRecorder:
    """Bounded per-process span buffer: the local ring, plus the spans
    of router-traced requests keyed by trace id.

    Everything is recorded through one path, on one clock: stamps are
    ``time.monotonic()`` (:meth:`now`), and the wall clock enters only
    in :meth:`drain`.

    **The ring** holds the last ``ring`` spans this process recorded
    (:meth:`span`, :meth:`record_local`, :meth:`record_process`,
    :meth:`record_reentrant`),
    oldest evicted first (:attr:`evicted` counts them). It is read,
    never drained: :meth:`between`, :meth:`oldest`. ``ring`` is
    :data:`RING_FACTOR` times ``capacity`` unless given.

    **By trace**: lock-cheap by design — one lock, O(1) append, O(1)
    drain (the trace key pops whole). ``capacity`` is a TOTAL span
    count (``HOROVOD_TRACE_RING``); when it overflows, the oldest
    trace's spans are evicted whole (and counted), so a router that
    never collects cannot grow worker memory.

    Process-level spans (``weight_fence`` — not tied to any request)
    are drained onto the NEXT reply of any trace, so they reach the
    router's merged timeline without a dedicated channel. The
    scheduler's and executor's spans never ship.
    """

    def __init__(self, capacity: int = 4096, *,
                 ring: Optional[int] = None, pool: str = "",
                 replica: Optional[int] = None,
                 gen: Optional[int] = None):
        self.capacity = max(int(capacity), 1)
        self.ring = max(int(ring), 1) if ring is not None \
            else RING_FACTOR * self.capacity
        self.pool = pool
        self.replica = replica
        self.gen = gen
        self.dropped = 0
        #: spans the ring has evicted (a reader's "did it wrap?")
        self.evicted = 0
        self._total = 0
        self._lock = threading.Lock()
        self._by_trace: "OrderedDict[str, List[Span]]" = OrderedDict()
        self._ring: "deque[Span]" = deque()
        #: spans recorded where this recorder's lock may be held by the
        #: same thread (`record_reentrant`), until a holder takes them in
        self._parked: "deque[Span]" = deque(maxlen=self.ring)
        #: process-level spans waiting for the next drain to ship them
        self._unshipped: "deque[Span]" = deque(maxlen=64)
        self._ids = itertools.count(1)
        self._tls = threading.local()

    def configure(self, *, pool: Optional[str] = None,
                  replica: Optional[int] = None,
                  gen: Optional[int] = None) -> None:
        """Stamp the recording process's identity (pool/replica/gen)
        onto every subsequent span — the merged trace's pid row."""
        if pool is not None:
            self.pool = pool
        if replica is not None:
            self.replica = replica
        if gen is not None:
            self.gen = gen

    def now(self) -> float:
        """The recorder's clock, ``time.monotonic()``: the clock of
        ``submitted_at``, ``t_first`` and every deadline, so a stamp the
        scheduler already took is a span endpoint as it stands."""
        return time.monotonic()

    # -- the local ring -----------------------------------------------------
    def _stack(self) -> list:
        try:
            return self._tls.stack
        except AttributeError:
            self._tls.stack = []
            return self._tls.stack

    def _next_id(self) -> str:
        return format(next(self._ids), "x")

    def current(self) -> Optional[str]:
        """Id of the innermost span open on this thread, None if none."""
        stack = self._stack()
        return stack[-1].id if stack else None

    def _push(self, sp: Span) -> None:
        """(lock held) one span into the ring, the oldest out if full."""
        if len(self._ring) >= self.ring:
            self._ring.popleft()
            self.evicted += 1
        self._ring.append(sp)

    def _settle(self) -> None:
        """(lock held) parked spans join the ring."""
        while self._parked:
            self._push(self._parked.popleft())

    def _keep(self, sp: Span) -> None:
        with self._lock:
            self._settle()
            self._push(sp)

    def span(self, name: str, **attrs) -> _OpenSpan:
        """``with rec.span("sched_admit", depth=3) as sp:`` — a span
        around work on THIS thread. Records name, start, end, its own
        id and the id of the span open around it on the thread (a
        per-thread stack) into the ring; ``sp.set(rows=8)`` adds what
        is known only later. While open it is also a profiler
        ``TraceAnnotation("hvd/<name>")``. Never shipped."""
        return _OpenSpan(self, name, attrs)

    def record_local(self, name: str, t0: float, t1: float, *,
                     trace: str = "", span: Optional[str] = None,
                     parent: Optional[str] = None, ship=None,
                     **attrs) -> Span:
        """Record one completed span with stamps already taken on
        :meth:`now`'s clock — a request's ``queue_wait``, ``decode``...
        Always lands in the ring. ``ship`` (the request's router
        context, a TraceContext or its wire dict) additionally files
        it under that trace for the next :meth:`drain`, as a child of
        the carried context; without it ``trace``/``span``/``parent``
        give the local identity."""
        if ship is not None:
            from .context import TraceContext
            ctx = ship if isinstance(ship, TraceContext) \
                else TraceContext.from_wire(ship)
            if ctx is not None:
                trace, parent = ctx.trace_id, ctx.span_id
                span = ctx.child().span_id
            ship = ctx
        sp = Span(trace, span or self._next_id(), parent, name, t0, t1,
                  pool=self.pool, replica=self.replica, gen=self.gen,
                  extra=attrs or None)
        self._keep(sp)
        if ship is not None:
            self._file(sp)
        return sp

    def record_reentrant(self, name: str, t0: float, t1: float, *,
                         parent: Optional[str] = None, **attrs) -> None:
        """:meth:`record_local` for a caller that may have interrupted
        this recorder inside its own lock: a ``gc.callbacks`` hook runs
        wherever a collection starts. Where the lock is taken the span
        waits, lock-free, for the next call that holds it."""
        sp = Span("", self._next_id(), parent, name, t0, t1,
                  pool=self.pool, replica=self.replica, gen=self.gen,
                  extra=attrs or None)
        if self._lock.acquire(blocking=False):
            try:
                self._settle()
                self._push(sp)
            finally:
                self._lock.release()
        else:
            self._parked.append(sp)

    def between(self, lo: float, hi: float) -> List[Span]:
        """The ring's spans that overlap ``[lo, hi]`` (monotonic
        seconds), in the order they ended. Drains nothing."""
        with self._lock:
            self._settle()
            return [s for s in self._ring if s.t1 >= lo and s.t0 <= hi]

    def oldest(self) -> Optional[float]:
        """End stamp of the oldest span still in the ring (spans enter
        the ring as they end), None for an empty ring. With
        :attr:`evicted` > 0 a range that starts before it has lost
        spans; with nothing evicted it has not."""
        with self._lock:
            self._settle()
            return self._ring[0].t1 if self._ring else None

    # -- by trace: what the wire ships --------------------------------------
    def _file(self, sp: Span) -> None:
        with self._lock:
            self._by_trace.setdefault(sp.trace, []).append(sp)
            self._total += 1
            while self._total > self.capacity and self._by_trace:
                _tid, evicted = self._by_trace.popitem(last=False)
                self._total -= len(evicted)
                self.dropped += len(evicted)

    def record_process(self, name: str, t0: float, t1: float,
                       **extra) -> Span:
        """Record a process-level span (no trace; stamps on
        :meth:`now`'s clock): kept in the ring and piggybacked on the
        next drain of ANY trace."""
        sp = Span("", self._next_id(), None, name, t0, t1,
                  pool=self.pool, replica=self.replica, gen=self.gen,
                  extra=extra or None)
        self._keep(sp)
        with self._lock:
            self._unshipped.append(sp)
        return sp

    def drain(self, trace_id: Optional[str]) -> List[dict]:
        """Pop ``trace_id``'s spans (plus any pending process-level
        spans) as wire dicts — called at reply time; here the spans
        leave the process and go onto the wall clock. Empty list when
        the trace recorded nothing here."""
        with self._lock:
            spans = self._by_trace.pop(trace_id, []) if trace_id \
                else []
            self._total -= len(spans)
            procs = list(self._unshipped)
            self._unshipped.clear()
        base = wall_base()
        return [s.to_wire(base) for s in spans + procs]

    def pending(self) -> int:
        with self._lock:
            return self._total


_recorder: Optional[SpanRecorder] = None
_recorder_lock = threading.Lock()


def get_recorder() -> SpanRecorder:
    """The process-global recorder (lazily created with the configured
    ring capacity — ``HOROVOD_TRACE_RING``). Creating it installs the
    host-stall hooks (trace/stalls.py)."""
    global _recorder
    if _recorder is None:
        with _recorder_lock:
            if _recorder is None:
                cap = 4096
                try:
                    from ..core.config import Config
                    cap = int(Config.from_env().trace_ring)
                except Exception:  # noqa: BLE001 — a malformed env
                    pass           # must not break the recording path
                _recorder = SpanRecorder(cap)
                from . import stalls
                stalls.install()
    return _recorder


def configure_recorder(*, pool: Optional[str] = None,
                       replica: Optional[int] = None,
                       gen: Optional[int] = None) -> SpanRecorder:
    """Stamp the process identity on the global recorder (worker
    startup calls this once its rid/gen/pool are known)."""
    rec = get_recorder()
    rec.configure(pool=pool, replica=replica, gen=gen)
    return rec
