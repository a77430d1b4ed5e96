"""Admission-controlled request queue: backpressure instead of collapse.

The serving front door. Three properties the ROADMAP's "heavy traffic
from millions of users" target demands of it:

* **Bounded**: at most HOROVOD_SERVE_MAX_QUEUE requests wait; past that
  the queue *sheds load* — `submit` raises a structured `Rejected`
  carrying a `retry_after_ms` estimate (depth x observed per-request
  service time / batch width) so clients back off instead of piling on.
  Shedding is an accounting event (`shed_count`), never a crash.
* **Deadlined**: every request carries an absolute deadline
  (HOROVOD_SERVE_DEADLINE_MS default). The batcher resolves expired
  requests with status "expired" and whatever tokens were produced —
  a late answer is a wasted decode slot.
* **Handle-based**: `submit` returns a `ServeHandle` the caller waits
  on; resolution happens on the batcher thread (serve/batcher.py), the
  same one-writer discipline the engine uses for collective handles.
"""
from __future__ import annotations

import itertools
import threading
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Callable, List, Optional, Sequence, Tuple

from ..chaos import inject as _chaos
from ..obs import metrics as obs_metrics
from ..trace.spans import get_recorder as _trace_recorder


class Rejected(Exception):
    """Structured load-shed rejection (the HTTP 429 analog).

    `retry_after_ms` is the backoff hint (None when retrying cannot
    help, e.g. a prompt that can never fit the configured buckets).
    """

    def __init__(self, reason: str, retry_after_ms: Optional[float] = None):
        self.reason = reason
        self.retry_after_ms = retry_after_ms
        hint = "" if retry_after_ms is None \
            else f" (retry after {retry_after_ms:.0f} ms)"
        super().__init__(f"request rejected: {reason}{hint}")


class AdmitDropped(Rejected):
    """A chaos ``serve.admit`` drop: the request was lost at the queue
    door, as if the connection died mid-admission. A Rejected subclass
    so a standalone replica still answers it structurally (429 +
    retry-after — never a silent loss); the fleet router additionally
    distinguishes it to retry the request on another replica
    (serve/fleet.py)."""


@dataclass
class ServeRequest:
    rid: int
    prompt: List[int]
    max_new_tokens: int
    #: absolute monotonic deadline (seconds)
    deadline: float
    submitted_at: float
    handle: "ServeHandle" = field(repr=False, default=None)
    #: on-device sampling controls (serve/executor.py): temperature 0
    #: is greedy (the default — argmax semantics, deterministic, and
    #: bit-identical across kernels/configs WITHIN a version; exact
    #: float values may shift across code versions as program shapes
    #: change); top_p restricts to the smallest nucleus covering that
    #: probability mass; seed makes the request's token stream
    #: deterministic independent of batch placement and restarts
    temperature: float = 0.0
    top_p: float = 1.0
    seed: int = 0
    #: disaggregated prefill (serve/disagg.py): when True the batcher
    #: PARKS the sequence's KV (row + blocks stay allocated) at clean
    #: retirement instead of freeing it, so the endpoint can migrate
    #: the blocks to a decode replica (serve/kv_migrate.py). Parked
    #: rows are released by release_parked() or reaped past deadline.
    hold_kv: bool = False
    #: distributed-tracing context (horovod_tpu/trace): the wire-form
    #: ``{"trace", "span", "parent"}`` dict the router minted at
    #: admission, or None (untraced — the back-compat default). The
    #: batcher records queue_wait/prefill/decode spans against it and
    #: migration packets carry it forward (docs/tracing.md).
    trace: Optional[dict] = None

    def expired(self, now: Optional[float] = None) -> bool:
        return (now if now is not None else time.monotonic()) > self.deadline

    def trace_ids(self) -> Tuple[str, str]:
        """``(trace id, id of the request's root span)`` its spans are
        recorded under: the router's, where it minted a context; else
        local ones made from ``rid`` (every request is recorded in the
        process's span ring, traced by a router or not)."""
        t = self.trace
        if isinstance(t, dict) and t.get("trace") and t.get("span"):
            return str(t["trace"]), str(t["span"])
        return f"rid{self.rid}", f"rid{self.rid}"


class ServeHandle:
    """Caller-side completion handle; resolved exactly once by the
    batcher. `status` is "pending" | "ok" | "expired" | "error".

    ``on_resolve`` (optional, set via ``submit``) is invoked exactly
    once with the handle AFTER resolution — the fleet router's
    completion hook. It runs on the resolving thread and must never be
    called while a queue/batcher lock is held (lock-order discipline
    with the router's own lock).

    **Stamps** (``time.monotonic()`` seconds, the clock of deadlines;
    the scheduler writes them where the thing happens, a caller reads
    them once the handle is done — docs/serving.md): ``t_submit``;
    ``t_admit`` (its admission wave starts prefill — the queue wait
    ends); ``t_first`` (the first token exists: ``t_first - t_submit``
    is the time to first token); ``token_times``, one stamp per entry
    of ``tokens`` (the end of the step that emitted it, so
    ``token_times[0] == t_first`` and the differences are the token
    gaps); ``t_done`` (resolution). What never happened stays None /
    empty (a request that expired in the queue has no ``t_admit``)."""

    def __init__(self, rid: int,
                 on_resolve: Optional[Callable[["ServeHandle"],
                                               None]] = None,
                 request: Optional[ServeRequest] = None):
        self.rid = rid
        self.status = "pending"
        self.tokens: List[int] = []
        self.error: Optional[str] = None
        self.latency_ms: Optional[float] = None
        self.on_resolve = on_resolve
        self.t_submit: Optional[float] = (
            request.submitted_at if request is not None else None)
        self.t_admit: Optional[float] = None
        self.t_first: Optional[float] = None
        self.token_times: List[float] = []
        self.t_done: Optional[float] = None
        #: the scheduler-side request this handle answers, until it
        #: resolves (None for a router's outer handle): what `_resolve`
        #: records the ``request`` span from
        self._request = request
        self._event = threading.Event()
        self._rlock = threading.Lock()

    def done(self) -> bool:
        return self._event.is_set()

    def wait(self, timeout: Optional[float] = None) -> bool:
        return self._event.wait(timeout)

    def _resolve(self, tokens: Sequence[int], status: str,
                 latency_ms: Optional[float] = None,
                 error: Optional[str] = None) -> None:
        with self._rlock:   # one-shot; late expiry races are no-ops
            if self._event.is_set():
                return
            self.tokens = list(tokens)
            self.status = status
            self.error = error
            self.latency_ms = latency_ms
            self.t_done = time.monotonic()
            req, self._request = self._request, None
            if req is not None:
                # the request's root span, submit -> resolved, whoever
                # resolved it (the batcher, the queue's expiry paths);
                # in the ring before a waiter can wake and read it
                trace, root = req.trace_ids()
                _trace_recorder().record_local(
                    "request", self.t_submit, self.t_done, trace=trace,
                    span=root, rid=req.rid, status=status)
            self._event.set()
        cb = self.on_resolve
        if cb is not None:
            try:
                cb(self)
            except Exception:  # noqa: BLE001 — a hook must not mask
                pass           # the resolution it observes


class AdmissionQueue:
    """Bounded FIFO with load shedding and service-time-based backoff.

    Thread-safe: HTTP handler threads submit; the batcher thread pops.
    """

    def __init__(self, max_queue: int = 64,
                 default_deadline_ms: float = 30000.0,
                 max_prompt_len: Optional[int] = None,
                 replica_id: Optional[int] = None):
        if max_queue < 1:
            raise ValueError(f"max_queue must be >= 1; got {max_queue}")
        if default_deadline_ms <= 0:
            raise ValueError("default_deadline_ms must be > 0; got "
                             f"{default_deadline_ms}")
        self.max_queue = max_queue
        self.default_deadline_ms = default_deadline_ms
        #: longest admissible prompt (the batcher sets this to its
        #: largest prefill bucket so an unservable prompt is rejected at
        #: the door, not discovered holding a decode slot)
        self.max_prompt_len = max_prompt_len
        #: fleet replica this queue fronts (None = standalone): labels
        #: the metric series and addresses chaos serve.admit faults
        self.replica_id = replica_id
        self._dq: "deque[ServeRequest]" = deque()
        self._lock = threading.Lock()
        self._work = threading.Event()
        self._ids = itertools.count()
        self._submits = 0      # serve.admit chaos site counter
        # -- counters: registry-backed (horovod_tpu.obs); the legacy
        # attributes (shed_count & co) are properties over these, so the
        # SERVE timeline row / healthz keep their numbers while /metrics
        # exposes the same series fleet-wide. Standalone queues claim
        # their families fresh (one serving stack per process, and a new
        # queue's views must count from zero); a FLEET replica's queue
        # instead get-or-creates {replica=...}-labeled children, so one
        # replica's restart neither clobbers its siblings nor resets its
        # own fleet-visible counts.
        rl = {} if replica_id is None else {"replica": str(replica_id)}
        R = obs_metrics.get_registry()
        if replica_id is None:
            for fam in ("hvd_serve_admitted_total", "hvd_serve_shed_total",
                        "hvd_serve_completed_total",
                        "hvd_serve_expired_total", "hvd_serve_queue_depth"):
                R.unregister(fam)
        self._m_admitted = R.counter(
            "hvd_serve_admitted_total", "requests admitted to the queue",
            rl or None)
        self._m_shed = R.counter(
            "hvd_serve_shed_total",
            "requests load-shed at admission (queue full / unservable)",
            rl or None)
        self._m_completed = R.counter(
            "hvd_serve_completed_total", "requests retired ok", rl or None)
        self._m_expired = R.counter(
            "hvd_serve_expired_total", "requests expired past deadline",
            rl or None)
        self._m_depth = R.gauge(
            "hvd_serve_queue_depth", "requests waiting for a decode slot",
            rl or None)
        #: EWMA of per-request service time, fed back by the batcher on
        #: retirement; drives the retry_after_ms hint
        self._service_ms_ewma: Optional[float] = None

    # -- back-compat views over the registry counters ------------------------
    shed_count = property(
        lambda self: int(self._m_shed.value),
        lambda self, v: self._m_shed._set(v))
    admitted_count = property(
        lambda self: int(self._m_admitted.value),
        lambda self, v: self._m_admitted._set(v))
    completed_count = property(
        lambda self: int(self._m_completed.value),
        lambda self, v: self._m_completed._set(v))
    expired_count = property(
        lambda self: int(self._m_expired.value),
        lambda self, v: self._m_expired._set(v))

    # -- producer side ------------------------------------------------------
    def submit(self, prompt: Sequence[int], max_new_tokens: int = 16,
               deadline_ms: Optional[float] = None,
               on_resolve: Optional[Callable[[ServeHandle],
                                             None]] = None,
               temperature: float = 0.0, top_p: float = 1.0,
               seed: int = 0, hold_kv: bool = False,
               trace: Optional[dict] = None) -> ServeHandle:
        """Admit a request or raise `Rejected` (load shed / unservable).

        ``temperature`` / ``top_p`` / ``seed`` ride the request into
        the executor's on-device sampler (temperature 0 = greedy, the
        default); validation is fail-fast here at the door.
        ``trace`` is the wire-form tracing context (or None —
        untraced); it rides the request so the batcher can record its
        queue_wait/prefill/decode spans (docs/tracing.md).

        ``on_resolve`` is attached to the handle BEFORE it becomes
        poppable, so a completion can never race past the hook."""
        prompt = [int(t) for t in prompt]
        if max_new_tokens < 1:
            raise ValueError(
                f"max_new_tokens must be >= 1; got {max_new_tokens}")
        temperature = float(temperature)
        top_p = float(top_p)
        seed = int(seed)
        if not (temperature >= 0.0):
            raise ValueError(
                f"temperature must be >= 0 (0 = greedy); got "
                f"{temperature!r}")
        if not (0.0 < top_p <= 1.0):
            raise ValueError(
                f"top_p must be in (0, 1]; got {top_p!r}")
        # chaos serve.admit: the queue-door fault site. Disarmed cost is
        # one attribute read; delay sleeps inside the injector; drop
        # surfaces as AdmitDropped (a structured loss, never a silent
        # one — the fleet router absorbs it by retrying elsewhere).
        if _chaos._INJ is not None:
            with self._lock:
                n = self._submits
                self._submits += 1
            f = _chaos.fire("serve.admit", peer=self.replica_id, step=n)
            if f is not None and f.kind == "drop":
                self._m_shed.inc()
                raise AdmitDropped("chaos: admission dropped",
                                   retry_after_ms=self._retry_after_ms())
        with self._lock:
            if self.max_prompt_len is not None and \
                    (not prompt or len(prompt) > self.max_prompt_len):
                self._m_shed.inc()
                raise Rejected(
                    f"prompt length {len(prompt)} outside servable range "
                    f"[1, {self.max_prompt_len}]", retry_after_ms=None)
            if len(self._dq) >= self.max_queue:
                self._m_shed.inc()
                raise Rejected("queue full",
                               retry_after_ms=self._retry_after_ms_locked())
            now = time.monotonic()
            dl = (deadline_ms if deadline_ms is not None
                  else self.default_deadline_ms)
            rid = next(self._ids)
            req = ServeRequest(rid=rid, prompt=prompt,
                               max_new_tokens=max_new_tokens,
                               deadline=now + dl / 1000.0,
                               submitted_at=now,
                               temperature=temperature, top_p=top_p,
                               seed=seed, hold_kv=bool(hold_kv),
                               trace=trace)
            req.handle = ServeHandle(rid, on_resolve=on_resolve,
                                     request=req)
            self._dq.append(req)
            self._m_admitted.inc()
            self._m_depth.set(len(self._dq))
            self._work.set()
            return req.handle

    def _retry_after_ms_locked(self) -> float:
        # depth x EWMA service time is the expected drain time of the
        # queue ahead of the retrying client; 100 ms floor before the
        # first completion calibrates the estimator
        est = self._service_ms_ewma if self._service_ms_ewma else 100.0
        return max(1.0, len(self._dq) * est)

    def _retry_after_ms(self) -> float:
        with self._lock:
            return self._retry_after_ms_locked()

    # -- consumer (batcher) side -------------------------------------------
    def pop(self, n: int) -> List[ServeRequest]:
        """Take up to `n` requests FIFO. Already-expired requests are
        resolved "expired" here and do not count against `n`."""
        return self.pop_fitting(n, lambda req: True)

    def pop_fitting(self, n: int,
                    fits: Callable[[ServeRequest], bool]
                    ) -> List[ServeRequest]:
        """Take up to `n` unexpired requests FIFO, stopping at the
        FIRST one ``fits`` rejects — the paged-KV admission gate:
        capacity is measured in free BLOCKS (can this prompt + its
        generation budget be allocated without starving a running
        sequence?), not free slots, and a too-big head request is never
        queue-jumped (FIFO fairness; it admits once blocks free up).
        Already-expired requests are resolved "expired" and count
        against nothing.

        ``fits`` runs under the queue lock and must not take locks of
        its own. Handle resolution (and therefore any ``on_resolve``
        hook) runs AFTER the queue lock is released: the fleet
        router's hook takes its own lock and may submit back into a
        queue, so resolving under this lock would invert the
        router->queue lock order."""
        out: List[ServeRequest] = []
        dead: List[ServeRequest] = []
        with self._lock:
            now = time.monotonic()
            while self._dq and len(out) < n:
                req = self._dq[0]
                if req.expired(now):
                    self._dq.popleft()
                    self._m_expired.inc()
                    dead.append(req)
                    continue
                if not fits(req):
                    break
                self._dq.popleft()
                out.append(req)
            self._m_depth.set(len(self._dq))
            if not self._dq:
                self._work.clear()
        for req in dead:
            req.handle._resolve(
                [], "expired",
                latency_ms=(now - req.submitted_at) * 1000.0)
        return out

    def reap_expired(self) -> int:
        """Resolve every expired request still WAITING in the queue —
        called by the batcher once per scheduling iteration, so a
        client whose deadline passes while the fleet is saturated gets
        its structured deadline completion (HTTP 504, serve/http.py)
        within one iteration instead of discovering it by socket
        timeout. Returns the number reaped."""
        dead: List[ServeRequest] = []
        with self._lock:
            now = time.monotonic()
            if self._dq:
                keep: "deque[ServeRequest]" = deque()
                for req in self._dq:
                    (dead if req.expired(now) else keep).append(req)
                if dead:
                    self._dq = keep
                    self._m_expired.inc(len(dead))
                    self._m_depth.set(len(keep))
                    if not keep:
                        self._work.clear()
        for req in dead:
            req.handle._resolve(
                [], "expired",
                latency_ms=(now - req.submitted_at) * 1000.0)
        return len(dead)

    def note_service_ms(self, ms: float) -> None:
        """Batcher feedback on request retirement (EWMA, alpha=0.2)."""
        with self._lock:
            self._m_completed.inc()
            if self._service_ms_ewma is None:
                self._service_ms_ewma = ms
            else:
                self._service_ms_ewma += 0.2 * (ms - self._service_ms_ewma)

    def peek_prompts(self, n: int) -> List[Sequence[int]]:
        """Snapshot the first ``n`` waiting prompts (no pop, no
        resolution) — the KV tier's pre-admission promotion scan
        (serve/kvtier/): the batcher promotes ladder-held prefix runs
        for queued prompts BEFORE the admission wave matches against
        the tree, outside the queue lock."""
        with self._lock:
            return [req.prompt for _, req in
                    zip(range(n), self._dq)]

    def depth(self) -> int:
        with self._lock:
            return len(self._dq)

    def wait_for_work(self, timeout: Optional[float] = None) -> bool:
        """Block until at least one request is queued (batcher idle loop)."""
        return self._work.wait(timeout)

    def counters(self) -> dict:
        with self._lock:
            return {"queue_depth": len(self._dq),
                    "admitted": self.admitted_count,
                    "shed": self.shed_count,
                    "completed": self.completed_count,
                    "expired": self.expired_count,
                    "service_ms_ewma": self._service_ms_ewma}
