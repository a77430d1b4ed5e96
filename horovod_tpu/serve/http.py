"""Thin stdlib HTTP front end for the serve stack (optional).

Two endpoints, JSON in/out, zero dependencies beyond `http.server`:

* ``POST /generate``  body ``{"tokens": [...], "max_new_tokens": N,
  "deadline_ms": M?, "temperature": T?, "top_p": P?, "seed": S?}``
  (sampling keys optional; temperature 0 = greedy)
  -> ``200 {"tokens": [...], "status": "ok",
  "latency_ms": ...}``. Over capacity the admission queue sheds and the
  reply is ``429 {"error": "rejected", "reason": ...,
  "retry_after_ms": ...}`` with a standard ``Retry-After`` header —
  the structured load-shed contract (docs/serving.md).
* ``GET /healthz`` -> ``200`` with the queue/batcher/executor counters
  (queue depth, occupancy, shed count, tokens/s) plus ``replica_up`` /
  ``draining``; ``503`` (same payload) once the batcher thread has died
  or ``stop()`` ran — a real liveness signal a load balancer / the
  fleet router can route on, not a bare reachability ping.
* ``GET /metrics`` -> Prometheus text exposition of the process-global
  registry (horovod_tpu.obs) — serve latency histograms next to the
  engine's wire-byte counters, no second scrape port needed.

:func:`make_fleet_server` lifts the same contract fleet-wide: one
front door over a ``FleetRouter``/``ProcessFleetRouter`` whose
``/healthz`` aggregates per-replica state + live capacity (503 at zero
capacity) and whose ``/generate`` rides the failover/at-most-once/
capacity-scaled-shed machinery.

Production serving would sit behind a real frontend; this exists so the
whole vertical slice — socket to TPU decode step — is drivable from
curl and coverable by a loopback test.
"""
from __future__ import annotations

import json
import threading
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Optional, Tuple

from ..obs import metrics as obs_metrics
from ..obs.exporter import PROMETHEUS_CONTENT_TYPE
from .queue import Rejected


def retry_after_seconds(ms: float) -> int:
    """``Retry-After`` is whole seconds; round UP with a true ceiling
    so clients never come back early — and an exact 2000 ms maps to
    2 s, not 3 (the old ``int(ms/1000)+1`` overshot every
    exact-second hint by a full second). Floor of 1: a sub-second hint
    must not round to an immediate retry."""
    return max(1, int(-(-float(ms) // 1000.0)))


class _JsonHandler(BaseHTTPRequestHandler):
    """Shared plumbing for the per-replica and fleet front doors —
    one place for the reply/metrics/429 mechanics, so the two handlers
    cannot drift (the Retry-After rounding already did once)."""

    def log_message(self, *a):  # quiet: counters replace access logs
        pass

    def _reply(self, code: int, payload: dict,
               headers: Optional[Tuple[Tuple[str, str], ...]] = None):
        body = json.dumps(payload).encode()
        self.send_response(code)
        self.send_header("Content-Type", "application/json")
        self.send_header("Content-Length", str(len(body)))
        for k, v in headers or ():
            self.send_header(k, v)
        self.end_headers()
        self.wfile.write(body)

    def _reply_metrics(self):
        body = obs_metrics.get_registry().to_prometheus().encode()
        self.send_response(200)
        self.send_header("Content-Type", PROMETHEUS_CONTENT_TYPE)
        self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        self.wfile.write(body)

    def _reply_fleet_metrics(self, router):
        """``/metrics?fleet=1``: the router process's own registry
        snapshot merged with every live worker's (scraped over the
        ctrl socket, ``{"op": "metrics"}``) — one exposition for the
        whole fleet, HELP text borrowed from the local registry."""
        R = obs_metrics.get_registry()
        snaps = [R.snapshot()]
        snaps.extend(router.metrics_snapshots())
        body = obs_metrics.snapshot_to_prometheus(
            obs_metrics.merge_snapshots(snaps), help_from=R).encode()
        self.send_response(200)
        self.send_header("Content-Type", PROMETHEUS_CONTENT_TYPE)
        self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        self.wfile.write(body)

    def _reply_rejected(self, reason, retry_after_ms):
        """The structured 429: payload always carries the ms hint, the
        header its true-ceiling whole-second rendering."""
        hdrs = ()
        if retry_after_ms is not None:
            hdrs = (("Retry-After",
                     str(retry_after_seconds(retry_after_ms))),)
        self._reply(429, {"error": "rejected", "reason": reason,
                          "retry_after_ms": retry_after_ms}, hdrs)

    def _read_generate_request(self):
        """Parse a /generate body -> (prompt, max_new, deadline_ms,
        sampling kwargs); raises the (KeyError, ValueError, TypeError)
        family the caller maps to a structured 400. ``temperature`` /
        ``top_p`` / ``seed`` are optional (greedy default); their
        range validation is the queue's (fail-fast at submit)."""
        n = int(self.headers.get("Content-Length", "0"))
        req = json.loads(self.rfile.read(n) or b"{}")
        prompt = req["tokens"]
        max_new = int(req.get("max_new_tokens", 16))
        deadline_ms = req.get("deadline_ms")
        if deadline_ms is not None:
            deadline_ms = float(deadline_ms)
        sampling = {"temperature": float(req.get("temperature", 0.0)),
                    "top_p": float(req.get("top_p", 1.0)),
                    "seed": int(req.get("seed", 0))}
        return prompt, max_new, deadline_ms, sampling


def make_server(batcher, host: str = "127.0.0.1",
                port: int = 0) -> ThreadingHTTPServer:
    """Build (not start) an HTTP server bound to `batcher`'s queue.
    `port=0` picks a free port (see ``server.server_address``)."""
    queue = batcher.queue

    class Handler(_JsonHandler):
        # requests are held open while the batcher generates; the
        # threading server gives each its own thread

        def do_GET(self):
            # query-string tolerant, like the standalone exporter
            if self.path.split("?", 1)[0] == "/metrics":
                self._reply_metrics()
                return
            if self.path != "/healthz":
                self._reply(404, {"error": "not found"})
                return
            ex = batcher.executor
            # Liveness, not just reachability: once the batcher thread
            # has died (chaos crash, unhandled error) or stop() ran, no
            # queued request will ever be served again — a 200 here
            # would keep a load balancer routing traffic into a black
            # hole. 503 is what lets the router/LB actually use this
            # endpoint as its health probe (docs/serving.md).
            up = batcher.alive()
            draining = bool(getattr(batcher, "draining", False))
            info = {"ok": up and not draining,
                    "replica_up": up,
                    "draining": draining,
                    "occupancy": round(batcher.kv.occupancy(), 3),
                    "tokens_per_s": round(ex.tokens_per_s(), 1),
                    "iterations": batcher.iterations,
                    # the occupancy above is tokens-resident (pool
                    # blocks); the raw block counts and the prefix
                    # cache's sharing yield sit next to it
                    "kv_blocks_in_use": batcher.kv.pool.in_use(),
                    "kv_blocks_total": batcher.kv.pool.num_blocks}
            if batcher.prefix is not None:
                info["prefix_hits"] = batcher.prefix.hits
                info["prefix_tokens_saved"] = batcher.prefix.tokens_saved
            info.update(queue.counters())
            self._reply(200 if up else 503, info)

        def do_POST(self):
            if self.path != "/generate":
                self._reply(404, {"error": "not found"})
                return
            try:
                prompt, max_new, deadline_ms, sampling = \
                    self._read_generate_request()
                handle = queue.submit(prompt, max_new_tokens=max_new,
                                      deadline_ms=deadline_ms,
                                      **sampling)
            except (KeyError, ValueError, TypeError,
                    json.JSONDecodeError) as e:
                # covers submit's own validation too (bad token values,
                # max_new_tokens < 1, non-dict body): malformed input is
                # always a structured 400, never a dropped socket
                self._reply(400, {"error": "bad request", "detail": str(e)})
                return
            except Rejected as e:
                self._reply_rejected(e.reason, e.retry_after_ms)
                return
            # wait past the request's own deadline: the batcher resolves
            # expiry itself and this must not race it
            handle.wait(timeout=(deadline_ms or
                                 queue.default_deadline_ms) / 1000.0 + 30.0)
            if not handle.done():
                self._reply(504, {"error": "timeout"})
                return
            if handle.status == "expired":
                # the deadline completion is STRUCTURED: the batcher
                # resolves expiry within one scheduling iteration
                # (queue.reap_expired) and the client learns here, not
                # by its own socket timeout
                self._reply(504, {"error": "deadline",
                                  "tokens": handle.tokens,
                                  "latency_ms": handle.latency_ms})
                return
            if handle.status == "error":
                self._reply(500, {"error": handle.error or "error",
                                  "latency_ms": handle.latency_ms})
                return
            self._reply(200, {"tokens": handle.tokens,
                              "status": handle.status,
                              "latency_ms": handle.latency_ms})

    return ThreadingHTTPServer((host, port), Handler)


def serve_http(batcher, host: str = "127.0.0.1", port: int = 0):
    """Start the batcher thread + HTTP server; returns (server, thread).
    Call ``server.shutdown()`` then ``batcher.stop()`` to tear down."""
    batcher.start()
    srv = make_server(batcher, host, port)
    t = threading.Thread(target=srv.serve_forever, daemon=True,
                         name="hvd-serve-http")
    t.start()
    return srv, t


def make_fleet_server(router, host: str = "127.0.0.1",
                      port: int = 0) -> ThreadingHTTPServer:
    """The FLEET front door: one HTTP face over a ``FleetRouter`` or
    ``ProcessFleetRouter`` (anything with ``submit``/``healthz``).

    * ``POST /generate`` routes through the router — failover,
      at-most-once and capacity-scaled shedding all apply; a shed
      answers ``429`` with ``Retry-After`` (true-ceiling seconds) and
      ``retry_after_ms``, never a dropped socket.
    * ``GET /healthz`` serves the router's AGGREGATE liveness: per-
      replica up/draining/respawning plus live capacity (free queue
      depth + free KV blocks) — ``503`` once live capacity is zero,
      the same contract as the per-replica endpoint, lifted fleet-wide
      so a load balancer can front the whole fleet on one probe.
    * ``GET /metrics`` — the process-global Prometheus registry
      (router legs, failovers, respawns, net retries).
      ``GET /metrics?fleet=1`` additionally scrapes every live worker
      process's snapshot over the ctrl socket and serves the MERGED
      exposition (obs ``merge_snapshots``) — batcher/executor series
      from inside the workers next to the router's own, one scrape
      for the whole fleet (docs/metrics.md).
    """

    class Handler(_JsonHandler):
        def do_GET(self):
            path, _, query = self.path.partition("?")
            if path == "/metrics":
                if ("fleet=1" in query.split("&")
                        and hasattr(router, "metrics_snapshots")):
                    self._reply_fleet_metrics(router)
                else:
                    self._reply_metrics()
                return
            if self.path != "/healthz":
                self._reply(404, {"error": "not found"})
                return
            info = router.healthz()
            self._reply(200 if info.get("ok") else 503, info)

        def do_POST(self):
            if self.path != "/generate":
                self._reply(404, {"error": "not found"})
                return
            try:
                prompt, max_new, deadline_ms, sampling = \
                    self._read_generate_request()
                # sampling rides the fleet path since the routers
                # track (prompt, sampling) for failover re-submit:
                # per-row seeded streams are deterministic across
                # re-dispatch, so a sampled request fails over with
                # the same at-most-once bookkeeping as a greedy one
                handle = router.submit(prompt, max_new_tokens=max_new,
                                       deadline_ms=deadline_ms,
                                       **sampling)
            except (KeyError, ValueError, TypeError,
                    json.JSONDecodeError) as e:
                self._reply(400, {"error": "bad request",
                                  "detail": str(e)})
                return
            except Rejected as e:
                self._reply_rejected(e.reason, e.retry_after_ms)
                return
            handle.wait(timeout=(deadline_ms or 30000.0) / 1000.0 + 60.0)
            if not handle.done():
                self._reply(504, {"error": "timeout"})
                return
            if handle.status == "rejected":
                # async fleet-level shed (every worker's queue door
                # said no): same 429 + Retry-After contract as the
                # synchronous path
                self._reply_rejected(handle.error or "shed",
                                     handle.retry_after_ms)
                return
            if handle.status == "expired":
                self._reply(504, {"error": "deadline",
                                  "tokens": handle.tokens,
                                  "latency_ms": handle.latency_ms})
                return
            if handle.status == "error":
                self._reply(500, {"error": handle.error or "error",
                                  "latency_ms": handle.latency_ms})
                return
            self._reply(200, {"tokens": handle.tokens,
                              "status": handle.status,
                              "latency_ms": handle.latency_ms,
                              "replica": handle.replica})

    return ThreadingHTTPServer((host, port), Handler)
