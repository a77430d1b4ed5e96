"""90th percentile of submit -> `ServeHandle` resolved, on the caller's
clock, over the requests that resolved `ok` in the traced run's window
and were submitted after the profiler had stopped (its stop stalls the
loop for seconds, and the requests in flight with it). No bound: on one
seed it moves 2 to 9% from run to run (PERF.md section 2)."""
import numpy as np


def read(run):
    latencies = run.spans.get("request_latency_ms")
    if not latencies:
        return None
    return float(np.percentile(np.asarray(latencies, np.float64), 90))
