"""Share of the prompt tokens the radix prefix cache supplied: the sum
of ``cached_tokens`` (the tokens before each row's first fresh position,
which the step did not compute) over the sum of ``cached_tokens`` and
``tokens`` (those it ingested) of the prefill ``exec_step`` spans of the
whole measured window that the span ring still holds (a window holds a
few dozen prefills, its traced slice a handful). The executor sets both
from the host's own bookkeeping. Nothing found (no ring, no prefill, a
program that does not count what a hit supplied), nothing printed."""
import time

from chipbench import program_spans


def read(run):
    rec = program_spans._recorder()
    if rec is None or run.window_open is None:
        return None
    # recorder clock minus harness clock, sampled now
    t_open = run.window_open + rec.now() - time.perf_counter()
    t_close = t_open + run.window_s
    prefill = [s.extra for s in rec.between(t_open, t_close)
               if s.name == "exec_step" and s.t0 >= t_open
               and s.t1 <= t_close and s.extra.get("kind") == "prefill"]
    if not prefill or any("cached_tokens" not in e for e in prefill):
        return None
    cached = sum(e["cached_tokens"] for e in prefill)
    prompt = cached + sum(e["tokens"] for e in prefill)
    return 100.0 * cached / prompt if prompt else None
