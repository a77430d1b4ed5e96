"""Median over scheduling iterations of the wall time of one
`batcher.step()` less the executor time inside it: the scheduler's own
host work (retire, admit, plan, build the step's arrays)."""
import statistics


def read(run):
    spans = run.spans.get("schedule_self")
    if not spans:
        return None
    return 1e3 * statistics.median(spans)
