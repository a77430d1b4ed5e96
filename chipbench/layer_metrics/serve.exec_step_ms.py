"""Median decode step of the executor over the traced run's window, on
the harness's clock around `executor.step` (upload, program, readback)."""
import statistics


def read(run):
    spans = run.spans.get("executor_decode")
    if not spans:
        return None
    return 1e3 * statistics.median(spans)
