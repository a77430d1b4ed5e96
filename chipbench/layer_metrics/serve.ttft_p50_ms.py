"""Median time to first token, submit -> the first token exists, from
the program's own stamps: `t_first - t_submit` of the ``request`` spans
of the requests ``serve.request_p90_ms`` counts (chipbench/program_spans.py
has the population and the clocks)."""
import statistics

from chipbench import program_spans


def read(run):
    ttft = program_spans.host_samples(run, "ttft_ms")
    return None if ttft is None else statistics.median(ttft)
