"""99th percentile of the gap between two consecutive tokens of one
request, all requests pooled: what a caller streaming the answer sees
of a prefill that stops the decoders. From the ``token_times`` of the
``decode`` spans (one stamp per token, the end of the step that emitted
it). The cell pools over 1,000 gaps, so more than ten lie beyond the
99th; the ``info program_spans`` line also prints the median."""
from chipbench import program_spans


def read(run):
    gaps = program_spans.host_samples(run, "token_gaps_ms")
    return None if gaps is None else program_spans.percentile(gaps, 99)
