"""Mean share of the decode batch that held a live row: ``rows`` of the
decode ``exec_step`` spans over the server's ``max_batch``, over the
undisturbed part of the window (after the profiler's stop)."""
import statistics

from chipbench import program_spans


def read(run):
    occupancy = program_spans.host_samples(run, "occupancy")
    return None if occupancy is None else 100.0 * statistics.fmean(occupancy)
