"""Device idle time per decode iteration of the traced slice while the
scheduler worked between executor steps: gaps under a ``sched_*`` span
and outside every ``exec_step`` (retire, admit, building the step's
arrays)."""
from chipbench import program_spans


def read(run):
    return program_spans.per_decode_iteration_ms(run, "sched_s")
