"""Device idle time per decode iteration of the traced slice under
``exec_readback``: the host blocks in `np.asarray` of the step's tokens,
and the device has nothing queued behind the step it is waiting for."""
from chipbench import program_spans


def read(run):
    return program_spans.per_decode_iteration_ms(run, "readback_s")
