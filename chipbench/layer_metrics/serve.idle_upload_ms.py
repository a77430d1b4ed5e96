"""Device idle time per decode iteration of the traced slice while the
executor was still getting the step onto the device: gaps between device
operations under ``exec_upload`` (the step's `jnp.asarray` inputs),
``exec_dispatch`` (the jitted call returning) and the rest of a decode
``exec_step`` outside its readback."""
from chipbench import program_spans


def read(run):
    return program_spans.per_decode_iteration_ms(run, "upload_s")
