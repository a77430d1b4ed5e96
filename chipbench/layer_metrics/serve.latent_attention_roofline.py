"""The latent-attention decode kernel's share of its roofline: the least
time the chip could take to read every cached latent the traced decode
steps attended to ONCE a layer (the latent and the rope key at their
values' width, not the held lanes) and do the absorbed products (the
family's ``latent_decode_work`` of the ``context_tokens`` the executor
set on each decode ``exec_step`` span, over every layer), over the
device time of the kernel inside those steps.

The kernel is found by NAME: Mosaic custom calls named after
`_latent_attention_call` (ops/pallas_latent.py), one a layer and decode
step. A kernel that read the pool once as keys and again as values would
read under half. Nothing found (no ring, no counter, no kernel of that
name: a program without latent attention), nothing printed."""
from chipbench import exec_steps, roofline

PATTERN = r"latent_attention"


def read(run):
    decode = exec_steps.of_kind(run, "decode")
    if not decode or any("context_tokens" not in s[2] for s in decode) \
            or not hasattr(run.family, "latent_decode_work"):
        return None
    seconds = exec_steps.kernel_seconds(run, PATTERN, decode)
    if not seconds:
        return None
    work = run.family.latent_decode_work(
        run.shape, sum(s[2]["context_tokens"] for s in decode),
        run.shape.layers)
    return 100.0 * roofline.roofline_seconds(work, run.peak) / seconds
