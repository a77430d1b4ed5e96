"""The selective-scan decode update's share of its roofline: the least
time the chip could take to read and write the float32 conv and SSM
state of every (row, Mamba layer) the traced decode steps updated and do
the update's operations (the family's ``state_work`` of the
``state_rows`` the program COUNTED on each decode ``exec_step`` span),
over the device time of the kernel inside those steps.

The kernel is found by NAME: Mosaic custom calls named ``ssm_decode``
(`ops/selective_scan.py`), one a Mamba layer and decode step. A row the
step leaves out is copied through by the kernel and not counted, so a
step with idle rows reads lower, never higher. Nothing found (no ring,
no counter, no kernel of that name: a program without such layers),
nothing printed."""
from chipbench import exec_steps, roofline

PATTERN = r"ssm_decode"


def read(run):
    decode = exec_steps.of_kind(run, "decode")
    if not decode or any("state_rows" not in s[2] for s in decode) \
            or not hasattr(run.family, "state_work"):
        return None
    seconds = exec_steps.kernel_seconds(run, PATTERN, decode)
    if not seconds:
        return None
    work = run.family.state_work(
        run.shape, sum(s[2]["state_rows"] for s in decode))
    return 100.0 * roofline.roofline_seconds(work, run.peak) / seconds
