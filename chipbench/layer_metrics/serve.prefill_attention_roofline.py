"""The prefill attention's share of its roofline: the least time the chip
could take for the causal attention of the traced prefill steps, all
layers, a window layer's keys clipped at its window and each row counted
from its own cached prefix (the run's family counts it from the steps
the runner recorded), over the device time of the flash forward kernel
inside those steps' ``exec_step`` spans.

The kernel is found by NAME: Mosaic custom calls named after
`flash_prefill` (the jitted entry point in ops/pallas_attention.py).
Nothing found, nothing printed."""
from chipbench import exec_steps, roofline

PATTERN = r"flash_prefill"


def read(run):
    prefill = exec_steps.of_kind(run, "prefill")
    recorded = [s for s in (run.traced or {}).get("steps") or ()
                if s["kind"] == "prefill"]
    if not prefill or not recorded \
            or not hasattr(run.family, "prefill_attention_work"):
        return None
    seconds = exec_steps.kernel_seconds(run, PATTERN, prefill)
    if not seconds:
        return None
    work = run.family.prefill_attention_work(run.shape, recorded)
    return 100.0 * roofline.roofline_seconds(work, run.peak) / seconds
