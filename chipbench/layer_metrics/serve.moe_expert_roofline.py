"""The routed experts' share of their roofline in decode steps: the
least time the chip could take for the experts' work of the traced
decode steps (three products a (token, expert) pair; the weight bytes of
the experts the program COUNTED as having received a token, summed over
layers and steps: the ``experts_hit`` attribute of each ``exec_step``
span, never all of them, so a program that reads only what was hit
cannot read over 100%), over the device time of the grouped-matmul
kernels inside those steps.

The kernels are found by NAME (Mosaic custom calls named ``gmm``, jax's
Pallas grouped matmul, which `parallel/ep.py routed_experts` calls
twice a layer); a decode step's are those inside its ``exec_step`` span.
The sort, the gathers and the activation between the two products are
XLA fusions without a name of their own and are not in the time.
Nothing found (no ring, no counter, no kernel of that name), nothing
printed."""
from chipbench import exec_steps, roofline

PATTERN = r"^%?gmm[.\d]*$"


def read(run):
    decode = exec_steps.of_kind(run, "decode")
    if not decode or any("experts_hit" not in s[2] for s in decode):
        return None
    seconds = exec_steps.kernel_seconds(run, PATTERN, decode)
    if not seconds:
        return None
    work = run.family.expert_work(
        run.shape, tokens=sum(s[2]["rows"] for s in decode),
        experts_hit=sum(s[2]["experts_hit"] for s in decode))
    return 100.0 * roofline.roofline_seconds(work, run.peak) / seconds
