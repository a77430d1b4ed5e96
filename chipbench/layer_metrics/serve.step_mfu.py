"""The serving steps' share of the chip's peak: required forward
operations of every prompt and generated token processed in the traced
slice (the run's family counts them from the steps the runner recorded;
only the emitting position's logits count), over the slice's length x
peak bf16 FLOP/s."""


def read(run):
    t = run.traced
    if not t.get("steps") or not t.get("seconds"):
        return None
    need = run.family.serve_flops(run.shape, t["steps"])
    return 100.0 * need / (t["seconds"] * run.peak["bf16_flops_per_s"])
