"""The serving steps' share of the chip's peak: required forward
operations of every prompt and generated token processed in the traced
slice (chipbench/flops.py; only the emitting position's logits count),
over the slice's length x peak bf16 FLOP/s."""
from chipbench import flops
from chipbench.reference import Shape


def read(run):
    t = run.traced
    if not t.get("steps") or not t.get("seconds"):
        return None
    shape = Shape(run.config)
    s = t["steps"]
    need = flops.serve_flops(
        shape,
        prompt_tokens=sum(x["prompt_tokens"] for x in s),
        prompt_context=sum(x["prompt_context"] for x in s),
        decode_tokens=sum(x["decode_tokens"] for x in s),
        decode_context=sum(x["decode_context"] for x in s))
    # a prefill emits one token per row too: its head product
    need += 2 * shape.d * shape.padded_vocab * sum(
        x["emitted"] for x in s if x["kind"] == "prefill")
    return 100.0 * need / (t["seconds"] * run.peak["bf16_flops_per_s"])
