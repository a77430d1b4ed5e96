#!/usr/bin/env python
"""GPT train-step throughput + MFU on the current backend.

Methodology (see docs/benchmarks.md): two timed runs of different
lengths, each fenced by a host scalar readback of the loss; per-step
time is the slope, which cancels the fixed dispatch + readback latency.
MFU uses the standard 6 * params * tokens FLOP estimate over the v5e
bf16 peak (197 TFLOP/s) when on TPU.

Usage: python benchmarks/gpt_bench.py [--impl pallas|reference]
       [--layers 12] [--heads 12] [--head-dim 64] [--seq 1024]
       [--batch 8] [--vocab 50304]
"""
import argparse
import json
import os
import sys


sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks._timing import slope_time  # noqa: E402

V5E_BF16_PEAK = 197e12


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--family", default="gpt", choices=["gpt", "llama"])
    ap.add_argument("--impl", default="pallas",
                    choices=["pallas", "reference"])
    ap.add_argument("--layers", type=int, default=12)
    ap.add_argument("--heads", type=int, default=12)
    ap.add_argument("--kv-heads", type=int, default=None,
                    help="GQA kv heads (llama only; default = --heads)")
    ap.add_argument("--head-dim", type=int, default=64)
    ap.add_argument("--seq", type=int, default=1024)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--vocab", type=int, default=50304)
    ap.add_argument("--iters", type=int, default=10)
    ap.add_argument("--logits-dtype", default="f32",
                    choices=["f32", "bf16"],
                    help="lm_head compute dtype (both families): bf16 "
                    "halves logits/dlogits HBM bytes; CE math stays f32 "
                    "inside the kernel")
    ap.add_argument("--sp", type=int, default=1,
                    help="sequence-parallel degree (ring attention); "
                         "dp = devices // sp")
    ap.add_argument("--attention", default=None,
                    choices=["dense", "ring", "ulysses", "zigzag"],
                    help="override attention mode (default: ring when "
                         "--sp > 1 else dense; zigzag = causally "
                         "load-balanced ring)")
    args = ap.parse_args()
    if args.iters <= 0:
        ap.error("--iters must be positive")

    import jax

    import horovod_tpu as hvd
    from benchmarks._gpt_step import build_gpt_train_step

    hvd.init()
    n_dev = hvd.size()
    platform = jax.devices()[0].platform
    if n_dev % args.sp:
        ap.error(f"--sp {args.sp} must divide device count {n_dev}")
    attention = args.attention or ("ring" if args.sp > 1 else "dense")
    if attention in ("ring", "ulysses", "zigzag") and args.sp <= 1:
        ap.error(f"--attention {attention} requires --sp > 1")

    step, params, opt, tokens, targets, n_params, _mesh = \
        build_gpt_train_step(
            family=args.family, impl=args.impl, layers=args.layers,
            heads=args.heads, kv_heads=args.kv_heads,
            head_dim=args.head_dim, seq=args.seq, batch=args.batch,
            vocab=args.vocab, sp=args.sp, attention=attention,
            logits_dtype=args.logits_dtype)
    B, S = args.batch * n_dev, args.seq

    for _ in range(3):  # >1: the post-donation arg layouts can recompile
        params, opt, loss = step(params, opt, tokens, targets)
        float(loss)  # fenced per-step so compiles land inside warmup

    def run_fenced(n):
        nonlocal params, opt
        loss = None
        for _ in range(n):
            params, opt, loss = step(params, opt, tokens, targets)
        float(loss)

    step_time, timing = slope_time(run_fenced, args.iters, 3 * args.iters)

    tok_s = B * S / step_time
    flops_per_tok = 6 * n_params  # + attention term below
    embed_dim = args.heads * args.head_dim
    attn_flops = 12 * args.layers * embed_dim * S  # 2*6*L*E*S per tok
    mfu = ((flops_per_tok + attn_flops) * tok_s / (n_dev * V5E_BF16_PEAK)
           if platform == "tpu" else None)
    print(json.dumps({
        "metric": f"{args.family}_tokens_per_sec", "value": round(tok_s, 0),
        "unit": "tok/s", "impl": args.impl, "params_m": round(n_params / 1e6, 1),
        "batch": B, "seq": S, "ms_per_step": round(step_time * 1000, 2),
        "mfu_v5e": round(mfu, 3) if mfu is not None else None,
        "attention": attention,
        "logits_dtype": args.logits_dtype, "sp": args.sp,
        "platform": platform, "n_devices": n_dev, "timing": timing,
    }))


if __name__ == "__main__":
    main()
