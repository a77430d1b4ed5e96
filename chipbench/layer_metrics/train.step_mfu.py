"""The whole train step's share of the chips' peak: required forward +
backward operations per token (counted by the run's family) x tokens per
second of the traced slice, over chips x peak bf16 FLOP/s."""


def read(run):
    t = run.traced
    if not t.get("tokens") or not t.get("seconds"):
        return None
    per_token = run.family.train_flops_per_token(
        run.shape, int(run.traffic["seq_len"]))
    rate = t["tokens"] / t["seconds"]
    return 100.0 * per_token * rate / (
        run.chips * run.peak["bf16_flops_per_s"])
