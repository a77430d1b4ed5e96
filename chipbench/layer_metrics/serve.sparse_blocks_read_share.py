"""Of the blocks the traced decode steps' rows had cached, the share their
block-selecting layers attended to: ``blocks_attended`` (counted on the
device, summed over those layers and the live rows, back with the step's
one readback) over ``blocks_cached`` (what a walk of every row's whole
table would have read; the executor knows it from the positions), both
attributes of the decode ``exec_step`` spans. Lower is sparser; 100
means the selection never engaged. Nothing found (no ring, a program
that counts neither), nothing printed."""
from chipbench import exec_steps


def read(run):
    decode = exec_steps.of_kind(run, "decode")
    if not decode or any("blocks_attended" not in s[2]
                         or "blocks_cached" not in s[2] for s in decode):
        return None
    cached = sum(s[2]["blocks_cached"] for s in decode)
    if not cached:
        return None
    return 100.0 * sum(s[2]["blocks_attended"] for s in decode) / cached
