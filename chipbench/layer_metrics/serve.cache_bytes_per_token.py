"""Bytes of cache a token of live context costs: ``cache_bytes_held``
(the pool blocks the live rows' tables name, at the VALUES' width, plus
what each row holds whatever its context: rings and recurrent states)
over ``context_tokens`` (the live rows' contexts), both attributes the
executor sets on a decode ``exec_step`` span without a readback, summed
over the traced decode steps. A cache that keeps every attending layer's
K and V for every token reads that sum; one whose window layers keep a
ring and whose cross layers share a pool reads one layer's K and V plus
the rows' fixed part spread over their contexts. Nothing found (no ring,
a program that counts neither), nothing printed."""
from chipbench import exec_steps


def read(run):
    decode = exec_steps.of_kind(run, "decode")
    if not decode or any("cache_bytes_held" not in s[2]
                         or "context_tokens" not in s[2] for s in decode):
        return None
    tokens = sum(s[2]["context_tokens"] for s in decode)
    if not tokens:
        return None
    return sum(s[2]["cache_bytes_held"] for s in decode) / tokens
