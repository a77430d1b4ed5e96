"""Operations and bytes a GPT-2 shape REQUIRES, from its sizes alone.

Every utilization and roofline share the benchmark prints divides one of
these by a measured time. They count the work of the algorithm, not of
an implementation: causal attention is the lower triangle, nothing is
counted twice for recomputation, and a cache read is the logical bytes
of K and V, not the tile-padded ones. A multiply-add is two operations.

The functions in the first half take sizes and are checked against
hand-worked values; the second half is what the per-layer readers ask
for through ``chipbench/families/gpt2.py``: the same counts summed over
the model's layers and over the steps a runner recorded. Every GPT-2
layer does the same work, so the sum is a product here; a family whose
layers differ (a window on some) sums what each of them reads.
"""
from __future__ import annotations

from .reference import Shape


def layer_matmul_params(shape: Shape) -> int:
    """Weights of one block's four matrices (qkv, proj, fc, out)."""
    return 4 * shape.d * shape.d + 2 * shape.d * shape.ffn


def forward_flops_per_token(shape: Shape, context: float) -> float:
    """Forward operations for one token that attends to `context` keys
    (its own position included): the block matrices, QK^T and PV against
    the context, and the output head over the padded vocabulary (the
    rows the model that runs has)."""
    dense = 2 * layer_matmul_params(shape) * shape.layers
    attention = 4 * context * shape.d * shape.layers
    head = 2 * shape.d * shape.padded_vocab
    return dense + attention + head


def train_flops_per_token(shape: Shape, seq: int) -> float:
    """Forward + backward for one token of a full causal sequence of
    `seq` tokens: the mean context is (seq + 1) / 2, and the backward
    pass costs twice the forward."""
    return 3 * forward_flops_per_token(shape, (seq + 1) / 2)


def serve_flops(shape: Shape, prompt_tokens: int, prompt_context: float,
                decode_tokens: int, decode_context: float) -> float:
    """Forward operations of a serving window. `prompt_context` and
    `decode_context` are the SUMS, over the tokens of each kind, of the
    keys each attended to. Only the emitting position's logits are
    required: one head product per prefilled row is folded into
    `decode_tokens` by the caller (every emitted token needs one)."""
    dense = 2 * layer_matmul_params(shape) * shape.layers
    per_key = 4 * shape.d * shape.layers
    head = 2 * shape.d * shape.padded_vocab
    return (dense * (prompt_tokens + decode_tokens)
            + per_key * (prompt_context + decode_context)
            + head * decode_tokens)


def flash_attention_work(shape: Shape, batch: int, seq: int,
                         itemsize: int = 2) -> dict:
    """One layer's causal attention, forward and backward, over `batch`
    sequences. Forward is two S x S x D products per head; backward
    needs five (S again, dV, dP, dQ, dK); causality halves each.
    Bytes: forward reads q, k, v and writes o; backward reads q, k, v,
    o, do and writes dq, dk, dv."""
    per_product = 2 * seq * seq * shape.head_dim * shape.heads * batch / 2
    tensor = batch * shape.heads * seq * shape.head_dim * itemsize
    return {"flops": 7 * per_product, "bytes": 12 * tensor}


def paged_decode_work(shape: Shape, context_tokens: int,
                      itemsize: int = 2) -> dict:
    """One layer's decode attention (one query per row) over rows whose
    cached lengths sum to `context_tokens`: K and V are each read once,
    and each key costs a dot product and a weighted sum per head."""
    kv_bytes = 2 * context_tokens * shape.heads * shape.head_dim * itemsize
    return {"flops": 4 * context_tokens * shape.heads * shape.head_dim,
            "bytes": kv_bytes}


def param_count(shape: Shape) -> int:
    """Parameters of the model as the program builds it (untied head,
    padded vocabulary)."""
    d = shape.d
    per_layer = (layer_matmul_params(shape) + 3 * d + d + shape.ffn + d
                 + 4 * d)
    return (2 * shape.padded_vocab * d + shape.positions * d
            + per_layer * shape.layers + 2 * d)


# ---------------------------------------------------------------------------
# what the readers ask a family for: whole model, recorded steps
# ---------------------------------------------------------------------------

def _all_layers(work: dict, shape: Shape) -> dict:
    return {k: v * shape.layers for k, v in work.items()}


def serve_steps_flops(shape: Shape, steps) -> float:
    """Required forward operations of the executor steps a serving
    runner recorded (``prompt_tokens``, ``prompt_context``,
    ``decode_tokens``, ``decode_context``, ``emitted``, ``kind`` each).
    A prefill emits one token per row too: its head product."""
    need = serve_flops(
        shape,
        prompt_tokens=sum(x["prompt_tokens"] for x in steps),
        prompt_context=sum(x["prompt_context"] for x in steps),
        decode_tokens=sum(x["decode_tokens"] for x in steps),
        decode_context=sum(x["decode_context"] for x in steps))
    return need + 2 * shape.d * shape.padded_vocab * sum(
        x["emitted"] for x in steps if x["kind"] == "prefill")


def attention_work(shape: Shape, batch: int, seq: int) -> dict:
    """`flash_attention_work` of every layer of one train step."""
    return _all_layers(flash_attention_work(shape, batch, seq), shape)


def decode_attention_work(shape: Shape, steps) -> dict:
    """`paged_decode_work` of every layer over the recorded decode
    steps: each layer reads the K and V of every cached token."""
    context = sum(x["decode_context"] for x in steps)
    return _all_layers(paged_decode_work(shape, context), shape)


def decode_query_pattern(shape: Shape, rows: int) -> str:
    """A regular expression for the decode kernel's query operand in the
    trace event's text, one query per row, ``[rows, heads, 1,
    head_dim]``: what tells a decode call of the kernel from a prefill
    call of it."""
    return rf"\[{rows},{shape.heads},1,{shape.head_dim}\]"
