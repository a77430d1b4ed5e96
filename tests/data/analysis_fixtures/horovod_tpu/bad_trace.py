"""Seeded-bad fixture: records a span name the SPAN_LEGS table never
declares. MUST be flagged by trace-registry (undeclared-span)."""


def record_spans(asm, ctx, t0, t1):
    asm.span(ctx, "rogue_span", t0, t1)


def record_local_spans(rec, t0, t1):
    """The local ring's two entry points take the name FIRST."""
    with rec.span("rogue_thread_span", rows=3):
        rec.record_local("rogue_local_span", t0, t1, trace="rid1")
