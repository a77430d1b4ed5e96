"""Annotated twin: declared span names only, plus one deliberate
exemption. MUST produce zero findings."""


def record_spans(rec, asm, ctx, t0, t1):
    rec.record_local("good_span", t0, t1, ship=ctx)
    rec.record_process("ghost_span", t0, t1)
    asm.span(ctx, "lost_span", t0, t1)
    with rec.span("good_span", rows=3):
        rec.record_local("ghost_span", t0, t1, ship=ctx)
    # trace: exempt (fixture: ad-hoc name, suppressed on purpose)
    asm.span(ctx, "suppressed_span", t0, t1)
