"""horovod_tpu.trace: the jax-free distributed-tracing plane.

Per-request span propagation, cross-process collection and merge, and
an incident flight recorder over the serve fleet — the layer that
answers "where did this request's 500 ms go" when a request crosses
the front door, a prefill worker, a crc-framed KV migration and a
decode worker under failovers and autoscaling (docs/tracing.md):

    context.py   (trace_id, span_id, parent_id) minted at admission,
                 carried as one JSON field on every dispatch message /
                 migration header (absent => untraced, full back-compat)
    spans.py     THE span/leg registry (machine-checked against
                 docs/tracing.md by tools/check.py --pass
                 trace-registry) + the bounded per-process SpanRecorder
    stalls.py    host stalls into the local ring: jax's trace / lower /
                 compile-or-fetch events and long GC pauses, under the
                 span open on the thread that paid for them
    clock.py     per-worker clock offsets from heartbeat round trips
                 (minimum-delay filter; no clock protocol)
    collect.py   router-side TraceAssembler: leg attribution into
                 hvd_trace_leg_ms{leg,pool}, tail sampling, the
                 flight-recorder incident dump
    writer.py    merged clock-aligned Chrome-trace writer (one named
                 pid row per pool/replica/generation; valid JSON after
                 every flush, like timeline.py)

Stdlib-only: importable from routers' health threads, worker endpoint
threads and tools/trace_inspect.py without dragging jax in.
"""
from .context import TraceContext                       # noqa: F401
from .spans import (                                    # noqa: F401
    LEGS, SPAN_LEGS, SPAN_NAMES, Span, SpanRecorder,
    configure_recorder, get_recorder,
)
from .clock import ClockOffsets                         # noqa: F401
from .collect import (                                  # noqa: F401
    TraceAssembler, assembler_from_env, clock_key, leg_decompose,
)
from .writer import ChromeTraceWriter                   # noqa: F401
