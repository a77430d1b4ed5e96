"""Span/leg-registry lint (pass ``trace-registry``).

The tracing plane's equivalent of the knob and metric registries: the
span names processes record (``SpanRecorder.record_local`` /
``record_process`` / ``record_reentrant`` / ``span``,
``TraceAssembler.span``) and the
leg labels the
router's ``hvd_trace_leg_ms{leg,pool}`` histograms carry are declared
ONCE, in ``trace/spans.py``'s :data:`~horovod_tpu.trace.spans.
SPAN_LEGS` table (legs: the :data:`~horovod_tpu.trace.spans.LEGS`
tuple derived next to it) — and documented in docs/tracing.md's
registry tables. Four checks:

1. **Declared.** Every literal span name passed to a recording call
   anywhere in ``horovod_tpu/`` must be a ``SPAN_LEGS`` key (or carry
   a ``# trace: exempt (<why>)`` annotation). An undeclared name is
   exactly how a dashboard row goes dark: the recorder accepts any
   string, the docs never hear about it.
2. **Consistent.** Every non-None leg a ``SPAN_LEGS`` entry maps to
   must be in ``LEGS`` — the histogram's label set — or the leg
   decomposition would attribute time to a label no docs row and no
   alert ever mentions.
3. **Documented (spans).** Every declared span name has a row in
   docs/tracing.md's ``## Span registry`` table, and every row there
   names a declared span — both directions.
4. **Documented (legs).** Same, for ``LEGS`` against the
   ``## Leg registry`` table.

Suppression: ``# trace: exempt (<why>)`` on the call line or the
enclosing ``def``.
"""
from __future__ import annotations

import ast
import os
import re
from typing import Dict, List, Optional, Set, Tuple

from .core import (Finding, SourceFile, call_name,
                   enclosing_def_lines, str_const)

PASS_ID = "trace-registry"
ANNOTATION = "trace"
DESCRIPTION = ("span names recorded anywhere must be declared in "
               "trace/spans.py SPAN_LEGS and documented in "
               "docs/tracing.md, legs likewise")

_SPANS_PATH = "horovod_tpu/trace/spans.py"
_DOCS = "docs/tracing.md"

#: recording-call shapes: dotted-name suffix -> the positions the
#: span-name argument may take. ``TraceAssembler.span`` takes
#: (ctx, name, ...); ``record_process``, ``record_local``,
#: ``record_reentrant`` and ``SpanRecorder.span`` take (name, ...).
_RECORD_CALLS = {"span": (0, 1), "record_process": (0,),
                 "record_local": (0,), "record_reentrant": (0,)}


def _declared(sf: SourceFile) -> Tuple[Dict[str, Optional[str]],
                                       Tuple[str, ...]]:
    """Parse SPAN_LEGS (name -> leg|None) and LEGS out of
    trace/spans.py's AST — the declaration table, read without
    importing the package."""
    span_legs: Dict[str, Optional[str]] = {}
    legs: Tuple[str, ...] = ()
    if sf.tree is None:
        return span_legs, legs
    for node in ast.walk(sf.tree):
        if not isinstance(node, (ast.Assign, ast.AnnAssign)):
            continue
        targets = node.targets if isinstance(node, ast.Assign) \
            else [node.target]
        names = {t.id for t in targets if isinstance(t, ast.Name)}
        val = node.value
        if "SPAN_LEGS" in names and isinstance(val, ast.Call) \
                and val.args and isinstance(val.args[0],
                                            (ast.List, ast.Tuple)):
            for el in val.args[0].elts:
                if isinstance(el, ast.Tuple) and len(el.elts) == 2:
                    k = str_const(el.elts[0])
                    leg = str_const(el.elts[1])
                    if k is not None:
                        span_legs[k] = leg
        elif "LEGS" in names and isinstance(val, (ast.Tuple, ast.List)):
            legs = tuple(v for v in (str_const(e) for e in val.elts)
                         if v is not None)
    return span_legs, legs


def _recorded_names(sf: SourceFile) -> List[Tuple[str, int, int]]:
    """(span name, line, end_line) for every literal-name recording
    call in the file."""
    out: List[Tuple[str, int, int]] = []
    if sf.tree is None:
        return out
    for node in ast.walk(sf.tree):
        if not isinstance(node, ast.Call):
            continue
        cn = call_name(node)
        if cn is None:
            continue
        name = next(
            (n for n in (str_const(node.args[i]) for i in
                         _RECORD_CALLS.get(cn.rsplit(".", 1)[-1], ())
                         if i < len(node.args)) if n is not None), None)
        if name is not None:
            out.append((name, node.lineno,
                        getattr(node, "end_lineno", node.lineno)))
    return out


def _doc_tables(root: str) -> Optional[Tuple[Set[str], Set[str]]]:
    """First-backtick names from docs/tracing.md's ``## Span
    registry`` and ``## Leg registry`` tables."""
    path = os.path.join(root, _DOCS)
    if not os.path.exists(path):
        return None
    spans: Set[str] = set()
    legs: Set[str] = set()
    current: Optional[Set[str]] = None
    with open(path, "r", encoding="utf-8") as f:
        for ln in f:
            if ln.startswith("#"):
                head = ln.strip("# \n").lower()
                current = (spans if head == "span registry" else
                           legs if head == "leg registry" else None)
                continue
            if current is None or not ln.lstrip().startswith("|"):
                continue
            m = re.search(r"`([a-z0-9_]+)`", ln)
            if m:
                current.add(m.group(1))
    return spans, legs


def run(files: List[SourceFile], root: str) -> List[Finding]:
    findings: List[Finding] = []
    spans_sf: Optional[SourceFile] = None
    for sf in files:
        if sf.path == _SPANS_PATH:
            spans_sf = sf
            break
    if spans_sf is None:
        return findings     # no tracing plane in this tree
    span_legs, legs = _declared(spans_sf)
    if not span_legs:
        findings.append(spans_sf.make_finding(
            PASS_ID, 1, "missing-registry",
            f"{_SPANS_PATH} declares no parseable SPAN_LEGS table — "
            f"the one declaration every recorded span name must "
            f"appear in", key_text="SPAN_LEGS"))
        return findings

    # 1. every literal recorded name is declared
    for sf in files:
        if not sf.path.startswith("horovod_tpu/"):
            continue
        def_lines = (enclosing_def_lines(sf.tree)
                     if sf.tree is not None else {})
        for name, line, end in _recorded_names(sf):
            if name in span_legs:
                continue
            extra = [def_lines[line]] if line in def_lines else []
            if sf.annotated(ANNOTATION, line, end, extra_lines=extra):
                continue
            findings.append(sf.make_finding(
                PASS_ID, line, "undeclared-span",
                f"span {name!r} recorded here but not declared in "
                f"{_SPANS_PATH} SPAN_LEGS — declare it (and add its "
                f"docs/tracing.md row) or annotate "
                f"'# trace: exempt (<why>)'"))

    # 2. every mapped leg exists in LEGS
    for name, leg in sorted(span_legs.items()):
        if leg is not None and leg not in legs:
            findings.append(spans_sf.make_finding(
                PASS_ID, 1, "unknown-leg",
                f"SPAN_LEGS maps {name!r} to leg {leg!r}, which is "
                f"not in LEGS — hvd_trace_leg_ms would carry an "
                f"unregistered label", key_text=f"{name}:{leg}"))

    # 3./4. declaration <-> docs, both directions
    tables = _doc_tables(root)
    if tables is None:
        findings.append(spans_sf.make_finding(
            PASS_ID, 1, "missing-doc-table",
            f"{_DOCS} does not exist — the registry tables every "
            f"span/leg must appear in", key_text=_DOCS))
        return findings
    doc_spans, doc_legs = tables
    for name in sorted(set(span_legs) - doc_spans):
        findings.append(spans_sf.make_finding(
            PASS_ID, 1, "undocumented-span",
            f"span {name!r} is declared in SPAN_LEGS but has no row "
            f"in {_DOCS}'s span registry", key_text=name))
    for name in sorted(doc_spans - set(span_legs)):
        findings.append(spans_sf.make_finding(
            PASS_ID, 1, "stale-doc-span",
            f"{_DOCS} documents span {name!r} but SPAN_LEGS never "
            f"declares it — remove the row or declare the span",
            key_text=name))
    for leg in sorted(set(legs) - doc_legs):
        findings.append(spans_sf.make_finding(
            PASS_ID, 1, "undocumented-leg",
            f"leg {leg!r} is declared in LEGS but has no row in "
            f"{_DOCS}'s leg registry", key_text=leg))
    for leg in sorted(doc_legs - set(legs)):
        findings.append(spans_sf.make_finding(
            PASS_ID, 1, "stale-doc-leg",
            f"{_DOCS} documents leg {leg!r} but LEGS never declares "
            f"it — remove the row or declare the leg", key_text=leg))
    return findings
