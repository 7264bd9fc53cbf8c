#!/usr/bin/env python
"""Fail when a span name emitted in code is missing from the README.

Mirror of ``tools/check_metric_docs.py`` / ``check_session_property_docs``
/ ``check_endpoint_docs`` for the tracing vocabulary: spans have no
central registry (they are emitted inline via ``tracing.span(...)`` /
``tracer.start_span(...)``), so the source itself is scanned — every
string literal in the FIRST argument of a span call (both arms of a
conditional name count) must appear in README.md's span table. Wired as a
tier-1 test (tests/test_span_docs.py) and into ``tools/lint.py --all``
(shared plumbing: tools/gates.py).

Usage: ``python tools/check_span_docs.py [--readme PATH]`` — exit 0 when
every span is documented, 1 with the missing names otherwise.
"""
from __future__ import annotations

import re
import sys

if __package__ in (None, ""):  # script mode: tools/ on sys.path
    import gates
else:  # imported as tools.check_span_docs
    from tools import gates

# a span call is any `<tracing|...tracer>.span(` / `.start_span(`, or the
# after-the-fact `tracing.record(` / `.record_burst(` / `.record_span(` —
# the receiver prefix keeps unrelated `*_span(` helpers (e.g. ops/join.py
# dense_span) out of the vocabulary
_CALL_RE = re.compile(
    r"(?:tracing|[A-Za-z_][\w.]*tracer)\s*\.\s*"
    r"(?:(?:start_|record_)?span|record(?:_burst)?)\s*\(")
_STRING_RE = re.compile(r"\"([^\"]+)\"|'([^']+)'")


def _first_arg_slice(text: str, start: int) -> str:
    """The source slice of the call's first argument: from the opening
    paren to the first top-level comma or the closing paren."""
    depth = 0
    i = start
    in_str: str | None = None
    while i < len(text):
        c = text[i]
        if in_str:
            if c == in_str and text[i - 1] != "\\":
                in_str = None
        elif c in "\"'":
            in_str = c
        elif c in "([{":
            depth += 1
        elif c in ")]}":
            depth -= 1
            if depth == 0:
                return text[start : i]
        elif c == "," and depth == 1:
            return text[start : i]
        i += 1
    return text[start : i]


def emitted_span_names(root: str | None = None) -> list:
    """Every span name a ``tracing.span``/``tracer.start_span`` call can
    emit (all string literals of the first argument — a conditional name
    like ``"a" if x else "b"`` contributes both)."""
    names = set()
    for path in gates.iter_source_files(root):
        with open(path, encoding="utf-8") as f:
            text = f.read()
        for m in _CALL_RE.finditer(text):
            arg = _first_arg_slice(text, m.end() - 1)
            for sm in _STRING_RE.finditer(arg):
                # an f-string documents as its template: {x} -> <x>
                names.add(re.sub(r"\{(\w+)\}", r"<\1>",
                                 sm.group(1) or sm.group(2)))
    return sorted(names)


def documented_span_names(readme_path: str) -> set:
    """Backtick-quoted identifiers in the README (the span table uses
    backticks, but any backticked mention counts — the check is for
    presence)."""
    return gates.backticked_names(gates.read_readme(readme_path))


def check(readme_path: str | None = None) -> list:
    """Missing span names (empty means the docs are complete)."""
    documented = documented_span_names(readme_path)
    return [name for name in emitted_span_names() if name not in documented]


def main() -> int:
    return gates.gate_main(
        __doc__, check,
        "span names emitted in code but missing from the README span "
        "table:",
        "add each to the span table in README.md (### Tracing)",
        lambda: (f"ok: all {len(emitted_span_names())} emitted span names "
                 "are documented"))


if __name__ == "__main__":
    sys.exit(main())
