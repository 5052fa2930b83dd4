#!/usr/bin/env python
"""Lint: every `KernelLimits` field must be documented in doc/perf.md —
with its provenance tag and safe range.

ISSUE 7 moved the core onto the shared jtlint rule-runner
(jepsen_etcd_demo_tpu/analysis/rules/limits_doc.py, rule JTL301), so
doc lint and code lint share ONE findings format and ONE baseline
mechanism — `jepsen-tpu lint` runs this check automatically as a
project rule. This file stays as the historic CLI entry point and
importable API (tests/test_limits_doc.py pins both):

Usage: python tools/check_limits_doc.py  (exit 1 + every problem).
Importable: `missing_fields()` returns undocumented field names;
`doc_errors()` returns every mismatch as a human-readable string.
"""

from __future__ import annotations

import sys
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
DOC = REPO / "doc" / "perf.md"

sys.path.insert(0, str(REPO))

from jepsen_etcd_demo_tpu.analysis.rules import limits_doc as _core  # noqa: E402


def field_metadata() -> dict[str, dict]:
    return _core.field_metadata()


def range_text(meta: dict) -> str:
    return _core.range_text(meta)


def missing_fields(doc_path: Path = DOC) -> list[str]:
    """KernelLimits field names not mentioned (as `field` code spans) in
    the perf doc."""
    return _core.missing_fields(doc_path)


def doc_errors(doc_path: Path = DOC) -> list[str]:
    """Every documentation problem: a field absent from the doc, or a
    field whose doc row (the table line naming it) lacks — or
    contradicts — its `[kind]` tag or `lo..hi` safe range."""
    return _core.doc_errors(doc_path)


def main() -> int:
    problems = doc_errors()
    if problems:
        print(f"{DOC.relative_to(REPO)} has {len(problems)} KernelLimits "
              f"documentation problem(s):", file=sys.stderr)
        for p in problems:
            print(f"  - {p}", file=sys.stderr)
        print("Fix the 'KernelLimits reference' table in doc/perf.md: "
              "every field needs a row with its [envelope]/[arch]/[tunable] "
              "tag and its lo..hi safe range.", file=sys.stderr)
        return 1
    print(f"ok: all {len(field_metadata())} KernelLimits fields "
          f"documented (tag + safe range) in {DOC.relative_to(REPO)}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
