"""Tier-1 wiring of tools/check_limits_doc.py: every KernelLimits field
(ops/limits.py) must appear — as a backticked code span — in doc/perf.md's
"KernelLimits reference" table, WITH its [envelope]/[arch]/[tunable]
provenance tag and its lo..hi safe range matching the dataclass field
metadata (ISSUE 4 satellite: the autotuner's search bounds are the
documented bounds, enforced)."""

from __future__ import annotations

import sys
from pathlib import Path

TOOLS = Path(__file__).resolve().parent.parent / "tools"
sys.path.insert(0, str(TOOLS))

import check_limits_doc  # noqa: E402


def test_every_limits_field_documented():
    missing = check_limits_doc.missing_fields()
    assert not missing, (
        f"KernelLimits fields missing from doc/perf.md: {missing} — "
        f"add them to the 'KernelLimits reference' table")


def test_tags_and_ranges_consistent_with_metadata():
    errors = check_limits_doc.doc_errors()
    assert not errors, "\n".join(errors)


def test_lint_detects_missing_field(tmp_path):
    """The lint actually fails when a field is absent (guards against a
    vacuous check)."""
    doc = tmp_path / "perf.md"
    text = check_limits_doc.DOC.read_text(encoding="utf-8")
    doc.write_text(text.replace("`sparse_tile_words`", "(redacted)"))
    assert check_limits_doc.missing_fields(doc) == ["sparse_tile_words"]
    assert any("sparse_tile_words" in e
               for e in check_limits_doc.doc_errors(doc))


def test_lint_detects_wrong_tag(tmp_path):
    """A field re-tagged against its metadata kind must fail (the tag
    drives the tuner's conservative clamping — drift is dangerous)."""
    doc = tmp_path / "perf.md"
    text = check_limits_doc.DOC.read_text(encoding="utf-8")
    bad = text.replace(
        "| `long_scan_chunk` | [envelope]",
        "| `long_scan_chunk` | [tunable]")
    assert bad != text
    doc.write_text(bad)
    errs = check_limits_doc.doc_errors(doc)
    assert any("long_scan_chunk" in e and "[envelope]" in e for e in errs)


def test_lint_detects_wrong_range(tmp_path):
    doc = tmp_path / "perf.md"
    text = check_limits_doc.DOC.read_text(encoding="utf-8")
    meta = check_limits_doc.field_metadata()["sched_pipeline_depth"]
    want = check_limits_doc.range_text(meta)
    # A PREFIX-preserving drift (1..8 -> 1..80): a substring match would
    # stay green; the whole-cell match must fail.
    bad = text.replace(
        f"| `sched_pipeline_depth` | [tunable] | {want} |",
        f"| `sched_pipeline_depth` | [tunable] | {want}0 |")
    assert bad != text
    doc.write_text(bad)
    errs = check_limits_doc.doc_errors(doc)
    assert any("sched_pipeline_depth" in e and want in e for e in errs)


def test_cli_entry_exits_zero():
    assert check_limits_doc.main() == 0
