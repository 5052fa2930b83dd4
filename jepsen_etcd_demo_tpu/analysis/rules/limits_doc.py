"""JTL301 limits-doc: KernelLimits fields documented, tagged, ranged.

The refactored core of ``tools/check_limits_doc.py`` (which remains as
a thin CLI shim): every ``KernelLimits`` field must appear in
doc/perf.md's "KernelLimits reference" table with its
``[envelope]/[arch]/[tunable]`` provenance tag and its ``lo..hi`` safe
range, both MATCHING ``ops/limits.py field_meta()`` — the autotuner's
search bounds are the documented bounds, enforced (ISSUE 4; now ISSUE
7 moves it onto the shared rule-runner so doc lint and code lint share
one findings format and one baseline mechanism).

This is a :class:`~..core.ProjectRule`: it runs once per lint
invocation against the repo root, not per Python module. It imports
``ops.limits`` (dataclass metadata only — no jax), keeping the tier-1
lint path fast.
"""

from __future__ import annotations

from pathlib import Path
from typing import Optional

from ..core import PACKAGE_NAME, ProjectRule, register
from ..findings import Finding


def field_metadata() -> dict[str, dict]:
    from ...ops.limits import field_meta

    return field_meta()


def range_text(meta: dict) -> str:
    lo, hi = meta["range"]
    return f"{lo}..{hi}"


def doc_problems(doc_path: Path) -> list[tuple[str, Optional[int], str]]:
    """Every documentation problem as (field, doc line or None, message).
    Message text is the tools/check_limits_doc.py contract — stable
    wording, substring-matched by tests."""
    text = Path(doc_path).read_text(encoding="utf-8")
    lines = text.splitlines()
    problems: list[tuple[str, Optional[int], str]] = []
    for name, meta in field_metadata().items():
        span = f"`{name}`"
        rows = [(i, ln) for i, ln in enumerate(lines, start=1)
                if span in ln and ln.lstrip().startswith("|")]
        if span not in text or not rows:
            problems.append((name, None,
                             f"{name}: no table row in doc/perf.md "
                             f"(env JEPSEN_TPU_LIMIT_{name.upper()})"))
            continue
        # A field may appear in several tables (the probe-group map, the
        # reference); it passes when SOME row carries both its tag and
        # its range — the reference row. The range must fill a WHOLE
        # table cell: a bare substring test would let `1..80` satisfy a
        # wanted `1..8` (prefix drift the lint exists to catch).
        want_tag = f"[{meta['kind']}]"
        want_cell = f"| {range_text(meta)} |"
        cells = [(i, " ".join(r.split())) for i, r in rows]
        if any(want_tag in r and want_cell in r for _, r in cells):
            continue
        line0 = rows[0][0]
        has_tag = any(want_tag in r for _, r in cells)
        has_cell = any(want_cell in r for _, r in cells)
        if not has_tag:
            problems.append((name, line0,
                             f"{name}: no table row carries its "
                             f"provenance tag {want_tag} (tags: "
                             f"[envelope]/[arch]/[tunable])"))
        if not has_cell:
            problems.append((name, line0,
                             f"{name}: no table row carries its safe "
                             f"range `{range_text(meta)}` as a whole "
                             f"cell (ops/limits.py field_meta is the "
                             f"source of truth)"))
        if has_tag and has_cell:
            problems.append((name, line0,
                             f"{name}: tag {want_tag} and range "
                             f"`{range_text(meta)}` never appear in "
                             f"the SAME row"))
    return problems


def missing_fields(doc_path: Path) -> list[str]:
    """KernelLimits field names not mentioned (as `field` code spans) in
    the perf doc."""
    text = Path(doc_path).read_text(encoding="utf-8")
    return [name for name in field_metadata() if f"`{name}`" not in text]


def doc_errors(doc_path: Path) -> list[str]:
    """Every problem as a human-readable string (the historic
    tools/check_limits_doc.py API)."""
    return [msg for _, _, msg in doc_problems(doc_path)]


@register
class LimitsDocRule(ProjectRule):
    id = "JTL301"
    name = "limits-doc"
    scopes = None
    rationale = (
        "ISSUE 4: the autotuner searches each KernelLimits field "
        "inside its documented safe range — a doc row missing or "
        "contradicting ops/limits.py field_meta drifts the enforced "
        "bounds from the documented ones.")
    hint = ("fix the 'KernelLimits reference' table in doc/perf.md: "
            "every field needs a row with its [envelope]/[arch]/"
            "[tunable] tag and its lo..hi safe range")
    doc_relpath = "doc/perf.md"

    def _applicable(self, root: Path) -> bool:
        """This rule is about THIS repo's doc: linting a foreign tree
        (`lint /tmp/scratch/f.py` — root resolves outside the harness
        repo) must not manufacture a 'doc not found' failure."""
        return (Path(root) / self.doc_relpath).is_file() \
            or (Path(root) / PACKAGE_NAME).is_dir()

    def covered_paths(self, root: Path) -> list[str]:
        return [self.doc_relpath] if self._applicable(root) else []

    def check_project(self, root: Path, ctx=None) -> list[Finding]:
        if not self._applicable(root):
            return []
        doc = Path(root) / self.doc_relpath
        if not doc.is_file():
            return [Finding(rule=self.id, path=self.doc_relpath, line=1,
                            message=f"{self.doc_relpath} not found under "
                                    f"{root} — the KernelLimits "
                                    f"reference table lives there",
                            hint=self.hint)]
        lines = doc.read_text(encoding="utf-8").splitlines()
        out = []
        for _field, line, msg in doc_problems(doc):
            ln = line or 1
            out.append(Finding(
                rule=self.id, path=self.doc_relpath, line=ln,
                message=msg, hint=self.hint,
                snippet=lines[ln - 1] if 0 < ln <= len(lines) else ""))
        return out
