"""jtlint — a JAX-aware static analysis suite for this harness.

Five PRs in, the expensive bug classes moved from checker math into
harness hygiene: recompile storms from unstable jit-cache keys, donated
buffers read after donation, host syncs hidden in chunk loops, and the
thread/event-loop races ADVICE r5 and the round-5 bench already bit us with
(ISSUE 7). Every one is statically detectable; this package detects
them — AST-only, jax-free, fast enough for tier-1.

Library API:

    from jepsen_etcd_demo_tpu import analysis
    result = analysis.run_lint(["jepsen_etcd_demo_tpu"])
    result.findings            # unbaselined Finding rows
    analysis.all_rules()       # id -> rule (docs, hints, scopes)

CLI: ``jepsen-tpu lint [--strict] [paths...]`` (analysis/cli.py), also
``python -m jepsen_etcd_demo_tpu.analysis``. Rule reference, the
suppression syntax, and how to add a rule: doc/analysis.md.
"""

from .baseline import Baseline, DEFAULT_BASELINE
from .core import (CONCURRENCY_SCOPES, KERNEL_SCOPES, ModuleSource,
                   ProjectRule, Rule, all_rules, resolve_rules)
from .engine import LintResult, find_repo_root, run_lint
from .findings import Finding, fingerprint_findings, format_json, \
    format_sarif, format_text
from .flow import (CONTRACTS_FILE, FlowIndex, extract_contracts,
                   render_contracts)

__all__ = [
    "Baseline", "DEFAULT_BASELINE", "CONCURRENCY_SCOPES",
    "KERNEL_SCOPES", "ModuleSource", "ProjectRule", "Rule", "all_rules",
    "resolve_rules", "LintResult", "find_repo_root", "run_lint",
    "Finding", "fingerprint_findings", "format_json", "format_sarif",
    "format_text", "CONTRACTS_FILE", "FlowIndex", "extract_contracts",
    "render_contracts",
]
