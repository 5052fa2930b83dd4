"""tune — profile-guided autotuner for the KernelLimits knob space.

ISSUE 4 tentpole. The hot loop's speed is governed by ~20 `KernelLimits`
knobs whose defaults encode exactly one deployment;
`ops/calibrate.py` already measured ONE of them (the oracle crossover)
per backend and persisted it. This package generalizes that pattern —
the same profile-guided shape XLA and Triton autotuning use:

  * probes.py   — deterministic microbenchmarks per knob group, timing
                  the real production code paths under candidate limits
  * search.py   — bounded coordinate descent + successive halving inside
                  each field's safe range, under a wall-clock budget
  * profile.py  — the versioned on-disk profile store, keyed by
                  (jax backend, device kind, device count), auto-loaded
                  by `limits()` with precedence
                  env > set_limits() > tuned profile > default

Entry points: `jepsen-tpu tune` (cli/main.py), `run_tune()` below for
embedding, `tools/print_profile.py` for the resolved view.
"""

from __future__ import annotations

from . import profile
from .search import default_knobs, resolve_knobs, search

__all__ = ["default_knobs", "profile", "resolve_knobs", "run_tune",
           "search"]


def run_tune(knobs: list[str] | None = None, budget_s: float = 60.0,
             repeats: int = 2, scale: float = 1.0, model=None,
             dry_run: bool = False, calibrate_too: bool = True) -> dict:
    """Measure, choose, persist. Returns the summary record the CLI
    prints: the search output plus the persisted profile's identity
    (path/hash/platform) — or `"dry_run": True` with nothing written.

    `calibrate_too` folds a fresh oracle-crossover calibration
    (ops/calibrate.py) into the same profile entry, so one `tune` run
    produces the COMPLETE per-machine measurement set."""
    res = search(knobs=knobs, budget_s=budget_s, repeats=repeats,
                 scale=scale, model=model)
    out = dict(res)
    out["platform"] = profile.platform_key(require_jax_loaded=False) \
        or "unknown"
    if dry_run:
        out["dry_run"] = True
        return out
    calibration = None
    if calibrate_too:
        from dataclasses import asdict

        from ..ops import calibrate

        cal = calibrate.measure()
        calibrate.set_calibration(cal)
        calibration = asdict(cal)
        out["calibration"] = calibration
    path = profile.save_entry(res["values"], probes=res["probes"],
                              budget_s=budget_s, calibration=calibration)
    out["profile_path"] = path
    out["profile_hash"] = profile.profile_hash()
    out["dry_run"] = False
    return out
