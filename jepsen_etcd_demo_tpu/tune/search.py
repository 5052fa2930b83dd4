"""Autotuner search — bounded coordinate descent with successive halving.

The knob space is small (~a dozen searched fields) and each measurement
is a real kernel run, so the search optimizes for MEASUREMENT ECONOMY,
not search-space cleverness:

  * coordinate descent — knobs are searched one at a time, in group
    order, each against the best values already chosen for earlier knobs
    (the groups are nearly independent by construction: chunking, sparse
    crossover, bucketing, pipelining touch different code paths);
  * successive halving per knob — every candidate is timed once, the
    slower half is dropped, survivors are re-timed (best-of accumulates
    across rounds) until one remains, so obvious losers cost one cheap
    measurement and the final winner is backed by several;
  * a wall-clock budget — checked before every measurement; expiry keeps
    the defaults for everything not yet measured (a partial profile is
    valid — un-searched knobs simply stay at their dataclass defaults);
  * safety envelopes — candidates come from each field's safe range
    (ops/limits.py field metadata); [envelope] fields are additionally
    clamped to the conservative side of their default, so the tuner can
    never produce a profile that probes PAST a kill threshold the
    default encodes;
  * a noise guard — the winner must beat the default by >3% or the
    default is kept: a tuned profile should encode real measurements,
    not scheduler jitter.

Probe timings and chosen values land in obs gauges
(`tune.probe_s.<knob>`, `tune.chosen.<knob>`) when a telemetry capture
is active, and in the returned record (persisted into the profile's
`probes` section for provenance).
"""

from __future__ import annotations

import math
import os
import time

from .. import obs
from ..ops.limits import env_var, field_meta
from .probes import PROBES, KNOB_PINS, ProbeContext, ProbeUnavailable

# Winner must be at least this much faster than the default to displace
# it (fraction of the default's best time).
NOISE_MARGIN = 0.03

# Multiplicative ladder around the default for knobs whose probe offers
# no geometry-aware candidates.
LADDER = (0.25, 0.5, 1.0, 2.0, 4.0)


def default_knobs() -> list[str]:
    """Every field with a probe group — the `jepsen-tpu tune` default."""
    return [name for name, m in field_meta().items() if m.get("group")]


def resolve_knobs(spec: str | None) -> list[str]:
    """--knobs value -> field list: comma-separated field OR group names
    (unknown names raise with the valid vocabulary)."""
    if not spec:
        return default_knobs()
    meta = field_meta()
    by_group: dict[str, list[str]] = {}
    for name, m in meta.items():
        if m.get("group"):
            by_group.setdefault(m["group"], []).append(name)
    out: list[str] = []
    for tok in (t.strip() for t in spec.split(",")):
        if not tok:
            continue
        if tok in by_group:
            out.extend(n for n in by_group[tok] if n not in out)
        elif tok in meta:
            if meta[tok].get("group") is None:
                raise ValueError(
                    f"knob {tok!r} has no probe group (kind "
                    f"{meta[tok]['kind']}); tunable knobs: "
                    f"{', '.join(default_knobs())}")
            if tok not in out:
                out.append(tok)
        else:
            raise ValueError(
                f"unknown knob/group {tok!r}; knobs: "
                f"{', '.join(default_knobs())}; groups: "
                f"{', '.join(sorted(by_group))}")
    return out


def candidates_for(name: str, probe) -> list[int]:
    """Candidate values: the probe's geometry-aware list when it offers
    one, else a multiplicative ladder around the default — always
    clamped to the safe range, and for [envelope] fields to the
    conservative side of the default."""
    m = field_meta()[name]
    default = m["default"]
    lo, hi = m["range"]
    cons = m.get("conservative")
    if cons == "down":
        hi = min(hi, default)
    elif cons == "up":
        lo = max(lo, default)
    raw = None
    if hasattr(probe, "candidates"):
        raw = probe.candidates(name)
    if raw is None:
        raw = [int(default * f) for f in LADDER]
    vals = sorted({min(hi, max(lo, int(v))) for v in raw} | {default})
    return vals


def _measure(probe, knob: str, value: int, chosen: dict[str, int]) -> float:
    overrides = dict(chosen)
    overrides.update(KNOB_PINS.get(knob, {}))
    overrides[knob] = value
    return probe.measure(knob, overrides)


def _search_knob(probe, knob: str, chosen: dict[str, int],
                 deadline: float) -> dict:
    """Successive halving over one knob's candidates; returns the probe
    record ({chosen, default, candidates, best_s, seconds} or a skip)."""
    m = field_meta()[knob]
    default = m["default"]
    cands = candidates_for(knob, probe)
    best_s: dict[int, float] = {}
    t0 = time.perf_counter()
    # The DEFAULT is measured first: if the budget expires mid-knob the
    # noise guard must still have its baseline — a winner may never
    # displace a default that was not itself timed (the documented
    # "expiry keeps defaults" contract).
    live = [default] + [v for v in cands if v != default]
    measured = 0
    while live:
        for v in list(live):
            if time.perf_counter() > deadline:
                # Budget expired mid-knob: candidates measured so far
                # still count, unmeasured ones drop out.
                live = [x for x in live if x in best_s]
                break
            s = _measure(probe, knob, v, chosen)
            best_s[v] = min(best_s.get(v, math.inf), s)
            measured += 1
        if len(live) <= 1 or time.perf_counter() > deadline:
            break
        live = sorted(live, key=lambda v: best_s.get(v, math.inf))
        live = live[: max(1, math.ceil(len(live) / 2))]
        if len(live) == 1:
            break
    record = {
        "default": default,
        "candidates": cands,
        "best_s": {str(v): round(s, 5) for v, s in sorted(best_s.items())},
        "measurements": measured,
        "seconds": round(time.perf_counter() - t0, 3),
    }
    if default not in best_s:
        record["skipped"] = "budget exhausted before the default baseline"
        record["chosen"] = default
        return record
    winner = min(best_s, key=best_s.get)
    if winner != default \
            and best_s[winner] >= best_s[default] * (1.0 - NOISE_MARGIN):
        winner = default       # noise guard: the default keeps ties
    record["chosen"] = winner
    met = obs.get_metrics()
    # jtlint: disable=JTL107 -- bounded family: knob iterates the fixed
    # tunable-field set of ops/limits.py field_meta(); exported as one
    # labeled Prometheus family (obs/export.py LABELED_FAMILIES).
    met.gauge(f"tune.probe_s.{knob}").set(record["seconds"])
    # jtlint: disable=JTL107 -- bounded family: same knob set as above.
    met.gauge(f"tune.chosen.{knob}").set(winner)
    met.counter("tune.measurements").add(measured)
    return record


def search(knobs: list[str] | None = None, budget_s: float = 60.0,
           repeats: int = 2, scale: float = 1.0, model=None) -> dict:
    """Measure the knob space within `budget_s` seconds of wall clock;
    returns {"values": {field: tuned}, "probes": {field: record},
    "skipped": {field/group: reason}, "spent_s": float}. `values` holds
    only fields whose winner differs from the default — the persisted
    profile stays minimal and the hash stays "default" when nothing won.

    The active limits profile is restored on exit no matter what: the
    probes swap profiles via set_limits for every measurement."""
    from ..ops import limits as limits_mod

    meta = field_meta()
    knobs = list(knobs) if knobs is not None else default_knobs()
    ctx = ProbeContext(model=model, scale=scale, repeats=repeats)
    deadline = time.perf_counter() + budget_s
    t_start = time.perf_counter()

    by_group: dict[str, list[str]] = {}
    skipped: dict[str, str] = {}
    for name in knobs:
        m = meta.get(name)
        if m is None or not m.get("group"):
            skipped[name] = "no probe group"
            continue
        if os.environ.get(env_var(name)) is not None:
            # An env pin wins over any tuned value (precedence) — probing
            # it would measure a knob the profile can never move.
            skipped[name] = f"pinned by {env_var(name)}"
            continue
        by_group.setdefault(m["group"], []).append(name)

    prev_set = limits_mod._SET   # read-only peek; restore goes through
    #                              the public set_limits below
    values: dict[str, int] = {}
    probes_out: dict[str, dict] = {}
    try:
        for group, cls in PROBES.items():
            names = by_group.get(group)
            if not names:
                continue
            if time.perf_counter() > deadline:
                for n in names:
                    skipped[n] = "budget exhausted"
                continue
            obs.get_tracer().event("tune.probe_group", group=group,
                                   knobs=",".join(names))
            try:
                probe = cls(ctx)
            except Exception as e:
                # ProbeUnavailable (pallas off-TPU) or any fixture
                # failure: the GROUP is skipped, the run continues —
                # 'recorded as skipped, never an error'. A tune run must
                # never discard hours of already-measured groups because
                # one fixture couldn't build.
                for n in names:
                    skipped[n] = str(e) or type(e).__name__
                continue
            for knob in names:
                try:
                    rec = _search_knob(probe, knob, values, deadline)
                except Exception as e:
                    # A measurement blowing up mid-knob (e.g. a candidate
                    # geometry Mosaic refuses to compile) skips THIS knob
                    # and keeps its default; earlier winners survive to
                    # be persisted.
                    skipped[knob] = f"probe error: {e}"
                    continue
                probes_out[knob] = rec
                if rec["chosen"] != rec["default"]:
                    values[knob] = rec["chosen"]
    finally:
        limits_mod.set_limits(prev_set)
    return {
        "values": values,
        "probes": probes_out,
        "skipped": skipped,
        "spent_s": round(time.perf_counter() - t_start, 3),
        "budget_s": budget_s,
        "scale": scale,
        "repeats": ctx.repeats,
    }
