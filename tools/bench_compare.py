#!/usr/bin/env python
"""Diff two bench records — make the perf trajectory machine-checkable.

The repo accumulates one `BENCH_rNN.json` per round (the driver's
wrapper around `python bench.py`'s single JSON line), and until now the
only way to see a regression was to eyeball them. This tool diffs two
records lane by lane and exits nonzero when any lane dropped more than
the threshold:

    python tools/bench_compare.py old.json new.json --threshold-pct 5

Accepted inputs, per file: the driver wrapper (`{"parsed": {...}}` —
the `parsed` record is used; a wrapper whose bench crashed carries no
parsed record and compares as degraded), or the raw bench line itself.

Lanes (higher-is-better events/s or ratios, plus the INVERTED_LANES
seconds where a RISE is the regression — fleet_p99_s): the top-level
throughput + vs_baseline, the corpus_sched / sparse / tuned / streaming
/ fleet lane rates, the long-history lanes keyed by op count, and
cache / padding health. A lane absent from the OLD record is reported as
skipped, never a failure (older rounds predate newer lanes) — but a
lane the old record HAS and the new record LACKS means the candidate
bench dropped a lane (a lane crash, a schema break): that exits
nonzero with a message NAMING the lane, never a silent skip or a
KeyError traceback. A DEGRADED
record (`degraded: true` or `value == 0` / backend none) is not a
perf measurement at all: the comparison is reported as not-comparable
and exits 0 — a round that measured nothing must not read as a 100%
regression.
Likewise two records whose backend-health states differ (`healthy` vs
`degraded`/`wedged`, the ISSUE 8 supervisor stamp): not-comparable,
with both states named. The kernel_phases deep-attribution fields
(flops / bytes / device_mem_peak) are compared as INFORMATIONAL lanes
— deltas printed, never regression-gated (gating stays on events/s).

Importable: `load_record(path)`, `compare(old, new, threshold_pct)` —
`tests/test_bench_compare.py` smokes both plus the exit-code contract.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path
from typing import Any, Optional

DEFAULT_THRESHOLD_PCT = 10.0
# Per-lane tighter ratchets (ISSUE 17): lanes named here gate at
# min(their pct, --threshold-pct) instead of the global default. The
# pod-scaling work bought scaling_eps_per_chip a big step up; a 5%
# leash keeps the win from quietly eroding back while leaving the
# noisier single-host lanes on the 10% default.
LANE_THRESHOLD_PCT: dict[str, float] = {
    "scaling_eps_per_chip": 5.0,
}

# (lane name, path into the record). All higher-is-better.
LANES: list[tuple[str, tuple]] = [
    ("throughput_eps", ("value",)),
    ("vs_baseline", ("vs_baseline",)),
    ("corpus_sched_eps", ("detail", "corpus_sched", "events_per_sec")),
    ("cache_hit_rate", ("cache_hit_rate",)),
    ("sparse_dense_eps", ("detail", "sparse", "dense_events_per_sec")),
    ("sparse_sparse_eps", ("detail", "sparse", "sparse_events_per_sec")),
    ("dedup_off_eps", ("detail", "dedup", "off_events_per_sec")),
    ("dedup_on_eps", ("detail", "dedup", "on_events_per_sec")),
    ("tuned_default_eps", ("detail", "tuned", "default_events_per_sec")),
    ("tuned_tuned_eps", ("detail", "tuned", "tuned_events_per_sec")),
    ("streaming_speedup", ("detail", "streaming", "speedup_total")),
    ("streaming_overlap", ("detail", "streaming", "overlap_ratio")),
    # Elle lane (ISSUE 11): the auto (tiled/batched) route's rates are
    # the gated headline.
    ("elle_txns_eps", ("detail", "elle", "txns_per_sec")),
    ("elle_events_eps", ("detail", "elle", "events_per_sec")),
    # Serve lane (ISSUE 13): the K-concurrent-clients aggregate
    # throughput is the gated headline; the latency quantiles and
    # batch-fill context ride the informational lanes below.
    ("serve_agg_eps", ("detail", "serve", "events_per_sec")),
    # Campaign lane (ISSUE 15): end-to-end scenario throughput and the
    # batched shrinker's candidate-recheck rate are the gated
    # headlines; the sequential-baseline speedup and replay wall are
    # ratios/lower-better context on the informational lanes below.
    ("campaign_specs_eps", ("detail", "campaign", "specs_per_sec")),
    ("campaign_shrink_cps",
     ("detail", "campaign", "shrink_checks_per_sec")),
    # Fleet lane (ISSUE 18): aggregate events/s at the measured open-
    # loop latency knee — serving capacity at acceptable latency.
    ("fleet_agg_eps", ("detail", "fleet", "agg_eps")),
    # Long-haul lane (ISSUE 20): out-of-core end-to-end checking
    # throughput over the spilled route.
    ("longhaul_eps", ("detail", "longhaul", "events_per_sec")),
]
# Gated lanes where LOWER is better (seconds at the knee): regression
# when the value RISES past the threshold. Kept separate from LANES so
# every entry there stays uniformly higher-is-better.
INVERTED_LANES: list[tuple[str, tuple]] = [
    # Fleet lane (ISSUE 18): p99 request latency at the knee rung.
    ("fleet_p99_s", ("detail", "fleet", "p99_s")),
    # Long-haul lane (ISSUE 20): the lane's peak RSS DELTA — the whole
    # out-of-core claim held to a ceiling; a rise past the leash means
    # the spill tier stopped bounding host memory.
    ("longhaul_peak_rss_mb", ("detail", "longhaul", "peak_rss_mb")),
]
# Scaling-efficiency lanes (ISSUE 12): events/s PER CHIP on the mesh
# and the per-chip-vs-single-device efficiency ratio, recorded by
# __graft_entry__.dryrun_multichip into MULTICHIP_rNN.json. Gated like
# every other lane — but ONLY when both records measured the SAME mesh
# shape: per-chip numbers from different meshes are not a
# like-for-like comparison (the shapes are named in the skip note).
SCALING_LANES: list[tuple[str, tuple]] = [
    ("scaling_eps_per_chip", ("scaling", "events_per_chip")),
    ("scaling_efficiency", ("scaling", "efficiency_vs_single")),
]
SCALING_MESH_PATH = ("scaling", "mesh_shape")
# Long-history lanes: seconds, LOWER is better — handled via inversion.
LONG_LANES_PATH = ("detail", "long_history")
# Deep-attribution lanes (ISSUE 8): the kernel_phases cost_analysis
# totals. INFORMATIONAL — a flops delta explains a throughput move
# (did the work change, or the speed?) but is not itself a regression;
# gating stays on events/s exactly as before.
INFO_LANES: list[tuple[str, tuple]] = [
    ("kernel_flops", ("kernel_phases", "flops")),
    ("kernel_bytes", ("kernel_phases", "bytes")),
    ("device_mem_peak", ("kernel_phases", "device_mem_peak")),
    # Dedup-lane configs rates (ISSUE 10): raw (dedup-off) and unique
    # (canonical) configs/s are reported but NEVER gated — pruning
    # legitimately moves them, and the lane's gate is events/s above.
    ("dedup_raw_configs", ("detail", "dedup", "raw_configs_per_sec")),
    ("dedup_unique_configs", ("detail", "dedup",
                              "unique_configs_per_sec")),
    ("dedup_ratio", ("detail", "dedup", "frontier_dedup_ratio")),
    # Elle lane single-shot arms (ISSUE 11): the dense and whole-graph
    # tiled closures are measured once each (no best-of), and the
    # speedup is a ratio of two measurements — informational; gating
    # stays on the auto route's best-of rates above.
    ("elle_speedup_vs_dense", ("detail", "elle", "speedup_vs_dense")),
    ("elle_dense_s", ("detail", "elle", "dense_s")),
    ("elle_tiled_s", ("detail", "elle", "tiled_s")),
    # Scaling lane context (ISSUE 12): the totals behind the gated
    # per-chip rate — a total-eps move explains a per-chip move.
    ("scaling_total_eps", ("scaling", "events_per_sec")),
    ("scaling_single_eps", ("scaling", "single_device_eps")),
    # Serve lane context (ISSUE 13): latency quantiles are LOWER-better
    # and load-shaped, the serial arm is a one-measurement baseline,
    # and batch fill / speedup are ratios of measurements — all
    # informational; the gate stays on serve_agg_eps above.
    ("serve_serial_eps", ("detail", "serve", "serial_events_per_sec")),
    ("serve_speedup", ("detail", "serve", "speedup_vs_serial")),
    ("serve_p50_ms", ("detail", "serve", "latency_p50_ms")),
    ("serve_p99_ms", ("detail", "serve", "latency_p99_ms")),
    ("serve_batch_fill", ("detail", "serve", "batch_fill_avg")),
    ("serve_cache_hit_rate", ("detail", "serve", "cache_hit_rate")),
    # Campaign lane context (ISSUE 15): the batched-vs-sequential
    # shrink speedup is a ratio of two measurements, the replay wall is
    # LOWER-better, and the banked count tracks what the fuzzer found
    # (legitimately moves with the spec mix) — all informational; the
    # gates stay on specs/s and shrink-checks/s above.
    ("campaign_shrink_speedup",
     ("detail", "campaign", "speedup_vs_sequential")),
    ("campaign_replay_wall_s", ("detail", "campaign", "replay_wall_s")),
    ("campaign_banked", ("detail", "campaign", "banked")),
    # Scaling-ledger lanes (ISSUE 16): loss-bucket seconds are load-
    # shaped and LOWER-better where they are loss at all — purely
    # informational context for the gated throughput lanes (a padding_s
    # move explains a corpus_sched_eps move; it is not itself a
    # regression). The schema gate is check_ledger_record below, run by
    # the tier-1 smoke test, never by the lane comparison.
    ("ledger_execute_s", ("ledger", "execute_s")),
    ("ledger_padding_s", ("ledger", "padding_s")),
    ("ledger_straggler_s", ("ledger", "straggler_s")),
    ("ledger_dispatch_gap_s", ("ledger", "dispatch_gap_s")),
    ("ledger_encode_s", ("ledger", "encode_s")),
    ("ledger_h2d_s", ("ledger", "h2d_s")),
    # Spill-tier ledger buckets (ISSUE 20): disk-seconds are load- and
    # mode-shaped (the force-spill bench lane pays them on purpose) —
    # informational context for the gated longhaul_eps /
    # longhaul_peak_rss_mb lanes above.
    ("ledger_spill_read_s", ("ledger", "spill_read_s")),
    ("ledger_spill_write_s", ("ledger", "spill_write_s")),
    ("longhaul_compress_ratio", ("longhaul", "compress_ratio")),
    ("longhaul_spill_bytes_written",
     ("longhaul", "spill_bytes_written")),
    ("sched_ledger_coverage",
     ("detail", "corpus_sched", "ledger", "coverage")),
    ("sched_ledger_overhead_pct",
     ("detail", "corpus_sched", "ledger_overhead_pct")),
    # Fleet lane context (ISSUE 18): the knee arrival rate is load-
    # shaped, per-replica fill and spillover move with membership and
    # health events, and the affine-vs-random deltas are ratios of two
    # measurements — all informational; the gates stay on
    # fleet_agg_eps / fleet_p99_s above.
    ("fleet_knee_rate_rps", ("detail", "fleet", "knee_rate_rps")),
    ("fleet_hit_rate_delta", ("detail", "fleet", "hit_rate_delta")),
    ("fleet_agg_eps_ratio", ("detail", "fleet", "agg_eps_ratio")),
    ("fleet_spillover", ("detail", "fleet", "spillover")),
    ("fleet_replica_fill_min", ("detail", "fleet", "replica_fill_min")),
    ("fleet_affine_eps", ("detail", "fleet", "affine", "agg_eps")),
    ("fleet_random_eps", ("detail", "fleet", "random", "agg_eps")),
]

# The zeros-never-absent `ledger` object every bench record carries
# (obs.ledger_stats) and the windowed attribution shape
# (obs.ledger.attribute) the corpus_sched lane / MULTICHIP_SCALING
# line carry. check_ledger_record validates both.
LEDGER_STATS_KEYS = ("launches", "encode_s", "h2d_s", "h2d_bytes",
                     "compile_s", "execute_s", "padding_s",
                     "straggler_s", "dispatch_gap_s",
                     "spill_read_s", "spill_write_s")
LEDGER_ATT_KEYS = ("wall_s", "coverage", "buckets")
LEDGER_MIN_COVERAGE = 0.95


def check_ledger_record(rec: dict) -> list[str]:
    """Schema gate for the scaling ledger (ISSUE 16), returning the
    list of problems (empty = pass). Every record — the degraded paths
    included — must carry the all-keys `ledger` object (zeros
    permitted, never absent); a NON-degraded record's windowed
    attributions (detail.corpus_sched.ledger, scaling.ledger) must
    additionally explain >= 95% of their measured wall."""
    problems: list[str] = []
    led = rec.get("ledger")
    if not isinstance(led, dict):
        return ["record omits the `ledger` object entirely"]
    for key in LEDGER_STATS_KEYS:
        if key not in led:
            problems.append(f"ledger object missing key {key!r}")
    if is_degraded(rec):
        return problems
    lane = _dig_raw(rec, ("detail", "corpus_sched"))
    if isinstance(lane, dict) and "ledger" not in lane:
        problems.append("non-degraded corpus_sched lane omits its "
                        "windowed ledger attribution")
    for where, att in (("detail.corpus_sched.ledger",
                        _dig_raw(rec, ("detail", "corpus_sched",
                                       "ledger"))),
                       ("scaling.ledger",
                        _dig_raw(rec, ("scaling", "ledger")))):
        if att is None:
            continue
        if not isinstance(att, dict):
            problems.append(f"{where} is not an attribution object")
            continue
        for key in LEDGER_ATT_KEYS:
            if key not in att:
                problems.append(f"{where} missing key {key!r}")
        cov = att.get("coverage")
        if isinstance(cov, (int, float)) and att.get("wall_s") \
                and cov < LEDGER_MIN_COVERAGE:
            problems.append(
                f"{where} buckets explain only {cov:.1%} of wall "
                f"(need >= {LEDGER_MIN_COVERAGE:.0%})")
    return problems


# The zeros-never-absent `fleet` object every bench record carries
# (obs.fleet_stats — router counters/gauges) and the measured lane
# shape (bench.bench_fleet) a NON-degraded
# record's detail.fleet must carry. check_fleet_record validates both,
# mirroring check_ledger_record's contract.
FLEET_STATS_KEYS = ("requests", "spillover", "replica_errors",
                    "rejected", "restarts", "replicas",
                    "replicas_ready")
FLEET_LANE_KEYS = ("replicas", "histories", "events", "affine",
                   "random", "hit_rate_delta", "agg_eps_ratio",
                   "knee_rate_rps", "agg_eps", "p99_s", "knee_rungs",
                   "spillover", "replica_fill", "replica_fill_min",
                   "invalid", "verdicts_identical")
FLEET_ARM_KEYS = ("wall_s", "agg_eps", "agg_rps", "p50_s", "p99_s",
                  "warm_p99_s", "hit_rate", "lookups")


def check_fleet_record(rec: dict) -> list[str]:
    """Schema gate for the fleet lane (ISSUE 18), returning the list
    of problems (empty = pass). Every record — the degraded paths
    included — must carry the all-keys `fleet` router object (zeros
    permitted, never absent); a NON-degraded record must additionally
    carry the measured detail.fleet lane with both routing arms and
    certified verdict parity."""
    problems: list[str] = []
    fl = rec.get("fleet")
    if not isinstance(fl, dict):
        return ["record omits the `fleet` object entirely"]
    for key in FLEET_STATS_KEYS:
        if key not in fl:
            problems.append(f"fleet object missing key {key!r}")
    if is_degraded(rec):
        return problems
    lane = _dig_raw(rec, ("detail", "fleet"))
    if not isinstance(lane, dict):
        problems.append("non-degraded record omits the detail.fleet "
                        "lane")
        return problems
    for key in FLEET_LANE_KEYS:
        if key not in lane:
            problems.append(f"detail.fleet missing key {key!r}")
    for arm in ("affine", "random"):
        obj = lane.get(arm)
        if not isinstance(obj, dict):
            continue   # absence already reported above
        for key in FLEET_ARM_KEYS:
            if key not in obj:
                problems.append(
                    f"detail.fleet.{arm} missing key {key!r}")
    if lane.get("verdicts_identical") is not True:
        problems.append("non-degraded fleet lane did not certify "
                        "verdict parity (verdicts_identical != true)")
    return problems


# The zeros-never-absent `longhaul` object every bench record carries
# (obs.longhaul_stats — spill-tier counters/gauges) and the measured
# lane shape (bench.bench_longhaul) a
# NON-degraded record's detail.longhaul must carry — the peak-RSS field
# in particular, since the inverted longhaul_peak_rss_mb gate reads it.
# check_longhaul_record validates both, mirroring check_fleet_record.
LONGHAUL_STATS_KEYS = ("spill_writes", "spill_reads",
                       "spill_bytes_written", "spill_bytes_read",
                       "spill_evictions", "cache_evictions",
                       "compress_ratio", "peak_rss_mb")
LONGHAUL_LANE_KEYS = ("events", "segments", "segments_run",
                      "survived", "dead_step", "max_frontier",
                      "escalations", "spilled", "wall_s",
                      "events_per_sec", "peak_rss_mb",
                      "rss_budget_mb", "rss_ok",
                      "verdicts_identical", "crosscheck_events")


def check_longhaul_record(rec: dict) -> list[str]:
    """Schema gate for the long-haul out-of-core lane (ISSUE 20),
    returning the list of problems (empty = pass). Every record — the
    degraded paths included — must carry the all-keys `longhaul`
    spill-stats object (zeros permitted, never absent); a NON-degraded
    record must additionally carry the measured detail.longhaul lane
    with the peak-RSS field, the ceiling verdict, and certified
    spilled-vs-in-RAM verdict parity."""
    problems: list[str] = []
    lh = rec.get("longhaul")
    if not isinstance(lh, dict):
        return ["record omits the `longhaul` object entirely"]
    for key in LONGHAUL_STATS_KEYS:
        if key not in lh:
            problems.append(f"longhaul object missing key {key!r}")
    if is_degraded(rec):
        return problems
    lane = _dig_raw(rec, ("detail", "longhaul"))
    if not isinstance(lane, dict):
        problems.append("non-degraded record omits the detail.longhaul "
                        "lane")
        return problems
    for key in LONGHAUL_LANE_KEYS:
        if key not in lane:
            problems.append(f"detail.longhaul missing key {key!r}")
    if lane.get("verdicts_identical") is not True:
        problems.append("non-degraded longhaul lane did not certify "
                        "spilled-vs-in-RAM verdict parity "
                        "(verdicts_identical != true)")
    if lane.get("rss_ok") is not True:
        problems.append("non-degraded longhaul lane exceeded its host "
                        "RSS budget (rss_ok != true)")
    return problems


def load_record(path: str | Path) -> dict:
    """A bench record from a BENCH_rNN.json driver wrapper or a raw
    bench output file. A wrapper without a parseable record (the bench
    crashed / emitted nothing) returns a degraded stand-in rather than
    raising, so comparisons against a dead round degrade gracefully."""
    data = json.loads(Path(path).read_text())
    if "parsed" in data or "cmd" in data:      # driver wrapper
        rec = data.get("parsed")
        if not isinstance(rec, dict):
            return {"value": 0, "degraded": True,
                    "error": "wrapper has no parsed bench record"}
        return rec
    return data


def is_degraded(rec: dict) -> bool:
    return bool(rec.get("degraded")) or rec.get("backend") == "none" \
        or not rec.get("value")


def _dig(rec: dict, path: tuple) -> Optional[float]:
    v = _dig_raw(rec, path)
    return float(v) if isinstance(v, (int, float)) else None


def _long_lanes(rec: dict) -> dict[str, float]:
    """{'long_<ops>_eps': ops/kernel_s} per long-history entry — the
    seconds inverted into a rate so every lane is higher-is-better."""
    entries = _dig_raw(rec, LONG_LANES_PATH)
    out: dict[str, float] = {}
    if not isinstance(entries, list):
        return out
    for e in entries:
        if isinstance(e, dict) and e.get("kernel_s") and e.get("ops"):
            out[f"long_{e['ops']}_eps"] = e["ops"] / e["kernel_s"]
    return out


def _dig_raw(rec: dict, path: tuple):
    cur: Any = rec
    for p in path:
        if not isinstance(cur, dict) or p not in cur:
            return None
        cur = cur[p]
    return cur


def compare(old: dict, new: dict,
            threshold_pct: float = DEFAULT_THRESHOLD_PCT) -> dict:
    """Per-lane deltas + the regression verdict.

    Returns {"comparable": bool, "reason": str|None,
             "lanes": [{lane, old, new, delta_pct, regression}],
             "regressions": [lane...], "missing": [lane...],
             "threshold_pct": float} — `missing` names lanes the old
    record measures but the new record lacks (a dropped lane is a
    failure, not a skip)."""
    out: dict = {"comparable": True, "reason": None, "lanes": [],
                 "regressions": [], "missing": [],
                 "threshold_pct": threshold_pct}
    for rec, name in ((old, "old"), (new, "new")):
        if is_degraded(rec):
            out["comparable"] = False
            out["reason"] = (f"{name} record is degraded "
                             f"({rec.get('error') or rec.get('backend') or 'value 0'}); "
                             f"not a perf measurement")
            return out
    # Backend-health gate (ISSUE 8): records taken under DIFFERENT
    # supervisor states (healthy vs degraded/wedged) measure different
    # machines — same contract as the degraded gate, with the states
    # named. Absent health fields (pre-ISSUE-8 rounds) compare as
    # before.
    old_state = (old.get("health") or {}).get("state")
    new_state = (new.get("health") or {}).get("state")
    if old_state and new_state and old_state != new_state:
        out["comparable"] = False
        out["reason"] = (f"backend health differs: old record ran "
                         f"{old_state}, new record ran {new_state}; "
                         f"not a like-for-like perf measurement")
        return out
    pairs = [(lane, _dig(old, path), _dig(new, path))
             for lane, path in LANES]
    old_long, new_long = _long_lanes(old), _long_lanes(new)
    pairs += [(lane, old_long.get(lane), new_long.get(lane))
              for lane in sorted(set(old_long) | set(new_long))]
    # Scaling lanes gate ONLY same-mesh records (ISSUE 12): per-chip
    # rates from different mesh shapes are not like-for-like. A shape
    # mismatch skips the scaling lanes with both shapes named — it
    # never silently gates, and never blocks the other lanes.
    old_mesh = _dig_raw(old, SCALING_MESH_PATH)
    new_mesh = _dig_raw(new, SCALING_MESH_PATH)
    if old_mesh is not None and new_mesh is not None \
            and old_mesh != new_mesh:
        for lane, _path in SCALING_LANES:
            out["lanes"].append({
                "lane": lane, "old": None, "new": None,
                "delta_pct": None, "regression": False, "skipped": True,
                "note": (f"mesh shape differs: old {old_mesh} vs new "
                         f"{new_mesh}; per-chip rates not comparable")})
    else:
        pairs += [(lane, _dig(old, path), _dig(new, path))
                  for lane, path in SCALING_LANES]
    for lane, o, n in pairs:
        if o is not None and n is None:
            # The baseline RECORDS this lane (a 0 measurement counts —
            # overlap can legitimately be 0); the candidate dropped it.
            out["lanes"].append({"lane": lane, "old": round(o, 4),
                                 "new": None, "delta_pct": None,
                                 "regression": False, "missing": True})
            out["missing"].append(lane)
            continue
        if o is None or o == 0:
            out["lanes"].append({"lane": lane, "old": o, "new": n,
                                 "delta_pct": None, "regression": False,
                                 "skipped": True})
            continue
        delta = (n - o) / o * 100.0
        lane_thr = min(threshold_pct,
                       LANE_THRESHOLD_PCT.get(lane, threshold_pct))
        reg = delta < -lane_thr
        row = {"lane": lane, "old": round(o, 4), "new": round(n, 4),
               "delta_pct": round(delta, 2), "regression": reg}
        if lane_thr != threshold_pct:
            row["threshold_pct"] = lane_thr
        out["lanes"].append(row)
        if reg:
            out["regressions"].append(lane)
    # Lower-is-better gated lanes (seconds at the knee): the SAME
    # missing/skip/threshold contract as above with the regression
    # direction flipped — a rise past the leash fails.
    for lane, path in INVERTED_LANES:
        o, n = _dig(old, path), _dig(new, path)
        if o is not None and n is None:
            out["lanes"].append({"lane": lane, "old": round(o, 4),
                                 "new": None, "delta_pct": None,
                                 "regression": False, "missing": True})
            out["missing"].append(lane)
            continue
        if o is None or o == 0:
            out["lanes"].append({"lane": lane, "old": o, "new": n,
                                 "delta_pct": None, "regression": False,
                                 "skipped": True})
            continue
        delta = (n - o) / o * 100.0
        lane_thr = min(threshold_pct,
                       LANE_THRESHOLD_PCT.get(lane, threshold_pct))
        reg = delta > lane_thr
        row = {"lane": lane, "old": round(o, 4), "new": round(n, 4),
               "delta_pct": round(delta, 2), "regression": reg,
               "lower_is_better": True}
        if lane_thr != threshold_pct:
            row["threshold_pct"] = lane_thr
        out["lanes"].append(row)
        if reg:
            out["regressions"].append(lane)
    # Informational lanes: deltas reported, never gated (a flops move
    # explains a throughput move; it is not itself one). Absent fields
    # (pre-ISSUE-8 records) skip silently in either direction.
    for lane, path in INFO_LANES:
        o, n = _dig(old, path), _dig(new, path)
        if o is None or n is None or o == 0:
            out["lanes"].append({"lane": lane, "old": o, "new": n,
                                 "delta_pct": None, "regression": False,
                                 "skipped": True, "informational": True})
            continue
        out["lanes"].append({"lane": lane, "old": round(o, 4),
                             "new": round(n, 4),
                             "delta_pct": round((n - o) / o * 100.0, 2),
                             "regression": False, "informational": True})
    return out


def main(argv=None) -> int:
    p = argparse.ArgumentParser(
        description="diff two bench records; nonzero exit on a lane "
                    "regression beyond the threshold")
    p.add_argument("old", help="baseline record (BENCH_rNN.json or raw)")
    p.add_argument("new", help="candidate record")
    p.add_argument("--threshold-pct", type=float,
                   default=DEFAULT_THRESHOLD_PCT,
                   help="fail when a lane drops more than this percent "
                        f"(default {DEFAULT_THRESHOLD_PCT:g})")
    p.add_argument("--json", action="store_true",
                   help="emit the comparison as one JSON object")
    args = p.parse_args(argv)
    try:
        old, new = load_record(args.old), load_record(args.new)
    except (OSError, ValueError) as e:
        print(f"error: {e}", file=sys.stderr)
        return 2
    res = compare(old, new, args.threshold_pct)
    if args.json:
        print(json.dumps(res, indent=2))
    else:
        if not res["comparable"]:
            print(f"not comparable: {res['reason']}")
        else:
            w = max((len(r["lane"]) for r in res["lanes"]), default=4)
            for r in res["lanes"]:
                if r.get("skipped"):
                    print(f"{r['lane']:<{w}}  (skipped: absent in one "
                          f"record)")
                elif r.get("missing"):
                    print(f"{r['lane']:<{w}}  {r['old']:>12g} -> "
                          f"(MISSING from new record)")
                else:
                    flag = "  << REGRESSION" if r["regression"] else ""
                    if r.get("informational"):
                        flag = "  (informational)"
                    print(f"{r['lane']:<{w}}  {r['old']:>12g} -> "
                          f"{r['new']:>12g}  {r['delta_pct']:+7.2f}%{flag}")
    if not res["comparable"]:
        return 0
    # Report EVERY failure class in one run — a missing lane must not
    # hide a concurrent threshold regression behind a second CI trip.
    if res["missing"]:
        print(f"FAIL: {len(res['missing'])} lane(s) present in "
              f"{args.old} but missing from {args.new}: "
              f"{', '.join(res['missing'])} — the candidate bench "
              f"dropped a lane (lane crash / schema break)",
              file=sys.stderr)
    if res["regressions"]:
        print(f"FAIL: {len(res['regressions'])} lane(s) regressed more "
              f"than {args.threshold_pct:g}%: "
              f"{', '.join(res['regressions'])}", file=sys.stderr)
    if res["missing"] or res["regressions"]:
        return 1
    print(f"ok: no lane regressed more than {args.threshold_pct:g}%")
    return 0


if __name__ == "__main__":
    sys.exit(main())
