"""Smoke for tools/bench_compare.py (ISSUE 5 satellite): the perf
trajectory is machine-checkable — per-lane deltas, regression threshold,
nonzero exit on a drop, graceful not-comparable on degraded records."""

from __future__ import annotations

import json
import sys
from pathlib import Path

TOOLS = Path(__file__).resolve().parent.parent / "tools"
sys.path.insert(0, str(TOOLS))

import bench_compare  # noqa: E402


def _record(eps: float, sched_eps: float = 5000.0,
            stream_speedup: float = 1.4, flops: float = 2.0e9,
            health: str = "healthy") -> dict:
    return {
        "metric": "wgl_check_throughput", "value": eps,
        "unit": "history-events/sec", "vs_baseline": 12.0,
        "cache_hit_rate": 1.0,
        "kernel_phases": {"compile_s": 1.0, "execute_s": 2.0,
                          "encode_s": 0.5, "frontier_peak": 64,
                          "flops": flops, "bytes": 4.0e8,
                          "device_mem_peak": 0,
                          "profile_hash": "default"},
        "health": {"state": health, "last_transition": None},
        "degraded": False, "backend": "cpu",
        "detail": {
            "corpus_sched": {"events_per_sec": sched_eps},
            "sparse": {"dense_events_per_sec": 900.0,
                       "sparse_events_per_sec": 1100.0},
            "tuned": {"default_events_per_sec": 4000.0,
                      "tuned_events_per_sec": 4400.0},
            "streaming": {"speedup_total": stream_speedup,
                          "overlap_ratio": 0.5},
            "long_history": [{"ops": 1000, "kernel_s": 0.5},
                             {"ops": 10000, "kernel_s": 4.0}],
        },
    }


def test_no_regression_within_threshold():
    res = bench_compare.compare(_record(1000.0), _record(950.0),
                                threshold_pct=10.0)
    assert res["comparable"] is True
    assert res["regressions"] == []
    by_lane = {r["lane"]: r for r in res["lanes"]}
    assert by_lane["throughput_eps"]["delta_pct"] == -5.0
    assert by_lane["long_1000_eps"]["regression"] is False


def test_regression_detected_beyond_threshold():
    res = bench_compare.compare(_record(1000.0), _record(800.0),
                                threshold_pct=10.0)
    assert "throughput_eps" in res["regressions"]
    by_lane = {r["lane"]: r for r in res["lanes"]}
    assert by_lane["throughput_eps"]["delta_pct"] == -20.0
    assert by_lane["throughput_eps"]["regression"] is True
    # Only the dropped lane flags; flat lanes stay green.
    assert by_lane["corpus_sched_eps"]["regression"] is False


def test_long_history_lanes_invert_seconds():
    """Long lanes are recorded in seconds (lower is better); the
    comparison must invert them into rates so a SLOWER record reads as
    a drop, not a gain."""
    slow = _record(1000.0)
    slow["detail"]["long_history"] = [{"ops": 1000, "kernel_s": 1.0}]
    res = bench_compare.compare(_record(1000.0), slow, threshold_pct=10.0)
    assert "long_1000_eps" in res["regressions"]


def test_missing_lane_is_skipped_not_failed():
    old = _record(1000.0)
    del old["detail"]["streaming"]   # older round predates the lane
    res = bench_compare.compare(old, _record(1000.0), threshold_pct=10.0)
    by_lane = {r["lane"]: r for r in res["lanes"]}
    assert by_lane["streaming_speedup"].get("skipped") is True
    assert res["regressions"] == []


def test_lane_dropped_from_new_record_fails_by_name(tmp_path, capsys):
    """ISSUE 7 satellite: a lane the baseline measures but the candidate
    lacks is a FAILURE naming the lane (a lane crash / schema break),
    not a silent skip — and never a KeyError traceback."""
    old, new = _record(1000.0), _record(1000.0)
    del new["detail"]["streaming"]
    res = bench_compare.compare(old, new, threshold_pct=10.0)
    assert res["comparable"] is True
    assert set(res["missing"]) == {"streaming_speedup",
                                   "streaming_overlap"}
    by_lane = {r["lane"]: r for r in res["lanes"]}
    assert by_lane["streaming_speedup"].get("missing") is True
    assert by_lane["streaming_speedup"].get("skipped") is None
    # The CLI exits nonzero with the named-lane message on stderr.
    po, pn = tmp_path / "old.json", tmp_path / "new.json"
    po.write_text(json.dumps(old))
    pn.write_text(json.dumps(new))
    assert bench_compare.main([str(po), str(pn)]) == 1
    captured = capsys.readouterr()
    assert "streaming_speedup" in captured.err
    assert "missing from" in captured.err
    assert "MISSING" in captured.out


def test_missing_lane_and_regression_both_reported(tmp_path, capsys):
    """One run reports BOTH failure classes — a dropped lane must not
    hide a concurrent threshold regression behind a second CI trip
    (review finding)."""
    old, new = _record(1000.0), _record(700.0)
    del new["detail"]["streaming"]
    po, pn = tmp_path / "o.json", tmp_path / "n.json"
    po.write_text(json.dumps(old))
    pn.write_text(json.dumps(new))
    assert bench_compare.main([str(po), str(pn),
                               "--threshold-pct", "10"]) == 1
    err = capsys.readouterr().err
    assert "streaming_speedup" in err and "regressed" in err


def test_zero_valued_lane_dropped_still_fails():
    """A baseline lane recorded at 0 (overlap_ratio can legitimately be
    0) still counts as MEASURED: the candidate dropping it is a
    missing-lane failure, not a skip (review finding)."""
    old, new = _record(1000.0), _record(1000.0)
    old["detail"]["streaming"]["overlap_ratio"] = 0.0
    del new["detail"]["streaming"]
    res = bench_compare.compare(old, new)
    assert "streaming_overlap" in res["missing"]


def test_long_history_lane_dropped_also_fails(tmp_path):
    """The inversion-derived long lanes get the same missing-lane
    treatment as the fixed table."""
    old, new = _record(1000.0), _record(1000.0)
    new["detail"]["long_history"] = [{"ops": 1000, "kernel_s": 0.5}]
    res = bench_compare.compare(old, new)
    assert res["missing"] == ["long_10000_eps"]


def test_flops_bytes_lanes_are_informational_only():
    """ISSUE 8 satellite: the kernel_phases deep-attribution fields
    compare as INFORMATIONAL lanes — deltas reported, never gated. A
    50% flops drop alone must exit 0."""
    res = bench_compare.compare(_record(1000.0),
                                _record(1000.0, flops=1.0e9),
                                threshold_pct=10.0)
    assert res["comparable"] is True and res["regressions"] == []
    by_lane = {r["lane"]: r for r in res["lanes"]}
    assert by_lane["kernel_flops"]["informational"] is True
    assert by_lane["kernel_flops"]["delta_pct"] == -50.0
    assert by_lane["kernel_flops"]["regression"] is False
    assert by_lane["kernel_bytes"]["delta_pct"] == 0.0
    # device_mem_peak is 0 on CPU records: skipped, not divided by.
    assert by_lane["device_mem_peak"].get("skipped") is True


def test_flops_absent_in_old_record_skips_silently():
    """Pre-ISSUE-8 records have no flops field — the informational lane
    skips without joining `missing` (it is not a measured perf lane)."""
    old = _record(1000.0)
    del old["kernel_phases"]
    res = bench_compare.compare(old, _record(1000.0))
    by_lane = {r["lane"]: r for r in res["lanes"]}
    assert by_lane["kernel_flops"].get("skipped") is True
    assert "kernel_flops" not in res["missing"]


def test_health_state_difference_not_comparable(tmp_path, capsys):
    """ISSUE 8 satellite: records taken under different supervisor
    states (healthy vs degraded) measure different machines — reported
    not-comparable with BOTH states named, exit 0 (the degraded-record
    contract)."""
    res = bench_compare.compare(_record(1000.0),
                                _record(400.0, health="degraded"))
    assert res["comparable"] is False
    assert "healthy" in res["reason"] and "degraded" in res["reason"]
    po, pn = tmp_path / "o.json", tmp_path / "n.json"
    po.write_text(json.dumps(_record(1000.0)))
    pn.write_text(json.dumps(_record(400.0, health="degraded")))
    assert bench_compare.main([str(po), str(pn)]) == 0
    assert "not comparable" in capsys.readouterr().out


def test_health_absent_in_one_record_still_compares():
    """A pre-ISSUE-8 record without the health stamp compares exactly
    as before — the gate needs BOTH states to disagree."""
    old = _record(1000.0)
    del old["health"]
    res = bench_compare.compare(old, _record(950.0, health="healthy"))
    assert res["comparable"] is True


def test_degraded_record_not_comparable():
    """A round without a measurement (value 0 / degraded) must not read
    as a 100% regression."""
    dead = {"metric": "wgl_check_throughput", "value": 0,
            "vs_baseline": 0, "degraded": True, "backend": "none",
            "error": "JAX backend unusable"}
    res = bench_compare.compare(_record(1000.0), dead)
    assert res["comparable"] is False
    assert "degraded" in res["reason"]


def test_cli_exit_codes(tmp_path, capsys):
    old, new = tmp_path / "old.json", tmp_path / "new.json"
    old.write_text(json.dumps(_record(1000.0)))
    new.write_text(json.dumps(_record(800.0)))
    assert bench_compare.main([str(old), str(new),
                               "--threshold-pct", "10"]) == 1
    assert bench_compare.main([str(old), str(new),
                               "--threshold-pct", "30"]) == 0
    out = capsys.readouterr().out
    assert "throughput_eps" in out

    # Driver-wrapper inputs (BENCH_rNN.json shape) unwrap via "parsed";
    # a degraded new record compares as not-comparable, exit 0.
    wrapped = tmp_path / "wrapped.json"
    wrapped.write_text(json.dumps({
        "n": 5, "cmd": "python bench.py", "rc": 1,
        "parsed": {"metric": "wgl_check_throughput", "value": 0,
                   "vs_baseline": 0,
                   "error": "JAX backend unusable"}}))
    assert bench_compare.main([str(old), str(wrapped)]) == 0
    assert "not comparable" in capsys.readouterr().out


def test_wrapper_record_loads():
    """A driver-wrapper record (the {"n","cmd","rc","tail","parsed"}
    shape) unwraps to its bench record."""
    path = Path(__file__).resolve().parent / "data" / "bench_wrapper.json"
    rec = bench_compare.load_record(path)
    assert rec["metric"] == "wgl_check_throughput"
    assert rec["value"] == 1000.0


# -- scaling-efficiency lane (ISSUE 12) ------------------------------------

def _scaling_record(per_chip: float, efficiency: float = 0.8,
                    mesh_shape: dict | None = None) -> dict:
    """A MULTICHIP_rNN.json `parsed` record (dryrun_multichip shape)."""
    return {
        "value": per_chip, "backend": "cpu",
        "scaling": {"mesh_shape": mesh_shape or {"batch": 8},
                    "n_devices": 8, "events": 2677,
                    "events_per_sec": per_chip * 8,
                    "events_per_chip": per_chip,
                    "single_device_eps": per_chip / efficiency,
                    "efficiency_vs_single": efficiency},
    }


def test_scaling_lane_gated_like_the_others():
    """Events/s-per-chip and the efficiency ratio regression-gate: a
    pod sharding-overhead blowup fails CI exactly like a single-chip
    kernel regression."""
    res = bench_compare.compare(_scaling_record(5000.0, 0.8),
                                _scaling_record(3000.0, 0.45),
                                threshold_pct=10.0)
    assert "scaling_eps_per_chip" in res["regressions"]
    assert "scaling_efficiency" in res["regressions"]
    by_lane = {r["lane"]: r for r in res["lanes"]}
    assert by_lane["scaling_eps_per_chip"]["delta_pct"] == -40.0
    assert by_lane["scaling_total_eps"]["informational"] is True


def test_scaling_lane_within_threshold_passes():
    res = bench_compare.compare(_scaling_record(5000.0),
                                _scaling_record(4800.0),
                                threshold_pct=10.0)
    assert res["regressions"] == []


def test_scaling_mesh_shape_mismatch_skips_with_note():
    """Per-chip rates from DIFFERENT mesh shapes are not like-for-like:
    the scaling lanes skip with both shapes named instead of gating —
    and the rest of the comparison still runs."""
    res = bench_compare.compare(
        _scaling_record(5000.0, mesh_shape={"batch": 8}),
        _scaling_record(2000.0, mesh_shape={"host": 2, "batch": 8}),
        threshold_pct=10.0)
    assert res["comparable"] is True
    assert "scaling_eps_per_chip" not in res["regressions"]
    by_lane = {r["lane"]: r for r in res["lanes"]}
    lane = by_lane["scaling_eps_per_chip"]
    assert lane.get("skipped") is True
    assert "'batch': 8" in lane["note"] and "'host': 2" in lane["note"]


def test_scaling_lane_dropped_from_new_record_fails():
    """A MULTICHIP round that stops measuring scaling is a dropped
    lane — named failure, same policy as every other lane."""
    old, new = _scaling_record(5000.0), _scaling_record(5000.0)
    del new["scaling"]
    res = bench_compare.compare(old, new)
    assert "scaling_eps_per_chip" in res["missing"]


def test_multichip_r06_record_loads_and_self_compares():
    """The committed MULTICHIP_r06.json carries the per-chip numbers
    and the mesh shape; it loads through the driver-wrapper path and
    self-compares clean."""
    repo = Path(__file__).resolve().parent.parent
    rec = bench_compare.load_record(repo / "MULTICHIP_r06.json")
    scal = rec["scaling"]
    assert scal["events_per_chip"] > 0
    assert scal["mesh_shape"] == {"batch": 8}
    assert 0 < scal["efficiency_vs_single"] <= 8
    res = bench_compare.compare(rec, rec)
    assert res["comparable"] is True and res["regressions"] == []


# -- scaling-ledger schema gate (ISSUE 16) ----------------------------------

def _ledger_stats(**over) -> dict:
    base = {k: 0.0 for k in bench_compare.LEDGER_STATS_KEYS}
    base.update(over)
    return base


def _att(coverage: float, wall_s: float = 1.0) -> dict:
    return {"wall_s": wall_s, "coverage": coverage,
            "buckets": {"execute_s": wall_s * coverage}}


def test_ledger_lanes_are_informational_never_gated():
    """Loss-bucket seconds are load-dependent diagnostics: a 10x
    padding_s jump annotates the comparison but never fails it."""
    old, new = _record(1000.0), _record(1000.0)
    old["ledger"] = _ledger_stats(execute_s=5.0, padding_s=0.1)
    new["ledger"] = _ledger_stats(execute_s=5.0, padding_s=3.0)
    res = bench_compare.compare(old, new, threshold_pct=10.0)
    assert res["regressions"] == []
    by_lane = {r["lane"]: r for r in res["lanes"]}
    assert by_lane["ledger_padding_s"]["informational"] is True


def test_check_ledger_record_requires_object_on_every_record():
    rec = _record(1000.0)
    assert bench_compare.check_ledger_record(rec) == \
        ["record omits the `ledger` object entirely"]
    rec["ledger"] = _ledger_stats()
    del rec["ledger"]["padding_s"]
    assert any("padding_s" in p
               for p in bench_compare.check_ledger_record(rec))


def test_check_ledger_record_degraded_needs_only_zeros():
    """The degraded paths owe the zeros object, nothing more — no
    windowed attribution exists when no lane ran."""
    rec = {"value": 0, "degraded": True, "backend": "none",
           "ledger": _ledger_stats()}
    assert bench_compare.check_ledger_record(rec) == []


def test_check_ledger_record_gates_low_coverage_and_omission():
    """A non-degraded record whose corpus_sched lane omits the windowed
    attribution, or whose buckets explain < 95% of the measured wall,
    fails the schema gate by name."""
    rec = _record(1000.0)
    rec["ledger"] = _ledger_stats()
    probs = bench_compare.check_ledger_record(rec)
    assert any("omits its windowed ledger attribution" in p
               for p in probs)
    rec["detail"]["corpus_sched"]["ledger"] = _att(coverage=0.80)
    probs = bench_compare.check_ledger_record(rec)
    assert any("explain only 80.0%" in p for p in probs)
    rec["detail"]["corpus_sched"]["ledger"] = _att(coverage=0.97)
    assert bench_compare.check_ledger_record(rec) == []
    # The MULTICHIP surface is held to the same bar.
    rec["scaling"] = {"ledger": _att(coverage=0.5)}
    assert any("scaling.ledger" in p
               for p in bench_compare.check_ledger_record(rec))


# ---------------------------------------------------------------------
# fleet lane gates (ISSUE 18)


def _fleet_arm(eps: float, hit_rate: float, p99: float = 0.5) -> dict:
    return {"wall_s": 1.0, "agg_eps": eps, "agg_rps": eps / 100.0,
            "p50_s": p99 / 2, "p99_s": p99, "warm_p99_s": p99 / 2,
            "hit_rate": hit_rate, "lookups": 64}


def _fleet_lane(agg_eps: float = 5000.0, p99_s: float = 0.4) -> dict:
    return {
        "replicas": 2, "histories": 24, "events": 2400,
        "affine": _fleet_arm(agg_eps, 0.9, p99_s),
        "random": _fleet_arm(agg_eps * 0.7, 0.5, p99_s * 1.5),
        "hit_rate_delta": 0.4, "agg_eps_ratio": 1.43,
        "knee_rate_rps": 40.0, "agg_eps": agg_eps, "p99_s": p99_s,
        "knee_rungs": [{"offered_rps": 20.0, "agg_rps": 19.0,
                        "agg_eps": agg_eps, "p99_s": p99_s}],
        "spillover": 0, "replica_fill": {"r0": 12, "r1": 12},
        "replica_fill_min": 12, "invalid": 3,
        "verdicts_identical": True,
    }


def _fleet_stats(**over) -> dict:
    base = {k: 0 for k in bench_compare.FLEET_STATS_KEYS}
    base.update(over)
    return base


def _fleet_record(agg_eps: float = 5000.0, p99_s: float = 0.4) -> dict:
    rec = _record(1000.0)
    rec["fleet"] = _fleet_stats(requests=96, replicas=2,
                                replicas_ready=2)
    rec["detail"]["fleet"] = _fleet_lane(agg_eps, p99_s)
    return rec


def test_fleet_agg_eps_gated_like_the_others():
    res = bench_compare.compare(_fleet_record(5000.0),
                                _fleet_record(3000.0),
                                threshold_pct=10.0)
    assert "fleet_agg_eps" in res["regressions"]
    res = bench_compare.compare(_fleet_record(5000.0),
                                _fleet_record(4900.0),
                                threshold_pct=10.0)
    assert "fleet_agg_eps" not in res["regressions"]


def test_fleet_p99_is_gated_inverted():
    """Latency at the knee is lower-is-better: a RISE past the leash is
    the regression, a fall never is."""
    res = bench_compare.compare(_fleet_record(p99_s=0.4),
                                _fleet_record(p99_s=0.8),
                                threshold_pct=10.0)
    assert "fleet_p99_s" in res["regressions"]
    by_lane = {r["lane"]: r for r in res["lanes"]}
    assert by_lane["fleet_p99_s"]["lower_is_better"] is True
    res = bench_compare.compare(_fleet_record(p99_s=0.4),
                                _fleet_record(p99_s=0.1),
                                threshold_pct=10.0)
    assert "fleet_p99_s" not in res["regressions"]


def test_fleet_p99_dropped_from_new_record_fails_by_name():
    old, new = _fleet_record(), _fleet_record()
    del new["detail"]["fleet"]["p99_s"]
    del new["detail"]["fleet"]["agg_eps"]
    res = bench_compare.compare(old, new, threshold_pct=10.0)
    assert set(res["missing"]) >= {"fleet_p99_s", "fleet_agg_eps"}


def test_fleet_affinity_diagnostics_are_informational():
    """The affine-vs-random decomposition (hit-rate delta, per-arm eps,
    spillover, knee rate, per-replica fill) explains the gated numbers;
    it never gates on its own."""
    old, new = _fleet_record(), _fleet_record()
    new["detail"]["fleet"]["hit_rate_delta"] = 0.01
    new["detail"]["fleet"]["random"]["agg_eps"] = 9999.0
    new["detail"]["fleet"]["knee_rate_rps"] = 1.0
    res = bench_compare.compare(old, new, threshold_pct=10.0)
    assert res["regressions"] == []
    by_lane = {r["lane"]: r for r in res["lanes"]}
    for lane in ("fleet_hit_rate_delta", "fleet_random_eps",
                 "fleet_knee_rate_rps", "fleet_affine_eps",
                 "fleet_agg_eps_ratio", "fleet_replica_fill_min"):
        assert by_lane[lane]["informational"] is True, lane


def test_check_fleet_record_requires_object_on_every_record():
    rec = _record(1000.0)
    assert bench_compare.check_fleet_record(rec) == \
        ["record omits the `fleet` object entirely"]
    rec["fleet"] = _fleet_stats()
    del rec["fleet"]["spillover"]
    assert any("spillover" in p
               for p in bench_compare.check_fleet_record(rec))


def test_check_fleet_record_degraded_needs_only_zeros():
    """ISSUE 18 zeros-never-absent: the degraded paths owe the zeroed
    router-stats object, nothing more — no measured lane exists when no
    fleet ran."""
    rec = {"value": 0, "degraded": True, "backend": "none",
           "fleet": _fleet_stats()}
    assert bench_compare.check_fleet_record(rec) == []


def test_check_fleet_record_gates_lane_arms_and_parity():
    rec = _fleet_record()
    assert bench_compare.check_fleet_record(rec) == []
    del rec["detail"]["fleet"]["affine"]["hit_rate"]
    assert any("affine missing key 'hit_rate'" in p
               for p in bench_compare.check_fleet_record(rec))
    rec = _fleet_record()
    rec["detail"]["fleet"]["verdicts_identical"] = False
    assert any("verdict parity" in p
               for p in bench_compare.check_fleet_record(rec))
    rec = _fleet_record()
    del rec["detail"]["fleet"]
    assert any("omits the detail.fleet lane" in p
               for p in bench_compare.check_fleet_record(rec))


# -- long-haul out-of-core lane (ISSUE 20) ----------------------------------

def _longhaul_stats() -> dict:
    return {k: 0 for k in bench_compare.LONGHAUL_STATS_KEYS}


def _longhaul_record(eps: float = 10000.0,
                     peak_rss_mb: float = 40.0) -> dict:
    rec = _record(1000.0)
    rec["longhaul"] = _longhaul_stats()
    rec["detail"]["longhaul"] = {
        "events": 120000, "segments": 8, "segments_run": 8,
        "survived": True, "dead_step": -1, "max_frontier": 4,
        "escalations": 0, "spilled": True, "wall_s": 12.0,
        "events_per_sec": eps, "peak_rss_mb": peak_rss_mb,
        "rss_budget_mb": 512, "rss_ok": True,
        "verdicts_identical": True, "crosscheck_events": 120000,
    }
    return rec


def test_longhaul_eps_gated_and_peak_rss_inverted():
    """Throughput gates like every lane; the RSS ceiling gates
    INVERTED — more resident bytes is the regression the out-of-core
    tier exists to prevent."""
    res = bench_compare.compare(_longhaul_record(eps=10000.0),
                                _longhaul_record(eps=7000.0),
                                threshold_pct=10.0)
    assert "longhaul_eps" in res["regressions"]
    res = bench_compare.compare(
        _longhaul_record(peak_rss_mb=40.0),
        _longhaul_record(peak_rss_mb=400.0), threshold_pct=10.0)
    assert "longhaul_peak_rss_mb" in res["regressions"]
    # Lower RSS is an improvement, never a regression.
    res = bench_compare.compare(
        _longhaul_record(peak_rss_mb=400.0),
        _longhaul_record(peak_rss_mb=40.0), threshold_pct=10.0)
    assert res["regressions"] == []


def test_check_longhaul_record_requires_object_on_every_record():
    rec = _record(1000.0)
    assert bench_compare.check_longhaul_record(rec) == \
        ["record omits the `longhaul` object entirely"]
    rec["longhaul"] = _longhaul_stats()
    del rec["longhaul"]["peak_rss_mb"]
    assert any("peak_rss_mb" in p
               for p in bench_compare.check_longhaul_record(rec))


def test_check_longhaul_record_degraded_needs_only_zeros():
    rec = {"value": 0, "degraded": True, "backend": "none",
           "longhaul": _longhaul_stats()}
    assert bench_compare.check_longhaul_record(rec) == []


def test_check_longhaul_record_gates_lane_parity_and_ceiling():
    rec = _longhaul_record()
    assert bench_compare.check_longhaul_record(rec) == []
    rec["detail"]["longhaul"]["verdicts_identical"] = False
    assert any("verdict parity" in p
               for p in bench_compare.check_longhaul_record(rec))
    rec = _longhaul_record()
    rec["detail"]["longhaul"]["rss_ok"] = False
    assert any("RSS budget" in p
               for p in bench_compare.check_longhaul_record(rec))
    rec = _longhaul_record()
    del rec["detail"]["longhaul"]
    assert any("omits the detail.longhaul lane" in p
               for p in bench_compare.check_longhaul_record(rec))
