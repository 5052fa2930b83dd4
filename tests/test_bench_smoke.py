"""Fast CPU bench smoke (ISSUE 2 satellite): the corpus-throughput lane's
JSON contract is enforced without hardware — kernel_phases /
padding_waste / cache_hit_rate present, no exceptions, and the
acceptance bounds (padding-waste < 2.0 on a mixed-length corpus, warm
compile_s == 0) hold at tiny scale."""

from __future__ import annotations

import json

import bench
from jepsen_etcd_demo_tpu.models import CASRegister


def test_sched_corpus_lane_contract():
    model = CASRegister()
    lane = bench.bench_sched_corpus(model, n_hist=32, ops_range=(10, 120))
    # The bench JSON contract: every field present and JSON-serializable.
    for key in ("kernel_phases", "padding_waste", "cache_hit_rate",
                "events_per_sec", "launches", "buckets",
                "padding_waste_pad_to_max", "kernel"):
        assert key in lane, key
    json.dumps(lane)
    # Acceptance: the bucketed lane's measured padded/real ratio stays
    # under 2x on a mixed-length corpus, and beats pad-to-max.
    assert 1.0 <= lane["padding_waste"] < 2.0, lane
    assert lane["padding_waste"] < lane["padding_waste_pad_to_max"], lane
    # Mixed lengths really split into buckets (one bucket = no scheduler).
    assert len(lane["buckets"]) >= 2
    assert lane["launches"] >= len(lane["buckets"])
    # Acceptance: the second in-process run of the same bucket shapes
    # pays zero compile (PR 1 kernel-phase attribution), with every
    # kernel-LRU lookup a hit.
    assert lane["kernel_phases"]["compile_s"] == 0.0
    assert lane["kernel_phases"]["execute_s"] > 0.0
    assert lane["cache_hit_rate"] == 1.0
    assert set(lane["kernel_phases"]) == {
        "compile_s", "execute_s", "encode_s", "frontier_peak",
        "profile_hash"}
    # ISSUE 16: the lane carries its windowed ledger attribution — the
    # loss buckets must explain >= 95% of the measured warm wall (the
    # lane itself asserts this; re-check the emitted object) — and the
    # measured ledger overhead, reported (not asserted) by the lane.
    att = lane["ledger"]
    assert att["coverage"] >= 0.95, att
    assert set(att["buckets"]) == {
        "encode_s", "h2d_s", "compile_s", "execute_s", "padding_s",
        "straggler_s", "dispatch_gap_s", "spill_read_s",
        "spill_write_s", "other_s"}
    assert att["buckets"]["execute_s"] > 0
    assert "ledger_overhead_pct" in lane


def test_bench_refuses_the_cpu_backend(capsys):
    """No chip, no numbers: bench.main() on the CPU backend exits non-zero
    and prints no metric line (it never reruns the lanes on the CPU)."""
    assert bench.main() != 0
    assert capsys.readouterr().out.strip() == ""


def test_tuned_lane_contract(tmp_path, monkeypatch):
    """The bench's tuned-profile lane at tiny scale (ISSUE 4): both
    arms' events/s present, speedup_vs_default computed, the active
    profile hash reported, verdicts asserted identical inside the lane —
    and a planted tuned profile really drives the tuned arm."""
    from jepsen_etcd_demo_tpu.ops import limits as limits_mod
    from jepsen_etcd_demo_tpu.tune import profile

    monkeypatch.setenv("JEPSEN_TPU_TUNE_PROFILE",
                       str(tmp_path / "tuned_profile.json"))
    prev_set = limits_mod._SET
    limits_mod._SET = None
    profile.reset()
    try:
        profile.save_entry({"step_bucket_floor": 16,
                            "batch_bucket_floor": 4})
        model = CASRegister()
        lane = bench.bench_tuned(model, n_hist=24, ops_range=(10, 100))
        for key in ("default_events_per_sec", "tuned_events_per_sec",
                    "speedup_vs_default", "profile_hash", "tuned",
                    "tuned_fields", "default_s", "tuned_s"):
            assert key in lane, key
        json.dumps(lane)
        assert lane["tuned"] is True and lane["tuned_fields"] == 2
        assert lane["profile_hash"] == profile.profile_hash() != "default"
        assert lane["speedup_vs_default"] > 0
        # The lane restored the resolution state it found.
        assert limits_mod._SET is None
    finally:
        limits_mod._SET = prev_set
        profile.reset()


def test_streaming_lane_contract():
    """ISSUE 5 acceptance: the streaming lane reports streamed vs
    post-hoc end-to-end wall on the same generated run, asserts the
    verdicts bit-identical inside the lane, and measures
    overlap_ratio > 0 on the CPU backend."""
    model = CASRegister()
    lane = bench.bench_streaming(model, n_keys=4, ops_per_key=150,
                                 run_s=0.3)
    for key in ("keys", "events", "run_s", "post_check_s",
                "stream_drain_s", "post_total_s", "stream_total_s",
                "speedup_total", "overlap_ratio", "chunks", "kernel",
                "verdicts_identical"):
        assert key in lane, key
    json.dumps(lane)
    assert lane["verdicts_identical"] is True
    assert lane["kernel"] == "wgl3-dense-stream-chunked"
    assert lane["overlap_ratio"] > 0, lane
    assert lane["chunks"] >= lane["keys"]
    assert lane["stream_total_s"] > 0 and lane["post_total_s"] > 0


def test_sparse_lane_contract():
    """The bench's sparse lane at tiny scale: dense/sparse events-per-
    second fields present, verdict equivalence asserted inside the lane,
    live-tile ratio measured, sweep-mode counts consistent."""
    model = CASRegister()
    lane = bench.bench_sparse(model, n_ops=200, k_slots=13)
    for key in ("dense_events_per_sec", "sparse_events_per_sec",
                "live_tile_ratio", "sweep", "speedup_vs_dense", "kernel"):
        assert key in lane, key
    json.dumps(lane)
    assert lane["kernel"] == "wgl3-dense-sparse-chunked"
    assert 0.0 < lane["live_tile_ratio"] <= 1.0
    sweep = lane["sweep"]
    assert sweep["mode"] in ("sparse", "mixed")
    assert sweep["steps_sparse"] > 0
    assert sweep["steps_sparse"] + sweep["steps_dense"] <= lane["events"]


def test_dedup_lane_contract():
    """The bench's frontier-dedup lane at tiny scale (ISSUE 10): the
    gated sort-arm events/s present, verdict equivalence asserted
    inside the lane, raw vs unique configs/s reported separately,
    pruning > 0 on the symmetry-heavy fixtures, and the sort arm's
    escalation count never WORSE with dedup on (the CPU-provable
    algorithmic win; the events/s ordering itself is machine-dependent
    and gated round-over-round by bench_compare, not here)."""
    model = CASRegister()
    lane = bench.bench_dedup(model, n_ops=150, k_slots=13, sort_ops=80)
    for key in ("off_events_per_sec", "on_events_per_sec",
                "raw_configs_per_sec", "unique_configs_per_sec",
                "frontier_dedup_ratio", "configs_pruned",
                "speedup_vs_off", "table_off_s", "table_on_s"):
        assert key in lane, key
    json.dumps(lane)
    assert lane["configs_pruned"] > 0
    assert 0.0 < lane["frontier_dedup_ratio"] <= 1.0
    assert lane["max_frontier_on"] <= lane["max_frontier_off"]
    assert lane["unique_configs_per_sec"] > 0
    assert lane["sort_escalations_on"] <= lane["sort_escalations_off"]
    assert lane["sort_f_cap_on"] <= lane["sort_f_cap_off"]


def test_elle_lane_contract(tmp_path, monkeypatch):
    """The bench's elle lane at tiny scale (ISSUE 11): dense/auto/tiled
    arm walls and the auto-route rates present, route verdicts certified
    identical inside the lane (dense / batched auto / tiled / streamed /
    host-Tarjan), oracle pinning redirected to a scratch baseline so the
    committed 10k pin is untouched."""
    monkeypatch.setattr(bench, "BASELINE_FILE",
                        tmp_path / "bench_baseline.json")
    lane = bench.bench_elle(n_txns=300, n_keys=6, corpus=8,
                            corpus_txns=24)
    for key in ("dense_s", "auto_s", "tiled_s", "oracle_s", "infer_s",
                "events_per_sec", "txns_per_sec", "speedup_vs_dense",
                "vs_oracle", "graph_nodes", "graph_edges", "corpus",
                "kernel"):
        assert key in lane, key
    json.dumps(lane)
    assert lane["verdicts_identical"] is True
    assert lane["corpus"]["mismatches"] == 0
    assert lane["corpus"]["invalid"] >= 2
    assert sorted(lane["corpus"]["routes"]) == [
        "auto", "dense", "streamed", "tarjan", "tiled"]
    assert lane["txns_per_sec"] > 0
    # The tiny-scale pin landed in the scratch file, not the repo's.
    assert (tmp_path / "bench_baseline.json").exists()


def test_longhaul_lane_contract():
    """The long-haul out-of-core lane at tiny scale (ISSUE 20): every
    contract field present and JSON-serializable, the spilled route's
    verdict cross-checked bit-identical against the in-RAM route, RSS
    delta under the lane's pinned budget, and the zero-lane (degraded
    paths) carrying exactly the same key set."""
    import sys
    from pathlib import Path

    sys.path.insert(0, str(Path(bench.__file__).resolve().parent
                           / "tools"))
    import bench_compare

    model = CASRegister()
    lane = bench.bench_longhaul(model, events=16_384, seg_events=2048)
    json.dumps(lane)
    for key in bench_compare.LONGHAUL_LANE_KEYS:
        assert key in lane, key
    assert lane["spilled"] is True
    assert lane["survived"] is True and lane["dead_step"] == -1
    assert lane["verdicts_identical"] is True
    assert lane["crosscheck_events"] == 16_384
    assert lane["events_per_sec"] > 0
    assert lane["rss_ok"] is True
    assert lane["peak_rss_mb"] <= lane["rss_budget_mb"]

