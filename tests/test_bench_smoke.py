"""Fast CPU smokes of the program paths behind the chip's throughput
claims, at tiny scale: the corpus scheduler's bucketing and warm
compile cache, tuned profiles, streaming, the sparse sweep, frontier
dedup, the elle routes and the out-of-core long haul. Each drives the
program's own entry points and asserts its verdicts identical across
the routes that must agree."""

from __future__ import annotations

import random
import shutil
import tempfile
import time
from dataclasses import replace

from jepsen_etcd_demo_tpu import obs, sched
from jepsen_etcd_demo_tpu.models import CASRegister
from jepsen_etcd_demo_tpu.ops import wgl2, wgl3
from jepsen_etcd_demo_tpu.ops.encode import (encode_register_history,
                                             encode_return_steps,
                                             reslot_events)
from jepsen_etcd_demo_tpu.ops.limits import limits, set_limits
from jepsen_etcd_demo_tpu.utils.fuzz import (gen_register_history,
                                             interleave_keyed)

MODEL = CASRegister()
N_PROCS = 10
VERDICT_FIELDS = ("valid", "survived", "dead_step", "max_frontier",
                  "configs_explored")


def _mixed_corpus(n_hist: int, ops_range, seed: int):
    rng = random.Random(seed)
    lo, hi = ops_range
    return [encode_register_history(
        gen_register_history(rng, n_ops=rng.randrange(lo, hi),
                             n_procs=N_PROCS, p_info=0.002), k_slots=32)
        for _ in range(n_hist)]


def test_sched_corpus_lane_contract():
    """A mixed-length corpus through the bucketed scheduler, cold then
    warm: valid and deterministic verdicts, bucket padding under 2x and
    under pad-to-max, and a warm pass that compiles nothing and hits
    the kernel LRU on every lookup."""
    encs = _mixed_corpus(32, (10, 120), seed=0x5EDC)
    with obs.capture():
        results, _kernel, stats = sched.check_corpus(encs, MODEL)
    assert all(r["valid"] is True for r in results)
    with obs.capture() as warm:
        results2, _kernel, _stats = sched.check_corpus(encs, MODEL)
    assert results2 == results
    rets = [encode_return_steps(e).n_steps for e in encs]
    pad_to_max = len(rets) * wgl3.step_bucket(max(rets)) / sum(rets)
    assert 1.0 <= stats["padding_waste"] < 2.0, stats
    assert stats["padding_waste"] < pad_to_max, stats
    # Mixed lengths really split into buckets (one bucket = no scheduler).
    assert len(stats["buckets"]) >= 2
    assert stats["launches"] >= len(stats["buckets"])
    phases = obs.kernel_phases(warm.metrics)
    assert phases["compile_s"] == 0.0
    assert phases["execute_s"] > 0.0
    assert obs.sched_stats(warm.metrics)["cache_hit_rate"] == 1.0


def test_tuned_lane_contract(tmp_path, monkeypatch):
    """One corpus under the dataclass-default limits, then under a
    planted tuned profile: the profile really resolves, and it changes
    no verdict."""
    from jepsen_etcd_demo_tpu.ops import limits as limits_mod
    from jepsen_etcd_demo_tpu.ops.limits import KernelLimits
    from jepsen_etcd_demo_tpu.tune import profile

    monkeypatch.setenv("JEPSEN_TPU_TUNE_PROFILE",
                       str(tmp_path / "tuned_profile.json"))
    prev_set = limits_mod._SET
    limits_mod._SET = None
    profile.reset()
    try:
        profile.save_entry({"step_bucket_floor": 16,
                            "batch_bucket_floor": 4})
        encs = _mixed_corpus(24, (10, 100), seed=0x7D4E)
        verdicts = {}
        set_limits(KernelLimits())
        try:
            verdicts["default"] = sched.check_corpus(encs, MODEL)[0]
            set_limits(None)
            assert len(profile.tuned_limits() or {}) == 2
            assert limits().step_bucket_floor == 16
            verdicts["tuned"] = sched.check_corpus(encs, MODEL)[0]
        finally:
            set_limits(None)
        assert verdicts["default"] == verdicts["tuned"]
        assert profile.profile_hash() != "default"
        assert limits_mod._SET is None
    finally:
        limits_mod._SET = prev_set
        profile.reset()


def test_streaming_lane_contract():
    """One generated independent-key run replayed paced through the
    streaming session: every key's verdict equals the post-hoc corpus
    check's, and part of the checking overlapped the run."""
    from jepsen_etcd_demo_tpu.stream import StreamSession

    rng = random.Random(0x57CA)
    per_key = [gen_register_history(rng, n_ops=150, n_procs=N_PROCS,
                                    p_info=0.002) for _ in range(4)]
    ops = interleave_keyed(per_key)
    encs = [encode_register_history(h, k_slots=32) for h in per_key]
    prev = set_limits(replace(limits(), stream_flush_ops=64))
    try:
        post = sched.check_corpus(encs, MODEL)[0]
        assert all(r["valid"] is True for r in post)
        session = StreamSession(MODEL, keyed=True)
        batches = 40
        per = (len(ops) + batches - 1) // batches
        for i in range(batches):
            for op in ops[i * per:(i + 1) * per]:
                session.feed(op)
            time.sleep(0.3 / batches)
        streamed = session.finalize()
    finally:
        set_limits(prev)
    assert streamed is not None and len(streamed) == len(per_key)
    for k, p in enumerate(post):
        for f in ("valid", "dead_step", "max_frontier",
                  "configs_explored"):
            assert streamed[k][f] == p[f], (k, f)
    stats = session.stats()
    assert stats["overlap_ratio"] > 0, stats
    assert stats["chunks"] >= len(per_key)


def test_sparse_lane_contract():
    """One history reslotted to a wide table, swept dense-only then
    prefer-sparse: identical verdicts, a measured live-tile ratio and
    sweep-mode step counts that add up."""
    rng = random.Random(0x5BA5 + 200)
    enc = encode_register_history(
        gen_register_history(rng, n_ops=200, n_procs=N_PROCS,
                             p_info=0.002), k_slots=32)
    k = 13
    cfg = wgl3.dense_config(MODEL, k, enc.max_value, budget=1 << 28)
    assert cfg is not None
    enc = reslot_events(enc, k)
    rs = encode_return_steps(enc)
    out = {}
    for mode in (1, 2):
        prev = set_limits(replace(limits(), sparse_mode=mode))
        try:
            out[mode] = wgl3.check_steps3_long(rs, MODEL, cfg)
        finally:
            set_limits(prev)
    for f in VERDICT_FIELDS:
        assert out[1][f] == out[2][f], f
    sp = out[2]
    assert sp["kernel"] == "wgl3-dense-sparse-chunked"
    assert 0.0 < sp["live_tile_ratio"] <= 1.0
    sweep = sp["sweep"]
    assert sweep["mode"] in ("sparse", "mixed")
    assert sweep["steps_sparse"] > 0
    assert sweep["steps_sparse"] + sweep["steps_dense"] <= enc.n_events


def _sym_steps(n: int, value_range: int, k_floor: int):
    """A symmetry-heavy history: small value domain, crashed ops that
    pile up for the whole history."""
    rng = random.Random(0xDED1 + n)
    h = gen_register_history(
        rng, n_ops=n, n_procs=8, value_range=value_range,
        p_info=(6.0 if value_range > 1 else 15.0) / n)
    enc = encode_register_history(h, k_slots=32)
    k = max(k_floor, wgl3.tight_k_slots(enc))
    enc = reslot_events(enc, k) if enc.k_slots != k else enc
    return enc, encode_return_steps(enc), k


def test_dedup_lane_contract():
    """Frontier dedup off and on, through the resumable sort ladder and
    the chunked dense sweep: identical verdicts, configs pruned, and
    never more escalations, a wider frontier or a larger capacity with
    dedup on."""
    _enc, rs_s, _k = _sym_steps(80, value_range=1, k_floor=8)
    sort = {}
    for mode in (1, 2):
        prev = set_limits(replace(limits(), dedup_mode=mode))
        try:
            sort[mode] = wgl2.check_steps_resumable(rs_s, MODEL, f_cap=64)
        finally:
            set_limits(prev)
    for f in ("valid", "survived", "dead_step"):
        assert sort[1][f] == sort[2][f], f
    assert sort[2]["escalations"] <= sort[1]["escalations"]
    assert sort[2]["f_cap"] <= sort[1]["f_cap"]

    enc_t, rs_t, k = _sym_steps(150, value_range=2, k_floor=13)
    cfg = wgl3.dense_config(MODEL, k, max(enc_t.max_value, 4),
                            budget=1 << 28)
    assert cfg is not None
    table = {}
    for mode in (1, 2):
        prev = set_limits(replace(limits(), dedup_mode=mode,
                                  sparse_mode=1))
        try:
            table[mode] = wgl3.check_steps3_long(rs_t, MODEL, cfg)
        finally:
            set_limits(prev)
    off, on = table[1], table[2]
    for f in ("valid", "survived", "overflow", "dead_step"):
        assert off[f] == on[f], f
    dd = on["dedup"]
    assert dd["configs_pruned"] > 0
    assert 0.0 < dd["frontier_dedup_ratio"] <= 1.0
    assert on["max_frontier"] <= off["max_frontier"]
    assert on["configs_explored"] > 0


def test_elle_lane_contract():
    """A block-diagonal list-append history under the dense, auto and
    tiled closure routes (all valid, all equal), and a mixed-validity
    corpus whose verdicts agree across dense, batched auto, tiled,
    streamed and the host Tarjan fallback."""
    from jepsen_etcd_demo_tpu.checkers.elle import ElleChecker
    from jepsen_etcd_demo_tpu.stream import ElleStreamSession
    from jepsen_etcd_demo_tpu.utils.fuzz import (append_txn_ops,
                                                 gen_append_txns,
                                                 mutate_append_txns)

    rng = random.Random(0xE11E)
    txns = []
    for key in range(6):
        txns.extend(gen_append_txns(rng, n_txns=50, n_keys=1, max_len=1,
                                    first_key=key))
    history = append_txn_ops(txns)
    checker = ElleChecker()
    outs = {}
    for mode in (1, 0, 2):
        prev = set_limits(replace(limits(), elle_mode=mode))
        try:
            outs[mode] = checker.check({}, history)
        finally:
            set_limits(prev)
        assert outs[mode]["valid"] is True, (mode, outs[mode])
    assert outs[1] == outs[0] == outs[2]

    crng = random.Random(0xE11F)
    cases = []
    for i in range(8):
        t = gen_append_txns(crng, n_txns=24, n_keys=4, max_len=3)
        if i % 2:
            t = mutate_append_txns(crng, t)
        cases.append(append_txn_ops(t))
    routes = {"dense": {"elle_mode": 1}, "auto": {"elle_mode": 0},
              "tiled": {"elle_mode": 2},
              "tarjan": {"elle_mode": 0, "elle_cell_budget": 1 << 12}}
    verdicts = {}
    for name, overrides in routes.items():
        prev = set_limits(replace(limits(), **overrides))
        try:
            with obs.capture() as cap:
                verdicts[name] = [checker.check({}, h) for h in cases]
        finally:
            set_limits(prev)
        if name == "tarjan":
            # The pinned budget really routes every closure to the host
            # SCC oracle.
            stats = obs.elle_stats(cap.metrics)
            assert stats["graphs_oracle"] > 0, stats
            assert stats["graphs_dense"] == 0, stats
    streamed = []
    for h in cases:
        session = ElleStreamSession(checker)
        for op in h:
            session.feed(op)
        res = session.finalize()
        assert res is not None
        streamed.append(res["elle"])
    verdicts["streamed"] = streamed
    ref = verdicts["tarjan"]
    for name, got in verdicts.items():
        for a, b in zip(got, ref):
            assert (a["valid"], a["anomaly_types"]) == \
                (b["valid"], b["anomaly_types"]), name
    assert sum(1 for r in ref if r["valid"] is False) >= 2


def test_longhaul_lane_contract():
    """A multi-segment history checked through the spilled out-of-core
    route under a pinned RSS budget: valid, its verdict equal to the
    all-RAM route's, and the lane's RSS growth inside the budget."""
    from jepsen_etcd_demo_tpu.store import spill
    from jepsen_etcd_demo_tpu.stream import longhaul

    seed = 0x10A6
    # The chunk kernel's compile (and its RSS spike) before the lane.
    longhaul.run_longhaul(MODEL, events=4096, seg_events=2048,
                          seed=seed ^ 0x5A5A)
    td = tempfile.mkdtemp(prefix="jepsen-longhaul-")
    prev = set_limits(replace(limits(), host_spill_mode=2,
                              host_rss_budget_mb=512))
    try:
        with spill.spilling(td):
            rec = longhaul.run_longhaul(MODEL, events=16_384,
                                        seg_events=2048, seed=seed)
        set_limits(replace(limits(), host_spill_mode=1))
        inram = longhaul.run_longhaul(MODEL, events=16_384,
                                      seg_events=2048, seed=seed)
    finally:
        set_limits(prev)
        shutil.rmtree(td, ignore_errors=True)
    assert rec["spilled"] is True
    assert rec["survived"] is True and rec["dead_step"] == -1
    assert (rec["survived"], rec["dead_step"]) == \
        (inram["survived"], inram["dead_step"])
    assert rec["events_per_sec"] > 0
    assert rec["rss_ok"] is True
    assert rec["peak_rss_mb"] <= rec["rss_budget_mb"]
