"""Pod-scaling seam (ISSUE 17): device-side history encoding,
shard-aware bucketing, the cross-host launch pipeline, and the
warmup/diff tooling around them.

The load-bearing contract is bit-identity: the device encoder against
the host encoder (golden + fuzz, crashed-op pinning and LIFO slot
reuse included), the shard-aware bucketer against the legacy one-launch
discipline, and the mesh against the single-device arm — the perf work
must move seconds between ledger buckets without moving a single
verdict bit.
"""

from __future__ import annotations

import json
import random
import sys
from dataclasses import replace
from pathlib import Path

import numpy as np
import pytest

from jepsen_etcd_demo_tpu.models import CASRegister
from jepsen_etcd_demo_tpu.ops import encode_device, wgl3
from jepsen_etcd_demo_tpu.ops.encode import (IncrementalEncoder,
                                             encode_register_history,
                                             encode_return_steps)
from jepsen_etcd_demo_tpu.ops.limits import limits, set_limits
from jepsen_etcd_demo_tpu.ops.op import Op
from jepsen_etcd_demo_tpu.parallel import dense as pdense
from jepsen_etcd_demo_tpu.plan import LaunchPipeline
from jepsen_etcd_demo_tpu.sched import lpt_shard_order
from jepsen_etcd_demo_tpu.utils.fuzz import (gen_register_history,
                                             mutate_history)

TOOLS = Path(__file__).resolve().parent.parent / "tools"
sys.path.insert(0, str(TOOLS))

import bench_compare  # noqa: E402
import scaling_report  # noqa: E402

MODEL = CASRegister()


def _host_steps(enc):
    """The host expansion, with encode_mode pinned so a tuned/env
    profile can never silently route this reference through the
    device path."""
    prev = set_limits(replace(limits(), encode_mode=1))
    try:
        return encode_return_steps(enc)
    finally:
        set_limits(prev)


def _assert_steps_equal(dev, host):
    assert dev.n_steps == host.n_steps
    assert dev.n_ops == host.n_ops
    assert dev.k_slots == host.k_slots
    assert dev.max_pending == host.max_pending
    assert dev.max_value == host.max_value
    np.testing.assert_array_equal(dev.slot_tabs, host.slot_tabs)
    np.testing.assert_array_equal(dev.slot_active, host.slot_active)
    np.testing.assert_array_equal(dev.targets, host.targets)


# -- device encoder: golden + fuzz differentials -----------------------

def test_device_encoder_golden():
    """Hand-built history with a crashed op (invoke, never returns):
    the crashed op's slot stays active in every later snapshot and its
    tab row pins the invoke's fields — on device exactly as on host."""
    h = [
        Op(type="invoke", f="write", value=3, process=0, time=0.0, index=0),
        Op(type="invoke", f="read", value=None, process=1, time=0.1,
           index=1),
        Op(type="ok", f="write", value=3, process=0, time=0.2, index=2),
        Op(type="invoke", f="cas", value=(3, 4), process=2, time=0.3,
           index=3),
        Op(type="ok", f="read", value=3, process=1, time=0.4, index=4),
        # process 2's cas crashes: no completion ever recorded.
        Op(type="invoke", f="read", value=None, process=0, time=0.5,
           index=5),
        Op(type="ok", f="read", value=4, process=0, time=0.6, index=6),
    ]
    enc = encode_register_history(h, k_slots=8)
    host = _host_steps(enc)
    dev = encode_device.encode_return_steps_device(enc)
    assert host.n_steps == 3        # write-ok, read-ok, read-ok
    _assert_steps_equal(dev, host)
    # The crashed cas (slot assigned at its invoke) is active in the
    # final snapshot and never targeted.
    assert bool(host.slot_active[-1].sum()) and host.targets[-1] != -1


def test_device_encoder_fuzz_matches_host():
    """20 seeded fuzz histories (mutations, info/crash ops, slot-reuse
    interleavings): ReturnSteps bit-identical to the host encoder."""
    rng = random.Random(0x17E)
    for i in range(20):
        h = gen_register_history(rng, n_ops=rng.randrange(5, 80),
                                 n_procs=rng.randrange(2, 7))
        if i % 3 == 0:
            h = mutate_history(rng, h)
        enc = encode_register_history(h, k_slots=32)
        dev = encode_device.encode_return_steps_device(enc)
        _assert_steps_equal(dev, _host_steps(enc))


def test_device_encoder_padded_tail_matches_padded_to():
    """Rows past n_steps out of the compiled [r_cap] axis must be
    exactly ReturnSteps.padded_to's pad rows (tabs 0, active False,
    targets -1) — the bucketed launch consumes them unmasked."""
    enc = encode_register_history(
        gen_register_history(random.Random(3), n_ops=20), k_slots=16)
    host = _host_steps(enc)
    r_cap = wgl3.step_bucket(host.n_steps + 9)
    e_cap = encode_device.event_bucket(enc.n_events)
    fn = encode_device.cached_device_encoder(enc.k_slots, e_cap, r_cap)
    ev = encode_device.stack_events([enc], e_cap)[0]
    tabs, act, tgt = (np.asarray(x) for x in fn(ev))
    want = host.padded_to(r_cap)
    np.testing.assert_array_equal(tabs, want.slot_tabs)
    np.testing.assert_array_equal(act, want.slot_active)
    np.testing.assert_array_equal(tgt, want.targets)


def test_device_encoder_streaming_prefix():
    """The IncrementalEncoder's stable prefix (LIFO slot reuse, the
    watermark rule) encodes identically on device at checkpoints
    mid-stream and after finalize."""
    rng = random.Random(0x5F1)
    h = gen_register_history(rng, n_ops=60, n_procs=6, p_info=0.15)
    inc = IncrementalEncoder(MODEL)
    checked = 0
    for i, op in enumerate(h):
        inc.append(op)
        if i % 17 == 0 and inc.rows:
            enc = inc.encoded_history(k_slots=16)
            dev = encode_device.encode_return_steps_device(enc)
            _assert_steps_equal(dev, _host_steps(enc))
            checked += 1
    inc.finalize()
    enc = inc.encoded_history(k_slots=16)
    dev = encode_device.encode_return_steps_device(enc)
    _assert_steps_equal(dev, _host_steps(enc))
    assert checked > 0


def test_encode_mode2_routes_device():
    """encode_mode=2 routes the PUBLIC encode_return_steps through the
    device expansion — and the result is still bit-identical."""
    enc = encode_register_history(
        gen_register_history(random.Random(11), n_ops=40), k_slots=16)
    host = _host_steps(enc)
    prev = set_limits(replace(limits(), encode_mode=2))
    try:
        routed = encode_return_steps(enc)
    finally:
        set_limits(prev)
    _assert_steps_equal(routed, host)


def test_empty_history_device_encode():
    inc = IncrementalEncoder(MODEL)
    inc.finalize()
    enc = inc.encoded_history(k_slots=4)
    assert not encode_device.device_encode_feasible(enc)
    dev = encode_device.encode_return_steps_device(enc)
    assert dev.n_steps == 0 and dev.slot_tabs.shape == (0, 4, 4)


# -- shard-aware bucketing ---------------------------------------------

def _corpus(n, seed=0xD5, lo=10, hi=90):
    rng = random.Random(seed)
    encs = []
    for i in range(n):
        h = gen_register_history(rng, n_ops=rng.randrange(lo, hi),
                                 n_procs=4)
        if i % 3 == 0:
            h = mutate_history(rng, h)
        encs.append(encode_register_history(h, k_slots=16))
    return encs


def test_lpt_shard_order_properties():
    """Determinism, permutation validity, and balance: LPT block loads
    over 4 shards of a descending ramp beat corpus order's spread."""
    steps = [100, 90, 80, 70, 60, 50, 40, 30, 25, 20, 10, 0]
    perm = lpt_shard_order(steps, 4)
    assert sorted(perm) == list(range(len(steps)))
    assert perm == lpt_shard_order(steps, 4)      # deterministic
    block = len(steps) // 4
    loads = [sum(steps[p] for p in perm[i * block:(i + 1) * block])
             for i in range(4)]
    naive = [sum(steps[i * block:(i + 1) * block]) for i in range(4)]
    assert max(loads) - min(loads) <= max(naive) - min(naive)
    assert max(loads) <= max(naive)
    # Non-divisible and trivial shard counts degrade to identity.
    assert lpt_shard_order(steps[:-1], 4) == list(range(11))
    assert lpt_shard_order(steps, 1) == list(range(12))


def test_bucketed_matches_legacy_and_modes():
    """The shard-aware bucketer (mode 1, host & device encode) and the
    legacy one-launch discipline (mode 0) return IDENTICAL result dicts
    on the 8-device mesh."""
    encs = _corpus(19, seed=0xB1)
    cfg, steps, r_cap = wgl3.batch_steps3(encs, MODEL)
    mesh = pdense.batch_mesh()

    def run(**over):
        prev = set_limits(replace(limits(), **over))
        try:
            res, _ = pdense.check_steps_sharded(
                MODEL, cfg, steps, r_cap, mesh,
                encs=encs if over.get("encode_mode") != 1 else None)
            return res
        finally:
            set_limits(prev)

    legacy = run(shard_bucket_mode=0, encode_mode=1)
    host = run(shard_bucket_mode=1, encode_mode=1)
    dev = run(shard_bucket_mode=1, encode_mode=2)
    assert legacy == host == dev
    assert any(r["valid"] is False for r in legacy)   # mixed validity
    assert any(r["valid"] is True for r in legacy)


def test_bucketed_deterministic_across_mesh_shapes():
    """Verdict dicts identical between the single-device and 8-device
    meshes — shard packing must not leak into results."""
    encs = _corpus(13, seed=0xC2)
    cfg, steps, r_cap = wgl3.batch_steps3(encs, MODEL)
    one, _ = pdense.check_steps_sharded(MODEL, cfg, steps, r_cap,
                                        pdense.batch_mesh(1), encs=encs)
    eight, _ = pdense.check_steps_sharded(MODEL, cfg, steps, r_cap,
                                          pdense.batch_mesh(), encs=encs)
    assert one == eight


# -- LaunchPipeline ----------------------------------------------------

def test_launch_pipeline_depth_and_order():
    resolved = []
    pipe = LaunchPipeline(depth=2, resolve=resolved.append)
    pipe.submit("a")
    pipe.submit("b")
    assert len(pipe) == 2 and resolved == []
    pipe.submit("c")                 # over depth: oldest resolves
    assert resolved == ["a"] and len(pipe) == 2
    pipe.drain()
    assert resolved == ["a", "b", "c"] and len(pipe) == 0
    assert pipe.dispatched == 3


def test_launch_pipeline_rollback_mid_pipeline():
    """A falsification mid-pipeline rolls back the unresolved window:
    queued entries are dropped, and submitting past the rollback is a
    programming error."""
    resolved = []

    def resolve(entry):
        resolved.append(entry)
        if entry == "bad":
            pipe.rollback()

    pipe = LaunchPipeline(depth=3, resolve=resolve)
    for e in ("w0", "bad", "w2"):
        pipe.submit(e)
    pipe.drain()
    assert resolved == ["w0", "bad"]          # w2 dropped by rollback
    assert pipe.aborted and pipe.rolled_back == 1
    with pytest.raises(RuntimeError):
        pipe.submit("w3")


def test_launch_pipeline_default_depth_is_knob():
    prev = set_limits(replace(limits(), pod_pipeline_depth=5))
    try:
        assert LaunchPipeline().depth == 5
    finally:
        set_limits(prev)


# -- warmup + tooling smokes -------------------------------------------

def test_warmup_plans_record_passes_ledger_contract(tmp_path):
    from jepsen_etcd_demo_tpu.sched import warmup_plans

    rec = warmup_plans(rungs=1, k_slots=8)
    assert rec["launches"] >= 1 and rec["value"] == rec["launches"]
    assert any(f.startswith("wgl3-dense") for f in rec["families"])
    assert rec["cache_dir"] is None or Path(rec["cache_dir"]).exists()
    # The zeros-never-absent ledger object the bench contract requires.
    assert bench_compare.check_ledger_record(rec) == []
    for key in bench_compare.LEDGER_STATS_KEYS:
        assert key in rec["ledger"]


def test_warmup_env_kill_switch(tmp_path, monkeypatch):
    from jepsen_etcd_demo_tpu.sched import startup_warmup
    from jepsen_etcd_demo_tpu.sched.warmup import NO_WARMUP_ENV

    monkeypatch.setenv(NO_WARMUP_ENV, "1")
    assert startup_warmup() is None


def _att(wall, execute, padding, straggler):
    other = max(0.0, wall - execute - padding - straggler)
    return {"wall_s": wall, "coverage": 0.99, "launches": 4,
            "buckets": {"encode_s": 0.0, "h2d_s": 0.0, "compile_s": 0.0,
                        "execute_s": execute, "padding_s": padding,
                        "straggler_s": straggler, "dispatch_gap_s": 0.0,
                        "other_s": other}}


def test_scaling_report_diff_gates_regressions(tmp_path):
    old = {"parsed": {"scaling": {"ledger": _att(10, 4.5, 3.5, 2.0)}}}
    good = {"scaling": {"ledger": _att(8, 6.4, 0.9, 0.6)}}
    bad = _att(9, 2.0, 5.5, 1.3)          # padding share blew up
    paths = {}
    for name, rec in (("old", old), ("good", good), ("bad", bad)):
        p = tmp_path / f"{name}.json"
        p.write_text(json.dumps(rec))
        paths[name] = str(p)
    assert scaling_report.main(
        ["--diff", paths["old"], paths["good"]]) == 0
    assert scaling_report.main(
        ["--diff", paths["old"], paths["bad"]]) == 1
    res = scaling_report.diff_records(old, bad)
    assert res["comparable"] and "padding_s" in res["regressions"]
    # execute_s collapse alone is NOT a gated regression (ungated).
    assert "execute_s" not in res["regressions"]
    # Records without a ledger arm are not comparable (and not fatal).
    empty = tmp_path / "empty.json"
    empty.write_text("{}")
    assert scaling_report.main(
        ["--diff", str(empty), paths["good"]]) == 0


def test_bench_compare_scaling_lane_tight_ratchet():
    """scaling_eps_per_chip gates at the tighter per-lane 5% while the
    other lanes stay on the global threshold."""
    def rec(per_chip):
        return {"value": 1000.0,
                "scaling": {"events_per_chip": per_chip,
                            "efficiency_vs_single": 0.5,
                            "mesh_shape": {"batch": 8}}}

    res = bench_compare.compare(rec(1000.0), rec(930.0),
                                threshold_pct=10.0)
    assert "scaling_eps_per_chip" in res["regressions"]   # -7% > 5%
    res = bench_compare.compare(rec(1000.0), rec(970.0),
                                threshold_pct=10.0)
    assert res["regressions"] == []                       # -3% < 5%


def test_multichip_r07_record_loads_and_diff_gates_clean():
    """The committed MULTICHIP_r07.json is ledger-armed: it loads
    through the driver-wrapper path, self-compares clean on every
    bench lane, and self-diffs clean through the gated loss-bucket
    report (scaling_report --diff)."""
    repo = Path(__file__).resolve().parent.parent
    rec = bench_compare.load_record(repo / "MULTICHIP_r07.json")
    scal = rec["scaling"]
    assert scal["mesh_shape"] == {"batch": 8}
    assert scal["efficiency_vs_single"] >= 0.45   # the ISSUE 17 gate
    led = scal["ledger"]
    assert led["coverage"] >= 0.95
    wall = led["wall_s"]
    lost = led["buckets"]["padding_s"] + led["buckets"]["straggler_s"]
    assert lost / wall <= 0.276    # >=2x cut vs r06's 55.2% loss share
    assert bench_compare.compare(rec, rec)["regressions"] == []
    res = scaling_report.diff_records(rec, rec)
    assert res["comparable"] and res["regressions"] == []
