"""Benchmark: WGL linearizability checking throughput, TPU kernel vs CPU oracle.

Prints ONE JSON line:
  {"metric": ..., "value": N, "unit": ..., "vs_baseline": N, "detail": {...}}

The reference publishes no benchmark numbers (BASELINE.md): its checker is
knossos's JVM search, which this build replaces with the JAX/XLA kernels. The
baseline stand-in is therefore this repo's pure-Python oracle WGL checker
(checkers/oracle.py — same algorithm, same event encoding, host CPU), playing
the role of the JVM hot loop. vs_baseline = kernel events/sec ÷ oracle
events/sec on the same histories.

The oracle denominator is PINNED (VERDICT r2 weak #2): the first run on a
host measures the oracle once per corpus signature and records it in
bench_baseline.json (committed); later runs reuse the recorded seconds, so
vs_baseline is comparable round over round instead of wobbling with host
load. Delete the file (or change the corpus constants) to re-pin.

Workloads:
  * corpus — 1024 fuzzed 150-op cas-register histories (valid by
    construction: the checker must run to completion, the worst case for
    the search), checked in ONE batched launch of the dense lattice kernel
    (ops/wgl3.py) on one chip. BASELINE.json configs[2]/[4] (independent
    keys as one vmap, corpus-replay scale). On TPU the lane also reports a
    roofline estimate (see _roofline).
  * long history — 1k-op and 10k-op single-register histories through the
    single-history dense kernel (BASELINE.json configs[3]; north star:
    10k ops < 60 s where knossos-CPU DNFs). BENCH_100K=1 adds a 100k-op
    lane (minutes); its result is cached in bench_100k.json and merged
    into the detail on every subsequent run.
  * gset corpus — 256 grow-only-set histories through the same batched
    kernel (model-family lane, models/gset.py).
"""

from __future__ import annotations

import json
import os
import random
import sys
import time
from pathlib import Path

import numpy as np

N_OPS = 150           # ops per history (tutorial run scale, BASELINE configs[0])
N_PROCS = 10          # concurrency, matching the reference's 10 threads/key
CORPUS = 1024         # histories per batched launch — the full corpus-replay
#                       scale (BASELINE configs[4]: 1024 stored histories)
REPEATS = 3
LONG_OPS = (1_000, 10_000)

BASELINE_FILE = Path(__file__).parent / "bench_baseline.json"
LONG100K_FILE = Path(__file__).parent / "bench_100k.json"

# Peak numbers for the roofline estimate, keyed by the EXACT jax
# device_kind (a kind not listed is "not measured", never a neighbour's
# peaks). TPU v5e (Google Cloud "TPU v5e" documentation): 197 bf16
# TFLOP/s over 4 128x128 MXUs -> ~1.5 GHz core clock; the VPU is 8
# sublanes x 128 lanes x 4 ALUs at that clock => ~6.1e12 int32
# word-ops/s; HBM 819 GB/s. The roofline ALSO reports utilization
# against a MEASURED int32 ALU peak (_peak_microbench): the spec number
# assumes every ALU issue slot takes int ops.
PEAKS = {
    "TPU v5 lite": {"vpu_word_ops": 6.1e12, "hbm_Bps": 8.19e11},
}


def _device_seconds(fn) -> float | None:
    """Device-busy seconds for one call of fn (device-side events of a
    jax.profiler trace). Wall time also carries the dispatch+fetch round
    trip, which is NOT kernel time (VERDICT r3 item 1) — this is the
    honest kernel denominator. Returns None when no device events are
    captured."""
    import glob
    import gzip
    import shutil
    import tempfile

    import jax

    tmp = tempfile.mkdtemp(prefix="benchprof")
    try:
        with jax.profiler.trace(tmp):
            fn()
        traces = glob.glob(f"{tmp}/plugins/profile/*/*.trace.json.gz")
        if not traces:
            return None
        with gzip.open(traces[0]) as f:
            d = json.load(f)
        pids = {e["pid"]: e["args"].get("name", "")
                for e in d.get("traceEvents", [])
                if e.get("ph") == "M" and e.get("name") == "process_name"}
        dev_us = sum(
            e.get("dur", 0) for e in d["traceEvents"]
            if e.get("ph") == "X" and "TPU" in pids.get(e["pid"], "")
            and not e.get("name", "").startswith("jit_"))
        return dev_us / 1e6 if dev_us else None
    except (OSError, ValueError, KeyError):
        return None
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def _peak_microbench() -> float | None:
    """Measured int32 VPU word-ops/s ceiling: a pallas kernel of 8
    independent 4-op ALU chains on resident vregs (no memory traffic, no
    reduces — the best case for this kernel family's op mix). Pinned in
    bench_baseline.json per device kind; delete the entry to re-measure."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    kind = jax.devices()[0].device_kind
    try:
        rec = json.loads(BASELINE_FILE.read_text())["peak_microbench"]
        if rec.get("device_kind") == kind:
            return rec["word_ops_per_s"]
    except (OSError, ValueError, KeyError):
        pass

    # 5 vector ALU ops per chain-iteration: xor, shift, or, and, add.
    ITERS, UNROLL, OPS, SP, W = 200_000, 8, 5, 8, 128

    def kernel(x_ref, o_ref):
        def body(i, accs):
            out = []
            for a in accs:
                a = a ^ jnp.uint32(0x9E3779B9)
                a = a | (a << jnp.uint32(1))
                a = a & jnp.uint32(0x7FFFFFFF)
                a = a + jnp.uint32(i)
                out.append(a)
            return tuple(out)
        accs = tuple(x_ref[...] + jnp.uint32(k) for k in range(UNROLL))
        accs = jax.lax.fori_loop(0, ITERS, body, accs)
        acc = accs[0]
        for a in accs[1:]:
            acc = acc | a
        o_ref[...] = acc

    @jax.jit
    def run(x):
        return pl.pallas_call(
            kernel, out_shape=jax.ShapeDtypeStruct((SP, W), jnp.uint32))(x)

    x = jnp.asarray(np.arange(SP * W, dtype=np.uint32).reshape(SP, W))
    np.asarray(run(x))  # compile
    dev_s = _device_seconds(lambda: np.asarray(run(x)))
    if not dev_s:
        return None
    peak = ITERS * UNROLL * OPS * SP * W / dev_s
    try:
        data = json.loads(BASELINE_FILE.read_text())
    except (OSError, ValueError):
        data = {}
    data["peak_microbench"] = {
        "device_kind": kind, "word_ops_per_s": round(peak, -9),
        "pinned_at": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime())}
    BASELINE_FILE.write_text(json.dumps(data, indent=2) + "\n")
    print(f"# pinned measured VPU peak {peak/1e12:.2f} T word-ops/s -> "
          f"{BASELINE_FILE.name} (commit it)", file=sys.stderr)
    return peak


def build_corpus():
    from jepsen_etcd_demo_tpu.ops.encode import encode_register_history
    from jepsen_etcd_demo_tpu.utils.fuzz import gen_register_history

    rng = random.Random(0xBE7C)
    # p_info low: every :info op stays pending forever and occupies a slot
    # for the rest of the history (knossos semantics), so long histories
    # need them rare (or a wide slot table).
    return [encode_register_history(
        gen_register_history(rng, n_ops=N_OPS, n_procs=N_PROCS,
                             p_info=0.002), k_slots=32)
        for _ in range(CORPUS)]


def _signature(lane: str, encs) -> dict:
    """Cheap content signature binding a pinned oracle time to the exact
    corpus (seed/constants drift re-pins automatically)."""
    return {
        "lane": lane, "histories": len(encs),
        "events": int(sum(e.n_events for e in encs)),
        "checksum": int(sum(int(np.sum(e.events[: e.n_events],
                                       dtype=np.int64)) for e in encs)
                        & 0x7FFFFFFF),
    }


def _pinned_oracle(lane: str, sig: dict):
    try:
        rec = json.loads(BASELINE_FILE.read_text())[lane]
    except (OSError, ValueError, KeyError):
        return None
    return rec["oracle_s"] if rec.get("sig") == sig else None


def _pin_oracle(lane: str, sig: dict, oracle_s: float) -> None:
    try:
        data = json.loads(BASELINE_FILE.read_text())
    except (OSError, ValueError):
        data = {}
    data[lane] = {"sig": sig, "oracle_s": round(oracle_s, 4),
                  "pinned_at": time.strftime("%Y-%m-%dT%H:%M:%SZ",
                                             time.gmtime())}
    BASELINE_FILE.write_text(json.dumps(data, indent=2) + "\n")
    print(f"# pinned {lane} oracle baseline {oracle_s:.2f}s -> "
          f"{BASELINE_FILE.name} (commit it)", file=sys.stderr)


def _roofline(device_kind: str, cfg, steps, r_pad: int, batch: int,
              kernel_s: float, device_s: float | None = None,
              measured_peak: float | None = None,
              min_sweeps: int = 2) -> dict | None:
    """Lower-bound hardware-utilization estimate for the dense batched
    launch (VERDICT r2 missing #4; r3 item 1 split the denominator). Two
    ceilings:

      * HBM: the fused pallas kernel keeps the table in VMEM; its HBM
        traffic is the streamed colmask blocks (+ the prefetched targets),
        which is exactly computable from the launch shape.
      * VPU: word-ops are modeled from the guaranteed work — min_sweeps
        closure sweeps per real step of K slots x (2S+3) word-ops over
        the Sp x W table. min_sweeps is 2 for the grouped kernel (two
        unconditional sweeps per step) but 1 for the per-history kernel,
        whose first-sweep-silent steps stop after one sweep. Real sweeps
        can exceed the minimum, so vpu_pct is a LOWER bound.

    Utilization is computed on DEVICE time when a profiler measurement is
    available (wall carries the dispatch+fetch round trip), against
    BOTH the spec-sheet peak and the
    pinned measured int32 ALU peak (_peak_microbench — the honest ceiling
    for this op mix). roofline_pct stays the spec-peak wall-time figure
    for round-over-round comparability; roofline_pct_device /
    roofline_pct_measured are the sharper views."""
    peaks = PEAKS.get(device_kind)
    if peaks is None:
        return None
    S, K = cfg.n_states, cfg.k_slots
    sp = max(8, (S + 7) // 8 * 8)
    w = 1 << (K - 5)
    real_steps = int(sum(s.n_steps for s in steps))
    colmask_bytes = batch * r_pad * sp * 128 * 4 + batch * r_pad * 4
    word_ops = real_steps * min_sweeps * K * (2 * S + 3) * sp * w
    hbm_pct = colmask_bytes / kernel_s / peaks["hbm_Bps"] * 100
    vpu_pct = word_ops / kernel_s / peaks["vpu_word_ops"] * 100
    out = {
        "achieved_hbm_GBps": round(colmask_bytes / kernel_s / 1e9, 2),
        "achieved_word_Gops": round(word_ops / kernel_s / 1e9, 2),
        "hbm_pct": round(hbm_pct, 2),
        "vpu_pct_lower_bound": round(vpu_pct, 2),
        "roofline_pct": round(max(hbm_pct, vpu_pct), 2),
        "peaks_assumed": {"vpu_word_ops": peaks["vpu_word_ops"],
                          "hbm_Bps": peaks["hbm_Bps"]},
    }
    if device_s:
        out["device_s"] = round(device_s, 4)
        out["dispatch_fetch_s"] = round(max(0.0, kernel_s - device_s), 4)
        out["roofline_pct_device"] = round(
            word_ops / device_s / peaks["vpu_word_ops"] * 100, 2)
        if measured_peak:
            out["vpu_word_ops_measured"] = measured_peak
            out["roofline_pct_measured"] = round(
                word_ops / device_s / measured_peak * 100, 2)
    return out


def _measure_corpus(lane, encs, model):
    """Shared measurement harness for batched-corpus lanes: one batched
    launch via the production routing point (wgl3_pallas dispatch), best
    of REPEATS with ONE packed device->host fetch per launch (per-fetch
    round trips add to the wall), then the PINNED
    oracle denominator (measured once per corpus signature, reused after).
    The corpus must be valid by construction (the checker runs to
    completion — the search's worst case)."""
    import jax

    from jepsen_etcd_demo_tpu.checkers.oracle import check_events_oracle
    from jepsen_etcd_demo_tpu.ops import wgl3, wgl3_pallas

    cfg, steps, r_cap = wgl3.batch_steps3(encs, model)
    arrays = wgl3.stack_steps3(steps, r_cap)
    check, kernel_name = wgl3_pallas.packed_batch_checker(
        model, cfg, n_steps=r_cap, batch=len(encs))
    out = wgl3.unpack_np(check(*arrays))  # compile + warmup
    assert out["survived"].all(), "bench corpus must be valid by construction"
    best = float("inf")
    for _ in range(REPEATS):
        t0 = time.perf_counter()
        out = wgl3.unpack_np(check(*arrays))
        best = min(best, time.perf_counter() - t0)

    sig = _signature(lane, encs)
    oracle_s = _pinned_oracle(lane, sig)
    pinned = oracle_s is not None
    if not pinned:
        t0 = time.perf_counter()
        for enc in encs:
            assert check_events_oracle(enc, model).valid
        oracle_s = time.perf_counter() - t0
        _pin_oracle(lane, sig, oracle_s)
    m = {
        "kernel_s": best,
        "oracle_s": oracle_s,
        "oracle_pinned": pinned,
        "kernel": kernel_name,
        "k_slots": cfg.k_slots,
        "table_cells": cfg.n_states * cfg.n_masks,
        # §5.1 checker metric: configs explored per second of kernel wall
        # time (the search's unit of work; the oracle reports the same
        # counter for an apples-to-apples view).
        "configs_per_sec": float(out["configs_explored"].sum()) / best,
    }
    kind = jax.devices()[0].device_kind
    if kind in PEAKS:
        # Profiled launch + peak microbench only when a roofline will
        # actually be emitted for this device kind.
        device_s = _device_seconds(lambda: wgl3.unpack_np(check(*arrays)))
        measured_peak = _peak_microbench() if device_s else None
        roof = _roofline(kind, cfg, steps, r_cap, len(encs), best,
                         device_s, measured_peak,
                         min_sweeps=2 if "grouped" in kernel_name else 1)
        if roof:
            if lane == "register_corpus":
                roof["dispatch_floor"] = _dispatch_floor(
                    model, cfg, steps, r_cap, best,
                    roof.get("device_s"))
            m["roofline"] = roof
    return m


def _dispatch_floor(model, cfg, steps, r_cap, batch_wall_s, device_s):
    """VERDICT r4 next #1: attack the dispatch/fetch share of the corpus
    wall, or prove it irreducible WITH A MEASUREMENT. Two probes:

      * empty_launch_s — round trip of an already-compiled trivial
        launch + one-word fetch: the true per-launch floor of this
        backend.
      * pipelined_2wave_s — the corpus split into two sub-batches,
        both dispatched before any fetch. On a backend whose dispatch
        overlaps, this hides host prep under device compute; where each
        launch pays its own serialized round trip, wave-splitting
        regresses and the production path stays one launch.

    floor_irreducible is the recorded conclusion:
    empty_launch_s >= (batch_wall_s - device_s), i.e. the non-device
    share of the wall is within one empty round trip — nothing above
    the floor is left to hide."""
    from jepsen_etcd_demo_tpu.ops import wgl3, wgl3_pallas
    from jepsen_etcd_demo_tpu.ops.calibrate import measure_dispatch_floor

    empty = measure_dispatch_floor()
    B = len(steps) // 2
    waves = [wgl3.stack_steps3(steps[i * B:(i + 1) * B], r_cap)
             for i in range(2)]
    check, _ = wgl3_pallas.packed_batch_checker(model, cfg, n_steps=r_cap,
                                                batch=B)
    wgl3.unpack_np(check(*waves[0]))            # compile the wave shape
    wall2 = float("inf")
    for _ in range(REPEATS):
        t0 = time.perf_counter()
        outs = [check(*w) for w in waves]       # dispatch both, no fetch
        for o in outs:
            wgl3.unpack_np(o)                   # then fetch
        wall2 = min(wall2, time.perf_counter() - t0)
    non_device = (batch_wall_s - device_s) if device_s else None
    return {
        "empty_launch_s": round(empty, 4),
        "pipelined_2wave_s": round(wall2, 4),
        "floor_irreducible": (None if non_device is None
                              else bool(empty >= non_device)),
    }


def bench_corpus(model):
    encs = build_corpus()
    m = _measure_corpus("register_corpus", encs, model)
    m["events"] = int(sum(e.n_events for e in encs))
    m["histories_per_sec"] = CORPUS / m["kernel_s"]
    return m


def bench_gset_corpus():
    """Model-family lane: 256 grow-only-set histories through the same
    batched dense kernel (models/gset.py — the set state is its int32
    bitmask, 32-state table). Proves the family kernels run at corpus
    scale, not only under test geometries."""
    from jepsen_etcd_demo_tpu.models import GSet
    from jepsen_etcd_demo_tpu.ops.encode import encode_history
    from jepsen_etcd_demo_tpu.utils.fuzz import gen_gset_history

    model = GSet()
    rng = random.Random(0x65E7)
    encs = [encode_history(
        gen_gset_history(rng, n_ops=N_OPS, n_procs=N_PROCS, p_info=0.002),
        model, k_slots=32) for _ in range(256)]
    m = _measure_corpus("gset_corpus", encs, model)
    return {"histories": len(encs), "kernel_s": round(m["kernel_s"], 4),
            "oracle_s": round(m["oracle_s"], 4),
            "oracle_pinned": m["oracle_pinned"], "kernel": m["kernel"],
            "table_cells": m["table_cells"]}


def build_mixed_corpus(n_hist: int = 256, ops_range=(20, 300),
                       seed: int = 0x5EDC):
    """Mixed-length register corpus for the bucketed-scheduler lane: the
    length spread is the whole point (a uniform corpus has nothing to
    bucket)."""
    from jepsen_etcd_demo_tpu.ops.encode import encode_register_history
    from jepsen_etcd_demo_tpu.utils.fuzz import gen_register_history

    rng = random.Random(seed)
    lo, hi = ops_range
    return [encode_register_history(
        gen_register_history(rng, n_ops=rng.randrange(lo, hi),
                             n_procs=N_PROCS, p_info=0.002), k_slots=32)
        for _ in range(n_hist)]


def bench_sched_corpus(model, n_hist: int = 256, ops_range=(20, 300)) -> dict:
    """Corpus-throughput lane (ISSUE 2 tentpole): a mixed-length corpus
    through the bucketed scheduler (sched/engine.py), cold then warm.

    Reports events/s, the MEASURED padding-waste ratio (padded/real
    steps across the scheduled launches) next to the counterfactual
    pad-to-max ratio the old single-launch path would have paid, the
    scheduler kernel-LRU hit rate on the warm pass, and the warm pass's
    kernel-phase breakdown — whose compile_s must be 0 when every bucket
    shape was already compiled (the acceptance check
    tests/test_bench_smoke.py pins on a tiny corpus). Runs under its own
    telemetry captures (nested captures shadow the bench-wide one), so
    the lane's numbers are self-contained."""
    from jepsen_etcd_demo_tpu import obs, sched
    from jepsen_etcd_demo_tpu.ops import wgl3
    from jepsen_etcd_demo_tpu.ops.encode import EV_RETURN

    encs = build_mixed_corpus(n_hist, ops_range)
    with obs.capture() as cold_cap:
        t0 = time.perf_counter()
        results, kernel, stats = sched.check_corpus(encs, model)
        cold_s = time.perf_counter() - t0
    assert all(r["valid"] is True for r in results), \
        "sched corpus must be valid by construction"
    with obs.capture() as warm_cap:
        w0_ns = time.monotonic_ns()
        t0 = time.perf_counter()
        results2, kernel, _stats2 = sched.check_corpus(encs, model)
        warm_s = time.perf_counter() - t0
        w1_ns = time.monotonic_ns()
    assert results2 == results, "sched corpus must be deterministic"
    # Scaling-ledger attribution of the warm pass (ISSUE 16): the loss
    # buckets must explain >=95% of the measured wall. What the ledger
    # itself costs is reported against a ledger-off control arm
    # (interleaved best-of-3 per arm), not asserted: a wall-clock A/B
    # of a tiny corpus on a shared host is noise, and instrumentation
    # cost is measured on the chip.
    ledger_att = warm_cap.ledger.attribution(t0_ns=w0_ns, t1_ns=w1_ns)
    assert ledger_att["coverage"] >= 0.95, \
        f"ledger buckets explain only {ledger_att['coverage']:.1%} " \
        f"of the warm sched pass"

    def _warm_pass(with_ledger: bool) -> float:
        with obs.capture(with_ledger=with_ledger):
            p0 = time.perf_counter()
            sched.check_corpus(encs, model)
            return time.perf_counter() - p0

    on_s, off_s = warm_s, float("inf")
    for _ in range(3):
        on_s = min(on_s, _warm_pass(True))
        off_s = min(off_s, _warm_pass(False))
    overhead_pct = 100.0 * (on_s - off_s) / off_s if off_s > 0 else 0.0

    events = int(sum(e.n_events for e in encs))
    rets = [int((e.events[: e.n_events, 0] == EV_RETURN).sum())
            for e in encs]
    real = sum(rets)
    pad_to_max = (len(rets) * wgl3.step_bucket(max(rets)) / real
                  if real else 0.0)
    warm_sched = obs.sched_stats(warm_cap.metrics)
    return {
        "histories": n_hist,
        "events": events,
        "cold_s": round(cold_s, 4),
        "warm_s": round(warm_s, 4),
        "events_per_sec": round(events / warm_s, 1),
        "kernel": kernel,
        "launches": stats["launches"],
        "buckets": stats["buckets"],
        "padding_waste": stats["padding_waste"],
        "padding_waste_pad_to_max": round(pad_to_max, 4),
        "cache_hit_rate": warm_sched["cache_hit_rate"],
        "kernel_phases": obs.kernel_phases(warm_cap.metrics),
        "kernel_phases_cold": obs.kernel_phases(cold_cap.metrics),
        "ledger": ledger_att,
        "ledger_overhead_pct": round(max(0.0, overhead_pct), 2),
    }


def bench_sparse(model, n_ops: int = 150, k_slots: int = 20) -> dict:
    """Sparse active-tile lane (ISSUE 3 tentpole): ONE long register
    history reslotted to a WIDE table (k_slots beyond its real
    concurrency — the regime where the dense sweep wastes 2^K work on a
    tiny frontier), run through the chunked dense sweep under
    limits().sparse_mode pinned to dense-only (1) then prefer-sparse
    (2). Verdicts are asserted bit-identical; the lane reports events/s
    for BOTH modes, the measured live-tile ratio, and the sweep-mode
    step counts — the direction-optimizing win measured, not asserted.
    CPU-provable (tests/test_bench_smoke.py runs a tiny geometry)."""
    from dataclasses import replace

    from jepsen_etcd_demo_tpu.ops import wgl3
    from jepsen_etcd_demo_tpu.ops.encode import (encode_register_history,
                                                 encode_return_steps,
                                                 reslot_events)
    from jepsen_etcd_demo_tpu.ops.limits import limits, set_limits
    from jepsen_etcd_demo_tpu.utils.fuzz import gen_register_history

    rng = random.Random(0x5BA5 + n_ops)
    h = gen_register_history(rng, n_ops=n_ops, n_procs=N_PROCS,
                             p_info=0.002)
    enc = encode_register_history(h, k_slots=32)
    cfg = wgl3.dense_config(model, k_slots, enc.max_value, budget=1 << 28)
    assert cfg is not None, (k_slots, enc.max_value)
    enc = reslot_events(enc, k_slots) if enc.k_slots != k_slots else enc
    rs = encode_return_steps(enc)
    events = enc.n_events
    lane = {"ops": n_ops, "events": events, "k_slots": k_slots,
            "table_cells": cfg.n_states * cfg.n_masks}
    results = {}
    for mode, name in ((1, "dense"), (2, "sparse")):
        prev = set_limits(replace(limits(), sparse_mode=mode))
        try:
            wgl3.check_steps3_long(rs, model, cfg)        # compile/warm
            best = float("inf")
            for _ in range(REPEATS):
                t0 = time.perf_counter()
                out = wgl3.check_steps3_long(rs, model, cfg)
                best = min(best, time.perf_counter() - t0)
        finally:
            set_limits(prev)
        results[name] = out
        lane[f"{name}_s"] = round(best, 4)
        lane[f"{name}_events_per_sec"] = round(events / best, 1)
    sp = results["sparse"]
    for f in ("valid", "survived", "dead_step", "max_frontier",
              "configs_explored"):
        assert results["dense"][f] == sp[f], \
            f"sparse/dense verdict drift on {f}: {results}"
    lane["live_tile_ratio"] = sp.get("live_tile_ratio", -1.0)
    lane["sweep"] = sp.get("sweep", {})
    lane["kernel"] = sp.get("kernel", "")
    lane["speedup_vs_dense"] = (round(lane["dense_s"] / lane["sparse_s"], 2)
                                if lane["sparse_s"] else 0.0)
    return lane


def bench_dedup(model, n_ops: int = 600, k_slots: int = 16,
                sort_ops: int = 300) -> dict:
    """Frontier-dedup lane (ISSUE 10 tentpole), two arms over
    symmetry-heavy fixtures (small value domains + forever-pending
    populations, so equal-effect pending-op classes really exist), each
    run dedup-OFF (dedup_mode=1) then dedup-ON:

      * SORT arm — the GATED measurement (off/on_events_per_sec,
        tools/bench_compare.py): one single-value-domain history whose
        crashed ops interleave factorially, through the resumable sort
        ladder (wgl2.check_steps_resumable), where frontier size
        directly drives cost. Canonicalization collapses C(n,k)
        symmetric masks to n+1, avoiding whole 4x capacity escalations
        — measured 4.1x on the CPU backend at this scale.
      * TABLE arm — informational: the chunked dense sweep under
        dedup_mode=2 (the table passes canonicalize under force/tuned
        profiles only — a table sweep's cost is fixed in the table
        size). Reports the measured frontier_dedup_ratio, the pruned
        count, and raw (dedup-off) vs UNIQUE (canonical) configs/s as
        SEPARATE numbers, so the headline configs metric cannot
        silently improve by pruning.

    Verdict fields are asserted identical in both arms in both modes
    (canonicalization is a verdict-preserving quotient, ops/canon.py)."""
    from dataclasses import replace

    from jepsen_etcd_demo_tpu.ops import wgl2, wgl3
    from jepsen_etcd_demo_tpu.ops.encode import (encode_register_history,
                                                 encode_return_steps,
                                                 reslot_events)
    from jepsen_etcd_demo_tpu.ops.limits import limits, set_limits
    from jepsen_etcd_demo_tpu.utils.fuzz import gen_register_history

    def sym_steps(n, value_range, k_floor):
        # p_info is the symmetry dial AND the slot-pressure dial:
        # crashed ops accumulate for the whole history, so the rate
        # scales as ~6 (table) / ~15 (sort) expected crashes per run;
        # the slot width rides the history's real concurrency.
        rng = random.Random(0xDED1 + n)
        h = gen_register_history(rng, n_ops=n, n_procs=8,
                                 value_range=value_range,
                                 p_info=(6.0 if value_range > 1 else 15.0)
                                 / n)
        enc = encode_register_history(h, k_slots=32)
        k = max(k_floor, wgl3.tight_k_slots(enc))
        enc = reslot_events(enc, k) if enc.k_slots != k else enc
        return enc, encode_return_steps(enc), k

    def timed(fn):
        fn()                                   # compile/warm
        best = float("inf")
        for _ in range(REPEATS):
            t0 = time.perf_counter()
            out = fn()
            best = min(best, time.perf_counter() - t0)
        return best, out

    # -- sort arm (gated) --------------------------------------------
    enc_s, rs_s, _k = sym_steps(sort_ops, value_range=1, k_floor=8)
    lane = {"sort_ops": sort_ops, "sort_events": enc_s.n_events}
    sort_res = {}
    for mode, name in ((1, "off"), (2, "on")):
        prev = set_limits(replace(limits(), dedup_mode=mode))
        try:
            best, out = timed(lambda: wgl2.check_steps_resumable(
                rs_s, model, f_cap=64))
        finally:
            set_limits(prev)
        sort_res[name] = out
        lane[f"{name}_s"] = round(best, 4)
        lane[f"{name}_events_per_sec"] = round(enc_s.n_events / best, 1)
    for f in ("valid", "survived", "dead_step"):
        assert sort_res["off"][f] == sort_res["on"][f], \
            f"dedup sort-arm verdict drift on {f}: {sort_res}"
    lane["sort_f_cap_off"] = sort_res["off"]["f_cap"]
    lane["sort_f_cap_on"] = sort_res["on"]["f_cap"]
    lane["sort_escalations_off"] = sort_res["off"]["escalations"]
    lane["sort_escalations_on"] = sort_res["on"]["escalations"]
    lane["speedup_vs_off"] = (round(lane["off_s"] / lane["on_s"], 3)
                              if lane["on_s"] else 0.0)

    # -- table arm (informational) -----------------------------------
    enc_t, rs_t, k = sym_steps(n_ops, value_range=2, k_floor=k_slots)
    cfg = wgl3.dense_config(model, k, max(enc_t.max_value, 4),
                            budget=1 << 28)
    assert cfg is not None, (k, enc_t.max_value)
    events = enc_t.n_events
    lane.update({"ops": n_ops, "events": events, "k_slots": k,
                 "table_cells": cfg.n_states * cfg.n_masks})
    table_res = {}
    for mode, name in ((1, "off"), (2, "on")):
        prev = set_limits(replace(limits(), dedup_mode=mode,
                                  sparse_mode=1))
        try:
            best, out = timed(lambda: wgl3.check_steps3_long(
                rs_t, model, cfg))
        finally:
            set_limits(prev)
        table_res[name] = out
        lane[f"table_{name}_s"] = round(best, 4)
    off, on = table_res["off"], table_res["on"]
    for f in ("valid", "survived", "overflow", "dead_step"):
        assert off[f] == on[f], \
            f"dedup table-arm verdict drift on {f}: {table_res}"
    dd = on.get("dedup", {})
    assert dd.get("configs_pruned", 0) > 0, \
        f"symmetry-heavy corpus pruned nothing: {on}"
    lane["frontier_dedup_ratio"] = dd.get("frontier_dedup_ratio", 0.0)
    lane["configs_pruned"] = dd.get("configs_pruned", 0)
    # Raw vs unique configs/s, REPORTED SEPARATELY: raw counts the
    # dedup-off search's work, unique the canonical frontier's — gating
    # stays on the sort arm's events/s (bench_compare treats the
    # configs rates as informational).
    lane["raw_configs_per_sec"] = round(
        off["configs_explored"] / lane["table_off_s"], 1) \
        if lane["table_off_s"] else 0
    lane["unique_configs_per_sec"] = round(
        on["configs_explored"] / lane["table_on_s"], 1) \
        if lane["table_on_s"] else 0
    lane["max_frontier_off"] = off["max_frontier"]
    lane["max_frontier_on"] = on["max_frontier"]
    return lane


def bench_tuned(model, n_hist: int = 128, ops_range=(20, 300)) -> dict:
    """Tuned-profile lane (ISSUE 4 tentpole): ONE mixed-length corpus
    through the bucketed scheduler under the DATACLASS-DEFAULT limits
    profile, then under this platform's persisted tuning profile
    (tune/profile.py — whatever `jepsen-tpu tune` measured on this
    machine; the two arms are identical when no profile exists and the
    lane says so). Verdicts are asserted identical between arms — a
    tuned profile reroutes and re-chunks, it must never change an
    answer — and the lane reports both arms' events/s,
    `speedup_vs_default`, and the active profile hash. CPU-provable
    (tests/test_bench_smoke.py)."""
    from jepsen_etcd_demo_tpu import sched
    from jepsen_etcd_demo_tpu.ops.limits import KernelLimits, set_limits
    from jepsen_etcd_demo_tpu.tune import profile as tune_profile

    encs = build_mixed_corpus(n_hist, ops_range, seed=0x7D4E)
    events = int(sum(e.n_events for e in encs))
    # tuned_limits() is None while the platform is undetermined (no
    # initialized backend yet) — treat as "none apply" for the lane.
    tuned_fields = tune_profile.tuned_limits() or {}
    lane = {
        "histories": n_hist,
        "events": events,
        "profile_hash": tune_profile.profile_hash(),
        "tuned_fields": len(tuned_fields),
        "tuned": bool(tuned_fields),
    }
    verdicts = {}
    # set_limits installs a COMPLETE profile (beating the tuned file,
    # ops/limits.py precedence), so the default arm measures the shipped
    # dataclass values even on a machine with a profile; arm two clears
    # the programmatic override so the tuned profile resolves again.
    # set_limits returns the previous programmatic state (None included),
    # so the finally restores exactly what an embedding caller had.
    prev_set = set_limits(KernelLimits())
    try:
        for arm, prof in (("default", KernelLimits()), ("tuned", None)):
            set_limits(prof)
            results, kernel, _stats = sched.check_corpus(encs, model)  # warm
            best = float("inf")
            for _ in range(REPEATS):
                t0 = time.perf_counter()
                results, kernel, _stats = sched.check_corpus(encs, model)
                best = min(best, time.perf_counter() - t0)
            verdicts[arm] = results
            lane[f"{arm}_s"] = round(best, 4)
            lane[f"{arm}_events_per_sec"] = round(events / best, 1)
    finally:
        set_limits(prev_set)
    assert verdicts["default"] == verdicts["tuned"], \
        "tuned profile changed a verdict"
    lane["speedup_vs_default"] = (
        round(lane["default_s"] / lane["tuned_s"], 3)
        if lane["tuned_s"] else 0.0)
    return lane


def bench_serve(model, n_hist: int = 96, clients: int = 8,
                ops_range=(10, 48), n_procs: int = 4,
                coalesce_ms: int = 10, seed: int = 0x5E12E,
                invalid_every: int = 5, min_speedup: float | None = None
                ) -> dict:
    """Checking-as-a-service lane (ISSUE 13 tentpole): K concurrent CPU
    clients against an in-process serve daemon (the CoalescingScheduler
    core, exactly what `jepsen-tpu serve --check` dispatches through)
    vs the serial one-request-at-a-time baseline — SAME histories, SAME
    daemon configuration, daemon restarted between arms. The concurrent
    arm's win is the whole serving thesis: K closed-loop clients fill
    the coalescing window so per-launch dispatch overhead and the
    max-linger amortize across the batch, while the solo client pays
    both on every request (exactly the continuous-batching economics of
    inference serving; on parallel hardware the batched kernel itself
    adds the vectorization win on top — CPU only amortizes overhead).

    The warm pool is shared process state (that is the product), so
    both arms run after a warmup pass that compiles both arms' shapes —
    the lane measures request-path batching, not compile luck. The
    fixture keeps per-history concurrency small and uniform so the
    shared-geometry (max-k) padding of a coalesced batch stays honest
    work on a CPU (no SIMD batch axis to hide it).

    Reports aggregate events/s (gated round-over-round), p50/p99
    request latency and coalesced batch fill (informational), the
    warm-pool hit rate across the concurrent arm, and certifies every
    served verdict bit-identical to the post-hoc analyze route on the
    same encoded histories. A mix of valid and mutated-invalid
    histories keeps the parity check meaningful."""
    import threading

    from jepsen_etcd_demo_tpu import sched
    from jepsen_etcd_demo_tpu.ops import wgl3_pallas
    from jepsen_etcd_demo_tpu.ops.encode import encode_register_history
    from jepsen_etcd_demo_tpu.serve import CoalescingScheduler
    from jepsen_etcd_demo_tpu.utils.fuzz import (gen_register_history,
                                                 mutate_history)

    rng = random.Random(seed)
    lo, hi = ops_range
    encs = []
    for i in range(n_hist):
        hist = gen_register_history(rng, n_ops=rng.randrange(lo, hi),
                                    n_procs=n_procs, p_info=0.002)
        if invalid_every and i % invalid_every == invalid_every - 1:
            hist = mutate_history(rng, hist)
        encs.append(encode_register_history(hist, k_slots=8))
    events = int(sum(e.n_events for e in encs))

    # Post-hoc analyze route (the per-history auto router `analyze`
    # resolves through) — the parity oracle AND the warmup for the
    # serial arm's single-history shapes.
    posthoc = []
    for e in encs:
        outs, _kernel = wgl3_pallas.check_batch_encoded_auto([e], model)
        posthoc.append(outs[0])

    def run_arm(arm_clients: int) -> tuple[float, list, dict]:
        server = CoalescingScheduler(coalesce_ms=coalesce_ms)
        try:
            shards = [encs[i::arm_clients] for i in range(arm_clients)]
            idx_shards = [list(range(n_hist))[i::arm_clients]
                          for i in range(arm_clients)]
            results: list = [None] * n_hist
            errors: list = []

            def client(tenant_i: int):
                # Closed loop: submit, await the verdict, submit the
                # next — K of these concurrently is what the coalescer
                # merges into shared launches.
                try:
                    for idx, enc in zip(idx_shards[tenant_i],
                                        shards[tenant_i]):
                        req = server.submit(f"tenant-{tenant_i}", enc,
                                            model_name=model.name)
                        assert req.wait(300), "serve verdict timed out"
                        results[idx] = req.result
                except Exception as e:   # surfaced below, not swallowed
                    errors.append(e)

            threads = [threading.Thread(target=client, args=(i,))
                       for i in range(arm_clients)]
            t0 = time.perf_counter()
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            wall = time.perf_counter() - t0
            if errors:
                raise errors[0]
            stats = server.stats()
            return wall, results, stats
        finally:
            server.close()

    # Warmup: one throwaway concurrent pass compiles the coalesced
    # batch-bucket shapes the timed concurrent arm will launch.
    run_arm(arm_clients=clients)
    serial_wall, serial_results, _ = run_arm(arm_clients=1)
    cache1 = sched.kernel_cache().stats()
    conc_wall, conc_results, conc_stats = run_arm(arm_clients=clients)
    cache2 = sched.kernel_cache().stats()
    conc_lookups = (cache2["hits"] + cache2["misses"]
                    - cache1["hits"] - cache1["misses"])
    conc_hits = cache2["hits"] - cache1["hits"]

    # Parity: every served verdict (both arms) bit-identical to the
    # post-hoc analyze route on the same encoded history.
    for arm_name, arm in (("serial", serial_results),
                          ("concurrent", conc_results)):
        for i, (srv, post) in enumerate(zip(arm, posthoc)):
            assert srv["valid"] == post["valid"] \
                and srv["dead_step"] == int(post["dead_step"]), \
                (f"serve {arm_name} verdict diverged from analyze at "
                 f"history {i}: {srv['valid']}/{srv['dead_step']} vs "
                 f"{post['valid']}/{post['dead_step']}")

    lats = sorted(r["latency_s"] for r in conc_results)
    agg_eps = events / conc_wall
    serial_eps = events / serial_wall
    speedup = agg_eps / serial_eps if serial_eps else 0.0
    if min_speedup is not None:
        assert speedup >= min_speedup, \
            (f"serve acceptance: aggregate {agg_eps:.0f} ev/s is only "
             f"{speedup:.2f}x the serial baseline {serial_eps:.0f} ev/s "
             f"(need >= {min_speedup}x)")
    return {
        "histories": n_hist,
        "clients": clients,
        "events": events,
        "serial_s": round(serial_wall, 4),
        "concurrent_s": round(conc_wall, 4),
        "events_per_sec": round(agg_eps, 1),
        "serial_events_per_sec": round(serial_eps, 1),
        "speedup_vs_serial": round(speedup, 2),
        "latency_p50_ms": round(1000 * lats[len(lats) // 2], 2),
        "latency_p99_ms": round(
            1000 * lats[min(len(lats) - 1, int(0.99 * len(lats)))], 2),
        "batches": conc_stats["batches"],
        "coalesced_requests": conc_stats["coalesced_requests"],
        "batch_fill_avg": conc_stats["batch_fill_avg"],
        "cache_hit_rate": round(conc_hits / conc_lookups, 4)
        if conc_lookups else 1.0,
        "invalid": sum(1 for r in posthoc if r["valid"] is not True),
        "verdicts_identical": True,
    }


def _fleet_quantile(lats: list[float], q: float) -> float:
    if not lats:
        return 0.0
    s = sorted(lats)
    return s[min(len(s) - 1, int(q * len(s)))]


def bench_fleet(model, n_hist: int = 48, replicas: int = 2,
                ops_range=(8, 200), n_procs: int = 4,
                seed: int = 0xF1EE7, invalid_every: int = 7,
                max_knee_rungs: int = 4, assert_win: bool = True,
                request_timeout_s: float = 300.0) -> dict:
    """Fleet-scale serving lane (ISSUE 18 tentpole): N subprocess
    `serve --check` replicas behind the in-process shape-affine router
    (serve/router.py), driven OPEN-LOOP — Poisson arrivals at a fixed
    offered rate, the way a production inference fleet is loaded, not
    the closed-loop K-clients of the serve lane (closed loops
    self-throttle at the knee; open loops expose it).

    Two arms on identical corpora, schedules, and fresh fleets:
    *random* routing (the shape-blind control, fleet_spillover_mode=2)
    vs *affine* rendezvous routing. Each arm runs the same Poisson
    schedule twice — a cold pass that pays the compiles its routing
    policy induces, then a warm pass — so the arm aggregate carries the
    structural difference: random compiles ~every bucket on ~every
    replica, affine compiles each bucket once fleet-wide. Replicas run
    with the persistent XLA cache DISABLED (JEPSEN_TPU_NO_COMPILE_CACHE)
    so neither arm can launder its compile bill through the other's
    disk artifacts, and on the CPU backend — two subprocesses cannot
    share one TPU, and the lane measures routing economics (compile
    amortization, LRU locality, spillover), not chip throughput, which
    serve_agg_eps already gates.

    After the arms, an arrival-rate ladder walks the warm affine fleet
    to the latency knee: offered rate doubles per rung until p99
    inflects (> 4x the first rung's) or completions fall behind offered
    (< 0.7x), and the LAST GOOD rung's aggregate events/s and p99 are
    the gated `fleet_agg_eps` / `fleet_p99_s` headline — serving
    capacity at acceptable latency, not peak-burst throughput.

    Every verdict from every arm and pass is asserted bit-identical to
    the post-hoc analyze route; with `assert_win`, affine must beat
    random on whole-arm aggregate events/s AND warm kernel-cache hit
    rate (strictly)."""
    import threading
    import urllib.request

    from http.server import ThreadingHTTPServer

    from jepsen_etcd_demo_tpu.ops import wgl3_pallas
    from jepsen_etcd_demo_tpu.ops.encode import encode_register_history
    from jepsen_etcd_demo_tpu.serve.fleet import (FleetSupervisor,
                                                  make_fleet_handler)
    from jepsen_etcd_demo_tpu.serve.router import RANDOM, FleetRouter
    from jepsen_etcd_demo_tpu.utils.fuzz import (gen_register_history,
                                                 mutate_history)

    rng = random.Random(seed)
    lo, hi = ops_range
    hists, encs = [], []
    for i in range(n_hist):
        h = gen_register_history(rng, n_ops=rng.randrange(lo, hi),
                                 n_procs=n_procs, p_info=0.002)
        if invalid_every and i % invalid_every == invalid_every - 1:
            h = mutate_history(rng, h)
        hists.append(h)
        encs.append(encode_register_history(h, k_slots=8))
    events = int(sum(e.n_events for e in encs))
    bodies = [json.dumps({
        "tenant": f"tenant-{i % 3}", "model": model.name, "wait": True,
        "history": [json.loads(op.to_json()) for op in h],
    }).encode() for i, h in enumerate(hists)]

    posthoc = []
    for e in encs:
        outs, _kernel = wgl3_pallas.check_batch_encoded_auto([e], model)
        posthoc.append(outs[0])

    # One Poisson arrival schedule, reused by every pass of both arms
    # (same seed -> same offered load; the policy is the only variable).
    # The base rate is intentionally modest: the arms measure routing
    # economics under feasible load; the knee ladder finds capacity.
    arm_rate = max(2.0, n_hist / 12.0)
    sched_rng = random.Random(seed ^ 0xA221)
    t_arr, arm_schedule = 0.0, []
    for _ in range(n_hist):
        t_arr += sched_rng.expovariate(arm_rate)
        arm_schedule.append(t_arr)

    child_env = {
        "JAX_PLATFORMS": "cpu",
        "JEPSEN_TPU_NO_WARMUP": "1",
        "JEPSEN_TPU_NO_COMPILE_CACHE": "1",
        "JEPSEN_TPU_TELEMETRY": "0",
    }

    def open_loop_pass(base: str, schedule: list[float]
                       ) -> tuple[float, list, list]:
        """Dispatch every body at its absolute arrival offset; block a
        worker thread per request on the verdict. Returns (wall to last
        verdict, verdicts, latencies)."""
        results: list = [None] * len(bodies)
        lats: list = [0.0] * len(bodies)
        errors: list = []

        def worker(i: int):
            t_req = time.perf_counter()
            try:
                req = urllib.request.Request(
                    base + "/check", data=bodies[i],
                    headers={"Content-Type": "application/json"})
                with urllib.request.urlopen(
                        req, timeout=request_timeout_s) as r:
                    results[i] = json.loads(r.read().decode())
                lats[i] = time.perf_counter() - t_req
            except Exception as e:
                errors.append((i, e))

        threads = []
        t0 = time.perf_counter()
        for i, due in enumerate(schedule):
            delay = t0 + due - time.perf_counter()
            if delay > 0:
                time.sleep(delay)
            th = threading.Thread(target=worker, args=(i,))
            th.start()
            threads.append(th)
        for th in threads:
            th.join(request_timeout_s)
        wall = time.perf_counter() - t0
        if errors:
            i, e = errors[0]
            raise RuntimeError(
                f"fleet open-loop request {i} failed: "
                f"{type(e).__name__}: {e}")
        return wall, results, lats

    def fleet_up(mode: int):
        router = FleetRouter(spillover_mode=mode, salt=0,
                             poll_interval_s=0.5)
        sup = FleetSupervisor(_FLEET_STORE.name, n=replicas,
                              router=router, max_inflight=n_hist,
                              env=child_env)
        sup.start()
        httpd = ThreadingHTTPServer(
            ("127.0.0.1", 0),
            make_fleet_handler(_FLEET_STORE.name, router, sup))
        threading.Thread(target=httpd.serve_forever,
                         daemon=True).start()
        base = f"http://127.0.0.1:{httpd.server_address[1]}"
        return sup, router, httpd, base

    def fleet_down(sup, httpd):
        httpd.shutdown()
        httpd.server_close()
        sup.close()

    def replica_cache_totals(base: str) -> tuple[int, int]:
        with urllib.request.urlopen(base + "/serve/stats",
                                    timeout=30) as r:
            st = json.loads(r.read().decode())
        hits = misses = 0
        for rep in st["replicas"].values():
            kc = rep["scheduler"]["kernel_cache"]
            hits += kc["hits"]
            misses += kc["misses"]
        return hits, misses

    import tempfile
    _FLEET_STORE = tempfile.TemporaryDirectory(prefix="bench-fleet-")

    def run_arm(mode: int):
        """Fresh fleet, cold LRUs; the same schedule twice. The arm
        aggregate (both passes) carries the policy's compile bill; the
        warm pass isolates steady-state latency."""
        sup, router, httpd, base = fleet_up(mode)
        try:
            wall_a, res_a, lats_a = open_loop_pass(base, arm_schedule)
            wall_b, res_b, lats_b = open_loop_pass(base, arm_schedule)
            hits, misses = replica_cache_totals(base)
            with urllib.request.urlopen(base + "/fleet/stats",
                                        timeout=30) as r:
                fstats = json.loads(r.read().decode())
            lookups = hits + misses
            wall = wall_a + wall_b
            arm = {
                "wall_s": round(wall, 4),
                "agg_eps": round(2 * events / wall, 1),
                "agg_rps": round(2 * n_hist / wall, 2),
                "p50_s": round(_fleet_quantile(lats_a + lats_b, 0.50), 4),
                "p99_s": round(_fleet_quantile(lats_a + lats_b, 0.99), 4),
                "warm_p99_s": round(_fleet_quantile(lats_b, 0.99), 4),
                "hit_rate": round(hits / lookups, 4) if lookups else 0.0,
                "lookups": lookups,
            }
            return arm, (res_a, res_b), fstats, (sup, router, httpd, base)
        except BaseException:
            fleet_down(sup, httpd)
            raise

    # --- control arm: shape-blind random routing, then torn down ----
    rand_arm, rand_results, _rand_fstats, handles = run_arm(RANDOM)
    fleet_down(handles[0], handles[2])

    # --- affine arm: kept alive (warm) for the knee ladder -----------
    aff_arm, aff_results, aff_fstats, handles = run_arm(0)
    sup, router, httpd, base = handles

    # Verdict parity: every pass of every arm vs the analyze route.
    for arm_name, passes in (("random", rand_results),
                             ("affine", aff_results)):
        for res in passes:
            for i, (srv, post) in enumerate(zip(res, posthoc)):
                assert srv["valid"] == post["valid"] \
                    and srv["dead_step"] == int(post["dead_step"]), \
                    (f"fleet {arm_name} verdict diverged from analyze "
                     f"at history {i}: {srv['valid']}/{srv['dead_step']}"
                     f" vs {post['valid']}/{int(post['dead_step'])}")

    # --- open-loop knee ladder on the warm affine fleet --------------
    knee_rungs = []
    try:
        base_rate = max(arm_rate, 2 * n_hist / max(aff_arm["wall_s"], 1e-6))
        rate = base_rate / 2
        first_p99 = None
        for _ in range(max_knee_rungs):
            k_rng = random.Random(seed ^ int(rate * 1000))
            t_arr, schedule = 0.0, []
            for _ in range(n_hist):
                t_arr += k_rng.expovariate(rate)
                schedule.append(t_arr)
            wall, res, lats = open_loop_pass(base, schedule)
            p99 = _fleet_quantile(lats, 0.99)
            rung = {"offered_rps": round(rate, 2),
                    "agg_rps": round(n_hist / wall, 2),
                    "agg_eps": round(events / wall, 1),
                    "p99_s": round(p99, 4)}
            knee_rungs.append(rung)
            if first_p99 is None:
                first_p99 = max(p99, 1e-4)
            elif p99 > 4 * first_p99 \
                    or rung["agg_rps"] < 0.7 * rate:
                break   # past the knee — the previous rung is it
            rate *= 2
    finally:
        fleet_down(sup, httpd)
        _FLEET_STORE.cleanup()

    # The knee = the last rung still inside the latency/completion
    # envelope (the final entry may be the one that broke it).
    good = [r for r in knee_rungs
            if r["p99_s"] <= 4 * max(knee_rungs[0]["p99_s"], 1e-4)
            and r["agg_rps"] >= 0.7 * r["offered_rps"]]
    knee = good[-1] if good else knee_rungs[0]

    fills = {r["id"]: r["routed"] + r["spilled_in"]
             for r in aff_fstats["replicas"]}
    total_fill = sum(fills.values()) or 1
    spillover = int(aff_fstats["fleet"]["spillover"])

    if assert_win:
        assert aff_arm["agg_eps"] > rand_arm["agg_eps"], \
            (f"fleet acceptance: affine aggregate {aff_arm['agg_eps']} "
             f"ev/s does not beat random {rand_arm['agg_eps']} ev/s")
        assert aff_arm["hit_rate"] > rand_arm["hit_rate"], \
            (f"fleet acceptance: affine warm hit rate "
             f"{aff_arm['hit_rate']} not strictly above random "
             f"{rand_arm['hit_rate']}")

    return {
        # The replicas run on the CPU backend (a chip serves one
        # process): routing economics, not device throughput.
        "platform": "cpu",
        "replicas": replicas,
        "histories": n_hist,
        "events": events,
        "affine": aff_arm,
        "random": rand_arm,
        "hit_rate_delta": round(
            aff_arm["hit_rate"] - rand_arm["hit_rate"], 4),
        "agg_eps_ratio": round(
            aff_arm["agg_eps"] / rand_arm["agg_eps"], 2)
        if rand_arm["agg_eps"] else 0.0,
        "knee_rate_rps": knee["offered_rps"],
        "agg_eps": knee["agg_eps"],
        "p99_s": knee["p99_s"],
        "knee_rungs": knee_rungs,
        "spillover": spillover,
        "replica_fill": fills,
        "replica_fill_min": round(
            min(fills.values()) / total_fill, 4) if fills else 0.0,
        "invalid": sum(1 for r in posthoc if r["valid"] is not True),
        "verdicts_identical": True,
    }


def bench_campaign(model, n_specs: int = 48, seed: int = 0xCA3,
                   shrink_ops: int = 140) -> dict:
    """Scenario-factory lane (ISSUE 15 tentpole), three measurements:

      1. **Campaign end-to-end specs/s** — one smoke-scaled campaign
         (deterministic sim scenarios on the virtual-time loop, corpus-
         batched checking on the warm pool, triage + shrink + bank into
         a throwaway store) — the headline gated round over round.
      2. **Shrink-checks/s, batched vs sequential** — the SAME ddmin
         reduction of one seeded-invalid register history driven two
         ways: candidates re-checked as one corpus launch per round
         (the production route) vs one launch per candidate (what a
         naive shrinker pays). Identical candidate sequences by
         construction (verdicts are pure functions of candidates), so
         the speedup isolates the batching.
      3. **Banked-corpus replay wall** — the regression lane's cost:
         re-falsify everything the campaign banked in one batched
         launch per model.
    """
    import shutil
    import tempfile

    from jepsen_etcd_demo_tpu import sched
    from jepsen_etcd_demo_tpu.campaign import replay_corpus, run_campaign
    from jepsen_etcd_demo_tpu.campaign.triage import (ddmin_shrink,
                                                      make_check_batch)
    from jepsen_etcd_demo_tpu.utils.fuzz import (gen_register_history,
                                                 mutate_history)

    td = tempfile.mkdtemp(prefix="bench-campaign-")
    try:
        t0 = time.perf_counter()
        report = run_campaign(n_specs=n_specs, seed=seed, scale=0.4,
                              bug_rate=0.4, workers=4, store_root=td)
        campaign_wall = time.perf_counter() - t0
        rep = report.to_dict()

        # Shrink arms: a seeded-invalid history big enough that the
        # candidate batches have real width.
        rng = random.Random(seed)
        direct = lambda encs, m: sched.check_corpus(encs, m)[0]  # noqa: E731

        def sequential(encs, m):
            out = []
            for e in encs:
                out.extend(sched.check_corpus([e], m)[0])
            return out

        batched_probe = make_check_batch(model, direct)
        bad = None
        for _ in range(16):
            cand = mutate_history(
                rng, gen_register_history(rng, n_ops=shrink_ops,
                                          n_procs=6, p_info=0.01))
            if batched_probe([cand])[0]:
                bad = cand
                break
        assert bad is not None, "could not seed an invalid shrink fixture"
        # Warmup shrink compiles both arms' bucket shapes, then each
        # arm re-runs the identical reduction.
        ddmin_shrink(bad, batched_probe)
        t0 = time.perf_counter()
        sres = ddmin_shrink(bad, batched_probe)
        batched_wall = time.perf_counter() - t0
        t0 = time.perf_counter()
        seq_res = ddmin_shrink(bad, make_check_batch(model, sequential))
        seq_wall = time.perf_counter() - t0
        assert seq_res.to_ops == sres.to_ops \
            and seq_res.checks == sres.checks, \
            "sequential and batched ddmin diverged — candidate order " \
            "is no longer deterministic"

        t0 = time.perf_counter()
        replay = replay_corpus(td)
        replay_wall = time.perf_counter() - t0
        return {
            "specs": n_specs,
            "campaign_wall_s": round(campaign_wall, 4),
            "specs_per_sec": round(n_specs / campaign_wall, 2)
            if campaign_wall else 0.0,
            "keys_checked": rep["keys_checked"],
            "falsified_runs": rep["falsified_runs"],
            "unique_signatures": rep["unique_signatures"],
            "banked": len(rep["banked"]),
            "shrink_from_ops": sres.from_ops,
            "shrink_to_ops": sres.to_ops,
            "shrink_checks": sres.checks,
            "shrink_launches": sres.launches,
            "shrink_one_minimal": sres.one_minimal,
            "shrink_wall_s": round(batched_wall, 4),
            "shrink_checks_per_sec": round(sres.checks / batched_wall, 1)
            if batched_wall else 0.0,
            "sequential_shrink_wall_s": round(seq_wall, 4),
            "speedup_vs_sequential": round(seq_wall / batched_wall, 2)
            if batched_wall else 0.0,
            "replay_entries": replay["entries"],
            "replay_checked": replay["checked"],
            "replay_ok": replay["ok"],
            "replay_wall_s": round(replay_wall, 4),
        }
    finally:
        shutil.rmtree(td, ignore_errors=True)


def build_stream_run(n_keys: int = 16, ops_per_key: int = 400,
                     seed: int = 0x57CA):
    """ONE generated independent-key run for the streaming lane: per-key
    fuzzed register histories (valid by construction) with disjoint
    process-id ranges, round-robin interleaved into the single op stream
    a live run's recorder would produce, values wrapped as (key, v)
    tuples. Returns (interleaved ops, per-key histories) — the same run
    seen by both arms."""
    from jepsen_etcd_demo_tpu.utils.fuzz import (gen_register_history,
                                                 interleave_keyed)

    rng = random.Random(seed)
    per_key = [gen_register_history(rng, n_ops=ops_per_key,
                                    n_procs=N_PROCS, p_info=0.002)
               for _ in range(n_keys)]
    return interleave_keyed(per_key), per_key


def bench_streaming(model, n_keys: int = 16, ops_per_key: int = 400,
                    run_s: float = 0.8) -> dict:
    """Streaming check lane (ISSUE 5 tentpole): post-hoc vs streamed
    end-to-end wall clock on ONE generated run.

    The post arm pays run + the serial check tail
    (sched.check_corpus over the per-key encodings — the production
    post-hoc path); the stream arm replays the SAME op stream paced
    over `run_s` through the streaming session (stream/engine.py), so
    its tail is only the drain of whatever wasn't already swept while
    the "run" was live. Both arms are measured warm (kernels compiled
    by a first pass); verdicts are asserted bit-identical per key, and
    the lane reports the measured overlap_ratio — the acceptance
    criterion requires it > 0 on the CPU backend
    (tests/test_bench_smoke.py pins the contract at tiny scale).
    stream_flush_ops is pinned to 64 for the measurement so the chunk
    cadence (and therefore the lane) is machine-comparable."""
    from dataclasses import replace

    from jepsen_etcd_demo_tpu import sched
    from jepsen_etcd_demo_tpu.ops.encode import encode_register_history
    from jepsen_etcd_demo_tpu.ops.limits import limits, set_limits
    from jepsen_etcd_demo_tpu.stream import StreamSession

    ops, per_key = build_stream_run(n_keys, ops_per_key)
    encs = [encode_register_history(h, k_slots=32) for h in per_key]
    events = int(sum(e.n_events for e in encs))

    prev = set_limits(replace(limits(), stream_flush_ops=64))
    try:
        post_results, _k, _s = sched.check_corpus(encs, model)   # warm
        post_best = float("inf")
        for _ in range(REPEATS):
            t0 = time.perf_counter()
            post_results, _k, _s = sched.check_corpus(encs, model)
            post_best = min(post_best, time.perf_counter() - t0)
        assert all(r["valid"] is True for r in post_results)

        def replay():
            session = StreamSession(model, keyed=True)
            batches = 40
            per = (len(ops) + batches - 1) // batches
            t0 = time.perf_counter()
            for i in range(batches):
                for op in ops[i * per:(i + 1) * per]:
                    session.feed(op)
                time.sleep(run_s / batches)
            feed_wall = time.perf_counter() - t0
            t1 = time.perf_counter()
            results = session.finalize()
            drain = time.perf_counter() - t1
            return session, results, feed_wall, drain, \
                time.perf_counter() - t0

        replay()   # warm the (cfg, chunk) kernels through the session path
        session, sres, feed_wall, drain_s, stream_total = replay()
    finally:
        set_limits(prev)

    assert sres is not None and len(sres) == n_keys, \
        "streaming lane must stream every key"
    for k in range(n_keys):
        s, p = sres[k], post_results[k]
        for f in ("valid", "dead_step", "max_frontier",
                  "configs_explored"):
            assert s[f] == p[f], \
                f"streamed/post-hoc verdict drift on key {k} field {f}: " \
                f"{s[f]} != {p[f]}"
    stats = session.stats()
    post_total = feed_wall + post_best
    return {
        "keys": n_keys,
        "ops": len(ops),
        "events": events,
        "run_s": round(feed_wall, 4),
        "post_check_s": round(post_best, 4),
        "stream_drain_s": round(drain_s, 4),
        "post_total_s": round(post_total, 4),
        "stream_total_s": round(stream_total, 4),
        "speedup_total": (round(post_total / stream_total, 3)
                          if stream_total else 0.0),
        "overlap_ratio": stats["overlap_ratio"],
        "chunks": stats["chunks"],
        "restarts": stats["restarts"],
        "watermark_lag_max": stats["watermark_lag_max"],
        "kernel": "wgl3-dense-stream-chunked",
        "verdicts_identical": True,
    }


def bench_elle(n_txns: int = 10_000, n_keys: int = 100,
               corpus: int = 24, corpus_txns: int = 40) -> dict:
    """Elle transactional-checker lane (ISSUE 11 tentpole): ONE 10k-txn
    sparse list-append history (single-key txns over `n_keys` keys —
    the dependency graph decomposes into per-key components, the shape
    real multi-key workloads produce) checked end to end under three
    closure routes, plus a small mixed-validity corpus certified across
    EVERY route:

      * dense arm — limits().elle_mode=1: the seed [N, N] matrix-
        squaring closure on the whole graph (measured ONCE — at 10k
        nodes this is ~14 squarings of a [10112, 10112] f32 matmul);
      * auto arm (the GATED headline) — elle_mode=0: weak-component
        decomposition, vmapped bucketed batch launches for the small
        components, the tiled work-list kernel for big ones; best of
        REPEATS, events/s and txns/s reported;
      * tiled arm — elle_mode=2: the blocked work-list kernel forced on
        the whole graph (informational — on an interleaved graph most
        tiles are live, so this bounds the kernel, not the route);
      * oracle — the pinned pure-Python Tarjan/SCC cycle check on the
        same dependency graph (bench_baseline.json pinning, like every
        oracle denominator), plus the shared host inference wall.

    Verdicts: the 10k arms must agree (valid=True), and the corpus —
    half mutated to likely-anomalous — must produce BIT-IDENTICAL
    anomaly verdicts across dense / batched-auto / tiled / streamed /
    host-Tarjan-fallback routes (the acceptance criterion's 'all
    routes')."""
    import time as _time
    from dataclasses import replace

    from jepsen_etcd_demo_tpu import obs
    from jepsen_etcd_demo_tpu.checkers.elle import ElleChecker, ElleGraph
    from jepsen_etcd_demo_tpu.ops.limits import limits, set_limits
    from jepsen_etcd_demo_tpu.stream import ElleStreamSession
    from jepsen_etcd_demo_tpu.utils.fuzz import (append_txn_ops,
                                                 gen_append_txns,
                                                 mutate_append_txns)

    rng = random.Random(0xE11E)
    # Per-key txn runs are CONTIGUOUS (the workload-rotating-through-
    # keys shape): the dependency graph is block-diagonal, so the tiled
    # arm's occupancy skipping has real empty tiles to skip and the
    # auto arm's decomposition has real components — while the dense
    # arm still pays the full [N, N] closure either way.
    txns = []
    per_key = max(1, n_txns // n_keys)
    for k in range(n_keys):
        txns.extend(gen_append_txns(rng, n_txns=per_key, n_keys=1,
                                    max_len=1, first_key=k))
    n_txns = len(txns)
    history = append_txn_ops(txns)
    checker = ElleChecker()

    # Shared host inference wall (pairing + incremental graph build) —
    # identical across arms, measured once so the closure arms' deltas
    # are attributable to the closure route alone.
    t0 = _time.perf_counter()
    graph = ElleGraph()
    from jepsen_etcd_demo_tpu.checkers.elle import _pair_txns

    for t in _pair_txns(history):
        graph.add_txn(*t)
    ww, wr, rw = graph.edge_matrices()
    infer_s = _time.perf_counter() - t0
    full = ww | wr | rw
    n_nodes = full.shape[0]
    edges = int(full.sum())

    lane = {"txns": n_txns, "events": len(history), "keys": n_keys,
            "graph_nodes": n_nodes, "graph_edges": edges,
            "infer_s": round(infer_s, 4)}

    def timed_check(mode: int, repeats: int):
        prev = set_limits(replace(limits(), elle_mode=mode))
        try:
            out = checker.check({}, history)       # warm the kernels
            best = float("inf")
            for _ in range(repeats):
                t0 = _time.perf_counter()
                out = checker.check({}, history)
                best = min(best, _time.perf_counter() - t0)
        finally:
            set_limits(prev)
        return best, out

    dense_s, dense_out = timed_check(1, repeats=1)
    auto_s, auto_out = timed_check(0, repeats=REPEATS)
    tiled_s, tiled_out = timed_check(2, repeats=1)
    for name, out in (("dense", dense_out), ("auto", auto_out),
                      ("tiled", tiled_out)):
        assert out["valid"] is True, f"elle lane {name} arm: {out}"
    assert dense_out == auto_out == tiled_out, \
        "elle route verdict drift on the 10k history"

    # Pinned Tarjan/SCC oracle on the same dependency graph.
    from jepsen_etcd_demo_tpu.ops.cycles import _host_cycle_mask

    sig = {"lane": "elle", "txns": n_txns, "nodes": n_nodes,
           "edges": edges,
           "checksum": int(np.flatnonzero(full).sum() & 0x7FFFFFFF)}
    oracle_s = _pinned_oracle("elle", sig)
    pinned = oracle_s is not None
    if not pinned:
        t0 = _time.perf_counter()
        assert not _host_cycle_mask(full).any()
        oracle_s = _time.perf_counter() - t0
        _pin_oracle("elle", sig, oracle_s)

    lane.update({
        "dense_s": round(dense_s, 4),
        "auto_s": round(auto_s, 4),
        "tiled_s": round(tiled_s, 4),
        "oracle_s": round(oracle_s, 4),
        "oracle_pinned": pinned,
        "events_per_sec": round(len(history) / auto_s, 1),
        "txns_per_sec": round(n_txns / auto_s, 1),
        "speedup_vs_dense": round(dense_s / auto_s, 2) if auto_s else 0.0,
        "vs_oracle": (round((infer_s + oracle_s) / auto_s, 2)
                      if auto_s else 0.0),
        "kernel": "elle-closure-batch",
    })

    # Mixed-validity certification across EVERY route: dense, batched
    # auto, tiled, streamed, and the host Tarjan fallback (cell budget
    # pinned below any graph so every closure takes the SCC oracle).
    crng = random.Random(0xE11F)
    cases = []
    for i in range(corpus):
        t = gen_append_txns(crng, n_txns=corpus_txns, n_keys=4, max_len=3)
        if i % 2:
            t = mutate_append_txns(crng, t)
        cases.append(append_txn_ops(t))
    routes = {"dense": {"elle_mode": 1}, "auto": {"elle_mode": 0},
              "tiled": {"elle_mode": 2},
              "tarjan": {"elle_mode": 0, "elle_cell_budget": 1 << 12}}
    verdicts: dict[str, list] = {}
    for name, overrides in routes.items():
        prev = set_limits(replace(limits(), **overrides))
        try:
            with obs.capture() as rcap:
                verdicts[name] = [checker.check({}, h) for h in cases]
            if name == "tarjan":
                # The certification's independence claim: the pinned
                # budget must actually route every closure to the host
                # SCC oracle, not re-run a device route.
                rstats = obs.elle_stats(rcap.metrics)
                assert rstats["graphs_oracle"] > 0, rstats
                assert rstats["graphs_dense"] == 0, rstats
        finally:
            set_limits(prev)
    streamed = []
    for h in cases:
        session = ElleStreamSession(checker)
        for op in h:
            session.feed(op)
        res = session.finalize()
        assert res is not None, "elle lane corpus must stream"
        one = dict(res["elle"])
        one.pop("streamed", None)
        streamed.append(one)
    verdicts["streamed"] = streamed
    ref = verdicts["tarjan"]
    mismatches = sum(
        1 for name, outs in verdicts.items()
        for a, b in zip(outs, ref)
        if (a["valid"], a["anomaly_types"]) != (b["valid"],
                                               b["anomaly_types"]))
    invalid = sum(1 for r in ref if r["valid"] is False)
    assert invalid >= corpus // 4, f"tame elle mutation sweep: {invalid}"
    assert mismatches == 0, f"elle route certification: {verdicts}"
    lane["corpus"] = {"histories": corpus, "invalid": invalid,
                      "routes": sorted(verdicts), "mismatches": 0}
    lane["verdicts_identical"] = True
    return lane


def _profile_record() -> dict:
    """The profile stamp every bench record carries: active hash,
    tuned-field count, every non-default field with its provenance tag,
    and the tool that prints the full table."""
    try:
        from jepsen_etcd_demo_tpu.tune import profile as tune_profile

        rec = tune_profile.run_record()
    except Exception:
        from jepsen_etcd_demo_tpu import obs

        rec = {"hash": obs.active_profile_hash(), "tuned_fields": 0,
               "overrides": {}}
    rec["inspect"] = "python tools/print_profile.py"
    return rec


def bench_invalid_lane(model) -> dict:
    """Mixed-validity certification of the COMPILED pallas kernels
    (VERDICT r3 item 2: every prior bench lane was valid-by-construction,
    so nothing run on hardware had ever returned valid=False). 128
    histories, half mutated to likely-invalid, expected verdicts from the
    host oracle and per-field expectations (dead_step included) from the
    XLA dense kernel; both compiled pallas kernels — per-history and
    grouped — must agree exactly. Mismatches land in the JSON (and a
    nonzero count fails the bench loudly)."""
    from jepsen_etcd_demo_tpu.checkers.oracle import check_events_oracle
    from jepsen_etcd_demo_tpu.ops import wgl3, wgl3_pallas
    from jepsen_etcd_demo_tpu.ops.encode import encode_register_history
    from jepsen_etcd_demo_tpu.utils.fuzz import (gen_register_history,
                                                 mutate_history)

    rng = random.Random(0x1BAD)
    encs, hists, oracle_valid = [], [], []
    for i in range(128):
        h = gen_register_history(rng, n_ops=60, n_procs=8, p_info=0.01)
        if i % 2:
            h = mutate_history(rng, h)
        enc = encode_register_history(h, k_slots=16)
        encs.append(enc)
        hists.append(h)
        oracle_valid.append(check_events_oracle(enc, model).valid)

    cfg, steps, r_cap = wgl3.batch_steps3(encs, model)
    arrays = wgl3.stack_steps3(steps, r_cap)
    expected = wgl3.assemble_batch_results(
        wgl3.unpack_np(wgl3.cached_batch_checker3_packed(model, cfg)
                       (*arrays)), steps, cfg)
    lane = {"histories": len(encs),
            "invalid": sum(1 for v in oracle_valid if v is False),
            "mismatches": 0, "kernels": []}
    # The lane must actually exercise the dead/prune path, not fuzz tame.
    assert lane["invalid"] >= 16, f"tame mutation sweep: {lane['invalid']}"
    lane["mismatches"] += sum(
        1 for e, ov in zip(expected, oracle_valid) if e["valid"] is not ov)

    if not wgl3_pallas.use_pallas(cfg, n_steps=r_cap):
        lane["kernels"] = ["skipped: pallas unavailable on this backend"]
        return lane
    pallas_out = None
    for check, name in (
            (wgl3_pallas.cached_batch_checker_pallas(model, cfg),
             "wgl3-dense-pallas"),
            (wgl3_pallas.cached_batch_checker_pallas_grouped(model, cfg),
             "wgl3-dense-pallas-grouped")):
        out = wgl3.assemble_batch_results(
            wgl3.unpack_np(check(*arrays)), steps, cfg)
        if pallas_out is None:
            pallas_out = out
        mm = sum(1 for o, e in zip(out, expected)
                 if (o["valid"], o["dead_step"], o["max_frontier"],
                     o["configs_explored"])
                 != (e["valid"], e["dead_step"], e["max_frontier"],
                     e["configs_explored"]))
        lane["kernels"].append({"kernel": name, "mismatches": mm})
        lane["mismatches"] += mm

    lane["witnesses"] = _certify_witnesses(model, encs, hists, pallas_out,
                                           oracle_valid)
    lane["mismatches"] += lane["witnesses"]["mismatches"]

    # The RESUMABLE windowed kernel's compiled dead path: one long
    # mutated history driven in small windows (state carried across
    # launches), against the XLA chunked sweep.
    from dataclasses import replace

    from jepsen_etcd_demo_tpu.ops.encode import (encode_return_steps,
                                                 reslot_events)
    from jepsen_etcd_demo_tpu.ops.limits import limits, set_limits

    # dedup_mode pinned OFF for this certification: the pallas kernels
    # run no canonicalization pass, and the lane compares the SEARCH
    # metrics (max_frontier) bit-for-bit — the dedup lane owns the
    # canonicalized numbers. replace(), not a fresh KernelLimits: the
    # active profile may carry env overrides that must keep applying.
    prev = set_limits(replace(limits(), dedup_mode=1))
    try:
        for _ in range(20):   # mutations are LIKELY-invalid; insist on it
            h = mutate_history(rng, gen_register_history(
                rng, n_ops=4000, n_procs=8, p_info=0.002))
            enc = encode_register_history(h, k_slots=16)
            k = wgl3.tight_k_slots(enc)
            lcfg = wgl3.dense_config(model, k, enc.max_value)
            enc = reslot_events(enc, k) if enc.k_slots != k else enc
            rs = encode_return_steps(enc)
            ref = wgl3.check_steps3_long(rs, model, lcfg, chunk=512)
            if ref["valid"] is False:
                break
        assert ref["valid"] is False, "no invalid long mutation in 20 tries"
        set_limits(replace(limits(), dedup_mode=1, max_r_pallas=512))
        got = wgl3_pallas.check_steps3_long_pallas(rs, model, lcfg)
    finally:
        set_limits(prev)
    mm = sum(1 for f in ("valid", "survived", "dead_step", "max_frontier")
             if got[f] != ref[f])
    lane["kernels"].append({"kernel": "wgl3-dense-pallas-chunked",
                            "mismatches": mm,
                            "valid": bool(ref["valid"])})
    lane["mismatches"] += mm
    assert lane["mismatches"] == 0, f"invalid-lane certification: {lane}"
    return lane


def _certify_witnesses(model, encs, hists, pallas_out, oracle_valid,
                       n: int = 8) -> dict:
    """VERDICT r4 next #7: witness reconstruction had only ever consumed
    CPU-backend verdicts. Here the full Linearizable._explain ladder runs
    on the TPU kernel's OWN results for `n` invalid histories, and the
    reconstructed failing op must be the op returning at the host
    oracle's dead event — closing the last uncertified TPU surface (the
    dense frontier-recovery rungs re-run kernels downstream of these
    fields). knossos always emits its failing-op analysis
    (/root/reference/src/jepsen/etcdemo.clj:117); this proves ours is
    correct when fed from hardware."""
    from jepsen_etcd_demo_tpu.checkers.linearizable import (Linearizable,
                                                            _event_to_step)
    from jepsen_etcd_demo_tpu.checkers.oracle import check_events_oracle

    lin = Linearizable(model=model)
    out = {"checked": 0, "mismatches": 0, "detail": []}
    for i, valid in enumerate(oracle_valid):
        if valid is not False or out["checked"] >= n:
            continue
        enc = encs[i]
        ores = check_events_oracle(enc, model)
        ev = enc.events[ores.dead_event]
        want_op = model.describe_op(int(ev[2]), int(ev[3]), int(ev[4]),
                                    int(ev[5]))
        res = dict(pallas_out[i])          # the HARDWARE-produced verdict
        if res["valid"] is not False:
            # Kernel/oracle disagreement: already counted by the lane's
            # per-field mismatch pass; record it here too rather than
            # aborting the whole bench on an assert.
            out["checked"] += 1
            out["mismatches"] += 1
            out["detail"].append({"history": i,
                                  "kernel_valid": res["valid"],
                                  "oracle_valid": False})
            continue
        lin._explain(res, enc, model.prepare_history(hists[i]), None)
        ok = (res.get("failed_op") == want_op
              and res.get("witness") not in (None, "skipped")
              and res["dead_step"] == _event_to_step(enc, ores.dead_event))
        out["checked"] += 1
        if not ok:
            out["mismatches"] += 1
            out["detail"].append({
                "history": i, "want_op": want_op,
                "failed_op": res.get("failed_op"),
                "witness": res.get("witness")})
    assert out["checked"] >= 4, f"too few invalid histories: {out}"
    return out


def bench_long(model, n_ops: int, oracle_too: bool, p_info: float = 0.0005):
    """One long single-register history through the single dense kernel.

    p_info scales the forever-pending population; past ~17 simultaneously
    pending ops the geometry leaves the dense budget (that axis is the
    lattice-sharded kernel's lane, not this one), so the 100k lane runs
    with p_info=0 — history LENGTH is the variable here."""
    from jepsen_etcd_demo_tpu.checkers.oracle import check_events_oracle
    from jepsen_etcd_demo_tpu.ops import wgl3_pallas
    from jepsen_etcd_demo_tpu.ops.encode import encode_register_history
    from jepsen_etcd_demo_tpu.utils.fuzz import gen_register_history

    from dataclasses import replace

    from jepsen_etcd_demo_tpu.ops.limits import limits, set_limits

    rng = random.Random(0x10C0 + n_ops)
    h = gen_register_history(rng, n_ops=n_ops, n_procs=N_PROCS,
                             p_info=p_info)
    enc = encode_register_history(h, k_slots=64)
    run = lambda: wgl3_pallas.check_batch_encoded_auto([enc], model)

    # This lane measures the DEVICE KERNEL (round-over-round
    # comparability): pin the small-history oracle router off for the
    # measurement, then report the router's production-path wall
    # separately as routed_s when it would engage.
    prev = set_limits(replace(limits(), oracle_crossover_events=0))
    try:
        t0 = time.perf_counter()
        results, kernel = run()             # includes compile (cold)
        cold_s = time.perf_counter() - t0
        out = results[0]
        assert out["valid"] is True
        t0 = time.perf_counter()
        results, kernel = run()
        warm_s = time.perf_counter() - t0
        out = results[0]
    finally:
        set_limits(prev)
    d = {"ops": n_ops, "kernel_s": warm_s, "kernel_cold_s": cold_s,
         # The ROUTER's name, not the per-history dict's (which only the
         # ladder paths stamp): single-history pallas was mislabeled
         # "wgl3-dense" before.
         "kernel": kernel}
    # The resolved (calibrated or pinned) crossover decides whether the
    # production router would take the oracle here — report that path's
    # wall separately when it engages.
    if enc.n_events <= wgl3_pallas._oracle_crossover():
        results, routed_kernel = run()      # warm routed path
        t0 = time.perf_counter()
        results, routed_kernel = run()
        d["routed_s"] = time.perf_counter() - t0
        d["routed_kernel"] = routed_kernel
    if oracle_too:
        t0 = time.perf_counter()
        res = check_events_oracle(enc, model)
        assert res.valid
        d["oracle_s"] = time.perf_counter() - t0
    return d


def bench_longhaul(model, events: int | None = None,
                   seg_events: int = 16384, seed: int = 0x10A6,
                   rss_budget_mb: int = 512,
                   crosscheck_cap: int = 120_000) -> dict:
    """Long-haul out-of-core lane (ISSUE 20 tentpole): a synthetic
    multi-segment register history is generated chunk-by-chunk (the
    whole history NEVER exists in RAM), encoded through the
    content-addressed cache tier, and checked end-to-end through the
    spilled wgl2 route (stream/longhaul.py) under a PINNED host RSS
    budget — `peak_rss_mb` is the lane's ru_maxrss DELTA, gated
    inverted (lower is better) by tools/bench_compare.py next to the
    gated `longhaul_eps` throughput.

    Default scale keeps the driver's bench round fast;
    JEPSEN_TPU_BENCH_LONGHAUL_EVENTS scales the same lane to 10^8+
    events for the full out-of-core claim. Verdict parity is certified
    every round at the largest cross-checkable scale: the spilled route
    and the all-RAM route (host_spill_mode pinned off) must agree on
    survived/dead_step bit-identically."""
    import shutil
    import tempfile

    from dataclasses import replace

    from jepsen_etcd_demo_tpu.ops.limits import limits, set_limits
    from jepsen_etcd_demo_tpu.store import spill
    from jepsen_etcd_demo_tpu.stream import longhaul

    if events is None:
        events = int(os.environ.get(
            "JEPSEN_TPU_BENCH_LONGHAUL_EVENTS", 120_000))
    # Pay the XLA compile (and its RSS spike) BEFORE the measured lane:
    # the gated peak_rss_mb must measure the out-of-core engine, not
    # the one-time jit of the chunk kernel.
    longhaul.run_longhaul(model, events=4096, seg_events=2048,
                          seed=seed ^ 0x5A5A)
    td = tempfile.mkdtemp(prefix="jepsen-longhaul-")
    prev = set_limits(replace(limits(), host_spill_mode=2,
                              host_rss_budget_mb=rss_budget_mb))
    try:
        with spill.spilling(td):
            rec = longhaul.run_longhaul(
                model, events=events, seg_events=seg_events, seed=seed)
        ce = min(events, crosscheck_cap)
        if ce == events:
            spilled_verdict = (rec["survived"], rec["dead_step"])
        else:
            with spill.spilling(td):
                cc_spill = longhaul.run_longhaul(
                    model, events=ce, seg_events=seg_events, seed=seed,
                    tag="longhaul-cc")
            spilled_verdict = (cc_spill["survived"],
                               cc_spill["dead_step"])
        set_limits(replace(limits(), host_spill_mode=1))
        inram = longhaul.run_longhaul(model, events=ce,
                                      seg_events=seg_events, seed=seed)
        identical = spilled_verdict == (inram["survived"],
                                        inram["dead_step"])
        assert identical, (
            f"longhaul verdict divergence at {ce} events: spilled "
            f"{spilled_verdict} vs in-RAM "
            f"{(inram['survived'], inram['dead_step'])}")
    finally:
        set_limits(prev)
        shutil.rmtree(td, ignore_errors=True)
    rec["verdicts_identical"] = identical
    rec["crosscheck_events"] = ce
    rec["kernel"] = "wgl2-sort-chunked"
    return rec


def bench_100k(model) -> dict:
    """Opt-in 100k-op lane (BENCH_100K=1; minutes of wall clock): one
    100k-op register history through the production router — the step
    count exceeds one scan program, so this exercises the host-chunked
    dense sweep end to end (VERDICT r2 weak #7: record the claim or drop
    it). The result is cached in bench_100k.json (committed) and merged
    into every subsequent bench line."""
    d = bench_long(model, 100_000, oracle_too=False, p_info=0.0)
    d["recorded_at"] = time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime())
    import jax

    d["device"] = str(jax.devices()[0])
    LONG100K_FILE.write_text(json.dumps(d, indent=2) + "\n")
    print(f"# recorded 100k-op lane -> {LONG100K_FILE.name} (commit it)",
          file=sys.stderr)
    return d


def main():
    import jax

    from jepsen_etcd_demo_tpu import obs
    from jepsen_etcd_demo_tpu.models import CASRegister
    from jepsen_etcd_demo_tpu.sched import enable_persistent_cache

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        # A measurement that finds no chip fails; it never reruns the
        # lanes on the CPU under the device metric's name.
        print(f"bench.py: no TPU (jax.devices()[0].platform="
              f"{dev.platform!r}); nothing measured", file=sys.stderr)
        return 1
    enable_persistent_cache()   # kernel_cold_s amortizes across runs
    model = CASRegister()
    # SURVEY.md §5.1: jax.profiler traces for the checker kernel itself.
    # Opt-in (BENCH_PROFILE=<dir> or --profile <dir>) so the driver's plain
    # `python bench.py` stays fast; view with tensorboard/xprof.
    profile_dir = os.environ.get("BENCH_PROFILE")
    if "--profile" in sys.argv:
        profile_dir = sys.argv[sys.argv.index("--profile") + 1]
    # Every lane runs under one telemetry capture (obs/): the kernel-phase
    # breakdown printed next to the throughput figure is the same
    # compile/execute/encode attribution a test run writes to its
    # metrics.json, aggregated over the whole bench.
    with obs.capture() as cap:
        if profile_dir:
            with jax.profiler.trace(profile_dir):
                corpus = bench_corpus(model)
            print(f"# profiler trace written to {profile_dir}",
                  file=sys.stderr)
        else:
            corpus = bench_corpus(model)
        longs = [bench_long(model, n, oracle_too=(n <= 1000))
                 for n in LONG_OPS]
        gset = bench_gset_corpus()
        invalid_lane = bench_invalid_lane(model)
        # The lane opens its own nested captures (cold/warm
        # kernel-phase attribution), which shadow this one — its
        # numbers land in the top-level padding_waste /
        # cache_hit_rate fields instead.
        sched_lane = bench_sched_corpus(model)
        # Sparse active-tile lane: dense-vs-sparse sweep on one wide
        # long history (ISSUE 3) — the win measured, not asserted.
        sparse_lane = bench_sparse(model)
        # Frontier-dedup lane (ISSUE 10): dedup-off vs dedup-on on
        # one symmetry-heavy history, verdicts asserted identical,
        # raw vs unique configs/s reported separately.
        dedup_lane = bench_dedup(model)
        # Tuned-profile lane (ISSUE 4): default vs tuned-profile
        # limits on one corpus, verdicts asserted identical.
        tuned_lane = bench_tuned(model)
        # Streaming check lane (ISSUE 5): post-hoc vs streamed
        # end-to-end wall on one generated run, verdicts asserted
        # bit-identical, overlap_ratio measured.
        stream_lane = bench_streaming(model)
        # Elle transactional-checker lane (ISSUE 11): dense vs
        # tiled/batched closure on one 10k-txn sparse history,
        # verdicts certified bit-identical across every route.
        elle_lane = bench_elle()
        # Checking-as-a-service lane (ISSUE 13): K concurrent
        # clients against the in-process continuous-batching
        # daemon vs the serial baseline, verdicts certified
        # bit-identical to the analyze route; acceptance >= 3x.
        serve_lane = bench_serve(model, min_speedup=3.0)
        # Fleet-scale serving lane (ISSUE 18): open-loop Poisson
        # arrivals against N subprocess replicas behind the shape-
        # affine router; affine must beat shape-blind random on
        # aggregate events/s AND warm cache hit rate, p99 reported
        # at the measured latency knee, verdicts certified
        # bit-identical to the analyze route.
        fleet_lane = bench_fleet(model)
        # Scenario-factory lane (ISSUE 15): campaign specs/s end to
        # end, batched-vs-sequential ddmin shrink checks/s, and the
        # banked-corpus replay wall.
        campaign_lane = bench_campaign(model)
        # Long-haul out-of-core lane (ISSUE 20): segment-chained
        # checking through the spill tier under a pinned host RSS
        # budget; spilled vs in-RAM verdicts certified identical.
        longhaul_lane = bench_longhaul(model)
        # Inside the capture: the 100k lane's compile/execute/encode
        # seconds must land in the same kernel_phases breakdown as
        # every other lane when it actually runs.
        long100k = bench_100k(model) if os.environ.get("BENCH_100K") \
            else None

    if long100k is None:
        try:
            long100k = json.loads(LONG100K_FILE.read_text())
        except (OSError, ValueError):
            long100k = None

    kernel_eps = corpus["events"] / corpus["kernel_s"]
    oracle_eps = corpus["events"] / corpus["oracle_s"]
    detail = {
        "device": str(jax.devices()[0]),
        "corpus": CORPUS,
        "ops_per_history": N_OPS,
        "batch_wall_s": round(corpus["kernel_s"], 4),
        "oracle_wall_s": round(corpus["oracle_s"], 4),
        "oracle_pinned": corpus["oracle_pinned"],
        "histories_per_sec": round(corpus["histories_per_sec"], 2),
        "configs_per_sec": round(corpus["configs_per_sec"], 1),
        "kernel": corpus["kernel"],
        "k_slots": corpus["k_slots"],
        "table_cells": corpus["table_cells"],
        "long_history": [
            {k: (round(v, 4) if isinstance(v, float) else v)
             for k, v in d.items()} for d in longs],
        "gset_corpus": gset,
        "invalid_lane": invalid_lane,
        "corpus_sched": sched_lane,
        "sparse": sparse_lane,
        "dedup": dedup_lane,
        "tuned": tuned_lane,
        "streaming": stream_lane,
        "elle": elle_lane,
        "serve": serve_lane,
        "fleet": fleet_lane,
        "campaign": campaign_lane,
        "longhaul": longhaul_lane,
    }
    if "roofline" in corpus:
        detail["roofline"] = corpus["roofline"]
    try:
        # The measured oracle/device crossover the production router uses
        # on this platform (VERDICT r4 #3: recorded, not assumed).
        from dataclasses import asdict

        from jepsen_etcd_demo_tpu.ops.calibrate import get_calibration
        detail["calibration"] = asdict(get_calibration())
    except Exception as e:
        detail["calibration"] = {"error": str(e)}
    if long100k:
        detail["long_history_100k"] = long100k
    print(json.dumps({
        "metric": "wgl_check_throughput",
        "value": round(kernel_eps, 1),
        "unit": "history-events/sec",
        "vs_baseline": round(kernel_eps / oracle_eps, 2),
        # Where the harness's own time went (obs/): first-call compile vs
        # steady-state execute wall, host encode seconds, and the live-
        # config high-water mark — doc/telemetry.md maps each field to
        # its underlying metric key.
        "kernel_phases": obs.kernel_phases(cap.metrics),
        # The scheduler lane's contract fields (doc/perf.md): measured
        # padded/real step ratio across its bucketed launches and the
        # kernel-LRU hit rate of its warm pass.
        "padding_waste": sched_lane["padding_waste"],
        "cache_hit_rate": sched_lane["cache_hit_rate"],
        # Sparse-sweep accounting aggregated over the whole bench
        # capture (doc/perf.md): live-tile-ratio gauge + per-mode step/
        # check counters — zeros permitted, never absent.
        "sweep": obs.sweep_stats(cap.metrics),
        # Elle closure-engine accounting over the same capture
        # (ISSUE 11): per-route graph counts, launches, tiled rounds,
        # streamed txns — zeros permitted, never absent.
        "elle": obs.elle_stats(cap.metrics),
        # Serve-daemon accounting over the same capture (ISSUE 13):
        # request/batch/admission counters and latency quantiles —
        # zeros permitted, never absent.
        "serve": obs.serve_stats(cap.metrics),
        # Fleet-router accounting over the same capture (ISSUE 18):
        # routed/spillover/error/reject counters and replica occupancy
        # gauges — zeros permitted, never absent; detail.fleet carries
        # the measured open-loop lane.
        "fleet": obs.fleet_stats(cap.metrics),
        # Scenario-factory accounting over the same capture (ISSUE 15):
        # spec/falsification/shrink/bank counters — zeros permitted,
        # never absent.
        "campaign": obs.campaign_stats(cap.metrics),
        # Scaling-ledger accounting over the same capture (ISSUE 16):
        # launch count and per-bucket seconds (useful execute vs
        # padding/straggler waste, encode, H2D, compile, dispatch gap)
        # — zeros permitted, never absent; the corpus_sched lane's
        # `ledger` object carries the windowed attribution.
        "ledger": obs.ledger_stats(cap.metrics),
        # Spill-tier accounting over the same capture (ISSUE 20):
        # out-of-core read/write/eviction counters, the compress-ratio
        # and peak-RSS gauges — zeros permitted, never absent;
        # detail.longhaul carries the measured RSS-ceiling lane.
        "longhaul": obs.longhaul_stats(cap.metrics),
        # The tuning profile this round resolved (ISSUE 4): hash +
        # non-default fields with provenance; detail.tuned measures it.
        "profile": _profile_record(),
        # The device every number above ran on.
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(jax.devices())},
        "detail": detail,
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
