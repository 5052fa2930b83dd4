"""stream/longhaul.py — billion-event out-of-core checking (ISSUE 20).

The quiescent-boundary insight: at any history point where every invoked
op has RETURNED, each surviving config's pending mask is zero — the
whole search frontier collapses to a plain set of model states. A long
history cut at quiescent points therefore checks EXACTLY, segment by
segment, with an O(frontier) carry between segments: wgl2's
``init_frontier`` seeds segment k+1 from segment k's final state set,
and the concatenated verdict (survived / global dead step) is
bit-identical to checking the whole history in one piece — which is the
point: the whole history NEVER EXISTS. Each segment is generated on
demand from a seed (deterministic, resumable), encoded through the
content-addressed encode-cache tier, checked through the chunked sort
kernel (which spills its own intra-segment chunk checkpoints through
store/spill.py), and dropped.

Determinism under resume: every segment ends with an ANCHOR WRITE whose
value is derived from (seed, segment) alone, so segment k+1's
ground-truth initial register value is computable WITHOUT generating
segment k — a crash-resumed lane regenerates only the segment it died
in. The segment-chain checkpoint (``<tag>.seg`` in the active SpillDir)
carries the checker's own frontier state set; a torn checkpoint decodes
as absent and the lane recomputes from the start — slower, never wrong.

RSS accounting: the lane reports ``peak_rss_mb`` as the DELTA of
``ru_maxrss`` over the lane (store/spill.py rss_mb), checked against the
``host_rss_budget_mb`` knob (``rss_ok``), which holds the whole
out-of-core claim to a pinned ceiling.
"""

from __future__ import annotations

import random
import time
from typing import Any, Optional

import numpy as np

from .. import obs
from ..ops.limits import limits
from ..ops.op import INVOKE, OK, Op
from ..store import encode_cache
from ..store import spill as _spill
from ..utils.fuzz import gen_register_history

DEFAULT_SEG_EVENTS = 8192


def anchor_value(seed: int, k: int, value_range: int) -> int:
    """The deterministic register value segment k ends on — a pure
    function of (seed, k), so a resume at segment k+1 knows its initial
    state without generating segment k."""
    return random.Random(f"{seed}|anchor|{k}").randrange(value_range)


def segment_history(seed: int, k: int, n_ops: int, n_procs: int = 4,
                    value_range: int = 5) -> list[Op]:
    """Segment k of the synthetic long-haul history: a valid concurrent
    register history (utils/fuzz.py ground-truth simulation) starting
    from segment k-1's anchor value, QUIESCENT at both ends (p_info=0:
    every invoked op returns), closed by the anchor write for segment
    k. Deterministic per (seed, k) — resumable generation."""
    rng = random.Random(f"{seed}|seg|{k}")
    init = anchor_value(seed, k - 1, value_range) if k > 0 else None
    hist = gen_register_history(
        rng, n_ops=max(1, n_ops - 1), n_procs=n_procs,
        value_range=value_range, p_info=0.0, p_fail_read=0.05,
        initial_value=init)
    w = anchor_value(seed, k, value_range)
    proc = n_procs + 1000   # a process id no concurrent op ever holds
    hist.append(Op(type=INVOKE, f="write", value=w, process=proc))
    hist.append(Op(type=OK, f="write", value=w, process=proc))
    for i, op in enumerate(hist):
        op.index = i
        op.time = i * 1000
    return hist


def _seg_checkpoint_name(tag: str) -> str:
    return f"{tag}.seg"


def run_longhaul(model=None, *, events: int = 1_000_000,
                 seg_events: int = DEFAULT_SEG_EVENTS, seed: int = 0,
                 n_procs: int = 4, value_range: int = 5,
                 k_slots: int = 32, f_cap: int = 256,
                 tag: str = "longhaul", resume: bool = True,
                 mutate_segment: Optional[int] = None,
                 time_budget_s: Optional[float] = None
                 ) -> dict[str, Any]:
    """Check a synthetic ``events``-long history end to end without ever
    materializing it: generate → encode (through the encode-cache tier)
    → check → carry, one segment at a time. Returns the lane record —
    verdict fields (``survived``, global ``dead_step`` in cumulative
    return-step units) are bit-identical to a single whole-history
    check_encoded_resumable run (the parity tests hold this at every
    cross-checkable scale), plus throughput and RSS accounting.

    `mutate_segment` corrupts that segment's history
    (utils/fuzz.mutate_history) — the test hook for dead-verdict parity.
    With an active spill tier (store/spill.py) and the
    ``host_spill_mode`` policy engaged, the lane checkpoints its
    segment chain (and wgl2 its intra-segment chunks) to disk and
    `resume=True` continues a crashed lane from the last durable
    boundary; a torn checkpoint degrades to recompute, never a wrong
    verdict."""
    from ..ops import wgl2

    if model is None:
        from ..models import CASRegister
        model = CASRegister()
    t0 = time.monotonic()
    rss0 = _spill.rss_mb()
    n_ops_per_seg = max(2, seg_events // 2)
    n_segments = max(1, (events + seg_events - 1) // seg_events)
    sdir = _spill.active_spill()
    # The working-set estimate is the footprint the OLD route would pay:
    # the whole materialized history (~32 B/event host-side) — exactly
    # what the out-of-core route exists to avoid.
    est_mb = events * 32 / (1 << 20)
    do_spill = sdir is not None and _spill.spill_active(est_mb)
    ck_name = _seg_checkpoint_name(tag)

    start_k = 0
    carry: Optional[np.ndarray] = None
    returns_done = 0
    events_done = 0
    esc_total = 0
    mf_max = 0
    resumed_from = -1
    if do_spill and resume:
        d = _spill.load_frontier(sdir, ck_name)
        mt = (d or {}).get("meta") or {}
        if d is not None and mt.get("seed") == seed \
                and mt.get("seg_events") == seg_events \
                and mt.get("n_segments") == n_segments \
                and 0 < int(mt.get("seg", 0)) <= n_segments:
            start_k = int(mt["seg"])
            carry = np.asarray(d["states"])[
                np.asarray(d["valid"])].astype(np.int32)
            returns_done = int(mt.get("returns_done", 0))
            events_done = int(mt.get("events_done", 0))
            esc_total = int(mt.get("escalations", 0))
            mf_max = int(mt.get("max_frontier", 0))
            resumed_from = start_k

    survived = True
    dead_step = -1
    segments_run = 0
    for k in range(start_k, n_segments):
        hist = segment_history(seed, k, n_ops_per_seg,
                               n_procs=n_procs, value_range=value_range)
        if mutate_segment is not None and k == mutate_segment:
            from ..utils.fuzz import mutate_history
            hist = mutate_history(
                random.Random(f"{seed}|mut|{k}"), hist,
                value_range=value_range)
        enc = encode_cache.lookup(hist, model.name, k_slots)
        if enc is None:
            from ..ops.encode import encode_register_history
            enc = encode_register_history(hist, k_slots=k_slots)
            encode_cache.store(hist, model.name, k_slots, enc)
        res = wgl2.check_encoded_resumable(
            enc, model, f_cap=f_cap, time_budget_s=time_budget_s,
            init_frontier=carry, return_frontier=True,
            spill_tag=f"{tag}.s{k}" if do_spill else None)
        segments_run += 1
        events_done += len(hist)
        esc_total += int(res.get("escalations", 0))
        mf_max = max(mf_max, int(res.get("max_frontier", 0)))
        if do_spill:
            sdir.delete(f"{tag}.s{k}.ck")   # intra-segment ck consumed
        if not res["survived"]:
            survived = False
            dead_step = returns_done + int(res["dead_step"])
            break
        returns_done += int(res["n_steps"])
        states, masks, valid = res["frontier"]
        rows = np.flatnonzero(valid)
        # Quiescent boundary by construction (p_info=0): every pending
        # mask is zero, so the carry IS a plain state set.
        assert not masks[rows].any(), "non-quiescent segment boundary"
        carry = np.unique(states[rows]).astype(np.int32)
        if do_spill:
            _spill.spill_frontier(
                sdir, ck_name, carry,
                np.zeros((carry.size, 1), np.uint32),
                np.ones((carry.size,), bool),
                meta={"seg": k + 1, "seed": seed,
                      "seg_events": seg_events,
                      "n_segments": n_segments,
                      "returns_done": returns_done,
                      "events_done": events_done,
                      "escalations": esc_total,
                      "max_frontier": mf_max})
    if do_spill:
        sdir.delete(ck_name)    # lane complete: the chain checkpoint
        for k in range(start_k, n_segments):
            sdir.delete(f"{tag}.s{k}.ck")
    wall_s = time.monotonic() - t0
    peak_rss_mb = max(0.0, _spill.rss_mb() - rss0)
    rss_budget_mb = limits().host_rss_budget_mb
    m = obs.get_metrics()
    m.gauge("spill.peak_rss_mb").set(round(peak_rss_mb, 2))
    return {
        "events": events_done,
        "segments": n_segments,
        "segments_run": segments_run,
        "resumed_from": resumed_from,
        "survived": survived,
        "dead_step": dead_step,
        "max_frontier": mf_max,
        "escalations": esc_total,
        "spilled": do_spill,
        "wall_s": round(wall_s, 4),
        "events_per_sec": round(events_done / wall_s, 2)
        if wall_s > 0 else 0.0,
        "peak_rss_mb": round(peak_rss_mb, 2),
        "rss_budget_mb": rss_budget_mb,
        "rss_ok": peak_rss_mb <= rss_budget_mb,
    }
