"""Corpus throughput engine (sched/): bucket determinism, verdict
equivalence vs the unbatched path, pipeline drain on early-invalid exit,
and compile/kernel-cache hit accounting (ISSUE 2 acceptance)."""

from __future__ import annotations

import random

import numpy as np
import pytest

from jepsen_etcd_demo_tpu import obs, sched
from jepsen_etcd_demo_tpu.models import CASRegister
from jepsen_etcd_demo_tpu.ops import encode, wgl3, wgl3_pallas
from jepsen_etcd_demo_tpu.ops.encode import (encode_register_history,
                                             encode_return_steps)
from jepsen_etcd_demo_tpu.utils.fuzz import (gen_register_history,
                                             mutate_history)
from tests.golden import GOLDEN

MODEL = CASRegister()

RESULT_FIELDS = ("valid", "survived", "dead_step", "max_frontier",
                 "configs_explored", "op_count", "overflow")


def _mixed_corpus(seed: int, n: int, lo: int = 8, hi: int = 150,
                  mutate_every: int = 3):
    rng = random.Random(seed)
    encs = []
    for i in range(n):
        h = gen_register_history(rng, n_ops=rng.randrange(lo, hi),
                                 n_procs=rng.randrange(2, 8),
                                 p_info=rng.choice([0.0, 0.02]))
        if mutate_every and i % mutate_every == 0:
            h = mutate_history(rng, h)
        encs.append(encode_register_history(h, k_slots=16))
    return encs


class TestBucketAssignment:
    def test_deterministic_and_order_independent(self):
        counts = [0, 1, 17, 64, 65, 96, 97, 400, 4000, 17, 96]
        a = sched.assign_step_buckets(counts)
        b = sched.assign_step_buckets(counts)
        assert a == b
        # Order independence: the bucket is a pure function of the count.
        perm = list(reversed(counts))
        assert sched.assign_step_buckets(perm) == list(reversed(a))
        # Same count -> same bucket wherever it appears.
        assert a[2] == a[9] or counts[2] != counts[9]

    def test_buckets_bound_padding(self):
        # {2^k, 1.5*2^k} growth: padded/real < 1.5 for any count past the
        # floor, and the floor bounds the tiny tail.
        for n in range(65, 5000, 37):
            r = sched.assign_step_buckets([n])[0]
            assert r >= n
            assert r / n < 1.5, (n, r)

    def test_floor_tracks_limits(self):
        from jepsen_etcd_demo_tpu.ops.limits import (KernelLimits,
                                                     limits, set_limits)

        prev = set_limits(KernelLimits(step_bucket_floor=16))
        try:
            assert sched.assign_step_buckets([1, 10, 17]) == [16, 16, 24]
        finally:
            set_limits(prev)


class TestVerdictEquivalence:
    def test_golden_corpus_matches_unbatched(self):
        from jepsen_etcd_demo_tpu.checkers.linearizable import Linearizable

        lin = Linearizable(model=MODEL)
        encs, expected = [], []
        for _name, history, want in GOLDEN:
            encs.append(lin.encode(history))
            expected.append(want)
        results, _kernel, _stats = sched.check_corpus(encs, MODEL)
        for (name, _h, want), res in zip(GOLDEN, results):
            assert res["valid"] is want, (name, res)

    def test_fuzz_corpus_bit_identical_to_unbatched(self):
        encs = _mixed_corpus(0x5CED, 8)
        results, _kernel, stats = sched.check_corpus(encs, MODEL)
        invalid = 0
        for enc, got in zip(encs, results):
            want = wgl3.check_encoded3(enc, MODEL)
            want["op_count"] = enc.n_ops
            for f in RESULT_FIELDS:
                assert got[f] == want[f], (f, got, want)
            invalid += got["valid"] is False
        assert invalid >= 3, "sweep too tame"
        assert stats["launches"] >= 2, "mixed lengths must split buckets"

    def test_results_align_with_input_order_across_buckets(self):
        # Short and long histories interleaved: results must land at
        # their input positions, not bucket order.
        rng = random.Random(0xA11)
        encs = []
        for i in range(12):
            n = 10 if i % 2 else 120
            encs.append(encode_register_history(
                gen_register_history(rng, n_ops=n, n_procs=4, p_info=0.0),
                k_slots=16))
        results, _k, stats = sched.check_corpus(encs, MODEL)
        assert len(stats["buckets"]) >= 2
        for enc, res in zip(encs, results):
            assert res["op_count"] == enc.n_ops

    def test_single_history_delegates_to_auto_router(self):
        enc = _mixed_corpus(0x51, 1, mutate_every=0)[0]
        results, kernel, stats = sched.check_corpus([enc], MODEL)
        want, want_kernel = wgl3_pallas.check_batch_encoded_auto(
            [enc], MODEL)
        assert kernel == want_kernel
        assert results[0]["valid"] == want[0]["valid"]
        assert stats["launches"] == 0

    def test_general_partition_rides_sort_tiers(self):
        # Huge values defeat the dense table: the engine's general lane
        # must still produce exact verdicts matching the ladder.
        rng = random.Random(0xB16)
        encs = []
        for i in range(6):
            h = gen_register_history(rng, n_ops=rng.randrange(15, 50),
                                     n_procs=5, p_info=0.02)
            if i % 2:
                h = mutate_history(rng, h)
            for op in h:
                if isinstance(op.value, int):
                    op.value = op.value * 211
                elif isinstance(op.value, tuple):
                    op.value = tuple(v * 211 for v in op.value)
            encs.append(encode_register_history(h, k_slots=16))
        assert wgl3.dense_config(
            MODEL, wgl3.tight_k_slots(encs[0]), encs[0].max_value) is None
        results, _k, _s = sched.check_corpus(encs, MODEL)
        want, _wk = wgl3_pallas.check_batch_encoded_auto(encs, MODEL)
        for got, ref in zip(results, want):
            assert got["valid"] == ref["valid"], (got, ref)


class TestPipelinedSweeps:
    def test_long_sweep_pipelined_drains_on_early_invalid(self):
        """A mutated long history dies early: the pipelined chunk loop
        (poll interval > 1) must drain past the death and report fields
        bit-identical to the per-chunk synchronous loop."""
        from jepsen_etcd_demo_tpu.ops.limits import (KernelLimits,
                                                     limits, set_limits)
        from dataclasses import replace

        rng = random.Random(0xD1E)
        ref = None
        for _ in range(20):
            h = mutate_history(rng, gen_register_history(
                rng, n_ops=2000, n_procs=6, p_info=0.0))
            enc = encode_register_history(h, k_slots=16)
            k = wgl3.tight_k_slots(enc)
            cfg = wgl3.dense_config(MODEL, k, enc.max_value)
            from jepsen_etcd_demo_tpu.ops.encode import reslot_events

            enc = reslot_events(enc, k) if enc.k_slots != k else enc
            rs = encode_return_steps(enc)
            # Budgeted path = the synchronous per-chunk loop (reference).
            ref = wgl3.check_steps3_long(rs, MODEL, cfg, chunk=64,
                                         time_budget_s=3600.0)
            if ref["valid"] is False and ref["dead_step"] < rs.n_steps // 2:
                break
        assert ref["valid"] is False, "no early-invalid mutation found"
        # Pipelined path with a large poll interval: the death happens
        # chunks before the poll notices; the drain must stay exact.
        prev = set_limits(replace(limits(), sched_poll_chunks=5))
        try:
            got = wgl3.check_steps3_long(rs, MODEL, cfg, chunk=64)
        finally:
            set_limits(prev)
        for f in ("valid", "survived", "dead_step", "max_frontier",
                  "configs_explored"):
            assert got[f] == ref[f], (f, got, ref)

    def test_resumable_pipelined_matches_sync_depth1(self):
        """The double-buffered sort sweep (speculative in-flight chunks)
        must agree with depth-1 (fully synchronous) on verdict, death
        point, and escalation count — overflow rollback discards
        speculation exactly."""
        from jepsen_etcd_demo_tpu.ops.limits import (limits, set_limits)
        from jepsen_etcd_demo_tpu.ops.wgl2 import check_steps_resumable
        from dataclasses import replace

        rng = random.Random(0xD2E)
        checked = invalid = escalated = 0
        for i in range(8):
            h = gen_register_history(rng, n_ops=rng.randrange(30, 80),
                                     n_procs=6, p_info=0.05)
            if i % 2:
                h = mutate_history(rng, h)
            for op in h:
                if isinstance(op.value, int):
                    op.value = op.value * 211
                elif isinstance(op.value, tuple):
                    op.value = tuple(v * 211 for v in op.value)
            rs = encode_return_steps(encode_register_history(h, k_slots=16))
            prev = set_limits(replace(limits(), sched_pipeline_depth=1))
            try:
                ref = check_steps_resumable(rs, MODEL, f_cap=4, chunk=8)
            finally:
                set_limits(prev)
            prev = set_limits(replace(limits(), sched_pipeline_depth=3))
            try:
                got = check_steps_resumable(rs, MODEL, f_cap=4, chunk=8)
            finally:
                set_limits(prev)
            for f in ("valid", "survived", "dead_step", "max_frontier",
                      "escalations", "f_cap"):
                assert got[f] == ref[f], (f, got, ref)
            checked += 1
            invalid += ref["valid"] is False
            escalated += ref["escalations"] > 0
        assert invalid >= 2 and escalated >= 2, \
            f"sweep too tame ({invalid} invalid, {escalated} escalated)"

    def test_resumable_death_checkpoint_survives_pipelining(self):
        from jepsen_etcd_demo_tpu.ops.wgl2 import check_steps_resumable

        rng = random.Random(0xD3E)
        for _ in range(10):
            h = mutate_history(rng, gen_register_history(
                rng, n_ops=60, n_procs=5, p_info=0.02))
            for op in h:
                if isinstance(op.value, int):
                    op.value = op.value * 211
                elif isinstance(op.value, tuple):
                    op.value = tuple(v * 211 for v in op.value)
            rs = encode_return_steps(encode_register_history(h, k_slots=16))
            out = check_steps_resumable(rs, MODEL, f_cap=64, chunk=8,
                                        keep_death_checkpoint=True)
            if out["valid"] is False:
                states, masks, valid, c0 = out["death_checkpoint"]
                assert c0 <= out["dead_step"] < c0 + 8
                assert valid.any()
                return
        pytest.skip("no invalid mutation in 10 tries")


class TestCompileCache:
    def test_second_run_compile_s_zero_and_cache_hits(self):
        """ISSUE 2 acceptance: the second in-process run of the same
        bucket shapes reports compile_s == 0 via the PR 1 kernel-phase
        attribution, and every kernel-LRU lookup hits."""
        encs = _mixed_corpus(0xCAC, 10, mutate_every=0)
        cache = sched.kernel_cache()
        with obs.capture():
            first, _k, _s = sched.check_corpus(encs, MODEL)
        h0, m0 = cache.hits, cache.misses
        with obs.capture() as warm:
            second, _k2, _s2 = sched.check_corpus(encs, MODEL)
        assert second == first
        phases = obs.kernel_phases(warm.metrics)
        assert phases["compile_s"] == 0.0
        assert phases["execute_s"] > 0.0
        assert cache.misses == m0, "warm run must not rebuild any shape"
        assert cache.hits > h0
        stats = obs.sched_stats(warm.metrics)
        assert stats["cache_hit_rate"] == 1.0
        # The <2.0 corpus-scale padding bound is pinned by the bench
        # smoke lane (tests/test_bench_smoke.py); a 10-history corpus
        # only checks the ratio is recorded and sane.
        assert stats["padding_waste"] >= 1.0

    def test_kernel_cache_lru_evicts(self):
        from jepsen_etcd_demo_tpu.sched.compile_cache import KernelCache

        c = KernelCache(capacity=2)
        built = []
        for key in ("a", "b", "c", "a"):
            c.get((key,), lambda k=key: built.append(k) or k)
        assert built == ["a", "b", "c", "a"]   # "a" evicted, rebuilt
        assert c.stats()["entries"] == 2

    def test_persistent_cache_dir(self, monkeypatch):
        """One directory for every caller: JAX_COMPILATION_CACHE_DIR
        when set, else the fixed <checkout>/.xla-cache."""
        from pathlib import Path

        from jepsen_etcd_demo_tpu.sched.compile_cache import \
            compile_cache_dir

        checkout = Path(__file__).resolve().parent.parent
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        assert compile_cache_dir() == str(checkout / ".xla-cache")
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/jaxdir")
        assert compile_cache_dir() == "/jaxdir"

    def test_tuned_profile_and_calibration_live_beside_the_cache(
            self, tmp_path, monkeypatch):
        from pathlib import Path

        from jepsen_etcd_demo_tpu.ops.calibrate import calibration_path
        from jepsen_etcd_demo_tpu.tune.profile import profile_path

        monkeypatch.delenv("JEPSEN_TPU_TUNE_PROFILE", raising=False)
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert Path(profile_path()).parent == tmp_path
        assert Path(calibration_path()).parent == tmp_path


class TestEncodeCache:
    def test_roundtrip_hit_is_bit_identical(self, tmp_path):
        from jepsen_etcd_demo_tpu.checkers.linearizable import Linearizable
        from jepsen_etcd_demo_tpu.store import encode_cache

        rng = random.Random(0xE7C)
        h = gen_register_history(rng, n_ops=40, n_procs=5, p_info=0.02)
        lin = Linearizable(model=MODEL)
        cold = lin.encode(h)
        with encode_cache.activated(tmp_path):
            first = lin.encode(h)        # miss: writes the entry
            second = lin.encode(h)       # hit: loads it
        assert (tmp_path / (encode_cache.history_fingerprint(
            h, MODEL.name, lin.k_slots) + ".npz")).exists()
        for enc in (first, second):
            np.testing.assert_array_equal(enc.events, cold.events)
            assert (enc.n_events, enc.n_ops, enc.k_slots, enc.max_pending,
                    enc.max_value) == (cold.n_events, cold.n_ops,
                                       cold.k_slots, cold.max_pending,
                                       cold.max_value)

    def test_fingerprint_sensitive_to_content_and_model(self):
        from jepsen_etcd_demo_tpu.store import encode_cache

        rng = random.Random(0xF17)
        h = gen_register_history(rng, n_ops=20, n_procs=4)
        base = encode_cache.history_fingerprint(h, "cas-register", 24)
        assert base == encode_cache.history_fingerprint(
            h, "cas-register", 24)
        assert base != encode_cache.history_fingerprint(
            h, "cas-register", 32)
        assert base != encode_cache.history_fingerprint(
            h, "mutex", 24)
        mutated = mutate_history(rng, h)
        assert base != encode_cache.history_fingerprint(
            mutated, "cas-register", 24)

    def test_inactive_cache_is_noop(self, tmp_path):
        from jepsen_etcd_demo_tpu.store import encode_cache

        rng = random.Random(0x0FF)
        h = gen_register_history(rng, n_ops=10, n_procs=3)
        assert encode_cache.active_root() is None
        assert encode_cache.lookup(h, "cas-register", 24) is None
        encode_cache.store(h, "cas-register", 24,
                           encode_register_history(h))
        assert list(tmp_path.iterdir()) == []


# -- launch accounting ------------------------------------------------------

def _corpus_of(lengths: list[int], seed: int):
    rng = random.Random(seed)
    return [encode_register_history(
        gen_register_history(rng, n_ops=n, n_procs=3, p_info=0.0),
        k_slots=8)
        for n in lengths]


CORPORA = {
    "uniform": [40] * 12,
    "ragged": [10, 25, 40, 55, 70, 85, 100, 115, 20, 35, 50, 65, 80],
    "one-long": [20] * 11 + [300],
    "many-short": [6] * 40,
}


class TestShardRealSteps:
    @pytest.mark.parametrize("counts,n_shards,want", [
        ([3, 2, 1, 0], 2, [5, 1]),
        ([3, 2, 1], 2, [6]),              # uneven: one total
        ([4, 0, 0, 4, 1, 1, 0, 2], 4, [4, 4, 2, 2]),
        ([7, 7], 1, [14]),
    ])
    def test_contiguous_blocks(self, counts, n_shards, want):
        from jepsen_etcd_demo_tpu.sched.engine import _shard_real_steps

        assert _shard_real_steps(counts, n_shards) == want


class TestLaunchCounters:
    @pytest.mark.parametrize("n_shards", [1, 2, 4])
    @pytest.mark.parametrize("corpus", sorted(CORPORA))
    def test_counters_match_a_recount_of_the_launches(
            self, corpus, n_shards, monkeypatch):
        """sched.steps_real / steps_padded / launches / shard_steps_max
        after check_corpus equal a recount of the batches it stacked: per
        launch the real steps, batch x bucket, and on a mesh the largest
        contiguous shard block's real steps x the shard count."""
        from jepsen_etcd_demo_tpu.parallel.mesh import MESH_SHAPE_ENV

        monkeypatch.setenv(MESH_SHAPE_ENV, str(n_shards))
        encs = _corpus_of(CORPORA[corpus], seed=len(corpus) + n_shards)
        launched = []
        stack = encode.stack_return_steps

        def spy(encs, k_slots, r_cap, batch=None, order=None):
            arrays = stack(encs, k_slots, r_cap, batch=batch, order=order)
            # Real steps of every lane as launched: its non-pad targets.
            launched.append(([int(n) for n in (arrays[2] >= 0).sum(axis=1)],
                             int(r_cap)))
            return arrays

        monkeypatch.setattr(encode, "stack_return_steps", spy)
        with obs.capture() as cap:
            results, _, stats = sched.check_corpus(encs, MODEL)
        snap = cap.metrics.snapshot()

        def counter(name):
            return snap[name]["value"]

        want_real = sum(sum(c) for c, _ in launched)
        want_padded = sum(len(c) * r for c, r in launched)
        want_max = 0
        if n_shards > 1:
            for c, _ in launched:
                per = len(c) // n_shards
                assert per * n_shards == len(c), "batch off the mesh"
                want_max += n_shards * max(
                    sum(c[i * per:(i + 1) * per])
                    for i in range(n_shards))
        assert launched and counter("sched.launches") == len(launched)
        assert counter("sched.steps_real") == want_real
        assert counter("sched.steps_padded") == want_padded
        assert counter("sched.shard_steps_max") == want_max
        assert counter("sched.shard_launches") == (
            len(launched) if n_shards > 1 else 0)
        # Every history was launched exactly once, and the stats dict
        # agrees with the counters.
        assert want_real == sum(encode_return_steps(e).n_steps
                                for e in encs)
        assert (stats["launches"], stats["steps_real"],
                stats["steps_padded"]) == (len(launched), want_real,
                                           want_padded)
        assert all(r is not None for r in results)


# -- batched launch pack (ops/encode.py stack_return_steps) ------------------

def _per_key_stack(encs, k, r_cap, batch=None, order=None):
    """The per-key pack stack_return_steps stands in for: reslot where
    the width differs, expand, append all-pad lanes, permute, pad to
    r_cap and stack (as numpy)."""
    from jepsen_etcd_demo_tpu.ops.encode import ReturnSteps, reslot_events

    steps = [encode_return_steps(reslot_events(e, k) if e.k_slots != k
                                 else e) for e in encs]
    pad = ReturnSteps(np.zeros((0, k, 4), np.int32), np.zeros((0, k), bool),
                      np.zeros((0,), np.int32), 0, 0, k, 0)
    steps += [pad] * ((len(encs) if batch is None else batch) - len(encs))
    if order is not None:
        steps = [steps[j] for j in order]
    return tuple(np.asarray(a) for a in wgl3.stack_steps3(steps, r_cap))


def _bucket_shape(encs):
    """(k, r_cap) of one launch over encs, as check_corpus derives them."""
    k = max(wgl3.tight_k_slots(e) for e in encs)
    return k, wgl3.step_bucket(max(sched.engine._return_count(e)
                                   for e in encs))


def _fuzz_encs(seed, n, lo, hi, p_info, k_slots=24):
    rng = random.Random(seed)
    return [encode_register_history(
        gen_register_history(rng, n_ops=rng.randrange(lo, hi),
                             n_procs=rng.randrange(2, 8), p_info=p_info),
        k_slots=k_slots) for _ in range(n)]


def _benchmark_pool():
    """One unit of the register test's 1024-key pool, encoded per key in
    place as the independent checker does."""
    import json
    from pathlib import Path

    from benchmark import load
    from jepsen_etcd_demo_tpu.checkers.independent import route_by_key
    from jepsen_etcd_demo_tpu.checkers.linearizable import Linearizable
    from jepsen_etcd_demo_tpu.ops.op import Op

    root = Path(__file__).resolve().parents[1] / "benchmark"
    cfg = json.loads((root / "configs/etcd-register.json").read_text())
    mix = json.loads((root / "traffic/indep-1024.json").read_text())
    tr = load.Traffic(cfg, mix, seed=2800000019)
    lin = Linearizable(cfg["model"])
    return [lin.encode(h, keyed=True) for h in route_by_key(
        load.program_ops(tr.unit_keys(0), Op, keyed=True)).values()]


def _hand_built_wide():
    """Two overlapping ops named by slot ids 20 and 7 of a 24-slot table:
    id 20 reaches a tight width, so the pack must reslot it."""
    from jepsen_etcd_demo_tpu.ops.encode import (EV_INVOKE, EV_RETURN,
                                                 EncodedHistory, F_READ,
                                                 F_WRITE, NIL)

    ev = np.array([[EV_INVOKE, 20, F_WRITE, 3, 0, NIL],
                   [EV_INVOKE, 7, F_READ, 0, 0, 3],
                   [EV_RETURN, 20, F_WRITE, 3, 0, NIL],
                   [EV_INVOKE, 20, F_WRITE, 4, 0, NIL],
                   [EV_RETURN, 7, F_READ, 0, 0, 3],
                   [EV_RETURN, 20, F_WRITE, 4, 0, NIL]], np.int32)
    return EncodedHistory(events=ev, n_events=len(ev), n_ops=3, k_slots=24,
                          max_pending=2, max_value=4)


def _pack_case(name):
    """(encs, batch, order) of one bit-identity case."""
    empty = encode_register_history([], k_slots=24)
    if name == "indep-1024":
        return _benchmark_pool(), None, None
    if name == "fuzz-info":
        return _fuzz_encs(29, 48, 5, 60, p_info=0.15), None, None
    if name == "no-return":
        from jepsen_etcd_demo_tpu.ops.op import INVOKE, Op

        # Five writes invoked and never completed: pending forever.
        h = [Op(type=INVOKE, f="write", value=v, process=v, index=v)
             for v in range(5)]
        return [encode_register_history(h, k_slots=24)] + _fuzz_encs(
            3, 5, 5, 20, p_info=0.0), None, None
    if name == "empty":
        return [empty], None, None
    if name == "mixed-lengths":
        return (_fuzz_encs(5, 30, 4, 10, p_info=0.02)
                + _fuzz_encs(6, 4, 120, 160, p_info=0.02)), None, None
    if name == "pad-rows":
        return [empty] + _fuzz_encs(8, 11, 5, 40, p_info=0.05), 16, None
    if name == "lpt-order":
        encs = _fuzz_encs(9, 13, 5, 60, p_info=0.05)
        counts = [sched.engine._return_count(e) for e in encs] + [0] * 3
        order = sched.lpt_shard_order(counts, 4)
        assert order != list(range(16))
        return encs, 16, order
    assert name == "reslot"
    return [_hand_built_wide()] + _fuzz_encs(4, 6, 5, 30, p_info=0.0), \
        None, None


PACK_CASES = ("indep-1024", "fuzz-info", "no-return", "empty",
              "mixed-lengths", "pad-rows", "lpt-order", "reslot")


class TestBatchedPack:
    @pytest.mark.parametrize("lockstep", [True, False],
                             ids=["lockstep", "per-history"])
    @pytest.mark.parametrize("case", PACK_CASES)
    def test_bit_identical_to_the_per_key_pack(self, case, lockstep,
                                               monkeypatch):
        """Both expansions of stack_return_steps equal the per-key
        reslot + expand + pad + stack, bit for bit; the counters say
        which expansion ran and how many histories were reslotted."""
        encs, batch, order = _pack_case(case)
        k, r_cap = _bucket_shape(encs)
        monkeypatch.setattr(encode, "LOCKSTEP_EVENTS_PER_HISTORY",
                            10 ** 9 if lockstep else -1)
        with obs.capture() as cap:
            got = encode.stack_return_steps(encs, k, r_cap, batch=batch,
                                            order=order)
        snap = cap.metrics.snapshot()
        want = _per_key_stack(encs, k, r_cap, batch, order)
        for g, w in zip(got, want):
            assert g.dtype == w.dtype and g.shape == w.shape
            np.testing.assert_array_equal(g, w)
        assert snap["sched.pack.batched_histories"]["value"] == (
            len(encs) if lockstep else 0)
        assert snap["sched.pack.reslotted"]["value"] == (
            1 if case == "reslot" else 0)

    @pytest.mark.parametrize("seed", [1, 2, 3])
    def test_reslot_is_identity_on_encoder_output(self, seed):
        """The encoder takes slot ids lowest-free, so reslotting its
        output to any width that holds max_pending changes no id."""
        from jepsen_etcd_demo_tpu.ops.encode import reslot_events

        for e in _fuzz_encs(seed, 24, 5, 120, p_info=0.1):
            for k in range(e.max_pending, e.k_slots + 1):
                np.testing.assert_array_equal(
                    reslot_events(e, k).events, e.events[: e.n_events])

    def test_shape_rule_sends_a_10k_history_per_history(self):
        """One 10k-op history expands per history (the lockstep pass
        would walk its ~14,000 positions for one lane); a bucket of the
        register test's 1024 keys expands in lockstep."""
        import json
        from pathlib import Path

        from benchmark.gen import register_history
        from benchmark.load import program_ops
        from jepsen_etcd_demo_tpu.ops.op import Op

        root = Path(__file__).resolve().parents[1] / "benchmark"
        g = json.loads((root / "configs/etcd-register-10k.json")
                       .read_text())["generator"]
        h = register_history(random.Random(10), n_ops=g["ops_per_key"],
                             n_procs=g["threads_per_key"],
                             value_range=g["value_range"], p_read=g["p_read"],
                             p_write=g["p_write"], p_info=g["p_info"],
                             p_fail_read=g["p_fail_read"])
        e = encode_register_history(program_ops([h], Op, keyed=False),
                                    k_slots=24)
        assert e.n_events > 10000
        assert not encode.lockstep_wins(1, e.n_events)
        assert encode.lockstep_wins(1024, 200)
        k, r_cap = _bucket_shape([e])
        with obs.capture() as cap:
            got = encode.stack_return_steps([e], k, r_cap)
        assert cap.metrics.snapshot()[
            "sched.pack.batched_histories"]["value"] == 0
        for g_, w in zip(got, _per_key_stack([e], k, r_cap)):
            np.testing.assert_array_equal(g_, w)

    @pytest.mark.parametrize("n_shards", [1, 2, 4])
    def test_corpus_results_equal_the_per_key_pack(self, n_shards,
                                                   monkeypatch):
        """check_corpus over a 1024-key corpus on a virtual mesh gives
        every key the verdict and search counts it gets when its launch
        is packed key by key."""
        from jepsen_etcd_demo_tpu.parallel.mesh import MESH_SHAPE_ENV

        monkeypatch.setenv(MESH_SHAPE_ENV, str(n_shards))
        encs = _fuzz_encs(40 + n_shards, 1024, 4, 24, p_info=0.02)
        rng = random.Random(n_shards)
        for i in rng.sample(range(len(encs)), 64):
            h = gen_register_history(random.Random(i), n_ops=16, n_procs=3)
            encs[i] = encode_register_history(mutate_history(rng, h),
                                              k_slots=24)
        with obs.capture() as cap:
            batched, _, stats = sched.check_corpus(encs, MODEL)
        assert cap.metrics.snapshot()[
            "sched.pack.batched_histories"]["value"] == len(encs)
        monkeypatch.setattr(encode, "stack_return_steps", _per_key_stack)
        per_key, _, stats_ref = sched.check_corpus(encs, MODEL)
        assert stats == stats_ref
        assert any(not r["valid"] for r in per_key)
        for a, b in zip(batched, per_key):
            assert ({f: a[f] for f in ("valid", "dead_step", "max_frontier",
                                       "configs_explored")}
                    == {f: b[f] for f in ("valid", "dead_step",
                                          "max_frontier",
                                          "configs_explored")})
