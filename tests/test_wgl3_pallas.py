"""Pallas fused-scan dense WGL kernel (ops/wgl3_pallas.py).

Runs in interpreter mode on the virtual-CPU platform (conftest forces it),
differentially against the XLA dense kernel (ops/wgl3.py) and the oracle —
the pallas kernel must agree bit-for-bit on every field, including the
search metrics. The compiled path is exercised on real TPU by bench.py.
"""

from __future__ import annotations

import random

import pytest

from jepsen_etcd_demo_tpu.checkers.oracle import check_events_oracle
from jepsen_etcd_demo_tpu.models import CASRegister
from jepsen_etcd_demo_tpu.ops import wgl3, wgl3_pallas
from jepsen_etcd_demo_tpu.ops.limits import limits
from jepsen_etcd_demo_tpu.ops.encode import encode_register_history
from jepsen_etcd_demo_tpu.utils.fuzz import (gen_register_history,
                                             mutate_history)

from golden import GOLDEN

MODEL = CASRegister()
FIELDS = ("valid", "dead_step", "max_frontier", "configs_explored")


def _pallas(encs):
    return wgl3_pallas.check_batch_encoded_pallas(encs, MODEL,
                                                  interpret=True)


def test_golden_histories():
    encs, verdicts = [], []
    for name, hist, expected in GOLDEN:
        encs.append(encode_register_history(hist, k_slots=16))
        verdicts.append(expected)
    for one, expected, (name, _, _) in zip(_pallas(encs), verdicts, GOLDEN):
        assert one["valid"] is expected, name


def test_differential_vs_xla_kernel():
    """Fuzzed valid + mutated histories: every result field must match the
    XLA dense kernel exactly (same search, same metrics)."""
    encs = []
    for i in range(6):
        h = gen_register_history(random.Random(i), n_ops=70, n_procs=8,
                                 p_info=0.01)
        if i % 2:
            h = mutate_history(random.Random(1000 + i), h)
        encs.append(encode_register_history(h, k_slots=16))
    ref = wgl3.check_batch_encoded3(encs, MODEL)
    pal = _pallas(encs)
    for r, p in zip(ref, pal):
        for f in FIELDS:
            assert r[f] == p[f], f


def test_differential_vs_oracle_single():
    for i in range(3):
        h = gen_register_history(random.Random(50 + i), n_ops=50, n_procs=6)
        enc = encode_register_history(h, k_slots=16)
        want = check_events_oracle(enc, MODEL).valid
        assert _pallas([enc])[0]["valid"] is want


def test_step_chunking_long_history():
    """R > STEP_CHUNK forces the multi-chunk grid with scratch-carried
    search state; results must match the single-block XLA kernel."""
    h = gen_register_history(random.Random(9), n_ops=1100, n_procs=8,
                             p_info=0.0005)
    enc = encode_register_history(h, k_slots=32)
    steps = wgl3.step_bucket(
        sum(1 for op in h if op.type in ("ok", "info")))
    assert steps > limits().pallas_step_chunk, \
        "test must exercise chunking"
    r = wgl3.check_encoded3(enc, MODEL)
    p = _pallas([enc])[0]
    for f in FIELDS:
        assert r[f] == p[f], f


def test_feasibility_and_routing():
    assert not wgl3_pallas.pallas_feasible(None)
    cfg = wgl3.DenseConfig(k_slots=18, n_states=8, state_offset=1)
    assert not wgl3_pallas.pallas_feasible(cfg)   # K > MAX_K_PALLAS
    ok = wgl3.DenseConfig(k_slots=12, n_states=8, state_offset=1)
    assert wgl3_pallas.pallas_feasible(ok)
    # Tests run on the virtual CPU platform: the compiled-pallas routing
    # predicate must refuse (interpret mode is opt-in for tests only).
    assert not wgl3_pallas.pallas_available()
    assert not wgl3_pallas.use_pallas(ok)


def test_infeasible_k_raises():
    with pytest.raises(ValueError):
        wgl3_pallas.make_batch_checker_pallas(
            MODEL, wgl3.DenseConfig(k_slots=20, n_states=8, state_offset=1))


def test_chunk_alignment_pads_do_not_count():
    """Step buckets that are NOT multiples of STEP_CHUNK (e.g. 768) force
    chunk-alignment padding; those pad steps must not inflate
    configs_explored (regression: pallas counted them, XLA did not)."""
    h = gen_register_history(random.Random(77), n_ops=800, n_procs=8,
                             p_info=0.0005)
    enc = encode_register_history(h, k_slots=32)
    bucket = wgl3.step_bucket(
        sum(1 for op in h if op.type in ("ok", "info")))
    assert bucket > limits().pallas_step_chunk
    assert bucket % limits().pallas_step_chunk != 0, \
        "test must exercise chunk-alignment padding"
    r = wgl3.check_encoded3(enc, MODEL)
    p = _pallas([enc])[0]
    for f in FIELDS:
        assert r[f] == p[f], f


def test_batched_general_path_matches_ladder():
    """Non-dense histories (fifo-queue geometry) batch through one sort
    launch in check_batch_encoded_auto; verdicts must match the sequential
    per-history general ladder and the oracle."""
    import random

    from jepsen_etcd_demo_tpu.checkers.oracle import check_events_oracle
    from jepsen_etcd_demo_tpu.models import FIFOQueue
    from jepsen_etcd_demo_tpu.ops import wgl3, wgl3_pallas
    from jepsen_etcd_demo_tpu.ops.encode import encode_history
    from jepsen_etcd_demo_tpu.utils.fuzz import (gen_queue_history,
                                                 mutate_family_history)

    model = FIFOQueue()
    rng = random.Random(0xBA7C)
    encs, expected = [], []
    for i in range(9):
        h = gen_queue_history(rng, n_ops=14, n_procs=4, fifo=True)
        if i % 3 == 0:
            h = mutate_family_history(rng, h, "fifo-queue")
        enc = encode_history(model.prepare_history(h), model, k_slots=16)
        encs.append(enc)
        expected.append(check_events_oracle(enc, model).valid)
    # Sanity: this geometry must NOT be dense-feasible (else the test
    # exercises the wrong path).
    assert wgl3.dense_config(model, wgl3.tight_k_slots(encs[0]),
                             encs[0].max_value) is None
    results, kernel = wgl3_pallas.check_batch_encoded_auto(encs, model)
    assert [r["valid"] for r in results] == expected
    assert any(r["kernel"] == "wgl2-sort-batched" for r in results)


def test_batched_general_overflow_escalates_exactly():
    """A frontier-heavy history (12 forever-pending enqueues => 2^12
    reachable subsets > f_cap) overflows the batched sort pass and must
    escalate through the per-history ladder to an EXACT verdict, without
    disturbing its batch-mates."""
    import random

    from jepsen_etcd_demo_tpu.checkers.oracle import check_events_oracle
    from jepsen_etcd_demo_tpu.models import UnorderedQueue
    from jepsen_etcd_demo_tpu.ops import wgl3_pallas
    from jepsen_etcd_demo_tpu.ops.encode import encode_history
    from jepsen_etcd_demo_tpu.ops.op import Op
    from jepsen_etcd_demo_tpu.utils.fuzz import gen_queue_history

    model = UnorderedQueue()
    heavy = []
    for p in range(12):
        heavy.append(Op(type="invoke", f="enqueue", value=p, process=p))
    for p in range(12):
        heavy.append(Op(type="info", f="enqueue", value=p, process=p))
    heavy.append(Op(type="invoke", f="dequeue", value=None, process=20))
    heavy.append(Op(type="ok", f="dequeue", value=3, process=20))
    rng = random.Random(5)
    encs = [encode_history(model.prepare_history(h), model, k_slots=16)
            for h in ([heavy]
                      + [gen_queue_history(rng, n_ops=10, n_procs=3,
                                           fifo=False) for _ in range(3)])]
    expected = [check_events_oracle(e, model).valid for e in encs]
    results, _ = wgl3_pallas.check_batch_encoded_auto(encs, model)
    assert [r["valid"] for r in results] == expected
    # The heavy history escalated (its kernel names a ladder rung, not the
    # batched pass) and its verdict is exact, not "unknown".
    assert results[0]["kernel"] != "wgl2-sort-batched"
    assert results[0]["valid"] in (True, False)


def test_grouped_kernel_bit_identical_ragged():
    """The grouped kernel (G histories per program, interpret mode) must
    match the XLA kernel bit for bit on a ragged mixed batch — including
    per-history death metadata under group padding."""
    rng = random.Random(0x6A)
    encs = []
    for i in range(9):           # 9 % 8 != 0: exercises group padding
        h = gen_register_history(rng, n_ops=32, n_procs=6)
        if i % 3 == 0:
            h = mutate_history(rng, h)
        encs.append(encode_register_history(h, k_slots=16))
    cfg, steps, r_cap = wgl3.batch_steps3(encs, MODEL)
    arrays = wgl3.stack_steps3(steps, r_cap)
    import numpy as np
    ref = np.asarray(wgl3.cached_batch_checker3_packed(MODEL, cfg)(*arrays))
    got = np.asarray(wgl3_pallas.cached_batch_checker_pallas_grouped(
        MODEL, cfg, group=8, interpret=True)(*arrays))
    # The XLA packed result carries the extra live-tile telemetry
    # column; the 5 verdict fields must agree bit for bit.
    np.testing.assert_array_equal(ref[:, :got.shape[1]], got)


def test_grouped_kernel_multi_chunk_carry():
    """Histories longer than one grouped step-chunk: scratch-carried
    search state across grid chunks must stay bit-identical."""
    from jepsen_etcd_demo_tpu.ops.limits import KernelLimits, limits, \
        set_limits

    rng = random.Random(0x6B)
    encs = [encode_register_history(
        gen_register_history(rng, n_ops=55, n_procs=6), k_slots=16)
        for _ in range(8)]
    cfg, steps, r_cap = wgl3.batch_steps3(encs, MODEL)
    arrays = wgl3.stack_steps3(steps, r_cap)
    import numpy as np
    ref = np.asarray(wgl3.cached_batch_checker3_packed(MODEL, cfg)(*arrays))
    prev = set_limits(KernelLimits(pallas_step_chunk=128))  # RC=128/8=16
    try:
        got = np.asarray(wgl3_pallas.make_batch_checker_pallas_grouped(
            MODEL, cfg, group=8, interpret=True)(*arrays))
    finally:
        set_limits(prev)
    np.testing.assert_array_equal(ref[:, :got.shape[1]], got)


def test_resumable_long_sweep_matches_xla_chunked():
    """check_steps3_long_pallas (host-chained fused-kernel windows, state
    carried between launches) must match the XLA chunked sweep on every
    field, windows exercised by a tiny max_r_pallas."""
    import random

    from jepsen_etcd_demo_tpu.ops.encode import (encode_return_steps,
                                                 reslot_events)
    from jepsen_etcd_demo_tpu.ops.limits import KernelLimits, set_limits
    from jepsen_etcd_demo_tpu.utils.fuzz import mutate_history

    # dedup_mode pinned OFF: the pallas kernels run no canonicalization
    # pass, and this test compares the SEARCH metrics bit-for-bit
    # (tests/test_dedup.py owns the canonicalized comparisons).
    prev = set_limits(KernelLimits(max_r_pallas=64, pallas_step_chunk=32,
                                   dedup_mode=1))
    try:
        for trial in range(3):
            h = gen_register_history(random.Random(trial), n_ops=300,
                                     n_procs=6, p_info=0.01)
            if trial % 2:
                h = mutate_history(random.Random(100 + trial), h)
            enc = encode_register_history(h, k_slots=16)
            k = wgl3.tight_k_slots(enc)
            cfg = wgl3.dense_config(MODEL, k, enc.max_value)
            enc_r = reslot_events(enc, k) if enc.k_slots != k else enc
            rs = encode_return_steps(enc_r)
            ref = wgl3.check_steps3_long(rs, MODEL, cfg, chunk=64)
            got = wgl3_pallas.check_steps3_long_pallas(rs, MODEL, cfg,
                                                       interpret=True)
            for f in ("valid", "survived", "dead_step", "max_frontier",
                      "configs_explored"):
                assert got[f] == ref[f], (trial, f, got, ref)
    finally:
        set_limits(prev)


@pytest.mark.parametrize("group", [1, 4])
def test_smem_split_launch_matches_one_launch_and_oracle(group):
    """A batch too big for one launch's SMEM runs as several pallas calls
    (launch_parts), each indexing its own histories' colmask blocks by
    offset: per-history (group 1) and grouped kernel verdicts must equal
    the single-launch result bit for bit, and the oracle."""
    from dataclasses import replace

    import numpy as np

    from jepsen_etcd_demo_tpu.ops.limits import set_limits

    rng = random.Random(0x5917 + group)
    encs = []
    for i in range(13):          # ragged parts: [7, 6] / 4 x 4 padded
        h = gen_register_history(rng, n_ops=30, n_procs=6)
        if i % 3 == 1:
            h = mutate_history(rng, h)
        encs.append(encode_register_history(h, k_slots=16))
    cfg, steps, r_cap = wgl3.batch_steps3(encs, MODEL)
    arrays = wgl3.stack_steps3(steps, r_cap)

    def run():
        # Fresh launchers: the launch shape (and its parts) is cached
        # per launcher instance.
        if group == 1:
            prep = wgl3_pallas._cached_prep(MODEL, cfg)
            cm, tg, ln = prep(*arrays)
            launch = wgl3_pallas.local_pallas_launcher(MODEL, cfg, True)
            return np.asarray(launch(*arrays[2].shape)(ln, tg, cm))
        return np.asarray(wgl3_pallas.make_batch_checker_pallas_grouped(
            MODEL, cfg, group=group, interpret=True)(*arrays))

    one = run()
    b_pad = -(-len(encs) // group) * group
    assert len(wgl3_pallas.launch_parts(b_pad, r_cap, group)) == 1
    prev = set_limits(replace(limits(), max_prefetch_pallas=1 << 10))
    try:
        parts = wgl3_pallas.launch_parts(b_pad, r_cap, group)
        assert len(parts) >= 2 and sum(parts) == b_pad, parts
        split = run()
    finally:
        set_limits(prev)
    np.testing.assert_array_equal(one, split)
    got = [r["valid"] for r in wgl3.assemble_batch_results(
        wgl3.unpack_np(split), steps, cfg)]
    want = [check_events_oracle(e, MODEL).valid for e in encs]
    assert got == want and False in want
