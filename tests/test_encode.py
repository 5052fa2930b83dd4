"""History pairing/encoding unit tests (SURVEY.md §4: round-trip, padding,
:info open-op semantics)."""

import random

import numpy as np
import pytest

from jepsen_etcd_demo_tpu.checkers.independent import route_by_key
from jepsen_etcd_demo_tpu.models import get_model
from jepsen_etcd_demo_tpu.ops.op import (Op, INVOKE, OK, FAIL, INFO,
                                         history_to_jsonl, history_from_jsonl)
from jepsen_etcd_demo_tpu.ops.encode import (
    NIL, F_READ, F_WRITE, F_CAS, EV_INVOKE, EV_RETURN, EV_PAD,
    EncodedHistory, event_sources, pair_history, encode_history,
    encode_register_history, SlotOverflow)
from jepsen_etcd_demo_tpu.utils.fuzz import (gen_register_history,
                                             interleave_keyed)


def _h(*rows):
    return [Op(type=t, f=f, value=v, process=p, index=i)
            for i, (t, f, v, p) in enumerate(rows)]


def test_pairing_basic():
    h = _h((INVOKE, "write", 3, 0), (OK, "write", 3, 0),
           (INVOKE, "read", None, 1), (OK, "read", 3, 1))
    invs = pair_history(h)
    assert len(invs) == 2
    w, r = invs
    assert (w.f, w.a1, w.status) == (F_WRITE, 3, OK)
    assert (r.f, r.rv, r.status) == (F_READ, 3, OK)
    assert w.invoke_index == 0 and w.complete_index == 1


def test_pairing_interleaved_processes():
    h = _h((INVOKE, "write", 1, 0), (INVOKE, "write", 2, 1),
           (OK, "write", 2, 1), (OK, "write", 1, 0))
    invs = pair_history(h)
    assert [i.a1 for i in invs] == [1, 2]
    assert invs[0].complete_index == 3


def test_dangling_invoke_becomes_info():
    h = _h((INVOKE, "cas", (1, 2), 0))
    invs = pair_history(h)
    assert invs[0].status == INFO
    assert invs[0].complete_index == -1
    assert (invs[0].a1, invs[0].a2) == (1, 2)


def test_double_invoke_rejected():
    h = _h((INVOKE, "read", None, 0), (INVOKE, "read", None, 0))
    with pytest.raises(ValueError):
        pair_history(h)


def test_encoding_drops_fail_and_info_reads():
    h = _h((INVOKE, "write", 1, 0), (FAIL, "write", 1, 0),
           (INVOKE, "read", None, 1), (FAIL, "read", None, 1),
           (INVOKE, "read", None, 2), (INFO, "read", None, 2),
           (INVOKE, "write", 2, 3), (INFO, "write", 2, 3))
    enc = encode_register_history(h)
    # Only the info write survives, as a lone EV_INVOKE.
    assert enc.n_ops == 1
    assert enc.n_events == 1
    kind, slot, f, a1, _, _ = enc.events[0]
    assert (kind, f, a1) == (EV_INVOKE, F_WRITE, 2)


def test_event_order_and_slot_reuse():
    h = _h((INVOKE, "write", 1, 0), (OK, "write", 1, 0),
           (INVOKE, "read", None, 0), (OK, "read", 1, 0))
    enc = encode_register_history(h, k_slots=32)
    kinds = list(enc.events[:, 0])
    assert kinds == [EV_INVOKE, EV_RETURN, EV_INVOKE, EV_RETURN]
    # Sequential ops reuse slot 0.
    assert list(enc.events[:, 1]) == [0, 0, 0, 0]
    assert enc.max_pending == 1


def test_nil_read_encoding():
    h = _h((INVOKE, "read", None, 0), (OK, "read", None, 0))
    enc = encode_register_history(h)
    assert enc.events[0][5] == NIL


def test_slot_overflow():
    h = _h(*[(INVOKE, "write", 1, p) for p in range(5)])
    with pytest.raises(SlotOverflow):
        encode_register_history(h, k_slots=4)
    enc = encode_register_history(h, k_slots=8)
    assert enc.max_pending == 5


def test_padding():
    h = _h((INVOKE, "write", 1, 0), (OK, "write", 1, 0))
    enc = encode_register_history(h).padded_to(16)
    assert enc.events.shape == (16, 6)
    assert all(enc.events[i][0] == EV_PAD for i in range(2, 16))


def test_jsonl_round_trip():
    h = gen_register_history(random.Random(7), n_ops=30)
    text = history_to_jsonl(h)
    h2 = history_from_jsonl(text)
    assert len(h2) == len(h)
    for a, b in zip(h, h2):
        assert (a.type, a.f, a.process, a.index) == (b.type, b.f, b.process,
                                                     b.index)
        if a.f == "cas":
            assert tuple(a.value) == tuple(b.value)
        else:
            assert a.value == b.value
    # Encodings agree exactly.
    e1, e2 = encode_register_history(h), encode_register_history(h2)
    assert np.array_equal(e1.events, e2.events)


def test_fuzz_histories_encode(rng):
    for _ in range(20):
        h = gen_register_history(rng, n_ops=40, n_procs=6)
        enc = encode_register_history(h)
        assert enc.n_events >= enc.n_ops
        assert enc.max_pending <= 32


# -- the one-pass encoder against the encoder it replaced --------------------
#
# The oracle is the old three-stage path, kept here: pair_history's
# Invocations, their invoke and return points sorted by (history index,
# is_return), fail ops and info reads left out, slots from a LIFO stack.


def _old_points(invocations):
    points = []
    for inv in invocations:
        if inv.status == FAIL:
            continue
        if inv.status == INFO and inv.f == F_READ:
            continue
        points.append((inv.invoke_index, 0, inv))
        if inv.status == OK:
            points.append((inv.complete_index, 1, inv))
    points.sort(key=lambda p: (p[0], p[1]))
    return points


def _old_encode(history, model=None, k_slots=32, keyed=False):
    points = _old_points(pair_history(history, model, keyed))
    free = list(range(k_slots - 1, -1, -1))
    slot_of, rows, max_pending = {}, [], 0
    for hist_idx, is_return, inv in points:
        if not is_return:
            if not free:
                raise SlotOverflow(
                    f"more than {k_slots} simultaneously pending ops at "
                    f"history index {hist_idx}; raise k_slots")
            slot = free.pop()
            slot_of[inv.invoke_index] = slot
            rows.append([EV_INVOKE, slot, inv.f, inv.a1, inv.a2, inv.rv])
            max_pending = max(max_pending, k_slots - len(free))
        else:
            slot = slot_of.pop(inv.invoke_index)
            rows.append([EV_RETURN, slot, inv.f, inv.a1, inv.a2, inv.rv])
            free.append(slot)
    events = np.asarray(rows, dtype=np.int32).reshape(-1, 6)
    return EncodedHistory(
        events=events, n_events=len(rows),
        n_ops=sum(1 for _, r, _i in points if not r), k_slots=k_slots,
        max_pending=max_pending,
        max_value=int(events[:, 3:6].max()) if len(rows) else 0)


def _outcome(fn):
    try:
        return fn()
    except Exception as e:  # noqa: BLE001 - the exception is the outcome
        return e


def _assert_same_encoding(history, model=None, k_slots=32, keyed=False):
    want = _outcome(lambda: _old_encode(history, model, k_slots, keyed))
    got = _outcome(lambda: encode_history(history, model, k_slots, keyed))
    if isinstance(want, Exception):
        assert type(got) is type(want), (got, want)
        assert str(got) == str(want)
        return
    assert not isinstance(got, Exception), got
    assert got.events.dtype == np.int32
    assert got.events.shape == want.events.shape
    assert np.array_equal(got.events, want.events)
    assert (got.n_events, got.n_ops, got.max_pending, got.max_value,
            got.k_slots) == (want.n_events, want.n_ops, want.max_pending,
                             want.max_value, want.k_slots)
    if not keyed:
        assert event_sources(history, model) == [
            inv for _, _, inv in _old_points(pair_history(history, model))]


def _fuzz(gen, model, seeds=range(6)):
    def build():
        out = []
        for s in seeds:
            h = gen(random.Random(0xE7C0 + s))
            out.append(model.prepare_history(h) if model else h)
        return out
    return build


def _keyed_register_histories():
    rng = random.Random(0xE7C1)
    per_key = {k: gen_register_history(rng, n_ops=30, n_procs=4,
                                       p_info=0.1, p_fail_read=0.1)
               for k in ("a", 7, (1, 2))}
    return list(route_by_key(interleave_keyed(per_key)).values())


def _bare_keyed_completions():
    # Keyed records whose completions carry a bare value: a timeout's
    # :info with none, an ok read and an ok write that kept only v.
    return [_h((INVOKE, "write", ("k", 3), 0), (INFO, "write", None, 0),
               (INVOKE, "read", ("k", None), 1), (OK, "read", 3, 1),
               (INVOKE, "write", ("k", 4), 2), (OK, "write", 4, 2),
               (INVOKE, "read", ("k", None), 3), (INFO, "read", None, 3),
               (INVOKE, "cas", ("k", (4, 1)), 4), (FAIL, "cas", None, 4))]


def _edge_cases():
    return [
        # info writes pending forever: completed :info, and never completed
        _h((INVOKE, "write", 1, 0), (INFO, "write", 1, 0),
           (INVOKE, "cas", (1, 2), 1), (INVOKE, "write", 3, 2),
           (OK, "write", 3, 2), (INVOKE, "read", None, 3),
           (OK, "read", 2, 3)),
        # info reads dropped, completed or not
        _h((INVOKE, "read", None, 0), (INFO, "read", None, 0),
           (INVOKE, "read", None, 1), (INVOKE, "write", 0, 2),
           (OK, "write", 0, 2)),
        # fail ops of every f
        _h((INVOKE, "write", 1, 0), (FAIL, "write", 1, 0),
           (INVOKE, "cas", (1, 2), 1), (FAIL, "cas", (1, 2), 1),
           (INVOKE, "read", None, 2), (FAIL, "read", None, 2),
           (INVOKE, "read", None, 3), (OK, "read", None, 3)),
        # slots freed and reused most recent first
        _h((INVOKE, "write", 1, 0), (INVOKE, "write", 2, 1),
           (INVOKE, "write", 3, 2), (OK, "write", 2, 1),
           (OK, "write", 1, 0), (INVOKE, "read", None, 0),
           (INVOKE, "read", None, 1), (OK, "read", 3, 1),
           (OK, "read", 3, 0), (OK, "write", 3, 2)),
        # values that are not plain ints: bool, numpy int, float
        _h((INVOKE, "write", True, 0), (OK, "write", True, 0),
           (INVOKE, "cas", (np.int64(1), 2.0), 1), (OK, "cas", None, 1)),
        [],
    ]


_ERRORS = {
    "double-invoke": _h((INVOKE, "read", None, 0), (INVOKE, "read", None, 0)),
    "orphan-completion": _h((INVOKE, "write", 1, 0), (OK, "write", 1, 0),
                            (OK, "read", 1, 0)),
    "unknown-type": _h((INVOKE, "write", 1, 0), ("done", "write", 1, 0)),
    "negative-write": _h((INVOKE, "write", -2, 0), (OK, "write", -2, 0)),
    "negative-failed-write": _h((INVOKE, "write", -2, 0),
                                (FAIL, "write", -2, 0)),
    "negative-read": _h((INVOKE, "read", None, 0), (OK, "read", -3, 0)),
    "negative-open-cas": _h((INVOKE, "cas", (1, -1), 0)),
    "unsupported-f": _h((INVOKE, "add", 1, 0), (OK, "add", 1, 0)),
    "unsupported-failed-f": _h((INVOKE, "add", 1, 0), (FAIL, "add", 1, 0)),
    "cas-not-a-pair": _h((INVOKE, "cas", 1, 0), (OK, "cas", 1, 0)),
    # Two faults: the codec's, at an earlier completion, comes first.
    "codec-before-pairing": _h((INVOKE, "write", -1, 0),
                               (OK, "write", -1, 0),
                               (INVOKE, "read", None, 1),
                               (INVOKE, "read", None, 1)),
    # More open ops than slots and a codec fault later on.
    "codec-after-overflow": _h(*[(INVOKE, "write", 1, p) for p in range(5)],
                               (INVOKE, "write", -1, 9),
                               (OK, "write", -1, 9)),
}


def _families():
    from jepsen_etcd_demo_tpu.utils.fuzz import (gen_gset_history,
                                                 gen_multireg_history,
                                                 gen_queue_history)
    return {
        "register": (None, _fuzz(lambda r: gen_register_history(
            r, n_ops=60, n_procs=6, p_info=0.1, p_fail_read=0.1), None)),
        "cas-register": (get_model("cas-register"), _fuzz(
            lambda r: gen_register_history(r, n_ops=60, n_procs=6,
                                           p_info=0.1), None)),
        "multi-register": (get_model("multi-register"), _fuzz(
            lambda r: gen_multireg_history(r, n_ops=30, n_procs=4,
                                           p_info=0.15),
            get_model("multi-register"))),
        "gset": (get_model("gset"), _fuzz(
            lambda r: gen_gset_history(r, n_ops=30, n_procs=4, p_info=0.15),
            get_model("gset"))),
        "fifo-queue": (get_model("fifo-queue"), _fuzz(
            lambda r: gen_queue_history(r, n_ops=16, n_procs=4, fifo=True,
                                        p_info=0.15),
            get_model("fifo-queue"))),
        "unordered-queue": (get_model("unordered-queue"), _fuzz(
            lambda r: gen_queue_history(r, n_ops=16, n_procs=4, fifo=False,
                                        p_info=0.15),
            get_model("unordered-queue"))),
        "mutex": (get_model("mutex"), lambda: [get_model("mutex")
                  .prepare_history(_h((INVOKE, "acquire", None, 0),
                                      (OK, "acquire", None, 0),
                                      (INVOKE, "acquire", None, 1),
                                      (INVOKE, "release", None, 0),
                                      (OK, "release", None, 0),
                                      (INFO, "acquire", None, 1)))]),
    }


@pytest.mark.parametrize("family", ["register", "cas-register",
                                    "multi-register", "gset", "fifo-queue",
                                    "unordered-queue", "mutex"])
def test_one_pass_matches_old_encoder_families(family):
    model, histories = _families()[family]
    for h in histories():
        _assert_same_encoding(h, model, k_slots=32)


def _model_info_reads():
    # A model's own codec: its info reads are dropped too, completed or not.
    return [(get_model("gset"),
             _h((INVOKE, "add", 1, 0), (INFO, "add", None, 0),
                (INVOKE, "read", None, 1), (INFO, "read", None, 1),
                (INVOKE, "read", None, 2), (INVOKE, "add", 2, 3),
                (OK, "add", 2, 3), (INVOKE, "read", None, 1),
                (OK, "read", [2], 1))),
            (get_model("multi-register"),
             _h((INVOKE, "write", (0, 1), 0), (OK, "write", (0, 1), 0),
                (INVOKE, "read", (0, None), 1), (INFO, "read", None, 1),
                (INVOKE, "read", (1, None), 2), (INVOKE, "write", (1, 2), 3),
                (INFO, "write", (1, 2), 3)))]


@pytest.mark.parametrize("case", ["keyed", "bare-keyed-completions",
                                  "edge-cases", "small-k",
                                  "model-info-reads"])
def test_one_pass_matches_old_encoder_shapes(case):
    cas = get_model("cas-register")
    if case == "keyed":
        for h in _keyed_register_histories():
            _assert_same_encoding(h, cas, keyed=True)
            _assert_same_encoding(h, None, keyed=True)
    elif case == "bare-keyed-completions":
        for h in _bare_keyed_completions():
            _assert_same_encoding(h, cas, keyed=True)
    elif case == "model-info-reads":
        for model, h in _model_info_reads():
            _assert_same_encoding(h, model)
    elif case == "edge-cases":
        for h in _edge_cases():
            _assert_same_encoding(h, cas)
            _assert_same_encoding(h, None)
    else:
        # Slot overflow at the same point, with the same message, and the
        # same rows once k_slots fits.
        rng = random.Random(0xE7C2)
        for _ in range(8):
            h = gen_register_history(rng, n_ops=40, n_procs=6, p_info=0.2)
            for k in (1, 2, 3, 4, 6, 8):
                _assert_same_encoding(h, cas, k_slots=k)


@pytest.mark.parametrize("case", sorted(_ERRORS))
def test_one_pass_raises_as_old_encoder(case):
    h = _ERRORS[case]
    want = _outcome(lambda: _old_encode(h, None, k_slots=4))
    assert isinstance(want, Exception)
    _assert_same_encoding(h, None, k_slots=4)
    _assert_same_encoding(h, get_model("cas-register"), k_slots=4)


def test_one_pass_matches_old_encoder_benchmark_unit():
    """One unit of the register test's 1024-key pool, made by the
    benchmark's copied generator and encoded key by key in place."""
    import json
    from pathlib import Path

    from benchmark import load

    root = Path(__file__).resolve().parents[1] / "benchmark"
    cfg = json.loads((root / "configs/etcd-register.json").read_text())
    mix = json.loads((root / "traffic/indep-1024.json").read_text())
    tr = load.Traffic(cfg, mix, seed=2**31 + 27)
    ops = load.program_ops(tr.unit_keys(0), Op, keyed=True)
    per_key = route_by_key(ops)
    assert len(per_key) == 1024
    cas = get_model("cas-register")
    for h in per_key.values():
        _assert_same_encoding(h, cas, k_slots=16, keyed=True)


def test_inline_codec_counter():
    """encode.inline_codec_histories counts the histories encoded with the
    register codec bound directly: a cas-register one, not a gset one."""
    from jepsen_etcd_demo_tpu import obs
    from jepsen_etcd_demo_tpu.utils.fuzz import gen_gset_history

    reg = gen_register_history(random.Random(1), n_ops=20)
    gset = gen_gset_history(random.Random(2), n_ops=20)
    with obs.capture() as cap:
        encode_history(reg, get_model("cas-register"))
        encode_history(gset, get_model("gset"))
        encode_history(gset, get_model("gset"))
    snap = cap.metrics.snapshot()
    assert snap["encode.histories"]["value"] == 3
    assert snap["encode.inline_codec_histories"]["value"] == 1
