"""The per-key split routes records without copying them: the batched
encode reads each key's records through their (key, v) values, and only
keys that reach a single-history check get unwrapped Op copies. Every
encoding, fingerprint, verdict and witness equals the copied path's."""

from __future__ import annotations

import random

import numpy as np
import pytest

from jepsen_etcd_demo_tpu import obs
from jepsen_etcd_demo_tpu.checkers import IndependentChecker, Linearizable
from jepsen_etcd_demo_tpu.checkers.independent import (route_by_key,
                                                       split_by_key)
from jepsen_etcd_demo_tpu.models.cas_register import CASRegister
from jepsen_etcd_demo_tpu.ops.op import FAIL, INFO, INVOKE, OK, Op
from jepsen_etcd_demo_tpu.store import encode_cache
from jepsen_etcd_demo_tpu.utils.fuzz import (gen_register_history,
                                             interleave_keyed, mutate_history)

KEY_KINDS = {
    "int": lambda i: i,
    "str": lambda i: f"k{i}",
    "tuple": lambda i: ("t", i),
}
ORPHAN_PROC = 10 ** 7


def _stale_read(key, proc):
    """A key whose read sees a value never written: invalid."""
    return [Op(type=INVOKE, f="write", value=(key, 2), process=proc),
            Op(type=OK, f="write", value=(key, 2), process=proc),
            Op(type=INVOKE, f="read", value=(key, None), process=proc + 1),
            Op(type=OK, f="read", value=(key, 4), process=proc + 1)]


def _keyed_history(seed: int, kind: str, n_keys: int = 6,
                   invalid: bool = True) -> list[Op]:
    """An independent-key test history with CAS ops, :fail reads, :info
    completions carrying raw and keyed values, nemesis records, an orphan
    completion and (when `invalid`) keys that are not linearizable."""
    rng = random.Random(seed)
    keys = [KEY_KINDS[kind](i) for i in range(n_keys)]
    per_key = {}
    for i, k in enumerate(keys):
        h = gen_register_history(rng, n_ops=14, n_procs=3, p_info=0.3,
                                 p_fail_read=0.3)
        per_key[k] = mutate_history(rng, h) if invalid and i < 2 else h
    out: list[Op] = []
    for i, op in enumerate(interleave_keyed(per_key)):
        if op.type == INFO and i % 2:
            op.value = op.value[1]   # a timeout :info as recorded, unkeyed
        out.append(op)
        if i % 13 == 0:
            out.append(Op(type=INFO if i % 2 else INVOKE, f="start",
                          value=None, process="nemesis"))
        if i == 20:
            out.append(Op(type=OK, f="write", value=(keys[0], 3),
                          process=ORPHAN_PROC))
    if invalid:
        out.extend(_stale_read(KEY_KINDS[kind](n_keys), 10 ** 6))
    return out


def _features(h: list[Op]) -> set:
    seen = set()
    for op in h:
        if op.process == "nemesis":
            seen.add("nemesis")
        elif op.process == ORPHAN_PROC:
            seen.add("orphan")
        elif op.type == INFO:
            keyed = isinstance(op.value, tuple) and len(op.value) == 2
            seen.add("info keyed" if keyed else "info raw")
        elif op.type == FAIL and op.f == "read":
            seen.add("fail read")
        elif op.f == "cas":
            seen.add("cas")
    return seen


class _CopiedCAS(CASRegister):
    """The cas-register read only through unwrapped copies, as a model
    that translates histories is: the split's copied path."""

    def translates(self) -> bool:
        return True


@pytest.mark.parametrize("kind", sorted(KEY_KINDS))
@pytest.mark.parametrize("seed", range(4))
def test_routed_encoding_equals_copies(seed, kind):
    h = _keyed_history(seed, kind)
    assert _features(h) == {"nemesis", "orphan", "info keyed", "info raw",
                            "fail read", "cas"}
    lin = Linearizable(backend="jax")
    routed, split = route_by_key(h), split_by_key(h)
    assert list(routed) == list(split)
    for k, copies in split.items():
        assert [op.process for op in routed[k]] == \
            [op.process for op in copies]
        a = lin.encode(routed[k], keyed=True)
        b = lin.encode(copies)
        assert a.events.dtype == b.events.dtype
        assert a.events.shape == b.events.shape
        assert a.events.tobytes() == b.events.tobytes()
        assert (a.n_ops, a.max_pending, a.max_value, a.k_slots) == \
            (b.n_ops, b.max_pending, b.max_value, b.k_slots)
        assert encode_cache.history_fingerprint(
            routed[k], "cas-register", 24, keyed=True) == \
            encode_cache.history_fingerprint(copies, "cas-register", 24)


def test_route_keeps_the_records():
    h = _keyed_history(1, "str")
    routed = route_by_key(h)
    ids = {id(op) for op in h}
    assert all(id(op) in ids for sub in routed.values() for op in sub)
    assert sum(map(len, routed.values())) == sum(
        1 for op in h if op.process not in ("nemesis", ORPHAN_PROC))
    with pytest.raises(ValueError):
        route_by_key([Op(type=INVOKE, f="read", value=None, process=0)])


def test_keyed_encoding_hits_the_copies_cache_entry(tmp_path):
    h = _keyed_history(2, "int")
    lin = Linearizable(backend="jax")
    routed, split = route_by_key(h), split_by_key(h)
    with encode_cache.activated(tmp_path):
        for k in routed:
            lin.encode(routed[k], keyed=True)
        for k, copies in split.items():
            hit = encode_cache.lookup(copies, "cas-register", lin.k_slots)
            assert hit is not None
            assert np.array_equal(hit.events[:hit.n_events],
                                  lin.encode(copies).events[:hit.n_events])


def test_translating_model_refuses_keyed_records():
    h = _keyed_history(3, "int")
    with pytest.raises(ValueError, match="translates"):
        Linearizable("mutex", backend="jax").encode(
            next(iter(route_by_key(h).values())), keyed=True)


def _store_files(d) -> dict:
    out = {}
    for p in sorted(d.iterdir()):
        if p.suffix == ".npz":
            with np.load(p) as z:
                out[p.name] = {n: z[n].tolist() for n in z.files}
        else:
            out[p.name] = p.read_bytes()
    return out


@pytest.mark.parametrize("kind", sorted(KEY_KINDS))
@pytest.mark.parametrize("invalid", [False, True])
def test_checker_matches_copied_path(tmp_path, kind, invalid):
    h = _keyed_history(5, kind, invalid=invalid)
    got, want = {}, {}
    for out, model in ((got, CASRegister()), (want, _CopiedCAS())):
        d = tmp_path / type(model).__name__
        d.mkdir()
        res = IndependentChecker(Linearizable(model, backend="jax")).check(
            {}, h, {"store_dir": str(d)})
        for r in res.get("results", {}).values():
            if "witness_file" in r:
                r["witness_file"] = str(r["witness_file"]).replace(
                    str(d), "<store>")
        out.update(res=res, files=_store_files(d))
    assert got == want
    assert got["res"]["valid"] is (not invalid)
    if invalid:
        stale = str(KEY_KINDS[kind](6))
        assert got["res"]["results"][stale]["failed_op"] == "read -> 4"


def _count(cap, name):
    return cap.metrics.snapshot()[name]["value"]


def test_copies_only_the_rechecked_key():
    """64 keys, one invalid: only that key's records are copied, for its
    single-history re-check and witness."""
    rng = random.Random(64)
    per_key = {k: gen_register_history(rng, n_ops=10, n_procs=3)
               for k in range(63)}
    h = interleave_keyed(per_key) + _stale_read(63, 10 ** 6)
    h.insert(5, Op(type=INFO, f="start", value=None, process="nemesis"))
    with obs.capture() as cap:
        res = IndependentChecker(Linearizable(backend="jax")).check({}, h)
    assert res["key_count"] == 64
    assert [k for k, r in res["results"].items()
            if r["valid"] is not True] == ["63"]
    assert _count(cap, "check.split.records_routed") == len(h) - 1
    assert _count(cap, "check.split.records_copied") == 4


def _mutex_key(key, proc):
    ops = []
    for f in ("acquire", "release", "acquire", "release"):
        ops.append(Op(type=INVOKE, f=f, value=(key, None), process=proc))
        ops.append(Op(type=OK, f=f, value=(key, None), process=proc))
    return ops


def test_translating_model_copies_every_record():
    h = [op for k in range(4) for op in _mutex_key(k, 10 * k)]
    with obs.capture() as cap:
        res = IndependentChecker(Linearizable("mutex", backend="jax")).check(
            {}, h)
    assert res["valid"] is True and res["key_count"] == 4
    assert _count(cap, "check.split.records_routed") == len(h)
    assert _count(cap, "check.split.records_copied") == len(h)


def test_split_by_key_copies_every_record():
    h = _keyed_history(4, "int")
    with obs.capture() as cap:
        split = split_by_key(h)
    n = sum(map(len, split.values()))
    assert _count(cap, "check.split.records_routed") == n
    assert _count(cap, "check.split.records_copied") == n
