"""Independent-keys checker — equivalent of `independent/checker`.

The reference lifts a single-register workload onto many independent keys:
values become (key, value) tuples (src/jepsen/etcdemo.clj:90), and
`independent/checker` splits the history per key and runs the sub-checker on
each (src/jepsen/etcdemo.clj:115).

TPU twist: when the sub-checker is a `Linearizable` with the JAX backend — or
a `Compose` whose direct entries include one — all per-key histories are
encoded, padded to a common event length, stacked, and checked in ONE vmapped
kernel launch; per-key histories are embarrassingly parallel, so the key axis
is the batch axis (BASELINE.json configs[2]). Each distinct Linearizable
entry gets its own batched launch under its own result name; every other
composed checker still runs per key, unbatched.

The split routes records to keys without copying them: the batched encode
reads each key's records through their (key, v) values, and only a key
that reaches a single-history check (or a model that translates
histories) gets unwrapped Op copies.
"""

from __future__ import annotations

from typing import Any, Sequence

import numpy as np

from .base import Checker, merge_valid
from .compose import Compose
from .linearizable import Linearizable
from .. import obs
from ..ops.op import Op, INVOKE, keyed_value


def route_by_key(history: Sequence[Op]) -> dict[Any, list[Op]]:
    """Route a tuple-valued history's records to their keys, uncopied.

    Invocations carry (key, v) tuples; completions may or may not (e.g. a
    :write completion keeps the tuple, a timeout :info has whatever the invoke
    had). Like jepsen.independent, the key is taken from the op's tuple value;
    completions are routed to the key of their pending invocation, and
    orphan completions and nemesis records are dropped. Each key's list
    holds the original records in history order, values still keyed.
    """
    keyed: dict[Any, list[Op]] = {}
    key_of_process: dict[Any, Any] = {}
    for op in history:
        if op.process == "nemesis":
            continue
        if op.type == INVOKE:
            if not (isinstance(op.value, tuple) and len(op.value) == 2):
                raise ValueError(
                    f"independent history op without (key, value) tuple: {op}")
            k = op.value[0]
            key_of_process[op.process] = k
        else:
            k = key_of_process.pop(op.process, None)
            if k is None:
                continue
        keyed.setdefault(k, []).append(op)
    obs.get_metrics().counter("check.split.records_routed").add(
        sum(map(len, keyed.values())))
    return keyed


def _unwrap(op: Op) -> Op:
    """A copy of a routed record with its value unwrapped (keyed_value)."""
    return Op(type=op.type, f=op.f, value=keyed_value(op),
              process=op.process, time=op.time, index=op.index,
              error=op.error, seq=op.seq)


class _Unwrapped(dict):
    """Each key's unwrapped sub-history, copied on first use and shared
    by every reader of that key."""

    def __init__(self, routed: dict[Any, list[Op]]):
        super().__init__()
        self.routed = routed

    def __missing__(self, key) -> list[Op]:
        sub = self[key] = [_unwrap(op) for op in self.routed[key]]
        obs.get_metrics().counter("check.split.records_copied").add(len(sub))
        return sub


def split_by_key(history: Sequence[Op]) -> dict[Any, list[Op]]:
    """Split a tuple-valued history into per-key sub-histories of
    unwrapped copies (route_by_key's routing, keyed_value's values)."""
    subs = _Unwrapped(route_by_key(history))
    return {k: subs[k] for k in subs.routed}


class IndependentChecker(Checker):
    def __init__(self, sub_checker: Checker, batch_jax: bool = True):
        self.sub_checker = sub_checker
        self.batch_jax = batch_jax

    def check(self, test: dict, history: Sequence[Op],
              opts: dict | None = None) -> dict[str, Any]:
        with obs.phase("check.split"):
            routed = route_by_key(history)
            keys = sorted(routed, key=str)
        if not routed:
            return {"valid": True, "key_count": 0}
        # Unwrapped copies only for what reads Op values: a key that
        # reaches a sub-checker's single-history path, and the batched
        # encode of a model that translates histories.
        subs = _Unwrapped(routed)

        # Which checkers can ride the batched kernel? Only direct entries:
        # either the sub-checker itself, or first-level values of a Compose.
        batchable: dict[str | None, Linearizable] = {}
        if self.batch_jax and len(routed) > 1:
            if (isinstance(self.sub_checker, Linearizable)
                    and self.sub_checker.backend == "jax"):
                batchable[None] = self.sub_checker
            elif isinstance(self.sub_checker, Compose):
                for name, sub in self.sub_checker.checkers.items():
                    if isinstance(sub, Linearizable) and sub.backend == "jax":
                        batchable[name] = sub

        # Keys the run's streaming check session (stream/engine.py) has
        # already settled valid for a given model skip the batched
        # launch entirely — _check_key's per-key path picks the streamed
        # verdict up via Linearizable._stream_result. Invalid/unsettled
        # keys keep the full batched + ladder treatment (witnesses).
        stream_results = (opts or {}).get("stream_results") or {}

        def settled_for(lin: Linearizable) -> set:
            return {k for k, r in stream_results.items()
                    if isinstance(r, dict) and r.get("valid") is True
                    and r.get("model") == lin.model.name}

        def batch_keys(lin: Linearizable) -> dict[Any, list[Op]]:
            settled = settled_for(lin)
            todo = [k for k in routed if k not in settled]
            if not lin.model.translates():
                return {k: routed[k] for k in todo}
            with obs.phase("check.split"):
                return {k: subs[k] for k in todo}

        # The call's intermediates (encodings), freed in check.release.
        held: list = []
        batched: dict[str | None, dict[Any, dict]] = {
            name: _batched_linearizable(lin, batch_keys(lin),
                                        (opts or {}).get("store_dir"), held)
            for name, lin in batchable.items()
        }

        results: dict[Any, dict] = {}
        for k in keys:
            results[k] = self._check_key(test, subs, opts, batched, k)
        valid = merge_valid([r.get("valid") for r in results.values()])
        out = {"valid": valid, "key_count": len(routed),
               "results": {str(k): v for k, v in results.items()}}
        # Free the routed lists, copies and encodings here, inside a
        # phase, rather than after the call's last one.
        with obs.phase("check.release"):
            del routed, subs, batched, results, held
        return out

    def _check_key(self, test, subs, opts, batched, key):
        opts = dict(opts or {})
        opts["key"] = key  # sub-checkers emit per-key artifacts (timeline)

        def pick(name, checker):
            # A batched result settles the key only when valid: invalid keys
            # re-run the single-history path, which reconstructs and stores
            # the counterexample witness (linear-<key>.json/svg); "unknown"
            # re-runs for the escalation ladder, seeded past the capacities
            # the batched tiers already proved dead (f_cap_floor).
            pre = batched.get(name, {}).get(key)
            if pre is not None and pre["valid"] is True:
                return pre
            sub_opts = opts
            if pre and pre.get("f_cap_floor"):
                sub_opts = dict(opts)
                sub_opts["f_cap_floor"] = pre["f_cap_floor"]
            return checker.check(test, subs[key], sub_opts)

        if not isinstance(self.sub_checker, Compose):
            return pick(None, self.sub_checker)
        sub_results = {name: pick(name, sub)
                       for name, sub in self.sub_checker.checkers.items()}
        return {"valid": merge_valid([r.get("valid")
                                      for r in sub_results.values()]),
                **sub_results}


def _batched_linearizable(lin: Linearizable, keyed: dict[Any, list[Op]],
                          store_dir, held: list) -> dict[Any, dict]:
    """Encode every key's history into the return-major form, pad to one
    step count, run one vmapped kernel launch over the key batch.

    Prefers the dense lattice kernel (wgl3) — exact, no overflow — whenever
    the shared config table is feasible; falls back to the sort kernel.
    The encodings are appended to `held`, for the caller to free."""
    if not keyed:
        return {}
    with obs.get_tracer().span("check.linearizable.batched",
                               model=lin.model.name,
                               keys=len(keyed)) as sp:
        out = _batched_linearizable_traced(lin, keyed, store_dir, held)
        sp.set(settled=sum(1 for r in out.values()
                           if r.get("valid") is True))
        return out


def _batched_linearizable_traced(lin: Linearizable,
                                 keyed: dict[Any, list[Op]],
                                 store_dir, held: list) -> dict[Any, dict]:
    from ..ops import wgl3

    with obs.phase("check.encode"):
        # Routed records in place; a model that translates histories
        # was given unwrapped copies (IndependentChecker.check).
        in_place = not lin.model.translates()
        event_encs = {k: lin.encode(h, keyed=in_place)
                      for k, h in keyed.items()}
        held.append(event_encs)
        max_value = max(e.max_value for e in event_encs.values())
        tight = max(wgl3.tight_k_slots(e) for e in event_encs.values())
        cfg3 = wgl3.dense_config(lin.model, tight, max_value)
    if store_dir:
        from ..store.store import write_encoded_tensor

        for k, e in event_encs.items():
            # Empty encodings included (corpus tensor-coverage contract).
            write_encoded_tensor(store_dir, k, e, lin.model.name)

    # Dense path: one table geometry serves the whole batch — mask width =
    # the largest key's real concurrency. Launches go through the corpus
    # scheduler (sched/engine.py): per-key histories land in padded-length
    # buckets instead of all padding to the longest key, so a run with one
    # long-lived key no longer taxes every other key's launch.
    if cfg3 is not None:
        from .. import sched

        keys = list(event_encs)
        batch, kernel, _stats = sched.check_corpus(
            [event_encs[k] for k in keys], lin.model)
        return {
            k: {
                "valid": one["valid"],
                "backend": "jax-dense-batched",
                "kernel": kernel,
                "op_count": one["op_count"],
                "dead_step": one["dead_step"],
                "max_frontier": one["max_frontier"],
                "configs_explored": one["configs_explored"],
                "overflow": False,
                "f_cap": one["table_cells"],
            }
            for k, one in zip(keys, batch)
        }

    # Sort-kernel path: the shared batched general pass (one copy of the
    # pad/stack/launch/verdict logic, with its row-budget chunking and
    # LONG_SCAN_MAX guard — wgl3_pallas._batch_general). Keys the tiers
    # could not settle get an "unknown" marker carrying an f_cap_floor:
    # _check_key's pick() threads it into the single-path re-run, so the
    # ladder there starts past the capacities the tiers proved dead (one
    # ladder run per unsettled key, witnesses included).
    from ..ops.wgl3_pallas import LADDER_SEED_FACTOR, _batch_general

    keys = list(event_encs)
    slots: list = [None] * len(keys)
    overflowed, too_long, top = _batch_general(
        [event_encs[k] for k in keys], list(range(len(keys))),
        lin.model, slots, set(), f_cap=lin.f_cap)
    results = {}
    for i in overflowed:
        results[keys[i]] = {"valid": "unknown",
                            "f_cap_floor": LADDER_SEED_FACTOR * top}
    for k, one in zip(keys, slots):
        if one is None:
            continue
        # Keys mirror the single-history jax path's normalized schema
        # (linearizable.py) so consumers see one shape whatever path ran.
        results[k] = {
            "valid": one["valid"],
            "backend": "jax-batched",
            "op_count": one["op_count"],
            "dead_step": one["dead_step"],
            "max_frontier": one["max_frontier"],
            "overflow": one["overflow"],
            "f_cap": one["f_cap"],
        }
    return results
