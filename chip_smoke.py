"""chip_smoke.py — run the checker end to end on one TPU chip.

    python chip_smoke.py               # one chip: the four phases below
    python chip_smoke.py --four-chips  # four chips: the sharded paths only

One process touches JAX once and drives the system through the entry
points a user calls, at the reference's sizes (BASELINE.json):

  1. cli      `jepsen-tpu test -w register --fake` with the reference
              defaults (10 threads and 100 ops per key, partition
              nemesis, 5 s client timeout): exit 0 and valid; again with
              --stale-read-prob 0.8 --no-nemesis: exit 1, valid false, a
              dead_step.
  2. corpus   1024 register histories x 150 ops, 10 processes each
              (corpus_encs, one in eight mutated) through the
              production router and the corpus scheduler: compiled
              pallas kernels, every verdict equal to the CPU WGL oracle.
     split    1024 histories x 170 ops: one launch would overflow SMEM,
              so the router splits it into several compiled pallas
              calls; every verdict equal to the CPU WGL oracle.
  3. long     one 10k-op single-register history and a mutated copy:
              valid true / valid false with the XLA kernel's dead_step.
  4. serve    `serve --check`'s daemon in this process on port 0; a few
              POST /check histories answered with route "jax" and the
              analyze route's verdicts.

--four-chips runs the independent-key corpus sharded on the batch axis
(parallel/dense.py) and a wide single history sharded on the lattice
word axis (parallel/lattice.py), each against the single-device verdicts
on device 0, and checks that each mesh spans all four devices.

Each phase prints one line: wall and compile seconds, the kernel names,
and its verdicts. The exit code is non-zero if any phase fails or no TPU
is found. The last line of stdout is exactly
{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}.
"""

from __future__ import annotations

import argparse
import json
import os
import random
import sys
import tempfile
import threading
import time

N_KEYS_OPS = 100          # ops per key (reference default)
CORPUS = 1024             # histories in the corpus phases
N_OPS = 150               # ops per corpus history (BASELINE.json configs[0])
N_PROCS = 10              # concurrency: the reference's 10 threads per key
CORPUS_MUTATE_EVERY = 8   # one history in eight is made non-linearizable
SPLIT_OPS = 170           # 1024 x 170 ops encode to 192 steps: 2 launches
LONG_OPS = 10_000         # BASELINE.json configs[3]


class PhaseFailed(AssertionError):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise PhaseFailed(what)


def timed(fn):
    """(result, seconds) of fn()."""
    t0 = time.perf_counter()
    out = fn()
    return out, time.perf_counter() - t0


def cold_warm(fn):
    """Run fn twice: the first call pays tracing and compilation, the
    second is the steady state. Returns (result, wall_s, compile_s)."""
    _, cold = timed(fn)
    out, warm = timed(fn)
    return out, cold, max(0.0, cold - warm)


def _oracle_verdicts(encs, model) -> list[tuple]:
    from jepsen_etcd_demo_tpu.ops.wgl3_pallas import _oracle_result

    return [(r["valid"], r["dead_step"])
            for r in (_oracle_result(e, model) for e in encs)]


def _verdicts(results) -> list[tuple]:
    return [(r["valid"], int(r["dead_step"])) for r in results]


def _pallas_only(kernels, phase: str) -> None:
    for k in kernels:
        check("pallas" in k and "interpret" not in k,
              f"{phase}: kernel {k!r} is not a compiled pallas kernel")


# -- phases ---------------------------------------------------------------

def phase_cli(store: str, time_limit: float = 30.0, rate: float = 100.0,
              bad_time_limit: float = 10.0) -> dict:
    """`test -w register --fake` twice: a clean partitioned run must be
    valid, a stale-read run must be caught."""
    from jepsen_etcd_demo_tpu.cli.main import main as cli_main

    def run(extra: list[str], sub: str) -> tuple[int, dict, float]:
        root = os.path.join(store, sub)
        argv = ["test", "-w", "register", "--fake", "--store", root,
                "--ops-per-key", str(N_KEYS_OPS), "--concurrency", "10",
                "--rate", str(rate), "--seed", "7"] + extra
        rc, wall = timed(lambda: cli_main(argv))
        with open(os.path.join(root, "latest", "results.json")) as f:
            return rc, json.load(f), wall

    rc, res, wall = run(["--time-limit", str(time_limit)], "good")
    keys = res["indep"]["results"]
    # The kernels first called in this run (obs keys them by the
    # instrument_kernel name) — the CLI phase runs first, so that is
    # every kernel its check launched.
    with open(os.path.join(store, "good", "latest", "metrics.json")) as f:
        prefix = "wgl.compile_s."
        kernels = sorted(k[len(prefix):] for k in json.load(f)["metrics"]
                         if k.startswith(prefix))
    check(rc == 0 and res["valid"] is True,
          f"cli: clean run rc={rc} valid={res['valid']}")
    rc_b, bad, wall_b = run(["--time-limit", str(bad_time_limit),
                             "--stale-read-prob", "0.8", "--no-nemesis"],
                            "stale")
    dead = [r["linear"]["dead_step"] for r in bad["indep"]["results"]
            .values() if r["linear"]["valid"] is False]
    check(rc_b == 1 and bad["valid"] is False and dead
          and all(d >= 0 for d in dead),
          f"cli: stale-read run rc={rc_b} valid={bad['valid']} "
          f"dead_steps={dead}")
    return {"wall_s": wall + wall_b, "kernels": kernels,
            "clean": {"rc": rc, "valid": res["valid"],
                      "ops": res["op_count"], "keys": len(keys),
                      "wall_s": wall},
            "stale": {"rc": rc_b, "valid": bad["valid"],
                      "invalid_keys": len(dead),
                      "dead_step_min": min(dead), "wall_s": wall_b}}


def corpus_encs(n: int | None = None, n_ops: int | None = None):
    """The seeded corpus, one history in CORPUS_MUTATE_EVERY mutated so
    the comparison covers both verdicts."""
    from jepsen_etcd_demo_tpu.ops.encode import encode_register_history
    from jepsen_etcd_demo_tpu.utils.fuzz import (gen_register_history,
                                                 mutate_history)

    n = CORPUS if n is None else n
    rng, mrng = random.Random(0xBE7C), random.Random(0x5A0E)
    encs, hists = [], []
    for i in range(n):
        h = gen_register_history(rng, n_ops=n_ops or N_OPS,
                                 n_procs=N_PROCS, p_info=0.002)
        if i % CORPUS_MUTATE_EVERY == CORPUS_MUTATE_EVERY - 1:
            h = mutate_history(mrng, h)
        hists.append(h)
        encs.append(encode_register_history(h, k_slots=32))
    return encs, hists


def phase_corpus(encs, model, require_pallas: bool = True) -> dict:
    """The corpus through the production router and the corpus
    scheduler; every verdict against the CPU WGL oracle."""
    from dataclasses import asdict

    from jepsen_etcd_demo_tpu import sched
    from jepsen_etcd_demo_tpu.ops import wgl3, wgl3_pallas
    from jepsen_etcd_demo_tpu.ops.calibrate import get_calibration

    (auto, kernel), wall, comp = cold_warm(
        lambda: wgl3_pallas.check_batch_encoded_auto(encs, model))
    (by_sched, s_kernel, stats), s_wall, s_comp = cold_warm(
        lambda: sched.check_corpus(encs, model))
    if require_pallas:
        _pallas_only([kernel, s_kernel], "corpus")
    want, oracle_s = timed(lambda: _oracle_verdicts(encs, model))
    got, got_s = _verdicts(auto), _verdicts(by_sched)
    check(got == want, f"corpus: router verdicts differ from the oracle "
          f"at {sum(g != w for g, w in zip(got, want))} histories")
    check(got_s == want, f"corpus: sched verdicts differ from the oracle "
          f"at {sum(g != w for g, w in zip(got_s, want))} histories")
    cfg, _, r_cap = wgl3.batch_steps3(encs, model)
    cal = asdict(get_calibration())
    return {"wall_s": wall, "compile_s": comp, "kernels": [kernel],
            "sched": {"wall_s": s_wall, "compile_s": s_comp,
                      "kernel": s_kernel, "launches": stats["launches"]},
            "histories": len(encs),
            "events": int(sum(e.n_events for e in encs)),
            "k_slots": cfg.k_slots, "r_cap": r_cap,
            "launch_parts": wgl3_pallas.launch_parts(
                len(encs), r_cap,
                wgl3_pallas.pallas_group_size(cfg, r_cap, len(encs))),
            "invalid": sum(1 for v, _ in want if v is False),
            "oracle_s": oracle_s, "verdicts_equal_oracle": True,
            "calibration": {k: cal[k] for k in (
                "platform", "dispatch_floor_s", "oracle_events_per_s",
                "crossover_events")}}


def phase_split(encs, model) -> dict:
    """A corpus whose single launch the chip's compiler would refuse
    (SMEM): the router's launch_parts split, every verdict against the
    CPU WGL oracle."""
    from jepsen_etcd_demo_tpu.ops import wgl3, wgl3_pallas

    (auto, kernel), wall, comp = cold_warm(
        lambda: wgl3_pallas.check_batch_encoded_auto(encs, model))
    _pallas_only([kernel], "split")
    cfg, _, r_cap = wgl3.batch_steps3(encs, model)
    group = wgl3_pallas.pallas_group_size(cfg, r_cap, len(encs))
    parts = wgl3_pallas.launch_parts(len(encs), r_cap, group)
    check(len(parts) >= 2, f"split: {len(encs)} x {r_cap} steps ran as "
          f"one launch {parts}")
    want, oracle_s = timed(lambda: _oracle_verdicts(encs, model))
    got = _verdicts(auto)
    check(got == want, f"split: router verdicts differ from the oracle "
          f"at {sum(g != w for g, w in zip(got, want))} histories")
    return {"wall_s": wall, "compile_s": comp, "kernels": [kernel],
            "histories": len(encs),
            "events": int(sum(e.n_events for e in encs)),
            "k_slots": cfg.k_slots, "r_cap": r_cap, "group": group,
            "launch_parts": parts,
            "invalid": sum(1 for v, _ in want if v is False),
            "oracle_s": oracle_s, "verdicts_equal_oracle": True}


def long_encs(n_ops: int = LONG_OPS):
    """One long single-register history and a mutated copy."""
    from jepsen_etcd_demo_tpu.ops.encode import encode_register_history
    from jepsen_etcd_demo_tpu.utils.fuzz import (gen_register_history,
                                                 mutate_history)

    h = gen_register_history(random.Random(0x10C0 + n_ops), n_ops=n_ops,
                             n_procs=N_PROCS, p_info=0.0005)
    bad = mutate_history(random.Random(0xBAD), h)
    return [encode_register_history(x, k_slots=64) for x in (h, bad)]


def phase_long(encs, model, require_pallas: bool = True) -> dict:
    """Each history alone through the router; the XLA dense kernel on the
    same encoding is the reference."""
    from jepsen_etcd_demo_tpu.ops import wgl3, wgl3_pallas

    out, kernels, wall, comp = [], [], 0.0, 0.0
    for enc in encs:
        (res, kernel), w, c = cold_warm(
            lambda: wgl3_pallas.check_batch_encoded_auto([enc], model))
        out.append(res[0])
        kernels.append(kernel)
        wall, comp = wall + w, comp + c
    if require_pallas:
        _pallas_only(kernels, "long")
    ref = []
    for enc in encs:
        cfg, steps, r_cap = wgl3.batch_steps3([enc], model)
        arrays = wgl3.stack_steps3(steps, r_cap)
        ref.append(wgl3.assemble_batch_results(wgl3.unpack_np(
            wgl3.cached_batch_checker3_packed(model, cfg)(*arrays)),
            steps, cfg)[0])
    got, want = _verdicts(out), _verdicts(ref)
    check(got == want, f"long: router {got} != XLA kernel {want}")
    check(got[0][0] is True and got[1][0] is False and got[1][1] >= 0,
          f"long: expected valid then invalid with a dead_step, got {got}")
    return {"wall_s": wall, "compile_s": comp, "kernels": kernels,
            "events": [e.n_events for e in encs], "verdicts": got,
            "xla_verdicts": want}


def phase_serve(hists, model, store: str, clients: int = 8) -> dict:
    """The serve --check daemon in this process: concurrent POST /check
    answers against the analyze route on the same encoding."""
    import urllib.request
    from http.server import ThreadingHTTPServer

    from jepsen_etcd_demo_tpu.ops import wgl3_pallas
    from jepsen_etcd_demo_tpu.serve.daemon import (ServeDaemon,
                                                   make_serve_handler)

    daemon = ServeDaemon(store_root=store, default_model=model.name)
    httpd = ThreadingHTTPServer(("127.0.0.1", 0),
                                make_serve_handler(store, daemon))
    url = f"http://127.0.0.1:{httpd.server_address[1]}/check"
    server = threading.Thread(target=httpd.serve_forever, daemon=True)
    server.start()
    answers: list = [None] * len(hists)
    errors: list = []

    def post(i: int) -> None:
        body = json.dumps({"tenant": f"t{i % 3}", "model": model.name,
                           "wait": True, "history":
                           [json.loads(op.to_json()) for op in hists[i]]})
        req = urllib.request.Request(url, data=body.encode(), headers={
            "Content-Type": "application/json"})
        try:
            with urllib.request.urlopen(req, timeout=600) as r:
                answers[i] = json.loads(r.read())
        except Exception as e:   # reported below, not swallowed
            errors.append(f"history {i}: {type(e).__name__}: {e}")

    def round_trip():
        threads = [threading.Thread(target=post, args=(i,))
                   for i in range(len(hists))]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        check(not errors, f"serve: {errors[:3]}")
        return list(answers)

    try:
        got, wall, comp = cold_warm(round_trip)
    finally:
        httpd.shutdown()
        daemon.close()
        httpd.server_close()
    want = [wgl3_pallas.check_batch_encoded_auto(
        [daemon.encode(model.name, h)], model)[0][0] for h in hists]
    routes = sorted({a["route"] for a in got})
    check(routes == ["jax"], f"serve: routes {routes}, expected jax only")
    check(_verdicts(got) == _verdicts(want),
          f"serve: served verdicts {_verdicts(got)} != analyze route "
          f"{_verdicts(want)}")
    return {"wall_s": wall, "compile_s": comp,
            "kernels": sorted({a["kernel"] for a in got}),
            "requests": len(hists), "routes": routes,
            "batch_sizes": sorted({a["batch"]["size"] for a in got}),
            "invalid": sum(1 for a in got if a["valid"] is False),
            "verdicts_equal_analyze": True}


def phase_four_batch(encs, model) -> dict:
    """The independent-key corpus sharded on the batch axis vs the
    single-device launch on device 0."""
    import jax

    from jepsen_etcd_demo_tpu.ops import wgl3, wgl3_pallas
    from jepsen_etcd_demo_tpu.parallel.dense import (batch_mesh,
                                                     pad_batch_arrays)
    from jepsen_etcd_demo_tpu.plan import launch_multiple, plan_dense_batch
    from jepsen_etcd_demo_tpu.plan import resolve

    mesh = batch_mesh()
    ids = sorted(d.id for d in mesh.devices.flat)
    check(len(ids) == jax.device_count() == 4,
          f"four_batch: batch mesh spans devices {ids}")
    (res, kernel), wall, comp = cold_warm(
        lambda: wgl3_pallas.check_batch_encoded_auto(encs, model))
    # Where the sharded launch's result lives: one shard per device.
    cfg, steps, r_cap = wgl3.batch_steps3(encs, model)
    arrays, _ = pad_batch_arrays(
        wgl3.stack_steps3(steps, r_cap),
        launch_multiple(model, cfg, r_cap, len(encs)))
    plan = plan_dense_batch(model, cfg, n_steps=r_cap, batch=len(encs))
    out = resolve(plan)(*arrays)
    placed = sorted(d.id for d in out.sharding.device_set)
    check(placed == ids, f"four_batch: result on devices {placed}")
    check_single, single_kernel = wgl3_pallas.packed_batch_checker(
        model, cfg, n_steps=r_cap, batch=len(encs))
    single = wgl3.assemble_batch_results(wgl3.unpack_np(
        check_single(*wgl3.stack_steps3(steps, r_cap))), steps, cfg)
    check(_verdicts(res) == _verdicts(single),
          "four_batch: sharded verdicts differ from device 0's")
    return {"wall_s": wall, "compile_s": comp,
            "kernels": [kernel, plan.label, single_kernel],
            "histories": len(encs), "devices": placed,
            "invalid": sum(1 for v, _ in _verdicts(res) if v is False),
            "verdicts_equal_single": True}


def wide_history(n_ops: int = 200, pending: int = 14, seed: int = 0xA1DE):
    """One register history with `pending` forever-pending writes up
    front (k_slots near 20, a table the lattice axis shards), and a copy
    that ends with a read of a value nobody wrote."""
    from jepsen_etcd_demo_tpu.ops.encode import encode_register_history
    from jepsen_etcd_demo_tpu.ops.op import Op
    from jepsen_etcd_demo_tpu.utils.fuzz import gen_register_history

    rng = random.Random(seed)
    wide = [Op(type="invoke", f="write", value=5, process=f"w{i}",
               time=0) for i in range(pending)]
    h = wide + gen_register_history(rng, n_ops=n_ops, n_procs=6,
                                    p_info=0.0)
    t = max(op.time for op in h) + 1
    bad = h + [Op(type="invoke", f="read", value=None, process="r",
                  time=t),
               Op(type="ok", f="read", value=7, process="r", time=t + 1)]
    return [encode_register_history(x, k_slots=32) for x in (h, bad)]


def phase_four_lattice(encs, model) -> dict:
    """A wide single history sharded on the lattice word axis vs the
    single-device dense sweep on device 0."""
    from dataclasses import replace

    import jax

    from jepsen_etcd_demo_tpu.ops import wgl3
    from jepsen_etcd_demo_tpu.ops.encode import encode_return_steps
    from jepsen_etcd_demo_tpu.ops.limits import limits, set_limits
    from jepsen_etcd_demo_tpu.parallel import lattice

    mesh = lattice.lattice_mesh()
    ids = sorted(d.id for d in mesh.devices.flat)
    check(len(ids) == jax.device_count() == 4,
          f"four_lattice: lattice mesh spans devices {ids}")
    fields = ("survived", "dead_step", "max_frontier")
    rows, kernels, wall, comp = [], set(), 0.0, 0.0
    # Shard-local frontier dedup legitimately changes the search
    # metrics; pinned off so the comparison is field for field.
    prev = set_limits(replace(limits(), dedup_mode=1))
    try:
        for enc in encs:
            k = wgl3.tight_k_slots(enc)
            cfg = lattice.lattice_dense_config(model, k, enc.max_value, 4)
            check(cfg is not None, f"four_lattice: k={k} does not shard")
            rs = encode_return_steps(enc)
            shard, w, c = cold_warm(lambda: lattice.check_steps_lattice_long(
                rs, model, cfg, mesh=mesh))
            single = wgl3.check_steps3_long(rs, model, cfg)
            # configs_explored sums in f32 on both paths: past 2^24 the
            # two summation orders round differently.
            cfgs = (shard["configs_explored"], single["configs_explored"])
            check(all(shard[f] == single[f] for f in fields)
                  and abs(cfgs[0] - cfgs[1]) <= 1e-6 * max(cfgs),
                  f"four_lattice: {[(f, shard[f], single[f]) for f in fields]}"
                  f" configs_explored {cfgs}")
            rows.append({"k_slots": cfg.k_slots, "valid": shard["valid"],
                         "dead_step": shard["dead_step"]})
            kernels.add(shard["kernel"])
            wall, comp = wall + w, comp + c
    finally:
        set_limits(prev)
    check(rows[0]["valid"] is True and rows[1]["valid"] is False,
          f"four_lattice: expected valid then invalid, got {rows}")
    return {"wall_s": wall, "compile_s": comp, "kernels": sorted(kernels),
            "devices": ids, "histories": rows, "verdicts_equal_single": True}


# -- driver ---------------------------------------------------------------

def _print_phase(name: str, rec: dict) -> None:
    print(json.dumps({"phase": name, **rec}, default=str), flush=True)


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 4-device sharded paths")
    args = ap.parse_args(argv)

    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: no TPU (jax.devices()[0].platform="
              f"{devs[0].platform!r}); nothing run", file=sys.stderr)
        return 1
    if args.four_chips and len(devs) != 4:
        print(f"chip_smoke: --four-chips needs 4 devices, found "
              f"{len(devs)}", file=sys.stderr)
        return 1

    from jepsen_etcd_demo_tpu.models import CASRegister
    from jepsen_etcd_demo_tpu.sched import enable_persistent_cache

    cache_dir = enable_persistent_cache()
    cold = not (cache_dir and os.path.isdir(cache_dir)
                and os.listdir(cache_dir))
    print(json.dumps({"device": str(devs[0]), "kind": devs[0].device_kind,
                      "count": len(devs), "jax": jax.__version__,
                      "compile_cache": cache_dir, "cache_cold": cold}),
          flush=True)
    model = CASRegister()
    store = tempfile.mkdtemp(prefix="chip-smoke-")
    if args.four_chips:
        phases = [
            ("four_batch", lambda: phase_four_batch(corpus_encs()[0], model)),
            ("four_lattice", lambda: phase_four_lattice(wide_history(),
                                                        model)),
        ]
    else:
        corpus = {}

        def corpus_phase():
            corpus["encs"], corpus["hists"] = corpus_encs()
            return phase_corpus(corpus["encs"], model)

        def serve_phase():
            hists = corpus["hists"] if corpus else corpus_encs(64)[1]
            picks = list(range(CORPUS_MUTATE_EVERY - 1, 64,
                               CORPUS_MUTATE_EVERY)) + list(range(8))
            return phase_serve([hists[i] for i in picks], model, store)

        phases = [
            ("cli", lambda: phase_cli(store)),
            ("corpus", corpus_phase),
            ("split", lambda: phase_split(
                corpus_encs(n_ops=SPLIT_OPS)[0], model)),
            ("long", lambda: phase_long(long_encs(), model)),
            ("serve", serve_phase),
        ]
    failed = []
    for name, run in phases:
        t0 = time.perf_counter()
        try:
            rec = {"ok": True, **run()}
        except Exception as e:   # every phase runs; the exit code says
            rec = {"ok": False, "error": f"{type(e).__name__}: {e}"}
            failed.append(name)
        rec["phase_s"] = time.perf_counter() - t0
        _print_phase(name, rec)
    if failed:
        print(f"chip_smoke: phases failed: {failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
