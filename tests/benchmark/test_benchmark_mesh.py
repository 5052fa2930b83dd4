"""The four-chip cell (register-indep-4chip): its data files, its two
mesh metrics on hand-made traces and counter snapshots, the roofline
read over four device planes, and its shape checked on four virtual
devices key for key against the plain reference."""

import json
import random
from pathlib import Path

import pytest
from _small import small_spec

from benchmark import load, reference, run
from benchmark.loops import Done, answer
from benchmark.gen import register_history
from benchmark.trace import reduce
from jepsen_etcd_demo_tpu import obs
from jepsen_etcd_demo_tpu.parallel import mesh as pmesh

ROOT = Path(__file__).resolve().parents[2]
CELL = "register-indep-4chip"
MS = 1_000_000
KERNEL = 'custom_call_target="tpu_custom_call"'


def test_the_cell_loads_as_the_one_chip_cell_scaled_by_four():
    spec = run.load_cell(CELL)
    one = run.load_cell("register-indep")
    assert spec["cell"]["chips"] == 4
    for key in ("generator", "corrupt_one_in", "guarantee", "model"):
        assert spec["cfg"][key] == one["cfg"][key], key
    assert spec["cfg"]["reduced"] == []
    assert spec["mix"] == dict(one["mix"], keys_per_unit=4096, pool=4096)
    e2e = {m["name"] for m in spec["end_to_end"]}
    assert e2e == {"events_per_s", "setup_s"}
    per_layer = {m["name"] for m in spec["per_layer"]}
    assert {"device_busy_imbalance.mesh", "shard_waste.mesh"} <= per_layer
    assert per_layer >= {m["name"] for m in one["per_layer"]}
    assert all(m["moves"] in e2e for m in spec["per_layer"])


def _plane(name, events):
    return {"name": name, "lines": [{"name": "XLA Ops", "events": events}]}


def _trace(busy_ms):
    """A 100 ms window, device d busy busy_ms[d] ms inside it in one
    kernel op."""
    host = {"name": "/host:CPU", "lines": [{"name": "python", "events": [
        ["bench.window", 0, 100 * MS]]}]}
    return {"planes": [host] + [
        _plane(f"/device:TPU:{d}", [[KERNEL, 0, b * MS]] if b else [])
        for d, b in enumerate(busy_ms)]}


@pytest.mark.parametrize("busy_ms,want", [
    ([40, 40, 40, 40], 1.0),
    ([80, 40, 40, 40], 80 / 50),
    ([40, 0, 0, 0], 4.0),
])
def test_device_busy_imbalance_over_four_planes(busy_ms, want):
    r = run.Run(trace=reduce(_trace(busy_ms)))
    assert len(r.trace["busy_s"]) == 4
    assert run.reader("device_busy_imbalance.mesh")(r) == pytest.approx(want)


@pytest.mark.parametrize("busy_ms", [[40], [0, 0, 0, 0]])
def test_device_busy_imbalance_reads_nothing_without_a_mesh(busy_ms):
    read = run.reader("device_busy_imbalance.mesh")
    assert read(run.Run(trace=reduce(_trace(busy_ms)))) is None
    assert read(run.Run(trace=None)) is None


def _snap(**values):
    return {name.replace("__", "."): {"type": "counter", "value": v}
            for name, v in values.items()}


def test_shard_waste_from_counter_snapshots():
    read = run.reader("shard_waste.mesh")
    before = _snap(sched__steps_real=100, sched__shard_steps_max=104,
                   sched__shard_launches=2)
    after = _snap(sched__steps_real=1100, sched__shard_steps_max=1204,
                  sched__shard_launches=5)
    assert read(run.Run(counters0=before, counters1=after)) == \
        pytest.approx(1100 / 1000)
    # No sharded launch in the window (one chip): nothing to read.
    flat = _snap(sched__steps_real=1100, sched__shard_steps_max=104)
    assert read(run.Run(counters0=before, counters1=flat)) is None
    # A program from before the counters existed: nothing to read.
    old = _snap(sched__steps_real=1100)
    assert read(run.Run(counters0=_snap(sched__steps_real=100),
                        counters1=old)) is None


class _Pool:
    """The traffic a roofline reading needs: units of `keys` keys drawn
    round-robin from a pool."""

    def __init__(self, pool, keys):
        self.pool, self.keys_per_unit = pool, keys

    def pool_of(self, i, k):
        return k % len(self.pool)


def _roofline(n_devices, keys, pool, ms):
    cfg = run.load_cell(CELL)["cfg"]
    done = [Done(0, 1.0, [(True, -1)] * keys)]
    r = run.Run(trace=reduce(_trace([ms] * n_devices)), traced=done,
                traffic=_Pool(pool, keys), cfg=cfg,
                peaks=small_spec(CELL)["peaks"])
    return run.reader("wgl3_pallas_roofline")(r)


def test_roofline_of_four_chips_equals_one_chips():
    """The pallas seconds of every device plane are summed against one
    chip's peak: four chips, each sweeping what one chip sweeps in the
    same time, read the one chip's share."""
    rng = random.Random(26)
    g = run.load_cell(CELL)["cfg"]["generator"]
    pool = [register_history(
        rng, n_ops=g["ops_per_key"], n_procs=g["threads_per_key"],
        value_range=g["value_range"], p_read=g["p_read"],
        p_write=g["p_write"], p_info=g["p_info"],
        p_fail_read=g["p_fail_read"]) for _ in range(8)]
    one = _roofline(1, 8, pool, 30)
    four = _roofline(4, 32, pool, 30)
    assert 0 < one < 100
    assert four == pytest.approx(one)


def test_four_device_sharded_check_matches_the_reference(monkeypatch):
    """A 64-key unit of the cell's traffic (the cell's generator; one
    key in 8 corrupted so that invalid keys are compared too) through
    IndependentChecker on a mesh of four virtual devices: the batch
    takes the sharded route, the shard counters count it, and every
    key's (valid, dead_step) equals the reference's."""
    from jepsen_etcd_demo_tpu.checkers.independent import IndependentChecker
    from jepsen_etcd_demo_tpu.checkers.linearizable import Linearizable
    from jepsen_etcd_demo_tpu.ops.op import Op

    monkeypatch.setenv(pmesh.MESH_SHAPE_ENV, "4")
    spec = run.load_cell(CELL)
    cfg = dict(spec["cfg"], corrupt_one_in=8)
    mix = dict(spec["mix"], keys_per_unit=64, pool=64)
    traffic = load.Traffic(cfg, mix, 2**33 + 26)
    keys = traffic.unit_keys(0)
    want = [reference.check(h) for h in keys]
    assert any(v[0] is False for v in want)
    ops = load.program_ops(keys, Op, keyed=True)
    with obs.capture():
        res = IndependentChecker(Linearizable(
            cfg["model"], backend="jax")).check({}, ops)["results"]
        counters = obs.get_metrics().snapshot()
    got = [answer(res[str(k)]) for k in range(len(keys))]
    assert got == want
    kernels = {res[str(k)].get("kernel") for k in range(len(keys))
               if want[k][0] is True}
    assert kernels and all("sharded" in k for k in kernels), kernels
    assert counters["sched.shard_launches"]["value"] >= 1
    real = counters["sched.steps_real"]["value"]
    waited = counters["sched.shard_steps_max"]["value"]
    assert real <= waited <= 4 * real


def test_bench_json_adds_the_cell_to_the_keyed_metrics():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    keyed = {"device_idle_pct.throughput", "wgl3_pallas_roofline",
             "padding_waste.indep", "encode_pct.throughput",
             "split_pct.throughput", "pack_pct.throughput",
             "dispatch_pct.throughput", "fetch_wait_pct.throughput",
             "witness_pct.throughput", "gc_pct.throughput",
             "untraced_pct.throughput", "compile_s.setup",
             "cache_misses.setup", "device_busy_imbalance.mesh",
             "shard_waste.mesh"}
    listed = {m["name"] for m in bench["per_layer"]
              if CELL in m.get("workloads", [])}
    assert listed == keyed
