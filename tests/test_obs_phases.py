"""Host phases (obs.phase), the garbage-collection hook and the compile
counters: what a capture records, and that nothing is recorded without
one."""

from __future__ import annotations

import gc
import subprocess
import sys
import time
from contextlib import nullcontext

import pytest

from jepsen_etcd_demo_tpu import obs
from jepsen_etcd_demo_tpu.obs import read_jsonl


def _value(cap, name):
    return cap.metrics.snapshot()[name]["value"]


def test_phase_annotation_lands_on_the_host_plane(tmp_path):
    """A phase is an annotation on the profiler's host plane, on the
    same clock as the benchmark's own window span, and the benchmark's
    trace reader reads it back inside that window."""
    import jax

    from benchmark import trace

    with obs.capture():
        jax.profiler.start_trace(str(tmp_path))
        try:
            with jax.profiler.TraceAnnotation(trace.WINDOW_SPAN):
                time.sleep(0.002)
                with obs.phase("check.split"):
                    time.sleep(0.005)
                time.sleep(0.002)
        finally:
            jax.profiler.stop_trace()
    events = list(trace.host_events(trace.from_profile(str(tmp_path))))
    window = [(s, e) for n, s, e in events if n == trace.WINDOW_SPAN]
    split = [(s, e) for n, s, e in events if n == "check.split"]
    assert len(window) == 1 and len(split) == 1
    (ws, we), (ps, pe) = window[0], split[0]
    assert ws < ps < pe < we
    assert pe - ps >= 4_000_000


def test_nested_phases_count_the_outer_only(tmp_path):
    with obs.capture(tmp_path) as cap:
        with obs.phase("sched.pack"):
            time.sleep(0.01)
            with obs.phase("sched.dispatch"):
                time.sleep(0.01)
        with obs.phase("sched.dispatch"):
            pass
    assert _value(cap, "sched.pack_s") >= 0.02
    assert 0 <= _value(cap, "sched.dispatch_s") < 0.01
    # Both still write their span records, the inner one nested.
    spans = {r["name"]: r for r in read_jsonl(tmp_path / obs.TELEMETRY_FILE)
             if r["kind"] == "span"}
    assert spans["sched.dispatch"]["parent"] in (None,
                                                 spans["sched.pack"]["id"])
    assert {"sched.pack", "sched.dispatch"} <= set(spans)


def test_every_phase_counter_is_preregistered():
    with obs.capture() as cap:
        snap = cap.metrics.snapshot()
    assert len(obs.PHASES) == len(obs.PHASE_SECONDS)
    for name in obs.PHASE_SECONDS + obs.COMPILE_COUNTERS + (obs.GC_COUNTER,):
        assert snap[name] == {"type": "counter", "value": 0.0}


def test_phase_outside_a_capture_records_nothing_and_imports_no_jax():
    code = (
        "import sys\n"
        "from jepsen_etcd_demo_tpu import obs\n"
        "with obs.phase('check.encode'):\n"
        "    pass\n"
        "assert obs.get_metrics().snapshot() == {}\n"
        "assert obs.get_tracer().records() == []\n"
        "assert 'jax' not in sys.modules, 'phase imported jax'\n")
    subprocess.run([sys.executable, "-c", code], check=True, timeout=120)


@pytest.mark.parametrize("captured", [False, True])
def test_unknown_phase_raises(captured):
    with obs.capture() if captured else nullcontext():
        with pytest.raises(ValueError, match="unknown phase"):
            with obs.phase("check.split_by_key"):
                pass


def test_gc_hook_is_installed_for_the_capture_and_counts():
    """The hook is installed while a capture is open; a forced
    collection inside a phase reaches gc.collect_s at that phase's end,
    and one between phases (the caller's own work) is not counted."""
    assert obs._on_gc not in gc.callbacks
    with obs.capture() as cap:
        assert obs._on_gc in gc.callbacks
        gc.collect()
        assert _value(cap, obs.GC_COUNTER) == 0
        with obs.phase("check.release"):
            junk = [[i] for i in range(20_000)]
            del junk
            gc.collect()
        assert _value(cap, obs.GC_COUNTER) > 0
        gc.collect()
    assert obs._on_gc not in gc.callbacks
    assert not cap.gc_pending
    before = _value(cap, obs.GC_COUNTER)
    gc.collect()
    assert _value(cap, obs.GC_COUNTER) == before


def test_first_kernel_call_under_a_capture_lowers_once():
    """instrument_kernel lowers a jitted kernel ahead of its first call
    under a capture, and the call reuses that lowering: one lowering in
    all, and the result is the call's. Outside a capture the call lowers
    on its own."""
    import jax
    import jax.numpy as jnp

    class Kernel:
        def __init__(self):
            self.jitted = jax.jit(lambda x: x * 7 - 3)
            self.lowered_ahead = 0

        def lower(self, *args):
            self.lowered_ahead += 1
            return self.jitted.lower(*args)

        def __call__(self, *args):
            return self.jitted(*args)

    lowered = []

    def on(event, duration, **_):
        if event == "/jax/core/compile/jaxpr_to_mlir_module_duration":
            lowered.append(duration)

    x = jnp.arange(11, dtype=jnp.int32)
    inside, outside = Kernel(), Kernel()
    jax.monitoring.register_event_duration_secs_listener(on)
    try:
        with obs.capture() as cap:
            fn = obs.instrument_kernel("obs-phases-lower-ahead", inside)
            out = fn(x)
            fn(x)
        assert len(lowered) == 1
        obs.instrument_kernel("obs-phases-lower-ahead", outside)(x)
    finally:
        jax.monitoring.unregister_event_duration_listener(on)
    assert out.tolist() == [7 * i - 3 for i in range(11)]
    assert (inside.lowered_ahead, outside.lowered_ahead) == (1, 0)
    assert len(lowered) == 2
    assert _value(cap, "wgl.compile_calls") == 1
    assert _value(cap, "wgl.execute_calls") == 1


def test_compile_counters_count_under_a_capture_only():
    import jax
    import jax.numpy as jnp

    with obs.capture() as cap:
        jax.jit(lambda x: x * 3 + 1)(jnp.ones((7,), jnp.float32))
    for name in ("compile.trace_s", "compile.lower_s", "compile.backend_s"):
        assert _value(cap, name) > 0, name
    seen = {n: _value(cap, n) for n in obs.COMPILE_COUNTERS}
    jax.jit(lambda x: x * 5 - 2)(jnp.ones((9,), jnp.float32))
    assert {n: _value(cap, n) for n in obs.COMPILE_COUNTERS} == seen


@pytest.mark.parametrize("nested", [False, True], ids=["outermost", "nested"])
@pytest.mark.parametrize("name", obs.PHASES)
def test_phase_counter_advances_only_when_outermost(name, nested):
    """Each phase's <phase>_s counter takes the phase's seconds when it is
    the outermost open phase, and nothing when it opens inside another
    phase (whose counter takes them instead)."""
    outer = next(p for p in obs.PHASES if p != name)
    counter = obs.PHASE_SECONDS[obs.PHASES.index(name)]
    outer_counter = obs.PHASE_SECONDS[obs.PHASES.index(outer)]
    with obs.capture() as cap:
        with obs.phase(outer) if nested else nullcontext():
            with obs.phase(name):
                time.sleep(0.01)
    if nested:
        assert _value(cap, counter) == 0
        assert _value(cap, outer_counter) >= 0.01
    else:
        assert _value(cap, counter) >= 0.01
        assert all(_value(cap, c) == 0 for c in obs.PHASE_SECONDS
                   if c != counter)


# The counters the benchmark's per-layer metrics read (BENCHMARK.json,
# benchmark/run.py): each is in the capture after a check, zero or not.
BENCHMARK_COUNTERS = (obs.COMPILE_COUNTERS + obs.PHASE_SECONDS
                      + ("encode.encode_s", "sched.steps_real",
                         "sched.steps_padded", "sched.shard_steps_max",
                         "sched.launches", obs.GC_COUNTER))

# What one small check of each kind certainly moves (the tests' platform
# has 8 CPU devices, so the keys of the independent check are sharded).
ADVANCES = {
    "independent": {"encode.encode_s", "check.encode_s", "sched.pack_s",
                    "sched.steps_real", "sched.steps_padded",
                    "sched.shard_steps_max", "sched.launches"},
    "linearizable": {"encode.encode_s", "check.encode_s"},
}


@pytest.fixture(scope="module")
def check_snapshots():
    import random

    from jepsen_etcd_demo_tpu.checkers.independent import IndependentChecker
    from jepsen_etcd_demo_tpu.checkers.linearizable import Linearizable
    from jepsen_etcd_demo_tpu.utils.fuzz import (gen_register_history,
                                                 interleave_keyed,
                                                 mutate_history)

    rng = random.Random(28)
    per_key = {k: gen_register_history(rng, n_ops=30, n_procs=3)
               for k in range(6)}
    per_key[2] = mutate_history(rng, per_key[2])
    one = gen_register_history(rng, n_ops=80, n_procs=4)
    snaps = {}
    with obs.capture() as cap:
        res = IndependentChecker(Linearizable(backend="jax")).check(
            {}, interleave_keyed(per_key))
    assert len(res["results"]) == 6
    snaps["independent"] = cap.metrics.snapshot()
    with obs.capture() as cap:
        assert Linearizable(backend="jax").check({}, one)["valid"] is True
    snaps["linearizable"] = cap.metrics.snapshot()
    return snaps


@pytest.mark.parametrize("counter", BENCHMARK_COUNTERS)
@pytest.mark.parametrize("checker", sorted(ADVANCES))
def test_benchmark_counter_is_in_the_capture(check_snapshots, checker,
                                             counter):
    rec = check_snapshots[checker].get(counter)
    assert rec is not None and rec["type"] == "counter", counter
    assert rec["value"] >= 0
    if counter in ADVANCES[checker]:
        assert rec["value"] > 0, (checker, counter)
