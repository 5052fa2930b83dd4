"""Telemetry subsystem (jepsen_etcd_demo_tpu/obs/): span nesting and
serialization round-trip, metrics aggregation, compile/execute kernel
attribution, the capture stack, and the telemetry.jsonl / metrics.json
schema a fake_kv end-to-end run writes into its store dir."""

from __future__ import annotations

import json
import threading

import pytest

from jepsen_etcd_demo_tpu import obs
from jepsen_etcd_demo_tpu.obs.metrics import MetricsRegistry, read_metrics
from jepsen_etcd_demo_tpu.obs.trace import Tracer, read_jsonl


class TestTracer:
    def test_span_nesting_and_roundtrip(self, tmp_path):
        tr = Tracer()
        with tr.span("outer", phase="x") as outer:
            with tr.span("inner") as inner:
                tr.event("tick", n=1)
            outer.set(done=True)
        path = tmp_path / "telemetry.jsonl"
        tr.write(path)
        recs = read_jsonl(path)
        meta = recs[0]
        assert meta["kind"] == "meta" and meta["dropped"] == 0
        spans = {r["name"]: r for r in recs if r["kind"] == "span"}
        events = [r for r in recs if r["kind"] == "event"]
        # Parentage: inner under outer, outer a root.
        assert spans["outer"]["parent"] is None
        assert spans["inner"]["parent"] == spans["outer"]["id"]
        # The event is correlated to the INNER span (the enclosing one).
        assert events[0]["span"] == spans["inner"]["id"]
        assert events[0]["attrs"] == {"n": 1}
        # Monotonic-ns interval containment and post-hoc attrs.
        assert (spans["outer"]["t0_ns"] <= spans["inner"]["t0_ns"]
                <= spans["inner"]["t1_ns"] <= spans["outer"]["t1_ns"])
        assert spans["outer"]["attrs"] == {"phase": "x", "done": True}

    def test_error_status_and_reraise(self):
        tr = Tracer()
        with pytest.raises(ValueError):
            with tr.span("boom"):
                raise ValueError("x")
        (rec,) = tr.records()
        assert rec["status"] == "error"

    def test_thread_safety_and_unique_ids(self):
        tr = Tracer()

        def work(i):
            for _ in range(50):
                with tr.span(f"t{i}"):
                    tr.event("e")

        threads = [threading.Thread(target=work, args=(i,))
                   for i in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        recs = tr.records()
        ids = [r["id"] for r in recs]
        assert len(ids) == len(set(ids)) == 400
        # Spans opened on sibling threads must NOT nest under each other
        # (contextvars are per-thread): every span here is a root.
        assert all(r["parent"] is None for r in recs
                   if r["kind"] == "span")

    def test_record_cap_counts_drops(self):
        tr = Tracer(max_records=3)
        for _ in range(5):
            tr.event("e")
        recs = read_jsonl_text(tr.to_jsonl())
        assert recs[0]["dropped"] == 2
        assert sum(1 for r in recs if r["kind"] == "event") == 3

    def test_disabled_tracer_records_nothing(self):
        tr = Tracer(enabled=False)
        with tr.span("x") as sp:
            tr.event("e")
        assert sp.id is None and tr.records() == []


def read_jsonl_text(text: str) -> list[dict]:
    return [json.loads(line) for line in text.splitlines() if line.strip()]


class TestMetrics:
    def test_counter_gauge_histogram_aggregation(self, tmp_path):
        m = MetricsRegistry()
        m.counter("c").add()
        m.counter("c").add(2.5)
        for v in (3, -1, 7):
            m.gauge("g").set(v)
        for v in (1.0, 3.0):
            m.histogram("h").observe(v)
        snap = m.snapshot()
        assert snap["c"] == {"type": "counter", "value": 3.5}
        assert snap["g"] == {"type": "gauge", "last": 7.0, "min": -1.0,
                             "max": 7.0, "n": 3}
        # The pre-quantile consumer view is unchanged (ISSUE 8 keeps
        # snapshot() backward-compatible)...
        h = snap["h"]
        assert {k: h[k] for k in ("type", "count", "sum", "min", "max",
                                  "avg")} \
            == {"type": "histogram", "count": 2, "sum": 4.0,
                "min": 1.0, "max": 3.0, "avg": 2.0}
        # ...and the log-bucket sketch adds quantile estimates (~5%
        # relative error, clamped into [min, max]).
        assert 1.0 <= h["p50"] <= 1.1
        assert 2.85 <= h["p95"] <= 3.0 and 2.85 <= h["p99"] <= 3.0
        path = tmp_path / "metrics.json"
        m.write(path)
        assert read_metrics(path) == snap
        assert m.value("c") == 3.5 and m.value("g") == 7.0

    def test_type_conflict_raises(self):
        m = MetricsRegistry()
        m.counter("x")
        with pytest.raises(TypeError):
            m.gauge("x")

    def test_disabled_registry_is_noop(self):
        m = MetricsRegistry(enabled=False)
        m.counter("c").add(5)
        m.gauge("g").set(1)
        m.histogram("h").observe(1)
        assert m.snapshot() == {}


class TestCaptureStack:
    def test_get_tracer_outside_capture_is_noop(self):
        tr = obs.get_tracer()
        with tr.span("x"):
            pass
        assert tr.records() == []
        obs.get_metrics().counter("c").add()

    def test_capture_installs_and_writes(self, tmp_path):
        out = tmp_path / "run"
        with obs.capture(out) as cap:
            assert obs.get_tracer() is cap.tracer
            assert obs.get_metrics() is cap.metrics
            with obs.get_tracer().span("phase"):
                obs.get_metrics().counter("k").add(2)
        assert obs.get_tracer().enabled is False   # popped
        recs = read_jsonl(out / obs.TELEMETRY_FILE)
        assert any(r.get("name") == "phase" for r in recs)
        metrics = read_metrics(out / obs.METRICS_FILE)
        assert metrics["k"]["value"] == 2
        # The well-known phase keys are pre-registered at zero: never
        # absent, zeros permitted (the bench/e2e breakdown contract).
        for key in obs.PHASE_COUNTERS:
            assert key in metrics
        assert obs.PHASE_GAUGE in metrics

    def test_env_gate_disables(self, tmp_path, monkeypatch):
        monkeypatch.setenv("JEPSEN_TPU_TELEMETRY", "0")
        out = tmp_path / "run"
        with obs.capture(out) as cap:
            assert not cap.enabled
            obs.get_metrics().counter("c").add()
            with obs.get_tracer().span("x"):
                pass
        assert not (out / obs.TELEMETRY_FILE).exists()
        assert not (out / obs.METRICS_FILE).exists()

    def test_kernel_phases_zero_shape(self):
        # The unreachable-backend bench path: every timing field present
        # and zero, plus the active tuning-profile hash (ISSUE 4 — a
        # degraded record still states which profile it intended).
        phases = obs.kernel_phases(None)
        assert phases.pop("profile_hash") == obs.active_profile_hash()
        assert phases == {
            "compile_s": 0.0, "execute_s": 0.0, "encode_s": 0.0,
            "frontier_peak": 0, "flops": 0.0, "bytes": 0.0,
            "device_mem_peak": 0}


class TestKernelAttribution:
    def test_first_call_is_compile_rest_execute(self):
        calls = []
        fn = obs.instrument_kernel("k", lambda x: calls.append(x) or x)
        with obs.capture() as cap:
            assert fn(1) == 1 and fn(2) == 2 and fn(3) == 3
        snap = cap.metrics.snapshot()
        assert snap["wgl.compile_calls"]["value"] == 1
        assert snap["wgl.execute_calls"]["value"] == 2
        assert snap["wgl.compile_s"]["value"] >= 0
        assert snap["wgl.execute_s.k"]["count"] == 2
        assert calls == [1, 2, 3]

    def test_warm_kernel_under_fresh_capture_counts_as_execute(self):
        fn = obs.instrument_kernel("k2", lambda: None)
        fn()   # warmed outside any capture: compile not attributed
        with obs.capture() as cap:
            fn()
        snap = cap.metrics.snapshot()
        assert snap.get("wgl.compile_calls", {"value": 0})["value"] == 0
        assert snap["wgl.execute_calls"]["value"] == 1
        assert snap["wgl.compile_s"]["value"] == 0   # pre-registered zero


class TestEndToEndArtifacts:
    @pytest.fixture(scope="class")
    def run_dir(self, tmp_path_factory):
        """One hermetic fake_kv CLI run, shared by the schema assertions."""
        from jepsen_etcd_demo_tpu.cli.main import main
        from jepsen_etcd_demo_tpu.store import Store

        tmp = tmp_path_factory.mktemp("obs_e2e")
        store = str(tmp / "store")
        rc = main(["test", "-w", "register", "--fake", "--time-limit",
                   "1.5", "--rate", "150", "--recovery-wait", "0.2",
                   "--store", store, "--seed", "11"])
        assert rc == 0
        return Store(store).runs()[0].path

    def test_run_writes_both_artifacts(self, run_dir):
        assert (run_dir / obs.TELEMETRY_FILE).exists()
        assert (run_dir / obs.METRICS_FILE).exists()

    def test_phase_spans_distinct_and_nested(self, run_dir):
        recs = read_jsonl(run_dir / obs.TELEMETRY_FILE)
        spans = [r for r in recs if r["kind"] == "span"]
        names = {s["name"] for s in spans}
        # The acceptance contract: distinct spans for the run phases.
        assert {"setup", "run", "check", "store"} <= names
        # The checker spans nest under the check phase. (A fully-settled
        # batched pre-pass emits check.linearizable.batched; keys that
        # re-run the single path emit check.linearizable.)
        check = next(s for s in spans if s["name"] == "check")
        lin = [s for s in spans
               if s["name"].startswith("check.linearizable")]
        assert lin and all(s["parent"] == check["id"] for s in lin)
        # Phases are disjoint in time and ordered.
        by = {n: next(s for s in spans if s["name"] == n)
              for n in ("setup", "run", "check", "store")}
        assert (by["setup"]["t1_ns"] <= by["run"]["t0_ns"]
                <= by["run"]["t1_ns"] <= by["check"]["t0_ns"]
                <= by["check"]["t1_ns"] <= by["store"]["t0_ns"])

    def test_metrics_schema_compile_vs_execute(self, run_dir):
        metrics = read_metrics(run_dir / obs.METRICS_FILE)
        # Separate compile-vs-execute keys, always present...
        assert metrics["wgl.compile_s"]["type"] == "counter"
        assert metrics["wgl.execute_s"]["type"] == "counter"
        # ...and the run really exercised a WGL kernel (whichever phase
        # it landed in given warm jit caches from earlier tests).
        assert (metrics["wgl.compile_s"]["value"]
                + metrics["wgl.execute_s"]["value"]) > 0
        assert metrics["encode.encode_s"]["value"] > 0
        assert metrics["wgl.frontier_peak"]["max"] >= 1
        assert metrics["runner.ops_ok"]["value"] > 0
        assert metrics["runner.op_latency_s"]["count"] > 0

    def test_kernel_phases_from_run_metrics(self, run_dir):
        reg = MetricsRegistry()
        for name, rec in read_metrics(run_dir / obs.METRICS_FILE).items():
            if rec["type"] == "counter":
                reg.counter(name).add(rec["value"])
            elif rec["type"] == "gauge" and rec["max"] is not None:
                reg.gauge(name).set(rec["max"])
        phases = obs.kernel_phases(reg)
        assert set(phases) == {"compile_s", "execute_s", "encode_s",
                               "frontier_peak", "flops", "bytes",
                               "device_mem_peak", "profile_hash"}
        assert phases["frontier_peak"] >= 1

    def test_telemetry_disabled_run_writes_no_artifacts(self, tmp_path,
                                                        monkeypatch):
        from jepsen_etcd_demo_tpu.cli.main import main
        from jepsen_etcd_demo_tpu.store import Store

        monkeypatch.setenv("JEPSEN_TPU_TELEMETRY", "0")
        store = str(tmp_path / "store")
        assert main(["test", "-w", "register", "--fake", "--time-limit",
                     "1.0", "--rate", "150", "--recovery-wait", "0.2",
                     "--store", store, "--seed", "12"]) == 0
        run = Store(store).runs()[0].path
        assert not (run / obs.TELEMETRY_FILE).exists()
        assert not (run / obs.METRICS_FILE).exists()


def test_chip_smoke_refuses_the_cpu_backend(capsys):
    """chip_smoke.main() on the CPU backend exits non-zero before any
    phase runs and prints no result line."""
    import chip_smoke

    assert chip_smoke.main([]) != 0
    assert capsys.readouterr().out.strip() == ""


def test_chip_smoke_alone_fails_without_result(tmp_path):
    """In a directory that holds chip_smoke.py and nothing else of the
    repo, the script fails and prints no result line."""
    import shutil
    import subprocess
    import sys
    from pathlib import Path

    src = Path(__file__).resolve().parent.parent / "chip_smoke.py"
    shutil.copy(src, tmp_path / "chip_smoke.py")
    out = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout

