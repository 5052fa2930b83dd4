"""Live observability plane (ISSUE 8): Prometheus exposition golden
output, subscription-bus ordering under concurrent writers, backend
health state-machine transitions from passive signals, /metrics /healthz
/live(+SSE) endpoint smoke on the web harness, and the kernel_phases
flops/bytes contract on the CPU path."""

from __future__ import annotations

import http.client
import json
import threading
import time
import urllib.error
import urllib.request
from http.server import ThreadingHTTPServer

import pytest

from jepsen_etcd_demo_tpu import obs
from jepsen_etcd_demo_tpu.obs import export, health
from jepsen_etcd_demo_tpu.obs.metrics import MetricsRegistry


class TestPrometheusRendering:
    def test_exposition_golden_lines(self):
        reg = MetricsRegistry()
        reg.counter("encode.event_bytes").add(48)
        reg.gauge("wgl.frontier_peak").set(7)
        for v in (0.01, 0.02, 0.04):
            reg.histogram("runner.op_latency_s").observe(v)
        reg.histogram("wgl.compile_s.wgl3-chunk").observe(0.5)
        text = export.render_prometheus(reg.snapshot())
        lines = text.splitlines()
        # Counters/gauges under stable jepsen_tpu_* names, typed.
        assert "# TYPE jepsen_tpu_encode_event_bytes counter" in lines
        assert "jepsen_tpu_encode_event_bytes 48" in lines
        assert "jepsen_tpu_wgl_frontier_peak 7" in lines
        # Histograms export as summaries with the sketch quantiles.
        assert "# TYPE jepsen_tpu_runner_op_latency_s summary" in lines
        assert any(l.startswith('jepsen_tpu_runner_op_latency_s'
                                '{quantile="0.95"} ') for l in lines)
        assert "jepsen_tpu_runner_op_latency_s_count 3" in lines
        # The per-kernel family folds into ONE name + a kernel label
        # (the JTL107 boundedness contract, export.LABELED_FAMILIES) —
        # under a `_by_kernel` suffix so it can never collide with the
        # plain wgl.compile_s counter (one name, two types is an
        # invalid exposition).
        assert any(l.startswith('jepsen_tpu_wgl_compile_s_by_kernel'
                                '{kernel="wgl3-chunk",quantile="0.5"} ')
                   for l in lines)
        assert ('jepsen_tpu_wgl_compile_s_by_kernel_count'
                '{kernel="wgl3-chunk"} 1') in lines
        # Output is stable: same registry renders byte-identical text.
        assert text == export.render_prometheus(reg.snapshot())

    def test_name_and_label_sanitization(self):
        assert export.sanitize_metric_name("1bad.name-x") == "_1bad_name_x"
        assert export.sanitize_metric_name("a.b_c") == "a_b_c"
        assert export.sanitize_label_value('we"ird\nname') \
            == 'we\\"ird\\nname'
        reg = MetricsRegistry()
        reg.counter("weird-chars@here.s").add(1)
        text = export.render_prometheus(reg.snapshot())
        assert "jepsen_tpu_weird_chars_here_s 1" in text

    def test_never_set_gauge_renders_zero(self):
        # Pre-registered contract keys stay visible at zero (never
        # absent from a scrape either).
        reg = MetricsRegistry()
        reg.gauge("stream.overlap_ratio")
        assert "jepsen_tpu_stream_overlap_ratio 0" \
            in export.render_prometheus(reg.snapshot())

    def test_plain_and_labeled_families_never_collide(self):
        """The wgl.compile_s counter and wgl.compile_s.<kernel>
        histograms must export as DISTINCT families — a repeated family
        name (or two types under one name) invalidates the whole
        scrape."""
        reg = MetricsRegistry()
        reg.counter("wgl.compile_s").add(1.5)
        reg.histogram("wgl.compile_s.wgl3-chunk").observe(1.5)
        text = export.render_prometheus(reg.snapshot())
        type_lines = [l for l in text.splitlines()
                      if l.startswith("# TYPE ")]
        assert len(type_lines) == len(set(type_lines)) == 2
        assert "# TYPE jepsen_tpu_wgl_compile_s counter" in type_lines
        assert "# TYPE jepsen_tpu_wgl_compile_s_by_kernel summary" \
            in type_lines


class TestSubscriptionBus:
    def test_trace_records_ordered_under_concurrent_writers(self):
        n_threads, per_thread = 4, 200
        with obs.capture():
            sub = obs.subscribe(kinds={"event"},
                                maxsize=n_threads * per_thread + 16)
            try:
                tracer = obs.get_tracer()

                def writer(t):
                    for j in range(per_thread):
                        tracer.event("bus.test", t=t, j=j)

                threads = [threading.Thread(target=writer, args=(t,))
                           for t in range(n_threads)]
                for th in threads:
                    th.start()
                for th in threads:
                    th.join()
                got = []
                while len(got) < n_threads * per_thread:
                    rec = sub.get(timeout=2.0)
                    assert rec is not None, \
                        f"bus lost records: {len(got)} of " \
                        f"{n_threads * per_thread}"
                    if rec["attrs"].get("t") is not None:
                        got.append(rec)
            finally:
                sub.close()
        assert sub.dropped == 0
        # Per-writer order is preserved exactly (records publish under
        # the tracer lock, so the stream IS the append order).
        seen: dict[int, int] = {}
        for rec in got:
            t, j = rec["attrs"]["t"], rec["attrs"]["j"]
            assert j == seen.get(t, -1) + 1, f"writer {t} reordered"
            seen[t] = j
        assert all(v == per_thread - 1 for v in seen.values())

    def test_slow_consumer_drops_instead_of_backpressuring(self):
        with obs.capture():
            sub = obs.subscribe(kinds={"event"}, maxsize=4)
            try:
                for i in range(32):
                    obs.get_tracer().event("flood", i=i)
            finally:
                sub.close()
        assert sub.dropped > 0   # bounded queue, harness never blocked

    def test_metric_pump_streams_updated_instruments(self):
        with obs.capture():
            sub = obs.subscribe(kinds={"metric"})
            try:
                obs.get_metrics().counter("pump.test_metric").add(3)
                deadline = time.monotonic() + 5.0
                names = set()
                while time.monotonic() < deadline:
                    rec = sub.get(timeout=0.5)
                    if rec is None:
                        continue
                    names.add(rec["name"])
                    if "pump.test_metric" in names:
                        break
                assert "pump.test_metric" in names
                assert rec["metric"]["value"] == 3
            finally:
                sub.close()

    def test_kind_filter(self):
        with obs.capture():
            sub = obs.subscribe(kinds={"span"})
            try:
                obs.get_tracer().event("not.delivered")
                with obs.get_tracer().span("delivered"):
                    pass
                rec = sub.get(timeout=2.0)
                assert rec is not None and rec["kind"] == "span"
                assert rec["name"] == "delivered"
            finally:
                sub.close()


class TestHealthStateMachine:
    def test_consecutive_failures_walk_degraded_then_wedged(self):
        sup = health.BackendSupervisor(fail_degraded=1, fail_wedged=3)
        assert sup.state == health.HEALTHY
        sup.note_failure("err A", source="test")
        assert sup.state == health.DEGRADED
        snap = sup.snapshot()
        assert snap["last_transition"]["from"] == "healthy"
        assert snap["last_transition"]["to"] == "degraded"
        assert "err A" in snap["last_transition"]["reason"]
        assert snap["last_transition"]["source"] == "test"
        sup.note_failure("err B", source="test")
        assert sup.state == health.DEGRADED   # 2 < fail_wedged
        sup.note_failure("err C", source="test")
        assert sup.state == health.WEDGED
        assert sup.snapshot()["consecutive_failures"] == 3

    def test_wedged_failure_escalates_straight_to_wedged_and_back(self):
        """A caller that saw the device hang (wedged=True) drives
        healthy -> wedged at once; any later success drives it back."""
        sup = health.BackendSupervisor()
        sup.note_failure("dispatch hung", source="test", wedged=True)
        assert sup.state == health.WEDGED
        lt = sup.snapshot()["last_transition"]
        assert lt["from"] == "healthy" and lt["to"] == "wedged"
        sup.note_ok(source="test")
        assert sup.state == health.HEALTHY
        lt = sup.snapshot()["last_transition"]
        assert lt["from"] == "wedged" and lt["to"] == "healthy"

    def test_success_resets_consecutive_failures(self):
        sup = health.BackendSupervisor(fail_degraded=2, fail_wedged=3)
        sup.note_failure("x")
        sup.note_ok()
        sup.note_failure("y")
        assert sup.state == health.HEALTHY   # streak broken in between
        assert sup.snapshot()["consecutive_failures"] == 1

    def test_transitions_recorded_as_obs_events_and_gauge(self):
        with obs.capture() as cap:
            sup = health.BackendSupervisor(fail_degraded=1, fail_wedged=2)
            sup.note_failure("boom", source="test")
            sup.note_ok(source="test")
        events = [r for r in cap.tracer.records()
                  if r["kind"] == "event" and r["name"] == "health.transition"]
        assert [e["attrs"]["to"] for e in events] == ["degraded", "healthy"]
        snap = cap.metrics.snapshot()
        assert snap["health.state"]["last"] == 0.0   # back to healthy
        assert snap["health.state"]["max"] == 1.0    # visited degraded

    def test_process_supervisor_swap(self):
        fake = health.BackendSupervisor()
        prev = health.reset_supervisor(fake)
        try:
            assert health.get_supervisor() is fake
        finally:
            health.reset_supervisor(prev)


@pytest.fixture()
def web_server(tmp_path):
    from jepsen_etcd_demo_tpu.web.server import make_handler

    prev = health.reset_supervisor()   # isolate from other tests' state
    srv = ThreadingHTTPServer(("127.0.0.1", 0),
                              make_handler(str(tmp_path / "store")))
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    try:
        yield f"http://127.0.0.1:{srv.server_address[1]}"
    finally:
        srv.shutdown()
        srv.server_close()
        health.reset_supervisor(prev)


class TestWebEndpoints:
    def test_metrics_endpoint_prometheus_text(self, web_server):
        with obs.capture():
            obs.get_metrics().counter("runner.ops_ok").add(5)
            obs.get_metrics().histogram("runner.op_latency_s").observe(0.02)
            resp = urllib.request.urlopen(web_server + "/metrics")
            body = resp.read().decode()
        assert resp.headers["Content-Type"].startswith("text/plain")
        assert "jepsen_tpu_runner_ops_ok 5" in body
        assert 'quantile="0.99"' in body
        assert "jepsen_tpu_health_state 0" in body
        assert "jepsen_tpu_run_in_flight 1" in body
        # Pre-registered contract keys visible at zero mid-run.
        assert "jepsen_tpu_wgl_compile_s 0" in body
        # A valid exposition: every family declared exactly once — in
        # particular health.state (pre-registered in the capture AND a
        # process series) must not render twice.
        type_lines = [l for l in body.splitlines()
                      if l.startswith("# TYPE ")]
        assert len(type_lines) == len(set(type_lines))
        assert body.count("# TYPE jepsen_tpu_health_state gauge") == 1
        assert len([l for l in body.splitlines()
                    if l.startswith("jepsen_tpu_health_state ")]) == 1

    def test_metrics_endpoint_outside_any_run(self, web_server):
        body = urllib.request.urlopen(web_server + "/metrics").read().decode()
        assert "jepsen_tpu_up 1" in body
        assert "jepsen_tpu_run_in_flight 0" in body

    def test_healthz_reports_state_with_provenance(self, web_server):
        hz = json.load(urllib.request.urlopen(web_server + "/healthz"))
        assert hz["status"] == "healthy" and hz["state"] == "healthy"
        assert hz["run_in_flight"] is False
        assert "thresholds" in hz and "last_transition" in hz
        # Drive the supervisor wedged: /healthz turns 503 and carries
        # the transition provenance.
        health.get_supervisor().note_failure(
            "dispatch hung", source="test", wedged=True)
        try:
            with pytest.raises(urllib.error.HTTPError) as e:
                urllib.request.urlopen(web_server + "/healthz")
            assert e.value.code == 503
            hz = json.load(e.value)
            assert hz["status"] == "wedged"
            assert hz["last_transition"]["to"] == "wedged"
            assert "dispatch hung" in hz["last_transition"]["reason"]
        finally:
            health.get_supervisor().note_ok(source="test")

    def test_live_page_and_sse_stream(self, web_server):
        host = web_server.split("//")[1]
        page = urllib.request.urlopen(web_server + "/live").read().decode()
        assert "EventSource" in page and "/live/events" in page
        with obs.capture():
            obs.get_metrics().counter("runner.ops_ok").add(2)
            with obs.get_tracer().span("run"):
                conn = http.client.HTTPConnection(host, timeout=10)
                try:
                    conn.request("GET", "/live/events")
                    resp = conn.getresponse()
                    assert resp.status == 200
                    assert resp.getheader("Content-Type") \
                        == "text/event-stream"
                    line = resp.fp.readline().decode()
                    assert line.startswith("event: init"), line
                    init = json.loads(resp.fp.readline().decode()[6:])
                    assert init["run_in_flight"] is True
                    assert init["health"]["state"] == "healthy"
                    assert init["metrics"]["runner.ops_ok"]["value"] == 2
                    # A record emitted NOW arrives over the live stream.
                    obs.get_tracer().event("fault.partition", node="n1")
                    got = None
                    deadline = time.monotonic() + 8.0
                    while time.monotonic() < deadline and got is None:
                        ln = resp.fp.readline().decode()
                        if ln.startswith("event: event"):
                            payload = json.loads(
                                resp.fp.readline().decode()[6:])
                            if payload.get("name") == "fault.partition":
                                got = payload
                    assert got is not None, "SSE never delivered the event"
                    assert got["attrs"] == {"node": "n1"}
                finally:
                    conn.close()


class TestKernelCostContract:
    def test_kernel_phases_flops_bytes_on_cpu(self):
        """The CPU-path contract: a fresh jitted kernel's first call
        under a capture lands nonzero flops/bytes in kernel_phases and
        a per-kernel gauge pair; every field JSON-serializable."""
        jax = pytest.importorskip("jax")
        import jax.numpy as jnp

        fn = obs.instrument_kernel(
            "obs-export-cost-test",
            jax.jit(lambda a, b: (a @ b).sum() + a.shape[0]))
        with obs.capture() as cap:
            x = jnp.ones((37, 41), jnp.float32)
            fn(x, x.T)
            fn(x, x.T)
        phases = obs.kernel_phases(cap.metrics)
        json.dumps(phases)
        assert phases["flops"] > 0
        assert phases["bytes"] > 0
        assert phases["device_mem_peak"] >= 0   # CPU may not report one
        snap = cap.metrics.snapshot()
        assert snap["wgl.kernel_flops.obs-export-cost-test"]["last"] > 0
        assert snap["wgl.kernel_bytes.obs-export-cost-test"]["last"] > 0
        # Compile/execute attribution is unchanged by the cost capture.
        assert snap["wgl.compile_calls"]["value"] == 1
        assert snap["wgl.execute_calls"]["value"] == 1

    def test_cost_capture_env_gate(self, monkeypatch):
        jax = pytest.importorskip("jax")
        import jax.numpy as jnp

        monkeypatch.setenv("JEPSEN_TPU_KERNEL_COST", "0")
        fn = obs.instrument_kernel(
            "obs-export-cost-gated", jax.jit(lambda a: a * 2))
        with obs.capture() as cap:
            fn(jnp.ones((8,)))
        phases = obs.kernel_phases(cap.metrics)
        assert phases["flops"] == 0.0 and phases["bytes"] == 0.0
        assert "wgl.kernel_flops.obs-export-cost-gated" \
            not in cap.metrics.snapshot()

    def test_non_jit_callable_is_harmless(self):
        fn = obs.instrument_kernel("obs-export-plain", lambda x: x + 1)
        with obs.capture() as cap:
            assert fn(1) == 2
        assert obs.kernel_phases(cap.metrics)["flops"] == 0.0


class TestTraceTruncationSurfacing:
    def test_dropped_records_metric_and_footer(self):
        with obs.capture() as cap:
            cap.tracer.max_records = 3
            for i in range(6):
                obs.get_tracer().event("spam", i=i)
        assert cap.metrics.snapshot()["trace.dropped_records"]["value"] == 3
        lines = cap.tracer.to_jsonl().strip().splitlines()
        meta = json.loads(lines[0])
        footer = json.loads(lines[-1])
        assert meta["dropped"] == 3
        assert footer == {"kind": "footer", "truncated": True,
                          "records": 3, "dropped": 3}

    def test_no_footer_when_nothing_dropped(self):
        with obs.capture() as cap:
            obs.get_tracer().event("one")
        kinds = [json.loads(l)["kind"]
                 for l in cap.tracer.to_jsonl().strip().splitlines()]
        assert "footer" not in kinds

    def test_telemetry_page_renders_truncation_warning(self, tmp_path,
                                                       web_server):
        # web_server serves tmp_path/store — plant a truncated artifact.
        run = tmp_path / "store" / "t" / "1"
        run.mkdir(parents=True)
        with obs.capture(run) as cap:
            cap.tracer.max_records = 2
            with obs.get_tracer().span("run"):
                for i in range(8):
                    obs.get_tracer().event("spam", i=i)
        body = urllib.request.urlopen(
            web_server + "/telemetry/t/1").read().decode()
        assert "TRUNCATED" in body
        assert "incomplete" in body