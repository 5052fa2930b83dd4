"""Checking-as-a-service tests (ISSUE 13): the continuous-batching
scheduler (coalescing, weighted-fair queuing, admission control, the
supervisor-driven degraded/wedged contract), the HTTP daemon (warm-pool
sharing across tenants, streaming sessions, store artifacts on the web
index), the subprocess end-to-end submit->verdict flow with verdicts
bit-identical to the analyze path, concurrent clients coalescing with
the analyze route's verdicts, and the rolling SLO window."""

from __future__ import annotations

import json
import random
import threading
import time
import urllib.error
import urllib.request

import pytest

from jepsen_etcd_demo_tpu import obs, sched
from jepsen_etcd_demo_tpu.models import CASRegister
from jepsen_etcd_demo_tpu.obs import health
from jepsen_etcd_demo_tpu.ops.encode import encode_register_history
from jepsen_etcd_demo_tpu.serve import (CoalescingScheduler, Rejected,
                                        ServeDaemon, SessionManager,
                                        make_serve_handler, op_from_dict)
from jepsen_etcd_demo_tpu.utils.fuzz import (gen_register_history,
                                             mutate_history)

MODEL = CASRegister()


def _hist(rng, n_ops=40, n_procs=4, invalid=False):
    h = gen_register_history(rng, n_ops=n_ops, n_procs=n_procs,
                             p_info=0.002)
    return mutate_history(rng, h) if invalid else h


def _enc(hist):
    return encode_register_history(hist, k_slots=8)


def _posthoc(enc):
    from jepsen_etcd_demo_tpu.ops import wgl3_pallas

    outs, _ = wgl3_pallas.check_batch_encoded_auto([enc], MODEL)
    return outs[0]


@pytest.fixture
def healthy_supervisor():
    """A fresh supervisor — serve tests must not inherit another
    test's degraded state."""
    fake = health.BackendSupervisor()
    prev = health.reset_supervisor(fake)
    try:
        yield fake
    finally:
        health.reset_supervisor(prev)


class TestCoalescingScheduler:
    def test_concurrent_tenants_coalesce_into_one_batch(
            self, rng, healthy_supervisor):
        encs = [_enc(_hist(rng)) for _ in range(8)]
        with obs.capture() as cap:
            s = CoalescingScheduler(coalesce_ms=150, max_batch=16)
            try:
                reqs = []

                def client(t, mine):
                    for e in mine:
                        reqs.append(s.submit(t, e,
                                             model_name="cas-register"))

                ts = [threading.Thread(target=client,
                                       args=(f"t{i}", encs[i::2]))
                      for i in range(2)]
                for t in ts:
                    t.start()
                for t in ts:
                    t.join()
                for r in reqs:
                    assert r.wait(120), "verdict timed out"
            finally:
                s.close()
        batches = {r.result["batch"]["id"] for r in reqs}
        assert len(batches) == 1, \
            f"2 tenants x 4 requests should share one launch: {batches}"
        assert all(r.result["batch"]["size"] == 8 for r in reqs)
        assert all(r.result["batch"]["coalesced"] for r in reqs)
        assert all(r.result["route"] == "jax" for r in reqs)
        stats = obs.serve_stats(cap.metrics)
        assert stats["requests"] == 8
        assert stats["batches"] == 1
        assert stats["coalesced_requests"] == 8
        assert stats["latency_p50_s"] > 0

    def test_verdicts_match_posthoc_analyze_route(self, rng,
                                                  healthy_supervisor):
        hists = [_hist(rng, invalid=(i % 3 == 2)) for i in range(6)]
        encs = [_enc(h) for h in hists]
        posthoc = [_posthoc(e) for e in encs]
        assert any(p["valid"] is not True for p in posthoc), \
            "fixture must include invalid histories"
        s = CoalescingScheduler(coalesce_ms=50, max_batch=16)
        try:
            reqs = [s.submit("t", e, model_name="cas-register")
                    for e in encs]
            for r in reqs:
                assert r.wait(120)
        finally:
            s.close()
        for req, post in zip(reqs, posthoc):
            assert req.result["valid"] == post["valid"]
            assert req.result["dead_step"] == int(post["dead_step"])

    def test_weighted_fair_queuing_light_tenant_not_starved(
            self, rng, healthy_supervisor):
        """A flooding tenant's backlog must not starve an interactive
        tenant: with a small batch cap, the light tenant's single
        request rides one of the first batches (round-robin gives every
        tenant a turn per drain), not the last."""
        flood = [_enc(_hist(rng)) for _ in range(12)]
        light = _enc(_hist(rng))
        s = CoalescingScheduler(coalesce_ms=200, max_batch=4)
        try:
            flood_reqs = [s.submit("flood", e,
                                   model_name="cas-register")
                          for e in flood]
            light_req = s.submit("light", light,
                                 model_name="cas-register")
            assert light_req.wait(120)
            for r in flood_reqs:
                assert r.wait(120)
        finally:
            s.close()
        light_batch = light_req.result["batch"]["id"]
        last_flood_batch = max(r.result["batch"]["id"]
                               for r in flood_reqs)
        assert light_batch < last_flood_batch, \
            (f"light tenant served in batch {light_batch}, after the "
             f"whole flood backlog (last flood batch "
             f"{last_flood_batch})")

    def test_admission_control_rejects_past_inflight_bound(
            self, rng, healthy_supervisor):
        s = CoalescingScheduler(coalesce_ms=300, max_batch=16,
                                max_inflight=2)
        with obs.capture() as cap:
            try:
                e = _enc(_hist(rng))
                r1 = s.submit("t", e, model_name="cas-register")
                r2 = s.submit("t", e, model_name="cas-register")
                with pytest.raises(Rejected) as exc:
                    s.submit("t", e, model_name="cas-register")
                assert exc.value.status == 429
                assert "in-flight bound" in exc.value.reason
                # A different tenant is NOT throttled by t's backlog.
                other = s.submit("u", e, model_name="cas-register")
                assert r1.wait(120) and r2.wait(120) and other.wait(120)
                # Verdicts drained -> the tenant is admittable again.
                r4 = s.submit("t", e, model_name="cas-register")
                assert r4.wait(120)
            finally:
                s.close()
        assert obs.serve_stats(cap.metrics)["rejected_inflight"] == 1

    def test_degraded_sheds_to_cpu_oracle_with_identical_verdicts(
            self, rng):
        fake = health.BackendSupervisor(
            fail_degraded=1,
            fail_wedged=3)
        prev = health.reset_supervisor(fake)
        try:
            fake.note_failure("synthetic wobble", source="test")
            assert fake.snapshot()["state"] == health.DEGRADED
            hists = [_hist(rng, invalid=(i == 1)) for i in range(4)]
            encs = [_enc(h) for h in hists]
            posthoc = [_posthoc(e) for e in encs]
            with obs.capture() as cap:
                s = CoalescingScheduler(coalesce_ms=50, max_batch=16)
                try:
                    reqs = [s.submit("t", e, model_name="cas-register")
                            for e in encs]
                    for r in reqs:
                        assert r.wait(120)
                finally:
                    s.close()
            for req, post in zip(reqs, posthoc):
                assert req.result["route"] == "cpu-oracle"
                assert req.result["kernel"] == "cpu-oracle-shed"
                assert req.result["valid"] == post["valid"]
                assert req.result["dead_step"] == int(post["dead_step"])
            assert obs.serve_stats(cap.metrics)["shed_cpu"] == 4
        finally:
            health.reset_supervisor(prev)

    def test_wedged_rejects_503_then_drains_on_recovery(self, rng):
        fake = health.BackendSupervisor()
        prev = health.reset_supervisor(fake)
        try:
            with obs.capture() as cap:
                s = CoalescingScheduler(coalesce_ms=400, max_batch=16)
                try:
                    e = _enc(_hist(rng))
                    # Admitted while healthy; sits in the coalesce
                    # window when the backend wedges.
                    queued = s.submit("t", e, model_name="cas-register")
                    fake.note_failure("device hang", source="test",
                                      wedged=True)
                    assert fake.snapshot()["state"] == health.WEDGED
                    with pytest.raises(Rejected) as exc:
                        s.submit("t", e, model_name="cas-register")
                    assert exc.value.status == 503
                    assert exc.value.retry_after_s is not None
                    # The admitted request is parked, not dispatched
                    # onto the sick backend.
                    assert not queued.wait(0.8)
                    # Recovery: any success re-attaches; parked work
                    # drains.
                    fake.note_ok(source="test")
                    assert queued.wait(120), \
                        "admitted work must drain on recovery"
                    assert queued.result["valid"] is not None
                finally:
                    s.close()
            assert obs.serve_stats(cap.metrics)["rejected_wedged"] == 1
        finally:
            health.reset_supervisor(prev)

    def test_jax_dispatch_failure_falls_back_to_oracle(
            self, rng, healthy_supervisor, monkeypatch):
        """A dispatch crash on a believed-healthy backend must still
        produce verdicts (oracle fallback) and tell the supervisor."""
        def boom(*a, **k):
            raise RuntimeError("synthetic dispatch crash")

        monkeypatch.setattr(sched, "submit_corpus", boom)
        e = _enc(_hist(rng))
        post = _posthoc(e)
        s = CoalescingScheduler(coalesce_ms=20, max_batch=8)
        try:
            r = s.submit("t", e, model_name="cas-register")
            assert r.wait(120)
        finally:
            s.close()
        assert r.result["route"] == "cpu-oracle"
        assert r.result["valid"] == post["valid"]
        snap = healthy_supervisor.snapshot()
        assert snap["fail_total"] >= 1
        assert "synthetic dispatch crash" in snap["last_failure"]


class TestSubmitCorpusAsync:
    def test_submit_corpus_future_matches_sync(self, rng):
        encs = [_enc(_hist(rng)) for _ in range(6)]
        sync_results, sync_kernel, _ = sched.check_corpus(encs, MODEL)
        fut = sched.submit_corpus(encs, MODEL)
        results, kernel, stats = fut.result(timeout=120)
        assert results == sync_results
        assert kernel == sync_kernel
        assert stats["launches"] >= 1


def _start_daemon(tmp_path, **kw):
    from http.server import ThreadingHTTPServer

    daemon = ServeDaemon(store_root=str(tmp_path / "store"), **kw)
    httpd = ThreadingHTTPServer(
        ("127.0.0.1", 0),
        make_serve_handler(str(tmp_path / "store"), daemon))
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    return daemon, httpd, httpd.server_address[1]


def _post(port, path, body, timeout=120):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}",
        data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return r.status, json.loads(r.read().decode())


def _get(port, path, timeout=30):
    with urllib.request.urlopen(
            f"http://127.0.0.1:{port}{path}", timeout=timeout) as r:
        return r.status, r.read().decode()


def _op_dicts(hist):
    return [json.loads(op.to_json()) for op in hist]


class TestServeDaemonHTTP:
    def test_two_tenants_share_the_warm_kernel_pool(
            self, rng, tmp_path, healthy_supervisor):
        """The tier-1 smoke the ISSUE names: two tenants submit
        concurrently; a follow-up same-shape launch reuses the first's
        compiled kernel — cache_hit_rate > 0 across the exchange."""
        daemon, httpd, port = _start_daemon(tmp_path, coalesce_ms=100)
        try:
            with obs.capture():
                h1 = _hist(rng, n_ops=40)
                h2 = _hist(rng, n_ops=40)
                hits_before = sched.kernel_cache().stats()["hits"]
                out = [None, None]

                def client(i, h, tenant):
                    out[i] = _post(port, "/check",
                                   {"tenant": tenant,
                                    "history": _op_dicts(h)})

                ts = [threading.Thread(target=client,
                                       args=(i, h, f"tenant-{i}"))
                      for i, h in enumerate((h1, h2))]
                for t in ts:
                    t.start()
                for t in ts:
                    t.join()
                for status, body in out:
                    assert status == 200
                    assert body["valid"] is True
                    assert body["tenant"].startswith("tenant-")
                # A third same-shape submission must hit the LRU the
                # first exchange warmed.
                status, body = _post(
                    port, "/check",
                    {"tenant": "tenant-3",
                     "history": _op_dicts(_hist(rng, n_ops=40))})
                assert status == 200 and body["valid"] is True
                hits_after = sched.kernel_cache().stats()["hits"]
                assert hits_after > hits_before, \
                    "second tenant's launch must reuse compiled kernels"
                stats = daemon.scheduler.stats()
                assert stats["kernel_cache"]["hit_rate"] > 0
        finally:
            httpd.shutdown()
            httpd.server_close()
            daemon.close()

    def test_serve_stats_metrics_and_polling(self, rng, tmp_path,
                                             healthy_supervisor):
        daemon, httpd, port = _start_daemon(tmp_path, coalesce_ms=10)
        try:
            with obs.capture():
                h = _hist(rng, n_ops=30)
                # Async submit -> poll contract.
                status, body = _post(port, "/check",
                                     {"tenant": "t", "wait": False,
                                      "history": _op_dicts(h)})
                assert status == 202 and body["pending"]
                rid = body["request_id"]
                deadline = time.monotonic() + 120
                while time.monotonic() < deadline:
                    status, text = _get(port, f"/check/{rid}")
                    if status == 200:
                        break
                    time.sleep(0.05)
                verdict = json.loads(text)
                assert verdict["valid"] is True
                assert verdict["request_id"] == rid
                # /serve/stats + the /metrics serve families.
                status, text = _get(port, "/serve/stats")
                assert status == 200
                stats = json.loads(text)
                assert stats["scheduler"]["requests_done"] >= 1
                status, text = _get(port, "/metrics")
                assert status == 200
                assert "jepsen_tpu_serve_requests" in text
                assert "jepsen_tpu_serve_tenant_latency_seconds" in text
                assert 'tenant="t"' in text
        finally:
            httpd.shutdown()
            httpd.server_close()
            daemon.close()

    def test_streaming_session_verdict_matches_posthoc(
            self, rng, tmp_path, healthy_supervisor):
        daemon, httpd, port = _start_daemon(tmp_path)
        try:
            with obs.capture():
                hist = _hist(rng, n_ops=60)
                post = _posthoc(_enc(hist))
                status, sess = _post(port, "/serve/session",
                                     {"tenant": "t",
                                      "model": "cas-register"})
                assert status == 201
                ops = _op_dicts(hist)
                half = len(ops) // 2
                for chunk in (ops[:half], ops[half:]):
                    status, fed = _post(port, sess["ops"],
                                        {"ops": chunk})
                    assert status == 200
                assert fed["ops_fed"] == len(ops)
                status, verdict = _post(port, sess["close"], {})
                assert status == 200
                assert verdict["valid"] == post["valid"]
                assert verdict["streamed"] is True
                # Closed sessions are gone.
                with pytest.raises(urllib.error.HTTPError) as exc:
                    _post(port, sess["ops"], {"ops": []}, timeout=30)
                assert exc.value.code == 404
        finally:
            httpd.shutdown()
            httpd.server_close()
            daemon.close()

    def test_artifacts_land_in_store_and_render_on_index(
            self, rng, tmp_path, healthy_supervisor):
        from jepsen_etcd_demo_tpu.store import Store

        daemon, httpd, port = _start_daemon(tmp_path, coalesce_ms=10)
        try:
            with obs.capture():
                h = _hist(rng, n_ops=30)
                status, body = _post(port, "/check",
                                     {"tenant": "artisan",
                                      "history": _op_dicts(h)})
                assert status == 200
                # Artifacts write AFTER the waiter wakes (store I/O
                # must not ride request latency) — poll briefly.
                store = Store(str(tmp_path / "store"))
                deadline = time.monotonic() + 30
                runs = []
                while time.monotonic() < deadline:
                    runs = store.runs()
                    # telemetry.jsonl is the LAST artifact written —
                    # once it exists the run dir is complete.
                    if runs and (runs[0].path
                                 / "telemetry.jsonl").exists():
                        break
                    time.sleep(0.05)
                assert len(runs) == 1, \
                    "served verdict must land as a browsable store run"
                results = runs[0].read_results()
                assert results["check_mode"] == "serve"
                assert results["valid"] == body["valid"]
                assert results["serve"]["tenant"] == "artisan"
                assert (runs[0].path / "history.jsonl").exists()
                assert (runs[0].path / "telemetry.jsonl").exists()
                # The run index renders it like a CLI run: linked run
                # dir, serve check-mode column, tenant summary.
                status, html_text = _get(port, "/")
                assert status == 200
                assert "serve/" in html_text
                assert "tenant artisan" in html_text
                assert ">serve</td>" in html_text
        finally:
            httpd.shutdown()
            httpd.server_close()
            daemon.close()

    def test_webhook_delivers_verdict(self, rng, tmp_path,
                                      healthy_supervisor):
        """`POST /check` with a webhook: the verdict is POSTed back to
        the callback URL (the third ingestion answer mode next to wait
        and poll)."""
        from http.server import BaseHTTPRequestHandler, HTTPServer

        received = []
        got = threading.Event()

        class Hook(BaseHTTPRequestHandler):
            def do_POST(self):
                n = int(self.headers.get("Content-Length") or 0)
                received.append(json.loads(self.rfile.read(n).decode()))
                self.send_response(200)
                self.send_header("Content-Length", "0")
                self.end_headers()
                got.set()

            def log_message(self, *a):
                pass

        hook = HTTPServer(("127.0.0.1", 0), Hook)
        threading.Thread(target=hook.serve_forever, daemon=True).start()
        daemon, httpd, port = _start_daemon(tmp_path, coalesce_ms=10)
        try:
            with obs.capture() as cap:
                status, body = _post(
                    port, "/check",
                    {"tenant": "hooked",
                     "history": _op_dicts(_hist(rng, n_ops=24)),
                     "webhook": "http://127.0.0.1:"
                                f"{hook.server_address[1]}/verdict"})
                assert status == 200
                assert got.wait(60), "webhook never delivered"
                assert received[0]["valid"] == body["valid"]
                assert received[0]["request_id"] == body["request_id"]
                assert obs.serve_stats(cap.metrics)["webhooks"] == 1
        finally:
            hook.shutdown()
            hook.server_close()
            httpd.shutdown()
            httpd.server_close()
            daemon.close()

    def test_session_manager_wedged_rejects_503(self, rng):
        fake = health.BackendSupervisor()
        prev = health.reset_supervisor(fake)
        try:
            fake.note_failure("hang", source="test", wedged=True)
            mgr = SessionManager(max_per_tenant=4)
            with pytest.raises(Rejected) as exc:
                mgr.open("t", MODEL, "cas-register")
            assert exc.value.status == 503
            assert exc.value.retry_after_s is not None
        finally:
            health.reset_supervisor(prev)


class TestSubprocessEndToEnd:
    def test_daemon_submit_verdict_matches_analyze(self, rng, tmp_path):
        """The ISSUE's integration test: a real `jepsen-tpu serve
        --check` subprocess on an ephemeral port, two tenants submitting
        concurrently over HTTP, every verdict bit-identical to the
        post-hoc analyze path on the same histories."""
        import os
        import subprocess
        import sys

        from jepsen_etcd_demo_tpu.checkers import Linearizable

        hists = [_hist(rng, n_ops=40, invalid=(i % 2 == 1))
                 for i in range(4)]
        env = dict(os.environ, JAX_PLATFORMS="cpu",
                   PYTHONPATH=os.getcwd())
        proc = subprocess.Popen(
            [sys.executable, "-m", "jepsen_etcd_demo_tpu.cli.main",
             "serve", "--check", "--port", "0",
             "--store", str(tmp_path / "store")],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
            text=True)
        try:
            line = proc.stdout.readline()
            ready = json.loads(line)
            port = ready["port"]
            assert ready["check"] is True
            verdicts = [None] * len(hists)

            def client(tenant_i):
                for idx in range(tenant_i, len(hists), 2):
                    status, body = _post(
                        port, "/check",
                        {"tenant": f"tenant-{tenant_i}",
                         "history": _op_dicts(hists[idx])},
                        timeout=300)
                    assert status == 200
                    verdicts[idx] = body

            ts = [threading.Thread(target=client, args=(i,))
                  for i in range(2)]
            for t in ts:
                t.start()
            for t in ts:
                t.join(300)
            lin = Linearizable(model="cas-register")
            for hist, served in zip(hists, verdicts):
                assert served is not None, "client thread died"
                analyzed = lin.check({}, hist, {})
                assert served["valid"] == analyzed["valid"]
                if "dead_step" in analyzed:
                    assert served["dead_step"] == \
                        int(analyzed["dead_step"])
            # Served checks are browsable history in the daemon's store.
            status, text = _get(port, "/serve/stats")
            assert status == 200
            assert json.loads(text)["scheduler"]["requests_done"] == 4
        finally:
            proc.terminate()
            proc.wait(timeout=30)


class TestBenchServeLane:
    def test_lane_contract_tiny_scale(self, healthy_supervisor):
        """Four closed-loop clients against one coalescing scheduler:
        every served verdict equals the analyze route's on the same
        encoded history (invalid ones included), concurrent requests
        coalesce into shared launches, and the latencies are ordered."""
        rng = random.Random(0x5E12E)
        encs = [_enc(_hist(rng, n_ops=rng.randrange(10, 48),
                           invalid=(i % 5 == 4))) for i in range(16)]
        posthoc = [_posthoc(e) for e in encs]
        server = CoalescingScheduler(coalesce_ms=5)
        served: list = [None] * len(encs)
        errors: list = []

        def client(c: int):
            try:
                for i in range(c, len(encs), 4):
                    req = server.submit(f"tenant-{c}", encs[i],
                                        model_name=MODEL.name)
                    assert req.wait(300), "serve verdict timed out"
                    served[i] = req.result
            except Exception as e:
                errors.append(e)

        try:
            threads = [threading.Thread(target=client, args=(c,))
                       for c in range(4)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(300)
            stats = server.stats()
        finally:
            server.close()
        assert not errors, errors
        for srv, post in zip(served, posthoc):
            assert srv["valid"] == post["valid"]
            assert srv["dead_step"] == int(post["dead_step"])
        assert sum(1 for p in posthoc if p["valid"] is not True) > 0, \
            "parity fixture must include invalid histories"
        assert stats["coalesced_requests"] > 0, \
            "concurrent clients must have coalesced"
        assert 0 < stats["batch_fill_avg"] <= 1.0
        lats = sorted(r["latency_s"] for r in served)
        assert lats[-1] >= lats[len(lats) // 2] > 0

    def test_serve_stats_zero_contract(self):
        stats = obs.serve_stats(None)
        assert stats == {
            "requests": 0, "batches": 0, "coalesced_requests": 0,
            "shed_cpu": 0, "rejected_inflight": 0,
            "rejected_wedged": 0, "webhooks": 0, "queue_depth": 0,
            "batch_fill": 0.0, "latency_p50_s": 0.0,
            "latency_p99_s": 0.0}


class TestRollingWindow:
    def test_quantiles_and_pruning(self):
        from jepsen_etcd_demo_tpu.serve.scheduler import RollingWindow

        w = RollingWindow(window_s=10.0)
        for i in range(100):
            w.observe((i + 1) / 100.0, now=100.0)
        p50, p99 = w.quantiles(now=100.0)
        assert p50 == pytest.approx(0.5, abs=0.02)
        assert p99 == pytest.approx(0.99, abs=0.02)
        # Outside the window everything is pruned.
        assert w.values(now=200.0) == []

    def test_burn_rate_is_breach_share_over_budget(self, monkeypatch):
        from jepsen_etcd_demo_tpu.serve.scheduler import (RollingWindow,
                                                          slo_target_s)

        monkeypatch.setenv("JEPSEN_TPU_SERVE_SLO_P99_S", "1.0")
        monkeypatch.setenv("JEPSEN_TPU_SERVE_SLO_BUDGET", "0.01")
        w = RollingWindow(window_s=60.0)
        for v in [0.5] * 98 + [2.0] * 2:
            w.observe(v, now=10.0)
        # 2% of requests breach a 1% budget -> burning 2x.
        assert w.burn_rate(now=10.0) == pytest.approx(2.0)
        assert slo_target_s() == pytest.approx(1.0)


class TestOpFromDict:
    def test_round_trips_history_jsonl_shape(self):
        op = op_from_dict({"type": "invoke", "f": "cas",
                           "value": [1, 2], "process": 3, "time": 9})
        assert op.value == (1, 2) and op.process == 3
        with pytest.raises(ValueError):
            op_from_dict({"value": 1})


class TestClientDrivenBounds:
    def test_oversized_body_rejected_400(self, tmp_path,
                                         healthy_supervisor):
        daemon, httpd, port = _start_daemon(tmp_path)
        try:
            req = urllib.request.Request(
                f"http://127.0.0.1:{port}/check", data=b"{}",
                headers={"Content-Type": "application/json",
                         "Content-Length": str((64 << 20) + 1)})
            with pytest.raises(urllib.error.HTTPError) as exc:
                urllib.request.urlopen(req, timeout=30)
            assert exc.value.code == 400
        finally:
            httpd.shutdown()
            httpd.server_close()
            daemon.close()

    def test_feed_racing_close_answers_409_not_silent_accept(
            self, rng, healthy_supervisor):
        sess = SessionManager(max_per_tenant=4).open(
            "t", MODEL, "cas-register")
        ops = [op for op in _hist(rng, n_ops=12)]
        sess.feed(ops[:4])
        sess.close()
        with pytest.raises(Rejected) as exc:
            sess.feed(ops[4:])
        assert exc.value.status == 409
