"""Fleet-scale serving tests (ISSUE 18): the shape-affine router
(routing-key derivation drift-pinned to ops/wgl3.step_bucket,
rendezvous hashing's minimal-redistribution property, per-mode
candidate ordering, health-state transitions, bounded stickiness),
admission 429s carrying Retry-After, /healthz surfacing
warmup/readiness, and the subprocess end-to-end contract: a real
2-replica fleet behind the router HTTP surface with verdicts
bit-identical to the single-daemon and analyze routes, lossless
spillover through a mid-load replica kill, and a warm zero-downtime
restart."""

from __future__ import annotations

import json
import os
import threading
import urllib.error
import urllib.request
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import pytest

from jepsen_etcd_demo_tpu import obs
from jepsen_etcd_demo_tpu.models import CASRegister
from jepsen_etcd_demo_tpu.obs import health
from jepsen_etcd_demo_tpu.serve import (CoalescingScheduler, FleetRouter,
                                        FleetSupervisor, Rejected,
                                        make_fleet_handler,
                                        rendezvous_order, routing_key)
from jepsen_etcd_demo_tpu.serve.router import (AFFINE, DEGRADED, DOWN,
                                               RANDOM, READY, STICKY_CAP,
                                               STRICT, WEDGED, step_bucket)
from jepsen_etcd_demo_tpu.utils.fuzz import (gen_register_history,
                                             mutate_history)

MODEL = CASRegister()

#: Subprocess replicas must not grab a real accelerator (two processes
#: cannot share one TPU) and must start fast — the fleet tests measure
#: routing behaviour, not chip throughput.
_CHILD_ENV = {"JAX_PLATFORMS": "cpu", "JEPSEN_TPU_NO_WARMUP": "1",
              "JEPSEN_TPU_NO_COMPILE_CACHE": "1",
              "JEPSEN_TPU_TELEMETRY": "0"}


def _hist(rng, n_ops=32, n_procs=4, invalid=False):
    h = gen_register_history(rng, n_ops=n_ops, n_procs=n_procs,
                             p_info=0.002)
    return mutate_history(rng, h) if invalid else h


def _op_dicts(hist):
    return [json.loads(op.to_json()) for op in hist]


def _post_url(url, body, timeout=300):
    req = urllib.request.Request(
        url, data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"})
    try:
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            return resp.status, json.loads(resp.read().decode()), resp
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read().decode()), e


def _get_url(url, timeout=30):
    try:
        with urllib.request.urlopen(url, timeout=timeout) as resp:
            return resp.status, json.loads(resp.read().decode())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read().decode())


@pytest.fixture
def healthy_supervisor():
    fake = health.BackendSupervisor()
    prev = health.reset_supervisor(fake)
    try:
        yield fake
    finally:
        health.reset_supervisor(prev)


class TestRoutingKey:
    def test_step_bucket_parity_with_wgl3(self):
        """The router's jax-free bucket ladder must never drift from the
        scheduler's (ops/wgl3.step_bucket) — affinity only pays off when
        the router and the replica agree on the compiled geometry."""
        from jepsen_etcd_demo_tpu.ops import wgl3

        for floor in (8, 32, 64):
            for n in range(1, 400):
                assert step_bucket(n, floor) == \
                    wgl3.step_bucket(n, floor=floor), (n, floor)

    def test_key_counts_completions_excluding_nemesis(self):
        history = [
            {"type": "invoke", "f": "read", "process": 0},
            {"type": "ok", "f": "read", "process": 0},
            {"type": "fail", "f": "cas", "process": 1},
            {"type": "info", "f": "write", "process": 2},
            {"type": "ok", "f": "kill", "process": "nemesis"},
        ]
        # 3 completions (ok/fail/info), the nemesis op excluded, the
        # invoke excluded: bucket = step_bucket(3, floor).
        assert routing_key("cas-register", history, 32) == \
            f"cas-register|r{step_bucket(3, 32)}"

    def test_key_varies_with_model_and_bucket(self):
        small = [{"type": "ok", "process": 0}] * 4
        large = [{"type": "ok", "process": 0}] * 100
        assert routing_key("cas-register", small, 32) != \
            routing_key("mutex", small, 32)
        assert routing_key("cas-register", small, 32) != \
            routing_key("cas-register", large, 32)
        # Same bucket -> same key: affinity is per-shape, not per-history.
        assert routing_key("cas-register", small, 32) == \
            routing_key("cas-register", small[:2], 32)


class TestRendezvousHashing:
    def test_order_is_deterministic_and_membership_invariant(self):
        reps = ["r0", "r1", "r2", "r3"]
        for key in ("cas-register|r32", "mutex|r96"):
            a = rendezvous_order(key, reps, salt=0)
            b = rendezvous_order(key, list(reversed(reps)), salt=0)
            assert a == b
            assert sorted(a) == sorted(reps)

    def test_removal_redistributes_only_the_removed_replicas_keys(self):
        """The property the whole design leans on: dropping one replica
        re-deals ONLY its keys — every other shard's kernel LRU stays
        hot through the membership change."""
        reps = ["r0", "r1", "r2"]
        keys = [f"cas-register|r{step_bucket(n, 8)}|{i}"
                for i, n in enumerate(range(1, 200))]
        before = {k: rendezvous_order(k, reps, salt=0)[0] for k in keys}
        after = {k: rendezvous_order(k, ["r0", "r2"], salt=0)[0]
                 for k in keys}
        moved = [k for k in keys
                 if before[k] != "r1" and after[k] != before[k]]
        assert moved == []
        orphans = [k for k in keys if before[k] == "r1"]
        assert orphans, "fixture must exercise the removed replica"

    def test_salt_re_deals_the_ring(self):
        reps = ["r0", "r1", "r2"]
        keys = [f"k{i}" for i in range(64)]
        owners0 = [rendezvous_order(k, reps, salt=0)[0] for k in keys]
        owners1 = [rendezvous_order(k, reps, salt=1)[0] for k in keys]
        assert owners0 != owners1


class TestFleetRouterUnit:
    def _router(self, mode=AFFINE, n=3):
        r = FleetRouter(salt=0, spillover_mode=mode, bucket_floor=32,
                        poll_interval_s=3600.0)
        for i in range(n):
            r.add_replica(f"http://127.0.0.1:1{i:04d}", rid=f"r{i}",
                          state=READY)
        return r

    def _set_state(self, r, rid, state):
        with r._lock:
            r._replicas[rid].state = state

    def test_affine_candidates_follow_hrw_with_degraded_last(self):
        r = self._router()
        try:
            key = "cas-register|r48"
            order = rendezvous_order(key, ["r0", "r1", "r2"], salt=0)
            assert [c.id for c in r.candidates(key)] == order
            # Degrade the owner: it drops to the back (last resort),
            # the rest keep HRW order.
            self._set_state(r, order[0], DEGRADED)
            assert [c.id for c in r.candidates(key)] == \
                order[1:] + [order[0]]
            # Wedged/down replicas are drained out entirely.
            self._set_state(r, order[1], WEDGED)
            self._set_state(r, order[2], DOWN)
            assert [c.id for c in r.candidates(key)] == [order[0]]
        finally:
            r.close()

    def test_strict_mode_is_owner_or_nothing(self):
        r = self._router(mode=STRICT)
        try:
            key = "cas-register|r48"
            owner = rendezvous_order(key, ["r0", "r1", "r2"], salt=0)[0]
            assert [c.id for c in r.candidates(key)] == [owner]
            self._set_state(r, owner, WEDGED)
            assert r.candidates(key) == []
        finally:
            r.close()

    def test_random_mode_rotates_over_routable_replicas(self):
        r = self._router(mode=RANDOM)
        try:
            self._set_state(r, "r1", DOWN)
            firsts = {r.candidates("ignored")[0].id for _ in range(8)}
            assert firsts == {"r0", "r2"}, \
                "round-robin must touch every routable replica"
        finally:
            r.close()

    def test_forward_with_no_routable_replica_rejects_503(self):
        with obs.capture() as cap:
            r = FleetRouter(salt=0, spillover_mode=AFFINE,
                            bucket_floor=32, poll_interval_s=3600.0)
            try:
                status, body, rep = r.forward("POST", "/check", b"{}",
                                              "cas-register|r32")
            finally:
                r.close()
        assert status == 503 and rep is None
        assert json.loads(body.decode())["retry_after_s"] > 0
        stats = obs.fleet_stats(cap.metrics)
        assert stats["requests"] == 1 and stats["rejected"] == 1

    def test_sticky_maps_are_bounded(self):
        r = FleetRouter(salt=0, spillover_mode=AFFINE, bucket_floor=32,
                        poll_interval_s=3600.0)
        try:
            r.add_replica("http://127.0.0.1:10000", rid="r0",
                          state=READY)
            for i in range(STICKY_CAP + 64):
                r.record_sticky("verdict", f"v{i}", "r0")
            assert r.stats()["sticky"]["verdicts"] == STICKY_CAP
            # The survivors are the newest ids (FIFO eviction).
            status, _ = r.forward_sticky("GET", "/check/v0", None,
                                         "verdict", "v0")
            assert status == 404
        finally:
            r.close()

    def test_health_poll_state_transitions(self):
        stub = _StubReplica()
        with obs.capture():
            r = FleetRouter(salt=0, spillover_mode=AFFINE,
                            bucket_floor=32, poll_interval_s=3600.0,
                            health_timeout_s=5.0)
            try:
                r.add_replica(stub.url, rid="r0", state=READY)

                def state_after(healthz):
                    stub.healthz = healthz
                    r.poll_health_once()
                    return r.stats()["replicas"][0]["state"]

                assert state_after(
                    (200, {"status": "healthy"})) == READY
                assert state_after(
                    (200, {"status": "healthy",
                           "serve": {"ready": False}})) == "cold"
                assert state_after(
                    (200, {"status": "degraded"})) == DEGRADED
                # A wedged daemon answers 503 WITH a JSON body — that is
                # a live, drained replica, not a dead one.
                assert state_after(
                    (503, {"status": "wedged"})) == WEDGED
                # Recovery: one clean poll re-admits it.
                assert state_after(
                    (200, {"status": "healthy"})) == READY
                stub.close()
                r.poll_health_once()
                assert r.stats()["replicas"][0]["state"] == DOWN
            finally:
                r.close()
                stub.close()

    def test_forward_spills_past_429_and_counts_it(self):
        busy, ok = _StubReplica(), _StubReplica()
        busy.check_status = 429
        with obs.capture() as cap:
            r = FleetRouter(salt=0, spillover_mode=AFFINE,
                            bucket_floor=32, poll_interval_s=3600.0)
            try:
                key = "cas-register|r32"
                order = rendezvous_order(key, ["a", "b"], salt=0)
                # Pin the busy stub to the key's OWNER slot so the
                # request must spill to the healthy runner-up.
                urls = {order[0]: busy.url, order[1]: ok.url}
                for rid in order:
                    r.add_replica(urls[rid], rid=rid, state=READY)
                status, body, rep = r.forward("POST", "/check",
                                              b'{"x": 1}', key)
                assert status == 200 and rep == order[1]
                assert json.loads(body.decode())["valid"] is True
                assert len(busy.requests) == 1 and len(ok.requests) == 1
                reps = {v["id"]: v for v in r.stats()["replicas"]}
                assert reps[order[1]]["spilled_in"] == 1
                assert reps[order[0]]["routed"] == 0
            finally:
                r.close()
        stats = obs.fleet_stats(cap.metrics)
        assert stats["spillover"] == 1
        assert stats["replica_errors"] == 1
        busy.close()
        ok.close()


class _StubReplica:
    """A minimal stand-in for a serve --check replica: programmable
    /healthz and /check answers, so router unit tests never pay a
    subprocess."""

    def __init__(self):
        self.healthz = (200, {"status": "healthy"})
        self.check_status = 200
        self.requests = []
        owner = self

        class _H(BaseHTTPRequestHandler):
            def log_message(self, *a):
                pass

            def _reply(self, status, body):
                payload = json.dumps(body).encode()
                self.send_response(status)
                self.send_header("Content-Type", "application/json")
                self.send_header("Content-Length", str(len(payload)))
                self.end_headers()
                self.wfile.write(payload)

            def do_GET(self):
                if self.path == "/healthz":
                    st, body = owner.healthz
                    return self._reply(st, body)
                return self._reply(404, {"error": "nope"})

            def do_POST(self):
                n = int(self.headers.get("Content-Length") or 0)
                owner.requests.append((self.path,
                                       self.rfile.read(n) if n else b""))
                if owner.check_status == 200:
                    return self._reply(200, {"valid": True,
                                             "dead_step": -1,
                                             "request_id": "stub"})
                return self._reply(owner.check_status,
                                   {"error": "busy", "retry_after_s": 1})

        self.httpd = ThreadingHTTPServer(("127.0.0.1", 0), _H)
        self.url = f"http://127.0.0.1:{self.httpd.server_address[1]}"
        self._thread = threading.Thread(
            target=self.httpd.serve_forever, daemon=True)
        self._thread.start()
        self._closed = False

    def close(self):
        if self._closed:
            return
        self._closed = True
        self.httpd.shutdown()
        self.httpd.server_close()
        self._thread.join(10)


class TestAdmissionRetryAfter:
    def test_inflight_429_carries_retry_after(self, rng,
                                              healthy_supervisor):
        """ISSUE 18 satellite: the inflight-bound 429 is retryable-soon
        (one batch drains it) — the Rejected record must say so, which
        is what the daemon surfaces as the Retry-After header and the
        router re-surfaces fleet-wide."""
        from jepsen_etcd_demo_tpu.ops.encode import \
            encode_register_history

        s = CoalescingScheduler(coalesce_ms=300, max_batch=16,
                                max_inflight=2)
        try:
            enc = encode_register_history(_hist(rng), k_slots=8)
            r1 = s.submit("t", enc, model_name="cas-register")
            r2 = s.submit("t", enc, model_name="cas-register")
            with pytest.raises(Rejected) as exc:
                s.submit("t", enc, model_name="cas-register")
            assert exc.value.status == 429
            assert exc.value.retry_after_s is not None
            assert exc.value.retry_after_s >= 1
            assert r1.wait(120) and r2.wait(120)
        finally:
            s.close()


@pytest.mark.slow
class TestFleetSubprocessEndToEnd:
    def test_fleet_parity_spillover_and_restart(self, rng, tmp_path):
        """The ISSUE's integration test, one fleet for the whole story:
        2 real replicas behind the router surface, 3 tenants over HTTP,
        every verdict bit-identical to the single-daemon and analyze
        routes (invalid histories included); then one replica killed
        mid-load without losing an accepted request; then a warm
        zero-downtime restart of a survivor."""
        from jepsen_etcd_demo_tpu.checkers import Linearizable

        hists = [_hist(rng, n_ops=24 + 12 * (i % 3),
                       invalid=(i % 3 == 2)) for i in range(6)]
        with obs.capture() as cap:
            # Poll slowly: phase 2 must witness the PASSIVE detection
            # path (connect failure -> DOWN -> spill), not lose the
            # race to the active poller.
            router = FleetRouter(salt=0, spillover_mode=AFFINE,
                                 poll_interval_s=30.0,
                                 request_timeout_s=300.0)
            sup = FleetSupervisor(str(tmp_path / "store"), n=2,
                                  router=router, env=dict(_CHILD_ENV),
                                  max_inflight=32)
            httpd = None
            try:
                sup.start()
                httpd = ThreadingHTTPServer(
                    ("127.0.0.1", 0),
                    make_fleet_handler(str(tmp_path / "store"), router,
                                       sup))
                front = f"http://127.0.0.1:{httpd.server_address[1]}"
                t = threading.Thread(target=httpd.serve_forever,
                                     daemon=True)
                t.start()

                urls = sup.replica_urls()
                assert len(urls) == 2
                # Satellite: every replica's /healthz carries the
                # warmup/readiness block (NO_WARMUP -> warmed False).
                for u in urls.values():
                    st, hz = _get_url(u + "/healthz")
                    assert st == 200
                    assert hz["serve"]["ready"] is True
                    assert hz["serve"]["warmed"] is False
                    assert "warmup_launches" in hz["serve"]

                # Phase 1: 3 tenants concurrently, verdict parity.
                verdicts = [None] * len(hists)

                def client(tenant_i):
                    for idx in range(tenant_i, len(hists), 3):
                        st, body, _ = _post_url(
                            front + "/check",
                            {"tenant": f"tenant-{tenant_i}",
                             "model": "cas-register", "wait": True,
                             "history": _op_dicts(hists[idx])})
                        assert st == 200, body
                        verdicts[idx] = body

                ts = [threading.Thread(target=client, args=(i,))
                      for i in range(3)]
                for th in ts:
                    th.start()
                for th in ts:
                    th.join(300)

                # The victim must be a replica that OWNED traffic in
                # phase 1 (routed > 0): killing it guarantees at least
                # one phase-2 request hits the dead owner first and
                # spills (checks are pure, so the retry is lossless).
                st, fs = _get_url(front + "/fleet/stats")
                assert st == 200
                routed = {v["id"]: v["routed"] for v in fs["replicas"]}
                victim = max(sorted(routed), key=lambda k: routed[k])
                assert routed[victim] > 0
                (survivor,) = [rid for rid in urls if rid != victim]

                lin = Linearizable(model="cas-register")
                for hist, served in zip(hists, verdicts):
                    assert served is not None, "client thread died"
                    analyzed = lin.check({}, hist, {})
                    assert served["valid"] == analyzed["valid"]
                    if "dead_step" in analyzed:
                        assert served["dead_step"] == \
                            int(analyzed["dead_step"])
                    # Single-daemon route: the same history straight at
                    # one replica, bypassing the router.
                    st, direct, _ = _post_url(
                        urls[survivor] + "/check",
                        {"tenant": "direct", "model": "cas-register",
                         "wait": True, "history": _op_dicts(hist)})
                    assert st == 200
                    assert direct["valid"] == served["valid"]
                    assert direct["dead_step"] == served["dead_step"]
                assert any(v["valid"] is not True for v in verdicts), \
                    "parity fixture must include invalid histories"

                # Phase 2: kill the owning replica, then load again —
                # the router spills every request to the survivor, so
                # nothing accepted is lost.
                sup.kill_replica(victim)
                killed = [None] * len(hists)

                def client2(tenant_i):
                    for idx in range(tenant_i, len(hists), 3):
                        st, body, _ = _post_url(
                            front + "/check",
                            {"tenant": f"tenant-{tenant_i}",
                             "model": "cas-register", "wait": True,
                             "history": _op_dicts(hists[idx])})
                        assert st == 200, body
                        killed[idx] = body

                ts = [threading.Thread(target=client2, args=(i,))
                      for i in range(3)]
                for th in ts:
                    th.start()
                for th in ts:
                    th.join(300)
                for before, after in zip(verdicts, killed):
                    assert after is not None, \
                        "kill-mid-load lost an accepted request"
                    assert after["valid"] == before["valid"]
                    assert after["dead_step"] == before["dead_step"]

                st, fs = _get_url(front + "/fleet/stats")
                assert st == 200
                states = {v["id"]: v["state"] for v in fs["replicas"]}
                assert READY in states.values()
                assert fs["fleet"]["requests"] >= 2 * len(hists)

                # Phase 3: warm zero-downtime restart of the survivor.
                new_id = sup.restart_replica(survivor)
                assert new_id not in (victim, survivor)
                st, body, _ = _post_url(
                    front + "/check",
                    {"tenant": "tenant-0", "model": "cas-register",
                     "wait": True, "history": _op_dicts(hists[0])})
                assert st == 200
                assert body["valid"] == verdicts[0]["valid"]
            finally:
                if httpd is not None:
                    httpd.shutdown()
                    httpd.server_close()
                sup.close()
        stats = obs.fleet_stats(cap.metrics)
        assert stats["restarts"] == 1
        assert stats["spillover"] >= 1, \
            "killing the owner mid-load must have spilled requests"


@pytest.mark.slow
class TestBenchFleetLane:
    def test_lane_contract_tiny_scale(self, healthy_supervisor):
        """The open-loop lane at toy scale: schema complete (the
        bench_compare gate and the schema check both pass on it),
        verdict parity certified, both arms measured. The affine-beats-
        random assertion is left to the real bench run — at this scale
        the win is not statistically forced."""
        import sys
        from pathlib import Path

        import bench

        sys.path.insert(0, str(Path(bench.__file__).parent / "tools"))
        import bench_compare

        lane = bench.bench_fleet(MODEL, n_hist=10, replicas=2,
                                 ops_range=(8, 64), max_knee_rungs=1,
                                 assert_win=False)
        for key in bench_compare.FLEET_LANE_KEYS:
            assert key in lane, key
        json.dumps(lane)
        assert lane["verdicts_identical"] is True
        assert lane["invalid"] > 0
        assert lane["agg_eps"] > 0 and lane["p99_s"] > 0
        for arm in ("affine", "random"):
            for key in bench_compare.FLEET_ARM_KEYS:
                assert key in lane[arm], (arm, key)
            assert lane[arm]["lookups"] > 0
        rec = {"metric": "wgl_check_throughput", "value": 1.0,
               "degraded": False, "backend": "cpu",
               "fleet": obs.fleet_stats(None),
               "detail": {"fleet": lane}}
        assert bench_compare.check_fleet_record(rec) == []


class TestFleetPlatformGuard:
    def test_refuses_several_replicas_on_an_accelerator(self, tmp_path):
        """Each replica process would claim every chip: off the CPU
        backend the supervisor refuses more than one, before spawning."""
        from jepsen_etcd_demo_tpu.serve.fleet import FleetPlatformError

        sup = FleetSupervisor(str(tmp_path / "store"), n=2,
                              env={"JAX_PLATFORMS": "tpu"})
        with pytest.raises(FleetPlatformError, match="2 serve replicas"):
            sup.start()
        assert sup.replica_urls() == {}

    @staticmethod
    def _live_replica(sup, rid: str):
        """Register a stand-in replica process (alive, never ready)."""
        import subprocess
        import sys

        from jepsen_etcd_demo_tpu.serve.fleet import ReplicaProc

        rp = ReplicaProc(rid, subprocess.Popen(
            [sys.executable, "-c", "import time; time.sleep(120)"]), "", "")
        with sup._lock:
            sup._procs[rid] = rp
        return rp

    def test_spawn_refuses_a_second_live_replica_on_an_accelerator(
            self, tmp_path):
        """The guard sits in spawn_replica, so no path (start, restart,
        rolling restart) starts a replica beside a live one off CPU."""
        from jepsen_etcd_demo_tpu.serve.fleet import FleetPlatformError

        sup = FleetSupervisor(str(tmp_path / "store"), n=1,
                              env={"JAX_PLATFORMS": "tpu"})
        try:
            self._live_replica(sup, "r0")
            with pytest.raises(FleetPlatformError, match="beside live"):
                sup.spawn_replica()
            assert list(sup._procs) == ["r0"]
        finally:
            sup.close()

    def test_restart_on_an_accelerator_stops_the_old_replica_first(
            self, tmp_path, monkeypatch):
        from jepsen_etcd_demo_tpu.serve.fleet import ReplicaProc

        sup = FleetSupervisor(str(tmp_path / "store"), n=1,
                              env={"JAX_PLATFORMS": "tpu"})
        old = self._live_replica(sup, "r0")
        sup.router.add_replica("http://127.0.0.1:9", rid="r0", state=READY)
        old_exit_at_spawn = []

        def spawn():
            old_exit_at_spawn.append(old.proc.poll())
            rp = ReplicaProc("r1", old.proc, "", "")
            with sup._lock:
                sup._procs["r1"] = rp
            return rp

        monkeypatch.setattr(sup, "spawn_replica", spawn)
        monkeypatch.setattr(sup, "_bring_up",
                            lambda rp: "http://127.0.0.1:10")
        try:
            assert sup.rolling_restart() == ["r1"]
            assert old_exit_at_spawn[0] is not None
            assert sup.router.replica_ids() == ["r1"]
        finally:
            sup.close()
