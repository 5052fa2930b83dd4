"""Fleet supervisor: N `serve --check` replicas behind the shape-affine
router (ISSUE 18 tentpole; serve/router.py is the routing half).

The supervisor owns replica *processes*; the router owns replica
*membership*. Keeping them separate is what makes zero-downtime restart
a three-line protocol:

1. spawn a replacement (`serve --check --port 0 --ready-file ...`) —
   the replica runs `sched/warmup.warmup_plans` before binding, so the
   ready-file contract means "warm", not just "listening";
2. optionally replay a warmup corpus through the replacement's own
   POST /check (tenant ``_warmup``) so its kernel LRU holds the fleet's
   live shapes, then `router.swap_replica` — one lock hold admits the
   replacement and evicts the old replica, so no routing decision ever
   sees neither;
3. drain the old replica (poll /serve/stats until pending+inflight hit
   zero, bounded) and only then terminate it — in-flight verdicts land.

Every replica shares the fleet-wide warm state: the one persistent XLA
compile cache (sched/compile_cache.compile_cache_dir) and the
O_EXCL-locked tuned-profile file next to it (tune/profile.py), so one
replica's tune benefits all.

Replicas are separate processes, and a TPU chip belongs to one process
at a time: every replica on an accelerator would claim every chip. So
the supervisor runs several replicas only on the CPU backend
(JAX_PLATFORMS=cpu in the replicas' env). Otherwise `spawn_replica`
refuses to start a replica while another is alive
(:class:`FleetPlatformError`), and a restart stops the old replica
before its replacement starts — one process per host, with downtime.

`serve_fleet` is the CLI entry (`jepsen-tpu serve --check --fleet`):
supervisor + router + the fleet HTTP surface (web/server.py StoreHandler
+ /check forwarding + /fleet/stats) under one obs capture, so the
fleet.* counters land on the router's own /metrics.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import tempfile
import time
import urllib.request
from http.server import ThreadingHTTPServer
from pathlib import Path
from typing import Any, Optional

from .. import obs
from ..obs.sync import maybe_wrap
from ..web import server as web_server
from .daemon import MAX_BODY_BYTES
from .router import READY, FleetRouter, routing_key

#: How long a replica may take from spawn to ready-file (imports +
#: startup warmup + bind). Generous: a cold XLA cache pays real
#: compiles here so traffic never does.
READY_TIMEOUT_S = 180.0

#: Drain bound for a replaced replica: in-flight verdicts get this long
#: to land before the old process is terminated anyway.
DRAIN_TIMEOUT_S = 60.0


class FleetPlatformError(RuntimeError):
    """More than one replica process asked for on an accelerator."""


class ReplicaProc:
    """One spawned replica: process handle + the ready record."""

    def __init__(self, rid: str, proc: subprocess.Popen,
                 ready_file: str, log_path: str):
        self.id = rid
        self.proc = proc
        self.ready_file = ready_file
        self.log_path = log_path
        self.ready: dict[str, Any] = {}
        self.url: Optional[str] = None

    def wait_ready(self, timeout: float = READY_TIMEOUT_S) -> dict:
        """Block on the --ready-file contract and return the ready
        record. Raises RuntimeError when the process dies or the
        deadline passes first. Does NOT publish ``self.url`` — that
        write belongs to the supervisor, under its membership lock
        (handler threads read it through replica_urls())."""
        deadline = time.monotonic() + timeout
        path = Path(self.ready_file)
        while time.monotonic() < deadline:
            if self.proc.poll() is not None:
                tail = ""
                try:
                    tail = Path(self.log_path).read_text()[-2000:]
                except OSError:
                    pass
                raise RuntimeError(
                    f"replica {self.id} exited rc={self.proc.returncode} "
                    f"before ready; log tail:\n{tail}")
            if path.exists():
                try:
                    text = path.read_text()
                    if text.strip():
                        rec = json.loads(text)
                        if "serving" not in rec:
                            raise KeyError("serving")
                        self.ready = rec
                        return rec
                except (json.JSONDecodeError, KeyError):
                    pass   # partial write — poll again
            time.sleep(0.05)
        raise RuntimeError(
            f"replica {self.id} not ready within {timeout}s")

    def terminate(self, grace_s: float = 10.0) -> None:
        if self.proc.poll() is None:
            self.proc.terminate()
            try:
                self.proc.wait(timeout=grace_s)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait(timeout=grace_s)

    def kill(self) -> None:
        """Hard kill — the failure-injection path (tests): no drain, no
        grace, exactly what a crashed replica looks like."""
        if self.proc.poll() is None:
            self.proc.kill()
            self.proc.wait(timeout=10)


class FleetSupervisor:
    """Spawn/adopt N replicas, keep the router's membership in sync,
    and run the zero-downtime restart protocol."""

    def __init__(self, store_root: str = "store", *,
                 n: Optional[int] = None, host: str = "127.0.0.1",
                 default_model: str = "cas-register",
                 coalesce_ms: Optional[int] = None,
                 max_batch: Optional[int] = None,
                 max_inflight: Optional[int] = None,
                 router: Optional[FleetRouter] = None,
                 env: Optional[dict] = None,
                 warm_corpus: Optional[list[dict]] = None,
                 ready_timeout_s: float = READY_TIMEOUT_S):
        import threading

        from ..ops.limits import limits

        self.store_root = str(store_root)
        self.n = limits().fleet_replicas if n is None else int(n)
        self.host = host
        self.default_model = default_model
        self.coalesce_ms = coalesce_ms
        self.max_batch = max_batch
        self.max_inflight = max_inflight
        self.router = router if router is not None else FleetRouter()
        self.env_overrides = dict(env or {})
        #: Histories replayed through a replacement replica before it
        #: takes traffic (each a {"model": ..., "history": [...]}).
        self.warm_corpus = list(warm_corpus or [])
        self.ready_timeout_s = ready_timeout_s
        self._lock = maybe_wrap(threading.Lock(),
                                "serve.fleet.FleetSupervisor._lock")
        # jtsan: guarded-by=self._lock
        self._procs: dict[str, ReplicaProc] = {}
        self._seq = 0            # jtsan: guarded-by=self._lock
        self._tmpdir = tempfile.mkdtemp(prefix="jepsen-fleet-")

    # ------------------------------------------------------------------
    # spawning

    def _child_env(self) -> dict:
        env = dict(os.environ)
        # The package must be importable in the child no matter where
        # the fleet was launched from.
        pkg_root = str(Path(__file__).resolve().parents[2])
        env["PYTHONPATH"] = pkg_root + (
            os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH")
            else "")
        env.update(self.env_overrides)
        return env

    def _on_accelerator(self) -> bool:
        return self._child_env().get("JAX_PLATFORMS", "").lower() != "cpu"

    def _platform_error(self, what: str) -> FleetPlatformError:
        platforms = self._child_env().get("JAX_PLATFORMS", "")
        return FleetPlatformError(
            f"refusing to start {what} on "
            f"JAX_PLATFORMS={platforms or '<default accelerator>'}: "
            f"each replica process would claim every chip, and a chip "
            f"serves one process. Run one replica per host, or set "
            f"JAX_PLATFORMS=cpu for a CPU fleet.")

    def spawn_replica(self) -> ReplicaProc:
        """Start one `serve --check` subprocess (not yet routed). Off
        the CPU backend, refuses while another replica is alive: the
        check, the spawn and the registration are one critical section,
        so two concurrent spawns cannot both pass."""
        accel = self._on_accelerator()
        env = self._child_env()
        with self._lock:
            live = [r for r, rp in self._procs.items()
                    if rp.proc.poll() is None]
            if accel and live:
                raise self._platform_error(
                    f"a serve replica beside live replica(s) {live}")
            rid = f"r{self._seq}"
            self._seq += 1
            ready_file = os.path.join(self._tmpdir, f"{rid}.ready.json")
            log_path = os.path.join(self._tmpdir, f"{rid}.log")
            with open(log_path, "wb") as logf:
                proc = subprocess.Popen(self._replica_cmd(ready_file),
                                        stdout=logf, stderr=logf, env=env)
            rp = ReplicaProc(rid, proc, ready_file, log_path)
            self._procs[rid] = rp
        return rp

    def _replica_cmd(self, ready_file: str) -> list[str]:
        cmd = [sys.executable, "-m", "jepsen_etcd_demo_tpu.cli.main",
               "serve", "--check", "--host", self.host, "--port", "0",
               "--store", self.store_root, "--model", self.default_model,
               "--ready-file", ready_file]
        if self.coalesce_ms is not None:
            cmd += ["--coalesce-ms", str(self.coalesce_ms)]
        if self.max_batch is not None:
            cmd += ["--max-batch", str(self.max_batch)]
        if self.max_inflight is not None:
            cmd += ["--max-inflight", str(self.max_inflight)]
        return cmd

    def start(self) -> None:
        """Spawn the fleet, wait for every ready-file, admit everyone
        READY, start the router's health poller."""
        if self.n > 1 and self._on_accelerator():
            raise self._platform_error(f"{self.n} serve replicas")
        procs = [self.spawn_replica() for _ in range(self.n)]
        for rp in procs:
            self.router.add_replica(self._bring_up(rp), rid=rp.id,
                                    state=READY)
        self.router.refresh_gauges()
        self.router.start()

    def _bring_up(self, rp: ReplicaProc) -> str:
        """Wait for a spawned replica's ready-file, publish its url and
        warm it; returns the url."""
        url = rp.wait_ready(self.ready_timeout_s)["serving"]
        with self._lock:
            rp.url = url
        if self.warm_corpus:
            self.warm_replica(url)
        return url

    def adopt(self, url: str, rid: Optional[str] = None):
        """Route to a replica this supervisor did not spawn (it owns
        its own lifecycle; health polling still applies)."""
        return self.router.add_replica(url, rid=rid, state=READY)

    # ------------------------------------------------------------------
    # warm restart

    def warm_replica(self, url: str,
                     timeout_s: float = READY_TIMEOUT_S) -> int:
        """Replay the warmup corpus through the replica's own /check
        (tenant ``_warmup``, wait=true) so its kernel LRU holds the
        fleet's live shapes before it takes traffic. Best-effort: a
        failed warmup request leaves the replica cold for that shape,
        never broken."""
        warmed = 0
        for item in self.warm_corpus:
            body = json.dumps({
                "tenant": "_warmup",
                "model": item.get("model", self.default_model),
                "history": item["history"], "wait": True,
            }).encode()
            req = urllib.request.Request(
                url + "/check", data=body,
                headers={"Content-Type": "application/json"})
            try:
                with urllib.request.urlopen(req, timeout=timeout_s):
                    warmed += 1
            except Exception:
                pass
        return warmed

    def restart_replica(self, rid: str) -> str:
        """Zero-downtime restart: replacement up + warm first, swap the
        hash slice, drain the old replica, then terminate it. Off the
        CPU backend the two cannot run at once (one process per chip):
        the old replica is evicted, drained and stopped first. Returns
        the replacement's id."""
        with self._lock:
            if rid not in self._procs:
                raise KeyError(f"no replica {rid!r}")
        if self._on_accelerator():
            self.router.remove_replica(rid)
            self._retire(rid)
            new = self.spawn_replica()
            self.router.add_replica(self._bring_up(new), rid=new.id,
                                    state=READY)
            obs.get_metrics().counter("fleet.restarts").add(1)
            self.router.refresh_gauges()
            return new.id
        new = self.spawn_replica()
        self.router.swap_replica(rid, self._bring_up(new), rid=new.id)
        self.router.refresh_gauges()
        self._retire(rid)
        return new.id

    def _retire(self, rid: str) -> None:
        """Drain a replica the router no longer routes to, then stop it.
        Re-validate under this acquisition and bind what the dict
        actually holds (JTL503): the drained/terminated process is
        exactly the one popped, not an earlier peek."""
        with self._lock:
            old = self._procs.pop(rid, None)
            old_url = old.url if old is not None else None
        if old is not None:
            if old_url:
                self._drain(old_url)
            old.terminate()

    def rolling_restart(self) -> list[str]:
        """Restart every replica one at a time (config/code rollout):
        on the CPU backend the fleet never drops below n-0 routable
        replicas because each replacement is admitted before its
        predecessor drains."""
        with self._lock:
            rids = list(self._procs)
        return [self.restart_replica(rid) for rid in rids]

    def _drain(self, url: str,
               timeout_s: float = DRAIN_TIMEOUT_S) -> bool:
        """Poll the evicted replica's /serve/stats until every admitted
        request has a verdict (pending 0, inflight 0)."""
        deadline = time.monotonic() + timeout_s
        while time.monotonic() < deadline:
            try:
                with urllib.request.urlopen(
                        url + "/serve/stats", timeout=10) as resp:
                    st = json.loads(resp.read().decode())
                sch = st.get("scheduler", {})
                inflight = sum(t.get("inflight", 0)
                               for t in sch.get("tenants", {}).values())
                if sch.get("pending", 0) == 0 and inflight == 0:
                    return True
            except Exception:
                return False   # already gone — nothing left to drain
            time.sleep(0.1)
        return False

    # ------------------------------------------------------------------
    # failure injection / teardown

    def kill_replica(self, rid: str) -> None:
        """Crash one replica (tests): no drain, no router courtesy —
        the router finds out via connect failures and health polls."""
        with self._lock:
            rp = self._procs.get(rid)
        if rp is not None:
            rp.kill()

    def replica_urls(self) -> dict[str, str]:
        with self._lock:
            return {rid: rp.url for rid, rp in self._procs.items()
                    if rp.url}

    def close(self) -> None:
        self.router.close()
        with self._lock:
            procs = list(self._procs.values())
            self._procs.clear()
        for rp in procs:
            rp.terminate()


# ----------------------------------------------------------------------
# the fleet's HTTP surface


class FleetHandler(web_server.StoreHandler):
    """StoreHandler (run index, /metrics with fleet.* families,
    /healthz for the ROUTER process) + request forwarding:

    * POST /check               -> routing_key(model, history) -> owner
    * GET  /check/<id>          -> sticky to the verdict's origin
    * POST /serve/session*      -> sticky session routing
    * GET  /fleet/stats         -> router + per-replica view
    * GET  /serve/stats         -> fan-out to every replica
    """

    router_obj: FleetRouter = None        # bound by make_fleet_handler
    supervisor_obj: FleetSupervisor = None

    def _send_json(self, body: dict, status: int = 200) -> None:
        payload = (json.dumps(body, indent=2, default=str) + "\n").encode()
        self.send_response(status)
        self.send_header("Content-Type",
                         "application/json; charset=utf-8")
        self.send_header("Content-Length", str(len(payload)))
        self.end_headers()
        self.wfile.write(payload)

    def _relay(self, status: int, body: bytes) -> None:
        """Pass an upstream response through byte-identical (verdict
        parity is a contract — the router must not re-encode JSON)."""
        self.send_response(status)
        self.send_header("Content-Type",
                         "application/json; charset=utf-8")
        self.send_header("Content-Length", str(len(body)))
        if status in (429, 503):
            # Upstream Retry-After is in the JSON body; re-surface the
            # header for clients that only look there.
            try:
                ra = json.loads(body.decode()).get("retry_after_s")
            except (json.JSONDecodeError, UnicodeDecodeError, AttributeError):
                ra = None
            self.send_header("Retry-After", str(int(ra)) if ra else "1")
        self.end_headers()
        self.wfile.write(body)

    def _read_raw(self) -> bytes:
        n = int(self.headers.get("Content-Length") or 0)
        if n <= 0:
            return b"{}"
        if n > MAX_BODY_BYTES:
            raise ValueError(
                f"request body of {n} bytes exceeds the "
                f"{MAX_BODY_BYTES}-byte bound")
        return self.rfile.read(n)

    def do_POST(self):
        r = self.router_obj
        path = self.path.rstrip("/")
        try:
            raw = self._read_raw()
            if path == "/check":
                body = json.loads(raw.decode("utf-8"))
                key = routing_key(
                    str(body.get("model")
                        or (self.supervisor_obj.default_model
                            if self.supervisor_obj else "cas-register")),
                    body.get("history") or [], r.bucket_floor)
                status, out, rep = r.forward("POST", "/check", raw, key)
                if rep and status in (200, 202):
                    try:
                        rid = json.loads(out.decode()).get("request_id")
                    except json.JSONDecodeError:
                        rid = None
                    if rid:
                        r.record_sticky("verdict", rid, rep)
                return self._relay(status, out)
            if path == "/serve/session":
                body = json.loads(raw.decode("utf-8"))
                model = str(body.get("model")
                            or (self.supervisor_obj.default_model
                                if self.supervisor_obj
                                else "cas-register"))
                status, out, rep = r.forward(
                    "POST", "/serve/session", raw, f"{model}|session")
                if rep and status in (200, 201):
                    try:
                        sid = json.loads(out.decode()).get("session_id")
                    except json.JSONDecodeError:
                        sid = None
                    if sid:
                        r.record_sticky("session", sid, rep)
                return self._relay(status, out)
            if path.startswith("/serve/session/"):
                rest = path[len("/serve/session/"):]
                for suffix in ("/ops", "/close"):
                    if rest.endswith(suffix):
                        sid = rest[:-len(suffix)]
                        status, out = r.forward_sticky(
                            "POST", path, raw, "session", sid)
                        return self._relay(status, out)
            self._send_json({"error": f"unknown endpoint {self.path}"},
                            status=404)
        except (ValueError, KeyError, json.JSONDecodeError) as e:
            self._send_json({"error": f"{type(e).__name__}: {e}"},
                            status=400)
        except (BrokenPipeError, ConnectionResetError):
            return

    def do_GET(self):
        r = self.router_obj
        path = self.path.rstrip("/")
        try:
            if path.startswith("/check/"):
                rid = path[len("/check/"):]
                status, out = r.forward_sticky(
                    "GET", path, None, "verdict", rid)
                return self._relay(status, out)
            if path == "/fleet/stats":
                view = r.stats()
                if self.supervisor_obj is not None:
                    view["processes"] = self.supervisor_obj.replica_urls()
                return self._send_json(view)
            if path == "/serve/stats":
                out = {}
                for rep in list(r.replica_ids()):
                    status, body = r.send_to(rep, "GET", "/serve/stats")
                    if status == 200:
                        try:
                            out[rep] = json.loads(body.decode())
                        except json.JSONDecodeError:
                            pass
                return self._send_json({"replicas": out})
        except (BrokenPipeError, ConnectionResetError):
            return
        return super().do_GET()


def make_fleet_handler(store_root: str, router: FleetRouter,
                       supervisor: Optional[FleetSupervisor] = None):
    class _Bound(FleetHandler):
        router_obj = router
        supervisor_obj = supervisor

        def __init__(self, *args, **kw):
            super().__init__(*args, store_root=store_root, **kw)

    return _Bound


def serve_fleet(store_root: str = "store", host: str = "127.0.0.1",
                port: int = 8080, replicas: Optional[int] = None,
                default_model: str = "cas-register",
                coalesce_ms: Optional[int] = None,
                max_batch: Optional[int] = None,
                max_inflight: Optional[int] = None,
                ready_file: Optional[str] = None) -> int:
    """`jepsen-tpu serve --check --fleet`: spawn the replica fleet,
    bind the router surface, serve until interrupted."""
    sup = FleetSupervisor(store_root, n=replicas, host=host,
                          default_model=default_model,
                          coalesce_ms=coalesce_ms, max_batch=max_batch,
                          max_inflight=max_inflight)
    with obs.capture():
        try:
            sup.start()
            httpd = ThreadingHTTPServer(
                (host, port),
                make_fleet_handler(store_root, sup.router, sup))
            actual_port = httpd.server_address[1]
            ready = {"serving": f"http://{host}:{actual_port}",
                     "port": actual_port, "store": str(store_root),
                     "check": True, "fleet": sup.n,
                     "replicas": sup.replica_urls()}
            print(json.dumps(ready), flush=True)
            if ready_file:
                Path(ready_file).write_text(json.dumps(ready))
            try:
                httpd.serve_forever()
            except KeyboardInterrupt:
                pass
            finally:
                httpd.server_close()
        finally:
            sup.close()
    return 0
