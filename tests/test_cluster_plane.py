"""Hermetic tests for the real-cluster plane (VERDICT round-1 item 6).

Every component that talks to a real cluster — the etcd v2 HTTP client, the
daemon/archive helpers, the SSH argv assembly, the iptables partitioner, the
etcd DB orchestration — exercised without any cluster:

  * a stub in-process etcd v2 HTTP server (threading http.server) asserting
    the wire protocol: quorum param, prevValue/prevIndex CAS encodings,
    errorCode 100 -> NotFound, 101 -> cas False, timeouts -> Timeout;
  * LocalRunner driving the daemon helpers against this host;
  * a RecordingRunner capturing the exact shell the partitioner / DB / OS
    layers would run over SSH.
"""

from __future__ import annotations

import asyncio
import json
import random
import tarfile
import threading
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from urllib.parse import urlparse, parse_qs

import pytest

from jepsen_etcd_demo_tpu.clients.base import NotFound, Timeout
from jepsen_etcd_demo_tpu.clients.etcd import EtcdClient
from jepsen_etcd_demo_tpu.control.daemon import (daemon_running,
                                                 install_archive,
                                                 start_daemon, stop_daemon)
from jepsen_etcd_demo_tpu.control.runner import (CommandResult, LocalRunner,
                                                 Runner, SSHRunner,
                                                 runner_for)
from jepsen_etcd_demo_tpu.nemesis.partition import PartitionRandomHalves


def go(coro):
    return asyncio.run(coro)


# --- stub etcd v2 server ---------------------------------------------------

class StubEtcd:
    """In-memory etcd v2 keys API with modifiedIndex semantics."""

    def __init__(self):
        self.data: dict[str, tuple[str, int]] = {}   # key -> (value, idx)
        self.index = 0
        self.requests: list[dict] = []               # wire-protocol log
        self.delay_s = 0.0
        self.interfere_once = False                  # mutate before next PUT
        self.drop_delete_response_once = False       # apply, lose the ack
        self.server: ThreadingHTTPServer | None = None

    def put_internal(self, key: str, value: str) -> None:
        self.index += 1
        self.data[key] = (value, self.index)

    def start(self) -> str:
        stub = self

        class Handler(BaseHTTPRequestHandler):
            def log_message(self, *a):
                pass

            def _reply(self, body: dict, status: int = 200):
                payload = json.dumps(body).encode()
                self.send_response(status)
                self.send_header("Content-Type", "application/json")
                self.send_header("Content-Length", str(len(payload)))
                self.end_headers()
                self.wfile.write(payload)

            def _record(self, form):
                u = urlparse(self.path)
                stub.requests.append({
                    "method": self.command,
                    "key": u.path.rsplit("/", 1)[-1],
                    "path": u.path[len("/v2/keys"):],
                    "params": {k: v[0] for k, v in
                               parse_qs(u.query).items()},
                    "form": {k: v[0] for k, v in form.items()},
                })
                return stub.requests[-1]

            def do_GET(self):
                if stub.delay_s:
                    import time
                    time.sleep(stub.delay_s)
                req = self._record({})
                path = req["path"].lstrip("/")
                children = sorted(
                    (idx, k, v) for k, (v, idx) in stub.data.items()
                    if k.startswith(path + "/"))
                if path not in stub.data and not children:
                    self._reply({"errorCode": 100,
                                 "message": "Key not found"}, 404)
                    return
                if children:   # dir listing (sorted=creation order)
                    self._reply({"action": "get", "node": {
                        "key": f"/{path}", "dir": True,
                        "nodes": [{"key": f"/{k}", "value": v,
                                   "modifiedIndex": idx}
                                  for idx, k, v in children]}})
                    return
                v, idx = stub.data[path]
                self._reply({"action": "get",
                             "node": {"key": f"/{path}", "value": v,
                                      "modifiedIndex": idx}})

            def do_POST(self):
                length = int(self.headers.get("Content-Length", 0))
                form = parse_qs(self.rfile.read(length).decode())
                req = self._record(form)
                path = req["path"].lstrip("/")
                stub.index += 1
                node = f"{path}/{stub.index:020d}"
                stub.data[node] = (req["form"].get("value", ""), stub.index)
                self._reply({"action": "create",
                             "node": {"key": f"/{node}",
                                      "value": stub.data[node][0],
                                      "modifiedIndex": stub.index}}, 201)

            def do_DELETE(self):
                req = self._record({})
                path, params = req["path"].lstrip("/"), req["params"]
                if path not in stub.data:
                    self._reply({"errorCode": 100,
                                 "message": "Key not found"}, 404)
                    return
                v, idx = stub.data[path]
                if ("prevIndex" in params
                        and int(params["prevIndex"]) != idx):
                    self._reply({"errorCode": 101,
                                 "message": "Compare failed"}, 412)
                    return
                del stub.data[path]
                if stub.drop_delete_response_once:
                    stub.drop_delete_response_once = False
                    self.connection.close()   # applied, but ack lost
                    return
                self._reply({"action": "delete",
                             "node": {"key": f"/{path}", "value": v,
                                      "modifiedIndex": idx}})

            def do_PUT(self):
                if stub.delay_s:
                    import time
                    time.sleep(stub.delay_s)
                length = int(self.headers.get("Content-Length", 0))
                form = parse_qs(self.rfile.read(length).decode())
                req = self._record(form)
                key, params = req["key"], req["params"]
                value = req["form"].get("value", "")
                if stub.interfere_once and "prevIndex" in params:
                    stub.interfere_once = False
                    stub.put_internal(key, "interfered")
                if "prevValue" in params or "prevIndex" in params:
                    if key not in stub.data:
                        self._reply({"errorCode": 100,
                                     "message": "Key not found"}, 404)
                        return
                    cur, idx = stub.data[key]
                    if ("prevValue" in params
                            and params["prevValue"] != cur) or \
                       ("prevIndex" in params
                            and int(params["prevIndex"]) != idx):
                        self._reply({"errorCode": 101,
                                     "message": "Compare failed"}, 412)
                        return
                stub.put_internal(key, value)
                self._reply({"action": "set",
                             "node": {"key": f"/{key}", "value": value,
                                      "modifiedIndex": stub.index}})

        class QuietServer(ThreadingHTTPServer):
            def handle_error(self, request, client_address):
                pass  # client-side timeouts abort connections mid-reply

        self.server = QuietServer(("127.0.0.1", 0), Handler)
        threading.Thread(target=self.server.serve_forever,
                         daemon=True).start()
        return f"http://127.0.0.1:{self.server.server_address[1]}"

    def stop(self):
        if self.server:
            self.server.shutdown()


@pytest.fixture
def stub():
    s = StubEtcd()
    s.url = s.start()
    yield s
    s.stop()


class TestEtcdClient:
    def test_get_missing_returns_none_and_records_no_quorum(self, stub):
        async def t():
            c = EtcdClient(stub.url)
            assert await c.get("nope") is None
            await c.close()
        go(t())
        assert stub.requests[-1]["params"] == {}

    def test_quorum_get_sends_quorum_param(self, stub):
        async def t():
            c = EtcdClient(stub.url)
            await c.reset("r", 5)
            assert await c.get("r", quorum=True) == "5"
            await c.close()
        go(t())
        assert stub.requests[-1]["params"] == {"quorum": "true"}

    def test_reset_and_get_roundtrip(self, stub):
        async def t():
            c = EtcdClient(stub.url)
            await c.reset("k", 3)
            assert await c.get("k") == "3"
            await c.close()
        go(t())
        assert stub.requests[0]["form"] == {"value": "3"}

    def test_cas_success_and_failure(self, stub):
        async def t():
            c = EtcdClient(stub.url)
            await c.reset("k", 1)
            assert await c.cas("k", 1, 2) is True      # matches
            assert await c.cas("k", 1, 3) is False     # stale prevValue
            assert await c.get("k") == "2"
            await c.close()
        go(t())
        cas_reqs = [r for r in stub.requests if "prevValue" in r["params"]]
        assert [r["params"]["prevValue"] for r in cas_reqs] == ["1", "1"]

    def test_cas_on_missing_key_raises_notfound(self, stub):
        async def t():
            c = EtcdClient(stub.url)
            with pytest.raises(NotFound):
                await c.cas("ghost", 1, 2)
            await c.close()
        go(t())

    def test_get_with_index_missing_raises_notfound(self, stub):
        async def t():
            c = EtcdClient(stub.url)
            with pytest.raises(NotFound):
                await c.get_with_index("ghost")
            await c.close()
        go(t())

    def test_swap_retries_on_previndex_conflict(self, stub):
        async def t():
            c = EtcdClient(stub.url)
            await c.reset("s", "a")
            stub.interfere_once = True     # first prevIndex PUT goes stale
            out = await c.swap("s", lambda v: v + "x")
            await c.close()
            return out
        out = go(t())
        # The retry re-read the interfered value and applied fn to THAT.
        assert out == "interferedx"
        prev_idx_puts = [r for r in stub.requests
                         if "prevIndex" in r["params"]]
        assert len(prev_idx_puts) == 2     # conflict, then success

    def test_timeout_maps_to_timeout_error(self, stub):
        async def t():
            c = EtcdClient(stub.url, timeout_s=0.05)
            stub.delay_s = 0.5
            with pytest.raises(Timeout):
                await c.get("k")
            await c.close()
        go(t())

    def test_connection_refused_is_determinate_fail(self):
        """A dead server (kill-nemesis window) refuses TCP outright: the
        request was never transmitted, so the client raises the
        DETERMINATE ConnectionRefused (a ClientError -> :fail), not the
        indeterminate Timeout -> :info — otherwise every op in a kill
        window becomes a forever-pending slot the checker must carry."""
        import socket

        from jepsen_etcd_demo_tpu.clients.base import ConnectionRefused
        from jepsen_etcd_demo_tpu.clients.register import RegisterClient
        from jepsen_etcd_demo_tpu.ops.op import Op

        # Hold the port BOUND (never listen()ed) for the test's whole
        # duration: connects get ECONNREFUSED deterministically, and no
        # other process can grab the port in a close-to-connect gap.
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            port = s.getsockname()[1]

            async def t():
                c = EtcdClient(f"http://127.0.0.1:{port}", timeout_s=2.0)
                with pytest.raises(ConnectionRefused):
                    await c.get("k")
                rc = RegisterClient(lambda test, node: c, conn=c)
                done = await rc.invoke({}, Op(type="invoke", f="write",
                                              value=("0", 1), process=0))
                await c.close()
                return done

            done = go(t())
        assert done.type == "fail"          # determinate, NOT info


# --- daemon helpers over LocalRunner ---------------------------------------

class TestDaemon:
    def test_daemon_lifecycle_idempotent(self, tmp_path):
        r = LocalRunner()
        pidfile = str(tmp_path / "d.pid")
        logfile = str(tmp_path / "d.log")

        async def t():
            await start_daemon(r, "/bin/sleep", ["30"], logfile=logfile,
                               pidfile=pidfile, chdir=str(tmp_path),
                               su=False)
            assert await daemon_running(r, pidfile)
            pid1 = (tmp_path / "d.pid").read_text().strip()
            # Second start is a no-op on a live pidfile.
            await start_daemon(r, "/bin/sleep", ["30"], logfile=logfile,
                               pidfile=pidfile, chdir=str(tmp_path),
                               su=False)
            assert (tmp_path / "d.pid").read_text().strip() == pid1
            await stop_daemon(r, pidfile, su=False)
            assert not await daemon_running(r, pidfile)
            # Stop is idempotent.
            await stop_daemon(r, pidfile, su=False)
        go(t())

    def test_install_archive_unpacks_stripping_top_dir(self, tmp_path):
        src = tmp_path / "pkg" / "etcd-v9"
        src.mkdir(parents=True)
        (src / "etcd").write_text("#!/bin/sh\necho fake-etcd\n")
        tgz = tmp_path / "rel.tar.gz"
        with tarfile.open(tgz, "w:gz") as t:
            t.add(src, arcname="etcd-v9")
        dest = tmp_path / "opt"

        async def t():
            await install_archive(LocalRunner(), f"file://{tgz}",
                                  str(dest), su=False)
        go(t())
        assert (dest / "etcd").read_text().endswith("fake-etcd\n")


# --- SSH argv assembly (no ssh spawned) ------------------------------------

class TestSSHArgv:
    def test_basic_argv(self):
        r = SSHRunner("n1", username="admin", port=2222,
                      private_key="/k/id", connect_timeout_s=7)
        argv = r._ssh_argv("echo hi")
        assert argv[:3] == ["ssh", "-p", "2222"]
        assert "-o" in argv and "BatchMode=yes" in argv
        assert "ConnectTimeout=7" in argv
        assert "-i" in argv and "/k/id" in argv
        assert "StrictHostKeyChecking=no" in argv
        assert argv[-2:] == ["admin@n1", "echo hi"]

    def test_strict_host_checking_drops_overrides(self):
        argv = SSHRunner("n1", strict_host_key_checking=True)._ssh_argv("x")
        assert "StrictHostKeyChecking=no" not in argv

    def test_sudo_wrapping_for_non_root(self, monkeypatch):
        captured = {}

        async def fake_spawn(self, argv, check, timeout_s, env=None):
            captured["argv"] = list(argv)
            return CommandResult(list(argv), 0, "", "")

        monkeypatch.setattr(SSHRunner, "_spawn", fake_spawn)
        go(SSHRunner("n1", username="admin").run("rm -rf /opt/etcd",
                                                 su=True))
        assert captured["argv"][-1] == "sudo sh -c 'rm -rf /opt/etcd'"
        # root needs no sudo wrap
        go(SSHRunner("n1", username="root").run("ls", su=True))
        assert captured["argv"][-1] == "ls"

    def test_upload_download_argv(self, monkeypatch):
        calls = []

        async def fake_spawn(self, argv, check, timeout_s, env=None):
            calls.append(list(argv))
            return CommandResult(list(argv), 0, "", "")

        monkeypatch.setattr(SSHRunner, "_spawn", fake_spawn)
        r = SSHRunner("n2", username="u", port=2022)
        go(r.upload("/a", "/b"))
        go(r.download("/c", "/d"))
        assert calls[0][0] == "scp" and calls[0][-2:] == ["/a", "u@n2:/b"]
        assert calls[1][-2:] == ["u@n2:/c", "/d"]

    def test_password_rides_sshpass_env(self, monkeypatch):
        """jepsen's --password (VERDICT r4 missing #2): sshpass prefix,
        password in SSHPASS env only (argv is world-readable via ps),
        BatchMode dropped so the auth prompt can be answered."""
        import shutil

        calls = []

        async def fake_spawn(self, argv, check, timeout_s, env=None):
            calls.append((list(argv), env))
            return CommandResult(list(argv), 0, "", "")

        monkeypatch.setattr(SSHRunner, "_spawn", fake_spawn)
        # argv assembly only — no sshpass binary on this image (the
        # transport's which() guard would otherwise raise before _spawn).
        monkeypatch.setattr(shutil, "which",
                            lambda name: f"/usr/bin/{name}")
        r = SSHRunner("n1", username="admin", password="hunter2")
        go(r.run("ls"))
        go(r.upload("/a", "/b"))
        go(r.download("/c", "/d"))
        for argv, env in calls:
            assert argv[:2] == ["sshpass", "-e"]
            assert "hunter2" not in " ".join(argv)
            assert env["SSHPASS"] == "hunter2"
            assert "BatchMode=yes" not in argv
            assert "NumberOfPasswordPrompts=1" in argv
        # Key auth unchanged: no sshpass, BatchMode on, no env override.
        calls.clear()
        go(SSHRunner("n1", username="admin").run("ls"))
        argv, env = calls[0]
        assert argv[0] == "ssh" and "BatchMode=yes" in argv and env is None

    def test_runner_for_plumbs_password(self):
        r = runner_for({"ssh": {"username": "u", "password": "pw"}}, "n3")
        assert isinstance(r, SSHRunner) and r.password == "pw"

    def test_store_redacts_ssh_password(self):
        # The whole point of the SSHPASS-env design is that the secret
        # never lands on an observable surface — including the store's
        # test.json artifact.
        from jepsen_etcd_demo_tpu.store.store import _jsonable_test

        out = _jsonable_test({"ssh": {"username": "u", "password": "pw"},
                              "name": "t"})
        assert out["ssh"] == {"username": "u", "password": "<redacted>"}
        # No password (or key auth): dict passes through untouched.
        out = _jsonable_test({"ssh": {"username": "u", "password": None}})
        assert out["ssh"]["password"] is None


# --- RecordingRunner: iptables + DB orchestration command assembly ---------

class RecordingRunner(Runner):
    def __init__(self, node: str, log: list):
        self.node = node
        self.log = log

    async def run(self, cmd: str, su: bool = False, check: bool = True,
                  timeout_s: float = 120.0) -> CommandResult:
        self.log.append((self.node, cmd, su))
        return CommandResult(["sh", "-c", cmd], 0, "", "")


def recording_test(nodes, log):
    """Test map whose runner_for produces RecordingRunners."""
    import jepsen_etcd_demo_tpu.nemesis.partition as part

    return {"nodes": nodes, "_log": log}


class TestPartitionCommands:
    def _run_nemesis(self, op_f):
        from jepsen_etcd_demo_tpu.ops.op import Op
        import jepsen_etcd_demo_tpu.nemesis.partition as part

        log = []
        test = {"nodes": ["n1", "n2", "n3", "n4", "n5"]}
        nem = PartitionRandomHalves(seed=7)

        def fake_runner_for(t, node):
            return RecordingRunner(node, log)

        orig = part.runner_for
        part.runner_for = fake_runner_for
        try:
            go(nem.invoke(test, Op(type="invoke", f=op_f, value=None,
                                   process="nemesis")))
        finally:
            part.runner_for = orig
        return log

    def test_partition_drops_both_directions_with_sudo(self):
        log = self._run_nemesis("start")
        drops = [(n, c) for n, c, su in log if "iptables -A INPUT" in c]
        assert all(su for _, _, su in log)
        # Every cross-half pair appears once per direction: minority(2) x
        # majority(3) x 2 directions = 12 DROP rules on 5 nodes.
        assert len(drops) == 12
        nodes_with_rules = {n for n, _ in drops}
        assert nodes_with_rules == {"n1", "n2", "n3", "n4", "n5"}
        assert all("-j DROP" in c and "-s " in c for _, c in drops)

    def test_heal_flushes_all_nodes(self):
        log = self._run_nemesis("stop")
        flushes = [n for n, c, su in log if "iptables -F" in c]
        assert sorted(flushes) == ["n1", "n2", "n3", "n4", "n5"]


class TestEtcdDBCommands:
    def test_setup_installs_and_starts_with_cluster_flags(self):
        from jepsen_etcd_demo_tpu.db.etcd import EtcdDB, initial_cluster

        log = []
        r = RecordingRunner("n2", log)
        db = EtcdDB(settle_s=0.0)
        go(db.setup({"nodes": ["n1", "n2", "n3"]}, r, "n2"))
        joined = " && ".join(c for _, c, _ in log)
        assert "storage.googleapis.com/etcd/v3.1.5" in joined   # ref :162
        assert "--strip-components=1" in joined
        assert "--name n2" in joined
        assert "--listen-peer-urls http://n2:2380" in joined
        assert "--listen-client-urls http://n2:2379" in joined
        assert "--initial-cluster-state new" in joined
        assert initial_cluster(["n1", "n2", "n3"]) in joined
        assert "/opt/etcd/etcd.pid" in joined

    def test_teardown_stops_and_wipes(self):
        from jepsen_etcd_demo_tpu.db.etcd import EtcdDB

        log = []
        go(EtcdDB().teardown({"nodes": ["n1"]}, RecordingRunner("n1", log),
                             "n1"))
        joined = " && ".join(c for _, c, _ in log)
        assert "kill -9" in joined and "rm -rf /opt/etcd" in joined

    def test_debian_os_setup_commands(self):
        from jepsen_etcd_demo_tpu.db.debian import debian_setup

        log = []
        go(debian_setup(RecordingRunner("n1", log), "n1"))
        joined = " && ".join(c for _, c, _ in log)
        assert "apt-get" in joined


def test_local_runner_upload_download_roundtrip(tmp_path):
    """Runner transfer symmetry: LocalRunner implements the same
    upload/download surface as SSHRunner (db/LogFiles collection works in
    local mode)."""
    import asyncio
    from jepsen_etcd_demo_tpu.control.runner import LocalRunner

    src = tmp_path / "src.txt"
    src.write_text("log line\n")
    r = LocalRunner("n1")

    async def go():
        await r.upload(str(src), str(tmp_path / "up.txt"))
        await r.download(str(tmp_path / "up.txt"),
                         str(tmp_path / "down.txt"), check=True)

    asyncio.run(go())
    assert (tmp_path / "down.txt").read_text() == "log line\n"


class TestClockSkewCommands:
    """ClockSkewNemesis: date-shift command assembly + inverse restore."""

    def _nemesis_log(self, ops):
        from jepsen_etcd_demo_tpu.nemesis import clock as clk
        from jepsen_etcd_demo_tpu.ops.op import Op

        log = []
        test = {"nodes": ["n1", "n2", "n3", "n4", "n5"]}
        nem = clk.ClockSkewNemesis(seed=7, max_skew_s=30)

        orig = clk.runner_for
        clk.runner_for = lambda t, node: RecordingRunner(node, log)
        try:
            for f in ops:
                go(nem.invoke(test, Op(type="invoke", f=f, value=None,
                                       process="nemesis")))
        finally:
            clk.runner_for = orig
        return log

    def test_start_shifts_subset_with_sudo(self):
        log = self._nemesis_log(["start"])
        assert log and all(su for _, _, su in log)
        assert all("date -s @$(( $(date +%s) +" in c for _, c, _ in log)
        assert len({n for n, _, _ in log}) == len(log)  # one shift per node

    def test_stop_applies_inverse_deltas(self):
        log = self._nemesis_log(["start", "stop"])
        shifts = {}
        for n, c, _ in log:
            delta = int(c.split("+")[-1].rstrip(" )"))
            shifts.setdefault(n, []).append(delta)
        for n, ds in shifts.items():
            assert len(ds) == 2 and ds[0] + ds[1] == 0, (n, ds)

    def test_strobe_runs_oscillation_with_monotonic_restore(self):
        """ClockStrobeNemesis (jepsen's strobe-clock): one shell program
        per node that oscillates +/-delta and then restores the wall
        clock from the MONOTONIC clock under an EXIT trap — `date -s`
        truncation would otherwise walk the clock ~2*cycles*period_s
        behind real time, and an interrupted burst must still restore."""
        from jepsen_etcd_demo_tpu.nemesis import clock as clk
        from jepsen_etcd_demo_tpu.ops.op import Op

        log = []
        test = {"nodes": ["n1", "n2", "n3", "n4", "n5"]}
        nem = clk.ClockStrobeNemesis(seed=7, max_skew_s=8, cycles=5)
        orig = clk.runner_for
        clk.runner_for = lambda t, node: RecordingRunner(node, log)
        try:
            op = go(nem.invoke(test, Op(type="invoke", f="start",
                                        value=None, process="nemesis")))
        finally:
            clk.runner_for = orig
        assert log and all(su for _, _, su in log)
        for node, cmd, _ in log:
            assert "for i in $(seq 5)" in cmd
            assert cmd.count("date -s @$(( $(date +%s) + ") == 1
            assert cmd.count("date -s @$(( $(date +%s) - ") == 1
            # Monotonic-anchored restore under a trap: t0 + elapsed
            # uptime, applied however the loop exits.
            assert "/proc/uptime" in cmd
            assert "trap restore EXIT" in cmd
            assert "t0 + (m1 - m0)" in cmd
            assert node in op.value["strobed"]
            assert op.value["strobed"][node]["cycles"] == 5
        # The burst self-restores: there is nothing recorded to invert.
        assert nem.applied == {}


def test_pick_nemesis_registry():
    from jepsen_etcd_demo_tpu.compose import pick_nemesis
    from jepsen_etcd_demo_tpu.clients.fake_kv import FakeKVStore
    from jepsen_etcd_demo_tpu.nemesis import (
        ClockSkewNemesis, FakeClockSkewNemesis, FakePartitionNemesis,
        NoopNemesis, PartitionRandomHalves)

    store = FakeKVStore()
    assert isinstance(pick_nemesis({}, store=store), FakePartitionNemesis)
    assert isinstance(pick_nemesis({"nemesis": "clock"}, store=store),
                      FakeClockSkewNemesis)
    assert isinstance(pick_nemesis({"nemesis": "noop"}, store=store),
                      NoopNemesis)
    with pytest.raises(ValueError, match="fake"):
        pick_nemesis({"nemesis": "kill"}, store=store)
    assert isinstance(pick_nemesis({}), PartitionRandomHalves)
    assert isinstance(pick_nemesis({"nemesis": "clock"}), ClockSkewNemesis)
    from jepsen_etcd_demo_tpu.nemesis import (ClockStrobeNemesis,
                                              PartitionBridge,
                                              PartitionIsolatedNode,
                                              PartitionMajoritiesRing)

    assert isinstance(pick_nemesis({"nemesis": "clock-strobe"}),
                      ClockStrobeNemesis)
    assert isinstance(pick_nemesis({"nemesis": "partition-node"}),
                      PartitionIsolatedNode)
    assert isinstance(pick_nemesis({"nemesis": "partition-bridge"}),
                      PartitionBridge)
    assert isinstance(pick_nemesis({"nemesis": "partition-ring"}),
                      PartitionMajoritiesRing)
    with pytest.raises(ValueError, match="unknown"):
        pick_nemesis({"nemesis": "sharknado"})


class TestEtcdQueue:
    """The etcd v2 atomic in-order-keys queue recipe (EtcdClient
    enqueue/dequeue) against the stub, including the indeterminacy
    protocol the linearizability encoding depends on."""

    def test_enqueue_dequeue_fifo_order(self, stub):
        async def t():
            srv, client = stub, EtcdClient(stub.url)
            await client.enqueue("q", 7)
            await client.enqueue("q", 8)
            assert await client.dequeue("q") == "7"
            assert await client.dequeue("q") == "8"
            posts = [r for r in srv.requests if r["method"] == "POST"]
            assert [p["form"]["value"] for p in posts] == ["7", "8"]
            deletes = [r for r in srv.requests if r["method"] == "DELETE"]
            assert all("prevIndex" in d["params"] for d in deletes)
            await client.close()
        go(t())

    def test_dequeue_empty_raises_notfound(self, stub):
        async def t():
            srv, client = stub, EtcdClient(stub.url)
            with pytest.raises(NotFound):
                await client.dequeue("q")
            await client.enqueue("q", 1)
            assert await client.dequeue("q") == "1"
            with pytest.raises(NotFound):
                await client.dequeue("q")
            await client.close()
        go(t())

    def test_lost_delete_ack_is_indeterminate_with_claimed_value(self, stub):
        """DELETE applied but the response lost: once the claim was SENT
        the removal is indeterminate forever, so the client must surface
        IndeterminateDequeue with the claimed value (the one encodable
        indeterminate-dequeue shape, models/queues.py)."""
        from jepsen_etcd_demo_tpu.clients.etcd import IndeterminateDequeue

        async def t():
            srv, client = stub, EtcdClient(stub.url)
            await client.enqueue("q", 5)
            srv.drop_delete_response_once = True
            with pytest.raises(IndeterminateDequeue) as ei:
                await client.dequeue("q")
            assert ei.value.value == "5"
            await client.close()
        go(t())


def test_swap_retry_exhaustion_is_determinate_fail():
    """64 determinate CAS failures = the swap definitely never applied:
    RetriesExhausted is a ClientError (-> :fail), NOT a Timeout (-> :info)
    — spurious open-forever ops multiply the checker's search space."""
    import asyncio

    from jepsen_etcd_demo_tpu.clients.base import (ClientError,
                                                   RetriesExhausted, Timeout)
    from jepsen_etcd_demo_tpu.clients.fake_kv import FakeKVStore

    assert issubclass(RetriesExhausted, ClientError)
    assert not issubclass(RetriesExhausted, Timeout)

    async def scenario():
        cluster = FakeKVStore(["n1"], seed=1)
        await cluster.reset("n1", "k", "0")

        async def contended_swap():
            # fn returns a NEW value each call, but another writer always
            # sneaks in between read and cas: force it by mutating under
            # the swap's feet via the fn side effect.
            def fn(cur):
                # Sabotage: bump the stored value so the upcoming CAS
                # (predicated on `cur`) must fail determinately.
                cluster.data["k"] = str(int(cluster.data["k"]) + 1)
                return str(int(cur) + 100)

            await cluster.swap("n1", "k", fn)

        with pytest.raises(RetriesExhausted):
            await contended_swap()

    asyncio.run(scenario())


def test_fake_partitioned_op_waits_the_client_timeout():
    """A partitioned op reports its timeout only after timeout_s, as a
    real client would: with 0 a thread turns the whole partition into a
    stream of forever-pending ops (one per generator tick)."""
    import time

    from jepsen_etcd_demo_tpu.clients.fake_kv import FakeKVStore

    async def run(store):
        store.isolate({"n1"})
        t0 = time.monotonic()
        with pytest.raises(Timeout):
            await store.reset("n1", "k", 1)
        return time.monotonic() - t0

    assert asyncio.run(run(FakeKVStore(seed=1, timeout_s=0.2))) >= 0.2
    assert asyncio.run(run(FakeKVStore(seed=1, timeout_s=0))) < 0.2
    # The default is the reference client's 5 s, and the fake
    # composition (CLI --fake and campaigns alike) keeps it.
    from jepsen_etcd_demo_tpu.compose import fake_test
    assert FakeKVStore().timeout_s == 5.0
    assert fake_test({"no_nemesis": True})["fake_store"].timeout_s == 5.0
