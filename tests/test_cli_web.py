"""CLI + web server tests (VERDICT round-1 item 7): the jepsen exit-code
contract (0 valid / 1 invalid), the analyze re-check round-trip, argparse
validation parity with the reference's cli-opts
(/root/reference/src/jepsen/etcdemo.clj:177-190), and a web smoke test."""

from __future__ import annotations

import json
import threading
import urllib.parse
import urllib.request
from http.server import ThreadingHTTPServer

import pytest

from jepsen_etcd_demo_tpu.cli.main import build_parser, main
from jepsen_etcd_demo_tpu.store import Store
from jepsen_etcd_demo_tpu.web.server import make_handler


def _run_cli(tmp_path, *extra, workload="register", time_limit="1.5"):
    # --recovery-wait 0.2: the fake store heals instantly, so the
    # reference-default 10 s quiet window is pure test wall clock.
    return main(["test", "-w", workload, "--fake",
                 "--time-limit", time_limit, "--rate", "150",
                 "--recovery-wait", "0.2",
                 "--store", str(tmp_path / "store"), "--seed", "11",
                 *extra])


class TestParser:
    def test_workload_is_required(self, capsys):
        with pytest.raises(SystemExit) as e:
            build_parser().parse_args(["test"])
        assert e.value.code == 2
        assert "--workload" in capsys.readouterr().err

    def test_workload_validated_against_registry(self, capsys):
        with pytest.raises(SystemExit):
            build_parser().parse_args(["test", "-w", "nope"])
        assert "invalid choice" in capsys.readouterr().err

    def test_rate_must_be_positive(self, capsys):
        # reference validator: "must be a positive number" (:183)
        with pytest.raises(SystemExit):
            build_parser().parse_args(["test", "-w", "register",
                                       "-r", "-3"])
        assert "positive" in capsys.readouterr().err

    def test_ops_per_key_must_be_positive_int(self):
        with pytest.raises(SystemExit):
            build_parser().parse_args(["test", "-w", "register",
                                       "--ops-per-key", "0"])

    def test_defaults_match_reference(self):
        a = build_parser().parse_args(["test", "-w", "register"])
        assert a.quorum is False          # :179
        assert a.rate == 10.0             # :180
        assert a.ops_per_key == 100       # :184
        assert a.nodes == "n1,n2,n3,n4,n5"  # noop-test defaults [dep]
        # The post-heal quiet window keeps the reference's 10 s default;
        # tests shrink it explicitly (the fake heals instantly).
        assert a.recovery_wait == 10.0
        # The fake cluster's partitioned ops wait the reference client's
        # 5000 ms timeout (clients/etcd.py) before reporting :info.
        from jepsen_etcd_demo_tpu.cli.main import _test_opts
        from jepsen_etcd_demo_tpu.compose import fake_test
        assert fake_test(_test_opts(a))["fake_store"].timeout_s == 5.0

    def test_password_flag_reaches_ssh_opts(self):
        # jepsen's standard ssh opt set includes password auth and a
        # per-run port (noop-test ssh map [dep]); plumbed through to
        # runner_for's ssh dict (control/runner.py sshpass transport,
        # SSHRunner port).
        from jepsen_etcd_demo_tpu.cli.main import _test_opts
        a = build_parser().parse_args(
            ["test", "-w", "register", "--password", "pw",
             "--username", "u", "--ssh-port", "2222"])
        opts = _test_opts(a)
        assert opts["ssh"] == {"username": "u", "private_key": None,
                               "password": "pw", "port": 2222}
        a = build_parser().parse_args(["test", "-w", "register"])
        assert _test_opts(a)["ssh"]["port"] == 22


class TestExitContract:
    def test_valid_run_exits_zero_and_stores(self, tmp_path, capsys):
        rc = _run_cli(tmp_path)
        out = capsys.readouterr().out.strip().splitlines()[-1]
        assert rc == 0
        assert json.loads(out)["valid"] is True
        runs = Store(str(tmp_path / "store")).runs()
        assert len(runs) == 1
        assert (runs[0].path / "history.jsonl").exists()
        assert (runs[0].path / "jepsen.log").exists()

    def test_invalid_run_exits_one(self, tmp_path, capsys):
        rc = _run_cli(tmp_path, "--stale-read-prob", "0.8", "--no-nemesis",
                      time_limit="1.0")
        assert rc == 1
        assert json.loads(
            capsys.readouterr().out.strip().splitlines()[-1])["valid"] \
            is False

    def test_test_count_runs_n_and_keeps_separate_logs(self, tmp_path,
                                                       capsys):
        rc = _run_cli(tmp_path, "--test-count", "2", time_limit="1.0")
        assert rc == 0
        runs = Store(str(tmp_path / "store")).runs()
        assert len(runs) == 2
        # Regression (round-1 advisor): the log handler must be detached
        # per run — run 1's log must not contain run 2's lines.
        log1 = (runs[0].path / "jepsen.log").read_text()
        log2 = (runs[1].path / "jepsen.log").read_text()
        assert "setting up" in log1 and "setting up" in log2
        assert log1.count("=== valid:") == 1
        assert log2.count("=== valid:") == 1

    def test_live_port_serves_plane_during_run(self, tmp_path):
        """ISSUE 8 acceptance: with a run in flight under --live-port,
        the SAME process serves /metrics (Prometheus text with
        jepsen_tpu_* series and run_in_flight 1) and /healthz — and the
        server is gone once the run ends."""
        import socket
        import time as time_mod
        import urllib.error
        import urllib.request

        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            port = s.getsockname()[1]
        seen = {}

        def poll():
            deadline = time_mod.monotonic() + 20
            while time_mod.monotonic() < deadline and "metrics" not in seen:
                try:
                    body = urllib.request.urlopen(
                        f"http://127.0.0.1:{port}/metrics",
                        timeout=2).read().decode()
                    if "jepsen_tpu_run_in_flight 1" in body:
                        seen["metrics"] = body
                        seen["healthz"] = json.load(urllib.request.urlopen(
                            f"http://127.0.0.1:{port}/healthz", timeout=2))
                except (urllib.error.URLError, OSError, ValueError):
                    pass
                time_mod.sleep(0.05)

        poller = threading.Thread(target=poll, daemon=True)
        poller.start()
        rc = _run_cli(tmp_path, "--live-port", str(port), time_limit="2.5")
        poller.join(timeout=25)
        assert rc == 0
        assert "metrics" in seen, "live plane never answered mid-run"
        assert "jepsen_tpu_up 1" in seen["metrics"]
        assert "jepsen_tpu_runner_ops_ok" in seen["metrics"]
        assert seen["healthz"]["run_in_flight"] is True
        # Shut down with the test loop: the port no longer answers.
        with pytest.raises((urllib.error.URLError, OSError)):
            urllib.request.urlopen(f"http://127.0.0.1:{port}/healthz",
                                   timeout=2)


class TestAnalyze:
    def test_analyze_roundtrip_agrees(self, tmp_path, capsys):
        assert _run_cli(tmp_path) == 0
        run_dir = Store(str(tmp_path / "store")).runs()[0].path
        capsys.readouterr()
        rc = main(["analyze", str(run_dir), "-w", "register"])
        assert rc == 0
        assert json.loads(
            capsys.readouterr().out.strip().splitlines()[-1])["valid"] \
            is True

    def test_analyze_flags_corruption(self, tmp_path, capsys):
        rc = _run_cli(tmp_path, "--stale-read-prob", "0.8", "--no-nemesis",
                      time_limit="1.0")
        assert rc == 1
        run_dir = Store(str(tmp_path / "store")).runs()[0].path
        capsys.readouterr()
        assert main(["analyze", str(run_dir), "-w", "register"]) == 1
        # analyze re-writes results + witness artifacts into the run dir
        assert list(run_dir.glob("linear-*.json"))

    def test_analyze_oracle_backend(self, tmp_path, capsys):
        assert _run_cli(tmp_path, time_limit="1.0") == 0
        run_dir = Store(str(tmp_path / "store")).runs()[0].path
        assert main(["analyze", str(run_dir), "-w", "register",
                     "--backend", "oracle"]) == 0


class TestWebServer:
    def test_telemetry_page_renders_spans_and_metrics(self, tmp_path):
        """The per-run telemetry page (obs/ artifacts): the index links
        it, the page renders the phase span tree and the metric table,
        and missing/escaping paths 404."""
        import urllib.error

        assert _run_cli(tmp_path, time_limit="1.0") == 0
        store_root = str(tmp_path / "store")
        httpd = ThreadingHTTPServer(("127.0.0.1", 0),
                                    make_handler(store_root))
        threading.Thread(target=httpd.serve_forever, daemon=True).start()
        port = httpd.server_address[1]
        try:
            idx = urllib.request.urlopen(
                f"http://127.0.0.1:{port}/").read().decode()
            assert "/telemetry/" in idx
            rel = Store(store_root).runs()[0].path.relative_to(
                Store(store_root).root)
            page = urllib.request.urlopen(
                f"http://127.0.0.1:{port}/telemetry/"
                f"{urllib.parse.quote(str(rel))}").read().decode()
            # Span tree: the run phases render nested.
            for phase in ("setup", "run", "check", "store"):
                assert f"<b>{phase}</b>" in page
            # Metric table: the well-known phase keys render.
            assert "wgl.compile_s" in page
            assert "wgl.execute_s" in page
            assert "encode.encode_s" in page
            assert "runner.op_latency_s" in page
            # No telemetry / path escape -> 404, not a traceback.
            for bad in ("no/such/run", "..%2F..%2Fetc"):
                with pytest.raises(urllib.error.HTTPError) as e:
                    urllib.request.urlopen(
                        f"http://127.0.0.1:{port}/telemetry/{bad}")
                assert e.value.code == 404
        finally:
            httpd.shutdown()

    def test_index_and_static_serving(self, tmp_path, capsys):
        assert _run_cli(tmp_path, time_limit="1.0") == 0
        store_root = str(tmp_path / "store")
        httpd = ThreadingHTTPServer(("127.0.0.1", 0),
                                    make_handler(store_root))
        threading.Thread(target=httpd.serve_forever, daemon=True).start()
        port = httpd.server_address[1]
        try:
            idx = urllib.request.urlopen(
                f"http://127.0.0.1:{port}/").read().decode()
            assert "test runs" in idx
            assert "True" in idx       # verdict rendered
            rel = Store(store_root).runs()[0].path.relative_to(
                Store(store_root).root)
            quoted = urllib.parse.quote(str(rel))
            hist = urllib.request.urlopen(
                f"http://127.0.0.1:{port}/files/{quoted}/history.jsonl"
            ).read().decode()
            assert '"invoke"' in hist
        finally:
            httpd.shutdown()


def test_nodes_file_overrides_nodes(tmp_path):
    from jepsen_etcd_demo_tpu.cli.main import build_parser, _test_opts

    nf = tmp_path / "nodes.txt"
    nf.write_text("na\nnb\n\nnc\n")
    args = build_parser().parse_args(
        ["test", "-w", "register", "--nodes-file", str(nf)])
    assert _test_opts(args)["nodes"] == ["na", "nb", "nc"]
    args = build_parser().parse_args(["test", "-w", "register",
                                      "--nodes", "x1,x2"])
    assert _test_opts(args)["nodes"] == ["x1", "x2"]


def test_analyze_autodetects_workload_and_model(tmp_path, capsys):
    """`analyze <run>` with no -w/--model re-checks under the workload the
    run's test.json records (a queue run must NOT be checked as a
    cas-register)."""
    store = str(tmp_path / "store")
    assert main(["test", "-w", "queue", "--fake", "--no-nemesis",
                 "--time-limit", "1.0", "--recovery-wait", "0.2", "--rate", "150",
                 "--store", store, "--seed", "41"]) == 0
    run_dir = str((tmp_path / "store" / "latest").resolve())
    assert main(["analyze", run_dir]) == 0
    import json as _json
    out = _json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["valid"] is True


def test_corpus_replay_batches_all_runs(tmp_path, capsys):
    """`corpus` re-checks every stored run's per-key histories in one
    batched launch (BASELINE configs[4]): a healthy store exits 0; adding
    a corrupted run flips the corpus verdict to 1 and names the run."""
    import json as _json

    store = str(tmp_path / "store")
    assert main(["test", "-w", "register", "--fake", "--no-nemesis",
                 "--time-limit", "1.2", "--recovery-wait", "0.2", "--rate", "150",
                 "--store", store, "--seed", "21"]) == 0
    assert main(["test", "-w", "register", "--fake", "--no-nemesis",
                 "--time-limit", "1.2", "--recovery-wait", "0.2", "--rate", "150",
                 "--store", store, "--seed", "22"]) == 0
    rc = main(["corpus", store])
    out = _json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0 and out["valid"] is True
    assert out["runs"] == 2 and out["keys"] >= 2

    assert main(["test", "-w", "register", "--fake", "--no-nemesis",
                 "--time-limit", "1.2", "--recovery-wait", "0.2", "--rate", "150",
                 "--store", store, "--seed", "23",
                 "--stale-read-prob", "0.8"]) == 1
    rc = main(["corpus", store])
    out = _json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 1 and out["valid"] is False
    assert out["invalid"] and out["runs"] == 3


def test_corpus_replay_routes_models_by_workload(tmp_path, capsys):
    """A store mixing register and queue runs corpus-replays each run
    under its own model (test.json workload -> CORPUS_MODELS); a buggy
    queue run flips the verdict and is named with its model."""
    import json as _json

    store = str(tmp_path / "store")
    assert main(["test", "-w", "register", "--fake", "--no-nemesis",
                 "--time-limit", "1.0", "--recovery-wait", "0.2", "--rate", "150",
                 "--store", store, "--seed", "31"]) == 0
    assert main(["test", "-w", "queue", "--fake", "--no-nemesis",
                 "--time-limit", "1.0", "--recovery-wait", "0.2", "--rate", "150",
                 "--store", store, "--seed", "32"]) == 0
    rc = main(["corpus", store])
    out = _json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0 and out["valid"] is True and out["runs"] == 2
    # Runs persist their device-plane tensors; corpus loads them directly.
    assert out["from_tensors"] == out["keys"] > 0
    # --reencode (the post-encoder-fix path) must reach the same verdict.
    rc = main(["corpus", store, "--reencode"])
    out2 = _json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0 and out2["valid"] is True
    assert out2["from_tensors"] == 0 and out2["keys"] == out["keys"]

    # Whole-history workloads join the corpus too (one tensor per run).
    assert main(["test", "-w", "mutex", "--fake", "--no-nemesis",
                 "--time-limit", "1.0", "--recovery-wait", "0.2", "--rate", "150",
                 "--store", store, "--seed", "34"]) == 0
    rc = main(["corpus", store])
    out = _json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0 and out["valid"] is True and out["runs"] == 3

    assert main(["test", "-w", "queue", "--fake", "--no-nemesis",
                 "--time-limit", "1.0", "--recovery-wait", "0.2", "--rate", "150",
                 "--store", store, "--seed", "33",
                 "--reorder-prob", "0.7"]) == 1
    rc = main(["corpus", store])
    out = _json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 1 and out["valid"] is False
    assert any(e["model"] == "fifo-queue" for e in out["invalid"])


def test_index_shows_failure_detail(tmp_path):
    """The run index's detail column surfaces WHY an invalid run failed
    (the per-key failing op from the witness)."""
    assert _run_cli(tmp_path, "--stale-read-prob", "0.8",
                    "--no-nemesis", time_limit="1.0") == 1
    httpd = ThreadingHTTPServer(("127.0.0.1", 0),
                                make_handler(str(tmp_path / "store")))
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    port = httpd.server_address[1]
    try:
        idx = urllib.request.urlopen(
            f"http://127.0.0.1:{port}/").read().decode()
        assert "False" in idx
        assert "key " in idx        # detail names the failing key
        assert " ops" in idx        # perf count rendered
    finally:
        httpd.shutdown()


def test_index_and_telemetry_show_sweep_columns(tmp_path):
    """ISSUE 3 satellite: the run index gains sweep-mode and
    live-tile-ratio columns (next to check-eps / pad-waste), and the
    per-run telemetry page mirrors them in its summary strip — fed from
    the wgl.sweep_* counters and wgl.live_tile_ratio gauge in
    metrics.json."""
    run = tmp_path / "store" / "fake" / "20260803T000000"
    run.mkdir(parents=True)
    (run / "results.json").write_text(json.dumps({"valid": True}))
    (run / "telemetry.jsonl").write_text("")
    (run / "metrics.json").write_text(json.dumps({"metrics": {
        "wgl.sweep_steps_sparse": {"type": "counter", "value": 120},
        "wgl.sweep_steps_dense": {"type": "counter", "value": 40},
        "wgl.live_tile_ratio": {"type": "gauge", "last": 0.0625,
                                "min": 0.01, "max": 0.2, "n": 5},
    }}))
    httpd = ThreadingHTTPServer(("127.0.0.1", 0),
                                make_handler(str(tmp_path / "store")))
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    port = httpd.server_address[1]
    try:
        idx = urllib.request.urlopen(
            f"http://127.0.0.1:{port}/").read().decode()
        assert "<th>sweep</th>" in idx
        assert "<th>live tiles</th>" in idx
        assert "mixed (75% sp)" in idx
        assert "6.2%" in idx
        tele = urllib.request.urlopen(
            f"http://127.0.0.1:{port}/telemetry/fake/20260803T000000"
        ).read().decode()
        assert "mixed (75% sp)" in tele
        assert "live tiles" in tele
    finally:
        httpd.shutdown()


def test_index_shows_whole_history_failure_detail(tmp_path):
    """A failed mutex (whole-history) run's index row names the failing op
    — there are no per-key results for these workloads."""
    store = str(tmp_path / "store")
    assert main(["test", "-w", "mutex", "--fake", "--no-nemesis",
                 "--time-limit", "1.0", "--recovery-wait", "0.2", "--rate", "150",
                 "--store", store, "--seed", "63",
                 "--lost-write-prob", "0.5"]) == 1
    httpd = ThreadingHTTPServer(("127.0.0.1", 0), make_handler(store))
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    try:
        idx = urllib.request.urlopen(
            f"http://127.0.0.1:{httpd.server_address[1]}/").read().decode()
        assert "acquire" in idx or "release" in idx
    finally:
        httpd.shutdown()
