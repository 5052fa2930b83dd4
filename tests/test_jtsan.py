"""jtsan (ISSUE 14): the JTL501-506 interprocedural concurrency rules,
the `# jtsan:` annotation/wrap-name verification, the contracts.json
sync section, --changed dirtiness for the serve-era scopes, the tier-1
wall-clock guard, and the static-vs-runtime cross-validation: every
lock order the sanitizer witnesses under serve-daemon load must be an
edge the static model predicted, and a deliberately injected inversion
is caught by BOTH halves."""

from __future__ import annotations

import json
import subprocess
import sys
import threading
import time
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent
FIXTURES = REPO / "tests" / "lint_fixtures"
PKG = REPO / "jepsen_etcd_demo_tpu"

from jepsen_etcd_demo_tpu import analysis  # noqa: E402
from jepsen_etcd_demo_tpu.analysis import cli as lint_cli  # noqa: E402
from jepsen_etcd_demo_tpu.analysis.core import ProjectRule  # noqa: E402
from jepsen_etcd_demo_tpu.analysis.flow.index import FlowIndex  # noqa: E402
from jepsen_etcd_demo_tpu.analysis.flow.sync import sync_model  # noqa: E402
from jepsen_etcd_demo_tpu.obs import sync as obs_sync  # noqa: E402


def _lint_sync(dirname, rule_id):
    d = FIXTURES / dirname
    rules = analysis.all_rules()
    return analysis.run_lint([d], rules={rule_id: rules[rule_id]},
                             root=d)


# (rule id, positive fixture dir, expected (file, line) findings,
# negative fixture dir). Golden against the checked-in mini-projects —
# editing a fixture means re-blessing deliberately, same contract as
# GOLDEN/FLOW_GOLDEN in test_lint.py.
SYNC_GOLDEN = [
    ("JTL501", "sync_race_pos", [("engine.py", 20)], "sync_race_neg"),
    ("JTL502", "sync_order_pos", [("locker_a.py", 14)],
     "sync_order_neg"),
    ("JTL503", "sync_cta_pos", [("registry.py", 18)], "sync_cta_neg"),
    ("JTL504", "sync_block_pos", [("worker.py", 16)], "sync_block_neg"),
    ("JTL505", "sync_leak_pos", [("daemon.py", 19), ("daemon.py", 28)],
     "sync_leak_neg"),
]


@pytest.mark.parametrize("rule_id,pos,locs,neg", SYNC_GOLDEN,
                         ids=[g[0] for g in SYNC_GOLDEN])
def test_sync_rule_fixture_golden(rule_id, pos, locs, neg):
    res = _lint_sync(pos, rule_id)
    got = sorted((f.path, f.line) for f in res.findings)
    assert got == sorted(locs), (
        f"{rule_id} on {pos}: expected {sorted(locs)}, got {got}:\n"
        + analysis.format_text(res.findings))
    assert all(f.rule == rule_id and f.fingerprint
               for f in res.findings)
    neg_res = _lint_sync(neg, rule_id)
    assert not neg_res.findings, (
        f"{rule_id} false positives on {neg}:\n"
        + analysis.format_text(neg_res.findings))


def test_sync_rules_registered_with_fixture_dirs():
    """The 5xx family rides the same fixture-pair enforcement as the
    4xx rules (JTL506, the contract gate, is pinned by its own tests
    below — like JTL406)."""
    sync_ids = {i for i in analysis.all_rules() if i.startswith("JTL5")}
    assert sync_ids == {"JTL501", "JTL502", "JTL503", "JTL504",
                       "JTL505", "JTL506"}
    assert {g[0] for g in SYNC_GOLDEN} == sync_ids - {"JTL506"}
    for r in (analysis.all_rules()[i] for i in sorted(sync_ids)):
        assert isinstance(r, ProjectRule)
    for _rid, pos, _locs, neg in SYNC_GOLDEN:
        assert (FIXTURES / pos).is_dir() and (FIXTURES / neg).is_dir()


def test_wfq_incident_regression_fixture():
    """The PR 13-era incident class: dispatch rotates the WFQ slot
    under the queue condition, stats() reads the rotation under a
    SEPARATE stats lock — each side individually locked, lock-sets
    disjoint. JTL501 names both locks."""
    res = _lint_sync("sync_wfq_pos", "JTL501")
    assert [(f.path, f.line) for f in res.findings] \
        == [("scheduler.py", 23)]
    msg = res.findings[0].message
    assert "_rotation" in msg
    assert "_cond" in msg and "_stats_lock" in msg
    assert "no common lock-set" in msg


def test_jtsan_clean_on_real_tree():
    """Acceptance: JTL501-506 over the real package report ZERO
    findings — the real races/leaks this pass surfaced were FIXED
    (scheduler tenant-latency, model_for check-then-act, session
    finalize-under-lock, the daemon session-shutdown gap, the metric
    snapshot reads), and what remains is justified inline."""
    rules = {i: r for i, r in analysis.all_rules().items()
             if i.startswith("JTL5")}
    res = analysis.run_lint([PKG], rules=rules, root=REPO)
    assert not res.findings, analysis.format_text(res.findings)
    # The deliberate lock-free fast path + self-terminating pump are
    # suppressed WITH justifications, not silently.
    assert res.suppressed, "expected justified JTL5xx suppressions"
    for f in res.suppressed:
        assert f.rule.startswith("JTL5")


def test_annotation_verification_is_not_trust(tmp_path):
    """JTL506: unknown directives, unbound annotations, and dangling
    references are findings — a `# jtsan:` annotation is VERIFIED
    against the tree, never trusted."""
    (tmp_path / "m.py").write_text(
        "import threading\n\n\n"
        "class C:\n"
        "    def __init__(self):\n"
        "        self._lock = threading.Lock()\n"
        "        # jtsan: guarded-by=self._nope\n"
        "        self.items = {}\n\n"
        "    # jtsan: returns=NoSuchClass\n"
        "    def get(self):\n"
        "        return self.items\n\n\n"
        "# jtsan: frobnicate=yes\n"
        "X = 1\n\n"
        "# jtsan: hb=self.done\n"
        "Y = 2\n")
    rules = analysis.all_rules()
    res = analysis.run_lint([tmp_path], rules={"JTL506": rules["JTL506"]},
                            root=tmp_path)
    msgs = sorted(f.message for f in res.findings)
    assert len(msgs) == 4, analysis.format_text(res.findings)
    assert any("guarded-by='self._nope'" in m for m in msgs)
    assert any("unknown class 'NoSuchClass'" in m for m in msgs)
    assert any("unknown jtsan directive `frobnicate`" in m for m in msgs)
    assert any("hb='self.done'" in m for m in msgs)


def test_wrap_name_literal_verified_against_model(tmp_path):
    """JTL506: a maybe_wrap() name literal that drifts from the model's
    canonical lock id is a finding — otherwise a rename silently breaks
    the witnessed-vs-modeled comparison."""
    (tmp_path / "m.py").write_text(
        "import threading\n\n"
        "from jepsen_etcd_demo_tpu.obs.sync import maybe_wrap\n\n\n"
        "class C:\n"
        "    def __init__(self):\n"
        "        self._lock = maybe_wrap(threading.Lock(),\n"
        "                                'wrong.Name._lock')\n")
    rules = analysis.all_rules()
    res = analysis.run_lint([tmp_path], rules={"JTL506": rules["JTL506"]},
                            root=tmp_path)
    assert len(res.findings) == 1, analysis.format_text(res.findings)
    assert "'wrong.Name._lock'" in res.findings[0].message
    assert "'m.C._lock'" in res.findings[0].message
    # The real tree's wrap names all verify (part of the strict gate,
    # asserted here against drift directly).
    real = analysis.run_lint(
        [PKG], rules={"JTL506": rules["JTL506"]}, root=REPO)
    assert not [f for f in real.findings
                if "wrap name" in f.message], \
        analysis.format_text(real.findings)


def test_contracts_sync_section_checked_in():
    """The checked-in contracts.json carries the regenerated sync
    section: canonical lock ids, thread roots, guarded structures with
    the threads that touch them, and the may-happen lock-order edges
    (the JTL406 byte-diff gates content drift; JTL506 names a deleted
    section)."""
    c = json.loads((REPO / "contracts.json").read_text(encoding="utf-8"))
    sync = c["sync"]
    assert "serve.scheduler.CoalescingScheduler._lock" in sync["locks"]
    assert sync["locks"]["serve.scheduler.CoalescingScheduler._lock"] \
        == "condition"
    assert "thread:serve.scheduler.CoalescingScheduler._run" \
        in sync["threads"]
    assert "handler:web.server.StoreHandler" in sync["threads"]
    g = sync["guarded"]["serve.scheduler.CoalescingScheduler._queues"]
    assert g["lock"] == "serve.scheduler.CoalescingScheduler._lock"
    assert ["serve.scheduler.CoalescingScheduler._lock",
            "obs.metrics.MetricsRegistry._lock"] in sync["order"]
    # Deleting the section is a JTL506 finding on a harness tree.
    model = sync_model(FlowIndex.build(REPO))
    fresh = model.contract_section()
    assert fresh == sync, "sync section stale vs the tree"


def test_sync_section_missing_is_a_finding(tmp_path):
    (tmp_path / "jepsen_etcd_demo_tpu").mkdir()
    (tmp_path / "jepsen_etcd_demo_tpu" / "m.py").write_text("X = 1\n")
    (tmp_path / "contracts.json").write_text("{}\n")
    rules = analysis.all_rules()
    res = analysis.run_lint([tmp_path], rules={"JTL506": rules["JTL506"]},
                            root=tmp_path)
    assert any("no `sync` section" in f.message for f in res.findings), \
        analysis.format_text(res.findings)


def test_strict_lint_wall_clock_with_jtsan():
    """CI/tooling satellite: the FULL strict lint — jtsan's
    interprocedural pass included — stays inside the 5 s tier-1 bound
    PR 8 established; the concurrency model must not eat the budget."""
    t0 = time.monotonic()
    res = analysis.run_lint([PKG], root=REPO)
    wall = time.monotonic() - t0
    assert not res.findings, analysis.format_text(res.findings)
    assert wall < 5.0, f"full lint took {wall:.1f}s — over the bound"


def test_changed_mode_serve_edit_retriggers_sync_rules(tmp_path, capsys):
    """--changed dirtiness satellite: an edit under serve/ (or
    obs/sync.py) dirties the package contract graph and re-runs the
    JTL5xx project rules — the same rule as the flow rules, regressed
    on a scratch git repo."""
    def git(*a):
        subprocess.run(["git", "-c", "user.email=t@t", "-c",
                        "user.name=t", *a], cwd=tmp_path, check=True,
                       capture_output=True)

    (tmp_path / "pyproject.toml").write_text("")
    serve = tmp_path / "jepsen_etcd_demo_tpu" / "serve"
    serve.mkdir(parents=True)
    obs_dir = tmp_path / "jepsen_etcd_demo_tpu" / "obs"
    obs_dir.mkdir()
    clean = (FIXTURES / "sync_race_neg" / "engine.py").read_text()
    racy = (FIXTURES / "sync_race_pos" / "engine.py").read_text()
    (serve / "engine.py").write_text(clean)
    (obs_dir / "sync.py").write_text("TRACE = 0\n")
    git("init")
    git("add", ".")
    git("commit", "-m", "base")
    # Unchanged tree: quiet no-op.
    assert lint_cli.main(["--changed", "HEAD", "--strict",
                          "--no-baseline", "--rules", "JTL501",
                          str(tmp_path)]) == 0
    assert "nothing to lint" in capsys.readouterr().out
    # Edit under serve/: the sync rules re-run and find the race.
    (serve / "engine.py").write_text(racy)
    assert lint_cli.main(["--changed", "HEAD", "--strict",
                          "--no-baseline", "--rules", "JTL501",
                          str(tmp_path)]) == 1
    assert "JTL501" in capsys.readouterr().out
    git("add", ".")
    git("commit", "-m", "racy")
    # Edit ONLY obs/sync.py: the race is in an UNCHANGED file, but the
    # package-graph dirtying re-runs the project rules full-tree.
    (obs_dir / "sync.py").write_text("TRACE = 1\n")
    assert lint_cli.main(["--changed", "HEAD", "--strict",
                          "--no-baseline", "--rules", "JTL501",
                          str(tmp_path)]) == 1
    out = capsys.readouterr().out
    assert "JTL501" in out and "engine.py" in out


def test_lint_report_and_sarif_carry_5xx(capsys):
    """CI/tooling satellite: tools/lint_report.py buckets the real
    tree's JTL5xx suppressions with their justifications (and the
    ledger is healthy — no stale, no justification-free), and --format
    sarif carries the 5xx rule metadata + findings."""
    out = subprocess.run(
        [sys.executable, str(REPO / "tools" / "lint_report.py"),
         "--json"], capture_output=True, text=True, cwd=REPO,
        timeout=180)
    report = json.loads(out.stdout)
    assert out.returncode == 0, out.stdout + out.stderr
    assert report["ok"], report["stale_suppressions"]
    assert report["rules"]["JTL501"]["suppressed"] == 1
    assert "lock-free" in \
        report["rules"]["JTL501"]["suppressions"][0]["justification"]
    assert report["rules"]["JTL505"]["suppressed"] == 2
    for s in report["rules"]["JTL505"]["suppressions"]:
        assert s["justification"]
    rules = {"JTL503": analysis.all_rules()["JTL503"]}
    res = analysis.run_lint([FIXTURES / "sync_cta_pos"], rules=rules,
                            root=FIXTURES / "sync_cta_pos")
    doc = json.loads(analysis.format_sarif(res.findings, rules))
    run = doc["runs"][0]
    assert {r["id"] for r in run["tool"]["driver"]["rules"]} \
        == {"JTL503"}
    assert [r["ruleId"] for r in run["results"]] == ["JTL503"]


# -- runtime sanitizer + cross-validation (the dynamic half) ---------------

def test_maybe_wrap_is_identity_when_disabled(monkeypatch):
    monkeypatch.delenv(obs_sync.SYNC_TRACE_ENV, raising=False)
    lock = threading.Lock()
    assert obs_sync.maybe_wrap(lock, "x.Y._lock") is lock


def test_witnessed_lock_orders_are_predicted_under_serve_load(
        tmp_path, rng, monkeypatch):
    """THE cross-validation acceptance: drive the serve scheduler under
    load with the sanitizer on; every witnessed acquisition order must
    be an edge the static model predicts, in both health states, with
    the supervisor transitioning mid-run. Disagreement in either
    direction fails."""
    from jepsen_etcd_demo_tpu import obs
    from jepsen_etcd_demo_tpu.obs import health
    from jepsen_etcd_demo_tpu.serve import CoalescingScheduler
    from jepsen_etcd_demo_tpu.ops.encode import encode_register_history
    from jepsen_etcd_demo_tpu.utils.fuzz import gen_register_history

    monkeypatch.setenv(obs_sync.SYNC_TRACE_ENV, "1")
    obs_sync.reset_witness()
    # Constructed AFTER the env gate so every lock is wrapped.
    fake = health.BackendSupervisor()
    prev = health.reset_supervisor(fake)
    try:
        with obs.capture() as cap:
            s = CoalescingScheduler(coalesce_ms=30, max_batch=8)
            try:
                encs = [encode_register_history(
                    gen_register_history(rng, n_ops=24, n_procs=3),
                    k_slots=8) for _ in range(6)]
                reqs = [s.submit(f"t{i % 2}", e,
                                 model_name="cas-register")
                        for i, e in enumerate(encs)]
                for r in reqs:
                    assert r.wait(120), "verdict timed out"
                s.stats()
                # Supervisor transitions exercise the health-lock ->
                # obs edges (gauge + event under the supervisor lock).
                fake.note_failure("injected degradation", source="test")
                fake.note_ok(source="test")
            finally:
                s.close()
            summary = obs_sync.publish_metrics()
            assert cap.metrics.value("sync.lock_acquisitions") \
                == summary["acquisitions"] > 0
    finally:
        health.reset_supervisor(prev)
    witnessed = obs_sync.witnessed_edges()
    assert witnessed, "sanitizer witnessed no lock nesting under load"
    model = sync_model(FlowIndex.build(REPO))
    problems = obs_sync.cross_validate(model.edge_pairs())
    assert problems == [], "\n".join(problems)
    # The serve-era edges the model predicts were actually exercised.
    assert ("serve.scheduler.CoalescingScheduler._lock",
            "obs.metrics.MetricsRegistry._lock") in witnessed
    assert ("obs.health.BackendSupervisor._lock",
            "obs.metrics.MetricsRegistry._lock") in witnessed


def test_injected_inversion_caught_by_both_halves(monkeypatch):
    """A deliberately injected lock-order inversion is caught by BOTH
    halves: the runtime sanitizer reports the witnessed two-direction
    pair (and the unmodeled-edge direction), and the static model's
    JTL502 reports the same shape written as code (sync_order_pos)."""
    monkeypatch.setenv(obs_sync.SYNC_TRACE_ENV, "1")
    obs_sync.reset_witness()
    a = obs_sync.maybe_wrap(
        threading.Lock(), "serve.scheduler.CoalescingScheduler._lock")
    b = obs_sync.maybe_wrap(
        threading.Lock(), "obs.metrics.MetricsRegistry._lock")
    with a:
        with b:
            pass
    with b:
        with a:   # the inversion
            pass
    unmodeled = obs_sync.maybe_wrap(threading.Lock(),
                                    "nowhere.Fake._lock")
    with a:
        with unmodeled:
            pass
    model = sync_model(FlowIndex.build(REPO))
    problems = obs_sync.cross_validate(model.edge_pairs())
    assert any("inversion" in p for p in problems), problems
    assert any("nowhere.Fake._lock" in p and "not an edge" in p
               for p in problems), problems
    obs_sync.reset_witness()
    # The static half: the same inversion as code is a JTL502 cycle.
    res = _lint_sync("sync_order_pos", "JTL502")
    assert len(res.findings) == 1
    assert "cycle" in res.findings[0].message


def test_condition_wait_records_held_while_blocking(monkeypatch):
    monkeypatch.setenv(obs_sync.SYNC_TRACE_ENV, "1")
    obs_sync.reset_witness()
    outer = obs_sync.maybe_wrap(threading.Lock(), "t.Outer._lock")
    cond = obs_sync.maybe_wrap(threading.Condition(), "t.Inner._cond")
    with outer:
        with cond:
            cond.wait(0.01)
    blocking = obs_sync.witnessed_blocking()
    assert ("t.Outer._lock", "Condition.wait") in blocking
    obs_sync.reset_witness()


# -- the serve fixes jtsan pinned ------------------------------------------

def test_model_for_returns_one_instance_under_race(monkeypatch):
    """The JTL503 fix: racing model_for() callers all get the ONE
    instance the registry holds (setdefault's return is bound)."""
    from jepsen_etcd_demo_tpu.obs import health
    from jepsen_etcd_demo_tpu.serve import CoalescingScheduler

    fake = health.BackendSupervisor()
    prev = health.reset_supervisor(fake)
    try:
        s = CoalescingScheduler(coalesce_ms=5, max_batch=2)
        try:
            import jepsen_etcd_demo_tpu.models as models

            calls = []
            real = models.get_model

            def counted(name):
                calls.append(name)
                return real(name)

            monkeypatch.setattr(models, "get_model", counted)
            first = s.model_for("cas-register")
            second = s.model_for("cas-register")
            assert first is second
            assert len(calls) == 1
        finally:
            s.close()
    finally:
        health.reset_supervisor(prev)


def test_pre_fix_daemon_shutdown_gap_is_detected(tmp_path):
    """Reverting the ServeDaemon.close fix on a scratch copy of the
    package makes JTL505 fire on the session-shutdown gap — the rule
    genuinely pins the fix (ownership resolved through the
    SessionManager registry AND the close_all -> close delegation)."""
    import shutil

    shutil.copytree(PKG, tmp_path / "jepsen_etcd_demo_tpu")
    d = tmp_path / "jepsen_etcd_demo_tpu" / "serve" / "daemon.py"
    text = d.read_text(encoding="utf-8")
    assert "        self.sessions.close_all()\n" in text
    d.write_text(text.replace("        self.sessions.close_all()\n", ""),
                 encoding="utf-8")
    rules = analysis.all_rules()
    res = analysis.run_lint([tmp_path / "jepsen_etcd_demo_tpu"],
                            rules={"JTL505": rules["JTL505"]},
                            root=tmp_path)
    hits = [f for f in res.findings
            if "ServeDaemon.sessions" in f.message]
    assert hits, analysis.format_text(res.findings)
    assert "never releases it" in hits[0].message


def test_daemon_close_finalizes_open_sessions(tmp_path):
    """The JTL505 fix: ServeDaemon.close() reaches every open streaming
    session — consumer threads are joined, the registry drains (the
    shutdown gap the static pass surfaced)."""
    from jepsen_etcd_demo_tpu.obs import health
    from jepsen_etcd_demo_tpu.serve import ServeDaemon

    fake = health.BackendSupervisor()
    prev = health.reset_supervisor(fake)
    try:
        d = ServeDaemon(store_root=str(tmp_path / "store"),
                        write_artifacts=False)
        model = d.scheduler.model_for("cas-register")
        sess = d.sessions.open("t1", model, "cas-register")
        consumer = sess._session._thread
        assert consumer.is_alive()
        d.close()
        assert d.sessions.stats()["open_sessions"] == 0
        consumer.join(timeout=10)
        assert not consumer.is_alive(), \
            "session consumer thread leaked past daemon close"
    finally:
        health.reset_supervisor(prev)
