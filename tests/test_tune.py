"""Profile-guided autotuner (tune/ + ops/limits.py resolution, ISSUE 4):
profile round-trip and auto-load, the full precedence ladder
(env > set_limits > tuned profile > default) with per-field provenance,
loud env validation, the calibration migration off the legacy sidecar,
a capped deterministic CPU-mode `tune` smoke, and verdict bit-identity
between default and tuned profiles on the golden + fuzz corpora."""

from __future__ import annotations

import json
import os
import random
import subprocess
import sys

import pytest

from jepsen_etcd_demo_tpu import obs, sched
from jepsen_etcd_demo_tpu.models import CASRegister
from jepsen_etcd_demo_tpu.ops import limits as limits_mod
from jepsen_etcd_demo_tpu.ops.encode import encode_register_history
from jepsen_etcd_demo_tpu.ops.limits import (KernelLimits, LimitsEnvError,
                                             field_meta, limits,
                                             limits_provenance, set_limits)
from jepsen_etcd_demo_tpu.tune import (default_knobs, profile,
                                       resolve_knobs, run_tune)
from jepsen_etcd_demo_tpu.utils.fuzz import (gen_register_history,
                                             mutate_history)
from tests.golden import GOLDEN

MODEL = CASRegister()


@pytest.fixture
def store(tmp_path, monkeypatch):
    """Isolated profile store + clean resolution state, restored after."""
    path = tmp_path / "tuned_profile.json"
    monkeypatch.setenv("JEPSEN_TPU_TUNE_PROFILE", str(path))
    prev_set = limits_mod._SET
    limits_mod._SET = None      # earlier tests may have left a set_limits
    profile.reset()
    yield path
    limits_mod._SET = prev_set
    profile.reset()


class TestProfileStore:
    def test_roundtrip_autoload_and_provenance(self, store):
        """The acceptance contract: write -> limits() auto-loads ->
        values reflected -> provenance tags correct."""
        assert limits().long_scan_chunk == 16384          # pre: default
        profile.save_entry({"long_scan_chunk": 4096,
                            "step_bucket_floor": 16})
        assert store.exists()
        lim = limits()                                    # auto-load
        assert lim.long_scan_chunk == 4096
        assert lim.step_bucket_floor == 16
        assert lim.dense_cell_budget == 1 << 20           # untouched
        prov = limits_provenance()
        assert prov["long_scan_chunk"] == "tuned"
        assert prov["step_bucket_floor"] == "tuned"
        assert prov["dense_cell_budget"] == "default"
        assert profile.profile_hash() != "default"
        # A fresh "process" (dropped caches) resolves identically.
        profile.reset()
        assert limits().long_scan_chunk == 4096

    def test_hash_is_content_addressed(self, store):
        profile.save_entry({"long_scan_chunk": 4096})
        h1 = profile.profile_hash()
        profile.save_entry({"long_scan_chunk": 2048})
        h2 = profile.profile_hash()
        assert h1 != h2 and "default" not in (h1, h2)
        profile.save_entry({"long_scan_chunk": 4096})
        assert profile.profile_hash() == h1               # same content

    def test_version_mismatch_ignored_wholesale(self, store):
        profile.save_entry({"long_scan_chunk": 4096})
        data = json.loads(store.read_text())
        data["version"] = profile.PROFILE_VERSION + 1
        store.write_text(json.dumps(data))
        profile.reset()
        assert limits().long_scan_chunk == 16384
        assert profile.profile_hash() == "default"

    def test_unknown_and_out_of_range_fields_dropped(self, store):
        profile.save_entry({"long_scan_chunk": 4096,
                            "not_a_field": 7,
                            "sparse_worklist_cap": 10 ** 9,   # > hi
                            "sched_pipeline_depth": 0})       # < lo
        lim = limits()
        assert lim.long_scan_chunk == 4096                # valid applies
        assert lim.sparse_worklist_cap == 512             # dropped
        assert lim.sched_pipeline_depth == 2              # dropped

    def test_other_platform_entry_inert(self, store):
        profile.save_entry({"long_scan_chunk": 4096})
        data = json.loads(store.read_text())
        key = profile.platform_key()
        data["profiles"]["tpu/TPU v9/256"] = data["profiles"].pop(key)
        store.write_text(json.dumps(data))
        profile.reset()
        assert limits().long_scan_chunk == 16384

    def test_pre_jax_limits_call_does_not_freeze_defaults(self, store):
        """Code-review regression: a limits() call made BEFORE jax is
        imported (CLI flag handling, encode paths) must not freeze an
        empty tuned set for the process lifetime — the resolution stays
        un-memoized while the platform key is unresolvable, reports
        "unknown" instead of claiming "default", and picks the profile
        up on the first call after a backend exists."""
        store.write_text(json.dumps({
            "version": profile.PROFILE_VERSION,
            "profiles": {"cpu/cpu/1": {
                "limits": {"long_scan_chunk": 4096}}}}))
        code = (
            "import sys; assert 'jax' not in sys.modules;"
            "from jepsen_etcd_demo_tpu.ops.limits import limits;"
            "from jepsen_etcd_demo_tpu.tune import profile;"
            "assert limits().long_scan_chunk == 16384;"   # undetermined
            "assert profile.profile_hash() == 'unknown';"
            "rec = profile.run_record();"
            "assert rec['hash'] == 'unknown' and 'note' in rec, rec;"
            "import jax; jax.devices();"
            "lim = limits();"
            "assert lim.long_scan_chunk == 4096, lim.long_scan_chunk;"
            "assert profile.profile_hash() != 'unknown';"
            "print('TUNED_OK')")
        env = dict(os.environ, JAX_PLATFORMS="cpu",
                   PYTHONPATH=os.getcwd(),
                   JEPSEN_TPU_TUNE_PROFILE=str(store))
        env.pop("XLA_FLAGS", None)    # a virtual-device count would
        #                               change the subprocess's key
        out = subprocess.run([sys.executable, "-c", code], env=env,
                             capture_output=True, text=True)
        assert out.returncode == 0, out.stderr
        assert "TUNED_OK" in out.stdout

    def test_disable_env(self, store, monkeypatch):
        profile.save_entry({"long_scan_chunk": 4096})
        monkeypatch.setenv("JEPSEN_TPU_TUNE_PROFILE", "0")
        profile.reset()
        assert limits().long_scan_chunk == 16384
        assert profile.profile_hash() == "default"


class TestPrecedence:
    def test_env_beats_tuned_profile(self, store, monkeypatch):
        """ISSUE 4 satellite: env must beat a tuned profile."""
        profile.save_entry({"long_scan_chunk": 4096})
        monkeypatch.setenv("JEPSEN_TPU_LIMIT_LONG_SCAN_CHUNK", "2048")
        limits_mod._reload()
        try:
            assert limits().long_scan_chunk == 2048
            assert limits_provenance()["long_scan_chunk"] == "env"
        finally:
            monkeypatch.delenv("JEPSEN_TPU_LIMIT_LONG_SCAN_CHUNK")
            limits_mod._reload()

    def test_set_limits_beats_tuned_profile(self, store):
        profile.save_entry({"long_scan_chunk": 4096})
        prev = set_limits(KernelLimits())
        try:
            assert limits().long_scan_chunk == 16384
            assert limits_provenance()["long_scan_chunk"] == "default"
        finally:
            set_limits(prev)
        # prev was None (no programmatic profile), so the restore
        # re-enables tuned-profile resolution rather than freezing a
        # snapshot — the save/restore idiom is exact.
        assert prev is None
        assert limits().long_scan_chunk == 4096           # tuned again

    def test_env_beats_set_limits(self, store, monkeypatch):
        monkeypatch.setenv("JEPSEN_TPU_LIMIT_LONG_SCAN_CHUNK", "1024")
        limits_mod._reload()
        try:
            set_limits(KernelLimits(long_scan_chunk=8192))
            assert limits().long_scan_chunk == 1024
            assert limits_provenance()["long_scan_chunk"] == "env"
        finally:
            set_limits(None)
            monkeypatch.delenv("JEPSEN_TPU_LIMIT_LONG_SCAN_CHUNK")
            limits_mod._reload()


class TestEnvValidation:
    """ISSUE 4 satellite: malformed JEPSEN_TPU_LIMIT_* must fail loudly
    with the field name and accepted range, not a bare int() ValueError."""

    def test_non_integer_names_var_and_range(self, monkeypatch):
        monkeypatch.setenv("JEPSEN_TPU_LIMIT_LONG_SCAN_CHUNK", "banana")
        with pytest.raises(LimitsEnvError) as ei:
            limits_mod._parse_env()
        msg = str(ei.value)
        assert "JEPSEN_TPU_LIMIT_LONG_SCAN_CHUNK" in msg
        assert "banana" in msg and "256..1048576" in msg

    def test_out_of_range_names_var_and_range(self, monkeypatch):
        monkeypatch.setenv("JEPSEN_TPU_LIMIT_SCHED_PIPELINE_DEPTH", "99")
        with pytest.raises(LimitsEnvError) as ei:
            limits_mod._parse_env()
        msg = str(ei.value)
        assert "JEPSEN_TPU_LIMIT_SCHED_PIPELINE_DEPTH" in msg
        assert "1..8" in msg

    def test_import_time_failure_is_loud(self):
        """A malformed env kills the IMPORT with the diagnostic (the
        operator sees the field immediately, not a routing mystery)."""
        code = "import jepsen_etcd_demo_tpu.ops.limits"
        env = dict(os.environ, JAX_PLATFORMS="cpu",
                   JEPSEN_TPU_LIMIT_SORT_ROW_BUDGET="2.5",
                   PYTHONPATH=os.getcwd())
        out = subprocess.run([sys.executable, "-c", code], env=env,
                             capture_output=True, text=True)
        assert out.returncode != 0
        assert "JEPSEN_TPU_LIMIT_SORT_ROW_BUDGET" in out.stderr
        assert "1024..268435456" in out.stderr

    def test_hex_accepted(self, monkeypatch):
        monkeypatch.setenv("JEPSEN_TPU_LIMIT_LONG_SCAN_CHUNK", "0x1000")
        assert limits_mod._parse_env()["long_scan_chunk"] == 4096

    def test_zero_padded_decimal_accepted(self, monkeypatch):
        """Pre-ISSUE-4 int() accepted "010" as decimal 10; the literal
        parser must not regress working deployment configs."""
        monkeypatch.setenv("JEPSEN_TPU_LIMIT_STEP_BUCKET_FLOOR", "010")
        assert limits_mod._parse_env()["step_bucket_floor"] == 10


class TestCalibrationMigration:
    """ISSUE 4 satellite: ops/calibrate.py persists via the shared
    profile store; legacy calibration.json sidecars are read once,
    re-persisted in the new format, and ignored thereafter."""

    @pytest.fixture
    def cal_env(self, store, tmp_path, monkeypatch):
        from jepsen_etcd_demo_tpu.ops.calibrate import set_calibration

        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        prev = set_calibration(None)
        yield tmp_path
        set_calibration(prev)

    def _legacy_sidecar(self, tmp_path, crossover=1234):
        from jepsen_etcd_demo_tpu.ops import calibrate

        sidecar = tmp_path / "calibration.json"
        sidecar.write_text(json.dumps({
            "platform": calibrate.platform_tag(),
            "dispatch_floor_s": 0.01, "oracle_events_per_s": 123400.0,
            "crossover_events": crossover,
            "measured_at": "2026-07-01T00:00:00Z",
            "version": calibrate.CAL_VERSION}))
        return sidecar

    def test_legacy_sidecar_migrates_into_store(self, cal_env, store):
        from jepsen_etcd_demo_tpu.ops import calibrate

        sidecar = self._legacy_sidecar(cal_env)
        cal = calibrate.get_calibration()      # no measure: sidecar wins
        assert cal.crossover_events == 1234
        # ...and was re-persisted into the shared store.
        entry = json.loads(store.read_text())[
            "profiles"][profile.platform_key()]
        assert entry["calibration"]["crossover_events"] == 1234
        # The sidecar is now IGNORED: change it, drop memory, reload.
        self._legacy_sidecar(cal_env, crossover=9999)
        calibrate.set_calibration(None)
        profile.reset()
        assert calibrate.get_calibration().crossover_events == 1234
        sidecar.unlink()
        calibrate.set_calibration(None)
        profile.reset()
        assert calibrate.get_calibration().crossover_events == 1234

    def test_store_roundtrip_without_sidecar(self, cal_env, store):
        """The other direction: a calibration measured under the NEW
        format round-trips through the store alone."""
        from jepsen_etcd_demo_tpu.ops import calibrate

        cal = calibrate.get_calibration()      # measures + persists
        assert not (cal_env / "calibration.json").exists()  # no sidecar
        calibrate.set_calibration(None)
        profile.reset()
        assert calibrate.get_calibration() == cal
        # Tuned limits saved LATER must not clobber the calibration.
        profile.save_entry({"long_scan_chunk": 4096})
        calibrate.set_calibration(None)
        assert calibrate.get_calibration() == cal
        assert limits().long_scan_chunk == 4096

    def test_stale_version_sidecar_not_migrated(self, cal_env, store):
        from jepsen_etcd_demo_tpu.ops import calibrate

        sidecar = cal_env / "calibration.json"
        sidecar.write_text(json.dumps({
            "platform": calibrate.platform_tag(),
            "dispatch_floor_s": 9.0, "oracle_events_per_s": 1.0,
            "crossover_events": 9,
            "measured_at": "2020-01-01T00:00:00Z",
            "version": calibrate.CAL_VERSION - 1}))
        cal = calibrate.get_calibration()      # re-measures
        assert cal.crossover_events != 9


class TestKnobResolution:
    def test_default_knobs_are_grouped_fields(self):
        knobs = default_knobs()
        meta = field_meta()
        assert knobs and all(meta[k]["group"] for k in knobs)
        assert "step_bucket_floor" in knobs
        assert "sparse_min_tiles" in knobs

    def test_group_and_field_spec(self):
        assert resolve_knobs("sched") == ["step_bucket_floor",
                                          "batch_bucket_floor"]
        assert resolve_knobs("long_scan_chunk,sched") == [
            "long_scan_chunk", "step_bucket_floor", "batch_bucket_floor"]
        with pytest.raises(ValueError, match="unknown knob"):
            resolve_knobs("warp_drive")
        with pytest.raises(ValueError, match="no probe group"):
            resolve_knobs("sparse_mode")

    def test_worker_candidates_clamped_conservative(self):
        from jepsen_etcd_demo_tpu.tune.search import candidates_for

        cands = candidates_for("long_scan_chunk", probe=object())
        default = field_meta()["long_scan_chunk"]["default"]
        assert all(v <= default for v in cands)       # [envelope], down
        assert default in cands and len(cands) >= 2

    def test_candidates_stay_in_safe_range(self):
        from jepsen_etcd_demo_tpu.tune.search import candidates_for

        for name in default_knobs():
            lo, hi = field_meta()[name]["range"]
            for v in candidates_for(name, probe=object()):
                assert lo <= v <= hi, (name, v)


class TestTuneSmoke:
    """Capped deterministic CPU-mode tune (tier-1): a seconds-scale
    budget, one cheap knob, and the full persist -> auto-load ->
    provenance pipeline."""

    def test_tune_writes_profile_and_limits_autoload(self, store):
        with obs.capture() as cap:
            out = run_tune(knobs=["sched_poll_chunks"], budget_s=20,
                           repeats=1, scale=0.05, calibrate_too=False)
        assert out["dry_run"] is False
        assert store.exists()
        rec = out["probes"]["sched_poll_chunks"]
        lo, hi = field_meta()["sched_poll_chunks"]["range"]
        assert lo <= rec["chosen"] <= hi
        assert rec["measurements"] >= 1
        # The persisted profile auto-loads and provenance agrees.
        prov = limits_provenance()
        if out["values"]:
            assert getattr(limits(), "sched_poll_chunks") == \
                out["values"]["sched_poll_chunks"]
            assert prov["sched_poll_chunks"] == "tuned"
            assert out["profile_hash"] != "default"
        else:
            assert prov["sched_poll_chunks"] == "default"
        # Probe telemetry gauges landed in the capture.
        snap = cap.metrics.snapshot()
        assert snap["tune.chosen.sched_poll_chunks"]["last"] == \
            rec["chosen"]
        assert snap["tune.measurements"]["value"] >= 1
        # The active profile is restored: no set_limits leak.
        assert limits_mod._SET is None

    def test_dry_run_persists_nothing(self, store):
        out = run_tune(knobs=["sched_poll_chunks"], budget_s=10,
                       repeats=1, scale=0.05, dry_run=True)
        assert out["dry_run"] is True
        assert not store.exists()

    def test_budget_expiry_keeps_defaults(self, store):
        out = run_tune(knobs=["step_bucket_floor", "sched_poll_chunks"],
                       budget_s=0.0, repeats=1, scale=0.05,
                       calibrate_too=False)
        assert out["values"] == {}
        skipped = set(out["skipped"]) | {
            k for k, r in out["probes"].items() if "skipped" in r}
        assert {"step_bucket_floor", "sched_poll_chunks"} <= skipped
        assert limits().step_bucket_floor == 32

    def test_env_pinned_knob_excluded(self, store, monkeypatch):
        monkeypatch.setenv("JEPSEN_TPU_LIMIT_SCHED_POLL_CHUNKS", "4")
        limits_mod._reload()
        try:
            out = run_tune(knobs=["sched_poll_chunks"], budget_s=5,
                           repeats=1, scale=0.05, calibrate_too=False)
            assert "sched_poll_chunks" in out["skipped"]
            assert "JEPSEN_TPU_LIMIT_SCHED_POLL_CHUNKS" in \
                out["skipped"]["sched_poll_chunks"]
        finally:
            monkeypatch.delenv("JEPSEN_TPU_LIMIT_SCHED_POLL_CHUNKS")
            limits_mod._reload()

    def test_pallas_group_skipped_off_tpu(self, store):
        out = run_tune(knobs=resolve_knobs("pallas"), budget_s=5,
                       repeats=1, scale=0.05, calibrate_too=False,
                       dry_run=True)
        assert out["values"] == {}
        assert "pallas unavailable" in \
            out["skipped"].get("pallas_step_chunk", "")


class TestVerdictBitIdentity:
    """Acceptance: checker verdicts are bit-identical under default and
    tuned profiles on the golden + fuzz corpora — a profile reroutes and
    re-chunks, it must never change an answer."""

    RESULT_FIELDS = ("valid", "survived", "dead_step", "max_frontier",
                     "configs_explored", "op_count", "overflow")

    def _corpus(self):
        encs = [encode_register_history(h, k_slots=16)
                for _name, h, _want in GOLDEN if h]
        rng = random.Random(0x7E57)
        # 8 histories keep several distinct bucket shapes per arm while
        # bounding the double compile bill (each arm's floors compile
        # their own shapes — that difference IS the coverage).
        for i in range(8):
            h = gen_register_history(rng, n_ops=rng.randrange(8, 150),
                                     n_procs=rng.randrange(2, 8),
                                     p_info=rng.choice([0.0, 0.02]))
            if i % 3 == 0:
                h = mutate_history(rng, h)
            encs.append(encode_register_history(h, k_slots=16))
        return encs

    def test_golden_and_fuzz_corpora(self, store):
        # An AGGRESSIVE but in-range tuned profile: different chunking,
        # bucketing, pipelining and sparse routing than the defaults.
        profile.save_entry({
            "long_scan_chunk": 1024, "step_bucket_floor": 8,
            "batch_bucket_floor": 2, "sched_pipeline_depth": 1,
            "sched_poll_chunks": 2, "sparse_min_tiles": 1,
            "sparse_density_threshold_pct": 60})
        encs = self._corpus()
        runs = {}
        for arm, prof in (("default", KernelLimits()), ("tuned", None)):
            set_limits(prof)
            try:
                results, _kernel, _stats = sched.check_corpus(encs, MODEL)
            finally:
                set_limits(None)
            runs[arm] = results
        assert limits().long_scan_chunk == 1024   # tuned really active
        for i, (d, t) in enumerate(zip(runs["default"], runs["tuned"])):
            for f in self.RESULT_FIELDS:
                assert d.get(f) == t.get(f), (i, f, d, t)
        # Expected verdicts on the golden prefix still hold.
        golden = [(n, w) for n, h, w in GOLDEN if h]
        for (name, want), res in zip(golden, runs["tuned"]):
            assert res["valid"] is want, (name, res)


class TestReportingSurfaces:
    def test_run_record_and_report(self, store):
        profile.save_entry({"long_scan_chunk": 4096})
        rec = profile.run_record()
        assert rec["hash"] == profile.profile_hash() != "default"
        assert rec["tuned_fields"] == 1
        assert rec["overrides"] == {"long_scan_chunk": "tuned"}
        rep = profile.report()
        f = rep["fields"]["long_scan_chunk"]
        assert f["value"] == 4096 and f["provenance"] == "tuned"
        assert f["range"] == [256, 1 << 20] and f["kind"] == "envelope"
        assert rep["profile_hash"] == rec["hash"]
        json.dumps(rep)

    def test_print_profile_tool(self, store):
        profile.save_entry({"step_bucket_floor": 16})
        sys.path.insert(0, os.path.join(os.getcwd(), "tools"))
        import print_profile

        rep = print_profile.report()
        assert rep["fields"]["step_bucket_floor"]["provenance"] == "tuned"
        assert print_profile.main([]) == 0
        assert print_profile.main(["--json"]) == 0

    def test_cli_print_profile(self, store, capsys):
        from jepsen_etcd_demo_tpu.cli.main import main

        profile.save_entry({"step_bucket_floor": 16})
        assert main(["tune", "--print-profile"]) == 0
        rep = json.loads(capsys.readouterr().out)
        assert rep["fields"]["step_bucket_floor"]["value"] == 16
        assert rep["profile_hash"] == profile.profile_hash()

    def test_cli_tune_dry_run_smoke(self, store, capsys):
        from jepsen_etcd_demo_tpu.cli.main import main

        rc = main(["tune", "--knobs", "sched_poll_chunks", "--budget-s",
                   "5", "--repeats", "1", "--scale", "0.05", "--dry-run"])
        assert rc == 0
        out = json.loads(capsys.readouterr().out)
        assert out["dry_run"] is True
        assert not store.exists()

    def test_cli_tune_unknown_knob_errors(self, store, capsys):
        from jepsen_etcd_demo_tpu.cli.main import main

        assert main(["tune", "--knobs", "warp_drive", "--dry-run"]) == 2

    def test_sweep_mode_env_does_not_leak(self, store, monkeypatch):
        """--sweep-mode rides the env layer for one invocation: a later
        in-process cli call WITHOUT the flag restores whatever the
        operator had exported (including nothing)."""
        import argparse
        import importlib

        # cli/__init__ rebinds the name `main` to the entry FUNCTION,
        # shadowing the submodule on attribute imports.
        cli = importlib.import_module("jepsen_etcd_demo_tpu.cli.main")

        var = limits_mod.env_var("sparse_mode")
        monkeypatch.delenv(var, raising=False)
        monkeypatch.setattr(cli, "_SWEEP_ENV_DISPLACED", None)
        limits_mod._reload()
        cli._apply_sweep_mode(argparse.Namespace(sweep_mode="sparse"))
        assert os.environ[var] == "2" and limits().sparse_mode == 2
        cli._apply_sweep_mode(argparse.Namespace(sweep_mode=None))
        assert var not in os.environ and limits().sparse_mode == 0
        # An operator-exported value survives a flagged invocation.
        monkeypatch.setenv(var, "1")
        limits_mod._reload()
        cli._apply_sweep_mode(argparse.Namespace(sweep_mode="sparse"))
        assert limits().sparse_mode == 2
        cli._apply_sweep_mode(argparse.Namespace(sweep_mode=None))
        assert os.environ[var] == "1" and limits().sparse_mode == 1
        monkeypatch.delenv(var)
        limits_mod._reload()

    def test_runner_stamps_results_with_profile(self, store, tmp_path):
        """The web run index's profile column feeds off results.json
        (runner/core.py stamps tune/profile.run_record)."""
        from jepsen_etcd_demo_tpu.cli.main import main
        from jepsen_etcd_demo_tpu.store import Store
        from jepsen_etcd_demo_tpu.web.server import _index_html

        profile.save_entry({"step_bucket_floor": 16})
        h = profile.profile_hash()
        root = str(tmp_path / "st")
        assert main(["test", "-w", "register", "--fake", "--time-limit",
                     "1.0", "--rate", "150", "--recovery-wait", "0.2",
                     "--store", root, "--seed", "5"]) == 0
        run = Store(root).runs()[0]
        rec = run.read_results()["profile"]
        assert rec["hash"] == h
        assert rec["overrides"]["step_bucket_floor"] == "tuned"
        idx = _index_html(Store(root))
        assert "<th>profile</th>" in idx
        assert h in idx
