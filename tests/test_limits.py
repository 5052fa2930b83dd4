"""KernelLimits profile (ops/limits.py): env overrides + routing effect."""

from __future__ import annotations

import os
import subprocess
import sys

from jepsen_etcd_demo_tpu.ops.limits import KernelLimits, limits, set_limits


def test_defaults_fit_one_v5e_core():
    lim = limits()
    assert lim.dense_cell_budget == 1 << 20
    assert lim.long_scan_max == 32768
    assert lim.sort_row_budget == 1 << 21
    # One pallas launch's SMEM stays inside a v5e core's 1 MiB.
    assert lim.max_prefetch_pallas == (1 << 18) - 4096


def test_set_limits_roundtrip():
    before = limits().dense_cell_budget
    prev = set_limits(KernelLimits(dense_cell_budget=1 << 10))
    try:
        assert limits().dense_cell_budget == 1 << 10
    finally:
        set_limits(prev)
    # set_limits returns the previous PROGRAMMATIC state (None when none
    # was installed), so the restore recovers the exact prior resolution.
    assert limits().dense_cell_budget == before


def test_limits_change_dense_routing():
    """A smaller cell budget must reroute geometries the default admits."""
    from jepsen_etcd_demo_tpu.models import CASRegister
    from jepsen_etcd_demo_tpu.ops.wgl3 import dense_config

    model = CASRegister()
    assert dense_config(model, 12, 4) is not None
    prev = set_limits(KernelLimits(dense_cell_budget=1 << 8))
    try:
        assert dense_config(model, 12, 4) is None
    finally:
        set_limits(prev)


def test_env_override_loads_in_subprocess():
    code = (
        "from jepsen_etcd_demo_tpu.ops.limits import limits;"
        "lim = limits();"
        "assert lim.long_scan_max == 12345, lim;"
        "assert lim.dense_cell_budget == 1 << 20;"  # others untouched
        "print('OK')"
    )
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JEPSEN_TPU_LIMIT_LONG_SCAN_MAX="12345",
               PYTHONPATH=os.getcwd())
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True)
    assert out.returncode == 0, out.stderr
    assert "OK" in out.stdout
