"""Force JAX onto a virtual n-device CPU platform (tests + dryruns).

Single source of the forcing recipe used by tests/conftest.py and
__graft_entry__.dryrun_multichip (SURVEY.md §4: mesh tests run on simulated
devices). Must run BEFORE any JAX backend initialization (the first
jax.devices() call); the env vars it sets carry the same platform into
subprocesses.
"""

from __future__ import annotations

import os


def force_virtual_cpu(n_devices: int) -> None:
    """Best-effort: point JAX at a virtual CPU platform with n devices.

    Raises RuntimeError (with the observed device count) when the forcing
    didn't take — a backend was already initialized, or a conflicting
    xla_force_host_platform_device_count was inherited from the
    environment.
    """
    os.environ["JAX_PLATFORMS"] = "cpu"  # for any subprocesses
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            f"{flags} --xla_force_host_platform_device_count={n_devices}"
        ).strip()

    import jax

    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_num_cpu_devices", n_devices)
    if len(jax.devices()) < n_devices:
        raise RuntimeError(
            f"force_virtual_cpu({n_devices}): only {len(jax.devices())} "
            f"device(s) visible. Either a JAX backend was initialized "
            f"before this call (use a fresh process), or the environment "
            f"carried a conflicting XLA_FLAGS="
            f"{os.environ.get('XLA_FLAGS')!r}")
