"""Per-platform oracle/device crossover calibration.

Round-4 review (VERDICT.md weak #2 / next #3): the event-count crossover
below which a single tiny history routes to the exact host oracle instead
of a device launch was a hardcoded constant (2048) that encoded ONE
backend's ~0.1 s dispatch floor. On a runtime with fast dispatch the
router would still refuse the TPU for the reference's entire default
envelope (~150-op tutorial histories, BASELINE.md), and on a slower
link it would under-route. The crossover is a property of the PLATFORM,
so it is measured per platform here, once, and persisted next to the XLA
compile cache:

  crossover_events = dispatch_floor_s * oracle_events_per_s

i.e. the history size at which the oracle's whole runtime equals the
device dispatch+fetch round trip that a launch pays before any compute.
Below it the host oracle finishes before a device launch could even
report back; above it the kernel wins. Both factors are measured, not
assumed:

  * dispatch_floor_s — best observed round trip of an already-compiled
    trivial launch (dispatch + fetch of one word). The minimum over a few
    repeats deliberately estimates the FLOOR, not the mean: routing only
    needs "a launch cannot possibly beat the oracle below this size".
  * oracle_events_per_s — `check_events_oracle` throughput on a synthetic
    register history at tutorial-like concurrency (utils/fuzz.py, fixed
    seed), the same regime the route serves.

The router consumes this via `limits().oracle_crossover_events == -1`
(auto, the default); a fixed positive value or the
`JEPSEN_TPU_LIMIT_ORACLE_CROSSOVER_EVENTS` env override bypasses
measurement entirely, and 0 disables oracle routing. Persistence is keyed by the JAX backend + device kind,
so one cache file serves a laptop CPU run and a TPU pod worker without
cross-talk.

Persistence lives in the SHARED tuning-profile store (tune/profile.py —
ISSUE 4: one file, one version bump discipline) as this platform's
``calibration`` section. The pre-autotuner ``calibration.json`` sidecar
is a LEGACY migration source: read once when the store has no
calibration for this platform, re-persisted into the store, and ignored
thereafter (the store's copy is authoritative even if the sidecar later
changes).
"""

from __future__ import annotations

import json
import os
import time
from dataclasses import asdict, dataclass

# Bump whenever the PROBE SEMANTICS change (shape, concurrency, p_info):
# a persisted calibration measured under an old probe must be invalidated
# or routers keep consuming crossovers the change existed to correct.
# v2: probe moved to the 10-thread no-:info canonical envelope (the
# 5-proc v1 probe over-measured oracle rate ~8x).
CAL_VERSION = 2

# Clamp bounds for the derived crossover: even on an instant-dispatch
# runtime the oracle is never beaten below a few dozen events (launch
# bookkeeping alone), and above ~64k events the dense/chunked kernels win
# regardless of dispatch cost (the oracle is super-linear in the worst
# case there, so extrapolating its measured rate would over-route).
CROSSOVER_MIN = 64
CROSSOVER_MAX = 1 << 16

# Probe shape: the reference's default envelope — 10 threads per key
# (BASELINE.md), no forever-pending :info ops. Oracle throughput is
# geometry-sensitive (the closure explores ~2^pending masks per state):
# 5-proc histories measure ~175k events/s, 10-proc ~21k, and each
# pending-forever :info op drags the rest of the history (~9k at
# p_info=0.002, ~4k at 2000 ops) — measured r5. The derived crossover
# was ~2k events behind a remote ~0.09 s dispatch floor; a local v5e
# measures 0.000881 s and clamps to CROSSOVER_MIN (my chip run, PR 21).
# Wider/slower histories mis-route only within the bounded band the
# max_pending gate + transition budget allow.
PROBE_OPS = 1000
PROBE_PROCS = 10


@dataclass(frozen=True)
class Calibration:
    platform: str              # "<backend>/<device_kind>"
    dispatch_floor_s: float
    oracle_events_per_s: float
    crossover_events: int
    measured_at: str
    version: int = CAL_VERSION


_CAL: Calibration | None = None


def calibration_path() -> str:
    """The LEGACY sidecar path (next to the persistent XLA compile
    cache). New calibrations persist into the shared tuning-profile
    store (tune/profile.py); this file is only ever read, once, as a
    migration source."""
    from ..sched.compile_cache import compile_cache_dir

    return os.path.join(compile_cache_dir(), "calibration.json")


def platform_tag() -> str:
    import jax

    try:
        dev = jax.devices()[0]
        return f"{jax.default_backend()}/{dev.device_kind}"
    except Exception:
        return "unknown/unknown"


def measure_dispatch_floor(repeats: int = 5) -> float:
    """Round trip of an already-compiled trivial launch: dispatch one
    jitted add on a [8,128] i32 tile and fetch one word back. np.asarray
    (not block_until_ready) forces the fetch, so the round trip
    includes the result reaching the host."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    x = jnp.zeros((8, 128), jnp.int32)
    # jtlint: disable=JTL105 -- a calibration PROBE, not a production
    # kernel: instrument_kernel would fold this throwaway launch into
    # wgl.compile_s/execute_s and skew the attribution it calibrates.
    run = jax.jit(lambda a: (a + 1).sum())
    np.asarray(run(x))   # compile outside the timed region
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        np.asarray(run(x))
        best = min(best, time.perf_counter() - t0)
    return best


def measure_oracle_rate(repeats: int = 3) -> float:
    """`check_events_oracle` throughput (events/s) on a fixed-seed
    register history at tutorial concurrency."""
    import random

    from ..checkers.oracle import check_events_oracle
    from ..models import CASRegister
    from .encode import encode_register_history
    from ..utils.fuzz import gen_register_history

    rng = random.Random(0xCA11B)
    enc = encode_register_history(
        gen_register_history(rng, n_ops=PROBE_OPS, n_procs=PROBE_PROCS,
                             p_info=0.0))
    model = CASRegister()
    check_events_oracle(enc, model)      # warm (imports, caches)
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        check_events_oracle(enc, model)
        best = min(best, time.perf_counter() - t0)
    return enc.n_events / best


def measure() -> Calibration:
    floor = measure_dispatch_floor()
    rate = measure_oracle_rate()
    crossover = int(min(max(floor * rate, CROSSOVER_MIN), CROSSOVER_MAX))
    return Calibration(
        platform=platform_tag(), dispatch_floor_s=round(floor, 6),
        oracle_events_per_s=round(rate, 1), crossover_events=crossover,
        measured_at=time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()))


def _validate(data) -> Calibration | None:
    """A Calibration from a raw dict, or None when it is torn, from an
    older probe (CAL_VERSION mismatch), or from another platform."""
    try:
        cal = Calibration(**data)
    except (ValueError, TypeError):
        return None
    if cal.version != CAL_VERSION or cal.platform != platform_tag():
        return None
    return cal


def _load() -> Calibration | None:
    """This platform's calibration from the shared profile store."""
    from ..tune import profile

    data = profile.load_calibration()
    return None if data is None else _validate(data)


def _load_legacy_sidecar() -> Calibration | None:
    """The pre-ISSUE-4 calibration.json sidecar, consulted only when the
    profile store has no calibration for this platform (the migration
    read — after re-persisting into the store, the sidecar is ignored
    even if it changes)."""
    try:
        data = json.loads(open(calibration_path()).read())
    except (OSError, ValueError):
        return None
    return _validate(data) if isinstance(data, dict) else None


def _persist(cal: Calibration) -> None:
    """Into the shared profile store (atomic replace inside); like the
    old sidecar write, persistence is an optimization, never a failure
    mode."""
    from ..tune import profile

    profile.save_calibration(asdict(cal))


def get_calibration() -> Calibration:
    """Active calibration: in-memory, else the profile store, else the
    legacy sidecar (migrated into the store on first read), else
    measured now and persisted into the store."""
    global _CAL
    if _CAL is not None:
        return _CAL
    cal = _load()
    if cal is None:
        cal = _load_legacy_sidecar()
        if cal is not None:
            _persist(cal)           # migrate: store copy is authoritative
    if cal is None:
        cal = measure()
        _persist(cal)
    _CAL = cal
    return cal


def set_calibration(cal: Calibration | None) -> Calibration | None:
    """Swap the in-memory calibration (tests / embedding runtimes);
    returns the previous one. None re-enables load-or-measure."""
    global _CAL
    prev = _CAL
    _CAL = cal
    return prev
