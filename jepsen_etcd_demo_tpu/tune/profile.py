"""Persisted tuning profiles — the autotuner's store (ISSUE 4 tentpole).

ONE versioned JSON file, living next to the persistent XLA compile cache
(same lifecycle: a per-user, per-machine measurement cache), holding one
entry per ``(jax backend, device kind, device count)`` platform key:

    {
      "version": 1,
      "profiles": {
        "cpu/TFRT_CPU_0/1": {
          "limits": {"long_scan_chunk": 8192, ...},   # tuned overrides
          "calibration": {...},                        # ops/calibrate.py
          "measured_at": "2026-08-03T...Z",
          "budget_s": 60.0,
          "probes": {...}                              # raw timings
        }
      }
    }

``ops/limits.py`` auto-loads the entry for the running platform lazily
(the first ``limits()`` call after a jax backend exists) and applies it
below env and ``set_limits`` overrides; ``ops/calibrate.py`` reads and
writes its oracle-crossover calibration through the same entry (one
file, one version bump discipline — the old ``calibration.json`` sidecar
is read once as a legacy migration source and ignored thereafter).

Version discipline: bump PROFILE_VERSION whenever the probe semantics or
the schema change; a mismatched file is ignored wholesale (stale
measurements must not steer a newer kernel stack). Unknown fields and
out-of-range values inside an entry are dropped individually — a profile
tuned by a build with wider ranges must not break this one's startup.

Env knobs:
  JEPSEN_TPU_TUNE_PROFILE=<path>  explicit profile file path
  JEPSEN_TPU_TUNE_PROFILE=0       disable tuned-profile loading entirely
"""

from __future__ import annotations

import hashlib
import json
import os
import sys
import time

PROFILE_VERSION = 1
PROFILE_FILE = "tuned_profile.json"

_DISABLE = ("0", "false", "no", "off")

# Memoized platform entry: None = not yet determined;
# (profile_path, platform_key|None, entry|None) after — keyed by the
# PATH so a profile-path change (compile cache enabled, env updated)
# after an early "no profile here" answer is not permanently ignored.
_CACHE: tuple[str, str | None, dict | None] | None = None
# Parsed profile FILE, keyed by path — so the undetermined state (file
# present, platform key unresolvable yet) costs dict lookups per
# limits() call, not a disk read + JSON parse. Cleared by reset().
_FILE_CACHE: tuple[str, dict | None] | None = None


def profile_enabled() -> bool:
    return os.environ.get("JEPSEN_TPU_TUNE_PROFILE", "").lower() \
        not in _DISABLE


def profile_path() -> str:
    """The profile file: JEPSEN_TPU_TUNE_PROFILE (explicit path) wins,
    else it lives next to the persistent XLA compile cache
    (sched/compile_cache.compile_cache_dir — the one directory every
    caller uses)."""
    explicit = os.environ.get("JEPSEN_TPU_TUNE_PROFILE")
    if explicit and explicit.lower() not in _DISABLE:
        return explicit
    from ..sched.compile_cache import compile_cache_dir

    return os.path.join(compile_cache_dir(), PROFILE_FILE)


def _backend_ready() -> bool:
    """True only when a jax backend is ALREADY initialized in this
    process. Module-import is NOT the test — a process may import jax
    without initializing a backend, and touching ``jax.devices()``
    would claim the chip. The xla_bridge backend
    registry is the initialized-state source of truth; if the internal
    moves in a future jax, we fail CLOSED (not ready -> the profile is
    reported "unknown" rather than risking a hang)."""
    if "jax" not in sys.modules:
        return False
    try:
        from jax._src import xla_bridge

        return bool(xla_bridge._backends)
    except Exception:
        return False


def platform_key(require_jax_loaded: bool = True) -> str | None:
    """``backend/device_kind/device_count`` for the running process —
    with ``xH`` (host count) appended on a multi-process pod, so a
    pod's tuned profile is keyed by its MESH SHAPE and an elastic
    re-shard (device or host count changed between runs) can only MISS
    the profile store, never resolve a stale entry tuned for a mesh
    that no longer exists. Single-process keys keep the historical
    3-part form (every existing profile stays valid). None when the
    key cannot be determined. With ``require_jax_loaded`` (the
    default) the key resolves only when a backend is ALREADY initialized
    (_backend_ready): probing devices initializes one, and a lazy
    profile load must never be the thing that claims the chip."""
    if require_jax_loaded and not _backend_ready():
        return None
    try:
        import jax

        dev = jax.devices()[0]
        key = f"{jax.default_backend()}/{dev.device_kind}/" \
              f"{jax.device_count()}"
        if jax.process_count() > 1:
            key += f"x{jax.process_count()}"
        # An explicit mesh shape (--mesh-shape / JEPSEN_TPU_MESH_SHAPE)
        # changes the sharded lanes' layout without changing the device
        # or host counts — 2x4 and 4x2 tune differently, so the shape
        # joins the key (absent = the default mesh for those counts).
        from ..parallel.mesh import requested_shape

        shape = requested_shape()
        if shape is not None:
            key += "@" + "x".join(str(s) for s in shape)
        return key
    except Exception:
        return None


def _read_file(use_cache: bool = True) -> dict | None:
    """The parsed, version-checked profile file (None when absent, torn,
    or version-mismatched). Parses once per path until reset() — the
    undetermined state re-consults this on every limits() resolution."""
    global _FILE_CACHE
    path = profile_path()
    if use_cache and _FILE_CACHE is not None and _FILE_CACHE[0] == path:
        return _FILE_CACHE[1]
    try:
        data = json.loads(open(path).read())
    except (OSError, ValueError):
        data = None
    if not isinstance(data, dict) \
            or data.get("version") != PROFILE_VERSION \
            or not isinstance(data.get("profiles"), dict):
        data = None
    _FILE_CACHE = (path, data)
    return data


def _entry_state() -> tuple[dict | None, bool]:
    """(this platform's entry or None, undetermined?) — the one place
    the lookup ladder lives. ``undetermined`` is True exactly when a
    valid profile file exists but the platform key cannot resolve yet
    (no initialized jax backend): callers must treat that as "ask again
    later", never as "no profile" (ops/limits.py keeps retrying, the
    reporting surfaces say "unknown").

    The no-backend guarantee: the FILE is read first (plain I/O, parse
    cached); the platform key — and thus jax — is only consulted when
    the file exists, and even then only when a backend is ALREADY
    initialized (_backend_ready). Machines where no one ever ran
    ``jepsen-tpu tune`` never touch jax from here."""
    global _CACHE
    if not profile_enabled():
        return None, False
    path = profile_path()
    if _CACHE is not None and _CACHE[0] == path:
        return _CACHE[2], False
    data = _read_file()
    if data is None:
        _CACHE = (path, None, None)
        return None, False
    key = platform_key()
    if key is None:
        # No initialized backend yet: retry on a later call rather than
        # caching a miss the backend could satisfy.
        return None, True
    entry = data["profiles"].get(key)
    entry = entry if isinstance(entry, dict) else None
    _CACHE = (path, key, entry)
    return entry, False


def load_entry() -> dict | None:
    """This platform's profile entry, memoized once determinable. None
    when the profile is disabled, the file is absent/torn/version-
    mismatched, the platform key cannot resolve (yet), or the file has
    no entry for this platform."""
    return _entry_state()[0]


def _valid_limits(entry: dict | None) -> dict[str, int]:
    """An entry's limit overrides validated against the dataclass
    metadata: unknown fields and out-of-range values are dropped
    individually (a stale-but-version-matching profile must degrade
    field-wise, not explode). The SAME validated view feeds both
    resolution (tuned_limits) and identity (profile_hash), so the hash
    can never disagree with what actually applied."""
    raw = (entry or {}).get("limits")
    if not isinstance(raw, dict):
        return {}
    from ..ops.limits import field_meta

    meta = field_meta()
    out: dict[str, int] = {}
    for name, val in raw.items():
        m = meta.get(name)
        if m is None or not isinstance(val, int) \
                or isinstance(val, bool):
            continue
        lo, hi = m["range"]
        if lo <= val <= hi:
            out[name] = val
    return out


def tuned_limits() -> dict[str, int] | None:
    """The validated tuned KernelLimits overrides for this platform.
    Returns ``None`` — not ``{}`` — while the answer is undetermined
    (profile file present, platform key unresolvable without an
    initialized jax backend): ops/limits.py keeps retrying instead of
    freezing an empty tuned set."""
    entry, undetermined = _entry_state()
    if undetermined:
        return None
    return _valid_limits(entry)


def profile_hash(entry: dict | None = None) -> str:
    """Short content hash identifying the tuned overrides that ACTUALLY
    apply (the validated view — a profile whose fields are all dropped
    hashes "default", and two profiles validating identically hash the
    same). ``"default"`` when no tuned entry applies to this platform;
    ``"unknown"`` when a profile file EXISTS but the platform key cannot
    resolve (no initialized jax backend — the bench's all-probes-dead
    path): a degraded record must not claim "default" about a profile it
    simply could not look up. Lands in every bench record and in each
    run's results.json so a number can always be traced back to the knob
    values that produced it."""
    if entry is None:
        entry, undetermined = _entry_state()
        if undetermined:
            return "unknown"
    limits_dict = _valid_limits(entry)
    if not limits_dict:
        return "default"
    blob = json.dumps(limits_dict, sort_keys=True).encode()
    return hashlib.sha256(blob).hexdigest()[:12]


def save_entry(limits: dict[str, int], probes: dict | None = None,
               budget_s: float | None = None,
               calibration: dict | None = None) -> str:
    """Persist this platform's entry (read-modify-write, atomic replace:
    pod workers share the cache dir and a torn read would discard the
    whole profile). Preserves other platforms' entries and — unless a
    new one is given — this platform's existing calibration section.
    Returns the file path. Invalidates the limits() memo so the new
    profile takes effect in-process."""
    key = platform_key(require_jax_loaded=False)
    if key is None:
        raise RuntimeError("cannot resolve a platform key (no jax "
                           "backend); refusing to persist a profile")
    path = profile_path()
    with _file_lock(path):
        # Fresh read (no parse cache) UNDER the lock: read-modify-write
        # must see what is on disk NOW, not what this process parsed
        # earlier — and no other writer may slip between read and
        # replace.
        data = _read_file(use_cache=False) \
            or {"version": PROFILE_VERSION, "profiles": {}}
        old = data["profiles"].get(key) or {}
        entry = {
            "limits": dict(sorted(limits.items())),
            "measured_at": time.strftime("%Y-%m-%dT%H:%M:%SZ",
                                         time.gmtime()),
        }
        if budget_s is not None:
            entry["budget_s"] = round(budget_s, 3)
        if probes is not None:
            entry["probes"] = probes
        cal = calibration if calibration is not None \
            else old.get("calibration")
        if cal is not None:
            entry["calibration"] = cal
        data["profiles"][key] = entry
        _write_file(path, data)
    reset()
    return path


def save_calibration(calibration: dict) -> None:
    """Persist only the calibration section of this platform's entry
    (ops/calibrate.py's write path), leaving tuned limits untouched.
    Best-effort like the old sidecar: persistence is an optimization,
    never a failure mode."""
    try:
        key = platform_key(require_jax_loaded=False)
        if key is None:
            return
        path = profile_path()
        with _file_lock(path):
            data = _read_file(use_cache=False) \
                or {"version": PROFILE_VERSION, "profiles": {}}
            entry = data["profiles"].setdefault(key, {"limits": {}})
            entry["calibration"] = calibration
            _write_file(path, data)
        reset()
    except OSError:
        pass


def load_calibration() -> dict | None:
    """This platform's calibration section, or None."""
    entry = load_entry()
    cal = (entry or {}).get("calibration")
    return cal if isinstance(cal, dict) else None


def _write_file(path: str, data: dict) -> None:
    import tempfile

    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    fd, tmp = tempfile.mkstemp(dir=os.path.dirname(path) or ".")
    with os.fdopen(fd, "w") as f:
        json.dump(data, f, indent=2, sort_keys=True)
        f.write("\n")
    os.replace(tmp, path)


class _file_lock:
    """Best-effort O_EXCL lock around the read-modify-write of the
    SHARED multi-platform file: pod workers of different device kinds
    persist calibrations/profiles through one path, and os.replace alone
    prevents torn reads but not lost updates (A and B read at t0, A
    writes, B's write discards A's platform entry). On contention past
    the timeout — or a stale lock from a killed writer — we proceed
    unlocked: persistence is an optimization, never a failure mode."""

    def __init__(self, path: str, timeout_s: float = 5.0):
        self.lock = path + ".lock"
        self.timeout_s = timeout_s
        self.fd: int | None = None

    def __enter__(self):
        import time as _time

        deadline = _time.monotonic() + self.timeout_s
        while True:
            try:
                os.makedirs(os.path.dirname(self.lock) or ".",
                            exist_ok=True)
                self.fd = os.open(self.lock,
                                  os.O_CREAT | os.O_EXCL | os.O_WRONLY)
                return self
            except FileExistsError:
                # Self-heal a stale lock (a writer killed between create
                # and unlink would otherwise disable the protection — and
                # add a full timeout stall — for every later persist).
                try:
                    if _time.time() - os.stat(self.lock).st_mtime \
                            > self.timeout_s:
                        os.unlink(self.lock)
                        continue
                except OSError:
                    pass                 # raced: re-try the O_EXCL open
                if _time.monotonic() > deadline:
                    return self          # contended: best-effort
                _time.sleep(0.05)
            except OSError:
                return self              # unwritable dir: best-effort

    def __exit__(self, *exc):
        if self.fd is not None:
            os.close(self.fd)
            try:
                os.unlink(self.lock)
            except OSError:
                pass
        return False


def reset() -> None:
    """Drop the memoized entry, the parsed-file cache, AND the limits()
    resolution built on them (tests; called automatically after every
    persist)."""
    global _CACHE, _FILE_CACHE
    _CACHE = None
    _FILE_CACHE = None
    from ..ops import limits as limits_mod

    limits_mod._TUNED = None
    limits_mod._LIMITS = None


# -- provenance / reporting -------------------------------------------------

def run_record() -> dict:
    """The compact profile stamp a run/bench record carries: the active
    profile hash, how many fields the PERSISTED profile tunes on this
    platform (counted from the store, so an embedding set_limits that
    merely snapshots the resolution doesn't hide them), and every field
    whose resolved value did not come from the dataclass default (with
    its provenance tag). ``tools/print_profile.py`` prints the full
    table."""
    from ..ops.limits import limits_provenance

    prov = limits_provenance()
    tuned = tuned_limits()
    rec = {
        "hash": profile_hash(),
        "tuned_fields": len(tuned or {}),
        "overrides": {k: v for k, v in sorted(prov.items())
                      if v != "default"},
    }
    if tuned is None:
        rec["note"] = ("profile file present but platform unresolvable "
                       "(no jax backend); run tools/print_profile.py "
                       "on the target machine")
    return rec


def report() -> dict:
    """The full resolved-limits report behind ``tools/print_profile.py``
    and ``jepsen-tpu tune --print-profile``: per-field value, default,
    provenance, kind, safe range and env var, plus the profile file's
    identity.

    This is an EXPLICIT operator diagnostic, so — unlike the lazy
    resolution path — it initializes a jax backend when one isn't up
    yet: a standalone `python tools/print_profile.py` must show the
    tuned values real runs resolve, not an eternal "unknown" (set
    JAX_PLATFORMS=cpu to stay off the TPU). If backend init fails, it
    degrades to the guarded view:
    platform "unknown", hash "unknown", defaults — still printable."""
    if not _backend_ready():
        try:
            import jax

            jax.devices()
        except Exception:
            pass
    from ..ops.limits import (env_var, field_meta, limits,
                              limits_provenance)

    lim = limits()
    meta = field_meta()
    prov = limits_provenance()
    fields_out = {}
    for name, m in meta.items():
        fields_out[name] = {
            "value": getattr(lim, name),
            "default": m["default"],
            "provenance": prov[name],
            "kind": m["kind"],
            "range": list(m["range"]),
            "env": env_var(name),
        }
    entry = load_entry()
    return {
        "platform": platform_key() or "unknown",
        "profile_path": profile_path(),
        "profile_version": PROFILE_VERSION,
        "profile_hash": profile_hash(),
        "profile_enabled": profile_enabled(),
        "measured_at": (entry or {}).get("measured_at"),
        "calibration": load_calibration(),
        "fields": fields_out,
    }
