"""Backend health supervisor: healthy -> degraded -> wedged, and back.

The serve daemon and the dispatch paths need one view of whether the
device answers; this is it.

State machine (one supervisor per process, :func:`get_supervisor`):

  healthy --[consecutive failures >= fail_degraded]--> degraded
  degraded --[consecutive failures >= fail_wedged]--> wedged
  * --[any success]--> healthy           (recovery is immediate: the
                                          backend either completes a
                                          dispatch or it doesn't)

Signals are PASSIVE only: the hot paths report outcomes they already
have. Every successful kernel dispatch in stream/engine.py's consumer
and sched/engine.py's bucket launcher is a free health proof
(:meth:`~BackendSupervisor.note_ok`, a few ns), and a dispatch exception
is a failure (:meth:`~BackendSupervisor.note_failure`). There is no
active probe: a probe would have to run in a child process, and a child
cannot reach the chip its parent holds.

Transitions are recorded as obs events (`health.transition`) and the
`health.state` gauge (0 healthy / 1 degraded / 2 wedged) when a capture
is active, and carry last-transition provenance (when, why, which
caller) — exposed verbatim by `/healthz` (web/server.py).

Env knobs (doc/telemetry.md "Backend health"):
  JEPSEN_TPU_HEALTH_FAIL_DEGRADED     consecutive failures -> degraded (1)
  JEPSEN_TPU_HEALTH_FAIL_WEDGED       consecutive failures -> wedged (3)
"""

from __future__ import annotations

import os
import threading
import time
from typing import Optional

HEALTHY = "healthy"
DEGRADED = "degraded"
WEDGED = "wedged"
# State -> numeric level: the health.state gauge and the /metrics
# jepsen_tpu_health_state series share this one mapping.
STATE_LEVEL = {HEALTHY: 0, DEGRADED: 1, WEDGED: 2}
_STATE_LEVEL = STATE_LEVEL


def _env_int(var: str, default: int) -> int:
    try:
        return int(os.environ.get(var, ""))
    except ValueError:
        return default


class BackendSupervisor:
    """The healthy/degraded/wedged state machine. Thread-safe: passive
    notes come from the stream consumer thread, the asyncio event loop,
    and sched's caller concurrently."""

    def __init__(self, fail_degraded: Optional[int] = None,
                 fail_wedged: Optional[int] = None):
        self.fail_degraded = fail_degraded if fail_degraded is not None \
            else max(1, _env_int("JEPSEN_TPU_HEALTH_FAIL_DEGRADED", 1))
        self.fail_wedged = fail_wedged if fail_wedged is not None \
            else max(self.fail_degraded,
                     _env_int("JEPSEN_TPU_HEALTH_FAIL_WEDGED", 3))
        from .sync import maybe_wrap

        self._lock = maybe_wrap(threading.Lock(),
                                "obs.health.BackendSupervisor._lock")
        self.state = HEALTHY
        self._since_wall = time.time()
        self._consecutive_failures = 0
        self._ok_total = 0
        self._fail_total = 0
        self._last_failure_reason: Optional[str] = None
        self._last_transition: Optional[dict] = None

    # -- signals ----------------------------------------------------------

    def note_ok(self, source: str = "passive") -> None:
        """A backend interaction succeeded (a kernel dispatch).
        Recovery is immediate: any success proves the backend answers."""
        with self._lock:
            self._ok_total += 1
            self._consecutive_failures = 0
            if self.state != HEALTHY:
                self._transition(HEALTHY, f"backend interaction succeeded "
                                          f"({source})", source)

    def note_failure(self, reason: str, source: str = "passive",
                     wedged: bool = False) -> None:
        """A backend interaction failed. `wedged=True` (the caller saw
        the device hang) escalates straight to wedged; otherwise
        consecutive failures walk the thresholds."""
        with self._lock:
            self._fail_total += 1
            self._consecutive_failures += 1
            self._last_failure_reason = reason
            if wedged:
                if self.state != WEDGED:
                    self._transition(WEDGED, reason, source)
                return
            n = self._consecutive_failures
            if n >= self.fail_wedged and self.state != WEDGED:
                self._transition(
                    WEDGED, f"{n} consecutive failures "
                            f"(>= fail_wedged={self.fail_wedged}): "
                            f"{reason}", source)
            elif n >= self.fail_degraded and self.state == HEALTHY:
                self._transition(
                    DEGRADED, f"{n} consecutive failure(s) "
                              f"(>= fail_degraded={self.fail_degraded}): "
                              f"{reason}", source)

    # -- state ------------------------------------------------------------

    def _transition(self, to: str, reason: str, source: str) -> None:
        """Record a state change (caller holds the lock)."""
        frm = self.state
        self.state = to
        self._since_wall = time.time()
        self._last_transition = {
            "from": frm, "to": to, "reason": reason, "source": source,
            "at": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        }
        # Observability of the observer: transitions land in the active
        # capture as an event + gauge (no-ops outside a capture).
        from . import get_metrics, get_tracer

        get_tracer().event("health.transition", **self._last_transition)
        get_metrics().gauge("health.state").set(_STATE_LEVEL[to])

    def snapshot(self) -> dict:
        """The /healthz view: current state with
        last-transition provenance and signal counters."""
        with self._lock:
            return {
                "state": self.state,
                "since": time.strftime("%Y-%m-%dT%H:%M:%SZ",
                                       time.gmtime(self._since_wall)),
                "consecutive_failures": self._consecutive_failures,
                "ok_total": self._ok_total,
                "fail_total": self._fail_total,
                "last_failure": self._last_failure_reason,
                "last_transition": dict(self._last_transition)
                if self._last_transition else None,
                "thresholds": {"fail_degraded": self.fail_degraded,
                               "fail_wedged": self.fail_wedged},
            }


_supervisor_lock = threading.Lock()
_supervisor: Optional[BackendSupervisor] = None


def get_supervisor() -> BackendSupervisor:
    """The process-wide supervisor (created on first use — env knobs
    are read then)."""
    global _supervisor
    with _supervisor_lock:
        if _supervisor is None:
            _supervisor = BackendSupervisor()
        return _supervisor


def reset_supervisor(sup: Optional[BackendSupervisor] = None
                     ) -> Optional[BackendSupervisor]:
    """Swap (or clear) the process supervisor; returns the previous one.
    Tests install their own supervisors through this."""
    global _supervisor
    with _supervisor_lock:
        prev, _supervisor = _supervisor, sup
        return prev
