"""Live telemetry export: Prometheus text exposition + subscription bus.

PR 1 made every run observable *after* the fact (telemetry.jsonl /
metrics.json artifacts); this module makes the process observable
*while it runs* — the layer ROADMAP item 1's checking-as-a-service
daemon stands on:

  * :func:`render_prometheus` — the active MetricsRegistry as
    Prometheus text exposition (text/plain; version=0.0.4): counters
    and gauges under stable ``jepsen_tpu_*`` names, histograms as
    summaries with p50/p95/p99 quantile lines (the obs/metrics.py
    log-bucket sketch), per-kernel/per-knob metric families split into
    a label instead of exploding the name space. Served by
    ``web/server.py`` at ``/metrics``.
  * :func:`subscribe` — an in-process bus streaming span/event/metric
    records AS THEY ARE APPENDED, so the web layer's ``/live`` SSE page
    (and the future daemon) consume telemetry without polling files.
    Trace records are published synchronously from the tracer's append
    (exact append order); metric updates are coalesced by a pump thread
    that drains the registry's dirty set a few times per second —
    streaming every ``counter.add`` on a hot kernel path would cost
    more than the kernels.

Zero-overhead discipline: with no subscribers, publish() is one
attribute check; with telemetry disabled (JEPSEN_TPU_TELEMETRY=0) the
null tracer never publishes at all and /metrics renders an empty
registry. Everything is stdlib-only.
"""

from __future__ import annotations

import json
import queue
import re
import threading
import time
from typing import Iterable, Optional

from .metrics import MetricsRegistry

PROM_PREFIX = "jepsen_tpu_"
PROM_CONTENT_TYPE = "text/plain; version=0.0.4; charset=utf-8"

# Dotted metric families whose LAST component is an open-ended (but
# statically bounded — kernel names, knob names) member set: exported
# as one Prometheus family with a label instead of one metric name per
# member. The exported family name gains a `_by_<label>` suffix so it
# can NEVER collide with a plain metric of the same prefix (the
# `wgl.compile_s` counter and the `wgl.compile_s.<kernel>` histograms
# must be distinct Prometheus families — one name with two types is an
# invalid exposition). `wgl.compile_s.wgl3-chunk` ->
# `jepsen_tpu_wgl_compile_s_by_kernel{kernel="wgl3-chunk"}`.
LABELED_FAMILIES = {
    "wgl.compile_s": "kernel",
    "wgl.execute_s": "kernel",
    "tune.probe_s": "knob",
    "tune.chosen": "knob",
}

_NAME_SUB = re.compile(r"[^a-zA-Z0-9_:]")
_QUANTILE_KEYS = (("p50", "0.5"), ("p95", "0.95"), ("p99", "0.99"))


def sanitize_metric_name(name: str) -> str:
    """Dotted registry name -> Prometheus metric name body: every
    character outside [a-zA-Z0-9_:] becomes '_', and a leading digit is
    prefixed so the result always matches the exposition grammar."""
    out = _NAME_SUB.sub("_", name)
    if out and out[0].isdigit():
        out = "_" + out
    return out


def sanitize_label_value(value: str) -> str:
    """Escape a label value per the exposition format (backslash,
    double-quote, newline)."""
    return (str(value).replace("\\", "\\\\").replace('"', '\\"')
            .replace("\n", "\\n"))


def _family_of(name: str) -> tuple[str, Optional[str], Optional[str]]:
    """(exported family, label name, label value) — label parts None
    for plain (unlabeled) metrics; labeled families export under a
    `_by_<label>` name so they never collide with a plain metric."""
    for fam, label in LABELED_FAMILIES.items():
        if name.startswith(fam + ".") and len(name) > len(fam) + 1:
            return f"{fam}_by_{label}", label, name[len(fam) + 1:]
    return name, None, None


def _fmt(v) -> str:
    if v is None:
        return "NaN"
    f = float(v)
    if f == int(f) and abs(f) < 1e15:
        return str(int(f))
    return repr(f)


def render_prometheus(snapshot: dict[str, dict],
                      extra_lines: Iterable[str] = ()) -> str:
    """A MetricsRegistry snapshot as Prometheus text exposition.

    Counters render as-is, gauges render their `last` (0 when never
    set — the pre-registered contract keys stay visible), histograms
    render as summaries: quantile lines from the log-bucket sketch plus
    `_sum` / `_count`. One `# TYPE` line per family, families sorted
    for a stable (diffable, goldenable) output. `extra_lines` lets the
    web layer append process-level series (health state, up)."""
    families: dict[str, dict] = {}   # prom name -> {type, lines: [...]}
    for name, rec in sorted(snapshot.items()):
        fam, label, member = _family_of(name)
        prom = PROM_PREFIX + sanitize_metric_name(fam)
        kind = rec.get("type")
        lbl = (f'{{{label}="{sanitize_label_value(member)}"}}'
               if label is not None else "")
        if kind == "counter":
            f = families.setdefault(prom, {"type": "counter", "lines": []})
            f["lines"].append(f"{prom}{lbl} {_fmt(rec.get('value', 0))}")
        elif kind == "gauge":
            f = families.setdefault(prom, {"type": "gauge", "lines": []})
            f["lines"].append(
                f"{prom}{lbl} {_fmt(rec.get('last') or 0)}")
        elif kind == "histogram":
            f = families.setdefault(prom, {"type": "summary", "lines": []})
            for key, q in _QUANTILE_KEYS:
                qlbl = (lbl[:-1] + f',quantile="{q}"}}') if lbl \
                    else f'{{quantile="{q}"}}'
                f["lines"].append(f"{prom}{qlbl} {_fmt(rec.get(key))}")
            f["lines"].append(f"{prom}_sum{lbl} {_fmt(rec.get('sum', 0))}")
            f["lines"].append(
                f"{prom}_count{lbl} {_fmt(rec.get('count', 0))}")
    out: list[str] = []
    for prom in sorted(families):
        out.append(f"# TYPE {prom} {families[prom]['type']}")
        out.extend(families[prom]["lines"])
    out.extend(extra_lines)
    return "\n".join(out) + "\n"


# -- subscription bus ------------------------------------------------------

class Subscription:
    """One subscriber's bounded record queue. Records are dicts with a
    "kind" key: span / event (tracer records, exact append order) and
    metric ({"kind": "metric", "name": ..., "metric": snapshot}). A
    full queue drops the oldest-unread records' successors and counts
    them (`dropped`) — a slow consumer must never backpressure the
    harness."""

    def __init__(self, kinds: Optional[set] = None, maxsize: int = 4096):
        self.kinds = set(kinds) if kinds else None
        self.dropped = 0
        self.closed = False
        self._q: queue.Queue = queue.Queue(maxsize=maxsize)

    def _offer(self, rec: dict) -> None:
        if self.closed or (self.kinds and rec.get("kind") not in self.kinds):
            return
        try:
            self._q.put_nowait(rec)
        except queue.Full:
            self.dropped += 1

    def get(self, timeout: Optional[float] = None) -> Optional[dict]:
        """Next record, or None on timeout / after close."""
        try:
            return self._q.get(timeout=timeout) if timeout is not None \
                else self._q.get_nowait()
        except queue.Empty:
            return None

    def close(self) -> None:
        self.closed = True
        _BUS.unsubscribe(self)


# jtlint: disable=JTL505 -- the pump thread is self-terminating by
# design: _pump_metrics exits (and clears self._pump) the moment the
# last subscriber closes, and it is daemon=True — a module-global bus
# has no shutdown path to join it from, and needs none.
class _Bus:
    """Module-global publish/subscribe fan-out. `publish` is called
    from the tracer's append path (under the tracer lock), so the
    no-subscriber fast path must stay one attribute check."""

    def __init__(self):
        from .sync import maybe_wrap

        self._lock = maybe_wrap(threading.Lock(),
                                "obs.export._Bus._lock")
        self._subs: tuple[Subscription, ...] = ()
        self._pump: Optional[threading.Thread] = None
        self.pump_interval_s = 0.25

    @property
    def active(self) -> bool:
        with self._lock:
            return bool(self._subs)

    def subscribe(self, kinds: Optional[set] = None,
                  maxsize: int = 4096) -> Subscription:
        sub = Subscription(kinds=kinds, maxsize=maxsize)
        with self._lock:
            self._subs = self._subs + (sub,)
            if self._pump is None or not self._pump.is_alive():
                self._pump = threading.Thread(
                    target=self._pump_metrics, name="obs-metric-pump",
                    daemon=True)
                self._pump.start()
        return sub

    def unsubscribe(self, sub: Subscription) -> None:
        with self._lock:
            self._subs = tuple(s for s in self._subs if s is not sub)

    def publish(self, rec: dict) -> None:
        # Deliberate lock-free fast path: _subs is an IMMUTABLE tuple
        # swapped under the bus lock, publish runs inside the tracer's
        # append (every span on every thread) and must stay one
        # attribute check when nobody subscribed. A reader sees either
        # the old or the new tuple — both are safe to fan out to.
        # jtlint: disable=JTL501 -- lock-free by design: immutable
        # tuple swap (writers hold the bus lock), benign stale read;
        # taking the lock here would serialize every traced span
        # against subscribe/unsubscribe.
        subs = self._subs
        if not subs:
            return
        for s in subs:
            s._offer(rec)

    def _pump_metrics(self) -> None:
        """Coalesced metric streaming: while any subscriber exists,
        drain the ACTIVE registry's dirty set every pump_interval_s and
        publish one `metric` record per changed instrument. Exits when
        the last subscriber closes (a later subscribe restarts it)."""
        from . import get_metrics   # late: obs package is initialized

        while True:
            with self._lock:
                if not self._subs:
                    self._pump = None
                    return
            reg = get_metrics()
            if isinstance(reg, MetricsRegistry) and reg.enabled:
                try:
                    for name, snap in sorted(reg.drain_dirty().items()):
                        self.publish({"kind": "metric", "name": name,
                                      "metric": snap})
                except Exception:   # pragma: no cover - never kill the pump
                    pass
            time.sleep(self.pump_interval_s)


_BUS = _Bus()


def subscribe(kinds: Optional[set] = None,
              maxsize: int = 4096) -> Subscription:
    """Subscribe to the live telemetry stream. `kinds` filters record
    kinds ({"span", "event", "metric"}); None receives everything.
    Close the subscription when done — an abandoned one just fills its
    bounded queue and counts drops, but costs a fan-out check per
    record while registered."""
    return _BUS.subscribe(kinds=kinds, maxsize=maxsize)


def bus_publish(rec: dict) -> None:
    """The tracer listener obs.capture() installs: forward one appended
    trace record to the bus (no-op without subscribers)."""
    _BUS.publish(rec)


def bus_active() -> bool:
    return _BUS.active


# -- SSE helpers -----------------------------------------------------------

def sse_message(data, event: Optional[str] = None) -> bytes:
    """One Server-Sent-Events message: `data` is JSON-encoded (unless
    already a string); multi-line data is framed per the SSE spec."""
    if not isinstance(data, str):
        data = json.dumps(data, default=str)
    out = []
    if event:
        out.append(f"event: {event}")
    out.extend(f"data: {line}" for line in data.split("\n"))
    return ("\n".join(out) + "\n\n").encode("utf-8")
