"""Tiny stdlib HTTP server over store/ — `lein run serve` equivalent.

The reference serves its store with ring/jetty + a directory browser
(src/jepsen/etcdemo.clj:198; deps jepsen.etcdemo.iml:82-99). Same capability
on http.server: an index of runs with verdicts, static file serving of
each run dir (charts, timelines, logs, history), and a per-run telemetry
page (/telemetry/<run>) rendering the span tree + metric table the
harness records in telemetry.jsonl / metrics.json (obs/).

Live observability plane (ISSUE 8) — three process-level endpoints on
top of the per-run artifacts:

  /metrics     Prometheus text exposition of the ACTIVE capture's
               registry (obs/export.py) + backend health series
  /healthz     the backend supervisor's state as JSON (obs/health.py);
               HTTP 503 when wedged so load balancers see it
  /live        an in-flight-run page fed by Server-Sent Events from
               /live/events (the obs subscription bus): span tree, op
               throughput, nemesis events, stream gauges, health

These observe the SERVING PROCESS — they show a run in flight when the
server shares the process with the runner (`jepsen-tpu test
--live-port N`, or the future checking-as-a-service daemon)."""

from __future__ import annotations

import html
import json
import urllib.parse
from functools import partial
from http.server import SimpleHTTPRequestHandler, ThreadingHTTPServer

from .. import obs
from ..obs import (METRICS_FILE, TELEMETRY_FILE, export, health,
                   read_jsonl, read_metrics)
from ..store import Store


def _run_summary(results: dict) -> str:
    """Compact why-it-failed / what-ran column: op count, rate, and for
    invalid runs the failing detail (per-key failed ops, elle anomaly
    types) pulled from the composed result tree. Served checks
    (serve/daemon.py artifacts, ISSUE 13) summarize their tenant /
    batch / route instead — they are browsable history like CLI runs."""
    bits = []
    srv = results.get("serve") or {}
    if srv.get("tenant"):
        bits.append(f"tenant {srv['tenant']}")
        batch = srv.get("batch") or {}
        if batch.get("size"):
            bits.append(f"batch of {batch['size']}")
        if srv.get("route") and srv["route"] != "jax":
            bits.append(f"route {srv['route']}")
        if srv.get("op_count"):
            bits.append(f"{srv['op_count']} ops")
    perf = results.get("perf") or {}
    if perf.get("count"):
        bits.append(f"{perf['count']} ops")
    if perf.get("rate_hz"):
        bits.append(f"{perf['rate_hz']:.0f}/s")
    indep = results.get("indep") or {}
    for key, sub in (indep.get("results") or {}).items():
        lin = sub.get("linear", sub) if isinstance(sub, dict) else {}
        if isinstance(lin, dict) and lin.get("valid") is False:
            op = lin.get("failed_op")
            bits.append(f"key {key}: {op}" if op else f"key {key}: invalid")
    # Whole-history workloads (gset/mutex/multiregister) have no per-key
    # results — the failing op sits directly under indep.linear.
    whole_lin = indep.get("linear") or {}
    if whole_lin.get("valid") is False:
        op = whole_lin.get("failed_op")
        bits.append(str(op) if op else "invalid")
    elle = indep.get("elle") or {}
    if elle.get("anomaly_types"):
        bits.append("anomalies: " + ", ".join(elle["anomaly_types"]))
    if indep.get("lost_count"):   # untruncated ('lost' caps at 100)
        bits.append(f"lost adds: {indep['lost_count']}")
    return "; ".join(str(b) for b in bits[:4])


def _check_perf_columns(run) -> tuple[str, str, str, str]:
    """(throughput, padding-waste, sweep-mode, live-tile-ratio) columns
    for the run index, from the run's metrics.json (obs/): check
    throughput = encoded history events over the kernels'
    compile+execute wall, padding waste = the last launch's padded/real
    step ratio (wgl3._record_padding), sweep mode = which dense-lattice
    sweep the run's checks took (the wgl.sweep_* counters — sparse
    engine, ops/wgl3_sparse.py), live tiles = the wgl.live_tile_ratio
    occupancy gauge. Blank when the run has no telemetry or never
    launched a kernel."""
    try:
        metrics = read_metrics(run.path / METRICS_FILE)
    except Exception:
        return "", "", "", ""

    def counter(name: str) -> float:
        rec = metrics.get(name) or {}
        return float(rec.get("value", 0.0)) \
            if rec.get("type") == "counter" else 0.0

    events = counter("encode.event_bytes") / 24.0   # 6 int32 per event
    kernel_s = counter("wgl.compile_s") + counter("wgl.execute_s")
    eps = f"{events / kernel_s:,.0f}/s" if events and kernel_s else ""
    ratio = (metrics.get("wgl.step_padding_ratio") or {}).get("last")
    waste = f"{ratio:.2f}x" if isinstance(ratio, (int, float)) else ""
    sp = counter("wgl.sweep_steps_sparse")
    dn = counter("wgl.sweep_steps_dense")
    if sp and dn:
        sweep = f"mixed ({100 * sp / (sp + dn):.0f}% sp)"
    elif sp:
        sweep = "sparse"
    elif dn or counter("wgl.sweep_checks_dense") \
            or counter("wgl.sweep_checks_mixed"):
        sweep = "dense"
    else:
        sweep = ""
    lt = (metrics.get("wgl.live_tile_ratio") or {}).get("last")
    live = f"{lt:.1%}" if isinstance(lt, (int, float)) else ""
    return eps, waste, sweep, live


def _stream_columns(results: dict) -> tuple[str, str]:
    """(check mode, overlap ratio) columns for the run index, from the
    run's results.json (runner/core.py stamps check_mode + the stream
    session record; serve/daemon.py stamps "serve", ISSUE 13). Blank
    for runs recorded before streaming existed; overlap shows only for
    streamed runs (a post run has none by definition)."""
    mode = results.get("check_mode")
    if mode not in ("post", "stream", "serve"):
        return "", ""
    if mode == "serve":
        ov = ((results.get("serve") or {}).get("stream")
              or {}).get("overlap_ratio")
        return mode, (f"{ov:.0%}" if isinstance(ov, (int, float)) else "")
    if mode != "stream":
        return mode, ""
    ov = (results.get("stream") or {}).get("overlap_ratio")
    return mode, (f"{ov:.0%}" if isinstance(ov, (int, float)) else "")


def _corpus_banner_html(store: Store) -> str:
    """Regression-corpus summary strip for the index (campaign/bank.py,
    ISSUE 15): banked minimal witnesses per anomaly signature. Empty
    string when the store has no bank."""
    try:
        from ..campaign.bank import bank_summary

        summary = bank_summary(store.root)
    except Exception:
        return ""
    if not summary:
        return ""
    sigs = ", ".join(f"{html.escape(slug)} ({n})"
                     for slug, n in sorted(summary["signatures"].items()))
    return (f"<p style='background:#eef3fb;padding:8px'>regression "
            f"corpus: <b>{summary['total']}</b> banked witness(es) — "
            f"{sigs} — replay with <code>jepsen-tpu campaign "
            f"--replay-corpus</code></p>")


def _index_html(store: Store) -> str:
    rows = []
    for run in reversed(store.runs()):
        rel = run.path.relative_to(store.root)
        try:
            results = run.read_results()
            valid = results.get("valid")
        except Exception:
            results, valid = {}, "?"
        try:
            summary = _run_summary(results)
        except Exception:   # off-schema results must not hide the verdict
            summary = ""
        color = {True: "#2a9d43", False: "#d43a2a"}.get(valid, "#e9a820")
        href = urllib.parse.quote(f"/files/{rel}/")
        tele = ""
        if (run.path / TELEMETRY_FILE).exists():
            thref = urllib.parse.quote(f"/telemetry/{rel}")
            tele = f"<a href='{thref}'>telemetry</a>"
        eps, waste, sweep, live = _check_perf_columns(run)
        mode, overlap = _stream_columns(results)
        rows.append(
            f"<tr><td><a href='{href}'>"
            f"{html.escape(str(rel))}</a></td>"
            f"<td style='color:{color};font-weight:bold'>{valid}</td>"
            f"<td style='color:#666'>{html.escape(summary)}</td>"
            f"<td>{html.escape(eps)}</td>"
            f"<td>{html.escape(waste)}</td>"
            f"<td>{html.escape(sweep)}</td>"
            f"<td>{html.escape(live)}</td>"
            f"<td>{html.escape(mode)}</td>"
            f"<td>{html.escape(overlap)}</td>"
            f"<td><code>{html.escape(_profile_column(results))}</code></td>"
            f"<td>{tele}</td></tr>")
    return (
        "<!doctype html><html><head><meta charset='utf-8'>"
        "<title>jepsen-tpu store</title>"
        "<style>body{font-family:sans-serif}td{padding:4px 12px}</style>"
        "</head><body><h2>test runs</h2>"
        "<p><a href='/live'>live</a> · <a href='/metrics'>metrics</a> · "
        "<a href='/healthz'>healthz</a></p>"
        f"{_corpus_banner_html(store)}"
        f"<table><tr><th>run</th><th>valid</th><th>detail</th>"
        f"<th>check eps</th><th>pad waste</th>"
        f"<th>sweep</th><th>live tiles</th>"
        f"<th>check mode</th><th>overlap</th>"
        f"<th>profile</th>"
        f"<th>obs</th></tr>"
        f"{''.join(rows)}</table>"
        "</body></html>")


def _profile_column(results: dict) -> str:
    """Which tuning profile the run's check resolved (runner/core.py
    stamps results.json with tune/profile.run_record): the short hash,
    plus the tuned-field count when any applied. Blank for runs recorded
    before the autotuner existed."""
    prof = results.get("profile")
    if not isinstance(prof, dict) or not prof.get("hash"):
        return ""
    h = str(prof["hash"])
    n = prof.get("tuned_fields") or 0
    return f"{h} ({n} tuned)" if n else h


# -- telemetry page --------------------------------------------------------

def _perf_summary_html(run_dir) -> str:
    """Compact per-run strip on the telemetry page mirroring the index's
    perf columns (check eps / pad waste / sweep mode / live-tile ratio),
    plus the streaming check gauges (stream/engine.py) next to them —
    overlap ratio and the watermark's lag high-water mark; empty when
    the run recorded none of them."""
    class _Run:
        path = run_dir

    eps, waste, sweep, live = _check_perf_columns(_Run)
    bits = [("check eps", eps), ("pad waste", waste), ("sweep", sweep),
            ("live tiles", live)]
    bits += _dedup_bits(run_dir)
    bits += _stream_gauge_bits(run_dir)
    bits += _elle_bits(run_dir)
    bits += _spill_bits(run_dir)
    shown = [f"{name}: <b>{html.escape(val)}</b>"
             for name, val in bits if val]
    return f"<p class='a'>{' · '.join(shown)}</p>" if shown else ""


def _dedup_bits(run_dir) -> list[tuple[str, str]]:
    """Frontier-dedup telemetry (ISSUE 10, ops/canon.py) for the
    telemetry strip: configs pruned by canonicalization, the dedup
    ratio gauge, and the previously-silent sparse work-list overflow
    rounds — all blank for runs that recorded none."""
    try:
        metrics = read_metrics(run_dir / METRICS_FILE)
    except Exception:
        return []
    out: list[tuple[str, str]] = []
    c = metrics.get("wgl.configs_pruned") or {}
    if c.get("type") == "counter" and c.get("value"):
        out.append(("configs pruned", f"{c['value']:,.0f}"))
    g = metrics.get("wgl.frontier_dedup_ratio") or {}
    if g.get("type") == "gauge" and g.get("n") \
            and isinstance(g.get("last"), (int, float)):
        out.append(("dedup ratio", f"{g['last']:.1%}"))
    c = metrics.get("wgl.sparse_overflow_rounds") or {}
    if c.get("type") == "counter" and c.get("value"):
        out.append(("sparse overflow rounds", f"{c['value']:,.0f}"))
    return out


def _elle_bits(run_dir) -> list[tuple[str, str]]:
    """Elle closure-engine telemetry (ISSUE 11, ops/cycles.py) for the
    strip: graphs per route (dense / batched / tiled / oracle) and the
    streamed-session txn count — blank for runs without txn checks."""
    try:
        metrics = read_metrics(run_dir / METRICS_FILE)
    except Exception:
        return []

    def counter(name: str) -> int:
        c = metrics.get(name) or {}
        return int(c.get("value") or 0) if c.get("type") == "counter" \
            else 0

    routes = [(r, counter(f"elle.graphs_{r}"))
              for r in ("dense", "batched", "tiled", "oracle")]
    out: list[tuple[str, str]] = []
    if any(v for _, v in routes):
        out.append(("elle graphs",
                    " / ".join(f"{v} {r}" for r, v in routes if v)))
    txns = counter("elle.stream_txns")
    if txns:
        out.append(("elle streamed txns", f"{txns:,}"))
    return out


def _spill_bits(run_dir) -> list[tuple[str, str]]:
    """Out-of-core spill-tier telemetry (ISSUE 20, store/spill.py +
    store/encode_cache.py) for the strip: spill traffic (writes/reads
    with byte volumes), checkpoint compression ratio, eviction counts
    (window + encode-cache GC), and the long-haul lane's peak-RSS delta
    — all blank for runs that never spilled."""
    try:
        metrics = read_metrics(run_dir / METRICS_FILE)
    except Exception:
        return []

    def counter(name: str) -> int:
        c = metrics.get(name) or {}
        return int(c.get("value") or 0) if c.get("type") == "counter" \
            else 0

    out: list[tuple[str, str]] = []
    w, r = counter("spill.writes"), counter("spill.reads")
    if w or r:
        out.append(("spill",
                    f"{w} w / {r} r "
                    f"({counter('spill.bytes_written') / (1 << 20):.1f}"
                    f" / {counter('spill.bytes_read') / (1 << 20):.1f}"
                    " MB)"))
    g = metrics.get("spill.compress_ratio") or {}
    if g.get("type") == "gauge" and g.get("n") \
            and isinstance(g.get("last"), (int, float)):
        out.append(("spill compress", f"{g['last']:.2f}x"))
    ev = counter("spill.evictions") + counter("encode.cache_evictions")
    if ev:
        out.append(("spill evictions", f"{ev:,}"))
    g = metrics.get("spill.peak_rss_mb") or {}
    if g.get("type") == "gauge" and g.get("n") \
            and isinstance(g.get("last"), (int, float)):
        out.append(("long-haul peak rss", f"{g['last']:g} MB"))
    return out


def _stream_gauge_bits(run_dir) -> list[tuple[str, str]]:
    """The stream.overlap_ratio / stream.watermark_lag gauges from the
    run's metrics.json, formatted for the telemetry strip. A post-hoc
    run records both at zero-n (pre-registered, never set) — shown
    blank."""
    try:
        metrics = read_metrics(run_dir / METRICS_FILE)
    except Exception:
        return []
    out: list[tuple[str, str]] = []
    g = metrics.get("stream.overlap_ratio") or {}
    if g.get("type") == "gauge" and g.get("n") \
            and isinstance(g.get("last"), (int, float)):
        out.append(("stream overlap", f"{g['last']:.0%}"))
    g = metrics.get("stream.watermark_lag") or {}
    if g.get("type") == "gauge" and g.get("n") \
            and g.get("max") is not None:
        out.append(("watermark lag", f"{g.get('last'):g} "
                                     f"(max {g['max']:g})"))
    return out

def _fmt_ms(ns: int) -> str:
    return f"{ns / 1e6:,.1f}"


def _fmt_attrs(attrs: dict) -> str:
    if not attrs:
        return ""
    return html.escape(", ".join(f"{k}={v}" for k, v in attrs.items()))


def _span_tree_html(records: list[dict]) -> str:
    """Nested list of spans (parent links -> tree), each with duration
    and attrs; events render under their enclosing span. Spans keep
    completion order within one parent — close enough to timeline order
    for phase-level reading, and robust to concurrent workers."""
    spans = [r for r in records if r.get("kind") == "span"]
    events = [r for r in records if r.get("kind") == "event"]
    children: dict = {}
    for s in spans:
        children.setdefault(s.get("parent"), []).append(s)
    ev_by_span: dict = {}
    for e in events:
        ev_by_span.setdefault(e.get("span"), []).append(e)
    for group in (children, ev_by_span):
        for v in group.values():
            v.sort(key=lambda r: r.get("t0_ns", r.get("t_ns", 0)))

    def render(span_id) -> str:
        out = []
        for e in ev_by_span.get(span_id, []):
            out.append(
                f"<li class='ev'>⚡ {html.escape(str(e['name']))}"
                f" <span class='t'>@{_fmt_ms(e.get('t_ns', 0))} ms</span>"
                f" <span class='a'>{_fmt_attrs(e.get('attrs') or {})}"
                f"</span></li>")
        for s in children.get(span_id, []):
            dur = s.get("t1_ns", 0) - s.get("t0_ns", 0)
            err = " class='err'" if s.get("status") == "error" else ""
            out.append(
                f"<li><span{err}><b>{html.escape(str(s['name']))}</b></span>"
                f" <span class='t'>{_fmt_ms(dur)} ms</span>"
                f" <span class='a'>{_fmt_attrs(s.get('attrs') or {})}"
                f"</span><ul>{render(s['id'])}</ul></li>")
        return "".join(out)

    # Roots: spans with no recorded parent (parent None or missing — a
    # dropped/unclosed parent must not hide its finished children).
    known = {s["id"] for s in spans}
    roots = [s for s in spans
             if s.get("parent") is None or s.get("parent") not in known]
    children[None] = sorted(roots, key=lambda s: s.get("t0_ns", 0))
    return f"<ul class='tree'>{render(None)}</ul>"


def _kernel_attribution_html(metrics: dict) -> str:
    """Per-kernel attribution table: every kernel geometry the run
    compiled, with its compile/execute wall (the
    wgl.compile_s.<k>/wgl.execute_s.<k> histograms). Empty string when
    the run recorded no per-kernel series (older artifacts)."""
    kernels: dict[str, dict] = {}

    def fold(prefix: str, field: str, value_of):
        for name, rec in metrics.items():
            if name.startswith(prefix + "."):
                kernels.setdefault(name[len(prefix) + 1:], {})[field] = \
                    value_of(rec)

    fold("wgl.compile_s", "compiles", lambda r: r.get("count", 0))
    fold("wgl.compile_s", "compile_s", lambda r: r.get("sum", 0.0))
    fold("wgl.execute_s", "calls", lambda r: r.get("count", 0))
    fold("wgl.execute_s", "execute_s", lambda r: r.get("sum", 0.0))
    fold("wgl.execute_s", "p95_s", lambda r: r.get("p95"))
    if not kernels:
        return ""

    rows = []
    for k in sorted(kernels):
        r = kernels[k]
        p95 = r.get("p95_s")
        p95_cell = f"{p95:,.4g}s" if isinstance(p95, (int, float)) else ""
        rows.append(
            f"<tr><td><code>{html.escape(k)}</code></td>"
            f"<td>{r.get('compiles', 0)}</td>"
            f"<td>{r.get('compile_s', 0.0):.3f}</td>"
            f"<td>{r.get('calls', 0)}</td>"
            f"<td>{r.get('execute_s', 0.0):.3f}</td>"
            f"<td>{p95_cell}</td></tr>")
    return ("<h3>kernel attribution</h3>"
            "<table><tr><th>kernel</th><th>compiles</th>"
            "<th>compile s</th><th>calls</th><th>execute s</th>"
            "<th>p95 call</th></tr>"
            f"{''.join(rows)}</table>")


def _metrics_table_html(metrics: dict) -> str:
    rows = []
    for name, rec in sorted(metrics.items()):
        kind = rec.get("type", "?")
        if kind == "counter":
            val = f"{rec.get('value', 0):,.6g}"
        elif kind == "gauge":
            val = (f"last {rec.get('last')} / min {rec.get('min')} / "
                   f"max {rec.get('max')} (n={rec.get('n', 0)})")
        else:
            val = (f"n {rec.get('count', 0)}, sum {rec.get('sum', 0):.6g}, "
                   f"min {rec.get('min')}, max {rec.get('max')}, "
                   f"avg {round(rec['avg'], 6) if rec.get('avg') is not None else None}")
            if rec.get("p50") is not None:
                val += (f", p50 {rec['p50']:.4g} / p95 "
                        f"{rec.get('p95'):.4g} / p99 {rec.get('p99'):.4g}")
        rows.append(f"<tr><td><code>{html.escape(name)}</code></td>"
                    f"<td>{kind}</td><td>{html.escape(val)}</td></tr>")
    return (f"<table><tr><th>metric</th><th>type</th><th>value</th></tr>"
            f"{''.join(rows)}</table>")


def _telemetry_html(store: Store, rel: str) -> str | None:
    """Render <store>/<rel>'s telemetry artifacts; None -> 404 (missing
    run, no artifacts, or a path escaping the store root)."""
    root = store.root.resolve()
    run_dir = (root / rel).resolve()
    if root not in run_dir.parents or not run_dir.is_dir():
        return None
    tele = run_dir / TELEMETRY_FILE
    metr = run_dir / METRICS_FILE
    if not tele.exists() and not metr.exists():
        return None
    parts = [
        "<!doctype html><html><head><meta charset='utf-8'>",
        f"<title>telemetry — {html.escape(rel)}</title>",
        "<style>body{font-family:sans-serif;margin:2em}"
        "td{padding:2px 10px;border-bottom:1px solid #eee}"
        "ul.tree,ul.tree ul{list-style:none;border-left:1px solid #ccc;"
        "padding-left:1.2em;margin:2px 0}"
        ".t{color:#2a6db0}.a{color:#888;font-size:90%}"
        ".err{color:#d43a2a}.ev{color:#555}"
        ".warn{color:#b05a00;background:#fff3e0;border:1px solid #e9a820;"
        "padding:8px;font-weight:bold}</style></head><body>",
        f"<h2>telemetry — {html.escape(rel)}</h2>",
        f"<p><a href='/'>index</a> · "
        f"<a href='{urllib.parse.quote(f'/files/{rel}/')}'>run files</a></p>",
        _perf_summary_html(run_dir),
    ]
    if tele.exists():
        records = read_jsonl(tele)
        meta = next((r for r in records if r.get("kind") == "meta"), {})
        footer = next((r for r in records if r.get("kind") == "footer"), {})
        n_spans = sum(1 for r in records if r.get("kind") == "span")
        n_events = sum(1 for r in records if r.get("kind") == "event")
        dropped = int(meta.get("dropped") or footer.get("dropped") or 0)
        if dropped:
            # Truncation is a first-class warning, not a footnote: a
            # truncated span tree must never read as a complete one.
            parts.append(
                f"<p class='warn'>&#9888; telemetry TRUNCATED: {dropped} "
                f"record(s) dropped after the tracer's max_records cap "
                f"— the span tree below is incomplete</p>")
        parts.append(
            f"<h3>span tree</h3><p class='a'>{n_spans} spans, "
            f"{n_events} events; started {html.escape(str(meta.get('wall_start', '?')))}"
            f"</p>")
        parts.append(_span_tree_html(records))
    if metr.exists():
        try:
            metrics = read_metrics(metr)
            parts.append(_kernel_attribution_html(metrics))
            parts.append("<h3>metrics</h3>")
            parts.append(_metrics_table_html(metrics))
        except Exception as e:   # a torn metrics.json must not 500 the page
            parts.append(f"<p class='err'>metrics.json unreadable: "
                         f"{html.escape(str(e))}</p>")
    parts.append("</body></html>")
    return "".join(parts)


# -- live observability plane (ISSUE 8) ------------------------------------

def _metrics_text() -> str:
    """The /metrics payload: the active capture's registry as
    Prometheus text (empty registry outside a run), plus the process
    series — up and the backend supervisor's state (both as a level
    gauge and a labeled info series)."""
    reg = obs.get_metrics()
    snap = reg.snapshot() if getattr(reg, "enabled", False) else {}
    # The supervisor IS the authority on health: drop the capture's
    # pre-registered health.state gauge so the exposition carries
    # exactly one jepsen_tpu_health_state family (a duplicate TYPE line
    # would make the whole scrape invalid).
    snap.pop("health.state", None)
    hs = health.get_supervisor().snapshot()
    level = health.STATE_LEVEL.get(hs["state"], -1)
    extra = [
        "# TYPE jepsen_tpu_up gauge",
        "jepsen_tpu_up 1",
        "# TYPE jepsen_tpu_health_state gauge",
        f"jepsen_tpu_health_state {level}",
        "# TYPE jepsen_tpu_health_info gauge",
        f'jepsen_tpu_health_info{{state='
        f'"{export.sanitize_label_value(hs["state"])}"}} 1',
        "# TYPE jepsen_tpu_run_in_flight gauge",
        f"jepsen_tpu_run_in_flight {int(obs.capture_active())}",
    ]
    return export.render_prometheus(snap, extra_lines=extra)


def _healthz() -> tuple[int, dict]:
    """(status code, body) for /healthz: the supervisor snapshot with
    last-transition provenance. 503 when wedged — a load balancer (or
    the future daemon's failover watcher) can act on the code alone."""
    hs = health.get_supervisor().snapshot()
    body = {"status": hs["state"], **hs,
            "run_in_flight": obs.capture_active(),
            "telemetry_enabled": obs.telemetry_enabled()}
    return (503 if hs["state"] == health.WEDGED else 200), body


_LIVE_PAGE = """<!doctype html><html><head><meta charset='utf-8'>
<title>jepsen-tpu live</title>
<style>body{font-family:sans-serif;margin:2em}
#health{padding:8px;font-weight:bold;display:inline-block}
.healthy{background:#e2f5e5;color:#2a9d43}
.degraded{background:#fff3e0;color:#b05a00}
.wedged{background:#fde3e0;color:#d43a2a}
table{border-collapse:collapse}td,th{padding:2px 10px;
border-bottom:1px solid #eee;text-align:left}
ul.tree,ul.tree ul{list-style:none;border-left:1px solid #ccc;
padding-left:1.2em;margin:2px 0}
.t{color:#2a6db0}.a{color:#888;font-size:90%}.ev{color:#555}
#idle{color:#888}</style></head><body>
<h2>live run <span id='health'>connecting&hellip;</span></h2>
<p><a href='/'>index</a> &middot; <a href='/metrics'>metrics</a>
&middot; <a href='/healthz'>healthz</a></p>
<p id='idle' hidden>no run in flight in the serving process &mdash;
start one with <code>jepsen-tpu test &hellip; --live-port</code></p>
<table id='stats'><tr>
<th>ops ok</th><th>ops/s</th><th>ops fail</th><th>stream overlap</th>
<th>watermark lag</th><th>frontier peak</th><th>serve queue</th>
<th>batch fill</th><th>campaign specs</th><th>falsified</th>
<th>banked</th><th>SLO p99</th>
<th>SLO burn</th></tr><tr>
<td id='ok'>0</td><td id='rate'>&ndash;</td><td id='fail'>0</td>
<td id='overlap'>&ndash;</td><td id='lag'>&ndash;</td>
<td id='frontier'>&ndash;</td><td id='squeue'>&ndash;</td>
<td id='sfill'>&ndash;</td><td id='cspecs'>&ndash;</td>
<td id='cfals'>&ndash;</td><td id='cbank'>&ndash;</td>
<td id='slop99'>&ndash;</td>
<td id='sloburn'>&ndash;</td></tr></table>
<h3>nemesis / events</h3><ul id='events'></ul>
<h3>span tree</h3><ul class='tree' id='spans'></ul>
<script>
const spans = {}, waiting = {}, seenIds = new Set();
let okPrev = null, okPrevT = null;
function el(id){return document.getElementById(id);}
function met(name, m){
  if (name === 'runner.ops_ok'){
    const now = Date.now();
    if (okPrev !== null && now > okPrevT)
      el('rate').textContent = ((m.value - okPrev) * 1000 /
                                (now - okPrevT)).toFixed(1);
    okPrev = m.value; okPrevT = now;
    el('ok').textContent = m.value;
  } else if (name === 'runner.ops_fail') el('fail').textContent = m.value;
  else if (name === 'stream.overlap_ratio' && m.last !== null)
    el('overlap').textContent = (100 * m.last).toFixed(0) + '%';
  else if (name === 'stream.watermark_lag' && m.last !== null)
    el('lag').textContent = m.last;
  else if (name === 'wgl.frontier_peak' && m.max !== null)
    el('frontier').textContent = m.max;
  else if (name === 'serve.queue_depth' && m.last !== null)
    el('squeue').textContent = m.last;
  else if (name === 'serve.batch_fill' && m.last !== null)
    el('sfill').textContent = (100 * m.last).toFixed(0) + '%';
  else if (name === 'campaign.specs')
    el('cspecs').textContent = m.value;
  else if (name === 'campaign.runs_falsified')
    el('cfals').textContent = m.value;
  else if (name === 'campaign.banked')
    el('cbank').textContent = m.value;
  else if (name === 'serve.slo_p99_s' && m.last !== null)
    el('slop99').textContent = (1000 * m.last).toFixed(0) + ' ms';
  else if (name === 'serve.slo_burn_rate' && m.last !== null)
    el('sloburn').textContent = m.last.toFixed(2) + 'x';
  else if (name === 'health.state') setHealth(m.last);
}
function setHealth(v){
  const s = typeof v === 'string' ? v
          : ['healthy', 'degraded', 'wedged'][v] || '?';
  const h = el('health'); h.textContent = s; h.className = s;
}
function addSpan(r){
  const li = document.createElement('li');
  const ms = ((r.t1_ns - r.t0_ns) / 1e6).toFixed(1);
  li.innerHTML = '<b></b> <span class=t>' + ms + ' ms</span>';
  li.querySelector('b').textContent = r.name;
  const ul = document.createElement('ul'); li.appendChild(ul);
  spans[r.id] = ul;
  // Spans stream in COMPLETION order, so children precede their
  // parent: adopt any that already rendered at the root (appendChild
  // moves them), and if our own parent is still open, render at the
  // root now and wait to be adopted ourselves.
  for (const c of waiting[r.id] || []) ul.appendChild(c);
  delete waiting[r.id];
  if (r.parent === null || spans[r.parent]) {
    (spans[r.parent] || el('spans')).appendChild(li);
  } else {
    el('spans').appendChild(li);
    (waiting[r.parent] = waiting[r.parent] || []).push(li);
  }
}
function addRecord(r){
  if (r.id !== undefined){
    if (seenIds.has(r.id)) return;  // init tail / live queue overlap
    seenIds.add(r.id);
  }
  r.kind === 'span' ? addSpan(r) : addEvent(r);
}
function addEvent(r){
  const li = document.createElement('li');
  li.className = 'ev';
  li.textContent = '⚡ ' + r.name + ' ' + JSON.stringify(r.attrs);
  el('events').appendChild(li);
  if (el('events').children.length > 50)
    el('events').removeChild(el('events').firstChild);
}
const es = new EventSource('/live/events');
es.addEventListener('init', e => {
  const d = JSON.parse(e.data);
  setHealth(d.health.state);
  el('idle').hidden = d.run_in_flight;
  for (const [n, m] of Object.entries(d.metrics)) met(n, m);
  for (const r of d.records) addRecord(r);
});
es.addEventListener('span', e => addRecord(JSON.parse(e.data)));
es.addEventListener('event', e => addRecord(JSON.parse(e.data)));
es.addEventListener('metric', e => {
  const d = JSON.parse(e.data); met(d.name, d.metric);
});
</script></body></html>"""


class StoreHandler(SimpleHTTPRequestHandler):
    """/ -> run index; /telemetry/<run> -> span tree + metric table;
    /metrics, /healthz, /live, /live/events -> the serving process's
    live observability plane; /files/... -> static serving rooted at
    the store."""

    def __init__(self, *args, store_root: str = "store", **kw):
        self.store = Store(store_root)
        super().__init__(*args, directory=str(store_root), **kw)

    def _send_html(self, body: str, status: int = 200) -> None:
        self._send_payload(body.encode(), "text/html; charset=utf-8",
                           status)

    def _send_payload(self, payload: bytes, ctype: str,
                      status: int = 200) -> None:
        self.send_response(status)
        self.send_header("Content-Type", ctype)
        self.send_header("Content-Length", str(len(payload)))
        self.end_headers()
        self.wfile.write(payload)

    def _serve_sse(self) -> None:
        """/live/events: subscribe to the obs bus and stream records as
        Server-Sent Events until the client disconnects. Opens with an
        `init` event (current metrics snapshot, health, the tracer's
        buffered records so the span tree starts populated); then spans/
        events arrive in append order and coalesced `metric` records a
        few times per second (the bus's pump). A 1 s heartbeat detects
        dead clients promptly."""
        sub = obs.subscribe()
        try:
            self.send_response(200)
            self.send_header("Content-Type", "text/event-stream")
            self.send_header("Cache-Control", "no-cache")
            self.end_headers()
            reg = obs.get_metrics()
            tracer = obs.get_tracer()
            init = {
                "run_in_flight": obs.capture_active(),
                "health": health.get_supervisor().snapshot(),
                "metrics": reg.snapshot()
                if getattr(reg, "enabled", False) else {},
                # The most recent already-recorded trace tail — enough
                # to seed the page without replaying a whole long run.
                # Through the LOCKED tail() reader: handler threads
                # must not slice the live record list while the run's
                # threads append (jtsan's snapshot-under-lock
                # discipline), and copying the whole buffer per SSE
                # connect was O(max_records) anyway.
                "records": tracer.tail(500) if tracer.enabled else [],
            }
            self.wfile.write(export.sse_message(init, event="init"))
            self.wfile.flush()
            while True:
                rec = sub.get(timeout=1.0)
                if rec is None:
                    self.wfile.write(b": ping\n\n")
                else:
                    self.wfile.write(
                        export.sse_message(rec, event=rec.get("kind")))
                self.wfile.flush()
        except (BrokenPipeError, ConnectionResetError, OSError):
            pass   # client went away — the normal way an SSE stream ends
        finally:
            sub.close()

    def do_GET(self):
        if self.path in ("/", "/index.html"):
            self._send_html(_index_html(self.store))
            return
        if self.path.rstrip("/") == "/metrics":
            self._send_payload(_metrics_text().encode(),
                               export.PROM_CONTENT_TYPE)
            return
        if self.path.rstrip("/") == "/healthz":
            status, body = _healthz()
            self._send_payload(
                (json.dumps(body, indent=2) + "\n").encode(),
                "application/json; charset=utf-8", status)
            return
        if self.path.rstrip("/") == "/live":
            self._send_html(_LIVE_PAGE)
            return
        if self.path.rstrip("/") == "/live/events":
            self._serve_sse()
            return
        if self.path.startswith("/telemetry/"):
            rel = urllib.parse.unquote(
                self.path[len("/telemetry/"):]).strip("/")
            try:
                body = _telemetry_html(self.store, rel)
            except Exception as e:   # never 500 on a torn artifact
                body = (f"<!doctype html><p>telemetry unreadable: "
                        f"{html.escape(str(e))}</p>")
            if body is None:
                self._send_html("<!doctype html><p>no telemetry for "
                                f"{html.escape(rel)}</p>", status=404)
            else:
                self._send_html(body)
            return
        if self.path.startswith("/files/"):
            self.path = self.path[len("/files"):]
        return super().do_GET()

    def log_message(self, fmt, *args):  # quiet
        pass


def make_handler(store_root: str):
    return partial(StoreHandler, store_root=store_root)


def serve(store_root: str = "store", host: str = "127.0.0.1",
          port: int = 8080):
    httpd = ThreadingHTTPServer((host, port), make_handler(store_root))
    print(f"serving {store_root} on http://{host}:{port} "
          f"(/live, /metrics, /healthz)")
    try:
        httpd.serve_forever()
    except KeyboardInterrupt:
        pass
    finally:
        httpd.server_close()
