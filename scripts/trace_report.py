"""Per-phase latency report from a /v1/traces JSON export.

Reads one or more trace exports (the payload of ``GET /v1/traces`` on the
router or engine — or both, merged: the two halves of a routed request share
one trace id) and renders a self-time attribution table: for every span name,
how much wall time the stack spent IN that phase, excluding time attributed to
its child spans. Self-times of a well-formed trace sum to the root span's
duration, so gaps (network hops, scheduling turnaround) surface as parent
self-time instead of silently vanishing — exactly the property the old
two-pass engine-direct benchmark contrast lacked.

Usage:
    curl -s $ROUTER/v1/traces > r.json
    curl -s $ENGINE/v1/traces > e.json
    python scripts/trace_report.py r.json e.json

Cross-link mode (the "why was this request slow" one-liner): given a trace
id and a flight-recorder export (``GET /v1/debug/flightrecorder`` on the
engine, or an anomaly dump file), render the trace's spans interleaved
chronologically with the engine events from the matching window — the
scheduler dispatches, KV evictions/restores, sheds, and compiles that
surrounded the request:

    curl -s $ENGINE/v1/debug/flightrecorder > fr.json
    python scripts/trace_report.py r.json e.json \
        --flightrecorder fr.json --trace-id <32-hex id>
"""

from __future__ import annotations

import argparse
import json
from typing import Iterable, Optional


def _spans_of(export) -> list[dict]:
    """Accept a /v1/traces export, a {"traces": [...]} dict, a list of trace
    groups, or a bare span list."""
    if isinstance(export, dict):
        export = export.get("traces", [])
    spans: list[dict] = []
    for item in export:
        if isinstance(item, dict) and "spans" in item:
            spans.extend(item["spans"])
        elif isinstance(item, dict):
            spans.append(item)
    return spans


def merge_exports(*exports) -> dict[str, list[dict]]:
    """Merge exports (possibly from different processes) into
    {trace_id: [span, ...]}, deduped by span id."""
    by_trace: dict[str, dict[str, dict]] = {}
    for ex in exports:
        for s in _spans_of(ex):
            by_trace.setdefault(s["trace_id"], {})[s["span_id"]] = s
    return {t: list(ss.values()) for t, ss in by_trace.items()}


def _percentile(vals: list[float], q: float) -> float:
    if not vals:
        return 0.0
    s = sorted(vals)
    return s[min(len(s) - 1, int(len(s) * q))]


def trace_breakdown(spans: list[dict]) -> Optional[dict]:
    """One trace's attribution: root duration, per-name self time, and the
    share of the root covered by leaf phases."""
    if not spans:
        return None
    by_id = {s["span_id"]: s for s in spans}
    children: dict[str, list[dict]] = {}
    roots = []
    for s in spans:
        parent = s.get("parent_id")
        if parent and parent in by_id:
            children.setdefault(parent, []).append(s)
        else:
            roots.append(s)
    root = max(roots, key=lambda s: s.get("duration_ms", 0.0))
    # Restrict accounting to the chosen root's subtree: a partial trace
    # (span ring wrapped mid-trace, or router/engine export windows
    # misaligned across pods) can carry orphan chains whose parents were
    # lost; counting those would push shares and leaf coverage past 100%
    # and silently corrupt the table.
    subtree: list[dict] = []
    stack = [root]
    while stack:
        s = stack.pop()
        subtree.append(s)
        stack.extend(children.get(s["span_id"], []))
    self_ms: dict[str, float] = {}
    leaf_ms = 0.0
    for s in subtree:
        kids = children.get(s["span_id"], [])
        own = max(
            0.0,
            s.get("duration_ms", 0.0) - sum(k.get("duration_ms", 0.0) for k in kids),
        )
        self_ms[s["name"]] = self_ms.get(s["name"], 0.0) + own
        if not kids:
            leaf_ms += s.get("duration_ms", 0.0)
    e2e = root.get("duration_ms", 0.0)
    return {
        "trace_id": root["trace_id"],
        "root": root["name"],
        "e2e_ms": e2e,
        "self_ms": self_ms,
        "leaf_coverage": (leaf_ms / e2e) if e2e > 0 else 0.0,
    }


def phase_table(merged: dict[str, list[dict]]) -> dict:
    """Aggregate attribution across traces.

    Returns {"phases": {name: {count, p50_self_ms, p99_self_ms, total_ms,
    share}}, "traces": N, "e2e_p50_ms": ..., "leaf_coverage_p50": ...} where
    ``share`` is the phase's fraction of total root wall time."""
    per_name: dict[str, list[float]] = {}
    e2es: list[float] = []
    coverages: list[float] = []
    for spans in merged.values():
        b = trace_breakdown(spans)
        if b is None or b["e2e_ms"] <= 0:
            continue
        e2es.append(b["e2e_ms"])
        coverages.append(b["leaf_coverage"])
        for name, ms in b["self_ms"].items():
            per_name.setdefault(name, []).append(ms)
    total_e2e = sum(e2es)
    phases = {}
    for name, vals in sorted(
        per_name.items(), key=lambda kv: -sum(kv[1])
    ):
        total = sum(vals)
        phases[name] = {
            "count": len(vals),
            "p50_self_ms": round(_percentile(vals, 0.5), 2),
            "p99_self_ms": round(_percentile(vals, 0.99), 2),
            "total_ms": round(total, 2),
            "share": round(total / total_e2e, 4) if total_e2e else 0.0,
        }
    return {
        "phases": phases,
        "traces": len(e2es),
        "e2e_p50_ms": round(_percentile(e2es, 0.5), 2),
        "leaf_coverage_p50": round(_percentile(coverages, 0.5), 4),
    }


def render_table(table: dict) -> str:
    lines = [
        f"traces: {table['traces']}   e2e p50: {table['e2e_p50_ms']} ms   "
        f"leaf-phase coverage p50: {table['leaf_coverage_p50']:.1%}",
        f"{'phase':<28} {'count':>6} {'p50 self ms':>12} "
        f"{'p99 self ms':>12} {'share':>7}",
    ]
    for name, row in table["phases"].items():
        lines.append(
            f"{name:<28} {row['count']:>6} {row['p50_self_ms']:>12.2f} "
            f"{row['p99_self_ms']:>12.2f} {row['share']:>6.1%}"
        )
    return "\n".join(lines)


# -- cross-link mode (trace spans x flight-recorder events) -------------------


def _recorder_events(export) -> list[dict]:
    """Accept a /v1/debug/flightrecorder export, an anomaly dump, or a bare
    event list."""
    if isinstance(export, dict):
        export = export.get("events", [])
    return [e for e in export if isinstance(e, dict) and "kind" in e]


def _event_line(ev: dict) -> str:
    d = ev.get("data") or {}
    kind = ev["kind"]
    if kind == "sched":
        gate = d.get("gate") or {}
        detail = (
            f"{d.get('batch_kind')} rows={d.get('rows')} "
            f"bursts={d.get('bursts')} chunk_tokens={d.get('chunk_tokens')} "
            f"waiting={d.get('waiting')} alternate={gate.get('alternate')}"
        )
    elif kind == "step":
        detail = (
            f"{d.get('batch_kind')} wall={d.get('wall_ms')}ms "
            f"fetched={d.get('fetched')}"
        )
    elif kind == "kv":
        detail = " ".join(
            f"{k}={v}" for k, v in d.items() if k != "victim_scores"
        )
    else:
        detail = " ".join(f"{k}={v}" for k, v in sorted(d.items()))
    return f"event  {kind:<8} step={ev.get('step', -1):<6} {detail}"


def crosslink_report(
    merged: dict[str, list[dict]],
    recorder_export,
    trace_id: str,
    window_slack_s: float = 1.0,
) -> str:
    """Render one trace's spans interleaved (chronologically, by wall-clock
    start) with the flight-recorder events of the matching window: events
    stamped with the trace id itself, plus every event inside the trace's
    [start - slack, end + slack] wall window — the dispatches that served
    OTHER requests in between are exactly what explains a queue-shaped gap."""
    spans = merged.get(trace_id)
    if not spans:
        return f"trace {trace_id} not found in the supplied exports"
    events = _recorder_events(recorder_export)
    t0 = min(s["start"] for s in spans)
    t1 = max(s["start"] + s.get("duration_ms", 0.0) / 1000 for s in spans)
    window = [
        e for e in events
        if e.get("trace_id") == trace_id
        or (t0 - window_slack_s) <= e.get("t", 0.0) <= (t1 + window_slack_s)
    ]
    rows: list[tuple[float, str]] = []
    for s in sorted(spans, key=lambda s: s["start"]):
        rows.append((
            s["start"],
            f" span  {s['name']:<26} +{(s['start'] - t0) * 1000:8.1f}ms "
            f"dur={s.get('duration_ms', 0.0):.1f}ms",
        ))
    for e in window:
        linked = "*" if e.get("trace_id") == trace_id else " "
        rows.append((
            e.get("t", t0),
            f"{linked}{_event_line(e)}  +{(e.get('t', t0) - t0) * 1000:.1f}ms",
        ))
    rows.sort(key=lambda r: r[0])
    linked_n = sum(1 for e in window if e.get("trace_id") == trace_id)
    head = (
        f"trace {trace_id}: {len(spans)} spans over "
        f"{(t1 - t0) * 1000:.1f} ms; {len(window)} engine events in window "
        f"({linked_n} cross-linked by trace id; * marks them)"
    )
    return "\n".join([head] + [r[1] for r in rows])


def report(paths: Iterable[str]) -> str:
    exports = []
    for p in paths:
        with open(p) as f:
            exports.append(json.load(f))
    return render_table(phase_table(merge_exports(*exports)))


def main() -> None:
    ap = argparse.ArgumentParser(
        description="Render a per-phase latency table from /v1/traces exports"
    )
    ap.add_argument("paths", nargs="+", help="JSON export file(s); exports "
                    "from router and engine merge by trace id")
    ap.add_argument("--flightrecorder", default=None,
                    help="flight-recorder export or anomaly dump (JSON); "
                         "with --trace-id, renders the trace's spans "
                         "interleaved with the matching engine-event window")
    ap.add_argument("--trace-id", default=None,
                    help="32-hex trace id for cross-link mode")
    args = ap.parse_args()
    if args.flightrecorder or args.trace_id:
        if not (args.flightrecorder and args.trace_id):
            ap.error("cross-link mode needs BOTH --flightrecorder and --trace-id")
        exports = []
        for p in args.paths:
            with open(p) as f:
                exports.append(json.load(f))
        with open(args.flightrecorder) as f:
            recorder = json.load(f)
        print(crosslink_report(merge_exports(*exports), recorder, args.trace_id))
        return
    print(report(args.paths))


if __name__ == "__main__":
    main()
