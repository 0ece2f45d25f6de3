"""Median over requests of one span's SELF time: its duration minus its
children's, children found across the router's and the engine's exports by
trace id (the arithmetic of scripts/trace_report.py, copied: the yardstick
lives with the benchmark). params: span (name of the span whose self time is
wanted), source ("router" | "engine")."""

import statistics


def spans_of(export):
    items = export.get("traces", export) if isinstance(export, dict) else export
    out = []
    for item in items:
        out.extend(item["spans"] if isinstance(item, dict) and "spans" in item else [item])
    return out


def read(ctx, params):
    if not ctx.get("spans"):
        return None
    every = [s for ex in ctx["spans"].values() for s in spans_of(ex)]
    kids: dict = {}
    for s in every:
        if s.get("parent_id"):
            kids.setdefault((s["trace_id"], s["parent_id"]), []).append(s)
    own = []
    for s in spans_of(ctx["spans"][params["source"]]):
        if s["name"] != params["span"]:
            continue
        children = kids.get((s["trace_id"], s["span_id"]), [])
        own.append(max(0.0, s.get("duration_ms", 0.0)
                       - sum(k.get("duration_ms", 0.0) for k in children)))
    return statistics.median(own) if own else None
