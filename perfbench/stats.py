"""Arithmetic from a request log to end-to-end numbers. No clock, no I/O.

A request record (times in seconds on the load generator's monotonic clock):
  due, sent, first, last, chunks [[t, ...]], ok, prompt_tokens, cached_tokens,
  output_tokens, want_tokens, stream ("open" | "closed"), measured (bool)
A failed request (`ok` false) stays in every denominator and counts as the
worst value: its latency is `worst_ms`.
"""

from __future__ import annotations

import math


def percentile(values, q: float) -> float:
    """Nearest-rank percentile (q in 0..100) of a non-empty list."""
    s = sorted(values)
    rank = max(1, math.ceil(q / 100.0 * len(s)))
    return s[rank - 1]


def ttft_ms(req: dict, worst_ms: float) -> float:
    """First streamed token minus the time the request was DUE."""
    if not req["ok"] or req.get("first") is None:
        return worst_ms
    return (req["first"] - req["due"]) * 1000.0


def tpot_ms(req: dict, worst_ms: float):
    """(last token - first token) / (output tokens - 1); None for a request
    of one token, which has no gap to measure."""
    if not req["ok"]:
        return worst_ms
    n = req.get("output_tokens") or 0
    if n < 2:
        return None
    return (req["last"] - req["first"]) * 1000.0 / (n - 1)


SERIES = {"ttft_ms": ttft_ms, "tpot_ms": tpot_ms}


def chunk_tokens(req: dict) -> list[tuple[float, float]]:
    """(arrival time, tokens) of each streamed chunk. The stream does not say
    how many tokens a chunk carries: the first carries one (the prefill's
    sample) and the rest are shared evenly among the later chunks."""
    times = req.get("chunks") or []
    n = req.get("output_tokens") or 0
    if not times or n < 1:
        return []
    if len(times) == 1:
        return [(times[0], float(n))]
    each = (n - 1) / (len(times) - 1)
    return [(times[0], 1.0)] + [(t, each) for t in times[1:]]


def end_to_end(spec: dict, requests, window: tuple[float, float], worst_ms: float):
    """One end-to-end metric from its data file's `stat`.
    Returns (value or None, sample count)."""
    stat = spec["stat"]
    measured = [r for r in requests if r["measured"]]
    if stat["kind"] == "percentile":
        vals = [SERIES[stat["of"]](r, worst_ms) for r in measured]
        vals = [v for v in vals if v is not None]
        if len(vals) < max(1, stat.get("min_samples", 1)):
            return None, len(vals)
        return percentile(vals, stat["q"]), len(vals)
    raise ValueError(f"unknown end-to-end stat {stat['kind']!r}")
