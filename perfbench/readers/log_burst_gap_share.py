"""100 * (1 - a bare burst / a row's usual gap between two bursts), from the
request log alone, over every request due in the window. A running row gets
its tokens in bursts (one decode dispatch each); the gap between two of them is
that dispatch plus whatever else took the device's turn in between, which in a
mix that prefills is the prefill dispatches (the scheduler runs one program a
step). The bare burst is a low percentile of ALL gaps of all requests (a turn
of the loop in which nothing stood between two bursts); a row's usual gap is
the median over requests of (last chunk - first chunk) / (chunks - 1), the
quantity `tpot_p50_ms` is made of, so a stall that a few requests saw does not
move it. One clock, some thousands of gaps a window: it reads the whole window
where a traced share reads 3 s of it.
params: low_percentile; min_gaps (under it there is nothing to read)."""

import stats as pstats


def read(ctx, params):
    gaps, usual = [], []
    for r in ctx["requests"]:
        times = r.get("chunks") or []
        if not (r["ok"] and r.get("measured")) or len(times) < 3:
            continue
        gaps += [b - a for a, b in zip(times, times[1:])]
        usual.append((times[-1] - times[0]) / (len(times) - 1))
    if len(gaps) < params.get("min_gaps", 100):
        return None
    mid = pstats.percentile(usual, 50)
    if mid <= 0:
        return None
    return 100.0 * (1.0 - pstats.percentile(gaps, params["low_percentile"]) / mid)
