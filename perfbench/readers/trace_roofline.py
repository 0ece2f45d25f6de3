"""The decode attention kernel's share of its roofline, in %: the least time
the chip could take to read the KV bytes that the request log shows were
needed in the traced sub-window (bytes / peak bytes/s, counted by costs.py)
over the device time of the kernel's events there. params: patterns [regex of
operation names].

The work is taken from the request log on the load generator's clock, between
the profiler's start and stop calls: the reads of every output token streamed
there, at its context. The two clocks are not tied, so RATES are compared:
work a second of that interval, against kernel seconds a second of the
device's traced window. The edges smear by one decode burst."""

import costs
import stats as pstats
from readers_common import matching


def work_decode(ctx, lo, hi):
    contexts = []
    for r in ctx["requests"]:
        if not r["ok"]:
            continue
        seen = 0.0
        for t, n in pstats.chunk_tokens(r):
            if lo <= t < hi:
                contexts += [r["prompt_tokens"] + int(seen) + i for i in range(int(round(n)))]
            seen += n
    return costs.decode_attn_bytes(ctx["config"], contexts)


def read(ctx, params):
    tr, sub = ctx.get("trace"), ctx.get("sub")
    if not tr or not sub:
        return None
    hit = matching(tr["ops"], params["patterns"])
    kernel_share = sum(v[1] for v in hit.values()) / tr["devices"] / tr["window_s"]
    lo, hi = sub["start_hi"], sub["stop_lo"]
    if kernel_share <= 0 or hi <= lo:
        return None
    least = work_decode(ctx, lo, hi) / ctx["peaks"]["hbm_bytes_per_s"]
    return 100.0 * (least / (hi - lo)) / kernel_share
