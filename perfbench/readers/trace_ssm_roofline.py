"""A selective-scan kernel's share of its HBM roofline, in %: the least time
the chip could take to move the bytes the scan needed for the work the request
log shows in the traced sub-window (costs_ssm.py / peak bytes/s), over the
device time of the kernel's events there. params: phase ("decode" |
"prefill"), patterns [regex of operation names].

decode: every output token streamed in the sub-window but each request's
first, which its prefill produced (one state in and out a layer + the token's
rows). prefill: the prompt tokens of the requests whose
FIRST token fell in the sub-window (their prefill ended there; chunk = the
engine's default 512). As in trace_roofline.py the two clocks are not tied, so
RATES are compared: work a second of the load generator's interval against
kernel seconds a second of the device's traced window. Returns None where the
trace holds no such kernel (a program without one)."""

import costs_ssm
import stats as pstats
from readers_common import matching


def work(ctx, phase, lo, hi) -> float:
    ok = [r for r in ctx["requests"] if r["ok"]]
    if phase == "decode":
        # a request's first chunk carries the one token its prefill sampled
        tokens = sum(int(round(n)) for r in ok for t, n in pstats.chunk_tokens(r)[1:]
                     if lo <= t < hi)
        return costs_ssm.decode_bytes(ctx["config"], tokens)
    return costs_ssm.prefill_bytes(
        ctx["config"], [r["prompt_tokens"] for r in ok if lo <= r["first"] < hi])


def read(ctx, params):
    tr, sub = ctx.get("trace"), ctx.get("sub")
    if not tr or not sub or tr["window_s"] <= 0:
        return None
    hit = matching(tr["ops"], params["patterns"])
    kernel_share = sum(v[1] for v in hit.values()) / tr["devices"] / tr["window_s"]
    lo, hi = sub["start_hi"], sub["stop_lo"]
    if kernel_share <= 0 or hi <= lo:
        return None
    least = work(ctx, params["phase"], lo, hi) / ctx["peaks"]["hbm_bytes_per_s"]
    return 100.0 * (least / (hi - lo)) / kernel_share
