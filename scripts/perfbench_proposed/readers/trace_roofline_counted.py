"""The decode attention kernel's share of its roofline, in %, with the work
COUNTED BY THE ENGINE: the change of its counter of KV tokens attended
(`decode_kv_tokens_read_total`: min(context, window) for every decoded token,
counted from the batches it dispatched) over the window, times the KV bytes a
token holds (costs.py), over the peak bytes/s: the least time the chip could
take; over the device time of the kernel's events. `kernel.decode_attn_roofline`
infers the same work from the client's chunk times instead.

The counter is read at the window's edges and the trace covers a few seconds
inside it, so RATES are compared, each per second in which the engine ran: the
work over the window less the seconds the engine stood at first dispatches
there (`first_dispatch_seconds_total`), against the kernel's seconds over the
traced extent less its gaps of `stall_gap_s` or more (a first dispatch idles
the device for seconds; the host's turn between two bursts for milliseconds).
It holds as far as the traced seconds are like the window's. Nothing to read
from a program that does not count. params: patterns [regex of operation
names], counter, stalled (the counter of seconds stood), stall_gap_s."""

import costs
from readers_common import matching


def read(ctx, params):
    tr, s0, s1 = ctx.get("trace"), ctx["snap0"], ctx["snap1"]
    name, stalled = params["counter"], params["stalled"]
    if not tr or name not in s1["stats"]:
        return None
    traced_s = tr["window_s"] - sum(s for _, s in tr["top_gaps"] if s >= params["stall_gap_s"])
    ran_s = (s1["t"] - s0["t"]) - (s1["stats"].get(stalled, 0.0) - s0["stats"].get(stalled, 0.0))
    kernel_s = sum(v[1] for v in matching(tr["ops"], params["patterns"]).values()) / tr["devices"]
    if traced_s <= 0 or ran_s <= 0 or kernel_s <= 0:
        return None
    tokens = s1["stats"][name] - s0["stats"].get(name, 0)
    least = tokens * costs.kv_bytes_per_token(ctx["config"]) / ctx["peaks"]["hbm_bytes_per_s"]
    return 100.0 * (least / ran_s) / (kernel_s / traced_s)
