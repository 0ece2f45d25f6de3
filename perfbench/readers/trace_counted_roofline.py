"""A kernel's share of its roofline, in %, with the work COUNTED BY THE DEVICE
and priced by a costs module named in the metric's file: the change over the
window of the engine's counters (`/stats`, from a small output of the step
programs) -> the least time the chip could take (`<costs>.<least>(config,
{counter: change}, peaks)`), over the device time of the kernel's events.

The form of trace_moe_roofline.py, for any kernel whose work the step programs
count: the counters are read at the window's edges and the trace covers a few
seconds inside it, so RATES are compared, each per second in which the engine
ran: the least time over the seconds the engine's loop spent outside its
`wait` section between the two snapshots, less the seconds it stood at first
dispatches there, against the kernel's seconds over the traced extent less its
gaps of `stall_gap_s` or more. Two intervals and two clocks: the share holds as
far as the traced seconds resemble the window (PERF.md section 7). None where
the trace holds no such operation or the program does not count (the parent
commit). params: patterns [regex of operation names], costs (module), least
(function), counters [names], ran [the loop's section counters], stalled,
stall_gap_s."""

import importlib

from readers_common import matching


def read(ctx, params):
    tr, s0, s1 = ctx.get("trace"), ctx["snap0"], ctx["snap1"]
    stalled = params["stalled"]
    if not tr or any(n not in s1["stats"] for n in params["counters"]):
        return None
    traced_s = tr["window_s"] - sum(s for _, s in tr["top_gaps"] if s >= params["stall_gap_s"])
    delta = lambda name: s1["stats"][name] - s0["stats"].get(name, 0)  # noqa: E731
    ran_s = sum(delta(n) for n in params["ran"] if n in s1["stats"]) - (
        delta(stalled) if stalled in s1["stats"] else 0.0)
    kernel_s = sum(v[1] for v in matching(tr["ops"], params["patterns"]).values()) / tr["devices"]
    if traced_s <= 0 or ran_s <= 0 or kernel_s <= 0:
        return None
    least = getattr(importlib.import_module(params["costs"]), params["least"])(
        ctx["config"], {n: delta(n) for n in params["counters"]}, ctx["peaks"])
    return 100.0 * (least / ran_s) / (kernel_s / traced_s)
