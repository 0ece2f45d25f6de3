"""The grouped expert product's share of its roofline, in %, with the work
COUNTED BY THE DEVICE: the change over the window of the engine's counters of
experts read (experts with at least one row, over expert layers and steps) and
rows routed (`/stats`, from a small output of the step programs), priced by
costs_moe.py: the least time the chip could take is the larger of the weight
reads over the HBM peak and the products over the bf16 peak; over the device
time of the kernel's events.

The counters are read at the window's edges and the trace covers a few seconds
inside it, so RATES are compared, each per second in which the engine ran (the
form of scripts/perfbench_proposed/readers/trace_roofline_counted.py): the
least time over the seconds the engine's loop spent outside its `wait` section
between the two snapshots (`ran`: the loop's other sections; a traced run's
second snapshot comes up to 90 s after the window's end, when the profiler has
stopped, and the engine idles meanwhile) less the seconds it stood at first
dispatches there, against the kernel's seconds over the traced extent less its
gaps of `stall_gap_s` or more. Decode bursts are bound by the reads and prefill
chunks by the products; the maximum of the window's sums is under the sum of
each dispatch's maximum, so over ONE interval the share could not pass 100%.
Here the two rates come from two intervals and two clocks (counters and loop
sections between the snapshots, the kernel's seconds in the few traced
seconds): the share holds as far as the traced seconds resemble the window,
and a change to the host's turn (what the loop's sections book) moves it with
no change to the kernel. Pricing the traced seconds themselves needs the
counters read at the trace's edges (PERF.md section 7). None where the trace holds no such operation or the program does
not count. params: patterns [regex of operation names], reads, rows (counter
names), ran [the loop's section counters], stalled (the counter of seconds
stood), stall_gap_s."""

import costs_moe
from readers_common import matching


def read(ctx, params):
    tr, s0, s1 = ctx.get("trace"), ctx["snap0"], ctx["snap1"]
    reads, rows, stalled = params["reads"], params["rows"], params["stalled"]
    if not tr or reads not in s1["stats"] or rows not in s1["stats"]:
        return None
    traced_s = tr["window_s"] - sum(s for _, s in tr["top_gaps"] if s >= params["stall_gap_s"])
    delta = lambda name: s1["stats"][name] - s0["stats"].get(name, 0)  # noqa: E731
    ran_s = sum(delta(n) for n in params["ran"] if n in s1["stats"]) - (
        delta(stalled) if stalled in s1["stats"] else 0.0)
    kernel_s = sum(v[1] for v in matching(tr["ops"], params["patterns"]).values()) / tr["devices"]
    if traced_s <= 0 or ran_s <= 0 or kernel_s <= 0:
        return None
    least = costs_moe.least_seconds(ctx["config"], delta(reads), delta(rows), ctx["peaks"])
    return 100.0 * (least / ran_s) / (kernel_s / traced_s)
