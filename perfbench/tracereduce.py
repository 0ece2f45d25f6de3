#!/usr/bin/env python3
"""From a profiler trace (.xplane.pb) to the few numbers the readers use.

Run as a process of its own (`python3 perfbench/tracereduce.py <file> --out
<json>`), with JAX held to the CPU: the file is read with JAX's own reader
(`jax.profiler.ProfileData`), and the benchmark's parent stays off JAX.

Output, seconds throughout, averaged over the device planes:
  window_s   the traced window: first event start to last event end on the
             DEVICE plane. (The host's python line starts seconds earlier, while
             the profiler itself starts up and the device is not yet traced.)
  busy_s     union of the intervals in which an operation ran on the device
  ops        {name: [count, total_s, median_s]} of the device's operation line,
             leaf operations only: a `while` that holds other operations is
             left out, or its body would count twice
  modules    the same for its program (XLA module) line
  programs_with  {kernel: [count, total_s, median_s]} the program runs that hold
             a custom call of that name (`.N` suffix cut): the step programs
             carry no stable names yet, their kernels do
  top_ops    [[name, total_s], ...] the leaf operations that took most time
  top_gaps   [[name of the operation before the gap, seconds], ...] longest idle gaps
  planes     what was found, for a reader of the file: names, lines, counts
"""

from __future__ import annotations

import argparse
import json
import statistics
import sys

OP_LINES = ("XLA Ops",)
MODULE_LINES = ("XLA Modules",)


def is_device(plane_name: str) -> bool:
    return plane_name.startswith("/device:") and "CPU" not in plane_name.upper().split(":")[1]


def union_and_gaps(events):
    """events: sorted [(start, end, name)]. Returns (busy, gaps [(seconds, name before)])."""
    busy, gaps, cur_s, cur_e, cur_name = 0, [], None, None, None
    for s, e, name in events:
        if cur_e is None:
            cur_s, cur_e, cur_name = s, e, name
        elif s <= cur_e:
            if e > cur_e:
                cur_e, cur_name = e, name
        else:
            busy += cur_e - cur_s
            gaps.append((s - cur_e, cur_name))
            cur_s, cur_e, cur_name = s, e, name
    if cur_e is not None:
        busy += cur_e - cur_s
    return busy, gaps


def leaves(events):
    """events sorted by start: drop every event that holds a later one."""
    out, stack = [], []  # stack of [event, has_child]
    for ev in events:
        while stack and stack[-1][0][1] <= ev[0]:
            top, parent = stack.pop()
            if not parent:
                out.append(top)
        if stack:
            stack[-1][1] = True
        stack.append([ev, False])
    out += [top for top, parent in stack if not parent]
    return out


def kernel_name(op_name: str):
    """`%ragged_paged_attention_decode.10 = ... custom-call(...)` -> the kernel's name."""
    head, _, rest = op_name.partition(" = ")
    if "custom-call(" not in rest:
        return None
    return head.lstrip("%").rsplit(".", 1)[0] if head.rsplit(".", 1)[-1].isdigit() else head.lstrip("%")


def programs_with(modules, ops) -> dict:
    """The module events that hold each kernel, by time containment."""
    import bisect

    modules = sorted(modules)
    starts = [m[0] for m in modules]
    hit: dict[str, set] = {}
    for s, e, name in ops:
        k = kernel_name(name)
        if k is None:
            continue
        i = bisect.bisect_right(starts, s) - 1
        if i >= 0 and modules[i][1] >= e:
            hit.setdefault(k, set()).add(i)
    return {k: table([modules[i] for i in idx]) for k, idx in hit.items()}


def table(events) -> dict:
    by: dict[str, list] = {}
    for s, e, name in events:
        by.setdefault(name, []).append(e - s)
    return {n: [len(d), sum(d) / 1e9, statistics.median(d) / 1e9] for n, d in by.items()}


def merge(rows: dict) -> list:
    """One [count, total_s, median_s] row from a table's rows (the median of
    the medians, weighted by count)."""
    pairs = sorted((v[2], v[0]) for v in rows.values())
    half, seen, med = sum(c for _, c in pairs) / 2.0, 0, pairs[-1][0]
    for m, c in pairs:
        seen += c
        if seen >= half:
            med = m
            break
    return [sum(v[0] for v in rows.values()), sum(v[1] for v in rows.values()), med]


def reduce(path: str) -> dict:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    lo, hi, found, devices = None, None, [], []
    for plane in data.planes:
        lines = []
        for line in plane.lines:
            evs = [(ev.start_ns, ev.start_ns + ev.duration_ns, ev.name) for ev in line.events]
            lines.append((line.name, evs))
            if evs and is_device(plane.name):
                a, b = min(e[0] for e in evs), max(e[1] for e in evs)
                lo, hi = (a if lo is None else min(lo, a)), (b if hi is None else max(hi, b))
        found.append({"plane": plane.name, "lines": {n: len(e) for n, e in lines}})
        if is_device(plane.name) and any(e for _, e in lines):
            devices.append((plane.name, dict(lines)))
    if not devices or lo is None:
        raise SystemExit(f"tracereduce: no device plane with events in {path}: {found}")
    window = (hi - lo) / 1e9
    per_dev, ops_all, mods_all, gaps_all, with_kernel = [], [], [], [], {}
    for name, lines in devices:
        ops = next((lines[n] for n in OP_LINES if lines.get(n)), None)
        if ops is None:  # an unknown layout: every line but the program and step lines
            ops = [e for n, evs in lines.items()
                   if n not in MODULE_LINES and n != "Steps" for e in evs]
        ops.sort()
        busy, gaps = union_and_gaps(ops)
        per_dev.append(busy / 1e9)
        ops_all += leaves(ops)
        gaps_all += gaps
        mods = next((lines[n] for n in MODULE_LINES if lines.get(n)), [])
        mods_all += mods
        for k, v in programs_with(mods, ops).items():
            old = with_kernel.get(k)
            with_kernel[k] = v if old is None else {**old, **v}
    ops_t = table(ops_all)
    n_dev = len(devices)
    top = sorted(([n, v[1] / n_dev] for n, v in ops_t.items()), key=lambda x: -x[1])
    gaps_all.sort(key=lambda g: -g[0])
    return {
        "window_s": window, "busy_s": sum(per_dev) / n_dev, "devices": n_dev,
        "ops": ops_t, "modules": table(mods_all), "top_ops": top[:25],
        "programs_with": {k: merge(v) for k, v in with_kernel.items()},
        "top_gaps": [[n, g / 1e9] for g, n in gaps_all[:25]],
        "idle_s": window - sum(per_dev) / n_dev, "planes": found,
    }


def main(argv) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("trace")
    p.add_argument("--out", required=True)
    args = p.parse_args(argv)
    out = reduce(args.trace)
    with open(args.out, "w") as f:
        json.dump(out, f)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
