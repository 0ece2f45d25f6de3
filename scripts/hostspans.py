#!/usr/bin/env python3
"""From a profiler trace (.xplane.pb) to names for the device's idle time and
for its operations.

Run like `perfbench/tracereduce.py` (whose plane and line names and leaf
walk it shares), as a process of its own with JAX held to the CPU:
`JAX_PLATFORMS=cpu python3 scripts/hostspans.py <file> --out <json>`.

The program writes host spans into the profiler's own trace while a profile
runs (`production_stack_tpu/tracing/profiler.py`): `pstpu.loop.<section>` for
each section of the engine loop and `pstpu.first_dispatch` around the first
call of a step-program shape. They lie on the host plane, on the thread that
drives the device. Spans nest (a dispatch holds its staging); at any instant
the INNERMOST open span is what the host was doing. Since PR 47 the host is
one dispatch AHEAD of the device, so what it did during a gap need not be why
the device waited: the turn that enqueued the dispatch AFTER the gap says why
nothing was queued behind the one before (`pstpu.loop.step`'s `drain`).

A gap is named, in this order (`name_gap`):
  `(under clock skew)`  it is shorter than twice the estimated skew between
                        the two planes' clocks: not guessed
  `drain:<reason>`      the `pstpu.loop.step` span open when the next device
                        program started carries a `drain` (`late`,
                        `first_dispatch`, `no_pages`, `idle`, ...: the words of
                        `/stats` `queue_ahead_drains_total`)
  `pstpu.<span>`        the innermost span covering most of it, `(no span)`
                        where none does (the turn was opened before the
                        profile started, or the profile was started around
                        the program's control: its spans are the no-op)

Output, seconds throughout:
  spans         {name: [count, total_s]} of the `pstpu.*` spans found
  clock_skew_s  what was added to the device plane's times: the device
                plane's clock runs ahead of the host plane's (by 1.0-1.7 ms in
                the probe of PR 25, 0.29-0.37 ms in the engine's traces of PR
                48); the least "host saw the program complete"
                minus "program ended on the device" over the traced programs
                bounds it from above and is taken as the estimate. 0.0 where
                the host plane shows no completions
  gaps          [[the gap's name by the rule above, gap seconds, {name:
                seconds} of the innermost spans that cover it, "(no span)" for
                the rest], ...] the longest device idle gaps first
  idle_by_span  {name: idle seconds} over ALL gaps, "(no span)" for the rest
  idle_s        the sum
  scopes        {scope: device seconds} of the leaf operations by the first
                `jax.named_scope` of their `tf_op` path (the operation's
                metadata: `jit(pstpu_step)/attention/dot_general` -> attention),
                "(unscoped)" where there is none
  op_scopes     {operation name: scope} for the operations seen
"""

from __future__ import annotations

import argparse
import bisect
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "perfbench"))

import tracereduce  # noqa: E402

SPAN_PREFIX = "pstpu."
STEP_SPAN = "pstpu.loop.step"
UNDER_SKEW = "(under clock skew)"
NO_SPAN = "(no span)"
HOST_PLANE = "/host:CPU"
COMPLETE = "CompleteCallbacks"  # libtpu's host event when a program's run ends
STRUCTURAL = ("while", "body", "cond", "closed_call", "checkpoint", "pjit")


def innermost(spans):
    """spans: [(start, end, name)] of ONE thread (they nest). Returns
    disjoint [(start, end, name)], sorted: the innermost open span at each
    instant."""
    out, stack, cur = [], [], 0
    for s, e, name in sorted(spans, key=lambda x: (x[0], -x[1])):
        while stack and stack[-1][0] <= s:
            end, top = stack.pop()
            if end > cur:
                out.append((cur, end, top))
                cur = end
        if stack and s > cur:
            out.append((cur, s, stack[-1][1]))
        cur = max(cur, s) if stack else s
        stack.append((e, name))
    while stack:
        end, top = stack.pop()
        if end > cur:
            out.append((cur, end, top))
            cur = end
    return out


def gap_intervals(events):
    """events: sorted [(start, end, name)]. The intervals in which none ran."""
    gaps, hi = [], None
    for s, e, _ in events:
        if hi is not None and s > hi:
            gaps.append((hi, s))
        hi = e if hi is None else max(hi, e)
    return gaps


def name_gap(a, b, cover, drains, skew) -> str:
    """The name of the device's idle gap [a, b) (host clock, ns); the rule is
    in the module's docstring. `cover` {innermost span: ns} as `attribute`
    gives it with NO_SPAN for the rest, `drains` sorted [(start, end, drain)]
    of the step spans that carry one, `skew` the estimated clock skew in ns."""
    if b - a < 2 * skew:
        return UNDER_SKEW
    i = bisect.bisect_right(drains, (b, float("inf"), "")) - 1
    if i >= 0 and drains[i][1] >= b:  # the turn open when the next program started
        return "drain:" + drains[i][2]
    return max(cover, key=cover.get)


def attribute(gaps, segments):
    """For each gap, {name: ns} of the segments that cover it."""
    starts = [seg[0] for seg in segments]
    out = []
    for a, b in gaps:
        cover = {}
        i = max(0, bisect.bisect_right(starts, a) - 1)
        while i < len(segments) and segments[i][0] < b:
            s, e, name = segments[i]
            ov = min(b, e) - max(a, s)
            if ov > 0:
                cover[name] = cover.get(name, 0) + ov
            i += 1
        out.append(cover)
    return out


def scope_of(tf_op: str) -> str:
    """`jit(pstpu_multi_step_k8)/while/body/attention/dot_general` -> attention."""
    parts = tf_op.split("/")
    for p in parts[1:-1]:
        if p not in STRUCTURAL and not p.startswith("jit("):
            return p
    return "(unscoped)"


# -- the operations' metadata, which JAX's reader does not show ------------------
# `ProfileData` gives an event's own stats, not those of its metadata, where
# `tf_op` lies. A minimal protobuf reader for that alone: XSpace.planes(1) ->
# XPlane.event_metadata(4) / stat_metadata(5) -> XEventMetadata{name(2),
# stats(5)} -> XStat{metadata_id(1), str_value(5), ref_value(7)}.

def _varint(buf: bytes, i: int) -> tuple:
    val = shift = 0
    while True:
        b = buf[i]
        i += 1
        val |= (b & 0x7F) << shift
        shift += 7
        if b < 0x80:
            return val, i


def _fields(buf: bytes):
    """(field number, value) of a message: an int for a varint, bytes for a
    length-delimited field; fixed-width fields are skipped."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        field, wire = key >> 3, key & 7
        if wire == 0:
            val, i = _varint(buf, i)
            yield field, val
        elif wire == 2:
            size, i = _varint(buf, i)
            yield field, buf[i:i + size]
            i += size
        elif wire == 1:
            i += 8
        elif wire == 5:
            i += 4
        else:
            raise ValueError(f"wire type {wire} in an xplane file")


def op_tf_ops(path: str) -> dict:
    """{operation name: tf_op} from the device planes' event metadata."""
    with open(path, "rb") as f:
        space = f.read()
    out = {}
    for field, plane in _fields(space):
        if field != 1:
            continue
        name, events, stat_names = "", [], {}
        for f2, v in _fields(plane):
            if f2 == 2:
                name = v.decode(errors="replace")
            elif f2 == 4:
                events += [m for k, m in _fields(v) if k == 2]
            elif f2 == 5:
                meta = dict(_fields(next(m for k, m in _fields(v) if k == 2)))
                stat_names[meta.get(1, 0)] = meta.get(2, b"").decode(errors="replace")
        if not tracereduce.is_device(name):
            continue
        for ev in events:
            ev_name, tf_op = "", None
            for f3, v in _fields(ev):
                if f3 == 2:
                    ev_name = v.decode(errors="replace")
                elif f3 == 5:
                    stat = dict(_fields(v))
                    if stat_names.get(stat.get(1)) == "tf_op":
                        tf_op = (stat[5].decode(errors="replace") if 5 in stat
                                 else stat_names.get(stat.get(7), ""))
            if tf_op:
                out[ev_name] = tf_op
    return out


def reduce(path: str) -> dict:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    device_ops, ends_by_run, completes, threads, drains = [], {}, {}, [], []
    for plane in data.planes:
        if tracereduce.is_device(plane.name):
            for line in plane.lines:
                evs = [(ev.start_ns, ev.start_ns + ev.duration_ns, ev.name) for ev in line.events]
                if line.name in tracereduce.OP_LINES:
                    device_ops.append(sorted(evs))
                elif line.name in tracereduce.MODULE_LINES:
                    for ev in line.events:
                        run = dict(ev.stats).get("run_id")
                        if run is not None:
                            ends_by_run[run] = ev.start_ns + ev.duration_ns
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                spans = []
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        spans.append((ev.start_ns, ev.start_ns + ev.duration_ns, ev.name))
                        if ev.name == STEP_SPAN:
                            drain = dict(ev.stats).get("drain")
                            if drain:
                                drains.append((ev.start_ns, ev.start_ns + ev.duration_ns, str(drain)))
                    elif ev.name == COMPLETE:
                        run = dict(ev.stats).get("run_id")
                        if run is not None:
                            completes[run] = ev.start_ns
                if spans:
                    threads.append(spans)
    skews = [completes[r] - end for r, end in ends_by_run.items() if r in completes]
    skew = min(skews) if skews else 0
    # the engine loop is one thread; a span of another thread names no gap
    spans = max(threads, key=len) if threads else []
    segments = innermost(spans)
    drains.sort()
    found: dict = {}
    for s, e, name in spans:
        row = found.setdefault(name, [0, 0.0])
        row[0] += 1
        row[1] += (e - s) / 1e9
    gaps, idle = [], {}
    for ops in device_ops:
        intervals = [(a + skew, b + skew) for a, b in gap_intervals(ops)]
        for (a, b), cover in zip(intervals, attribute(intervals, segments)):
            rest = (b - a) - sum(cover.values())
            if rest > 0:
                cover[NO_SPAN] = rest
            for name, ns in cover.items():
                idle[name] = idle.get(name, 0.0) + ns / 1e9
            gaps.append([name_gap(a, b, cover, drains, skew), (b - a) / 1e9,
                         {n: v / 1e9 for n, v in cover.items()}])
    gaps.sort(key=lambda g: -g[1])
    n_dev = max(1, len(device_ops))
    tf_ops = op_tf_ops(path)
    scopes, op_scopes = {}, {}
    for ops in device_ops:
        for s, e, name in tracereduce.leaves(ops):
            scope = scope_of(tf_ops[name]) if name in tf_ops else "(unscoped)"
            op_scopes[name] = scope
            scopes[scope] = scopes.get(scope, 0.0) + (e - s) / 1e9 / n_dev
    return {
        "spans": found, "clock_skew_s": skew / 1e9, "gaps": gaps[:25],
        "idle_by_span": {n: v / n_dev for n, v in idle.items()},
        "idle_s": sum(idle.values()) / n_dev, "scopes": scopes, "op_scopes": op_scopes,
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
