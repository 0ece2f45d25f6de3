#!/usr/bin/env python3
"""A held window reads what it read: the numbers a run printed, computed
again from what it kept (`<out>/requests.json`: the request log, the window,
the two snapshots; `<out>/trace_reduced*.json`), with the `stats.py` and the
readers of ANOTHER checkout's perfbench directory (the parent's):

  python3 perfbench/tools/replay.py --with <parent>/perfbench <run's --out> <run's result line .json> ...

No clock, no chip, no JAX. Prints one line a number and exits 1 where one
differs in any digit. What the other checkout has no file for (a metric this
one added) is named and passed over.
"""

from __future__ import annotations

import argparse
import json
import os
import sys


def replay(other: str, out_dir: str, result_path: str) -> int:
    sys.path.insert(0, other)
    for name in ("manifest", "stats", "readers_common"):
        sys.modules.pop(name, None)
    import manifest
    import stats

    with open(os.path.join(out_dir, "requests.json")) as f:
        log = json.load(f)
    with open(result_path) as f:
        result = json.loads(f.read().strip().splitlines()[-1])
    cell = manifest.load_json("cells", log["cell"] + ".json", base=other)
    doc = manifest.load_json("configs", cell["config"] + ".json", base=other)
    window, worst_ms = tuple(log["window"]), 120000.0
    sub = log["sub"]
    trace = None
    if sub:
        nth = os.path.basename(sub["dir"])[len("trace"):]
        with open(os.path.join(out_dir, f"trace_reduced{nth}.json")) as f:
            trace = json.load(f)
    ctx = {"cell": cell, "config": doc, "requests": log["requests"], "window": window,
           "snap0": log["snap0"], "snap1": log["snap1"], "spans": {}, "trace": trace, "sub": sub,
           "device_kind": result["device"]["kind"], "base_dir": other, "worst_ms": worst_ms,
           "peaks": manifest.peaks(result["device"]["kind"], other)}
    bad = 0
    for name, printed in result["metrics"].items():
        if os.path.exists(os.path.join(other, "end_to_end", name + ".json")):
            spec = manifest.load_json("end_to_end", name + ".json", base=other)
            again = (log["setup_s"] if spec["stat"]["kind"] == "setup"
                     else stats.end_to_end(spec, log["requests"], window, worst_ms)[0])
        elif os.path.exists(os.path.join(other, "layer_metrics", name + ".json")):
            spec = manifest.load_json("layer_metrics", name + ".json", base=other)
            again = manifest.load_module("readers", spec["reader"], other).read(ctx, spec.get("params", {}))
        else:
            print(f"  {name}: {printed['value']!r} (no file in {other}: passed over)")
            continue
        same = again == printed["value"]
        bad += not same
        print(f"  {name}: printed {printed['value']!r} again {again!r} {'same' if same else 'DIFFERS'}")
    return bad


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__, formatter_class=argparse.RawTextHelpFormatter)
    p.add_argument("--with", dest="other", required=True, help="the other checkout's perfbench directory")
    p.add_argument("pairs", nargs="+", help="<run's out directory> <file holding its result line>, in turn")
    args = p.parse_args(argv)
    bad = 0
    for out_dir, result_path in zip(args.pairs[::2], args.pairs[1::2]):
        print(f"== {out_dir}")
        bad += replay(os.path.abspath(args.other), out_dir, result_path)
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
