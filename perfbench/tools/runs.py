#!/usr/bin/env python3
"""Several benchmark runs in one call on the chip, one after the other, each
as the driver makes it, with what each left kept under one directory:

  chiprun --timeout 3600 -- python3 perfbench/tools/runs.py --out chiprun_out/<dir> \\
      [--tree <checkout>] [--own-cache] [--seconds 51] <cell>:<seed>:<trace>[:freeze@<s>[x<for>]] ...

`--tree` is the checkout the command runs in (a `git archive` copy; default:
this one). `freeze@<s>` stops the benchmark's parent from outside (SIGSTOP,
SIGCONT 1.5 s later, or `<for>` s) `<s>` seconds after its window opened: the
frozen load generator that `run.py` has to survive. Prints one block a run: exit code,
wall seconds, the lateness and void notes, the result line.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import signal
import subprocess
import sys
import threading
import time

KEEP = ("late in the window", "window opens", "voided", "perfbench FAILED", "stall at",
        "heartbeat stalls", "trace taken", "trace is kept")


def freeze_later(proc: subprocess.Popen, err_path: str, after_s: float, for_s: float) -> None:
    """SIGSTOP `after_s` seconds past the newest "window opens" note."""
    seen = 0
    while proc.poll() is None:
        with open(err_path, errors="replace") as f:
            opened = f.read().count("window opens")
        if opened > seen:
            seen = opened
            time.sleep(after_s)
            if proc.poll() is None:
                os.kill(proc.pid, signal.SIGSTOP)
                time.sleep(for_s)
                os.kill(proc.pid, signal.SIGCONT)
            return
        time.sleep(0.2)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__, formatter_class=argparse.RawTextHelpFormatter)
    p.add_argument("--out", required=True)
    p.add_argument("--tree", default=os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))
    p.add_argument("--seconds", default="51")
    p.add_argument("--own-cache", action="store_true",
                   help="as the driver's check: no JAX_COMPILATION_CACHE_DIR, so the first run of the call "
                        "compiles into the checkout's own perfbench/.jax_cache and the others find it")
    p.add_argument("runs", nargs="+")
    args = p.parse_args(argv)
    out = os.path.abspath(args.out)
    os.makedirs(out, exist_ok=True)
    summary = []
    env = dict(os.environ)
    if args.own_cache:
        env.pop("JAX_COMPILATION_CACHE_DIR", None)
    for i, spec in enumerate(args.runs):
        cell, seed, trace, *rest = spec.split(":")
        freeze = [float(v) for v in (rest[0].split("@")[1].split("x") + ["1.5"])[:2]] if rest else None
        label = f"{i:02d}-{cell}-{seed}-t{trace}" + ("-frozen" if rest else "")
        run_dir = os.path.join(out, label)
        err_path = os.path.join(out, label + ".err")
        t = time.monotonic()
        with open(os.path.join(out, label + ".json"), "w") as so, open(err_path, "w") as se:
            proc = subprocess.Popen(
                [sys.executable, "perfbench/run.py", "--workload", cell, "--seed", seed,
                 "--seconds", args.seconds, "--trace", trace, "--out", run_dir],
                cwd=args.tree, stdout=so, stderr=se, env=env)
            if freeze is not None:
                threading.Thread(target=freeze_later, args=(proc, err_path, *freeze), daemon=True).start()
            rc = proc.wait()
        wall = time.monotonic() - t
        for d in os.listdir(run_dir) if os.path.isdir(run_dir) else []:
            if d.startswith("trace") and os.path.isdir(os.path.join(run_dir, d)):
                shutil.rmtree(os.path.join(run_dir, d), ignore_errors=True)  # tens of MB a run
        with open(err_path, errors="replace") as f:
            notes = [ln.rstrip() for ln in f if any(k in ln for k in KEEP)]
        with open(os.path.join(out, label + ".json")) as f:
            last = (f.read().strip().splitlines() or [""])[-1]
        summary.append({"label": label, "rc": rc, "wall_s": wall, "result": last})
        print(f"== {label}: rc={rc} after {wall:.0f} s", *notes, last[:1500], sep="\n", flush=True)
    with open(os.path.join(out, "summary.json"), "w") as f:
        json.dump(summary, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
