#!/usr/bin/env python3
"""How many threads build the listed step programs at start-up: measured on the chip.

    chiprun --timeout 1500 -- python3 scripts/preload_workers.py \\
        chiprun_out/preload_workers.json jamba2-3b.chat 0 1 2 3 4 8

AFTER a benchmark run of that cell in the same call, from the same checkout
(`perfbench/tools/runs.py --own-cache`: it fills `perfbench/.jax_cache` and the
store inside it). For each count, a process of its own builds the engine the
cell's engine child builds (`perfbench/engine_main.build_engine_config`, so the
identity is the child's) with `step_programs.Preloader.WORKERS` set to it, and
reports when the engine stood, when the loader ended, and what each program
cost on its worker; 0 builds nothing and is the start-up the loader shares the
host with. `Preloader.WORKERS` names the count chosen (PERF.md section 6, PR
50); what that run read stands in scripts/preload_workers_result.json. Refuses
any platform but a TPU."""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

T0 = time.monotonic()
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "perfbench")


def child(cell_name: str, workers: int) -> dict:
    sys.path[:0] = [ROOT, BENCH]
    import jax

    if jax.default_backend() != "tpu":
        raise SystemExit(f"JAX runs on {jax.default_backend()!r}: nothing to measure")
    import engine_main

    from production_stack_tpu.engine import step_programs
    from production_stack_tpu.engine.engine import LLMEngine

    with open(os.path.join(BENCH, "cells", cell_name + ".json")) as f:
        cell = json.load(f)
    with open(os.path.join(BENCH, "configs", cell["config"] + ".json")) as f:
        doc = json.load(f)
    ecfg = engine_main.build_engine_config(
        doc, cell, argparse.Namespace(port=0, seed=1, debug=False))
    if workers:
        step_programs.Preloader.WORKERS = workers
    else:
        step_programs.StepProgramStore.listed = lambda self, identity: []
    jax.devices()  # the backend's own start is not the loader's
    t_backend = time.monotonic()
    engine = LLMEngine(ecfg)
    t_engine = time.monotonic()
    loader = engine.runner.preloaded
    built = loader.stats()
    pending = (built["step_program_preload_listed"] - built["step_program_preloaded_total"]
               - built["step_program_preload_failed_total"])
    assert loader.wait(600)
    return {
        "workers": workers, "backend_s": t_backend - T0,
        "engine_built_s": t_engine - t_backend, "pending_when_built": pending,
        "all_loaded_s": time.monotonic() - t_backend, **loader.stats(),
    }


def main() -> int:
    if sys.argv[1] == "--child":
        print("RESULT " + json.dumps(child(sys.argv[2], int(sys.argv[3]))), flush=True)
        return 0
    out, cell_name, counts = sys.argv[1], sys.argv[2], sys.argv[3:]
    env = dict(os.environ, PSTPU_COMPILE_CACHE_DIR=os.path.join(BENCH, ".jax_cache"))
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    results = []
    for n in counts:
        done = subprocess.run(
            [sys.executable, __file__, "--child", cell_name, n], env=env, cwd=ROOT,
            capture_output=True, text=True, timeout=900)
        lines = [ln for ln in done.stdout.splitlines() if ln.startswith("RESULT ")]
        if done.returncode or not lines:
            print(done.stderr[-3000:], file=sys.stderr)
            return 1
        result = json.loads(lines[0][len("RESULT "):])
        # "preloaded <program> <key>: 0.41 s (lower ..., compile or load ..., cache hit)"
        result["programs"] = [ln.split("step_programs: ", 1)[1] for ln in done.stderr.splitlines()
                              if "step_programs: preloaded pstpu" in ln]
        results.append(result)
        print(json.dumps({k: v for k, v in result.items() if k != "programs"}), flush=True)
    os.makedirs(os.path.dirname(os.path.abspath(out)), exist_ok=True)
    with open(out, "w") as f:
        json.dump(results, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
