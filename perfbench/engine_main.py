#!/usr/bin/env python3
"""The engine child of a benchmark run: the one process that holds the chip.

Reads the configuration's file, builds the model config from its published
keys (with `reduced` already applied in the file), registers it under the
configuration's name, and serves it through the program's own entry point
(`engine.api_server.serve`). Beside the engine's port it listens on a control
port for what only this process can do: report the device and its memory,
start and stop `jax.profiler`, and run the plain reference on the engine's own
parameters.

The surface of the program used here (PERF.md "The program surface"):
`<model module>.PRESETS`, `<config class>.from_hf_config`,
`engine.config.add_engine_args` / `config_from_args`,
`engine.api_server.serve(cfg)`, and `server.engine.runner.params`.
"""

from __future__ import annotations

import argparse
import asyncio
import importlib
import json
import os
import signal
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for _p in (ROOT, HERE):
    if _p not in sys.path:
        sys.path.insert(0, _p)

TOP = 20  # the served API reports at most 20 top log-probabilities a token


def match_reference(next_logprobs, prompt_ids, steps, tol, pad_to):
    """Follow a greedy served continuation through the reference.

    The API returns log-probabilities but no token ids, so the tokens are
    inferred: at each step the reference's next-token distribution (given the
    chain so far) must show the served top-20 log-probabilities within `tol`,
    value for value, and the served token is the reference's candidate whose
    log-probability is within `tol` of the served choice. Where two candidates
    are that close (a near-tie that bf16 may order either way) both are tried,
    depth first; a wrong guess shows at the next step, whose whole top-20
    differs.

    Returns {"ok", "steps_matched", "max_abs_diff", "ties_tried"}.
    """
    import numpy as np

    state = {"max": 0.0, "ties": 0, "deepest": 0, "worst_at_fail": None}

    def walk(chain, i):
        state["deepest"] = max(state["deepest"], i)
        if i == len(steps):
            return True
        lp = np.asarray(next_logprobs(prompt_ids + chain, pad_to))
        order = np.argsort(-lp)[:TOP + 4]
        served_top = np.asarray(steps[i]["top"], np.float64)
        diff = float(np.max(np.abs(np.sort(lp[order])[::-1][:len(served_top)] - served_top)))
        if diff > tol:
            if state["worst_at_fail"] is None or i >= state["deepest"]:
                state["worst_at_fail"] = diff
            return False
        candidates = [int(t) for t in order if abs(lp[t] - steps[i]["chosen"]) <= tol]
        state["ties"] += max(0, len(candidates) - 1)
        for tok in candidates:
            if walk(chain + [tok], i + 1):
                state["max"] = max(state["max"], diff)
                return True
        return False

    ok = walk([], 0)
    return {
        "ok": ok, "steps": len(steps), "steps_matched": state["deepest"],
        "max_abs_diff": state["max"] if ok else state["worst_at_fail"],
        "ties_tried": state["ties"], "tolerance": tol,
    }


def build_engine_config(doc: dict, cell: dict, args):
    """Register the configuration and parse the engine's arguments."""
    pb = doc["perfbench"]
    module = importlib.import_module(pb["model_module"])
    model_cfg = getattr(module, pb["config_class"]).from_hf_config(doc)
    module.PRESETS[doc["name"]] = model_cfg

    from production_stack_tpu.engine.config import add_engine_args, config_from_args

    parser = argparse.ArgumentParser("perfbench-engine")
    add_engine_args(parser)
    argv = [
        "--model", doc["name"], "--host", "127.0.0.1", "--port", str(args.port),
        # any whole number is a seed; the engine's key takes 31 bits of it
        "--seed", str(args.seed % (2**31 - 1)),
        *pb.get("engine_args", []), *cell.get("engine_args", []),
    ]
    if args.debug:
        argv.append("--enable-debug-endpoints")
    return config_from_args(parser.parse_args(argv))


async def run(args) -> None:
    from aiohttp import web

    with open(args.config) as f:
        doc = json.load(f)
    with open(args.cell) as f:
        cell = json.load(f)
    import jax

    if jax.default_backend() != args.platform:
        # before anything is built: off the chip the benchmark measures nothing
        raise SystemExit(f"perfbench engine: JAX runs on {jax.default_backend()!r}, "
                         f"not {args.platform!r}")
    ecfg = build_engine_config(doc, cell, args)

    from production_stack_tpu.engine.api_server import serve

    server, runner = await serve(ecfg)

    loop = asyncio.get_running_loop()
    reference = importlib.import_module("reference." + doc["perfbench"]["reference"])

    async def device(_request):
        devs = jax.local_devices()
        stats = [d.memory_stats() or {} for d in devs]
        return web.json_response({
            "platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(jax.devices()),
            "memory_peak_bytes": max(s.get("peak_bytes_in_use", 0) for s in stats),
            "memory_in_use_bytes": max(s.get("bytes_in_use", 0) for s in stats),
            "memory_limit_bytes": max(s.get("bytes_limit", 0) for s in stats),
        })

    async def profile_start(request):
        body = await request.json()
        os.makedirs(body["dir"], exist_ok=True)
        await loop.run_in_executor(None, jax.profiler.start_trace, body["dir"])
        return web.json_response({"ok": True})

    async def profile_stop(_request):
        await loop.run_in_executor(None, jax.profiler.stop_trace)
        return web.json_response({"ok": True})

    async def reference_check(request):
        body = await request.json()
        params = server.engine.runner.params

        def work():
            return match_reference(
                lambda toks, pad: reference.next_token_logprobs(params, doc, toks, pad),
                list(body["prompt_ids"]), body["steps"], float(body["tolerance"]),
                int(body["pad_to"]),
            )

        return web.json_response(await loop.run_in_executor(None, work))

    control = web.Application(client_max_size=64 * 2**20)
    control.router.add_get("/device", device)
    control.router.add_post("/profile/start", profile_start)
    control.router.add_post("/profile/stop", profile_stop)
    control.router.add_post("/reference", reference_check)
    crunner = web.AppRunner(control)
    await crunner.setup()
    await web.TCPSite(crunner, "127.0.0.1", args.control_port).start()

    stop = asyncio.Event()
    for sig in (signal.SIGTERM, signal.SIGINT):
        loop.add_signal_handler(sig, stop.set)
    await stop.wait()
    await crunner.cleanup()
    try:
        await asyncio.wait_for(runner.cleanup(), 10)
    except asyncio.TimeoutError:
        pass
    server.engine.stop()


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--config", required=True)
    p.add_argument("--cell", required=True)
    p.add_argument("--port", type=int, required=True)
    p.add_argument("--control-port", type=int, required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--debug", action="store_true")
    p.add_argument("--platform", default="tpu")
    asyncio.run(run(p.parse_args()))


if __name__ == "__main__":
    main()
