#!/usr/bin/env python3
"""The control of a llama-family cell's reference check, at the cell's own
sizes, through the harness's own comparison (`engine_main.match_reference`):
the REFERENCE put in the program's place and computed on weights of the
nearest precision below the bfloat16 the configuration states. It has to come
out `correct: false`; the cell's tolerance belongs between the largest reading
sound runs of the cell give (each run prints its own: `check reference: ...
max |dlogprob|`) and the smallest reading here. Run it on the chip:

  chiprun -- python3 perfbench/tools/llama_lowprec_control.py [--base <perfbench dir>] <out.json> <cell> <seed> [<seed> ...]

For each seed (weights and prompt drawn from it), two controls:

  fp8    the weights cast to float8_e4m3fn and back, as the other families'
         controls (`scripts/*_lowprec_control.py`): THE control
  int8   the weights rounded to 8 bits with one scale an output channel
         (absmax / 127): what a weight-only int8 path would hold; read beside
         it, to say how fine the comparison resolves

each followed greedily for the cell's `output_tokens` from a prompt of its
`prompt_tokens`, its sorted top-20 log-probabilities judged against the
float32 reference on the bfloat16 weights. `reading` is the largest
|dlogprob| over the steps (what the check compares and prints), `correct` is
`match_reference`'s verdict under the cell's tolerance. One copy of the
weights is on the chip at a time (7.5 GB for `mistral-7b-d16`): they are
rounded in place and drawn again from the seed for the reference's own pass.
"""
import argparse
import importlib
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)
sys.path[:0] = [ROOT, HERE]

import manifest  # noqa: E402
from engine_main import match_reference  # noqa: E402


def fp8(a):
    # the barrier keeps XLA from folding the two conversions into none
    return lax.optimization_barrier(a.astype(jnp.float8_e4m3fn)).astype(a.dtype)


def int8(a):
    if a.ndim < 2:
        return a
    x = a.astype(jnp.float32)
    scale = jnp.max(jnp.abs(x), axis=-2, keepdims=True) / 127.0  # one an output channel
    q = lax.optimization_barrier(jnp.round(x / jnp.where(scale > 0, scale, 1.0)).astype(jnp.int8))
    return (q.astype(jnp.float32) * scale).astype(a.dtype)


CONTROLS = {"fp8": fp8, "int8": int8}


def top20(lp):
    top = np.sort(np.asarray(lp, np.float64))[::-1][:20]
    return {"chosen": float(top[0]), "top": [float(x) for x in top]}


def follow(next_lp, ids, n):
    """Greedy continuation: (served steps, chosen tokens)."""
    steps, out = [], []
    for _ in range(n):
        lp = np.asarray(next_lp(ids + out))
        steps.append(top20(lp))
        out.append(int(np.argmax(lp)))
    return steps, out


def main(argv):
    p = argparse.ArgumentParser(description=__doc__, formatter_class=argparse.RawTextHelpFormatter)
    p.add_argument("--base", default=HERE, help="the perfbench directory the cell and its configuration are read from")
    p.add_argument("out")
    p.add_argument("cell")
    p.add_argument("seeds", nargs="+", type=int)
    args = p.parse_args(argv)
    out_path, cell_name, seeds = args.out, args.cell, args.seeds
    cell = manifest.load_json("cells", cell_name + ".json", base=args.base)
    doc = manifest.load_json("configs", cell["config"] + ".json", base=args.base)
    pb = doc["perfbench"]
    module = importlib.import_module(pb["model_module"])
    ref = importlib.import_module("reference." + pb["reference"])
    cfg = getattr(module, pb["config_class"]).from_hf_config(doc)
    spec = cell["correctness"]["reference"]
    n_prompt, n_out, tol = spec["prompt_tokens"], spec["output_tokens"], spec["tolerance"]
    pad = -(-(n_prompt + n_out) // 128) * 128
    draw = jax.jit(lambda k: module.init_params(cfg, k))
    rows = []
    for seed in seeds:
        rng = np.random.default_rng(seed)
        ids = [256] + [int(t) for t in rng.integers(32, 127, n_prompt - 1)]
        row = {"seed": seed}
        for name, rounding in CONTROLS.items():
            params = draw(jax.random.key(seed % (2**31 - 1)))
            before = np.asarray(params["embed"][:8], np.float32)
            low = jax.jit(lambda p: jax.tree.map(rounding, p), donate_argnums=0)(params)
            del params
            if np.array_equal(before, np.asarray(low["embed"][:8], np.float32)):
                raise SystemExit(f"the {name} rounding left the weights as they were")
            steps, out = follow(lambda t: ref.next_token_logprobs(low, doc, t, pad), ids, n_out)
            del low
            params = draw(jax.random.key(seed % (2**31 - 1)))

            def true(toks, pad_to=pad):
                return ref.next_token_logprobs(params, doc, toks, pad_to)

            per_step = [
                float(np.max(np.abs(np.asarray(top20(true(ids + out[:i]))["top"])
                                    - np.asarray(steps[i]["top"]))))
                for i in range(n_out)]
            verdict = match_reference(true, ids, steps, tol, pad)
            del params
            row[name] = {"reading": max(per_step), "per_step": per_step,
                         "correct": bool(verdict["ok"]), "steps_matched": verdict["steps_matched"]}
            print(f"seed {seed} {name}: reading {max(per_step):.4f} (mean of steps "
                  f"{np.mean(per_step):.4f}); tolerance {tol}: correct {verdict['ok']}, "
                  f"{verdict['steps_matched']}/{n_out} steps", flush=True)
        rows.append(row)
    os.makedirs(os.path.dirname(os.path.abspath(out_path)), exist_ok=True)
    with open(out_path, "w") as f:
        json.dump({"cell": cell_name, "tolerance": tol, "platform": jax.default_backend(),
                   "rows": rows}, f, indent=1)
    for name in CONTROLS:
        r = [row[name]["reading"] for row in rows]
        print(f"{name}: readings min {min(r):.4f} max {max(r):.4f}; "
              f"correct in {sum(row[name]['correct'] for row in rows)} of {len(rows)}")


if __name__ == "__main__":
    main(sys.argv[1:])
