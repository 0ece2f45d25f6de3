#!/usr/bin/env python3
"""What `jamba2-3b.chat`'s reference check reads for a sound program and for two
low-precision controls, at the cell's own sizes (1,152-token prompt, 12 greedy
steps), through the harness's own comparison (`perfbench/engine_main.py:
match_reference`). Run it on the chip: `chiprun -- python3
scripts/jamba_lowprec_control.py <out.json> <seed> [<seed> ...]`.

For each seed (weights and prompt drawn from it):

  program   `models/jamba.forward` as the engine runs it (bf16, chunks of 512,
            then single steps, the scan kernel where the platform has it)
  fp8       the REFERENCE on weights rounded to float8_e4m3fn, the nearest
            precision below the bfloat16 the configuration states
  bf16state the REFERENCE with its recurrent state rounded to bfloat16 between
            two steps, the nearest precision below the float32 it states

each followed greedily for 12 tokens and its top-20 log-probabilities judged
against the float32 reference on the bf16 weights. `reading` is the largest
|dlogprob| over the 12 steps' sorted top-20 (what the check compares and
prints), `chosen_token` the largest distance of a served token's own
log-probability (the check finds the token by it), `correct` is
`match_reference`'s verdict under the cell's tolerance. The
tolerance belongs between the programs' largest reading and the controls'
smallest; every control has to come out `correct: false`.
"""
import dataclasses
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "perfbench")]

from engine_main import match_reference  # noqa: E402
from reference import jamba as ref  # noqa: E402

from production_stack_tpu.models import jamba  # noqa: E402
from production_stack_tpu.ops.pallas.ssm_scan import resolve_ssm_impl  # noqa: E402

PAGE, CHUNK = 64, 512


def top20(lp):
    lp = np.asarray(lp, np.float64)
    top = np.sort(lp)[::-1][:20]
    return {"chosen": float(top[0]), "top": [float(x) for x in top]}


def follow(next_lp, ids, n):
    """Greedy continuation: (served steps, chosen tokens)."""
    steps, out = [], []
    for _ in range(n):
        lp = np.asarray(next_lp(ids + out))
        steps.append(top20(lp))
        out.append(int(np.argmax(lp)))
    return steps, out


def program_steps(cfg, params, ids, n):
    pages = -(-(len(ids) + n) // PAGE) + 1
    k, v = jamba.init_kv_pages(cfg, pages + 1, PAGE)
    state = jamba.init_state(cfg, 2)
    table = jnp.arange(1, pages + 1, dtype=jnp.int32)[None]
    slots = jnp.asarray([0], jnp.int32)
    fwd = jax.jit(
        lambda p, t, pos, k, v, lens, st: jamba.forward(
            p, cfg, t, pos, k, v, table, lens, state=st, state_slots=slots))

    def logprobs(logits):
        x = np.asarray(logits, np.float64)
        return x - x.max() - np.log(np.sum(np.exp(x - x.max())))

    for lo in range(0, len(ids), CHUNK):
        c = min(CHUNK, len(ids) - lo)
        t = np.zeros((1, CHUNK), np.int32)
        pos = np.full((1, CHUNK), -1, np.int32)
        t[0, :c], pos[0, :c] = ids[lo:lo + c], np.arange(lo, lo + c)
        logits, k, v, state = fwd(params, t, pos, k, v, jnp.asarray([lo + c]), state)
    steps, out = [], []
    for i in range(n):
        lp = logprobs(logits[0])
        steps.append(top20(lp))
        out.append(int(np.argmax(lp)))
        if i + 1 < n:
            at = len(ids) + i
            logits, k, v, state = fwd(
                params, np.asarray([[out[-1]]], np.int32), np.asarray([[at]], np.int32),
                k, v, jnp.asarray([at + 1]), state)
    return steps, out


def main(argv):
    out_path, seeds = argv[0], [int(s) for s in argv[1:]]
    doc = json.load(open(os.path.join(ROOT, "perfbench", "configs", "jamba2-3b.json")))
    cell = json.load(open(os.path.join(ROOT, "perfbench", "cells", "jamba2-3b.chat.json")))
    spec = cell["correctness"]["reference"]
    n_prompt, n_out, tol = spec["prompt_tokens"], spec["output_tokens"], spec["tolerance"]
    pad = -(-(n_prompt + n_out) // 128) * 128
    impl = resolve_ssm_impl(jax.default_backend())[0]
    cfg = dataclasses.replace(
        jamba.JambaConfig.from_hf_config(doc), max_model_len=4096, attn_impl="xla", ssm_impl=impl)
    rows = []
    for seed in seeds:
        params = jax.jit(lambda k: jamba.init_params(cfg, k))(jax.random.key(seed % (2**31 - 1)))
        rng = np.random.default_rng(seed)
        ids = [256] + [int(t) for t in rng.integers(32, 127, n_prompt - 1)]

        def true(toks, pad_to=pad):
            return ref.next_token_logprobs(params, doc, toks, pad_to)

        # the barrier keeps XLA from folding the two conversions into none
        low = jax.jit(lambda p: jax.tree.map(
            lambda a: lax.optimization_barrier(a.astype(jnp.float8_e4m3fn)).astype(a.dtype),
            p))(params)
        if bool(jnp.all(low["embed"] == params["embed"])):
            raise SystemExit("the float8 rounding left the weights as they were")
        served = {
            "program": program_steps(cfg, params, ids, n_out),
            "fp8": follow(lambda t: ref.next_token_logprobs(low, doc, t, pad), ids, n_out),
            "bf16state": follow(
                lambda t: ref.next_token_logprobs(params, doc, t, pad, jnp.bfloat16), ids, n_out),
        }
        del low
        row = {"seed": seed}
        for name, (steps, out) in served.items():
            per_step = [
                float(np.max(np.abs(np.asarray(top20(true(ids + out[:i]))["top"])
                                    - np.asarray(steps[i]["top"]))))
                for i in range(n_out)]
            # the check finds a served token among the reference's candidates
            # by its log-probability: that distance has to fit the tolerance too
            chosen = max(abs(float(np.asarray(true(ids + out[:i]))[out[i]]) - steps[i]["chosen"])
                         for i in range(n_out))
            verdict = match_reference(true, ids, steps, tol, pad)
            row[name] = {"reading": max(per_step), "per_step": per_step, "chosen_token": chosen,
                         "correct": bool(verdict["ok"]), "steps_matched": verdict["steps_matched"]}
            print(f"seed {seed} {name}: reading {max(per_step):.4f} "
                  f"(mean of steps {np.mean(per_step):.4f}), chosen token {chosen:.4f}; "
                  f"tolerance {tol}: correct {verdict['ok']}, "
                  f"{verdict['steps_matched']}/{n_out} steps", flush=True)
        rows.append(row)
        del params
    with open(out_path, "w") as f:
        json.dump({"tolerance": tol, "platform": jax.default_backend(), "ssm_impl": impl,
                   "rows": rows}, f, indent=1)
    for name in ("program", "fp8", "bf16state"):
        r = [row[name]["reading"] for row in rows]
        print(f"{name}: readings min {min(r):.4f} max {max(r):.4f}; "
              f"correct in {sum(row[name]['correct'] for row in rows)} of {len(rows)}")


if __name__ == "__main__":
    main(sys.argv[1:])
