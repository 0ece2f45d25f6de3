#!/usr/bin/env python3
"""What `nemotron3-nano-30b-ep8.chat`'s reference check reads for a sound
program and for four controls, at the cell's own sizes (1,152-token prompt, 12
greedy steps), through the harness's own comparison
(`perfbench/engine_main.py:match_reference`). Run it on the chip:

    chiprun -- python3 scripts/nemotron_lowprec_control.py <out.json> \
        [--controls=program,fp8,...] <seed> [<seed> ...]

The option may be given again between seeds: it holds for the seeds that
follow it. For each seed (weights and prompt drawn from it):

  program       `models/nemotron_h.forward` as the engine runs it (bf16, chunks
                of 512, then single steps, both kernels where the platform has
                them)
  fp8           the REFERENCE on weights rounded to float8_e4m3fn, the nearest
                precision below the bfloat16 the configuration states
  bf16state     the REFERENCE with the recurrent state rounded to bfloat16
                between two steps (the 2 MiB state is the new mechanism)
  wrong_expert  the program on the same weights with every layer's held
                experts rolled by one under an unchanged router
  wrong_group   the program with every head reading the B and C of the group
                before its own (a group index off by one in the recurrence)
  exact_recurrence  (not a control: a diagnosis) the program with the
                recurrence in float32 `jax.numpy` (`ssm_impl="xla"`):
                what the SSD kernels' own rounding adds to the reading

each followed greedily for 12 tokens and its top-20 log-probabilities judged
against the float32 reference on the true bf16 weights. `reading` is the
largest |dlogprob| over the 12 steps' sorted top-20 (what the check compares
and prints), `chosen_token` the largest distance of a served token's own
log-probability, `correct` `match_reference`'s verdict under the cell's
tolerance. The tolerance belongs above the program's largest reading and below
the smallest of the controls that have to come out `correct: false`.

The weights are 11.9 GB: a second copy does not fit the chip beside the first,
so the rounded ones are drawn from the seed when they are needed and the
experts are rolled in place, a layer at a time, and rolled back.
NEMOTRON_CONTROL_TOY=1 rehearses on the CPU at a sixteenth of the widths.
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
sys.path[:0] = [ROOT, os.path.join(ROOT, "perfbench"), os.path.join(ROOT, "scripts")]

from engine_main import match_reference  # noqa: E402
from jamba_lowprec_control import follow, top20  # noqa: E402
from reference import nemotron_h as ref  # noqa: E402

from production_stack_tpu.models import nemotron_h as nh  # noqa: E402
from production_stack_tpu.ops.pallas import ssd_scan  # noqa: E402
from production_stack_tpu.ops.pallas.ssm_scan import resolve_ssm_impl  # noqa: E402

PAGE, CHUNK = 64, 512
CONTROLS = ("program", "fp8", "bf16state", "wrong_expert", "wrong_group", "exact_recurrence")
TOY = {"hidden_size": 256, "vocab_size": 4096, "moe_intermediate_size": 208,
       "moe_shared_expert_intermediate_size": 416, "num_attention_heads": 4,
       "num_key_value_heads": 2, "head_dim": 32, "mamba_num_heads": 16}


def program(cfg, n_total, wrong_group=False):
    """(params, ids, n) -> (served steps, chosen tokens) through one jitted
    forward that every seed shares."""
    pages = -(-n_total // PAGE) + 1
    table = jnp.arange(1, pages + 1, dtype=jnp.int32)[None]
    slots = jnp.asarray([0], jnp.int32)
    sound = ssd_scan.ssd_scan

    def off_by_one(x, dt, a, b_mat, c_mat, *rest, **kw):
        return sound(x, dt, a, jnp.roll(b_mat, 1, axis=2), jnp.roll(c_mat, 1, axis=2),
                     *rest, **kw)

    def forward(p, t, pos, k, v, lens, st):
        # the fault is planted while the program is traced
        ssd_scan.ssd_scan = off_by_one if wrong_group else sound
        try:
            return nh.forward(p, cfg, t, pos, k, v, table, lens, state=st, state_slots=slots)
        finally:
            ssd_scan.ssd_scan = sound

    fwd = jax.jit(forward)

    def logprobs(logits):
        x = np.asarray(logits, np.float64)
        return x - x.max() - np.log(np.sum(np.exp(x - x.max())))

    def steps_of(params, ids, n):
        k, v = nh.init_kv_pages(cfg, pages + 1, PAGE)
        state = nh.init_state(cfg, 1)
        for lo in range(0, len(ids), CHUNK):
            c = min(CHUNK, len(ids) - lo)
            t = np.zeros((1, CHUNK), np.int32)
            pos = np.full((1, CHUNK), -1, np.int32)
            t[0, :c], pos[0, :c] = ids[lo:lo + c], np.arange(lo, lo + c)
            logits, k, v, state, _ = fwd(params, t, pos, k, v, jnp.asarray([lo + c]), state)
        steps, out = [], []
        for i in range(n):
            lp = logprobs(logits[0])
            steps.append(top20(lp))
            out.append(int(np.argmax(lp)))
            if i + 1 < n:
                at = len(ids) + i
                logits, k, v, state, _ = fwd(
                    params, np.asarray([[out[-1]]], np.int32), np.asarray([[at]], np.int32),
                    k, v, jnp.asarray([at + 1]), state)
        return steps, out

    return steps_of


_roll_layer = jax.jit(
    lambda w, layer, shift: w.at[layer].set(jnp.roll(w[layer], shift, axis=0)),
    donate_argnums=0, static_argnums=2)


def roll_experts(params, shift: int) -> None:
    """Every expert layer's held experts rolled along the expert axis, in place."""
    mp = params["moe_layers"]
    for name in ("w1", "w2"):
        for layer in range(mp[name].shape[0]):
            mp[name] = _roll_layer(mp[name], layer, shift)


def drawers(cfg):
    """Jitted draws of the true and of the float8-rounded weights."""
    init = lambda k: nh.init_params(cfg, k)  # noqa: E731
    # the barrier keeps XLA from folding the two conversions into none
    low = lambda k: jax.tree.map(  # noqa: E731
        lambda a: lax.optimization_barrier(a.astype(jnp.float8_e4m3fn)).astype(a.dtype),
        init(k))
    return jax.jit(init), jax.jit(low)


def main(argv):
    out_path, plan = argv[0], argv[1:]
    doc = json.load(open(os.path.join(ROOT, "perfbench", "configs", "nemotron3-nano-30b-ep8.json")))
    cell = json.load(open(os.path.join(ROOT, "perfbench", "cells",
                                       "nemotron3-nano-30b-ep8.chat.json")))
    if os.environ.get("NEMOTRON_CONTROL_TOY"):
        doc = dict(doc, **TOY)
    spec = cell["correctness"]["reference"]
    n_prompt, n_out, tol = spec["prompt_tokens"], spec["output_tokens"], spec["tolerance"]
    pad = -(-(n_prompt + n_out) // 128) * 128
    impl = resolve_ssm_impl(jax.default_backend())[0]
    cfg = dataclasses.replace(
        nh.NemotronHConfig.from_hf_config(doc), max_model_len=4096, ssm_impl=impl)
    steps_of = {False: program(cfg, n_prompt + n_out),
                True: program(cfg, n_prompt + n_out, wrong_group=True),
                "exact": program(dataclasses.replace(cfg, ssm_impl="xla"), n_prompt + n_out)}
    controls, rows = CONTROLS, []
    draw, draw_low = drawers(cfg)
    for word in plan:
        if word.startswith("--controls="):
            controls = tuple(word.split("=", 1)[1].split(","))
            if set(controls) - set(CONTROLS):
                raise SystemExit(f"controls are {CONTROLS}, not {controls}")
            continue
        seed = int(word)
        key = jax.random.key(seed % (2**31 - 1))
        rng = np.random.default_rng(seed)
        ids = [256] + [int(t) for t in rng.integers(32, 127, n_prompt - 1)]
        served = {}
        if "fp8" in controls:
            low = draw_low(key)
            served["fp8"] = follow(lambda t: ref.next_token_logprobs(low, doc, t, pad), ids, n_out)
            low_embed = np.asarray(low["embed"][:8], np.float32)
            del low
        params = draw(key)
        if "fp8" in controls and np.array_equal(
                low_embed, np.asarray(params["embed"][:8], np.float32)):
            raise SystemExit("the float8 rounding left the weights as they were")
        if "bf16state" in controls:
            served["bf16state"] = follow(
                lambda t: ref.next_token_logprobs(params, doc, t, pad, state_dtype=jnp.bfloat16),
                ids, n_out)
        if "program" in controls:
            served["program"] = steps_of[False](params, ids, n_out)
        if "wrong_group" in controls:
            served["wrong_group"] = steps_of[True](params, ids, n_out)
        if "exact_recurrence" in controls:
            served["exact_recurrence"] = steps_of["exact"](params, ids, n_out)
        if "wrong_expert" in controls:
            roll_experts(params, -1)
            served["wrong_expert"] = steps_of[False](params, ids, n_out)
            roll_experts(params, 1)
        seen = {}

        def true(toks, pad_to=pad):
            # the chains share their prompt and often their first tokens
            if tuple(toks) not in seen:
                seen[tuple(toks)] = np.asarray(ref.next_token_logprobs(params, doc, toks, pad_to))
            return seen[tuple(toks)]

        row = {"seed": seed}
        for name in controls:
            steps, out = served[name]
            per_step = [
                float(np.max(np.abs(np.asarray(top20(true(ids + out[:i]))["top"])
                                    - np.asarray(steps[i]["top"]))))
                for i in range(n_out)]
            chosen = max(abs(float(true(ids + out[:i])[out[i]]) - steps[i]["chosen"])
                         for i in range(n_out))
            verdict = match_reference(true, ids, steps, tol, pad)
            row[name] = {"reading": max(per_step), "per_step": per_step, "chosen_token": chosen,
                         "correct": bool(verdict["ok"]), "steps_matched": verdict["steps_matched"]}
            print(f"seed {seed} {name}: reading {max(per_step):.4f} "
                  f"(mean of steps {np.mean(per_step):.4f}), chosen token {chosen:.4f}; "
                  f"tolerance {tol}: correct {verdict['ok']}, "
                  f"{verdict['steps_matched']}/{n_out} steps", flush=True)
        rows.append(row)
        del params, seen
        os.makedirs(os.path.dirname(os.path.abspath(out_path)), exist_ok=True)
        with open(out_path, "w") as f:
            json.dump({"tolerance": tol, "platform": jax.default_backend(), "ssm_impl": impl,
                       "rows": rows}, f, indent=1)
    for name in CONTROLS:
        r = [row[name] for row in rows if name in row]
        if r:
            print(f"{name}: readings min {min(x['reading'] for x in r):.4f} "
                  f"max {max(x['reading'] for x in r):.4f}; "
                  f"correct in {sum(x['correct'] for x in r)} of {len(r)}")


if __name__ == "__main__":
    main(sys.argv[1:])
