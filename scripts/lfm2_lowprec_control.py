#!/usr/bin/env python3
"""What `lfm2-8b-a1b-d16.chat`'s reference check reads for a sound program, for
a low-precision control and for a planted wrong-expert fault, at the cell's own
sizes (1,152-token prompt, 12 greedy steps), through the harness's own
comparison (`perfbench/engine_main.py:match_reference`). Run it on the chip:

    chiprun -- python3 scripts/lfm2_lowprec_control.py <out.json> \
        [--own-share=R] [--controls=program,fp8,wrong_expert] <seed> [<seed> ...]

The two options may be given again between seeds: each holds for the seeds
that follow it. For each seed (weights and prompt drawn from it):

  program       `models/lfm2.forward` as the engine runs it (bf16, chunks of
                512, then single steps, the grouped-product kernel where the
                platform has it)
  fp8           the REFERENCE on weights rounded to float8_e4m3fn, the nearest
                precision below the bfloat16 the configuration states
  wrong_expert  the same program on the same weights with every layer's
                experts rolled by one under an unchanged router: what an
                expert index off by one in the grouped product computes (every
                assignment meets its neighbour's weights)

each followed greedily for 12 tokens and its top-20 log-probabilities judged
against the float32 reference on the true bf16 weights. `reading` is the
largest |dlogprob| over the 12 steps' sorted top-20 (what the check compares
and prints), `chosen_token` the largest distance of a served token's own
log-probability (the check finds the token by it), `correct` is
`match_reference`'s verdict under the cell's tolerance. The tolerance belongs
above the programs' largest reading and below the smallest of either control;
every control has to come out `correct: false`.

`--own-share` draws the experts with another `lfm2.EXPERT_OWN_SHARE` than the
tree's (how the tree's was chosen: PERF.md section 6, PR 46).

The weights are 10.8 GB: a second copy does not fit the chip beside the first,
so the rounded ones are drawn from the seed when they are needed and the
experts are rolled in place, a layer at a time, and rolled back.
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
from reference import lfm2_moe as ref  # noqa: E402

from production_stack_tpu.models import lfm2  # noqa: E402

PAGE, CHUNK = 64, 512
CONTROLS = ("program", "fp8", "wrong_expert")
#: a rehearsal on the CPU (LFM2_CONTROL_TOY=1): the configuration's depth,
#: experts and top-k at a sixteenth of its widths
TOY = {"hidden_size": 256, "intermediate_size": 1024, "moe_intermediate_size": 256,
       "vocab_size": 4096, "num_attention_heads": 4, "num_key_value_heads": 2}


def program(cfg, n_total):
    """(params, ids, n) -> (served steps, chosen tokens) through one jitted
    forward that every seed shares."""
    pages = -(-n_total // PAGE) + 1
    table = jnp.arange(1, pages + 1, dtype=jnp.int32)[None]
    slots = jnp.asarray([0], jnp.int32)
    fwd = jax.jit(
        lambda p, t, pos, k, v, lens, st: lfm2.forward(
            p, cfg, t, pos, k, v, table, lens, state=st, state_slots=slots))

    def logprobs(logits):
        x = np.asarray(logits, np.float64)
        return x - x.max() - np.log(np.sum(np.exp(x - x.max())))

    def steps_of(params, ids, n):
        k, v = lfm2.init_kv_pages(cfg, pages + 1, PAGE)
        state = lfm2.init_state(cfg, 2)
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
    """Every expert layer's weights rolled along the expert axis, in place."""
    mp = params["moe_ffn"]
    for name in ("w13", "w2"):
        for layer in range(mp[name].shape[0]):
            mp[name] = _roll_layer(mp[name], layer, shift)


def drawers(cfg, own_share: float):
    """Jitted draws of the true and of the float8-rounded weights under
    `own_share` (read when the draw is traced, so each share has its own)."""

    def init(k):
        lfm2.EXPERT_OWN_SHARE = own_share
        return lfm2.init_params(cfg, k)

    # the barrier keeps XLA from folding the two conversions into none
    low = lambda k: jax.tree.map(  # noqa: E731
        lambda a: lax.optimization_barrier(a.astype(jnp.float8_e4m3fn)).astype(a.dtype),
        init(k))
    return jax.jit(init), jax.jit(low)


def main(argv):
    out_path, plan = argv[0], argv[1:]
    doc = json.load(open(os.path.join(ROOT, "perfbench", "configs", "lfm2-8b-a1b-d16.json")))
    cell = json.load(open(os.path.join(ROOT, "perfbench", "cells", "lfm2-8b-a1b-d16.chat.json")))
    if os.environ.get("LFM2_CONTROL_TOY"):
        doc = dict(doc, **TOY)
    spec = cell["correctness"]["reference"]
    n_prompt, n_out, tol = spec["prompt_tokens"], spec["output_tokens"], spec["tolerance"]
    pad = -(-(n_prompt + n_out) // 128) * 128
    cfg = dataclasses.replace(
        lfm2.Lfm2Config.from_hf_config(doc), max_model_len=4096, attn_impl="xla")
    steps_of = program(cfg, n_prompt + n_out)
    tree_share = own_share = lfm2.EXPERT_OWN_SHARE
    controls, draws, rows = CONTROLS, {}, []
    for word in plan:
        if word.startswith("--own-share="):
            own_share = float(word.split("=", 1)[1])
            continue
        if word.startswith("--controls="):
            controls = tuple(word.split("=", 1)[1].split(","))
            if set(controls) - set(CONTROLS):
                raise SystemExit(f"controls are {CONTROLS}, not {controls}")
            continue
        seed = int(word)
        if own_share not in draws:
            draws[own_share] = drawers(cfg, own_share)
        draw, draw_low = draws[own_share]
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
        if "program" in controls:
            served["program"] = steps_of(params, ids, n_out)
        if "wrong_expert" in controls:
            roll_experts(params, -1)
            served["wrong_expert"] = steps_of(params, ids, n_out)
            roll_experts(params, 1)
        seen = {}

        def true(toks, pad_to=pad):
            # the chains share their prompt and often their first tokens
            if tuple(toks) not in seen:
                seen[tuple(toks)] = np.asarray(ref.next_token_logprobs(params, doc, toks, pad_to))
            return seen[tuple(toks)]

        row = {"seed": seed, "own_share": own_share}
        for name in controls:
            steps, out = served[name]
            per_step = [
                float(np.max(np.abs(np.asarray(top20(true(ids + out[:i]))["top"])
                                    - np.asarray(steps[i]["top"]))))
                for i in range(n_out)]
            # the check finds a served token among the reference's candidates
            # by its log-probability: that distance has to fit the tolerance too
            chosen = max(abs(float(true(ids + out[:i])[out[i]]) - steps[i]["chosen"])
                         for i in range(n_out))
            verdict = match_reference(true, ids, steps, tol, pad)
            row[name] = {"reading": max(per_step), "per_step": per_step, "chosen_token": chosen,
                         "correct": bool(verdict["ok"]), "steps_matched": verdict["steps_matched"]}
            print(f"own share {own_share} seed {seed} {name}: reading {max(per_step):.4f} "
                  f"(mean of steps {np.mean(per_step):.4f}), chosen token {chosen:.4f}; "
                  f"tolerance {tol}: correct {verdict['ok']}, "
                  f"{verdict['steps_matched']}/{n_out} steps", flush=True)
        rows.append(row)
        del params, seen
        with open(out_path, "w") as f:
            json.dump({"tolerance": tol, "platform": jax.default_backend(),
                       "tree_own_share": tree_share, "rows": rows}, f, indent=1)
    for share in sorted({row["own_share"] for row in rows}):
        for name in CONTROLS:
            r = [row[name] for row in rows if row["own_share"] == share and name in row]
            if r:
                print(f"own share {share} {name}: readings min "
                      f"{min(x['reading'] for x in r):.4f} max {max(x['reading'] for x in r):.4f}; "
                      f"correct in {sum(x['correct'] for x in r)} of {len(r)}")


if __name__ == "__main__":
    main(sys.argv[1:])
