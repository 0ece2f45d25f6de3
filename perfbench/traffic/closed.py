"""A closed loop of one-shot requests: a fixed pool of callers, each of which
waits for its answer, thinks, and asks something that shares NOTHING with any
other request. Every prompt token is prefilled: no prefix is cached.

Who sends traffic of this shape: document question answering and
retrieval-augmented assistants behind the router. Every request carries its own
retrieved passages (no prefix in common), and a fixed pool of callers each
waits for its answer, does its own work (`think_s`), and asks again. A cell's
numbers are its own; `mistral-7b-d16.rag`'s are synthetic (README.md).

`generate(params, seed, seconds, tokenizer)` returns the plan `mix.py`
describes (same keys, same meaning). Its one stream kind (`params["streams"]`,
any number of them, merged into one plan):

  closed    users, prompt_tokens [lo, hi], quantum, output_tokens [lo, hi],
            think_s, think_spread, warm_seconds, ramp, population_seed

Lengths are TOTAL prompt tokens as the engine counts them, log-uniform on
`prompt_tokens` and rounded to `quantum` (a prompt of whole prefill chunks
meets the shapes an open stream of the same quantum has met); outputs are
log-uniform on `output_tokens`; a turn's think time is `think_s` x uniform
[1 - think_spread, 1 + think_spread]. Sizes and think times are drawn from the
mix's own `population_seed`: every `--seed` offers the same multiset of them.
`--seed` writes the text (and the weights) and deals the draws among the users
in another order: callers that wait for their replies pace themselves, so the
arrangement does not decide what is measured as it does in an open stream near
its knee (`mix.py`). The first turns are spread over one think time where the
warm-up starts; `warm_seconds` of the loop run before the window; `ramp` is a
set-up phase of the stream's own lengths (the longest among them), as an open
stream's. Every client gets turns enough for the shortest think time and
answers that take no time at all.

The helpers (`text`, `log_uniform`, `one_shot`, `ramp`, `overhead`) are
`mix.py`'s own, loaded from the file beside this one: not a copy.
"""

from __future__ import annotations

import math
import os
import random

import manifest

mix = manifest.load_module(
    "traffic", "mix", base=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
one_shot = mix.one_shot  # run.py asks a generator module for `generate` and `one_shot`


def closed_stream(s, name, seed, seconds, tok):
    order = random.Random(f"{seed}/{name}/closed")  # the seed writes the text and deals the draws
    pop = random.Random(f"{s.get('population_seed', 0)}/{name}/closed/{seconds}")
    warm = float(s.get("warm_seconds", 0))
    users, q = int(s["users"]), int(s.get("quantum", 1))
    think, spread = float(s["think_s"]), float(s.get("think_spread", 0.0))
    # a turn takes at least the shortest think time: never fewer turns than a user can take
    turns_each = math.ceil((warm + seconds) / (think * (1.0 - spread))) + 4
    n = users * turns_each
    prompts = mix.shuffled(mix.log_uniform(pop, *s["prompt_tokens"], n, q), order)
    outputs = mix.shuffled(mix.log_uniform(pop, *s["output_tokens"], n), order)
    thinks = mix.shuffled([think * pop.uniform(1.0 - spread, 1.0 + spread) for _ in range(n)], order)
    setup = []
    if s.get("ramp"):
        ramp_pop = random.Random(f"{s.get('population_seed', 0)}/{name}/ramp")
        m = int(s["ramp"]["requests"])
        # the stream's own lengths; the longest the mix can send outlasts them all
        lengths = mix.log_uniform(ramp_pop, *s["prompt_tokens"], m - 1, q)
        lengths.append(mix.log_uniform(ramp_pop, s["prompt_tokens"][1], s["prompt_tokens"][1], 1, q)[0])
        setup.append(mix.ramp(f"{name}.ramp", [
            one_shot(order, f"r{seed:x}.{i} ", p, 0, tok, name) for i, p in enumerate(lengths)
        ], s["ramp"]))
    clients = []
    for u in range(users):
        turns = []
        for j in range(turns_each):
            k = u * turns_each + j
            # its own tag from the first character on: no page is shared by accident
            turn = one_shot(order, f"c{seed:x}.{u}.{j} ", prompts[k], outputs[k], tok, name)
            turn["think_s"] = thinks[k]
            turns.append(turn)
        # spread the users over one think time, so they do not ask in step
        clients.append({"turns": turns, "think_s": think,
                        "first_due_s": -warm + think * order.random()})
    return {"setup": setup, "clients": clients, "warm_seconds": warm}


def generate(params: dict, seed: int, seconds: float, tokenizer: dict) -> dict:
    plan = {"setup": [], "open": [], "clients": [], "warm_seconds": 0.0}
    for i, s in enumerate(params["streams"]):
        if s["kind"] != "closed":
            raise SystemExit(f"perfbench: traffic/closed.py has no stream kind {s['kind']!r}")
        part = closed_stream(s, s.get("name", f"s{i}"), int(seed), float(seconds), tokenizer)
        plan["setup"].extend(part["setup"])
        plan["clients"].extend(part["clients"])
        plan["warm_seconds"] = max(plan["warm_seconds"], part["warm_seconds"])
    return plan
