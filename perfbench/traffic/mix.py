"""The one traffic generator: a mix is a data file of parameters, this reads it.

`generate(params, seed, seconds, tokenizer)` returns a plan. No I/O, no clock:
the same `(params, seed, seconds)` gives the same plan, byte for byte.

  plan = {
    "setup":   [phase, ...]  each done and all its requests awaited before the
               next: {"name", "requests": [request, ...]} sent together (a cache
               to build, a ramp of concurrent requests), or {"name", "open":
               [request with "due_s" >= 0, ...]} sent when due (warm-up traffic,
               then drained: whatever queue its first meeting with the system
               builds is gone before the window). A phase marked
               "every_window" builds state that belongs to this plan's own text
               (a cache of its histories): a run that measures a further window
               under another seed's plan runs that plan's marked phases again;
    "open":    [request with "due_s", ...]  open loop: sent when due, whatever
               the system does. due_s < 0 is the lead-in flowing into the window;
    "clients": [{"first_due_s", "think_s", "turns": [request, ...]}, ...]
               closed loop: a client's next turn is due its own `think_s`
               after its previous reply ended. Clients start with the warm-up
               (first_due_s <= 0) and run through it into the window;
    "warm_seconds": how long "open" and "clients" run before the window opens,
  }
  request = {"messages", "max_tokens", "prompt_tokens", "stream": <name>}

Sizes and gaps are DRAWN: exponential gaps (Poisson arrivals), log-uniform
lengths, uniform think times. A drawn set has its bursts and its runs of long
requests. The draws come from the mix's own `population_seed`: every seed
offers the same amount of work. An OPEN stream offers it on the same schedule
too, request for request as drawn, and `--seed` writes the text (and the
weights): until PR 45 it also dealt the draws in another order (a plain
shuffle), which makes other bursts of the same draws, and at 0.7-0.8 x the
knee the median time per token followed the arrangement and not the system
(PERF.md section 6: two runs of one seed lay 0.15-0.19 ms apart, four seeds
0.53-1.06). SESSIONS, whose users wait for their replies and so pace
themselves, are still dealt by `--seed` in another order.

Stream kinds (`params["streams"]`, any number, merged into one plan):
  open      independent users: rate_rps, prompt_tokens [lo, hi], output_tokens
            [lo, hi], quantum, ramp, warm_seconds (a drained set-up phase of the
            mix itself), lead_seconds (the mix flowing into the window)
  sessions  users with a shared system prompt, their own history and a growing
            conversation: users, rounds, system_tokens, history_tokens [lo, hi],
            question_tokens [lo, hi], output_tokens, think_s, think_spread,
            warm_seconds, ramp
Lengths are TOTAL prompt tokens as the engine counts them (template and BOS
included), rounded to `quantum` (1 if not given).

`ramp` {requests, first_tokens, step_tokens} is a set-up phase that knows
nothing of the engine: that many requests of the stream's own lengths sent
together, the i-th asking for first_tokens + i * step_tokens output tokens, so
the number in flight climbs to `requests` as they are admitted and then falls
through every value down to 1. Whatever batch sizes the engine compiles for,
each is visited once before the warm-up traffic starts.
"""

from __future__ import annotations

import math
import random

WORDS = (
    "the quick brown fox jumps over the lazy dog while seven wizards quietly "
    "mix a potion of black quartz and judge my vow to pack the box with five "
    "dozen liquor jugs before the bright sphinx of night wakes"
).split()


def text(rng: random.Random, n: int, tag: str) -> str:
    """Exactly n ASCII characters (= n byte-tokenizer tokens), distinct from
    the first characters on (the tag), so nothing is shared by accident."""
    parts, size = [tag], len(tag)
    while size < n:
        w = rng.choice(WORDS)
        parts.append(w)
        size += len(w) + 1
    return " ".join(parts)[:n].ljust(n, ".")


def log_uniform(rng: random.Random, lo: float, hi: float, n: int, quantum: int = 1) -> list[int]:
    """n draws, log-uniform on [lo, hi], each rounded to the quantum."""
    out = []
    for _ in range(n):
        v = math.exp(rng.uniform(math.log(lo), math.log(hi)))
        out.append(max(quantum, int(round(v / quantum)) * quantum))
    return out


def poisson_times(rng: random.Random, n: int, span: float) -> list[float]:
    """n arrival times in [0, span): n + 1 exponential gaps drawn by `rng`,
    scaled to fill the span. That is a Poisson process given that n arrivals
    fell into the span, so the offered rate is exact and the bunching is a
    Poisson process's own."""
    gaps = [rng.expovariate(1.0) for _ in range(n + 1)]
    scale = span / sum(gaps)
    times, t = [], 0.0
    for g in gaps[:n]:
        t += g * scale
        times.append(t)
    return times


def shuffled(values: list, order: random.Random) -> list:
    values = list(values)
    order.shuffle(values)
    return values


def overhead(tok: dict, roles: list[str]) -> int:
    """Tokens the chat template and BOS add around the messages' contents."""
    return (tok["bos_tokens"] + tok["generation_prompt_tokens"]
            + sum(tok["message_overhead_tokens"][r] for r in roles))


def one_shot(rng, tag, total_tokens, max_tokens, tok, stream) -> dict:
    n = total_tokens - overhead(tok, ["user"])
    return {
        "messages": [{"role": "user", "content": text(rng, n, tag)}],
        "max_tokens": max_tokens, "prompt_tokens": total_tokens, "stream": stream,
    }


def ramp(name: str, requests: list[dict], spec: dict) -> dict:
    """The requests, the i-th asking for first_tokens + i * step_tokens."""
    first, step = int(spec["first_tokens"]), int(spec["step_tokens"])
    return {"name": name, "requests": [
        dict(r, max_tokens=first + i * step) for i, r in enumerate(requests)]}


def open_stream(s, name, seed, seconds, tok):
    words = random.Random(f"{seed}/{name}/open")  # the seed writes the text, the mix its sizes and times
    warm, lead = float(s.get("warm_seconds", 0)), float(s.get("lead_seconds", 0))
    rate, q = float(s["rate_rps"]), int(s.get("quantum", 1))
    plan = {"setup": [], "open": [], "clients": [], "warm_seconds": lead}
    if s.get("ramp"):
        pop = random.Random(f"{s.get('population_seed', 0)}/{name}/ramp")
        n = int(s["ramp"]["requests"])
        # the stream's own lengths; the longest the mix can send outlasts them all
        prompts = log_uniform(pop, *s["prompt_tokens"], n - 1, q)
        prompts.append(log_uniform(pop, s["prompt_tokens"][1], s["prompt_tokens"][1], 1, q)[0])
        plan["setup"].append(ramp(f"{name}.ramp", [
            one_shot(words, f"r{seed:x}.{i} ", p, 0, tok, name) for i, p in enumerate(prompts)
        ], s["ramp"]))
    for part, span, offset in (("warm", warm, None), ("lead", lead, -lead),
                               ("window", float(seconds), 0.0)):
        if span <= 0:
            continue
        # warm-up and lead-in are the same mix under draws of their own, never the window's
        pop = random.Random(f"{s.get('population_seed', 0)}/{name}/{part}/{span}")
        n = max(1, int(round(rate * span)))
        times = poisson_times(pop, n, span)
        prompts = log_uniform(pop, *s["prompt_tokens"], n, q)
        outputs = log_uniform(pop, *s["output_tokens"], n)
        reqs = []
        for i, t in enumerate(times):
            r = one_shot(words, f"{part[:2]}{seed:x}.{i} ", prompts[i], outputs[i], tok, name)
            r["due_s"] = (offset or 0.0) + t
            reqs.append(r)
        if offset is None:
            plan["setup"].append({"name": f"{name}.warm", "open": reqs})
        else:
            plan["open"].extend(reqs)
    return plan


def sessions_stream(s, name, seed, seconds, tok):
    """Multi-round QA: every user's prompt is system + own history + the turns
    of the conversation so far; only the newest question (and the previous
    answer) is uncached. After `rounds` turns the user starts a new
    conversation over the same system prompt and history, as a user opens a
    new chat: the pool's working set stays bounded."""
    order = random.Random(f"{seed}/{name}/sessions")
    pop = random.Random(f"{s.get('population_seed', 0)}/{name}/sessions/{seconds}")
    warm = float(s.get("warm_seconds", 0))
    users, rounds = int(s["users"]), int(s["rounds"])
    think, spread = float(s["think_s"]), float(s.get("think_spread", 0.0))
    out_tokens = int(s["output_tokens"])
    ov = tok["message_overhead_tokens"]
    system = {"role": "system", "content": text(order, int(s["system_tokens"]), f"s{seed:x} ")}
    histories = shuffled(log_uniform(pop, *s["history_tokens"], users, int(s.get("quantum", 1))), order)
    # a turn takes at least the shortest think time: never fewer turns than a user can take
    turns_each = math.ceil((warm + seconds) / (think * (1.0 - spread))) + 4
    questions = shuffled(log_uniform(pop, *s["question_tokens"], users * turns_each), order)
    # with ONE think time the users fall into step (those served together
    # return together); people do not: uniform on [1 - spread, 1 + spread] x think_s
    thinks = shuffled([think * pop.uniform(1.0 - spread, 1.0 + spread)
                       for _ in range(users * turns_each)], order)
    base = (tok["bos_tokens"] + tok["generation_prompt_tokens"]
            + ov["system"] + int(s["system_tokens"]))
    clients, cache_phase = [], []
    for u in range(users):
        opening = [system, {"role": "user", "content": text(order, histories[u], f"h{seed:x}.{u} ")}]
        opening_total = base + ov["user"] + histories[u]
        cache_phase.append({"messages": list(opening), "max_tokens": 1,
                            "prompt_tokens": opening_total, "stream": name})
        turns = []
        for j in range(turns_each):
            if j % rounds == 0:
                msgs, total = opening, opening_total
            # the previous reply enters the conversation as text of its length:
            # the served tokens of a random-weight model do not decode to text
            reply = text(order, out_tokens, f"a{u}.{j} ")
            ask = text(order, questions[u * turns_each + j], f"q{seed:x}.{u}.{j} ")
            msgs = msgs + [{"role": "assistant", "content": reply},
                           {"role": "user", "content": ask}]
            total += ov["assistant"] + len(reply) + ov["user"] + len(ask)
            turns.append({"messages": list(msgs), "max_tokens": out_tokens,
                          "prompt_tokens": total, "stream": name,
                          "think_s": thinks[u * turns_each + j]})
        # spread the users over one think time, so they do not ask in step
        clients.append({"turns": turns, "think_s": think,
                        "first_due_s": -warm + think * order.random()})
    # the cached histories are this plan's own text: a further window of a run,
    # which takes a plan of another seed, builds them again (`every_window`)
    setup = [{"name": f"{name}.histories", "requests": cache_phase, "every_window": True}]
    if s.get("ramp"):
        n = min(users, int(s["ramp"]["requests"]))
        setup.append(ramp(f"{name}.ramp", [c["turns"].pop(0) for c in clients[:n]], s["ramp"]))
    return {"setup": setup, "open": [], "clients": clients, "warm_seconds": warm}


KINDS = {"open": open_stream, "sessions": sessions_stream}


def generate(params: dict, seed: int, seconds: float, tokenizer: dict) -> dict:
    plan = {"setup": [], "open": [], "clients": [], "warm_seconds": 0.0}
    for i, s in enumerate(params["streams"]):
        part = KINDS[s["kind"]](s, s.get("name", f"s{i}"), int(seed), float(seconds), tokenizer)
        for key in ("setup", "open", "clients"):
            plan[key].extend(part[key])
        plan["warm_seconds"] = max(plan["warm_seconds"], part["warm_seconds"])
    plan["open"].sort(key=lambda r: r["due_s"])
    return plan
