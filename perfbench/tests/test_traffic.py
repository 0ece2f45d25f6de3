"""The generator: deterministic in (params, seed, seconds), no clock; sizes and
gaps are draws, the same under every seed: an open stream keeps their order
too (the seed writes the text), sessions are dealt in another order."""

import copy
import time

import pytest

from traffic import mix

TOK = {"kind": "byte", "bos_tokens": 1, "generation_prompt_tokens": 14,
       "message_overhead_tokens": {"system": 12, "user": 10, "assistant": 15}}
OPEN = {"kind": "open", "name": "chat", "rate_rps": 6.0, "prompt_tokens": [128, 2048],
        "quantum": 256, "output_tokens": [32, 256], "warm_seconds": 8, "lead_seconds": 5, "population_seed": 24,
        "ramp": {"requests": 16, "first_tokens": 64, "step_tokens": 4}}
SESSIONS = {"kind": "sessions", "name": "qa", "users": 9, "rounds": 3, "system_tokens": 1024,
            "history_tokens": [1024, 3072], "quantum": 64, "question_tokens": [64, 256],
            "output_tokens": 96, "think_s": 2.5, "think_spread": 0.6, "warm_seconds": 5,
            "ramp": {"requests": 9, "first_tokens": 64, "step_tokens": 8}}


def every_request(plan):
    out = [r for ph in plan["setup"] for r in ph.get("requests", []) + ph.get("open", [])]
    return out + plan["open"] + [t for c in plan["clients"] for t in c["turns"]]


def tokens(messages):
    """What the byte tokenizer and the chat template make of the messages."""
    return 1 + 14 + sum(TOK["message_overhead_tokens"][m["role"]] + len(m["content"])
                        for m in messages)


@pytest.mark.parametrize("stream", [OPEN, SESSIONS], ids=lambda s: s["kind"])
def test_deterministic_and_clockless(stream, monkeypatch):
    params = {"streams": [stream]}
    for fn in ("time", "monotonic", "perf_counter"):
        monkeypatch.setattr(time, fn, lambda: pytest.fail("the generator read a clock"))
    frozen = copy.deepcopy(params)
    a = mix.generate(params, 2**31 + 77, 20, TOK)
    b = mix.generate(params, 2**31 + 77, 20, TOK)
    assert a == b and params == frozen
    assert a != mix.generate(params, 2**31 + 78, 20, TOK)


@pytest.mark.parametrize("stream", [OPEN, SESSIONS], ids=lambda s: s["kind"])
def test_stated_prompt_tokens_are_what_the_engine_will_count(stream):
    plan = mix.generate({"streams": [stream]}, 5, 20, TOK)
    reqs = every_request(plan)
    assert reqs
    for r in reqs:
        assert tokens(r["messages"]) == r["prompt_tokens"]
        assert all(ord(ch) < 128 for m in r["messages"] for ch in m["content"])


def window_of(seed, seconds=30):
    plan = mix.generate({"streams": [OPEN]}, seed, seconds, TOK)
    return [r for r in plan["open"] if r["due_s"] >= 0], [r for r in plan["open"] if r["due_s"] < 0]


@pytest.mark.parametrize("seed", [2, 2**31 + 5, 3450002201])
def test_open_loop_offers_every_seed_the_same_schedule_in_other_words(seed):
    """The order of the draws moved the median time per token more than the
    system's own noise did (PERF.md section 6), so the seed no longer deals it."""
    a, warm_a = window_of(1)
    b, warm_b = window_of(seed)
    assert len(a) == len(b) == 180  # rate x seconds, exactly
    assert all(0 <= r["due_s"] < 30 for r in a)
    schedule = lambda rs: [(r["due_s"], r["prompt_tokens"], r["max_tokens"]) for r in rs]  # noqa: E731
    assert schedule(a) == schedule(b) and schedule(warm_a) == schedule(warm_b)
    assert all(x["messages"] != y["messages"] for x, y in zip(a, b))
    phases = lambda s: mix.generate({"streams": [OPEN]}, s, 30, TOK)["setup"]  # noqa: E731
    for x, y in zip(phases(1), phases(seed)):  # the ramp and the warm-up too
        xs, ys = x.get("requests") or x["open"], y.get("requests") or y["open"]
        assert [(r.get("due_s"), r["prompt_tokens"], r["max_tokens"]) for r in xs] == \
               [(r.get("due_s"), r["prompt_tokens"], r["max_tokens"]) for r in ys]


def test_open_loop_draws_its_sizes_and_its_window():
    a, warm_a = window_of(1)
    assert set(r["prompt_tokens"] for r in a) <= set(range(256, 2049, 256))
    assert len(set(r["prompt_tokens"] for r in a)) >= 6 and len(set(r["max_tokens"] for r in a)) > 60
    # the lead-in is the same mix under draws of its own, never the window's
    assert len(warm_a) == 30 and all(-5 <= r["due_s"] < 0 for r in warm_a)


def test_arrivals_are_poisson_draws_not_an_even_spread():
    a, _ = window_of(3, 200)
    gaps = [y["due_s"] - x["due_s"] for x, y in zip(a, a[1:])]
    mean = sum(gaps) / len(gaps)
    var = sum((g - mean) ** 2 for g in gaps) / len(gaps)
    assert 0.8 < var / mean ** 2 < 1.25  # exponential gaps: the deviation equals the mean
    # bunches: some second of the 200 holds at least twice the mean rate, some none
    per_second = [sum(1 for r in a if int(r["due_s"]) == k) for k in range(200)]
    assert max(per_second) >= 12 and min(per_second) <= 1


def test_a_ramp_knows_nothing_of_the_engine():
    plan = mix.generate({"streams": [OPEN]}, 4, 20, TOK)
    phase, warm = plan["setup"]
    assert phase["name"] == "chat.ramp" and warm["name"] == "chat.warm"
    # the warm-up is the mix itself for 8 s from a clock of its own, and is drained
    assert len(warm["open"]) == 48 and all(0 <= r["due_s"] < 8 for r in warm["open"])
    assert [r["max_tokens"] for r in phase["requests"]] == [64 + 4 * i for i in range(16)]
    # the longest prompt the mix can send is among them, and outlasts the others
    assert phase["requests"][-1]["prompt_tokens"] == 2048


def test_sessions_share_a_prefix_grow_and_start_over():
    plan = mix.generate({"streams": [SESSIONS]}, 9, 20, TOK)
    assert [ph["name"] for ph in plan["setup"]] == ["qa.histories", "qa.ramp"]
    assert len(plan["setup"][0]["requests"]) == 9 and len(plan["setup"][1]["requests"]) == 9
    system = plan["setup"][0]["requests"][0]["messages"][0]
    thinks, questions = [], []
    for u, c in enumerate(plan["clients"]):
        assert -5 <= c["first_due_s"] <= -2.5 and c["think_s"] == 2.5
        opening = plan["setup"][0]["requests"][u]["messages"]
        assert 1024 <= len(opening[1]["content"]) <= 3072 and len(opening[1]["content"]) % 64 == 0
        for t in c["turns"]:
            assert t["messages"][:2] == opening and t["messages"][0] == system
            assert t["max_tokens"] == 96 and len(t["messages"]) in (4, 6, 8)  # 3 rounds at most
            thinks.append(t["think_s"])
            questions.append(len(t["messages"][-1]["content"]))
        pairs = list(zip(c["turns"], c["turns"][1:]))
        grown = [b["messages"][:len(a["messages"])] == a["messages"] for a, b in pairs]
        assert any(grown) and not all(grown)  # turns extend the last, until a new conversation
    assert 1.0 <= min(thinks) < 1.3 and 3.7 < max(thinks) <= 4.0  # 2.5 s x [0.4, 1.6], drawn
    assert 64 <= min(questions) < 80 and 220 < max(questions) <= 256
    # every user can take more turns than the run has room for
    assert all(len(c["turns"]) >= 25 / 1.0 for c in plan["clients"])
