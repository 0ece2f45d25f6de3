"""What PR 53 adds to the benchmark: the closed-loop generator of one-shot
requests (`traffic/closed.py`) and its cell `mistral-7b-d16.rag`, the metric
`steps.prefill_device_share` with its reader and the window-long
`steps.prefill_between_bursts_share` beside it, BENCHMARK.json against the files (the ORDER
rule: accepted entries first and as they were, whoever appended after), the
accepted cells' plans held to a digest, and the cell's whole command rehearsed
on the CPU at a toy size."""

import argparse
import asyncio
import copy
import hashlib
import json
import os
import shutil
import subprocess
import time
from collections import Counter

import pytest

import manifest
import run

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)
TOY = os.path.join(HERE, "tests", "data", "rehearsal")
DOC = manifest.load_json("configs", "mistral-7b-d16.json")
CELL = manifest.load_json("cells", "mistral-7b-d16.rag.json")
PARAMS = CELL["traffic"]["params"]
STREAM = PARAMS["streams"][0]
TOK = DOC["perfbench"]["tokenizer"]
closed = manifest.load_module("traffic", "closed")
mix = manifest.load_module("traffic", "mix")
ACCEPTED = ["mistral-7b-d16.chat", "qwen2.5-7b-d14.sessions", "jamba2-3b.chat",
            "lfm2-8b-a1b-d16.chat", "nemotron3-nano-30b-ep8.chat"]
BIG = 3450002201  # the driver's seeds pass 2**31


def tokens(messages):
    """What the byte tokenizer and the chat template make of the messages."""
    return (TOK["bos_tokens"] + TOK["generation_prompt_tokens"]
            + sum(TOK["message_overhead_tokens"][m["role"]] + len(m["content"]) for m in messages))


def every_request(plan):
    out = [r for ph in plan["setup"] for r in ph.get("requests", []) + ph.get("open", [])]
    return out + plan["open"] + [t for c in plan["clients"] for t in c["turns"]]


def digest(plan) -> str:
    return hashlib.sha256(json.dumps(plan, sort_keys=True).encode()).hexdigest()


# -- the generator ----------------------------------------------------------------

def test_the_plan_is_the_same_bytes_for_the_same_arguments_and_reads_no_clock(monkeypatch):
    for fn in ("time", "monotonic", "perf_counter"):
        monkeypatch.setattr(time, fn, lambda: pytest.fail("the generator read a clock"))
    frozen = copy.deepcopy(PARAMS)
    a, b = closed.generate(PARAMS, BIG, 51, TOK), closed.generate(PARAMS, BIG, 51, TOK)
    assert json.dumps(a) == json.dumps(b) and PARAMS == frozen
    assert digest(a) != digest(closed.generate(PARAMS, BIG + 1, 51, TOK))
    assert digest(a) != digest(closed.generate(PARAMS, BIG, 50, TOK))


@pytest.mark.parametrize("seed", [1, 6, BIG])
def test_every_prompt_is_whole_chunks_in_range_and_counts_what_the_engine_will(seed):
    plan = closed.generate(PARAMS, seed, 51, TOK)
    reqs = every_request(plan)
    assert len(plan["clients"]) == 16 and not plan["open"] and plan["warm_seconds"] == STREAM["warm_seconds"]
    for r in reqs:
        assert r["prompt_tokens"] % 512 == 0 and 1024 <= r["prompt_tokens"] <= 2048
        assert tokens(r["messages"]) == r["prompt_tokens"]
        assert all(ord(ch) < 128 for m in r["messages"] for ch in m["content"])
        assert [m["role"] for m in r["messages"]] == ["user"] and r["stream"] == "rag"
    turns = [t for c in plan["clients"] for t in c["turns"]]
    assert all(64 <= t["max_tokens"] <= 256 for t in turns)
    assert all(0.8 - 1e-9 <= t["think_s"] <= 3.2 + 1e-9 for t in turns)
    share = Counter(t["prompt_tokens"] for t in turns)
    # log-uniform on [1024, 2048] rounded to 512: 32 / 49 / 19% (ISSUE 53)
    for size, want in ((1024, 0.322), (1536, 0.485), (2048, 0.193)):
        assert share[size] / len(turns) == pytest.approx(want, abs=0.04)


@pytest.mark.parametrize("seed", [1, BIG])
def test_no_two_requests_share_their_first_16_characters(seed):
    """Nothing is shared, so every prompt token is prefilled: the first page
    (BOS, the template's 9 characters, 54 of the content) differs already."""
    plans = [closed.generate(PARAMS, s, 51, TOK) for s in (seed, run.window_seed(seed, 1))]
    heads = [r["messages"][0]["content"][:16] for p in plans for r in every_request(p)]
    assert len(set(heads)) == len(heads)
    # the checks' own prompts (run.py: `g<seed> `, `f<seed> `) start otherwise too
    assert not any(h[0] in "gf" for h in heads)


def test_two_seeds_offer_the_same_sizes_and_think_times_dealt_otherwise():
    a, b = (closed.generate(PARAMS, s, 51, TOK) for s in (1, BIG))
    dealt = lambda p: [(t["prompt_tokens"], t["max_tokens"], t["think_s"])  # noqa: E731
                       for c in p["clients"] for t in c["turns"]]
    for key in (0, 1, 2):  # each drawn on its own, each dealt on its own
        assert sorted(d[key] for d in dealt(a)) == sorted(d[key] for d in dealt(b))
    assert dealt(a) != dealt(b)
    ramp = lambda p: [(r["prompt_tokens"], r["max_tokens"]) for r in p["setup"][0]["requests"]]  # noqa: E731
    assert ramp(a) == ramp(b)  # the set-up is the same work under every seed


def test_no_client_can_run_out_of_turns():
    """The shortest think time and answers that take no time at all."""
    plan = closed.generate(PARAMS, 2, 51, TOK)
    shortest = STREAM["think_s"] * (1.0 - STREAM["think_spread"])
    for c in plan["clients"]:
        assert len(c["turns"]) * shortest >= STREAM["warm_seconds"] + 51
        assert -STREAM["warm_seconds"] <= c["first_due_s"] < -STREAM["warm_seconds"] + STREAM["think_s"]
    assert len({c["first_due_s"] for c in plan["clients"]}) == 16  # not in step


def test_the_ramp_is_the_stream_s_own_lengths_with_the_longest_among_them():
    (phase,) = closed.generate(PARAMS, 3, 51, TOK)["setup"]
    reqs = phase["requests"]
    assert phase["name"] == "rag.ramp" and "every_window" not in phase and len(reqs) == 16
    assert [r["max_tokens"] for r in reqs] == [64 + 8 * i for i in range(16)]
    assert max(r["prompt_tokens"] for r in reqs) == 2048
    assert all(r["prompt_tokens"] in (1024, 1536, 2048) for r in reqs)


def test_a_further_window_is_the_same_work_under_other_text_and_marks_no_phase():
    """What `tests/test_windows.py` asks of every cell through `mix.generate`
    (it does not look up the cell's generator, and is red for this one)."""
    plan = closed.generate(PARAMS, 5, 2.0, TOK)
    again = closed.generate(PARAMS, run.window_seed(5, 1), 2.0, TOK)
    assert [p["name"] for p in plan["setup"] if p.get("every_window")] == []
    work = lambda p: [sorted(t[k] for c in p["clients"] for t in c["turns"])  # noqa: E731
                      for k in ("prompt_tokens", "max_tokens", "think_s")]
    assert work(again) == work(plan)
    assert json.dumps(again["clients"]) != json.dumps(plan["clients"])


def test_the_module_takes_its_helpers_from_mix_and_knows_its_own_kind_alone():
    # loaded from the file beside it, not copied
    assert closed.one_shot.__code__.co_filename == mix.one_shot.__code__.co_filename == mix.__file__
    assert closed.mix.__file__ == mix.__file__ and "def text" not in open(closed.__file__).read()
    chat = manifest.load_json("cells", "mistral-7b-d16.chat.json")["traffic"]["params"]
    with pytest.raises(SystemExit, match="no stream kind 'open'"):
        closed.generate(chat, 7, 51, TOK)
    two = {"streams": [STREAM, dict(STREAM, name="b", users=3, warm_seconds=5)]}
    plan = closed.generate(two, 7, 51, TOK)
    assert len(plan["clients"]) == 19 and plan["warm_seconds"] == STREAM["warm_seconds"] and len(plan["setup"]) == 2


# what `mix.generate` gave for the accepted cells' params on the parent
# (09f0fa1; `tests/data/golden_plans.json` was written from `git show
# 09f0fa1:perfbench/traffic/mix.py`): this PR leaves `traffic/mix.py` as it
# was, so their plans are byte for byte the parent's
GOLDEN = os.path.join(HERE, "tests", "data", "golden_plans.json")


@pytest.mark.parametrize("name", ACCEPTED)
@pytest.mark.parametrize("seed", [1, BIG])
def test_the_accepted_cells_plans_are_what_they_were_on_the_parent(name, seed):
    with open(GOLDEN) as f:
        golden = json.load(f)
    cell = manifest.load_json("cells", name + ".json")
    tok = manifest.load_json("configs", cell["config"] + ".json")["perfbench"]["tokenizer"]
    assert cell["traffic"]["generator"] == "mix"
    plan = mix.generate(cell["traffic"]["params"], seed, 51, tok)
    assert digest(plan) == golden[f"{name}/{seed}"]


# -- the metrics ------------------------------------------------------------------

def _metric(name):
    spec = manifest.load_json("layer_metrics", name + ".json")
    return manifest.load_module("readers", spec["reader"]), spec


def test_prefill_device_share_reads_the_prefill_programs_by_name():
    reader, spec = _metric("steps.prefill_device_share")
    params = spec["params"]
    tr = {"busy_s": 2.0, "devices": 1, "modules": {
        "jit_pstpu_step(1)": [10, 0.4, 0.04], "jit_pstpu_step_lp(2)": [1, 0.1, 0.1],
        "jit_pstpu_multi_step_k8(3)": [20, 1.4, 0.07], "jit_pstpu_spec_s8_k4_n3(4)": [1, 0.05, 0.05]}}
    assert reader.read({"trace": tr}, params) == pytest.approx(25.0)
    # a program that names nothing (`jit__unknown`), no trace, an idle device: nothing, never 0
    assert reader.read({"trace": dict(tr, modules={"jit__unknown(9)": [5, 1.0, 0.2]})}, params) is None
    assert reader.read({"trace": None}, params) is None
    assert reader.read({"trace": dict(tr, busy_s=0.0)}, params) is None
    assert (spec["layer"], spec["moves"], spec["unit"]) == ("jitted steps", "tpot_p50_ms", "%")


def test_the_proposed_copy_is_the_same_reader():
    """`scripts/perfbench_proposed/` still holds the original (a `benchmark` PR
    may not delete it: PERF.md section 7); the two must not drift."""
    theirs = os.path.join(ROOT, "scripts", "perfbench_proposed")
    if not os.path.isdir(theirs):
        pytest.skip("the proposed copy is gone, as PERF.md section 7 asks")
    for rel in ("readers/trace_module_share.py", "layer_metrics/steps.prefill_device_share.json"):
        with open(os.path.join(theirs, rel)) as a, open(os.path.join(HERE, rel)) as b:
            assert a.read() == b.read()


def _stream(first, gaps, ok=True, measured=True):
    times = [first]
    for g in gaps:
        times.append(times[-1] + g)
    return {"ok": ok, "measured": measured, "chunks": times}


@pytest.mark.parametrize("between, want", [
    (0.0, 0.0),        # nothing ever stands between two bursts: no share
    (0.030, 25.0),     # a bare burst of 90 ms in a usual gap of 120
    (0.090, 50.0),
])
def test_prefill_between_bursts_share_reads_the_whole_window_from_the_log(between, want):
    reader, spec = _metric("steps.prefill_between_bursts_share")
    params = spec["params"]
    # 20 rows, 30 gaps each: one gap in three is a bare burst of 90 ms, two carry `between` x 1.5
    gaps = [0.090, 0.090 + 1.5 * between, 0.090 + 1.5 * between] * 10
    ctx = {"requests": [_stream(5.0 + 0.01 * i, gaps) for i in range(20)]}
    assert reader.read(ctx, params) == pytest.approx(want, abs=1e-6)
    # a stall that one request in twenty saw moves neither the bare burst nor the usual gap
    stalled = ctx["requests"][:19] + [_stream(5.0, gaps[:-1] + [9.0])]
    assert reader.read({"requests": stalled}, params) == pytest.approx(want, abs=1e-6)
    # requests that failed or were not due in the window are not read
    more = ctx["requests"] + [_stream(1.0, [5.0] * 30, ok=False), _stream(1.0, [5.0] * 30, measured=False)]
    assert reader.read({"requests": more}, params) == pytest.approx(want, abs=1e-6)


def test_prefill_between_bursts_share_returns_nothing_where_there_is_too_little_to_read():
    reader, spec = _metric("steps.prefill_between_bursts_share")
    params = spec["params"]
    assert reader.read({"requests": []}, params) is None
    assert reader.read({"requests": [_stream(1.0, [0.1] * 50)]}, params) is None  # 50 gaps < min_gaps
    assert reader.read({"requests": [_stream(1.0, [0.1])] * 500}, params) is None  # no request has 3 chunks
    assert (spec["layer"], spec["moves"], spec["unit"], spec["source"], spec["better"]) == (
        "jitted steps", "tpot_p50_ms", "%", "host_clock", "lower")


def test_prefill_between_bursts_share_on_a_log_of_the_chip_reads_what_the_bursts_say():
    """Numbers of a real window's shape: bursts of 92 ms, of which a third are
    followed by one prefill chunk of 25 ms, a sixth by two rows' of 48 and a
    twelfth by four rows' of 91 (the program durations of PERF.md section 5)."""
    import random

    reader, spec = _metric("steps.prefill_between_bursts_share")
    rng = random.Random(53)
    def gap():
        extra = rng.choices([0.0, 0.025, 0.048, 0.091], [5, 4, 2, 1])[0]
        return 0.092 + extra + rng.uniform(-0.001, 0.001)
    ctx = {"requests": [_stream(rng.uniform(0, 30), [gap() for _ in range(rng.randint(8, 32))])
                        for _ in range(170)]}
    # the mean extra is (4 x 25 + 2 x 48 + 91) / 12 = 23.9 ms on 92: 20.6% of the usual gap
    assert reader.read(ctx, spec["params"]) == pytest.approx(20.6, abs=1.5)


# -- the manifest -----------------------------------------------------------------

def test_benchmark_json_holds_what_the_files_say_accepted_entries_first():
    """The ORDER rule, not "mine come last": every list starts with what the
    parent (09f0fa1) had, in that order and as it was, and this PR's names
    follow them, whoever appends after."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        current = json.load(f)
    assert manifest.build(current) == current and manifest.main([]) == 0
    assert current["command"] == ["python3", "perfbench/run.py"] and current["paths"] == ["perfbench"]
    assert current["run_seconds"] == 51
    assert [(e["name"], e["bound"]) for e in current["end_to_end"]] == [("setup_s", 0.1), ("tpot_p50_ms", 0.1)]
    cells = [w["name"] for w in current["workloads"]]
    assert cells[:5] == ACCEPTED and cells.index("mistral-7b-d16.rag") == 5
    assert [c["name"] for c in current["configs"]][:5] == [
        "mistral-7b-d16", "qwen2.5-7b-d14", "jamba2-3b", "lfm2-8b-a1b-d16", "nemotron3-nano-30b-ep8"]
    names = [m["name"] for m in current["per_layer"]]
    assert names[0] == "client.ttft_p50_ms" and names[21] == "kernel.ssd_share_of_busy"
    assert names[22:24] == ["steps.prefill_device_share", "steps.prefill_between_bursts_share"]
    by_name = {m["name"]: m for m in current["per_layer"]}
    assert "steps.step_mfu" not in by_name  # built in this PR's first session and taken out (PERF.md section 7)
    for name in ("steps.prefill_device_share", "steps.prefill_between_bursts_share"):
        assert by_name[name]["workloads"][0] == "mistral-7b-d16.rag"
        assert by_name[name]["moves"] == "tpot_p50_ms" and by_name[name]["layer"] == "jitted steps"
    # the accepted metrics the cell names gain it BEHIND the cells they had
    for name in ("kernel.decode_attn_roofline", "steps.decode_dispatch_device_ms_p50"):
        assert by_name[name]["workloads"][:3] == [
            "mistral-7b-d16.chat", "qwen2.5-7b-d14.sessions", "mistral-7b-d16.rag"]
    assert by_name["kv.prefix_hit_share"]["workloads"][:2] == ["qwen2.5-7b-d14.sessions", "mistral-7b-d16.rag"]
    assert sum(w["chips"] == 4 for w in current["workloads"]) == 0
    rag = current["workloads"][5]
    assert rag["config"] == "mistral-7b-d16" and rag["traffic"] == "rag" and len(rag["why"]) <= 200


def test_the_cell_is_the_chat_cell_of_its_configuration_in_a_closed_loop():
    chat = manifest.load_json("cells", "mistral-7b-d16.chat.json")
    assert CELL["config"] == chat["config"] and CELL["engine_args"] == chat["engine_args"] == []
    assert CELL["end_to_end"] == chat["end_to_end"] == ["tpot_p50_ms", "setup_s"]
    assert CELL["per_layer"] == chat["per_layer"] + [
        "steps.prefill_device_share", "steps.prefill_between_bursts_share", "kv.prefix_hit_share"]
    # the reference follows the cell's LONGEST prompt (four prefill chunks), under the chat cell's limit
    ref, theirs = CELL["correctness"]["reference"], chat["correctness"]["reference"]
    assert ref["prompt_tokens"] == STREAM["prompt_tokens"][1] == 4 * STREAM["quantum"]
    assert (ref["output_tokens"], ref["tolerance"]) == (theirs["output_tokens"], theirs["tolerance"]) == (12, 0.08)
    assert "cached_vs_cold" not in CELL["correctness"]
    # the bit-for-bit repeat stays under one page: a longer prompt's second sending comes from cached pages
    assert CELL["correctness"]["greedy_prompt_tokens"] == chat["correctness"]["greedy_prompt_tokens"] == 48 < 64
    assert CELL["traffic"]["generator"] == "closed" and STREAM["kind"] == "closed"
    # inside the chat cell's shapes: the same chunk, no longer a prompt, no longer an answer
    theirs = chat["traffic"]["params"]["streams"][0]
    assert STREAM["quantum"] == theirs["quantum"] == 512
    assert STREAM["prompt_tokens"][1] == theirs["prompt_tokens"][1] == 2048
    assert STREAM["output_tokens"][1] == theirs["output_tokens"][1] == 256
    # 16 callers at their longest fit the default pool of 61k tokens: no preemption
    assert STREAM["users"] * (2048 + 256) < 61_000


def test_the_generator_loads_beside_a_copy_of_the_tree_without_git(tmp_path):
    """As the driver's checkout: only the files, at another path, no JAX."""
    tree = tmp_path / "perfbench"
    shutil.copytree(HERE, tree, ignore=shutil.ignore_patterns(".jax_cache", ".out", "__pycache__"))
    code = ("import sys, json; sys.path.insert(0, sys.argv[1]); import manifest;"
            "g = manifest.load_module('traffic', 'closed', sys.argv[1]);"
            "c = manifest.load_json('cells', 'mistral-7b-d16.rag.json', base=sys.argv[1]);"
            "d = manifest.load_json('configs', 'mistral-7b-d16.json', base=sys.argv[1]);"
            "p = g.generate(c['traffic']['params'], 3450002201, 51, d['perfbench']['tokenizer']);"
            "assert 'jax' not in sys.modules; print(len(p['clients']), g.mix.__file__)")
    out = subprocess.run(["python3", "-c", code, str(tree)], capture_output=True, text=True, cwd=tmp_path)
    assert out.returncode == 0, out.stderr
    assert out.stdout.split() == ["16", str(tree / "traffic" / "mix.py")]


# -- the whole command, at a toy size on the CPU ----------------------------------

@pytest.mark.parametrize("trace", [0, 1])
def test_the_cell_s_command_rehearsed_on_the_cpu(tmp_path, trace):
    tree, cell = _toy_tree(tmp_path)
    args = argparse.Namespace(workload="tiny-llama.rag", seed=BIG, seconds=4.0, trace=trace,
                              out=str(tmp_path / "out"))
    res = asyncio.run(run.run_cell(args, args.workload, str(tree), allow_platform="cpu"))
    assert res["correct"] and res["failed"] == 0 and res["attempted"] > 8
    names = {k[len("cpu_rehearsal."):] for k in res["metrics"]}
    log = json.load(open(tmp_path / "out" / "requests.json"))["requests"]
    # nothing shared: not one prompt token came from cached pages
    assert all(r["cached_tokens"] == 0 for r in log if r["stream"] == "rag")
    # a closed loop: no caller has two requests in flight
    assert sum(r["measured"] for r in log) == res["attempted"]
    if not trace:
        assert names == set(cell["end_to_end"])
        return
    # no device plane on the CPU: the trace readers return nothing and are left out; so does
    # `steps.prefill_between_bursts_share`, which finds under 100 gaps in the toy's 8-24 token answers
    assert names == {"client.ttft_p50_ms", "client.ttft_p95_ms", "sched.loop_host_share",
                     "sched.preemptions", "kv.evicted_pages", "steps.compiles_in_window",
                     "bench.windows_voided", "kv.prefix_hit_share"}


# -- `correct` has been seen to fail ------------------------------------------------

BROKEN_ENGINE = '''\
"""The engine child with its sampler broken: every token it emits is the one
after the token it sampled (the log-probabilities it reports are the sound
step's). Then the real `engine_main.py`, unchanged."""
import os, runpy, sys
REAL, ROOT = {real!r}, {root!r}
sys.path[:0] = [ROOT, REAL]
from production_stack_tpu.engine import runner
_sample, _with_lp = runner.sample, runner.sample_with_logprobs
runner.sample = lambda logits, *a, **k: (_sample(logits, *a, **k) + 1) % logits.shape[-1]
def altered(logits, *a, **k):
    ids, *rest = _with_lp(logits, *a, **k)
    return ((ids + 1) % logits.shape[-1], *rest)
runner.sample_with_logprobs = altered
sys.argv[0] = os.path.join(REAL, "engine_main.py")
runpy.run_path(sys.argv[0], run_name="__main__")
'''


def _toy_tree(tmp_path):
    tree = tmp_path / "perfbench"
    shutil.copytree(HERE, tree, ignore=shutil.ignore_patterns(".jax_cache", ".out", "__pycache__"))
    shutil.copy(os.path.join(TOY, "configs", "tiny-llama.json"), tree / "configs" / "tiny-llama.json")
    cell = json.loads(json.dumps(CELL))
    cell["config"] = "tiny-llama"
    cell["engine_args"] = ["--max-model-len", "2048"]
    cell["traffic"]["params"]["streams"][0].update(
        users=6, think_s=0.4, prompt_tokens=[128, 256], quantum=64, output_tokens=[8, 24],
        warm_seconds=2, ramp={"requests": 6, "first_tokens": 8, "step_tokens": 2})
    cell["correctness"]["reference"].update(prompt_tokens=320, output_tokens=6)
    cell["correctness"]["greedy_prompt_tokens"] = 48
    # the accepted `trace_roofline` divides by the traced extent, which is 0 where
    # the CPU's trace has no device plane: the toy does not name its metric
    cell["per_layer"].remove("kernel.decode_attn_roofline")
    (tree / "cells" / "tiny-llama.rag.json").write_text(json.dumps(cell))
    return tree, cell


def test_a_token_altered_where_it_is_produced_reads_not_correct(tmp_path, monkeypatch, capfd):
    """The rest of a run driven over a broken timed path (the harness's look
    for a chip skipped, as in every rehearsal): the window's requests all
    complete, and the reference check says the served tokens are not the
    model's."""
    tree, _ = _toy_tree(tmp_path)
    (tree / "engine_main.py").write_text(BROKEN_ENGINE.format(real=HERE, root=ROOT))
    monkeypatch.setattr(run, "HERE", str(tree))  # the children are started from the copy
    args = argparse.Namespace(workload="tiny-llama.rag", seed=7, seconds=3.0, trace=0,
                              out=str(tmp_path / "out"))
    res = asyncio.run(run.run_cell(args, args.workload, str(tree), allow_platform="cpu"))
    err = capfd.readouterr().err
    assert res["failed"] == 0 and res["attempted"] > 5  # the traffic is served, wrongly
    assert res["correct"] is False
    assert "check reference: FAILED" in err


def test_the_reference_in_float8_put_in_the_program_s_place_reads_not_correct(tmp_path, capsys):
    """The control (`tools/llama_lowprec_control.py`; on the chip it runs at the
    cell's own size) at a size a test can hold: the float32 reference on
    weights cast to float8_e4m3fn fails the comparison under the cell's own
    tolerance on every seed."""
    import importlib.util

    tree, _ = _toy_tree(tmp_path)
    spec = importlib.util.spec_from_file_location(
        "llama_lowprec_control", os.path.join(HERE, "tools", "llama_lowprec_control.py"))
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    out = tmp_path / "control.json"
    tool.main(["--base", str(tree), str(out), "tiny-llama.rag", "1", "2", "3"])
    rows = json.load(open(out))
    assert rows["tolerance"] == CELL["correctness"]["reference"]["tolerance"] == 0.08
    for row in rows["rows"]:
        assert row["fp8"]["correct"] is False and row["fp8"]["reading"] > 2 * 0.08
