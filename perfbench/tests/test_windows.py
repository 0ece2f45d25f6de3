"""The pure pieces of a run that measures a void window again: the seed of
window k, the budget rule, what a stalled beat was, the list order the manifest
keeps. (ISSUE 45 asked for these under tests/, which tier-1 collects; a
benchmark PR adds no file outside perfbench/, so they stand here.)"""

import json
import os

import pytest

import manifest
import run


def sample(cpu, waited, **more):
    return dict({"cpu_s": cpu, "waited_s": waited}, **more)


@pytest.mark.parametrize("span, before, after, kind", [
    (1.4, sample(10.0, 2.0), sample(11.35, 2.0), "busy"),        # its own CPU time went with the wall
    (1.4, sample(10.0, 2.0), sample(10.8, 2.1), "busy"),         # half of it: still at work
    (1.4, sample(10.0, 2.0), sample(10.01, 3.3), "starved"),     # it stood on a run queue
    (1.4, sample(10.0, 2.0), sample(10.01, 2.75), "starved"),
    (1.55, sample(10.0, 2.0), sample(10.0, 2.001), "stopped"),   # SIGSTOP, or the whole host stood still
    (0.12, sample(1.0, 0.5), sample(1.001, 0.5), "stopped"),
    (1.4, sample(10.0, None), sample(10.01, None), "starved_or_stopped"),  # no /proc/self/schedstat
    (1.4, sample(10.0, None), sample(11.3, None), "busy"),
])
def test_what_a_stalled_beat_was(span, before, after, kind):
    cause = run.stall_cause(span, before, after)
    assert cause["kind"] == kind
    assert cause["cpu_s"] == pytest.approx(after["cpu_s"] - before["cpu_s"])
    line = run.stall_table([(5.0, span - 0.05, cause)], 4.0, [])
    assert "late" in line and kind in line and ("too few streams to tell" in line) == (span > 0.6)


@pytest.mark.parametrize("queued, says", [(True, "the engine went on"), (False, "the engine stood too")])
def test_what_the_streams_say_of_a_stall(queued, says):
    # 20 streams, a chunk each 12.5 ms, read as they come; the generator stands still 5.0 .. 6.5 s
    log = [{"chunks": [k * 0.0125 for k in range(240, 400)]} for _ in range(20)]
    for r in log:
        # what lay in the sockets is read the moment it wakes; or nothing came meanwhile
        r["chunks"] += [6.5 + k * 1e-5 for k in range(120 if queued else 1)] + [
            6.5125 + k * 0.0125 for k in range(40)]
    assert says in run.streams_beside(log, 5.0, 1.45)


def test_the_host_sample_reads_this_host():
    s = run.host_sample()
    assert s["cpu_s"] > 0 and set(s) == {"cpu_s", "waited_s"}
    assert run.stall_cause(0.1, s, run.host_sample())["kind"] in (
        "busy", "starved", "stopped", "starved_or_stopped")


@pytest.mark.parametrize("seed", [0, 7, 2**31 + 11, 3450000101])
def test_the_seed_of_window_k_is_made_of_the_seed_and_k_alone(seed):
    assert run.window_seed(seed, 0) == seed  # a window that holds is the run the driver asked for
    ks = [run.window_seed(seed, k) for k in range(run.MAX_WINDOWS)]
    assert len(set(ks)) == len(ks) and ks == [run.window_seed(seed, k) for k in range(run.MAX_WINDOWS)]
    assert all(0 <= k < 2**32 for k in ks[1:])
    assert run.window_seed(seed, 1) != run.window_seed(seed + 1, 1)


@pytest.mark.parametrize("elapsed, window, room", [
    (95.0, 61.0, True),      # a warm chat run voided mid-window: 95 + 61 + 40 = 196
    (229.0, 61.0, True),     # the last second a cold run keeps room: 229 + 61 + 40 = 330
    (229.1, 61.0, False),
    (200.0, 96.4, False),    # sessions pays its histories and 40 s of the loop again
    (120.0, 96.4, True),
    (331.0, 0.0, False),
])
def test_the_budget_rule(elapsed, window, room):
    assert run.RUN_LIMIT_S - run.RUN_MARGIN_S == 330.0 and run.TAIL_S == 40.0
    assert run.room_for_a_window(elapsed, window) is room


def test_a_marked_phase_is_the_sessions_histories_alone():
    cells = {n: manifest.load_json("cells", n + ".json") for n in manifest.names("cells")}
    mix = manifest.load_module("traffic", "mix")
    for name, cell in cells.items():
        doc = manifest.load_json("configs", cell["config"] + ".json")
        plan = mix.generate(cell["traffic"]["params"], 5, 2.0, doc["perfbench"]["tokenizer"])
        marked = [p["name"] for p in plan["setup"] if p.get("every_window")]
        assert marked == (["qa.histories"] if cell["traffic"]["name"] == "sessions" else []), name
        # a further window's plan is the same work under other text
        again = mix.generate(cell["traffic"]["params"], run.window_seed(5, 1), 2.0,
                             doc["perfbench"]["tokenizer"])
        def work(p):  # the sizes drawn, whatever the order and the text
            return [sorted(r[k] for r in p["open"] + p["setup"][0]["requests"])
                    for k in ("prompt_tokens", "max_tokens")]

        assert work(again) == work(plan)
        assert json.dumps([again["open"], again["clients"]]) != json.dumps([plan["open"], plan["clients"]])


@pytest.mark.parametrize("found, accepted, order", [
    (["a", "b", "c"], ["c", "a"], ["c", "a", "b"]),       # accepted order first, new names appended
    (["a", "b", "c"], [], ["a", "b", "c"]),
    (["b", "c"], ["c", "a", "b"], ["c", "b"]),            # a name whose file went is dropped
    (["k.z", "k.a", "j"], ["j"], ["j", "k.z", "k.a"]),    # new names in the order found (sorted by `names`)
])
def test_the_manifest_keeps_the_accepted_order_and_appends(found, accepted, order):
    assert manifest.accepted_first(found, [{"name": n} for n in accepted]) == order


def test_manifest_check_is_green():
    assert manifest.main([]) == 0
    with open(os.path.join(os.path.dirname(manifest.HERE), "BENCHMARK.json")) as f:
        names = [m["name"] for m in json.load(f)["per_layer"]]
    assert names[-1] == "bench.windows_voided" and names[0] == "client.ttft_p50_ms"


class _Serving:
    """What `measure_window` needs of a `Serving`, with no child behind it."""
    allow_platform, session, ebase, cbase, load_s = "tpu", None, "", "", 0.0

    def __init__(self):
        self.load = run.Load(None, "", "m")

    async def snapshot(self):
        return {"t": run.time.monotonic(), "stats": {}, "metrics": ""}


@pytest.mark.parametrize("stop_s, empty, room, want", [
    (0.0, [True, False], True, "again_in_the_window"),    # an empty device plane, t1 leaves the time
    (0.0, [False], True, "kept"),                         # a sound trace is taken once
    (1.0, [True, False], True, "void"),                   # no time before t1: a further window takes it
    (1.0, [True, False], False, "kept_bad"),              # nor room for a window: as before this PR
    (0.0, [True, True], True, "again_and_still_bad"),     # once more, not twice
])
def test_a_bad_trace_is_taken_once_more(tmp_path, monkeypatch, stop_s, empty, room, want):
    import asyncio

    async def post_json(session, url, body, timeout=0):
        if url.endswith("/profile/stop"):
            await asyncio.sleep(stop_s)
        return {}

    left = list(empty)

    def reduce_trace(sub, out):
        if left.pop(0):
            raise run.BenchFailure("tracereduce failed: no device plane with events in x")
        return {"busy_s": 1.0}

    monkeypatch.setattr(run, "post_json", post_json)
    monkeypatch.setattr(run, "reduce_trace", reduce_trace)
    monkeypatch.setattr(run, "TRACE_SECONDS", 0.2)
    plan = {"clients": [], "open": [], "warm_seconds": 0.0}
    taken = []
    w = asyncio.run(run.measure_window(_Serving(), plan, 3.0, True, str(tmp_path), taken, lambda: room))
    if want == "again_in_the_window":
        assert w["void"] is None and len(taken) == 2 and taken[0]["bad"] and w["sub"] is taken[1]
        assert w["sub"]["trace"] == {"busy_s": 1.0} and w["sub"]["dir"].endswith("trace.1")
    elif want == "kept":
        assert w["void"] is None and len(taken) == 1 and w["sub"]["bad"] is None
    elif want == "void":
        assert "no device plane" in w["void"] and "to take it again" in w["void"] and w["snap1"] is None
        # the further window's trace is the one taken again: a second bad one is kept
        left[:] = [True]
        w2 = asyncio.run(run.measure_window(_Serving(), plan, 3.0, True, str(tmp_path), taken, lambda: room))
        assert w2["void"] is None and len(taken) == 2 and w2["sub"]["bad"]
    else:
        assert w["void"] is None and w["sub"]["bad"] and w["sub"]["trace"] is None
        assert len(taken) == (2 if want == "again_and_still_bad" else 1)
