"""One dispatch queued on the device behind the one that runs (engine._turn,
scheduler.schedule(ahead_of=)): the queued loop serves what a drained loop
serves, request for request, and gives back everything it was lent.

One module over the tiny presets of the three model files. The drained loop is
the SAME engine with every batch sent down its synchronous path (no option
does that: the test replaces ``_synchronous``), so both loops share their
compiled step programs. Presets are float32 copies: which rows share a batch
differs between the two loops, and bfloat16 lets that reorder a near-tie."""

import asyncio
import dataclasses
import time

import numpy as np
import pytest

from production_stack_tpu import tracing
from production_stack_tpu.engine import devicemon
from production_stack_tpu.engine.config import EngineConfig
from production_stack_tpu.engine.engine import LLMEngine
from production_stack_tpu.engine.kv_manager import KVPageManager
from production_stack_tpu.engine.scheduler import SamplingParams, Scheduler, Sequence
from production_stack_tpu.models import jamba, lfm2, llama

FAMILIES = {"llama": (llama, "llama-debug"), "jamba": (jamba, "jamba-debug"),
            "lfm2": (lfm2, "lfm2-debug")}
PAGES = 96


@pytest.fixture(scope="module", params=sorted(FAMILIES))
def engine(request):
    module, preset = FAMILIES[request.param]
    name = preset + "-queue-f32"
    module.PRESETS[name] = dataclasses.replace(module.PRESETS[preset], dtype="float32")
    eng = LLMEngine(EngineConfig(
        model=name, max_model_len=256, max_num_seqs=4, num_pages=PAGES, page_size=8,
        prefill_chunk=32, kv_cache_memory_gb=0.01, enable_prefix_caching=False))
    eng.start()
    yield eng
    eng.stop()
    del module.PRESETS[name]


@pytest.fixture
def drained(engine, monkeypatch):
    """Sends every batch of ``engine`` down the synchronous path while set."""
    def switch(on: bool):
        if on:
            monkeypatch.setattr(engine, "_synchronous", lambda batch: "host_staged_rows",
                                raising=False)
        else:
            monkeypatch.undo()
    return switch


def _params(n, **kw):
    kw.setdefault("ignore_eos", True)
    return SamplingParams(max_tokens=n, temperature=0.0, **kw)


async def _one(eng, name, prompt, params, delay=0.0):
    await asyncio.sleep(delay)
    toks, text, last = [], "", None
    async for out in eng.generate(f"{name}-{np.random.randint(1 << 30)}", prompt=prompt,
                                  params=params):
        toks += out.token_ids
        text += out.text_delta
        last = out
    return {"tokens": toks, "text": text, "reason": last.finish_reason,
            "usage": last.completion_tokens}


# arrivals mid-flight (every 30 ms, while earlier rows decode), a prompt of
# three chunks (its last chunk ends inside a running dispatch), a row that
# ends by length after one burst and one that ends inside its prefill dispatch
TRAFFIC = [
    ("short", "a b c", 20, 0.0),
    ("chunked", "the prompt of three chunks, " * 3, 26, 0.03),
    ("one-burst", "ends after one burst", 8, 0.06),
    ("one-token", "ends in its prefill", 1, 0.09),
    ("late", "arrives while the others decode", 33, 0.12),
    ("later", "and one more", 17, 0.20),
]


def _serve(eng, traffic=TRAFFIC, **kw):
    async def run():
        return await asyncio.gather(*[
            _one(eng, name, prompt, _params(n, **kw), delay)
            for name, prompt, n, delay in traffic])
    return asyncio.run(run())


def _idle(eng):
    """Wait until the loop has nothing in flight and nothing to do."""
    deadline = time.time() + 10
    while time.time() < deadline and (eng._inflight is not None or eng.scheduler.has_work()):
        time.sleep(0.01)
    assert eng._inflight is None and not eng.scheduler.has_work()


def _everything_back(eng):
    _idle(eng)
    assert eng.kv.usage() == 0.0 and eng.kv.num_free() == PAGES
    if eng.kv.state_slots:
        assert eng.kv.slots_in_use() == 0
    assert eng.stats()["engine_step_errors_total"] == 0


def _queued(eng):
    return sum(eng.stats()["queued_ahead_dispatches_total"].values())


def _planned_behind(eng, since_step):
    """Kinds of the dispatches since ``since_step`` that were planned and
    enqueued behind a running one (on the CPU a toy's burst may have ended by
    then: ``late``, and the seam is the same)."""
    return {e["data"]["batch_kind"] for e in tracing.get_flightrecorder().events(kind="sched")
            if e["step"] > since_step
            and (e["data"].get("queued_ahead") or e["data"].get("drain") == "late")}


def test_the_queued_loop_serves_what_the_drained_loop_serves(engine, drained):
    drained(True)
    want = _serve(engine)
    assert _queued(engine) == 0
    drained(False)
    q0, step0 = _queued(engine), engine.step_idx
    got = _serve(engine)
    assert got == want
    assert [r["usage"] for r in got] == [n for _, _, n, _ in TRAFFIC]
    assert {r["reason"] for r in got} == {"length"}
    # decode behind decode / behind prefill, and prefill behind decode
    assert _planned_behind(engine, step0) == {"decode", "prefill"}
    assert _queued(engine) > q0
    _everything_back(engine)


def test_a_hold_that_is_too_long_costs_idle_time_and_no_tokens(engine, drained, monkeypatch):
    drained(True)
    want = _serve(engine)
    drained(False)

    class EightyMs(dict):  # every shape "took the device 80 ms the last time"
        def get(self, key, default=None):
            return 0.08
    monkeypatch.setattr(engine, "_device_secs", EightyMs())
    monkeypatch.setattr(engine, "_turn_secs", 0.001)  # no compile of a first pass in it
    s0 = engine.stats()
    got = _serve(engine)
    s1 = engine.stats()
    assert got == want
    # the loop held each dispatch back to a margin before that end, reading its
    # inbox meanwhile; the toy's dispatch had ended long before: `late`
    held = s1["engine_dispatch_hold_seconds_total"] - s0["engine_dispatch_hold_seconds_total"]
    assert held > 0.1
    assert s1["queue_ahead_drains_total"]["late"] > s0["queue_ahead_drains_total"]["late"]
    _everything_back(engine)


def test_a_device_fed_input_compiles_nothing(engine):
    for _ in range(8):  # until a pass meets no shape for the first time
        _idle(engine)
        events, q0 = devicemon.compile_totals()[1], _queued(engine)
        firsts, seams = engine.stats()["first_dispatches_total"], len(engine._seams_built)
        _serve(engine)
        if (engine.stats()["first_dispatches_total"], len(engine._seams_built)) == (firsts, seams):
            break
    assert engine.stats()["first_dispatches_total"] == firsts
    assert _queued(engine) > q0
    assert devicemon.compile_totals()[1] == events
    _everything_back(engine)


def test_a_row_that_ends_by_eos_while_it_rides_in_the_queued_dispatch(engine, drained,
                                                                      monkeypatch):
    (ref,) = _serve(engine, [("ref", "which token ends it", 40, 0.0)])
    # a token of the third burst that no earlier position holds: with it as
    # EOS the row ends there, and the burst queued behind that one names it
    pos = next(p for p in range(17, 24) if ref["tokens"][p] not in ref["tokens"][:p])
    monkeypatch.setattr(engine.tokenizer, "eos_token_id", ref["tokens"][pos], raising=False)
    traffic = [("eos", "which token ends it", 40, 0.0), ("other", "beside it", 40, 0.0)]
    drained(True)
    want = _serve(engine, traffic, ignore_eos=False)
    drained(False)
    monkeypatch.setattr(engine.tokenizer, "eos_token_id", ref["tokens"][pos], raising=False)
    q0 = engine.stats()["queued_ahead_dispatches_total"]["decode"]
    got = _serve(engine, traffic, ignore_eos=False)
    assert got == want
    # its surplus is neither streamed nor counted
    assert got[0]["reason"] == "stop" and got[0]["usage"] == pos + 1
    assert got[0]["tokens"] == ref["tokens"][: pos + 1]
    assert engine.stats()["queued_ahead_dispatches_total"]["decode"] > q0
    _everything_back(engine)


def test_a_row_that_ends_by_a_stop_string_while_it_rides_in_the_queued_dispatch(engine, drained):
    (ref,) = _serve(engine, [("ref", "where the text stops", 40, 0.0)])
    # the text a token past the first burst adds, where nothing before it reads
    # the same: as a stop string it ends the row while its next burst is queued
    decode = engine.tokenizer.decode
    stop = next(
        piece for m in range(10, 40)
        for before in [decode(ref["tokens"][: m - 1])]
        for piece in [decode(ref["tokens"][:m])[len(before):]]
        if piece and "\ufffd" not in piece and piece not in before)
    traffic = [("stop", "where the text stops", 40, 0.0), ("other", "beside it", 40, 0.0)]
    drained(True)
    want = _serve(engine, traffic, stop=[stop])
    drained(False)
    got = _serve(engine, traffic, stop=[stop])
    assert got == want
    assert got[0]["reason"] == "stop" and stop not in got[0]["text"]
    assert got[0]["usage"] == len(got[0]["tokens"]) < 40 and got[1]["usage"] == 40
    _everything_back(engine)


def test_an_abort_during_the_queued_dispatch(engine):
    (ref,) = _serve(engine, [("ref", "the one that stays", 48, 0.0)])

    async def run():
        async def aborted():
            n = 0
            async for out in engine.generate("abort-me", prompt="the one that goes",
                                             params=_params(200)):
                n += len(out.token_ids)
                if n >= 9 and not out.finished:
                    engine.abort("abort-me")  # its next burst is queued already
                if out.finished:
                    return out.finish_reason, n
        return await asyncio.gather(
            aborted(), _one(engine, "stays", "the one that stays", _params(48)))
    (reason, n), stays = asyncio.run(run())
    assert reason == "abort" and n < 200
    assert stays == ref
    _everything_back(engine)


@pytest.mark.parametrize("staged", ["logprobs", "penalty"])
def test_a_row_the_host_stages_takes_the_synchronous_path(engine, staged):
    (plain,) = _serve(engine, [("plain", "staged from the host", 20, 0.0)])
    _idle(engine)
    s0 = engine.stats()
    kw = {"logprobs": 1} if staged == "logprobs" else {"repetition_penalty": 1.0001}
    (got,) = _serve(engine, [("staged", "staged from the host", 20, 0.0)], **kw)
    s1 = engine.stats()
    assert s1["queued_ahead_dispatches_total"] == s0["queued_ahead_dispatches_total"]
    assert (s1["queue_ahead_drains_total"]["host_staged_rows"]
            > s0["queue_ahead_drains_total"]["host_staged_rows"])
    if staged == "logprobs":
        assert got == plain
    else:
        assert got["usage"] == 20 and got["reason"] == "length"
    _everything_back(engine)


def test_each_reason_the_loop_ran_dry_is_counted(engine, monkeypatch):
    _serve(engine)
    _idle(engine)
    d0 = dict(engine.stats()["queue_ahead_drains_total"])

    def risen(reason):
        return engine.stats()["queue_ahead_drains_total"][reason] - d0[reason]

    # idle: the first dispatch after the loop waited for work
    _serve(engine, [("again", "a b c", 4, 0.0)])
    assert risen("idle") >= 1
    # first_dispatch: a shape nobody has dispatched (a fourth prefill width)
    firsts = engine.stats()["first_dispatches_total"]
    _serve(engine, [("long", "a prompt of a length no other test has, " * 5, 4, 0.0),
                    ("beside", "a b c", 30, 0.0)])
    assert engine.stats()["first_dispatches_total"] > firsts and risen("first_dispatch") >= 1
    # device_cmd: the loop drains before a device command runs, then runs it
    async def with_cmds():
        task = asyncio.ensure_future(_one(engine, "cmd", "while commands wait", _params(200)))
        seen = []
        while not task.done():  # one command after the other while it decodes
            seen.append(await asyncio.get_running_loop().run_in_executor(
                None, lambda: engine._run_on_device_thread(lambda: engine._inflight)))
        return seen, await task
    seen, out = asyncio.run(with_cmds())
    assert seen and set(seen) == {None} and out["usage"] == 200 and risen("device_cmd") >= 1
    # late: a host slower than the device finds it idle at the next enqueue
    plan = engine._plan

    def slow_plan():
        time.sleep(0.05)
        return plan()
    monkeypatch.setattr(engine, "_plan", slow_plan)
    _serve(engine, [("slow", "a slow host", 24, 0.0)])
    monkeypatch.undo()
    assert risen("late") >= 1
    # no_pages: the pool has no page for the queued burst without preempting;
    # the drained loop then preempts as it always did
    held = engine.kv.allocate(engine.kv.num_free() - 12)
    try:
        for _ in range(3):  # the first pass meets the shapes of a pool this small
            got = _serve(engine, [("grow-" + c, c, 60, 0.0) for c in "pqr"])
            assert [r["usage"] for r in got] == [60, 60, 60]
            if risen("no_pages"):
                break
    finally:
        engine.kv.free(held)
    assert risen("no_pages") >= 1
    _everything_back(engine)


def test_a_failing_dispatch_with_one_queued_behind_it_aborts_the_rows_of_both(engine, monkeypatch):
    step = engine.runner.step

    def refuse_behind_a_running_one(inp, *a, **kw):
        if engine._inflight is not None:
            raise RuntimeError("the device lost the queued dispatch")
        return step(inp, *a, **kw)

    async def run():
        n, second, last = 0, None, None
        async for out in engine.generate("runs", prompt="decodes when it fails",
                                         params=_params(200)):
            n, last = n + len(out.token_ids), out
            if n >= 9 and second is None:  # it decodes: the next one's prefill queues behind it
                monkeypatch.setattr(engine.runner, "step", refuse_behind_a_running_one)
                second = asyncio.ensure_future(
                    _one(engine, "queued", "its prefill is queued behind", _params(8)))
        return {"reason": last.finish_reason, "usage": n}, await second
    errors = engine.stats()["engine_step_errors_total"]
    first, second = asyncio.run(run())
    monkeypatch.undo()
    assert first["reason"] == second["reason"] == "error" and first["usage"] < 200
    assert engine.stats()["engine_step_errors_total"] == errors + 1
    _idle(engine)
    assert engine.kv.usage() == 0.0 and engine.kv.num_free() == PAGES
    if engine.kv.state_slots:
        assert engine.kv.slots_in_use() == 0
    engine.step_errors_total = errors  # the next test's "no step failed" starts from here
    (after,) = _serve(engine, [("after", "a b c", 6, 0.0)])
    assert after["reason"] == "length" and after["usage"] == 6


# -- the scheduler's plan behind a running dispatch, on its own ----------------

def _sched(pages=32, **kw):
    kv = KVPageManager(pages, 8)
    kw.setdefault("enable_prefix_caching", False)
    return Scheduler(kv, max_num_seqs=4, max_model_len=128, prefill_chunk=16,
                     decode_steps=4, **kw), kv


def _seq(name, prompt_len, max_tokens, **kw):
    return Sequence(name, list(range(1, prompt_len + 1)),
                    SamplingParams(max_tokens=max_tokens, ignore_eos=True, **kw))


def _allow(s):
    return s.params.logprobs is None


def test_a_plan_behind_a_prefill_feeds_the_rows_it_completes_and_leaves_the_state_as_it_was():
    sched, kv = _sched()
    a, b = _seq("a", 10, 9), _seq("b", 32, 9)
    sched.add(a)
    sched.add(b)
    first = sched.schedule()
    assert first.kind == "prefill" and first.chunk_sizes == [10, 16]
    behind = sched.schedule(ahead_of=first, allow=_allow)
    # b's next chunk: a prefill needs no token at all
    assert behind.kind == "prefill" and behind.seqs == [b] and behind.fed_from is None
    assert list(behind.positions[0, :3]) == [16, 17, 18]
    assert (a.num_computed, a.output_ids, b.num_computed) == (0, [], 0)
    sched.apply_step(first, np.array([7, 0]), eos_token_id=0)
    # behind THAT one, the first decode of both: a's input is the token `first`
    # sampled (the host has it), b's is the one `behind` will sample
    third = sched.schedule(ahead_of=behind, allow=_allow)
    assert third.kind == "decode" and third.seqs == [a, b] and list(third.fed_from) == [-1, 0]
    assert list(third.input_ids[:, 0]) == [7, -1] and list(third.kv_lens) == [11, 33]
    assert (b.num_computed, b.output_ids) == (16, [])


def test_a_plan_behind_a_burst_leaves_out_the_row_that_ends_in_it_by_length():
    sched, kv = _sched()
    ends, stays = _seq("ends", 6, 5), _seq("stays", 6, 30)
    for s in (ends, stays):
        sched.add(s)
    first = sched.schedule()
    sched.apply_step(first, np.array([3, 4]), eos_token_id=0)
    burst = sched.schedule()
    assert burst.kind == "decode" and burst.seqs == [ends, stays]
    behind = sched.schedule(ahead_of=burst, allow=_allow)
    # `ends` has 1 of its 5 tokens and the burst makes the other 4
    assert behind.seqs == [stays] and list(behind.fed_from) == [1]
    assert behind.input_ids[0, 0] == -1  # the device knows it, the host not yet
    assert behind.positions[0, 0] == 6 + 1 + 4 - 1 and behind.kv_lens[0] == 6 + 1 + 4
    assert ends.output_ids == [3] and stays.output_ids == [4]
    assert ends in sched.running  # it holds its seat and pages until the burst is applied


def test_a_plan_behind_a_running_dispatch_never_preempts():
    sched, kv = _sched(pages=6)
    a, b = _seq("a", 6, 60), _seq("b", 6, 60)
    for s in (a, b):
        sched.add(s)
    first = sched.schedule()
    sched.apply_step(first, np.array([3, 4]), eos_token_id=0)
    burst = sched.schedule()
    assert burst.seqs == [a, b]
    kv.allocate(kv.num_free())  # nothing left to grow into
    a.output_ids += [5] * 14  # the burst behind this one needs a fourth page a row
    b.output_ids += [5] * 14
    held = (list(a.pages), list(b.pages), sched._last_kind)
    assert sched.schedule(ahead_of=burst, allow=_allow) is None
    assert sched.ahead_refusal == "no_pages" and sched.preemptions_total == 0
    assert (a.pages, b.pages, sched._last_kind) == held and sched.running == [a, b]


def test_a_resident_row_the_host_stages_refuses_the_plan_and_a_pinned_row_keeps_what_it_holds():
    sched, kv = _sched()
    a, b = _seq("a", 6, 60), _seq("b", 6, 60, logprobs=1)
    sched.add(a)
    first = sched.schedule()
    sched.apply_step(first, np.array([3]), eos_token_id=0)
    burst = sched.schedule()
    sched.pin(burst)
    sched.add(b)
    assert sched.schedule(ahead_of=burst, allow=_allow) is None
    assert sched.ahead_refusal == "host_staged_rows"
    free = kv.num_free()
    sched._finish(a, "abort")  # while the burst that names it runs
    assert a.finished and a not in sched.running
    assert kv.num_free() == free and a.pages and a.release_pending
    sched.retire(burst)
    assert not a.pages and kv.num_free() > free and not a.release_pending


# -- riders in the queued loop, and the families that have none ----------------

def test_a_burst_queued_behind_a_mixed_prefill_takes_the_riders_tokens_from_the_device(
        engine, drained, monkeypatch):
    """llama: the running decode rows ride the prefill dispatch, and the burst
    enqueued BEHIND it is fed their tokens from its device-resident result
    (rows past the chunk's own). What is served is the drained loop's. The
    state families carry none and serve the same."""
    # rows that still decode when the later prompts arrive, however fast the toy is
    traffic = [("long", "a b c", 150, 0.0), ("longer", "d e f g", 170, 0.0),
               ("chunked", "the prompt of three chunks, " * 3, 40, 0.02),
               ("late", "arrives while the others decode", 30, 0.05)]
    drained(True)
    want = _serve(engine, traffic)
    drained(False)
    if engine.scheduler.rider_refusal:
        # jamba, lfm2: no slot, no rider, the same tokens
        assert engine.scheduler.rider_refusal == "family"
        assert _serve(engine, traffic) == want
        stats = engine.stats()
        assert stats["prefill_dispatches_total"] > 0 == stats["prefill_rider_rows_total"]
        assert not any(stats["prefill_riderless_dispatches_total"].values())
        return _everything_back(engine)
    seen, enqueue = [], engine._enqueue

    def spy(batch, feeds, running, first):
        seen.append((batch, feeds.batch if feeds is not None else None))
        return enqueue(batch, feeds, running, first)

    monkeypatch.setattr(engine, "_enqueue", spy)
    rows0 = engine.stats()["prefill_rider_rows_total"]
    assert _serve(engine, traffic) == want
    mixed = [b for b, _ in seen if b.kind == "prefill" and b.riders and b.riders.seqs]
    assert mixed and engine.stats()["prefill_rider_rows_total"] - rows0 == sum(
        len(b.riders.seqs) for b in mixed)
    mixed_ids = {id(b) for b in mixed}   # (a batch compares by its arrays)
    fed_by_riders = [
        (b, f) for b, f in seen
        if b.kind == "decode" and id(f) in mixed_ids and b.fed_from is not None
        and (b.fed_from >= len(f.kv_lens)).any()
    ]
    assert fed_by_riders
    for burst, f in fed_by_riders:
        for i, s in enumerate(burst.seqs):
            if s in f.riders.seqs:   # its token: its row of the slot, on the device
                assert burst.fed_from[i] == len(f.kv_lens) + f.riders.seqs.index(s)
                assert burst.input_ids[i, 0] == -1
    # a mixed dispatch behind a burst takes its riders' inputs from the burst
    assert any(id(b) in mixed_ids and f is not None and f.kind == "decode"
               and (b.riders.fed_from >= 0).any() for b, f in seen)
    _everything_back(engine)


def _digest(batch) -> dict:
    """A planned batch, array for array."""
    import hashlib

    def h(a):
        a = np.ascontiguousarray(a)
        return f"{a.dtype}{list(a.shape)}:{hashlib.sha1(a.tobytes()).hexdigest()[:16]}"
    arrays = ("input_ids", "positions", "page_table", "kv_lens", "temperature", "top_k",
              "top_p", "lora_ids", "kv_limits", "state_slots", "fed_from")
    return {"kind": batch.kind, "seqs": [s.seq_id for s in batch.seqs],
            "chunks": list(batch.chunk_sizes), "bursts": batch.bursts,
            **{k: h(getattr(batch, k)) for k in arrays if getattr(batch, k) is not None}}


# who arrives before which turn of the loop: (turn, name, prompt tokens, max_tokens)
ARRIVALS = [
    (0, "a", 6, 14), (0, "b", 40, 9), (2, "c", 21, 30), (3, "d", 70, 6), (5, "e", 3, 1),
    (9, "f", 33, 12), (9, "g", 12, 22), (14, "h", 50, 5), (20, "i", 8, 40),
]
# how the engine builds the scheduler of a family whose prefill carries no
# riders: state slots (jamba, lfm2), one decode page-table width (lfm2), and
# the llama family on a runner that refuses (a mesh)
PLANS = {
    "jamba": dict(state_slots=4),
    "lfm2": dict(state_slots=4, decode_page_bucket_floor=16),
    "llama-refused": dict(),
}


def plan_script(scheduler_cls, kv_cls, family: str, **kw) -> list:
    """The batches the queued loop plans over ARRIVALS (its own order: plan
    behind the running dispatch, pin, then apply and retire the running one;
    tokens a fixed function of the turn), each as ``_digest`` has it."""
    opts = dict(PLANS[family])
    kv = kv_cls(40, 8, state_slots=opts.pop("state_slots", 0))
    sched = scheduler_cls(kv, max_num_seqs=4, max_model_len=128, prefill_chunk=16,
                          decode_steps=4, enable_prefix_caching=False, **opts, **kw)
    out, running, turn = [], None, 0
    while turn < 200 and (turn <= ARRIVALS[-1][0] or sched.has_work() or running):
        for _, name, n, most in (a for a in ARRIVALS if a[0] == turn):
            sched.add(_seq(name, n, most))
        batch = (sched.schedule() if running is None
                 else sched.schedule(ahead_of=running, allow=_allow))
        if batch is not None:
            out.append(_digest(batch))
            sched.pin(batch)
        if running is not None:
            shape = (len(running.kv_lens), 4) if running.kind == "decode" else (
                len(running.kv_lens),)
            sched.apply_step(running, np.full(shape, 3 + turn % 5), eos_token_id=0)
            sched.retire(running)
        running, turn = batch, turn + 1
    assert not sched.has_work() and kv.num_free() == 40
    return out


@pytest.mark.parametrize("family", sorted(PLANS))
def test_a_family_that_does_not_ride_plans_the_parents_batches_array_for_array(family):
    """``tests/data/parent_plans.json`` holds what the scheduler of the commit
    BEFORE riders planned over this script (written by running ``plan_script``
    on that commit's ``scheduler.py``, PR 54): jamba, lfm2 and a llama runner
    that refuses plan the same batches still, array for array."""
    import json
    import pathlib

    golden = json.loads(
        (pathlib.Path(__file__).parent / "data" / "parent_plans.json").read_text())
    refusal = "mesh" if family == "llama-refused" else "family"
    got = plan_script(Scheduler, KVPageManager, family, rider_refusal=refusal)
    assert len(got) > 30 and {b["kind"] for b in got} == {"prefill", "decode"}
    assert got == golden[family]


def _engine_beside(store_dir, name, tp=1):
    """An engine whose runner finds ``store_dir`` as the step-program store
    (and so builds what it lists as it starts), the loader finished."""
    from production_stack_tpu.engine.step_programs import StepProgramStore

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(StepProgramStore, "beside_compile_cache",
                   classmethod(lambda cls: StepProgramStore(str(store_dir))))
        eng = LLMEngine(EngineConfig(
            model=name, max_model_len=256, max_num_seqs=4, num_pages=PAGES, page_size=8,
            prefill_chunk=32, kv_cache_memory_gb=0.01, enable_prefix_caching=False,
            tensor_parallel_size=tp))
    assert eng.runner.preloaded.wait(120)
    eng.start()
    return eng


@pytest.mark.parametrize("family,tp", [*((f, 1) for f in sorted(FAMILIES)), ("llama", 2)])
def test_a_preloaded_shape_still_goes_out_drained_and_its_event_says_preloaded(
        tmp_path, family, tp):
    """PR 47's invariant beside PR 50's loader: ``_shape_known`` means "has
    been dispatched", so a shape whose executable was built at start-up still
    goes out with nothing in flight, timed to its result, and is counted."""
    module, preset = FAMILIES[family]
    name = preset + "-preload-f32"
    module.PRESETS[name] = dataclasses.replace(module.PRESETS[preset], dtype="float32")
    try:
        eng = _engine_beside(tmp_path, name, tp)
        try:
            want = _serve(eng)
            assert eng.stats()["step_program_preload_listed"] == 0
        finally:
            eng.stop()
        tracing.get_flightrecorder().reset()
        eng = _engine_beside(tmp_path, name, tp)
        try:
            stats = eng.stats()
            assert stats["step_program_preload_listed"] == stats["step_program_preloaded_total"] > 0
            assert stats["step_program_preload_pending_at_first_dispatch"] is None
            got = _serve(eng)
            _everything_back(eng)
            stats = eng.stats()
        finally:
            eng.stop()
    finally:
        del module.PRESETS[name]
    assert [r["tokens"] for r in got] == [r["tokens"] for r in want]
    served = stats["step_program_preload_served_total"]
    assert served > 0 and stats["step_program_preload_failed_total"] == 0
    assert stats["step_program_preload_pending_at_first_dispatch"] == 0
    # every first dispatch, preloaded or not, followed a ``sched`` event that
    # was NOT queued ahead, and nothing else ran beside it
    events = [(e["kind"], e["data"]) for e in tracing.get_flightrecorder().events()]
    firsts, sched = [], None
    for kind, data in events:
        if kind == "sched":
            sched = data
        elif kind == "compile" and data.get("event") == "first_dispatch":
            assert sched is not None and sched["queued_ahead"] is False and sched["drain"]
            firsts.append(data["store"])
    assert firsts.count("preloaded") == served and set(firsts) <= {"preloaded", "hit", "write"}
    assert len(firsts) == stats["first_dispatches_total"]
    if set(firsts) == {"preloaded"}:  # (a batch formed otherwise meets a new shape)
        assert stats["first_dispatch_compile_seconds_total"] == 0
