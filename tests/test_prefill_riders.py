"""Running decode rows take one step inside every prefill dispatch
(scheduler._plan_riders, runner.StepInput.riders, llama.forward(riders=)).

What the benchmark's ``correct`` cannot see (PERF.md section 7: it follows ONE
request with nothing else in flight, so a row that rides wrongly beside other
rows passes it). Three levels over the toy llama and a qwen-like toy whose qkv
biases are drawn: the step program (a riding row's logits, token and K/V are
the row's own through a decode dispatch; a planted fault is told apart; empty
slots change nothing), the scheduler's plan (who rides, when the slot goes out
empty and why, what the queued-ahead loop plans behind a mixed dispatch) and
the engine (the same tokens with riders and by alternation, no step program
more, the counters, a state family never plans one)."""

import asyncio
import dataclasses
import time

import jax
import numpy as np
import pytest

from production_stack_tpu import tracing
from production_stack_tpu.engine.config import EngineConfig
from production_stack_tpu.engine.engine import LLMEngine
from production_stack_tpu.engine.kv_manager import KVPageManager
from production_stack_tpu.engine.runner import ModelRunner, StepInput
from production_stack_tpu.engine.scheduler import (
    SamplingParams, Scheduler, Sequence, host_staged,
)
from production_stack_tpu.models import jamba, llama

PAGE, POOL, CTX, CHUNK = 8, 64, 16, 16
# the tolerance the toy's programs are held to against one another
# (tests/test_model.py: a batch against its rows alone)
TOL = dict(rtol=2e-2, atol=2e-2)


# -- the step program --------------------------------------------------------


def _params(cfg):
    """Host parameters; the qkv biases DRAWN (``init_params`` leaves them
    zero, and a rider that skipped its bias would pass)."""
    params = jax.tree.map(np.asarray, llama.init_params(cfg, jax.random.key(0)))
    rng = np.random.RandomState(3)
    for name in ("bq", "bk", "bv"):
        if name in params["layers"]:
            b = params["layers"][name]
            params["layers"][name] = rng.normal(0, 0.5, b.shape).astype(b.dtype)
    return params


def _rows(rng, B, T, lo, base, vocab):
    """A prefill batch: B rows of T tokens at positions lo.., 4 pages a row
    from page ``base``."""
    return StepInput(
        rng.randint(0, vocab, (B, T)).astype(np.int32),
        np.tile(np.arange(lo, lo + T, dtype=np.int32), (B, 1)),
        (base + np.arange(B * 4, dtype=np.int32)).reshape(B, 4),
        np.full((B,), lo + T, np.int32),
        np.zeros(B, np.float32), np.zeros(B, np.int32), np.ones(B, np.float32),
    )


def _decode(n):
    """The next step of n rows that hold CTX tokens in pages 4 i .. 4 i + 3."""
    return StepInput(
        (5 + 2 * np.arange(n, dtype=np.int32))[:, None],
        np.full((n, 1), CTX, np.int32),
        np.arange(4 * n, dtype=np.int32).reshape(n, 4),
        np.full((n,), CTX + 1, np.int32),
        np.zeros(n, np.float32), np.zeros(n, np.int32), np.ones(n, np.float32),
    )


def _slot(decode: StepInput, R: int, width: int, fault=None):
    """``decode``'s rows in a riders' slot of R rows, the rest inert."""
    n = len(decode.kv_lens)
    ids, pos = np.zeros((R, 1), np.int32), np.full((R, 1), -1, np.int32)
    table, lens = np.zeros((R, width), np.int32), np.zeros((R,), np.int32)
    ids[:n], pos[:n], lens[:n] = decode.input_ids, decode.positions, decode.kv_lens
    table[:n, : decode.page_table.shape[1]] = decode.page_table
    if fault == "one_short":  # a token early: without its newest cached token
        lens[:n] -= 1
        pos[:n] -= 1
    if fault == "neighbour":  # every row carries the next row's input token
        ids[:n] = np.roll(ids[:n], 1, axis=0)
    return (ids, pos, table, lens, np.zeros(R, np.float32), np.zeros(R, np.int32),
            np.ones(R, np.float32), None)


def _both_ways(preset, attn, n, R, rows, fault=None, f32=False):
    """n rows with CTX tokens of context each and ``rows`` rows with one chunk
    prefilled; then (a) the n rows' decode step and the others' second chunk
    as two dispatches, (b) the chunk with the n rows riding in a slot of R."""
    cfg = dataclasses.replace(llama.PRESETS[preset], attn_impl=attn)
    if f32:
        cfg = dataclasses.replace(cfg, dtype="float32")
    params = _params(cfg)
    base = 4 * n

    def runner():
        r = ModelRunner(cfg, params=params, num_pages=POOL, page_size=PAGE, seed=0)
        assert r.rider_refusal is None
        r.step(_rows(np.random.RandomState(1), n, CTX, 0, 0, cfg.vocab_size))
        r.step(_rows(np.random.RandomState(2), rows, CHUNK, 0, base, cfg.vocab_size))
        return r

    chunk = lambda: _rows(  # noqa: E731
        np.random.RandomState(4), rows, CHUNK, CHUNK, base, cfg.vocab_size)
    apart = runner()
    d_ids, d_logits = apart.step(_decode(n))
    c_ids, c_logits = apart.step(chunk())
    mixed = runner()
    before = [np.asarray(mixed.k_pages), np.asarray(mixed.v_pages)]
    inp = chunk()
    inp.riders = _slot(_decode(n), R, 8, fault)
    m_ids, m_logits = mixed.step(inp)
    out = lambda *xs: [np.asarray(x).astype(np.float32) for x in xs]  # noqa: E731
    return {
        "n": n, "R": R, "rows": rows, "base": base,
        "decode": out(d_ids, d_logits), "chunk": out(c_ids, c_logits),
        "mixed": out(m_ids, m_logits), "before": out(*before),
        "apart_pools": out(apart.k_pages, apart.v_pages),
        "mixed_pools": out(mixed.k_pages, mixed.v_pages),
    }


def _riders_agree(w) -> bool:
    """Whether the riding rows' logits and tokens are the decode dispatch's."""
    n, B = w["n"], w["rows"]
    ids, logits = w["mixed"]
    d_ids, d_logits = w["decode"]
    return bool(
        np.allclose(logits[B:B + n], d_logits, **TOL)
        and (ids[B:B + n] == d_ids).all()
    )


# 1, 3 and R riders beside chunks of 1-4 rows, both attention paths, the toy
# llama and the toy with drawn qkv biases
CASES = [
    ("llama-debug", "xla", 1, 4, 1),
    ("llama-debug", "xla", 3, 4, 2),
    ("qwen2-debug", "xla", 4, 4, 4),
    ("qwen2-debug", "xla", 3, 4, 3),
    ("llama-debug", "pallas_interpret", 3, 4, 1),
    ("qwen2-debug", "pallas_interpret", 4, 4, 2),
]


@pytest.fixture(scope="module", params=CASES, ids=lambda c: "-".join(map(str, c)))
def ways(request):
    return _both_ways(*request.param)


def test_a_riding_rows_logits_and_token_are_its_own_through_a_decode_dispatch(ways):
    ids, logits = ways["mixed"]
    B, R = ways["rows"], ways["R"]
    assert ids.shape == (B + R,) and logits.shape[0] == B + R
    assert _riders_agree(ways)


def test_a_chunk_rows_logits_and_token_are_a_rider_free_prefills(ways):
    B = ways["rows"]
    ids, logits = ways["mixed"]
    c_ids, c_logits = ways["chunk"]
    np.testing.assert_allclose(logits[:B], c_logits, **TOL)
    np.testing.assert_array_equal(ids[:B], c_ids)


def test_the_riders_kv_lands_in_the_decode_steps_page_slots_and_nowhere_else(ways):
    """Both ways leave the same pools: a rider's token in slot CTX of its own
    pages (page 2 of its table, row 0 of the page), the chunk's in its own,
    and nothing anywhere else (page 0 heads the padded rows' tables)."""
    n, rows, base = ways["n"], ways["rows"], ways["base"]
    for mixed, apart, before in zip(ways["mixed_pools"], ways["apart_pools"],
                                    ways["before"]):
        np.testing.assert_allclose(mixed, apart, **TOL)
        changed = np.abs(mixed - before).reshape(
            mixed.shape[0], mixed.shape[1], mixed.shape[2], -1).max(axis=(0, 3))
        written = {(int(p), int(t)) for p, t in zip(*np.nonzero(changed))}
        riders = {(4 * i + CTX // PAGE, CTX % PAGE) for i in range(n)}
        chunk = {(base + 4 * i + (CHUNK + t) // PAGE, (CHUNK + t) % PAGE)
                 for i in range(rows) for t in range(CHUNK)}
        assert written == riders | chunk


@pytest.mark.parametrize("attn", ["xla", "pallas_interpret"])
@pytest.mark.parametrize("fault", ["one_short", "neighbour"])
def test_a_planted_fault_is_told_apart(attn, fault):
    """The comparison above is no formality: a rider that attends with
    ``kv_len`` (and its position) one short, or is fed its neighbour's token, FAILS it (float32,
    so that the margin is the fault's and not bfloat16's)."""
    sound = _both_ways("qwen2-debug", attn, 3, 4, 1, f32=True)
    assert _riders_agree(sound)
    d = np.abs(sound["mixed"][1][1:4] - sound["decode"][1]).max()
    planted = _both_ways("qwen2-debug", attn, 3, 4, 1, fault=fault, f32=True)
    off = np.abs(planted["mixed"][1][1:4] - planted["decode"][1]).max()
    assert not _riders_agree(planted)
    assert off > 10 * max(d, 1e-3)
    # the chunk's rows do not see the fault
    np.testing.assert_allclose(planted["mixed"][1][:1], planted["chunk"][1], **TOL)


@pytest.mark.parametrize("attn", ["xla", "pallas_interpret"])
def test_empty_rider_slots_leave_pages_and_outputs_untouched(attn):
    """A slot in which nothing rides: the chunk's logits and tokens are the
    rider-free program's and no page but the chunk's own is written."""
    cfg = dataclasses.replace(llama.PRESETS["qwen2-debug"], attn_impl=attn)
    params = _params(cfg)
    chunk = lambda: _rows(np.random.RandomState(4), 2, CHUNK, 0, 8, cfg.vocab_size)  # noqa: E731
    plain = ModelRunner(cfg, params=params, num_pages=POOL, page_size=PAGE, seed=0)
    p_ids, p_logits = plain.step(chunk())
    slotted = ModelRunner(cfg, params=params, num_pages=POOL, page_size=PAGE, seed=0)
    inp = chunk()
    inp.riders = _slot(_decode(0), 4, 8)
    s_ids, s_logits = slotted.step(inp)
    np.testing.assert_array_equal(np.asarray(s_ids)[:2], np.asarray(p_ids))
    np.testing.assert_allclose(np.asarray(s_logits)[:2], np.asarray(p_logits), **TOL)
    for a, b in ((slotted.k_pages, plain.k_pages), (slotted.v_pages, plain.v_pages)):
        a, b = (np.asarray(x).astype(np.float32) for x in (a, b))
        np.testing.assert_allclose(a, b, **TOL)
        assert not a[:, :8].any() and not a[:, 16:].any()


@pytest.mark.parametrize("build, why", [
    (lambda: ModelRunner(jamba.PRESETS["jamba-debug"], num_pages=8, page_size=PAGE,
                         state_slots=2), "family"),
    (lambda: ModelRunner(llama.PRESETS["llama-debug"], num_pages=8, page_size=PAGE,
                         enable_lora=True), "lora"),
    (lambda: ModelRunner(dataclasses.replace(llama.PRESETS["llama-debug"],
                                             kv_cache_dtype="int8"),
                         num_pages=8, page_size=PAGE), "kv_quant"),
    (lambda: ModelRunner(dataclasses.replace(llama.PRESETS["llama-debug"],
                                             kv_write_mode="pre"),
                         num_pages=8, page_size=PAGE), "kv_write_mode"),
])
def test_the_runner_reports_what_stands_in_the_way(build, why):
    assert build().rider_refusal == why


# -- the scheduler's plan ------------------------------------------------------


def _sched(pages=48, **kw):
    kv = KVPageManager(pages, PAGE)
    kw.setdefault("enable_prefix_caching", False)
    kw.setdefault("rider_refusal", None)
    kw.setdefault("max_num_seqs", 8)
    return Scheduler(kv, max_model_len=128, prefill_chunk=16, decode_steps=4, **kw), kv


def _seq(name, prompt_len, max_tokens, **kw):
    kw.setdefault("ignore_eos", True)
    return Sequence(name, list(range(1, prompt_len + 1)),
                    SamplingParams(max_tokens=max_tokens, **kw))


def _decoding(sched, *seqs):
    """Admit ``seqs``, prefill them and give each its first token; the next
    plan is made as after a burst (the gate alternates behind a prefill)."""
    for s in seqs:
        sched.add(s)
    while any(s.in_prefill or not s.output_ids for s in seqs):
        batch = sched.schedule()
        assert batch.kind == "prefill"
        sched.apply_step(batch, np.full((len(batch.kv_lens) + sched.rider_slots,), 3),
                         eos_token_id=0)
    sched._last_kind = "decode"
    for key in sched.prefill_riderless_dispatches:
        sched.prefill_riderless_dispatches[key] = 0
    sched.prefill_dispatches_total = 0
    sched.prefill_rider_dispatches_total = sched.prefill_rider_rows_total = 0


def _counts(sched):
    return (sched.prefill_dispatches_total, sched.prefill_rider_dispatches_total,
            sched.prefill_rider_rows_total,
            {k: n for k, n in sched.prefill_riderless_dispatches.items() if n})


def test_running_rows_ride_with_their_last_token_position_pages_and_parameters():
    sched, kv = _sched()
    a, b = _seq("a", 6, 30), _seq("b", 9, 30, temperature=0.7, top_k=5, top_p=0.9)
    _decoding(sched, a, b)
    a.output_ids[-1], b.output_ids[-1] = 41, 42
    c = _seq("c", 40, 5)
    sched.add(c)
    batch = sched.schedule()
    r = batch.riders
    assert batch.kind == "prefill" and batch.seqs == [c] and r.seqs == [a, b]
    assert batch.rows == [c, a, b]
    R = sched.rider_slots
    assert R == 8 and r.page_table.shape == (R, sched.rider_pages) == (R, 16)
    assert list(r.input_ids[:3, 0]) == [41, 42, 0]
    assert list(r.positions[:3, 0]) == [6, 9, -1] and list(r.kv_lens[:3]) == [7, 10, 0]
    assert list(r.page_table[1, : len(b.pages)]) == b.pages and not r.page_table[2:].any()
    assert (r.temperature[1], r.top_k[1], r.top_p[1]) == (np.float32(0.7), 5, np.float32(0.9))
    assert r.fed_from is None
    # the chunk is what it would have been: nothing made smaller, nobody delayed
    assert batch.chunk_sizes == [16]
    assert _counts(sched) == (1, 1, 2, {})
    # applied: the chunk's rows first, then one token a rider
    tokens = np.zeros((len(batch.kv_lens) + R,), np.int64)
    tokens[len(batch.kv_lens):len(batch.kv_lens) + 2] = [51, 52]
    events = sched.apply_step(batch, tokens, eos_token_id=0)
    assert [(s.seq_id, t, row) for s, t, row, _ in events] == [("a", 51, 1), ("b", 52, 2)]
    assert a.output_ids[-2:] == [41, 51] and c.num_computed == 16


def test_a_rider_ends_by_eos_or_by_max_tokens_inside_the_dispatch():
    sched, kv = _sched()
    eos, full, goes_on = (_seq("eos", 6, 30, ignore_eos=False), _seq("full", 6, 2),
                          _seq("goes-on", 6, 30))
    _decoding(sched, eos, full, goes_on)
    sched.add(_seq("c", 80, 5))   # a backlog: a burst follows every chunk
    mixed = sched.schedule()
    assert mixed.riders.seqs == [eos, full, goes_on]
    sched.pin(mixed)
    # what the queued-ahead loop plans behind it: ``full`` ends in it by
    # length and is left out, ``eos`` may end and stays in (its tokens tell)
    behind = sched.schedule(ahead_of=mixed, allow=lambda s: True)
    assert behind.kind == "decode" and behind.seqs == [eos, goes_on]
    B = len(mixed.kv_lens)
    assert list(behind.fed_from[:2]) == [B + 0, B + 2]
    tokens = np.full((B + sched.rider_slots,), 9)
    tokens[B] = 7   # the EOS id below
    events = sched.apply_step(mixed, tokens, eos_token_id=7)
    assert [(s.seq_id, t) for s, t, _, _ in events] == [("eos", 7), ("full", 9), ("goes-on", 9)]
    assert (eos.finish_reason, full.finish_reason) == ("stop", "length")
    assert not goes_on.finished and goes_on.output_ids == [3, 9]
    # their pages go back when the dispatch has retired, not before
    assert eos.pages and full.release_pending
    sched.retire(mixed)
    assert not eos.pages and not full.pages and goes_on.pages


def test_a_bare_scheduler_and_a_family_without_the_capability_plan_none():
    for why in ("family", "mesh"):
        sched, kv = _sched(rider_refusal=why)
        a = _seq("a", 6, 30)
        _decoding(sched, a)
        pages = list(a.pages)
        sched.add(_seq("c", 40, 5))
        batch = sched.schedule()
        assert batch.kind == "prefill" and batch.riders is None and batch.rows == batch.seqs
        # nothing is counted but the dispatch, and nothing is lent to a row
        assert _counts(sched) == (1, 0, 0, {}) and a.pages == pages
    assert Scheduler(KVPageManager(8, PAGE)).rider_refusal == "family"


@pytest.mark.parametrize("kw, why", [
    ({"spec_k": 2}, "speculative"), ({"decode_pipeline": 2}, "decode_pipeline")])
def test_speculation_and_chained_bursts_keep_their_rows(kw, why):
    sched, kv = _sched(**kw)
    assert sched.rider_refusal == why


@pytest.mark.parametrize("params", [
    {"logprobs": 2}, {"presence_penalty": 0.5}, {"logit_bias": {3: 1.0}},
    {"min_tokens": 20, "ignore_eos": False}])
def test_one_row_the_host_stages_and_the_dispatch_carries_none(params):
    sched, kv = _sched()
    a, b = _seq("a", 6, 30), _seq("b", 6, 30, **params)
    assert host_staged(b) and not host_staged(a)
    _decoding(sched, a, b)
    sched.add(_seq("c", 40, 5))
    batch = sched.schedule()
    assert batch.kind == "prefill" and batch.riders.seqs == [] and batch.rows == batch.seqs
    assert (batch.riders.positions == -1).all() and not batch.riders.kv_lens.any()
    assert _counts(sched) == (1, 0, 0, {"cannot_ride": 1})


@pytest.mark.parametrize("params", [{"logprobs": 1}, {"logit_bias": {3: 1.0}},
                                    {"repetition_penalty": 1.2}])
def test_a_chunk_the_host_stages_runs_another_program_variant_which_has_no_slot(params):
    sched, kv = _sched()
    a = _seq("a", 6, 30)
    _decoding(sched, a)
    sched.add(_seq("c", 40, 5, **params))
    batch = sched.schedule()
    assert batch.kind == "prefill" and batch.riders is None
    assert _counts(sched) == (1, 0, 0, {"cannot_ride": 1})


def test_one_row_without_a_page_and_the_dispatch_carries_none_and_preempts_nobody():
    sched, kv = _sched()
    a, b = _seq("a", 6, 30), _seq("b", 6, 30)
    _decoding(sched, a, b)
    a.pages, spare = a.pages[:1], a.pages[1:]   # a's next token opens a page
    a.output_ids += [5] * (PAGE - a.num_tokens)
    sched.add(_seq("c", 40, 5))
    sched._try_admit()
    kv.free(spare)
    kv.allocate(kv.num_free())
    batch = sched.schedule()
    assert batch.kind == "prefill" and batch.riders.seqs == []
    assert _counts(sched) == (1, 0, 0, {"no_page": 1}) and sched.preemptions_total == 0
    assert a in sched.running and len(a.pages) == 1


def test_more_rows_than_the_slot_holds_and_the_dispatch_carries_none():
    sched, kv = _sched(pages=96, max_num_seqs=4)
    sched.rider_slots = 2   # the slot of a narrower engine
    rows = [_seq(f"r{i}", 4, 30) for i in range(3)]
    _decoding(sched, *rows)
    sched.add(_seq("c", 80, 5))   # a backlog: a burst follows every chunk
    first = sched.schedule()
    assert first.kind == "prefill" and first.riders.seqs == []
    assert len(first.riders.kv_lens) == 2
    assert _counts(sched) == (1, 0, 0, {"over_width": 1})
    # alternation serves them, as it did before there were riders
    sched.apply_step(first, np.full((len(first.kv_lens) + 2,), 9), eos_token_id=0)
    burst = sched.schedule()
    assert burst.kind == "decode" and burst.seqs == rows


def test_a_preempted_row_and_a_row_that_ends_never_ride():
    sched, kv = _sched()
    a, b, ends = _seq("a", 6, 30), _seq("b", 6, 30), _seq("ends", 6, 5)
    _decoding(sched, a, b, ends)
    sched._preempt(b)
    burst = sched._plan_decode([a, ends])
    assert burst.seqs == [a, ends]
    sched.add(_seq("c", 40, 5))
    sched.waiting.sort(key=lambda s: s.seq_id != "c")  # c before the preempted b
    behind = sched.schedule(ahead_of=burst, allow=lambda s: True)
    # `ends` has 1 of its 5 tokens and the burst makes the other 4; b waits
    assert behind.kind == "prefill" and behind.riders.seqs == [a]
    assert b not in behind.riders.seqs and ends not in behind.rows
    # a's input is the burst's last token for it: row 0 of that result
    assert list(behind.riders.fed_from[:2]) == [0, -1]
    assert behind.riders.input_ids[0, 0] == -1
    assert behind.riders.positions[0, 0] == 6 + 1 + 4 - 1 and behind.riders.kv_lens[0] == 11
    assert a.output_ids == [3] and ends.output_ids == [3]   # put back


def test_the_dispatch_behind_a_mixed_one_is_planned_from_the_right_lengths():
    sched, kv = _sched()
    a, b = _seq("a", 6, 30), _seq("b", 9, 2)
    _decoding(sched, a, b)
    c = _seq("c", 20, 5)
    sched.add(c)
    mixed = sched.schedule()
    assert mixed.kind == "prefill" and mixed.riders.seqs == [a, b] and mixed.chunk_sizes == [16]
    sched.pin(mixed)
    assert (a.inflight, c.inflight) == (1, 1)
    behind = sched.schedule(ahead_of=mixed, allow=lambda s: True)
    # b's second token is its last: it ends in the mixed dispatch and is left
    # out; c's last chunk follows, a rides again, fed from its row of the
    # mixed result (behind the chunk's padded rows)
    assert behind.kind == "prefill" and behind.seqs == [c] and behind.chunk_sizes == [4]
    assert behind.riders.seqs == [a]
    assert list(behind.riders.fed_from[:2]) == [len(mixed.kv_lens) + 0, -1]
    assert behind.riders.positions[0, 0] == 7 and behind.riders.kv_lens[0] == 8
    # the state is as it was: nothing of the projection stays
    assert (a.output_ids, b.output_ids, c.num_computed) == ([3], [3], 0)
    # and behind THAT one, the burst: a has made two more tokens by then
    tokens = np.full((len(mixed.kv_lens) + sched.rider_slots,), 9)
    sched.apply_step(mixed, tokens, eos_token_id=0)
    sched.retire(mixed)
    assert b.finished and b.finish_reason == "length" and a.output_ids == [3, 9]
    burst = sched.schedule(ahead_of=behind, allow=lambda s: True)
    assert burst.kind == "decode" and burst.seqs == [a, c]
    assert list(burst.fed_from[:2]) == [len(behind.kv_lens) + 0, 0]
    assert list(burst.kv_lens[:2]) == [6 + 3, 20 + 1]


# -- the engine ----------------------------------------------------------------

# a chat-like script: arrivals while earlier rows decode (the first decodes
# for as long as the others take to arrive, however fast the toy is), a
# prompt of three chunks, rows that end inside a dispatch
TRAFFIC = [
    ("long", "a b c", 180, 0.0),
    ("chunked", "the prompt of three chunks, " * 3, 26, 0.03),
    ("one-burst", "ends after one burst", 8, 0.06),
    ("late", "arrives while the others decode", 33, 0.12),
    ("later", "and one more, of two chunks and a little", 17, 0.20),
]


def _serve(eng):
    async def one(name, prompt, n, delay):
        await asyncio.sleep(delay)
        toks = []
        async for out in eng.generate(
                f"{name}-{np.random.randint(1 << 30)}", prompt=prompt,
                params=SamplingParams(max_tokens=n, temperature=0.0, ignore_eos=True)):
            toks += out.token_ids
        return toks

    async def run():
        return await asyncio.gather(*[one(*t) for t in TRAFFIC])
    return asyncio.run(run())


def _engine(module, preset):
    name = preset + "-riders-f32"
    module.PRESETS[name] = dataclasses.replace(module.PRESETS[preset], dtype="float32")
    eng = LLMEngine(EngineConfig(
        model=name, max_model_len=256, max_num_seqs=4, num_pages=96, page_size=8,
        prefill_chunk=32, kv_cache_memory_gb=0.01, enable_prefix_caching=False))
    eng.start()
    return eng, name


COUNTERS = ("prefill_dispatches_total", "prefill_rider_dispatches_total",
            "prefill_rider_rows_total", "prefill_riderless_dispatches_total")


@pytest.fixture(scope="module", params=["llama-debug", "qwen2-debug"])
def served(request):
    """The script through one engine twice: as the runner reports (riders),
    then with the scheduler told that the family has none (alternation, the
    parent's plan)."""
    tracing.get_flightrecorder().reset()   # the engine before left its events
    eng, name = _engine(llama, request.param)
    try:
        assert eng.scheduler.rider_refusal is None and eng.stats()["rider_refusal"] == ""
        step0 = eng.step_idx
        with_riders = _serve(eng)
        stats = eng.stats()
        events = [e["data"] for e in tracing.get_flightrecorder().events(kind="sched")
                  if e["step"] > step0]
        programs = set(eng.runner._programs)
        eng.scheduler.rider_refusal = "family"
        without = _serve(eng)
        deadline = time.time() + 10   # the last dispatch retires, its pages go back
        while time.time() < deadline and (
                eng._inflight is not None or eng.scheduler.has_work()):
            time.sleep(0.01)
        return {"with": with_riders, "without": without, "stats": stats, "events": events,
                "programs": programs, "all_programs": set(eng.runner._programs),
                "after": eng.stats(),
                "free": eng.kv.num_free()}
    finally:
        eng.stop()
        del llama.PRESETS[name]


def test_greedy_tokens_of_rows_that_rode_are_those_served_by_alternation(served):
    assert served["with"] == served["without"]
    assert [len(t) for t in served["with"]] == [n for _, _, n, _ in TRAFFIC]
    assert served["free"] == 96   # every page came back


def test_the_counters_say_how_often_rows_rode_and_the_sched_event_how_many(served):
    s = served["stats"]
    riderless = s["prefill_riderless_dispatches_total"]
    assert set(riderless) == {"cannot_ride", "over_width", "no_page"}
    assert s["prefill_dispatches_total"] >= s["prefill_rider_dispatches_total"] > 0
    assert s["prefill_rider_rows_total"] >= s["prefill_rider_dispatches_total"]
    # every prefill dispatch that had decode demand carried its riders
    assert sum(riderless.values()) == 0
    rode = [e["riders"] for e in served["events"] if e["batch_kind"] == "prefill"]
    assert sum(rode) == s["prefill_rider_rows_total"] and max(rode) <= 4
    assert sum(1 for n in rode if n) == s["prefill_rider_dispatches_total"]
    assert all(e["riders"] == 0 for e in served["events"] if e["batch_kind"] == "decode")
    # told that the family has none, the same traffic carries and counts none
    after = served["after"]
    assert after["rider_refusal"] == "family"
    assert after["prefill_dispatches_total"] > s["prefill_dispatches_total"]
    assert all(after[k] == s[k] for k in COUNTERS[1:])


def test_a_prefill_program_has_one_form_and_a_run_builds_no_more_of_them(served):
    """ONE slot width: a (batch, chunk, pages) bucket of the parent's tables
    has one prefill program with the slot as it has one without (the second
    pass, where the scheduler plans no slot, as the parent does), whatever
    number of rows rode. The count over the script is the count of buckets it
    met, plus nothing."""
    def prefill(programs):
        return [key for key in programs if key[2][1] > 1]
    slot = lambda key: key[5][8:]  # noqa: E731 - what follows the eight batch arguments
    mixed = prefill(served["programs"])
    assert mixed and all(len(slot(k)) == 7 for k in mixed)
    assert len({slot(k) for k in mixed}) == 1          # one width, one page-table width
    assert slot(mixed[0])[:3] == ((4, 1), (4, 1), (4, 32))
    buckets = {(k[2], k[3]) for k in mixed}
    assert len(mixed) == len(buckets)                  # no bucket has two programs
    sched = Scheduler
    assert all(B in sched.DECODE_BATCH_BUCKETS and T in sched.CHUNK_BUCKETS
               and P in sched.PAGE_BUCKETS for (B, T), (_, P) in buckets)
    plain = prefill(served["all_programs"] - served["programs"])
    assert plain and all(len(slot(k)) == 0 for k in plain)
    assert len(plain) == len({(k[2], k[3]) for k in plain})
    # the decode programs are what they were
    decode = {k for k in served["programs"] if k[2][1] == 1}
    assert decode and all(len(k[5]) <= 9 for k in decode)   # the batch's own arguments


def test_a_state_family_never_plans_a_rider():
    eng, name = _engine(jamba, "jamba-debug")
    try:
        assert eng.scheduler.rider_refusal == "family"
        _serve(eng)
        s = eng.stats()
        assert s["rider_refusal"] == "family" and s["prefill_dispatches_total"] > 0
        assert s["prefill_rider_dispatches_total"] == 0 == s["prefill_rider_rows_total"]
        assert sum(s["prefill_riderless_dispatches_total"].values()) == 0
    finally:
        eng.stop()
        del jamba.PRESETS[name]
