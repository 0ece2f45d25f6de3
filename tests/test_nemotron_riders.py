"""Running decode rows take one step inside every prefill dispatch of the
Mamba-2 hybrid (models/nemotron_h.forward(riders=), planned by
scheduler._plan_riders, handed over by runner.StepInput.riders).

The shape of tests/test_prefill_riders.py, for a family whose rows keep a
recurrent state beside their pages: what the benchmark's ``correct`` cannot
see (it follows ONE request with nothing else in flight). On the float32 toy
(all three kinds of block, 8 experts top-2; ``ssm_impl`` and ``moe_impl`` in
the two forms the family resolves to), three levels: the program (a riding
row's logits, token, SSD state, convolution tail and K/V are what its own
decode step leaves; a chunk row's are a rider-free prefill's; nothing else is
written; five planted faults are told apart), the scheduler's plan (the
riders' state slots; a rider that ends frees its seat) and the engine (the
same tokens with riders and by alternation, the counters, no step program
more)."""

import contextlib
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from production_stack_tpu import tracing
from production_stack_tpu.engine.config import EngineConfig
from production_stack_tpu.engine.engine import LLMEngine
from production_stack_tpu.engine.kv_manager import KVPageManager
from production_stack_tpu.engine.runner import ModelRunner, StepInput
from production_stack_tpu.engine.scheduler import Scheduler
from production_stack_tpu.models import nemotron_h as nh
from production_stack_tpu.ops.pallas import ssd_scan

# the llama family's script (arrivals while earlier rows decode, a prompt of
# three chunks, rows that end inside a dispatch) and its scheduler helpers
from test_prefill_riders import TRAFFIC, _decoding, _seq, _serve  # noqa: E402

PAGE, CTX, CHUNK, SEATS, POOL = 8, 16, 16, 8, 64
# float32 on both sides: what differs is the order of summation (a joint
# token axis against the rows' own); read 2e-6
TOL = 2e-5
# the least a planted fault has to read in the part it breaks
FAULT = 1e-3


# -- the step program --------------------------------------------------------


def _toy(ssm, moe):
    cfg = dataclasses.replace(
        nh.PRESETS["nemotron-h-debug"], dtype=jnp.float32, ssm_impl=ssm, moe_impl=moe)
    return cfg, nh.init_params(cfg, jax.random.key(3))


def _forward(cfg, params):
    return jax.jit(
        lambda ids, pos, table, lens, slots, k, v, st, riders=None: nh.forward(
            params, cfg, ids, pos, k, v, table, lens, state=st, state_slots=slots,
            riders=riders))


@contextlib.contextmanager
def _planted(fault):
    """A fault in the part of the program that only a rider runs (T == 1
    beside a chunk), planted while the mixed program is traced."""
    if fault == "state_not_advanced":
        at, name, sound = ssd_scan, "ssd_scan", ssd_scan.ssd_scan

        def broken(x, dt, a, b, c, d, pool, *rest, **kw):
            y, new = sound(x, dt, a, b, c, d, pool, *rest, **kw)
            return y, (pool if x.shape[1] == 1 else new)
    elif fault == "tail_not_shifted":
        at, name, sound = nh, "_conv_and_scan", nh._conv_and_scan

        def broken(xbc, dt, lp, cfg, state, li, row):
            y, new = sound(xbc, dt, lp, cfg, state, li, row)
            return y, (dict(new, conv=state["conv"]) if xbc.shape[1] == 1 else new)
    elif fault == "first_true":
        at, name, sound = nh, "_rows", nh._rows

        def broken(positions, slots):
            row = sound(positions, slots)
            if positions.shape[1] == 1:
                row["first"] = positions[:, 0] >= 0
            return row
    else:
        yield
        return
    setattr(at, name, broken)
    try:
        yield
    finally:
        setattr(at, name, sound)


def _chunk(rng, rows, lo, base, first_slot, vocab, ragged=False):
    """``rows`` rows of CHUNK tokens at positions lo.., 4 pages a row from
    ``base``, seats from ``first_slot``; ``ragged``: row i ends 3 i early."""
    ids = rng.randint(0, vocab, (rows, CHUNK)).astype(np.int32)
    pos = np.tile(np.arange(lo, lo + CHUNK, dtype=np.int32), (rows, 1))
    lens = np.full((rows,), lo + CHUNK, np.int32)
    if ragged:
        for i in range(rows):
            pos[i, CHUNK - 3 * i:], lens[i] = -1, lo + CHUNK - 3 * i
    table = (base + np.arange(rows * 4, dtype=np.int32)).reshape(rows, 4)
    return ids, pos, table, lens, first_slot + np.arange(rows, dtype=np.int32)


def _decode(n):
    """The next step of n rows that hold CTX tokens in pages 4 i .. 4 i + 3,
    seats 0 .. n - 1."""
    return ((5 + 2 * np.arange(n, dtype=np.int32))[:, None],
            np.full((n, 1), CTX, np.int32),
            np.arange(4 * n, dtype=np.int32).reshape(n, 4),
            np.full((n,), CTX + 1, np.int32), np.arange(n, dtype=np.int32))


def _slot(n, R, width, chunk_slot=None, fault=None):
    """``_decode(n)``'s rows in a riders' slot of R rows, the rest inert
    (position -1, kv_len 0, the null seat)."""
    d_ids, d_pos, d_table, d_lens, d_slots = _decode(n)
    ids, pos = np.zeros((R, 1), np.int32), np.full((R, 1), -1, np.int32)
    table, lens = np.zeros((R, width), np.int32), np.zeros((R,), np.int32)
    slots = np.full((R,), SEATS, np.int32)
    ids[:n], pos[:n], lens[:n], slots[:n] = d_ids, d_pos, d_lens, d_slots
    table[:n, :4] = d_table
    if fault == "chunk_rows_slot":   # the first rider steps a chunk row's state
        slots[0] = chunk_slot
    if fault == "kv_one_off":        # every rider's K/V lands a position late
        pos[:n] += 1
    return tuple(jnp.asarray(a) for a in (ids, pos, table, lens, slots))


def _both_ways(ssm, moe, n, R, rows, fault=None):
    """n rows with CTX tokens of context and ``rows`` rows with one chunk
    prefilled, in pools their last owners left DIRTY; then (a) the n rows'
    decode step and the others' second chunk as two dispatches, (b) the chunk
    with the n rows riding in a slot of R."""
    cfg, params = _toy(ssm, moe)
    fwd = _forward(cfg, params)
    base = 4 * n
    k, v = nh.init_kv_pages(cfg, POOL, PAGE)
    state = jax.tree.map(lambda a: a + 3.0, nh.init_state(cfg, SEATS))
    V = cfg.vocab_size
    _, k, v, state, _ = fwd(*_chunk(np.random.RandomState(1), n, 0, 0, 0, V), k, v, state)
    _, k, v, state, _ = fwd(*_chunk(np.random.RandomState(2), rows, 0, base, n, V), k, v, state)
    second = _chunk(np.random.RandomState(4), rows, CHUNK, base, n, V, ragged=True)
    d_logits, k1, v1, s1, d_did = fwd(*_decode(n), k, v, state)
    c_logits, k1, v1, s1, c_did = fwd(*second, k1, v1, s1)
    with _planted(fault):
        m_logits, k2, v2, s2, m_did = _forward(cfg, params)(
            *second, k, v, state, _slot(n, R, 8, chunk_slot=n, fault=fault))
    out = lambda *xs: [np.asarray(x, np.float32) for x in xs]  # noqa: E731
    return {
        "ssm": ssm, "n": n, "R": R, "rows": rows, "base": base,
        "lens": second[3] - CHUNK,
        "decode": out(d_logits)[0], "chunk": out(c_logits)[0], "mixed": out(m_logits)[0],
        "before": out(k, v, state["ssm"], state["conv"]),
        "apart": out(k1, v1, s1["ssm"], s1["conv"]),
        "mixed_pools": out(k2, v2, s2["ssm"], s2["conv"]),
        "did": [np.asarray(x) for x in (d_did, c_did, m_did)],
    }


def _off(w) -> dict:
    """How far the mixed dispatch is from the two dispatches, by part and by
    kind of row: the riders' logits, seats (0 .. n - 1) and pages (the first 4
    n), the chunk's logits, seats and pages (the rest)."""
    n, B = w["n"], w["rows"]
    worst = lambda a, b: float(np.abs(a - b).max())  # noqa: E731
    off = {
        "riders_logits": worst(w["mixed"][B:B + n], w["decode"]),
        "riders_tokens": int((w["mixed"][B:B + n].argmax(-1) != w["decode"].argmax(-1)).sum()),
        "chunk_logits": worst(w["mixed"][:B], w["chunk"]),
    }
    k2, v2, ssm2, conv2 = w["mixed_pools"]
    k1, v1, ssm1, conv1 = w["apart"]
    for kind, seats, pages in (("riders", slice(0, n), slice(0, 4 * n)),
                               ("chunk", slice(n, None), slice(4 * n, None))):
        off[kind + "_kv"] = max(worst(k2[:, pages], k1[:, pages]),
                                worst(v2[:, pages], v1[:, pages]))
        off[kind + "_ssm"] = worst(ssm2[:, seats], ssm1[:, seats])
        off[kind + "_conv"] = worst(conv2[:, seats], conv1[:, seats])
    return off


def _chunk_tol(ssm):
    """What a CHUNK's rows are held to: ``ssd_scan_prefill`` rounds what
    enters its block products to bfloat16 whatever the model's dtype
    (tests/test_nemotron_h.py KERNEL_TOLERANCE), so a last-bit difference in
    its input (the joint token axis sums in another order) can move a whole
    bfloat16 step: read 1.3e-3. A rider never passes through it."""
    return TOL if ssm == "xla" else 1e-2


# 1, 3 and R riders beside chunks of 1-2 rows; the recurrence and the grouped
# product as plain jax.numpy and as the kernels (interpret mode on the CPU)
CASES = [
    ("xla", "xla", 3, 4, 2),
    ("xla", "xla", 1, 4, 1),
    ("pallas_interpret", "pallas_interpret", 3, 4, 2),
    ("pallas_interpret", "xla", 4, 4, 1),
]


@pytest.fixture(scope="module", params=CASES, ids=lambda c: "-".join(map(str, c)))
def ways(request):
    return _both_ways(*request.param)


def test_a_riding_rows_logits_token_state_tail_and_kv_are_its_own_decode_steps(ways):
    B, R = ways["rows"], ways["R"]
    assert ways["mixed"].shape[0] == B + R
    off = _off(ways)
    assert off["riders_tokens"] == 0
    assert max(off["riders_logits"], off["riders_ssm"], off["riders_conv"],
               off["riders_kv"]) < TOL, off


def test_a_chunk_rows_logits_state_and_pages_are_a_rider_free_prefills(ways):
    off = _off(ways)
    assert max(off["chunk_logits"], off["chunk_ssm"], off["chunk_conv"],
               off["chunk_kv"]) < _chunk_tol(ways["ssm"]), off
    np.testing.assert_array_equal(
        ways["mixed"][:ways["rows"]].argmax(-1), ways["chunk"].argmax(-1))


def test_no_seat_and_no_page_slot_but_the_riders_and_the_chunks_is_written(ways):
    """A rider's token lands in slot CTX of its own pages and its seat's state
    and tail move; the chunk's rows' likewise; every other seat, the null one
    included, and every other page slot is what it was."""
    n, rows, base, lens = ways["n"], ways["rows"], ways["base"], ways["lens"]
    k0, v0, ssm0, conv0 = ways["before"]
    k2, v2, ssm2, conv2 = ways["mixed_pools"]
    for mixed, before in ((k2, k0), (v2, v0)):
        changed = np.abs(mixed - before).reshape(
            mixed.shape[0], mixed.shape[1], mixed.shape[2], -1).max(axis=(0, 3))
        written = {(int(p), int(t)) for p, t in zip(*np.nonzero(changed))}
        riders = {(4 * i + CTX // PAGE, CTX % PAGE) for i in range(n)}
        chunk = {(base + 4 * i + (CHUNK + t) // PAGE, (CHUNK + t) % PAGE)
                 for i in range(rows) for t in range(lens[i])}
        assert written == riders | chunk
    for mixed, before in ((ssm2, ssm0), (conv2, conv0)):
        moved = np.abs(mixed - before).reshape(
            mixed.shape[0], mixed.shape[1], -1).max(axis=(0, 2))
        assert set(np.nonzero(moved)[0]) == set(range(n + rows))


def test_the_devices_counters_count_the_live_riders_and_the_chunk_each_once(ways):
    """``ssd_decode_tokens_total`` += the LIVE riders (an inert row counts
    nothing), the prefill counters the chunk's rows alone, the expert layers'
    rows those of both; an expert that both kinds of row route to is read
    once where two dispatches read it twice."""
    d, c, m = ways["did"]
    n, E = ways["n"], 8
    assert list(m[-4:]) == [n, *c[-3:]] and list(d[-4:]) == [n, 0, 0, 0]
    np.testing.assert_array_equal(m[:E], d[:E] + c[:E])          # rows by expert
    assert max(d[E], c[E]) <= m[E] <= d[E] + c[E]                # reads
    assert m[E + 1] == c[E + 1] == d[E + 1]                      # slots a dispatch


@pytest.mark.parametrize("impl", ["xla", "pallas_interpret"])
@pytest.mark.parametrize("fault, shows_in, silent_in", [
    ("chunk_rows_slot", ("riders_logits", "riders_ssm", "riders_conv", "chunk_ssm"), ()),
    ("state_not_advanced", ("riders_ssm",), ("riders_logits", "riders_conv", "riders_kv")),
    ("tail_not_shifted", ("riders_conv",), ("riders_logits", "riders_ssm", "riders_kv")),
    ("first_true", ("riders_logits", "riders_ssm", "riders_kv"), ()),
    ("kv_one_off", ("riders_kv",), ("riders_logits", "riders_ssm", "riders_conv")),
])
def test_a_planted_fault_is_told_apart(impl, fault, shows_in, silent_in):
    """The comparisons above are no formality: a rider that steps a chunk
    row's seat, whose state is not advanced, whose tail is not shifted, that
    starts from zero as a first chunk does, or whose K/V lands a position off
    FAILS the part it breaks (and, where the fault leaves this step's output
    alone, only the state it leaves behind tells)."""
    off = _off(_both_ways(impl, impl, 3, 4, 1, fault=fault))
    for part in shows_in:
        assert off[part] > FAULT, (part, off)
    for part in silent_in:
        assert off[part] < TOL, (part, off)
    if fault != "chunk_rows_slot":   # the chunk's rows do not see the fault
        assert max(off["chunk_logits"], off["chunk_ssm"], off["chunk_conv"],
                   off["chunk_kv"]) < _chunk_tol(impl), off


@pytest.mark.parametrize("impl", ["xla", "pallas_interpret"])
def test_an_empty_slot_leaves_pools_and_outputs_untouched(impl):
    """A slot in which nothing rides: the chunk's logits are the rider-free
    program's, and pages, states and tails (the null seat's too) are what that
    program leaves."""
    cfg, params = _toy(impl, impl)
    k, v = nh.init_kv_pages(cfg, POOL, PAGE)
    state = jax.tree.map(lambda a: a + 3.0, nh.init_state(cfg, SEATS))
    chunk = _chunk(np.random.RandomState(4), 2, 0, 8, 2, cfg.vocab_size, ragged=True)
    plain = _forward(cfg, params)(*chunk, k, v, state)
    slotted = _forward(cfg, params)(*chunk, k, v, state, _slot(0, 4, 8))
    assert slotted[0].shape[0] == 2 + 4
    np.testing.assert_allclose(slotted[0][:2], plain[0], atol=TOL, rtol=0)
    for a, b in zip(jax.tree.leaves(slotted[1:4]), jax.tree.leaves(plain[1:4])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=TOL, rtol=0)
    assert list(slotted[4][-4:]) == list(plain[4][-4:])   # no token stepped
    for pool in (slotted[3]["ssm"], slotted[3]["conv"]):     # the null seat stands
        np.testing.assert_array_equal(np.asarray(pool[:, SEATS]), 3.0)


def test_the_seat_a_rider_left_starts_its_next_owner_from_zero():
    """A rider ends in the dispatch, its seat goes to a new sequence: that
    sequence's first chunk reads what it would read in a pool nobody used."""
    cfg, params = _toy("xla", "xla")
    fwd = _forward(cfg, params)
    w_k, w_v = nh.init_kv_pages(cfg, POOL, PAGE)
    state = nh.init_state(cfg, SEATS)
    V = cfg.vocab_size
    _, k, v, used, _ = fwd(*_chunk(np.random.RandomState(1), 2, 0, 0, 0, V), w_k, w_v, state)
    _, k, v, used, _ = fwd(*_chunk(np.random.RandomState(4), 1, 0, 8, 2, V), k, v, used,
                           _slot(2, 4, 8))
    assert np.abs(np.asarray(used["ssm"][:, 0])).max() > 0     # seat 0 was stepped
    newcomer = _chunk(np.random.RandomState(9), 1, 0, 12, 0, V)   # seat 0 again
    after, *_ = fwd(*newcomer, k, v, used)
    fresh, *_ = fwd(*newcomer, w_k, w_v, state)
    np.testing.assert_allclose(np.asarray(after), np.asarray(fresh), atol=TOL, rtol=0)


def test_the_runner_hands_the_riders_seats_over_as_the_fifth_entry():
    """``StepInput.riders`` through ``ModelRunner.step``: the eighth entry (the
    riders' state slots) reaches ``forward`` behind the four a llama rider
    has; the result is the direct call's. A family with state refuses a slot
    without seats."""
    cfg, params = _toy("xla", "xla")
    host = jax.tree.map(np.asarray, params)
    runner = ModelRunner(cfg, params=host, num_pages=POOL, page_size=PAGE, seed=0,
                         state_slots=SEATS)
    assert runner.rider_refusal is None and runner.has_state
    sampling = lambda n: (np.zeros(n, np.float32), np.zeros(n, np.int32),  # noqa: E731
                          np.ones(n, np.float32))

    def step(chunk, riders=None):
        ids, pos, table, lens, slots = chunk
        inp = StepInput(ids, pos, table, lens, *sampling(len(lens)), state_slots=slots)
        if riders is not None:
            inp.riders = tuple(np.asarray(a) for a in riders[:4]) + sampling(4) + (
                np.asarray(riders[4]),)
        return runner.step(inp)

    V = cfg.vocab_size
    step(_chunk(np.random.RandomState(1), 2, 0, 0, 0, V))
    k, v, state = runner.k_pages, runner.v_pages, runner.state
    second = _chunk(np.random.RandomState(4), 1, 0, 8, 2, V)
    want = _forward(cfg, params)(*second, k, v, state, _slot(2, 4, 8))
    ids, logits = step(second, _slot(2, 4, 8))
    np.testing.assert_allclose(np.asarray(logits), np.asarray(want[0]), atol=TOL, rtol=0)
    np.testing.assert_array_equal(np.asarray(ids), np.asarray(want[0]).argmax(-1))
    np.testing.assert_allclose(
        np.asarray(runner.state["ssm"]), np.asarray(want[3]["ssm"]), atol=TOL, rtol=0)
    ids_, pos_, table_, lens_, slots_ = second
    seatless = StepInput(ids_, pos_, table_, lens_, *sampling(1), state_slots=slots_)
    seatless.riders = tuple(np.asarray(a) for a in _slot(2, 4, 8)[:4]) + sampling(4) + (None,)
    with pytest.raises(ValueError, match="riders need their state slots"):
        runner.step(seatless)


# -- the scheduler's plan ------------------------------------------------------


def _sched(seats, pages=48, **kw):
    kv = KVPageManager(pages, PAGE, state_slots=seats)
    return Scheduler(kv, max_model_len=128, prefill_chunk=16, decode_steps=4,
                     enable_prefix_caching=False, rider_refusal=None, max_num_seqs=4,
                     **kw), kv


def test_the_plan_gives_each_rider_its_seat_and_the_padding_the_null_one():
    sched, kv = _sched(seats=4)
    a, b = _seq("a", 6, 30), _seq("b", 9, 30)
    _decoding(sched, a, b)
    c = _seq("c", 40, 5)
    sched.add(c)
    batch = sched.schedule()
    r = batch.riders
    assert batch.kind == "prefill" and batch.seqs == [c] and r.seqs == [a, b]
    assert len({a.state_slot, b.state_slot, c.state_slot}) == 3
    assert list(r.state_slots) == [a.state_slot, b.state_slot, 4, 4]
    assert r.state_slots.dtype == np.int32
    assert list(batch.state_slots[:1]) == [c.state_slot]
    # a slot in which nobody rides names the null seat in every row
    sched2, _ = _sched(seats=4)
    sched2.add(_seq("d", 40, 5))
    empty = sched2.schedule().riders
    assert empty.seqs == [] and list(empty.state_slots) == [4] * 4
    # a family that keeps pages only plans none (as ScheduledBatch.state_slots)
    sched3, _ = _sched(seats=0)
    sched3.add(_seq("e", 40, 5))
    assert sched3.schedule().riders.state_slots is None


def test_a_rider_that_ends_in_the_dispatch_frees_its_seat_for_the_next_owner():
    sched, kv = _sched(seats=3)
    ends, goes_on = _seq("ends", 6, 2), _seq("goes-on", 6, 30)
    _decoding(sched, ends, goes_on)
    c = _seq("c", 40, 5)
    sched.add(c)
    mixed = sched.schedule()
    assert mixed.riders.seqs == [ends, goes_on] and kv.slots_in_use() == 3
    seat = ends.state_slot
    sched.pin(mixed)
    waits = _seq("waits", 20, 5)     # every seat is taken: it waits
    sched.add(waits)
    sched.apply_step(mixed, np.full((len(mixed.kv_lens) + sched.rider_slots,), 9),
                     eos_token_id=0)
    assert ends.finished and ends.finish_reason == "length"
    # the seat goes back when the dispatch has retired, not before
    assert ends.state_slot == seat and kv.slots_in_use() == 3
    sched.retire(mixed)
    assert ends.state_slot is None and kv.slots_in_use() == 2
    nxt = sched.schedule()
    while waits not in nxt.rows:
        sched.apply_step(nxt, np.full((len(nxt.kv_lens) + sched.rider_slots,), 9),
                         eos_token_id=0)
        nxt = sched.schedule()
    # the next owner of the seat starts at position 0: ``first`` in the program
    assert waits.state_slot == seat
    i = nxt.seqs.index(waits)
    assert nxt.positions[i, 0] == 0 and nxt.state_slots[i] == seat
    assert ends not in nxt.rows and goes_on in nxt.rows


# -- the engine ----------------------------------------------------------------

COUNTERS = ("prefill_rider_dispatches_total", "prefill_rider_rows_total",
            "prefill_riderless_dispatches_total")


def _drained(eng):
    deadline = time.time() + 10   # the last dispatch retires, its counters arrive
    while time.time() < deadline and (
            eng._inflight is not None or eng.scheduler.has_work()):
        time.sleep(0.01)
    return eng.stats()


@pytest.fixture(scope="module")
def served():
    """The script through one engine twice: as the runner reports (riders),
    then with the scheduler told that the family has none (alternation)."""
    tracing.get_flightrecorder().reset()
    name = "nemotron-h-debug-riders-f32"
    nh.PRESETS[name] = dataclasses.replace(
        nh.PRESETS["nemotron-h-debug"], dtype=jnp.float32)
    eng = LLMEngine(EngineConfig(
        model=name, max_model_len=256, max_num_seqs=4, num_pages=96, page_size=8,
        prefill_chunk=32, kv_cache_memory_gb=0.01, enable_prefix_caching=False))
    eng.start()
    try:
        assert eng.runner.rider_refusal is None and eng.scheduler.rider_refusal is None
        s0, step0 = _drained(eng), eng.step_idx
        with_riders = _serve(eng)
        stats = _drained(eng)
        events = [e["data"] for e in tracing.get_flightrecorder().events(kind="sched")
                  if e["step"] > step0]
        programs = set(eng.runner._programs)
        eng.scheduler.rider_refusal = "family"
        without = _serve(eng)
        return {"with": with_riders, "without": without, "s0": s0, "stats": stats,
                "events": events, "programs": programs, "after": _drained(eng),
                "all_programs": set(eng.runner._programs), "free": eng.kv.num_free(),
                "seats": sorted(eng.kv.free_slots)}
    finally:
        eng.stop()
        del nh.PRESETS[name]


def test_greedy_tokens_of_rows_that_rode_are_those_served_by_alternation(served):
    assert served["with"] == served["without"]
    assert [len(t) for t in served["with"]] == [n for _, _, n, _ in TRAFFIC]
    # every page and every seat came back
    assert served["free"] == 96 and served["seats"] == [0, 1, 2, 3]


def test_the_counters_say_how_often_rows_rode(served):
    s0, s = served["s0"], served["stats"]
    assert s["rider_refusal"] == ""
    assert s["prefill_dispatches_total"] >= s["prefill_rider_dispatches_total"] > 0
    assert s["prefill_rider_rows_total"] >= s["prefill_rider_dispatches_total"]
    # every prefill dispatch that had decode demand carried its riders
    assert sum(s["prefill_riderless_dispatches_total"].values()) == 0
    rode = [e["riders"] for e in served["events"] if e["batch_kind"] == "prefill"]
    assert sum(rode) == s["prefill_rider_rows_total"] - s0["prefill_rider_rows_total"]
    assert max(rode) <= 4
    # told that the family has none, the same traffic carries and counts none
    after = served["after"]
    assert after["rider_refusal"] == "family"
    assert after["prefill_dispatches_total"] > s["prefill_dispatches_total"]
    assert all(after[k] == s[k] for k in COUNTERS)


def test_the_device_counts_a_live_riders_step_and_nothing_else_new(served):
    """``ssd_decode_tokens_total`` (the device's, what ``kernel.ssd_decode_
    roofline`` divides by) against the host's own count of the same window
    (``engine._count_work``: a burst's steps a row, one a live rider): equal
    with riders and by alternation, so a mixed dispatch adds its live riders
    exactly; the chunks' tokens are the prompts' either way."""
    s0, s, after = served["s0"], served["stats"], served["after"]
    d = lambda a, b, key: b[key] - a[key]  # noqa: E731
    rode = d(s0, s, "prefill_rider_rows_total")
    assert rode > 0
    for a, b in ((s0, s), (s, after)):
        assert d(a, b, "ssd_decode_tokens_total") == d(a, b, "ssm_decode_tokens_total") > 0
        assert d(a, b, "ssd_prefill_tokens_total") == d(a, b, "ssm_prefill_tokens_total")
    # the same tokens were made both times: the riders' steps are steps the
    # bursts of the second pass make instead (a burst may run past a row's end)
    assert d(s0, s, "ssd_prefill_tokens_total") == d(s, after, "ssd_prefill_tokens_total")
    assert d(s0, s, "ssd_prefill_rows_total") == d(s, after, "ssd_prefill_rows_total")


def test_a_prefill_program_has_one_form_and_a_run_builds_no_more_of_them(served):
    """ONE slot width: a (batch, chunk, pages) bucket has one prefill program
    with the slot as it has one without (the second pass), whatever number of
    rows rode; the riders' arguments are the llama family's seven and the
    seats."""
    def prefill(programs):
        return [key for key in programs if key[2][1] > 1]
    mixed = prefill(served["programs"])
    # the argument shapes end with the slot's: ids, positions, page table,
    # lengths, three sampling vectors and the seats
    assert mixed and len({k[5][-8:] for k in mixed}) == 1
    assert mixed[0][5][-8:] == ((4, 1), (4, 1), (4, 32)) + ((4,),) * 5
    buckets = {(k[2], k[3]) for k in mixed}
    assert len(mixed) == len(buckets)                  # no bucket has two programs
    plain = prefill(served["all_programs"] - served["programs"])
    assert plain and len(plain) == len({(k[2], k[3]) for k in plain})
    assert {len(k[5]) for k in plain} == {len(mixed[0][5]) - 8}
    assert {(k[2], k[3]) for k in plain} <= buckets
