"""Scheduler prefill/decode fairness: with both kinds of work present the
scheduler must ALTERNATE prefill chunks and decode bursts — strict prefill
priority starves in-flight decodes under a steady arrival stream (the
multi-round-qa workload measured 64-token answers taking ~40 s). Chunked
prefill exists precisely so decode latency survives long prompts."""

import numpy as np
import pytest

from production_stack_tpu.engine.kv_manager import KVPageManager
from production_stack_tpu.engine.scheduler import (
    SamplingParams,
    Scheduler,
    Sequence,
)


def _mk_scheduler(**kw):
    kv = KVPageManager(num_pages=256, page_size=8)
    base = dict(max_num_seqs=8, max_model_len=512, prefill_chunk=16,
                prefill_batch=2, enable_prefix_caching=False, decode_steps=4,
                decode_pipeline=3)
    base.update(kw)
    return Scheduler(kv, **base)


def _drive(sched, steps=64):
    """Run the schedule/apply loop with fake sampled tokens; returns the
    sequence of batch kinds."""
    kinds = []
    for _ in range(steps):
        batch = sched.schedule()
        if batch is None:
            break
        kinds.append(batch.kind)
        if batch.kind == "prefill":
            toks = np.full((len(batch.kv_lens),), 7, np.int32)
        else:
            toks = np.full(
                (len(batch.kv_lens), sched.decode_steps * batch.bursts),
                7, np.int32,
            )
        sched.apply_step(batch, toks, eos_token_id=-1)
    return kinds


def test_alternates_prefill_and_decode():
    sched = _mk_scheduler()
    # one sequence already decoding...
    dec = Sequence("dec", prompt_ids=[1] * 8,
                   params=SamplingParams(max_tokens=64, ignore_eos=True))
    sched.add(dec)
    kinds = _drive(sched, steps=1)
    assert kinds == ["prefill"]  # its prompt prefills first
    # ...then a steady stream of long-prompt arrivals
    for i in range(4):
        sched.add(Sequence(f"p{i}", prompt_ids=[2] * 96,
                           params=SamplingParams(max_tokens=4, ignore_eos=True)))
    kinds = _drive(sched, steps=40)
    # decode bursts must interleave with the prefill chunks, not trail them:
    # the decoding row makes progress while 4 x 96-token prompts chunk through
    first_decodes = [i for i, k in enumerate(kinds) if k == "decode"]
    prefills_before_first_decode = len(
        [k for k in kinds[: first_decodes[0]] if k == "prefill"]
    )
    assert first_decodes[0] <= 1, kinds
    assert prefills_before_first_decode <= 1, kinds
    # and strict alternation holds while both kinds of work exist
    both_zone = kinds[: kinds.index("decode") + 6]
    assert all(
        a != b for a, b in zip(both_zone, both_zone[1:])
    ), kinds


def test_no_chaining_while_prefills_pending():
    sched = _mk_scheduler()
    dec = Sequence("dec", prompt_ids=[1] * 8,
                   params=SamplingParams(max_tokens=64, ignore_eos=True))
    sched.add(dec)
    _drive(sched, steps=1)  # prefill dec's prompt
    sched.add(Sequence("p0", prompt_ids=[2] * 96,
                       params=SamplingParams(max_tokens=4, ignore_eos=True)))
    batch = sched.schedule()
    if batch.kind == "prefill":
        toks = np.full((len(batch.kv_lens),), 7, np.int32)
        sched.apply_step(batch, toks, eos_token_id=-1)
        batch = sched.schedule()
    assert batch.kind == "decode"
    assert batch.bursts == 1  # a chain would delay the next prefill chunk


def test_pure_decode_still_chains():
    sched = _mk_scheduler()
    dec = Sequence("dec", prompt_ids=[1] * 8,
                   params=SamplingParams(max_tokens=64, ignore_eos=True))
    sched.add(dec)
    _drive(sched, steps=1)
    batch = sched.schedule()
    assert batch.kind == "decode"
    assert batch.bursts == 3  # quiescent batch: full decode_pipeline


def test_decode_fallback_replans_from_live_state():
    """Page-pressure preemption inside _plan_decode evicts prefilling rows
    (pages freed, moved back to waiting); the prefill fallback must re-derive
    its candidates from self.running — planning a chunk for a preempted seq
    would scatter its KV into page 0, a page another sequence owns."""
    kv = KVPageManager(num_pages=4, page_size=8)  # 32 KV slots total
    sched = Scheduler(kv, max_num_seqs=4, max_model_len=256, prefill_chunk=8,
                      prefill_batch=1, enable_prefix_caching=False,
                      decode_steps=4)
    dec = Sequence("dec", prompt_ids=[1] * 8,
                   params=SamplingParams(max_tokens=64, ignore_eos=True))
    sched.add(dec)
    _drive(sched, steps=1)  # prefill dec (1 page)
    # a long prompt that will eat the remaining pages while chunking
    sched.add(Sequence("p0", prompt_ids=[2] * 24,
                       params=SamplingParams(max_tokens=4, ignore_eos=True)))
    for _ in range(24):
        batch = sched.schedule()
        if batch is None:
            break
        # invariant: every planned sequence is live and owns its pages
        for s in batch.seqs:
            assert s in sched.running
            assert s.pages, f"{s.seq_id} planned with no pages ({batch.kind})"
        toks = (
            np.full((len(batch.kv_lens),), 7, np.int32)
            if batch.kind == "prefill"
            else np.full((len(batch.kv_lens), sched.decode_steps * batch.bursts),
                         7, np.int32)
        )
        sched.apply_step(batch, toks, eos_token_id=-1)


def test_chains_when_admission_blocked():
    """Oversubscription (waiting requests but every seat taken): chaining
    must still engage — blocked arrivals cannot start regardless, and the
    chain drains the running set (and so the queue) bursts-fold faster on
    fetch-RTT-bound hosts. This is what decides multi-round-qa TTFT."""
    sched = _mk_scheduler(max_num_seqs=1)
    dec = Sequence("dec", prompt_ids=[1] * 8,
                   params=SamplingParams(max_tokens=64, ignore_eos=True))
    sched.add(dec)
    _drive(sched, steps=1)  # prefill; dec now holds the only seat
    sched.add(Sequence("blocked", prompt_ids=[2] * 8,
                       params=SamplingParams(max_tokens=4, ignore_eos=True)))
    batch = sched.schedule()
    assert batch.kind == "decode"
    assert batch.bursts == 3, "seat-blocked waiting work must not stop chains"


def test_chain_depth_grows_on_quiescent_streak():
    """Consecutive fully-chained dispatches with nothing else runnable double
    the chain depth up to decode_pipeline_cap (each chained dispatch pays one
    fetch round trip, so depth sets the RTT share of decode time)."""
    sched = _mk_scheduler(decode_pipeline=2)
    dec = Sequence("dec", prompt_ids=[1] * 8,
                   params=SamplingParams(max_tokens=512, ignore_eos=True))
    sched.add(dec)
    _drive(sched, steps=1)
    depths = []
    for _ in range(4):
        batch = sched.schedule()
        assert batch.kind == "decode"
        depths.append(batch.bursts)
        toks = np.full(
            (len(batch.kv_lens), sched.decode_steps * batch.bursts), 7, np.int32
        )
        sched.apply_step(batch, toks, eos_token_id=-1)
    assert depths[0] == 2  # first chain: configured decode_pipeline
    assert depths[1] > depths[0]  # streak doubles it...
    assert max(depths) <= sched.decode_pipeline_cap  # ...up to the cap
    # an arrival-rate signal caps the depth back down (adaptive)
    sched.arrival_rate = 1000.0
    sched.burst_seconds = 1.0
    batch = sched.schedule()
    assert batch.bursts == 1


def test_chain_floor_requires_runahead_when_burst_exceeds_budget():
    """When a SINGLE burst already exceeds the 100 ms chain-wait budget
    (long-context decode ~0.5 s/burst) and admission is OPEN, the one-extra-
    burst floor is only justified by run-ahead prefill (it starts an arrival
    DURING the chain). Without run-ahead — engine has none, or the batch
    wants logprobs — an arrival would wait a full extra burst for nothing,
    so the dispatch must fall back to bursts=1."""
    def quiesced(**kw):
        s = _mk_scheduler(**kw)
        dec = Sequence("dec", prompt_ids=[1] * 8,
                       params=SamplingParams(max_tokens=512, ignore_eos=True))
        s.add(dec)
        _drive(s, steps=1)
        s.burst_seconds = 0.5   # one burst >> chain_wait_budget_s (0.1)
        s.arrival_rate = 0.0    # admission OPEN, quiescent
        return s

    # run-ahead available (LLMEngine sets this): the floor keeps one
    # extra burst
    sched = quiesced()
    sched.runahead_available = True
    assert sched.schedule().bursts == 2
    # a driver without the run-ahead path (bare-scheduler default): no
    # chaining past the budget
    assert quiesced().schedule().bursts == 1
    # logprobs batches fetch whole-chain (no run-ahead dispatch behind
    # them), so they get no floor either
    sched = _mk_scheduler()
    sched.runahead_available = True
    dec = Sequence("dec", prompt_ids=[1] * 8,
                   params=SamplingParams(max_tokens=512, ignore_eos=True,
                                         logprobs=2))
    sched.add(dec)
    _drive(sched, steps=1)
    sched.burst_seconds = 0.5
    sched.arrival_rate = 0.0
    assert sched.schedule().bursts == 1
    # blocked admission is unaffected: chaining still engages in full
    sched = quiesced(max_num_seqs=1)
    sched.add(Sequence("blocked", prompt_ids=[2] * 8,
                       params=SamplingParams(max_tokens=4, ignore_eos=True)))
    assert sched.schedule().bursts == 3


def test_runahead_prefill_is_disjoint_from_chain():
    """schedule_prefill_runahead plans prefill work ONLY for sequences
    outside the in-flight chain, admitting fresh arrivals; chunk accounting
    via apply_step lets repeated calls walk the whole prompt."""
    sched = _mk_scheduler()
    dec = Sequence("dec", prompt_ids=[1] * 8,
                   params=SamplingParams(max_tokens=64, ignore_eos=True))
    sched.add(dec)
    _drive(sched, steps=1)
    chain = sched.schedule()
    assert chain.kind == "decode"
    # a new request arrives mid-chain
    sched.add(Sequence("new", prompt_ids=[2] * 32,
                       params=SamplingParams(max_tokens=4, ignore_eos=True)))
    exclude = {id(s) for s in chain.seqs}
    ra = sched.schedule_prefill_runahead(exclude)
    assert ra is not None and ra.kind == "prefill"
    assert all(id(s) not in exclude for s in ra.seqs)
    assert ra.seqs[0].seq_id == "new"
    sched.apply_step(ra, np.full((len(ra.kv_lens),), 7, np.int32), -1)
    ra2 = sched.schedule_prefill_runahead(exclude)
    assert ra2 is not None and ra2.chunk_sizes[0] == 16  # next chunk
    sched.apply_step(ra2, np.full((len(ra2.kv_lens),), 7, np.int32), -1)
    assert sched.schedule_prefill_runahead(exclude) is None  # prompt done
    # the chain itself still applies cleanly afterwards
    toks = np.full(
        (len(chain.kv_lens), sched.decode_steps * chain.bursts), 7, np.int32
    )
    sched.apply_step(chain, toks, eos_token_id=-1)


def test_interleave_gate_on_resident_decode_demand():
    """A big resident decode batch must interleave even when the prefill
    backlog is SHORT (< 2 chunks): each skipped interleave stalls that many
    live streams for a whole chunk. The old backlog-only gate made them
    wait out the entire prefill."""
    sched = _mk_scheduler(prefill_batch=2)
    # 4 sequences already decoding (>= max(2, prefill_batch) demand)
    for i in range(4):
        sched.add(Sequence(f"d{i}", prompt_ids=[1] * 8,
                           params=SamplingParams(max_tokens=64,
                                                 ignore_eos=True)))
    kinds = _drive(sched, steps=1)
    assert kinds == ["prefill"]
    # one SHORT prompt arrives: backlog (24) < 2 * prefill_chunk (32)
    sched.add(Sequence("short", prompt_ids=[2] * 24,
                       params=SamplingParams(max_tokens=4, ignore_eos=True)))
    kinds = _drive(sched, steps=4)
    # the decode batch must not trail the whole prefill: alternation starts
    # within one chunk of the prompt
    assert "decode" in kinds[:2], kinds


def test_lone_long_prompt_never_interleaves_without_decoders():
    """No decode-ready sequences -> no interleave slots: a lone long prompt
    runs chunk after chunk with zero decode dispatches in between."""
    sched = _mk_scheduler()
    sched.add(Sequence("long", prompt_ids=[2] * 128,
                       params=SamplingParams(max_tokens=4, ignore_eos=True)))
    kinds = _drive(sched, steps=8)  # 128 / 16 = 8 chunks
    assert kinds == ["prefill"] * 8, kinds


def test_small_decode_batch_short_backlog_keeps_strict_priority():
    """One decoding row + a short prefill flurry (backlog < 2 chunks,
    demand < prefill_batch): the fast strict-priority path clears the
    flurry first — alternating would pay a fetch round trip per burst."""
    sched = _mk_scheduler(prefill_batch=2)
    dec = Sequence("dec", prompt_ids=[1] * 8,
                   params=SamplingParams(max_tokens=64, ignore_eos=True))
    sched.add(dec)
    _drive(sched, steps=1)
    sched.add(Sequence("p0", prompt_ids=[2] * 24,
                       params=SamplingParams(max_tokens=4, ignore_eos=True)))
    batch = sched.schedule()
    assert batch.kind == "prefill"  # 24 < 2*16 backlog, demand 1 < 2


@pytest.mark.parametrize("floor, widths", [
    (0, {2, 4}),      # the scheduler's own buckets follow the contexts
    (16, {16}),       # one width whatever the contexts
    (24, {32}),       # rounded up to a bucket
    (4096, {128}),    # never wider than max_model_len's own bucket
], ids=["none", "floor-16", "rounds-up", "capped"])
def test_decode_page_bucket_floor_leaves_one_width(floor, widths):
    """--decode-page-bucket-floor pads every decode dispatch's page table to
    one width, so the decode programs differ by batch bucket alone; prefill
    dispatches keep the scheduler's buckets (their attention pays for the
    width), and 0 changes nothing."""
    sched = _mk_scheduler(decode_pipeline=1, decode_page_bucket_floor=floor)
    # a long row that ends with the first burst, a short one that goes on
    for i, (n, out) in enumerate(((24, 3), (4, 8))):
        sched.add(Sequence(f"s{i}", prompt_ids=[1] * n,
                           params=SamplingParams(max_tokens=out, ignore_eos=True)))
    seen = {"prefill": set(), "decode": set()}
    for _ in range(16):
        batch = sched.schedule()
        if batch is None:
            break
        seen[batch.kind].add(batch.page_table.shape[1])
        shape = ((len(batch.kv_lens),) if batch.kind == "prefill"
                 else (len(batch.kv_lens), sched.decode_steps * batch.bursts))
        sched.apply_step(batch, np.full(shape, 7, np.int32), eos_token_id=-1)
    assert seen["decode"] == widths
    assert seen["prefill"] <= {2, 4}
