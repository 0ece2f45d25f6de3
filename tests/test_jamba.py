"""Jamba on the normal path: pages for the attention layers and a slot of
recurrent state a sequence for the state-space layers, against the plain
reference (``perfbench/reference/jamba.py``, loaded by path: one reference
file, no second copy).

On the toy (two periods of 4, attention at offset 1, float32 so that only the
order of summation differs): prefill in chunks, then decode in bursts, through
the engine's own scheduler, page manager, slots and step programs, agrees with
the reference's full forward pass in LOGITS; so does the forward called by
hand, and there each of seven broken models exceeds the tolerance.
"""

import asyncio
import dataclasses
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

_ROOT = os.path.join(os.path.dirname(os.path.abspath(__file__)), os.pardir)
sys.path.insert(0, os.path.join(_ROOT, "perfbench"))
from reference import jamba as reference  # noqa: E402

from production_stack_tpu.engine.config import EngineConfig  # noqa: E402
from production_stack_tpu.engine.engine import LLMEngine  # noqa: E402
from production_stack_tpu.engine.kv_manager import KVPageManager  # noqa: E402
from production_stack_tpu.engine.scheduler import (  # noqa: E402
    SamplingParams,
    Scheduler,
    Sequence,
)
from production_stack_tpu.models import jamba  # noqa: E402
from production_stack_tpu.ops.pallas import ssm_scan  # noqa: E402

# the toy as a published config.json would state it
DOC = {
    "model_type": "jamba", "hidden_size": 128, "intermediate_size": 256,
    "num_hidden_layers": 8, "num_attention_heads": 4, "num_key_value_heads": 1,
    "head_dim": 32, "attn_layer_period": 4, "attn_layer_offset": 1,
    "mamba_d_state": 16, "mamba_d_conv": 4, "mamba_expand": 2, "mamba_dt_rank": 8,
    "mamba_conv_bias": True, "mamba_proj_bias": False, "num_experts": 1,
    "rms_norm_eps": 1e-6, "vocab_size": 512, "max_position_embeddings": 256,
    "tie_word_embeddings": True, "sliding_window": None,
}
# float32 on both sides: only the order of summation differs
TOLERANCE = 5e-4
PAGE, CHUNK, BURST = 8, 16, 4
SEED = 3
# what the cell on the chip judges with (bf16 weights and activations there)
with open(os.path.join(_ROOT, "perfbench", "cells", "jamba2-3b.chat.json")) as _f:
    CELL_TOLERANCE = json.load(_f)["correctness"]["reference"]["tolerance"]


def _logprobs(logits):
    logits = np.asarray(logits, np.float64)
    return logits - logits.max() - np.log(np.sum(np.exp(logits - logits.max())))


@pytest.fixture(scope="module")
def toy():
    cfg = dataclasses.replace(jamba.JambaConfig.from_hf_config(DOC), dtype=jnp.float32)
    assert cfg == dataclasses.replace(
        jamba.PRESETS["jamba-debug"], dtype=jnp.float32)
    assert cfg.layer_kinds == ("ssm", "attn", "ssm", "ssm") * 2
    return cfg, jamba.init_params(cfg, jax.random.key(SEED))


# -- the forward, called by hand ---------------------------------------------------

def served_logprobs(cfg, params, tokens, n_prompt, *, impl="xla"):
    """Prefill ``n_prompt`` tokens in chunks of CHUNK, then decode the rest one
    by one, through pages and a slot of a pool that its last owner left DIRTY;
    the log-probabilities after the prompt and after every step."""
    cfg = dataclasses.replace(cfg, ssm_impl=impl, attn_impl="xla")
    pages = -(-len(tokens) // PAGE) + 1
    k, v = jamba.init_kv_pages(cfg, pages + 1, PAGE)
    state = jax.tree.map(lambda a: a + 3.0, jamba.init_state(cfg, 3))
    table = jnp.arange(1, pages + 1, dtype=jnp.int32)[None]
    slots = jnp.asarray([1], jnp.int32)
    fwd = jax.jit(lambda ids, pos, k, v, lens, st: jamba.forward(
        params, cfg, ids, pos, k, v, table, lens, state=st, state_slots=slots))
    out = []
    for lo in range(0, n_prompt, CHUNK):
        c = min(CHUNK, n_prompt - lo)
        ids = np.zeros((1, CHUNK), np.int32)
        pos = np.full((1, CHUNK), -1, np.int32)
        ids[0, :c], pos[0, :c] = tokens[lo:lo + c], np.arange(lo, lo + c)
        logits, k, v, state = fwd(ids, pos, k, v, jnp.asarray([lo + c]), state)
    out.append(_logprobs(logits[0]))
    for i in range(n_prompt, len(tokens)):
        logits, k, v, state = fwd(
            np.asarray([[tokens[i]]], np.int32), np.asarray([[i]], np.int32),
            k, v, jnp.asarray([i + 1]), state)
        out.append(_logprobs(logits[0]))
    return out


def worst_against_reference(served, params, tokens, n_prompt):
    """max |dlogprob| over the top-20 of every step (what the check on the chip
    compares) and over the whole vocabulary."""
    top, whole = 0.0, 0.0
    for step, got in enumerate(served):
        ref = np.asarray(reference.next_token_logprobs(
            params, DOC, tokens[:n_prompt + step], pad_to=64), np.float64)
        whole = max(whole, float(np.max(np.abs(got - ref))))
        top = max(top, float(np.max(np.abs(
            np.sort(got)[-20:] - np.sort(ref)[-20:]))))
    return top, whole


TOKENS = [int(t) for t in np.random.default_rng(0).integers(0, 512, 45)]
N_PROMPT = 37  # three chunks: the state crosses two chunk boundaries


@pytest.mark.parametrize("impl", ["xla", "pallas_interpret"])
def test_forward_in_chunks_then_steps_agrees_with_the_reference(toy, impl):
    cfg, params = toy
    served = served_logprobs(cfg, params, TOKENS, N_PROMPT, impl=impl)
    top, whole = worst_against_reference(served, params, TOKENS, N_PROMPT)
    assert whole < TOLERANCE, (top, whole)


def _drop(which, good):
    """``jamba._norm_dt_b_c`` with one of its three norms left out."""
    def norms(dt, b_mat, c_mat, lp, eps):
        return tuple(
            raw if name == which else normed
            for name, raw, normed in zip(
                ("dt", "b", "c"), (dt, b_mat, c_mat), good(dt, b_mat, c_mat, lp, eps))
        )
    return norms


@pytest.mark.parametrize("broken", [
    "dt_norm", "b_norm", "c_norm", "b_conv", "bf16_state", "attn_offset",
    "state_not_reset",
])
def test_the_reference_tells_a_broken_model(toy, broken, monkeypatch):
    cfg, params = toy
    gross = True  # it also exceeds what the cell on the chip tolerates
    if broken.endswith("_norm"):
        monkeypatch.setattr(
            jamba, "_norm_dt_b_c", _drop(broken[:-5], jamba._norm_dt_b_c))
    elif broken == "b_conv":
        params = dict(params, layers=dict(
            params["layers"], conv_b=jnp.zeros_like(params["layers"]["conv_b"])))
    elif broken == "bf16_state":
        cfg = dataclasses.replace(cfg, ssm_state_dtype=jnp.bfloat16)
        gross = False  # a rounding of the state: over the float32 tolerance only
    elif broken == "attn_offset":
        cfg = dataclasses.replace(cfg, attn_layer_offset=2)
    else:
        rows = jamba._rows
        monkeypatch.setattr(jamba, "_rows", lambda positions, slots: dict(
            rows(positions, slots), first=jnp.zeros((positions.shape[0],), bool)))
    served = served_logprobs(cfg, params, TOKENS, N_PROMPT)
    top, whole = worst_against_reference(served, toy[1], TOKENS, N_PROMPT)
    assert whole > 10 * TOLERANCE, (broken, top, whole)
    if gross:
        assert top > CELL_TOLERANCE, (broken, top, whole)


# -- the kernel against the jax.numpy path ---------------------------------------------

@pytest.mark.parametrize("B,T,Di,N", [(5, 1, 256, 16), (3, 512, 256, 16), (2, 128, 1024, 8)],
                         ids=["decode-T1", "prefill-T512", "two-Di-blocks"])
def test_scan_kernel_matches_the_jnp_path_with_ragged_rows(B, T, Di, N):
    ks = jax.random.split(jax.random.key(B * T), 8)
    slots_n, layers = 6, 3
    lens = jnp.asarray(([T, T // 2 + 1, 0, 3, 1] * 2)[:B], jnp.int32).clip(0, T)
    valid = (jnp.arange(T)[None, :] < lens[:, None])[..., None]
    u = jax.random.normal(ks[0], (B, T, Di)) * valid
    z = jax.random.normal(ks[1], (B, T, Di))
    delta = jax.nn.softplus(jax.random.normal(ks[2], (B, T, Di)) - 2.0) * valid
    b_mat, c_mat = (jax.random.normal(k, (B, T, N)) for k in ks[3:5])
    a = -jnp.exp(0.5 * jax.random.normal(ks[5], (N, Di)))
    d = jax.random.normal(ks[6], (Di,))
    pool = jax.random.normal(ks[7], ssm_scan.state_pool_shape(layers, slots_n, N, Di))
    # a padded row (lens 0) reads and writes the null slot
    slots = jnp.asarray(([2, 0, slots_n, 4, 5] * 2)[:B], jnp.int32)
    first = jnp.asarray(([True, False, False, False, True] * 2)[:B])
    args = (u, delta, z, b_mat, c_mat, a, d, pool, slots, first, lens, jnp.int32(1))
    y0, p0 = ssm_scan.selective_scan(*args, impl="xla")
    y1, p1 = ssm_scan.selective_scan(*args, impl="pallas_interpret")
    np.testing.assert_allclose(y1, y0, atol=2e-5, rtol=1e-5)
    np.testing.assert_allclose(p1[:, :slots_n], p0[:, :slots_n], atol=2e-5, rtol=1e-5)
    # padded positions give nothing, other layers and slots are untouched
    assert not np.any(np.asarray(y1) * ~np.asarray(valid))
    untouched = np.ones(pool.shape[:2], bool)
    untouched[1, np.asarray(slots)] = False
    np.testing.assert_array_equal(np.asarray(p1)[untouched], np.asarray(pool)[untouched])
    # a row that starts its sequence ignored what the slot held
    fresh = ssm_scan.selective_scan(
        *args[:7], jnp.zeros_like(pool), *args[8:], impl="pallas_interpret")[1]
    np.testing.assert_allclose(fresh[1, 2], p1[1, 2], atol=1e-6)


# -- the scheduler's slots -------------------------------------------------------------

def _sched(slots=3, pages=40):
    kv = KVPageManager(pages, PAGE, state_slots=slots)
    return kv, Scheduler(
        kv, max_num_seqs=slots, max_model_len=256, prefill_chunk=CHUNK,
        prefill_batch=4, enable_prefix_caching=False, decode_steps=BURST)


def _seq(name, n_prompt, n_out):
    return Sequence(name, list(range(1, n_prompt + 1)),
                    SamplingParams(max_tokens=n_out, temperature=0.0, ignore_eos=True))


def _run(sched, until):
    while sched.has_work() and not until():
        batch = sched.schedule()
        assert batch is not None
        B = len(batch.kv_lens)
        assert batch.state_slots.shape == (B,)
        held = [s.state_slot for s in batch.seqs]
        assert list(batch.state_slots[:len(held)]) == held
        assert len(set(held)) == len(held) and None not in held
        assert all(batch.state_slots[len(held):] == sched.kv.state_slots)  # null slot
        shape = (B,) if batch.kind == "prefill" else (B, BURST)
        sched.apply_step(batch, np.full(shape, 7, np.int32), eos_token_id=0)


def test_slots_are_taken_at_admission_and_never_leak():
    kv, sched = _sched()
    seqs = [_seq(f"s{i}", 10 + 9 * i, 12) for i in range(5)]
    for s in seqs:
        sched.add(s)
    _run(sched, lambda: sched.num_running() == 3 and not any(
        s.in_prefill for s in sched.running))
    assert kv.slots_in_use() == 3 and sched.num_waiting() == 2
    assert all(s.state_slot is None for s in sched.waiting)
    sched.abort(sched.running[0].seq_id)      # a running sequence
    sched.abort(sched.waiting[-1].seq_id)     # one that never held a slot
    assert kv.slots_in_use() == 2
    victim = sched.running[-1]
    sched._preempt(victim)
    assert victim.state_slot is None and kv.slots_in_use() == 1
    _run(sched, lambda: False)
    assert all(s.finished for s in seqs)
    assert kv.slots_in_use() == 0 and sorted(kv.free_slots) == [0, 1, 2]
    assert kv.num_free() == kv.num_pages
    with pytest.raises(AssertionError):
        kv.free_slot(1)  # a double free is a bug, not a no-op


def test_a_preempted_sequence_computes_its_output_again_before_it_decodes():
    kv, sched = _sched()
    s = _seq("p", 20, 30)
    sched.add(s)
    _run(sched, lambda: len(s.output_ids) >= 6)
    had = list(s.output_ids)
    sched._preempt(s)
    assert s.recompute_len == 20 + len(had) - 1 and s.in_prefill
    batch = sched.schedule()
    assert batch.kind == "prefill" and list(batch.input_ids[0, :CHUNK]) == s.prompt_ids[:CHUNK]
    while s.in_prefill:
        sched.apply_step(batch, np.full((len(batch.kv_lens),), 9, np.int32), 0)
        if s.in_prefill:
            batch = sched.schedule()
    # the recomputed prefill ended on the last output but one; nothing was emitted
    assert s.output_ids == had and s.num_computed == 20 + len(had) - 1
    batch = sched.schedule()
    assert batch.kind == "decode" and batch.input_ids[0, 0] == had[-1]
    assert batch.positions[0, 0] == 20 + len(had) - 1


# -- the engine's normal path ----------------------------------------------------------

@pytest.fixture(scope="module")
def engine(toy):
    cfg, _ = toy
    jamba.PRESETS["jamba-test-f32"] = cfg
    # 14 pages of 8: two sequences of ~30 + 40 tokens cannot both grow, so the
    # page pool preempts one of them mid-decode
    eng = LLMEngine(EngineConfig(
        model="jamba-test-f32", max_model_len=256, max_num_seqs=3, num_pages=14,
        page_size=PAGE, prefill_chunk=CHUNK, decode_steps=BURST, seed=SEED))
    eng.start()
    yield eng
    eng.stop()
    del jamba.PRESETS["jamba-test-f32"]


def _generate(engine, jobs):
    """Run (prompt ids, n) jobs at once; per job (token ids, per-token top-20)."""
    async def one(i, prompt, n):
        ids, tops = [], []
        async for out in engine.generate(
            f"j{i}-{np.random.randint(1 << 30)}", prompt_token_ids=list(prompt),
            params=SamplingParams(max_tokens=n, temperature=0.0, ignore_eos=True,
                                  logprobs=20),
        ):
            ids += out.token_ids
            tops += out.logprobs or []
        return ids, tops

    async def run():
        return await asyncio.gather(*(one(i, p, n) for i, (p, n) in enumerate(jobs)))
    return asyncio.run(run())


def _check(params, prompt, ids, tops):
    """Every generated token's top-20 log-probabilities, value for value,
    against the reference's distribution after the same prefix."""
    assert len(ids) == len(tops)
    worst = 0.0
    for step, entry in enumerate(tops):
        ref = np.asarray(reference.next_token_logprobs(
            params, DOC, list(prompt) + ids[:step], pad_to=96))
        got = np.asarray(entry["top_logprobs"])
        worst = max(worst, float(np.max(np.abs(got - ref[entry["top_ids"]]))))
        assert ids[step] == entry["top_ids"][0]  # greedy
    return worst


def test_engine_serves_mixed_lengths_recycles_slots_and_resumes_after_preemption(engine, toy):
    _, params = toy
    assert engine.runner.params["layers"]["conv_b"].dtype == jnp.float32
    rng = np.random.default_rng(5)
    prompt = lambda n: [int(t) for t in rng.integers(1, 512, n)]  # noqa: E731
    # 1: a batch of mixed lengths (one chunk, two chunks, three chunks)
    jobs = [(prompt(5), 9), (prompt(21), 10), (prompt(37), 6)]
    s0 = engine.stats()
    for (p, n), (ids, tops) in zip(jobs, _generate(engine, jobs)):
        assert len(ids) == n and _check(params, p, ids, tops) < TOLERANCE
    s1 = engine.stats()
    assert s1["ssm_state_slots"] == 3 and s1["ssm_state_slots_in_use"] == 0
    assert s1["ssm_prefill_tokens_total"] - s0["ssm_prefill_tokens_total"] == 5 + 21 + 37
    # the first token of each comes from its prefill, the rest from bursts
    assert s1["ssm_decode_tokens_total"] - s0["ssm_decode_tokens_total"] >= 8 + 9 + 5
    assert s1["step_program_store_bypassed"] == {}
    # 2: a slot recycled from a finished sequence (every slot has been used)
    p = prompt(19)
    (ids, tops), = _generate(engine, [(p, 7)])
    assert _check(params, p, ids, tops) < TOLERANCE
    # 3: two sequences the pool cannot hold: one is preempted and resumed
    before = engine.stats()["num_preemptions_total"]
    jobs = [(prompt(30), 40), (prompt(28), 40)]
    for (p, n), (ids, tops) in zip(jobs, _generate(engine, jobs)):
        assert len(ids) == n and _check(params, p, ids, tops) < TOLERANCE
    after = engine.stats()
    assert after["num_preemptions_total"] > before
    assert after["ssm_state_slots_in_use"] == 0 and engine.kv.num_free() == 14


def test_stats_and_metrics_carry_the_state_surface(engine):
    from aiohttp.test_utils import TestClient, TestServer

    from production_stack_tpu.engine.api_server import EngineServer

    s = engine.stats()
    assert s["ssm_kernel"] == "xla" and "no TPU backend" in s["ssm_kernel_reason"]
    assert s["ssm_state_bytes"] == 4 * engine.model_cfg.state_bytes_per_slot
    assert set(s["state_family_off"]) == {"prefix_caching", "migration"}
    assert engine.scheduler.enable_prefix_caching is False and engine.migration is None
    assert len(s["state_family_refusals"]) == 9
    assert all("recurrent state" in why or "fp pages" in why or "llama" in why
               or "one device" in why for why in s["state_family_refusals"].values())

    async def scrape():
        async with TestClient(TestServer(EngineServer(engine.cfg, engine).build_app())) as c:
            return await (await c.get("/metrics")).text()
    text = asyncio.run(scrape())
    for name in ("ssm_state_slots", "ssm_state_slots_in_use", "ssm_state_bytes",
                 "ssm_prefill_tokens_total", "ssm_decode_tokens_total"):
        assert f"vllm:{name}{{" in text, name


# -- what the family refuses at start-up --------------------------------------------------

@pytest.mark.parametrize("option,reason", [
    (dict(kv_offload_cpu_gb=1.0), "no state snapshot"),
    (dict(kv_offload_dir="/nonexistent"), "no state snapshot"),
    (dict(warm_start=True), "no state snapshot"),
    (dict(kv_directory_url="http://127.0.0.1:1"), "no state snapshot"),
    (dict(kv_fabric=True), "no state snapshot"),
    (dict(kv_role="producer", kv_peer_url="http://127.0.0.1:1"), "no state snapshot"),
    (dict(speculative_k=3), "cannot be taken back"),
    (dict(enable_lora=True), "llama family"),
    (dict(kv_cache_dtype="int8"), "fp pages"),
    (dict(tensor_parallel_size=2), "one device"),
    (dict(pipeline_parallel_size=2), "one device"),
    (dict(sequence_parallel_size=2), "one device"),
], ids=lambda v: next(iter(v)) if isinstance(v, dict) else None)
def test_the_family_refuses_what_cannot_serve_it_at_start_up(option, reason):
    with pytest.raises(ValueError, match="keeps recurrent state") as e:
        LLMEngine(EngineConfig(model="jamba-debug", max_model_len=256, num_pages=16,
                               page_size=PAGE, **option))
    assert reason in str(e.value) and "cannot start with --" in str(e.value)
