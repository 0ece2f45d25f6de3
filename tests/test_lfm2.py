"""LFM2-MoE on the normal path: sparse experts grouped by expert, a
short-convolution mixer whose tail rides the state slots, per-head q/k norms,
against the plain reference (``perfbench/reference/lfm2_moe.py``, loaded by
path: one reference file, no second copy).

On the toy (2 dense + 6 sparse layers in the published order, 8 experts top-2,
seeded weights with a non-zero ``expert_bias``, float32 so that only the order
of summation differs): prefill in chunks, then decode steps, through pages and
slots, agrees with the reference's full forward pass in LOGITS; each of five
broken models exceeds the tolerance; the grouped product equals the plain
per-token sum; the shares of a layer add up to the layer; the engine's own
scheduler, slots, step programs and counters serve it.
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
from reference import lfm2_moe as reference  # noqa: E402

from production_stack_tpu.engine.config import EngineConfig  # noqa: E402
from production_stack_tpu.engine.engine import LLMEngine  # noqa: E402
from production_stack_tpu.engine.scheduler import SamplingParams  # noqa: E402
from production_stack_tpu.models import lfm2  # noqa: E402
from production_stack_tpu.ops import moe  # noqa: E402

# the toy as a published config.json would state it
DOC = {
    "model_type": "lfm2_moe", "hidden_size": 128, "intermediate_size": 256,
    "moe_intermediate_size": 64, "num_hidden_layers": 8,
    "layer_types": ["conv", "conv", "full_attention", "conv", "conv", "conv",
                    "full_attention", "conv"],
    "num_dense_layers": 2, "num_experts": 8, "num_experts_per_tok": 2,
    "norm_topk_prob": True, "use_expert_bias": True, "routed_scaling_factor": 1,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 32,
    "conv_L_cache": 3, "conv_bias": False, "rope_theta": 1000000,
    "norm_eps": 1e-5, "vocab_size": 512, "max_position_embeddings": 256,
}
# float32 on both sides: only the order of summation differs (read 1e-5); a
# router near-tie ordered the other way would read ~1e-1, far over it
TOLERANCE = 5e-4
PAGE, CHUNK, BURST = 8, 16, 4
SEED = 3
with open(os.path.join(_ROOT, "perfbench", "cells", "lfm2-8b-a1b-d16.chat.json")) as _f:
    CELL_TOLERANCE = json.load(_f)["correctness"]["reference"]["tolerance"]


def _logprobs(logits):
    logits = np.asarray(logits, np.float64)
    return logits - logits.max() - np.log(np.sum(np.exp(logits - logits.max())))


@pytest.fixture(scope="module")
def toy():
    cfg = dataclasses.replace(lfm2.Lfm2Config.from_hf_config(DOC), dtype=jnp.float32)
    assert cfg == dataclasses.replace(lfm2.PRESETS["lfm2-debug"], dtype=jnp.float32)
    assert (cfg.num_conv_layers, cfg.num_kv_layers, cfg.num_moe_layers) == (6, 2, 6)
    params = lfm2.init_params(cfg, jax.random.key(SEED))
    assert float(jnp.abs(params["moe_ffn"]["expert_bias"]).min()) > 0
    return cfg, params


# -- the forward, called by hand ---------------------------------------------------

def served_logprobs(cfg, params, tokens, n_prompt, *, impl="xla"):
    """Prefill ``n_prompt`` tokens in chunks of CHUNK, then decode the rest one
    by one, through pages and a slot of a pool that its last owner left DIRTY;
    the log-probabilities after the prompt and after every step."""
    cfg = dataclasses.replace(cfg, moe_impl=impl, attn_impl="xla")
    pages = -(-len(tokens) // PAGE) + 1
    k, v = lfm2.init_kv_pages(cfg, pages + 1, PAGE)
    state = jax.tree.map(lambda a: a + 3.0, lfm2.init_state(cfg, 3))
    table = jnp.arange(1, pages + 1, dtype=jnp.int32)[None]
    slots = jnp.asarray([1], jnp.int32)
    fwd = jax.jit(lambda ids, pos, k, v, lens, st: lfm2.forward(
        params, cfg, ids, pos, k, v, table, lens, state=st, state_slots=slots))
    out = []
    for lo in range(0, n_prompt, CHUNK):
        c = min(CHUNK, n_prompt - lo)
        ids = np.zeros((1, CHUNK), np.int32)
        pos = np.full((1, CHUNK), -1, np.int32)
        ids[0, :c], pos[0, :c] = tokens[lo:lo + c], np.arange(lo, lo + c)
        logits, k, v, state, _ = fwd(ids, pos, k, v, jnp.asarray([lo + c]), state)
    out.append(_logprobs(logits[0]))
    for i in range(n_prompt, len(tokens)):
        logits, k, v, state, _ = fwd(
            np.asarray([[tokens[i]]], np.int32), np.asarray([[i]], np.int32),
            k, v, jnp.asarray([i + 1]), state)
        out.append(_logprobs(logits[0]))
    return out


def worst_against_reference(served, params, tokens, n_prompt, **kw):
    """max |dlogprob| over the top-20 of every step (what the check on the chip
    compares) and over the whole vocabulary."""
    top, whole = 0.0, 0.0
    for step, got in enumerate(served):
        ref = np.asarray(reference.next_token_logprobs(
            params, DOC, tokens[:n_prompt + step], pad_to=64, **kw), np.float64)
        whole = max(whole, float(np.max(np.abs(got - ref))))
        top = max(top, float(np.max(np.abs(
            np.sort(got)[-20:] - np.sort(ref)[-20:]))))
    return top, whole


TOKENS = [int(t) for t in np.random.default_rng(0).integers(0, 512, 45)]
N_PROMPT = 37  # three chunks: the tail crosses two chunk boundaries


@pytest.mark.parametrize("impl", ["xla", "pallas_interpret"])
def test_forward_in_chunks_then_steps_agrees_with_the_reference(toy, impl):
    cfg, params = toy
    served = served_logprobs(cfg, params, TOKENS, N_PROMPT, impl=impl)
    top, whole = worst_against_reference(served, params, TOKENS, N_PROMPT)
    assert whole < TOLERANCE, (top, whole)


def test_a_share_of_the_experts_agrees_with_the_reference_given_the_same_share(toy):
    cfg, params = toy
    held = (2, 4)
    # the program's stacks hold the experts held and no others
    share = dict(params, moe_ffn=dict(
        params["moe_ffn"], **{n: params["moe_ffn"][n][:, 2:6] for n in ("w13", "w2")}))
    served = served_logprobs(
        dataclasses.replace(cfg, experts_held=held), share, TOKENS, N_PROMPT)
    top, whole = worst_against_reference(
        served, params, TOKENS, N_PROMPT, experts_held=held)
    assert whole < TOLERANCE, (top, whole)
    # and the share is not the whole model
    assert worst_against_reference(served, params, TOKENS, N_PROMPT)[1] > 100 * TOLERANCE


@pytest.mark.parametrize("broken", [
    "expert_bias_dropped", "topk_not_normalised", "qk_norm_after_rope",
    "conv_looks_ahead", "tail_not_carried", "expert_index_off_by_one",
])
def test_the_reference_tells_a_broken_model(toy, broken, monkeypatch):
    cfg, params = toy
    if broken == "expert_bias_dropped":
        params = dict(params, moe_ffn=dict(
            params["moe_ffn"],
            expert_bias=jnp.zeros_like(params["moe_ffn"]["expert_bias"])))
    elif broken == "topk_not_normalised":
        cfg = dataclasses.replace(cfg, norm_topk_prob=False)
    elif broken == "qk_norm_after_rope":
        from production_stack_tpu.ops.norms import rms_norm
        from production_stack_tpu.ops.rope import apply_rope

        monkeypatch.setattr(lfm2, "_qk_norm_rope", lambda q, k, lp, cos, sin, eps: (
            rms_norm(apply_rope(q, cos, sin), lp["q_norm"], eps),
            rms_norm(apply_rope(k, cos, sin), lp["k_norm"], eps)))
    elif broken == "conv_looks_ahead":
        taps = lfm2._taps
        monkeypatch.setattr(lfm2, "_taps", lambda seq, w, T: taps(
            jnp.concatenate([seq[:, 1:], jnp.zeros_like(seq[:, :1])], axis=1), w, T))
    elif broken == "tail_not_carried":
        rows = lfm2._rows
        monkeypatch.setattr(lfm2, "_rows", lambda positions, slots: dict(
            rows(positions, slots), first=jnp.ones((positions.shape[0],), bool)))
    else:
        # what a grouped product that reads every expert's neighbour computes:
        # the experts rolled by one under an unchanged router (the planted
        # fault of scripts/lfm2_lowprec_control.py, which the cell's check has
        # to fail on the chip)
        params = dict(params, moe_ffn=dict(params["moe_ffn"], **{
            n: jnp.roll(params["moe_ffn"][n], -1, axis=1) for n in ("w13", "w2")}))
    served = served_logprobs(cfg, params, TOKENS, N_PROMPT)
    top, whole = worst_against_reference(served, toy[1], TOKENS, N_PROMPT)
    # over the float32 tolerance by far; four of the six also over what the
    # cell on the chip tolerates (bf16 weights and activations there), the
    # wrong expert among them (0.76 here; through the chip's own comparison
    # 0.23-0.49, PERF.md section 6: a layer's experts are drawn alike only as
    # far as that leaves it visible, lfm2.init_params). Not a dropped bias (a
    # few other CHOICES among experts drawn alike), nor the order of norm and
    # rope in the toy's 2 attention layers of 8: those two are the float32
    # tolerance's to hold
    assert whole > 10 * TOLERANCE, (broken, top, whole)
    if broken not in ("expert_bias_dropped", "qk_norm_after_rope"):
        assert whole > CELL_TOLERANCE, (broken, top, whole)


# -- the expert layer --------------------------------------------------------------------

def _plain_experts(h, experts, weights, w13, w2, layer, E, held=None):
    """Every token times its chosen experts, one at a time."""
    first, count = held or (0, E)
    inter = w2.shape[1]
    out = np.zeros((h.shape[0], w2.shape[2]), np.float64)
    for t in range(h.shape[0]):
        for j in range(experts.shape[1]):
            e = int(experts[t, j])
            if first <= e < first + count:
                a = np.asarray(h[t], np.float64) @ np.asarray(w13[layer * E + e], np.float64)
                act = a[:inter] / (1 + np.exp(-a[:inter])) * a[inter:]
                out[t] += float(weights[t, j]) * (act @ np.asarray(w2[layer * E + e], np.float64))
    return out


BIAS = jnp.asarray([0.0, -10.0, 0.1, -0.1, 0.0, -10.0, 3.0, 3.0])


def _expert_layer(tokens, seed=0, E=8, K=2, H=128, inter=64, layers=3, bias=BIAS):
    ks = jax.random.split(jax.random.key(seed), 5)
    h = jax.random.normal(ks[0], (tokens, H), jnp.float32)
    w13 = jax.random.normal(ks[1], (layers * E, H, 2 * inter), jnp.float32) * H**-0.5
    w2 = jax.random.normal(ks[2], (layers * E, inter, H), jnp.float32) * inter**-0.5
    router = jax.random.normal(ks[3], (H, E), jnp.float32) * H**-0.5
    # experts 1 and 5 are never chosen (empty groups), 6 and 7 nearly always
    experts, weights = moe.route(h, router, bias, K)
    return h, experts, weights, w13, w2, router


@pytest.mark.parametrize("impl", ["xla", "pallas_interpret"])
@pytest.mark.parametrize("tokens", [5, 40, 300], ids=["one-tile-5", "one-tile-40", "three-tiles-300"])
def test_grouped_product_equals_the_plain_sum_under_ragged_and_empty_groups(tokens, impl):
    E = 8
    h, experts, weights, w13, w2, _ = _expert_layer(tokens, seed=tokens)
    out, counters = jax.jit(lambda h, e, w, layer: moe.expert_ffn(
        h, e, w, w13, w2, layer, num_experts=E, impl=impl))(h, experts, weights, jnp.int32(1))
    want = _plain_experts(h, experts, weights, w13, w2, 1, E)
    np.testing.assert_allclose(out, want, atol=2e-5, rtol=1e-5)
    rows = np.bincount(np.asarray(experts).reshape(-1), minlength=E)
    assert rows[1] == rows[5] == 0 and rows.max() > 4 * max(1, np.median(rows))
    assert list(counters[:E]) == list(rows)
    assert counters[E] == np.sum(rows > 0) and counters[E + 1] == E
    assert moe.counter_stats(np.asarray(counters), E)["moe_routed_rows_total"] == 2 * tokens


def test_padding_is_routed_nowhere():
    E = 8
    h, experts, weights, w13, w2, _ = _expert_layer(12, seed=9)
    valid = jnp.arange(12) < 7
    out, counters = moe.expert_ffn(
        h, experts, weights, w13, w2, jnp.int32(0), num_experts=E, valid=valid)
    assert not np.any(np.asarray(out[7:])) and int(jnp.sum(counters[:E])) == 2 * 7


@pytest.mark.parametrize("impl", ["xla", "pallas_interpret"])
def test_the_shares_add_up_to_the_uncut_layer(impl):
    """Four shares of 2 experts each, summed, give the whole layer: in the
    program and in the reference alike."""
    E = 8
    bias = 0.1 * jnp.arange(E, dtype=jnp.float32)
    h, experts, weights, w13, w2, router = _expert_layer(40, seed=4, bias=bias)
    layer = jnp.int32(2)
    whole, counted = moe.expert_ffn(h, experts, weights, w13, w2, layer, num_experts=E, impl=impl)
    # a share's stacks hold its 2 experts of each of the 3 layers
    stacked = [w.reshape((3, E) + w.shape[1:]) for w in (w13, w2)]
    shares = [moe.expert_ffn(
        h, experts, weights,
        *(w[:, first:first + 2].reshape((-1,) + w.shape[2:]) for w in stacked),
        layer, num_experts=E, experts_held=(first, 2), impl=impl)
              for first in range(0, E, 2)]
    np.testing.assert_allclose(sum(s[0] for s in shares), whole, atol=2e-5, rtol=1e-5)
    assert list(sum(s[1] for s in shares)) == list(counted)
    # no share is empty or the whole
    assert all(0 < float(jnp.abs(s[0]).max()) for s in shares)
    # the reference's layer, given the shares
    s = reference.settings(DOC)
    lp = {"router": router, "expert_bias": bias,
          "w13": w13[2 * E:3 * E], "w2": w2[2 * E:3 * E]}
    with jax.default_matmul_precision("highest"):
        ref_whole = reference._moe_ffn(h, lp, s)
        ref_shares = sum(reference._moe_ffn(h, lp, dict(s, held=(first, 2)))
                         for first in range(0, E, 2))
    np.testing.assert_allclose(ref_shares, ref_whole, atol=2e-5, rtol=1e-5)
    np.testing.assert_allclose(whole, ref_whole, atol=2e-5, rtol=1e-5)


# -- the counters are forward's own output ------------------------------------------------

@pytest.mark.parametrize("burst", [False, True])
def test_step_programs_hand_out_the_counters_of_a_family_without_state(burst):
    """``forward`` returns its counters as a last element of its own, so a
    family that keeps NO recurrent state counts too: the step program and the
    deferred burst (which sums its steps) put them behind the page pools."""
    import types

    from production_stack_tpu.engine import runner

    B, V, L, P, page, KH, D, k = 2, 16, 1, 4, 8, 1, 8, 3
    cfg = types.SimpleNamespace(step_counters=2, dtype=jnp.float32)

    def forward(params, cfg, ids, pos, k_pages, v_pages, page_table, lens, kv_burst=None):
        logits = jax.nn.one_hot(ids[:, -1] + 1, V)
        did = jnp.stack([jnp.sum(pos >= 0), jnp.int32(1)]).astype(jnp.int32)
        if kv_burst is not None:
            return logits, kv_burst[0], kv_burst[1], did
        return logits, k_pages, v_pages, did

    pools = jnp.zeros((L, P, page, KH, D), jnp.float32)
    ids = jnp.asarray([[3], [5]], jnp.int32)
    pos = jnp.asarray([[2], [-1]], jnp.int32)       # the second row is padding
    table = jnp.asarray([[1, 2], [0, 0]], jnp.int32)
    lens = jnp.asarray([3, 0], jnp.int32)
    greedy = (jnp.zeros((B,)), jnp.zeros((B,), jnp.int32), jnp.ones((B,)),
              jax.random.key_data(jax.random.key(0)))
    if burst:
        out = runner._multi_step_deferred_fn(
            forward, cfg, k, False, False, None, pools, pools, ids, pos, table, lens,
            jnp.asarray([8, 0], jnp.int32), *greedy)
        assert len(out) == 5 and list(out[-1]) == [k, k]   # a live row a step, k steps
        assert list(out[0][0]) == [4, 5, 6]
    else:
        out = runner._step_fn(
            forward, cfg, False, False, None, pools, pools, ids, pos, table, lens, *greedy)
        assert len(out) == 5 and list(out[-1]) == [1, 1]
        assert int(out[0][0]) == 4


# -- the burst's attention ----------------------------------------------------------------

def test_burst_attention_reads_rows_as_stored_and_equals_flash_attention():
    """One token a row over gathered pages + the burst's window, kv heads side
    by side in a row: the same numbers as ``flash_attention`` over the
    concatenation, under ragged contexts, a window partly filled and a padded
    row."""
    from production_stack_tpu.ops.attention import (
        burst_attention, burst_kv_positions, flash_attention)

    B, S, C, NH, KH, D = 4, 48, 4, 4, 2, 32
    ks = jax.random.split(jax.random.key(7), 5)
    q = jax.random.normal(ks[0], (B, 1, NH, D))
    kc, vc = (jax.random.normal(k, (B, S, KH * D)) for k in ks[1:3])
    kw, vw = (jax.random.normal(k, (B, C, KH * D)) for k in ks[3:5])
    kv_lens = jnp.asarray([40, 7, 1, 0])      # the last row is padding
    cur = jnp.asarray([3, 4, 1, 1])           # window entries in use
    positions = jnp.where(kv_lens > 0, kv_lens - 1, -1)[:, None]
    kv_pos = burst_kv_positions(kv_lens, cur, S, C)
    got = burst_attention(q, kc, vc, kw, vw, kv_pos, positions, KH)
    heads = lambda a, b: jnp.concatenate([a, b], axis=1).reshape(B, S + C, KH, D)  # noqa: E731
    want = flash_attention(q, heads(kc, kw), heads(vc, vw), q_positions=positions,
                           kv_lens=kv_lens, kv_positions=kv_pos)
    np.testing.assert_allclose(got[:3], want[:3], atol=2e-5, rtol=1e-5)
    assert np.all(np.isfinite(np.asarray(got)))


# -- the configuration of the benchmark ---------------------------------------------------

def test_the_configuration_counts_the_parameters_its_notes_state():
    with open(os.path.join(_ROOT, "perfbench", "configs", "lfm2-8b-a1b-d16.json")) as f:
        doc = json.load(f)
    cfg = lfm2.Lfm2Config.from_hf_config(doc)
    assert cfg == lfm2.PRESETS["lfm2-8b-a1b-d16"]
    assert (cfg.num_layers, cfg.num_conv_layers, cfg.num_kv_layers,
            cfg.num_dense_layers, cfg.num_moe_layers) == (16, 12, 4, 2, 14)
    shapes = jax.eval_shape(lambda: lfm2.init_params(cfg, jax.random.key(0)))
    count = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes))
    assert count == doc["parameters"] == 5_399_129_024
    assert f"{count:,}" in doc["notes"]
    # every width, the experts, top-4 and the vocabulary as published
    assert doc["reduced"].keys() == {"num_hidden_layers", "layer_types"}
    assert (doc["num_experts"], doc["num_experts_per_tok"], doc["vocab_size"],
            doc["moe_intermediate_size"], doc["intermediate_size"]) == (
        32, 4, 65536, 1792, 7168)
    assert tuple(doc["layer_types"]) == tuple(
        doc["reduced"]["layer_types"]["published"][:16])
    assert cfg.state_bytes_per_slot == 12 * 2 * 2048 * 2
    assert jax.eval_shape(lambda: lfm2.init_state(cfg, 64))["conv"].shape == (12, 65, 2, 2048)


# -- the engine's normal path ----------------------------------------------------------

@pytest.fixture(scope="module")
def engine(toy):
    cfg, _ = toy
    lfm2.PRESETS["lfm2-test-f32"] = cfg
    # 14 pages of 8: two sequences of ~30 + 40 tokens cannot both grow, so the
    # page pool preempts one of them mid-decode
    eng = LLMEngine(EngineConfig(
        model="lfm2-test-f32", max_model_len=256, max_num_seqs=3, num_pages=14,
        page_size=PAGE, prefill_chunk=CHUNK, decode_steps=BURST, seed=SEED))
    eng.start()
    yield eng
    eng.stop()
    del lfm2.PRESETS["lfm2-test-f32"]


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


def _moe_adds_up(s):
    assert sum(s["moe_expert_rows"]) == s["moe_routed_rows_total"]
    assert 0 < s["moe_expert_reads_total"] <= s["moe_expert_slots_total"]
    assert s["moe_expert_slots_total"] % (8 * 6) == 0  # experts held x expert layers


def test_engine_serves_mixed_lengths_recycles_slots_and_resumes_after_preemption(engine, toy):
    _, params = toy
    assert engine.runner.params["moe_ffn"]["w13"].dtype == jnp.float32
    rng = np.random.default_rng(5)
    prompt = lambda n: [int(t) for t in rng.integers(1, 512, n)]  # noqa: E731
    # 1: a batch of mixed lengths (one chunk, two chunks, three chunks)
    jobs = [(prompt(5), 9), (prompt(21), 10), (prompt(37), 6)]
    s0 = engine.stats()
    for (p, n), (ids, tops) in zip(jobs, _generate(engine, jobs)):
        assert len(ids) == n and _check(params, p, ids, tops) < TOLERANCE
    s1 = engine.stats()
    assert s1["ssm_state_slots"] == 3 and s1["ssm_state_slots_in_use"] == 0
    assert s1["step_program_store_bypassed"] == {}
    # the device counted what it routed: every prompt token and every decoded
    # token crossed 6 expert layers with 2 experts each (a burst may run past
    # a sequence's end, never short of it)
    routed = s1["moe_routed_rows_total"] - s0["moe_routed_rows_total"]
    assert routed >= 2 * 6 * (5 + 21 + 37 + 8 + 9 + 5)
    assert routed % (2 * 6) == 0
    _moe_adds_up(s1)
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
    # slots are taken at admission and never leak with this family
    assert after["ssm_state_slots_in_use"] == 0 and engine.kv.num_free() == 14
    assert sorted(engine.kv.free_slots) == [0, 1, 2]
    _moe_adds_up(after)


def test_one_page_table_width_for_decode_serves_the_same_tokens(toy):
    """``Lfm2Config.decode_one_page_width``: the engine hands the scheduler a
    floor, every decode dispatch is padded to max_model_len's own page table,
    so the decode programs differ by batch bucket alone, and what is served
    still follows the reference through chunks, slots and bursts."""
    cfg, params = toy
    lfm2.PRESETS["lfm2-test-f32-floor"] = cfg
    eng = LLMEngine(EngineConfig(
        model="lfm2-test-f32-floor", max_model_len=256, max_num_seqs=3, num_pages=40,
        page_size=PAGE, prefill_chunk=CHUNK, decode_steps=BURST, seed=SEED))
    assert cfg.decode_one_page_width
    assert eng.scheduler.decode_page_bucket_floor == 256 // PAGE
    widths = set()
    plan = eng.scheduler.schedule

    def watched():
        batch = plan()
        if batch is not None and batch.kind == "decode":
            widths.add(batch.page_table.shape[1])
        return batch
    eng.scheduler.schedule = watched
    eng.start()
    try:
        rng = np.random.default_rng(11)
        jobs = [([int(t) for t in rng.integers(1, 512, n)], out) for n, out in ((5, 9), (37, 6))]
        for (p, n), (ids, tops) in zip(jobs, _generate(eng, jobs)):
            assert len(ids) == n and _check(params, p, ids, tops) < TOLERANCE
        assert widths == {256 // PAGE}
        assert eng.stats()["ssm_state_slots_in_use"] == 0
    finally:
        eng.stop()
        del lfm2.PRESETS["lfm2-test-f32-floor"]


def test_stats_and_metrics_carry_the_expert_and_state_surface(engine):
    from aiohttp.test_utils import TestClient, TestServer

    from production_stack_tpu.engine.api_server import EngineServer

    s = engine.stats()
    # a family with state and no scan: nothing was resolved for it
    assert s["ssm_kernel"] == "" and s["ssm_kernel_reason"] == ""
    assert s["ssm_state_bytes"] == 4 * engine.model_cfg.state_bytes_per_slot
    assert s["conv_state_bytes"] == s["ssm_state_bytes"] == 4 * 6 * 2 * 128 * 4
    assert set(s["state_family_off"]) == {"prefix_caching", "migration"}
    assert len(s["state_family_refusals"]) == 9
    assert len(s["moe_expert_rows"]) == 8
    _moe_adds_up(s)

    async def scrape():
        async with TestClient(TestServer(EngineServer(engine.cfg, engine).build_app())) as c:
            return await (await c.get("/metrics")).text()
    text = asyncio.run(scrape())
    for name in ("ssm_state_slots", "ssm_state_slots_in_use", "conv_state_bytes",
                 "moe_routed_rows_total", "moe_expert_reads_total",
                 "moe_expert_slots_total"):
        assert f"vllm:{name}{{" in text, name


def test_the_family_refuses_what_cannot_serve_it_at_start_up():
    with pytest.raises(ValueError, match="keeps recurrent state") as e:
        LLMEngine(EngineConfig(model="lfm2-debug", max_model_len=256, num_pages=16,
                               page_size=PAGE, tensor_parallel_size=2))
    assert "one device" in str(e.value)
