"""Nemotron-H on the normal path: Mamba-2 (SSD) mixers whose 2 MiB state rides
the state slots, ungated relu^2 experts of which the chip holds a share, a
shared expert, attention without rope, against the plain reference
(``perfbench/reference/nemotron_h.py``, loaded by path: one reference file, no
second copy).

On the toy (all three kinds of block in three runs of units, 8 experts top-2
at a width no multiple of 128 divides, seeded weights in which no term is an
identity, float32 so that only the order of summation differs): prefill in
chunks, then decode steps, through pages and slots, agrees with the
reference's step-by-step forward pass in LOGITS; each of seven broken models
exceeds the tolerance; the SSD kernels in interpret mode equal the
``jax.numpy`` path; the ungated grouped product equals the plain per-token
sum; the eight shares of a layer add up to the layer; the engine's own
scheduler, slots, step programs and counters serve it.
"""

import asyncio
import dataclasses
import json
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

_ROOT = os.path.join(os.path.dirname(os.path.abspath(__file__)), os.pardir)
sys.path.insert(0, os.path.join(_ROOT, "perfbench"))
from reference import nemotron_h as reference  # noqa: E402

from production_stack_tpu.engine.config import EngineConfig  # noqa: E402
from production_stack_tpu.engine.engine import LLMEngine  # noqa: E402
from production_stack_tpu.engine.scheduler import SamplingParams  # noqa: E402
from production_stack_tpu.models import nemotron_h as nh  # noqa: E402
from production_stack_tpu.ops import moe  # noqa: E402
from production_stack_tpu.ops.pallas import ssd_scan  # noqa: E402

# the toy as a published config.json would state it
DOC = {
    "model_type": "nemotron_h", "hidden_size": 128, "vocab_size": 512,
    "num_hidden_layers": 11, "hybrid_override_pattern": "EMEM*EMEMM*",
    "mamba_num_heads": 4, "mamba_head_dim": 64, "n_groups": 2, "ssm_state_size": 128,
    "conv_kernel": 4, "chunk_size": 128, "n_routed_experts": 8, "num_experts_per_tok": 2,
    "moe_intermediate_size": 80, "moe_shared_expert_intermediate_size": 160,
    "n_shared_experts": 1, "norm_topk_prob": True, "routed_scaling_factor": 2.5,
    "n_group": 1, "topk_group": 1, "num_attention_heads": 4, "num_key_value_heads": 2,
    "head_dim": 32, "layer_norm_epsilon": 1e-5, "max_position_embeddings": 256,
    "mlp_hidden_act": "relu2", "mamba_hidden_act": "silu", "use_conv_bias": True,
    "tie_word_embeddings": False,
}
# float32 on both sides and the jax.numpy recurrence: only the order of
# summation differs (read 4e-6: the chunked form against single steps); a
# router near-tie ordered the other way would read ~1e-1, far over it
TOLERANCE = 5e-4
# the SSD prefill kernel rounds what ENTERS its block products to bfloat16
# (ops/pallas/ssd_scan.py says which and why) whatever the model's dtype: read
# 2.6e-3 on this toy; ten times under the smallest planted fault (3e-2)
KERNEL_TOLERANCE = 1e-2
PAGE, CHUNK, BURST = 8, 16, 4
SEED = 3
with open(os.path.join(_ROOT, "perfbench", "cells", "nemotron3-nano-30b-ep8.chat.json")) as _f:
    CELL_TOLERANCE = json.load(_f)["correctness"]["reference"]["tolerance"]


def _logprobs(logits):
    logits = np.asarray(logits, np.float64)
    return logits - logits.max() - np.log(np.sum(np.exp(logits - logits.max())))


@pytest.fixture(scope="module")
def toy():
    cfg = dataclasses.replace(nh.NemotronHConfig.from_hf_config(DOC), dtype=jnp.float32)
    assert cfg == dataclasses.replace(nh.PRESETS["nemotron-h-debug"], dtype=jnp.float32)
    assert (cfg.num_ssm_layers, cfg.num_kv_layers, cfg.num_moe_layers) == (5, 2, 4)
    # three runs of units: a lone E; M E, M * E, M E; M, M *
    assert [(shape, len(units)) for shape, units in nh._runs(nh._units(cfg.pattern))] == [
        ((False, True), 1), ((True, True), 3), ((True, False), 2)]
    params = nh.init_params(cfg, jax.random.key(SEED))
    assert float(jnp.abs(params["moe_layers"]["expert_bias"]).min()) > 0
    return cfg, params


# -- the forward, called by hand ---------------------------------------------------

def served_logprobs(cfg, params, tokens, n_prompt, *, impl="xla"):
    """Prefill ``n_prompt`` tokens in chunks of CHUNK, then decode the rest one
    by one, through pages and a slot of pools that their last owner left
    DIRTY; the log-probabilities after the prompt and after every step."""
    cfg = dataclasses.replace(cfg, moe_impl=impl, ssm_impl=impl)
    pages = -(-len(tokens) // PAGE) + 1
    k, v = nh.init_kv_pages(cfg, pages + 1, PAGE)
    state = jax.tree.map(lambda a: a + 3.0, nh.init_state(cfg, 3))
    table = jnp.arange(1, pages + 1, dtype=jnp.int32)[None]
    slots = jnp.asarray([1], jnp.int32)
    fwd = jax.jit(lambda ids, pos, k, v, lens, st: nh.forward(
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


def worst_against_reference(served, params, tokens, n_prompt, doc=DOC, **kw):
    """max |dlogprob| over the top-20 of every step (what the check on the chip
    compares) and over the whole vocabulary."""
    top, whole = 0.0, 0.0
    for step, got in enumerate(served):
        ref = np.asarray(reference.next_token_logprobs(
            params, doc, tokens[:n_prompt + step], pad_to=64, **kw), np.float64)
        whole = max(whole, float(np.max(np.abs(got - ref))))
        top = max(top, float(np.max(np.abs(
            np.sort(got)[-20:] - np.sort(ref)[-20:]))))
    return top, whole


TOKENS = [int(t) for t in np.random.default_rng(0).integers(0, 512, 45)]
N_PROMPT = 37  # three chunks: the state crosses two chunk boundaries


@pytest.mark.parametrize("impl, tolerance", [
    ("xla", TOLERANCE), ("pallas_interpret", KERNEL_TOLERANCE)])
def test_forward_in_chunks_then_steps_agrees_with_the_reference(toy, impl, tolerance):
    cfg, params = toy
    served = served_logprobs(cfg, params, TOKENS, N_PROMPT, impl=impl)
    top, whole = worst_against_reference(served, params, TOKENS, N_PROMPT)
    assert whole < tolerance, (top, whole)


def _share(params, cfg, first, count):
    """The parameter tree of the chip that holds experts first .. first + count."""
    mp = params["moe_layers"]
    return dict(params, moe_layers=dict(
        mp, **{n: mp[n][:, first:first + count] for n in ("w1", "w2")}))


def test_a_share_of_the_experts_agrees_with_the_reference_given_the_same_share(toy):
    cfg, params = toy
    held = (2, 4)
    share = _share(params, cfg, *held)
    served = served_logprobs(
        dataclasses.replace(cfg, experts_held=held), share, TOKENS, N_PROMPT)
    doc = dict(DOC, n_routed_experts=4, experts_held={"first": 2, "count": 4, "of": 8})
    assert nh.NemotronHConfig.from_hf_config(doc).experts_held == held
    top, whole = worst_against_reference(served, share, TOKENS, N_PROMPT, doc=doc)
    assert whole < TOLERANCE, (top, whole)
    # and the share is not the whole model
    whole_model = served_logprobs(cfg, params, TOKENS, N_PROMPT)
    assert max(float(np.max(np.abs(a - b))) for a, b in zip(served, whole_model)) > 100 * TOLERANCE


@pytest.mark.parametrize("broken", [
    "selection_bias_dropped", "d_skip_dropped", "group_index_off_by_one",
    "state_kept_in_bfloat16", "state_not_carried", "gate_norm_over_all_channels",
    "expert_index_off_by_one",
])
def test_the_reference_tells_a_broken_model(toy, broken, monkeypatch):
    cfg, params = toy
    if broken == "selection_bias_dropped":
        params = dict(params, moe_layers=dict(
            params["moe_layers"],
            expert_bias=jnp.zeros_like(params["moe_layers"]["expert_bias"])))
    elif broken == "d_skip_dropped":
        params = dict(params, ssm_layers=dict(
            params["ssm_layers"], d_skip=jnp.zeros_like(params["ssm_layers"]["d_skip"])))
    elif broken == "group_index_off_by_one":
        # every head reads the B and C of the group before its own (the
        # planted fault of scripts/nemotron_lowprec_control.py)
        sound = ssd_scan.ssd_scan
        monkeypatch.setattr(ssd_scan, "ssd_scan", lambda x, dt, a, b, c, *rest, **kw: sound(
            x, dt, a, jnp.roll(b, 1, axis=2), jnp.roll(c, 1, axis=2), *rest, **kw))
    elif broken == "state_kept_in_bfloat16":
        cfg = dataclasses.replace(cfg, ssm_state_dtype=jnp.bfloat16)
    elif broken == "state_not_carried":
        rows = nh._rows
        monkeypatch.setattr(nh, "_rows", lambda positions, slots: dict(
            rows(positions, slots), first=jnp.ones((positions.shape[0],), bool)))
    elif broken == "gate_norm_over_all_channels":
        gated = nh._gated_norm
        monkeypatch.setattr(nh, "_gated_norm", lambda y, z, w, groups, eps: gated(y, z, w, 1, eps))
    else:
        # what a grouped product that reads every expert's neighbour computes
        params = dict(params, moe_layers=dict(params["moe_layers"], **{
            n: jnp.roll(params["moe_layers"][n], -1, axis=1) for n in ("w1", "w2")}))
    served = served_logprobs(cfg, params, TOKENS, N_PROMPT)
    top, whole = worst_against_reference(served, toy[1], TOKENS, N_PROMPT)
    # each over the float32 tolerance, which the sound program keeps by a
    # hundredfold (read 4e-6). The state kept in bfloat16 between steps is the
    # least (1.5e-3 over 37 + 8 positions on this toy, under the SSD kernel's
    # own rounding: on the chip, at 1,164 positions, it reads INSIDE the sound
    # range, PERF.md section 6, PR 51: this test is what holds it). A dropped
    # selection bias (drawn at sigma 0.02: other CHOICES for a few tokens) reads
    # 0.10, two hundred times the tolerance. Every other reads 1.4-3.3 over the
    # vocabulary and 0.33-0.77 over the sorted top-20 the chip compares: over
    # what the cell tolerates
    assert whole > 2 * TOLERANCE, (broken, top, whole)
    if broken == "selection_bias_dropped":
        assert whole > 100 * TOLERANCE, (broken, top, whole)
    elif broken != "state_kept_in_bfloat16":
        assert top > CELL_TOLERANCE and whole > 10 * KERNEL_TOLERANCE, (broken, top, whole)


# -- the recurrence's kernels against its jax.numpy path ---------------------------

def _recurrence(B, T, lens, first, *, seed=0, NH=8, G=2, P=64, N=128, layers=2, slots=3):
    k = jax.random.split(jax.random.key(seed), 7)
    bf = lambda v: v.astype(jnp.bfloat16).astype(jnp.float32)  # noqa: E731
    lens = jnp.asarray(lens, jnp.int32)
    dt = jax.nn.softplus(jax.random.normal(k[1], (B, T, NH)) - 2.0)
    dt = jnp.where(jnp.arange(T)[None, :, None] < lens[:, None, None], dt, 0.0)
    # a live row takes a seat (two rows never share one), a padded row the null slot
    seats = iter(range(slots))
    slot_ids = jnp.asarray([next(seats) if n > 0 else slots for n in lens], jnp.int32)
    return dict(
        x=bf(jax.random.normal(k[0], (B, T, NH, P))), dt=dt,
        a=-jnp.exp(jax.random.normal(k[2], (NH,))),
        b_mat=bf(jax.random.normal(k[3], (B, T, G, N))),
        c_mat=bf(jax.random.normal(k[4], (B, T, G, N))),
        d=jax.random.normal(k[5], (NH,)),
        # a pool its last owners left dirty
        pool=jax.random.normal(k[6], ssd_scan.state_pool_shape(layers, slots, NH, P, N)),
        slots=slot_ids, first=jnp.asarray(first), lens=lens, layer=jnp.int32(1),
    )


def _plain_recurrence(c):
    """One position at a time, in float64."""
    x, dt, bm, cm = (np.asarray(c[n], np.float64) for n in ("x", "dt", "b_mat", "c_mat"))
    a, d = np.asarray(c["a"], np.float64), np.asarray(c["d"], np.float64)
    B, T, NH, _ = x.shape
    per = NH // bm.shape[2]
    s = np.array(ssd_scan.from_pool(c["pool"][1, c["slots"]]), np.float64)
    s[np.asarray(c["first"])] = 0
    y = np.zeros(x.shape)
    for b in range(B):
        for t in range(int(c["lens"][b])):
            for h in range(NH):
                s[b, h] = (np.exp(dt[b, t, h] * a[h]) * s[b, h]
                           + dt[b, t, h] * np.outer(x[b, t, h], bm[b, t, h // per]))
                y[b, t, h] = s[b, h] @ cm[b, t, h // per] + d[h] * x[b, t, h]
    return y, s


@pytest.mark.parametrize("case", [
    dict(B=3, T=1, lens=[1, 0, 1], first=[False, False, True]),       # decode, a padded row
    dict(B=2, T=256, lens=[256, 130], first=[True, False]),           # ends off the 128 block
    dict(B=2, T=128, lens=[100, 0], first=[False, False]),            # recycled slot, padded row
    dict(B=1, T=16, lens=[11], first=[True]),                         # a chunk under one block
], ids=["decode", "two-blocks", "recycled-and-padded", "short-chunk"])
def test_the_recurrence_s_kernels_equal_its_plain_path_and_the_single_steps(case):
    c = _recurrence(**case)
    want_y, want_s = _plain_recurrence(c)
    live = np.asarray(c["lens"]) > 0
    for impl, tol in (("xla", 2e-5), ("pallas_interpret", 2e-5 if case["T"] == 1 else 6e-3)):
        y, pool = ssd_scan.ssd_scan(*(c[n] for n in (
            "x", "dt", "a", "b_mat", "c_mat", "d", "pool", "slots", "first", "lens", "layer")),
            impl=impl)
        got_s = np.asarray(ssd_scan.from_pool(pool[1, c["slots"]]))
        # (the prefill kernel's products take bfloat16 operands: 6e-3 of the largest)
        assert np.max(np.abs(np.asarray(y) - want_y)) < tol * np.max(np.abs(want_y)), impl
        assert np.max(np.abs(got_s[live] - want_s[live])) < tol * np.max(np.abs(want_s[live])), impl
        # padded positions give nothing; the other layer, the seats of no row
        # and the null slot keep what they held
        assert not np.any(np.asarray(y)[np.arange(case["T"])[None, :] >= np.asarray(c["lens"])[:, None]])
        assert bool(jnp.all(pool[0] == c["pool"][0]))
        untouched = [s for s in range(c["pool"].shape[1]) if s not in np.asarray(c["slots"])[live]]
        assert bool(jnp.all(pool[1, jnp.asarray(untouched)] == c["pool"][1, jnp.asarray(untouched)]))


def test_the_pool_s_layout_is_a_permutation_of_heads_channels_and_state():
    s = jax.random.normal(jax.random.key(1), (2, 8, 64, 128))
    pool = ssd_scan.to_pool(s)
    assert pool.shape == (2, 4, 128, 128)
    assert bool(jnp.all(ssd_scan.from_pool(pool) == s))
    # heads 2i and 2i + 1 side by side, transposed
    assert bool(jnp.all(pool[0, 1, :, 64:] == s[0, 3].T))
    assert ssd_scan.state_pool_shape(23, 32, 64, 64, 128) == (23, 33, 32, 128, 128)


# -- the expert layer --------------------------------------------------------------------

def _plain_ungated(h, experts, weights, w1, w2, layer, stored, first):
    """Every token times its chosen experts of the share, one at a time."""
    out = np.zeros((h.shape[0], w2.shape[2]), np.float64)
    inter = w2.shape[1]
    for t in range(h.shape[0]):
        for j in range(experts.shape[1]):
            e = int(experts[t, j]) - first
            if 0 <= e < stored:
                up = np.asarray(h[t], np.float64) @ np.asarray(w1[layer * stored + e], np.float64)
                act = np.square(np.maximum(up[:inter], 0.0))
                out[t] += float(weights[t, j]) * (act @ np.asarray(w2[layer * stored + e], np.float64))
    return out


def _ungated_layer(tokens, seed=0, E=8, K=2, H=128, inter=80, cols=80, layers=3, stored=8):
    ks = jax.random.split(jax.random.key(seed), 5)
    h = jax.random.normal(ks[0], (tokens, H), jnp.float32)
    w1 = jax.random.normal(ks[1], (layers * stored, H, inter), jnp.float32) * H**-0.5
    w1 = jnp.pad(w1, [(0, 0), (0, 0), (0, cols - inter)])
    w2 = jax.random.normal(ks[2], (layers * stored, inter, H), jnp.float32) * inter**-0.5
    router = jax.random.normal(ks[3], (H, E), jnp.float32) * H**-0.5
    bias = jnp.asarray([0.0, -10.0, 0.1, -0.1, 0.0, -10.0, 3.0, 3.0])[:E]
    experts, weights = moe.route(h, router, bias, K, scaling=2.5)
    return h, experts, weights, w1, w2


@pytest.mark.parametrize("impl", ["xla", "pallas_interpret"])
@pytest.mark.parametrize("cols", [80, 128], ids=["width-80-as-is", "width-80-stored-128"])
@pytest.mark.parametrize("tokens", [5, 300], ids=["one-tile", "three-tiles"])
def test_ungated_experts_at_a_width_no_lane_tile_divides_equal_the_plain_sum(tokens, cols, impl):
    E = 8
    h, experts, weights, w1, w2 = _ungated_layer(tokens, seed=tokens, cols=cols)
    out, counters = jax.jit(lambda h, e, w, layer: moe.expert_ffn(
        h, e, w, w1, w2, layer, num_experts=E, impl=impl, form="relu2",
    ))(h, experts, weights, jnp.int32(1))
    want = _plain_ungated(h, experts, weights, w1, w2, 1, E, 0)
    np.testing.assert_allclose(out, want, atol=5e-5, rtol=2e-5)
    rows = np.bincount(np.asarray(experts).reshape(-1), minlength=E)
    assert list(counters[:E]) == list(rows) and counters[E + 1] == E
    with pytest.raises(ValueError, match="unknown expert form"):
        moe.expert_ffn(h, experts, weights, w1, w2, jnp.int32(0), num_experts=E, form="gelu")


@pytest.mark.parametrize("impl", ["xla", "pallas_interpret"])
def test_the_eight_shares_add_up_to_the_uncut_layer(impl, monkeypatch):
    """128 experts top-6 in eight shares ``experts_held = (16 i, 16)``, each
    chip's tree holding its 16 alone: the routed parts summed + the shared
    expert ONCE give the uncut layer, in the program and in the reference
    alike."""
    doc = dict(DOC, hybrid_override_pattern="E", num_hidden_layers=1,
               n_routed_experts=128, num_experts_per_tok=6)
    cfg = dataclasses.replace(nh.NemotronHConfig.from_hf_config(doc), dtype=jnp.float32, moe_impl=impl)
    params = nh.init_params(cfg, jax.random.key(4))
    mp = params["moe_layers"]
    x = jax.random.normal(jax.random.key(5), (1, 40, cfg.hidden_size), jnp.float32)
    valid = jnp.ones((1, 40), bool)

    def layer(cfg, mp):
        flat = tuple(mp[n].reshape((-1,) + mp[n].shape[2:]) for n in ("w1", "w2"))
        return nh._moe_layer(x, mp, flat, cfg, jnp.int32(0), valid, impl)

    whole, counted = layer(cfg, mp)
    # what every chip computes alike, counted once
    lp = nh._at(mp, 0, skip=("w1", "w2"))
    h = nh.rms_norm(x, lp["mlp_norm"], cfg.norm_eps)
    shared = (nh._relu2(h @ lp["w_up"]) @ lp["w_down"])
    shares = []
    for i in range(8):
        held = dataclasses.replace(cfg, experts_held=(16 * i, 16))
        assert held.experts_stored == 16
        out, did = layer(held, dict(mp, **{n: mp[n][:, 16 * i:16 * i + 16] for n in ("w1", "w2")}))
        shares.append((out - shared, did))
    np.testing.assert_allclose(sum(s[0] for s in shares) + shared, whole, atol=5e-5, rtol=2e-5)
    assert list(sum(s[1] for s in shares)[:128]) == list(counted[:128])
    assert int(jnp.sum(counted[:128])) == 6 * 40
    assert all(float(jnp.abs(s[0]).max()) > 0 for s in shares)
    # the reference's layer: the uncut one, and its shares given the same trees
    s = reference.settings(doc)
    one = lambda tree: {n: a[0] for n, a in tree.items()}  # noqa: E731
    with jax.default_matmul_precision("highest"):
        hr = reference._rmsnorm(x[0], mp["mlp_norm"][0], s["eps"])
        ref_whole = reference._experts(hr, one(mp), s)
        ref_shared = reference._relu2(hr @ mp["w_up"][0]) @ mp["w_down"][0]
        ref_shares = sum(
            reference._experts(hr, one(dict(mp, **{
                n: mp[n][:, 16 * i:16 * i + 16] for n in ("w1", "w2")})),
                dict(s, held=(16 * i, 16))) - ref_shared
            for i in range(8))
    np.testing.assert_allclose(ref_shares + ref_shared, ref_whole, atol=5e-5, rtol=2e-5)
    np.testing.assert_allclose(whole[0], ref_whole, atol=5e-5, rtol=2e-5)


# -- the configuration of the benchmark ---------------------------------------------------

def test_the_configuration_counts_the_parameters_its_notes_state():
    with open(os.path.join(_ROOT, "perfbench", "configs", "nemotron3-nano-30b-ep8.json")) as f:
        doc = json.load(f)
    cfg = nh.NemotronHConfig.from_hf_config(doc)
    assert cfg == nh.PRESETS["nemotron3-nano-30b-ep8"]
    assert (cfg.num_layers, cfg.num_ssm_layers, cfg.num_moe_layers, cfg.num_kv_layers) == (52, 23, 23, 6)
    assert cfg.d_inner == 4096 and cfg.conv_dim == 6144      # NOT expand x hidden
    shapes = jax.eval_shape(lambda: nh.init_params(cfg, jax.random.key(0)))
    stored = sum(math.prod(x.shape) for x in jax.tree.leaves(shapes))
    # zero columns the up matrices are stored with: no parameters
    padding = 23 * 16 * 2688 * (cfg.expert_cols - cfg.moe_intermediate_size)
    assert stored - padding == doc["parameters"] == 5_874_983_232
    assert f"{doc['parameters']:,}" in doc["notes"]
    # by kind, as the issue reckons them
    count = lambda tree: sum(math.prod(x.shape) for x in jax.tree.leaves(tree))  # noqa: E731
    assert count(shapes["ssm_layers"]) == 23 * 38_744_896
    assert count(shapes["attn_layers"]) == 6 * 23_399_040
    assert count(shapes["moe_layers"]) - padding == 23 * 179_948_288
    assert count([shapes["embed"], shapes["lm_head"], shapes["final_norm"]]) == 704_645_760
    # every width, top-6, the router's 128 and the vocabulary as published
    assert doc["reduced"].keys() == {"n_routed_experts"}
    assert (cfg.num_experts, cfg.experts_held, doc["num_experts_per_tok"], doc["vocab_size"],
            doc["moe_intermediate_size"], doc["moe_shared_expert_intermediate_size"]) == (
        128, (0, 16), 6, 131072, 1856, 3712)
    # all 128 experts: the 31.6 B the model's card states
    assert doc["parameters"] + 23 * 112 * 2 * 2688 * 1856 == 31_577_940_288
    # the seats are set by the state's bytes
    assert cfg.state_bytes_per_slot == 49_082_368
    pools = jax.eval_shape(lambda: nh.init_state(cfg, 32))
    assert sum(math.prod(p.shape) * p.dtype.itemsize for p in jax.tree.leaves(pools)) == (
        33 * cfg.state_bytes_per_slot)


# -- the engine's normal path ----------------------------------------------------------

@pytest.fixture(scope="module")
def engine(toy):
    cfg, _ = toy
    nh.PRESETS["nemotron-test-f32"] = cfg
    # 14 pages of 8: two sequences of ~30 + 40 tokens cannot both grow, so the
    # page pool preempts one of them mid-decode
    eng = LLMEngine(EngineConfig(
        model="nemotron-test-f32", max_model_len=256, max_num_seqs=3, num_pages=14,
        page_size=PAGE, prefill_chunk=CHUNK, decode_steps=BURST, seed=SEED))
    eng.start()
    yield eng
    eng.stop()
    del nh.PRESETS["nemotron-test-f32"]


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
    cfg, params = toy
    assert engine.runner.params["moe_layers"]["w1"].dtype == jnp.float32
    rng = np.random.default_rng(5)
    prompt = lambda n: [int(t) for t in rng.integers(1, 512, n)]  # noqa: E731
    # 1: a batch of mixed lengths (one chunk, two chunks, three chunks)
    jobs = [(prompt(5), 9), (prompt(21), 10), (prompt(37), 6)]
    s0 = engine.stats()
    for (p, n), (ids, tops) in zip(jobs, _generate(engine, jobs)):
        assert len(ids) == n and _check(params, p, ids, tops) < TOLERANCE
    s1 = engine.stats()
    assert s1["ssm_state_slots"] == 3 and s1["ssm_state_slots_in_use"] == 0
    # the device counted what it did: every prompt token crossed the SSD layers
    # in a chunk, every decoded token in a step (a burst may run past a
    # sequence's end, never short of it), and 4 expert layers routed 2 each
    walked = s1["ssd_prefill_tokens_total"] - s0["ssd_prefill_tokens_total"]
    assert walked == 5 + 21 + 37
    assert s1["ssd_prefill_rows_total"] - s0["ssd_prefill_rows_total"] == 1 + 2 + 3
    assert s1["ssd_prefill_chunks_total"] - s0["ssd_prefill_chunks_total"] == 6
    assert s1["ssd_decode_tokens_total"] - s0["ssd_decode_tokens_total"] >= 8 + 9 + 5
    routed = s1["moe_routed_rows_total"] - s0["moe_routed_rows_total"]
    assert routed >= 2 * 4 * (5 + 21 + 37 + 8 + 9 + 5) and routed % (2 * 4) == 0
    assert 0 < s1["moe_expert_reads_total"] <= s1["moe_expert_slots_total"]
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


def test_stats_and_metrics_read_seats_and_bytes_from_one_place(engine):
    from aiohttp.test_utils import TestClient, TestServer

    from production_stack_tpu.engine.api_server import EngineServer

    s = engine.stats()
    cfg = engine.model_cfg
    assert (s["ssm_kernel"], s["ssm_kernel_reason"]) == ("xla", "no TPU backend (platform=cpu)")
    # 5 layers x (4 heads x 64 x 128 float32 + 3 rows of 4 x 64 + 2 x 2 x 128)
    assert s["ssm_state_bytes_per_slot"] == cfg.state_bytes_per_slot == 5 * (
        4 * 64 * 128 * 4 + 3 * 768 * 4)
    assert s["ssm_state_bytes"] == 4 * cfg.state_bytes_per_slot == engine.runner.state_pool_bytes()
    assert s["ssm_state_bytes"] == sum(int(a.nbytes) for a in jax.tree.leaves(engine.runner.state))
    assert s["conv_state_bytes"] == 4 * 5 * 3 * 768 * 4
    assert set(s["state_family_off"]) == {"prefix_caching", "migration"}
    assert len(s["state_family_refusals"]) == 9
    assert len(s["moe_expert_rows"]) == 8

    async def scrape():
        async with TestClient(TestServer(EngineServer(engine.cfg, engine).build_app())) as c:
            return await (await c.get("/metrics")).text()
    text = asyncio.run(scrape())
    for name in ("ssm_state_slots", "ssm_state_slots_in_use", "ssm_state_bytes",
                 "ssm_state_bytes_per_slot", "conv_state_bytes", "ssd_decode_tokens_total",
                 "ssd_prefill_tokens_total", "ssd_prefill_chunks_total", "ssd_prefill_rows_total",
                 "moe_routed_rows_total", "moe_expert_reads_total", "moe_expert_slots_total"):
        assert f"vllm:{name}{{" in text, name


def test_a_pool_that_is_not_the_size_the_configuration_states_refuses_to_start(monkeypatch):
    """``state_bytes_per_slot`` is what the log line, ``/stats`` and the seats
    are reckoned from: a family whose ``init_state`` allocates another size is
    told so when the pools are built."""
    init = nh.init_state
    monkeypatch.setattr(nh, "init_state", lambda cfg, slots: dict(
        init(cfg, slots), extra=jnp.zeros((slots + 1, 16), jnp.float32)))
    with pytest.raises(ValueError, match="state_bytes_per_slot"):
        LLMEngine(EngineConfig(model="nemotron-h-debug", max_model_len=256, max_num_seqs=3,
                               num_pages=16, page_size=PAGE))


def test_the_family_refuses_what_cannot_serve_it_at_start_up():
    with pytest.raises(ValueError, match="keeps recurrent state") as e:
        LLMEngine(EngineConfig(model="nemotron-h-debug", max_model_len=256, num_pages=16,
                               page_size=PAGE, tensor_parallel_size=2))
    assert "one device" in str(e.value)
    with pytest.raises(ValueError, match="XLA path only"):
        cfg = dataclasses.replace(nh.PRESETS["nemotron-h-debug"], attn_impl="pallas", ssm_impl="xla")
        k, v = nh.init_kv_pages(cfg, 4, PAGE)
        nh.forward(nh.init_params(cfg, jax.random.key(0)), cfg, jnp.zeros((1, 1), jnp.int32),
                   jnp.zeros((1, 1), jnp.int32), k, v, jnp.zeros((1, 2), jnp.int32),
                   jnp.ones((1,), jnp.int32), state=nh.init_state(cfg, 1),
                   state_slots=jnp.zeros((1,), jnp.int32))
