"""The plain reference against the program's forward, on the CPU at a tiny
registered configuration with window and bias on: the reference is trusted
before it judges the chip."""

import dataclasses
import glob
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from production_stack_tpu.models import llama
from reference import llama_family

DOC = {
    "architectures": ["Qwen2ForCausalLM"], "hidden_size": 128, "intermediate_size": 256,
    "num_hidden_layers": 2, "num_attention_heads": 4, "num_key_value_heads": 2,
    "head_dim": 32, "vocab_size": 512, "rope_theta": 10000.0, "rms_norm_eps": 1e-5,
    "max_position_embeddings": 256, "sliding_window": 8, "use_sliding_window": True,
    "attention_bias": True, "tie_word_embeddings": False,
}
PAGE = 8
CELLS = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "cells")
# the widest tolerance any committed cell judges the chip with
TOLERANCE = max(json.load(open(f))["correctness"]["reference"]["tolerance"]
                for f in glob.glob(os.path.join(CELLS, "*.json")))


@pytest.fixture(scope="module")
def model():
    cfg = dataclasses.replace(
        llama.LlamaConfig.from_hf_config(DOC), dtype=jnp.float32, attn_impl="xla")
    assert cfg.sliding_window == 8 and cfg.attention_bias
    params = llama.init_params(cfg, jax.random.key(3))
    for i, name in enumerate(("bq", "bk", "bv")):  # init leaves them zero
        shape = params["layers"][name].shape
        params["layers"][name] = 0.3 * jax.random.normal(jax.random.key(10 + i), shape)
    return cfg, params


def served_logits(cfg, params, tokens, n_prompt):
    """Prefill `n_prompt` tokens, then decode the rest one by one through the
    paged cache; returns the logits after each step from the prompt on."""
    pages = -(-len(tokens) // PAGE) + 1
    k, v = llama.init_kv_pages(cfg, pages + 1, PAGE)
    table = jnp.arange(1, pages + 1, dtype=jnp.int32)[None]
    out = []
    ids = jnp.asarray(tokens[:n_prompt], jnp.int32)[None]
    pos = jnp.arange(n_prompt, dtype=jnp.int32)[None]
    logits, k, v = llama.forward(params, cfg, ids, pos, k, v, table, jnp.asarray([n_prompt]))
    out.append(np.asarray(logits[0]))
    for i in range(n_prompt, len(tokens)):
        ids = jnp.asarray([[tokens[i]]], jnp.int32)
        pos = jnp.asarray([[i]], jnp.int32)
        logits, k, v = llama.forward(params, cfg, ids, pos, k, v, table, jnp.asarray([i + 1]))
        out.append(np.asarray(logits[0]))
    return out


def test_program_forward_agrees_with_reference(model):
    cfg, params = model
    tokens = list(np.random.default_rng(0).integers(0, 512, 30))
    n_prompt = 24  # three times the window: the window is engaged
    served = served_logits(cfg, params, tokens, n_prompt)
    for step, logits in enumerate(served):
        seq = tokens[:n_prompt + step]
        ref = np.asarray(llama_family.next_token_logprobs(params, DOC, seq, pad_to=32))
        got = logits - np.log(np.sum(np.exp(logits - logits.max()))) - logits.max()
        # float32 on both sides: only summation order differs
        np.testing.assert_allclose(got, ref, atol=2e-4, rtol=0)


@pytest.mark.parametrize("broken", ["window", "bias", "rope_theta"])
def test_reference_tells_a_wrong_model(model, broken):
    cfg, params = model
    tokens = list(np.random.default_rng(1).integers(0, 512, 24))
    good = np.asarray(llama_family.next_token_logprobs(params, DOC, tokens))
    doc = dict(DOC)
    if broken == "window":
        doc["use_sliding_window"] = False
    elif broken == "bias":
        doc["attention_bias"] = False
    else:
        doc["rope_theta"] = 1e6
    bad = np.asarray(llama_family.next_token_logprobs(params, doc, tokens))
    # as the check on the chip sees it: the top-20 log-probabilities, value for
    # value, against the tolerance the cells use there
    top = np.argsort(-good)[:20]
    assert np.max(np.abs(np.sort(good[top]) - np.sort(bad)[-20:])) > TOLERANCE
