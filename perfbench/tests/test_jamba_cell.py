"""What PR 39 adds to the benchmark: the `jamba2-3b` configuration at its
published width and depth, the selective scan's byte counts, the three new
readers on a synthetic trace table and request log, BENCHMARK.json against the
files, and the cell's whole command rehearsed on the CPU at a toy size."""

import argparse
import asyncio
import json
import math
import os
import shutil

import jax
import pytest

import costs_ssm
import manifest
import run

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)
DOC = manifest.load_json("configs", "jamba2-3b.json")
CELL = manifest.load_json("cells", "jamba2-3b.chat.json")
PEAKS = manifest.peaks("TPU v5 lite")


# -- the configuration ----------------------------------------------------------

def test_the_configuration_is_the_published_one_uncut():
    from production_stack_tpu.models import jamba

    assert DOC["reduced"] == {} and DOC["num_hidden_layers"] == 28
    cfg = jamba.JambaConfig.from_hf_config(DOC)
    assert cfg.layer_kinds.count("attn") == 2 and cfg.layer_kinds[7] == cfg.layer_kinds[21] == "attn"
    tree = jax.eval_shape(lambda: jamba.init_params(cfg, jax.random.key(0)))
    assert sum(math.prod(x.shape) for x in jax.tree.leaves(tree)) == 3_029_337_472
    # what a sequence keeps beside its pages, and a token's pages
    assert cfg.state_bytes_per_slot == 26 * (16 * 5120 * 4 + 3 * 5120 * 2) == 9_318_400
    k, _ = jax.eval_shape(lambda: jamba.init_kv_pages(cfg, 8, 64))
    assert k.shape == (2, 8, 64, 1, 128)  # 2 layers hold pages, not 28
    # the registered preset is the file's model
    assert dict(vars(jamba.PRESETS["jamba2-3b"]), max_model_len=0) == dict(vars(cfg), max_model_len=0)


def test_the_scan_s_bytes_from_shapes():
    assert costs_ssm.dims(DOC) == {"Di": 5120, "N": 16, "Ls": 26, "act": 2}
    assert costs_ssm.state_bytes(DOC) == 327_680
    assert costs_ssm.token_bytes(DOC) == 5120 * 10 + 128
    assert costs_ssm.decode_bytes(DOC, 3) == 3 * 26 * (2 * 327_680 + 51_328)
    # 600 tokens are two chunks: the state crosses HBM in and out twice
    assert costs_ssm.prefill_bytes(DOC, [600, 512]) == 26 * (1112 * 51_328 + 3 * 2 * 327_680)


# -- the readers -------------------------------------------------------------------

def _context(decode_kernel_s, prefill_kernel_s, busy_s=2.0):
    """3 s of trace; the load generator's interval 100.0-103.0 holds 40 decoded
    tokens (chunks of 8) and the first token of two requests (a first token is
    its prefill's, not decode work)."""
    def request(first, prompt, n_chunks):
        chunks = [first + 0.5 * i for i in range(n_chunks)]
        return {"ok": True, "first": first, "last": chunks[-1], "chunks": chunks,
                "prompt_tokens": prompt, "output_tokens": 1 + 8 * (n_chunks - 1)}
    requests = [
        request(99.6, 512, 4),    # first token before: 24 output tokens inside
        request(100.5, 1024, 3),  # first token inside (not counted) + 16 decoded
        request(102.95, 600, 2),  # first token inside; the next chunk after
        {"ok": False, "first": 101.0, "chunks": [101.0], "prompt_tokens": 512, "output_tokens": 1},
    ]
    ops = {
        "%ssm_step_decode.7 = (f32[64,1,40,128], f32[26,65,16,40,128]) custom-call(...)":
            [400, decode_kernel_s, decode_kernel_s / 400],
        "%ssm_scan_prefill.3 = (f32[4,512,40,128], f32[26,65,16,40,128]) custom-call(...)":
            [26, prefill_kernel_s, prefill_kernel_s / 26],
        "%fusion.12 = bf16[64,8192] fusion(...)": [800, 1.0, 0.00125],
    }
    modules = {"jit_pstpu_multi_step_k8(1)": [10, 0.70, 0.070], "jit_pstpu_multi_step_k8_lp(2)": [1, 0.09, 0.09],
               "jit_pstpu_step(3)": [5, 0.5, 0.1]}
    return {"config": DOC, "requests": requests, "peaks": PEAKS,
            "trace": {"ops": ops, "modules": modules, "devices": 1, "window_s": 3.0, "busy_s": busy_s},
            "sub": {"start_lo": 99.9, "start_hi": 100.0, "stop_lo": 103.0, "stop_hi": 104.0}}


def _metric(name):
    spec = manifest.load_json("layer_metrics", name + ".json")
    return manifest.load_module("readers", spec["reader"]), spec["params"]


def test_roofline_shares_read_the_kernels_by_name_and_cannot_pass_100():
    decode_tokens, prompts = 24 + 16, [1024, 600]
    least_decode = costs_ssm.decode_bytes(DOC, decode_tokens) / 819e9
    least_prefill = costs_ssm.prefill_bytes(DOC, prompts) / 819e9
    reader, params = _metric("kernel.ssm_decode_roofline")
    # a kernel that took exactly the least time reads 100%, a slower one less
    assert reader.read(_context(least_decode, 1.0), params) == pytest.approx(100.0)
    assert reader.read(_context(4 * least_decode, 1.0), params) == pytest.approx(25.0)
    reader, params = _metric("kernel.ssm_prefill_roofline")
    assert reader.read(_context(1.0, least_prefill), params) == pytest.approx(100.0)
    assert reader.read(_context(1.0, 20 * least_prefill), params) == pytest.approx(5.0)
    # by construction: the least time is the bytes at the PEAK, and the kernel's
    # events lie inside the traced window they are measured in, so a share over
    # 100% would need the kernel to move its bytes faster than the peak
    for phase, least in (("decode", least_decode), ("prefill", least_prefill)):
        assert least * 819e9 == reader.work(_context(1, 1), phase, 100.0, 103.0)
    # a program without the kernels (the parent commit): nothing, and no error
    ctx = _context(1.0, 1.0)
    ctx["trace"]["ops"] = {"%fusion.12 = bf16[64,8192] fusion(...)": [800, 1.0, 0.00125]}
    for name in ("kernel.ssm_decode_roofline", "kernel.ssm_prefill_roofline", "kernel.ssm_share_of_busy"):
        reader, params = _metric(name)
        assert reader.read(ctx, params) is None
        assert reader.read({"trace": None, "sub": None}, params) is None


def test_share_of_busy_and_burst_duration():
    reader, params = _metric("kernel.ssm_share_of_busy")
    assert reader.read(_context(0.3, 0.2, busy_s=2.0), params) == pytest.approx(25.0)
    reader, params = _metric("steps.decode_burst_device_ms_p50")
    assert reader.read(_context(1, 1), params) == pytest.approx(70.0)  # the prefill program is not a burst
    ctx = _context(1, 1)
    ctx["trace"]["modules"] = {"jit_pstpu_step(3)": [5, 0.5, 0.1]}
    assert reader.read(ctx, params) is None


# -- the manifest ------------------------------------------------------------------

def test_benchmark_json_holds_what_the_files_say_with_new_entries_last():
    """The driver reads an entry put first or in the middle of a list as a
    change to what was there. So BENCHMARK.json keeps the accepted entries
    first, in their accepted order, and appends the new ones; since PR 45
    `manifest.build` keeps that order itself."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        current = json.load(f)
    assert manifest.build(current) == current
    assert [c["name"] for c in current["configs"]] == ["mistral-7b-d16", "qwen2.5-7b-d14", "jamba2-3b"]
    assert [w["name"] for w in current["workloads"]][-1] == "jamba2-3b.chat"
    assert [m["name"] for m in current["per_layer"]][-5:-1] == [
        "kernel.ssm_decode_roofline", "kernel.ssm_prefill_roofline", "kernel.ssm_share_of_busy",
        "steps.decode_burst_device_ms_p50"]
    # the new cell names no metric that assumes attention on every layer
    assert not {"kernel.decode_attn_roofline", "steps.decode_dispatch_device_ms_p50",
                "kv.prefix_hit_share"} & set(CELL["per_layer"])
    stream = CELL["traffic"]["params"]["streams"][0]
    assert stream["rate_rps"] == pytest.approx(0.7 * stream["knee_rps"], rel=0.02)


# -- the whole command, at a toy size on the CPU ------------------------------------------

TOY = {
    "name": "tiny-jamba", "source": "perfbench/tests: a toy for the CPU rehearsal",
    "why": "rehearsal only", "architectures": ["JambaForCausalLM"], "model_type": "jamba",
    "hidden_size": 128, "intermediate_size": 256, "num_hidden_layers": 8,
    "num_attention_heads": 4, "num_key_value_heads": 1, "head_dim": 32,
    "attn_layer_period": 4, "attn_layer_offset": 1, "mamba_d_state": 16, "mamba_d_conv": 4,
    "mamba_expand": 2, "mamba_dt_rank": 8, "mamba_conv_bias": True, "mamba_proj_bias": False,
    "num_experts": 1, "rms_norm_eps": 1e-6, "vocab_size": 512, "max_position_embeddings": 4096,
    "tie_word_embeddings": True, "sliding_window": None, "torch_dtype": "bfloat16",
    "reduced": {}, "chips": 1,
    "perfbench": dict(DOC["perfbench"], engine_args=["--kv-cache-memory-gb", "0.05"]),
}


@pytest.mark.parametrize("trace", [0, 1])
def test_the_cell_s_command_rehearsed_on_the_cpu(tmp_path, trace):
    copy = tmp_path / "perfbench"
    shutil.copytree(HERE, copy, ignore=shutil.ignore_patterns(".jax_cache", ".out", "__pycache__"))
    (copy / "configs" / "tiny-jamba.json").write_text(json.dumps(TOY))
    cell = json.loads(json.dumps(CELL))
    cell["config"] = "tiny-jamba"
    cell["engine_args"] = ["--max-model-len", "2048"]
    cell["traffic"]["params"]["streams"][0].update(
        rate_rps=4.0, prompt_tokens=[64, 256], quantum=64, output_tokens=[8, 24], warm_seconds=2,
        lead_seconds=1, ramp={"requests": 8, "first_tokens": 16, "step_tokens": 2})
    # three chunks of the toy's prefill: the state crosses two chunk boundaries
    cell["correctness"]["reference"].update(prompt_tokens=1152, output_tokens=12, tolerance=0.25)
    (copy / "cells" / "tiny-jamba.chat.json").write_text(json.dumps(cell))
    args = argparse.Namespace(workload="tiny-jamba.chat", seed=2**31 + 39, seconds=4.0,
                              trace=trace, out=str(tmp_path / "out"))
    res = asyncio.run(run.run_cell(args, args.workload, str(copy), allow_platform="cpu"))
    assert res["correct"] and res["failed"] == 0 and res["attempted"] > 8
    names = {k[len("cpu_rehearsal."):] for k in res["metrics"]}
    if not trace:
        assert names == set(cell["end_to_end"])
        return
    # no device plane on the CPU: the trace readers return nothing and are left
    # out; the counters and the client's statistics are there
    assert names == {"client.ttft_p50_ms", "client.ttft_p95_ms", "sched.loop_host_share",
                     "sched.preemptions", "kv.evicted_pages", "steps.compiles_in_window",
                     "bench.windows_voided"}
