"""What PR 46 adds to the benchmark: the `lfm2-8b-a1b-d16` configuration (the
published widths, 32 experts, top-4 and vocabulary; 16 of 24 layers), the
expert layer's cost functions on hand-counted cases, the new reader on a
made-up context, BENCHMARK.json against the files, and the cell's whole
command rehearsed on the CPU at a toy size."""

import argparse
import asyncio
import json
import math
import os
import shutil

import jax
import pytest

import costs_moe
import manifest
import run

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)
DOC = manifest.load_json("configs", "lfm2-8b-a1b-d16.json")
CELL = manifest.load_json("cells", "lfm2-8b-a1b-d16.chat.json")
PEAKS = manifest.peaks("TPU v5 lite")
NEW_METRICS = ["kernel.moe_roofline", "kernel.moe_share_of_busy", "moe.experts_read_share"]


# -- the configuration ----------------------------------------------------------

def test_the_configuration_is_the_published_one_cut_in_depth_alone():
    from production_stack_tpu.models import lfm2

    assert sorted(DOC["reduced"]) == ["layer_types", "num_hidden_layers"]
    assert DOC["reduced"]["num_hidden_layers"] == {"published": 24, "run": 16}
    published = DOC["reduced"]["layer_types"]["published"]
    assert len(published) == 24 and DOC["layer_types"] == published[:16]
    # four whole periods, in the published ratio
    assert DOC["layer_types"] == ["conv", "conv", "full_attention", "conv"] * 4
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "LFM2-8B-A1B")
    assert DOC["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key not in DOC["reduced"]:
            assert DOC[key] == value, key
    cfg = lfm2.Lfm2Config.from_hf_config(DOC)
    tree = jax.eval_shape(lambda: lfm2.init_params(cfg, jax.random.key(0)))
    assert sum(math.prod(x.shape) for x in jax.tree.leaves(tree)) == DOC["parameters"] == 5_399_129_024
    # a token's pages (4 layers hold them, the kv heads side by side) and what
    # a sequence keeps beside them
    k, _ = jax.eval_shape(lambda: lfm2.init_kv_pages(cfg, 8, 64))
    assert k.shape == (4, 8, 64, 1, 8 * 64)
    assert cfg.state_bytes_per_slot == 12 * 2 * 2048 * 2 == 98_304
    assert DOC["perfbench"]["engine_args"] == ["--kv-cache-memory-gb", "2"]


def test_the_expert_layer_s_costs_from_shapes():
    assert costs_moe.dims(DOC) == {"H": 2048, "I": 1792, "w": 2}
    assert costs_moe.expert_bytes(DOC) == 3 * 2048 * 1792 * 2 == 22_020_096
    assert costs_moe.row_flops(DOC) == 6 * 2048 * 1792 == 22_020_096
    # a decode step of 20 rows that touches 30 of 32 experts in 14 layers is
    # bound by the reads: 420 experts x 22 MB at 819 GB/s
    reads, rows = 30 * 14, 20 * 4 * 14
    assert costs_moe.least_seconds(DOC, reads, rows, PEAKS) == pytest.approx(reads * 22_020_096 / 819e9)
    assert costs_moe.least_seconds(DOC, reads, rows, PEAKS) == pytest.approx(0.011292, rel=1e-3)
    # a prefill dispatch of 2,048 tokens reads all 32 and is bound by the products
    reads, rows = 32 * 14, 2048 * 4 * 14
    assert costs_moe.least_seconds(DOC, reads, rows, PEAKS) == pytest.approx(rows * 22_020_096 / 197e12)
    assert rows * 22_020_096 / 197e12 > reads * 22_020_096 / 819e9


# -- the readers -------------------------------------------------------------------

def _context(kernel_s, reads, rows, slots, *, window_s=50.0, stood_s=0.0, busy_s=2.0):
    """3 s of trace inside a window of `window_s`; the engine's counters at the
    window's edges."""
    ops = {
        "%moe_grouped.7 = bf16[128,3584]{1,0:T(8,128)(2,1)} custom-call(...)": [3000, 0.6 * kernel_s, 1e-4],
        "%moe_grouped.9 = f32[128,2048]{1,0:T(8,128)} custom-call(...)": [3000, 0.4 * kernel_s, 1e-4],
        "%fusion.12 = bf16[64,8192] fusion(...)": [800, 1.0, 0.00125],
    }
    stats0 = {"moe_expert_reads_total": 1000, "moe_routed_rows_total": 5000,
              "moe_expert_slots_total": 2000, "first_dispatch_seconds_total": 10.0,
              "engine_loop_step_seconds_total": 70.0, "engine_loop_emit_seconds_total": 1.0,
              "engine_loop_wait_seconds_total": 30.0}
    # the loop ran `window_s` (49 in its step section, 1 emitting) and waited
    # 65 s more before the second snapshot came (the profiler's stop)
    stats1 = {"moe_expert_reads_total": 1000 + reads, "moe_routed_rows_total": 5000 + rows,
              "moe_expert_slots_total": 2000 + slots, "first_dispatch_seconds_total": 10.0 + stood_s,
              "engine_loop_step_seconds_total": 70.0 + window_s - 1.0,
              "engine_loop_emit_seconds_total": 2.0, "engine_loop_wait_seconds_total": 95.0}
    return {"config": DOC, "peaks": PEAKS,
            "snap0": {"t": 100.0, "stats": stats0}, "snap1": {"t": 165.0 + window_s, "stats": stats1},
            "trace": {"ops": ops, "devices": 1, "window_s": 3.0, "busy_s": busy_s, "top_gaps": []}}


def _metric(name):
    spec = manifest.load_json("layer_metrics", name + ".json")
    return manifest.load_module("readers", spec["reader"]), spec["params"]


def test_the_roofline_reads_the_kernel_by_name_and_the_engine_s_counters():
    reader, params = _metric("kernel.moe_roofline")
    reads, rows = 400_000, 1_000_000
    least = costs_moe.least_seconds(DOC, reads, rows, PEAKS)  # over 50 s of window
    # a kernel that took exactly the least time a second reads 100%, a slower one less
    assert reader.read(_context(3.0 * least / 50.0, reads, rows, 448_000), params) == pytest.approx(100.0)
    assert reader.read(_context(4 * 3.0 * least / 50.0, reads, rows, 448_000), params) == pytest.approx(25.0)
    # seconds the engine stood at first dispatches are no seconds of work
    assert reader.read(_context(3.0 * least / 40.0, reads, rows, 448_000, stood_s=10.0),
                       params) == pytest.approx(100.0)
    assert params["ran"] and "engine_loop_wait_seconds_total" not in params["ran"]
    # a gap of a second or more in the trace is no traced second
    ctx = _context(2.0 * least / 50.0, reads, rows, 448_000)
    ctx["trace"]["top_gaps"] = [["%fusion.12", 1.0], ["%fusion.12", 0.02]]
    assert reader.read(ctx, params) == pytest.approx(100.0)
    # the least time prices each count at the PEAK and takes the larger bound:
    # over one interval a kernel that reads every counted expert and multiplies
    # every counted row takes at least that long (the reader compares two
    # intervals, so its share holds as far as the traced seconds resemble the
    # window: its docstring)
    for reads, rows in ((448, 80 * 14), (448, 8192 * 14), (1, 1)):
        by_bytes = reads * costs_moe.expert_bytes(DOC) / 819e9
        by_flops = rows * costs_moe.row_flops(DOC) / 197e12
        assert costs_moe.least_seconds(DOC, reads, rows, PEAKS) == max(by_bytes, by_flops)
    # a program without the kernel or without the counters (the parent commit):
    # nothing, and no error
    ctx = _context(1.0, 10, 10, 10)
    ctx["trace"]["ops"] = {"%fusion.12 = bf16[64,8192] fusion(...)": [800, 1.0, 0.00125]}
    for name in ("kernel.moe_roofline", "kernel.moe_share_of_busy"):
        reader, params = _metric(name)
        assert reader.read(ctx, params) is None
        assert reader.read(dict(ctx, trace=None), params) is None
    ctx = _context(1.0, 10, 10, 10)
    for snap in ("snap0", "snap1"):
        ctx[snap]["stats"] = {"first_dispatch_seconds_total": 1.0}
    for name in ("kernel.moe_roofline", "moe.experts_read_share"):
        reader, params = _metric(name)
        assert reader.read(ctx, params) is None


def test_share_of_busy_and_experts_read_share():
    reader, params = _metric("kernel.moe_share_of_busy")
    assert reader.read(_context(1.5, 1, 1, 1, busy_s=2.0), params) == pytest.approx(75.0)
    reader, params = _metric("moe.experts_read_share")
    assert reader.read(_context(1.0, 420, 1120, 448), params) == pytest.approx(93.75)
    assert reader.read(_context(1.0, 448, 1120, 448), params) == pytest.approx(100.0)


# -- the manifest ------------------------------------------------------------------

def test_benchmark_json_holds_what_the_files_say_with_new_entries_last():
    """BENCHMARK.json keeps the accepted entries first, in their accepted
    order and as they were, and appends this PR's."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        current = json.load(f)
    assert manifest.build(current) == current
    assert [c["name"] for c in current["configs"]][:4] == [
        "mistral-7b-d16", "qwen2.5-7b-d14", "jamba2-3b", "lfm2-8b-a1b-d16"]
    assert [w["name"] for w in current["workloads"]][:4] == [
        "mistral-7b-d16.chat", "qwen2.5-7b-d14.sessions", "jamba2-3b.chat", "lfm2-8b-a1b-d16.chat"]
    names = [m["name"] for m in current["per_layer"]]
    assert names[14] == "bench.windows_voided" and names[15:18] == NEW_METRICS
    for m in current["per_layer"][15:18]:
        assert m["workloads"] == ["lfm2-8b-a1b-d16.chat"] and m["moves"] == "tpot_p50_ms"
    assert [(e["name"], e["bound"]) for e in current["end_to_end"]] == [("setup_s", 0.1), ("tpot_p50_ms", 0.1)]
    assert current["run_seconds"] == 51
    # the cell reports the eight metrics every cell reports, its own three and
    # the decode burst's device time (it runs the `jit_pstpu_multi_step`
    # programs that metric finds by name: the accepted entry gains this cell's
    # name at the end of its `workloads`, and nothing else of it changes); none
    # that assumes a mechanism it lacks
    assert set(CELL["per_layer"]) == set(NEW_METRICS) | {
        "client.ttft_p50_ms", "client.ttft_p95_ms", "sched.loop_host_share", "sched.preemptions",
        "kv.evicted_pages", "steps.compiles_in_window", "steps.decode_burst_device_ms_p50",
        "device.idle_share.rate", "bench.windows_voided"}
    burst = next(m for m in current["per_layer"] if m["name"] == "steps.decode_burst_device_ms_p50")
    assert burst["workloads"] == ["jamba2-3b.chat", "lfm2-8b-a1b-d16.chat"]
    assert CELL["end_to_end"] == ["tpot_p50_ms", "setup_s"]
    stream = CELL["traffic"]["params"]["streams"][0]
    assert stream["rate_rps"] == pytest.approx(0.7 * stream["knee_rps"], rel=0.02)
    # the chat mix of jamba2-3b.chat, parameter for parameter but the rate
    other = manifest.load_json("cells", "jamba2-3b.chat.json")["traffic"]["params"]["streams"][0]
    rate = {"rate_rps", "knee_rps", "knee_note"}
    assert {k: v for k, v in stream.items() if k not in rate} == {
        k: v for k, v in other.items() if k not in rate}


# -- the whole command, at a toy size on the CPU ------------------------------------------

TOY = {
    "name": "tiny-lfm2", "source": "perfbench/tests: a toy for the CPU rehearsal",
    "why": "rehearsal only", "architectures": ["Lfm2MoeForCausalLM"], "model_type": "lfm2_moe",
    "hidden_size": 128, "intermediate_size": 256, "moe_intermediate_size": 64,
    "num_hidden_layers": 8, "layer_types": DOC["layer_types"][:8], "num_dense_layers": 2,
    "num_experts": 8, "num_experts_per_tok": 2, "norm_topk_prob": True, "use_expert_bias": True,
    "routed_scaling_factor": 1, "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 32,
    "conv_L_cache": 3, "conv_bias": False, "rope_theta": 1000000, "norm_eps": 1e-5,
    "vocab_size": 512, "max_position_embeddings": 4096, "torch_dtype": "bfloat16",
    "reduced": {}, "chips": 1,
    "perfbench": dict(DOC["perfbench"], engine_args=["--kv-cache-memory-gb", "0.05"]),
}


@pytest.mark.parametrize("trace", [0, 1])
def test_the_cell_s_command_rehearsed_on_the_cpu(tmp_path, trace):
    copy = tmp_path / "perfbench"
    shutil.copytree(HERE, copy, ignore=shutil.ignore_patterns(".jax_cache", ".out", "__pycache__"))
    (copy / "configs" / "tiny-lfm2.json").write_text(json.dumps(TOY))
    cell = json.loads(json.dumps(CELL))
    cell["config"] = "tiny-lfm2"
    cell["engine_args"] = ["--max-model-len", "2048"]
    cell["traffic"]["params"]["streams"][0].update(
        rate_rps=4.0, prompt_tokens=[64, 256], quantum=64, output_tokens=[8, 24], warm_seconds=2,
        lead_seconds=1, ramp={"requests": 8, "first_tokens": 16, "step_tokens": 2})
    # three chunks of the toy's prefill: the tail crosses two chunk boundaries
    cell["correctness"]["reference"].update(prompt_tokens=1152, output_tokens=12, tolerance=0.3)
    (copy / "cells" / "tiny-lfm2.chat.json").write_text(json.dumps(cell))
    args = argparse.Namespace(workload="tiny-lfm2.chat", seed=2**31 + 46, seconds=4.0,
                              trace=trace, out=str(tmp_path / "out"))
    res = asyncio.run(run.run_cell(args, args.workload, str(copy), allow_platform="cpu"))
    assert res["correct"] and res["failed"] == 0 and res["attempted"] > 8
    names = {k[len("cpu_rehearsal."):] for k in res["metrics"]}
    if not trace:
        assert names == set(cell["end_to_end"])
        return
    # no device plane on the CPU: the trace readers return nothing and are left
    # out; the counters (the device's own among them) and the client's
    # statistics are there
    assert names == {"client.ttft_p50_ms", "client.ttft_p95_ms", "sched.loop_host_share",
                     "sched.preemptions", "kv.evicted_pages", "steps.compiles_in_window",
                     "moe.experts_read_share", "bench.windows_voided"}
    share = res["metrics"]["cpu_rehearsal.moe.experts_read_share"]["value"]
    assert 0 < share <= 100
