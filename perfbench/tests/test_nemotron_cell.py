"""What PR 51 adds to the benchmark: the `nemotron3-nano-30b-ep8` configuration
(every published key but the experts held: 16 of 128, one chip's share of a
v5e-8; 52 of 52 layers), the SSD recurrence's and the ungated experts' cost
functions on hand-counted cases, the counted-roofline reader on a made-up
context, BENCHMARK.json against the files, and the cell's whole command
rehearsed on the CPU at a toy size."""

import argparse
import asyncio
import json
import math
import os
import shutil

import jax
import pytest

import costs_moe
import costs_moe_ungated
import costs_ssd
import manifest
import run

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)
DOC = manifest.load_json("configs", "nemotron3-nano-30b-ep8.json")
CELL = manifest.load_json("cells", "nemotron3-nano-30b-ep8.chat.json")
PEAKS = manifest.peaks("TPU v5 lite")
NEW_METRICS = ["kernel.moe_ungated_roofline", "kernel.ssd_decode_roofline",
               "kernel.ssd_prefill_roofline", "kernel.ssd_share_of_busy"]
MIB = 2**20


# -- the configuration ----------------------------------------------------------

def test_the_configuration_is_the_published_one_with_a_share_of_the_experts():
    from production_stack_tpu.models import nemotron_h as nh

    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "NVIDIA-Nemotron-3-Nano-30B-A3B-BF16")
    assert DOC["source"] == row["source_url"]
    assert sorted(DOC["reduced"]) == ["n_routed_experts"]
    assert DOC["reduced"]["n_routed_experts"]["published"] == row["config"]["n_routed_experts"] == 128
    assert DOC["n_routed_experts"] == DOC["reduced"]["n_routed_experts"]["run"] == 16
    assert DOC["experts_held"] == {"first": 0, "count": 16, "of": 128}
    for key, value in row["config"].items():
        if key not in DOC["reduced"]:
            assert DOC[key] == value, key
    # nothing of the depth is cut: the published pattern letter for letter
    pattern = DOC["hybrid_override_pattern"]
    assert len(pattern) == DOC["num_hidden_layers"] == 52
    assert (pattern.count("M"), pattern.count("E"), pattern.count("*")) == (23, 23, 6)
    assert "v5e-8" in DOC["deployment"] and "8 chips" in DOC["deployment"]
    cfg = nh.NemotronHConfig.from_hf_config(DOC)
    assert cfg == nh.PRESETS["nemotron3-nano-30b-ep8"]
    assert (cfg.num_experts, cfg.experts_held, cfg.num_experts_per_tok) == (128, (0, 16), 6)
    tree = jax.eval_shape(lambda: nh.init_params(cfg, jax.random.key(0)))
    stored = sum(math.prod(x.shape) for x in jax.tree.leaves(tree))
    # the up matrices are stored with zero columns to whole lane tiles (1,920):
    # no parameters, and `notes` says their bytes
    padding = 23 * 16 * 2688 * (cfg.expert_cols - 1856)
    assert cfg.expert_cols == 1920 and f"{2 * padding:,}" in DOC["notes"]
    assert stored - padding == DOC["parameters"] == 5_874_983_232
    # a token's pages (6 blocks hold them, the kv heads side by side), what a
    # sequence keeps beside them, and the seats that sets
    k, _ = jax.eval_shape(lambda: nh.init_kv_pages(cfg, 8, 64))
    assert k.shape == (6, 8, 64, 1, 2 * 128)
    assert cfg.state_bytes_per_slot == 23 * (64 * 64 * 128 * 4 + 3 * 6144 * 2) == 49_082_368
    pools = jax.eval_shape(lambda: nh.init_state(cfg, 32))
    assert pools["ssm"].shape == (23, 33, 32, 128, 128) and pools["conv"].shape == (23, 33, 3, 6144)
    assert DOC["perfbench"]["engine_args"] == ["--max-num-seqs", "32", "--kv-cache-memory-gb", "0.5"]
    # weights as stored + 33 slots + the pool fill well over a quarter of the chip
    held = 2 * stored + 33 * cfg.state_bytes_per_slot + 0.5e9
    assert 0.8 * 16e9 < held < 15.75e9


def test_the_recurrence_s_costs_from_shapes():
    assert costs_ssd.dims(DOC) == {"NH": 64, "P": 64, "G": 8, "N": 128, "Q": 128, "Ls": 23, "act": 2}
    assert costs_ssd.state_bytes(DOC) == 2 * MIB
    # x and y rows of 4096 and B, C of 1024 in bf16, dt of 64 in float32
    assert costs_ssd.token_bytes(DOC) == 2 * 4096 * 2 + 64 * 4 + 2 * 1024 * 2 == 20_736
    # a decode step of 32 rows: 23 layers x 32 x (4 MiB + its rows) = 3.1 GB
    least = costs_ssd.decode_least_seconds(DOC, {"ssd_decode_tokens_total": 32}, PEAKS)
    assert least == pytest.approx(23 * 32 * (4 * MIB + 20_736) / 819e9)
    assert least == pytest.approx(3.79e-3, rel=5e-3)
    # C B^T a group and three products a head, at blocks of 128
    assert costs_ssd.token_flops(DOC) == 8 * 2 * 128 * 128 + 64 * (2 * 128 * 64 + 4 * 128 * 64)
    # a 2,048-token prompt in four dispatches: the bytes bound it, not the products
    counted = {"ssd_prefill_tokens_total": 2048, "ssd_prefill_rows_total": 4}
    by_bytes = 23 * (2048 * 20_736 + 4 * 4 * MIB) / 819e9
    by_flops = 23 * 2048 * costs_ssd.token_flops(DOC) / 197e12
    assert costs_ssd.prefill_least_seconds(DOC, counted, PEAKS) == pytest.approx(by_bytes)
    assert by_bytes > by_flops > 0.4 * by_bytes


def test_an_ungated_expert_is_two_matrices_not_three():
    assert costs_moe_ungated.dims(DOC) == {"H": 2688, "I": 1856, "w": 2}
    assert costs_moe_ungated.expert_bytes(DOC) == 2 * 2688 * 1856 * 2 == 19_955_712
    assert costs_moe_ungated.row_flops(DOC) == 4 * 2688 * 1856
    # the accepted count would price a third matrix this family does not have
    assert costs_moe.expert_bytes(DOC) == 1.5 * costs_moe_ungated.expert_bytes(DOC)
    assert "kernel.moe_roofline" not in CELL["per_layer"]
    # a decode step of 32 rows that touches 13 of 16 held experts in 23 layers
    # is bound by the reads; a prefill dispatch of 2,048 tokens (an eighth of
    # its 6 x 2,048 assignments held) by neither much more than the other
    counted = {"moe_expert_reads_total": 13 * 23, "moe_routed_rows_total": 24 * 23}
    assert costs_moe_ungated.least_seconds(DOC, counted, PEAKS) == pytest.approx(
        13 * 23 * 19_955_712 / 819e9)
    counted = {"moe_expert_reads_total": 16 * 23, "moe_routed_rows_total": 1536 * 23}
    assert costs_moe_ungated.least_seconds(DOC, counted, PEAKS) == pytest.approx(
        max(16 * 23 * 19_955_712 / 819e9, 1536 * 23 * 4 * 2688 * 1856 / 197e12))


# -- the reader -------------------------------------------------------------------

def _context(kernel, kernel_s, counted, *, window_s=50.0, stood_s=0.0, busy_s=2.0):
    """3 s of trace inside a window of `window_s`; the engine's counters at the
    window's edges."""
    ops = {
        f"%{kernel}.7 = f32[32,32,128]{{2,1,0:T(8,128)}} custom-call(...)": [3000, 0.6 * kernel_s, 1e-4],
        f"%{kernel}.9 = f32[32,32,128]{{2,1,0:T(8,128)}} custom-call(...)": [3000, 0.4 * kernel_s, 1e-4],
        "%fusion.12 = bf16[64,8192] fusion(...)": [800, 1.0, 0.00125],
    }
    stats0 = dict({n: 1000 for n in counted}, first_dispatch_seconds_total=10.0,
                  engine_loop_step_seconds_total=70.0, engine_loop_emit_seconds_total=1.0,
                  engine_loop_wait_seconds_total=30.0)
    stats1 = dict({n: 1000 + v for n, v in counted.items()},
                  first_dispatch_seconds_total=10.0 + stood_s,
                  engine_loop_step_seconds_total=70.0 + window_s - 1.0,
                  engine_loop_emit_seconds_total=2.0, engine_loop_wait_seconds_total=95.0)
    return {"config": DOC, "peaks": PEAKS,
            "snap0": {"t": 100.0, "stats": stats0}, "snap1": {"t": 165.0 + window_s, "stats": stats1},
            "trace": {"ops": ops, "devices": 1, "window_s": 3.0, "busy_s": busy_s, "top_gaps": []}}


def _metric(name):
    spec = manifest.load_json("layer_metrics", name + ".json")
    return manifest.load_module("readers", spec["reader"]), spec["params"]


@pytest.mark.parametrize("name, kernel, counted, least", [
    ("kernel.ssd_decode_roofline", "ssd_step_decode", {"ssd_decode_tokens_total": 40_000},
     lambda c: costs_ssd.decode_least_seconds(DOC, c, PEAKS)),
    ("kernel.ssd_prefill_roofline", "ssd_scan_prefill",
     {"ssd_prefill_tokens_total": 120_000, "ssd_prefill_rows_total": 300},
     lambda c: costs_ssd.prefill_least_seconds(DOC, c, PEAKS)),
    ("kernel.moe_ungated_roofline", "moe_grouped",
     {"moe_expert_reads_total": 400_000, "moe_routed_rows_total": 1_000_000},
     lambda c: costs_moe_ungated.least_seconds(DOC, c, PEAKS)),
])
def test_a_counted_roofline_reads_its_kernel_by_name_and_the_engine_s_counters(
        name, kernel, counted, least):
    reader, params = _metric(name)
    t = least(counted)  # over 50 s of window
    # a kernel that took exactly the least time a second reads 100%, a slower one less
    assert reader.read(_context(kernel, 3.0 * t / 50.0, counted), params) == pytest.approx(100.0)
    assert reader.read(_context(kernel, 4 * 3.0 * t / 50.0, counted), params) == pytest.approx(25.0)
    # seconds the engine stood at first dispatches are no seconds of work
    assert reader.read(_context(kernel, 3.0 * t / 40.0, counted, stood_s=10.0),
                       params) == pytest.approx(100.0)
    assert params["ran"] and "engine_loop_wait_seconds_total" not in params["ran"]
    # a program without the kernel or without the counters (the parent commit):
    # nothing, and no error
    ctx = _context("some_other_kernel", 1.0, counted)
    assert reader.read(ctx, params) is None
    assert reader.read(dict(ctx, trace=None), params) is None
    ctx = _context(kernel, 1.0, counted)
    for snap in ("snap0", "snap1"):
        ctx[snap]["stats"] = {"first_dispatch_seconds_total": 1.0}
    assert reader.read(ctx, params) is None


def test_share_of_busy_finds_both_recurrence_kernels():
    reader, params = _metric("kernel.ssd_share_of_busy")
    ctx = _context("ssd_step_decode", 1.0, {})
    ctx["trace"]["ops"]["%ssd_scan_prefill.3 = f32[4,512,4096] custom-call(...)"] = [90, 0.5, 5e-3]
    assert reader.read(ctx, params) == pytest.approx(75.0)
    assert reader.read(_context("ssm_step_decode", 1.0, {}), params) is None


# -- the manifest ------------------------------------------------------------------

def test_benchmark_json_holds_what_the_files_say_with_new_entries_last():
    """BENCHMARK.json keeps the accepted entries first, in their accepted
    order and as they were, and appends this PR's."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        current = json.load(f)
    assert manifest.build(current) == current
    assert [c["name"] for c in current["configs"]][:5] == [
        "mistral-7b-d16", "qwen2.5-7b-d14", "jamba2-3b", "lfm2-8b-a1b-d16", "nemotron3-nano-30b-ep8"]
    assert [w["name"] for w in current["workloads"]][:5] == [
        "mistral-7b-d16.chat", "qwen2.5-7b-d14.sessions", "jamba2-3b.chat", "lfm2-8b-a1b-d16.chat",
        "nemotron3-nano-30b-ep8.chat"]
    assert current["workloads"][4]["chips"] == 1 and len(current["workloads"][4]["why"]) <= 200
    names = [m["name"] for m in current["per_layer"]]
    assert names[18:22] == NEW_METRICS
    for m in current["per_layer"][18:22]:
        assert m["workloads"] == ["nemotron3-nano-30b-ep8.chat"] and m["moves"] == "tpot_p50_ms"
        assert m["unit"] == "%" and m["layer"] == "kernels"
    assert [(e["name"], e["bound"]) for e in current["end_to_end"]] == [("setup_s", 0.1), ("tpot_p50_ms", 0.1)]
    assert current["run_seconds"] == 51
    # the accepted metrics this cell names gain its name at the END of their
    # `workloads`, and nothing else of them changes; it does not name the
    # roofline that counts three matrices an expert
    by_name = {m["name"]: m for m in current["per_layer"]}
    assert by_name["steps.decode_burst_device_ms_p50"]["workloads"] == [
        "jamba2-3b.chat", "lfm2-8b-a1b-d16.chat", "nemotron3-nano-30b-ep8.chat"]
    for name in ("moe.experts_read_share", "kernel.moe_share_of_busy"):
        assert by_name[name]["workloads"] == ["lfm2-8b-a1b-d16.chat", "nemotron3-nano-30b-ep8.chat"]
    assert by_name["kernel.moe_roofline"]["workloads"] == ["lfm2-8b-a1b-d16.chat"]
    assert set(CELL["per_layer"]) == set(NEW_METRICS) | {
        "client.ttft_p50_ms", "client.ttft_p95_ms", "sched.loop_host_share", "sched.preemptions",
        "kv.evicted_pages", "steps.compiles_in_window", "steps.decode_burst_device_ms_p50",
        "kernel.moe_share_of_busy", "moe.experts_read_share", "device.idle_share.rate",
        "bench.windows_voided"}
    assert CELL["end_to_end"] == ["tpot_p50_ms", "setup_s"]
    stream = CELL["traffic"]["params"]["streams"][0]
    assert stream["rate_rps"] == pytest.approx(0.7 * stream["knee_rps"], rel=0.02)
    # the chat mix of jamba2-3b.chat and lfm2-8b-a1b-d16.chat, parameter for
    # parameter but the rate
    rate = {"rate_rps", "knee_rps", "knee_note"}
    for other in ("jamba2-3b.chat", "lfm2-8b-a1b-d16.chat"):
        theirs = manifest.load_json("cells", other + ".json")["traffic"]["params"]["streams"][0]
        assert {k: v for k, v in stream.items() if k not in rate} == {
            k: v for k, v in theirs.items() if k not in rate}
    assert CELL["correctness"]["reference"]["tolerance"] <= 0.2


# -- the whole command, at a toy size on the CPU ------------------------------------------

TOY = dict(
    {k: v for k, v in DOC.items() if k not in ("notes", "assumed", "deployment", "parameters")},
    name="tiny-nemotron", source="perfbench/tests: a toy for the CPU rehearsal", why="rehearsal only",
    hidden_size=128, vocab_size=512, num_hidden_layers=11, hybrid_override_pattern="EMEM*EMEMM*",
    mamba_num_heads=4, n_groups=2, n_routed_experts=4, num_experts_per_tok=2,
    experts_held={"first": 2, "count": 4, "of": 8}, moe_intermediate_size=80,
    moe_shared_expert_intermediate_size=160, num_attention_heads=4, head_dim=32,
    max_position_embeddings=4096, reduced={},
    perfbench=dict(DOC["perfbench"], engine_args=["--max-num-seqs", "8", "--kv-cache-memory-gb", "0.05"]),
)


@pytest.mark.parametrize("trace", [0, 1])
def test_the_cell_s_command_rehearsed_on_the_cpu(tmp_path, trace):
    copy = tmp_path / "perfbench"
    shutil.copytree(HERE, copy, ignore=shutil.ignore_patterns(".jax_cache", ".out", "__pycache__"))
    (copy / "configs" / "tiny-nemotron.json").write_text(json.dumps(TOY))
    cell = json.loads(json.dumps(CELL))
    cell["config"] = "tiny-nemotron"
    cell["engine_args"] = ["--max-model-len", "2048"]
    cell["traffic"]["params"]["streams"][0].update(
        rate_rps=4.0, prompt_tokens=[64, 256], quantum=64, output_tokens=[8, 24], warm_seconds=2,
        lead_seconds=1, ramp={"requests": 8, "first_tokens": 16, "step_tokens": 2})
    # three chunks of the toy's prefill: the state crosses two chunk boundaries
    cell["correctness"]["reference"].update(prompt_tokens=1152, output_tokens=12, tolerance=0.3)
    (copy / "cells" / "tiny-nemotron.chat.json").write_text(json.dumps(cell))
    args = argparse.Namespace(workload="tiny-nemotron.chat", seed=2**31 + 51, seconds=4.0,
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
