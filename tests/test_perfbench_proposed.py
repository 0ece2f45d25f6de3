"""The per-layer metrics that read the engine's own measurement (PR 25), which
wait in `scripts/perfbench_proposed/` for a `benchmark` PR to move them into
`perfbench/` and name them in the cells. Their readers on hand-made contexts:
each returns a number where there is something to read and nothing (None)
where the program or the run gives it nothing, as under a program that lacks
the counter or the names. Then (slow) the four through perfbench's own command
on its toy cell."""

import argparse
import asyncio
import json
import os
import shutil
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PERFBENCH = os.path.join(ROOT, "perfbench")
PROPOSED = os.path.join(ROOT, "scripts", "perfbench_proposed")
sys.path.insert(0, PERFBENCH)  # the readers import perfbench's costs and readers_common
import manifest  # noqa: E402

DOC = {"hidden_size": 4096, "num_attention_heads": 32, "num_key_value_heads": 8,
       "num_hidden_layers": 16, "intermediate_size": 14336, "vocab_size": 32000,
       "torch_dtype": "bfloat16"}
KERNEL = "%ragged_paged_attention_decode.10 = bf16[64,32,128] custom-call(s32[64,16] %x)"


def reader(name):
    return manifest.load_module("readers", name, PROPOSED)


def proposed_params(metric):
    return manifest.load_json("layer_metrics", metric + ".json", base=PROPOSED)["params"]


def test_counter_at_open():
    read = reader("counter_at_open").read
    ctx = {"snap0": {"stats": {"first_dispatch_seconds_total": 112.5}}}
    assert read(ctx, {"name": "first_dispatch_seconds_total"}) == 112.5
    assert read({"snap0": {"stats": {}}}, {"name": "first_dispatch_seconds_total"}) is None


def test_trace_module_share():
    read = reader("trace_module_share").read
    params = proposed_params("steps.prefill_device_share")
    tr = {"busy_s": 2.0, "devices": 1, "modules": {
        "jit_pstpu_step(1)": [10, 0.4, 0.04], "jit_pstpu_step_lp(2)": [1, 0.1, 0.1],
        "jit_pstpu_multi_step_k8(3)": [20, 1.4, 0.07], "jit_pstpu_spec_s8_k4_n3(4)": [1, 0.05, 0.05]}}
    assert read({"trace": tr}, params) == pytest.approx(25.0)
    # a program that names nothing (the parent's `jit__unknown`), and no trace at all
    assert read({"trace": dict(tr, modules={"jit__unknown(9)": [5, 1.0, 0.2]})}, params) is None
    assert read({"trace": None}, params) is None and read({"trace": dict(tr, busy_s=0.0)}, params) is None


def test_trace_roofline_counted():
    read = reader("trace_roofline_counted").read
    params = proposed_params("kernel.decode_attn_roofline_counted")
    # 3 s traced, of which a first dispatch idled the device for 1.2 s; the kernel ran 0.36 s
    tr = {"window_s": 3.0, "devices": 1, "top_gaps": [["%fusion.1", 1.2], ["%fusion.1", 0.012]],
          "ops": {KERNEL: [100, 0.36, 0.0036], "%fusion.1": [5, 1.0, 0.2]}}
    # a window of 51 s in which the engine stood 11 s at first dispatches
    snap0 = {"t": 100.0, "stats": {"decode_kv_tokens_read_total": 1_000_000,
                                   "first_dispatch_seconds_total": 120.0}}
    snap1 = {"t": 151.0, "stats": {"decode_kv_tokens_read_total": 1_000_000 + 40_000_000,
                                   "first_dispatch_seconds_total": 131.0}}
    ctx = {"trace": tr, "snap0": snap0, "snap1": snap1, "config": DOC,
           "peaks": {"hbm_bytes_per_s": 819e9}}
    # 4e7 tokens x 64 KiB a token in the 40 s the engine ran, against a kernel
    # busy 0.36 s of the 1.8 s the device was not stood still
    least = 40_000_000 * 2 * 16 * 8 * 128 * 2 / 819e9
    assert read(ctx, params) == pytest.approx(100.0 * (least / 40.0) / (0.36 / 1.8))
    # a parent's engine does not count; a trace without the kernel; no trace
    assert read(dict(ctx, snap1={"t": 151.0, "stats": {}}), params) is None
    assert read(dict(ctx, trace=dict(tr, ops={"%fusion.1": [5, 1.0, 0.2]})), params) is None
    assert read(dict(ctx, trace=None), params) is None


def _metrics(queued, drains):
    lines = ["# TYPE vllm:queued_ahead_dispatches_total counter"]
    lines += [f'vllm:queued_ahead_dispatches_total{{model_name="m",kind="{k}"}} {v}'
              for k, v in queued.items()]
    lines += [f'vllm:queue_ahead_drains_total{{model_name="m",reason="{k}"}} {v}'
              for k, v in drains.items()]
    return "\n".join(lines) + "\n"


def test_queued_ahead_share_reads_the_labelled_series_of_metrics():
    read = reader("metrics_ratio").read
    params = proposed_params("sched.queued_ahead_share")
    ctx = {"snap0": {"metrics": _metrics({"decode": 100, "prefill": 20}, {"idle": 30, "late": 1})},
           "snap1": {"metrics": _metrics({"decode": 500, "prefill": 153}, {"idle": 31, "late": 9,
                                                                           "first_dispatch": 2})}}
    # 533 of 544 dispatches of the window went out behind a running one (PERF.md, PR 47's sessions run)
    assert read(ctx, params) == pytest.approx(100.0 * 533 / 544)
    # a program without the counters (the parent of PR 47), and a window that dispatched nothing
    assert read({"snap0": {"metrics": ""}, "snap1": {"metrics": "vllm:num_requests_running 1\n"}},
                params) is None
    assert read(dict(ctx, snap0=ctx["snap1"]), params) is None


def test_dispatch_hold_share_through_the_accepted_reader():
    read = manifest.load_module("readers", "counter_ratio", PERFBENCH).read
    params = proposed_params("sched.dispatch_hold_share")
    sections = {"wait": 0.5, "schedule": 0.4, "step": 47.0, "apply": 1.6, "emit": 1.5}
    s0 = {f"engine_loop_{k}_seconds_total": 10.0 for k in sections}
    s1 = {k: v + sections[k.split("_")[2]] for k, v in s0.items()}
    s0["engine_dispatch_hold_seconds_total"], s1["engine_dispatch_hold_seconds_total"] = 3.0, 40.74
    ctx = {"snap0": {"stats": s0}, "snap1": {"stats": s1}}
    assert read(ctx, params) == pytest.approx(100.0 * 37.74 / 51.0)
    # the parts of `step` are not in the denominator: the five sections are the loop's wall
    assert not any("dispatch" in n or "chain" in n for n in params["den"])
    # a program without the hold (before PR 47): nothing of it counted
    del s1["engine_dispatch_hold_seconds_total"]
    assert read(ctx, params) == 0.0


def test_the_proposed_metrics_keep_the_benchmarks_words():
    """A proposed file becomes a file of the benchmark by being moved: its layer
    is one BENCHMARK.json names, its reader exists, and the README lists it."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        layers = {m["layer"] for m in json.load(f)["per_layer"]}
    with open(os.path.join(PROPOSED, "README.md")) as f:
        readme = f.read()
    for name in manifest.names("layer_metrics", PROPOSED):
        spec = manifest.load_json("layer_metrics", name + ".json", base=PROPOSED)
        assert spec["layer"] in layers and spec["moves"] == "tpot_p50_ms" or name.endswith("setup_s"), name
        assert any(os.path.exists(os.path.join(d, "readers", spec["reader"] + ".py"))
                   for d in (PROPOSED, PERFBENCH)), name
        assert f"`{name}`" in readme, name


@pytest.mark.slow  # ~1 min: an engine and a router child on the CPU
def test_the_four_are_read_through_perfbenchs_own_command(tmp_path):
    """The toy cell of perfbench/tests/test_rehearsal.py in a copy of perfbench/
    with the proposed files laid in and their names appended, as the
    `benchmark` PR will do. The counters give a number; the trace readers
    find no device plane on the CPU and leave their metrics out, as they do
    under a program that names nothing."""
    import run

    tree = tmp_path / "perfbench"
    shutil.copytree(PERFBENCH, tree, ignore=shutil.ignore_patterns(".jax_cache", ".out", "__pycache__"))
    shutil.copytree(os.path.join(PERFBENCH, "tests", "data", "rehearsal"), tree, dirs_exist_ok=True)
    shutil.copytree(PROPOSED, tree, dirs_exist_ok=True)
    cell_path = tree / "cells" / "tiny-llama.rehearsal.json"
    cell = json.load(open(cell_path))
    cell["per_layer"] += sorted(f[:-5] for f in os.listdir(os.path.join(PROPOSED, "layer_metrics")))
    json.dump(cell, open(cell_path, "w"))
    args = argparse.Namespace(workload="tiny-llama.rehearsal", seed=2**31 + 25, seconds=3.0,
                              trace=1, out=str(tmp_path / "out"))
    res = asyncio.run(run.run_cell(args, args.workload, str(tree), allow_platform="cpu"))
    assert res["correct"] and res["failed"] == 0
    got = {k[len("cpu_rehearsal."):]: v["value"] for k, v in res["metrics"].items()}
    # the set-up met every shape of the toy: seconds stood before the window, and
    # what the window added to them (nothing, or a shape the set-up missed)
    assert got["steps.first_dispatch_setup_s"] > 0
    assert 0 <= got["steps.first_dispatch_stall_s"] < 3.0
    # no device plane on the CPU: nothing to read, and the line leaves them out
    assert "steps.prefill_device_share" not in got
    assert "kernel.decode_attn_roofline_counted" not in got
