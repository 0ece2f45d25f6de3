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
