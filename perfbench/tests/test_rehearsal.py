"""The whole command, rehearsed on the CPU at a toy size. The rehearsal is
reachable only from here (`run_cell(..., allow_platform="cpu")` on a temporary
copy that adds the toy's files); the command line itself refuses to run off
the TPU, and never imports JAX."""

import argparse
import asyncio
import json
import os
import shutil
import subprocess
import sys

import pytest

import run

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)
TOY = os.path.join(HERE, "tests", "data", "rehearsal")


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    copy = tmp_path_factory.mktemp("rehearsal") / "perfbench"
    shutil.copytree(HERE, copy, ignore=shutil.ignore_patterns(".jax_cache", ".out", "__pycache__"))
    # the toy's own files: its configuration and cell, and metrics that no cell of
    # the benchmark reports yet (spans, gauges, a TTFT held to a bound), so that
    # what run.py hands to such readers stays rehearsed
    for kind in sorted(os.listdir(TOY)):
        for f in os.listdir(os.path.join(TOY, kind)):
            shutil.copy(os.path.join(TOY, kind, f), copy / kind / f)
    return copy


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_on_the_cpu(tree, tmp_path, trace):
    args = argparse.Namespace(workload="tiny-llama.rehearsal", seed=2**31 + 11, seconds=4.0,
                              trace=trace, out=str(tmp_path / "out"))
    res = asyncio.run(run.run_cell(args, args.workload, str(tree), allow_platform="cpu"))
    assert res["correct"] and res["failed"] == 0 and res["attempted"] > 10
    assert res["device"]["platform"] == "cpu"
    cell = json.load(open(tree / "cells" / "tiny-llama.rehearsal.json"))
    # no number from the CPU stands under a metric's name
    assert res["metrics"] and all(k.startswith("cpu_rehearsal.") for k in res["metrics"])
    names = {k[len("cpu_rehearsal."):] for k in res["metrics"]}
    if trace:
        # trace readers find no device plane and return nothing: left out
        assert names == set(cell["per_layer"])
        assert res["metrics"]["cpu_rehearsal.kv.prefix_hit_share"]["value"] > 10
    else:
        assert names == set(cell["end_to_end"])
    log = json.load(open(tmp_path / "out" / "requests.json"))
    assert sum(1 for r in log["requests"] if r["measured"]) == res["attempted"]
    assert "jax" not in sys.modules or os.environ.get("JAX_PLATFORMS") == "cpu"


def _windows(monkeypatch, limits):
    """`run.STALL_S` set anew before each window of a run: limits[k] for window
    k, the last for all further ones. Returns the list of windows as they came back."""
    real, opened = run.measure_window, []

    async def measure_window(*a, **k):
        monkeypatch.setattr(run, "STALL_S", limits[min(len(opened), len(limits) - 1)])
        opened.append(None)
        opened[-1] = await real(*a, **k)
        return opened[-1]

    monkeypatch.setattr(run, "measure_window", measure_window)
    return opened


@pytest.mark.parametrize("trace", [0, 1])
def test_a_window_whose_generator_ran_late_is_measured_again(tree, tmp_path, monkeypatch, capfd, trace):
    opened = _windows(monkeypatch, [1e-9, 1.0])  # any lateness voids the first window
    args = argparse.Namespace(workload="tiny-llama.rehearsal", seed=5, seconds=3.0, trace=trace,
                              out=str(tmp_path / "out"))
    res = asyncio.run(run.run_cell(args, args.workload, str(tree), allow_platform="cpu"))
    err = capfd.readouterr().err
    first, second = opened
    assert "window 1 voided" in err and "window 2 held after 1 voided" in err
    assert "ms late in the window" in first["void"] and second["void"] is None
    assert res["correct"] and res["failed"] == 0 and res["attempted"] > 5
    log = json.load(open(tmp_path / "out" / "requests.json"))
    assert log["voided"] == [first["void"]] and log["window"] == [second["t0"], second["t1"]]
    # the void window was abandoned at its first late beat (in the last second of its
    # lead-in, so it never took a snapshot), not at its t1
    assert first["snap0"] is None and first["snap1"] is None
    # its requests stay in the log, unmeasured, and the second window's are other text
    assert sum(1 for r in log["requests"] if r["measured"]) == res["attempted"]
    assert all(second["t0"] <= r["due"] < second["t1"] for r in log["requests"] if r["measured"])
    assert json.dumps(first["plan"]["open"]) != json.dumps(second["plan"]["open"])
    assert [p["name"] for p in second["plan"]["setup"] if p.get("every_window")] == ["qa.histories"]
    assert err.count("set-up phase qa.histories") == 2 and err.count("set-up phase chat.ramp") == 1
    # setup_s is the FIRST window's opening, whatever happens after
    assert log["setup_s"] == first["t0"] - run.T_START < second["t0"] - run.T_START - 1.0
    if trace:
        assert res["metrics"]["cpu_rehearsal.bench.windows_voided"]["value"] == 1
        assert res["metrics"]["cpu_rehearsal.kv.prefix_hit_share"]["value"] > 10
    else:
        assert res["metrics"]["cpu_rehearsal.setup_s"]["value"] == log["setup_s"]


def test_a_run_whose_every_window_is_void_fails_after_its_windows(tree, tmp_path, monkeypatch, capfd):
    opened = _windows(monkeypatch, [1e-9])
    args = argparse.Namespace(workload="tiny-llama.rehearsal", seed=5, seconds=2.0, trace=0,
                              out=str(tmp_path / "out"))
    with pytest.raises(run.BenchFailure, match="ms late in the window.*no room to measure again"):
        asyncio.run(run.run_cell(args, args.workload, str(tree), allow_platform="cpu"))
    assert len(opened) == run.MAX_WINDOWS


def test_a_run_with_no_room_left_fails_at_its_first_void_window(tree, tmp_path, monkeypatch):
    opened = _windows(monkeypatch, [1e-9])
    monkeypatch.setattr(run, "RUN_LIMIT_S", 1.0)  # the budget rule, not the count, ends it
    args = argparse.Namespace(workload="tiny-llama.rehearsal", seed=5, seconds=2.0, trace=0,
                              out=str(tmp_path / "out"))
    with pytest.raises(run.BenchFailure, match="no room to measure again: window 1 of 3"):
        asyncio.run(run.run_cell(args, args.workload, str(tree), allow_platform="cpu"))
    assert len(opened) == 1


def test_the_command_refuses_to_run_off_the_tpu(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(HERE, "run.py"), "--workload", "mistral-7b-d16.chat",
         "--seed", "1", "--seconds", "1", "--trace", "0", "--out", str(tmp_path / "out")],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0 and proc.stdout.strip() == ""
    assert "not 'tpu'" in proc.stderr


def test_the_command_needs_the_program_beside_it(tmp_path):
    shutil.copytree(HERE, tmp_path / "perfbench",
                    ignore=shutil.ignore_patterns(".jax_cache", ".out", "__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    proc = subprocess.run(
        [sys.executable, "perfbench/run.py", "--workload", "mistral-7b-d16.chat",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, capture_output=True, text=True, timeout=60)
    assert proc.returncode != 0 and proc.stdout.strip() == ""
