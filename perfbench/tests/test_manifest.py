"""The harness is driven by data: a cell, a configuration, a traffic generator,
a per-layer metric and an end-to-end metric can each be added as NEW FILES
plus manifest entries, in a temporary copy, with no edit to a file that is there."""

import filecmp
import json
import os
import shutil

import pytest

import manifest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)


def test_benchmark_json_is_what_the_files_say():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        current = json.load(f)
    assert manifest.build(current) == current
    assert len(json.dumps(current)) < 64 * 1024
    e2e = {m["name"] for m in current["end_to_end"]}
    for m in current["per_layer"]:
        assert m["moves"] in e2e


def test_every_cell_reports_setup_and_another_and_a_layer_metric():
    for name in manifest.names("cells"):
        cell = manifest.load_json("cells", name + ".json")
        assert "setup_s" in cell["end_to_end"] and len(cell["end_to_end"]) >= 2
        assert cell["per_layer"] and cell["chips"] in (1, 4)
        assert 1 <= len(cell["why"]) <= 200


def test_new_files_only(tmp_path):
    copy = tmp_path / "perfbench"
    shutil.copytree(HERE, copy, ignore=shutil.ignore_patterns(".jax_cache", ".out", "__pycache__"))
    base = str(copy)
    # a configuration, with its reference family
    doc = manifest.load_json("configs", "mistral-7b-d16.json")
    doc.update(name="other-7b", why="a new family", source="https://example.org/other/config.json")
    doc["perfbench"] = dict(doc["perfbench"], reference="other_family")
    (copy / "configs" / "other-7b.json").write_text(json.dumps(doc))
    (copy / "reference" / "other_family.py").write_text("def next_token_logprobs(*a, **k):\n    return None\n")
    # a traffic generator and a cell that uses it
    (copy / "traffic" / "replay.py").write_text(
        "def generate(params, seed, seconds, tokenizer):\n"
        "    return {'setup': [], 'open': [], 'clients': [], 'warm_seconds': 0.0}\n")
    # a per-layer metric with a reader of its own, and an end-to-end metric
    (copy / "readers" / "fixed.py").write_text("def read(ctx, params):\n    return params['value']\n")
    (copy / "layer_metrics" / "sched.batch_occupancy.json").write_text(json.dumps({
        "layer": "admission + scheduler", "unit": "%", "better": "higher",
        "source": "program_counter", "moves": "tpot_p95_ms", "reader": "fixed",
        "params": {"value": 50.0}}))
    (copy / "end_to_end" / "tpot_p95_ms.json").write_text(json.dumps({
        "unit": "ms", "better": "lower", "bound": 0.05, "source": "host_clock",
        "stat": {"kind": "percentile", "of": "tpot_ms", "q": 95, "min_samples": 200}}))
    cell = manifest.load_json("cells", "mistral-7b-d16.chat.json")
    cell.update(config="other-7b", why="a new cell",
                traffic={"name": "replayed", "generator": "replay", "params": {}},
                end_to_end=["tpot_p50_ms", "tpot_p95_ms", "setup_s"],
                per_layer=["sched.loop_host_share", "sched.batch_occupancy"])
    (copy / "cells" / "other-7b.replayed.json").write_text(json.dumps(cell))

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        current = json.load(f)
    built = manifest.build(current, base)
    assert {c["name"] for c in built["configs"]} == {c["name"] for c in current["configs"]} | {"other-7b"}
    new = next(w for w in built["workloads"] if w["name"] == "other-7b.replayed")
    assert new == {"name": "other-7b.replayed", "config": "other-7b", "traffic": "replayed",
                   "chips": 1, "why": "a new cell"}
    metric = next(m for m in built["per_layer"] if m["name"] == "sched.batch_occupancy")
    assert metric["workloads"] == ["other-7b.replayed"] and metric["moves"] == "tpot_p95_ms"
    host = next(m for m in built["per_layer"] if m["name"] == "sched.loop_host_share")
    assert "workloads" not in host  # still every cell
    assert next(m for m in built["end_to_end"] if m["name"] == "tpot_p95_ms")["workloads"] == [
        "other-7b.replayed"]
    # what the harness loads by name, it finds
    assert manifest.load_module("traffic", "replay", base).generate({}, 1, 1, {})["open"] == []
    assert manifest.load_module("readers", "fixed", base).read({}, {"value": 50.0}) == 50.0
    # and nothing that was there was edited
    cmp = filecmp.dircmp(HERE, base, ignore=[".jax_cache", ".out", "__pycache__"])

    def unchanged(c):
        assert not c.diff_files and not c.left_only, (c.diff_files, c.left_only)
        for sub in c.subdirs.values():
            unchanged(sub)
    unchanged(cmp)


def test_a_metric_whose_moved_metric_a_cell_lacks_is_refused(tmp_path):
    copy = tmp_path / "perfbench"
    shutil.copytree(HERE, copy, ignore=shutil.ignore_patterns(".jax_cache", ".out", "__pycache__"))
    shutil.copy(os.path.join(HERE, "tests", "data", "rehearsal", "layer_metrics",
                             "router.self_ms_p50.json"), copy / "layer_metrics")  # moves ttft_p50_ms
    cell = manifest.load_json("cells", "qwen2.5-7b-d14.sessions.json")
    cell["per_layer"] = cell["per_layer"] + ["router.self_ms_p50"]
    (copy / "cells" / "qwen2.5-7b-d14.sessions2.json").write_text(json.dumps(cell))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        current = json.load(f)
    with pytest.raises(SystemExit, match="moves ttft_p50_ms"):
        manifest.build(current, str(copy))


def test_peaks_refuse_an_unknown_device():
    assert manifest.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(SystemExit, match="no peaks for device kind"):
        manifest.peaks("TPU v9")
    with pytest.raises(SystemExit, match="no peaks for device kind"):
        manifest.peaks("cpu")
