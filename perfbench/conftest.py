"""One expected failure, named: `tests/test_manifest.py::
test_benchmark_json_is_what_the_files_say` holds BENCHMARK.json to
`manifest.build`'s order, which is sorted by name. A PR may only APPEND to
BENCHMARK.json's lists (an entry put first or in the middle reads as a change
to an accepted one), and PR 39's names (`jamba2-3b`, `kernel.ssm_*`) sort into
the middle. While BENCHMARK.json holds exactly the entries `build` gives, in
another order, that test is an expected failure; an entry that is missing,
stale or extra still fails it. The repair is `manifest.build` keeping the
accepted order and appending new names: an edit to an accepted benchmark file,
so a `benchmark` PR's (PERF.md section 7). `tests/test_jamba_cell.py` holds
the entries and the appended order meanwhile."""

import json
import os

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))


def _order_alone_differs() -> bool:
    import manifest

    with open(os.path.join(os.path.dirname(HERE), "BENCHMARK.json")) as f:
        current = json.load(f)
    built = manifest.build(current)
    lists = ("configs", "workloads", "end_to_end", "per_layer")
    by_name = dict(current, **{k: sorted(current[k], key=lambda e: e["name"]) for k in lists})
    return built != current and built == by_name


def pytest_collection_modifyitems(items):
    for item in items:
        if item.nodeid.endswith("test_manifest.py::test_benchmark_json_is_what_the_files_say"):
            if _order_alone_differs():
                item.add_marker(pytest.mark.xfail(
                    strict=True, reason="BENCHMARK.json appends new entries; manifest.build "
                    "sorts them by name (a benchmark PR's repair, PERF.md section 7)"))
