#!/usr/bin/env python3
"""Finds the benchmark's data by name, and writes BENCHMARK.json from it.

Everything that belongs to one configuration, one cell, one end-to-end metric
or one per-layer metric is a file of its own under `perfbench/`:

  configs/<config>.json  cells/<cell>.json  end_to_end/<metric>.json
  layer_metrics/<metric>.json  readers/<reader>.py  traffic/<generator>.py
  reference/<family>.py

A later PR adds files and never edits one; `python3 perfbench/manifest.py
--write` then regenerates the lists of BENCHMARK.json (configs, workloads, the
metrics and the cells each is reported in) and keeps `command`, `paths` and
`run_seconds` as they are. Every list keeps the order BENCHMARK.json has and
new names are appended, sorted among themselves: an entry put first or in the
middle reads as a change to an accepted one. `--check` fails when
BENCHMARK.json is out of date.
"""

from __future__ import annotations

import importlib.util
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))


def load_json(*parts, base: str = HERE) -> dict:
    path = os.path.join(base, *parts)
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError:
        raise SystemExit(f"perfbench: no file {os.path.relpath(path, os.path.dirname(base))}")


def load_module(kind: str, name: str, base: str = HERE):
    """`readers/<name>.py` or `traffic/<name>.py`, loaded by path so that a
    new file is found without an edit anywhere."""
    path = os.path.join(base, kind, name + ".py")
    if not os.path.exists(path):
        raise SystemExit(f"perfbench: no {kind} module {name!r} ({path})")
    spec = importlib.util.spec_from_file_location(f"perfbench_{kind}_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def names(kind: str, base: str = HERE) -> list[str]:
    d = os.path.join(base, kind)
    return sorted(f[:-5] for f in os.listdir(d) if f.endswith(".json"))


def accepted_first(found: list[str], accepted: list[dict]) -> list[str]:
    """`found` in the order the accepted list names them, then the new names."""
    have = [e["name"] for e in accepted if e["name"] in found]
    return have + [n for n in found if n not in have]


def peaks(device_kind: str, base: str = HERE) -> dict:
    table = load_json("peaks.json", base=base)
    if device_kind not in table:
        raise SystemExit(
            f"perfbench: no peaks for device kind {device_kind!r}; known: {sorted(table)}")
    return table[device_kind]


def build(current: dict, base: str = HERE) -> dict:
    rel = os.path.basename(base)
    cells = {n: load_json("cells", n + ".json", base=base)
             for n in accepted_first(names("cells", base), current.get("workloads", []))}
    used = accepted_first(sorted({c["config"] for c in cells.values()}), current.get("configs", []))
    out = {
        "command": current["command"], "paths": current["paths"],
        "run_seconds": current["run_seconds"], "configs": [], "workloads": [],
        "end_to_end": [], "per_layer": [],
    }
    for n in used:
        doc = load_json("configs", n + ".json", base=base)
        out["configs"].append({
            "name": n, "source": doc["source"], "file": f"{rel}/configs/{n}.json",
            "reduced": sorted(doc.get("reduced", {})), "why": doc["why"],
        })
    for n, c in cells.items():
        out["workloads"].append({
            "name": n, "config": c["config"], "traffic": c["traffic"]["name"],
            "chips": c["chips"], "why": c["why"],
        })
    for kind, key in (("end_to_end", "end_to_end"), ("layer_metrics", "per_layer")):
        for n in accepted_first(names(kind, base), current.get(key, [])):
            m = load_json(kind, n + ".json", base=base)
            where = [c for c, cell in cells.items() if n in cell[key]]
            if not where:
                continue
            entry = {"name": n, "unit": m["unit"], "better": m["better"]}
            if key == "end_to_end":
                entry.update(bound=m["bound"], source=m["source"])
            else:
                entry.update(source=m["source"], layer=m["layer"], moves=m["moves"])
                missing = [c for c in where if m["moves"] not in cells[c]["end_to_end"]]
                if missing:
                    raise SystemExit(f"perfbench: {n} moves {m['moves']}, which {missing} do not report")
            if len(where) < len(cells):
                entry["workloads"] = where
            out[key].append(entry)
    return out


def main(argv) -> int:
    path = os.path.join(os.path.dirname(HERE), "BENCHMARK.json")
    with open(path) as f:
        current = json.load(f)
    built = build(current)
    if "--write" in argv:
        with open(path, "w") as f:
            json.dump(built, f, indent=1)
            f.write("\n")
        return 0
    if built != current:
        print("BENCHMARK.json is out of date: run python3 perfbench/manifest.py --write",
              file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
