"""Doc-rot guard: every engine/router CLI flag mentioned in tutorials and
docs must actually exist in the parsers. The tutorials are the reference
curriculum's parity surface — a renamed flag silently breaks them. Likewise
every file of this repository a page cites must be in the tree: a deleted
tool that a page still sends people to is the same rot."""

import argparse
import functools
import os
import pathlib
import re

import pytest

REPO = pathlib.Path(__file__).resolve().parent.parent


def _known_flags() -> set:
    from production_stack_tpu.engine.config import add_engine_args

    flags = set()
    p = argparse.ArgumentParser()
    add_engine_args(p)
    for a in p._actions:
        flags.update(a.option_strings)
    # router + benchmark + fake-engine flags: only REGISTERED flags count — a
    # flag name quoted in help text or an error message must not satisfy the
    # guard (the fake engine is a first-party CLI: its fault-injection flags
    # are documented in docs/failure-handling.md)
    for rel in (("production_stack_tpu", "router", "parser.py"),
                ("production_stack_tpu", "testing", "fake_engine.py"),
                ("production_stack_tpu", "kvoffload", "cache_server.py"),
                ("benchmarks", "multi_round_qa.py"),
                ("perfbench", "run.py"),
                ("scripts", "chaos_check.py"),
                ("scripts", "trace_report.py"),
                ("scripts", "hostspans.py"),
                ("scripts", "kv_directory_report.py"),
                ("scripts", "fleet_controller.py"),
                ("scripts", "graftcheck", "__main__.py")):
        src = REPO.joinpath(*rel).read_text()
        flags.update(re.findall(r'add_argument\(\s*"(--[a-z0-9-]+)"', src))
    return flags


def test_doc_flags_exist():
    known = _known_flags()
    # flags that belong to OTHER tools (kubectl/helm/gcloud/docker/
    # huggingface-cli/kgateway) or are the REFERENCE's vLLM flags quoted in
    # comparison tables
    foreign = {
        "--set", "--cluster", "--zone", "--machine-type", "--num-nodes",
        "--node-locations", "--tpu-topology", "--namespace", "--values",
        "--pod-network-cidr", "--print-join-command", "--context", "--help",
        "--version", "--watch", "--timeout", "--create-namespace", "--wait",
        "--kubeconfig", "--dry-run", "--image", "--tag", "--push", "--file",
        "--output", "--rm", "--overrides", "--local-dir", "--pool",
        "--enable-autoscaling",
        # git flags quoted when documenting graftcheck --changed
        "--porcelain",
        # reference vLLM flags, quoted when contrasting with our design
        "--distributed-executor-backend", "--enable-auto-tool-choice",
        # pytest flags quoted in the README dev section
        "--durations",
    }
    missing = {}
    pages = (
        list(REPO.glob("tutorials/**/*.md"))
        + list(REPO.glob("docs/*.md"))
        + [REPO / "README.md"]
    )
    for md in pages:
        text = md.read_text()
        for flag in set(re.findall(r"(?<![\w-])(--[a-z][a-z0-9_-]{2,})", text)):
            if flag in known or flag in foreign or flag.startswith("--xla"):
                continue
            missing.setdefault(md.name, []).append(flag)
    assert not missing, f"flags documented but not implemented: {missing}"


def test_the_benchmarks_trace_modes_on_the_pages_are_the_parsers():
    """`--trace <0|1>`: every value a page that tells how to run the benchmark
    gives is one `perfbench/run.py` takes (`--trace 2`, measure and then trace in
    one run, was built twice and left out: PERF.md section 7)."""
    src = REPO.joinpath("perfbench", "run.py").read_text()
    choices = re.search(r'"--trace", type=int, choices=\(([\d, ]+)\)', src)
    modes = {m.strip() for m in choices[1].split(",")}
    for rel in ("docs/benchmarking.md", "perfbench/README.md"):
        text = REPO.joinpath(rel).read_text()
        given = set(re.findall(r"--trace (\d)\b", text))
        for alt in re.findall(r"--trace <([\d|]+)>", text):
            given |= set(alt.split("|"))
        assert given and given <= modes, (rel, given)


def test_the_hostspans_command_keeps_its_flags():
    """`scripts/hostspans.py <file> --out <json>` as the pages give it."""
    src = REPO.joinpath("scripts", "hostspans.py").read_text()
    assert re.findall(r'add_argument\(\s*"([a-z-]+)"', src) == ["trace", "--out"]


# -- cited files exist ----------------------------------------------------------

CITING_PAGES = (
    "README.md",
    "docs/benchmarking.md",
    "docs/developer-guide.md",
    "docs/observability.md",
    "docs/tracing.md",
    "docs/static-analysis.md",
    "docs/multichip-serving.md",
    "docs/kv-fabric.md",
    "tutorials/07-benchmark-multi-round-qa-single-tpu.md",
)
_CITED_SUFFIXES = (".py", ".sh", ".json", ".md")
# files the commands on those pages write or download, named without a
# directory: not files of the repository
_MADE_BY_COMMANDS = {"r.json", "e.json", "fr.json", "sharegpt_processed.json"}


def _ignored() -> set:
    """Paths .gitignore keeps out of the tree (run outputs, caches)."""
    lines = (REPO / ".gitignore").read_text().splitlines()
    return {ln.strip().strip("/") for ln in lines
            if ln.strip() and not ln.startswith("#")} | {".git"}


@functools.cache
def _tree_files() -> frozenset:
    """Every file git would commit, as a repo-relative posix path (from the
    filesystem: the checkout under test need not be a git repository)."""
    skip, out = _ignored(), set()
    for root, dirs, files in os.walk(REPO):
        rel = pathlib.Path(root).relative_to(REPO)
        dirs[:] = [d for d in dirs
                   if d != "__pycache__" and (rel / d).as_posix() not in skip]
        out.update((rel / f).as_posix() for f in files)
    return frozenset(out - skip)


def _cited_paths(text: str) -> set:
    """File names in backticks or fenced commands that end like a file of
    ours; ``{a,b}`` alternatives are spelled out."""
    fenced = re.findall(r"```.*?```", text, re.S)
    inline = re.findall(r"`([^`\n]+)`", re.sub(r"```.*?```", "", text, flags=re.S))
    cited = set()
    for span in fenced + inline:
        for tok in re.findall(r"[\w./{},*-]+", span):
            tok = tok.rstrip(".,:;")
            if not tok.endswith(_CITED_SUFFIXES):
                continue
            m = re.fullmatch(r"(.*)\{([^{}]+)\}(.*)", tok)
            if m:
                cited.update(m[1] + alt + m[3] for alt in m[2].split(","))
            else:
                cited.add(tok)
    return cited


@pytest.mark.parametrize("page", CITING_PAGES)
def test_cited_files_exist(page):
    """A path that starts with a top-level directory of the tree, or with a
    directory of the package, must be a file there (``*`` as a glob); a bare
    file name must be the name of some file in the tree."""
    files = _tree_files()
    names = {f.rsplit("/", 1)[-1] for f in files}
    top_dirs = {f.split("/", 1)[0] for f in files if "/" in f}
    package_dirs = {f.split("/")[1] for f in files
                    if f.startswith("production_stack_tpu/") and f.count("/") > 1}
    dead = []
    for cited in sorted(_cited_paths((REPO / page).read_text())):
        first, _, rest = cited.partition("/")
        if not rest:
            ok = cited in names or cited in _MADE_BY_COMMANDS
        elif first in top_dirs:
            ok = any(REPO.glob(cited)) if "*" in cited else cited in files
        elif first in package_dirs:
            ok = "production_stack_tpu/" + cited in files
        else:
            continue  # another project's path (the reference's, a cluster's)
        if not ok:
            dead.append(cited)
    assert not dead, f"{page} cites files that are not in the tree: {dead}"


def test_no_generated_number_blocks():
    """The doc-number generator is gone (PR 34): a page that still carries
    its marker would promise numbers nothing renders."""
    marker = "BENCH_" + "NUMBERS_START"  # split: a grep for the name finds only the records
    records = {"ISSUE.md", "CHANGES.md"}  # the order and the record may name it
    holding = [
        f for f in _tree_files() - records
        if marker in (REPO / f).read_text(errors="replace")
    ]
    assert not holding, f"generated-number markers left in: {holding}"


def test_graftcheck_default_roots_exist():
    import sys

    sys.path.insert(0, str(REPO / "scripts"))
    try:
        from graftcheck import core
    finally:
        sys.path.pop(0)
    missing = [r for r in core.DEFAULT_ROOTS if not (REPO / r).exists()]
    assert not missing, f"graftcheck scans paths that do not exist: {missing}"
