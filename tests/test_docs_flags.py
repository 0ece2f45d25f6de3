"""Doc-rot guard: every engine/router CLI flag mentioned in tutorials and
docs must actually exist in the parsers. The tutorials are the reference
curriculum's parity surface — a renamed flag silently breaks them."""

import argparse
import pathlib
import re

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
