"""graftcheck core: one parsed view of the tree, shared by every checker.

The tree is walked ONCE (same shape as check_metrics_coverage.py): every
package .py file is read and ast-parsed into a PyFile, and the checkers run
over that shared RepoIndex — no checker re-reads the filesystem. Findings
carry a line number for humans and a line-independent identity key
(``rule:path:scope:detail``) for the baseline, so unrelated edits above a
baselined finding cannot churn the baseline file.

Suppression contract (docs/static-analysis.md):

    x = blocking_thing()  # graftcheck: disable=GC001 — <why this is safe>

applies to findings on its own line; a standalone suppression comment
applies to the next line. The reason (anything after the dash) is
MANDATORY, and a suppression that matches no finding is itself reported —
the same rot policy the metrics guard applies to its allowlist.

Baseline contract: ``baseline.json`` next to this module holds
``{"key": <finding key>, "reason": <why fixing is not local>}`` entries for
proven-benign pre-existing findings. Every entry needs a non-empty reason,
and an entry matching no current finding is rot (fails the guard), so the
baseline can only shrink unless a justified entry is added consciously.
"""

from __future__ import annotations

import ast
import dataclasses
import json
import pathlib
import re
from typing import Callable, Iterable, Optional

REPO = pathlib.Path(__file__).resolve().parent.parent.parent

# meta-rules (suppression/baseline hygiene). Not suppressible themselves.
META_RULES = ("GC-SUPPRESS-REASON", "GC-SUPPRESS-UNUSED", "GC-BASELINE")

# default scan surface: the package plus the asyncio/JAX-driving entrypoints
# (chaos scripts + the benchmark load generator). tests/ are
# deliberately out of scope — fixture files MUST violate rules.
DEFAULT_ROOTS = ("production_stack_tpu", "scripts", "benchmarks")

_SUPPRESS_RE = re.compile(
    r"#\s*graftcheck:\s*disable=((?:GC\d{3})(?:\s*,\s*GC\d{3})*)"
    r"(?:\s*[—–-]+\s*(\S.*))?"
)


@dataclasses.dataclass
class Finding:
    rule: str          # "GC001".."GC005" or a META_RULES id
    path: str          # repo-relative posix path
    line: int          # 1-based, for humans
    scope: str         # dotted enclosing scope ("Class.method" / "<module>")
    detail: str        # stable short identity ("time.sleep", "open via _x")
    message: str       # full human-readable description

    @property
    def key(self) -> str:
        """Line-independent identity used by baseline.json."""
        return f"{self.rule}:{self.path}:{self.scope}:{self.detail}"

    def render(self) -> str:
        return f"{self.path}:{self.line} [{self.rule}] {self.scope}: {self.message}"


@dataclasses.dataclass
class Suppression:
    line: int           # line the comment sits on
    rules: tuple        # ("GC001", ...)
    reason: str         # "" = missing (a violation)
    standalone: bool    # comment-only line -> applies to the NEXT line
    used: bool = False


class PyFile:
    def __init__(self, path: pathlib.Path, repo: pathlib.Path):
        self.abspath = path
        self.path = path.relative_to(repo).as_posix()
        self.text = path.read_text(encoding="utf-8", errors="replace")
        self.lines = self.text.splitlines()
        try:
            self.tree: Optional[ast.Module] = ast.parse(self.text)
        except SyntaxError:
            self.tree = None
        self.suppressions = self._parse_suppressions()

    def _parse_suppressions(self) -> list[Suppression]:
        out = []
        for i, line in enumerate(self.lines, start=1):
            m = _SUPPRESS_RE.search(line)
            if not m:
                continue
            rules = tuple(r.strip() for r in m.group(1).split(","))
            out.append(Suppression(
                line=i,
                rules=rules,
                reason=(m.group(2) or "").strip(),
                standalone=line.strip().startswith("#"),
            ))
        return out

    def suppression_for(self, rule: str, line: int) -> Optional[Suppression]:
        """Inline suppression on the finding's line, or a standalone comment
        directly above it."""
        for s in self.suppressions:
            if rule not in s.rules:
                continue
            if (s.line == line and not s.standalone) or (
                s.standalone and s.line == line - 1
            ):
                return s
        return None


class RepoIndex:
    """Every package .py file, read + parsed once. ``by_module`` maps dotted
    module names (``production_stack_tpu.router.app``) to PyFile so GC001 can
    resolve one level of intra-package calls through imports."""

    def __init__(self, repo: pathlib.Path = REPO,
                 roots: Iterable[str] = DEFAULT_ROOTS):
        self.repo = repo
        self.files: list[PyFile] = []
        self.by_module: dict[str, PyFile] = {}
        for root in roots:
            base = repo / root
            if base.is_file():
                self._add(base)
                continue
            for path in sorted(base.rglob("*.py")):
                # the analyzer's own sources carry example violations and
                # suppression syntax in documentation — scanning itself
                # would report its own docs as rot
                if "graftcheck" in path.parts:
                    continue
                self._add(path)

    def _add(self, path: pathlib.Path) -> None:
        pf = PyFile(path, self.repo)
        self.files.append(pf)
        mod = pf.path[:-3].replace("/", ".")
        if mod.endswith(".__init__"):
            mod = mod[: -len(".__init__")]
        self.by_module[mod] = pf

    def get(self, relpath: str) -> Optional[PyFile]:
        for f in self.files:
            if f.path == relpath:
                return f
        return None


# -- shared AST helpers --------------------------------------------------------

def dotted_name(node: ast.AST) -> Optional[str]:
    """'a.b.c' for nested Name/Attribute chains, else None."""
    parts: list[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def expr_text(node: ast.AST) -> str:
    """Canonical source-ish text for expression identity (use-after-donate
    tracking compares these)."""
    try:
        return ast.unparse(node)
    except Exception:  # noqa: BLE001 - identity only needs stability
        return ast.dump(node)


def walk_scoped(tree: ast.AST):
    """Yield (scope, node) for every function/class body node, where scope is
    the dotted enclosing def/class path ('' at module level)."""
    def visit(node, scope):
        for child in ast.iter_child_nodes(node):
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef, ast.ClassDef)):
                sub = f"{scope}.{child.name}" if scope else child.name
                yield sub, child
                yield from visit(child, sub)
            else:
                yield from visit(child, scope)
    yield from visit(tree, "")


def iter_nodes_skipping_nested_defs(body: Iterable[ast.stmt]):
    """Walk statements' subtrees without descending into nested function or
    class definitions (a nested def is a different execution context — for
    GC001 it is almost always an executor thunk)."""
    stack = list(body)
    while stack:
        node = stack.pop()
        yield node
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.ClassDef, ast.Lambda)):
            continue  # a nested def is a different execution context
        stack.extend(ast.iter_child_nodes(node))


# -- incremental (--changed) support -------------------------------------------

# contract checkers diff whole surfaces against each other; a one-file diff
# filter would hide the far side of a drift, so their findings always
# survive --changed filtering (they are cheap — pure extraction + set diff)
CONTRACT_RULES = ("GC005", "GC009", "GC010")


def changed_paths(repo: pathlib.Path = REPO) -> "Optional[set[str]]":
    """Repo-relative posix paths touched in the working tree + index
    (staged, unstaged, untracked), from ``git status --porcelain``. Returns
    None when git (or the repository index) is unavailable — callers fall
    back to the full tree."""
    import subprocess

    try:
        out = subprocess.run(
            ["git", "-C", str(repo), "status", "--porcelain",
             "--untracked-files=all"],
            capture_output=True, text=True, timeout=30,
        )
    except (OSError, subprocess.TimeoutExpired):
        return None
    if out.returncode != 0:
        return None
    paths: set[str] = set()
    for line in out.stdout.splitlines():
        if len(line) < 4:
            continue
        path = line[3:]
        if " -> " in path:  # rename: old -> new; the NEW path is the live one
            path = path.split(" -> ", 1)[1]
        paths.add(path.strip().strip('"'))
    return paths


def filter_changed(violations: "list[Finding]",
                   changed: "set[str]") -> "list[Finding]":
    """Pre-commit view: keep findings on changed files, every contract-rule
    finding (the drift may sit on the unchanged side), and baseline-rot
    findings only when baseline.json itself changed."""
    out = []
    for f in violations:
        if f.rule in CONTRACT_RULES:
            out.append(f)
        elif f.rule == "GC-BASELINE":
            if f.path in changed:
                out.append(f)
        elif f.path in changed:
            out.append(f)
    return out


# -- runner --------------------------------------------------------------------

BASELINE_PATH = pathlib.Path(__file__).resolve().parent / "baseline.json"


def load_baseline(path: pathlib.Path = BASELINE_PATH) -> list[dict]:
    if not path.exists():
        return []
    return json.loads(path.read_text())


def _checkers() -> list:
    from . import gc001_eventloop, gc002_donation, gc003_tracer, gc004_locks
    from . import gc005_endpoints, gc006_tasks, gc007_ownership
    from . import gc008_offloop, gc009_wire, gc010_metrics

    return [gc001_eventloop, gc002_donation, gc003_tracer, gc004_locks,
            gc005_endpoints, gc006_tasks, gc007_ownership, gc008_offloop,
            gc009_wire, gc010_metrics]


def run_graftcheck(
    repo: pathlib.Path = REPO,
    roots: Iterable[str] = DEFAULT_ROOTS,
    baseline: Optional[list[dict]] = None,
    checkers: Optional[list] = None,
    index: Optional[RepoIndex] = None,
) -> "tuple[list[Finding], dict]":
    """Run every checker over one shared RepoIndex. Returns
    ``(violations, stats)`` where violations is everything NOT silenced by a
    reasoned suppression or a matching baseline entry — including the
    hygiene meta-findings (reasonless suppression, unused suppression,
    baseline rot). Empty list == the guard passes.

    With an explicit ``checkers`` subset, hygiene checks scope to the
    selected rules: baseline entries and suppressions for UNSELECTED rules
    are neither applied nor reported as rot — `--rule GC001` on a clean
    tree must pass, not trip over another rule's silencers."""
    index = index or RepoIndex(repo, roots)
    baseline = load_baseline() if baseline is None else baseline
    active = checkers if checkers is not None else _checkers()
    active_rules = {c.RULE for c in active}
    baseline = [
        e for e in baseline
        if (e.get("key") or "").split(":", 1)[0] in active_rules
    ]
    raw: list[Finding] = []
    for checker in active:
        raw.extend(checker.check(index))

    violations: list[Finding] = []
    suppressed = 0
    for f in raw:
        pf = index.get(f.path)
        sup = pf.suppression_for(f.rule, f.line) if pf else None
        if sup is not None:
            sup.used = True
            if not sup.reason:
                violations.append(Finding(
                    "GC-SUPPRESS-REASON", f.path, sup.line, f.scope, f.detail,
                    f"suppression of {f.rule} has no reason — "
                    "'# graftcheck: disable=GCnnn — <reason>' is the contract",
                ))
            else:
                suppressed += 1
            continue
        violations.append(f)

    # baseline: reasoned entries silence matching findings; rot fails
    by_key: dict[str, list[Finding]] = {}
    for f in list(violations):
        if f.rule not in META_RULES:  # hygiene findings cannot be baselined
            by_key.setdefault(f.key, []).append(f)
    baselined = 0
    for entry in baseline:
        key = entry.get("key", "")
        reason = (entry.get("reason") or "").strip()
        matched = by_key.pop(key, None)
        if not reason:
            violations.append(Finding(
                "GC-BASELINE", "scripts/graftcheck/baseline.json", 0,
                "<baseline>", key,
                f"baseline entry {key!r} has no reason — justifications are "
                "mandatory",
            ))
            continue
        if matched is None:
            violations.append(Finding(
                "GC-BASELINE", "scripts/graftcheck/baseline.json", 0,
                "<baseline>", key,
                f"baseline entry {key!r} matches no current finding "
                "(stale — delete it)",
            ))
            continue
        for f in matched:
            violations.remove(f)
            baselined += 1

    # unused suppressions are rot, exactly like a stale baseline entry
    # (only for rules that actually ran — a GC004 suppression is not rot
    # just because this invocation only ran GC001)
    for pf in index.files:
        for s in pf.suppressions:
            if not s.used and set(s.rules) & active_rules:
                violations.append(Finding(
                    "GC-SUPPRESS-UNUSED", pf.path, s.line, "<module>",
                    f"unused:{s.line}",
                    f"suppression of {', '.join(s.rules)} matches no finding "
                    "(stale — delete it)",
                ))

    stats = {
        "files": len(index.files),
        "raw_findings": len(raw),
        "suppressed": suppressed,
        "baselined": baselined,
        "violations": len(violations),
    }
    return violations, stats
