"""scripts/hostspans.py on a small trace recorded on the v5e (PR 25's probe: two named
programs, `jit_pstpu_step` and `jit_pstpu_multi_step_k8`, run in turn inside
`pstpu.loop.*` spans that sleep 1-3 ms each), and its pieces on hand-made events."""

import os
import sys

import pytest

_ROOT = os.path.join(os.path.dirname(os.path.abspath(__file__)), os.pardir)
sys.path.insert(0, os.path.join(_ROOT, "scripts"))
import hostspans  # noqa: E402  (puts perfbench/ on the path for tracereduce)
import tracereduce  # noqa: E402

SMALL = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data", "small_spans.xplane.pb")


def test_small_recorded_trace_names_gaps_and_operations():
    r = hostspans.reduce(SMALL)
    # four loop iterations, the staging nested inside the dispatch
    assert {n: v[0] for n, v in r["spans"].items()} == {
        "pstpu.loop." + s: 4 for s in ("wait", "schedule", "step", "stage", "apply", "emit")}
    # the device plane's clock ran ahead of the host plane's: bounded, and applied
    assert r["clock_skew_s"] == pytest.approx(0.00155387, rel=1e-6)
    # the three long gaps are the rest of the loop between two dispatches; the
    # span that sleeps longest (apply, 3 ms) covers most of each
    long = [g for g in r["gaps"] if g[1] > 1e-3]
    assert len(long) == 3 and all(g[0] == "pstpu.loop.apply" for g in long)
    for name, secs, cover in long:
        assert secs == pytest.approx(0.0098, rel=0.03) and sum(cover.values()) == pytest.approx(secs)
        # the staging is INSIDE the step span: counted once, under its own name
        assert cover["pstpu.loop.stage"] == pytest.approx(0.00108, rel=0.05)
        assert cover["pstpu.loop.step"] < 0.001
    assert sum(r["idle_by_span"].values()) == pytest.approx(r["idle_s"])
    assert r["idle_by_span"]["(no span)"] < 0.01 * r["idle_s"]
    # idle time agrees with the other reducer's
    assert r["idle_s"] == pytest.approx(tracereduce.reduce(SMALL)["idle_s"], rel=1e-3)
    # operations are put down to the named scope of their metadata's tf_op
    assert set(r["scopes"]) == {"mlp", "lm_head", "(unscoped)"}
    assert r["scopes"]["mlp"] == pytest.approx(27.8e-6, rel=0.01)
    fusion = next(n for n in r["op_scopes"] if n.startswith("%convolution_reduce_fusion"))
    assert r["op_scopes"][fusion] == "lm_head"


def test_the_named_programs_are_told_apart_in_the_module_table():
    mods = tracereduce.reduce(SMALL)["modules"]
    assert sorted(n.split("(")[0] for n in mods) == ["jit_pstpu_multi_step_k8", "jit_pstpu_step"]


def test_innermost_span_at_each_instant():
    spans = [(0, 100, "step"), (10, 30, "stage"), (15, 20, "first"), (40, 90, "fetch"),
             (50, 60, "apply"), (120, 130, "wait")]
    assert hostspans.innermost(spans) == [
        (0, 10, "step"), (10, 15, "stage"), (15, 20, "first"), (20, 30, "stage"),
        (30, 40, "step"), (40, 50, "fetch"), (50, 60, "apply"), (60, 90, "fetch"),
        (90, 100, "step"), (120, 130, "wait")]


def test_gaps_are_attributed_by_overlap():
    ops = [(0, 10, "a"), (5, 20, "b"), (55, 70, "c"), (125, 140, "d")]
    gaps = hostspans.gap_intervals(ops)
    assert gaps == [(20, 55), (70, 125)]
    segments = hostspans.innermost([(0, 100, "step"), (40, 90, "fetch"), (120, 130, "wait")])
    assert hostspans.attribute(gaps, segments) == [
        {"step": 20, "fetch": 15}, {"fetch": 20, "step": 10, "wait": 5}]


def test_scope_of_a_tf_op_path():
    assert hostspans.scope_of("jit(pstpu_step)/attention/dot_general") == "attention"
    assert hostspans.scope_of("jit(pstpu_multi_step_k8)/while/body/jit(_take)/kv_gather/gather") == "kv_gather"
    assert hostspans.scope_of("jit(pstpu_step)/while/body/add") == "(unscoped)"


def test_a_gap_under_twice_the_clock_skew_is_not_guessed():
    r = hostspans.reduce(SMALL)
    floor = 2 * r["clock_skew_s"]
    short = [g for g in r["gaps"] if g[1] < floor]
    assert short and all(g[0] == "(under clock skew)" for g in short)
    assert all(g[0] != "(under clock skew)" for g in r["gaps"] if g[1] >= floor)
    # its seconds still go to the spans that cover it: the sums are what they were
    assert sum(r["idle_by_span"].values()) == pytest.approx(r["idle_s"])
    assert "(under clock skew)" not in r["idle_by_span"]


@pytest.mark.parametrize("gap, cover, want", [
    # the turn open when the next program started says what had emptied the loop
    ((1000, 9000), {"pstpu.loop.schedule": 5000, "pstpu.loop.hold": 3000}, "drain:late"),
    # ... whatever the host did for most of the gap (it waited on its inbox: nothing to run)
    ((20000, 90000), {"pstpu.loop.wait": 69000, "pstpu.loop.step": 1000}, "drain:idle"),
    # a turn that was queued ahead carries no drain: the innermost span covering most of it
    ((100000, 104000), {"pstpu.loop.fetch": 3000, "pstpu.loop.apply": 1000}, "pstpu.loop.fetch"),
    # the turn with a drain ended before the next program started: not its gap
    ((200000, 205000), {"pstpu.loop.emit": 4000, "(no span)": 1000}, "pstpu.loop.emit"),
    # shorter than twice the skew: not guessed, drain or no drain
    ((8500, 9000), {"pstpu.loop.call": 500}, "(under clock skew)"),
    # no span over it (a turn opened before the profile started, a profile around the control)
    ((300000, 310000), {"(no span)": 10000}, "(no span)"),
])
def test_the_rule_by_which_a_gap_is_named(gap, cover, want):
    drains = [(8000, 12000, "late"), (88000, 95000, "idle"), (190000, 199000, "first_dispatch")]
    assert hostspans.name_gap(*gap, cover, drains, 400) == want
