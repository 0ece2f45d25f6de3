"""tracereduce.py on a small trace recorded on the v5e (two jitted programs,
four runs each, 2 ms sleeps between), and its pieces on hand-made events."""

import os

import pytest

import tracereduce

SMALL = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data", "small_tpu.xplane.pb")


def test_small_recorded_trace():
    r = tracereduce.reduce(SMALL)
    assert r["devices"] == 1 and any(p["plane"] == "/device:TPU:0" for p in r["planes"])
    # the device's own extent, not the host's python line (which starts 38 ms earlier)
    assert r["window_s"] == pytest.approx(0.014068133, rel=1e-6)
    assert r["busy_s"] == pytest.approx(7.9954e-05, rel=1e-4)
    assert r["busy_s"] + r["idle_s"] == pytest.approx(r["window_s"])
    assert {v[0] for v in r["modules"].values()} == {4} and len(r["modules"]) == 2
    matmul = next(v for n, v in r["ops"].items() if n.startswith("%convolution_reduce_fusion"))
    assert matmul[0] == 4 and matmul[2] == pytest.approx(11.819e-6, rel=1e-3)
    assert r["top_ops"][0][0].startswith("%convolution_reduce_fusion")
    # the longest gaps are the sleeps after the matmul program
    assert r["top_gaps"][0][1] == pytest.approx(0.0035, rel=0.05)
    assert r["programs_with"] == {}  # no custom call in this trace


def test_union_counts_overlap_once_and_names_the_gap():
    evs = [(0, 10, "a"), (5, 20, "b"), (30, 40, "c"), (32, 35, "d")]
    busy, gaps = tracereduce.union_and_gaps(evs)
    assert busy == 30 and gaps == [(10, "b")]


def test_a_while_that_holds_other_operations_is_not_a_leaf():
    evs = [(0, 100, "%while.1"), (0, 40, "%fusion.1"), (40, 90, "%kernel.2"), (100, 120, "%copy")]
    assert [e[2] for e in tracereduce.leaves(evs)] == ["%fusion.1", "%kernel.2", "%copy"]


def test_programs_are_found_by_the_kernel_inside_them():
    mods = [(0, 100, "jit__unknown(1)"), (200, 300, "jit__unknown(2)"), (400, 520, "jit__unknown(1)")]
    ops = [(10, 20, "%ragged_paged_attention_decode.10 = bf16[64,32,128] custom-call(s32[64,16] %x)"),
           (30, 40, "%ragged_paged_attention_decode.10 = bf16[64,32,128] custom-call(s32[64,16] %x)"),
           (210, 220, "%ragged_paged_attention_prefill.15 = (bf16[4,512,32,128]) custom-call(%y)"),
           (410, 420, "%ragged_paged_attention_decode.10 = bf16[64,32,128] custom-call(s32[64,16] %x)"),
           (430, 440, "%fusion.3 = bf16[4] fusion(%z)")]
    got = tracereduce.programs_with(mods, ops)
    assert set(got) == {"ragged_paged_attention_decode", "ragged_paged_attention_prefill"}
    assert tracereduce.merge(got["ragged_paged_attention_decode"]) == [2, 220e-9, 110e-9]
    assert tracereduce.merge(got["ragged_paged_attention_prefill"])[0] == 1
