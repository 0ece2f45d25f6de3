"""Percentile, due-time TTFT, TPOT and failure arithmetic on hand-made logs."""

import pytest

import manifest
import stats


def req(due, first, last, n, ok=True, measured=True, chunks=None, sent=None):
    return {"due": due, "sent": due if sent is None else sent, "first": first, "last": last,
            "chunks": chunks or [first, last], "ok": ok, "output_tokens": n,
            "prompt_tokens": 100, "cached_tokens": 0, "measured": measured}


def spec(name):
    if name == "tpot_p50_ms":  # the committed file
        return manifest.load_json("end_to_end", name + ".json")
    of, q = name.split("_")[0], int(name.split("_p")[1].split("_")[0])
    stat = {"kind": "percentile", "of": of + "_ms", "q": q}
    return {"stat": stat | ({"min_samples": 200} if q == 95 else {})}


def test_percentile_is_nearest_rank():
    vals = list(range(1, 101))
    assert stats.percentile(vals, 50) == 50 and stats.percentile(vals, 95) == 95
    assert stats.percentile([7.0], 95) == 7.0 and stats.percentile([1, 2, 3, 4], 50) == 2


def test_ttft_is_timed_from_when_the_request_was_due():
    late = req(due=10.0, first=10.5, last=11.5, n=11, sent=10.3)  # the generator ran late
    assert stats.ttft_ms(late, 1e5) == pytest.approx(500.0)
    assert stats.tpot_ms(late, 1e5) == pytest.approx(100.0)
    assert stats.tpot_ms(req(0, 1, 1, 1), 1e5) is None  # one token has no gap


def test_a_failed_request_lands_in_the_tail_not_outside_it():
    good = [req(i, i + 0.1, i + 1.1, 11) for i in range(19)]
    bad = req(19, None, None, 0, ok=False)
    value, n = stats.end_to_end(spec("ttft_p95_ms") | {"stat": {"kind": "percentile", "of": "ttft_ms", "q": 95}},
                                good + [bad], (0, 20), worst_ms=120000.0)
    assert n == 20 and value == pytest.approx(100.0)  # rank 19 of 20 is still a good one
    value, _ = stats.end_to_end({"stat": {"kind": "percentile", "of": "ttft_ms", "q": 96}},
                                good + [bad], (0, 20), worst_ms=120000.0)
    assert value == 120000.0  # rank 20 is the failure, at the worst value
    value, _ = stats.end_to_end(spec("tpot_p50_ms"), [bad] * 3 + good[:2], (0, 20), 120000.0)
    assert value == 120000.0  # and it is in the denominator of every metric


def test_p95_is_left_out_below_its_sample_count():
    few = [req(i, i + 0.1, i + 1.1, 11) for i in range(199)]
    assert stats.end_to_end(spec("ttft_p95_ms"), few, (0, 200), 1e5) == (None, 199)
    value, n = stats.end_to_end(spec("ttft_p95_ms"), few + few[:1], (0, 200), 1e5)
    assert n == 200 and value == pytest.approx(100.0)


def test_only_requests_due_in_the_window_are_measured():
    warm = req(-5, -4.9, 2.0, 71, measured=False, chunks=[-4.9] + [-4.0 + 0.1 * i for i in range(70)])
    inside = req(1, 1.5, 3.5, 21, chunks=[1.5] + [1.6 + 0.1 * i for i in range(20)])
    value, n = stats.end_to_end(spec("ttft_p50_ms"), [warm, inside], (0, 10), 1e5)
    assert (value, n) == (pytest.approx(500.0), 1)


def test_chunk_tokens_share_the_count():
    r = req(0, 1.0, 2.0, 17, chunks=[1.0, 1.5, 2.0])
    assert stats.chunk_tokens(r) == [(1.0, 1.0), (1.5, 8.0), (2.0, 8.0)]
