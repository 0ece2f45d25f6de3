"""Doc-rot guard for MEASURED NUMBERS (round 4 verdict: docs quoted a run
that wasn't the official artifact). The numbers tables in README.md and
docs/benchmarking.md are generated blocks; this test re-renders them from the
checked-in BENCH_DETAILS.json and compares TOLERANCE-BASED: stable parts
(counts, configs, qps points, ratios, labels) must match exactly, while
measured perf numbers (latencies, throughputs, page traffic) may drift within
±20% — a fresh bench run's ordinary run-to-run noise no longer turns the
suite red, but a stale table or a real regression still does."""

import json
import os
import sys

import pytest

ROOT = os.path.join(os.path.dirname(os.path.abspath(__file__)), os.pardir)
sys.path.insert(0, os.path.join(ROOT, "scripts"))

import update_bench_docs as ubd  # noqa: E402


def test_docs_numbers_match_artifact():
    details_path = os.path.join(ROOT, "BENCH_DETAILS.json")
    if not os.path.exists(details_path):
        pytest.skip("no BENCH_DETAILS.json checked in: the docs say 'not measured'")
    with open(details_path) as f:
        block = ubd.render_block(json.load(f))
    for rel in ubd.DOC_PATHS:
        with open(os.path.join(ROOT, rel)) as f:
            text = f.read()
        assert ubd.START in text and ubd.END in text, f"{rel}: markers missing"
        start = text.index(ubd.START)
        end = text.index(ubd.END) + len(ubd.END)
        mismatches = ubd.compare_blocks(text[start:end], block)
        assert not mismatches, (
            f"{rel}: measured-numbers block is stale — run "
            "`python scripts/update_bench_docs.py` after bench.py and commit "
            "both the docs and BENCH_DETAILS.json:\n" + "\n".join(mismatches)
        )


def test_update_refuses_an_artifact_that_did_not_run_on_a_tpu():
    ubd.require_tpu_artifact(_details())  # platform "tpu": accepted
    cpu = _details()
    cpu["extras"]["platform"] = "cpu"
    with pytest.raises(SystemExit, match="platform='cpu'"):
        ubd.require_tpu_artifact(cpu)
    with pytest.raises(SystemExit, match="platform=None"):
        ubd.require_tpu_artifact({"value": 1.0})


def _details(p50=123.4, tps=400.0):
    return {
        "value": p50,
        "extras": {
            "qa_qps": 2.0, "qa_tokens_per_sec_per_chip": tps,
            "qa_kv_hit_rate": 0.95, "qa_users": 20, "qa_rounds": 5,
            "qa_history_words": 1200, "qa_avg_prompt_tokens": 9000,
            "qa_kv_offload_saved_pages": 10, "qa_kv_offload_loaded_pages": 5,
            "qa_points": [{"qps": 1.0, "p50_ttft_ms": 150.0},
                          {"qps": 2.0, "p50_ttft_ms": p50}],
            "platform": "tpu", "model": "llama-3.2-1b-class",
            "decode_tokens_per_sec_by_batch": {"16": 1500.0, "32": 1900.0},
        },
    }


def test_compare_blocks_tolerates_perf_drift_within_band():
    """A ±20% move in measured perf numbers (the headline p50, throughputs)
    must NOT flag the docs as stale — that is ordinary bench run-to-run
    noise, and the old exact-match guard turned every honest re-bench red."""
    docs = ubd.render_block(_details(p50=123.4, tps=400.0))
    fresh = ubd.render_block(_details(p50=123.4 * 1.15, tps=400.0 * 0.9))
    assert ubd.compare_blocks(docs, fresh) == []


def test_compare_blocks_flags_perf_drift_beyond_band():
    docs = ubd.render_block(_details(p50=123.4))
    fresh = ubd.render_block(_details(p50=123.4 * 1.5))
    mismatches = ubd.compare_blocks(docs, fresh)
    assert mismatches and "perf number" in mismatches[0]


def test_compare_blocks_keeps_stable_parts_exact():
    """Configs/counts (users, rounds, qps points) are not measurements —
    any change there means the docs describe a different run shape and must
    fail regardless of magnitude."""
    d = _details()
    d2 = json.loads(json.dumps(d))
    d2["extras"]["qa_users"] = 21  # within 20% of 20, but config, not perf
    mismatches = ubd.compare_blocks(
        ubd.render_block(d), ubd.render_block(d2)
    )
    assert mismatches and "stable" in mismatches[0]


def _details_with_quant(match=0.995, tps_int8=120.0):
    d = _details()
    d["extras"].update({
        "kv_quant_token_match_rate": match,
        "kv_quant_decode_speedup": 1.58,
        "kv_quant_context": 16384,
        "decode_at_16k_tokens_per_sec_int8": tps_int8,
        "decode_at_16k_tokens_per_sec_fp_contrast": 76.0,
    })
    return d


def test_compare_blocks_flags_quality_regression():
    """ISSUE 14 bugfix: the int8-KV greedy token-match rate is a QUALITY
    number — a regression must FAIL the guard instead of passing as a perf
    number within ±20% (0.85 is 'within 20%' of 0.995)."""
    docs = ubd.render_block(_details_with_quant(match=0.995))
    fresh = ubd.render_block(_details_with_quant(match=0.85))
    mismatches = ubd.compare_blocks(docs, fresh)
    assert mismatches and "quality number" in mismatches[0]


def test_compare_blocks_tolerates_quality_jitter_and_quant_perf_drift():
    """A few near-tie tokens of match-rate jitter (±0.005) and ordinary
    perf drift on the int8 tok/s pair stay within the band."""
    docs = ubd.render_block(_details_with_quant(match=0.995, tps_int8=120.0))
    fresh = ubd.render_block(
        _details_with_quant(match=0.993, tps_int8=112.0)
    )
    assert ubd.compare_blocks(docs, fresh) == []


def test_render_block_is_deterministic():
    details = {
        "value": 123.4,
        "extras": {
            "qa_qps": 2.0, "qa_tokens_per_sec_per_chip": 400.0,
            "qa_kv_hit_rate": 0.95, "qa_users": 20, "qa_rounds": 5,
            "qa_history_words": 1200, "qa_avg_prompt_tokens": 9000,
            "qa_kv_offload_saved_pages": 10, "qa_kv_offload_loaded_pages": 5,
            "qa_points": [{"qps": 1.0, "p50_ttft_ms": 150.0},
                          {"qps": 2.0, "p50_ttft_ms": 123.4}],
            "platform": "tpu", "model": "llama-3.2-1b-class",
            "decode_tokens_per_sec_by_batch": {"16": 1500.0, "32": 1900.0},
        },
    }
    b1 = ubd.render_block(details)
    b2 = ubd.render_block(json.loads(json.dumps(details)))
    assert b1 == b2
    assert b1.startswith(ubd.START) and b1.endswith(ubd.END)
    assert "123" in b1 and "1,900" in b1
