"""Regenerate the measured-numbers blocks in docs from the bench artifact.

Round 4's docs quoted a run that wasn't the official artifact (−46%/+94%
deltas vs BENCH_r04). Numbers now live in generated blocks bracketed by
BENCH_NUMBERS markers, rewritten from ``BENCH_DETAILS.json`` (written by every
``python bench.py`` run); ``tests/test_docs_numbers.py`` fails when the
checked-in docs disagree with the checked-in artifact.

Usage: python scripts/update_bench_docs.py  (run after bench.py; commit both)
"""

from __future__ import annotations

import json
import os
import re
import sys

ROOT = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")
START = "<!-- BENCH_NUMBERS_START generated by scripts/update_bench_docs.py from BENCH_DETAILS.json; do not edit by hand -->"
END = "<!-- BENCH_NUMBERS_END -->"

# Tolerance band for MEASURED performance numbers (latencies, throughputs,
# offload page traffic, avg served prompt length): a fresh bench run moves
# them a little every time, and an exact-match guard turned the suite red on
# every honest re-bench. Stable parts of the block — counts, configs, qps
# points, ratios, labels — still must match exactly.
PERF_TOLERANCE = 0.20
# QUALITY numbers (the int8-KV greedy token-match rate) are NOT perf: a
# 20% band would wave through a catastrophic quality regression as "a perf
# number within tolerance" (the ISSUE 14 bugfix). They get a tight
# ABSOLUTE band instead — re-bench jitter on a match rate is a few
# near-tie tokens, not 20 points.
QUALITY_TOLERANCE = 0.005
# a number is "perf" when this unit/context immediately follows it; the
# "x (32k/16k" / "x)" forms are the prefill scaling and decode-interference
# RATIOS (ISSUE 6), "x recorder-off" the flight-recorder overhead ratio
# (ISSUE 7), "x int8/fp" the quantized-KV decode speedup (ISSUE 14) —
# measured quantities that drift run-to-run like any latency, so they get
# the same tolerance band
_PERF_NUM_RE = re.compile(
    r"(\d[\d,]*(?:\.\d+)?)"
    r"(?=\s*(?:ms\b|tok/s|pages spilled|restored\b|tokens/request"
    r"|x \(32k/16k|x\)|x recorder-off|x int8/fp))"
)
# a number is "quality" when the greedy token-match context follows it;
# matched BEFORE the perf pass so a quality value can never be reclassified
_QUALITY_NUM_RE = re.compile(
    r"(\d(?:\.\d+)?)(?=\s*greedy token-match)"
)


def _split_perf(block: str) -> "tuple[str, list[float], list[float]]":
    """Separate a rendered block into (skeleton, perf numbers, quality
    numbers): every number recognized as a measured value is replaced by a
    placeholder and collected, so two blocks can be compared
    structurally-exact but value-tolerant — with SEPARATE tolerances for
    perf and quality classes."""
    perf: list[float] = []
    quality: list[float] = []

    def subq(m: "re.Match[str]") -> str:
        quality.append(float(m.group(1)))
        return "\x01"

    def sub(m: "re.Match[str]") -> str:
        perf.append(float(m.group(1).replace(",", "")))
        return "\x00"

    block = _QUALITY_NUM_RE.sub(subq, block)
    return _PERF_NUM_RE.sub(sub, block), perf, quality


def compare_blocks(doc_block: str, fresh_block: str) -> list[str]:
    """Mismatches between the docs' checked-in block and a freshly rendered
    one. Empty list = the docs still honestly describe the artifact:
    structure, configs, counts, and ratios are identical, every measured
    perf number agrees within ``PERF_TOLERANCE``, and every quality number
    (greedy token-match rate) agrees within the tight absolute
    ``QUALITY_TOLERANCE`` — a quality regression FAILS the guard instead of
    passing as a perf number within ±20%."""
    doc_skel, doc_nums, doc_q = _split_perf(doc_block)
    new_skel, new_nums, new_q = _split_perf(fresh_block)
    if doc_skel != new_skel:
        for dl, nl in zip(doc_skel.splitlines(), new_skel.splitlines()):
            if dl != nl:
                return [
                    "stable (non-perf) content differs; first divergence:\n"
                    f"  docs:     {dl!r}\n  artifact: {nl!r}"
                ]
        return ["stable (non-perf) content differs (line counts diverge)"]
    out = []
    for i, (d, n) in enumerate(zip(doc_nums, new_nums)):
        base = max(abs(n), 1e-9)
        if abs(d - n) / base > PERF_TOLERANCE:
            out.append(
                f"perf number #{i}: docs say {d:g}, artifact renders {n:g} "
                f"(off by more than ±{PERF_TOLERANCE:.0%})"
            )
    for i, (d, n) in enumerate(zip(doc_q, new_q)):
        if abs(d - n) > QUALITY_TOLERANCE:
            out.append(
                f"quality number #{i}: docs say {d:g}, artifact renders "
                f"{n:g} (off by more than ±{QUALITY_TOLERANCE:g} absolute "
                "— quality deltas are not perf-tolerant)"
            )
    return out


def _fmt(v, digits=0):
    if v is None:
        return "n/a"
    if isinstance(v, float):
        return f"{v:,.{digits}f}"
    return f"{v:,}"


def _decode_contrast(e: dict) -> str:
    """Engine-side contrast text for the steady-state decode row.

    Fixed-bench artifacts carry BOTH a symmetric engine-direct number
    (median-of-3 over pooled per-thread sessions — same estimator and
    transport as the routed number) and the trace-derived rate; no caveat
    is needed for them. Artifacts predating the transport fix carry only
    the legacy fresh-TCP direct number, whose instability caveat must keep
    rendering (that number really was an artifact — see measurement notes
    in docs/benchmarking.md)."""
    traced = e.get("http_decode_engine_tokens_per_sec_traced")
    direct = e.get("http_decode_engine_direct_tokens_per_sec")
    if traced is not None and direct is not None:
        return (
            f"engine-direct {_fmt(direct)} tok/s, symmetric median-of-3; "
            f"engine-side {_fmt(traced)} tok/s from the same requests' "
            "decode spans"
        )
    if traced is not None:
        return (
            f"engine-side {_fmt(traced)} tok/s from the same requests' "
            "decode spans"
        )
    return f"engine-direct contrast {_fmt(direct)}" + (
        " — unstable, see measurement notes"
        if (direct or 0) < (e.get("http_decode_tokens_per_sec") or 0) / 2
        else ""
    )


def render_block(details: dict) -> str:
    e = details.get("extras", {})
    pts = e.get("qa_points") or []
    by_qps = {p["qps"]: p for p in pts}
    head_qps = e.get("qa_qps")
    sweep = ", ".join(
        f"{_fmt(p['p50_ttft_ms'])} ms at qps {p['qps']:g}" for p in pts
    )
    qps_str = f"{head_qps:g}" if head_qps is not None else "n/a"
    rows = [
        ("multi-round-qa p50 TTFT via router (PRIMARY)",
         f"{_fmt(details.get('value'))} ms at qps {qps_str}"
         + (f" (sweep: {sweep})" if sweep else "")),
        ("multi-round-qa generation throughput",
         f"{_fmt(e.get('qa_tokens_per_sec_per_chip'))} tok/s/chip at the "
         f"headline point, KV hit rate {_fmt((e.get('qa_kv_hit_rate') or 0) * 100)}%"),
        ("multi-round-qa served prompt size",
         f"avg {_fmt(e.get('qa_avg_prompt_tokens'))} tokens/request "
         f"({e.get('qa_users')} users x {e.get('qa_rounds')} rounds, "
         f"~{_fmt((e.get('qa_history_words') or 0) * 7)}-token histories)"),
        ("multi-round-qa KV offload traffic",
         f"{_fmt(e.get('qa_kv_offload_saved_pages'))} pages spilled / "
         f"{_fmt(e.get('qa_kv_offload_loaded_pages'))} restored during the "
         "headline point"),
        ("p50 TTFT, 1k-token prefill (engine level)",
         f"{_fmt(e.get('p50_ttft_ms_1k_prefill'))} ms"),
        ("p50 TTFT through router + API server (sequential)",
         f"{_fmt(e.get('http_p50_ttft_ms'))} ms (engine-direct "
         f"{_fmt(e.get('http_engine_direct_p50_ttft_ms'))} ms)"),
        ("decode throughput (runner, by batch)",
         ", ".join(
             f"B={b}: {_fmt(v)} tok/s"
             for b, v in (e.get("decode_tokens_per_sec_by_batch") or {}).items()
         ) or "n/a"),
        ("steady-state decode through router + SSE",
         f"{_fmt(e.get('http_decode_tokens_per_sec'))} tok/s at concurrency "
         f"{e.get('http_decode_concurrency')} ("
         + _decode_contrast(e)
         + ")"),
        ("mixed prefill+decode through the stack",
         f"{_fmt(e.get('http_stack_tokens_per_sec'))} tok/s at concurrency "
         f"{e.get('http_concurrency')} (1k prefill + 64 gen per request)"),
        ("long-context chunked prefill",
         f"16k: {_fmt(e.get('prefill_16k_tokens_per_sec'))} tok/s; "
         f"32k: {_fmt(e.get('prefill_32k_tokens_per_sec'))} tok/s; "
         f"decode on a 16k history {_fmt(e.get('decode_at_16k_tokens_per_sec'))} tok/s"),
        ("16k prompt served end-to-end (32k config)",
         f"TTFT {_fmt(e.get('http_16k_ttft_ms'))} ms"),
    ]
    # conditional rows for the ragged-prefill metrics: artifacts predating
    # ISSUE 6 don't carry them, and rendering "n/a" would claim a
    # measurement that never ran
    if e.get("prefill_scaling_ratio") is not None:
        rows.append((
            "long-context prefill scaling",
            f"{e['prefill_scaling_ratio']:g} x (32k/16k tok/s; >= 1.0 = "
            "flat scaling)",
        ))
    if e.get("decode_itl_p99_ms_baseline") is not None:
        ratio = e.get("decode_itl_interference_ratio")
        rows.append((
            "decode p99 inter-token latency vs a 32k prefill",
            f"{_fmt(e.get('decode_itl_p99_ms_baseline'))} ms alone, "
            f"{_fmt(e.get('decode_itl_p99_ms_with_32k_prefill'))} ms with "
            "the prefill in flight"
            + (f" ({ratio:g} x)" if ratio is not None else ""),
        ))
    # conditional tp>1 serving row (ISSUE 12): only runs where the backend
    # exposes >= 2 devices (TPU slice / virtual CPU mesh) — rendering "n/a"
    # on single-chip artifacts would claim a measurement that never ran
    tp_shapes = sorted(
        int(k.rsplit("_tp", 1)[1]) for k in e
        if k.startswith("http_decode_tokens_per_sec_tp")
    )
    if len(tp_shapes) > 1:
        decode = "; ".join(
            f"tp={n}: {_fmt(e.get(f'http_decode_tokens_per_sec_tp{n}'))} tok/s"
            for n in tp_shapes
        )
        prefill = "; ".join(
            f"tp={n}: {_fmt(e.get(f'http_prefill_tokens_per_sec_tp{n}'))} tok/s"
            for n in tp_shapes
            if e.get(f"http_prefill_tokens_per_sec_tp{n}") is not None
        )
        rows.append((
            "tensor-parallel engine via HTTP "
            f"({e.get('tp_phase_model')}, {e.get('tp_phase_devices')} devices)",
            f"decode {decode}" + (f"; prefill {prefill}" if prefill else ""),
        ))
    # conditional quantized-KV row (ISSUE 14): artifacts predating the
    # kv_quant phase lack the keys. The tok/s pair and the speedup are
    # perf-tolerant; the token-match rate is a QUALITY number under the
    # tight absolute band (see compare_blocks)
    if e.get("kv_quant_token_match_rate") is not None:
        ctx = e.get("kv_quant_context") or 0
        tag = f"{ctx // 1024}k" if ctx >= 1024 else f"{ctx}"
        rows.append((
            f"decode on a {tag} history with int8 KV (kv_cache_dtype)",
            f"{_fmt(e.get(f'decode_at_{tag}_tokens_per_sec_int8'))} tok/s "
            f"vs {_fmt(e.get(f'decode_at_{tag}_tokens_per_sec_fp_contrast'))}"
            f" tok/s fp ({(e.get('kv_quant_decode_speedup') or 0):g} x "
            "int8/fp); "
            f"quality {e['kv_quant_token_match_rate']:g} greedy token-match",
        ))
    # conditional KV fabric row (ISSUE 16): loopback throughput of the
    # engine-to-engine transfer plane; artifacts predating the phase lack
    # the keys
    if e.get("kv_fabric_push_pages_per_sec") is not None:
        rows.append((
            "KV fabric loopback (engine-to-engine transfer plane)",
            f"push {_fmt(e.get('kv_fabric_push_pages_per_sec'))} pages/s "
            f"({_fmt(e.get('kv_fabric_push_mb_per_sec'))} MB/s), "
            f"pull {_fmt(e.get('kv_fabric_pull_pages_per_sec'))} pages/s; "
            f"probe {_fmt(e.get('kv_fabric_probe_mb_per_sec'))} MB/s",
        ))
    if e.get("flightrecorder_overhead_ratio") is not None:
        rows.append((
            "flight-recorder decode overhead",
            f"{e['flightrecorder_overhead_ratio']:g} x recorder-off "
            "throughput (acceptance >= 0.98)",
        ))
    lines = [START, "", "| Metric | Value |", "|---|---|"]
    for k, v in rows:
        lines.append(f"| {k} | {v} |")
    lines += [
        "",
        f"_Platform: {e.get('platform')}; model: {e.get('model')}._",
        "", END,
    ]
    return "\n".join(lines)


def update_file(path: str, block: str) -> bool:
    with open(path) as f:
        text = f.read()
    pat = re.compile(re.escape(START) + ".*?" + re.escape(END), re.DOTALL)
    if not pat.search(text):
        raise SystemExit(f"{path}: BENCH_NUMBERS markers not found")
    new = pat.sub(lambda _: block, text)
    if new != text:
        with open(path, "w") as f:
            f.write(new)
        return True
    return False


DOC_PATHS = ("docs/benchmarking.md", "README.md")


def require_tpu_artifact(details: dict) -> None:
    """A CPU run is never written under a device metric's name: only an
    artifact that says it ran on a TPU may reach the docs."""
    platform = (details.get("extras") or {}).get("platform")
    if platform != "tpu":
        raise SystemExit(
            f"refusing BENCH_DETAILS.json with platform={platform!r}: the "
            "docs' numbers stand under device metric names and come only "
            "from a chip run"
        )


def main() -> None:
    details_path = os.path.join(ROOT, "BENCH_DETAILS.json")
    if not os.path.exists(details_path):
        raise SystemExit("BENCH_DETAILS.json not found; run python bench.py first")
    with open(details_path) as f:
        details = json.load(f)
    require_tpu_artifact(details)
    block = render_block(details)
    for rel in DOC_PATHS:
        changed = update_file(os.path.join(ROOT, rel), block)
        print(f"{rel}: {'updated' if changed else 'up to date'}")


if __name__ == "__main__":
    sys.exit(main())
