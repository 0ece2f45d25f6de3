"""Per-phase Prometheus histograms backing the tracing subsystem.

Four request-phase distributions, named to mirror vLLM's metric definitions so
the reference dashboard's phase-breakdown queries work unchanged against our
``/metrics`` (the same contract utils/metrics.py keeps for TTFT/e2e):

- ``vllm:request_queue_time_seconds``   — scheduler admit -> first dispatch
- ``vllm:request_prefill_time_seconds`` — first dispatch -> first token
- ``vllm:time_per_output_token_seconds``— decode time / output tokens (TPOT)
- ``vllm:kv_offload_restore_seconds``   — offload-tier restore batches (no
  vLLM equivalent; kept in the ``vllm:`` namespace so one scrape job covers
  the engine surface)

These are observed by the ENGINE (it owns the phases) and always-on — a few
histogram observes per request are noise next to a device step — while span
recording is gated by the sampling knob. The router's ``/metrics`` renders
them too (zero-count in a router-only process) so dashboards can point either
scrape job at the same panel set.
"""

from __future__ import annotations

from production_stack_tpu.utils.metrics import LATENCY_BUCKETS, Histogram

# vLLM's time_per_output_token histogram boundaries (seconds)
TPOT_BUCKETS = (
    0.01, 0.025, 0.05, 0.075, 0.1, 0.15, 0.2, 0.3, 0.4, 0.5, 0.75, 1.0, 2.5,
)
# restore batches are bounded by kv_offload_max_io_pages; the range spans
# sub-millisecond to tens of seconds (not measured on a directly attached chip)
RESTORE_BUCKETS = (
    0.001, 0.005, 0.01, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 30.0,
)

queue_time_hist = Histogram(
    "vllm:request_queue_time_seconds", LATENCY_BUCKETS,
    "Request queue wait (arrival to first prefill dispatch)",
)
prefill_time_hist = Histogram(
    "vllm:request_prefill_time_seconds", LATENCY_BUCKETS,
    "Prefill phase duration (first dispatch to first token)",
)
decode_step_time_hist = Histogram(
    "vllm:time_per_output_token_seconds", TPOT_BUCKETS,
    "Mean decode time per output token (first token to finish)",
)
offload_restore_hist = Histogram(
    "vllm:kv_offload_restore_seconds", RESTORE_BUCKETS,
    "KV offload-tier restore batch duration",
)
# dispatch-granular long-context prefill observability (ISSUE 6): per-chunk
# device wall time, and decode step time per token WHILE a prefill is
# resident — the pair the Grafana prefill-phase panel charts to show a 32k
# prompt streaming through without starving co-scheduled decodes
prefill_chunk_hist = Histogram(
    "vllm:prefill_chunk_seconds", TPOT_BUCKETS + (5.0, 10.0),
    "One chunked-prefill dispatch's device wall time",
)
interleaved_decode_hist = Histogram(
    "vllm:interleaved_decode_step_seconds", TPOT_BUCKETS,
    "Decode time per output token for bursts interleaved with an "
    "in-flight prefill",
)

PHASE_HISTOGRAMS = (
    queue_time_hist,
    prefill_time_hist,
    decode_step_time_hist,
    offload_restore_hist,
    prefill_chunk_hist,
    interleaved_decode_hist,
)


def render_phase_histograms(labels: str) -> list[str]:
    """Exposition lines for all four phase histograms under ``labels``."""
    lines: list[str] = []
    for h in PHASE_HISTOGRAMS:
        lines.extend(h.render(labels))
    return lines


def reset_phase_histograms() -> None:
    """Debug/bench only (the /metrics/reset endpoints)."""
    for h in PHASE_HISTOGRAMS:
        h.reset()
