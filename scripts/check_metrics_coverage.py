#!/usr/bin/env python3
"""Tier-1 guard: every Prometheus metric the stack emits must be visible.

PRs 2-6 each hand-added Grafana panels for their new metrics and nothing
caught a forgotten one — a metric nobody can see might as well not exist.
This guard statically extracts every ``vllm:`` / ``vllm_router:`` / ``fake:``
metric name emitted by the code and asserts each one is

1. **documented** — the name appears somewhere under ``docs/`` (the metrics
   reference table in docs/observability.md is the canonical home), and
2. **dashboarded** — the name appears in a Grafana dashboard
   (observability/tpu-stack-dashboard.json or the KV-offload dashboard
   ConfigMap), unless it is in ``DASHBOARD_ALLOWLIST`` (metrics that are
   intentionally scrape-only: debug/bench surfaces, redundant aliases,
   per-process internals).

Extraction is intentionally layered, because not every emitted name is a
single string literal:

- full-name literals anywhere under ``production_stack_tpu/`` (skipping
  f-string prefixes — a match immediately followed by ``{``);
- ``emit("<name>", ...)`` first arguments in engine/api_server.py (emitted
  under the ``vllm:`` namespace);
- the engine ``stats()`` dict keys the /metrics loop forwards with a
  ``vllm:`` prefix (``out["kv_*..."]`` in engine/engine.py, the
  ``warm_start_*`` keys in kvoffload/warmstart.py);
- ``GENERATED``: dynamic families built with f-strings (TTFT hop gauges,
  engine-loop section counters) that no literal scan can see. Adding a new
  dynamic family? List its expansion here or the guard cannot protect it.

Run standalone (``python scripts/check_metrics_coverage.py``) or through
tier-1 (tests/test_metrics_coverage.py). Exit code 1 + a report on gaps.
"""

from __future__ import annotations

import json
import pathlib
import re
import sys

REPO = pathlib.Path(__file__).resolve().parent.parent

METRIC_RE = re.compile(r"(?:vllm|vllm_router|fake):[a-z][a-z0-9_]*[a-z0-9]")

# dynamic metric families (f-string built) -> concrete series names
GENERATED = [
    # engine/api_server.py: vllm:ttft_hop_{hop}_ms over the engine hops
    *(f"vllm:ttft_hop_{hop}_ms" for hop in (
        "accept_to_submit", "submit_to_first_token", "first_token_to_write",
        "admission_wait",
    )),
    # router/app.py via request_service.get_hop_quantiles(): router hops
    *(f"vllm_router:ttft_hop_{hop}_ms" for hop in (
        "recv_to_route", "route_to_connect", "connect_to_first_chunk",
    )),
    # engine/engine.py: loop_seconds sections -> vllm:engine_loop_*_seconds_total
    *(f"vllm:engine_loop_{sec}_seconds_total" for sec in (
        "wait", "schedule", "step", "apply", "emit", "chain_dispatch",
        "chain_fetch",
    )),
    # ... and the parts of step under a prefix of their own
    *(f"vllm:engine_dispatch_{sec}_seconds_total"
      for sec in ("stage", "call", "fetch", "hold", "runahead")),
    # engine/api_server.py: labelled by kind / by reason (engine._turn)
    "vllm:queued_ahead_dispatches_total",
    "vllm:queue_ahead_drains_total",
    # engine/api_server.py: decode rows riding prefill dispatches
    # (scheduler._plan_riders), the last labelled by reason
    "vllm:prefill_dispatches_total",
    "vllm:prefill_rider_dispatches_total",
    "vllm:prefill_rider_rows_total",
    "vllm:prefill_riderless_dispatches_total",
    # engine/api_server.py: first-dispatch wall by phase (runner._dispatch)
    *(f"vllm:first_dispatch_{phase}_seconds_total" for phase in (
        "trace", "lower", "compile", "run",
    )),
]

# intentionally NOT on a dashboard (documentation in docs/ is still
# mandatory). Keep each entry justified.
DASHBOARD_ALLOWLIST = {
    # redundant with the counters the dashboard derives rates from, or
    # debug-grade engine internals charted on demand, not by default
    "vllm:num_preemptions_total",
    "vllm:num_requests_swapped",
    "vllm:gpu_prefix_cache_hits_total",      # dashboard charts the rate gauge
    "vllm:gpu_prefix_cache_queries_total",
    "vllm:engine_loop_wait_seconds_total",   # loop-section breakdown is a
    "vllm:engine_loop_schedule_seconds_total",   # bench/debug surface
    "vllm:engine_loop_step_seconds_total",
    "vllm:engine_loop_apply_seconds_total",
    "vllm:engine_loop_emit_seconds_total",
    "vllm:engine_loop_chain_dispatch_seconds_total",
    "vllm:engine_loop_chain_fetch_seconds_total",
    "vllm:engine_dispatch_stage_seconds_total",
    "vllm:engine_dispatch_runahead_seconds_total",
    "vllm:engine_dispatch_fetch_seconds_total",
    "vllm:engine_dispatch_hold_seconds_total",
    "vllm:engine_dispatch_call_seconds_total",
    "vllm:queued_ahead_dispatches_total",    # how often the loop kept one
    "vllm:queue_ahead_drains_total",         # dispatch queued: bench/debug
    "vllm:prefill_dispatches_total",         # how often decode rows rode a
    "vllm:prefill_rider_dispatches_total",   # prefill dispatch and why not:
    "vllm:prefill_rider_rows_total",         # the same bench/debug surface
    "vllm:prefill_riderless_dispatches_total",
    "vllm:decode_kv_tokens_read_total",      # the benchmark's counted roofline reads it
    "vllm:first_dispatches_total",           # first-dispatch stalls: a start-up
    "vllm:first_dispatch_seconds_total",     # and bench surface; the dashboard
    "vllm:first_dispatch_trace_seconds_total",   # charts compile seconds
    "vllm:first_dispatch_lower_seconds_total",
    "vllm:first_dispatch_compile_seconds_total",
    "vllm:first_dispatch_run_seconds_total",
    "vllm:step_program_store_hits_total",    # how often a first dispatch found
    "vllm:step_program_store_writes_total",  # its exported program: the same
    "vllm:step_program_store_errors_total",  # start-up and bench surface
    "vllm:step_program_preload_listed",      # what the loader built of the
    "vllm:step_program_preloaded_total",     # store's listing at start-up,
    "vllm:step_program_preload_failed_total",    # and how many first
    "vllm:step_program_preload_served_total",    # dispatches took one: the
    "vllm:step_program_preload_seconds",     # same start-up and bench surface
    "vllm:step_program_preload_pending_at_first_dispatch",
    "vllm:ssm_state_slots",                  # a family with recurrent state
    "vllm:ssm_state_slots_in_use",           # (models/jamba.py) alone emits
    "vllm:ssm_state_bytes",                  # these; GET /stats shows them, no
    "vllm:ssm_prefill_tokens_total",         # dashboard and no benchmark
    "vllm:ssm_decode_tokens_total",          # reader reads them yet
    "vllm:ssm_state_bytes_per_slot",         # the same: a seat's bytes
    "vllm:conv_state_bytes",                 # the same families' conv tails
    "vllm:ssd_decode_tokens_total",          # Mamba-2 layers (models/
    "vllm:ssd_prefill_tokens_total",         # nemotron_h.py), counted by the
    "vllm:ssd_prefill_chunks_total",         # device; /stats and the
    "vllm:ssd_prefill_rows_total",           # benchmark's SSD rooflines
    "vllm:moe_routed_rows_total",            # sparse experts (models/lfm2.py):
    "vllm:moe_expert_reads_total",           # the benchmark reads them from
    "vllm:moe_expert_slots_total",           # GET /stats; no dashboard yet
    "vllm:decode_dispatches_total",          # dispatch-shape bench telemetry
    "vllm:decode_chained_dispatches_total",
    "vllm:runahead_prefill_dispatches_total",
    "vllm:ttft_hop_accept_to_submit_ms",     # hop quantiles back bench
    "vllm:ttft_hop_submit_to_first_token_ms",    # attribution; the dashboard
    "vllm:ttft_hop_first_token_to_write_ms",     # charts the histograms
    "vllm:ttft_hop_admission_wait_ms",
    "vllm_router:ttft_hop_recv_to_route_ms",
    "vllm_router:ttft_hop_route_to_connect_ms",
    "vllm_router:ttft_hop_connect_to_first_chunk_ms",
    "vllm:spec_decode_num_draft_tokens_total",   # spec decode is off by
    "vllm:spec_decode_num_accepted_tokens_total",    # default (ROADMAP 5
    "vllm:spec_decode_draft_acceptance_rate",        # adds its panels)
    "vllm:kv_transfer_pinned_offer_bytes",   # leak probes for the transfer
    "vllm:kv_transfer_leaked_offers_total",  # sweep, asserted in tests
    "vllm:kv_transfer_cap_evicted_offers_total",
    "vllm:kv_offload_device_loaded_pages_total",  # disagg-only duplicate of
    "vllm:kv_transfer_received_chunks_total",     # the charted sent/chunks
    "vllm:kv_transfer_received_bytes_total",      # series
    "vllm:kv_offload_dropped_evictions_total",
    "vllm:warm_start_spilled_pages_total",   # dashboard charts restored +
    "vllm:warm_start_stale_manifests_skipped_total",  # age + generation
    "vllm:trace_spans_recorded_total",       # dashboard charts the dropped
    "vllm:trace_buffer_capacity",            # series; these are its context
    "vllm:flightrecorder_events_total",      # dashboard charts drops + dumps
    "vllm:flightrecorder_capacity",
    "vllm:flightrecorder_enabled",
    "vllm:tpu_hbm_bytes_limit",              # dashboard charts in_use vs
    "vllm:kv_pool_used_bytes",               # headroom; limits/pool are
    "vllm:kv_pool_device_bytes",             # their denominators
    "vllm:compile_events_total",             # dashboard charts the seconds
    "vllm:compile_cache_entries",
    "vllm:compile_cache_bytes",
    "vllm:engine_step_duty_cycle",
    "vllm:device_count",                     # static device report: read by
    "vllm:device_info",                      # chip_smoke.py and bench rows,
    "vllm:engine_program_fault",             # mirrored by /health 503
    "vllm_router:slo_request_outcomes_total",  # dashboard charts attainment
    "vllm_router:slo_records_total",           # these are its diagnostics
    "vllm_router:cpu_usage_perc",            # charted via the memory panel
    "vllm_router:num_swapped_requests",
    "vllm_router:avg_latency",               # dashboard charts the histogram
    # router-side mirrors of engine series the dashboard already charts
    # under their vllm: names (the mirrors exist so a router-only scrape
    # job still covers the fleet)
    "vllm_router:engine_running_requests",
    "vllm_router:engine_waiting_requests",
    "vllm_router:gpu_cache_usage_perc",
    "vllm_router:gpu_prefix_cache_hit_rate",
    "vllm_router:finished_requests",
    "vllm_router:time_to_first_token_seconds",   # dashboard heatmaps chart
    "vllm_router:e2e_request_latency_seconds",   # the engine-side histograms
    "vllm:kv_transfer_device_pages_total",   # device-path detail of the
                                             # charted chunks/s series
    # fake-engine-only observability: consumed by chaos assertions, never
    # deployed to a cluster with Grafana
    "fake:running_peak",
    "fake:served_total",
    "fake:completed_total",
    "fake:abort_requests_total",
    "fake:migrations_out_total",
    "fake:migrations_in_total",
    "fake:warm_prefetch_chunks",
    "fake:warm_prefix_hits_total",
    "fake:served_by_class_total",   # per-SLO-class split behind the chaos
    "fake:shed_by_class_total",     # batch-first shed assertions

    # fleet-controller diagnostics: the dashboard charts decisions-by-kind
    # and the saturation signal; started/failed/inflight are the drill-down
    # behind a decisions anomaly, charted on demand
    "vllm:fleet_controller_migrations_started_total",
    "vllm:fleet_controller_migrations_failed_total",
    "vllm:fleet_controller_migrations_inflight",
}


def _read(path: pathlib.Path) -> str:
    return path.read_text(encoding="utf-8", errors="replace")


def emitted_metrics() -> set[str]:
    names: set[str] = set()
    for path in (REPO / "production_stack_tpu").rglob("*.py"):
        text = _read(path)
        for m in METRIC_RE.finditer(text):
            end = m.end()
            # f-string family prefix ("vllm:ttft_hop_{hop}_ms"): covered by
            # GENERATED, the truncated literal is not a real series name
            if end < len(text) and text[end] in "{_":
                continue
            names.add(m.group(0))
    # engine /metrics emit("<name>", ...) -> vllm:<name>
    api = _read(REPO / "production_stack_tpu" / "engine" / "api_server.py")
    for m in re.finditer(r'emit\(\s*"([a-z0-9_]+)"', api):
        names.add(f"vllm:{m.group(1)}")
    # engine stats() dict keys the /metrics loop forwards under vllm:
    eng = _read(REPO / "production_stack_tpu" / "engine" / "engine.py")
    for m in re.finditer(
        r'out\["((?:kv_|spec_decode_|warm_start_)[a-z0-9_]+)"\]', eng
    ):
        names.add(f"vllm:{m.group(1)}")
    warm = _read(REPO / "production_stack_tpu" / "kvoffload" / "warmstart.py")
    for m in re.finditer(r'"(warm_start_[a-z0-9_]+)":', warm):
        names.add(f"vllm:{m.group(1)}")
    names.update(GENERATED)
    return names


_BRACE_RE = re.compile(
    r"((?:vllm|vllm_router|fake):[a-z0-9_]*)\{([a-z0-9_,]+)\}([a-z0-9_]*)"
)


def _expand_brace_families(text: str) -> str:
    """Docs may name metric families compactly —
    ``vllm:engine_loop_{wait,step}_seconds_total`` — one table row per
    family instead of seven near-identical ones. Append the expansions so
    the substring check sees every concrete series name."""
    extra = []
    for m in _BRACE_RE.finditer(text):
        for part in m.group(2).split(","):
            extra.append(f"{m.group(1)}{part}{m.group(3)}")
    return text + "\n" + "\n".join(extra)


def coverage_texts() -> tuple[str, str]:
    """(dashboard text, docs text) the names are checked against."""
    dashboards = _read(REPO / "observability" / "tpu-stack-dashboard.json")
    dashboards += _read(REPO / "observability" / "kvoffload-dashboard-cm.yaml")
    docs = "".join(
        _read(p) for p in sorted((REPO / "docs").glob("*.md"))
    )
    docs += _read(REPO / "README.md")
    return dashboards, _expand_brace_families(docs)


def check() -> list[str]:
    """Returns human-readable violations (empty = guard passes)."""
    dashboards, docs = coverage_texts()
    emitted = emitted_metrics()
    violations = []
    for name in sorted(emitted):
        missing = []
        if name not in docs:
            missing.append("docs/")
        if name not in dashboards and name not in DASHBOARD_ALLOWLIST:
            missing.append("dashboard")
        if missing:
            violations.append(f"{name}: not in {', '.join(missing)}")
    # allowlist hygiene: an entry for a metric nobody emits anymore is rot
    for name in sorted(DASHBOARD_ALLOWLIST - emitted):
        violations.append(f"{name}: allowlisted but not emitted (stale entry)")
    return violations


def main() -> int:
    names = emitted_metrics()
    violations = check()
    print(f"{len(names)} emitted metric names checked")
    if violations:
        print("METRICS COVERAGE FAILED:")
        for v in violations:
            print(f"  - {v}")
        print(
            "\nEvery emitted metric must appear in docs/ (the reference "
            "table in docs/observability.md) and in a Grafana dashboard "
            "(or scripts/check_metrics_coverage.py DASHBOARD_ALLOWLIST "
            "with a justification)."
        )
        return 1
    print("METRICS COVERAGE PASSED")
    return 0


if __name__ == "__main__":
    sys.exit(main())
