"""Tracing subsystem tests (docs/tracing.md).

Unit: W3C traceparent parsing, span-collector ring buffer under concurrent
writers, head-sampling edge cases (0.0 / 1.0), trace_report self-time math.

E2E (tier-1-safe: the router and fake engine are lightweight aiohttp
processes, no JAX): one routed request must produce ONE trace whose spans —
router.request > routing/proxy > engine.request > queue/prefill/decode —
parent under a single trace id, with self-times covering >= 90% of the
client-measured e2e latency; plus the /metrics smoke check that both servers
expose the four per-phase histograms under their vLLM-compatible names.
"""

import os
import sys
import threading
import time

import pytest
import requests

from production_stack_tpu.testing.procs import (
    free_port,
    start_proc,
    stop_proc,
    wait_healthy,
)
from production_stack_tpu.tracing import (
    Span,
    SpanCollector,
    SpanContext,
    TRACEPARENT_HEADER,
)

sys.path.insert(
    0, os.path.join(os.path.dirname(os.path.abspath(__file__)), os.pardir, "scripts")
)
import trace_report  # noqa: E402

PHASE_METRICS = (
    "vllm:request_queue_time_seconds",
    "vllm:request_prefill_time_seconds",
    "vllm:time_per_output_token_seconds",
    "vllm:kv_offload_restore_seconds",
)


# -- context / traceparent ----------------------------------------------------


def test_traceparent_roundtrip():
    ctx = SpanContext.new_root()
    parsed = SpanContext.parse(ctx.to_traceparent())
    assert parsed.trace_id == ctx.trace_id
    assert parsed.span_id == ctx.span_id
    assert parsed.sampled is True
    not_sampled = SpanContext.new_root(sampled=False)
    assert SpanContext.parse(not_sampled.to_traceparent()).sampled is False


@pytest.mark.parametrize(
    "header",
    [
        None,
        "",
        "garbage",
        "00-shorttrace-0011223344556677-01",
        "00-" + "0" * 32 + "-0011223344556677-01",  # all-zero trace id
        "00-" + "a" * 32 + "-" + "0" * 16 + "-01",  # all-zero span id
        "ff-" + "a" * 32 + "-0011223344556677-01",  # version ff is invalid
        "00-" + "a" * 32 + "-0011223344556677",  # missing flags
    ],
)
def test_traceparent_malformed_ignored(header):
    assert SpanContext.parse(header) is None


def test_child_links_parent_and_keeps_identity():
    root = SpanContext.new_root()
    child = root.child()
    assert child.trace_id == root.trace_id
    assert child.parent_id == root.span_id
    assert child.span_id != root.span_id
    assert child.sampled == root.sampled
    # the sampled decision rides into grandchildren unchanged (head-based)
    assert root.child().child().sampled == root.sampled


def test_from_headers_never_raises():
    class Boom:
        def get(self, _):
            raise RuntimeError("broken header mapping")

    assert SpanContext.from_headers(Boom()) is None


# -- collector: ring buffer ---------------------------------------------------


def test_ring_buffer_bounded_under_concurrent_writers():
    col = SpanCollector(capacity=64, sample_rate=1.0)
    ctx = SpanContext.new_root()
    n_threads, per_thread = 8, 500

    def writer(i):
        for j in range(per_thread):
            col.record(f"w{i}", ctx.child(), time.time(), 0.001, j=j)

    threads = [threading.Thread(target=writer, args=(i,)) for i in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    # every record landed (no lost updates on the counter) ...
    assert col.recorded == n_threads * per_thread
    # ... but memory stays bounded by capacity, and no slot tore: every
    # surviving entry is a whole Span
    spans = col.spans()
    assert len(spans) == 64
    assert all(isinstance(s, Span) and s.trace_id == ctx.trace_id for s in spans)


def test_ring_buffer_overwrites_oldest():
    col = SpanCollector(capacity=4, sample_rate=1.0)
    ctx = SpanContext.new_root()
    for i in range(10):
        col.record("s", ctx.child(), float(i), 0.1, i=i)
    kept = sorted(s.attrs["i"] for s in col.spans())
    assert kept == [6, 7, 8, 9]


def test_capacity_floor_is_one():
    col = SpanCollector(capacity=0)
    col.record("s", SpanContext.new_root(), time.time(), 0.1)
    assert len(col.spans()) == 1


# -- collector: sampling edge cases -------------------------------------------


def test_sample_rate_zero_records_nothing():
    col = SpanCollector(capacity=16, sample_rate=0.0)
    for _ in range(50):
        ctx = SpanContext.new_root(sampled=col.sample())
        assert ctx.sampled is False
        col.record("s", ctx, time.time(), 0.1)
    assert col.spans() == [] and col.recorded == 0
    # a fresh root from headers inherits the rate-0 decision
    assert col.root_from_headers({}).sampled is False


def test_sample_rate_one_records_everything():
    col = SpanCollector(capacity=256, sample_rate=1.0)
    for _ in range(100):
        assert col.sample() is True
        col.record("s", SpanContext.new_root(), time.time(), 0.1)
    assert col.recorded == 100


def test_sample_rate_clamped():
    assert SpanCollector(sample_rate=-0.5).sample_rate == 0.0
    assert SpanCollector(sample_rate=1.5).sample_rate == 1.0


def test_sampling_deterministic_in_trace_id():
    col = SpanCollector(sample_rate=0.5)  # threshold: first 8 hex < 0x80000000
    low = "7fffffff" + "0" * 24
    high = "80000000" + "0" * 24
    for _ in range(3):
        assert col.sample(low) is True
        assert col.sample(high) is False


def test_rate_zero_kill_switch_beats_remote_sampled_flag():
    """Rate 0.0 is the operator's off switch: a client-supplied traceparent
    with the sampled bit set must not force recording back on (the trace id
    is still adopted for log correlation)."""
    col = SpanCollector(capacity=16, sample_rate=0.0)
    remote = SpanContext.new_root(sampled=True)
    ctx = col.root_from_headers({TRACEPARENT_HEADER: remote.to_traceparent()})
    assert ctx.trace_id == remote.trace_id and ctx.sampled is False
    col.record("s", ctx.child(), time.time(), 0.1)
    assert col.spans() == []


def test_unsampled_remote_context_is_honored():
    """The sampled flag in an incoming traceparent is authoritative: a
    rate-1.0 collector must still drop spans of a not-sampled trace."""
    col = SpanCollector(capacity=16, sample_rate=1.0)
    remote = SpanContext.new_root(sampled=False)
    ctx = col.root_from_headers({TRACEPARENT_HEADER: remote.to_traceparent()})
    assert ctx.trace_id == remote.trace_id and ctx.sampled is False
    col.record("s", ctx.child(), time.time(), 0.1)
    assert col.spans() == []


# -- collector: export --------------------------------------------------------


def test_export_groups_filters_and_limits():
    col = SpanCollector(capacity=32, sample_rate=1.0)
    a, b = SpanContext.new_root(), SpanContext.new_root()
    col.record("root_a", a, 1.0, 0.5)
    col.record("child_a", a.child(), 1.1, 0.2)
    col.record("root_b", b, 2.0, 0.5)
    export = col.export()
    assert {t["trace_id"] for t in export["traces"]} == {a.trace_id, b.trace_id}
    # most recently started trace first
    assert export["traces"][0]["trace_id"] == b.trace_id
    only_a = col.export(trace_id=a.trace_id)["traces"]
    assert len(only_a) == 1 and len(only_a[0]["spans"]) == 2
    assert len(col.export(limit=1)["traces"]) == 1


# -- trace_report self-time math ----------------------------------------------


def _span(name, span_id, parent, start, dur_ms, trace="t" * 32):
    return {
        "trace_id": trace,
        "span_id": span_id,
        "parent_id": parent,
        "name": name,
        "start": start,
        "duration_ms": dur_ms,
        "attrs": {},
    }


def test_trace_breakdown_self_times_sum_to_root():
    spans = [
        _span("root", "r1", None, 0.0, 100.0),
        _span("proxy", "p1", "r1", 0.01, 60.0),
        _span("engine", "e1", "p1", 0.02, 40.0),
    ]
    b = trace_report.trace_breakdown(spans)
    assert b["root"] == "root" and b["e2e_ms"] == 100.0
    assert b["self_ms"] == {"root": 40.0, "proxy": 20.0, "engine": 40.0}
    assert sum(b["self_ms"].values()) == b["e2e_ms"]


def test_phase_table_shares_sum_to_one():
    merged = trace_report.merge_exports(
        {"traces": [{"trace_id": "t" * 32, "spans": [
            _span("root", "r1", None, 0.0, 100.0),
            _span("leaf", "l1", "r1", 0.0, 75.0),
        ]}]}
    )
    table = trace_report.phase_table(merged)
    assert table["traces"] == 1
    assert abs(sum(p["share"] for p in table["phases"].values()) - 1.0) < 1e-6
    assert table["phases"]["leaf"]["total_ms"] == 75.0
    rendered = trace_report.render_table(table)
    assert "leaf" in rendered and "share" in rendered


def test_trace_breakdown_ignores_orphan_chains():
    """A partial trace (ring wrapped mid-trace / misaligned export windows)
    can carry spans whose parents were lost; attribution must cover only
    the chosen root's subtree or shares would sum past 100%."""
    spans = [
        _span("root", "r1", None, 0.0, 100.0),
        _span("leaf", "l1", "r1", 0.0, 80.0),
        # orphan: parent span was dropped from the export
        _span("stray", "s1", "gone", 0.0, 500.0),
    ]
    b = trace_report.trace_breakdown(spans)
    assert b["root"] == "stray"  # largest parentless span wins root
    assert b["self_ms"] == {"stray": 500.0}
    b2 = trace_report.trace_breakdown(spans[:2] + [
        _span("stray", "s1", "gone", 0.0, 10.0)
    ])
    assert b2["root"] == "root"
    assert "stray" not in b2["self_ms"]
    assert sum(b2["self_ms"].values()) == b2["e2e_ms"]
    assert b2["leaf_coverage"] <= 1.0


def test_merge_exports_dedupes_across_processes():
    s = _span("x", "s1", None, 0.0, 1.0)
    merged = trace_report.merge_exports({"traces": [{"trace_id": s["trace_id"],
                                                     "spans": [s]}]},
                                        {"traces": [{"trace_id": s["trace_id"],
                                                     "spans": [s]}]})
    assert len(merged[s["trace_id"]]) == 1


# -- e2e: router + fake engine ------------------------------------------------


@pytest.fixture(scope="module")
def stack():
    """One fake engine behind the router, started once for the module."""
    eport, rport = free_port(), free_port()
    fake = start_proc(
        ["-m", "production_stack_tpu.testing.fake_engine",
         "--port", str(eport), "--model", "fake/model", "--speed", "500"]
    )
    engine_url = f"http://127.0.0.1:{eport}"
    wait_healthy(f"{engine_url}/health", fake, timeout=60)
    router = start_proc(
        ["-m", "production_stack_tpu.router.app", "--port", str(rport),
         "--static-backends", engine_url, "--static-models", "fake/model",
         "--engine-stats-interval", "1", "--enable-debug-endpoints"]
    )
    router_url = f"http://127.0.0.1:{rport}"
    wait_healthy(f"{router_url}/health", router, timeout=60)
    try:
        yield router_url, engine_url
    finally:
        stop_proc(router)
        stop_proc(fake)


def _merged_trace_export(router_url, engine_url):
    return trace_report.merge_exports(*(
        requests.get(f"{u}/v1/traces?limit=100", timeout=10).json()
        for u in (router_url, engine_url)
    ))


def test_e2e_routed_request_produces_one_parented_trace(stack):
    router_url, engine_url = stack
    session = requests.Session()
    # long enough that serving time dominates the client library's fixed
    # per-request overhead (the coverage assertion compares stack-recorded
    # phase time against CLIENT-measured e2e)
    body = {"model": "fake/model", "prompt": "hello", "max_tokens": 128}
    session.post(f"{router_url}/v1/completions", json=body, timeout=15)  # warm
    known = set(_merged_trace_export(router_url, engine_url))

    t0 = time.perf_counter()
    r = session.post(f"{router_url}/v1/completions", json=body, timeout=15)
    e2e_ms = (time.perf_counter() - t0) * 1000
    assert r.status_code == 200
    req_id = r.headers.get("X-Request-Id")
    assert req_id  # router echoes the id it forwarded to the engine

    merged = _merged_trace_export(router_url, engine_url)
    fresh = {t: spans for t, spans in merged.items() if t not in known}
    # ONE routed request -> ONE trace spanning both processes
    assert len(fresh) == 1
    (trace_id, spans), = fresh.items()
    names = {s["name"] for s in spans}
    assert {"router.request", "router.routing", "router.proxy",
            "engine.request", "engine.queue", "engine.prefill",
            "engine.decode"} <= names
    assert all(s["trace_id"] == trace_id for s in spans)

    # every span except the root parents onto another span in the SAME trace
    by_id = {s["span_id"]: s for s in spans}
    roots = [s for s in spans if s["parent_id"] not in by_id]
    assert len(roots) == 1 and roots[0]["name"] == "router.request"
    # the engine half nests under the router's proxy span
    proxy = next(s for s in spans if s["name"] == "router.proxy")
    eng_req = next(s for s in spans if s["name"] == "engine.request")
    assert eng_req["parent_id"] == proxy["span_id"]
    # spans and logs correlate on the echoed request id
    assert proxy["attrs"]["request_id"] == req_id

    # phase attribution covers the measured latency: self-times sum to the
    # root span, and the root covers >= 90% of the client-measured e2e
    b = trace_report.trace_breakdown(spans)
    assert sum(b["self_ms"].values()) == pytest.approx(b["e2e_ms"], rel=1e-6)
    assert b["e2e_ms"] >= 0.9 * e2e_ms


def test_e2e_client_traceparent_adopted(stack):
    router_url, engine_url = stack
    remote = SpanContext.new_root()
    r = requests.post(
        f"{router_url}/v1/completions",
        json={"model": "fake/model", "prompt": "x", "max_tokens": 2},
        headers={TRACEPARENT_HEADER: remote.to_traceparent()},
        timeout=15,
    )
    assert r.status_code == 200
    merged = _merged_trace_export(router_url, engine_url)
    assert remote.trace_id in merged
    names = {s["name"] for s in merged[remote.trace_id]}
    assert "router.request" in names and "engine.decode" in names


def test_e2e_unsampled_traceparent_records_no_spans(stack):
    router_url, engine_url = stack
    remote = SpanContext.new_root(sampled=False)
    r = requests.post(
        f"{router_url}/v1/completions",
        json={"model": "fake/model", "prompt": "x", "max_tokens": 2},
        headers={TRACEPARENT_HEADER: remote.to_traceparent()},
        timeout=15,
    )
    assert r.status_code == 200
    merged = _merged_trace_export(router_url, engine_url)
    assert remote.trace_id not in merged


def test_e2e_trace_id_filter(stack):
    router_url, engine_url = stack
    remote = SpanContext.new_root()
    requests.post(
        f"{router_url}/v1/completions",
        json={"model": "fake/model", "prompt": "x", "max_tokens": 2},
        headers={TRACEPARENT_HEADER: remote.to_traceparent()},
        timeout=15,
    )
    filtered = requests.get(
        f"{router_url}/v1/traces?trace_id={remote.trace_id}", timeout=10
    ).json()
    assert [t["trace_id"] for t in filtered["traces"]] == [remote.trace_id]
    assert requests.get(
        f"{router_url}/v1/traces?limit=bogus", timeout=10
    ).status_code == 400


def test_collector_counts_ring_wrap_and_sampling_drops():
    """Satellite (ISSUE 7): span loss was silent — ring-wrap overwrites and
    head-sampling rejections must be countable before someone debugs with
    an incomplete trace."""
    col = SpanCollector(capacity=4, sample_rate=1.0)
    ctx = SpanContext.new_root()
    for i in range(10):
        col.record("s", ctx.child(), float(i), 0.1)
    assert col.overwritten == 6  # 10 recorded into 4 slots
    unsampled = SpanContext.new_root(sampled=False)
    for _ in range(3):
        col.record("s", unsampled.child(), 0.0, 0.1)
    assert col.sampling_rejected == 3
    assert col.recorded == 10  # rejections never consumed slots
    from production_stack_tpu.tracing.collector import render_collector_metrics

    # the render helper reads the PROCESS-global collector; just assert the
    # series names and label plumbing (values belong to that collector)
    lines = "\n".join(render_collector_metrics('model_name="m"'))
    assert 'vllm:trace_spans_dropped_total{model_name="m",reason="ring_wrap"}' in lines
    assert 'vllm:trace_spans_dropped_total{model_name="m",reason="unsampled"}' in lines
    assert 'vllm:trace_buffer_capacity{model_name="m"}' in lines
    col.reset()
    assert col.overwritten == 0 and col.sampling_rejected == 0


def test_flightrecorder_hot_path_overhead_micro():
    """Satellite (ISSUE 7): the recorder rides the engine's dispatch path —
    its per-event cost must stay micro-scale (its cost in decode throughput
    on the chip is not measured; this is the unit-scale tripwire).
    Bounds are deliberately loose for noisy CI hosts."""
    from production_stack_tpu.tracing import FlightRecorder

    fr = FlightRecorder(capacity=8192, enabled=True)
    n = 20000
    t0 = time.perf_counter()
    for i in range(n):
        fr.record("sched", step=i, batch_kind="decode", rows=8, bursts=4)
    per_enabled = (time.perf_counter() - t0) / n
    fr.set_enabled(False)
    t0 = time.perf_counter()
    for i in range(n):
        fr.record("sched", step=i, batch_kind="decode", rows=8, bursts=4)
    per_disabled = (time.perf_counter() - t0) / n
    assert per_enabled < 100e-6, f"record() cost {per_enabled * 1e6:.1f}us"
    assert per_disabled < 20e-6, (
        f"disabled record() cost {per_disabled * 1e6:.1f}us"
    )


def test_profiler_span_builds_nothing_while_no_profile_runs():
    """The engine loop opens a span for every section of every iteration
    (tracing/profiler.py): with no profile running that is one flag test and
    the ONE shared no-op object, never a TraceAnnotation."""
    from production_stack_tpu.tracing import profiler

    assert not profiler.active()
    first = profiler.span("pstpu.loop.step", kind="decode", rows=8)
    assert first is profiler.span("pstpu.loop.apply") and first is profiler._NO_SPAN
    with first as inside:
        assert inside is first
    n = 200000
    t0 = time.perf_counter()
    for _ in range(n):
        with profiler.span("pstpu.loop.step"):
            pass
    per_span = (time.perf_counter() - t0) / n
    assert per_span < 5e-6, f"an inactive span cost {per_span * 1e6:.2f}us"


def _parse_label_sets(metrics_text):
    import re

    pair_re = re.compile(r'([a-zA-Z_][a-zA-Z0-9_]*)="([^"]*)"')
    out = {}
    for line in metrics_text.splitlines():
        if line.startswith("#") or "{" not in line:
            continue
        label_blob = line[line.index("{") + 1:line.rindex("}")]
        for key, value in pair_re.findall(label_blob):
            out.setdefault(key, set()).add(value)
    return out


def test_metric_label_cardinality_bounded(stack):
    """Satellite (ISSUE 7): no Prometheus series may carry per-request
    labels — one label key whose values track request ids turns a scrape
    into an unbounded time-series explosion. Drive traffic, then assert
    label keys are a closed set and per-key value counts stay small."""
    router_url, engine_url = stack
    for _ in range(5):
        requests.post(
            f"{router_url}/v1/completions",
            json={"model": "fake/model", "prompt": "x", "max_tokens": 2},
            timeout=15,
        )
    allowed = {
        "model_name", "server", "backend", "quantile", "le", "kind",
        "source", "device", "reason", "objective", "model", "outcome",
        # SLO class (docs/failure-handling.md): closed two-value set
        "priority",
        # vllm:device_info: one static series per engine process
        "platform", "device_kind", "attn_impl_prefill", "attn_impl_decode",
    }
    forbidden = {"request_id", "seq_id", "trace_id", "x_request_id"}
    for url in (router_url, engine_url):
        labels = _parse_label_sets(requests.get(f"{url}/metrics", timeout=10).text)
        assert not (set(labels) & forbidden), (url, set(labels) & forbidden)
        assert set(labels) <= allowed, (url, set(labels) - allowed)
        for key, values in labels.items():
            assert len(values) < 64, (url, key, len(values))
            # no label VALUE smuggling a request id either (uuid4-shaped or
            # the engine's req- prefix)
            for v in values:
                assert not v.startswith(("req-", "cmpl-", "chatcmpl-")), (key, v)
                assert len(v) < 80, (key, v)


def test_smoke_both_metrics_endpoints_expose_phase_histograms(stack):
    """Tier-1 smoke: the four per-phase histograms are present on BOTH
    /metrics surfaces under their vLLM-compatible names (the dashboard's
    phase-breakdown row queries either scrape job)."""
    router_url, engine_url = stack
    for url in (router_url, engine_url):
        text = requests.get(f"{url}/metrics", timeout=10).text
        for name in PHASE_METRICS:
            assert f"# TYPE {name} histogram" in text, f"{name} missing on {url}"
            assert f"{name}_bucket" in text
