"""Live sequence migration + fleet controller (ISSUE 10; docs/migration.md).

Layers, cheapest first:

- **wire/state units** — sealed snapshot roundtrip + corruption rejection,
  continuation-budget math, unmigratable-reason gating.
- **router re-pin units** — SessionPinRegistry TTL/forget semantics and the
  SessionRouter consulting pins before its hash ring.
- **controller decision units** — FleetDecider hysteresis (engage above the
  high watermark, stay engaged to the low one), cooldown, the
  max-concurrent-migrations cap, drain planning, and warm-up detection —
  pure logic, injected clock, no I/O.
- **fake-engine HTTP e2e** — migrate a live stream fake -> fake directly,
  then THROUGH the router (splice: client sees one uninterrupted stream),
  then with the source SIGTERM'd right after the handoff (the stream
  survives its source's death; the continuation executes exactly once
  fleet-wide), then a rollback when the target is unreachable (the stream
  completes locally, untouched).
- **real CPU engines** — the acceptance run: a greedy stream migrated
  mid-decode between two LLMEngine instances produces token output
  BIT-IDENTICAL to the unmigrated run, with the KV chain actually shipped
  through the offload tier and restored (not recomputed) on the target.
"""

import asyncio
import json
import signal
import threading
import time

import pytest
import requests

from production_stack_tpu.kvoffload.serde import KVIntegrityError
from production_stack_tpu.migration import (
    Action,
    BackendView,
    ControllerPolicy,
    FleetDecider,
    SequenceSnapshot,
    continuation_params,
    snapshot_from_wire,
    snapshot_to_wire,
    unmigratable_reason,
)
from production_stack_tpu.testing.procs import (
    free_port,
    start_proc,
    stop_proc,
    wait_healthy,
)

# ---------------------------------------------------------------------------
# wire/state units
# ---------------------------------------------------------------------------

def _params_doc(max_tokens=16, **over):
    doc = {
        "max_tokens": max_tokens, "temperature": 0.0, "top_k": 0,
        "top_p": 1.0, "stop": [], "ignore_eos": True, "min_tokens": 0,
        "seed": None, "presence_penalty": 0.0, "frequency_penalty": 0.0,
        "repetition_penalty": 1.0,
    }
    doc.update(over)
    return doc


def _snap(output_len=4, **over):
    kw = dict(
        request_id="r-1", model="llama-debug", page_size=16,
        tokens=list(range(32 + output_len)), prompt_len=32,
        output_len=output_len, params=_params_doc(),
        page_hashes=["ab" * 16], meta={"oid": "cmpl-r-1", "chat": False},
    )
    kw.update(over)
    return SequenceSnapshot(**kw)


class TestSnapshotWire:
    def test_roundtrip(self):
        s = _snap()
        s2 = snapshot_from_wire(snapshot_to_wire(s))
        assert s2.tokens == s.tokens
        assert s2.params == s.params
        assert s2.page_hashes == s.page_hashes
        assert s2.meta["oid"] == "cmpl-r-1"

    def test_corrupt_wire_rejected(self):
        data = bytearray(snapshot_to_wire(_snap()))
        data[len(data) // 2] ^= 0xFF  # bit flip inside the body
        with pytest.raises((KVIntegrityError, ValueError)):
            snapshot_from_wire(bytes(data))

    def test_truncated_wire_rejected(self):
        data = snapshot_to_wire(_snap())
        with pytest.raises((KVIntegrityError, ValueError)):
            snapshot_from_wire(data[: len(data) - 4])

    def test_continuation_budget_shrinks_by_emitted(self):
        p = continuation_params(
            _snap(output_len=5, params=_params_doc(max_tokens=16,
                                                   min_tokens=8))
        )
        assert p.max_tokens == 11
        assert p.min_tokens == 3

    def test_nothing_left_to_generate_refused(self):
        with pytest.raises(ValueError):
            continuation_params(
                _snap(output_len=16, params=_params_doc(max_tokens=16))
            )

    def test_unmigratable_reasons(self):
        from production_stack_tpu.engine.scheduler import (
            SamplingParams,
            Sequence,
        )

        def seq(**over):
            s = Sequence(
                seq_id="s", prompt_ids=list(range(8)),
                params=SamplingParams(max_tokens=16),
            )
            s.num_computed = 8  # decode phase
            s.output_ids = [1, 2]
            for k, v in over.items():
                setattr(s, k, v)
            return s

        assert unmigratable_reason(seq()) is None
        assert "finished" in unmigratable_reason(seq(finished=True))
        assert "prefilling" in unmigratable_reason(seq(num_computed=4))
        assert "no tokens" in unmigratable_reason(seq(output_ids=[]))
        assert "LoRA" in unmigratable_reason(seq(lora_slot=2))
        s = seq(); s.params.logprobs = 4
        assert "logprobs" in unmigratable_reason(s)
        s = seq(); s.params.presence_penalty = 0.5
        assert "penalties" in unmigratable_reason(s)
        s = seq(); s.output_ids = list(range(16))
        assert "about to finish" in unmigratable_reason(s)
        # repetition penalty spans prompt+output: migrates fine
        s = seq(); s.params.repetition_penalty = 1.2
        assert unmigratable_reason(s) is None


# ---------------------------------------------------------------------------
# router re-pin units
# ---------------------------------------------------------------------------

class TestSessionRepin:
    def test_pin_lookup_ttl_and_forget(self):
        from production_stack_tpu.router.resilience import SessionPinRegistry

        reg = SessionPinRegistry()
        reg.pin("u1", "http://b", ttl=100)
        assert reg.lookup("u1") == "http://b"
        # expired pin evaporates
        assert reg.lookup("u1", now=time.monotonic() + 101) is None
        assert reg.lookup("u1") is None  # and stays gone
        reg.pin("u2", "http://dead")
        reg.forget_backend("http://dead")
        assert reg.lookup("u2") is None

    def test_session_router_prefers_pin_over_ring(self):
        from production_stack_tpu.router.resilience import get_session_pins
        from production_stack_tpu.router.routing_logic import SessionRouter
        from production_stack_tpu.router.service_discovery import EndpointInfo
        from production_stack_tpu.router.utils import SingletonMeta

        SingletonMeta._instances.pop(SessionRouter, None)
        router = SessionRouter(session_key="x-user-id")
        eps = [
            EndpointInfo(url=u, model_names=["m"], added_timestamp=0)
            for u in ("http://a", "http://b")
        ]

        class Req:
            headers = {"x-user-id": "alice"}

        home = asyncio.run(router.route_request(eps, {}, {}, Req(), {}))
        other = "http://a" if home == "http://b" else "http://b"
        get_session_pins().pin("alice", other)
        try:
            assert asyncio.run(
                router.route_request(eps, {}, {}, Req(), {})
            ) == other
            # a pin at a departed backend is ignored (ring takes over)
            assert asyncio.run(
                router.route_request(
                    [e for e in eps if e.url != other], {}, {}, Req(), {}
                )
            ) != other
        finally:
            get_session_pins().clear()
            SingletonMeta._instances.pop(SessionRouter, None)


# ---------------------------------------------------------------------------
# controller decision units (pure logic, injected clock)
# ---------------------------------------------------------------------------

def _views(hot_wait=8, cold_wait=0, migratable=None):
    hot = BackendView(
        url="http://hot", waiting=hot_wait,
        migratable=migratable if migratable is not None else [
            {"request_id": "long", "output_tokens": 40},
            {"request_id": "short", "output_tokens": 2},
        ],
    )
    cold = BackendView(url="http://cold", waiting=cold_wait)
    return [hot, cold]


def _policy(**over):
    kw = dict(
        rebalance_high_delta=0.5, rebalance_low_delta=0.2, cooldown_s=10.0,
        max_concurrent_migrations=2, rebalance_k=1, saturation_queue_ref=8,
    )
    kw.update(over)
    return ControllerPolicy(**kw)


class TestControllerDecisions:
    def test_rebalance_picks_longest_stream_hot_to_cold(self):
        d = FleetDecider(_policy())
        actions = d.decide(_views(), now=0.0)
        reb = [a for a in actions if a.kind == "rebalance"]
        assert len(reb) == 1
        assert reb[0].source == "http://hot"
        assert reb[0].target == "http://cold"
        assert reb[0].request_ids == ["long"]  # hottest/longest first

    def test_hysteresis_engages_high_disengages_low(self):
        d = FleetDecider(_policy(cooldown_s=0.0))
        # below the high watermark: no action, not engaged
        assert d.decide(_views(hot_wait=3), now=0.0) == []
        assert not d._engaged
        # crosses high: engages and acts
        assert d.decide(_views(hot_wait=8), now=1.0)
        assert d._engaged
        # BETWEEN the watermarks: stays engaged (delta 0.375 in [0.2, 0.5))
        assert d.decide(_views(hot_wait=3), now=2.0)
        assert d._engaged
        # below low: disengages, no action
        assert d.decide(_views(hot_wait=1), now=3.0) == []
        assert not d._engaged
        # between the watermarks again: must NOT re-engage (no flapping)
        assert d.decide(_views(hot_wait=3), now=4.0) == []

    def test_cooldown_spaces_actions(self):
        d = FleetDecider(_policy(cooldown_s=10.0))
        assert d.decide(_views(), now=100.0)
        assert d.decide(_views(), now=105.0) == []  # inside the cooldown
        assert d.decide(_views(), now=111.0)        # past it

    def test_max_concurrent_migrations_cap(self):
        d = FleetDecider(_policy(cooldown_s=0.0, max_concurrent_migrations=2,
                                 rebalance_k=4))
        # cap already consumed by in-flight migrations: no decision
        assert d.decide(_views(), inflight_migrations=2, now=0.0) == []
        # one slot left: the k=4 ask is clamped to 1 stream
        acts = d.decide(_views(), inflight_migrations=1, now=1.0)
        assert len(acts) == 1 and len(acts[0].request_ids) == 1

    def test_warm_up_on_new_engine(self):
        d = FleetDecider(_policy())
        d.decide([BackendView(url="http://a")], now=0.0)
        acts = d.decide(
            [BackendView(url="http://a"), BackendView(url="http://new")],
            now=1.0,
        )
        warm = [a for a in acts if a.kind == "warm_up"]
        assert len(warm) == 1 and warm[0].target == "http://new"
        assert d.decisions_total["warm_up"] == 1

    def test_plan_drain_spreads_coolest_first(self):
        d = FleetDecider(_policy())
        views = [
            BackendView(url="http://victim", migratable=[
                {"request_id": f"r{i}", "output_tokens": i} for i in range(4)
            ]),
            BackendView(url="http://busy", waiting=6),
            BackendView(url="http://idle", waiting=0),
        ]
        plan = d.plan_drain(views, "http://victim")
        assert len(plan) == 4
        assert all(a.kind == "drain" and a.source == "http://victim"
                   for a in plan)
        # longest stream first, coolest target first, round-robin spread
        assert plan[0].request_ids == ["r3"]
        assert plan[0].target == "http://idle"
        assert {a.target for a in plan} == {"http://idle", "http://busy"}

    def test_plan_drain_no_survivors_is_empty(self):
        d = FleetDecider(_policy())
        views = [BackendView(url="http://victim", migratable=[
            {"request_id": "r", "output_tokens": 1}
        ])]
        assert d.plan_drain(views, "http://victim") == []

    def test_controller_metrics_text_renders(self):
        from production_stack_tpu.migration.controller import FleetController

        ctrl = FleetController(engine_urls=["http://a"])
        ctrl.decider.decisions_total["rebalance"] = 3
        text = ctrl.metrics_text()
        assert 'vllm:fleet_controller_decisions_total{kind="rebalance"} 3' in text
        assert "vllm:fleet_controller_fleet_saturation" in text
        assert Action("rebalance").kind == "rebalance"


# ---------------------------------------------------------------------------
# fake-engine HTTP e2e (no TPUs; real wire shapes)
# ---------------------------------------------------------------------------

def _start_fake(extra=None, speed=25):
    port = free_port()
    proc = start_proc(
        ["-m", "production_stack_tpu.testing.fake_engine",
         "--port", str(port), "--model", "fake/model",
         "--speed", str(speed)] + (extra or [])
    )
    return proc, f"http://127.0.0.1:{port}"


def _start_router(urls, extra=None, model="fake/model"):
    port = free_port()
    proc = start_proc([
        "-m", "production_stack_tpu.router.app",
        "--port", str(port),
        "--static-backends", ",".join(urls),
        "--static-models", ",".join([model] * len(urls)),
        "--engine-stats-interval", "1",
        "--retry-backoff-base", "0.01",
    ] + (extra or []))
    return proc, f"http://127.0.0.1:{port}"


def _stream_lines(url, rid, max_tokens, out_lines, done_evt, status_box=None):
    try:
        r = requests.post(
            f"{url}/v1/completions",
            json={"model": "fake/model", "prompt": "x",
                  "max_tokens": max_tokens, "stream": True},
            headers={"X-Request-Id": rid}, stream=True, timeout=60,
        )
        if status_box is not None:
            status_box.append(r.status_code)
        for line in r.iter_lines():
            if line:
                out_lines.append(line)
    except requests.RequestException as e:
        out_lines.append(f"EXC {e}".encode())
    finally:
        done_evt.set()


def _counter(url: str, name: str) -> float:
    import re

    text = requests.get(f"{url}/metrics", timeout=5).text
    m = re.search(rf"{re.escape(name)}(?:\{{[^}}]*\}})? ([0-9.]+)", text)
    return float(m.group(1)) if m else 0.0


def _wait_stream_live(url: str, rid: str, timeout=10.0) -> bool:
    t0 = time.time()
    while time.time() - t0 < timeout:
        reqs = requests.get(f"{url}/migratable", timeout=5).json()["requests"]
        if any(r["request_id"] == rid and r["migratable"] for r in reqs):
            return True
        time.sleep(0.1)
    return False


class TestFakeMigrationHTTP:
    def test_direct_fake_to_fake_migration(self):
        """Source half + continuation half carry exactly max_tokens content
        chunks; wire counters and usage continuity hold."""
        A, ua = _start_fake(speed=20)
        B, ub = _start_fake(speed=100)
        try:
            wait_healthy(f"{ua}/health", A, timeout=30)
            wait_healthy(f"{ub}/health", B, timeout=30)
            lines, done = [], threading.Event()
            t = threading.Thread(
                target=_stream_lines, args=(ua, "m1", 20, lines, done)
            )
            t.start()
            assert _wait_stream_live(ua, "m1")
            mr = requests.post(
                f"{ua}/migrate_out",
                json={"request_id": "m1", "target_url": ub}, timeout=30,
            )
            assert mr.status_code == 200 and mr.json()["migrated"], mr.text
            assert done.wait(30)
            # source leg: ends with the control event, never [DONE]
            assert b"pstpu_migration" in lines[-1]
            assert not any(b"[DONE]" in l for l in lines)
            src_chunks = sum(1 for l in lines if b'"text"' in l)
            ar = requests.post(
                f"{ub}/migrate_attach", json={"request_id": "m1"},
                stream=True, timeout=30,
            )
            cont = [l for l in ar.iter_lines() if l]
            cont_chunks = sum(1 for l in cont if b'"text"' in l)
            assert src_chunks + cont_chunks == 20, (src_chunks, cont_chunks)
            assert any(b"[DONE]" in l for l in cont)
            usage = json.loads(
                [l for l in cont if b'"usage"' in l][-1][len(b"data: "):]
            )["usage"]
            # usage reports WHOLE-request totals, not just the continuation
            assert usage["completion_tokens"] == 20
            assert _counter(ua, "fake:migrations_out_total") == 1
            assert _counter(ub, "fake:migrations_in_total") == 1
        finally:
            stop_proc(A)
            stop_proc(B)

    def test_router_splices_migrated_stream_uninterrupted(self):
        """THE router-handoff contract: the client sees one uninterrupted
        stream — full token count, [DONE], no control-event leak — and the
        router counts the re-pin."""
        A, ua = _start_fake(speed=15)
        B, ub = _start_fake(speed=100)
        router = None
        try:
            wait_healthy(f"{ua}/health", A, timeout=30)
            wait_healthy(f"{ub}/health", B, timeout=30)
            router, base = _start_router([ua, ub])
            wait_healthy(f"{base}/health", router, timeout=30)
            lines, done, status = [], threading.Event(), []
            t = threading.Thread(
                target=_stream_lines,
                args=(base, "m2", 24, lines, done, status),
            )
            t.start()
            src = None
            t0 = time.time()
            while src is None and time.time() - t0 < 15:
                for u in (ua, ub):
                    reqs = requests.get(
                        f"{u}/migratable", timeout=5
                    ).json()["requests"]
                    if any(r["request_id"] == "m2" for r in reqs):
                        src = u
                time.sleep(0.1)
            assert src is not None, "stream never became migratable"
            tgt = ub if src == ua else ua
            mr = requests.post(
                f"{src}/migrate_out",
                json={"request_id": "m2", "target_url": tgt}, timeout=30,
            )
            assert mr.status_code == 200 and mr.json()["migrated"], mr.text
            assert done.wait(30)
            assert status == [200]
            content = sum(1 for l in lines if b'"text"' in l)
            assert content == 24, lines[-3:]
            assert any(b"[DONE]" in l for l in lines)
            assert not any(b"pstpu_migration" in l for l in lines), (
                "control event leaked to the client"
            )
            usage = json.loads(
                [l for l in lines if b'"usage"' in l][-1][len(b"data: "):]
            )["usage"]
            assert usage["completion_tokens"] == 24
            assert _counter(base, "vllm_router:session_repins_total") == 1
            assert _counter(
                base, "vllm_router:migration_splice_failures_total"
            ) == 0
        finally:
            if router is not None:
                stop_proc(router)
            stop_proc(A)
            stop_proc(B)

    def test_stream_survives_source_sigterm_after_handoff(self):
        """Mid-stream SIGTERM of the source right after the handoff commits:
        the spliced stream still completes from the target, and the
        continuation executes exactly once fleet-wide (the source never
        counts the migrated stream completed — no double execution)."""
        A, ua = _start_fake(speed=15)
        B, ub = _start_fake(speed=60)
        router = None
        try:
            wait_healthy(f"{ua}/health", A, timeout=30)
            wait_healthy(f"{ub}/health", B, timeout=30)
            router, base = _start_router([ua, ub])
            wait_healthy(f"{base}/health", router, timeout=30)
            lines, done, status = [], threading.Event(), []
            t = threading.Thread(
                target=_stream_lines,
                args=(base, "m3", 30, lines, done, status),
            )
            t.start()
            src = None
            t0 = time.time()
            while src is None and time.time() - t0 < 15:
                for u in (ua, ub):
                    reqs = requests.get(
                        f"{u}/migratable", timeout=5
                    ).json()["requests"]
                    if any(r["request_id"] == "m3" for r in reqs):
                        src = u
                time.sleep(0.1)
            assert src is not None
            tgt = ub if src == ua else ua
            src_proc = A if src == ua else B
            mr = requests.post(
                f"{src}/migrate_out",
                json={"request_id": "m3", "target_url": tgt}, timeout=30,
            )
            assert mr.status_code == 200 and mr.json()["migrated"], mr.text
            # the source dies the instant the handoff committed
            src_proc.send_signal(signal.SIGTERM)
            assert done.wait(30)
            assert status == [200]
            assert sum(1 for l in lines if b'"text"' in l) == 30
            assert any(b"[DONE]" in l for l in lines)
            # exactly-once: only the target ran the continuation to the end
            assert _counter(tgt, "fake:completed_total") == 1
            assert src_proc.wait(timeout=20) == 0
        finally:
            if router is not None:
                stop_proc(router)
            stop_proc(A)
            stop_proc(B)

    def test_failed_ship_rolls_back_and_stream_completes_locally(self):
        """Target unreachable: /migrate_out reports failure, the frozen
        stream resumes decoding locally, and the client sees a complete,
        untouched stream (the PR 2 'request survives' contract)."""
        A, ua = _start_fake(speed=40)
        try:
            wait_healthy(f"{ua}/health", A, timeout=30)
            dead = f"http://127.0.0.1:{free_port()}"
            lines, done = [], threading.Event()
            t = threading.Thread(
                target=_stream_lines, args=(ua, "m4", 20, lines, done)
            )
            t.start()
            assert _wait_stream_live(ua, "m4")
            mr = requests.post(
                f"{ua}/migrate_out",
                json={"request_id": "m4", "target_url": dead}, timeout=30,
            )
            assert mr.status_code == 502
            assert mr.json()["migrated"] is False
            assert done.wait(30)
            assert sum(1 for l in lines if b'"text"' in l) == 20
            assert any(b"[DONE]" in l for l in lines)
            assert not any(b"pstpu_migration" in l for l in lines)
            assert _counter(ua, "fake:migrations_out_total") == 0
            assert _counter(ua, "fake:completed_total") == 1
        finally:
            stop_proc(A)


# ---------------------------------------------------------------------------
# real CPU engines: bit-identical greedy continuation (the acceptance run)
# ---------------------------------------------------------------------------

def test_greedy_continuation_bit_identical_across_cpu_engines(tmp_path):
    """A greedy stream frozen mid-decode on engine A and resumed on engine B
    emits, end to end, EXACTLY the token ids of the unmigrated baseline run
    — and the KV chain genuinely moved (saved through A's offload tier,
    prefetched + restored into B's pool rather than recomputed)."""
    from production_stack_tpu.engine.config import EngineConfig
    from production_stack_tpu.engine.engine import LLMEngine
    from production_stack_tpu.engine.scheduler import SamplingParams
    from production_stack_tpu.tracing.flightrecorder import (
        configure_flightrecorder,
        get_flightrecorder,
    )

    was_enabled = get_flightrecorder().enabled

    def mk():
        cfg = EngineConfig(
            model="llama-debug", max_model_len=256, num_pages=64,
            page_size=16, prefill_chunk=64, decode_steps=2,
            kv_offload_dir=str(tmp_path / "kv"), kv_offload_disk_gb=1,
            kv_offload_max_io_pages=0, flight_recorder=False,
        )
        e = LLMEngine(cfg)
        e.start()
        return e

    A, B = mk(), mk()
    prompt = "The quick brown fox jumps over the lazy dog. " * 3
    params = SamplingParams(max_tokens=40, temperature=0.0, ignore_eos=True)

    async def collect(engine, seq_id, *, prompt=None, prompt_ids=None, p):
        ids, reason = [], None
        async for out in engine.generate(
            seq_id, prompt=prompt, prompt_token_ids=prompt_ids, params=p
        ):
            ids.extend(out.token_ids)
            if out.finished:
                reason = out.finish_reason
        return ids, reason

    async def run() -> None:
        loop = asyncio.get_running_loop()
        # baseline runs on A (the future SOURCE): engine B must stay cold,
        # or the continuation would share the baseline's registered pages
        # from B's own prefix cache and the restore path would go untested
        base_ids, base_reason = await collect(
            A, "baseline", prompt=prompt, p=params
        )
        assert len(base_ids) == 40 and base_reason == "length"

        got: list = []
        frozen = asyncio.Event()

        async def source():
            async for out in A.generate("mig", prompt=prompt, params=params):
                got.extend(out.token_ids)
                if not frozen.is_set() and len(got) >= 6:
                    frozen.set()
                if out.finished:
                    return out.finish_reason

        task = asyncio.create_task(source())
        await frozen.wait()
        snap = await loop.run_in_executor(
            None, A.migration.freeze_and_snapshot, "mig",
            {"request_id": "mig"},
        )
        # full wire roundtrip (seal + CRC verify), like the HTTP path
        snap2 = snapshot_from_wire(snapshot_to_wire(snap))
        await loop.run_in_executor(
            None, A.migration.commit, "mig", len(snap2.page_hashes)
        )
        assert await task == "migrated"
        assert snap2.output_len >= 6
        assert len(snap2.page_hashes) > 0, "no KV pages shipped"
        # target side: pull the chain into local tiers, then resume
        n = await loop.run_in_executor(
            None, B.migration.prefetch_pages, snap2.page_hashes
        )
        assert n == len(snap2.page_hashes), "shipped chain not fully pulled"
        hits0 = B.kv.offload_hits
        cont_ids, cont_reason = await collect(
            B, snap2.request_id, prompt_ids=snap2.tokens,
            p=continuation_params(snap2),
        )
        assert cont_reason == "length"
        # the shipped pages were RESTORED into B's pool, not recomputed
        assert B.kv.offload_hits - hits0 > 0
        merged = snap2.tokens[snap2.prompt_len:] + cont_ids
        assert merged == base_ids, (
            f"continuation diverged: emitted {snap2.output_len} + "
            f"{len(cont_ids)} tokens != baseline {len(base_ids)}"
        )
        # acceptance counters: out == in >= 1 across the pair
        assert A.migration.stats()["migrations_out_total"] == 1
        assert A.migration.stats()["migration_pages_moved_total"] == len(
            snap2.page_hashes
        )

    try:
        asyncio.run(run())
    finally:
        A.stop()
        B.stop()
        # ``flight_recorder=False`` switched the PROCESS-GLOBAL recorder off:
        # leave it as the next test of this worker expects it (tests/
        # test_step_program_store.py reads its first_dispatch events)
        configure_flightrecorder(enabled=was_enabled)


@pytest.mark.slow  # ~30 s: 2 subprocess engines + router SSE splice;
# migration choreography has in-process engine-level coverage above
def test_real_engine_http_migration_via_router(tmp_path):
    """Acceptance e2e over the wire: two real CPU engine processes sharing
    an offload directory behind the router; a greedy stream is migrated
    mid-decode and the CLIENT sees one uninterrupted stream (full token
    count, [DONE], no control-event leak) while the engines' counters agree:
    vllm:migrations_out_total == vllm:migrations_in_total == 1 with pages
    moved."""
    cache_dir = str(tmp_path / "xla")
    offload = str(tmp_path / "kv")

    def engine_argv(port):
        return [
            "-m", "production_stack_tpu.engine.api_server",
            "--model", "llama-debug", "--port", str(port),
            "--max-model-len", "256", "--num-pages", "64",
            "--page-size", "16", "--prefill-chunk", "64",
            "--decode-steps", "1",
            "--kv-offload-dir", offload, "--kv-offload-disk-gb", "1",
            "--kv-offload-max-io-pages", "0",
            "--compilation-cache-dir", cache_dir,
        ]

    pa, pb = free_port(), free_port()
    A = start_proc(engine_argv(pa))
    B = start_proc(engine_argv(pb))
    ua, ub = f"http://127.0.0.1:{pa}", f"http://127.0.0.1:{pb}"
    router = None
    try:
        wait_healthy(f"{ua}/health", A, timeout=240)
        wait_healthy(f"{ub}/health", B, timeout=240)
        router, base = _start_router([ua, ub], model="llama-debug")
        wait_healthy(f"{base}/health", router, timeout=30)
        lines, done, status = [], threading.Event(), []

        def reader():
            try:
                r = requests.post(
                    f"{base}/v1/completions",
                    # 61 prompt tokens + 128 output stays well inside
                    # max_model_len 256; 128 single-token decode steps keep
                    # the stream alive long enough to migrate mid-decode
                    json={"model": "llama-debug", "prompt": "hello " * 10,
                          "max_tokens": 128, "temperature": 0.0,
                          "ignore_eos": True, "stream": True},
                    headers={"X-Request-Id": "real-mig"},
                    stream=True, timeout=240,
                )
                status.append(r.status_code)
                for line in r.iter_lines():
                    if line:
                        lines.append(line)
            finally:
                done.set()

        t = threading.Thread(target=reader)
        t.start()
        # find the serving engine and wait for emitted output (migratable)
        src, t0 = None, time.time()
        while src is None and time.time() - t0 < 120:
            for u in (ua, ub):
                try:
                    reqs = requests.get(
                        f"{u}/migratable", timeout=5
                    ).json()["requests"]
                except requests.RequestException:
                    continue
                if any(
                    r["request_id"] == "real-mig" and r["migratable"]
                    for r in reqs
                ):
                    src = u
            time.sleep(0.1)
        assert src is not None, "stream never became migratable"
        tgt = ub if src == ua else ua
        mr = requests.post(
            f"{src}/migrate_out",
            json={"request_id": "real-mig", "target_url": tgt}, timeout=60,
        )
        assert mr.status_code == 200 and mr.json()["migrated"], mr.text
        assert mr.json()["pages_moved"] > 0, mr.text
        assert done.wait(240)
        assert status == [200]
        assert any(b"[DONE]" in l for l in lines), lines[-3:]
        assert not any(b"pstpu_migration" in l for l in lines)
        assert not any(b'"error"' in l and b'"choices"' not in l
                       for l in lines), lines[-3:]
        usage = json.loads(
            [l for l in lines if b'"usage"' in l][-1][len(b"data: "):]
        )["usage"]
        # whole-request usage across the handoff: all 128 tokens accounted
        assert usage["completion_tokens"] == 128, usage
        assert _counter(src, "vllm:migrations_out_total") == 1
        assert _counter(tgt, "vllm:migrations_in_total") == 1
        assert _counter(src, "vllm:migration_pages_moved_total") > 0
        assert _counter(base, "vllm_router:session_repins_total") == 1
    finally:
        if router is not None:
            stop_proc(router)
        stop_proc(A)
        stop_proc(B)


def test_fleet_controller_cli_once_against_fakes():
    """scripts/fleet_controller.py --once: one decision tick against live
    fakes exits 0 and prints a JSON action list."""
    A, ua = _start_fake(speed=200)
    B, ub = _start_fake(speed=200)
    try:
        wait_healthy(f"{ua}/health", A, timeout=30)
        wait_healthy(f"{ub}/health", B, timeout=30)
        import subprocess
        import sys

        from production_stack_tpu.testing.procs import REPO_ROOT, cpu_env

        out = subprocess.run(
            [sys.executable, "scripts/fleet_controller.py",
             "--engines", f"{ua},{ub}", "--once"],
            cwd=REPO_ROOT, env=cpu_env(), capture_output=True, text=True,
            timeout=60,
        )
        assert out.returncode == 0, out.stderr[-2000:]
        assert isinstance(json.loads(out.stdout.strip() or "[]"), list)
    finally:
        stop_proc(A)
        stop_proc(B)
