"""OpenAI-compatible HTTP server for the TPU engine (aiohttp).

Implements the serving-engine contract the reference stack expects of vLLM
(SURVEY.md §1 L4): OpenAI API, Prometheus `/metrics` with `vllm:*`-compatible
metric names (so the reference's router scraper, Grafana dashboards, and
prometheus-adapter autoscaling rules work unchanged — stats/engine_stats.py:63-76
in /root/reference), `/health`, `/v1/models`, `/tokenize`, `/detokenize`, and
the sleep/wake endpoints used for pod hibernation
(service_discovery.py:383-408 in /root/reference).
"""

from __future__ import annotations

import argparse
import asyncio
import collections
import inspect
import json
import time
import uuid
from typing import Optional

from aiohttp import web

from production_stack_tpu import __version__
from production_stack_tpu.engine.config import EngineConfig, add_engine_args, config_from_args
from production_stack_tpu.engine.engine import LLMEngine
from production_stack_tpu.engine.scheduler import SamplingParams
from production_stack_tpu.utils.logging import init_logger

logger = init_logger(__name__)

# Per-request TTFT hop samples for streaming requests, in ms:
# (accept->engine-submit, submit->first engine output, first output->first
# SSE write). /metrics exposes p50/p99 per hop; together with the router's
# hop gauges this attributes stack tail latency to a stage.
_ttft_hops: collections.deque = collections.deque(maxlen=2048)

# Cumulative distributions backing the dashboard's TTFT / latency heatmap
# panels (reference vllm-dashboard.json:34-1312); vLLM-compatible names and
# bucket boundaries so those panel queries work unchanged.
from production_stack_tpu.utils.metrics import (  # noqa: E402
    LATENCY_BUCKETS,
    TTFT_BUCKETS,
    Histogram,
)

_ttft_hist = Histogram(
    "vllm:time_to_first_token_seconds", TTFT_BUCKETS,
    "Time to first token distribution",
)
_latency_hist = Histogram(
    "vllm:e2e_request_latency_seconds", LATENCY_BUCKETS,
    "End-to-end request latency distribution",
)


def _ttft_hop_quantiles() -> dict:
    if not _ttft_hops:
        return {}
    names = ("accept_to_submit", "submit_to_first_token", "first_token_to_write")
    out = {}
    for name, vals in zip(names, zip(*_ttft_hops)):
        s = sorted(vals)
        out[name] = {
            "p50": s[len(s) // 2],
            "p99": s[min(len(s) - 1, int(len(s) * 0.99))],
        }
    return out


async def _tag_stream(i, gen):
    async for out in gen:
        yield i, out


async def _merge_streams(gens):
    """Merge n RequestOutput streams into (choice_index, output) tuples,
    preserving per-stream order."""
    q: asyncio.Queue = asyncio.Queue()

    async def pump(i, g):
        try:
            async for out in g:
                await q.put((i, out))
        except Exception as e:  # surface stream errors to the consumer
            await q.put((i, e))
        finally:
            await q.put((i, None))

    tasks = [asyncio.ensure_future(pump(i, g)) for i, g in enumerate(gens)]
    try:
        open_streams = len(gens)
        while open_streams:
            i, out = await q.get()
            if out is None:
                open_streams -= 1
                continue
            if isinstance(out, Exception):
                raise out
            yield i, out
    finally:
        for t in tasks:
            t.cancel()


def _chat_lp_content(tok, token_ids, entries):
    """OpenAI chat logprobs format: choices[].logprobs.content[]."""
    content = []
    for tid, e in zip(token_ids, entries):
        s = tok.decode([tid])
        content.append({
            "token": s,
            "logprob": e["logprob"],
            "bytes": list(s.encode("utf-8", errors="replace")),
            "top_logprobs": [
                {
                    "token": tok.decode([i]),
                    "logprob": lp,
                    "bytes": list(tok.decode([i]).encode("utf-8", errors="replace")),
                }
                for i, lp in zip(e["top_ids"], e["top_logprobs"])
            ],
        })
    return content


def _completion_lp(tok, token_ids, entries, offset0):
    """OpenAI completions logprobs format; returns (dict, next_offset)."""
    toks, tlps, tops, offs = [], [], [], []
    off = offset0
    for tid, e in zip(token_ids, entries):
        s = tok.decode([tid])
        toks.append(s)
        tlps.append(e["logprob"])
        top: dict = {}
        for i, lp in zip(e["top_ids"], e["top_logprobs"]):
            # distinct ids can decode to the same string (byte fragments);
            # entries arrive best-first, so keep the first (highest) lp
            top.setdefault(tok.decode([i]), lp)
        tops.append(top)
        offs.append(off)
        off += len(s)
    return (
        {"tokens": toks, "token_logprobs": tlps, "top_logprobs": tops,
         "text_offset": offs},
        off,
    )


def _sampling_params(
    body: dict, default_max: int = 256, vocab_size: "Optional[int]" = None
) -> SamplingParams:
    stop = body.get("stop") or []
    if isinstance(stop, str):
        stop = [stop]
    return SamplingParams(
        max_tokens=int(body.get("max_tokens") or body.get("max_completion_tokens") or default_max),
        temperature=float(body.get("temperature", 1.0)),
        top_k=int(body.get("top_k", 0)),
        top_p=float(body.get("top_p", 1.0)),
        stop=list(stop),
        ignore_eos=bool(body.get("ignore_eos", False)),
        min_tokens=int(body.get("min_tokens", 0)),
        seed=body.get("seed"),
        presence_penalty=float(body.get("presence_penalty", 0.0)),
        frequency_penalty=float(body.get("frequency_penalty", 0.0)),
        repetition_penalty=float(body.get("repetition_penalty", 1.0)),
        logit_bias=_parse_logit_bias(body.get("logit_bias"), vocab_size),
    )


def _parse_logit_bias(raw, vocab_size: "Optional[int]" = None) -> "Optional[dict]":
    """OpenAI logit_bias: {"<token_id>": bias in [-100, 100]}, <= 300 keys."""
    if not raw:
        return None
    if not isinstance(raw, dict) or len(raw) > 300:
        raise ValueError("logit_bias must be a dict of at most 300 entries")
    out = {}
    for k, v in raw.items():
        try:
            tid, bv = int(k), float(v)
        except (TypeError, ValueError):
            raise ValueError(f"invalid logit_bias entry {k!r}: {v!r}") from None
        if tid < 0:
            raise ValueError(f"logit_bias token id {tid} is negative")
        if vocab_size is not None and tid >= vocab_size:
            # OpenAI rejects out-of-vocab keys with a 400; silently dropping
            # them on device (scatter mode='drop') would hide client bugs
            raise ValueError(
                f"logit_bias token id {tid} out of range for vocab size {vocab_size}"
            )
        if not -100.0 <= bv <= 100.0:
            raise ValueError(f"logit_bias value {bv} outside [-100, 100]")
        out[tid] = bv
    return out


def _shed_response(retry_after_s: float, message: str) -> web.Response:
    """Load-shed contract (docs/failure-handling.md): an overloaded engine
    answers 429 with a Retry-After hint instead of queueing the request into
    unbounded TTFT. The shed-aware router treats this as an immediate
    failover signal that must NOT trip the circuit breaker."""
    retry = max(1, int(-(-retry_after_s // 1)))  # ceil, floor 1 s
    return web.json_response(
        {
            "error": {
                "message": message,
                "type": "overloaded_error",
                "code": 429,
            }
        },
        status=429,
        headers={"Retry-After": str(retry)},
    )


def _request_priority(headers, body) -> str:
    """Per-request SLO class (docs/failure-handling.md priority classes):
    the X-Priority header wins, then the body's "priority" field; anything
    outside the closed {interactive, batch} set degrades to interactive so
    the label cardinality stays bounded."""
    p = headers.get("X-Priority") or (
        body.get("priority") if isinstance(body, dict) else None
    )
    p = str(p).strip().lower() if p else "interactive"
    return p if p in ("interactive", "batch") else "interactive"


def _usage(out) -> dict:
    return {
        "prompt_tokens": out.prompt_tokens,
        "completion_tokens": out.completion_tokens,
        "total_tokens": out.prompt_tokens + out.completion_tokens,
        "prompt_tokens_details": {"cached_tokens": out.cached_tokens},
    }


class EngineServer:
    def _vocab_size(self) -> "Optional[int]":
        """Model vocab size for request validation, when the engine knows it
        (fake/test engines may not carry a model config)."""
        model_cfg = getattr(self.engine, "model_cfg", None)
        return getattr(model_cfg, "vocab_size", None)

    def __init__(self, cfg: EngineConfig, engine: Optional[LLMEngine] = None):
        self.cfg = cfg
        self.engine = engine or LLMEngine(cfg)
        try:
            gen_params = inspect.signature(self.engine.generate).parameters
            self._engine_accepts_trace = "trace" in gen_params
            self._engine_accepts_shed_exempt = "shed_exempt" in gen_params
            self._engine_accepts_priority = "priority" in gen_params
        except (TypeError, ValueError):
            self._engine_accepts_trace = False
            self._engine_accepts_shed_exempt = False
            self._engine_accepts_priority = False
        try:
            sat = getattr(self.engine, "saturated", None)
            self._saturated_accepts_priority = sat is not None and (
                "priority" in inspect.signature(sat).parameters
            )
        except (TypeError, ValueError):
            self._saturated_accepts_priority = False
        self.start_time = time.time()
        # device telemetry sampler (engine/devicemon.py): HBM per device,
        # KV pool vs headroom, compile activity, step duty cycle — rendered
        # into /metrics on scrape (duck-typed engines degrade gracefully)
        from production_stack_tpu.engine.devicemon import DeviceMonitor

        self.devmon = DeviceMonitor(self.engine)
        # graceful drain (SIGTERM): /health flips to 503 so readiness
        # probes / router health checks pull the pod from rotation, new
        # generation requests are refused, and in-flight ones finish
        self.draining = False
        # request-id -> (engine sequence ids, registered-at, streaming,
        # presentation meta), for router-initiated aborts (POST /abort) and
        # live migration (POST /migrate_out): a router that deadline-aborts
        # a hung stream must be able to free this engine's scheduler slot and
        # KV pages without relying on the TCP connection being noticed, and
        # the fleet controller must be able to name a victim stream by its
        # wire id. The meta dict carries what a migration TARGET needs to
        # keep emitting client-shaped chunks (oid/chat/created/model).
        self._live_requests: "dict[str, tuple]" = {}
        # live migration (docs/migration.md; all event-loop-owned):
        # req_id -> {"target", "request_id"} set by a committed migrate_out,
        # consumed by the streaming loop to emit the handoff control event
        self._migrated_out: "dict[str, dict]" = {}
        # req_id -> parked migrated-in continuation ({"q", "task", "snap",
        # "t"}) awaiting the router's POST /migrate_attach
        self._parked: "dict[str, dict]" = {}
        self._mig_session = None  # lazy aiohttp client for /migrate_in ships

    # -- handlers -----------------------------------------------------------

    async def health(self, request: web.Request) -> web.Response:
        if self.draining:
            return web.Response(status=503, text="draining")
        fault = getattr(self.engine, "program_fault", None)
        if fault:
            # a step program failed to build (engine.py): the engine keeps
            # answering so /stats and the flight recorder stay readable,
            # but it must leave rotation rather than serve errors
            return web.Response(status=503, text=f"program fault: {fault}")
        return web.Response(text="")

    async def abort(self, request: web.Request) -> web.Response:
        """Router-initiated abort (POST /abort {"request_id": ...}): free the
        scheduler slot and KV pages of a request whose client-side stream was
        deadline-aborted. Closing the proxy connection only reaches an engine
        that is actively writing; this endpoint reaches a hung one. Abort of
        an unknown or already-finished request is a no-op (200, aborted=false)
        so the router can fire-and-forget."""
        try:
            body = await request.json()
        except Exception:  # noqa: BLE001 - malformed abort is harmless
            body = {}
        req_id = body.get("request_id") or request.query.get("request_id")
        if not req_id:
            return web.json_response(
                {"error": {"message": "request_id required"}}, status=400
            )
        entry = self._live_requests.pop(req_id, None)
        for sid in entry[0] if entry else [req_id]:
            self.engine.abort(sid)
        logger.info("abort requested for %s (live=%s)", req_id, entry is not None)
        return web.json_response({"request_id": req_id, "aborted": entry is not None})

    # -- live sequence migration (docs/migration.md) -------------------------

    async def _mig_client(self):
        """Lazy shared client session for shipping snapshots to targets."""
        import aiohttp

        if self._mig_session is None or self._mig_session.closed:
            self._mig_session = aiohttp.ClientSession(
                timeout=aiohttp.ClientTimeout(total=30, sock_connect=5)
            )
        return self._mig_session

    async def _close_mig_client(self, app=None) -> None:
        if self._mig_session is not None and not self._mig_session.closed:
            await self._mig_session.close()
        self._mig_session = None

    async def migratable(self, request: web.Request) -> web.Response:
        """Controller victim listing: live single-choice streaming requests
        with their progress and migratability verdict. Read-only snapshot of
        scheduler state — racing the device thread can only mis-list a
        request for one tick; the authoritative re-check runs at freeze."""
        mig = getattr(self.engine, "migration", None)
        out: list = []
        if mig is not None:
            from production_stack_tpu.migration import unmigratable_reason

            running = {
                s.seq_id: s for s in list(self.engine.scheduler.running)
            }
            for rid, entry in list(self._live_requests.items()):
                sub_ids, _ts, streaming, _meta = entry
                if not streaming or len(sub_ids) != 1:
                    continue
                seq = running.get(sub_ids[0])
                if seq is None or seq.finished:
                    continue
                reason = unmigratable_reason(seq)
                out.append({
                    "request_id": rid,
                    "output_tokens": len(seq.output_ids),
                    "prompt_tokens": len(seq.prompt_ids),
                    "age_s": round(time.monotonic() - seq.arrival_time, 3),
                    "migratable": reason is None,
                    "reason": reason,
                    # SLO class so the controller's latency-protection
                    # policy can pick batch victims only
                    "priority": _meta.get("priority") or "interactive",
                })
        return web.json_response({"requests": out})

    async def kv_fabric_info(self, request: web.Request) -> web.Response:
        """Fabric discovery: disagg producers, directory pullers, and
        migration sources resolve this engine's fabric listener address (and
        its generation/dtype handshake facts) from here."""
        srv = getattr(self.engine, "_fabric_server", None)
        if srv is None:
            return web.json_response({"enabled": False})
        return web.json_response({
            "enabled": True,
            "addr": srv.address,
            "generation": srv.generation,
            "quant": srv.quant,
            "page_size": srv.page_size,
        })

    async def migrate_out(self, request: web.Request) -> web.Response:
        """Freeze a running stream, ship its snapshot to the target engine's
        /migrate_in, then commit (the stream ends with the handoff control
        event the router splices on) or roll back (the sequence resumes
        decoding locally — nothing was client-visible)."""
        mig = getattr(self.engine, "migration", None)
        if mig is None:
            return web.json_response(
                {"migrated": False, "error": "migration disabled"}, status=501
            )
        try:
            body = await request.json()
            rid = body["request_id"]
            target = str(body["target_url"]).rstrip("/")
        except (KeyError, TypeError, ValueError):
            return web.json_response(
                {"migrated": False,
                 "error": "request_id and target_url required"},
                status=400,
            )
        entry = self._live_requests.get(rid)
        if entry is None:
            return web.json_response(
                {"migrated": False, "error": f"request {rid!r} is not live"},
                status=409,
            )
        sub_ids, _ts, streaming, meta = entry
        if not streaming or len(sub_ids) != 1:
            return web.json_response(
                {"migrated": False,
                 "error": "only single-choice streaming requests migrate"},
                status=409,
            )
        from production_stack_tpu.migration import (
            MigrationError,
            snapshot_to_wire,
        )

        loop = asyncio.get_running_loop()
        snap_meta = {**meta, "request_id": rid}
        # fabric handoff: resolve the target's fabric listener FIRST so the
        # freeze can ship the page chain engine-to-engine (zero shared-tier
        # I/O); an unresolvable/disabled fabric degrades to the tier save
        # inside _freeze
        fabric_addr = None
        if getattr(self.engine, "_fabric_client", None) is not None:
            try:
                session = await self._mig_client()
                async with session.get(f"{target}/kv_fabric") as resp:
                    if resp.status == 200:
                        info = await resp.json()
                        if info.get("enabled"):
                            fabric_addr = info.get("addr")
            except Exception as e:  # noqa: BLE001 - tier path covers it
                logger.debug("fabric resolve for %s failed: %s", target, e)
        try:
            # device-thread work off the event loop (GC001 discipline)
            snap = await loop.run_in_executor(
                None,
                lambda: mig.freeze_and_snapshot(
                    sub_ids[0], snap_meta, fabric_addr
                ),
            )
        except MigrationError as e:
            return web.json_response(
                {"migrated": False, "error": str(e)}, status=409
            )
        ok, detail = False, ""
        try:
            session = await self._mig_client()
            async with session.post(
                f"{target}/migrate_in", data=snapshot_to_wire(snap),
                headers={"Content-Type": "application/octet-stream"},
            ) as resp:
                detail = (await resp.text())[:200]
                ok = resp.status == 200
        except Exception as e:  # noqa: BLE001 - any ship failure rolls back
            detail = repr(e)
        if not ok:
            # fallback: the sequence re-enters the running set and keeps
            # streaming locally — the client never noticed the attempt
            await loop.run_in_executor(None, mig.rollback, sub_ids[0])
            logger.warning(
                "migrate_out %s -> %s refused: %s", rid, target, detail
            )
            return web.json_response(
                {"migrated": False, "error": detail or "target refused"},
                status=502,
            )
        # control-event metadata BEFORE commit: the commit's terminal emit
        # races the streaming loop's pop of this entry. Janitor: when the
        # client disconnected between freeze and commit, the streaming
        # handler already tore down (its pop ran before this set) and the
        # terminal emit finds no consumer — nothing would ever pop the
        # entry, and a reused wire id would see a stale handoff target
        self._migrated_out[rid] = {"target": target, "request_id": rid}
        loop.call_later(60.0, self._migrated_out.pop, rid, None)
        await loop.run_in_executor(
            None, mig.commit, sub_ids[0], len(snap.page_hashes)
        )
        logger.info(
            "migrated %s -> %s (%d pages restorable)",
            rid, target, len(snap.page_hashes),
        )
        return web.json_response({
            "migrated": True, "target": target,
            "pages_moved": len(snap.page_hashes),
        })

    async def migrate_in(self, request: web.Request) -> web.Response:
        """Accept a sealed snapshot and park the continuation: KV blobs
        prefetch into the local tiers, the sequence re-admits through the
        ordinary prefix-cache path (shipped pages share, the tail recomputes
        deterministically), and outputs buffer until /migrate_attach."""
        mig = getattr(self.engine, "migration", None)
        if mig is None:
            return web.json_response(
                {"accepted": False, "error": "migration disabled"}, status=501
            )
        if self.draining:
            return web.json_response(
                {"accepted": False, "error": "draining"}, status=503
            )
        if self.engine.is_sleeping:
            return web.json_response(
                {"accepted": False, "error": "sleeping"}, status=503
            )
        saturated = getattr(self.engine, "saturated", None)
        if saturated is not None and saturated():
            # a saturated target must refuse extra work — 429 tells the
            # controller to pick a cooler target (breaker-neutral, like any
            # shed)
            return _shed_response(
                getattr(self.engine, "shed_retry_after", lambda: 1.0)(),
                "engine saturated; pick a cooler migration target",
            )
        from production_stack_tpu.kvoffload.serde import KVIntegrityError
        from production_stack_tpu.migration import (
            continuation_params,
            snapshot_from_wire,
        )

        data = await request.read()
        try:
            snap = snapshot_from_wire(data)
            params = continuation_params(snap)
        except (KVIntegrityError, ValueError, KeyError, TypeError) as e:
            return web.json_response(
                {"accepted": False, "error": f"bad snapshot: {e}"}, status=400
            )
        if snap.model != self.cfg.name:
            return web.json_response(
                {"accepted": False,
                 "error": f"model mismatch: {snap.model!r} != {self.cfg.name!r}"},
                status=409,
            )
        rid = snap.request_id
        if rid in self._parked or rid in self._live_requests:
            return web.json_response(
                {"accepted": False, "error": f"{rid!r} already live here"},
                status=409,
            )
        loop = asyncio.get_running_loop()
        q: asyncio.Queue = asyncio.Queue()
        task = loop.create_task(self._pump_migrated(snap, params, q))
        self._parked[rid] = {
            "q": q, "task": task, "snap": snap, "t": time.monotonic(),
        }
        # chained migration: the continuation is itself a live, migratable
        # stream (an engine holding migrated-in work must still evacuate).
        # prior_completion accumulates tokens emitted on EVERY previous hop:
        # a re-freeze snapshots only THIS engine's output_ids, so without
        # the running total a 2+-hop stream's final usage would drop the
        # first hop's tokens
        self._live_requests[rid] = (
            [rid], time.monotonic(), True,
            {**snap.meta,
             "prior_completion": snap.output_len
             + int(snap.meta.get("prior_completion") or 0)},
        )
        # a router that died mid-handoff must not leak a decoding sequence:
        # unattached continuations abort after the timeout
        loop.call_later(
            max(1.0, getattr(self.cfg, "migrate_attach_timeout_s", 30.0)),
            self._expire_parked, rid,
        )
        mig.note_migrate_in()
        return web.json_response({
            "accepted": True, "request_id": rid,
            "restorable_pages": len(snap.page_hashes),
        })

    async def _pump_migrated(self, snap, params, q: asyncio.Queue) -> None:
        """Parked continuation driver: prefetch the snapshot's KV blobs into
        the local tiers (executor — tier reads block), then resume decoding
        and buffer outputs for the attach stream. shed_exempt: a migrated
        stream is mid-flight — shedding it would drop a committed stream."""
        loop = asyncio.get_running_loop()
        mig = self.engine.migration
        try:
            if snap.page_hashes and snap.page_size == self.cfg.page_size:
                await loop.run_in_executor(
                    None, mig.prefetch_pages, snap.page_hashes
                )
            kwargs = {}
            if self._engine_accepts_priority:
                # the continuation keeps its SLO class across the hop, so a
                # migrated batch stream stays a latency-protection victim on
                # the target too
                p = snap.meta.get("priority")
                kwargs["priority"] = (
                    p if p in ("interactive", "batch") else "interactive"
                )
            async for out in self.engine.generate(
                snap.request_id, prompt_token_ids=snap.tokens, params=params,
                shed_exempt=True, **kwargs,
            ):
                await q.put(out)
        except asyncio.CancelledError:
            raise
        except Exception as e:  # noqa: BLE001 - surfaced on the attach stream
            await q.put(e)
        finally:
            self._live_requests.pop(snap.request_id, None)
            await q.put(None)

    def _expire_parked(self, rid: str) -> None:
        parked = self._parked.pop(rid, None)
        if parked is None:
            return  # attached (or already expired)
        parked["task"].cancel()
        self.engine.abort(rid)
        mig = getattr(self.engine, "migration", None)
        if mig is not None:
            mig.failures += 1
        logger.warning(
            "migrated-in continuation %s expired unattached; aborted", rid
        )

    async def migrate_attach(self, request: web.Request) -> web.StreamResponse:
        """Stream a parked continuation in the client wire shape. The final
        usage block reports WHOLE-request totals (pre- + post-migration), so
        the spliced stream is indistinguishable from an unmigrated one."""
        if getattr(self.engine, "migration", None) is None:
            return web.json_response(
                {"error": {"message": "migration disabled"}}, status=501
            )
        try:
            body = await request.json()
        except Exception:  # noqa: BLE001 - allow query-only attaches
            body = {}
        rid = body.get("request_id") or request.query.get("request_id")
        if not rid:
            return web.json_response(
                {"error": {"message": "request_id required"}}, status=400
            )
        # tiny grace for reordering: the source commits (ending its stream)
        # only after our /migrate_in returned, so the parked entry normally
        # exists before any attach arrives
        deadline = time.monotonic() + 10.0
        parked = self._parked.pop(rid, None)
        while parked is None and time.monotonic() < deadline:
            await asyncio.sleep(0.05)
            parked = self._parked.pop(rid, None)
        if parked is None:
            return web.json_response(
                {"error": {"message": f"no parked continuation for {rid!r}"}},
                status=404,
            )
        snap, q = parked["snap"], parked["q"]
        meta = snap.meta
        chat = bool(meta.get("chat"))
        oid = meta.get("oid") or (("chatcmpl-" if chat else "cmpl-") + rid)
        created = int(meta.get("created") or time.time())
        model = meta.get("model") or snap.model
        kind = "chat.completion" if chat else "text_completion"
        resp = web.StreamResponse(
            status=200,
            headers={
                "Content-Type": "text/event-stream",
                "Cache-Control": "no-cache",
                "X-Request-Id": rid,
            },
        )
        await resp.prepare(request)

        async def send(obj: dict):
            await resp.write(f"data: {json.dumps(obj)}\n\n".encode())

        new_tokens = 0
        try:
            while True:
                out = await q.get()
                if out is None:
                    break
                if isinstance(out, Exception):
                    await send({"error": {
                        "message": f"migrated continuation failed: {out}",
                        "type": "upstream_error", "code": 502,
                    }})
                    await resp.write_eof()
                    return resp
                if (
                    out.finished
                    and out.finish_reason == "migrated"
                    and (mi := self._migrated_out.pop(rid, None)) is not None
                ):
                    # chained migration: this continuation moved AGAIN —
                    # hand the splice the next hop and end this leg
                    await send({"pstpu_migration": mi})
                    await resp.write_eof()
                    return resp
                new_tokens = out.completion_tokens
                if out.finished and out.finish_reason in (
                    "abort", "error", "shed"
                ):
                    await send({"error": {
                        "message": (
                            "migrated continuation ended with "
                            f"{out.finish_reason!r}"
                        ),
                        "type": "upstream_error", "code": 502,
                    }})
                    await resp.write_eof()
                    return resp
                if chat:
                    delta = (
                        {"content": out.text_delta} if out.text_delta else {}
                    )
                    choice = {"index": 0, "delta": delta,
                              "finish_reason": out.finish_reason}
                    obj = "chat.completion.chunk"
                else:
                    choice = {"index": 0, "text": out.text_delta,
                              "logprobs": None,
                              "finish_reason": out.finish_reason}
                    obj = "text_completion"
                await send({
                    "id": oid, "object": obj, "created": created,
                    "model": model, "choices": [choice],
                })
            prompt_tokens = int(meta.get("prompt_tokens") or snap.prompt_len)
            # whole-request total: every previous hop's tokens + the tokens
            # already emitted when THIS hop froze + this continuation's
            completion = (
                int(meta.get("prior_completion") or 0)
                + snap.output_len + new_tokens
            )
            await send({
                "id": oid, "object": f"{kind}.chunk" if chat else kind,
                "created": created, "model": model, "choices": [],
                "usage": {
                    "prompt_tokens": prompt_tokens,
                    "completion_tokens": completion,
                    "total_tokens": prompt_tokens + completion,
                },
            })
            await resp.write(b"data: [DONE]\n\n")
        except (ConnectionResetError, asyncio.CancelledError):
            # the splicing router (or client) went away: reclaim the seq
            parked["task"].cancel()
            self.engine.abort(rid)
            raise
        await resp.write_eof()
        return resp

    async def drain(self, timeout: float = 30.0) -> None:
        """Stop accepting generation work and wait for the engine to go
        idle (in-flight requests complete) or ``timeout`` to pass."""
        self.draining = True
        # SIGTERM anomaly dump FIRST (forced — this process is going away):
        # the pre-drain scheduler/KV window is what a rolling-restart
        # postmortem needs, and waiting out the drain would overwrite it
        from production_stack_tpu.tracing import get_flightrecorder

        get_flightrecorder().dump("sigterm_drain", force=True)
        logger.info("draining: refusing new requests, waiting for %d in flight",
                    self.engine.scheduler.num_running())
        deadline = time.time() + timeout
        while time.time() < deadline and self.engine.scheduler.has_work():
            await asyncio.sleep(0.2)
        if self.engine.scheduler.has_work():
            logger.warning("drain timeout: %d request(s) still running",
                           self.engine.scheduler.num_running())
        # warm-start manifest: spill the hot working set AFTER in-flight work
        # finished (their pages are registered by now), so the next
        # incarnation restores it instead of recomputing (warm restarts)
        spill = getattr(self.engine, "warm_spill", None)
        if spill is not None:
            try:
                n = await asyncio.get_running_loop().run_in_executor(None, spill)
                if n:
                    logger.info("drain: warm-start manifest spilled (%d pages)", n)
            except Exception:  # noqa: BLE001 - shutdown keeps going
                logger.exception("drain: warm-start spill failed")

    async def version(self, request: web.Request) -> web.Response:
        return web.json_response({"version": __version__})

    async def models(self, request: web.Request) -> web.Response:
        data = [
            {
                "id": self.cfg.name,
                "object": "model",
                "created": int(self.start_time),
                "owned_by": "production-stack-tpu",
                "max_model_len": self.cfg.max_model_len,
            }
        ]
        # loaded LoRA adapters appear as servable models with a parent pointer
        # (vLLM convention; the reference LoraAdapter controller and router
        # model discovery both read this listing)
        for name in self.engine.list_lora_adapters():
            data.append(
                {
                    "id": name,
                    "object": "model",
                    "created": int(self.start_time),
                    "owned_by": "production-stack-tpu",
                    "parent": self.cfg.name,
                    "max_model_len": self.cfg.max_model_len,
                }
            )
        return web.json_response({"object": "list", "data": data})

    async def tokenize(self, request: web.Request) -> web.Response:
        body = await request.json()
        text = body.get("prompt")
        if text is None and "messages" in body:
            text = self.engine.tokenizer.apply_chat_template(body["messages"])
        ids = self.engine.tokenizer.encode(text or "")
        return web.json_response(
            {"tokens": ids, "count": len(ids), "max_model_len": self.cfg.max_model_len}
        )

    async def detokenize(self, request: web.Request) -> web.Response:
        body = await request.json()
        return web.json_response({"prompt": self.engine.tokenizer.decode(body.get("tokens", []))})

    async def metrics(self, request: web.Request) -> web.Response:
        s = self.engine.stats()
        m = self.cfg.name
        lines = []

        def emit(name: str, kind: str, value, help_: str = ""):
            lines.append(f"# HELP vllm:{name} {help_ or name}")
            lines.append(f"# TYPE vllm:{name} {kind}")
            lines.append(f'vllm:{name}{{model_name="{m}"}} {value}')

        emit("num_requests_running", "gauge", s["num_requests_running"])
        emit("num_requests_waiting", "gauge", s["num_requests_waiting"])
        emit("num_requests_swapped", "gauge", s.get("num_requests_swapped", 0))
        emit("num_preemptions_total", "counter",
             s.get("num_preemptions_total", 0))
        # overload surface: saturation state + load sheds (admission control)
        emit("engine_saturated", "gauge", s.get("engine_saturated", 0),
             "1 while the waiting queue is at its max_waiting_seqs bound")
        emit("num_requests_shed_total", "counter",
             s.get("num_requests_shed_total", 0),
             "generation requests shed with 429 (queue full or queue deadline)")
        # per-SLO-class overload surface (docs/failure-handling.md priority
        # classes): shed order, batch-early saturation, and the interactive
        # latency signal the fleet controller's latency protection scrapes
        emit("num_requests_shed_interactive_total", "counter",
             s.get("num_requests_shed_interactive_total", 0),
             "interactive-class requests shed with 429")
        emit("num_requests_shed_batch_total", "counter",
             s.get("num_requests_shed_batch_total", 0),
             "batch-class requests shed with 429")
        emit("engine_saturated_batch", "gauge",
             s.get("engine_saturated_batch", 0),
             "1 while batch-class admission is shedding (interactive reserve)")
        emit("interactive_ttft_p99_ms", "gauge",
             s.get("interactive_ttft_p99_ms", 0.0),
             "p99 TTFT over the recent interactive ok-request window")
        emit("interactive_itl_p99_ms", "gauge",
             s.get("interactive_itl_p99_ms", 0.0),
             "p99 inter-token latency over the recent interactive window")
        emit("tensor_parallel_degree", "gauge",
             s.get("tensor_parallel", 1),
             "tp mesh-axis size of the serving mesh (chips per replica)")
        emit("device_count", "gauge", s.get("device_count", 0),
             "devices JAX reports in this process (len(jax.devices()))")
        emit("engine_step_errors_total", "counter",
             s.get("engine_step_errors_total", 0),
             "engine steps that raised (their batches finished with error)")
        emit("engine_program_fault", "gauge",
             int(bool(s.get("engine_program_fault"))),
             "1 once a step program failed to build (/health answers 503)")
        # the strings of the same report (platform, device kind, resolved
        # attention implementations), info-style: constant 1, labels carry it
        lines.append("# HELP vllm:device_info device and resolved attention paths")
        lines.append("# TYPE vllm:device_info gauge")
        lines.append(
            f'vllm:device_info{{model_name="{m}",'
            f'platform="{s.get("platform", "")}",'
            f'device_kind="{s.get("device_kind", "")}",'
            f'attn_impl_prefill="{s.get("attn_impl_prefill", "")}",'
            f'attn_impl_decode="{s.get("attn_impl_decode", "")}"}} 1'
        )
        emit("gpu_cache_usage_perc", "gauge", s["gpu_cache_usage_perc"])
        emit("gpu_prefix_cache_hit_rate", "gauge", s["gpu_prefix_cache_hit_rate"])
        emit("gpu_prefix_cache_hits_total", "counter", s["gpu_prefix_cache_hits_total"])
        emit("gpu_prefix_cache_queries_total", "counter", s["gpu_prefix_cache_queries_total"])
        emit("prompt_tokens_total", "counter", s["prompt_tokens_total"])
        emit("generation_tokens_total", "counter", s["generation_tokens_total"])
        emit("decode_dispatches_total", "counter", s["decode_dispatches_total"])
        emit("decode_chained_dispatches_total", "counter",
             s["decode_chained_dispatches_total"])
        emit("runahead_prefill_dispatches_total", "counter",
             s.get("runahead_prefill_dispatches_total", 0))
        emit("decode_kv_tokens_read_total", "counter",
             s.get("decode_kv_tokens_read_total", 0),
             "KV tokens the decoded tokens attended (min(context, window) each)")
        for name, help_ in (
            ("prefill_dispatches_total", "prefill dispatches planned"),
            ("prefill_rider_dispatches_total",
             "prefill dispatches in which the running decode rows took one step"),
            ("prefill_rider_rows_total",
             "decode rows that took a step inside a prefill dispatch (a token each)"),
        ):
            emit(name, "counter", s.get(name, 0), help_)
        # one dispatch queued behind the one that runs (engine._turn): how
        # many went out that way, and what had emptied the loop for the rest
        for name, label, help_ in (
            ("queued_ahead_dispatches_total", "kind",
             "dispatches enqueued while the one before them still ran"),
            ("queue_ahead_drains_total", "reason",
             "dispatches that found the device idle, by what emptied the loop"),
            ("prefill_riderless_dispatches_total", "reason",
             "prefill dispatches planned while decode rows ran that carried none"),
        ):
            lines.append(f"# HELP vllm:{name} {help_}")
            lines.append(f"# TYPE vllm:{name} counter")
            for key, n in sorted(s.get(name, {}).items()):
                lines.append(
                    f'vllm:{name}{{model_name="{m}",{label}="{key}"}} {n}'
                )
        if "ssm_state_slots" in s:
            # a family with recurrent state beside its pages (models/jamba.py)
            emit("ssm_state_slots", "gauge", s["ssm_state_slots"],
                 "slots of the recurrent-state pool (one a running sequence)")
            emit("ssm_state_slots_in_use", "gauge", s["ssm_state_slots_in_use"],
                 "state slots held by admitted sequences")
            emit("ssm_state_bytes", "gauge", s["ssm_state_bytes"],
                 "bytes of the recurrent-state pool, the null slot included")
            emit("ssm_state_bytes_per_slot", "gauge",
                 s["ssm_state_bytes_per_slot"],
                 "bytes one running sequence keeps beside its pages")
            emit("ssm_prefill_tokens_total", "counter",
                 s["ssm_prefill_tokens_total"],
                 "prompt tokens the selective scan walked in prefill chunks")
            emit("ssm_decode_tokens_total", "counter",
                 s["ssm_decode_tokens_total"],
                 "output tokens the selective scan stepped in decode bursts")
            emit("conv_state_bytes", "gauge", s["conv_state_bytes"],
                 "bytes of the convolution tails in the state pool")
        if "moe_routed_rows_total" in s:
            # sparse experts (ops/moe.py), counted by the device
            emit("moe_routed_rows_total", "counter", s["moe_routed_rows_total"],
                 "token-to-expert assignments the expert layers computed")
            emit("moe_expert_reads_total", "counter", s["moe_expert_reads_total"],
                 "experts with at least one row, over expert layers and steps")
            emit("moe_expert_slots_total", "counter", s["moe_expert_slots_total"],
                 "experts held, over expert layers and steps")
        if "ssd_decode_tokens_total" in s:
            # Mamba-2 layers (models/nemotron_h.py), counted by the device
            emit("ssd_decode_tokens_total", "counter", s["ssd_decode_tokens_total"],
                 "output tokens the SSD layers stepped in decode bursts")
            emit("ssd_prefill_tokens_total", "counter", s["ssd_prefill_tokens_total"],
                 "prompt tokens the SSD layers walked in chunks")
            emit("ssd_prefill_chunks_total", "counter", s["ssd_prefill_chunks_total"],
                 "chunks of chunk_size positions those tokens lay in")
            emit("ssd_prefill_rows_total", "counter", s["ssd_prefill_rows_total"],
                 "rows of prefill dispatches: a state in and out once a layer")
        emit("first_dispatches_total", "counter",
             s.get("first_dispatches_total", 0),
             "step-program shapes dispatched for the first time in this process")
        emit("first_dispatch_seconds_total", "counter",
             s.get("first_dispatch_seconds_total", 0.0),
             "wall seconds the engine stood at first dispatches")
        for phase in ("trace", "lower", "compile", "run"):
            emit(f"first_dispatch_{phase}_seconds_total", "counter",
                 s.get(f"first_dispatch_{phase}_seconds_total", 0.0))
        # the store of exported step programs beside the compile cache
        emit("step_program_store_hits_total", "counter",
             s.get("step_program_store_hits_total", 0),
             "first dispatches that found their exported step program")
        emit("step_program_store_writes_total", "counter",
             s.get("step_program_store_writes_total", 0),
             "step programs exported and written beside the compile cache")
        emit("step_program_store_errors_total", "counter",
             s.get("step_program_store_errors_total", 0),
             "blobs deleted and rebuilt + programs jax.export refused")
        # what the loader built of the store's listing as the process started
        emit("step_program_preload_listed", "gauge",
             s.get("step_program_preload_listed", 0),
             "step programs the store listed for this process's identity")
        emit("step_program_preloaded_total", "counter",
             s.get("step_program_preloaded_total", 0),
             "listed step programs built off the loop's thread")
        emit("step_program_preload_failed_total", "counter",
             s.get("step_program_preload_failed_total", 0),
             "listed step programs not found, built or callable: unlisted")
        emit("step_program_preload_served_total", "counter",
             s.get("step_program_preload_served_total", 0),
             "first dispatches that ran a preloaded executable")
        emit("step_program_preload_seconds", "gauge",
             s.get("step_program_preload_seconds", 0.0),
             "wall seconds the loader has worked")
        if s.get("step_program_preload_pending_at_first_dispatch") is not None:
            emit("step_program_preload_pending_at_first_dispatch", "gauge",
                 s["step_program_preload_pending_at_first_dispatch"],
                 "listed programs still to build when the first dispatch came")
        for k in sorted(s):  # kv offload / transfer / spec / warm-start / loop
            if k.startswith(("kv_", "spec_decode_", "engine_loop_", "engine_dispatch_",
                             "warm_start_")):
                kind = "counter" if k.endswith("_total") else "gauge"
                emit(k, kind, s[k])
        # TTFT hop breakdown for streaming requests (accept->submit->first
        # token->first SSE write), p50/p99 over the sample window. ONE TYPE
        # line per metric name — a duplicate would fail the whole Prometheus
        # scrape
        hops = _ttft_hop_quantiles()
        # engine-side admission wait (arrival -> first prefill dispatch):
        # the slice of submit_to_first_token a chained decode dispatch can
        # inflate; exposed so the bench can prove the adaptive chain cap
        waits = getattr(self.engine, "admission_wait_ms", None)
        if waits:
            # the engine thread appends concurrently; iterating a mutating
            # deque raises RuntimeError — snapshot with a bounded retry
            s_w = None
            for _ in range(3):
                try:
                    s_w = sorted(waits)
                    break
                except RuntimeError:
                    continue
            if s_w:
                hops["admission_wait"] = {
                    "p50": s_w[len(s_w) // 2],
                    "p99": s_w[min(len(s_w) - 1, int(len(s_w) * 0.99))],
                }
        for hop, qs in hops.items():
            lines.append(f"# TYPE vllm:ttft_hop_{hop}_ms gauge")
            for q, v in qs.items():
                lines.append(
                    f'vllm:ttft_hop_{hop}_ms{{model_name="{m}",quantile="{q}"}} '
                    f"{round(v, 3)}"
                )
        # distribution histograms (dashboard TTFT/latency heatmap panels)
        lines.extend(_ttft_hist.render(f'model_name="{m}"'))
        lines.extend(_latency_hist.render(f'model_name="{m}"'))
        # per-phase histograms (tracing subsystem): queue wait, prefill,
        # time-per-output-token, offload restore — the dashboard's
        # phase-breakdown panels read these
        from production_stack_tpu.tracing import (
            render_collector_metrics,
            render_flightrecorder_metrics,
            render_phase_histograms,
        )

        # live-migration surface (docs/migration.md): counters + the
        # freeze-to-commit duration histogram
        mig = getattr(self.engine, "migration", None)
        if mig is not None:
            ms = mig.stats()
            for k in sorted(ms):
                emit(k, "counter", ms[k])
            lines.extend(mig.duration_hist.render(f'model_name="{m}"'))
        # KV fabric surface (docs/kv-fabric.md): stream/pull latency
        # histograms + the per-peer probed-bandwidth gauge the disagg router
        # and fleet controller scrape for transfer-cost-aware placement.
        # Counters (kv_fabric_*_total) already rendered via engine.stats()
        fab = getattr(self.engine, "_fabric_client", None)
        if fab is not None:
            lines.extend(fab.push_hist.render(f'model_name="{m}"'))
            lines.extend(fab.pull_hist.render(f'model_name="{m}"'))
            peers = fab.probe_cache.snapshot()
            lines.append(
                "# HELP vllm:kv_fabric_peer_bandwidth_bytes_per_sec "
                "probed engine-to-engine fabric bandwidth per peer"
            )
            lines.append(
                "# TYPE vllm:kv_fabric_peer_bandwidth_bytes_per_sec gauge"
            )
            if not peers:
                # zero-valued placeholder keeps the name scrapeable (and the
                # dashboard panel non-empty) before the first probe completes
                lines.append(
                    f"vllm:kv_fabric_peer_bandwidth_bytes_per_sec"
                    f'{{model_name="{m}",peer="none"}} 0'
                )
            for addr, link in sorted(peers.items()):
                lines.append(
                    f"vllm:kv_fabric_peer_bandwidth_bytes_per_sec"
                    f'{{model_name="{m}",peer="{addr}"}} '
                    f"{round(link.bandwidth, 1)}"
                )
        lines.extend(render_phase_histograms(f'model_name="{m}"'))
        # span-loss + flight-recorder health (trace debugging is only
        # trustworthy when its own drops are measurable)
        lines.extend(render_collector_metrics(f'model_name="{m}"'))
        lines.extend(render_flightrecorder_metrics(f'model_name="{m}"'))
        # TPU device telemetry (engine/devicemon.py): HBM in use/limit per
        # device, KV pool vs headroom, compile cache + seconds, duty cycle
        try:
            lines.extend(self.devmon.metrics_lines(m))
        except Exception:  # noqa: BLE001 - telemetry must never break a scrape
            logger.exception("device telemetry sampling failed")
        return web.Response(text="\n".join(lines) + "\n", content_type="text/plain")

    async def slo_records(self, request: web.Request) -> web.Response:
        """Per-request SLO terminal records since a cursor (docs/
        observability.md). The router's stats scraper polls this with
        ``?since=<last seq>`` each scrape interval and aggregates the
        records into per-model/backend SLO attainment counters; the log is
        a bounded ring, so a scraper further behind than its capacity sees
        a gap (records dropped, not blocked)."""
        try:
            since = int(request.query.get("since", "0"))
        except (TypeError, ValueError):
            return web.json_response({"error": "since must be an int"}, status=400)
        log = getattr(self.engine, "slo_records", None)
        records: list = []
        # an exhausted snapshot retry must NOT report head=0 — the scraper
        # reads head < cursor as "engine restarted" and would reset its
        # cursor, double-counting every retained record next round; head ==
        # the caller's cursor is the safe "nothing new" answer
        head = since
        if log:
            # the engine thread appends concurrently; iterating a mutating
            # deque raises RuntimeError — snapshot with a bounded retry
            for _ in range(3):
                try:
                    snap = list(log)
                    # max, not snap[-1]: the device thread and the event
                    # loop (api-shed records) both append, so the tail can
                    # momentarily be out of seq order
                    head = max((r["seq"] for r in snap), default=0)
                    records = [r for r in snap if r["seq"] > since]
                    break
                except RuntimeError:
                    continue
        elif log is not None:
            head = 0  # empty log: a true fresh-counter signal is correct
        next_cursor = max((r["seq"] for r in records), default=since)
        return web.json_response({
            "model": self.cfg.name,
            "since": since,
            "next": next_cursor,
            # current max record seq: a head BELOW the caller's cursor means
            # this process restarted (fresh counter) — the scraper resets its
            # cursor instead of waiting for the new counter to catch up
            "head": head,
            "records": records,
        })

    async def flightrecorder(self, request: web.Request) -> web.Response:
        """Flight-recorder export (debug surface; docs/observability.md).
        Filters: ?request_id= ?trace_id= ?kind= ?since_step= ?until_step=
        ?limit=."""
        from production_stack_tpu.tracing import flightrecorder

        payload, status = flightrecorder.export_for_query(request.query)
        return web.json_response(payload, status=status)

    async def profile_start(self, request: web.Request) -> web.Response:
        """Start the device profiler in this process (debug surface;
        docs/tracing.md). Body: {"dir": <directory the trace is written
        under>}. The program's ``pstpu.*`` host spans switch on with it."""
        from production_stack_tpu.tracing import profiler

        try:
            log_dir = str((await request.json())["dir"])
        except (ValueError, KeyError, TypeError):
            return web.json_response(
                {"error": {"message": 'body must be {"dir": "<path>"}'}},
                status=400,
            )
        try:
            await asyncio.get_running_loop().run_in_executor(
                None, profiler.start, log_dir
            )
        except RuntimeError as e:
            return web.json_response({"error": {"message": str(e)}}, status=409)
        return web.json_response({"ok": True, "dir": log_dir})

    async def profile_stop(self, request: web.Request) -> web.Response:
        """Stop the profiler and write the trace; answers {"stop_s", "path"}."""
        from production_stack_tpu.tracing import profiler

        try:
            done = await asyncio.get_running_loop().run_in_executor(
                None, profiler.stop
            )
        except RuntimeError as e:
            return web.json_response({"error": {"message": str(e)}}, status=409)
        return web.json_response({"ok": True, **done})

    async def stats(self, request: web.Request) -> web.Response:
        """JSON engine state snapshot (saturation, queue depths, KV pool,
        shed counters) — the machine-readable twin of /metrics for
        autoscalers and the router's shed-aware logic (docs/failure-handling
        overload section)."""
        s = dict(self.engine.stats())
        s["saturation"] = {
            "saturated": bool(s.get("engine_saturated", 0)),
            "max_waiting_seqs": getattr(self.cfg, "max_waiting_seqs", 0),
            "queue_deadline_s": getattr(self.cfg, "queue_deadline_s", 0.0),
            "retry_after_s": getattr(
                self.engine, "shed_retry_after", lambda: 1.0
            )(),
            "draining": self.draining,
        }
        return web.json_response(s)

    async def traces(self, request: web.Request) -> web.Response:
        """Span ring-buffer export (read-only debug surface; docs/tracing.md).
        ?trace_id= filters to one trace, ?limit= caps the trace count."""
        from production_stack_tpu.tracing import export_for_query

        payload, status = export_for_query(request.query)
        return web.json_response(payload, status=status)

    async def metrics_reset(self, request: web.Request) -> web.Response:
        """Clear the TTFT hop sample windows (debug/bench endpoint): per-phase
        quantiles require each phase to start from an empty window, else the
        gauges pool samples from differently-loaded phases. Counters and
        serving stats are untouched."""
        from production_stack_tpu.tracing import (
            get_collector,
            get_flightrecorder,
            reset_phase_histograms,
        )

        _ttft_hops.clear()
        _ttft_hist.reset()
        _latency_hist.reset()
        reset_phase_histograms()
        get_collector().reset()
        get_flightrecorder().reset()
        waits = getattr(self.engine, "admission_wait_ms", None)
        if waits is not None:
            waits.clear()
        return web.json_response({"status": "ok"})

    async def chat_completions(self, request: web.Request) -> web.StreamResponse:
        try:
            body = await request.json()
            messages = body.get("messages", [])
            if not isinstance(messages, list):
                raise ValueError("'messages' must be a list")
            tools, tool_style = self._resolve_tools(body)
        except (ValueError, TypeError) as e:
            return web.json_response({"error": {"message": f"invalid request: {e}"}}, status=400)
        prompt = self.engine.tokenizer.apply_chat_template(messages, tools=tools)
        return await self._generate(
            request, body, prompt, chat=True, tool_style=tool_style
        )

    def _resolve_tools(self, body: dict) -> "tuple[Optional[list], Optional[str]]":
        """(tools to render into the template, parser style or None).

        tool_choice: "none" drops the schemas entirely; a named function
        narrows the rendered schemas to that tool (the strongest steer
        available without constrained decoding); "auto"/"required" render
        all. Reference behavior comes from vLLM's --tool-call-parser flags
        (/root/reference/tutorials/13-tool-enabled-installation.md)."""
        tools = body.get("tools")
        if tools is not None:
            if not isinstance(tools, list):
                raise ValueError("'tools' must be a list")
            for t in tools:
                # validate shape HERE, where ValueError maps to a 400 —
                # malformed entries must not crash template rendering later
                if not (
                    isinstance(t, dict)
                    and isinstance(t.get("function"), dict)
                    and isinstance(t["function"].get("name"), str)
                ):
                    raise ValueError(
                        "each tool must be {'type': 'function', "
                        "'function': {'name': ..., ...}}"
                    )
        for msg in body.get("messages", []):
            for c in (msg.get("tool_calls") or []) if isinstance(msg, dict) else []:
                fn = c.get("function") if isinstance(c, dict) else None
                if not (isinstance(fn, dict) and isinstance(fn.get("name"), str)
                        and isinstance(fn.get("arguments", ""), str)):
                    raise ValueError(
                        "message tool_calls must carry function.name and "
                        "string function.arguments"
                    )
        choice = body.get("tool_choice", "auto" if tools else "none")
        if not tools or choice == "none" or self.cfg.tool_call_parser == "off":
            return None, None
        if isinstance(choice, dict):
            name = (choice.get("function") or {}).get("name")
            named = [
                t for t in tools
                if (t.get("function") or {}).get("name") == name
            ]
            if not named:
                raise ValueError(f"tool_choice names unknown tool {name!r}")
            tools = named
        elif choice not in ("auto", "required"):
            raise ValueError(f"invalid tool_choice {choice!r}")
        return tools, self.cfg.tool_call_parser

    async def completions(self, request: web.Request) -> web.StreamResponse:
        try:
            body = await request.json()
        except (ValueError, TypeError) as e:
            return web.json_response({"error": {"message": f"invalid request: {e}"}}, status=400)
        prompt = body.get("prompt", "")
        if isinstance(prompt, list):
            prompt = prompt[0] if prompt else ""
        return await self._generate(request, body, prompt, chat=False)

    async def _generate(
        self, request: web.Request, body: dict, prompt: str, chat: bool,
        tool_style: Optional[str] = None,
    ) -> web.StreamResponse:
        t_accept = time.perf_counter()
        t_accept_wall = time.time()
        # distributed tracing: adopt the router's traceparent (its sampled
        # flag wins) or root a new trace for engine-direct requests; the
        # engine.request span context parents every per-phase span the
        # engine loop records for this request (docs/tracing.md)
        from production_stack_tpu.tracing import get_collector

        _collector = get_collector()
        trace_ctx = _collector.root_from_headers(request.headers).child()
        if self.draining:
            return web.json_response(
                {"error": {"message": "engine is draining for shutdown"}},
                status=503,
            )
        if self.engine.is_sleeping:
            return web.json_response({"error": "engine is sleeping"}, status=503)
        # per-request SLO class, parsed before the saturation check so the
        # shed watermark is class-aware (batch saturates the interactive
        # reserve early — see scheduler.saturated)
        priority = _request_priority(request.headers, body)
        # admission control: a full waiting queue sheds HERE, before any
        # scheduler state exists for the request — a clean 429 + Retry-After
        # the router can fail over on (duck-typed: fakes/tests may lack it)
        saturated = getattr(self.engine, "saturated", None)
        if saturated is not None and (
            saturated(priority) if self._saturated_accepts_priority
            else saturated()
        ):
            # event-loop-owned counter (the engine thread owns requests_shed;
            # two writers on one dict slot would drop increments)
            if hasattr(self.engine, "api_requests_shed"):
                self.engine.api_requests_shed += 1
            note_shed = getattr(self.engine, "note_api_shed", None)
            if note_shed is not None:
                # flight-recorder shed event + burst trigger + SLO terminal
                # record (no Sequence exists for a fast-path shed)
                try:
                    note_shed(
                        request.headers.get("X-Request-Id"),
                        priority=priority,
                    )
                except TypeError:  # duck-typed engine predating priority
                    note_shed(request.headers.get("X-Request-Id"))
            retry = getattr(self.engine, "shed_retry_after", lambda: 1.0)()
            return _shed_response(
                retry,
                f"engine saturated: {self.engine.scheduler.num_waiting()} "
                "requests already waiting",
            )
        model = body.get("model", self.cfg.name)
        lora_name = None
        if model != self.cfg.name:
            if self.engine.lora is not None and self.engine.lora.is_adapter(model):
                lora_name = model
            else:
                return web.json_response(
                    {"error": {"message": f"model {model!r} does not exist",
                               "type": "NotFoundError", "code": 404}},
                    status=404,
                )
        req_id = request.headers.get("X-Request-Id") or f"req-{uuid.uuid4().hex[:16]}"
        try:
            params = _sampling_params(body, vocab_size=self._vocab_size())
        except (ValueError, TypeError) as e:
            return web.json_response(
                {"error": {"message": f"invalid request: {e}"}}, status=400
            )
        if not (-2.0 <= params.presence_penalty <= 2.0
                and -2.0 <= params.frequency_penalty <= 2.0
                and params.repetition_penalty > 0):
            return web.json_response(
                {"error": {"message": "penalties out of range: presence/frequency in [-2, 2], repetition > 0"}},
                status=400,
            )
        if (
            params.wants_penalties or params.logit_bias or params.min_tokens > 0
        ) and self.cfg.speculative_k:
            return web.json_response(
                {"error": {"message": "sampling penalties, logit_bias, and "
                                      "min_tokens are not supported with "
                                      "speculative decoding"}},
                status=400,
            )
        # logprobs: completions takes an int (top count), chat takes
        # logprobs=true + top_logprobs=N; the chosen token's logprob is
        # always included when enabled
        lp_count = None
        if chat:
            if body.get("logprobs"):
                lp_count = int(body.get("top_logprobs") or 0)
        elif body.get("logprobs") is not None:
            lp_count = int(body["logprobs"])
        if lp_count is not None:
            from production_stack_tpu.ops.sampling import TOP_LOGPROBS

            if not 0 <= lp_count <= TOP_LOGPROBS:
                return web.json_response(
                    {"error": {"message": f"logprobs must be in [0, {TOP_LOGPROBS}]"}},
                    status=400,
                )
            if self.cfg.speculative_k:
                return web.json_response(
                    {"error": {"message": "logprobs are not supported with speculative decoding"}},
                    status=400,
                )
            params.logprobs = lp_count
        stream = bool(body.get("stream", False))
        created = int(time.time())
        kind = "chat.completion" if chat else "text_completion"
        oid = ("chatcmpl-" if chat else "cmpl-") + req_id

        # Tokenize and validate *before* streaming starts — generate() is an
        # async generator, so errors inside it would surface after the 200.
        prompt_ids = self.engine.tokenizer.encode(prompt)
        if len(prompt_ids) + 1 > self.cfg.max_model_len:
            return web.json_response(
                {
                    "error": {
                        "message": (
                            f"prompt has {len(prompt_ids)} tokens, "
                            f"max_model_len is {self.cfg.max_model_len}"
                        )
                    }
                },
                status=400,
            )
        n = 1 if body.get("n") is None else int(body["n"])
        best_of = n if body.get("best_of") is None else int(body["best_of"])
        if not 1 <= n <= 64 or best_of != n:
            return web.json_response(
                {"error": {"message": f"n must be in [1, 64] and best_of == n, got n={n} best_of={best_of}"}},
                status=400,
            )
        # n parallel samples: one engine sequence per choice. Sub-sequences
        # get '#i'-suffixed ids (plain req_id when n == 1 so request tracing
        # and the reference-format routing logs stay stable). Siblings launch
        # AFTER choice 0's prefill completes: the scheduler registers the
        # prompt's pages in the prefix cache at that point, so siblings share
        # the prompt KV instead of re-prefilling it n times.
        sub_ids = [req_id] if n == 1 else [f"{req_id}#{i}" for i in range(n)]
        # register for POST /abort; engine.abort is idempotent, so a stale
        # entry (rare engine-internal error path) only costs dict space.
        # Bound growth by evicting the oldest entry ONLY when it is clearly a
        # leak (hours old) — under legitimate >8k-concurrent load the oldest
        # entry is a live long-running stream whose abortability must survive
        if len(self._live_requests) > 8192:
            oldest = next(iter(self._live_requests))
            if time.monotonic() - self._live_requests[oldest][1] > 3600:
                self._live_requests.pop(oldest)
        self._live_requests[req_id] = (
            sub_ids, time.monotonic(), stream,
            # presentation meta a migration target needs to keep emitting
            # client-shaped chunks (and honest whole-request usage totals);
            # priority rides along so /migratable can class-filter victims
            # and a migrated continuation keeps its SLO class
            {"oid": oid, "chat": chat, "created": created, "model": model,
             "prompt_tokens": len(prompt_ids), "priority": priority},
        )

        def _gen(sid):
            kwargs = dict(
                prompt_token_ids=prompt_ids, params=params, lora_name=lora_name
            )
            # duck-typed engines (tests, fakes) may predate the trace kwarg;
            # they still get the engine.request span, just no phase spans.
            # For n > 1 only choice 0 carries the context: n concurrent
            # sibling phase-span sets under one engine.request would sum past
            # the parent's wall time and corrupt the self-time attribution,
            # so the trace follows one representative sequence
            if self._engine_accepts_trace and sid == sub_ids[0]:
                kwargs["trace"] = trace_ctx
            # parallel-sampling siblings (choice > 0) launch only after
            # choice 0's first output — their request is mid-flight, so they
            # are exempt from engine-side load shedding (choice 0's own shed
            # still 429s the whole request cleanly and aborts them)
            if self._engine_accepts_shed_exempt and sid != sub_ids[0]:
                kwargs["shed_exempt"] = True
            if self._engine_accepts_priority:
                kwargs["priority"] = priority
            return self.engine.generate(sid, **kwargs)

        def _shed_whole_request() -> web.Response:
            """Queue-deadline shed before any output: abort every choice and
            answer 429 + Retry-After for the request as a whole."""
            self._live_requests.pop(req_id, None)
            for sid in sub_ids:
                self.engine.abort(sid)
            return _shed_response(
                getattr(self.engine, "shed_retry_after", lambda: 1.0)(),
                "request shed: queue deadline exceeded before dispatch",
            )

        t_submit = time.perf_counter()
        if n == 1:
            gens = [_gen(sub_ids[0])]
        else:
            prefilled = asyncio.Event()

            async def first(sid):
                try:
                    async for out in _gen(sid):
                        prefilled.set()
                        yield out
                finally:
                    prefilled.set()  # error/abort must not wedge siblings

            async def sibling(sid):
                await prefilled.wait()
                async for out in _gen(sid):
                    yield out

            gens = [first(sub_ids[0])] + [sibling(sid) for sid in sub_ids[1:]]
        gen = gens[0]

        if not stream:
            t_first_box = [None]

            async def collect(i, g):
                text, finish_reason, last = [], None, None
                tok_ids, lp_entries = [], []
                async for out in g:
                    if t_first_box[0] is None:
                        t_first_box[0] = time.perf_counter()
                    text.append(out.text_delta)
                    last = out
                    if out.logprobs is not None:
                        tok_ids.extend(out.token_ids)
                        lp_entries.extend(out.logprobs)
                    if out.finished:
                        finish_reason = out.finish_reason
                return i, "".join(text), finish_reason, last, tok_ids, lp_entries

            try:
                results = await asyncio.gather(
                    *(collect(i, g) for i, g in enumerate(gens))
                )
            except (Exception, asyncio.CancelledError):
                # one failed choice (or a client disconnect) must not leave
                # its n-1 siblings generating — and holding KV pages — until
                # their own completion
                self._live_requests.pop(req_id, None)
                for sid in sub_ids:
                    self.engine.abort(sid)
                raise
            if any(r[2] == "shed" for r in results):
                # the request never produced a token, so a clean 429 +
                # Retry-After is still an honest answer (any non-shed
                # siblings are aborted — the request sheds whole)
                return _shed_whole_request()
            choices, lasts = [], []
            for i, full, finish_reason, last, tok_ids, lp_entries in results:
                lasts.append(last)
                lp_obj = None
                if lp_count is not None:
                    if chat:
                        lp_obj = {"content": _chat_lp_content(
                            self.engine.tokenizer, tok_ids, lp_entries)}
                    else:
                        lp_obj, _ = _completion_lp(
                            self.engine.tokenizer, tok_ids, lp_entries, 0)
                if chat:
                    message = {"role": "assistant", "content": full}
                    if tool_style is not None:
                        from production_stack_tpu.engine.tool_parser import parse_tool_calls

                        content, tool_calls = parse_tool_calls(full, tool_style)
                        if tool_calls:
                            message = {
                                "role": "assistant",
                                "content": content or None,
                                "tool_calls": tool_calls,
                            }
                            if finish_reason == "stop":
                                finish_reason = "tool_calls"
                    choices.append({
                        "index": i,
                        "message": message,
                        "logprobs": lp_obj,
                        "finish_reason": finish_reason,
                    })
                else:
                    choices.append({"index": i, "text": full, "logprobs": lp_obj,
                                    "finish_reason": finish_reason})
            usage = _usage(lasts[0]) if lasts[0] else {}
            if usage and len(lasts) > 1:
                # prompt counted once; completion tokens summed over choices
                usage["completion_tokens"] = sum(
                    (_usage(l) or {}).get("completion_tokens", 0) for l in lasts if l
                )
                usage["total_tokens"] = usage["prompt_tokens"] + usage["completion_tokens"]
            if t_first_box[0] is not None:
                _ttft_hist.observe(t_first_box[0] - t_accept)
            _latency_hist.observe(time.perf_counter() - t_accept)
            _collector.record(
                "engine.request", trace_ctx, t_accept_wall,
                time.perf_counter() - t_accept,
                request_id=req_id, model=model, stream=False, n=n,
            )
            self._live_requests.pop(req_id, None)
            return web.json_response(
                {
                    "id": oid,
                    "object": kind,
                    "created": created,
                    "model": model,
                    "choices": choices,
                    "usage": usage,
                },
                headers={"X-Request-Id": req_id},
            )

        merged = _tag_stream(0, gen) if n == 1 else _merge_streams(gens)
        # queue-deadline shedding: when the engine may still shed queued
        # requests, defer the response headers until the first engine output
        # arrives — a shed then converts to a clean 429 + Retry-After, where
        # committed 200 headers would force the error into the SSE stream.
        # Engines that cannot shed queued work keep the immediate-headers
        # behavior unchanged.
        first_item = None
        if getattr(self.engine, "can_shed_queued", lambda: False)():
            try:
                first_item = await merged.__anext__()
            except StopAsyncIteration:
                first_item = None
            except (Exception, asyncio.CancelledError):
                self._live_requests.pop(req_id, None)
                for sid in sub_ids:
                    self.engine.abort(sid)
                raise
            if (
                first_item is not None
                and first_item[1].finished
                and first_item[1].finish_reason == "shed"
            ):
                await merged.aclose()  # cancel _merge_streams pump tasks now
                return _shed_whole_request()

        async def _chain_first(first, agen):
            if first is not None:
                yield first
            async for item in agen:
                yield item

        resp = web.StreamResponse(
            status=200,
            headers={
                "Content-Type": "text/event-stream",
                "Cache-Control": "no-cache",
                "X-Request-Id": req_id,
            },
        )
        await resp.prepare(request)

        async def send(obj: dict):
            await resp.write(f"data: {json.dumps(obj)}\n\n".encode())

        # chat role chunks are sent lazily with each choice's FIRST engine
        # output (not at request accept): the first streamed bytes must not
        # precede prefill completion, or client-measured TTFT would be ~0
        role_sent = [not chat] * n
        lasts: list = [None] * n
        parsers = tool_idx = None
        if chat and tool_style is not None:
            from production_stack_tpu.engine.tool_parser import StreamingToolParser

            parsers = [StreamingToolParser(tool_style) for _ in range(n)]
            tool_idx = [0] * n
        migrated_away = False
        try:
            lp_offsets = [0] * n
            t_first_out = None
            hop_done = False
            async for i, out in _chain_first(first_item, merged):
                lasts[i] = out
                if (
                    out.finished
                    and out.finish_reason == "migrated"
                    and (mi := self._migrated_out.pop(req_id, None)) is not None
                ):
                    # live migration handoff (docs/migration.md): the
                    # continuation now decodes on the target engine. Emit
                    # the control event the router's splice watches for and
                    # end this leg WITHOUT [DONE] — the router (or an
                    # engine-direct client) attaches to the target's
                    # /migrate_attach for the rest of the stream.
                    await send({"pstpu_migration": mi})
                    migrated_away = True
                    break
                if i == 0 and t_first_out is None:
                    t_first_out = time.perf_counter()
                if not role_sent[i]:
                    role_sent[i] = True
                    await send(
                        {
                            "id": oid, "object": "chat.completion.chunk",
                            "created": created, "model": model,
                            "choices": [{"index": i, "delta": {"role": "assistant"},
                                         "finish_reason": None}],
                        }
                    )
                # emit EVERY engine output (vLLM streams a chunk per step even
                # when the incremental detokenizer held text back as an
                # incomplete UTF-8 sequence): the first chunk is what clients
                # measure TTFT against, and it must track prefill completion,
                # not the first printable character
                lp_obj = None
                if lp_count is not None and out.logprobs is not None:
                    if chat:
                        lp_obj = {"content": _chat_lp_content(
                            self.engine.tokenizer, out.token_ids, out.logprobs)}
                    else:
                        lp_obj, lp_offsets[i] = _completion_lp(
                            self.engine.tokenizer, out.token_ids,
                            out.logprobs, lp_offsets[i])
                if chat:
                    finish_reason = out.finish_reason
                    if parsers is None:
                        deltas = [{"content": out.text_delta} if out.text_delta else {}]
                    else:
                        # split the raw delta into content vs tool-call events;
                        # candidate tool-call text is withheld until it either
                        # completes (a tool_calls delta) or fails to parse at
                        # end-of-stream (flushed back as content)
                        p = parsers[i]
                        events = p.push(out.text_delta or "")
                        if out.finished:
                            events.extend(p.finish())
                            if p.tool_calls and finish_reason == "stop":
                                finish_reason = "tool_calls"
                        deltas = []
                        for ev in events:
                            if ev[0] == "content" and ev[1]:
                                deltas.append({"content": ev[1]})
                            elif ev[0] == "call":
                                deltas.append(
                                    {"tool_calls": [{"index": tool_idx[i], **ev[1]}]}
                                )
                                tool_idx[i] += 1
                        # always emit at least one chunk per engine output:
                        # the first chunk is the client's TTFT signal
                        deltas = deltas or [{}]
                    for j, d in enumerate(deltas):
                        last_d = j == len(deltas) - 1
                        choice = {
                            "index": i,
                            "delta": d,
                            "logprobs": lp_obj if last_d else None,
                            "finish_reason": finish_reason if last_d else None,
                        }
                        await send(
                            {
                                "id": oid, "object": "chat.completion.chunk",
                                "created": created, "model": model, "choices": [choice],
                            }
                        )
                else:
                    await send(
                        {
                            "id": oid, "object": "text_completion", "created": created,
                            "model": model,
                            "choices": [
                                {
                                    "index": i, "text": out.text_delta,
                                    "logprobs": lp_obj,
                                    "finish_reason": out.finish_reason,
                                }
                            ],
                        }
                    )
                if i == 0 and t_first_out is not None and not hop_done:
                    hop_done = True
                    _ttft_hops.append((
                        (t_submit - t_accept) * 1000,
                        (t_first_out - t_submit) * 1000,
                        (time.perf_counter() - t_first_out) * 1000,
                    ))
                    _ttft_hist.observe(t_first_out - t_accept)
            if lasts[0] is not None and not migrated_away:
                usage = _usage(lasts[0])
                if n > 1:
                    usage["completion_tokens"] = sum(
                        (_usage(l) or {}).get("completion_tokens", 0) for l in lasts if l
                    )
                    usage["total_tokens"] = usage["prompt_tokens"] + usage["completion_tokens"]
                await send(
                    {
                        "id": oid, "object": f"{kind}.chunk" if chat else kind,
                        "created": created, "model": model, "choices": [],
                        "usage": usage,
                    }
                )
            if not migrated_away:
                await resp.write(b"data: [DONE]\n\n")
        except (ConnectionResetError, asyncio.CancelledError):
            self._live_requests.pop(req_id, None)
            self._migrated_out.pop(req_id, None)
            for sid in sub_ids:
                self.engine.abort(sid)
            raise
        self._live_requests.pop(req_id, None)
        self._migrated_out.pop(req_id, None)
        _latency_hist.observe(time.perf_counter() - t_accept)
        _collector.record(
            "engine.request", trace_ctx, t_accept_wall,
            time.perf_counter() - t_accept,
            request_id=req_id, model=model, stream=True, n=n,
        )
        await resp.write_eof()
        return resp

    def _check_pooling_model(self, body: dict):
        """404/400 for unknown or adapter model names on the pooling endpoints
        (embeddings run the base weights only)."""
        model = body.get("model", self.cfg.name)
        if model == self.cfg.name:
            return None
        if self.engine.lora is not None and self.engine.lora.is_adapter(model):
            return web.json_response(
                {"error": {"message": f"model {model!r} is a LoRA adapter; "
                                      "pooling endpoints serve the base model"}},
                status=400,
            )
        return web.json_response(
            {"error": {"message": f"model {model!r} does not exist",
                       "type": "NotFoundError", "code": 404}},
            status=404,
        )

    def _tokenize_inputs(self, raw) -> list[list[int]]:
        """OpenAI `input` field: str | [str] | [int] | [[int]] -> token lists."""
        if isinstance(raw, str):
            raw = [raw]
        if not isinstance(raw, list):
            raise ValueError("'input' must be a string or a list")
        if raw and isinstance(raw[0], int):
            raw = [raw]
        out = []
        for item in raw:
            if isinstance(item, str):
                out.append(self.engine.tokenizer.encode(item))
            elif isinstance(item, list):
                out.append([int(t) for t in item])
            else:
                raise ValueError(
                    "'input' items must be strings or token-id lists"
                )
        return out

    async def embeddings(self, request: web.Request) -> web.Response:
        """OpenAI-compatible /v1/embeddings: mean-pooled, L2-normalized last
        hidden states (surface parity with the router passthrough endpoint,
        routers/main_router.py in /root/reference)."""
        if self.draining:
            return web.json_response(
                {"error": {"message": "engine is draining for shutdown"}},
                status=503,
            )
        try:
            body = await request.json()
            inputs = self._tokenize_inputs(body.get("input", []))
        except (ValueError, TypeError) as e:
            return web.json_response({"error": {"message": f"invalid request: {e}"}}, status=400)
        err = self._check_pooling_model(body)
        if err is not None:
            return err
        if not inputs:
            return web.json_response({"error": {"message": "'input' is required"}}, status=400)
        try:
            vecs = await self.engine.embed(inputs)
        except (ValueError, RuntimeError) as e:
            return web.json_response({"error": {"message": str(e)}}, status=400)
        total = sum(len(i) for i in inputs)
        return web.json_response(
            {
                "object": "list",
                "model": body.get("model", self.cfg.name),
                "data": [
                    {"object": "embedding", "index": i, "embedding": v.tolist()}
                    for i, v in enumerate(vecs)
                ],
                "usage": {"prompt_tokens": total, "total_tokens": total},
            }
        )

    async def rerank(self, request: web.Request) -> web.Response:
        """/v1/rerank: order documents by cosine relevance to the query."""
        if self.draining:
            return web.json_response(
                {"error": {"message": "engine is draining for shutdown"}},
                status=503,
            )
        try:
            body = await request.json()
            query = body["query"]
            documents = list(body["documents"])
            top_n = max(0, int(body.get("top_n", len(documents))))
        except (KeyError, ValueError, TypeError) as e:
            return web.json_response(
                {"error": {"message": f"invalid request (need query, documents): {e}"}},
                status=400,
            )
        err = self._check_pooling_model(body)
        if err is not None:
            return err
        if not documents:
            return web.json_response({"error": {"message": "'documents' is empty"}}, status=400)
        try:
            vecs = await self.engine.embed(self._tokenize_inputs([query] + documents))
        except (ValueError, RuntimeError) as e:
            return web.json_response({"error": {"message": str(e)}}, status=400)
        scores = vecs[1:] @ vecs[0]
        order = sorted(range(len(documents)), key=lambda i: -float(scores[i]))[:top_n]
        return web.json_response(
            {
                "id": f"rerank-{uuid.uuid4().hex[:16]}",
                "model": body.get("model", self.cfg.name),
                "results": [
                    {
                        "index": i,
                        "document": {"text": documents[i]},
                        "relevance_score": float(scores[i]),
                    }
                    for i in order
                ],
            }
        )

    async def score(self, request: web.Request) -> web.Response:
        """/v1/score: cosine similarity for (text_1, text_2) pairs."""
        if self.draining:
            return web.json_response(
                {"error": {"message": "engine is draining for shutdown"}},
                status=503,
            )
        try:
            body = await request.json()
            t1, t2 = body["text_1"], body["text_2"]
        except (KeyError, ValueError, TypeError) as e:
            return web.json_response(
                {"error": {"message": f"invalid request (need text_1, text_2): {e}"}},
                status=400,
            )
        err = self._check_pooling_model(body)
        if err is not None:
            return err
        def as_items(x):
            """str -> [str]; [int,...] -> [[int,...]]; [str|list,...] -> itself."""
            if isinstance(x, str):
                return [x]
            if isinstance(x, list) and x and isinstance(x[0], int):
                return [x]
            if isinstance(x, list):
                return x
            raise TypeError("text fields must be strings or token-id lists")

        try:
            left = as_items(t1)
            right = as_items(t2)
        except TypeError as e:
            return web.json_response({"error": {"message": str(e)}}, status=400)
        if len(left) == 1:
            left = left * len(right)
        if len(left) != len(right):
            return web.json_response(
                {"error": {"message": "text_1 and text_2 lengths do not match"}},
                status=400,
            )
        try:
            inputs = self._tokenize_inputs(left + right)
            vecs = await self.engine.embed(inputs)
        except (ValueError, RuntimeError) as e:
            return web.json_response({"error": {"message": str(e)}}, status=400)
        n = len(left)
        return web.json_response(
            {
                "id": f"score-{uuid.uuid4().hex[:16]}",
                "object": "list",
                "model": body.get("model", self.cfg.name),
                "data": [
                    {"index": i, "object": "score",
                     "score": float(vecs[i] @ vecs[n + i])}
                    for i in range(n)
                ],
                "usage": {"prompt_tokens": sum(len(i) for i in inputs)},
            }
        )

    async def sleep(self, request: web.Request) -> web.Response:
        if not self.cfg.enable_sleep_mode:
            return web.json_response({"error": "sleep mode disabled"}, status=400)
        try:
            level = int(request.query.get("level", "1"))
            # executor: sleep waits for the device thread (an in-flight step
            # must drain first) — the event loop must keep serving probes
            await asyncio.get_running_loop().run_in_executor(
                None, self.engine.sleep, level
            )
        except ValueError as e:  # bad level param
            return web.json_response({"error": str(e)}, status=400)
        return web.Response(text="")

    async def wake_up(self, request: web.Request) -> web.Response:
        if not self.cfg.enable_sleep_mode:
            return web.json_response({"error": "sleep mode disabled"}, status=400)
        await asyncio.get_running_loop().run_in_executor(
            None, self.engine.wake_up
        )
        return web.Response(text="")

    async def is_sleeping(self, request: web.Request) -> web.Response:
        return web.json_response({"is_sleeping": self.engine.is_sleeping})

    async def load_lora_adapter(self, request: web.Request) -> web.Response:
        """Contract parity: the reference LoraAdapter controller POSTs
        {lora_name, lora_path} here (loraadapter_controller.go:586-601)."""
        body = await request.json()
        name, path = body.get("lora_name"), body.get("lora_path")
        if not name or not path:
            return web.json_response(
                {"error": "lora_name and lora_path are required"}, status=400
            )
        try:
            slot = await asyncio.get_running_loop().run_in_executor(
                None, self.engine.load_lora_adapter, name, path
            )
        except ValueError as e:
            return web.json_response({"error": str(e)}, status=400)
        return web.json_response({"status": "success", "lora_name": name, "slot": slot})

    async def unload_lora_adapter(self, request: web.Request) -> web.Response:
        body = await request.json()
        name = body.get("lora_name")
        if not name:
            return web.json_response({"error": "lora_name is required"}, status=400)
        try:
            await asyncio.get_running_loop().run_in_executor(
                None, self.engine.unload_lora_adapter, name
            )
        except ValueError as e:
            return web.json_response({"error": str(e)}, status=400)
        return web.json_response({"status": "success", "lora_name": name})

    # -- app ---------------------------------------------------------------

    def build_app(self) -> web.Application:
        # client_max_size: aiohttp's 1 MiB default would reject /migrate_in
        # snapshots for long-context sequences (a 128k-token stream's token
        # list alone is ~1 MB) — exactly the long streams migration exists
        # to protect. 64 MiB bounds a ~1M-token snapshot.
        app = web.Application(client_max_size=64 << 20)
        r = app.router
        r.add_get("/health", self.health)
        r.add_get("/ping", self.health)
        r.add_get("/version", self.version)
        r.add_get("/v1/models", self.models)
        r.add_get("/metrics", self.metrics)
        r.add_get("/stats", self.stats)
        # SLO terminal records: an intra-cluster read-only surface like
        # /stats (the router's scraper consumes it in production, so it is
        # NOT debug-gated; it carries request ids and timings, no content)
        r.add_get("/slo_records", self.slo_records)
        if self.cfg.enable_debug_endpoints:
            # unauthenticated debug surfaces — benchmark/debug runs only.
            # /v1/traces is read-only but exposes request ids and timings;
            # wiping the hop-quantile sample windows (/metrics/reset)
            # corrupts live observability, so production servers register
            # neither. The flight recorder additionally exposes scheduler
            # internals, so it rides the same gate.
            r.add_get("/v1/traces", self.traces)
            r.add_get("/v1/debug/flightrecorder", self.flightrecorder)
            r.add_post("/metrics/reset", self.metrics_reset)
            # the device profiler writes wherever the caller says: debug only
            r.add_post("/v1/debug/profile/start", self.profile_start)
            r.add_post("/v1/debug/profile/stop", self.profile_stop)
        r.add_post("/abort", self.abort)
        # live sequence migration (docs/migration.md): registered even when
        # --no-migration (handlers answer 501) so the wire surface — and the
        # GC005 fake-engine parity contract — stays stable
        r.add_get("/migratable", self.migratable)
        # KV fabric discovery (docs/kv-fabric.md): peers resolve this
        # engine's fabric listener here (--kv-fabric-port 0 binds an
        # ephemeral port, so config alone cannot name it). Registered even
        # when the fabric is off (answers enabled:false) so the surface —
        # and the fake-engine parity contract — stays stable.
        r.add_get("/kv_fabric", self.kv_fabric_info)
        r.add_post("/migrate_out", self.migrate_out)
        r.add_post("/migrate_in", self.migrate_in)
        r.add_post("/migrate_attach", self.migrate_attach)
        r.add_post("/tokenize", self.tokenize)
        r.add_post("/detokenize", self.detokenize)
        r.add_post("/v1/chat/completions", self.chat_completions)
        r.add_post("/v1/completions", self.completions)
        r.add_post("/v1/embeddings", self.embeddings)
        r.add_post("/v1/rerank", self.rerank)
        r.add_post("/v2/rerank", self.rerank)
        r.add_post("/v1/score", self.score)
        r.add_post("/sleep", self.sleep)
        r.add_post("/wake_up", self.wake_up)
        r.add_get("/is_sleeping", self.is_sleeping)
        r.add_post("/v1/load_lora_adapter", self.load_lora_adapter)
        r.add_post("/v1/unload_lora_adapter", self.unload_lora_adapter)
        app.on_cleanup.append(self._close_mig_client)
        return app


def _resolve_process_id(cfg: EngineConfig) -> int:
    """Process id for multi-host serving: explicit flag, else JAX_PROCESS_ID,
    else the StatefulSet hostname ordinal (``engine-llama3-2`` -> 2)."""
    import os
    import socket as socket_mod

    if cfg.distributed_process_id is not None:
        return int(cfg.distributed_process_id)
    if os.environ.get("JAX_PROCESS_ID"):
        return int(os.environ["JAX_PROCESS_ID"])
    host = socket_mod.gethostname()
    tail = host.rsplit("-", 1)[-1]
    if not tail.isdigit():
        raise ValueError(
            f"cannot derive process id from hostname {host!r}; set "
            "--distributed-process-id or JAX_PROCESS_ID"
        )
    return int(tail)


def _init_multihost(cfg: EngineConfig) -> int:
    """Rendezvous the JAX multi-controller runtime (the reference's Ray
    cluster + EXPECTED_NODES barrier, ray-cluster.yaml:46-47 — replaced by
    jax.distributed's coordination service). Returns this process's id."""
    import jax

    if not cfg.distributed_coordinator:
        raise ValueError(
            "--distributed-num-processes > 1 requires --distributed-coordinator"
        )
    # KV offload tiers work multi-host: get_page is a REPLICATED dispatch
    # that gathers the page fully-replicated (SPMD) so the leader's host
    # fetch sees the whole page; set_page restores broadcast the bytes back.
    # The tiers/controller/cache-server connections are leader-only
    # (followers get them disabled in serve()).
    # sleep mode works multi-host at BOTH levels: drop_kv_pools/reset_kv
    # and offload_params/restore_params are replicated dispatches — each
    # process offloads its own param shards to its own host RAM and
    # re-materializes them on wake.
    # LoRA works multi-host: the leader parses adapter checkpoints and the
    # resulting set_lora_slot/clear_lora_slot device writes are REPLICATED
    # dispatches — followers receive the weights over the step stream, so
    # adapters need no shared filesystem.
    # Disaggregated prefill works multi-host on BOTH paths: the TCP path's
    # page fetches (get_page) and restores (set_page) are REPLICATED SPMD
    # dispatches with the sender/receiver leader-only; the device-to-device
    # path runs a transfer endpoint per process (runner.kv_endpoint_start,
    # armed by engine.enable_multihost_device_kv after the broadcaster is
    # wired) so pages move shard-cluster to shard-cluster over DCN with no
    # host serde — the NIXL GPU-direct analogue.
    pid = _resolve_process_id(cfg)
    logger.info(
        "multi-host init: process %d/%d, coordinator %s",
        pid, cfg.distributed_num_processes, cfg.distributed_coordinator,
    )
    jax.distributed.initialize(
        coordinator_address=cfg.distributed_coordinator,
        num_processes=cfg.distributed_num_processes,
        process_id=pid,
    )
    return pid


async def serve(cfg: EngineConfig, engine: Optional[LLMEngine] = None):
    if cfg.distributed_num_processes > 1 and engine is None:
        from production_stack_tpu.engine.distributed import (
            BroadcastingRunner,
            StepBroadcaster,
            follower_loop,
        )

        pid = _init_multihost(cfg)
        if pid != 0:
            # follower: identical RUNNER construction (same model, mesh,
            # pools, seed), then replay the leader's device dispatches
            # forever. Host-side KV tiers / controller / remote-cache
            # connections are leader-only — a follower building them would
            # double-register with the KV index controller and waste host
            # RAM on a tier nothing reads. This call BLOCKS until the
            # leader shuts down.
            import dataclasses as _dc

            engine = LLMEngine(_dc.replace(
                cfg, kv_offload_cpu_gb=0.0, kv_offload_dir=None,
                kv_remote_url=None, kv_controller_url=None,
                kv_role="none",
            ))
            leader_host = cfg.distributed_coordinator.rsplit(":", 1)[0]
            await asyncio.get_event_loop().run_in_executor(
                None,
                follower_loop,
                engine.runner,
                leader_host,
                cfg.worker_sync_port,
            )
            raise SystemExit(0)
        engine = LLMEngine(cfg)
        bc = StepBroadcaster(
            cfg.worker_sync_port, cfg.distributed_num_processes - 1
        )
        engine.runner = BroadcastingRunner(engine.runner, bc)
        if engine.lora is not None:
            # LoRAManager captured the raw runner at engine construction;
            # re-point it at the wrapper or set_lora_slot/clear_lora_slot
            # would bypass replication and followers would keep zero slots
            engine.lora.runner = engine.runner
        if engine._offload is not None:
            # same capture pattern: the offload connector's get_page/set_page
            # must go through the broadcaster or followers desync on the
            # SPMD page-gather program
            engine._offload.runner = engine.runner
        if cfg.kv_role != "none" and cfg.kv_transfer_device:
            # device-to-device KV across hosts: per-process endpoints +
            # replicated offer/pull/restore dispatches (must come after the
            # BroadcastingRunner wrap so followers mirror every step)
            engine.enable_multihost_device_kv()
    from production_stack_tpu.tracing import configure_tracing

    configure_tracing(
        sample_rate=cfg.trace_sample_rate, capacity=cfg.trace_buffer_size
    )
    server = EngineServer(cfg, engine)
    server.engine.start()
    app = server.build_app()
    runner = web.AppRunner(app)
    await runner.setup()
    site = web.TCPSite(runner, cfg.host, cfg.port)
    await site.start()
    logger.info("engine API listening on %s:%d (model=%s)", cfg.host, cfg.port, cfg.name)
    return server, runner


def main():
    import os as os_mod

    from production_stack_tpu.utils.signals import wait_for_termination

    p = argparse.ArgumentParser("tpu-engine")
    add_engine_args(p)
    args = p.parse_args()
    cfg = config_from_args(args)

    async def _run():
        server, runner = await serve(cfg)
        await wait_for_termination()
        # K8s pod rotation: SIGTERM -> refuse new work + flip /health to 503
        # (readiness pulls the pod from rotation) -> let in-flight requests
        # finish -> clean shutdown, all inside terminationGracePeriodSeconds
        await server.drain(float(os_mod.environ.get("PSTPU_DRAIN_TIMEOUT", "30")))
        try:
            await asyncio.wait_for(runner.cleanup(), 15)
        except Exception:  # noqa: BLE001 - best-effort teardown
            pass
        server.engine.stop()
        logger.info("engine shut down cleanly")

    asyncio.run(_run())


if __name__ == "__main__":
    main()
