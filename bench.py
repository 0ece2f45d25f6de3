"""Benchmark entry point (driver-run, real TPU).

Primary metric (round 4+): p50 TTFT of the multi-round-qa workload driven
through the FULL serving stack — streaming HTTP client -> router -> engine
API server -> LLMEngine — the reference's canonical benchmark
(/root/reference/benchmarks/multi-round-qa/run.sh, multi-round-qa.py), scaled
to one chip (14 users x 5 rounds, ~1k-token shared system prompt,
~8.6k-token per-user histories, 100-token answers, CPU offload tier live). The north star (BASELINE.json) is Llama-3-8B < 200 ms p50 TTFT on
v5e-8 (8 chips) via the router; 1B on 1 chip carries the same per-chip
FLOP/byte load, so ``vs_baseline = 200 / qa_p50_ttft_ms`` (>1.0 beats the
target). Extras carry the rest of BASELINE.json's metric triple (QA
tokens/sec/chip, KV-cache hit rate) plus the engine-level micro benches
(prefill TTFT, decode tok/s/chip, 16k/32k long-context) and per-phase TTFT
hop breakdowns.
"""

from __future__ import annotations

import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(
    0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "scripts")
)
import trace_report  # noqa: E402  (scripts/trace_report.py)


def main() -> None:
    import dataclasses

    from production_stack_tpu.engine.config import EngineConfig
    from production_stack_tpu.engine.runner import ModelRunner, StepInput
    from production_stack_tpu.models import llama
    from production_stack_tpu.utils.compile_cache import enable_persistent_cache

    # repo-local persistent cache: repeat bench runs (and the serving phase's
    # many (batch, pages)-bucket programs) compile once per machine, not once
    # per invocation
    enable_persistent_cache(
        os.path.join(os.path.dirname(os.path.abspath(__file__)), ".cache", "xla")
    )

    platform = jax.default_backend()
    if platform != "tpu":
        # a CPU run must never print the device metrics' names
        print(
            f"bench.py measures the chip; JAX found platform={platform!r}. "
            "Run it through the chip tool (tests cover the CPU).",
            file=sys.stderr,
        )
        sys.exit(2)
    # the off-TPU sizes further down are unreachable now; ROADMAP S0
    # replaces this script rather than this PR pruning it
    on_tpu = True
    # PSTPU_BENCH_MODEL_DIR: a local HF directory (safetensors + tokenizer)
    # benches REAL weights through the production loader; default is the
    # flagship preset with random weights (hermetic environments)
    model_dir = os.environ.get("PSTPU_BENCH_MODEL_DIR")
    runner_kw = {}
    long_targets = []
    if model_dir:
        from production_stack_tpu.engine.model_loader import load_model

        mod, cfg, params = load_model(model_dir)
        runner_kw = {"params": params, "module": mod}
        model_desc = f"{model_dir} (real weights)"
        prefill_len, decode_batch, ctx_pages, page_size = 1024, 16, 16, 64
        if not on_tpu:
            prefill_len, decode_batch, ctx_pages, page_size = 64, 4, 8, 8
        # respect the checkpoint's context limit: positions past a short
        # position table clamp silently and would bench garbage
        prefill_len = min(prefill_len, (cfg.max_model_len - 1) // page_size * page_size)
        ctx_pages = min(ctx_pages, (cfg.max_model_len - 1) // page_size)
        long_targets = [
            t for t in (16384, 32768) if t + 1 <= cfg.max_model_len
        ]
    else:
        # max_model_len=32768 (values-17-kv-aware parity): the long-context
        # phase proves 16k/32k chunked prefill + decode on the real chip
        cfg = dataclasses.replace(
            llama.PRESETS["llama-3.2-1b"], max_model_len=32768
        )
        model_desc = "llama-3.2-1b-class (random weights)"
        # decode at the SERVING operating point (32 seats, the stack phase's
        # max_num_seqs) — per-step cost is mostly batch-independent, so
        # tokens/sec/chip scales with B until HBM pressure
        prefill_len, decode_batch, ctx_pages = 1024, 32, 16  # 1k contexts
        page_size = 64
        long_targets = [16384, 32768]
    # pool sized for BOTH the decode phase (decode_batch rows of ctx_pages)
    # and the long-context phase (one sequence of up to 32k tokens + a
    # decode-step page of headroom)
    lc_pages_max = max(
        [ctx_pages] + [t // page_size + 2 for t in long_targets]
    )
    num_pages = decode_batch * ctx_pages + lc_pages_max

    runner = ModelRunner(
        cfg, num_pages=num_pages, page_size=page_size, seed=0, **runner_kw
    )
    rng = np.random.RandomState(0)

    # --- TTFT: single-request prefill of `prefill_len` tokens + sample ---
    max_pages = prefill_len // page_size
    ttft_inp = StepInput(
        input_ids=rng.randint(0, cfg.vocab_size, (1, prefill_len)),
        positions=np.arange(prefill_len)[None],
        page_table=np.arange(max_pages)[None] + decode_batch * ctx_pages,
        kv_lens=np.full((1,), prefill_len),
        temperature=np.zeros(1),
        top_k=np.zeros(1, int),
        top_p=np.ones(1),
    )
    # Three warmups: the first compiles; the next absorb the one-time relayout
    # after the donated KV pool is first returned by the program. Fetch to host
    # (np.asarray): the host copy is what a served token pays for, and it
    # keeps the compile out of the first timed iteration.
    for _ in range(3):
        ids, _ = runner.step(ttft_inp)
        np.asarray(ids)
    ttfts = []
    for _ in range(20):
        t0 = time.perf_counter()
        ids, _ = runner.step(ttft_inp)
        np.asarray(ids)  # TTFT ends when the host holds the first token
        ttfts.append((time.perf_counter() - t0) * 1000)
    p50_ttft = float(np.percentile(ttfts, 50))
    p99_ttft = float(np.percentile(ttfts, 99))

    # --- decode throughput: sequences at ~1k context, at the serving batch
    # (decode_batch) and at B=16 for cross-round comparability ---
    k = EngineConfig().decode_steps  # fused burst length, as LLMEngine serves
    # leave k KV slots of headroom so the burst never writes past the pages
    # each row owns
    ctx = ctx_pages * page_size - k - 1
    decode_points = {}
    for B in sorted({min(16, decode_batch), decode_batch}):
        pt = np.arange(B * ctx_pages).reshape(B, ctx_pages)
        dec = StepInput(
            input_ids=rng.randint(0, cfg.vocab_size, (B, 1)),
            positions=np.full((B, 1), ctx),
            page_table=pt,
            kv_lens=np.full((B,), ctx + 1),
            temperature=np.full(B, 0.7),
            top_k=np.full(B, 40),
            top_p=np.full(B, 0.95),
        )
        # engine decode path: fused multi-step bursts — one dispatch yields
        # k tokens/seq, amortizing host<->device round trips exactly as
        # LLMEngine serves
        for _ in range(2):  # compile, then post-donation relayout (see above)
            toks = runner.step_multi(dec, k)
            np.asarray(toks)  # host fetch, as in the timed loop
        bursts = 16
        t0 = time.perf_counter()
        for _ in range(bursts):
            toks = runner.step_multi(dec, k)
        np.asarray(toks)
        dt = time.perf_counter() - t0
        decode_points[B] = B * k * bursts / dt
    B = decode_batch
    decode_tps = decode_points[B]

    # --- long context (values-17 parity, 32k max_model_len): chunked prefill
    # of one 16k then 32k sequence in engine-style 1k chunks, plus a decode
    # burst at >=16k context (the "multi-round turn on a long history" shape).
    # Throughput counts the WHOLE sequence against wall time, chunks
    # dispatched back-to-back with one final fetch (fetch-per-chunk would
    # bill ~100 ms RTT per chunk for compute that runs async anyway).
    lc_metrics = {}
    lc_base = decode_batch * ctx_pages  # pool region after the decode rows
    for long_ctx in long_targets:
        if num_pages * page_size < long_ctx + page_size:
            continue
        chunk = prefill_len  # 1024: same chunk bucket phase 1 compiled
        n_chunks = long_ctx // chunk
        long_ctx = n_chunks * chunk  # bill exactly what runs
        lc_pages = long_ctx // page_size + 1
        lc_ids = rng.randint(0, cfg.vocab_size, (1, long_ctx))
        pt_lc = (np.arange(lc_pages) + lc_base)[None, :]

        def run_long_prefill():
            for c in range(n_chunks):
                ids, _ = runner.step(StepInput(
                    input_ids=lc_ids[:, c * chunk:(c + 1) * chunk],
                    positions=np.arange(c * chunk, (c + 1) * chunk)[None],
                    page_table=pt_lc,
                    kv_lens=np.full((1,), (c + 1) * chunk),
                    temperature=np.zeros(1),
                    top_k=np.zeros(1, int),
                    top_p=np.ones(1),
                ))
            np.asarray(ids)

        run_long_prefill()  # compile the (1, chunk, pages-bucket) variant
        t0 = time.perf_counter()
        run_long_prefill()
        dt = time.perf_counter() - t0
        tag = f"{long_ctx // 1024}k"
        lc_metrics[f"prefill_{tag}_ms"] = round(dt * 1000, 2)
        lc_metrics[f"prefill_{tag}_tokens_per_sec"] = round(long_ctx / dt, 1)

        # decode burst on the fresh long history: one user's next turn
        # (skipped when the burst would step past the rope table, e.g. a
        # full-32k prefill at max_model_len=32768)
        if long_ctx + k >= cfg.max_model_len:
            continue
        lc_dec = StepInput(
            input_ids=rng.randint(0, cfg.vocab_size, (1, 1)),
            positions=np.full((1, 1), long_ctx),
            page_table=pt_lc,
            kv_lens=np.full((1,), long_ctx + 1),
            temperature=np.full(1, 0.7),
            top_k=np.full(1, 40),
            top_p=np.full(1, 0.95),
        )
        for _ in range(2):
            np.asarray(runner.step_multi(lc_dec, k))
        reps = 4
        t0 = time.perf_counter()
        for _ in range(reps):
            lc_toks = runner.step_multi(lc_dec, k)
        np.asarray(lc_toks)
        lc_metrics[f"decode_at_{tag}_tokens_per_sec"] = round(
            k * reps / (time.perf_counter() - t0), 1
        )

    # flat-scaling headline for the ragged prefill kernel: 32k tok/s over
    # 16k tok/s. >= 1.0 means cost per token stopped growing with context
    # (BENCH_r05 measured 0.73 on the XLA path — the number ISSUE 6 chases)
    if (
        "prefill_16k_tokens_per_sec" in lc_metrics
        and "prefill_32k_tokens_per_sec" in lc_metrics
    ):
        lc_metrics["prefill_scaling_ratio"] = round(
            lc_metrics["prefill_32k_tokens_per_sec"]
            / max(lc_metrics["prefill_16k_tokens_per_sec"], 1e-9),
            3,
        )

    # free phase-1 device buffers before the serving stack allocates its own
    del runner, dec, ttft_inp, ids, toks
    import gc

    gc.collect()

    # --- quantized KV contrast (ISSUE 14): the same long-context decode
    # with kv_cache_dtype=int8 — the kernel streams HALF the HBM bytes per
    # step — plus the recorded quality delta: greedy token-match rate vs
    # the fp pool on the same prompt (acceptance wants >= 0.99). Runs AFTER
    # the phase-1 runner is freed (it builds two fresh runners of its own —
    # double model residency would thrash HBM, same reason
    # tp_engine_metrics runs here). Fail-soft like the serving phases;
    # artifacts predating this phase simply lack the keys and
    # update_bench_docs renders the row conditionally.
    try:
        lc_metrics.update(kv_quant_metrics(
            cfg, runner_kw, page_size, prefill_len, long_targets, k,
            np.random.RandomState(7),
        ))
    except Exception as e:  # noqa: BLE001 - record, keep benching
        lc_metrics["kv_quant_error"] = repr(e)

    # --- KV fabric loopback (ISSUE 16): push/pull throughput of the
    # engine-to-engine transfer plane over a real listener — host-side
    # only (no device), so it measures the wire + framing cost the disagg
    # stream and migration ship pay per page. Fail-soft like the rest.
    try:
        lc_metrics.update(kv_fabric_metrics(page_size))
    except Exception as e:  # noqa: BLE001 - record, keep benching
        lc_metrics["kv_fabric_error"] = repr(e)

    extras = {
        # pool dtype of the phase-1/serving engines (the quantized contrast
        # rides its own kv_quant_* / *_int8 keys)
        "kv_cache_dtype": "auto",
        "p50_ttft_ms_1k_prefill": round(p50_ttft, 2),
        "p99_ttft_ms_1k_prefill": round(p99_ttft, 2),
        "decode_tokens_per_sec_per_chip": round(decode_tps, 1),
        "decode_batch": B,
        "decode_context": ctx + 1,
        "decode_tokens_per_sec_by_batch": {
            str(b): round(v, 1) for b, v in decode_points.items()
        },
        "platform": platform,
        "model": model_desc,
    }
    extras.update(lc_metrics)
    extras.update(http_stack_metrics(on_tpu, model_dir))
    extras.update(tp_engine_metrics(on_tpu))

    qa_p50 = extras.get("qa_p50_ttft_ms")
    if qa_p50:
        primary = {
            "metric": "multi_round_qa_p50_ttft_ms_via_router_1chip",
            "value": qa_p50,
            "unit": "ms",
            "vs_baseline": round(200.0 / qa_p50, 3),
            "extras": extras,
        }
    else:
        # fail-soft: the QA phase could not run (error recorded in extras);
        # fall back to the engine-level prefill TTFT so the line still prints
        primary = {
            "metric": "p50_ttft_ms_1k_prefill_flagship_1chip",
            "value": round(p50_ttft, 2),
            "unit": "ms",
            "vs_baseline": round(200.0 / p50_ttft, 3),
            "extras": extras,
        }
    emit_primary(primary)
    if extras.get("qa_dispersion_gate_failed"):
        # the dispersion gate is a HARD failure: a headline whose reps
        # disagree beyond the docs-guard tolerance is not citable, and a
        # green exit would let it into BENCH_DETAILS/docs unchallenged.
        # Results are already emitted above for debugging the spread.
        print(
            "FAIL: qa p50 TTFT rep dispersion "
            f"{extras.get('qa_p50_dispersion_max')} exceeds tolerance "
            f"{extras.get('qa_dispersion_tolerance')} — rerun; do not cite",
            flush=True,
        )
        raise SystemExit(1)


def kv_quant_metrics(
    cfg, runner_kw, page_size, prefill_len, long_targets, k, rng
) -> dict:
    """Quantized-KV contrast phase (ISSUE 14): chunk-prefill one long
    prompt, then run CHAINED greedy decode bursts on it twice — fp pools vs
    ``kv_cache_dtype=int8`` — and record throughput for both plus the
    greedy token-match rate between the two continuations (the quality
    delta the acceptance bound reads; the engines share weights, seed, and
    prompt, so any divergence is quantization error flipping a greedy
    near-tie). Keys: ``decode_at_<tag>_tokens_per_sec_int8``,
    ``decode_at_<tag>_tokens_per_sec_fp_contrast``,
    ``kv_quant_decode_speedup``, ``kv_quant_token_match_rate``,
    ``kv_quant_context``."""
    import dataclasses

    from production_stack_tpu.engine.runner import ModelRunner, StepInput

    if not any(f.name == "kv_cache_dtype" for f in dataclasses.fields(cfg)):
        return {}
    ctxs = [t for t in long_targets if t + k + 1 < cfg.max_model_len]
    # CPU/debug fallback: a small context still proves the path end-to-end
    target = max(ctxs) if ctxs else min(
        128, (cfg.max_model_len - 2 * k - 2) // page_size * page_size
    )
    if target < page_size:
        return {}
    chunk = min(prefill_len, target)
    n_chunks = max(target // chunk, 1)
    target = n_chunks * chunk
    bursts = 4
    pages = (target + bursts * k) // page_size + 2
    ids = rng.randint(0, cfg.vocab_size, (1, target))
    out = {}
    toks_by = {}
    tps_by = {}
    for name in ("fp", "int8"):
        c = cfg if name == "fp" else dataclasses.replace(
            cfg, kv_cache_dtype="int8"
        )
        r = ModelRunner(c, num_pages=pages, page_size=page_size, seed=0,
                        **runner_kw)
        pt = np.arange(pages)[None, :]
        for ci in range(n_chunks):
            pids, _ = r.step(StepInput(
                input_ids=ids[:, ci * chunk:(ci + 1) * chunk],
                positions=np.arange(ci * chunk, (ci + 1) * chunk)[None],
                page_table=pt,
                kv_lens=np.full((1,), (ci + 1) * chunk),
                temperature=np.zeros(1),
                top_k=np.zeros(1, int),
                top_p=np.ones(1),
            ))
        dec = StepInput(
            input_ids=np.asarray(pids)[:, None],
            positions=np.full((1, 1), target),
            page_table=pt,
            kv_lens=np.full((1,), target + 1),
            temperature=np.zeros(1),      # greedy: the match is meaningful
            top_k=np.zeros(1, int),
            top_p=np.ones(1),
            kv_limits=np.full((1,), target + bursts * k + 1),
        )
        chained = lambda: [
            np.asarray(t)
            for t in r.step_multi_pipelined(dec, k, bursts=bursts)
        ]
        chained()  # compile both program variants (burst + seam)
        toks = chained()  # post-donation settle; tokens for the match
        t0 = time.perf_counter()
        timed = chained()
        dt = time.perf_counter() - t0
        toks_by[name] = np.concatenate(toks, axis=1)[0]
        tps_by[name] = bursts * k / dt
        del r
    tag = f"{target // 1024}k" if target >= 1024 else f"{target}"
    out[f"decode_at_{tag}_tokens_per_sec_int8"] = round(tps_by["int8"], 1)
    out[f"decode_at_{tag}_tokens_per_sec_fp_contrast"] = round(
        tps_by["fp"], 1
    )
    out["kv_quant_decode_speedup"] = round(
        tps_by["int8"] / max(tps_by["fp"], 1e-9), 3
    )
    out["kv_quant_token_match_rate"] = round(
        float((toks_by["fp"] == toks_by["int8"]).mean()), 4
    )
    out["kv_quant_context"] = target
    return out


def kv_fabric_metrics(page_size: int) -> dict:
    """KV fabric loopback phase (ISSUE 16): stand up a real fabric
    listener, then push and pull batches of synthetic llama-debug-shaped
    pages through the versioned CRC'd wire path (docs/kv-fabric.md) and
    record pages/s + MB/s for both directions plus the probed loopback
    bandwidth the peer-selection score would see. Keys:
    ``kv_fabric_push_pages_per_sec``, ``kv_fabric_pull_pages_per_sec``,
    ``kv_fabric_push_mb_per_sec``, ``kv_fabric_probe_mb_per_sec``,
    ``kv_fabric_page_kb``."""
    import numpy as np

    from production_stack_tpu.kvfabric.client import KVFabricClient
    from production_stack_tpu.kvfabric.server import KVFabricServer
    from production_stack_tpu.kvfabric.wire import decode_frame, encode_frame

    L, KH, D = 2, 4, 16  # llama-debug pool geometry
    n_pages, rounds = 64, 8
    rng = np.random.RandomState(3)
    keys = [bytes([i, 0xFA] + [0] * 30).hex() for i in range(n_pages)]
    ks = [rng.randn(L, page_size, KH, D).astype(np.float32)
          for _ in range(n_pages)]
    vs = [rng.randn(L, page_size, KH, D).astype(np.float32)
          for _ in range(n_pages)]
    frame = encode_frame(keys, ks, vs)
    resident = {"keys": keys, "frame": frame}

    def pages_fn(want):
        return resident["keys"], resident["frame"]

    sunk = [0]

    def sink_fn(decoded):
        sunk[0] += len(decoded["keys"])
        return len(decoded["keys"])

    srv = KVFabricServer("127.0.0.1", 0, generation=1, page_size=page_size,
                         nlayers=L, pages_fn=pages_fn, sink_fn=sink_fn)
    srv.start()
    cli = KVFabricClient(retries=0, timeout=30.0)
    out = {}
    try:
        addr = srv.address
        assert cli.push(addr, frame), "warm-up push failed"  # connect+frame
        t0 = time.perf_counter()
        for _ in range(rounds):
            assert cli.push(addr, frame)
        dt = time.perf_counter() - t0
        out["kv_fabric_push_pages_per_sec"] = round(rounds * n_pages / dt, 1)
        out["kv_fabric_push_mb_per_sec"] = round(
            rounds * len(frame) / dt / 2**20, 1
        )
        assert cli.pull(addr, keys) is not None, "warm-up pull failed"
        t0 = time.perf_counter()
        for _ in range(rounds):
            got = cli.pull(addr, keys)
            assert got is not None and len(got["keys"]) == n_pages
        dt = time.perf_counter() - t0
        out["kv_fabric_pull_pages_per_sec"] = round(rounds * n_pages / dt, 1)
        link = cli.probe(addr)
        out["kv_fabric_probe_mb_per_sec"] = round(link.bandwidth / 2**20, 1)
        out["kv_fabric_page_kb"] = round(
            decode_frame(frame)["pages"][0][0].nbytes * 2 / 1024, 2
        )
    finally:
        cli.close()
        srv.stop()
    return out


def tp_engine_metrics(on_tpu: bool) -> dict:
    """Tensor-parallel SERVING phase (ISSUE 12): the same HTTP llama path as
    the stack phases, served by engines at tp=1 vs tp=2/4 — decode and
    prefill tok/s per shape (``http_decode_tokens_per_sec_tp{N}`` /
    ``http_prefill_tokens_per_sec_tp{N}``). Runs only when the backend
    exposes >= 2 devices (a TPU slice, or the virtual CPU mesh tests/CI
    provision); a single-chip run records nothing, and update_bench_docs
    renders the rows conditionally. Fail-soft like the stack phases."""
    import asyncio
    import threading

    out: dict = {}
    try:
        import concurrent.futures as cf

        import requests

        from production_stack_tpu.engine import api_server as engine_api
        from production_stack_tpu.engine.config import EngineConfig
        from production_stack_tpu.testing.procs import free_port

        n_dev = len(jax.devices())
        tps = [1] + [t for t in (2, 4) if t <= n_dev]
        if len(tps) == 1:
            return out
        # flagship on TPU slices (8 kv heads shard over tp in {2, 4});
        # the tp-shardable debug twin on the virtual CPU mesh
        model = "llama-3.2-1b" if on_tpu else "llama-debug-4kv"
        plen, gen, conc, n_pre = (1024, 64, 8, 6) if on_tpu else (64, 16, 4, 3)
        prompt_words = "tensor parallel serving phase " * (plen // 30)

        for tp in tps:
            loop = asyncio.new_event_loop()
            thread = threading.Thread(target=loop.run_forever, daemon=True)
            thread.start()
            server = runner = None
            try:
                port = free_port()
                cfg = EngineConfig(
                    model=model, host="127.0.0.1", port=port,
                    tensor_parallel_size=tp,
                    max_model_len=4096 if on_tpu else 512,
                    max_num_seqs=max(conc, 8), prefill_chunk=plen,
                    num_pages=None if on_tpu else 256,
                )
                server, runner = asyncio.run_coroutine_threadsafe(
                    engine_api.serve(cfg), loop
                ).result(600)
                url = f"http://127.0.0.1:{port}/v1/completions"
                # one Session per worker thread: requests.Session is not
                # thread-safe, and the decode sub-phase posts concurrently
                # (same pattern as http_stack_metrics' http_session)
                tls = threading.local()

                def one(max_tokens, prompt):
                    sess = getattr(tls, "session", None)
                    if sess is None:
                        sess = tls.session = requests.Session()
                    r = sess.post(url, json={
                        "model": model, "prompt": prompt,
                        "max_tokens": max_tokens, "temperature": 0.0,
                        "ignore_eos": True,
                    }, timeout=600)
                    r.raise_for_status()
                    return r.json()["usage"]

                # prefill: fresh non-cacheable prompts, 1 gen token each
                one(1, f"warm {prompt_words}")
                t0 = time.perf_counter()
                toks = sum(
                    one(1, f"p{i} {prompt_words}")["prompt_tokens"]
                    for i in range(n_pre)
                )
                out[f"http_prefill_tokens_per_sec_tp{tp}"] = round(
                    toks / (time.perf_counter() - t0), 1
                )
                # decode: concurrent short-prompt generations at steady state
                with cf.ThreadPoolExecutor(max_workers=conc) as pool:
                    list(pool.map(
                        lambda i: one(gen, f"warmup {i}"), range(conc)
                    ))
                    t0 = time.perf_counter()
                    done = list(pool.map(
                        lambda i: one(gen, f"decode bench {i}"),
                        range(conc * 2),
                    ))
                dt = time.perf_counter() - t0
                out[f"http_decode_tokens_per_sec_tp{tp}"] = round(
                    sum(u["completion_tokens"] for u in done) / dt, 1
                )
                out["tp_phase_devices"] = n_dev
                out["tp_phase_model"] = model
            finally:
                if runner is not None:
                    async def _cleanup(r=runner):
                        try:
                            await asyncio.wait_for(r.cleanup(), 10)
                        except Exception:  # noqa: BLE001
                            pass
                    try:
                        asyncio.run_coroutine_threadsafe(
                            _cleanup(), loop
                        ).result(30)
                    except Exception:  # noqa: BLE001 - teardown best-effort
                        pass
                if server is not None:
                    try:
                        server.engine.stop()
                    except Exception:  # noqa: BLE001
                        pass
                loop.call_soon_threadsafe(loop.stop)
                thread.join(timeout=10)
                if not loop.is_running():
                    loop.close()
    except Exception as e:  # noqa: BLE001 - fail-soft, like the stack phases
        out["tp_phase_error"] = f"{type(e).__name__}: {e}"
    return out


def emit_primary(primary: dict) -> None:
    """Print the verbose payload first, then a FINAL metric line guaranteed
    to fit the driver's tail-capture window.

    The driver parses the LAST ~2,000 chars of stdout; round 4's final line
    embedded full per-point hop breakdowns, overflowed that window, and the
    official number was recorded as ``parsed: null``. The full payload now
    goes to ``BENCH_DETAILS.json`` + an earlier stdout line; the final line
    keeps only scalar extras and is hard-capped at 1,500 chars."""
    details_path = os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "BENCH_DETAILS.json"
    )
    try:
        with open(details_path, "w") as f:
            json.dump(primary, f, indent=1)
    except OSError:
        pass
    print(json.dumps({"bench_details": primary}), flush=True)

    extras = primary.get("extras", {})
    compact_extras = {
        k: v for k, v in extras.items()
        if isinstance(v, (int, float, str, bool)) or v is None
    }
    # per-QPS sweep summary in minimal form (the full points live in details)
    pts = extras.get("qa_points") or []
    if pts:
        compact_extras["qa_ttft_p50_by_qps"] = {
            str(p["qps"]): p["p50_ttft_ms"] for p in pts
        }
        compact_extras["qa_admission_wait_p50_by_qps"] = {
            str(p["qps"]): p["ttft_breakdown_ms"]
            .get("engine.admission_wait", {}).get("p50")
            for p in pts if p.get("ttft_breakdown_ms")
        }
    final = dict(primary, extras=compact_extras)
    line = json.dumps(final)
    # hard cap: drop extras keys (longest encoding first) until it fits
    while len(line) > 1500 and compact_extras:
        victim = max(
            compact_extras, key=lambda k: len(json.dumps({k: compact_extras[k]}))
        )
        compact_extras.pop(victim)
        final = dict(primary, extras=compact_extras)
        line = json.dumps(final)
    print(line, flush=True)


def http_stack_metrics(on_tpu: bool, model_dir: "str | None" = None) -> dict:
    """Serving-stack phases — everything below runs through the FULL stack:
    streaming HTTP client -> router (round-robin, static discovery) -> engine
    API server -> LLMEngine — matching the north star's shape ("p50 TTFT …
    via router", BASELINE.json). Both servers run in-process on one asyncio
    loop (a chip belongs to one process at a time).

    Sub-phases, each with its own TTFT hop window (POST /metrics/reset
    between phases so quantiles describe the phase they ship with):
      1. sequential TTFT through the router (+ engine-direct contrast)
      2. saturated throughput + steady-state decode through the stack
      3. multi-round-qa — THE PRIMARY PHASE (qa_* metrics)
    Fail-soft: returns partial metrics if a phase breaks so the primary
    metric line always prints."""
    import asyncio
    import threading

    engine_server = None
    engine_runner = None
    router_runner = None
    loop = None
    loop_thread = None
    pool = None
    out: dict = {}
    try:
        import concurrent.futures as cf

        import numpy as np
        import requests

        from production_stack_tpu.engine import api_server as engine_api
        from production_stack_tpu.engine.config import EngineConfig
        from production_stack_tpu.router import app as router_app
        from production_stack_tpu.router.parser import parse_args
        from production_stack_tpu.testing.procs import free_port

        # same weights as phase 1: the HTTP metrics must describe the model
        # the JSON line names
        model = model_dir or ("llama-3.2-1b" if on_tpu else "llama-debug")
        # byte tokenizer: ~1 token per char
        plen, n_reqs, conc, gen = (1000, 10, 8, 64) if on_tpu else (64, 3, 2, 8)
        eport, rport = free_port(), free_port()
        loop = asyncio.new_event_loop()
        loop_thread = threading.Thread(target=loop.run_forever, daemon=True)
        loop_thread.start()
        # decode_pipeline=4: burst chaining pays one fetch round trip per 4
        # bursts instead of 1. The scheduler's adaptive chain cap
        # (scheduler.py) shortens chains under a live arrival stream, so
        # TTFT no longer pays for the chaining that decode throughput earns.
        cfg = EngineConfig(
            model=model, host="127.0.0.1", port=eport,
            # max_model_len=32768: the SERVING config matches the reference's
            # canonical kv-aware deployment (values-17-kv-aware.yaml:15 /
            # helm/examples/values-32k-kv-aware.yaml) — every HTTP request in
            # this run is admitted under a 32k context budget, and the QA
            # phase's ~9k-token histories actually exercise it
            max_model_len=32768 if on_tpu else 4096,
            # 4.25 GB KV ≈ 2,020 pages: the 14-user QA working set (~2,030
            # pages incl. decode growth) runs at ~100-102% of capacity — the
            # LRU evicts idle users' tail pages as answers grow, so the
            # offload tier engages at the margin (capped spills/restores +
            # cheap recompute past the cap) WITHOUT the full-history thrash
            # a deeply overcommitted pool produces (measured: at 107%
            # occupancy on a 4.0 GB pool the hit rate collapsed to 0.24 and
            # every request recomputed ~2/3 of its 9.7k-token prompt)
            max_num_seqs=32, kv_cache_memory_gb=4.25, prefill_chunk=1024,
            # CPU offload tier: the QA phase's 14-user x ~9.7k-token working
            # set runs at ~100-102% of the KV pool, so the LRU's marginal
            # evictions spill here and restore on the user's next round —
            # the reference's LMCache CPU-offload story, measured end-to-end
            kv_offload_cpu_gb=10.0 if on_tpu else 0.0,
            kv_offload_max_io_pages=8 if on_tpu else 0,
            # QA arrival clusters put many short cached-prefix prefills in
            # the queue at once; batching 8 per dispatch halves the
            # RTT-bound dispatch count on the admission path
            prefill_batch=8,
            decode_pipeline=(
                int(os.environ.get("PSTPU_BENCH_DECODE_PIPELINE", "4"))
                if on_tpu else 1
            ),
            # CPU jit ignores buffer donation, so pool updates copy the whole
            # pool per step — keep it small there; TPU updates are in-place
            num_pages=None if on_tpu else 2048,
            # the per-phase hop windows below need POST /metrics/reset
            enable_debug_endpoints=True,
        )
        engine_server, engine_runner = asyncio.run_coroutine_threadsafe(
            engine_api.serve(cfg), loop
        ).result(300)
        rargs = parse_args([
            "--host", "127.0.0.1", "--port", str(rport),
            "--service-discovery", "static",
            "--static-backends", f"http://127.0.0.1:{eport}",
            "--static-models", model,
            # prefixaware: the reference's canonical QA run routes on KV
            # locality (run.sh kvaware setup); with one engine the routing
            # decision is trivial but the trie lookup cost is real and on
            # the TTFT path, so the headline pays for it honestly
            "--routing-logic", "prefixaware",
            "--enable-debug-endpoints",  # per-phase hop-window resets
        ])
        _, router_runner = asyncio.run_coroutine_threadsafe(
            router_app.serve(rargs), loop
        ).result(60)

        url = f"http://127.0.0.1:{rport}/v1/completions"
        engine_url = f"http://127.0.0.1:{eport}/v1/completions"
        rng = np.random.RandomState(7)

        # Persistent HTTP session per thread + ONE shared worker pool for
        # every concurrent phase: a fresh requests.post pays TCP setup per
        # request, and per-phase executors would discard the threads (and
        # their sessions) between passes. The retired engine-direct decode
        # contrast read a physically impossible 235-276 tok/s against a
        # routed 1,800+ for exactly this reason — its sync client opened a
        # fresh connection per request while the router held a pooled
        # aiohttp session to the engine. Reusing sessions makes routed and
        # direct measurements symmetric in transport, not just estimator.
        tls = threading.local()

        def http_session() -> "requests.Session":
            s = getattr(tls, "session", None)
            if s is None:
                s = requests.Session()
                tls.session = s
            return s

        pool = cf.ThreadPoolExecutor(max_workers=32)

        def settle_traces() -> None:
            """The router records its root span in the handler's finally
            block, which can run AFTER the client finishes reading the
            stream; wait until the collector stops growing so scrapes and
            resets see a complete phase window (no missing roots, no
            stragglers leaking past a reset)."""
            last = -1
            for _ in range(20):
                cur = requests.get(
                    f"http://127.0.0.1:{rport}/v1/traces?limit=1", timeout=30
                ).json()["recorded_total"]
                if cur == last:
                    return
                last = cur
                time.sleep(0.05)

        def scrape_traces() -> dict:
            """Merged trace export for the CURRENT phase window (router +
            engine share the span collector in this co-hosted topology, but
            merge_exports dedupes, so this also works against split pods)."""
            settle_traces()
            merged = trace_report.merge_exports(*(
                requests.get(
                    f"http://127.0.0.1:{port}/v1/traces?limit=400", timeout=30
                ).json()
                for port in (rport, eport)
            ))
            return merged

        def reset_hop_windows():
            settle_traces()
            for port in (rport, eport):
                requests.post(
                    f"http://127.0.0.1:{port}/metrics/reset", timeout=30
                ).raise_for_status()

        def hop_gauges(metrics_text: str, prefix: str) -> dict:
            out_h = {}
            for line in metrics_text.splitlines():
                if "ttft_hop_" not in line or line.startswith("#"):
                    continue
                name_part, val = line.rsplit(" ", 1)
                hop = name_part.split("ttft_hop_")[1].split("_ms")[0]
                q = name_part.split('quantile="')[1].split('"')[0]
                out_h.setdefault(hop, {})[q] = float(val)
            return {f"{prefix}.{h}": qs for h, qs in out_h.items()}

        def scrape_hops() -> dict:
            breakdown = {}
            rtext = requests.get(
                f"http://127.0.0.1:{rport}/metrics", timeout=30
            ).text
            etext = requests.get(
                f"http://127.0.0.1:{eport}/metrics", timeout=30
            ).text
            breakdown.update(hop_gauges(rtext, "router"))
            breakdown.update(hop_gauges(etext, "engine"))
            return breakdown

        def engine_counters() -> dict:
            etext = requests.get(
                f"http://127.0.0.1:{eport}/metrics", timeout=30
            ).text
            c = {}
            for line in etext.splitlines():
                if line.startswith("vllm:") and "_total{" in line:
                    c[line.split("{")[0]] = float(line.rsplit(" ", 1)[1])
            return c

        def one_request(max_tokens: int, target: str = None,
                        prompt_len: int = None) -> tuple[float, float, int]:
            # unique prompt every call so the prefix cache can't shortcut TTFT
            prompt = "".join(
                chr(rng.randint(97, 123)) for _ in range(prompt_len or plen)
            )
            t0 = time.perf_counter()
            ttft = None
            chunks = 0
            with http_session().post(
                target or url,
                json={"model": model, "prompt": prompt, "max_tokens": max_tokens,
                      "stream": True, "temperature": 0.0, "ignore_eos": True},
                stream=True, timeout=600,
            ) as r:
                r.raise_for_status()
                for line in r.iter_lines():
                    if not line.startswith(b"data:") or b"[DONE]" in line:
                        continue
                    chunks += 1
                    if ttft is None:
                        ttft = time.perf_counter() - t0
            return ttft, time.perf_counter() - t0, chunks

        # ---- sub-phase 1: sequential TTFT (own hop window) ----------------
        for _ in range(2):
            one_request(16)  # compile prefill chunk + decode burst shapes
        reset_hop_windows()
        ttfts = [one_request(16)[0] * 1000 for _ in range(n_reqs)]
        # scrape BEFORE the engine-direct contrast requests so the hop
        # quantiles describe exactly the routed requests measured above
        ttft_breakdown = scrape_hops()
        # per-phase attribution from the SAME routed requests' traces
        # (router.request > routing/proxy > engine queue/prefill/decode):
        # self-times sum to the root span, so transport/proxy overhead shows
        # up as a phase instead of an unexplained residue
        ttft_traces = scrape_traces()
        ttft_attr = trace_report.phase_table(ttft_traces)
        eng_ttfts = [one_request(16, engine_url)[0] * 1000 for _ in range(n_reqs)]
        out.update({
            "ttft_phase_attribution": ttft_attr["phases"],
            "ttft_trace_e2e_p50_ms": ttft_attr["e2e_p50_ms"],
            "ttft_trace_leaf_coverage_p50": ttft_attr["leaf_coverage_p50"],
            "http_p50_ttft_ms": round(float(np.percentile(ttfts, 50)), 2),
            "http_p99_ttft_ms": round(float(np.percentile(ttfts, 99)), 2),
            # engine-server-direct TTFT baseline; router overhead is
            # http_p50_ttft_ms minus this
            "http_engine_direct_p50_ttft_ms": round(
                float(np.percentile(eng_ttfts, 50)), 2
            ),
            # hops from THIS phase only; router hop p50s sum to ~the client
            # p50 (client-side connect/read overhead is the remainder)
            "ttft_breakdown_ms": ttft_breakdown,
            "ttft_breakdown_router_p50_sum_ms": round(sum(
                qs.get("p50", 0.0) for h, qs in ttft_breakdown.items()
                if h.startswith("router.")
            ), 2),
            "http_prefill_tokens": plen,
        })

        # ---- sub-phase 2: saturated throughput + steady-state decode ------
        # concurrent batch shapes (decode batch bucket, multi-seq prefill)
        # compile on first use — warm them up outside the measured window.
        # Two rounds: ramp-up/down crosses several (batch, pages) buckets,
        # and any bucket left cold would compile inside the measured window
        def measure_stack_tps():
            t0 = time.perf_counter()
            list(pool.map(lambda _i: one_request(gen), range(conc)))
            return conc * gen / (time.perf_counter() - t0)

        for _ in range(2):
            measure_stack_tps()  # warm the concurrent batch shape buckets
        sc0 = engine_counters()
        # median of 3: one 8-request burst is a short window and the number
        # moved widely across otherwise-identical runs
        stack_tps = float(np.median([measure_stack_tps() for _ in range(3)]))
        sc1 = engine_counters()
        # r3->r4 this number fell 36% when the phase's engine config widened
        # (prefill_batch 4->8 among others); bisect the live scheduling knob
        # in-process (same engine, same compiled programs otherwise) and
        # attribute via dispatch counters so a future regression has a cause
        # attached, not just a delta
        stack_bisect = {}
        if on_tpu:
            sched = engine_server.engine.scheduler
            orig_pb = sched.prefill_batch
            try:
                sched.prefill_batch = 4
                measure_stack_tps()  # warm the B=4 bucket
                stack_bisect["stack_tokens_per_sec_prefill_batch_4"] = round(
                    float(np.median(
                        [measure_stack_tps() for _ in range(3)]
                    )), 1
                )
            finally:
                sched.prefill_batch = orig_pb
        # per-burst dispatch counts: the sc0..sc1 window brackets the THREE
        # median runs, so divide — raw deltas would read as a 3x scheduler
        # change against earlier rounds' single-burst numbers
        stack_disp = {
            k.split(":")[1]: round((sc1.get(k, 0) - sc0.get(k, 0)) / 3, 1)
            for k in (
                "vllm:decode_dispatches_total",
                "vllm:decode_chained_dispatches_total",
                "vllm:runahead_prefill_dispatches_total",
            )
        }

        # steady-state decode THROUGH the stack: short prefill, long decode,
        # fixed concurrency at the engine's full decode batch; rate counts
        # only the post-first-chunk window of each stream, so prefill time
        # is excluded and what remains is the router/SSE per-chunk overhead
        # on top of the engine's decode rate
        # 384-token streams: the steady-state window (deep quiescent chains)
        # dominates the ramp, which is what "steady-state decode" measures.
        # Concurrency = the engine's full seat count (its decode batch).
        dec_gen = 384 if on_tpu else 16
        dec_conc = 32 if on_tpu else conc
        def decode_request(_i, target=None):
            ttft, total, chunks = one_request(dec_gen, target=target, prompt_len=64)
            return ttft, total, chunks

        def decode_pass(target=None):
            """One fixed-concurrency decode pass; returns (aggregate
            post-first-chunk tok/s, raw results)."""
            res = list(pool.map(
                lambda _i: decode_request(_i, target), range(dec_conc)
            ))
            rates = [
                (dec_gen - 1) / (total - ttft)
                for ttft, total, _ in res if total > ttft
            ]
            return float(sum(rates)), res

        # warm BOTH targets' shape buckets and connection pools
        decode_pass()
        decode_pass(engine_url)
        # fresh trace window: the engine-side attribution below must describe
        # ONLY the measured runs (the warm runs' spans would pollute it)
        reset_hop_windows()
        c0 = engine_counters()
        # median of N — symmetric with the engine-direct contrast below
        n_passes = 3
        routed_passes = [decode_pass()[0] for _ in range(n_passes)]
        c1 = engine_counters()
        decode_tps = float(np.median(routed_passes))
        # Trace-derived engine-side rate from the routed requests' own
        # engine.decode spans — the attribution that cannot disagree with
        # the routed number about which side the time went to. Scraped
        # BEFORE the direct passes so the window brackets exactly the three
        # routed passes; normalize per pass.
        dec_traces = scrape_traces()
        dec_spans = [
            s for spans in dec_traces.values() for s in spans
            if s["name"] == "engine.decode" and s.get("duration_ms", 0) > 0
        ]
        # the trace window brackets all n_passes routed passes; the span-rate
        # sum is a per-pass aggregate, so normalize by the SAME pass count
        traced_engine_tps = float(sum(
            (s.get("attrs", {}).get("output_tokens", 1) - 1)
            / (s["duration_ms"] / 1000.0)
            for s in dec_spans
        )) / n_passes
        decode_attr = trace_report.phase_table(dec_traces)
        # Engine-direct contrast: the SAME workload with the router
        # bypassed, measured with the SAME estimator (median of 3) and the
        # SAME transport (persistent per-thread sessions). The earlier
        # incarnation read a physically impossible 235-276 tok/s against a
        # routed 1,800+ because its fresh-TCP-per-request sync client was
        # measuring connection setup, not the engine; with pooled
        # connections the two numbers are directly comparable and their gap
        # IS the router/SSE per-chunk overhead.
        direct_passes = [decode_pass(engine_url)[0] for _ in range(n_passes)]
        direct_tps = float(np.median(direct_passes))
        total_disp = (
            c1.get("vllm:decode_dispatches_total", 0)
            - c0.get("vllm:decode_dispatches_total", 0)
        )
        chained = (
            c1.get("vllm:decode_chained_dispatches_total", 0)
            - c0.get("vllm:decode_chained_dispatches_total", 0)
        )
        out.update(stack_bisect)
        out.update({
            "http_stack_dispatches": stack_disp,
            "http_stack_tokens_per_sec": round(stack_tps, 1),
            "http_decode_tokens_per_sec": round(decode_tps, 1),
            # same workload with the router bypassed — symmetric estimator
            # (median of 3) and transport (pooled sessions), so the gap to
            # the routed number is real router/SSE overhead
            "http_decode_engine_direct_tokens_per_sec": round(direct_tps, 1),
            # engine-side rate derived from the routed requests' own
            # engine.decode spans (docs/benchmarking.md)
            "http_decode_engine_tokens_per_sec_traced": round(
                traced_engine_tps, 1
            ),
            "http_decode_phase_attribution": decode_attr["phases"],
            "http_decode_trace_leaf_coverage_p50": decode_attr[
                "leaf_coverage_p50"
            ],
            "http_decode_concurrency": dec_conc,
            # fraction of decode dispatches that chained bursts IN THIS
            # PHASE: chaining only engages on a quiescent batch, and each
            # unchained dispatch pays a fetch round trip — a low ratio
            # explains a low decode rate through the stack
            "http_decode_chained_dispatch_ratio": (
                round(chained / total_disp, 3) if total_disp else None
            ),
            "http_concurrency": conc,
        })

        # ---- sub-phase 2b: flight-recorder overhead (ISSUE 7) -------------
        # The recorder rides the engine dispatch path (one dict append per
        # sched/step event); acceptance: decode throughput with it ENABLED
        # must stay >= 0.98x recorder-off. Measured in-process on the live
        # engine: flip the recorder, rerun the identical decode passes,
        # flip back. Ratio = on / off (>= 1.0 means no measurable cost).
        try:
            from production_stack_tpu.tracing import get_flightrecorder

            _fr = get_flightrecorder()
            _fr.set_enabled(False)
            try:
                off_passes = [decode_pass()[0] for _ in range(n_passes)]
            finally:
                _fr.set_enabled(True)
            fr_off_tps = float(np.median(off_passes))
            fr_ratio = decode_tps / fr_off_tps if fr_off_tps else None
            out["flightrecorder_overhead_ratio"] = (
                round(fr_ratio, 4) if fr_ratio is not None else None
            )
            if fr_ratio is not None and fr_ratio < 0.98:
                print(
                    f"WARNING: flight recorder costs "
                    f"{(1 - fr_ratio) * 100:.1f}% decode throughput "
                    f"(ratio {fr_ratio:.4f} < 0.98 acceptance)"
                )
        except Exception as e:  # noqa: BLE001 - fail-soft like every phase
            print(f"flight-recorder overhead phase failed: {e}")

        # ---- sub-phase 2c: decode interference from a long prefill --------
        # Sustained decode streams at fixed concurrency, measured twice:
        # inter-token gaps with NO prefill in flight, then gaps inside the
        # window where one ~32k-token prompt streams its chunks through the
        # same engine. The scheduler's demand-gated chunk interleave
        # (scheduler.schedule) is what keeps the ratio bounded — acceptance
        # is p99 regression <= 1.3x while the long prefill is in flight.
        try:
            itl_conc = 8 if on_tpu else 2
            itl_gen = 256 if on_tpu else 24
            # longest prompt the 32k serving config can take and still
            # decode one token (CPU: scaled to the 4096 config)
            long_plen = (32768 - 512) if on_tpu else 2048

            def itl_stream(gen):
                """One decode stream; returns (chunk_timestamp, gap_ms)."""
                prompt = "".join(
                    chr(rng.randint(97, 123)) for _ in range(64)
                )
                gaps = []
                last = None
                with http_session().post(
                    url,
                    json={"model": model, "prompt": prompt,
                          "max_tokens": gen, "stream": True,
                          "temperature": 0.0, "ignore_eos": True},
                    stream=True, timeout=600,
                ) as r:
                    r.raise_for_status()
                    for line in r.iter_lines():
                        if not line.startswith(b"data:") or b"[DONE]" in line:
                            continue
                        now = time.perf_counter()
                        if last is not None:
                            gaps.append((now, (now - last) * 1000))
                        last = now
                return gaps

            def long_prefill_request():
                """Submit the long prompt and return its (t0, t_first) —
                the in-flight-prefill window the interference gaps are
                filtered to."""
                prompt = "".join(
                    chr(rng.randint(97, 123)) for _ in range(long_plen)
                )
                t0 = time.perf_counter()
                with http_session().post(
                    url,
                    json={"model": model, "prompt": prompt, "max_tokens": 1,
                          "stream": True, "temperature": 0.0,
                          "ignore_eos": True},
                    stream=True, timeout=600,
                ) as r:
                    r.raise_for_status()
                    for line in r.iter_lines():
                        if line.startswith(b"data:") and b"[DONE]" not in line:
                            break  # first token: the prefill retired
                return t0, time.perf_counter()

            long_prefill_request()  # warm the long-context page buckets
            # baseline pass: decode streams alone
            base_gaps = [
                g for gs in pool.map(lambda _i: itl_stream(itl_gen),
                                     range(itl_conc))
                for _, g in gs
            ]
            # interference pass: same streams, long prefill mid-flight
            futs = [pool.submit(itl_stream, itl_gen)
                    for _ in range(itl_conc)]
            time.sleep(0.75 if on_tpu else 0.2)  # let streams establish
            w0, w1 = long_prefill_request()
            inter_all = [ts_g for f in futs for ts_g in f.result()]
            inter_gaps = [g for ts, g in inter_all if w0 <= ts <= w1]
            out["decode_itl_p99_ms_baseline"] = round(
                float(np.percentile(base_gaps, 99)), 2
            ) if base_gaps else None
            out["decode_itl_p99_ms_with_32k_prefill"] = round(
                float(np.percentile(inter_gaps, 99)), 2
            ) if inter_gaps else None
            out["decode_itl_interference_ratio"] = (
                round(
                    out["decode_itl_p99_ms_with_32k_prefill"]
                    / out["decode_itl_p99_ms_baseline"],
                    3,
                )
                if base_gaps and inter_gaps else None
            )
            out["interference_prefill_tokens"] = long_plen
            out["interference_prefill_ms"] = round((w1 - w0) * 1000, 2)
            out["decode_itl_concurrency"] = itl_conc
        except Exception as e:  # noqa: BLE001 - fail-soft like the QA phase
            out["decode_itl_error"] = repr(e)

        # ---- sub-phase 3 (PRIMARY): multi-round-qa through the router -----
        import sys

        sys.path.insert(0, os.path.join(
            os.path.dirname(os.path.abspath(__file__)), "benchmarks"
        ))
        from multi_round_qa import UserSessionManager
        from multi_round_qa import parse_args as qa_parse_args

        qa_points = []
        qa_err = None
        # Canonical workload SHAPE (reference multi-round-qa/run.sh:14-35:
        # 320 users x 10 rounds, 1k shared prefix, 20k-token histories, KV
        # pre-populated into CPU offload), scaled to one 1B chip: 14 users,
        # ~1,200-word (~8.5k-token with the byte tokenizer) histories. The
        # working set (~135k tokens by the last round) slightly exceeds the
        # ~131k-token HBM budget, so cold histories spill to the CPU tier
        # and restore on later rounds — offload engages and hit rate must
        # survive the round-trips. kv_offload_max_io_pages=8 bounds each
        # spill/restore and the engine recomputes past the cap; whether a
        # cap pays on a directly attached chip is not measured (the
        # engine's start-up link probe would choose 0, unbounded, there).
        users, rounds, answer_len = (14, 5, 100) if on_tpu else (4, 2, 8)
        shared_words, hist_words = (150, 1200) if on_tpu else (20, 10)

        def run_qa(qps, n_users, n_rounds, ans, seed=0):
            qa_args = qa_parse_args([
                "--base-url", f"http://127.0.0.1:{rport}/v1",
                "--model", model,
                "--qps", str(qps),
                "--num-users", str(n_users),
                "--num-rounds", str(n_rounds),
                "--answer-len", str(ans),
                "--shared-prefix-len", str(shared_words),
                "--user-history-len", str(hist_words),
                "--round-gap", "1.0",
                "--log-interval", "0",
                # pinned workload seed: rep i of every bench invocation
                # replays the identical prompts/arrivals, so rep-to-rep
                # spread measures SYSTEM noise, not workload sampling
                "--seed", str(seed),
                # tails can hit a capped offload restore + recompute; record
                # them as latency, not as failures
                "--request-timeout", "600",
            ])
            mgr = UserSessionManager(qa_args)
            summary = asyncio.run_coroutine_threadsafe(
                mgr.run(), loop
            ).result(1800)
            return summary, mgr

        # warmup: the QA workload reaches context lengths (and so page-table
        # width buckets) and batch shapes the earlier phases never touched;
        # any bucket left cold would compile inside a measured point. Full user count at half rounds covers the
        # deepest decode batch; the persistent compile cache makes this
        # near-free on every run after a machine's first.
        try:
            # qps 2 (not 8): the cold warmup prefills every user's full
            # ~8.6k-token history — clustered arrivals would stack 14 such
            # prefills plus first-time spills into one backlog spike
            run_qa(2.0, users, max(1, rounds // 2), answer_len)
        except Exception:  # noqa: BLE001 - warmup is best-effort
            pass
        def measure_point(qps, seed=0):
            """One measured QA run at `qps` -> point dict (raises on a run
            with zero successful requests)."""
            reset_hop_windows()
            c0 = engine_counters()
            t0 = time.perf_counter()
            summary, mgr = run_qa(qps, users, rounds, answer_len, seed)
            elapsed = time.perf_counter() - t0
            if summary.completed == 0 or summary.p50_ttft != summary.p50_ttft:
                raise RuntimeError(
                    f"qa run at qps={qps}: no successful requests "
                    f"({summary.failed} failed)"
                )
            c1 = engine_counters()
            hits = (
                c1.get("vllm:gpu_prefix_cache_hits_total", 0)
                - c0.get("vllm:gpu_prefix_cache_hits_total", 0)
            )
            queries = (
                c1.get("vllm:gpu_prefix_cache_queries_total", 0)
                - c0.get("vllm:gpu_prefix_cache_queries_total", 0)
            )

            def delta(name):
                return c1.get(name, 0) - c0.get(name, 0)

            # served prompt length from the CLIENT's usage records (the
            # engine's prompt_tokens_total counts computed chunks only,
            # which caching makes tiny); evidences the >=8k histories
            ptoks = [r.prompt_tokens for r in mgr.records if r.prompt_tokens]
            return {
                "qps": qps,
                "p50_ttft_ms": round(summary.p50_ttft * 1000, 2),
                "p90_ttft_ms": round(summary.p90_ttft * 1000, 2),
                "avg_ttft_ms": round(summary.avg_ttft * 1000, 2),
                "gen_tokens_per_sec": round(
                    summary.avg_generation_throughput, 1
                ),
                "prompt_tokens_per_sec": round(
                    summary.avg_prompt_throughput, 1
                ),
                "kv_hit_rate": (
                    round(hits / queries, 4) if queries else None
                ),
                "completed": summary.completed,
                "failed": summary.failed,
                "elapsed_s": round(elapsed, 1),
                # evidence the canonical shape actually ran: avg served
                # prompt length (history included) and the offload tier's
                # spill/restore traffic during THIS point
                "avg_prompt_tokens": (
                    round(float(np.mean(ptoks))) if ptoks else 0
                ),
                "kv_offload_saved_pages": delta(
                    "vllm:kv_offload_saved_pages_total"
                ),
                "kv_offload_loaded_pages": delta(
                    "vllm:kv_offload_loaded_pages_total"
                ),
                "kv_offload_hit_pages": delta(
                    "vllm:kv_offload_hit_pages_total"
                ),
                "ttft_breakdown_ms": scrape_hops(),
            }

        # >=3 points, the top one past saturation (~19 req/s of pure decode
        # capacity falls to a few req/s once restores + new-turn prefills
        # land on the same chip). Each point runs MEDIAN-OF-3 (by headline
        # p50 TTFT): single runs swung 1.5-2x run-to-run — one unlucky
        # arrival cluster landing on a cold spill/restore window moves the
        # p50 of a 70-request sample — and the headline inherited the swing.
        # The reported point is the median rep in full (its counters and
        # breakdown describe one real run, not a chimera of three); the
        # per-rep p50s ride along as dispersion evidence.
        point_reps = 3 if on_tpu else 1
        # distinct PINNED seeds per rep: each rep is a different (but
        # fixed-forever) workload draw, so the median spans workload
        # variation while two back-to-back bench runs stay rep-for-rep
        # identical — the agreement the dispersion gate below enforces
        rep_seeds = [11, 23, 47][:point_reps]
        for qps in ([1.0, 2.0, 4.0] if on_tpu else [4.0]):
            reps = []
            rep_err = None
            for rep_seed in rep_seeds:
                try:
                    reps.append(measure_point(qps, rep_seed))
                except Exception as e:  # noqa: BLE001 - keep other reps/points
                    rep_err = f"{type(e).__name__}: {e}"
            if not reps:
                # only a point with ZERO usable reps is an error — one bad
                # rep of three is exactly the noise the median exists to eat
                qa_err = rep_err
                continue
            rep_p50s = [r["p50_ttft_ms"] for r in reps]
            # LOWER median: with an even rep count (one rep failed), taking
            # the higher of the middle pair would crown the pessimistic
            # outlier — the very swing this estimator removes
            point = sorted(reps, key=lambda r: r["p50_ttft_ms"])[
                (len(reps) - 1) // 2
            ]
            if len(reps) > 1:
                point["rep_p50_ttft_ms"] = rep_p50s  # run order, dispersion
                point["p50_ttft_dispersion"] = round(
                    (max(rep_p50s) - min(rep_p50s))
                    / max(point["p50_ttft_ms"], 1e-9), 4,
                )
            qa_points.append(point)
        # variance gate: the headline is only citable if the reps agree
        # within the SAME tolerance the docs guard applies to documented
        # numbers (scripts/update_bench_docs.PERF_TOLERANCE) — a spread the
        # docs guard would reject must fail the run that produced it, not
        # surface later as doc rot. main() exits non-zero on this flag.
        disps = [
            p["p50_ttft_dispersion"] for p in qa_points
            if "p50_ttft_dispersion" in p
        ]
        if disps:
            from scripts.update_bench_docs import PERF_TOLERANCE
            out["qa_p50_dispersion_max"] = max(disps)
            out["qa_dispersion_tolerance"] = PERF_TOLERANCE
            if max(disps) > PERF_TOLERANCE:
                out["qa_dispersion_gate_failed"] = True
        if qa_points:
            # headline point: the highest-QPS run that completed cleanly,
            # else the least-failing one (NOT the highest-qps failing run —
            # a mostly-failed sweep point would flatter the headline)
            clean = [p for p in qa_points if not p["failed"]]
            head = (
                max(clean, key=lambda p: p["qps"])
                if clean
                else min(qa_points, key=lambda p: p["failed"])
            )
            out.update({
                "qa_p50_ttft_ms": head["p50_ttft_ms"],
                "qa_p90_ttft_ms": head["p90_ttft_ms"],
                "qa_tokens_per_sec_per_chip": head["gen_tokens_per_sec"],
                "qa_kv_hit_rate": head["kv_hit_rate"],
                "qa_qps": head["qps"],
                "qa_users": users,
                "qa_rounds": rounds,
                "qa_answer_len": answer_len,
                "qa_history_words": hist_words,
                "qa_avg_prompt_tokens": head["avg_prompt_tokens"],
                "qa_kv_offload_saved_pages": head["kv_offload_saved_pages"],
                "qa_kv_offload_loaded_pages": head["kv_offload_loaded_pages"],
                "qa_points": qa_points,
            })
        if qa_err:
            out["qa_error"] = qa_err

        # ---- sub-phase 4: trace-driven mixed-class replay ----------------
        # a deterministic bursty/diurnal arrival trace (testing/trace_gen)
        # with mixed SLO classes replayed through the router: the per-class
        # outcome split evidences priority-aware admission under a
        # production-shaped arrival process, not a constant-QPS sweep
        try:
            from production_stack_tpu.testing.trace_gen import (
                generate_trace,
                trace_summary,
            )

            if on_tpu:
                tr_kw = dict(duration_s=12.0, base_qps=3.0,
                             min_context=1024, max_context=16384,
                             interactive_output=(16, 64),
                             batch_output=(64, 256))
            else:
                tr_kw = dict(duration_s=3.0, base_qps=4.0,
                             burst_period_s=1.5, burst_duration_s=0.5,
                             diurnal_period_s=3.0,
                             min_context=32, max_context=128,
                             interactive_output=(4, 8),
                             batch_output=(8, 16))
            trace = generate_trace(seed=20, **tr_kw)
            out["trace_shape"] = trace_summary(trace)

            def replay_one(req):
                prompt = "x" * req.prompt_tokens  # byte tokenizer: 1 tok/char
                try:
                    with http_session().post(
                        url,
                        json={"model": model, "prompt": prompt,
                              "max_tokens": req.output_tokens,
                              "stream": True, "temperature": 0.0,
                              "ignore_eos": True},
                        headers={"X-Priority": req.priority},
                        stream=True, timeout=600,
                    ) as r:
                        if r.status_code == 429:
                            return (req.priority, "shed")
                        r.raise_for_status()
                        for _line in r.iter_lines():
                            pass
                        return (req.priority, "ok")
                except Exception:  # noqa: BLE001 - counted, not fatal
                    return (req.priority, "error")

            t_base = time.perf_counter()
            futs = []
            for req in trace:
                delay = req.t - (time.perf_counter() - t_base)
                if delay > 0:
                    time.sleep(delay)
                futs.append(pool.submit(replay_one, req))
            by_class = {
                "interactive": {"ok": 0, "shed": 0, "error": 0},
                "batch": {"ok": 0, "shed": 0, "error": 0},
            }
            for f in futs:
                pri, outcome = f.result(timeout=600)
                by_class[pri][outcome] += 1
            out["trace_by_class"] = by_class
        except Exception as e:  # noqa: BLE001 - fail-soft like every phase
            out["trace_phase_error"] = f"{type(e).__name__}: {e}"

        # ---- 32k serving proof: one >=16k-token prompt through the FULL
        # stack (router -> api_server -> scheduler -> engine) under the
        # max_model_len=32768 config — the reference SERVES maxModelLen 32000
        # (values-17-kv-aware.yaml:15); ours must too, not just run 16k at
        # the runner. Chunked admission: 16 x 1k prefill chunks.
        if on_tpu:
            try:
                lc_ttft, lc_total, _ = one_request(8, prompt_len=16384)
                lc_ttft2, _, _ = one_request(8, prompt_len=16384)
                out["http_16k_ttft_ms"] = round(lc_ttft2 * 1000, 2)
                out["http_16k_cold_ttft_ms"] = round(lc_ttft * 1000, 2)
            except Exception as e:  # noqa: BLE001
                out["http_16k_error"] = f"{type(e).__name__}: {e}"
        return out
    except Exception as e:  # noqa: BLE001 - fail-soft by design
        out["http_stack_error"] = f"{type(e).__name__}: {e}"
        return out
    finally:
        if pool is not None:
            # join in-flight workers (the per-phase `with` blocks this pool
            # replaced did the same) so a phase that raised mid-pass cannot
            # leave streams running while the servers tear down below;
            # cancel_futures bounds the wait to already-running requests
            pool.shutdown(wait=True, cancel_futures=True)
        # Graceful teardown so no "Task was destroyed but it is pending!"
        # noise lands near the final metric line: cleanup() both aiohttp
        # runners (closes sites, runs on_cleanup hooks, drains handlers),
        # stop the engine, then stop and join the loop thread.
        if loop is not None:

            async def _shutdown():
                # bound each cleanup: AppRunner's default shutdown_timeout (60s
                # draining in-flight handlers) must not outlive our wait below,
                # or loop.close() would destroy the still-pending task
                for r in (router_runner, engine_runner):
                    if r is not None:
                        try:
                            await asyncio.wait_for(r.cleanup(), 10)
                        except Exception:  # noqa: BLE001
                            pass

            try:
                asyncio.run_coroutine_threadsafe(_shutdown(), loop).result(30)
            except Exception:  # noqa: BLE001 - teardown is best-effort
                pass
        if engine_server is not None:
            try:
                engine_server.engine.stop()
            except Exception:  # noqa: BLE001
                pass
        if loop is not None:
            loop.call_soon_threadsafe(loop.stop)
            if loop_thread is not None:
                loop_thread.join(timeout=10)
            if not loop.is_running():
                loop.close()


if __name__ == "__main__":
    main()
