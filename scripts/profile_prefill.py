"""Prefill-kernel memory-pipeline microbenchmark (mirror of
scripts/profile_decode.py for the chunked-prefill side).

Measures, per (chunk, context) bucket, what the ragged prefill attention
kernel (ops/pallas/prefill_attention.py, v2) actually achieves:

- ``hbm_gb_s``  — achieved page-streaming bandwidth: paged KV bytes the
  call's DMA ring moves (each query block sweeps the row's REAL history,
  k+v) / wall time.
- ``tok_s``     — kernel-level prefill tokens/sec (chunk tokens per call).
- the same numbers for the XLA gather+flash path (``--impl xla``/``both``)
  — the pre-kernel baseline that materializes a contiguous [B, S] copy of
  the pool and runs the online softmax as a lax.scan.
- ``fused_ms``  — the same kernel call with the fused paged-KV write on
  (the serving default): the delta over the read-only call is the
  in-kernel write cost that replaces the runner's post-scan scatter pass.
- ``contiguous_gb_s`` — a dense-copy ceiling on the same chip, so the
  scattered numbers have an upper bound next to them.

The ``mixed`` case runs one bucket twice — every row with the bucket's
full history vs. mixed 1k/16k-style histories in ONE batch — and checks
that call cost scales with the batch's REAL summed work, not the bucket
(the packed ragged grid's whole point). On TPU the check is asserted
(exit 1 on failure); under ``--interpret``/CPU timings are interpreter
noise, so it only smoke-tests numerics vs the XLA oracle (including
fused-write pool bit-identity vs the scatter path).

Run on the serving chip before retuning ``prefill_pages_per_block`` /
``prefill_prefetch_pages`` (engine/config.py); docs/benchmarking.md
"Hardware ceilings" records the measured pair per round.
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))

import jax
import jax.numpy as jnp
import numpy as np

from production_stack_tpu.ops.attention import (
    flash_attention,
    gather_kv_pages,
    stale_kv_positions,
    write_kv_pages,
)
from production_stack_tpu.ops.pallas.prefill_attention import (
    ragged_paged_attention_prefill,
)
from production_stack_tpu.utils.compile_cache import enable_persistent_cache

enable_persistent_cache(
    os.path.join(os.path.dirname(os.path.abspath(__file__)), "..", ".cache", "xla")
)

# llama-3.2-1b-class attention shape (the serving flagship on one chip)
NH, KH, D = 32, 8, 64


def _quantize_pools(kp, vp):
    """int8 twin of a pool pair + per-page per-kv-head scales
    (ops/quant.py contract), for the kv_cache_dtype=int8 sweep."""
    from production_stack_tpu.ops.quant import quantize_page_host

    qk, sk = quantize_page_host(np.asarray(kp, np.float32))
    qv, sv = quantize_page_host(np.asarray(vp, np.float32))
    return jnp.asarray(qk), jnp.asarray(qv), jnp.asarray(sk), jnp.asarray(sv)


def _case(rng, B, T, page_size, computed, dtype):
    """Chunk of T fresh tokens per row over ``computed[b]`` paged history.
    Pages are deliberately scattered across the pool (worst-case DMA
    locality — the serving steady state after churn)."""
    max_pages = max(1, -(-int(max(computed) + T) // page_size))
    P = B * max_pages + 8
    kp = jnp.asarray(rng.randn(P, page_size, KH, D), dtype)
    vp = jnp.asarray(rng.randn(P, page_size, KH, D), dtype)
    pt = (
        np.arange(B * max_pages, dtype=np.int32)
        .reshape(max_pages, B)
        .T.copy()  # row b owns pages b, B+b, 2B+b, ... (stride B)
    )
    q = jnp.asarray(rng.randn(B, T, NH, D), dtype)
    kc = jnp.asarray(rng.randn(B, T, KH, D), dtype)
    vc = jnp.asarray(rng.randn(B, T, KH, D), dtype)
    pos = np.full((B, T), -1, np.int32)
    for b in range(B):
        pos[b] = np.arange(computed[b], computed[b] + T)
    lens = jnp.asarray(np.asarray(computed) + T, jnp.int32)
    cl = jnp.full((B,), T, jnp.int32)
    return q, kp, vp, jnp.asarray(pt), jnp.asarray(pos), lens, kc, vc, cl


def _xla_path(q, kp, vp, pt, pos, lens, kc, vc):
    kg, vg = gather_kv_pages(kp, vp, pt)
    kv_pos = stale_kv_positions(pt, pos, kp.shape[1])
    k = jnp.concatenate([kg, kc.astype(kg.dtype)], axis=1)
    v = jnp.concatenate([vg, vc.astype(vg.dtype)], axis=1)
    return flash_attention(q, k, v, q_positions=pos, kv_lens=lens,
                           kv_positions=kv_pos)


_xla_jit = jax.jit(_xla_path)


def _time(fn, reps):
    first = lambda o: o[0] if isinstance(o, tuple) else o
    fn()  # compile
    np.asarray(first(fn()))  # post-donation/relayout settle + sync
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn()
    # host fetch: the timed region ends when the result lands
    np.asarray(first(out))
    return (time.perf_counter() - t0) / reps


def _streamed_bytes(computed, T, page_size, q_block, dtype, quant=False):
    """Paged KV bytes the kernel's ring moves per call: each of the chunk's
    query blocks sweeps its row's real history once (k+v)."""
    n_qb = -(-T // q_block)
    pages = -(-np.maximum(np.asarray(computed), 0) // page_size)
    itemsize = 1 if quant else np.dtype(dtype).itemsize
    per_page = page_size * KH * D * itemsize + (KH * 4 if quant else 0)
    return int(pages.sum()) * per_page * 2 * n_qb


def bench_bucket(rng, B, T, ctx, page_size, dtype, reps, impl, interpret,
                 computed=None, tag="", q_block=128):
    if computed is None:
        computed = np.full((B,), max(ctx - T, 0), np.int64)
    q, kp, vp, pt, pos, lens, kc, vc, cl = _case(
        rng, B, T, page_size, computed, dtype
    )
    quant = impl == "pallas_int8"
    if quant:
        # quantized-KV serving path: int8 ring reads (half the bytes) and
        # the fused write quantizing the chunk in-kernel
        qk, qv, sk, sv = _quantize_pools(kp, vp)
        fn = lambda: ragged_paged_attention_prefill(
            q, qk, qv, pt, pos, lens, kc, vc, cl,
            interpret=interpret, q_block=q_block,
            k_scales=sk, v_scales=sv,
        )
        fused_fn = lambda: ragged_paged_attention_prefill(
            q, qk, qv, pt, pos, lens, kc, vc, cl,
            interpret=interpret, q_block=q_block, fused_write=True,
            k_scales=sk, v_scales=sv,
        )
    elif impl == "pallas":
        fn = lambda: ragged_paged_attention_prefill(
            q, kp, vp, pt, pos, lens, kc, vc, cl,
            interpret=interpret, q_block=q_block,
        )
        fused_fn = lambda: ragged_paged_attention_prefill(
            q, kp, vp, pt, pos, lens, kc, vc, cl,
            interpret=interpret, q_block=q_block, fused_write=True,
        )
    else:
        fn = lambda: _xla_jit(q, kp, vp, pt, pos, lens, kc, vc)
        fused_fn = None
    dt = _time(fn, reps)
    nbytes = _streamed_bytes(computed, T, page_size, q_block, dtype, quant)
    out = {
        "tag": tag or f"B{B}_chunk{T}_ctx{ctx}_page{page_size}",
        "impl": impl,
        "batch": B,
        "chunk": T,
        "context": ctx,
        "page_size": page_size,
        "histories": sorted(set(int(x) for x in computed)),
        "step_ms": round(dt * 1000, 3),
        "streamed_kv_mb": round(nbytes / 1e6, 1),
        "hbm_gb_s": round(nbytes / dt / 1e9, 2),
        "tok_s": round(B * T / dt, 1),
        "kv_bytes_per_token": 2 * KH * D
        * (1 if quant else np.dtype(dtype).itemsize),
    }
    if fused_fn is not None:
        out["fused_ms"] = round(_time(fused_fn, reps) * 1000, 3)
    return out


def contiguous_ceiling(dtype, on_tpu):
    """Dense-copy bandwidth on the same chip: the number the scattered
    streams are measured against."""
    mb = 512 if on_tpu else 4
    n = mb * (1 << 20) // np.dtype(dtype).itemsize
    x = jnp.arange(n, dtype=jnp.int32).astype(dtype)
    f = jax.jit(lambda a: a * 1 + 1)
    np.asarray(f(x))
    t0 = time.perf_counter()
    reps = 4
    for _ in range(reps):
        y = f(x)
    np.asarray(y[:8])
    dt = (time.perf_counter() - t0) / reps
    return round(2 * x.nbytes / dt / 1e9, 2)


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument(
        "--impl", choices=["pallas", "xla", "both", "pallas_int8"],
        default="both",
        help="'both' sweeps pallas + xla + pallas_int8 (the quantized-KV "
        "kernel path: achieved GB/s, tok/s, bytes/token vs fp)",
    )
    ap.add_argument("--reps", type=int, default=0, help="0 = auto per backend")
    ap.add_argument("--batch", type=int, default=0)
    ap.add_argument("--chunk", type=int, default=0, help="chunk length T")
    ap.add_argument("--contexts", default="",
                    help="comma list of total contexts, e.g. 4096,16384,32768")
    ap.add_argument("--page-size", type=int, default=0)
    ap.add_argument("--interpret", action="store_true",
                    help="Pallas interpret mode: smoke-tests the script on "
                         "the CPU, its timings mean nothing")
    ap.add_argument("--json", default="", help="write full results here too")
    args = ap.parse_args()

    on_tpu = jax.default_backend() == "tpu"
    if not on_tpu and not args.interpret:
        raise SystemExit(
            f"platform={jax.default_backend()!r}: this script measures the "
            "chip. Run it through the chip tool, or pass --interpret to "
            "smoke-test it on the CPU (those timings mean nothing)."
        )
    interpret = args.interpret
    dtype = jnp.bfloat16 if on_tpu else jnp.float32
    reps = args.reps or (8 if on_tpu else 2)
    B = args.batch or (1 if on_tpu else 2)
    T = args.chunk or (1024 if on_tpu else 32)
    page_size = args.page_size or (64 if on_tpu else 8)
    q_block = 128 if on_tpu else 16
    contexts = (
        [int(c) for c in args.contexts.split(",") if c]
        or ([4096, 16384, 32768] if on_tpu else [64, 128])
    )
    impls = (
        ["pallas", "pallas_int8", "xla"] if args.impl == "both"
        else [args.impl]
    )
    rng = np.random.RandomState(0)

    results = {"platform": jax.default_backend(), "interpret": interpret,
               "buckets": [], "mixed": {}}
    results["contiguous_gb_s"] = contiguous_ceiling(dtype, on_tpu)
    print(f"contiguous_copy_gb_s {results['contiguous_gb_s']}")

    for ctx in contexts:
        for impl in impls:
            r = bench_bucket(rng, max(B, 1), min(T, ctx), ctx, page_size,
                             dtype, reps, impl, interpret, q_block=q_block)
            results["buckets"].append(r)
            print(json.dumps(r))

    # --- mixed-history case: one batch, a few long histories among short
    # ones — cost must track the batch's real summed work, not the bucket
    ctx = max(contexts)
    Bm = max(B, 8 if on_tpu else 2)
    Tm = min(T, max(contexts[0] // 2, page_size * 2))
    long_hist = ctx - Tm
    short_hist = max(page_size, long_hist // 16)
    mixed = np.full((Bm,), short_hist, np.int64)
    mixed[: max(1, Bm // 8)] = long_hist
    full = bench_bucket(rng, Bm, Tm, ctx, page_size, dtype, reps, "pallas",
                        interpret, tag="mixed_full", q_block=q_block)
    rag = bench_bucket(rng, Bm, Tm, ctx, page_size, dtype, reps, "pallas",
                       interpret, computed=mixed, tag="mixed_ragged",
                       q_block=q_block)
    byte_ratio = rag["streamed_kv_mb"] / max(full["streamed_kv_mb"], 1e-9)
    time_ratio = rag["step_ms"] / max(full["step_ms"], 1e-9)
    results["mixed"] = {
        "full": full, "ragged": rag,
        "byte_ratio": round(byte_ratio, 3),
        "time_ratio": round(time_ratio, 3),
    }
    print(json.dumps(results["mixed"]))

    # numerics smoke (the only meaningful mixed-case signal under the
    # interpreter): kernel vs XLA oracle, and fused-write pool contents
    # bit-identical to the scatter path
    q, kp, vp, pt, pos, lens, kc, vc, cl = _case(
        np.random.RandomState(1), Bm, Tm, page_size, mixed, dtype
    )
    ref = _xla_jit(q, kp, vp, pt, pos, lens, kc, vc)
    out = ragged_paged_attention_prefill(
        q, kp, vp, pt, pos, lens, kc, vc, cl,
        interpret=interpret, q_block=q_block,
    )
    tol = 3e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        atol=tol, rtol=tol,
    )
    _, kp_f, vp_f = ragged_paged_attention_prefill(
        q, kp, vp, pt, pos, lens, kc, vc, cl,
        interpret=interpret, q_block=q_block, fused_write=True,
    )
    kp_s, vp_s = write_kv_pages(kp, vp, kc.astype(kp.dtype),
                                vc.astype(vp.dtype), pt, pos)
    assert (np.asarray(kp_f) == np.asarray(kp_s)).all(), "fused k write"
    assert (np.asarray(vp_f) == np.asarray(vp_s)).all(), "fused v write"
    print("mixed_case_numerics OK (incl. fused-write pool bit-identity)")

    # quantized-path summary + numerics: int8-vs-fp kernel tok/s per bucket
    # (evidence for the retuned prefill_pages_per_block defaults), plus the
    # quantized kernel against the XLA oracle over the DEQUANTIZED pools
    if any(b["impl"] == "pallas_int8" for b in results["buckets"]):
        by_key = {}
        for b in results["buckets"]:
            by_key.setdefault((b["chunk"], b["context"]), {})[b["impl"]] = b
        speedups = {}
        for key, d in sorted(by_key.items()):
            if "pallas" in d and "pallas_int8" in d:
                speedups[d["pallas"]["tag"]] = {
                    "tok_s_fp": d["pallas"]["tok_s"],
                    "tok_s_int8": d["pallas_int8"]["tok_s"],
                    "speedup": round(
                        d["pallas_int8"]["tok_s"]
                        / max(d["pallas"]["tok_s"], 1e-9), 3,
                    ),
                    "bytes_per_token_fp": d["pallas"]["kv_bytes_per_token"],
                    "bytes_per_token_int8": d["pallas_int8"][
                        "kv_bytes_per_token"
                    ],
                }
        results["int8_speedup"] = speedups
        print(json.dumps({"int8_speedup": speedups}))
        qk, qv, sk, sv = _quantize_pools(kp, vp)
        kd = jnp.asarray(
            np.asarray(qk, np.float32)
            * np.asarray(sk)[:, None, :, None], dtype,
        )
        vd = jnp.asarray(
            np.asarray(qv, np.float32)
            * np.asarray(sv)[:, None, :, None], dtype,
        )
        ref_q = _xla_jit(q, kd, vd, pt, pos, lens, kc, vc)
        out_q = ragged_paged_attention_prefill(
            q, qk, qv, pt, pos, lens, kc, vc, cl,
            interpret=interpret, q_block=q_block,
            k_scales=sk, v_scales=sv,
        )
        np.testing.assert_allclose(
            np.asarray(out_q, np.float32), np.asarray(ref_q, np.float32),
            atol=tol, rtol=tol,
        )
        print("int8_dequant_numerics OK")

    ok = True
    if on_tpu and not args.interpret:
        # ragged scaling check: a mostly-short batch in a full-context
        # bucket must run much closer to its byte share than to the
        # bucket's cost. Prefill carries real chunk compute per row no
        # matter the history, so allow that floor plus dispatch overhead
        # over the pure byte ratio.
        limit = min(1.0, byte_ratio * 2 + 0.25)
        ok = time_ratio <= limit
        print(f"mixed_scaling {'OK' if ok else 'FAIL'} "
              f"time_ratio={time_ratio:.3f} byte_ratio={byte_ratio:.3f} "
              f"limit={limit:.3f}")
    else:
        print("mixed_scaling SKIPPED (interpret-mode timings are not real)")

    if args.json:
        with open(args.json, "w") as f:
            json.dump(results, f, indent=1)
    sys.exit(0 if ok else 1)


if __name__ == "__main__":
    main()
