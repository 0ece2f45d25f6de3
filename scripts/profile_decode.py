"""Decode-kernel memory-pipeline microbenchmark (the page-streaming floor).

Measures, per (batch, context, page_size) bucket, what the ragged paged
attention decode kernel actually achieves against HBM:

- ``hbm_gb_s``  — achieved page-streaming bandwidth: visible KV bytes the
  step must read (sum over rows of their REAL context, k+v) / wall time.
- ``tok_s``     — kernel-level decode tokens/sec (batch rows per call).
- the same numbers for the XLA gather path (``--impl xla`` / ``both``) —
  the pre-kernel baseline that materializes a contiguous [B, S] copy.
- ``contiguous_gb_s`` — a dense-copy ceiling on the same chip, so the
  scattered numbers have an upper bound next to them (round 5 measured
  ~200 GB/s contiguous vs 14-30 GB/s page-scattered; this script is how
  that pair gets re-measured after kernel changes).

The ``mixed`` case runs one bucket twice — every row at the bucket's full
context vs. most rows short — and checks that step cost scales with the
batch's real ``kv_lens``, not the bucket (the v2 ragged grid's whole
point). On TPU the check is asserted (exit 1 on failure); under
``--interpret``/CPU timings are interpreter noise, so it only smoke-tests
numerics vs the XLA oracle.

Run on the serving chip before retuning ``decode_pages_per_block`` /
``decode_prefetch_pages`` (engine/config.py); docs/benchmarking.md
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

from production_stack_tpu.ops.attention import paged_attention_decode
from production_stack_tpu.ops.pallas.paged_attention import (
    ragged_paged_attention_decode,
)
from production_stack_tpu.utils.compile_cache import enable_persistent_cache

enable_persistent_cache(
    os.path.join(os.path.dirname(os.path.abspath(__file__)), "..", ".cache", "xla")
)

# llama-3.2-1b-class attention shape (the serving flagship on one chip)
NH, KH, D = 32, 8, 64


def _scattered_case(rng, B, max_pages, page_size, lens, dtype):
    """Pools + a deliberately scattered page table: pages of a row are
    strided across the pool (worst-case DMA locality, the serving steady
    state after churn), not the fresh-allocation contiguous layout."""
    P = B * max_pages + 8
    kp = jnp.asarray(rng.randn(P, page_size, KH, D), dtype)
    vp = jnp.asarray(rng.randn(P, page_size, KH, D), dtype)
    pt = (
        np.arange(B * max_pages, dtype=np.int32)
        .reshape(max_pages, B)
        .T.copy()  # row b owns pages b, B+b, 2B+b, ... (stride B)
    )
    q = jnp.asarray(rng.randn(B, NH, D), dtype)
    return q, kp, vp, jnp.asarray(pt), jnp.asarray(lens, jnp.int32)


def _quantize_pools(kp, vp):
    """int8 twin of a pool pair + per-page per-kv-head scales
    (ops/quant.py contract), for the kv_cache_dtype=int8 sweep."""
    from production_stack_tpu.ops.quant import quantize_page_host

    # pool [P, page, KH, D]: the helper's leading axis is per-entry, so it
    # yields exactly one [KH] scale row per page
    qk, sk = quantize_page_host(np.asarray(kp, np.float32))
    qv, sv = quantize_page_host(np.asarray(vp, np.float32))
    return (
        jnp.asarray(qk), jnp.asarray(qv),
        jnp.asarray(sk), jnp.asarray(sv),
    )


def _time(fn, reps):
    fn()  # compile
    np.asarray(fn())  # post-donation/relayout settle + sync
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn()
    np.asarray(out)  # host fetch: the timed region ends when the result lands
    return (time.perf_counter() - t0) / reps


def _visible_bytes(lens, page_size, dtype, quant=False):
    pages = -(-np.maximum(np.asarray(lens), 0) // page_size)
    itemsize = 1 if quant else np.dtype(dtype).itemsize
    per_page = page_size * KH * D * itemsize + (KH * 4 if quant else 0)
    return int(pages.sum()) * per_page * 2  # k + v


def bench_bucket(rng, B, ctx, page_size, dtype, reps, impl, interpret,
                 lens=None, tag=""):
    """impl: pallas | xla | pallas_int8 (the kernel streaming int8 pages +
    dequantizing in its VMEM ring — the kv_cache_dtype=int8 serving path,
    halved byte stream)."""
    max_pages = -(-ctx // page_size)
    if lens is None:
        lens = np.full((B,), ctx, np.int32)
    q, kp, vp, pt, lens_d = _scattered_case(rng, B, max_pages, page_size,
                                            lens, dtype)
    quant = impl == "pallas_int8"
    if quant:
        qk, qv, sk, sv = _quantize_pools(kp, vp)
        fn = lambda: ragged_paged_attention_decode(
            q, qk, qv, pt, lens_d, interpret=interpret,
            k_scales=sk, v_scales=sv,
        )
    elif impl == "pallas":
        fn = lambda: ragged_paged_attention_decode(
            q, kp, vp, pt, lens_d, interpret=interpret
        )
    else:
        fn = lambda: paged_attention_decode(q, kp, vp, pt, lens_d)
    dt = _time(fn, reps)
    nbytes = _visible_bytes(lens, page_size, dtype, quant)
    per_tok = 2 * KH * D * (1 if quant else np.dtype(dtype).itemsize)
    return {
        "tag": tag or f"B{B}_ctx{ctx}_page{page_size}",
        "impl": impl,
        "batch": B,
        "context": ctx,
        "page_size": page_size,
        "kv_lens": sorted(set(int(x) for x in lens)),
        "step_ms": round(dt * 1000, 3),
        "visible_kv_mb": round(nbytes / 1e6, 1),
        "hbm_gb_s": round(nbytes / dt / 1e9, 2),
        "tok_s": round(B / dt, 1),
        "kv_bytes_per_token": per_tok,
    }


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
    # read + write of the whole buffer per iteration
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
    ap.add_argument("--contexts", default="", help="comma list, e.g. 1024,16384")
    ap.add_argument("--page-sizes", default="", help="comma list, e.g. 16,64,128")
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
    reps = args.reps or (16 if on_tpu else 2)
    B = args.batch or (16 if on_tpu else 2)
    contexts = (
        [int(c) for c in args.contexts.split(",") if c]
        or ([1024, 4096, 16384] if on_tpu else [64, 128])
    )
    page_sizes = (
        [int(p) for p in args.page_sizes.split(",") if p]
        or ([16, 64, 128] if on_tpu else [8, 16])
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

    for page_size in page_sizes:
        for ctx in contexts:
            for impl in impls:
                r = bench_bucket(rng, B, ctx, page_size, dtype, reps, impl,
                                 interpret)
                results["buckets"].append(r)
                print(json.dumps(r))

    # --- mixed-length case: cost must track real kv_lens, not the bucket ---
    ctx = max(contexts)
    page_size = page_sizes[-1] if len(page_sizes) == 1 else sorted(page_sizes)[1]
    short = max(page_size, ctx // 8)
    mixed_lens = np.full((B,), short, np.int32)
    mixed_lens[: max(1, B // 8)] = ctx  # a few long rows, mostly short
    full = bench_bucket(rng, B, ctx, page_size, dtype, reps, "pallas",
                        interpret, tag="mixed_full")
    mixed = bench_bucket(rng, B, ctx, page_size, dtype, reps, "pallas",
                         interpret, lens=mixed_lens, tag="mixed_ragged")
    byte_ratio = mixed["visible_kv_mb"] / max(full["visible_kv_mb"], 1e-9)
    time_ratio = mixed["step_ms"] / max(full["step_ms"], 1e-9)
    results["mixed"] = {
        "full": full, "ragged": mixed,
        "byte_ratio": round(byte_ratio, 3),
        "time_ratio": round(time_ratio, 3),
    }
    print(json.dumps(results["mixed"]))

    # numerics smoke for the ragged case (cheap everywhere, the only
    # meaningful mixed-case signal under the interpreter)
    q, kp, vp, pt, lens_d = _scattered_case(
        np.random.RandomState(1), B, -(-ctx // page_size), page_size,
        mixed_lens, dtype,
    )
    ref = paged_attention_decode(q, kp, vp, pt, lens_d)
    out = ragged_paged_attention_decode(q, kp, vp, pt, lens_d,
                                        interpret=interpret)
    tol = 3e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        atol=tol, rtol=tol,
    )
    print("mixed_case_numerics OK")

    # quantized-path summary + numerics: int8-vs-fp kernel tok/s per bucket
    # (the retuned decode_pages_per_block defaults are recorded from this
    # evidence), plus an interpret-safe oracle check — the quantized kernel
    # must match the XLA gather over the DEQUANTIZED pools to fp rounding
    if any(b["impl"] == "pallas_int8" for b in results["buckets"]):
        by_key = {}
        for b in results["buckets"]:
            by_key.setdefault((b["batch"], b["context"], b["page_size"]), {})[
                b["impl"]
            ] = b
        speedups = {}
        for key, d in sorted(by_key.items()):
            if "pallas" in d and "pallas_int8" in d:
                tag = d["pallas"]["tag"]
                speedups[tag] = {
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
        ref_q = paged_attention_decode(
            q,
            jnp.asarray(
                np.asarray(qk, np.float32)
                * np.asarray(sk)[:, None, :, None], dtype,
            ),
            jnp.asarray(
                np.asarray(qv, np.float32)
                * np.asarray(sv)[:, None, :, None], dtype,
            ),
            pt, lens_d,
        )
        out_q = ragged_paged_attention_decode(
            q, qk, qv, pt, lens_d, interpret=interpret,
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
        # bucket's cost. Allow generous slack over the pure byte ratio for
        # fixed per-step overhead (dispatch, warm-up, q/out traffic).
        limit = min(1.0, byte_ratio * 2 + 0.15)
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
