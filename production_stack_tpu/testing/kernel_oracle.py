"""Both ragged Pallas kernels against the XLA oracle, COMPILED — the check
interpret mode cannot make: it says nothing about Mosaic's numerics.

``python -m production_stack_tpu.testing.kernel_oracle --out result.json``
runs every case of ``CASES`` on the default backend (``chip_smoke.py`` phase
K starts it as a child on the chip) and exits non-zero if any case is outside
its tolerance. ``--interpret --tiny`` runs the same code path on the CPU at
toy sizes (tests/test_chip_smoke.py).

Tolerances are the ones the interpret-mode tests state for bf16 (3e-2,
tests/test_pallas_prefill.py::test_bf16, the decode kernel's docstring); the
oracle runs under ``jax.default_matmul_precision("highest")`` so it is the
more exact side on a TPU, where a float32 matmul is otherwise a bf16 pass.
A fused-write case checks pool CONTENTS: bit-identical to the scatter path
for fp pools; for int8 pools the quantizer's division may round one ulp
apart from XLA's, so scales agree to 1e-6 relative and bytes to +-1.
Shapes the runner's rule excludes (head_dim 64, one kv head per shard) are
asserted refused by the rule, never compiled.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import sys

import jax
import jax.numpy as jnp
import numpy as np

from production_stack_tpu.engine.runner import kernel_refusal
from production_stack_tpu.ops import quant
from production_stack_tpu.ops.attention import (
    flash_attention,
    gather_kv_pages,
    paged_attention_decode,
    stale_kv_positions,
    write_kv_pages,
)
from production_stack_tpu.ops.pallas.paged_attention import (
    ragged_paged_attention_decode,
)
from production_stack_tpu.ops.pallas.prefill_attention import (
    ragged_paged_attention_prefill,
)

TOL = 3e-2  # bf16, as stated by the interpret-mode tests

# (kind, name, shape/feature overrides). Attention shapes: mistral-7b /
# llama-3-8b one chip (32 q / 8 kv), qwen2.5-7b one chip (28 / 4), the
# mistral-7b tp=4 shard (8 / 2); head_dim 128 throughout.
CASES = [
    ("decode", "kh8_b4_burst", dict(NH=32, KH=8, B=4, cur=8)),
    ("decode", "kh4_b1", dict(NH=28, KH=4, B=1, cur=1)),
    ("decode", "kh2_b4_window", dict(NH=8, KH=2, B=4, cur=8, window=True)),
    ("decode", "kh8_b4_int8", dict(NH=32, KH=8, B=4, cur=8, int8=True)),
    ("decode", "kh4_b4_int8", dict(NH=28, KH=4, B=4, cur=1, int8=True)),
    ("prefill", "kh8_b1", dict(NH=32, KH=8, B=1)),
    ("prefill", "kh8_b4_fused", dict(NH=32, KH=8, B=4, fused=True)),
    ("prefill", "kh4_b4_fused", dict(NH=28, KH=4, B=4, fused=True)),
    ("prefill", "kh2_b4_window_fused",
     dict(NH=8, KH=2, B=4, fused=True, window=True)),
    ("prefill", "kh8_b4_int8_fused",
     dict(NH=32, KH=8, B=4, fused=True, int8=True)),
]
# the CPU run (--tiny, interpret mode) keeps one standalone case; run_steps
# covers the bf16 kernels (burst window, fused write, sliding window)
TINY = {"kh4_b4_int8"}
# excluded by engine/runner.kernel_refusal, with the models that have them
EXCLUDED = [
    ("head_dim_64 (llama-3.2-1b)", dict(head_dim=64, kv_heads_per_shard=8,
                                        pool_itemsize=2)),
    ("one_kv_head_per_shard (qwen2.5-7b at tp=4)",
     dict(head_dim=128, kv_heads_per_shard=1, pool_itemsize=2)),
    ("int8_two_kv_heads_per_shard (mistral-7b int8 at tp=4)",
     dict(head_dim=128, kv_heads_per_shard=2, pool_itemsize=1)),
]


def _sizes(tiny: bool) -> dict:
    # chip: page 64 (EngineConfig default), a prefill_chunk-sized chunk,
    # histories that span many pages and, windowed, outrun the window
    if tiny:
        return dict(D=128, page=8, maxp=8, T=16, window=12)
    return dict(D=128, page=64, maxp=64, T=512, window=1024)


def _quant_pool(fp: np.ndarray):
    """[P, page, KH, D] fp -> (int8 pool, [P, KH] scales), ops/quant.py."""
    q = np.zeros(fp.shape, np.int8)
    s = np.ones((fp.shape[0], fp.shape[2]), np.float32)
    for p in range(fp.shape[0]):
        qp, sp = quant.quantize_page_host(fp[None, p])
        q[p], s[p] = qp[0], sp[0]
    return q, s


def _pools(rng, P, page, KH, D, int8):
    """Random pools as the kernel sees them (+ scales) and as the oracle
    does (fp; dequantized when int8)."""
    out = []
    for _ in range(2):
        fp = rng.randn(P, page, KH, D).astype(np.float32)
        if int8:
            q, s = _quant_pool(fp)
            deq = q.astype(np.float32) * s[:, None, :, None]
            out.append((jnp.asarray(q), jnp.asarray(s),
                        jnp.asarray(deq, jnp.bfloat16)))
        else:
            bf = jnp.asarray(fp, jnp.bfloat16)
            out.append((bf, None, bf))
    return out


def _err(out, ref) -> float:
    return float(np.max(np.abs(
        np.asarray(out, np.float32) - np.asarray(ref, np.float32)
    )))


def run_decode(spec, sz, interpret, seed):
    rng = np.random.RandomState(seed)
    NH, KH, B, C = spec["NH"], spec["KH"], spec["B"], spec["cur"]
    D, page, maxp = sz["D"], sz["page"], sz["maxp"]
    int8 = spec.get("int8", False)
    window = sz["window"] if spec.get("window") else None
    P = B * maxp + 3
    (kp, ks, kp_o), (vp, vs, vp_o) = _pools(rng, P, page, KH, D, int8)
    pt = jnp.asarray(
        rng.permutation(P)[: B * maxp].reshape(B, maxp), jnp.int32
    )
    # ragged lengths up to the bucket; row 0 fills it
    top = maxp * page
    lens = np.array(
        [top] + [int(rng.randint(C + 1, top)) for _ in range(B - 1)], np.int32
    )
    cl = np.asarray([C] + [int(rng.randint(1, C + 1)) for _ in range(B - 1)],
                    np.int32)
    q = jnp.asarray(rng.randn(B, NH, D), jnp.bfloat16)
    kc = jnp.asarray(rng.randn(B, C, KH, D), jnp.bfloat16)
    vc = jnp.asarray(rng.randn(B, C, KH, D), jnp.bfloat16)
    # stacked pools + layer index, as the model's layer scan calls it
    stack = lambda x: None if x is None else jnp.stack([jnp.zeros_like(x), x])  # noqa: E731
    out = ragged_paged_attention_decode(
        q, stack(kp), stack(vp), pt, jnp.asarray(lens), window,
        k_cur=kc, v_cur=vc, cur_lens=jnp.asarray(cl), layer=1,
        interpret=interpret, k_scales=stack(ks), v_scales=stack(vs),
    )
    with jax.default_matmul_precision("highest"):
        ref = paged_attention_decode(
            q, kp_o, vp_o, pt, jnp.asarray(lens), window=window,
            k_cur=kc, v_cur=vc, cur_lens=jnp.asarray(cl),
        )
    return {"max_abs_err": _err(out, ref),
            "finite": bool(np.isfinite(np.asarray(out, np.float32)).all())}


def run_prefill(spec, sz, interpret, seed):
    rng = np.random.RandomState(seed)
    NH, KH, B = spec["NH"], spec["KH"], spec["B"]
    D, page, maxp, T = sz["D"], sz["page"], sz["maxp"], sz["T"]
    int8, fused = spec.get("int8", False), spec.get("fused", False)
    window = sz["window"] if spec.get("window") else None
    P = B * maxp + 3
    (kp, ks, kp_o), (vp, vs, vp_o) = _pools(rng, P, page, KH, D, int8)
    pt = jnp.asarray(
        rng.permutation(P)[: B * maxp].reshape(B, maxp), jnp.int32
    )
    # page-aligned histories (how the scheduler chunks: prefill_chunk %
    # page_size == 0), ragged chunk sizes, last row a short tail chunk
    room = maxp * page - T
    hist = [(int(rng.randint(0, room // page + 1)) * page) for _ in range(B)]
    hist[0] = room // page * page  # deepest history in row 0
    chunks = [T] + [int(rng.randint(1, T + 1)) for _ in range(B - 1)]
    pos = np.full((B, T), -1, np.int32)
    for b in range(B):
        pos[b, : chunks[b]] = np.arange(hist[b], hist[b] + chunks[b])
    lens = jnp.asarray([h + c for h, c in zip(hist, chunks)], jnp.int32)
    cl = jnp.asarray(chunks, jnp.int32)
    pos = jnp.asarray(pos)
    q = jnp.asarray(rng.randn(B, T, NH, D), jnp.bfloat16)
    kc = jnp.asarray(rng.randn(B, T, KH, D), jnp.bfloat16)
    vc = jnp.asarray(rng.randn(B, T, KH, D), jnp.bfloat16)
    with jax.default_matmul_precision("highest"):
        kg, vg = gather_kv_pages(kp_o, vp_o, pt)
        ref = flash_attention(
            q, jnp.concatenate([kg, kc], axis=1),
            jnp.concatenate([vg, vc], axis=1), q_positions=pos, kv_lens=lens,
            window=window, kv_positions=stale_kv_positions(pt, pos, page),
        )
    res = ragged_paged_attention_prefill(
        q, kp, vp, pt, pos, lens, kc, vc, cl, window, interpret=interpret,
        fused_write=fused, k_scales=ks, v_scales=vs,
    )
    out = res[0] if fused else res
    r = {"max_abs_err": _err(out, ref),
         "finite": bool(np.isfinite(np.asarray(out, np.float32)).all())}
    if fused and int8:
        kq, vq, sk, sv = quant.write_kv_pages_all_layers_quant(
            kp[None], vp[None], ks[None], vs[None], kc[None], vc[None],
            pt, pos,
        )
        # compare where the contract defines the bytes: the chunk's own
        # slots (past a chunk's end a fresh page holds kernel zeros vs the
        # scatter's leftovers — both invisible), and every page the chunk
        # did not touch must keep its exact old bytes
        pt_h, pos_h = np.asarray(pt), np.asarray(pos)
        b_i, t_i = np.nonzero(pos_h >= 0)
        pg = pt_h[b_i, pos_h[b_i, t_i] // page]
        sl = pos_h[b_i, t_i] % page
        untouched = np.setdiff1d(np.arange(P), pg)
        r["pool_scale_rel_err"] = max(
            float(np.max(np.abs(np.asarray(a)[pg] / np.asarray(b)[0][pg] - 1.0)))
            for a, b in ((res[3], sk), (res[4], sv))
        )
        r["pool_max_byte_diff"] = max(
            int(np.max(np.abs(
                np.asarray(a, np.int32)[pg, sl]
                - np.asarray(b, np.int32)[0][pg, sl]
            )))
            for a, b in ((res[1], kq), (res[2], vq))
        )
        r["pool_ok"] = bool(
            r["pool_scale_rel_err"] <= 1e-6 and r["pool_max_byte_diff"] <= 1
            and np.array_equal(np.asarray(res[1])[untouched],
                               np.asarray(kp)[untouched])
            and np.array_equal(np.asarray(res[2])[untouched],
                               np.asarray(vp)[untouched])
        )
    elif fused:
        ks_, vs_ = write_kv_pages(kp, vp, kc, vc, pt, pos)
        r["pool_ok"] = bool(
            np.array_equal(np.asarray(res[1]), np.asarray(ks_))
            and np.array_equal(np.asarray(res[2]), np.asarray(vs_))
        )
    return r


def run_steps(interpret: bool, tiny: bool) -> dict:
    """The jitted serving steps with the kernels inside — stacked pools, the
    fused write riding the layer scan as an aliased carry, the deferred
    burst window — against the same steps on the XLA path. mistral-7b widths
    with the window pulled in so it is active; depth cut to 2 (the scan
    compiles one layer).

    Two prefill chunks (the second reads the first from the pool, past the
    sliding window), a greedy decode burst, then one more step that reads
    back what the burst committed. The burst feeds its own samples back and
    random weights put the top logits a rounding apart, so the XLA side is
    TEACHER-FORCED with the kernel side's tokens: each must be within the
    tolerance of the XLA path's best logit at its step, and with the same
    token history both paths' logits must agree. Tolerance as in the
    engine-level interpret tests (rtol = atol = 5e-2,
    tests/test_pallas_attention.py)."""
    from production_stack_tpu.engine.runner import ModelRunner, StepInput
    from production_stack_tpu.models import llama

    if tiny:
        cfg = dataclasses.replace(
            llama.PRESETS["llama-debug"], head_dim=128, sliding_window=12,
        )
        B, T, page, k = 2, 16, 8, 3
    else:
        cfg = dataclasses.replace(
            llama.PRESETS["mistral-7b"], num_layers=2, sliding_window=640,
            max_model_len=2048,
        )
        B, T, page, k = 4, 512, 64, 8
    maxp = (2 * T + k) // page + 1
    rng = np.random.RandomState(0)
    ids = rng.randint(0, cfg.vocab_size, (B, 2 * T))
    # ragged: row b's second chunk is shorter by b pages
    chunk2 = [T - b * page for b in range(B)]
    pos2 = np.full((B, T), -1)
    for b in range(B):
        pos2[b, : chunk2[b]] = np.arange(T, T + chunk2[b])
    lens = np.asarray([T + c for c in chunk2])
    common = dict(
        page_table=np.arange(B * maxp).reshape(B, maxp),
        temperature=np.zeros(B), top_k=np.zeros(B, int), top_p=np.ones(B),
    )

    def one(r, tok, at):
        """Single decode step: token ``tok`` [B] at position ``at`` [B]."""
        _, lg = r.step(StepInput(
            input_ids=tok[:, None], positions=at[:, None], kv_lens=at + 1,
            **common,
        ))
        return np.asarray(lg)

    def run(impl, forced=None):
        r = ModelRunner(
            dataclasses.replace(cfg, attn_impl=impl),
            num_pages=B * maxp + 1, page_size=page, seed=0,
        )
        logits = []
        for chunk, pos, kv in (
            (ids[:, :T], np.tile(np.arange(T), (B, 1)), np.full((B,), T)),
            (ids[:, T:], pos2, lens),
        ):
            _, lg = r.step(StepInput(
                input_ids=chunk, positions=pos, kv_lens=kv, **common
            ))
            logits.append(np.asarray(lg))
        first = ids[:, 0]
        if forced is None:
            toks = np.asarray(r.step_multi(StepInput(
                input_ids=first[:, None], positions=lens[:, None],
                kv_lens=lens + 1, kv_limits=lens + k + 1, **common,
            ), k))
            burst = None
        else:
            toks = forced
            fed = np.concatenate([first[:, None], toks[:, :-1]], axis=1)
            burst = np.stack(
                [one(r, fed[:, j], lens + j) for j in range(k)], axis=1
            )  # [B, k, V]
        logits.append(one(r, toks[:, -1], lens + k))
        return r.attn, logits, toks, burst

    attn, got, toks, _ = run("pallas_interpret" if interpret else "pallas_prefill")
    _, ref, _, burst = run("xla", forced=toks)
    errs = [_err(a, b) for a, b in zip(got, ref)]
    # how far below the XLA path's best logit the kernel path's greedy pick sits
    picked = np.take_along_axis(burst, toks[..., None], axis=2)[..., 0]
    regret = float(np.max(burst.max(axis=2) - picked))
    finite = bool(all(np.isfinite(g).all() for g in got))
    return {
        "model": "llama-debug (head_dim 128)" if tiny else "mistral-7b widths, 2 layers",
        "prefill": attn.prefill, "decode": attn.decode,
        "max_abs_err": {"prefill_chunk1": errs[0], "prefill_chunk2": errs[1],
                        "after_decode_burst": errs[2]},
        "burst_greedy_regret": regret,
        "tol": "rtol 5e-2 + atol 5e-2; regret <= 0.25",
        "finite": finite,
        "ok": bool(
            finite and regret <= 0.25
            and all(np.allclose(a, b, rtol=5e-2, atol=5e-2)
                    for a, b in zip(got, ref))
        ),
    }


def run_all(interpret: bool = False, tiny: bool = False) -> dict:
    sz = _sizes(tiny)
    dev = jax.devices()[0]
    report = {
        "platform": dev.platform, "device_kind": dev.device_kind,
        "interpret": interpret, "sizes": sz, "tol": TOL, "cases": {},
        "excluded": {},
    }
    ok = True
    for seed, (kind, name, spec) in enumerate(CASES):
        if tiny and name not in TINY:
            continue
        fn = run_decode if kind == "decode" else run_prefill
        r = fn(spec, sz, interpret, seed)
        r["ok"] = bool(
            r["finite"] and r["max_abs_err"] <= TOL
            and r.get("pool_ok", True)
        )
        ok &= r["ok"]
        report["cases"][f"{kind}/{name}"] = r
        print(f"K {kind}/{name}: {json.dumps(r)}", flush=True)
    report["steps"] = run_steps(interpret, tiny)
    ok &= report["steps"]["ok"]
    print(f"K steps: {json.dumps(report['steps'])}", flush=True)
    for name, shape in EXCLUDED:
        reason = kernel_refusal(**shape)
        report["excluded"][name] = reason
        ok &= reason is not None
        print(f"K excluded {name}: {reason}", flush=True)
    report["ok"] = bool(ok)
    return report


def main() -> int:
    p = argparse.ArgumentParser("kernel-oracle")
    p.add_argument("--out", required=True, help="where the JSON report goes")
    p.add_argument("--interpret", action="store_true",
                   help="Pallas interpret mode (CPU); says nothing of Mosaic")
    p.add_argument("--tiny", action="store_true", help="toy sizes for the CPU")
    args = p.parse_args()
    report = run_all(interpret=args.interpret, tiny=args.tiny)
    with open(args.out, "w") as f:
        json.dump(report, f, indent=1)
    return 0 if report["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
