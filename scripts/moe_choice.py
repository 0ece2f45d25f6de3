#!/usr/bin/env python3
"""What computes the grouped product of ops/moe.py: measured on the chip.

    chiprun -- python3 scripts/moe_choice.py chiprun_out/moe_choice.json

One expert layer of LFM2-8B-A1B (32 experts held, top-4, 2048 x 1792, bf16) at
8 / 16 / 32 / 2,048 tokens, routed by a random router, through
``ops.moe.expert_ffn`` (sort, both grouped products, the sum back) with each
candidate as its grouped product:

  ragged_dot      ``jax.lax.ragged_dot`` (the ``xla`` implementation)
  megablox        ``jax.experimental.pallas.ops.tpu.megablox.gmm``, its default
                  tiling (128, 128, 128)
  megablox_wide   the same with whole-K tiles (rows, K, 896 or 1024)
  kernel_<rows>   ``moe_grouped`` (the ``pallas`` implementation) with
                  ``TILE_ROWS`` = 128 / 256 / 512 (one tile up to 512 rows)

each against its floor: max(experts touched x 3 x H x I x 2 B / 819 GB/s,
assignments x 6 x H x I FLOP / 197 TFLOP/s), and against every expert applied
to every token in float32 and kept where the token chose it. The result stands in scripts/moe_choice_result.json; ops/moe.py
names the winner. Refuses any platform but a TPU."""

from __future__ import annotations

import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax import lax  # noqa: E402

from production_stack_tpu.ops import moe  # noqa: E402

E, K, H, I = 32, 4, 2048, 1792
HBM, PEAK = 819e9, 197e12
REPEAT = 30


def megablox(tiling):
    from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm

    def grouped(lhs, rhs, sizes, base, *, out_dtype=None, impl=None):
        held = lax.dynamic_slice_in_dim(rhs, base, sizes.shape[0], axis=0)
        m, k, n = lhs.shape[0], lhs.shape[1], rhs.shape[2]
        tm, tk, tn = tiling(m, k, n)
        return gmm(lhs, held, sizes, preferred_element_type=out_dtype or lhs.dtype,
                   tiling=(tm, tk, tn))
    return grouped


def candidates():
    own = moe.grouped_matmul
    wide = lambda m, k, n: (min(m, 256), k, moe._tile_cols(n))  # noqa: E731
    out = {
        "ragged_dot": (own, "xla", None),
        "megablox": (megablox(lambda m, k, n: (min(m, 128), 128, 128)), "xla", None),
        "megablox_wide": (megablox(wide), "xla", None),
    }
    for rows in (128, 256, 512):
        out[f"kernel_{rows}"] = (own, "pallas", rows)
    return out


def plain(h, experts, weights, w13, w2):
    """Every expert applied to every token in float32, kept where the token
    chose it: no sorting, no grouping (a token-by-token gather of its four
    experts' weights took 1,000 s at 2,048 tokens)."""
    hp = jax.lax.Precision.HIGHEST
    x = h.astype(jnp.float32)

    def expert(out, e):
        a = jnp.dot(x, w13[e].astype(jnp.float32), precision=hp)
        y = jnp.dot(jax.nn.silu(a[:, :I]) * a[:, I:], w2[e].astype(jnp.float32), precision=hp)
        weight = jnp.sum(jnp.where(experts == e, weights, 0.0), axis=1)
        return out + weight[:, None] * y, None

    return jax.lax.scan(expert, jnp.zeros((h.shape[0], H), jnp.float32), jnp.arange(E))[0]


def main(out_path: str) -> int:
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"moe_choice: JAX runs on {dev.platform!r}, not a TPU")
    ks = jax.random.split(jax.random.key(46), 5)
    w13 = (jax.random.normal(ks[0], (E, H, 2 * I), jnp.float32) * H**-0.5).astype(jnp.bfloat16)
    w2 = (jax.random.normal(ks[1], (E, I, H), jnp.float32) * I**-0.5).astype(jnp.bfloat16)
    router = (jax.random.normal(ks[2], (H, E), jnp.float32) * H**-0.5).astype(jnp.bfloat16)
    bias = jax.random.normal(ks[3], (E,), jnp.float32) * 0.1
    rows = []
    for tokens in (8, 16, 32, 2048):
        h = jax.random.normal(jax.random.fold_in(ks[4], tokens), (tokens, H),
                              jnp.float32).astype(jnp.bfloat16)
        experts, weights = jax.jit(lambda h: moe.route(h, router, bias, K))(h)
        touched = len(set(np.asarray(experts).reshape(-1).tolist()))
        floor_ms = 1e3 * max(touched * 3 * H * I * 2 / HBM, tokens * K * 6 * H * I / PEAK)
        want = np.asarray(jax.jit(plain)(h, experts, weights, w13, w2))
        for name, (grouped, impl, tile) in candidates().items():
            moe.grouped_matmul = grouped
            if tile:
                moe.TILE_ROWS = tile
            # the weights are ARGUMENTS: closed over, they are 0.7 GB of
            # constants in every executable and each compile takes ~40 s
            fn = jax.jit(lambda h, e, w, w13, w2: moe.expert_ffn(
                h, e, w, w13, w2, jnp.int32(0), num_experts=E, impl=impl)[0])
            row = {"tokens": tokens, "candidate": name, "experts_touched": touched,
                   "floor_ms": floor_ms}
            try:
                got = np.asarray(jax.block_until_ready(fn(h, experts, weights, w13, w2)))
                t0 = time.perf_counter()
                for _ in range(REPEAT):
                    y = fn(h, experts, weights, w13, w2)
                jax.block_until_ready(y)
                row["ms"] = 1e3 * (time.perf_counter() - t0) / REPEAT
                row["share_of_floor"] = floor_ms / row["ms"]
                row["max_abs_diff"] = float(np.max(np.abs(got - want)))
            except Exception as e:  # noqa: BLE001 - a candidate the compiler refuses
                row["error"] = f"{type(e).__name__}: {str(e)[:300]}"
            rows.append(row)
            print(json.dumps(row), flush=True)
    os.makedirs(os.path.dirname(os.path.abspath(out_path)), exist_ok=True)
    with open(out_path, "w") as f:
        json.dump({"platform": dev.platform, "device": dev.device_kind,
                   "shape": {"experts": E, "top_k": K, "hidden": H, "width": I},
                   "rows": rows}, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1] if len(sys.argv) > 1 else "chiprun_out/moe_choice.json"))
