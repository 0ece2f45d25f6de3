#!/usr/bin/env python3
"""Which KIND of block of `models/nemotron_h.py` drifts from the reference, one
block at a time: every Mamba-2 and expert block of the program is given the
REFERENCE's own residual stream at its input (float32, one sequence, one
chunk) and its contribution `f(norm(x))` is compared with the reference's, so
no error is carried from block to block. Run it on the chip:

    chiprun -- python3 scripts/nemotron_block_errors.py <out.json> [--tokens=N] <seed> ...

Prints, a block, the relative error (norm of the difference over norm of the
reference's contribution) of the program as served (`pallas` kernels on a
TPU), and for a Mamba-2 block also with the recurrence in float32
`jax.numpy` (what is left is everything but the
SSD kernel's own rounding). NEMOTRON_CONTROL_TOY=1 rehearses on the CPU.
"""
import dataclasses
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "perfbench"), os.path.join(ROOT, "scripts")]

from nemotron_lowprec_control import TOY  # noqa: E402
from reference import nemotron_h as ref  # noqa: E402

from production_stack_tpu.models import nemotron_h as nh  # noqa: E402
from production_stack_tpu.ops import moe  # noqa: E402
from production_stack_tpu.ops.pallas.ssm_scan import resolve_ssm_impl  # noqa: E402


def main(argv):
    out_path, words = argv[0], argv[1:]
    tokens = 512
    doc = json.load(open(os.path.join(ROOT, "perfbench", "configs", "nemotron3-nano-30b-ep8.json")))
    if os.environ.get("NEMOTRON_CONTROL_TOY"):
        doc = dict(doc, **TOY)
    platform = jax.default_backend()
    base = dataclasses.replace(nh.NemotronHConfig.from_hf_config(doc), max_model_len=4096)
    served = dataclasses.replace(
        base, ssm_impl=resolve_ssm_impl(platform)[0], moe_impl=moe.resolve_moe_impl(platform))
    exact = dataclasses.replace(served, ssm_impl="xla")
    s = ref.settings(doc)
    frozen = tuple(sorted(s.items()))
    rows = []
    for word in words:
        if word.startswith("--tokens="):
            tokens = int(word.split("=", 1)[1])
            continue
        seed = int(word)
        params = jax.jit(lambda k: nh.init_params(base, k))(jax.random.key(seed % (2**31 - 1)))
        ids = jnp.asarray([256] + [int(t) for t in np.random.default_rng(seed).integers(32, 127, tokens - 1)])
        positions = jnp.arange(tokens, dtype=jnp.int32)[None]
        row = nh._rows(positions, jnp.zeros((1,), jnp.int32))
        valid = row["valid"]
        mp = params["moe_layers"]

        # (the parameters are ARGUMENTS: a jitted closure over them would lower
        # 12 GB of constants through the host)
        @jax.jit
        def mixer(params, x, i):
            out, _ = nh._ssd_mixer(x[None], nh._at(params["ssm_layers"], i), served,
                                   nh.init_state(served, 1), i, row)
            return out[0]

        @jax.jit
        def mixer_exact(params, x, i):
            out, _ = nh._ssd_mixer(x[None], nh._at(params["ssm_layers"], i), exact,
                                   nh.init_state(exact, 1), i, row)
            return out[0]

        @jax.jit
        def experts(params, x, i):
            mp = params["moe_layers"]
            # (the stacks seen flat INSIDE the program: a bitcast there, a copy outside)
            flat = tuple(mp[n].reshape((-1,) + mp[n].shape[2:]) for n in ("w1", "w2"))
            return nh._moe_layer(x[None], mp, flat, served, i, valid, served.moe_impl)[0][0]

        seen = {"M": 0, "*": 0, "E": 0}
        with jax.default_matmul_precision("highest"):
            x = ref._f32(params["embed"][ids])
        for at, kind in enumerate(s["pattern"]):
            group = params[ref._BLOCKS[kind][1]]
            lp = {name: a[seen[kind]] for name, a in group.items()}
            with jax.default_matmul_precision("highest"):
                nxt = ref._block(x, lp, kind, frozen)
            want = np.asarray(nxt - x, np.float64)
            rel = lambda got: float(  # noqa: E731
                np.linalg.norm(np.asarray(got, np.float64) - want) / np.linalg.norm(want))
            i = jnp.int32(seen[kind])
            entry = {"seed": seed, "block": at, "kind": kind, "stream_rms": float(jnp.sqrt(jnp.mean(x * x)))}
            if kind == "M":
                entry.update(served=rel(mixer(params, x, i)),
                             exact_recurrence=rel(mixer_exact(params, x, i)))
            elif kind == "E":
                entry.update(served=rel(experts(params, x, i)))
            rows.append(entry)
            print(json.dumps(entry), flush=True)
            seen[kind] += 1
            x = nxt
        del params
    os.makedirs(os.path.dirname(os.path.abspath(out_path)), exist_ok=True)
    with open(out_path, "w") as f:
        json.dump({"platform": platform, "tokens": tokens, "rows": rows}, f, indent=1)
    for kind, key in (("M", "served"), ("M", "exact_recurrence"), ("E", "served")):
        r = [e[key] for e in rows if e["kind"] == kind]
        print(f"{kind} {key}: median {np.median(r):.5f} max {max(r):.5f} over {len(r)} blocks")


if __name__ == "__main__":
    main(sys.argv[1:])
