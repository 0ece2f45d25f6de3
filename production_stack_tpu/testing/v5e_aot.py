"""Compile for a TPU v5e with no chip attached.

The installed libtpu compiles for a v5e topology it cannot see:
``jax.experimental.topologies.get_topology_desc`` hands back four abstract
``TPU v5 lite`` devices, and ``jax.jit(f).lower(<ShapeDtypeStructs placed on
them>).compile()`` runs the real XLA:TPU + Mosaic pipeline — so a kernel edit
Mosaic refuses, an SMEM/VMEM overflow or a sharding the partitioner rejects
fails here, on the CPU box, in seconds. It is evidence, not a chip run: it
says nothing about numerics or speed (``chip_smoke.py`` phase K checks the
numerics on the chip).

``python -m production_stack_tpu.testing.v5e_aot --out r.json [--slow]`` runs
the matrix tests/test_kernels_compile_v5e.py asserts on, in a process of its
own: describing the topology loads libtpu, which the suite's one long-lived
pytest process has no other use for. The recipe for kernel work is in
.claude/skills/verify/SKILL.md.
"""

from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import math
import sys

import jax
import jax.numpy as jnp
from jax import export as jax_export
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

from production_stack_tpu.parallel import shardings
from production_stack_tpu.parallel.mesh import make_mesh

TOPOLOGY = "v5e:2x2"


@functools.cache
def v5e_devices() -> tuple:
    """The four abstract devices of one v5e host (raises where the installed
    jax/libtpu cannot describe the topology)."""
    from jax.experimental import topologies

    return tuple(
        topologies.get_topology_desc(
            platform="tpu", topology_name=TOPOLOGY
        ).devices
    )


def _on_chip(shape, dtype):
    return jax.ShapeDtypeStruct(
        shape, dtype, sharding=SingleDeviceSharding(v5e_devices()[0])
    )


def compile_decode_kernel(
    *, B=8, NH=32, KH=8, D=128, page=64, max_pages=32, pool_pages=256,
    layers=2, dtype=jnp.bfloat16, quant=False, window=None, cur=8,
):
    """AOT-compile ragged_paged_attention_decode for one v5e chip at one
    (per-shard) attention shape; ``cur`` = burst window entries (0: none)."""
    from production_stack_tpu.ops.pallas.paged_attention import (
        ragged_paged_attention_decode,
    )

    def f(q, kp, vp, pt, lens, layer, kc, vc, cl, ks, vs):
        return ragged_paged_attention_decode(
            q, kp, vp, pt, lens, window, k_cur=kc, v_cur=vc, cur_lens=cl,
            layer=layer[0], k_scales=ks, v_scales=vs,
        )

    pool = _on_chip((layers, pool_pages, page, KH, D), jnp.int8 if quant else dtype)
    win = _on_chip((B, cur, KH, D), dtype) if cur else None
    sc = _on_chip((layers, pool_pages, KH), jnp.float32) if quant else None
    return jax.jit(f).lower(
        _on_chip((B, NH, D), dtype), pool, pool,
        _on_chip((B, max_pages), jnp.int32), _on_chip((B,), jnp.int32),
        _on_chip((1,), jnp.int32), win, win,
        _on_chip((B,), jnp.int32) if cur else None, sc, sc,
    ).compile()


def compile_prefill_kernel(
    *, B=4, T=512, NH=32, KH=8, D=128, page=64, max_pages=32, pool_pages=256,
    layers=2, dtype=jnp.bfloat16, quant=False, window=None, fused=True,
):
    """AOT-compile ragged_paged_attention_prefill for one v5e chip."""
    from production_stack_tpu.ops.pallas.prefill_attention import (
        ragged_paged_attention_prefill,
    )

    def f(q, kp, vp, pt, pos, lens, kc, vc, cl, layer, ks, vs):
        return ragged_paged_attention_prefill(
            q, kp, vp, pt, pos, lens, kc, vc, cl, window, layer=layer[0],
            fused_write=fused, k_scales=ks, v_scales=vs,
        )

    pool = _on_chip((layers, pool_pages, page, KH, D), jnp.int8 if quant else dtype)
    chunk = _on_chip((B, T, KH, D), dtype)
    sc = _on_chip((layers, pool_pages, KH), jnp.float32) if quant else None
    return jax.jit(f, donate_argnums=(1, 2) if fused else ()).lower(
        _on_chip((B, T, NH, D), dtype), pool, pool,
        _on_chip((B, max_pages), jnp.int32), _on_chip((B, T), jnp.int32),
        _on_chip((B,), jnp.int32), chunk, chunk, _on_chip((B,), jnp.int32),
        _on_chip((1,), jnp.int32), sc, sc,
    ).compile()


def compile_ssm_scan(*, B=64, T=1, Di=5120, N=16, layers=26, slots=64):
    """AOT-compile the selective-scan kernel (ops/pallas/ssm_scan.py) for one
    v5e chip: ``T = 1`` is ``ssm_step_decode``, ``T > 1`` ``ssm_scan_prefill``;
    the state pool is donated. Returns (compiled, the kernel's custom-call
    name as the trace will show it)."""
    from production_stack_tpu.ops.pallas import ssm_scan

    def f(u, delta, z, b_mat, c_mat, a, d, pool, slot, first, lens, layer):
        return ssm_scan.selective_scan(
            u, delta, z, b_mat, c_mat, a, d, pool, slot, first, lens, layer[0],
            impl="pallas",
        )

    f32 = jnp.float32
    seq, bc = _on_chip((B, T, Di), f32), _on_chip((B, T, N), f32)
    compiled = jax.jit(f, donate_argnums=(7,)).lower(
        seq, seq, seq, bc, bc, _on_chip((N, Di), f32), _on_chip((Di,), f32),
        _on_chip(ssm_scan.state_pool_shape(layers, slots, N, Di), f32),
        _on_chip((B,), jnp.int32), _on_chip((B,), jnp.bool_),
        _on_chip((B,), jnp.int32), _on_chip((1,), jnp.int32),
    ).compile()
    name = "ssm_step_decode" if T == 1 else "ssm_scan_prefill"
    if f"%{name}" not in compiled.as_text():
        raise AssertionError(f"no custom call named {name} in the compiled program")
    pool_bytes = 4 * math.prod(ssm_scan.state_pool_shape(layers, slots, N, Di))
    if compiled.memory_analysis().alias_size_in_bytes != pool_bytes:
        raise AssertionError("the state pool is not updated in place")
    return compiled


def _step_program(cfg, *, tp, B, T, max_pages, num_pages, page_size,
                  decode_steps, state_slots=64, riders=None):
    """(jitted step, how it was jitted, abstract arguments on a v5e mesh of
    ``tp`` chips) as ModelRunner builds them: a prefill/decode ``step``
    (``decode_steps=0``) or the ``decode_steps``-token deferred burst.
    ``riders``: (rows, page-table width) of the slot of decode rows a prefill
    step takes along (StepInput.riders)."""
    from production_stack_tpu import models
    from production_stack_tpu.engine import runner

    module = models.module_for_config(cfg)
    mesh = make_mesh(tp=tp, devices=v5e_devices())

    def on_mesh(shape, dtype, spec=P()):
        return jax.ShapeDtypeStruct(
            shape, dtype, sharding=NamedSharding(mesh, spec)
        )

    shapes = jax.eval_shape(lambda: module.init_params(cfg, jax.random.key(0)))
    params = jax.tree.map(
        lambda s, spec: on_mesh(s.shape, s.dtype, spec),
        shapes, shardings.param_specs_for(shapes),
    )
    # the pool in the family's own shape (models/lfm2.py merges the kv heads)
    pool = jax.eval_shape(
        lambda: module.init_kv_pages(cfg, num_pages, page_size)
    )[0]
    pool = on_mesh(pool.shape, pool.dtype, shardings.KV_PAGES_SPEC)
    forward = (
        functools.partial(module.forward, mesh=mesh) if tp > 1 else module.forward
    )
    # a family with recurrent state: the pool and the rows' slots ride behind
    # the (empty) slot of a quantised pool's scales, the pool donated
    state = ()
    if hasattr(module, "init_state"):
        pools = jax.eval_shape(lambda: module.init_state(cfg, state_slots))
        state = (jax.tree.map(lambda s: on_mesh(s.shape, s.dtype), pools),
                 on_mesh((B,), jnp.int32))
    row = lambda n: on_mesh((B, n), jnp.int32)  # noqa: E731
    vec = lambda dt: on_mesh((B,), dt)  # noqa: E731
    # the step's key crosses as raw key data (runner._next_key)
    sampling = (vec(jnp.float32), vec(jnp.int32), vec(jnp.float32),
                on_mesh((2,), jnp.uint32))
    rep = NamedSharding(mesh, P())
    if decode_steps:
        program = runner._named_program(
            f"pstpu_multi_step_k{decode_steps}", runner._multi_step_deferred_fn,
            forward, cfg, decode_steps, False, False,
        )
        args = (params, pool, pool, row(1), row(1), row(max_pages),
                vec(jnp.int32), vec(jnp.int32), *sampling)
        outs, scales_at = (rep, rep, None, None), 17
    else:
        program = runner._named_program(
            "pstpu_step", runner._step_fn, forward, cfg, False, False
        )
        args = (params, pool, pool, row(T), row(T), row(max_pages),
                vec(jnp.int32), *sampling)
        outs, scales_at = (rep, None, None, None), 16
    donate = (1, 2)
    if state:
        args = args + (None,) * (scales_at - len(args)) + state
        outs, donate = outs + (None,), (1, 2, scales_at)
    if riders is not None:
        R, r_pages = riders
        slot = lambda *shape, dt=jnp.int32: on_mesh((R, *shape), dt)  # noqa: E731
        args = args + (None,) * (18 - len(args)) + ((
            slot(1), slot(1), slot(r_pages), slot(),
            slot(dt=jnp.float32), slot(), slot(dt=jnp.float32),
            *([slot()] if state else []),   # the riders' state slots
        ),)
    if getattr(cfg, "step_counters", 0):
        outs = outs + (rep,)  # the dispatch's counters, fetched with the tokens
    kw = {"donate_argnums": donate, "out_shardings": outs}
    return jax.jit(program, **kw), kw, args


def compile_step_program(
    cfg, *, tp=1, B=4, T=512, max_pages=64, num_pages=512, page_size=64,
    decode_steps=0, report=None, state_slots=64, riders=None,
):
    """AOT-compile the serving step ModelRunner would dispatch for ``cfg`` on
    a v5e mesh of ``tp`` chips, the way ``runner._dispatch`` runs it: exported,
    serialised, deserialised, and ``jit(exported.call)`` with the pools
    donated. ``cfg.attn_impl`` must already be resolved
    (engine/runner.resolve_attn_impl). A ``report`` dict is filled with what
    tests/test_kernels_compile_v5e.py holds the store's path to: the blob's
    size, the ``tpu_custom_call``s in the plain lowering and in the blob, and
    the executable's aliased bytes against both pools' bytes a chip."""
    from production_stack_tpu.engine import runner

    jitted, kw, args = _step_program(
        cfg, tp=tp, B=B, T=T, max_pages=max_pages, num_pages=num_pages,
        page_size=page_size, decode_steps=decode_steps, state_slots=state_slots,
        riders=riders,
    )
    blob = jax_export.export(jitted, platforms=["tpu"])(*args).serialize()
    exported = jax_export.deserialize(blob)
    compiled = jax.jit(
        runner._named_program(jitted.__name__, exported.call), **kw
    ).lower(*args).compile()
    if report is not None:
        pool = args[1]
        report.update(
            blob_bytes=len(blob),
            custom_calls_plain=jitted.lower(*args).as_text().count(
                "tpu_custom_call"),
            custom_calls_blob=exported.mlir_module().count("tpu_custom_call"),
            alias_bytes=compiled.memory_analysis().alias_size_in_bytes,
            pool_bytes=2 * pool.dtype.itemsize * math.prod(
                pool.sharding.shard_shape(pool.shape)),
            # a family with recurrent state donates that pool too
            state_bytes=sum(
                x.dtype.itemsize * math.prod(x.shape)
                for i in kw["donate_argnums"][2:] for x in jax.tree.leaves(args[i])
            ),
        )
    return compiled


# -- the matrix tests/test_kernels_compile_v5e.py asserts on -------------------

def preset_shapes() -> dict:
    """Unique per-shard attention shapes over every ``llama.PRESETS`` family
    x {one chip, tp=4 shard} x {bf16, int8 pools}: id -> (NH, KH, D, int8).
    (The sliding window is a scalar operand of both kernels, not a shape.)"""
    from production_stack_tpu.models import llama

    seen: dict = {}
    for name, cfg in llama.PRESETS.items():
        for tp in (1, 4):
            if cfg.num_heads % tp or cfg.num_kv_heads % tp:
                continue  # the rule's "heads do not divide tp" branch
            for int8 in (False, True):
                key = (cfg.num_heads // tp, cfg.num_kv_heads // tp,
                       cfg.head_dim, int8)
                seen.setdefault(key, f"{name}/tp{tp}/{'int8' if int8 else 'bf16'}")
    return {v: k for k, v in seen.items()}


# every decode (batch, pages) bucket the benchmark's two cells dispatch
# (PERF_LEDGER.jsonl's breakdowns: bf16[64|32,32,128] in mistral-7b-d16.chat,
# bf16[32|16|8,28,128] in qwen2.5-7b-d14.sessions; contexts to 64 pages), at
# the cells' pool sizes and depths, with the burst window of 8 and the block
# the kernel derives: id -> compile_decode_kernel arguments
CELL_BUCKETS = {
    f"{cell}/b{B}xp{pages}": dict(
        B=B, max_pages=pages, pool_pages=pool_pages, layers=layers, **shape
    )
    for cell, shape, pool_pages, layers, buckets in (
        ("mistral-7b-d16.chat", dict(NH=32, KH=8, window=4096), 953, 16,
         [(B, p) for B in (64, 32) for p in (16, 32, 64)]),
        ("qwen2.5-7b-d14.sessions", dict(NH=28, KH=4, window=None), 2285, 14,
         [(32, 32)] + [(B, 64) for B in (32, 16, 8)]),
    )
    for B, pages in buckets
}


# whole step programs through the store's path (compile_step_program): one
# decode bucket and the prefill step of both cells' widths at their pool
# sizes, and the burst on a four-chip mesh: id -> preset + arguments
STEP_PROGRAMS = {
    "mistral-7b-d16.chat/burst-b64xp64": dict(
        preset="mistral-7b", B=64, max_pages=64, num_pages=953, decode_steps=8),
    # a llama cell's prefill program carries the riders' slot (16 decode
    # rows by max_model_len's pages): the prefill kernel AND the decode one
    "mistral-7b-d16.chat/prefill-b4xt512": dict(
        preset="mistral-7b", B=4, T=512, max_pages=64, num_pages=953,
        riders=(16, 64)),
    "mistral-7b-d16.rag/prefill-b1xt512": dict(
        preset="mistral-7b", B=1, T=512, max_pages=8, num_pages=953,
        riders=(16, 64)),
    "qwen2.5-7b-d14.sessions/burst-b16xp64": dict(
        preset="qwen2.5-7b", B=16, max_pages=64, num_pages=2285, decode_steps=8),
    "qwen2.5-7b-d14.sessions/prefill-b4xt512": dict(
        preset="qwen2.5-7b", B=4, T=512, max_pages=64, num_pages=2285,
        riders=(16, 64)),
    "mistral-7b/tp4/burst-b8xp64": dict(
        preset="mistral-7b", tp=4, B=8, max_pages=64, num_pages=128,
        decode_steps=8),
    # the state family: pools of the attention layers alone + the state pool
    "jamba2-3b.chat/burst-b64xp64": dict(
        preset="jamba2-3b", B=64, max_pages=64, num_pages=4096, decode_steps=8),
    "jamba2-3b.chat/prefill-b4xt512": dict(
        preset="jamba2-3b", B=4, T=512, max_pages=64, num_pages=4096),
    # sparse experts + convolution tails, all 16 layers (two scans): the
    # 10.8 GB of weights and the 2 GB pool have to fit beside the temporaries
    "lfm2-8b-a1b-d16.chat/burst-b32xp64": dict(
        preset="lfm2-8b-a1b-d16", B=32, max_pages=64, num_pages=4096,
        decode_steps=8),
    "lfm2-8b-a1b-d16.chat/prefill-b4xt512": dict(
        preset="lfm2-8b-a1b-d16", B=4, T=512, max_pages=64, num_pages=4096),
    # Mamba-2 mixers with the riders' slot, all 52 blocks (one scan of 23
    # units) at the cell's pools: ``ssd_scan_prefill`` for the chunk AND
    # ``ssd_step_decode`` for the riders in one program, beside the grouped
    # product's two calls
    "nemotron3-nano-30b-ep8.chat/prefill-b4xt512": dict(
        preset="nemotron3-nano-30b-ep8", B=4, T=512, max_pages=64,
        num_pages=1280, state_slots=32, riders=(16, 64)),
}

# the selective scan at the shapes the jamba2-3b.chat cell dispatches
SSM_SCANS = {
    "decode-b64": dict(B=64, T=1),
    "decode-b8": dict(B=8, T=1),
    "prefill-b4xt512": dict(B=4, T=512),
    "prefill-b1xt16": dict(B=1, T=16),
}


def smem_operand_bytes(*, B: int, max_pages: int, NH: int, KH: int) -> int:
    """Bytes of the decode kernel's scalar-prefetch operands as the traced
    ``pallas_call`` holds them (what ``decode_smem_bytes`` must equal)."""
    from production_stack_tpu.ops.pallas.paged_attention import (
        ragged_paged_attention_decode,
    )

    D, page, C = 128, 64, 8
    sd = jax.ShapeDtypeStruct
    pool = sd((2, 8, page, KH, D), jnp.bfloat16)
    win = sd((B, C, KH, D), jnp.bfloat16)
    jaxpr = jax.make_jaxpr(
        lambda q, kp, vp, pt, lens, kc, vc, cl: ragged_paged_attention_decode(
            q, kp, vp, pt, lens, None, k_cur=kc, v_cur=vc, cur_lens=cl, layer=0
        )
    )(sd((B, NH, D), jnp.bfloat16), pool, pool, sd((B, max_pages), jnp.int32),
      sd((B,), jnp.int32), win, win, sd((B,), jnp.int32))

    def calls(jp):
        for eqn in jp.eqns:
            if eqn.primitive.name == "pallas_call":
                yield eqn
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from calls(sub)

    (eqn,) = calls(jaxpr.jaxpr)
    n = eqn.params["grid_mapping"].num_index_operands
    return sum(v.aval.size * v.aval.dtype.itemsize for v in eqn.invars[:n])


# the prefill kernel takes 5-10 s per shape: the tier-1 run compiles the
# widest bf16 one, ``--slow`` the rest (and the SMEM boundary)
TIER1_PREFILL = (32, 8, 128, False)


def _attempt(fn, **kw) -> dict:
    try:
        fn(**kw)
        return {"compiled": True, "error": ""}
    except Exception as e:  # noqa: BLE001 - the compiler's refusal IS the result
        return {"compiled": False, "error": f"{type(e).__name__}: {e}"[:1500]}


def run_matrix(slow: bool = False) -> dict:
    from production_stack_tpu import models
    from production_stack_tpu.engine import runner
    from production_stack_tpu.models import jamba, lfm2, nemotron_h

    out: dict = {"decode": {}, "prefill": {}, "prefill_refused": {},
                 "smem": {}, "step_programs": {}, "ssm_scan": {}}
    for cid, (NH, KH, D, int8) in preset_shapes().items():
        refusal = runner.kernel_refusal(
            head_dim=D, kv_heads_per_shard=KH, pool_itemsize=1 if int8 else 2
        )
        kw = dict(NH=NH, KH=KH, D=D, quant=int8, window=4096)
        if not slow:
            out["decode"][cid] = dict(
                _attempt(compile_decode_kernel, B=8, **kw), refusal=refusal
            )
        if slow and refusal and "sublane tile" in refusal:
            out["prefill_refused"][cid] = _attempt(
                compile_prefill_kernel, B=4, T=512, **kw
            )
        tier1_shape = (NH, KH, D, int8) == TIER1_PREFILL
        if refusal is None and tier1_shape != slow:
            # prefill_batch x prefill_chunk of the default config, fused write
            out["prefill"][cid] = _attempt(
                compile_prefill_kernel, B=4, T=512, **kw
            )
    if not slow:
        for cid, kw in CELL_BUCKETS.items():
            out["decode"][cid] = dict(
                _attempt(compile_decode_kernel, **kw), refusal=None
            )
    if slow:
        for rows in (64, 128):  # 64 x 2048 fits the rule's budget, 128 does not
            out["smem"][str(rows)] = _attempt(
                compile_decode_kernel, B=rows, max_pages=2048, pool_pages=4096
            )
    else:
        for pid, kw in STEP_PROGRAMS.items():
            kw = dict(kw)
            tp = kw.get("tp", 1)
            name = kw.pop("preset")
            module, preset = models.find_preset(name)
            recurrent = hasattr(module, "init_state")
            # depth 2 (the layer scan compiles one layer), the attention
            # path resolved as the engine resolves it on the chip
            attn = runner.resolve_attn_impl(
                "auto", platform="tpu", n_devices=tp,
                fwd_takes_mesh=not recurrent,
                num_heads=preset.num_heads, num_kv_heads=preset.num_kv_heads,
                head_dim=preset.head_dim, tp=tp, pool_itemsize=2,
                max_batch=kw["B"], max_pages=kw["max_pages"],
            )
            if module is lfm2:
                # its whole depth: the scans compile each body once
                cfg = dataclasses.replace(
                    preset, attn_impl=attn.impl, moe_impl="pallas")
            elif module is nemotron_h:
                # its whole depth too; the attention blocks run the XLA path
                # whatever the rule allows (NemotronHConfig.attn_impl)
                cfg = dataclasses.replace(
                    preset, moe_impl="pallas", ssm_impl="pallas")
            else:
                cfg = dataclasses.replace(preset, num_layers=2, attn_impl=attn.impl)
            if module is jamba:
                # one period S S A S: both kinds of layer, two scanned runs
                cfg = dataclasses.replace(
                    cfg, num_layers=4, attn_layer_period=4, attn_layer_offset=2,
                    ssm_impl="pallas",
                )
            report: dict = {}
            out["step_programs"][pid] = dict(
                _attempt(compile_step_program, cfg=cfg, report=report, **kw),
                **report,
            )
        for sid, kw in SSM_SCANS.items():
            out["ssm_scan"][sid] = _attempt(compile_ssm_scan, **kw)
    return out


def main() -> int:
    p = argparse.ArgumentParser("v5e-aot")
    p.add_argument("--out", required=True)
    p.add_argument("--slow", action="store_true",
                   help="the costlier half of the matrix")
    args = p.parse_args()
    try:
        v5e_devices()
    except Exception as e:  # noqa: BLE001 - "cannot describe the topology"
        result = {"unavailable": f"{type(e).__name__}: {e}"[:500]}
    else:
        result = run_matrix(slow=args.slow)
    with open(args.out, "w") as f:
        json.dump(result, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
