"""Pipeline parallelism over the ``pp`` mesh axis — multi-host stage execution
without Ray.

The reference runs pipeline parallelism by provisioning a KubeRay cluster and
passing ``--pipeline-parallel-size`` to vLLM (/root/reference
helm/templates/ray-cluster.yaml:515-566; tutorials/15-basic-pipeline-parallel.md).
Here PP is a mesh axis: layers shard over ``pp`` (each device holds a
contiguous stage of the layer stack), microbatches flow stage-to-stage via
``lax.ppermute`` over ICI/DCN, and the whole schedule is one jitted SPMD
program — JAX's multi-controller runtime replaces the Ray choreography
(SURVEY.md §7 hard part #4).

Schedule: GPipe-style fill-drain. With M microbatches and S stages the scan
runs M + S - 1 ticks; device s is active on ticks [s, s + M). Bubble fraction
(S-1)/(M+S-1) — callers pick M >= 4*S for serving prefill.
"""

from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P



def pipeline_local(
    stage_fn: Callable,
    stage_params,
    microbatches: jnp.ndarray,  # [M, ...mb shape...] (replicated)
    *,
    axis_name: str = "pp",
):
    """Per-shard GPipe schedule — call inside shard_map over ``axis_name``.

    ``stage_fn(stage_params, x) -> y`` runs this device's slice of the layer
    stack; ``stage_params`` is the local stage's shard (layer axis already
    split by shard_map). Returns the final-stage outputs, [M, ...] on every
    device (psum-broadcast at the end).
    """
    S = lax.axis_size(axis_name)
    s = lax.axis_index(axis_name)
    M = microbatches.shape[0]
    T = M + S - 1
    perm = [(i, i + 1) for i in range(S - 1)]

    buf = jnp.zeros_like(microbatches[0])
    outs = jnp.zeros_like(microbatches)

    def tick(carry, t):
        buf, outs = carry
        mb_idx = jnp.clip(t - s, 0, M - 1)
        active = (t >= s) & (t - s < M)
        # stage 0 injects fresh microbatches; others consume the ppermuted buf
        x_in = jnp.where(s == 0, microbatches[jnp.clip(t, 0, M - 1)], buf)
        y = stage_fn(stage_params, x_in)
        y = jnp.where(active, y, jnp.zeros_like(y))
        # last stage records its (active) output
        outs = jnp.where(
            active & (s == S - 1),
            lax.dynamic_update_index_in_dim(outs, y, mb_idx, 0),
            outs,
        )
        # ship activations to the next stage (last stage sends nothing)
        buf = lax.ppermute(y, axis_name, perm)
        return (buf, outs), None

    (_, outs), _ = lax.scan(tick, (buf, outs), jnp.arange(T))
    # broadcast final outputs from the last stage to every device
    outs = lax.psum(jnp.where(s == S - 1, outs, jnp.zeros_like(outs)), axis_name)
    return outs


def pipeline_forward(
    mesh: Mesh,
    stage_fn: Callable,
    params,                    # pytree; every leaf's leading axis = num layers
    microbatches: jnp.ndarray, # [M, ...]
    *,
    axis_name: str = "pp",
):
    """Shard ``params``' layer axis over ``axis_name`` and run the pipeline.

    ``stage_fn(stage_params, x)`` sees the local ``layers/S``-sized stack —
    typically a ``lax.scan`` over its layers.
    """
    fn = functools.partial(pipeline_local, stage_fn, axis_name=axis_name)
    pspec = jax.tree.map(lambda _: P(axis_name), params)
    shard_fn = jax.shard_map(
        fn,
        mesh=mesh,
        in_specs=(pspec, P()),
        out_specs=P(),
        check_vma=False,
    )
    return shard_fn(params, microbatches)


def serving_layer_pipeline(
    mesh: Mesh,
    layer: Callable,
    x: jnp.ndarray,        # [B, T, H] embedded activations
    aux,                   # pytree of [B, ...] per-sequence tensors
    scan_xs,               # (layers, k_pages, v_pages, lora_layers) - [L, ...]
    *,
    axis_name: str = "pp",
):
    """GPipe schedule for the serving forward: the layer stack (and each
    layer's KV pool pages) shards into contiguous stages over ``axis_name``;
    microbatches over the batch dim relay stage-to-stage via ``ppermute``.

    Partial-manual shard_map: only ``axis_name`` is mapped, so the dp/sp/ep/tp
    GSPMD shardings of activations/params keep flowing automatically inside
    the body — PP composes with TP without explicit specs (the reference
    reaches the same pairing via Ray + vLLM, ray-cluster.yaml:560-566).

    ``layer`` is the model's scan body: ``layer((x, aux), (lp, kp, vp, ll)) ->
    ((x', aux), (k_new, v_new))`` (write-after-attend mode — pools read-only
    inside, per-layer chunk K/V out). Returns (x_final [B, T, H], (k_new,
    v_new) [L, B, T, KH, D] with L sharded over ``axis_name``).
    """
    pp = mesh.shape[axis_name]
    B, T, H = x.shape
    # microbatch count: enough to keep stages busy (bubble (S-1)/(M+S-1)),
    # bounded by the batch; B and pp are powers of two in serving buckets
    M = min(B, 2 * pp)
    while B % M:
        M -= 1
    mb = B // M
    layers, k_pages, v_pages, ll = scan_xs

    def body(x, aux, layers, kp, vp, ll):
        S = lax.axis_size(axis_name)
        s = lax.axis_index(axis_name)
        perm = [(i, i + 1) for i in range(S - 1)]
        KH, D = kp.shape[3], kp.shape[4]
        Ll = jax.tree.leaves(layers)[0].shape[0]
        xs = x.reshape(M, mb, T, H)
        aux_mb = jax.tree.map(lambda a: a.reshape(M, mb, *a.shape[1:]), aux)
        Tt = M + S - 1

        buf = jnp.zeros((mb, T, H), x.dtype)
        outs = jnp.zeros((M, mb, T, H), x.dtype)
        k_out = jnp.zeros((M, Ll, mb, T, KH, D), kp.dtype)
        v_out = jnp.zeros((M, Ll, mb, T, KH, D), vp.dtype)

        def tick(carry, t):
            buf, k_out, v_out, outs = carry
            mb_i = jnp.clip(t - s, 0, M - 1)
            active = (t >= s) & (t - s < M)
            x_in = jnp.where(s == 0, xs[jnp.clip(t, 0, M - 1)], buf)
            a = jax.tree.map(
                lambda z: lax.dynamic_index_in_dim(z, mb_i, 0, keepdims=False),
                aux_mb,
            )
            (y, _), (k_new, v_new) = lax.scan(layer, (x_in, a), (layers, kp, vp, ll))
            y = jnp.where(active, y, jnp.zeros_like(y))
            k_out = jnp.where(
                active,
                lax.dynamic_update_index_in_dim(k_out, k_new, mb_i, 0),
                k_out,
            )
            v_out = jnp.where(
                active,
                lax.dynamic_update_index_in_dim(v_out, v_new, mb_i, 0),
                v_out,
            )
            outs = jnp.where(
                active & (s == S - 1),
                lax.dynamic_update_index_in_dim(outs, y, mb_i, 0),
                outs,
            )
            # relay activations to the next stage (overlaps with next tick).
            # The relay runs in f32: XLA:CPU miscompiles bf16 collectives
            # under partially-manual shard_map (upcast is lossless, and on
            # TPU the extra convert fuses away).
            buf = lax.ppermute(
                y.astype(jnp.float32), axis_name, perm
            ).astype(y.dtype)
            return (buf, k_out, v_out, outs), None

        (_, k_out, v_out, outs), _ = lax.scan(
            tick, (buf, k_out, v_out, outs), jnp.arange(Tt)
        )
        # final activations live on the last stage; broadcast to all (f32:
        # see the relay note above)
        outs = lax.psum(
            jnp.where(s == S - 1, outs.astype(jnp.float32),
                      jnp.zeros(outs.shape, jnp.float32)),
            axis_name,
        ).astype(x.dtype)
        x_final = outs.reshape(B, T, H)
        # [M, Ll, mb, ...] -> [Ll, B, ...] (B split as m*mb + r)
        k_new = k_out.transpose(1, 0, 2, 3, 4, 5).reshape(Ll, B, T, KH, D)
        v_new = v_out.transpose(1, 0, 2, 3, 4, 5).reshape(Ll, B, T, KH, D)
        return x_final, k_new, v_new

    lead = P(axis_name)
    layer_specs = jax.tree.map(lambda _: lead, layers)
    ll_specs = None if ll is None else jax.tree.map(lambda _: lead, ll)
    aux_specs = jax.tree.map(lambda _: P(), aux)
    x_final, k_new, v_new = jax.shard_map(
        body,
        mesh=mesh,
        axis_names={axis_name},
        in_specs=(P(), aux_specs, layer_specs, lead, lead, ll_specs),
        out_specs=(P(), lead, lead),
        check_vma=False,
    )(x, aux, layers, k_pages, v_pages, ll)
    return x_final, (k_new, v_new)
