"""Ring attention: sequence-parallel exact attention over the ``sp`` mesh axis.

Long-context support the reference lacks entirely (SURVEY.md §2.3: "No ring
attention / Ulysses / context parallel anywhere in the tree") but the TPU
build treats as first-class: when one sequence's KV exceeds a chip's HBM, the
sequence is sharded over ``sp`` and KV blocks rotate around the ring via
``lax.ppermute`` while every device accumulates online-softmax partials for
its local queries. Compute and the KV transfer for the *next* step overlap
(XLA schedules the ppermute concurrently with the attention matmuls), so at
the steady state the ring adds no wall-clock over local attention — the
blockwise-parallel / ring-attention construction (Liu et al.; PAPERS.md).

All collectives are XLA ``ppermute`` over ICI neighbours — no NCCL, no
point-to-point runtime (the reference's NIXL/Ray have no role here).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


NEG_INF = -1e30


def _online_block(qf, k, v, visible, m, l, acc):
    """One online-softmax accumulation step of q against a KV block.

    qf:      [B, T, KH, G, D] f32 (pre-scaled)
    k, v:    [B, S, KH, D]
    visible: [B, T, S] bool
    m, l:    [B, T, KH, G] f32 running max / denominator
    acc:     [B, T, KH, G, D] f32 running numerator
    """
    kf = k.astype(jnp.float32)
    scores = jnp.einsum("btkgd,bskd->btkgs", qf, kf)
    scores = jnp.where(visible[:, :, None, None, :], scores, NEG_INF)
    m_new = jnp.maximum(m, scores.max(axis=-1))
    alpha = jnp.exp(jnp.minimum(m - m_new, 0.0))
    p = jnp.exp(scores - m_new[..., None])
    p = jnp.where(visible[:, :, None, None, :], p, 0.0)
    l_new = l * alpha + p.sum(axis=-1)
    acc_new = acc * alpha[..., None] + jnp.einsum(
        "btkgs,bskd->btkgd", p, v.astype(jnp.float32)
    )
    return m_new, l_new, acc_new


def ring_attention_local(
    q: jnp.ndarray,            # [B, Tl, NH, D] local query shard
    k: jnp.ndarray,            # [B, Sl, KH, D] local KV shard
    v: jnp.ndarray,            # [B, Sl, KH, D]
    q_positions: jnp.ndarray,  # [B, Tl] global positions; -1 = padding
    kv_lens: jnp.ndarray | None,  # [B] global valid KV length (offset mode)
    kv_positions: jnp.ndarray | None = None,  # [B, Sl] explicit positions
    *,
    axis_name: str = "sp",
    sm_scale: float | None = None,
) -> jnp.ndarray:
    """Per-shard body — call inside shard_map/pjit over ``axis_name``.

    Device i initially holds KV block i (global offset i*Sl). Each of the
    ``sp`` steps attends local queries to the currently-held block, then
    rotates the block to the next ring neighbour.

    With ``kv_positions`` the block's global positions are explicit (slots
    with position -1 are invisible) and rotate around the ring alongside K/V —
    the serving path uses this because page-pool gathers interleave stale
    pool slots and in-register chunk K/V, so slot index != global position.
    """
    B, Tl, NH, D = q.shape
    Sl, KH = k.shape[1], k.shape[2]
    G = NH // KH
    scale = sm_scale if sm_scale is not None else D**-0.5
    sp = lax.axis_size(axis_name)
    my = lax.axis_index(axis_name)
    perm = [(i, (i + 1) % sp) for i in range(sp)]

    qf = (q.astype(jnp.float32) * scale).reshape(B, Tl, KH, G, D)
    m = jnp.full((B, Tl, KH, G), NEG_INF, jnp.float32)
    l = jnp.zeros((B, Tl, KH, G), jnp.float32)
    acc = jnp.zeros((B, Tl, KH, G, D), jnp.float32)

    def step(carry, step_idx):
        m, l, acc, k, v, kvp = carry
        if kvp is not None:
            # explicit-position semantics match flash_attention's: a slot is
            # visible iff its position is valid (>= 0) and causal; kv_lens is
            # not consulted (invalid slots carry -1)
            visible = (kvp[:, None, :] <= q_positions[:, :, None]) & (
                kvp[:, None, :] >= 0
            )
        else:
            src = (my - step_idx) % sp      # who this block belongs to
            offset = src * Sl               # its global position offset
            idx = offset + jnp.arange(Sl)
            visible = (idx[None, None, :] <= q_positions[:, :, None]) & (
                idx[None, None, :] < kv_lens[:, None, None]
            )
        m, l, acc = _online_block(qf, k, v, visible, m, l, acc)
        # rotate the KV block while the next step's math is scheduled
        k = lax.ppermute(k, axis_name, perm)
        v = lax.ppermute(v, axis_name, perm)
        if kvp is not None:
            kvp = lax.ppermute(kvp, axis_name, perm)
        return (m, l, acc, k, v, kvp), None

    (m, l, acc, _, _, _), _ = lax.scan(
        step, (m, l, acc, k, v, kv_positions), jnp.arange(sp)
    )
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.reshape(B, Tl, NH, D).astype(q.dtype)


def ring_attention_serving(
    mesh: Mesh,
    q: jnp.ndarray,            # [B, T, NH, D] prefill chunk queries
    k: jnp.ndarray,            # [B, S, KH, D] gathered pool + chunk KV
    v: jnp.ndarray,
    q_positions: jnp.ndarray,  # [B, T] global positions, -1 pad
    kv_positions: jnp.ndarray,  # [B, S] per-slot global positions, -1 invalid
    *,
    axis_name: str = "sp",
    sm_scale: float | None = None,
) -> jnp.ndarray:
    """Sequence-parallel prefill attention inside the jitted serving step.

    Visibility comes from ``kv_positions`` alone (slot visible iff position
    >= 0 and <= query position) — matching flash_attention's explicit-
    positions semantics, which ignore kv_lens.

    Partial-manual shard_map: only ``sp`` is mapped — dp/tp shardings of
    the batch/head axes keep flowing through GSPMD automatically, so this
    composes with tensor parallelism without explicit specs. T and S pad up
    to multiples of sp (padded KV slots get position -1 => invisible; padded
    queries get position -1 => discarded rows).
    """
    sp = mesh.shape[axis_name]
    B, T = q.shape[:2]
    S = k.shape[1]
    pad_t, pad_s = (-T) % sp, (-S) % sp
    if pad_t:
        q = jnp.pad(q, ((0, 0), (0, pad_t), (0, 0), (0, 0)))
        q_positions = jnp.pad(q_positions, ((0, 0), (0, pad_t)), constant_values=-1)
    if pad_s:
        k = jnp.pad(k, ((0, 0), (0, pad_s), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_s), (0, 0), (0, 0)))
        kv_positions = jnp.pad(
            kv_positions, ((0, 0), (0, pad_s)), constant_values=-1
        )
    def fn(q, k, v, q_positions, kv_positions):
        return ring_attention_local(
            q, k, v, q_positions, None, kv_positions,
            axis_name=axis_name, sm_scale=sm_scale,
        )

    # when nested inside another partial-manual shard_map (e.g. the pp layer
    # pipeline), the context mesh is an AbstractMesh with that axis already
    # Manual — shard_map requires the matching mesh object, not the concrete
    # one we were constructed with
    ctx = jax.sharding.get_abstract_mesh()
    if not ctx.empty and ctx.manual_axes:
        mesh = ctx
    seq = P(None, axis_name, None, None)
    pos_spec = P(None, axis_name)
    out = jax.shard_map(
        fn,
        mesh=mesh,
        axis_names={axis_name},
        in_specs=(seq, seq, seq, pos_spec, pos_spec),
        out_specs=seq,
        check_vma=False,
    )(q, k, v, q_positions, kv_positions)
    return out[:, :T]


def ring_attention(
    mesh: Mesh,
    q: jnp.ndarray,            # [B, T, NH, D] global
    k: jnp.ndarray,            # [B, S, KH, D] global
    v: jnp.ndarray,
    q_positions: jnp.ndarray,  # [B, T]
    kv_lens: jnp.ndarray,      # [B]
    *,
    axis_name: str = "sp",
    sm_scale: float | None = None,
) -> jnp.ndarray:
    """Convenience wrapper: shard T/S over ``axis_name`` (heads over ``tp`` if
    the mesh has it) and run the ring. Output sharding matches q."""
    head_axis = "tp" if "tp" in mesh.axis_names and mesh.shape["tp"] > 1 else None
    qspec = P(None, axis_name, head_axis, None)
    kvspec = P(None, axis_name, head_axis, None)
    fn = functools.partial(
        ring_attention_local, axis_name=axis_name, sm_scale=sm_scale
    )
    shard_fn = jax.shard_map(
        fn,
        mesh=mesh,
        in_specs=(qspec, kvspec, kvspec, P(None, axis_name), P(None)),
        out_specs=qspec,
        check_vma=False,
    )
    return shard_fn(q, k, v, q_positions, kv_lens)
