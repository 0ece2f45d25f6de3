"""Paged attention for TPU serving — XLA reference implementations.

Design (TPU-first, cf. SURVEY.md §7 "hard parts" #1):

- KV lives in a *page pool* per layer: ``k_pages/v_pages: [num_pages, page_size,
  num_kv_heads, head_dim]`` in HBM. Sequences own pages through an integer
  ``page_table: [batch, max_pages_per_seq]``. All shapes are static under jit;
  the engine buckets batch and context so XLA compiles a handful of programs.
- Writes are flat scatters with ``mode='drop'`` so padded tokens vanish without
  branches (no dynamic control flow inside jit).
- Attention is an online-softmax ("flash") computation scanned over KV blocks,
  GQA-aware (einsum over grouped heads, no materialized head repeat). The same
  code path serves chunked prefill (T tokens against S context) and decode
  (T=1); decode first gathers the sequence's pages into a contiguous [B, S]
  view. A Pallas kernel that streams pages HBM->VMEM without the gather
  replaces this on TPU (ops/pallas/paged_attention.py); this module is the
  always-correct fallback and the unit-test oracle.

Reference behavior being matched: vLLM's PagedAttention + chunked prefill as
configured by the reference stack (helm/templates/deployment-vllm-multi.yaml:128-141
in /root/reference — the stack enables chunked prefill and prefix caching; the
engine must make those real).
"""

from __future__ import annotations

import jax.numpy as jnp
from jax import lax

NEG_INF = -1e30


def _kv_flat_indices(
    page_table: jnp.ndarray,
    positions: jnp.ndarray,
    page_size: int,
    num_pages: int,
) -> jnp.ndarray:
    """Flat pool-slot index per token ([B*T]); invalid tokens (padding, or
    positions beyond the owned pages) route to the out-of-range sentinel
    ``num_pages * page_size`` so scatters drop them."""
    B, T = positions.shape
    max_pages = page_table.shape[1]
    page_idx = positions // page_size
    slot = positions % page_size
    phys = jnp.take_along_axis(
        page_table, jnp.clip(page_idx, 0, max_pages - 1), axis=1
    )
    flat = phys * page_size + slot
    valid = (positions >= 0) & (page_idx < max_pages)
    return jnp.where(valid, flat, num_pages * page_size).reshape(-1)


def stale_kv_positions(
    page_table: jnp.ndarray,
    positions: jnp.ndarray,
    page_size: int,
) -> jnp.ndarray:
    """KV-slot positions for write-after-attend attention: paged slot j holds
    absolute position j while j < the chunk start (slots at/after it are
    stale — the current chunk's K/V ride in-register), then the chunk's own
    positions. Returns [B, S + T] for flash_attention(kv_positions=...)."""
    S = page_table.shape[1] * page_size
    chunk_start = jnp.maximum(positions[:, 0], 0)
    slot_pos = jnp.arange(S, dtype=jnp.int32)[None, :]
    paged_pos = jnp.where(slot_pos < chunk_start[:, None], slot_pos, -1)
    return jnp.concatenate([paged_pos, positions], axis=1)


def burst_kv_positions(
    kv_lens: jnp.ndarray,   # [B] total length incl. the current token
    cur_lens: jnp.ndarray,  # [B] in-register window entries (1..C)
    S: int,                 # paged slots (max_pages * page_size)
    C: int,                 # window capacity
) -> jnp.ndarray:
    """KV-slot positions for deferred-burst attention, [B, S + C]: paged
    slot j holds absolute position j while j < kv_lens - cur_lens (the
    stale boundary — later slots' K/V live in the window instead), and
    window entry j holds position ``kv_lens - cur_lens + j`` for
    j < cur_lens. Shared by the XLA oracle (paged_attention_decode), the
    model fallbacks, and mirrored by the Pallas kernel's masking — keep
    them in lockstep."""
    paged_end = kv_lens - cur_lens
    slot = jnp.arange(S, dtype=jnp.int32)[None, :]
    paged_pos = jnp.where(slot < paged_end[:, None], slot, -1)
    j = jnp.arange(C, dtype=jnp.int32)[None, :]
    win_pos = jnp.where(j < cur_lens[:, None], paged_end[:, None] + j, -1)
    return jnp.concatenate([paged_pos, win_pos], axis=1)


def write_kv_pages(
    k_pages: jnp.ndarray,
    v_pages: jnp.ndarray,
    k_new: jnp.ndarray,
    v_new: jnp.ndarray,
    page_table: jnp.ndarray,
    positions: jnp.ndarray,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Scatter new K/V tokens into the page pool.

    Args:
      k_pages, v_pages: [P, page_size, KH, D] page pools.
      k_new, v_new:     [B, T, KH, D] fresh keys/values for this step.
      page_table:       [B, max_pages] int32 page ids owned by each sequence.
      positions:        [B, T] int32 absolute token positions; -1 marks padding
                        (those writes are dropped).

    Returns updated (k_pages, v_pages). Callers should donate the pools so XLA
    updates them in place.
    """
    P, page_size, KH, D = k_pages.shape
    B, T = positions.shape
    flat = _kv_flat_indices(page_table, positions, page_size, P)
    k_flat = k_pages.reshape(P * page_size, KH, D)
    v_flat = v_pages.reshape(P * page_size, KH, D)
    k_flat = k_flat.at[flat].set(k_new.reshape(B * T, KH, D), mode="drop")
    v_flat = v_flat.at[flat].set(v_new.reshape(B * T, KH, D), mode="drop")
    return k_flat.reshape(k_pages.shape), v_flat.reshape(v_pages.shape)


def write_kv_pages_all_layers(
    k_pages: jnp.ndarray,
    v_pages: jnp.ndarray,
    k_new: jnp.ndarray,
    v_new: jnp.ndarray,
    page_table: jnp.ndarray,
    positions: jnp.ndarray,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """One batched scatter writing EVERY layer's fresh K/V into the stacked
    pools (write-after-attend mode).

    Why: per-layer pool-slice updates inside the layer scan force XLA to
    materialize pool-sized copies each iteration (profiled at ~half the
    decode step on v5e). Writing once, outside the scan, with the pools
    donated, updates in place.

    Args:
      k_pages, v_pages: [L, P, page_size, KH, D] stacked pools.
      k_new, v_new:     [L, B, T, KH, D] per-layer fresh keys/values.
      page_table:       [B, max_pages] int32.
      positions:        [B, T] int32 absolute positions; -1 dropped.
    """
    L, P, page_size, KH, D = k_pages.shape
    B, T = positions.shape
    flat = _kv_flat_indices(page_table, positions, page_size, P)
    if KH == 1:
        # one kv head: scatter [KH, D] ROWS into the pools seen as [L * P *
        # page, KH, D] (a bitcast of the layout the pools arrive in). The
        # [L, KH, D] windows below make XLA:TPU prefer the layer axis beside
        # the head dimension where KH is 1 and L small, and it then relays
        # BOTH POOLS out and back at every dispatch (the v5e compile of a
        # 2-layer, 1-kv-head pool showed two pool-sized copies in, two out)
        slots = P * page_size
        rows = jnp.where(
            flat[None, :] < slots,
            flat[None, :] + (jnp.arange(L, dtype=flat.dtype) * slots)[:, None],
            L * slots,
        ).reshape(-1)

        def put(pool, new):
            return pool.reshape(L * slots, KH, D).at[rows].set(
                new.reshape(L * B * T, KH, D), mode="drop"
            ).reshape(pool.shape)

        return put(k_pages, k_new), put(v_pages, v_new)
    k_flat = k_pages.reshape(L, P * page_size, KH, D)
    v_flat = v_pages.reshape(L, P * page_size, KH, D)
    k_flat = k_flat.at[:, flat].set(k_new.reshape(L, B * T, KH, D), mode="drop")
    v_flat = v_flat.at[:, flat].set(v_new.reshape(L, B * T, KH, D), mode="drop")
    return k_flat.reshape(k_pages.shape), v_flat.reshape(v_pages.shape)


def gather_kv_pages(
    k_pages: jnp.ndarray,
    v_pages: jnp.ndarray,
    page_table: jnp.ndarray,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Gather each sequence's pages into contiguous [B, S, KH, D] views,
    S = max_pages * page_size (bucketed by the scheduler)."""
    P, page_size, KH, D = k_pages.shape
    B, max_pages = page_table.shape
    k = k_pages[page_table]  # [B, max_pages, page_size, KH, D]
    v = v_pages[page_table]
    S = max_pages * page_size
    return k.reshape(B, S, KH, D), v.reshape(B, S, KH, D)


def flash_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    q_positions: jnp.ndarray,
    kv_lens: jnp.ndarray,
    *,
    sm_scale: float | None = None,
    block_size: int = 512,
    window: int | None = None,
    logit_softcap: float | None = None,
    kv_positions: jnp.ndarray | None = None,
) -> jnp.ndarray:
    """Online-softmax attention scanned over KV blocks (GQA-aware).

    Args:
      q:           [B, T, NH, D] queries (chunk of T tokens; T=1 for decode).
      k, v:        [B, S, KH, D] contiguous keys/values (gathered pages).
      q_positions: [B, T] absolute position of each query token; -1 = padding.
      kv_lens:     [B] valid KV length per sequence.
      sm_scale:    softmax scale; defaults to D**-0.5.
      block_size:  KV block per scan step (memory/compute tradeoff).
      window:      sliding-window size (Mistral-style): query at position p sees
                   KV positions (p - window, p]. None = full causal.
      kv_positions: optional [B, S] absolute position of each KV slot, -1 =
                   invalid. When given, visibility is (pos >= 0) & (pos <=
                   q_pos) & window, and ``kv_lens`` is ignored — this lets
                   callers attend over a concatenation of paged KV (slot j at
                   position j) and in-register current-chunk K/V (write-after-
                   attend mode: the pool is stale for the current chunk).

    Returns [B, T, NH, D] in q.dtype. Without kv_positions, KV index j is
    visible to a query at position p iff j <= p and j < kv_len.
    """
    B, T, NH, D = q.shape
    S, KH = k.shape[1], k.shape[2]
    G = NH // KH
    scale = sm_scale if sm_scale is not None else D**-0.5

    bs = min(block_size, S)
    num_blocks = -(-S // bs)
    pad = num_blocks * bs - S
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        if kv_positions is not None:
            kv_positions = jnp.pad(
                kv_positions, ((0, 0), (0, pad)), constant_values=-1
            )

    qf = (q.astype(jnp.float32) * scale).reshape(B, T, KH, G, D)
    kb = k.reshape(B, num_blocks, bs, KH, D).transpose(1, 0, 2, 3, 4)
    vb = v.reshape(B, num_blocks, bs, KH, D).transpose(1, 0, 2, 3, 4)
    pb = (
        None
        if kv_positions is None
        else kv_positions.reshape(B, num_blocks, bs).transpose(1, 0, 2)
    )

    m0 = jnp.full((B, T, KH, G), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, T, KH, G), jnp.float32)
    acc0 = jnp.zeros((B, T, KH, G, D), jnp.float32)

    def body(carry, inputs):
        m, l, acc, start = carry[0], carry[1], carry[2], carry[3]
        if pb is None:
            kblk, vblk = inputs
        else:
            kblk, vblk, posblk = inputs
        kf = kblk.astype(jnp.float32)
        scores = jnp.einsum("btkgd,bskd->btkgs", qf, kf)  # [B,T,KH,G,bs]
        if logit_softcap is not None:
            # Gemma-2: soft-bound scores to (-cap, cap) before masking
            scores = logit_softcap * jnp.tanh(scores / logit_softcap)
        if pb is None:
            idx = start + jnp.arange(bs)
            visible = (idx[None, None, :] <= q_positions[:, :, None]) & (
                idx[None, None, :] < kv_lens[:, None, None]
            )  # [B, T, bs]
            if window is not None:
                visible &= idx[None, None, :] > q_positions[:, :, None] - window
        else:
            pos = posblk[:, None, :]  # [B, 1, bs]
            visible = (pos >= 0) & (pos <= q_positions[:, :, None])
            if window is not None:
                visible &= pos > q_positions[:, :, None] - window
        scores = jnp.where(visible[:, :, None, None, :], scores, NEG_INF)
        m_new = jnp.maximum(m, scores.max(axis=-1))
        # Guard exp(NEG_INF - NEG_INF) for fully masked rows.
        alpha = jnp.exp(jnp.minimum(m - m_new, 0.0))
        p = jnp.exp(scores - m_new[..., None])
        p = jnp.where(visible[:, :, None, None, :], p, 0.0)
        l_new = l * alpha + p.sum(axis=-1)
        acc_new = acc * alpha[..., None] + jnp.einsum(
            "btkgs,bskd->btkgd", p, vblk.astype(jnp.float32)
        )
        return (m_new, l_new, acc_new, start + bs), None

    xs = (kb, vb) if pb is None else (kb, vb, pb)
    (m, l, acc, _), _ = lax.scan(body, (m0, l0, acc0, jnp.int32(0)), xs)
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.reshape(B, T, NH, D).astype(q.dtype)


def paged_attention_decode(
    q: jnp.ndarray,
    k_pages: jnp.ndarray,
    v_pages: jnp.ndarray,
    page_table: jnp.ndarray,
    seq_lens: jnp.ndarray,
    *,
    sm_scale: float | None = None,
    window=None,
    logit_softcap: float | None = None,
    k_cur: jnp.ndarray | None = None,   # [B, C, KH, D] in-register burst K/V
    v_cur: jnp.ndarray | None = None,
    cur_lens: jnp.ndarray | None = None,  # [B] valid window entries (1..C)
    k_scales: jnp.ndarray | None = None,  # [P, KH] f32 (int8 pools, ops/quant.py)
    v_scales: jnp.ndarray | None = None,
) -> jnp.ndarray:
    """Decode-step attention: one query token per sequence against its pages.

    q: [B, NH, D]; returns [B, NH, D]. XLA reference path (gather + flash);
    the Pallas kernel streams pages directly and skips the gather.

    With ``k_cur/v_cur`` (write-after-attend), pool slots at positions >=
    seq_lens - cur_lens are stale; window entry j holds the token at
    absolute position ``seq_lens - cur_lens + j`` (valid for j < cur_lens).
    A fused decode burst defers ALL its KV scatters this way: the pool stays
    read-only through the burst and the accumulated burst tokens ride in the
    window (runner._multi_step_fn).

    With ``k_scales/v_scales`` the pools are int8 and the gather
    dequantizes (ops/quant.py contract) — the oracle for the kernel's
    in-ring dequant; ``k_cur/v_cur`` stay fp.
    """
    if k_scales is not None:
        from production_stack_tpu.ops.quant import gather_kv_pages_quant

        k, v = gather_kv_pages_quant(
            k_pages, v_pages, k_scales, v_scales, page_table, dtype=q.dtype
        )
    else:
        k, v = gather_kv_pages(k_pages, v_pages, page_table)
    if k_cur is not None:
        B, C = k_cur.shape[0], k_cur.shape[1]
        if cur_lens is None:
            cur_lens = jnp.ones((B,), jnp.int32)
        kv_positions = burst_kv_positions(seq_lens, cur_lens, k.shape[1], C)
        k = jnp.concatenate([k, k_cur.astype(k.dtype)], axis=1)
        v = jnp.concatenate([v, v_cur.astype(v.dtype)], axis=1)
    else:
        kv_positions = None
    out = flash_attention(
        q[:, None],
        k,
        v,
        q_positions=(seq_lens - 1)[:, None],
        kv_lens=seq_lens,
        sm_scale=sm_scale,
        window=window,
        logit_softcap=logit_softcap,
        kv_positions=kv_positions,
    )
    return out[:, 0]


def burst_attention(q, kc, vc, k_win, v_win, kv_pos, q_pos, KH: int):
    """Decode attention of one token a row, XLA path, for pools that store a
    token's kv heads SIDE BY SIDE in one row of KH * D lanes (a head_dim
    under 128 lanes, which ``runner.kernel_refusal`` keeps off the kernels:
    models/lfm2.py). ``q`` [B, 1, NH, D]; ``kc`` / ``vc`` [B, S, KH * D] the
    row's gathered pages; ``k_win`` / ``v_win`` [B, C, KH * D] the burst's
    window; ``kv_pos`` [B, S + C] is ``burst_kv_positions``; ``q_pos`` [B,
    1]. Softmax over pages and window together, each read ONCE and never
    relaid out: a query head meets the whole row through a copy of itself
    that is zero outside its own kv head's D lanes (KH times the products,
    which are nothing beside the bytes). ``flash_attention`` concatenates,
    pads, blocks and transposes its keys and values: seven passes over a
    context that a [64, 4096] bucket makes 0.27 GB a layer (PERF.md section
    6, PR 46). Returns [B, 1, NH, D]."""
    B, _, NH, D = q.shape
    S = kc.shape[1]
    f32 = jnp.float32
    own = (jnp.arange(NH)[:, None] // (NH // KH) == jnp.arange(KH)[None, :])
    own = own[None, :, :, None]                                  # [1, NH, KH, 1]
    wide = jnp.where(own, (q[:, 0].astype(f32) * D**-0.5)[:, :, None, :], 0.0)
    wide = wide.reshape(B, NH, KH * D).astype(kc.dtype)
    seen = ((kv_pos >= 0) & (kv_pos <= q_pos[:, :1]))[:, None, :]
    scores = jnp.concatenate([
        jnp.einsum("bhc,bsc->bhs", wide, kc, preferred_element_type=f32),
        jnp.einsum("bhc,bsc->bhs", wide, k_win, preferred_element_type=f32),
    ], axis=-1)
    scores = jnp.where(seen, scores, NEG_INF)
    p = jnp.where(seen, jnp.exp(scores - jnp.max(scores, axis=-1, keepdims=True)), 0.0)
    out = (
        jnp.einsum("bhs,bsc->bhc", p[..., :S].astype(vc.dtype), vc,
                   preferred_element_type=f32)
        + jnp.einsum("bhs,bsc->bhc", p[..., S:].astype(vc.dtype), v_win,
                     preferred_element_type=f32)
    ) / jnp.maximum(jnp.sum(p, axis=-1), 1e-30)[..., None]
    out = jnp.sum(jnp.where(own, out.reshape(B, NH, KH, D), 0.0), axis=2)
    return out[:, None].astype(q.dtype)
