"""Pallas TPU kernel: ragged flash prefill over paged KV (v2) + fused
paged-KV write.

Why v2: chunked prefill throughput fell with context on the path v1 shared
with XLA (the records that showed it predate the current chip attachment and
are gone; the v2 kernel's own speed is not measured — PERF.md). The v1 kernel
kept the decode-v1 memory structure the decode kernel already abandoned (PR 3):

1. **Dense grid.** v1 ran grid = (B, n_qb, n_page_blocks) over the page
   BUCKET: a 1k-token history in a 32k bucket still executed ~500 dead
   (query-block x kv-block) cells whose BlockSpec fetches refetched the
   last page. v2 derives each (sequence, query-block)'s LIVE kv-block count
   from ``kv_lens``/``cur_lens`` (and the sliding window, per query block)
   on the host side, packs live cells into a 1D grid, and pads with no-op
   cells that alias the last live cell — prefill cost scales with each
   sequence's REAL history, so mixed 1k/16k batches cost the sum of their
   real work, and a 32k prompt's later chunks pay for 32k once, not
   bucket x chunks.

2. **Page-granular matmuls.** v1 fetched N pages per cell as N separate
   BlockSpec inputs and folded each page separately: a 64-slot score matmul
   fragments the MXU (measured XLA-parity on v5e — the kernel's whole
   advantage vanished into per-page overhead). v2 leaves the pools in HBM
   (``memory_space=ANY``) and drives a manually multi-buffered VMEM ring of
   page copies (``pltpu.make_async_copy``, ``prefill_prefetch_pages``
   deep): N pages land CONTIGUOUSLY in a ring slot and fold as ONE wide
   [KH, TQ, N*page] matmul — the "contiguous-KV variant" the v1 notes
   called the path to a win. Copies stay in flight across cell boundaries,
   so the HBM pipeline never drains between cells.

3. **Fused paged-KV write.** The chunk's own K/V used to ride the layer
   scan as stacked outputs and get committed by a separate post-scan
   scatter (``write_kv_pages_all_layers``): write the stack, read it back,
   scatter into the pool — 3 HBM traversals of the chunk's KV per step.
   With ``fused_write=True`` the kernel writes the chunk's K/V into its
   pool pages directly from VMEM (the pools are aliased input->output), so
   the chunk's KV crosses HBM once. Interior pages are single page-sized
   DMAs; a partial head/tail page (unaligned chunk start, or a chunk end
   mid-page) is read-modify-written so untouched slots keep their exact
   old bytes — tests assert the pool is bit-identical to the scatter path.

Masking model is unchanged from v1 (ops/attention.stale_kv_positions):
paged slot s holds absolute position s and is valid while s < paged_end =
kv_lens[b] - cur_lens[b]; the chunk's K/V ride in-register and fold at each
query block's last cell. Fused-write contract (and the causal block-skip):
valid chunk entries are CONTIGUOUS — entry j sits at position paged_end + j
— which is how the engine's scheduler builds every prefill chunk
(scheduler._plan_prefill). Upper-triangle chunk sub-blocks (entries no
query in the block can see) are skipped by a dynamic loop bound, so they
cost nothing.

Equivalent role in the reference: vLLM's CUDA prefill (flash-attn) kernels
inside the engine image; PAPERS "Ragged Paged Attention" is the direct
blueprint. Tests assert equivalence against the XLA oracle
(tests/test_pallas_prefill.py); the achieved page-streaming HBM GB/s on
the chip is not measured: no benchmark cell is prefill-bound (PERF.md).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
_FOLD_BLOCK = 128  # chunk-fold sub-block (CB): one score tensor's S extent


def _prefill_kernel(
    # scalar prefetch
    pt_ref,      # [B, max_pages] int32 page table
    lens_ref,    # [B] int32 kv lengths (chunk end)
    cl_ref,      # [B] int32 chunk sizes (valid in-register entries)
    win_ref,     # [1] int32 window (huge = full causal)
    layer_ref,   # [1] int32 layer into stacked pools
    seq_ref,     # [NC] packed cell -> batch row
    qb_ref,      # [NC] packed cell -> query block
    blk_ref,     # [NC] packed cell -> kv block within the (b, qb) live range
    cnt_ref,     # [B*n_qb] live cell count per (b, qb) (>= 1)
    lopg_ref,    # [B*n_qb] first live page (window start) per (b, qb)
    livepg_ref,  # [B*n_qb] live page count per (b, qb) — the packing's
                 # source of truth; the kernel must never re-derive it
    total_ref,   # [1] total live cells
    # inputs
    q_ref,       # [1, TQ, NH, D] (current (b, qb) block)
    pos_ref,     # [1, TQ, 1] int32 query positions (-1 pad)
    kp_hbm,      # [L, P, page, KH, D], memory_space=ANY (stays in HBM)
    vp_hbm,
    kc_ref,      # [1, Cw, KH, D] chunk K/V, front-padded by fp_pad slots
    vc_ref,
    cpos_ref,    # [1, n_cb, CB] chunk entry positions per fold sub-block
                 # (-1 pad), NOT front-padded: row ci = entries ci*CB..
    *refs,       # [ks_ref, vs_ref (quantized: [1, P, KH] f32 scale slabs),]
                 # o_ref [, kp_out, vp_out [, o_ksc, o_vsc]], then scratch
                 # (see wrapper)
    sm_scale: float,
    kv_heads: int,
    logit_softcap: float | None,
    pages_per_block: int,
    ring_blocks: int,
    n_qb: int,
    fused_write: bool,
    fp_pad: int,
    max_write_pages: int,
    quantized: bool = False,
):
    N = pages_per_block
    RB = ring_blocks
    i0 = 0
    if quantized:
        # int8 pools (ops/quant.py contract): the current layer's [P, KH]
        # scale slabs are VMEM-resident (constant index map, fetched once);
        # each cell's N ring pages dequantize right before the wide fold,
        # and the fused write QUANTIZES the chunk in-kernel so fp chunk KV
        # never crosses HBM either
        ks_ref, vs_ref = refs[0], refs[1]
        i0 = 2
    o_ksc = o_vsc = None
    if fused_write and quantized:
        (o_ref, kp_out, vp_out, o_ksc, o_vsc, k_buf, v_buf, ksem, vsem,
         wk_sem, wv_sem, rk_sem, rv_sem, wbuf_k, wbuf_v,
         qg_ref, m_ref, l_ref, acc_ref) = refs[i0:]
        kp_src, vp_src = kp_out, vp_out  # aliased with kp_hbm/vp_hbm
    elif fused_write:
        (o_ref, kp_out, vp_out, k_buf, v_buf, ksem, vsem,
         wk_sem, wv_sem, rk_sem, rv_sem, wbuf_k, wbuf_v,
         qg_ref, m_ref, l_ref, acc_ref) = refs[i0:]
        kp_src, vp_src = kp_out, vp_out  # aliased with kp_hbm/vp_hbm
    else:
        (o_ref, k_buf, v_buf, ksem, vsem,
         qg_ref, m_ref, l_ref, acc_ref) = refs[i0:]
        kp_src, vp_src = kp_hbm, vp_hbm
    KB = k_buf.shape[1]
    page_size = KB // N
    max_pages = pt_ref.shape[1]
    n_cells = seq_ref.shape[0]
    TQ, NH, D = q_ref.shape[1], q_ref.shape[2], q_ref.shape[3]
    KH = kv_heads
    G = NH // KH
    lyr = layer_ref[0]

    c = pl.program_id(0)
    total = total_ref[0]
    live = c < total
    b = seq_ref[c]
    qb = qb_ref[c]
    p = blk_ref[c]
    r = b * n_qb + qb

    def _pid_of(g):
        """Pool page id for global page stream index g (clamped for dead
        cells) — the quantized fold uses it to look up each page's scale."""
        cc = jnp.minimum(g // N, n_cells - 1)
        bb = seq_ref[cc]
        rr = bb * n_qb + qb_ref[cc]
        pi = blk_ref[cc] * N + g % N
        return pt_ref[bb, jnp.minimum(lopg_ref[rr] + pi, max_pages - 1)]

    def _copies(g):
        """DMA descriptors + go/no-go predicate for global page stream index
        g = cell*N + i. A page is fetched iff its cell is live and the page
        lies inside the cell's (b, qb) live range — the SAME predicate gates
        start and wait, so semaphore counts always pair. Page i of cell cc
        lands at offset i*page within ring slot cc % RB: the cell's N pages
        are CONTIGUOUS in VMEM and fold as one wide matmul."""
        cc = jnp.minimum(g // N, n_cells - 1)
        bb = seq_ref[cc]
        rr = bb * n_qb + qb_ref[cc]
        pi = blk_ref[cc] * N + g % N
        ok = (g < total * N) & (pi < livepg_ref[rr])
        pid = _pid_of(g)
        slot = cc % RB
        off = (g % N) * page_size
        s = g % (RB * N)
        kcp = pltpu.make_async_copy(
            kp_src.at[lyr, pid], k_buf.at[slot, pl.ds(off, page_size)],
            ksem.at[s],
        )
        vcp = pltpu.make_async_copy(
            vp_src.at[lyr, pid], v_buf.at[slot, pl.ds(off, page_size)],
            vsem.at[s],
        )
        return ok, kcp, vcp

    def _start(g):
        ok, kcp, vcp = _copies(g)

        @pl.when(ok)
        def _():
            kcp.start()
            vcp.start()

    @pl.when(live & (p == 0))
    def _():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)
        # per-GQA-group query scratch (see v1 notes: group-major scratch lets
        # the fold index groups dynamically from a fori_loop, and Mosaic
        # rejects the minor-dim collapse a row-packed layout would need)
        q4 = (
            q_ref[0] * jnp.asarray(sm_scale, q_ref.dtype)
        ).reshape(TQ, KH, G, D)
        for g in range(G):
            qg_ref[g] = q4[:, :, g].transpose(1, 0, 2)  # [KH, TQ, D]

    @pl.when(c == 0)
    def _():
        # warm-up: fill the ring's first RB-1 block slots; steady state below
        # tops off block c+RB-1 while consuming block c, so up to (RB-1)*N
        # page DMAs stay in flight across cell boundaries
        for g in range((RB - 1) * N):
            _start(jnp.int32(g))

    paged_end = lens_ref[b] - cl_ref[b]
    pos_q = pos_ref[0][None]  # [1, TQ, 1]: query positions ride sublanes
    win = win_ref[0]

    def fold(k, v, kv_pos, valid):
        """One online-softmax update; k/v [KH, S, D], kv_pos/valid
        [1, 1, S] (KV slots ride lanes — Mosaic has no rank-1 -> rank-3
        shape cast, so every mask is built rank-3 from the start).

        Groups run under a fori_loop, NOT a Python loop: every unrolled fold
        would get its own scoped-vmem stack for the [KH, TQ, S] f32 score
        temporaries, while a loop body compiles once and reuses one stack
        (v1's measured 26 MB-vs-16 MB lesson). Inputs stay in their own
        dtype (bf16 in production: MXU-native)."""
        vis = (
            valid
            & (kv_pos <= pos_q)
            & (pos_q >= 0)
            & (kv_pos > pos_q - win)
        )  # [1, TQ, S]

        def gbody(g, carry):
            s = lax.dot_general(
                qg_ref[g], k, (((2,), (2,)), ((0,), (0,))),
                preferred_element_type=jnp.float32,
            )  # [KH, TQ, S]
            if logit_softcap is not None:
                s = logit_softcap * jnp.tanh(s / logit_softcap)
            s = jnp.where(vis, s, NEG_INF)
            m_prev, l_prev = m_ref[g], l_ref[g]
            m_new = jnp.maximum(m_prev, s.max(axis=-1))
            alpha = jnp.exp(jnp.minimum(m_prev - m_new, 0.0))
            pij = jnp.exp(s - m_new[..., None])
            pij = jnp.where(vis, pij, 0.0)
            m_ref[g] = m_new
            l_ref[g] = l_prev * alpha + pij.sum(axis=-1)
            pv = lax.dot_general(
                pij.astype(v.dtype), v, (((2,), (1,)), ((0,), (0,))),
                preferred_element_type=jnp.float32,
            )  # [KH, TQ, D]
            acc_ref[g] = acc_ref[g] * alpha[..., None] + pv
            return carry

        lax.fori_loop(0, G, gbody, 0)

    # ---- paged KV: top off the ring, then ONE wide fold over the cell ----
    @pl.when(live)
    def _():
        for i in range(N):
            _start(c * N + i + (RB - 1) * N)
        for i in range(N):
            ok_i, kcp, vcp = _copies(c * N + i)

            @pl.when(ok_i)
            def _():
                kcp.wait()
                vcp.wait()

        @pl.when(p * N < livepg_ref[r])
        def _():
            slot = c % RB
            kb = k_buf[slot]
            vb = v_buf[slot]
            if quantized:
                # dequant at the ring exit: one [N, KH] scale block gathered
                # from the resident slab, broadcast over each page's slots
                sk = jnp.stack([ks_ref[0, _pid_of(c * N + i)] for i in range(N)])
                sv = jnp.stack([vs_ref[0, _pid_of(c * N + i)] for i in range(N)])
                kb = (
                    kb.astype(jnp.float32).reshape(N, page_size, KH, D)
                    * sk[:, None, :, None]
                ).reshape(KB, KH, D)
                vb = (
                    vb.astype(jnp.float32).reshape(N, page_size, KH, D)
                    * sv[:, None, :, None]
                ).reshape(KB, KH, D)
            k = kb.transpose(1, 0, 2)  # [KH, KB, D]
            v = vb.transpose(1, 0, 2)
            start = (lopg_ref[r] + p * N) * page_size
            idx = start + lax.broadcasted_iota(jnp.int32, (1, 1, KB), 2)
            # slots of pages beyond the live range hold stale ring bytes;
            # the validity bound (idx >= paged_end there) masks their
            # scores, but v must ALSO be sanitized: 0 * garbage in the
            # pij @ v matmul is NaN when the never-fetched slot is NaN
            row_ok = (
                start + lax.broadcasted_iota(jnp.int32, (1, KB, 1), 1)
                < paged_end
            )
            v = jnp.where(row_ok, v, jnp.zeros_like(v))
            fold(k, v, idx, idx < paged_end)

    # ---- fused paged-KV write: once per row, at its first cell ----------
    if fused_write and quantized:
        ps = page_size

        @pl.when(live & (qb == 0) & (p == 0) & (cl_ref[b] > 0))
        def _():
            s0 = paged_end              # chunk start (contiguous contract)
            e0 = s0 + cl_ref[b]
            lp0 = s0 // ps
            # quantize-in-kernel (ops/quant.py contract): FRESH pages
            # (page_start >= s0 — slot 0 is this chunk's) get scale =
            # amax/127 and fully-defined content (zeros beyond the chunk
            # end); the rare non-aligned HEAD page (page_start < s0, holds
            # this row's earlier tokens) keeps its OLD scale and clips new
            # tokens into it — rescaling it here would rewrite bytes the
            # SAME invocation's ring reads race against (scheduler chunks
            # are page-aligned in practice: prefill_chunk % page_size == 0,
            # so this path only runs for odd configs). New scales land in
            # the o_ksc/o_vsc output blocks; the wrapper scatters them into
            # the scales pool (a few KB — the page BYTES still cross HBM
            # exactly once, in int8).
            for j in range(max_write_pages):
                page_start = (lp0 + j) * ps
                pid = pt_ref[b, jnp.minimum(lp0 + j, max_pages - 1)]
                any_w = (page_start < e0) & (page_start + ps > s0)
                fresh = page_start >= s0
                src = page_start - s0 + fp_pad

                @pl.when(any_w)
                def _(j=j, page_start=page_start, pid=pid, src=src,
                      fresh=fresh):
                    gidx = page_start + lax.broadcasted_iota(
                        jnp.int32, (ps, 1, 1), 0
                    )
                    keep = (gidx >= s0) & (gidx < e0)
                    xk = jnp.where(
                        keep, kc_ref[0, pl.ds(src, ps)].astype(jnp.float32), 0.0
                    )
                    xv = jnp.where(
                        keep, vc_ref[0, pl.ds(src, ps)].astype(jnp.float32), 0.0
                    )
                    want_k = jnp.maximum(
                        jnp.max(jnp.abs(xk), axis=(0, 2)) / 127.0, 1e-8
                    )
                    want_v = jnp.maximum(
                        jnp.max(jnp.abs(xv), axis=(0, 2)) / 127.0, 1e-8
                    )
                    ns_k = jnp.where(fresh, want_k, ks_ref[0, pid])
                    ns_v = jnp.where(fresh, want_v, vs_ref[0, pid])
                    o_ksc[0, j] = ns_k
                    o_vsc[0, j] = ns_v
                    qk = jnp.clip(
                        jnp.round(xk / ns_k[None, :, None]), -127, 127
                    ).astype(wbuf_k.dtype)
                    qv = jnp.clip(
                        jnp.round(xv / ns_v[None, :, None]), -127, 127
                    ).astype(wbuf_v.dtype)

                    @pl.when(fresh)
                    def _():
                        wbuf_k[...] = qk
                        wbuf_v[...] = qv

                    @pl.when(~fresh)
                    def _():
                        # head page: read-modify-write; untouched slots keep
                        # their exact old bytes (old scale unchanged)
                        rk = pltpu.make_async_copy(
                            kp_out.at[lyr, pid], wbuf_k, rk_sem
                        )
                        rv = pltpu.make_async_copy(
                            vp_out.at[lyr, pid], wbuf_v, rv_sem
                        )
                        rk.start()
                        rv.start()
                        rk.wait()
                        rv.wait()
                        wbuf_k[...] = jnp.where(keep, qk, wbuf_k[...])
                        wbuf_v[...] = jnp.where(keep, qv, wbuf_v[...])

                    # single staging buffer: the write must land before the
                    # next page's quantization reuses it
                    wk = pltpu.make_async_copy(
                        wbuf_k, kp_out.at[lyr, pid], wk_sem.at[j]
                    )
                    wv = pltpu.make_async_copy(
                        wbuf_v, vp_out.at[lyr, pid], wv_sem.at[j]
                    )
                    wk.start()
                    wv.start()
                    wk.wait()
                    wv.wait()

    elif fused_write:
        ps = page_size

        @pl.when(live & (qb == 0) & (p == 0) & (cl_ref[b] > 0))
        def _():
            s0 = paged_end              # chunk start (contiguous contract)
            e0 = s0 + cl_ref[b]
            lp0 = s0 // ps

            def page_preds(j):
                page_start = (lp0 + j) * ps
                pid = pt_ref[b, jnp.minimum(lp0 + j, max_pages - 1)]
                any_w = (page_start < e0) & (page_start + ps > s0)
                full = (page_start >= s0) & (page_start + ps <= e0)
                src = page_start - s0 + fp_pad  # offset into padded chunk
                return page_start, pid, any_w, full, src

            # interior pages: one page-sized DMA straight from the chunk's
            # VMEM block; starts all go out first, waits batch below
            for j in range(max_write_pages):
                _, pid, any_w, full, src = page_preds(j)

                @pl.when(any_w & full)
                def _(j=j, pid=pid, src=src):
                    pltpu.make_async_copy(
                        kc_ref.at[0, pl.ds(src, ps)], kp_out.at[lyr, pid],
                        wk_sem.at[j],
                    ).start()
                    pltpu.make_async_copy(
                        vc_ref.at[0, pl.ds(src, ps)], vp_out.at[lyr, pid],
                        wv_sem.at[j],
                    ).start()

            # partial head/tail pages (at most one of each): read-modify-
            # write so slots outside [s0, e0) keep their exact old bytes —
            # bit-identical to the scatter path's dropped writes
            for j in range(max_write_pages):
                page_start, pid, any_w, full, src = page_preds(j)

                @pl.when(any_w & ~full)
                def _(j=j, page_start=page_start, pid=pid, src=src):
                    rk = pltpu.make_async_copy(
                        kp_out.at[lyr, pid], wbuf_k, rk_sem
                    )
                    rv = pltpu.make_async_copy(
                        vp_out.at[lyr, pid], wbuf_v, rv_sem
                    )
                    rk.start()
                    rv.start()
                    rk.wait()
                    rv.wait()
                    gidx = page_start + lax.broadcasted_iota(
                        jnp.int32, (ps, 1, 1), 0
                    )
                    keep = (gidx >= s0) & (gidx < e0)
                    wbuf_k[...] = jnp.where(
                        keep, kc_ref[0, pl.ds(src, ps)], wbuf_k[...]
                    )
                    wbuf_v[...] = jnp.where(
                        keep, vc_ref[0, pl.ds(src, ps)], wbuf_v[...]
                    )
                    wk = pltpu.make_async_copy(
                        wbuf_k, kp_out.at[lyr, pid], wk_sem.at[j]
                    )
                    wv = pltpu.make_async_copy(
                        wbuf_v, vp_out.at[lyr, pid], wv_sem.at[j]
                    )
                    wk.start()
                    wv.start()
                    wk.wait()
                    wv.wait()

            for j in range(max_write_pages):
                _, pid, any_w, full, src = page_preds(j)

                @pl.when(any_w & full)
                def _(j=j, pid=pid, src=src):
                    pltpu.make_async_copy(
                        kc_ref.at[0, pl.ds(src, ps)], kp_out.at[lyr, pid],
                        wk_sem.at[j],
                    ).wait()
                    pltpu.make_async_copy(
                        vc_ref.at[0, pl.ds(src, ps)], vp_out.at[lyr, pid],
                        wv_sem.at[j],
                    ).wait()

    # ---- last cell of (b, qb): fold the chunk, write the output ---------
    @pl.when(live & (p == cnt_ref[r] - 1))
    def _():
        CB = _FOLD_BLOCK

        @pl.when(qb * TQ < cl_ref[b])
        def _():
            # causal block-skip: entries past the block's last query are
            # invisible (positions are contiguous), so the loop bound is
            # min(cl, (qb+1)*TQ) — fully-masked upper-triangle sub-blocks
            # never execute
            bound = jnp.minimum(cl_ref[b], (qb + 1) * TQ)
            n_sub = pl.cdiv(bound, CB)

            def cbody(ci, carry):
                c0 = fp_pad + ci * CB
                kc = kc_ref[0, pl.ds(c0, CB)].transpose(1, 0, 2)
                vc = vc_ref[0, pl.ds(c0, CB)].transpose(1, 0, 2)
                cpos = cpos_ref[0, pl.ds(ci, 1), :][None]  # [1, 1, CB]
                fold(kc, vc, cpos, cpos >= 0)        # -1 pad = invisible
                return carry

            lax.fori_loop(0, n_sub, cbody, 0)

        out = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)[..., None]
        # [G, KH, TQ, D] -> [TQ, NH, D] with h = kh*G + g
        out = out.transpose(2, 1, 0, 3).reshape(TQ, NH, D)
        o_ref[0] = out.astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=(
        "sm_scale", "logit_softcap", "interpret", "pages_per_block",
        "prefetch_pages", "q_block", "fused_write",
    ),
)
def ragged_paged_attention_prefill(
    q: jnp.ndarray,          # [B, T, NH, D] chunk queries
    k_pages: jnp.ndarray,    # [P, page, KH, D] or [L, P, page, KH, D]
    v_pages: jnp.ndarray,
    page_table: jnp.ndarray, # [B, max_pages] int32
    positions: jnp.ndarray,  # [B, T] int32 absolute query positions, -1 pad
    kv_lens: jnp.ndarray,    # [B] int32 chunk-end lengths
    k_cur: jnp.ndarray,      # [B, T, KH, D] the chunk's K/V (post-write mode)
    v_cur: jnp.ndarray,
    cur_lens: jnp.ndarray,   # [B] valid chunk entries
    window=None,
    *,
    sm_scale: float | None = None,
    logit_softcap: float | None = None,
    interpret: bool = False,
    pages_per_block: int | None = None,
    prefetch_pages: int | None = None,
    q_block: int = 128,
    layer: jnp.ndarray | int | None = None,
    fused_write: bool = False,
    k_scales: jnp.ndarray | None = None,  # [P, KH] or [L, P, KH] f32 (int8)
    v_scales: jnp.ndarray | None = None,
):
    """Chunked-prefill attention over paged KV + in-register chunk K/V (v2).

    With ``k_scales/v_scales`` (int8 pools, ops/quant.py contract) the ring
    pages dequantize right before each cell's wide fold — half the HBM
    bytes per chunk — and ``fused_write=True`` quantizes the chunk's K/V
    in-kernel (fresh pages get amax/127 scales; a non-page-aligned head
    page clips into its existing scale), returning
    ``(out, k_pages, v_pages, k_scales, v_scales)``. ``k_cur/v_cur`` must
    arrive fp (they are the quantizer's input).

    Write-after-attend contract (ops/attention.stale_kv_positions): pool
    slots at positions >= kv_lens - cur_lens are stale — the chunk's K/V
    arrive in ``k_cur/v_cur`` and fold in at each query block's last cell.
    Valid chunk entries must be CONTIGUOUS and position-sorted: entry j
    holds position ``kv_lens - cur_lens + j`` for j < cur_lens (how the
    scheduler builds every chunk). Returns [B, T, NH, D] in q.dtype —
    matches the XLA oracle in interpret mode (tests assert equivalence at
    2e-5 in f32; the fold order differs, so output agreement is numerical
    — only the fused-write POOL contents are bit-identical, vs the
    scatter path).

    ``pages_per_block``: KV pages landed contiguously per packed grid cell
    (auto: ~512 KV slots), folded as ONE wide matmul — this is what fixes
    the v1 page-granular MXU fragmentation.

    ``prefetch_pages``: page DMAs kept in flight ahead of the cell being
    consumed (auto: ~2 cells' worth within a ~4 MB VMEM budget per pool
    array). Ring depth in cells is ``1 + ceil(prefetch/pages_per_block)``.

    ``fused_write=True``: additionally scatters the chunk's K/V into its
    pool pages from inside the kernel (pools aliased input->output) and
    returns ``(out, k_pages, v_pages)`` — replacing the post-scan
    ``write_kv_pages_all_layers`` pass on the prefill path. Untouched pool
    slots (before the chunk start, after the chunk end, other rows' pages)
    keep their exact old bytes.

    The grid is RAGGED: live (sequence, query-block, kv-block) cells pack
    to the front of a 1D grid sized for the bucket's worst case; trailing
    dead cells alias the last live cell (no DMA, no compute). Sliding
    windows shrink each query block's live page RANGE, not just the mask,
    so a 4k-window chunk at 128k context streams ~window bytes.
    """
    B, T, NH, D = q.shape
    quantized = k_scales is not None
    squeeze = k_pages.ndim == 4
    if squeeze:
        k_pages = k_pages[None]
        v_pages = v_pages[None]
        if quantized and k_scales.ndim == 2:
            k_scales = k_scales[None]
            v_scales = v_scales[None]
        layer = 0
    L, P, page_size, KH, _ = k_pages.shape
    max_pages = page_table.shape[1]
    G = NH // KH
    scale = sm_scale if sm_scale is not None else D**-0.5
    if pages_per_block is None:
        # ~512 contiguous KV slots per cell: wide enough to keep the MXU's
        # 128-lane S dim busy, small enough that the f32 score temporaries
        # ([KH, TQ, KB]) stay a few MB. int8 pools double the target —
        # half the ring bytes per slot buys a wider fold for the same VMEM
        # (the f32 score temporaries grow, hence x2 not x4; neither target
        # has been swept on this chip: ROADMAP S4 / S7 need a cell first)
        target = 1024 if quantized else 512
        pages_per_block = max(1, min(target // page_size, max_pages))
    N = max(1, min(pages_per_block, max_pages))
    KB = N * page_size
    n_blocks = -(-max_pages // N)
    if prefetch_pages is None:
        prefetch_pages = 2 * N  # two cells ahead
    block_bytes = KB * KH * D * jnp.dtype(k_pages.dtype).itemsize
    RB = max(2, 1 + -(-int(prefetch_pages) // N))
    RB = min(RB, max(2, (4 << 20) // max(block_bytes, 1)))
    TQ = min(q_block, T)
    n_qb = -(-T // TQ)
    if n_qb * TQ != T:
        pad = n_qb * TQ - T
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
        positions = jnp.pad(positions, ((0, 0), (0, pad)), constant_values=-1)
    # chunk buffer layout: [fp_pad front | T entries | tail pad]. The front
    # pad (one page) makes every fused-write source slice a non-negative
    # fixed-size offset even for an unaligned head page; the tail pad covers
    # the last page's overhang and rounds to whole fold sub-blocks.
    CB = _FOLD_BLOCK
    FP = page_size
    # tail must cover both the fold's whole-CB sub-block slices (from FP)
    # and the fused write's last-page overhang (T + page_size from FP)
    Cw = FP + -(-(T + page_size) // CB) * CB
    # the chunk buffer stays fp under int8 pools: it is both the fold's
    # in-register operand and the fused quantizer's input
    chunk_dt = q.dtype if quantized else k_pages.dtype
    kc = jnp.zeros((B, Cw, KH, D), chunk_dt)
    vc = jnp.zeros((B, Cw, KH, D), chunk_dt)
    kc = lax.dynamic_update_slice(
        kc, k_cur.astype(chunk_dt), (0, FP, 0, 0)
    )
    vc = lax.dynamic_update_slice(
        vc, v_cur.astype(chunk_dt), (0, FP, 0, 0)
    )
    cl = jnp.asarray(cur_lens, jnp.int32)
    # chunk entry positions, one [CB] row per fold sub-block. A (1, TQ) or
    # (1, Cw) block of a [B, .] array breaks the TPU block rule (last two
    # block dims divisible by (8, 128) or equal to the array's) once B > 1,
    # so both position operands carry their block as whole trailing dims.
    Tc = k_cur.shape[1]
    n_cb = -(-Tc // CB)
    cpos = jnp.where(
        (lax.broadcasted_iota(jnp.int32, (B, Tc), 1) < cl[:, None])
        & (positions[:, :Tc] >= 0),
        positions[:, :Tc],
        -1,
    )
    cpos = jnp.pad(
        cpos, ((0, 0), (0, n_cb * CB - Tc)), constant_values=-1
    ).reshape(B, n_cb, CB)
    win = (
        jnp.full((1,), 2**30, jnp.int32)
        if window is None
        else jnp.asarray(window, jnp.int32).reshape(1)
    )
    lyr = jnp.asarray(layer, jnp.int32).reshape(1)
    MAXW = -(-T // page_size) + 1  # pool pages one chunk can touch

    # ---- ragged cell maps: pack live (b, qb, kv-block) cells ------------
    lens32 = kv_lens.astype(jnp.int32)
    pe = lens32 - cl                                   # [B] paged_end
    qbi = jnp.arange(n_qb, dtype=jnp.int32)
    # earliest valid query of block qb sits at position pe + qb*TQ; its
    # window opens the live page range at (that - win + 1) — later query
    # blocks of a windowed model skip early pages entirely
    qstart = pe[:, None] + qbi[None, :] * TQ           # [B, n_qb]
    lo = jnp.clip(qstart - win[0] + 1, 0, None)
    lo_pg = lo // page_size                            # [B, n_qb]
    hi_pg = -(-jnp.maximum(pe, 0) // page_size)        # [B]
    live_pg = jnp.maximum(hi_pg[:, None] - lo_pg, 0)   # [B, n_qb]
    qlive = (qbi[None, :] * TQ) < cl[:, None]
    live_pg = jnp.where(qlive, live_pg, 0)
    # every (b, qb) keeps >= 1 cell so padded rows / dead query blocks
    # still initialize and write their (zero) output block
    cells = jnp.clip(-(-live_pg // N), 1, n_blocks).astype(jnp.int32)
    rflat = cells.reshape(-1)                          # [B*n_qb]
    Rn = B * n_qb
    cs = jnp.cumsum(rflat).astype(jnp.int32)
    starts = cs - rflat
    n_cells = Rn * n_blocks
    cidx = jnp.arange(n_cells, dtype=jnp.int32)
    total = cs[Rn - 1]
    rrow = jnp.minimum(
        jnp.searchsorted(cs, cidx, side="right").astype(jnp.int32), Rn - 1
    )
    dead = cidx >= total
    # dead cells alias the LAST live cell: index maps repeat, so the
    # pipeline neither fetches nor writes for them
    seq_of = jnp.where(dead, B - 1, rrow // n_qb)
    qb_of = jnp.where(dead, n_qb - 1, rrow % n_qb)
    blk_of = jnp.where(dead, rflat[Rn - 1] - 1, cidx - starts[rrow])
    total_arr = cs[Rn - 1:Rn]

    NS = 12  # scalar-prefetch operand count

    def qrow(c, *refs):
        so, qo = refs[5], refs[6]
        return (so[c], qo[c], 0, 0)

    def prow(c, *refs):
        so, qo = refs[5], refs[6]
        return (so[c], qo[c], 0)

    def crow(c, *refs):
        return (refs[5][c], 0, 0, 0)

    def crow2(c, *refs):
        return (refs[5][c], 0, 0)

    def scrow(c, *refs):
        # scale slabs: the CURRENT layer's whole [P, KH] slice — constant
        # block index, so the pipeline fetches it once
        return (refs[4][0], 0, 0)

    def oscrow(c, *refs):
        return (refs[5][c], 0, 0)

    in_specs = [
        pl.BlockSpec((1, TQ, NH, D), qrow),
        pl.BlockSpec((1, TQ, 1), prow),
        pl.BlockSpec(memory_space=pl.ANY),
        pl.BlockSpec(memory_space=pl.ANY),
        # explicit VMEM: the fused write DMAs pages straight out of these
        # blocks, and a DMA source needs a definite memory space
        pl.BlockSpec((1, Cw, KH, D), crow, memory_space=pltpu.VMEM),
        pl.BlockSpec((1, Cw, KH, D), crow, memory_space=pltpu.VMEM),
        pl.BlockSpec((1, n_cb, CB), crow2),
    ]
    operands = [q, positions[..., None], k_pages, v_pages, kc, vc, cpos]
    if quantized:
        in_specs += [
            pl.BlockSpec((1, P, KH), scrow),
            pl.BlockSpec((1, P, KH), scrow),
        ]
        operands += [k_scales, v_scales]
    out_shapes = [jax.ShapeDtypeStruct((B, n_qb * TQ, NH, D), q.dtype)]
    out_specs = [pl.BlockSpec((1, TQ, NH, D), qrow)]
    scratch = [
        pltpu.VMEM((RB, KB, KH, D), k_pages.dtype),
        pltpu.VMEM((RB, KB, KH, D), v_pages.dtype),
        pltpu.SemaphoreType.DMA((RB * N,)),
        pltpu.SemaphoreType.DMA((RB * N,)),
    ]
    io_aliases = {}
    if fused_write:
        out_shapes += [
            jax.ShapeDtypeStruct(k_pages.shape, k_pages.dtype),
            jax.ShapeDtypeStruct(v_pages.shape, v_pages.dtype),
        ]
        out_specs += [
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ]
        # operand index counts scalar prefetch: pools sit at NS+2 / NS+3
        io_aliases = {NS + 2: 1, NS + 3: 2}
        if quantized:
            # per-row new scales for the (<= MAXW) written pages; the
            # wrapper scatters them into the scales pool after the call
            out_shapes += [
                jax.ShapeDtypeStruct((B, MAXW, KH), jnp.float32),
                jax.ShapeDtypeStruct((B, MAXW, KH), jnp.float32),
            ]
            out_specs += [
                pl.BlockSpec((1, MAXW, KH), oscrow),
                pl.BlockSpec((1, MAXW, KH), oscrow),
            ]
        scratch += [
            pltpu.SemaphoreType.DMA((MAXW,)),
            pltpu.SemaphoreType.DMA((MAXW,)),
            pltpu.SemaphoreType.DMA,
            pltpu.SemaphoreType.DMA,
            pltpu.VMEM((page_size, KH, D), k_pages.dtype),
            pltpu.VMEM((page_size, KH, D), v_pages.dtype),
        ]
    scratch += [
        pltpu.VMEM((G, KH, TQ, D), q.dtype),     # per-group queries
        pltpu.VMEM((G, KH, TQ), jnp.float32),
        pltpu.VMEM((G, KH, TQ), jnp.float32),
        pltpu.VMEM((G, KH, TQ, D), jnp.float32),
    ]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=NS,
        grid=(n_cells,),
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=scratch,
    )
    kernel = functools.partial(
        _prefill_kernel, sm_scale=scale, kv_heads=KH,
        logit_softcap=logit_softcap, pages_per_block=N, ring_blocks=RB,
        n_qb=n_qb, fused_write=fused_write, fp_pad=FP,
        max_write_pages=MAXW, quantized=quantized,
    )
    if fused_write and quantized:
        # scale-scatter targets for the written pages, computed BEFORE the
        # aliased pallas_call (its operands are dead afterwards). The
        # validity mask mirrors the kernel's write predicate (any_w); a
        # non-fresh head page kept its old scale, so rewriting it is a
        # no-op, but masking dead rows keeps the scatter honest when the
        # o_* output blocks hold stale VMEM garbage (cl == 0 rows).
        s0_w = pe
        e0_w = lens32
        lp0_w = jnp.maximum(s0_w, 0) // page_size
        jw = jnp.arange(MAXW, dtype=jnp.int32)[None, :]
        logical_w = lp0_w[:, None] + jw
        pstart_w = logical_w * page_size
        any_w = (
            (pstart_w < e0_w[:, None])
            & (pstart_w + page_size > s0_w[:, None])
            & (cl[:, None] > 0)
            & (logical_w < max_pages)
        )
        pid_w = jnp.take_along_axis(
            page_table.astype(jnp.int32),
            jnp.clip(logical_w, 0, max_pages - 1), axis=1,
        )
        sc_target = jnp.where(any_w, pid_w, P).reshape(-1)  # P = dropped
        sc_layer = lyr[0]
    outs = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=tuple(out_shapes),
        interpret=interpret,
        input_output_aliases=io_aliases,
        # the default 16 MB scoped-vmem budget is a fraction of v5e's
        # physical VMEM; the f32 score temporaries of a TQ x KB cell need
        # more headroom than decode-sized cells
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=100 * 1024 * 1024
        ),
        cost_estimate=pl.CostEstimate(
            flops=4 * B * T * NH * D * (max_pages * page_size + T),
            bytes_accessed=(
                2 * max_pages * page_size * KH * D * 2 * B
                + 2 * B * T * (NH + 2 * KH) * D
            ),
            transcendentals=B * NH * T * (max_pages * page_size + T),
        ),
    )(
        page_table.astype(jnp.int32), lens32, cl, win, lyr,
        seq_of, qb_of, blk_of, cells.reshape(-1), lo_pg.reshape(-1),
        live_pg.reshape(-1).astype(jnp.int32), total_arr,
        *operands,
    )
    if fused_write and quantized:
        out, kp_new, vp_new, o_ksc, o_vsc = outs
        # scatter the written pages' new scales into the scales pool: page
        # bytes crossed HBM once, in-kernel; the scales are a few KB
        ks_new = k_scales.at[sc_layer, sc_target].set(
            o_ksc.reshape(-1, KH), mode="drop"
        )
        vs_new = v_scales.at[sc_layer, sc_target].set(
            o_vsc.reshape(-1, KH), mode="drop"
        )
        if squeeze:
            kp_new, vp_new = kp_new[0], vp_new[0]
            ks_new, vs_new = ks_new[0], vs_new[0]
        return out[:, :T], kp_new, vp_new, ks_new, vs_new
    if fused_write:
        out, kp_new, vp_new = outs
        if squeeze:
            kp_new, vp_new = kp_new[0], vp_new[0]
        return out[:, :T], kp_new, vp_new
    return outs[0][:, :T]
