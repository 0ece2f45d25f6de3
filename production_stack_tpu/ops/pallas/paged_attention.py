"""Pallas TPU kernel: ragged paged attention for the decode step (v2).

Why a kernel (SURVEY.md §7 hard part #1): the XLA reference path
(ops/attention.py paged_attention_decode) gathers each sequence's pages into a
contiguous [B, S, KH, D] tensor in HBM *before* attending — that copy is pure
HBM-bandwidth waste in the bandwidth-bound decode regime. This kernel streams
each page HBM->VMEM exactly once instead.

v2 restructures the memory pipeline around two ideas (its achieved
page-streaming rate on the current chip attachment is not measured —
PERF.md):

1. **Ragged packed grid.** v1 ran grid = (B, max_pages_bucket): a 50-page
   sequence in a 256-page bucket still executed ~200 dead grid cells whose
   index map clamped to the last page (refetch + masked compute). v2 derives
   each sequence's LIVE block count from ``kv_lens`` (and the sliding
   window) on the host side, packs all live (sequence, block) cells into a
   1D grid, and pads with no-op cells whose index maps alias the last live
   cell (no DMA, no compute). Decode cost therefore scales with the batch's
   REAL total context, not with B x bucket — which is what makes
   mixed-length decode batches (the multi-round-QA shape) cheap.

2. **Deep page prefetch.** v1 fetched N pages per cell as N separate small
   BlockSpec inputs, so at most one cell's worth of page DMAs overlapped
   compute and per-cell pipeline overhead dominated at small pages. v2
   leaves the pools in HBM
   (``memory_space=ANY``) and drives a manually multi-buffered VMEM ring of
   page copies with ``pltpu.make_async_copy``: R page DMAs stay in flight
   across cell boundaries (R = ``prefetch_pages``), so the HBM pipeline
   stays full regardless of page size or cell shape.

Layout within a cell is unchanged from v1: query/kv heads stay packed
[KH, G, D] so all heads of a page are one batched MXU call, and the
(m, l, acc) VMEM scratch persists across a sequence's consecutive cells —
the classic flash-decode accumulation.

Sliding-window attention (Mistral, Gemma-2's even layers) is handled by
starting each sequence's live range at the first page containing a visible
KV slot (``(kv_len - window) // page_size``), so a 4096-window sequence at
128k context streams ~window bytes, not ~context bytes. The window arrives
as a scalar-prefetch operand, so per-layer window sizes (Gemma-2
interleaves local/global) ride the decoder's layer scan. Logit softcapping
(Gemma-2) is a static transform on the scores.

Measure the achieved page-streaming HBM GB/s with
``scripts/profile_decode.py`` (per (batch, context, page_size) bucket, plus
a mixed-length case that checks cost scales with real ``kv_lens``).

Equivalent role in the reference: vLLM's CUDA PagedAttention decode kernel
(executed inside the engine image; configured by
helm/templates/deployment-vllm-multi.yaml in /root/reference).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _scale_column(row):
    """[1, KH] scale row (kv heads on lanes) -> [KH, 1] column (kv heads on
    sublanes), so it broadcasts against a [page, KH, D] page. Mosaic has no
    lane->sublane shape cast for a KH-wide vector; a masked lane reduction
    over a [KH, KH] identity is the transpose it does support."""
    KH = row.shape[1]
    eye = (
        lax.broadcasted_iota(jnp.int32, (KH, KH), 0)
        == lax.broadcasted_iota(jnp.int32, (KH, KH), 1)
    )
    return jnp.sum(jnp.where(eye, row, 0.0), axis=1, keepdims=True)


def _decode_kernel(
    # scalar prefetch
    pt_ref,      # [B, max_pages] int32 page table
    lens_ref,    # [B] int32 kv lengths
    win_ref,     # [1] int32 window size (huge = full causal)
    cl_ref,      # [B] int32 valid current-window entries (has_cur mode)
    layer_ref,   # [1] int32 layer index into the stacked pools
    seq_ref,     # [C_CELLS] int32 packed cell -> batch row
    blk_ref,     # [C_CELLS] int32 packed cell -> block index within the row
    cells_ref,   # [B] int32 live cell count per row (>= 1)
    livepg_ref,  # [B] int32 live page count per row (the packing's source
                 # of truth — the kernel must never re-derive it)
    total_ref,   # [1] int32 total live cells
    # inputs
    q_ref,       # [1, NH, D] (current cell's row)
    kp_hbm,      # [L, P, page_size, KH, D], memory_space=ANY (stays in HBM)
    vp_hbm,
    *refs,       # [ks_ref, vs_ref ([1, P, KH] f32 scale slabs, quantized),]
                 # [k_cur_ref, v_cur_ref ([1, C, KH, D]),] o_ref,
                 # k_buf/v_buf ([R, page, KH, D] VMEM ring), ksem/vsem,
                 # m/l/acc scratch
    sm_scale: float,
    kv_heads: int,
    logit_softcap: float | None,
    has_cur: bool,
    pages_per_block: int,
    prefetch: int,
    quantized: bool = False,
):
    i0 = 0
    if quantized:
        # int8 pools: the current layer's [P, KH] scale slabs ride as
        # whole VMEM blocks (constant index map — fetched once), and each
        # page dequantizes right after its DMA lands in the ring. The fp
        # values never exist in HBM — only the halved int8 byte stream does.
        ks_ref, vs_ref = refs[0], refs[1]
        i0 = 2
    if has_cur:
        # write-after-attend mode: the last cl_ref[b] tokens' pool slots are
        # stale; their K/V arrive in-register (a fused burst accumulates up
        # to C of them) and fold in on the row's last live cell
        (k_cur_ref, v_cur_ref, o_ref, k_buf, v_buf, ksem, vsem,
         m_ref, l_ref, acc_ref) = refs[i0:]
    else:
        (o_ref, k_buf, v_buf, ksem, vsem,
         m_ref, l_ref, acc_ref) = refs[i0:]
    N = pages_per_block
    R = prefetch
    page_size = k_buf.shape[1]
    max_pages = pt_ref.shape[1]
    n_cells = seq_ref.shape[0]
    NH, D = q_ref.shape[1], q_ref.shape[2]
    KH = kv_heads
    G = NH // KH
    lyr = layer_ref[0]

    c = pl.program_id(0)
    total = total_ref[0]
    live = c < total
    b = seq_ref[c]
    p = blk_ref[c]

    def _copies(g):
        """DMA descriptors (and their go/no-go predicate) for global
        page-stream index g = cell*N + i. A page is fetched iff its cell is
        live and it lies inside its row's live page range (livepg_ref, the
        same array the host packed the grid from) — the SAME predicate
        gates start and wait, so semaphore counts always pair. Also returns
        the page id so the quantized path can look up its scale row."""
        cc = jnp.minimum(g // N, n_cells - 1)
        bb = seq_ref[cc]
        pi = blk_ref[cc] * N + g % N  # page offset within the live range
        lo_pg = jnp.maximum(lens_ref[bb] - win_ref[0], 0) // page_size
        ok = (g < total * N) & (pi < livepg_ref[bb])
        pid = pt_ref[bb, jnp.minimum(lo_pg + pi, max_pages - 1)]
        s = g % R
        kcp = pltpu.make_async_copy(kp_hbm.at[lyr, pid], k_buf.at[s], ksem.at[s])
        vcp = pltpu.make_async_copy(vp_hbm.at[lyr, pid], v_buf.at[s], vsem.at[s])
        return ok, pid, kcp, vcp

    def _start(g):
        ok, _, kcp, vcp = _copies(g)

        @pl.when(ok)
        def _():
            kcp.start()
            vcp.start()

    @pl.when(live & (p == 0))
    def _():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(c == 0)
    def _():
        # warm-up: fill the ring; steady state below tops it off with copy
        # g+R-1 as it consumes copy g, so R page DMAs stay in flight
        for g in range(R - 1):
            _start(jnp.int32(g))

    kv_len = lens_ref[b]
    # paged slots hold positions < paged_end; in has_cur mode the final
    # cl_ref[b] slots (the in-register window) are stale in the pool
    paged_end = kv_len - cl_ref[b] if has_cur else kv_len
    lo = jnp.maximum(kv_len - win_ref[0], 0)   # first visible KV slot
    lo_pg = lo // page_size

    for i in range(N):

        @pl.when(live)
        def _(i=i):
            g = c * N + i
            _start(g + R - 1)
            ok, pid, kcp, vcp = _copies(g)

            @pl.when(ok)
            def _():
                kcp.wait()
                vcp.wait()
                s = g % R
                q = (q_ref[0].astype(jnp.float32) * sm_scale).reshape(KH, G, D)
                k = k_buf[s].astype(jnp.float32)  # [page, KH, D]
                v = v_buf[s].astype(jnp.float32)
                if quantized:
                    # dequant at the VMEM ring exit: per-page per-kv-head
                    # scale rows looked up from the resident slab
                    k = k * _scale_column(ks_ref[0, pl.ds(pid, 1), :])[None]
                    v = v * _scale_column(vs_ref[0, pl.ds(pid, 1), :])[None]
                k = k.transpose(1, 0, 2)  # [KH, page, D]
                v = v.transpose(1, 0, 2)
                # batched over KH: [KH, G, D] x [KH, page, D] -> [KH, G, page]
                scores = lax.dot_general(
                    q, k, (((2,), (2,)), ((0,), (0,))),
                    preferred_element_type=jnp.float32,
                )
                if logit_softcap is not None:
                    scores = logit_softcap * jnp.tanh(scores / logit_softcap)
                start = (lo_pg + p * N + i) * page_size
                idx = start + lax.broadcasted_iota(
                    jnp.int32, (1, 1, page_size), 2
                )
                visible = (idx >= lo) & (idx < paged_end)
                scores = jnp.where(visible, scores, NEG_INF)

                m_prev, l_prev = m_ref[...], l_ref[...]
                m_new = jnp.maximum(m_prev, scores.max(axis=-1))
                alpha = jnp.exp(jnp.minimum(m_prev - m_new, 0.0))
                pij = jnp.exp(scores - m_new[..., None])
                pij = jnp.where(visible, pij, 0.0)
                m_ref[...] = m_new
                l_ref[...] = l_prev * alpha + pij.sum(axis=-1)
                # [KH, G, page] x [KH, page, D] -> [KH, G, D]
                pv = lax.dot_general(
                    pij, v, (((2,), (1,)), ((0,), (0,))),
                    preferred_element_type=jnp.float32,
                )
                acc_ref[...] = acc_ref[...] * alpha[..., None] + pv

    @pl.when(live & (p == cells_ref[b] - 1))
    def _():
        m_prev, l_prev, acc = m_ref[...], l_ref[...], acc_ref[...]
        if has_cur:
            # one extra online-softmax update over the in-register window
            # (entries j < cl at positions paged_end + j; the final entry,
            # the current token, is always causally visible)
            q = (q_ref[0].astype(jnp.float32) * sm_scale).reshape(KH, G, D)
            kc = k_cur_ref[0].astype(jnp.float32).transpose(1, 0, 2)  # [KH, C, D]
            vc = v_cur_ref[0].astype(jnp.float32).transpose(1, 0, 2)
            C = kc.shape[1]
            s_cur = lax.dot_general(
                q, kc, (((2,), (2,)), ((0,), (0,))),
                preferred_element_type=jnp.float32,
            )  # [KH, G, C]
            if logit_softcap is not None:
                s_cur = logit_softcap * jnp.tanh(s_cur / logit_softcap)
            j = lax.broadcasted_iota(jnp.int32, (1, 1, C), 2)
            pos_j = paged_end + j
            vis = (j < cl_ref[b]) & (pos_j >= lo)
            s_cur = jnp.where(vis, s_cur, NEG_INF)
            m_new = jnp.maximum(m_prev, s_cur.max(axis=-1))
            alpha = jnp.exp(jnp.minimum(m_prev - m_new, 0.0))
            p_cur = jnp.exp(s_cur - m_new[..., None])
            p_cur = jnp.where(vis, p_cur, 0.0)
            l_prev = l_prev * alpha + p_cur.sum(axis=-1)
            pv = lax.dot_general(
                p_cur, vc, (((2,), (1,)), ((0,), (0,))),
                preferred_element_type=jnp.float32,
            )
            acc = acc * alpha[..., None] + pv
        out = acc / jnp.maximum(l_prev, 1e-30)[..., None]
        o_ref[0] = out.reshape(NH, D).astype(o_ref.dtype)


def _auto_pages_per_block(max_pages: int, page_size: int, itemsize: int) -> int:
    """~128 KV slots of bookkeeping per cell for short-context buckets;
    long-context buckets (>=128 pages) use ~512 — with the DMA ring the cell
    size no longer bounds fetch depth, it only amortizes the per-cell
    grid/index-map overhead. int8 pools double the slot target: each slot
    costs half the bytes, so the same VMEM/DMA budget amortizes twice the
    bookkeeping (re-sweep with scripts/profile_decode.py --impl pallas_int8
    when retuning)."""
    target = 512 if max_pages >= 128 else 128
    if itemsize == 1:
        target *= 2
    return max(1, min(target // page_size, max_pages))


def decode_smem_bytes(
    batch: int, max_pages: int, page_size: int, itemsize: int
) -> int:
    """SMEM the decode kernel's scalar-prefetch operands take at one
    (batch, pages) bucket with auto ``pages_per_block``: the [B, max_pages]
    page table, the two packed cell maps of B * n_blocks entries, and five
    [B] vectors — all int32. engine/runner.kernel_refusal holds the largest
    bucket against the chip's SMEM."""
    n_blocks = -(-max_pages // _auto_pages_per_block(max_pages, page_size, itemsize))
    return 4 * batch * (max_pages + 2 * n_blocks + 5)


@functools.partial(
    jax.jit,
    static_argnames=(
        "sm_scale", "logit_softcap", "interpret", "pages_per_block",
        "prefetch_pages",
    ),
)
def ragged_paged_attention_decode(
    q: jnp.ndarray,          # [B, NH, D]
    k_pages: jnp.ndarray,    # [P, page_size, KH, D] or [L, P, page, KH, D]
    v_pages: jnp.ndarray,
    page_table: jnp.ndarray, # [B, max_pages] int32
    seq_lens: jnp.ndarray,   # [B] int32
    window=None,             # scalar int (static or traced); None = full causal
    *,
    sm_scale: float | None = None,
    logit_softcap: float | None = None,
    interpret: bool = False,
    k_cur: jnp.ndarray | None = None,  # [B, KH, D] or [B, C, KH, D]
    v_cur: jnp.ndarray | None = None,
    cur_lens: jnp.ndarray | None = None,  # [B] valid window entries (1..C)
    pages_per_block: int | None = None,
    prefetch_pages: int | None = None,
    layer: jnp.ndarray | int | None = None,  # index into stacked pools
    k_scales: jnp.ndarray | None = None,  # [P, KH] or [L, P, KH] f32 (int8 pools)
    v_scales: jnp.ndarray | None = None,
) -> jnp.ndarray:
    """Decode attention over paged KV, streaming pages HBM->VMEM.

    With ``k_scales/v_scales`` (int8 pools, ops/quant.py contract) each
    page dequantizes right after its DMA lands in the VMEM ring — HBM
    streams HALF the bytes and fp values never round-trip through it. The
    current layer's [P, KH] scale slabs stay VMEM-resident (fetched once,
    constant index map; P*KH*4 bytes each — ~256 KB at 8k pages x 8 heads).
    ``k_cur/v_cur`` stay fp: the in-register window never quantizes.

    With ``k_cur/v_cur`` (write-after-attend mode), pool slots at positions
    >= ``seq_lens - cur_lens`` are treated as stale and the in-register
    window folds in instead: entry j holds the token at absolute position
    ``seq_lens - cur_lens + j`` (valid for j < cur_lens). A fused decode
    burst defers all its KV scatters this way — the pool stays read-only
    for the whole burst. [B, KH, D] k_cur means C=1 (single current token).
    Returns [B, NH, D] in q.dtype. Matches
    ops/attention.paged_attention_decode (the XLA oracle) — tests assert
    equivalence (atol 2e-5 in f32, 3e-2 in bf16).

    Stacked pools + ``layer``: passing the whole [L, P, page, KH, D] pool
    and a (traced) layer index lets the per-layer scan stream pages straight
    out of the stacked array — a per-layer ``k_pages[l]`` at the call site
    would materialize a pool-sized dynamic-slice copy every layer (profiled
    at ~1.5 ms/step on v5e), because XLA cannot fuse a slice into a
    pallas_call operand.

    ``pages_per_block``: pages processed per packed grid cell (auto: ~128 KV
    slots per cell, ~512 for >=128-page buckets). With the v2 DMA ring this
    mostly sets grid-bookkeeping granularity, not pipeline depth.

    ``prefetch_pages``: depth of the VMEM page-copy ring — how many page
    DMAs stay in flight ahead of compute (auto: up to 8, bounded by a ~2 MB
    per-array VMEM budget). This is what keeps the HBM pipeline full at
    small pages (v1's per-cell BlockSpec fetches could not).

    The grid itself is RAGGED: live (sequence, block) cells pack to the
    front of a 1D grid sized for the bucket's worst case, and trailing dead
    cells alias the last live cell's indices (no DMA, no compute) — so a
    50-page sequence in a 256-page bucket costs ~50 pages of work, and a
    mixed-length batch costs the sum of its REAL contexts.
    """
    B, NH, D = q.shape
    quantized = k_scales is not None
    if k_pages.ndim == 4:  # single-layer pools: free leading-axis view
        k_pages = k_pages[None]
        v_pages = v_pages[None]
        if quantized and k_scales.ndim == 2:
            k_scales = k_scales[None]
            v_scales = v_scales[None]
        layer = 0
    _, P_pool, page_size, KH, _ = k_pages.shape
    max_pages = page_table.shape[1]
    G = NH // KH
    scale = sm_scale if sm_scale is not None else D**-0.5
    has_cur = k_cur is not None
    if has_cur and k_cur.ndim == 3:
        k_cur = k_cur[:, None]  # [B, KH, D] -> C=1 window
        v_cur = v_cur[:, None]
    if pages_per_block is None:
        pages_per_block = _auto_pages_per_block(
            max_pages, page_size, jnp.dtype(k_pages.dtype).itemsize
        )
    N = max(1, min(pages_per_block, max_pages))
    n_blocks = -(-max_pages // N)
    n_cells = B * n_blocks
    if prefetch_pages is None:
        # ring depth: up to 8 pages in flight, bounded by ~2 MB of VMEM per
        # pool array (k and v each get a ring this size)
        slot_bytes = page_size * KH * D * jnp.dtype(k_pages.dtype).itemsize
        prefetch_pages = max(2, min(8, (2 << 20) // max(slot_bytes, 1)))
    R = max(2, int(prefetch_pages))
    win = (
        jnp.full((1,), 2**30, jnp.int32)
        if window is None
        else jnp.asarray(window, jnp.int32).reshape(1)
    )
    cl = (
        jnp.ones((B,), jnp.int32)
        if cur_lens is None
        else jnp.asarray(cur_lens, jnp.int32)
    )
    lyr = jnp.asarray(layer, jnp.int32).reshape(1)

    # ragged cell maps: pack each row's live blocks (pages holding visible,
    # non-stale KV slots) into a 1D grid; every row keeps >= 1 cell so
    # padded rows (kv_len 0) still initialize + write their (zero) output
    lens32 = seq_lens.astype(jnp.int32)
    pe = lens32 - cl if has_cur else lens32
    lo_pg = jnp.maximum(lens32 - win[0], 0) // page_size
    live_pg = jnp.maximum(-(-jnp.maximum(pe, 0) // page_size) - lo_pg, 0)
    cells = jnp.clip(-(-live_pg // N), 1, n_blocks).astype(jnp.int32)
    cs = jnp.cumsum(cells).astype(jnp.int32)       # [B] end cell per row
    starts = cs - cells                            # [B] first cell per row
    cidx = jnp.arange(n_cells, dtype=jnp.int32)
    total = cs[B - 1]
    row = jnp.minimum(
        jnp.searchsorted(cs, cidx, side="right").astype(jnp.int32), B - 1
    )
    dead = cidx >= total
    # dead cells alias the LAST live cell (row B-1's final block): index
    # maps repeat, so the pipeline neither fetches nor writes for them
    seq_of = jnp.where(dead, B - 1, row)
    blk_of = jnp.where(dead, cells[B - 1] - 1, cidx - starts[row])
    total_arr = cs[B - 1:]

    def row3(c, pt, lens, w, _cl, l, so, bo, ce, lp, tot):
        return (so[c], 0, 0)

    def row4(c, pt, lens, w, _cl, l, so, bo, ce, lp, tot):
        return (so[c], 0, 0, 0)

    def srow(c, pt, lens, w, _cl, l, so, bo, ce, lp, tot):
        # scale slabs: the whole [P, KH] slice of the CURRENT layer; the
        # constant block index means the pipeline fetches it once
        return (l[0], 0, 0)

    in_specs = [
        pl.BlockSpec((1, NH, D), row3),
        pl.BlockSpec(memory_space=pl.ANY),
        pl.BlockSpec(memory_space=pl.ANY),
    ]
    operands = [q, k_pages, v_pages]
    if quantized:
        in_specs += [
            pl.BlockSpec((1, P_pool, KH), srow),
            pl.BlockSpec((1, P_pool, KH), srow),
        ]
        operands += [k_scales, v_scales]
    if has_cur:
        C = k_cur.shape[1]
        in_specs += [
            pl.BlockSpec((1, C, KH, D), row4),
            pl.BlockSpec((1, C, KH, D), row4),
        ]
        operands += [k_cur, v_cur]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=10,
        grid=(n_cells,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, NH, D), row3),
        scratch_shapes=[
            pltpu.VMEM((R, page_size, KH, D), k_pages.dtype),
            pltpu.VMEM((R, page_size, KH, D), v_pages.dtype),
            pltpu.SemaphoreType.DMA((R,)),
            pltpu.SemaphoreType.DMA((R,)),
            pltpu.VMEM((KH, G), jnp.float32),
            pltpu.VMEM((KH, G), jnp.float32),
            pltpu.VMEM((KH, G, D), jnp.float32),
        ],
    )
    kernel = functools.partial(
        _decode_kernel, sm_scale=scale, kv_heads=KH,
        logit_softcap=logit_softcap, has_cur=has_cur, pages_per_block=N,
        prefetch=R, quantized=quantized,
    )
    kv_itemsize = jnp.dtype(k_pages.dtype).itemsize  # 1 for int8 pools
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, NH, D), q.dtype),
        interpret=interpret,
        cost_estimate=pl.CostEstimate(
            flops=4 * B * NH * D * max_pages * page_size,
            bytes_accessed=(
                2 * max_pages * page_size * KH * D * kv_itemsize * B
                + B * NH * D * 4
            ),
            transcendentals=B * NH * max_pages * page_size,
        ),
    )(
        page_table.astype(jnp.int32), lens32, win, cl, lyr,
        seq_of, blk_of, cells, live_pg.astype(jnp.int32), total_arr,
        *operands,
    )


def ragged_paged_attention_decode_sharded(
    mesh,
    q: jnp.ndarray,          # [B, NH, D], B sharded over dp / NH over tp
    k_pages: jnp.ndarray,    # [P, page_size, KH, D], KH sharded over tp
    v_pages: jnp.ndarray,
    page_table: jnp.ndarray, # [B, max_pages]
    seq_lens: jnp.ndarray,   # [B]
    window=None,
    *,
    sm_scale: float | None = None,
    logit_softcap: float | None = None,
    interpret: bool = False,
    k_cur: jnp.ndarray | None = None,
    v_cur: jnp.ndarray | None = None,
    cur_lens: jnp.ndarray | None = None,
    pages_per_block: int | None = None,
    prefetch_pages: int | None = None,
    layer: jnp.ndarray | int | None = None,
    k_scales: jnp.ndarray | None = None,  # [P, KH]/[L, P, KH], KH over tp
    v_scales: jnp.ndarray | None = None,
) -> jnp.ndarray:
    """The decode kernel on a multi-device mesh via manual shard_map.

    GSPMD cannot partition a pallas_call, so the north-star TP config (v5e-8,
    kv heads sharded over tp per shardings.KV_PAGES_SPEC) previously fell
    back to the XLA gather path whose HBM copy the kernel exists to avoid.
    Each (dp, tp) shard runs the kernel on its local batch rows and kv-head
    slice: attention is embarrassingly parallel over both axes (GQA groups
    stay whole because NH and KH divide by tp together), and page indices are
    global pool coordinates valid on every shard.

    sp/ep are ALSO mapped, with no spec mention: decode activations are
    replicated along them (sp shards the token dim of long prefills, ep the
    expert weights — neither shards a 1-token decode), so each (sp, ep)
    shard redundantly computes its (dp, tp) slice. Mapping them manually is
    what keeps GSPMD from trying — and failing — to partition the
    pallas_call along those axes, which is why sp/ep/pp serving configs
    used to regress decode to the XLA gather path (engine/runner.py).
    Under pp this function is called INSIDE the pipeline's shard_map over
    {pp} (parallel/pipeline.py serving_layer_pipeline) with stage-local
    layer pools — nested manual regions over disjoint axes.
    """
    from jax.sharding import PartitionSpec as P

    D = q.shape[-1]
    scale = sm_scale if sm_scale is not None else D**-0.5

    has_cur = k_cur is not None
    if has_cur and k_cur.ndim == 3:
        k_cur = k_cur[:, None]  # [B, KH, D] -> C=1 window
        v_cur = v_cur[:, None]
    if has_cur and cur_lens is None:
        cur_lens = jnp.ones(q.shape[:1], jnp.int32)
    quantized = k_scales is not None
    if k_pages.ndim == 4:  # single-layer pools
        k_pages = k_pages[None]
        v_pages = v_pages[None]
        if quantized and k_scales.ndim == 2:
            k_scales = k_scales[None]
            v_scales = v_scales[None]
        layer = 0
    lyr = jnp.asarray(layer, jnp.int32).reshape(1)

    def body(q, kp, vp, pt, lens, l, *rest):
        rest = list(rest)
        ks = vs = None
        if quantized:
            ks, vs = rest[0], rest[1]
            rest = rest[2:]
        kc, vc, cl = rest if has_cur else (None, None, None)
        return ragged_paged_attention_decode(
            q, kp, vp, pt, lens, window,
            sm_scale=scale, logit_softcap=logit_softcap, interpret=interpret,
            k_cur=kc, v_cur=vc, cur_lens=cl,
            pages_per_block=pages_per_block, prefetch_pages=prefetch_pages,
            layer=l[0], k_scales=ks, v_scales=vs,
        )

    head = P("dp", "tp", None)
    pool = P(None, None, None, "tp", None)
    in_specs = [head, pool, pool, P("dp", None), P("dp"), P()]
    operands = [q, k_pages, v_pages, page_table, seq_lens, lyr]
    if quantized:
        # scale slabs shard their KH axis over tp exactly like the pools'
        in_specs += [P(None, None, "tp"), P(None, None, "tp")]
        operands += [k_scales, v_scales]
    if has_cur:
        # the window's KH axis shards over tp like the pool's
        in_specs += [P("dp", None, "tp", None), P("dp", None, "tp", None), P("dp")]
        operands += [k_cur, v_cur, cur_lens]
    # EVERY mesh axis must be manual around a Mosaic call, size-1 ones too
    # (XLA:TPU: "Mosaic kernels cannot be automatically partitioned"): map
    # all of them, minus what an enclosing region (the pp pipeline) already
    # holds. Inside such a region the context mesh (with those axes marked
    # Manual) is the one the nested shard_map takes, not the concrete mesh.
    ctx = jax.sharding.get_abstract_mesh()
    manual_already = set() if ctx.empty else set(ctx.manual_axes)
    sm_mesh = ctx if manual_already else mesh
    manual = set(mesh.axis_names) - manual_already
    out = jax.shard_map(
        body,
        mesh=sm_mesh,
        axis_names=manual,
        in_specs=tuple(in_specs),
        out_specs=head,
        check_vma=False,
    )(*operands)
    return out
