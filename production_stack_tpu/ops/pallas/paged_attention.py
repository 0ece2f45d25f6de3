"""Pallas TPU kernel: ragged paged attention for the decode step.

Why a kernel (SURVEY.md §7 hard part #1): the XLA reference path
(ops/attention.py paged_attention_decode) gathers each sequence's pages into a
contiguous [B, S, KH, D] tensor in HBM *before* attending — that copy is pure
HBM-bandwidth waste in the bandwidth-bound decode regime. This kernel streams
each page HBM->VMEM exactly once instead.

Three ideas carry it:

1. **Ragged packed grid.** Each sequence's LIVE page range follows from
   ``kv_lens``, the sliding window and the burst's stale tail; the wrapper
   cuts it into blocks of N pages, packs all live (sequence, block) cells
   into a 1D grid sized for the bucket's worst case, and pads with no-op
   cells (no DMA, no compute). Decode cost scales with the batch's REAL
   total context, not with B x bucket.

2. **A ring of blocks.** The pools stay in HBM (``memory_space=ANY``). One
   grid cell consumes one block: N pages copied by N DMAs into one slot of a
   VMEM ring of RB blocks (``pltpu.make_async_copy``); while a cell computes,
   the next RB-1 blocks' copies are in flight, across cell and row
   boundaries. Only pages inside a row's live range are copied.

3. **The block is ONE tile.** A page is read as the pool stores it:
   ``[page_size * KH, D]`` rows in (token, kv head) order — a free view of
   ``[page_size, KH, D]``, never cast, split by head or transposed. A cell
   runs ONE score matmul of all NH query heads against the block's
   ``N * page_size * KH`` rows (the KV rows are the MXU's latched operand,
   the query rows stream; bf16 in, float32 out), ONE mask — a row counts for
   the G query heads of its own kv head, inside ``[lo, paged_end)`` — ONE
   online-softmax update of the float32 (m, l, acc) scratch, which persists
   across a sequence's cells, and ONE PV matmul. Scores, softmax state and
   probabilities are float32; every product is exact. The cross product
   costs KH times the arithmetic of a per-head kernel, on an MXU that has it
   to spare: what a cell costs beside its bytes is a chain of dependent
   steps, about 1 us on a v5e whether the tile holds one page or thirty-two
   (PERF.md, PR 31). So N is as large as the derivation below allows, and
   the kernel runs at 62-85% of the HBM roofline at the benchmark's shapes
   where its per-page predecessor ran at 20-32% (my chip runs, PR 31).

N and RB are DERIVED (``_auto_pages_per_block``, ``_auto_ring_blocks``) from
the page size, the kv heads a shard, the head dim, the pool's itemsize, the
ring's VMEM budget and the bucket's ``max_pages``; N sizes the grid too
(``ceil(max_pages / N)`` cells a row). ``GET /stats``
``decode_kernel_blocks`` reports both per bucket dispatched.

Sliding-window attention (Mistral, Gemma-2's even layers) starts each
sequence's live range at the first page containing a visible KV slot
(``(kv_len - window) // page_size``), so a 4096-window sequence at 128k
context streams ~window bytes, not ~context bytes. The window arrives as a
scalar-prefetch operand, so per-layer window sizes (Gemma-2 interleaves
local/global) ride the decoder's layer scan. Logit softcapping (Gemma-2) is
a static transform on the scores. int8 pools carry one scale per (page, kv
head): a score or probability COLUMN belongs to one of each, so the scale
multiplies the column and the int8 values enter the MXU as they are.

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
    sublanes). Mosaic has no lane->sublane shape cast for a KH-wide vector;
    a masked lane reduction over a [KH, KH] identity is the transpose it
    does support."""
    KH = row.shape[1]
    eye = (
        lax.broadcasted_iota(jnp.int32, (KH, KH), 0)
        == lax.broadcasted_iota(jnp.int32, (KH, KH), 1)
    )
    return jnp.sum(jnp.where(eye, row, 0.0), axis=1, keepdims=True)


def _div_mod(x, n: int):
    """(x // n, x % n) of a non-negative int32 vector by a static n; shifts
    where n is a power of two (every preset's KH), the VPU's division else."""
    if n & (n - 1) == 0:
        return x >> (n.bit_length() - 1), x & (n - 1)
    return lax.div(x, jnp.int32(n)), lax.rem(x, jnp.int32(n))


def _scale_lanes(row, cols: int):
    """[1, KH] scale row -> [1, cols] lane vector holding scale[c % KH] at
    column c: the scale of every (token, kv head)-major column of a page."""
    KH = row.shape[1]
    _, kh = _div_mod(lax.broadcasted_iota(jnp.int32, (KH, cols), 1), KH)
    mine = kh == lax.broadcasted_iota(jnp.int32, (KH, cols), 0)
    return jnp.sum(
        jnp.where(mine, _scale_column(row), 0.0), axis=0, keepdims=True
    )


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
    kp_hbm,      # [L, P, page_size * KH, D], memory_space=ANY (stays in HBM)
    vp_hbm,
    *refs,       # [ks_ref, vs_ref ([1, P, KH] f32 scale slabs, quantized),]
                 # [k_cur_ref, v_cur_ref ([1, C * KH, D]),] o_ref,
                 # k_buf/v_buf ([RB, N * page_size * KH, D] VMEM ring of
                 # blocks), ksem/vsem ([RB]), m/l/acc scratch
    sm_scale: float,
    kv_heads: int,
    page_size: int,
    logit_softcap: float | None,
    has_cur: bool,
    pages_per_block: int,
    ring_blocks: int,
    quantized: bool = False,
):
    i0 = 0
    if quantized:
        # int8 pools: the current layer's [P, KH] scale slabs ride as whole
        # VMEM blocks (constant index map — fetched once)
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
    RB = ring_blocks
    KH = kv_heads
    rows = page_size * KH  # one page as (token, kv head)-major rows
    max_pages = pt_ref.shape[1]
    n_cells = seq_ref.shape[0]
    NH = q_ref.shape[1]
    G = NH // KH
    lyr = layer_ref[0]

    c = pl.program_id(0)
    total = total_ref[0]
    live = c < total
    b = seq_ref[c]
    p = blk_ref[c]

    def _block(g):
        """Packed cell g's batch row, first page (an offset into the row's
        page table) and number of pages to fetch: those inside the row's
        live range (livepg_ref, the array the host packed the grid from),
        none for a dead cell. Start and wait both loop over that count, so
        semaphore counts always pair."""
        cc = jnp.minimum(g, n_cells - 1)
        bb = seq_ref[cc]
        first = blk_ref[cc] * N
        lo_pg = jnp.maximum(lens_ref[bb] - win_ref[0], 0) // page_size
        n_ok = jnp.where(g < total, jnp.clip(livepg_ref[bb] - first, 0, N), 0)
        return bb, lo_pg + first, n_ok

    def _page_id(bb, pg0, i):
        return pt_ref[bb, jnp.minimum(pg0 + i, max_pages - 1)]

    def _copies(g, bb, pg0, i):
        """Page i of cell g's block: pool page -> its rows of ring slot g % RB."""
        pid = _page_id(bb, pg0, i)
        s = g % RB
        dst = pl.ds(pl.multiple_of(i * rows, rows), rows)
        return (
            pltpu.make_async_copy(kp_hbm.at[lyr, pid], k_buf.at[s, dst], ksem.at[s]),
            pltpu.make_async_copy(vp_hbm.at[lyr, pid], v_buf.at[s, dst], vsem.at[s]),
        )

    def _each_page(g, act):
        """``act`` on both copies of every page cell g's block fetches."""
        bb, pg0, n_ok = _block(g)

        def body(i, carry):
            for cp in _copies(g, bb, pg0, i):
                act(cp)
            return carry

        lax.fori_loop(0, n_ok, body, 0)
        return bb, pg0, n_ok

    def _start(g):
        _each_page(g, lambda cp: cp.start())

    def _fold(k2, v2, pos0, lo, hi, k_scale=None, v_scale=None):
        """ONE online-softmax update of (m, l, acc) with a tile of KV rows.

        k2/v2 are [cols, D] in the pool's own (token, kv head)-major order:
        row t * KH + kh holds kv head kh of position pos0 + t. The score
        matmul runs every query head against every row — the KV rows are the
        MXU's latched operand, the NH query rows stream — and the mask keeps
        a row for its own kv head's G query heads only, so no page is ever
        split by head or transposed. Visible: lo <= position < hi."""
        q = q_ref[0]
        mxu = (
            jnp.bfloat16
            if q.dtype == jnp.bfloat16 and k2.dtype in (jnp.bfloat16, jnp.int8)
            else jnp.float32
        )
        s = lax.dot_general(
            q.astype(mxu), k2.astype(mxu), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * sm_scale                                        # [NH, cols]
        if k_scale is not None:
            s = s * k_scale
        if logit_softcap is not None:
            s = logit_softcap * jnp.tanh(s / logit_softcap)
        cols = k2.shape[0]
        t, kh = _div_mod(lax.broadcasted_iota(jnp.int32, (1, cols), 1), KH)
        pos = pos0 + t
        own, _ = _div_mod(lax.broadcasted_iota(jnp.int32, (NH, 1), 0), G)
        keep = (pos >= lo) & (pos < hi) & (kh == own)
        s = jnp.where(keep, s, NEG_INF)

        m_prev = m_ref[...]                                 # [NH, 1]
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        alpha = jnp.exp(jnp.minimum(m_prev - m_new, 0.0))
        pij = jnp.where(keep, jnp.exp(s - m_new), 0.0)
        m_ref[...] = m_new
        l_ref[...] = l_ref[...] * alpha + pij.sum(axis=1, keepdims=True)
        if v_scale is not None:
            pij = pij * v_scale
        # V widens to float32 (exact) for a float32 x float32 matmul: the
        # probabilities are never rounded. Measured no slower than bf16.
        pv = lax.dot_general(
            pij, v2.astype(jnp.float32), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                                   # [NH, D]
        acc_ref[...] = acc_ref[...] * alpha + pv

    @pl.when(live & (p == 0))
    def _():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(c == 0)
    def _():
        # a block whose row ends inside it leaves its last pages' rows
        # unfetched: they are masked out of the scores, and must be finite
        # (not whatever VMEM held) to be 0 x V in the PV matmul
        v_buf[...] = jnp.zeros_like(v_buf)
        # warm-up: fill the ring; steady state below tops it off with block
        # c+RB-1 as it consumes block c, so RB-1 blocks stay in flight
        for g in range(RB - 1):
            _start(jnp.int32(g))

    kv_len = lens_ref[b]
    # paged slots hold positions < paged_end; in has_cur mode the final
    # cl_ref[b] slots (the in-register window) are stale in the pool
    paged_end = kv_len - cl_ref[b] if has_cur else kv_len
    lo = jnp.maximum(kv_len - win_ref[0], 0)   # first visible KV slot

    @pl.when(live)
    def _():
        _start(c + RB - 1)
        bb, pg0, n_ok = _each_page(c, lambda cp: cp.wait())

        @pl.when(n_ok > 0)
        def _():
            s = c % RB
            k_scale = v_scale = None
            if quantized:
                # scale per page per kv head: a score column / probability
                # column belongs to one (page, kv head), so the scale
                # multiplies the column — the int8 values enter the MXU as
                # they are and no dequantised page is ever built
                def lanes(ref):
                    return jnp.concatenate(
                        [_scale_lanes(ref[0, pl.ds(_page_id(bb, pg0, i), 1), :], rows)
                         for i in range(N)], axis=1)

                k_scale, v_scale = lanes(ks_ref), lanes(vs_ref)
            _fold(k_buf[s], v_buf[s], pg0 * page_size, lo, paged_end,
                  k_scale, v_scale)

    @pl.when(live & (p == cells_ref[b] - 1))
    def _():
        if has_cur:
            # one more update over the in-register window (entries j < cl at
            # positions paged_end + j; the final entry, the current token, is
            # always causally visible)
            _fold(k_cur_ref[0], v_cur_ref[0], paged_end, lo, kv_len)
        out = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = out.astype(o_ref.dtype)


# What one grid cell costs beside its bytes is a chain of dependent steps
# (score matmul -> row max -> exp -> PV matmul -> rescale), about 1 us on a
# v5e whatever the tile holds (PERF.md, PR 31): a block has to be worth
# several of those in HBM time, and 4 MiB of K + V is 5 us at 819 GB/s.
# Larger blocks gained nothing and a row's masked tail (computed, never
# fetched) grows with them.
_BLOCK_BYTES = 4 << 20
# ... and at most this many (token, kv head) rows: the [NH, rows] score and
# probability tiles and the float32 view of the V block scale with them
# (int8 pools halve the bytes a row, not the rows).
_BLOCK_ROWS = 8192
# VMEM the ring of K and V blocks may take, both arrays together: three
# blocks of _BLOCK_BYTES (one consumed, two in flight).
_RING_VMEM_BYTES = 12 << 20
# Scoped VMEM of the whole call: the ring, the tiles above and Mosaic's own
# temporaries (a v5e core has 128 MiB; the default scope is 16).
_VMEM_LIMIT_BYTES = 32 << 20


def _auto_pages_per_block(
    max_pages: int, page_size: int, kv_heads: int, head_dim: int, itemsize: int
) -> int:
    """Pages one grid cell consumes as ONE tile — a pure function of what
    the call can see: as many as make ``_BLOCK_BYTES`` of K + V or
    ``_BLOCK_ROWS`` (token, kv head) rows, whichever is fewer, while two
    such blocks fit the ring's VMEM budget; never more than the bucket
    holds. It sizes the grid too: ``ceil(max_pages / N)`` cells a row."""
    page_bytes = 2 * page_size * kv_heads * head_dim * itemsize
    n = min(
        min(_BLOCK_BYTES, _RING_VMEM_BYTES // 2) // page_bytes,
        _BLOCK_ROWS // (page_size * kv_heads),
    )
    return max(1, min(n, max_pages))


def _auto_ring_blocks(
    pages_per_block: int, page_size: int, kv_heads: int, head_dim: int,
    itemsize: int,
) -> int:
    """Blocks in the VMEM ring (one consumed, the rest in flight): what the
    budget holds, 2 or 3 (a third block in flight measured 3-7% faster
    than two, a fourth nothing)."""
    block_bytes = 2 * pages_per_block * page_size * kv_heads * head_dim * itemsize
    return max(2, min(3, _RING_VMEM_BYTES // block_bytes))


def decode_block_shape(
    max_pages: int, page_size: int, kv_heads: int, head_dim: int,
    itemsize: int, pages_per_block: int | None = None,
    prefetch_pages: int | None = None,
) -> tuple[int, int]:
    """(pages a block, blocks in the ring) the kernel runs one (batch,
    pages) bucket with; the two overrides are engine/config.py's."""
    if pages_per_block is None:
        pages_per_block = _auto_pages_per_block(
            max_pages, page_size, kv_heads, head_dim, itemsize
        )
    n = max(1, min(int(pages_per_block), max_pages))
    if prefetch_pages is None:
        ring = _auto_ring_blocks(n, page_size, kv_heads, head_dim, itemsize)
    else:
        ring = max(2, -(-int(prefetch_pages) // n))
    return n, ring


def decode_smem_bytes(
    batch: int, max_pages: int, page_size: int, kv_heads: int, head_dim: int,
    itemsize: int,
) -> int:
    """SMEM the decode kernel's scalar-prefetch operands take at one
    (batch, pages) bucket with the derived block: the [B, max_pages] page
    table, the two packed cell maps of B * n_blocks entries, four [B]
    vectors (lengths, window entries, cells and live pages a row) and three
    scalars (window, layer, total) — all int32.
    engine/runner.kernel_refusal holds the largest bucket against the
    chip's SMEM."""
    n = _auto_pages_per_block(max_pages, page_size, kv_heads, head_dim, itemsize)
    n_blocks = -(-max_pages // n)
    return 4 * (batch * (max_pages + 2 * n_blocks + 4) + 3)


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

    With ``k_scales/v_scales`` (int8 pools, ops/quant.py contract) HBM
    streams HALF the bytes; the int8 values enter the matmuls as they are
    (exact in bf16 and float32) and a page's per-kv-head scale multiplies
    its score and probability columns in float32 — no dequantised page is
    ever built. The current layer's [P, KH] scale slabs stay VMEM-resident
    (fetched once, constant index map; P*KH*4 bytes each — ~256 KB at 8k
    pages x 8 heads). ``k_cur/v_cur`` stay fp: the in-register window never
    quantizes.

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

    ``pages_per_block``: pages one packed grid cell consumes as ONE tile
    (auto: ``_auto_pages_per_block`` — 4 MiB of K + V or 8192 (token, kv
    head) rows, whichever is fewer). It sizes the grid as well.

    ``prefetch_pages``: pages the VMEM ring holds, rounded up to whole blocks
    (auto: ``_auto_ring_blocks`` — three blocks where 12 MiB hold them, one
    consumed and two in flight; never fewer than two).

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
    L, P_pool, page_size, KH, _ = k_pages.shape
    max_pages = page_table.shape[1]
    scale = sm_scale if sm_scale is not None else D**-0.5
    # a page as the kernel consumes it: (token, kv head)-major rows of D.
    # Row-major, so the pool's own bytes — no page is relaid out anywhere.
    rows = page_size * KH
    k_pages = k_pages.reshape(L, P_pool, rows, D)
    v_pages = v_pages.reshape(L, P_pool, rows, D)
    has_cur = k_cur is not None
    if has_cur:
        # [B, KH, D] is a C=1 window; [B, C, KH, D] -> the same row order
        k_cur = k_cur.reshape(B, -1, D)
        v_cur = v_cur.reshape(B, -1, D)
    kv_itemsize = jnp.dtype(k_pages.dtype).itemsize  # 1 for int8 pools
    N, RB = decode_block_shape(
        max_pages, page_size, KH, D, kv_itemsize, pages_per_block,
        prefetch_pages,
    )
    n_blocks = -(-max_pages // N)
    n_cells = B * n_blocks
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
        in_specs += [
            pl.BlockSpec((1, k_cur.shape[1], D), row3),
            pl.BlockSpec((1, k_cur.shape[1], D), row3),
        ]
        operands += [k_cur, v_cur]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=10,
        grid=(n_cells,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, NH, D), row3),
        scratch_shapes=[
            pltpu.VMEM((RB, N * rows, D), k_pages.dtype),
            pltpu.VMEM((RB, N * rows, D), v_pages.dtype),
            pltpu.SemaphoreType.DMA((RB,)),
            pltpu.SemaphoreType.DMA((RB,)),
            pltpu.VMEM((NH, 1), jnp.float32),
            pltpu.VMEM((NH, 1), jnp.float32),
            pltpu.VMEM((NH, D), jnp.float32),
        ],
    )
    kernel = functools.partial(
        _decode_kernel, sm_scale=scale, kv_heads=KH, page_size=page_size,
        logit_softcap=logit_softcap, has_cur=has_cur, pages_per_block=N,
        ring_blocks=RB, quantized=quantized,
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, NH, D), q.dtype),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=_VMEM_LIMIT_BYTES),
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
