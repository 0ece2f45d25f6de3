"""Sparse mixture-of-experts feed-forward: route over all experts, group the
tokens by expert, ONE grouped matrix product over the experts held, weighted
sum back (ROADMAP M1 / D7).

``route`` scores every token against every expert (sigmoid scores in float32,
a selection bias that decides the SELECTION only, top-k, renormalised).
``expert_ffn`` sorts the ``tokens x k`` assignments by expert and runs the
experts' feed-forward as two grouped products over rows that lie expert by
expert: no ``[tokens, experts, width]`` intermediate, no loop over experts in
the program, and an expert with no row is never read. The expert's FORM is a
static argument of the one function: ``swiglu`` (``[gate | up]`` fused, then
``down``: models/lfm2.py) or ``relu2`` (``down(relu(up x)^2)``, no gate, two
matrices an expert: models/nemotron_h.py). ``experts_held = (first, count)``
is the chip's share of a layer (model-configs guide, section 4): the router
keeps its published width, an assignment to an expert not held contributes
nothing, and the shares of a layer add up to the whole layer.

The grouped product is ``grouped_matmul``: the Pallas kernel ``moe_grouped``
on a TPU (the name the device trace shows), ``jax.lax.ragged_dot`` elsewhere.
scripts/moe_choice.py measured both and the megablox ``gmm`` that ships with
jax at this repo's expert shapes on the chip (scripts/moe_choice_result.json).
The kernel takes the whole layer-stacked weights ``[layers * experts, K, N]``
and the layer as a scalar: a slice of the stack handed to a custom call would
be materialised, 0.7 GB copied a layer a step.

Counters: what the device routed, as a small int32 vector that ``expert_ffn``
returns and the step programs hand out beside the tokens: rows by expert,
experts with at least one row (the weight reads the mathematics needs) and
experts held (what reading every expert would be).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: rows of a sorted block up to which one tile holds every assignment (decode
#: batches: the product streams weights, each expert's tile is read once)
ONE_TILE_ROWS = 512
#: rows a tile beyond that (prefill chunks: ~256 rows an expert)
TILE_ROWS = 256
#: widest tile of output columns (a multiple of 128 lanes that divides N)
TILE_COLS = 1024


def resolve_moe_impl(platform: str) -> str:
    """The kernel wherever Mosaic compiles it, ``ragged_dot`` elsewhere."""
    return "pallas" if platform == "tpu" else "xla"


def num_counters(num_experts: int) -> int:
    """Length of the counter vector: rows by expert, reads, slots."""
    return num_experts + 2


def counter_stats(totals, num_experts: int) -> dict:
    """``/stats`` keys from the counter vector summed over dispatches."""
    rows = [int(x) for x in totals[:num_experts]]
    return {
        "moe_routed_rows_total": sum(rows),
        "moe_expert_reads_total": int(totals[num_experts]),
        "moe_expert_slots_total": int(totals[num_experts + 1]),
        "moe_expert_rows": rows,
    }


def route(h, w_router, expert_bias, top_k: int, *, norm_topk: bool = True,
          scaling: float = 1.0, use_bias: bool = True):
    """h [N, H] -> (experts [N, k] int32, weights [N, k] float32).

    ``s = sigmoid(h W)`` in float32; the k experts are the top-k of ``s +
    expert_bias`` (the bias decides the selection only); their weights are
    ``s`` at the selected, over (their sum + 1e-6) with ``norm_topk``, times
    ``scaling``."""
    with jax.named_scope("moe_router"):
        # float32 in and out: the top-k of 32 scores has near-ties, and every
        # one that rounding orders the other way swaps an expert
        scores = jax.nn.sigmoid(jnp.dot(
            h.astype(jnp.float32), w_router.astype(jnp.float32),
            precision=lax.Precision.HIGHEST,
        ))
        choose = scores + expert_bias.astype(jnp.float32) if use_bias else scores
        _, experts = lax.top_k(choose, top_k)
        weights = jnp.take_along_axis(scores, experts, axis=-1)
        if norm_topk:
            weights = weights / (jnp.sum(weights, axis=-1, keepdims=True) + 1e-6)
        return experts.astype(jnp.int32), weights * scaling


def _tile_cols(n: int) -> int:
    for tn in range(min(n, TILE_COLS), 0, -128):
        if n % tn == 0 and tn % 128 == 0:
            return tn
    # no multiple of 128 lanes divides the width (a toy's): ONE block of the
    # whole width. A real width that is no whole number of lane tiles
    # (Nemotron-H's experts: 1,856 = 14.5 x 128) is STORED padded to one
    # (models/nemotron_h.py ``expert_cols``) and never comes here
    return n


def tile_rows(rows: int) -> int:
    """Rows of a tile for ``rows`` sorted assignments (a multiple of 16: one
    bf16 sublane tile)."""
    return -(-rows // 16) * 16 if rows <= ONE_TILE_ROWS else TILE_ROWS


def whole_tiles(rows: int) -> int:
    """``rows`` rounded up to whole tiles."""
    tm = tile_rows(rows)
    return -(-rows // tm) * tm


def _visits(group_sizes, tm: int, tiles_m: int):
    """The kernel's walk over (group, row tile) pairs: every tile a non-empty
    group touches, groups in order, as scalar-prefetch tables of static length
    ``tiles_m + groups - 1`` (the most there can be) and the live count, which
    is the grid's extent: an empty group is never visited."""
    G = group_sizes.shape[0]
    ends = jnp.cumsum(group_sizes).astype(jnp.int32)
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32), ends])
    first_tile = offsets[:-1] // tm
    tiles = jnp.where(group_sizes > 0, (ends - 1) // tm - first_tile + 1, 0)
    upto = jnp.cumsum(tiles).astype(jnp.int32)
    live = upto[-1]
    step = jnp.minimum(jnp.arange(tiles_m + G - 1, dtype=jnp.int32),
                       jnp.maximum(live - 1, 0))
    gid = jnp.minimum(
        jnp.searchsorted(upto, step, side="right").astype(jnp.int32), G - 1
    )
    tile = jnp.clip(first_tile[gid] + step - (upto[gid] - tiles[gid]),
                    0, tiles_m - 1)
    return offsets, gid, tile.astype(jnp.int32), live


def _grouped_kernel(offsets_ref, gid_ref, tile_ref, base_ref,
                    lhs_ref, rhs_ref, out_ref, *, tm: int):
    step = pl.program_id(1)
    g = gid_ref[step]
    acc = jnp.dot(lhs_ref[...], rhs_ref[...], preferred_element_type=jnp.float32)
    row = tile_ref[step] * tm + lax.broadcasted_iota(jnp.int32, acc.shape, 0)
    mine = (row >= offsets_ref[g]) & (row < offsets_ref[g + 1])
    # a tile that several groups share is visited once by each, one after the
    # other: each writes its own rows and keeps the rest
    out_ref[...] = jnp.where(
        mine, acc, out_ref[...].astype(jnp.float32)
    ).astype(out_ref.dtype)


def _grouped_pallas(lhs, rhs, group_sizes, base, out_dtype, interpret: bool):
    m, k = lhs.shape
    n = rhs.shape[2]
    tm, tn = tile_rows(m), _tile_cols(n)
    if m % tm:
        raise ValueError(f"{m} sorted rows are not whole tiles of {tm}")
    tiles_m, G = m // tm, group_sizes.shape[0]
    offsets, gid, tile, live = _visits(group_sizes, tm, tiles_m)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(n // tn, live),
        in_specs=[
            pl.BlockSpec((tm, k), lambda j, s, o, g, t, b: (t[s], 0)),
            pl.BlockSpec((None, k, tn),
                         lambda j, s, o, g, t, b: (b[0] + g[s], 0, j)),
        ],
        out_specs=pl.BlockSpec((tm, tn), lambda j, s, o, g, t, b: (t[s], j)),
    )
    return pl.pallas_call(
        functools.partial(_grouped_kernel, tm=tm),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        interpret=interpret,
        name="moe_grouped",
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=64 * 2**20,
        ),
        cost_estimate=pl.CostEstimate(
            flops=2 * m * k * n, transcendentals=0,
            bytes_accessed=(
                lhs.dtype.itemsize * m * k + rhs.dtype.itemsize * G * k * n
                + jnp.dtype(out_dtype).itemsize * m * n
            ),
        ),
    )(offsets, gid, tile, jnp.reshape(base, (1,)).astype(jnp.int32), lhs, rhs)


def grouped_matmul(lhs, rhs, group_sizes, base, *, out_dtype=None,
                   impl: str = "xla"):
    """``out[r] = lhs[r] @ rhs[base + g]`` for the rows ``r`` of group ``g``:
    rows lie group by group (``group_sizes`` [G] int32), ``rhs`` is the whole
    stack ``[S, K, N]`` and ``base`` a scalar. Rows past the last group hold
    anything (the caller masks them)."""
    out_dtype = out_dtype or lhs.dtype
    if impl in ("pallas", "pallas_interpret"):
        return _grouped_pallas(
            lhs, rhs, group_sizes, base, out_dtype, impl == "pallas_interpret"
        )
    if impl != "xla":
        raise ValueError(f"unknown grouped-product implementation {impl!r}")
    held = lax.dynamic_slice_in_dim(rhs, base, group_sizes.shape[0], axis=0)
    return lax.ragged_dot(
        lhs, held, group_sizes, preferred_element_type=jnp.float32
    ).astype(out_dtype)


def expert_ffn(h, experts, weights, w13, w2, layer, *, num_experts: int,
               experts_held=None, valid=None, impl: str = "xla",
               form: str = "swiglu"):
    """The routed experts' feed-forward for tokens ``h`` [N, H].

    ``experts`` / ``weights`` [N, k] from ``route``; ``w13`` and ``w2`` ``[S,
    ...]`` the layer-stacked expert weights, ``layer`` this layer's index in
    the stack (a scalar, may be traced): with ``form="swiglu"`` ``w13`` is
    ``[S, H, 2 I]`` (gate | up) and an expert is ``w2 (silu(gate) * up)``; with
    ``form="relu2"`` it is ``[S, H, >= I]`` (up alone; columns past ``I`` are
    padding to whole lane tiles) and an expert is ``w2 relu(up)^2``. ``w2`` is
    ``[S, I, H]``. ``experts_held`` (first, count), default all
    ``num_experts``, is the chip's share of the router's experts, and the
    stacks hold the experts held and no others (``S = layers x count``).
    ``valid`` [N] marks real tokens (padding is routed nowhere). Returns (out
    [N, H] float32, counters int32 [E + 2])."""
    if form not in ("swiglu", "relu2"):
        raise ValueError(f"unknown expert form {form!r}")
    N, K = experts.shape
    first, count = experts_held or (0, num_experts)
    inter = w2.shape[1]
    with jax.named_scope("moe_experts"):
        local = experts - first
        ok = (local >= 0) & (local < count)
        if valid is not None:
            ok = ok & valid[:, None]
        # the sentinel group ``count`` (not held, or padding) sorts last
        group = jnp.where(ok, local, count).reshape(-1)
        rows = whole_tiles(N * K)
        group = jnp.concatenate(
            [group, jnp.full((rows - N * K,), count, jnp.int32)]
        )
        order = jnp.argsort(group, stable=True).astype(jnp.int32)
        sizes = jnp.sum(
            group[:, None] == jnp.arange(count, dtype=jnp.int32)[None, :], axis=0
        ).astype(jnp.int32)
        # where this layer's held experts start in the stacks
        base = layer * count
        x = jnp.take(h, jnp.minimum(order // K, N - 1), axis=0)
        up = grouped_matmul(x, w13, sizes, base, impl=impl)
        if form == "swiglu":
            act = (
                jax.nn.silu(up[:, :inter].astype(jnp.float32))
                * up[:, inter:].astype(jnp.float32)
            ).astype(h.dtype)
        else:
            # (columns past the expert's width are the stack's padding)
            act = jnp.square(
                jax.nn.relu(up[:, :inter].astype(jnp.float32))
            ).astype(h.dtype)
        y = grouped_matmul(act, w2, sizes, base, out_dtype=jnp.float32, impl=impl)
        # back to token order: where assignment a went in the sorted block
        where = jnp.zeros((rows,), jnp.int32).at[order].set(
            jnp.arange(rows, dtype=jnp.int32)
        )[:N * K]
        y = jnp.take(y, where, axis=0).reshape(N, K, -1)
        out = jnp.sum(jnp.where(ok[..., None], y * weights[..., None], 0.0), axis=1)
        counters = jnp.concatenate([
            jnp.zeros((num_experts,), jnp.int32).at[first:first + count].set(sizes),
            jnp.stack([jnp.sum(sizes > 0), jnp.int32(count)]).astype(jnp.int32),
        ])
    return out, counters
