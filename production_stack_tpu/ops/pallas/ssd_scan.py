"""Mamba-2 (SSD) recurrence over a slot-indexed state pool.

One state-space layer, for every row of a batch; ``NH`` heads of ``P``
channels, ``G`` groups of ``NH / G`` heads that share ``B_t`` and ``C_t`` of
width ``N``, ONE scalar ``A`` a head:

    S_t[h] = exp(dt_t[h] A[h]) S_{t-1}[h] + dt_t[h] * x_t[h] (outer) B_t[g]
    y_t[h] = S_t[h] C_t[g] + D[h] x_t[h]                      g = h // (NH / G)

``S[h]`` is ``[P, N]`` float32 and OUTLIVES the call: it is read from and
written back to ``pool[layer, slot]`` in place (one slot a running sequence,
the last slot a null slot for padded rows). A row's first chunk (``first``)
starts from zero, so a recycled slot needs no clearing; positions past a row's
``lens`` carry ``dt = 0`` (the caller masks it), which leaves the state as it
is, and whole padded rows (``lens == 0``) are skipped.

**The pool's layout** is ``[layers, slots + 1, NH / 2, N, 2 P]``: the states
of heads ``2i`` and ``2i + 1`` TRANSPOSED and side by side (``[N, 2 x 64]`` =
one ``[128, 128]`` float32 tile a pair at Nemotron-H's sizes; the same 2 MiB a
row a layer as ``[NH, P, N]``). So ``x_t``, ``dt_t`` and ``y_t`` keep the
``[.., NH * P]`` rows the matmuls leave them in (a pair is 128 lanes of the
row), ``y``'s sum over ``N`` runs over sublanes (vector adds, one cross-sublane
reduce a pair), and only ``B_t`` / ``C_t`` (``G * N`` numbers a token) have to
stand as columns.

Two kernels, named for the device trace:

- ``ssd_step_decode`` (``T == 1``): one grid cell a row streams the row's
  whole state through VMEM once (in and out, aliased) and does the update on
  the VPU in float32. Bandwidth-bound: 2 x 2 MiB a row a layer.
- ``ssd_scan_prefill`` (``T`` a multiple of ``BLOCK`` = 128 = the published
  ``chunk_size``): the chunked (SSD) form, no walk over single steps. Inside a
  block of 128 positions, with ``cum_t`` the running sum of ``dt A`` from the
  block's start,

      Y = (L o (C B^T) o dt) X  +  exp(cum) o (C S_in^T)  +  D X
      S_out^T = exp(cum_end) S_in^T + B^T (exp(cum_end - cum) o dt o X)
      L[t, s] = exp(cum_t - cum_s) for s <= t, else 0

  which is five ``[128, 128]`` products a head pair on the MXU (``C B^T`` once
  a group). The MXU's operands are bfloat16 with float32 accumulation, as in
  the published kernels of this recurrence (``L o C B^T o dt`` and the carried
  state are rounded where they ENTER a product; the state that is kept is
  float32, as are ``cum``, ``L`` and every sum). The state block stays in VMEM
  while the innermost grid axis walks a row's blocks.

Off the TPU the same mathematics runs as plain ``jax.numpy`` in float32
(``_ssd_jnp``: the chunked form for ``T > 1``, the step for ``T == 1``),
chosen by platform alone (``ssm_scan.resolve_ssm_impl``, the runner's one
rule for both recurrences); the tests run the kernels in
interpret mode against it, and the reference the benchmark compares with
(perfbench/reference/nemotron_h.py) walks single steps.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
#: positions a prefill grid cell takes at once: the published ``chunk_size``
BLOCK = 128


def state_pool_shape(layers: int, slots: int, heads: int, head_dim: int,
                     n_state: int):
    """``[layers, slots + 1 (the null slot), NH / 2, N, 2 P]``: stored the way
    the kernels read it, so no call relays the pool out."""
    if heads % 2 or 2 * head_dim != LANES or n_state % 8:
        raise ValueError(
            f"{heads} heads of {head_dim} channels, state {n_state}: the SSD "
            "state pool pairs heads of 64 channels into 128-lane tiles (and a "
            "pair shares its group's B and C: an even number of heads a group)"
        )
    return (layers, slots + 1, heads // 2, n_state, 2 * head_dim)


def to_pool(s):
    """``[B, NH, P, N]`` -> the pool's ``[B, NH / 2, N, 2 P]``."""
    B, NH, P, N = s.shape
    return s.reshape(B, NH // 2, 2, P, N).transpose(0, 1, 4, 2, 3).reshape(
        B, NH // 2, N, 2 * P)


def _pad_time(v, pad: int):
    """``pad`` more positions behind axis 1 (zeros: ``dt = 0`` there)."""
    return jnp.pad(v, [(0, 0), (0, pad)] + [(0, 0)] * (v.ndim - 2))


def from_pool(s):
    """The pool's ``[B, NH / 2, N, 2 P]`` -> ``[B, NH, P, N]``."""
    B, pairs, N, P2 = s.shape
    return s.reshape(B, pairs, N, 2, P2 // 2).transpose(0, 1, 3, 4, 2).reshape(
        B, 2 * pairs, P2 // 2, N)


# -- plain jax.numpy ---------------------------------------------------------


def _ssd_jnp(x, dt, a, b_mat, c_mat, d, pool, slots, first, lens, layer):
    """The recurrence in float32 ``jax.numpy``: one step for ``T == 1``, the
    chunked form over blocks of ``BLOCK`` otherwise (``lax.scan`` over the
    blocks carries the state)."""
    B, T, NH, P = x.shape
    G, N = b_mat.shape[2:]
    per = NH // G
    f32 = jnp.float32
    x, dt = x.astype(f32), dt.astype(f32)
    # float32 products whatever the platform's default would make of them
    einsum = functools.partial(jnp.einsum, precision=lax.Precision.HIGHEST)
    # every head meets its group's B and C
    b_h = jnp.repeat(b_mat.astype(f32), per, axis=2)   # [B, T, NH, N]
    c_h = jnp.repeat(c_mat.astype(f32), per, axis=2)
    s0 = from_pool(pool[layer, slots].astype(f32))
    s0 = jnp.where(first[:, None, None, None], 0.0, s0)
    valid = jnp.arange(T)[None, :] < lens[:, None]
    if T == 1:
        decay = jnp.exp(dt[:, 0] * a[None])                              # [B, NH]
        s = decay[..., None, None] * s0 + einsum(
            "bhp,bhn->bhpn", dt[:, 0, :, None] * x[:, 0], b_h[:, 0])
        y = einsum("bhpn,bhn->bhp", s, c_h[:, 0])[:, None]
    else:
        pad = -T % BLOCK
        blocks = lambda v: jnp.moveaxis(  # noqa: E731
            _pad_time(v, pad).reshape((B, -1, BLOCK) + v.shape[2:]), 1, 0)
        tri = jnp.tril(jnp.ones((BLOCK, BLOCK), bool))

        def block(s, xs):
            xb, dtb, bb, cb = xs            # [B, Q, NH, ..]
            cum = jnp.cumsum(dtb * a[None, None], axis=1)                # [B, Q, NH]
            diff = cum[:, :, None, :] - cum[:, None, :, :]               # [B, t, s, NH]
            decay = jnp.where(tri[None, :, :, None], jnp.exp(jnp.minimum(diff, 0.0)), 0.0)
            scores = einsum("bthn,bshn->btsh", cb, bb) * decay * dtb[:, None]
            y = einsum("btsh,bshp->bthp", scores, xb)
            y = y + jnp.exp(cum)[..., None] * einsum("bthn,bhpn->bthp", cb, s)
            tail = jnp.exp(cum[:, -1:, :] - cum) * dtb                   # [B, Q, NH]
            s = jnp.exp(cum[:, -1])[..., None, None] * s + einsum(
                "bshp,bshn->bhpn", tail[..., None] * xb, bb)
            return s, y

        s, ys = lax.scan(block, s0, (blocks(x), blocks(dt), blocks(b_h), blocks(c_h)))
        y = jnp.moveaxis(ys, 0, 1).reshape(B, T + pad, NH, P)[:, :T]
    y = jnp.where(valid[..., None, None], y + d[None, None, :, None] * x, 0.0)
    # a padded row keeps the state it read
    s = jnp.where((lens > 0)[:, None, None, None], s, s0)
    pool = pool.at[layer, slots].set(to_pool(s).astype(pool.dtype))
    return y, pool


# -- decode: one step a row --------------------------------------------------


def _decode_kernel(slots_ref, lens_ref, first_ref, layer_ref,
                   decay_ref, dtx_ref, dx_ref, bt_ref, ct_ref, s_in_ref,
                   y_ref, s_out_ref, *, pairs: int, groups: int):
    del slots_ref, layer_ref  # consumed by the index maps
    row = pl.program_id(0)
    live = lens_ref[row] > 0
    fresh = first_ref[row] != 0

    @pl.when(jnp.logical_not(live))
    def _():
        # a padded row: the null slot keeps what it held
        s_out_ref[...] = s_in_ref[...]
        y_ref[...] = jnp.zeros_like(y_ref)

    @pl.when(live)
    def _():
        per = pairs // groups
        n_state = bt_ref.shape[1]
        for g in range(groups):
            shape = (n_state, LANES)
            # this group's B and C as columns, across the lanes
            b_col = jnp.broadcast_to(bt_ref[0, :, g:g + 1], shape)
            c_col = jnp.broadcast_to(ct_ref[0, :, g:g + 1], shape)
            for i in range(g * per, (g + 1) * per):
                s = jnp.where(fresh, 0.0, s_in_ref[0, 0, i])
                s = decay_ref[0, i:i + 1, :] * s + dtx_ref[0, i:i + 1, :] * b_col
                s_out_ref[0, 0, i] = s
                y_ref[0, i:i + 1, :] = (
                    jnp.sum(s * c_col, axis=0, keepdims=True) + dx_ref[0, i:i + 1, :]
                )


def _ssd_decode_pallas(x, dt, a, b_mat, c_mat, d, pool, slots, first, lens,
                       layer, interpret):
    B, _, NH, P = x.shape
    G, N = b_mat.shape[2:]
    pairs = NH // 2
    if 2 * P != LANES or pairs % G:
        raise ValueError(
            f"ssd_step_decode is written for head_dim {LANES // 2} and an even "
            f"number of heads a group (got {P}, {NH} heads in {G} groups)"
        )
    f32 = jnp.float32
    x, dt = x[:, 0].astype(f32), dt[:, 0].astype(f32)            # [B, NH, P], [B, NH]
    rows = lambda v: v.reshape(B, pairs, LANES)  # noqa: E731
    per_lane = lambda v: rows(jnp.broadcast_to(v[..., None], (B, NH, P)))  # noqa: E731
    row = pl.BlockSpec((1, pairs, LANES), lambda b, *_: (b, 0, 0))
    col = pl.BlockSpec((1, N, G), lambda b, *_: (b, 0, 0))
    state = pl.BlockSpec(
        (1, 1, pairs, N, LANES),
        lambda b, slots, lens, first, layer: (layer[0], slots[b], 0, 0, 0),
    )
    y, pool = pl.pallas_call(
        functools.partial(_decode_kernel, pairs=pairs, groups=G),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4, grid=(B,),
            in_specs=[row, row, row, col, col, state],
            out_specs=[row, state],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((B, pairs, LANES), f32),
            jax.ShapeDtypeStruct(pool.shape, pool.dtype),
        ],
        # operand 9 (4 scalar-prefetch operands first) is the pool
        input_output_aliases={9: 1},
        interpret=interpret,
        name="ssd_step_decode",
        compiler_params=pltpu.CompilerParams(
            # rows share the null slot: in order, one after the other
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=64 * 2**20,
        ),
        cost_estimate=pl.CostEstimate(
            flops=6 * B * NH * P * N, transcendentals=0,
            bytes_accessed=4 * B * (2 * NH * P * N + 4 * NH * P + 2 * G * N),
        ),
    )(
        slots.astype(jnp.int32), lens.astype(jnp.int32),
        first.astype(jnp.int32), jnp.reshape(layer, (1,)).astype(jnp.int32),
        per_lane(jnp.exp(dt * a[None])), rows(dt[..., None] * x),
        rows(d[None, :, None] * x),
        jnp.swapaxes(b_mat[:, 0].astype(f32), 1, 2),
        jnp.swapaxes(c_mat[:, 0].astype(f32), 1, 2), pool,
    )
    return y.reshape(B, 1, NH, P), pool


# -- prefill: the chunked form -----------------------------------------------


def _prefill_kernel(slots_ref, lens_ref, first_ref, layer_ref,
                    x_ref, b_ref, bt_ref, c_ref, cols_ref, rows_ref, d_ref,
                    s_in_ref, y_ref, s_out_ref, *, heads: int):
    del slots_ref, layer_ref  # consumed by the index maps
    row, tb = pl.program_id(0), pl.program_id(2)
    n_valid = jnp.clip(lens_ref[row] - tb * BLOCK, 0, BLOCK)
    fresh = first_ref[row] != 0
    f32, bf16 = jnp.float32, jnp.bfloat16

    @pl.when((tb == 0) & fresh)
    def _():
        s_out_ref[...] = jnp.zeros_like(s_out_ref)

    @pl.when((tb == 0) & jnp.logical_not(fresh))
    def _():
        s_out_ref[...] = s_in_ref[...]

    @pl.when(n_valid == 0)
    def _():
        y_ref[...] = jnp.zeros_like(y_ref)

    @pl.when(n_valid > 0)
    def _():
        cols = cols_ref[0, 0]      # [Q, 2 * heads]: cum | dt, a column a head
        rows = rows_ref[0, 0]      # [2 * heads, Q]: cum | dt, a row a head
        cm, bm, btm = c_ref[0], b_ref[0], bt_ref[0, 0]
        # C B^T of the group: what position t takes from position s
        scores = lax.dot_general(
            cm, bm, (((1,), (1,)), ((), ())), preferred_element_type=f32)
        t_at = lax.broadcasted_iota(jnp.int32, (BLOCK, BLOCK), 0)
        s_at = lax.broadcasted_iota(jnp.int32, (BLOCK, BLOCK), 1)
        causal = s_at <= t_at
        low = lax.broadcasted_iota(jnp.int32, (BLOCK, LANES), 1) < LANES // 2
        low_row = low[:1]
        for i in range(heads // 2):
            lanes = slice(i * LANES, (i + 1) * LANES)
            xp = x_ref[0, :, lanes]                     # [Q, 2 P] bf16
            inside, update, grow, decay = [], [], [], []
            for h in (2 * i, 2 * i + 1):
                cum_c, cum_r = cols[:, h:h + 1], rows[h:h + 1, :]
                dt_r = rows[heads + h:heads + h + 1, :]
                end = cum_r[:, BLOCK - 1:]              # [1, 1]
                # exp(cum_t - cum_s) for s <= t (the difference is <= 0 there)
                span = jnp.where(causal, jnp.exp(jnp.minimum(cum_c - cum_r, 0.0)), 0.0)
                inside.append(jnp.dot(
                    (span * scores * dt_r).astype(bf16), xp, preferred_element_type=f32))
                update.append(jnp.dot(
                    (btm.astype(f32) * (jnp.exp(end - cum_r) * dt_r)).astype(bf16), xp,
                    preferred_element_type=f32))
                grow.append(jnp.exp(cum_c))
                decay.append(jnp.exp(end))
            s = s_out_ref[0, 0, i]                      # [N, 2 P] float32
            carried = jnp.dot(cm, s.astype(bf16), preferred_element_type=f32)
            y_ref[0, :, lanes] = (
                jnp.where(low, inside[0], inside[1])
                + jnp.where(low, grow[0], grow[1]) * carried
                + d_ref[0, :, lanes] * xp.astype(f32)
            )
            s_out_ref[0, 0, i] = (
                jnp.where(low_row, decay[0], decay[1]) * s
                + jnp.where(low, update[0], update[1])
            )


def _ssd_prefill_pallas(x, dt, a, b_mat, c_mat, d, pool, slots, first, lens,
                        layer, interpret):
    B, T, NH, P = x.shape
    G, N = b_mat.shape[2:]
    per = NH // G                    # heads a group: one grid cell
    if N != BLOCK or 2 * P != LANES or per % 2:
        raise ValueError(
            f"ssd_scan_prefill is written for state {BLOCK}, head_dim "
            f"{LANES // 2} and an even number of heads a group (got {N}, {P}, {per})"
        )
    f32, bf16 = jnp.float32, jnp.bfloat16
    pad = -T % BLOCK
    x, dt, b_mat, c_mat = (_pad_time(v, pad) for v in (x, dt.astype(f32), b_mat, c_mat))
    Tp, nb = T + pad, (T + pad) // BLOCK
    # the running sum of dt A from each block's start, by head
    cum = jnp.cumsum((dt * a[None, None]).reshape(B, nb, BLOCK, NH), axis=2)
    by_group = lambda v: v.reshape(B, Tp, G, per).transpose(0, 2, 1, 3)  # noqa: E731
    cols = jnp.concatenate([by_group(cum.reshape(B, Tp, NH)), by_group(dt)], axis=-1)
    seq = pl.BlockSpec((1, BLOCK, per * P), lambda b, g, t, *_: (b, t, g))
    bc = pl.BlockSpec((1, BLOCK, N), lambda b, g, t, *_: (b, t, g))
    state = pl.BlockSpec(
        (1, 1, per // 2, N, LANES),
        lambda b, g, t, slots, lens, first, layer: (layer[0], slots[b], g, 0, 0),
    )
    y, pool = pl.pallas_call(
        functools.partial(_prefill_kernel, heads=per),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4, grid=(B, G, nb),
            in_specs=[
                seq, bc,
                pl.BlockSpec((1, 1, N, BLOCK), lambda b, g, t, *_: (b, g, 0, t)),
                bc,
                pl.BlockSpec((1, 1, BLOCK, 2 * per), lambda b, g, t, *_: (b, g, t, 0)),
                pl.BlockSpec((1, 1, 2 * per, BLOCK), lambda b, g, t, *_: (b, g, 0, t)),
                pl.BlockSpec((1, 1, per * P), lambda b, g, t, *_: (g, 0, 0)),
                state,
            ],
            out_specs=[seq, state],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((B, Tp, NH * P), f32),
            jax.ShapeDtypeStruct(pool.shape, pool.dtype),
        ],
        # operand 11 (4 scalar-prefetch operands first) is the pool
        input_output_aliases={11: 1},
        interpret=interpret,
        name="ssd_scan_prefill",
        compiler_params=pltpu.CompilerParams(
            # rows share the null slot: in order; a row's groups and blocks too
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
            vmem_limit_bytes=64 * 2**20,
        ),
        cost_estimate=pl.CostEstimate(
            flops=2 * B * Tp * BLOCK * (G * N + NH * (2 * BLOCK + 4 * P)),
            transcendentals=B * Tp * NH * (BLOCK + 2),
            bytes_accessed=B * Tp * (6 * NH * P + 6 * G * N + 16 * NH)
            + 8 * B * NH * P * N,
        ),
    )(
        slots.astype(jnp.int32), lens.astype(jnp.int32),
        first.astype(jnp.int32), jnp.reshape(layer, (1,)).astype(jnp.int32),
        x.astype(bf16).reshape(B, Tp, NH * P),
        b_mat.astype(bf16).reshape(B, Tp, G * N),
        b_mat.astype(bf16).transpose(0, 2, 3, 1),
        c_mat.astype(bf16).reshape(B, Tp, G * N),
        cols, jnp.swapaxes(cols, 2, 3),
        jnp.broadcast_to(d.astype(f32)[:, None], (NH, P)).reshape(G, 1, per * P),
        pool,
    )
    valid = jnp.arange(T)[None, :] < lens[:, None]
    y = jnp.where(valid[..., None], y[:, :T], 0.0)
    return y.reshape(B, T, NH, P), pool


def _named(name: str, fn):
    def scan(x, dt, a, b_mat, c_mat, d, pool, slots, first, lens, layer,
             interpret=False):
        return fn(x, dt, a, b_mat, c_mat, d, pool, slots, first, lens, layer,
                  interpret)

    scan.__name__ = scan.__qualname__ = name
    return jax.jit(scan, static_argnames=("interpret",))


ssd_scan_prefill = _named("ssd_scan_prefill", _ssd_prefill_pallas)
ssd_step_decode = _named("ssd_step_decode", _ssd_decode_pallas)


def ssd_scan(x, dt, a, b_mat, c_mat, d, pool, slots, first, lens, layer, *,
             impl: str = "xla"):
    """``(y [B, T, NH, P] float32, pool)`` for one Mamba-2 layer.

    x: ``[B, T, NH, P]`` (after the convolution and its silu); dt: ``[B, T,
    NH]`` float32 after its softplus, ZERO at padded positions; a: ``[NH]``
    (``-exp(A_log)``); b_mat, c_mat: ``[B, T, G, N]``; d: ``[NH]``; pool:
    ``state_pool_shape(...)`` float32; slots, lens: ``[B]`` int32; first:
    ``[B]`` bool; layer: int32 scalar (index into the pool's first axis). impl:
    "pallas" | "pallas_interpret" | "xla"."""
    a, d = a.astype(jnp.float32), d.astype(jnp.float32)
    if impl == "xla":
        return _ssd_jnp(x, dt, a, b_mat, c_mat, d, pool, slots, first, lens, layer)
    if impl not in ("pallas", "pallas_interpret"):
        raise ValueError(f"unknown SSD implementation {impl!r}")
    fn = ssd_step_decode if x.shape[1] == 1 else ssd_scan_prefill
    return fn(x, dt, a, b_mat, c_mat, d, pool, slots, first, lens, layer,
              interpret=impl == "pallas_interpret")
