"""Selective scan (Mamba-1 recurrence) over a slot-indexed state pool.

The recurrence of one state-space layer, for every row of a batch:

    h_t = exp(delta_t (x) A) * h_{t-1} + (delta_t * u_t) (x) B_t
    y_t = (h_t . C_t + D * u_t) * silu(z_t)

with ``h`` a ``[N, Di]`` float32 state that OUTLIVES the call: it is read from
and written back to ``pool[layer, slot]`` (one slot a running sequence, the
last slot a null slot for padded rows). A row's first chunk (``first``) starts
from zero, so a recycled slot needs no clearing; a row's positions past
``lens`` and whole padded rows (``lens == 0``) are skipped: their ``y`` is zero
and their state is written back as it was read.

ONE kernel body runs both shapes of the serving path: ``T = chunk`` under the
name ``ssm_scan_prefill`` and ``T = 1`` under ``ssm_step_decode`` (the
profiler's trace finds kernels by custom-call name). Layout: ``Di`` is split
as ``[Di / 128, 128]`` (sublanes x lanes) and ``N`` is a LOOP, not a vector
axis: every operand of a step is a dense ``[S, 128]`` tile, ``B_t[n]`` and
``C_t[n]`` are scalars read from SMEM, and ``y_t`` accumulates over ``n`` in
registers with no cross-lane reduction. A chunk's ``u``, ``delta``, ``z`` and
``y`` keep the ``[time, Di]`` rows the matmuls leave them in: eight steps are
one ``[8, S * 128]`` load that splits into eight ``[S, 128]`` tiles inside the
kernel (handing it ``[T, S, 128]`` arrays cost four transposing HBM round trips
a layer in XLA). Time is walked inside the kernel; the
state stays in VMEM across the time blocks of a row (the output block of the
pool is resident while the innermost grid axis advances) and crosses HBM once
in and once out, in place (``input_output_aliases``).

Off the TPU the same mathematics runs as plain ``jax.numpy``
(``_selective_scan_jnp``), chosen by platform alone (``resolve_ssm_impl``, the
way ``runner.resolve_attn_impl`` chooses the attention path); the tests run the
kernel in interpret mode against it.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
# time steps a grid cell walks, and Di tiles ([8, 128] = one vreg a state row)
# it holds while walking them: 16 state rows stay in registers
_T_BLOCK = 128
_J_BLOCK = 8
_GROUP = 8  # time steps loaded at once: the sublanes of one float32 tile


def resolve_ssm_impl(platform: str) -> tuple[str, str]:
    """(implementation, why) from the platform alone: the kernel wherever
    Mosaic compiles it, plain ``jax.numpy`` elsewhere."""
    if platform == "tpu":
        return "pallas", ""
    return "xla", f"no TPU backend (platform={platform})"


def state_pool_shape(layers: int, slots: int, n_state: int, d_inner: int):
    """``[layers, slots + 1 (the null slot), N, Di / 128, 128]``: stored the
    way the kernel reads it, so no call relays the pool out."""
    if d_inner % LANES:
        raise ValueError(f"d_inner {d_inner} is not a multiple of {LANES} lanes")
    return (layers, slots + 1, n_state, d_inner // LANES, LANES)


def _tiles(x, d_inner):
    return x.reshape(x.shape[:-1] + (d_inner // LANES, LANES))


def _scan_kernel(slots_ref, lens_ref, first_ref, layer_ref,
                 u_ref, dt_ref, z_ref, b_ref, c_ref, a_ref, d_ref, h_in_ref,
                 y_ref, h_out_ref, *, n_state: int, t_block: int):
    del slots_ref, layer_ref  # consumed by the index maps
    row, tb = pl.program_id(0), pl.program_id(2)
    n_valid = jnp.clip(lens_ref[row] - tb * t_block, 0, t_block)
    fresh = first_ref[row] != 0

    @pl.when((tb == 0) & fresh)
    def _():
        h_out_ref[...] = jnp.zeros_like(h_out_ref)

    @pl.when((tb == 0) & jnp.logical_not(fresh))
    def _():
        h_out_ref[...] = h_in_ref[...]

    @pl.when(n_valid < t_block)
    def _():
        y_ref[...] = jnp.zeros_like(y_ref)

    d_skip = d_ref[...]

    if t_block == 1:
        # one step: each state row goes VMEM -> registers -> VMEM by itself
        @pl.when(n_valid > 0)
        def _():
            dt, u = dt_ref[0, 0], u_ref[0, 0]
            du = dt * u
            y = jnp.zeros_like(u)
            for n in range(n_state):
                h = jnp.exp(dt * a_ref[n]) * h_out_ref[0, 0, n] + du * b_ref[0, 0, n]
                h_out_ref[0, 0, n] = h
                y = y + h * c_ref[0, 0, n]
            zz = z_ref[0, 0]
            y_ref[0, 0] = (y + d_skip * u) * (zz / (1.0 + jnp.exp(-zz)))
        return

    # a chunk arrives as the matmuls leave it, [time, Di] rows: eight steps
    # are one [8, S * 128] load that splits into eight dense [S, 128] tiles
    lanes = d_skip.shape[0] * LANES

    def group(g, hs):
        rows = pl.ds(pl.multiple_of(g * _GROUP, _GROUP), _GROUP)
        tiles = lambda ref: ref[0, rows, :].astype(jnp.float32).reshape(  # noqa: E731
            _GROUP, lanes // LANES, LANES)
        dt8, u8, z8 = tiles(dt_ref), tiles(u_ref), tiles(z_ref)
        ys = []
        for i in range(_GROUP):
            dt, u = dt8[i], u8[i]
            du = dt * u
            y = jnp.zeros_like(u)
            at = (g * _GROUP + i) * n_state
            out = []
            for n in range(n_state):
                h = jnp.exp(dt * a_ref[n]) * hs[n] + du * b_ref[0, 0, at + n]
                y = y + h * c_ref[0, 0, at + n]
                out.append(h)
            hs = tuple(out)
            y = (y + d_skip * u) * (z8[i] / (1.0 + jnp.exp(-z8[i])))
            ys.append(jnp.where(g * _GROUP + i < n_valid, y, 0.0))  # padded: nothing
        y_ref[0, rows, :] = jnp.stack(ys).reshape(_GROUP, lanes).astype(y_ref.dtype)
        return hs

    # (a step past the row's end has delta = u = 0: it leaves h as it is)
    hs = tuple(h_out_ref[0, 0, n] for n in range(n_state))
    hs = lax.fori_loop(0, pl.cdiv(n_valid, _GROUP), group, hs)
    for n in range(n_state):
        h_out_ref[0, 0, n] = hs[n]


def _selective_scan_pallas(name, u, delta, z, b_mat, c_mat, a, d, pool,
                           slots, first, lens, layer, interpret):
    B, T, Di = u.shape
    N = a.shape[0]
    S = Di // LANES
    t_block = min(T, _T_BLOCK)
    # decode walks one step, so a cell takes the whole width; a prefill cell
    # holds N state rows in registers while it walks, one vreg each
    j_block = S if (T == 1 or S % _J_BLOCK) else _J_BLOCK
    if T % t_block:
        raise ValueError(f"chunk {T} is not a multiple of the time block {t_block}")
    grid = (B, S // j_block, T // t_block)
    f32 = jnp.float32

    if T > 1 and t_block % _GROUP:
        raise ValueError(f"chunk {T} is not a multiple of {_GROUP} steps")
    smem = lambda b, j, t, *_: (b, 0, t)  # noqa: E731
    state = lambda b, j, t, slots, lens, first, layer: (  # noqa: E731
        layer[0], slots[b], 0, j, 0)
    if T == 1:
        # one step a row: tiles [S, 128] (the relayout of a [B, 1, Di] row is
        # nothing); a chunk keeps the [time, Di] rows the matmuls leave
        seq_block = pl.BlockSpec(
            (1, 1, j_block, LANES), lambda b, j, t, *_: (b, t, j, 0))
        seq_shape, seq = (B, T, S, LANES), lambda x: _tiles(x.astype(f32), Di)
    else:
        seq_block = pl.BlockSpec(
            (1, t_block, j_block * LANES), lambda b, j, t, *_: (b, t, j))
        seq_shape, seq = (B, T, Di), lambda x: x.astype(f32)
    bc_block = pl.BlockSpec((1, 1, t_block * N), smem, memory_space=pltpu.SMEM)
    state_block = pl.BlockSpec((1, 1, N, j_block, LANES), state)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=grid,
        in_specs=[
            seq_block, seq_block, seq_block, bc_block, bc_block,
            pl.BlockSpec((N, j_block, LANES), lambda b, j, t, *_: (0, j, 0)),
            pl.BlockSpec((j_block, LANES), lambda b, j, t, *_: (j, 0)),
            state_block,
        ],
        out_specs=[seq_block, state_block],
    )
    steps = B * T * Di * N
    y, pool = pl.pallas_call(
        functools.partial(_scan_kernel, n_state=N, t_block=t_block),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct(seq_shape, f32),
            jax.ShapeDtypeStruct(pool.shape, pool.dtype),
        ],
        # operand 11 (4 scalar-prefetch operands first) is the pool
        input_output_aliases={11: 1},
        interpret=interpret,
        name=name,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        cost_estimate=pl.CostEstimate(
            flops=7 * steps, transcendentals=steps,
            bytes_accessed=4 * (4 * B * T * Di + 2 * B * T * N + 2 * B * N * Di),
        ),
    )(
        slots.astype(jnp.int32), lens.astype(jnp.int32),
        first.astype(jnp.int32), jnp.reshape(layer, (1,)).astype(jnp.int32),
        seq(u), seq(delta), seq(z),
        b_mat.astype(f32).reshape(B, 1, T * N),
        c_mat.astype(f32).reshape(B, 1, T * N),
        _tiles(a.astype(f32), Di), _tiles(d.astype(f32), Di), pool,
    )
    return y.reshape(B, T, Di), pool


def _selective_scan_jnp(u, delta, z, b_mat, c_mat, a, d, pool, slots, first,
                        lens, layer):
    """The same recurrence in plain ``jax.numpy``: gather the rows' states,
    walk time with ``lax.scan``, scatter them back. ``delta`` is zero past a
    row's ``lens`` (the caller masks it), which leaves ``h`` as it was."""
    B, T, Di = u.shape
    N = a.shape[0]
    f32 = jnp.float32
    u, delta, z = u.astype(f32), delta.astype(f32), z.astype(f32)
    h0 = pool[layer, slots].reshape(B, N, Di)
    h0 = jnp.where(first[:, None, None], 0.0, h0).astype(pool.dtype)
    valid = jnp.arange(T)[None, :] < lens[:, None]

    def step(h, xs):
        dt, ut, bt, ct, ok = xs
        h = jnp.exp(dt[:, None, :] * a[None]) * h + (dt * ut)[:, None, :] * bt[:, :, None]
        y = jnp.sum(h * ct[:, :, None], axis=1)
        # the state between two steps has the pool's type
        return h.astype(pool.dtype), jnp.where(ok[:, None], y + d[None] * ut, 0.0)

    swap = lambda x: jnp.swapaxes(x, 0, 1)  # noqa: E731
    h, ys = lax.scan(
        step, h0,
        (swap(delta), swap(u), swap(b_mat.astype(f32)), swap(c_mat.astype(f32)),
         swap(valid)),
    )
    y = swap(ys) * (z * jax.nn.sigmoid(z))
    pool = pool.at[layer, slots].set(h.reshape((B,) + pool.shape[2:]))
    return y, pool


def _named_scan(name: str):
    def scan(u, delta, z, b_mat, c_mat, a, d, pool, slots, first, lens, layer,
             interpret=False):
        return _selective_scan_pallas(
            name, u, delta, z, b_mat, c_mat, a, d, pool, slots, first, lens,
            layer, interpret,
        )

    scan.__name__ = scan.__qualname__ = name
    return jax.jit(scan, static_argnames=("interpret",))


ssm_scan_prefill = _named_scan("ssm_scan_prefill")
ssm_step_decode = _named_scan("ssm_step_decode")


def selective_scan(u, delta, z, b_mat, c_mat, a, d, pool, slots, first, lens,
                   layer, *, impl: str = "xla"):
    """``(y [B, T, Di] float32, pool)`` for one state-space layer.

    u, delta, z: ``[B, T, Di]`` (``u`` after the convolution and its silu;
    ``delta`` after its softplus, ZERO at padded positions); b_mat, c_mat:
    ``[B, T, N]``; a: ``[N, Di]`` (``-exp(A_log)`` transposed); d: ``[Di]``;
    pool: ``state_pool_shape(...)`` float32; slots, lens: ``[B]`` int32;
    first: ``[B]`` bool; layer: int32 scalar (index into the pool's first
    axis). impl: "pallas" | "pallas_interpret" | "xla"."""
    if impl == "xla":
        return _selective_scan_jnp(
            u, delta, z, b_mat, c_mat, a, d, pool, slots, first, lens, layer)
    fn = ssm_step_decode if u.shape[1] == 1 else ssm_scan_prefill
    return fn(u, delta, z, b_mat, c_mat, a, d, pool, slots, first, lens, layer,
              interpret=impl == "pallas_interpret")
