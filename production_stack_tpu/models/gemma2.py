"""Gemma-2 family (gemma-2-2b/9b/27b) as pure functional JAX.

Same TPU-first structure as models/llama.py (layer-stacked weights under one
``lax.scan``, paged KV pools, -1-position padding), with the Gemma-2
architectural differences:

- interleaved attention: even layers use a sliding window, odd layers are
  global. The per-layer window rides the decoder scan as an ``xs`` array, so
  one traced layer still serves both kinds (global layers get a window wider
  than any context — the comparison folds into the existing mask math).
- logit softcapping: ``cap * tanh(x / cap)`` on attention scores (50.0) and
  final logits (30.0).
- GeGLU MLP (tanh-approximate GELU on the gate path).
- sandwich norms: RMSNorm before *and after* each attention/MLP block, with
  Gemma's zero-centered ``(1 + w)`` weight parameterization.
- embeddings scaled by sqrt(hidden); attention scaled by
  ``query_pre_attn_scalar**-0.5`` instead of ``head_dim**-0.5``.

Reference parity: the reference stack serves any vLLM-supported model through
its engine contract (SURVEY.md §1 L4); Gemma-2 is a headline open-weights
family a reference user would expect to deploy unchanged.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax import lax

from production_stack_tpu.ops.attention import (
    flash_attention,
    gather_kv_pages,
    stale_kv_positions,
    write_kv_pages,
    write_kv_pages_all_layers,
)


@dataclass(frozen=True)
class Gemma2Config:
    vocab_size: int = 256000
    hidden_size: int = 3584
    intermediate_size: int = 14336
    num_layers: int = 42
    num_heads: int = 16
    num_kv_heads: int = 8
    head_dim: int = 256
    rope_theta: float = 10000.0
    rms_norm_eps: float = 1e-6
    max_model_len: int = 8192
    query_pre_attn_scalar: float = 256.0
    attn_logit_softcap: Optional[float] = 50.0
    final_logit_softcap: Optional[float] = 30.0
    sliding_window: int = 4096        # even layers; odd layers are global
    dtype: Any = jnp.bfloat16
    attn_impl: str = "auto"           # same contract as LlamaConfig.attn_impl
    kv_write_mode: str = "post"       # same contract as LlamaConfig.kv_write_mode
    decode_pages_per_block: int = 0   # same contract as LlamaConfig
    decode_prefetch_pages: int = 0
    prefill_pages_per_block: int = 0  # same contract as LlamaConfig
    prefill_prefetch_pages: int = 0
    prefill_fused_kv_write: bool = True
    # KV cache dtype (same contract as LlamaConfig.kv_cache_dtype): "int8"
    # stores quantized pages + per-page per-kv-head scales (ops/quant.py);
    # ModelRunner builds the scales pools and threads them as ``kv_scales``
    kv_cache_dtype: str = "auto"

    @property
    def tie_word_embeddings(self) -> bool:
        return True  # Gemma always ties the LM head to the embedding

    @property
    def num_kv_layers(self) -> int:
        return self.num_layers

    @staticmethod
    def from_hf_config(cfg: dict) -> "Gemma2Config":
        hidden = cfg["hidden_size"]
        heads = cfg["num_attention_heads"]
        return Gemma2Config(
            vocab_size=cfg["vocab_size"],
            hidden_size=hidden,
            intermediate_size=cfg["intermediate_size"],
            num_layers=cfg["num_hidden_layers"],
            num_heads=heads,
            num_kv_heads=cfg.get("num_key_value_heads", heads),
            head_dim=cfg.get("head_dim") or hidden // heads,
            rope_theta=cfg.get("rope_theta", 10000.0),
            rms_norm_eps=cfg.get("rms_norm_eps", 1e-6),
            max_model_len=cfg.get("max_position_embeddings", 8192),
            query_pre_attn_scalar=cfg.get("query_pre_attn_scalar", 256.0),
            attn_logit_softcap=cfg.get("attn_logit_softcapping", 50.0),
            final_logit_softcap=cfg.get("final_logit_softcapping", 30.0),
            sliding_window=cfg.get("sliding_window", 4096),
        )


PRESETS: dict[str, Gemma2Config] = {
    "gemma-2-9b": Gemma2Config(),
    "gemma-2-2b": Gemma2Config(
        hidden_size=2304,
        intermediate_size=9216,
        num_layers=26,
        num_heads=8,
        num_kv_heads=4,
        query_pre_attn_scalar=256.0,
    ),
    "gemma2-debug": Gemma2Config(
        vocab_size=512,
        hidden_size=128,
        intermediate_size=256,
        num_layers=2,            # layer 0 sliding, layer 1 global
        num_heads=4,
        num_kv_heads=2,
        head_dim=32,
        query_pre_attn_scalar=32.0,
        sliding_window=8,
        max_model_len=256,
    ),
}


def init_params(cfg: Gemma2Config, key: jax.Array) -> dict:
    """Random-normal parameter tree (layer-stacked). Norm weights start at
    zero — Gemma's RMSNorm multiplies by (1 + w)."""
    k_embed, k_layers = jax.random.split(key)
    L, H, I = cfg.num_layers, cfg.hidden_size, cfg.intermediate_size
    NH, KH, D = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim

    def normal(key, shape, scale):
        return (jax.random.normal(key, shape, jnp.float32) * scale).astype(cfg.dtype)

    ks = jax.random.split(k_layers, 7)
    scale = H**-0.5
    layers = {
        "attn_norm": jnp.zeros((L, H), cfg.dtype),
        "post_attn_norm": jnp.zeros((L, H), cfg.dtype),
        "mlp_norm": jnp.zeros((L, H), cfg.dtype),
        "post_mlp_norm": jnp.zeros((L, H), cfg.dtype),
        "wq": normal(ks[0], (L, H, NH * D), scale),
        "wk": normal(ks[1], (L, H, KH * D), scale),
        "wv": normal(ks[2], (L, H, KH * D), scale),
        "wo": normal(ks[3], (L, NH * D, H), (NH * D) ** -0.5),
        "w_gate": normal(ks[4], (L, H, I), scale),
        "w_up": normal(ks[5], (L, H, I), scale),
        "w_down": normal(ks[6], (L, I, H), I**-0.5),
    }
    return {
        "embed": normal(k_embed, (cfg.vocab_size, H), scale),
        "layers": layers,
        "final_norm": jnp.zeros((H,), cfg.dtype),
    }


def init_kv_pages(
    cfg: Gemma2Config, num_pages: int, page_size: int, dtype=None
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Layer-stacked page pools: [L, num_pages, page_size, KH, D]."""
    dtype = dtype or cfg.dtype
    shape = (cfg.num_layers, num_pages, page_size, cfg.num_kv_heads, cfg.head_dim)
    return jnp.zeros(shape, dtype), jnp.zeros(shape, dtype)


def _rms_norm_1p(x: jnp.ndarray, w: jnp.ndarray, eps: float) -> jnp.ndarray:
    """Gemma RMSNorm: zero-centered weight, stats and (1 + w) in fp32."""
    dtype = x.dtype
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * lax.rsqrt(var + eps) * (1.0 + w.astype(jnp.float32))).astype(dtype)


def _layer_windows(cfg: Gemma2Config) -> jnp.ndarray:
    """Per-layer window sizes for the decoder scan: even layers slide, odd
    layers see everything (a window wider than any position is a no-op)."""
    full = cfg.max_model_len + 1
    return jnp.asarray(
        [cfg.sliding_window if i % 2 == 0 else full for i in range(cfg.num_layers)],
        jnp.int32,
    )


# dp/tp only: ring-attention prefill and pipeline stages are llama-family
# features; the runner gates sp/pp on this declaration
MESH_AXES = ("dp", "tp")


def forward(
    params: dict,
    cfg: Gemma2Config,
    input_ids: jnp.ndarray,
    positions: jnp.ndarray,
    k_pages: jnp.ndarray,
    v_pages: jnp.ndarray,
    page_table: jnp.ndarray,
    kv_lens: jnp.ndarray,
    all_logits: bool = False,
    kv_burst=None,
    mesh=None,
    kv_scales=None,
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """One forward step (prefill chunk or decode) with paged KV.

    Same contract as models/llama.py:forward (including ``kv_burst``
    deferred-scatter decode); returns (logits[B, V] for each sequence's last
    valid token — [B, T, V] when ``all_logits``, used by speculative verify
    — and updated k_pages, v_pages; with ``kv_burst``: the accumulators).
    """
    from production_stack_tpu.ops.rope import apply_rope, rope_cos_sin

    B, T = input_ids.shape
    x = params["embed"][input_ids].astype(cfg.dtype)
    x = x * jnp.asarray(cfg.hidden_size**0.5, cfg.dtype)  # Gemma embed scaling
    cos, sin = rope_cos_sin(jnp.maximum(positions, 0), cfg.head_dim, cfg.rope_theta)
    sm_scale = cfg.query_pre_attn_scalar**-0.5
    eps = cfg.rms_norm_eps

    post_write = cfg.kv_write_mode == "post"
    burst = kv_burst is not None
    quant = kv_scales is not None
    if quant:
        k_scales, v_scales = kv_scales
        if not post_write:
            raise ValueError("kv_cache_dtype=int8 requires kv_write_mode='post'")
    else:
        k_scales = v_scales = None
    if burst:
        if not post_write or T != 1:
            raise ValueError("kv_burst requires kv_write_mode='post' decode")
        k_acc0, v_acc0, burst_counts = kv_burst
        C = k_acc0.shape[2]
        from production_stack_tpu.ops.attention import burst_kv_positions

        kv_pos = burst_kv_positions(
            kv_lens, burst_counts + 1,
            page_table.shape[1] * k_pages.shape[2], C,
        )
    elif post_write:
        # write-after-attend (see models/llama.py): stale pool + in-register
        # chunk K/V, one batched all-layer scatter after the scan
        kv_pos = stale_kv_positions(page_table, positions, k_pages.shape[2])

    # pallas decode streams straight from the stacked pools via a layer
    # index (see models/llama.py stream_pools); prefill kernel v2 does the
    # same for chunks — the per-layer window rides the scan as a traced
    # scalar-prefetch operand, so Gemma's interleaved local/global layers
    # each stream only their live page range
    single_dev = mesh is None or mesh.devices.size == 1
    prefill_kernel_ok = (
        T >= 16 and single_dev and kv_burst is None and post_write
        and cfg.attn_impl in ("pallas_prefill", "pallas_interpret")
    )
    stream_pools = (
        cfg.attn_impl.startswith("pallas") and post_write
        and (T == 1 or prefill_kernel_ok)
    )
    fused_prefill = (
        prefill_kernel_ok and stream_pools and T > 1
        and cfg.prefill_fused_kv_write
    )

    def layer(x_carry, layer_in):
        if fused_prefill:
            if quant:  # scales pools ride the same aliased carry
                x, kp_c, vp_c, ksc_c, vsc_c = x_carry
            else:
                x, kp_c, vp_c = x_carry  # pools ride the scan as aliased carry
                ksc_c = vsc_c = None
        else:
            x = x_carry
            kp_c = vp_c = ksc_c = vsc_c = None
        ksl = vsl = None  # per-layer scale slices (non-stream int8 path)
        if stream_pools:
            if burst:
                lp, li, window, ka, va = layer_in
            else:
                lp, li, window = layer_in
            kp = vp = None
        elif quant and burst:
            lp, kp, vp, ksl, vsl, window, ka, va = layer_in
        elif quant:
            lp, kp, vp, ksl, vsl, window = layer_in
        elif burst:
            lp, kp, vp, window, ka, va = layer_in
        else:
            lp, kp, vp, window = layer_in

        h = _rms_norm_1p(x, lp["attn_norm"], eps)
        q = (h @ lp["wq"]).reshape(B, T, cfg.num_heads, cfg.head_dim)
        k = (h @ lp["wk"]).reshape(B, T, cfg.num_kv_heads, cfg.head_dim)
        v = (h @ lp["wv"]).reshape(B, T, cfg.num_kv_heads, cfg.head_dim)
        q, k = apply_rope(q, cos, sin), apply_rope(k, cos, sin)
        # in-register window / chunk K/V stay fp under int8 pools — they
        # feed the quantizer (post-scan commit or fused in-kernel write)
        pool_dt = cfg.dtype if quant else k_pages.dtype
        if burst:
            rows = jnp.arange(B, dtype=jnp.int32)
            cnt = burst_counts
            kwin = ka.at[rows, cnt].set(k[:, 0].astype(pool_dt))
            vwin = va.at[rows, cnt].set(v[:, 0].astype(pool_dt))
        if not post_write:
            kp, vp = write_kv_pages(
                kp, vp, k.astype(kp.dtype), v.astype(vp.dtype), page_table, positions
            )
        if T == 1 and cfg.attn_impl.startswith("pallas"):
            # decode: page-streaming kernel; the per-layer window rides the
            # scan as a traced scalar-prefetch operand
            from production_stack_tpu.ops.pallas.paged_attention import (
                ragged_paged_attention_decode,
                ragged_paged_attention_decode_sharded,
            )

            if burst:
                cur_kw = dict(
                    k_cur=kwin, v_cur=vwin, cur_lens=burst_counts + 1
                )
            elif post_write:
                cur_kw = dict(
                    k_cur=k[:, 0].astype(pool_dt),
                    v_cur=v[:, 0].astype(pool_dt),
                )
            else:
                cur_kw = dict(k_cur=None, v_cur=None)
            if stream_pools:
                pool_args, layer_kw = (k_pages, v_pages), {"layer": li}
                if quant:
                    layer_kw.update(k_scales=k_scales, v_scales=v_scales)
            else:
                pool_args, layer_kw = (kp, vp), {}
                if quant:
                    layer_kw.update(k_scales=ksl, v_scales=vsl)
            common = dict(
                window=window, sm_scale=sm_scale,
                logit_softcap=cfg.attn_logit_softcap,
                interpret=cfg.attn_impl == "pallas_interpret",
                pages_per_block=cfg.decode_pages_per_block or None,
                prefetch_pages=cfg.decode_prefetch_pages or None,
                **cur_kw, **layer_kw,
            )
            if mesh is not None and mesh.devices.size > 1:
                attn = ragged_paged_attention_decode_sharded(
                    mesh, q[:, 0], *pool_args, page_table, kv_lens, **common
                )[:, None]
            else:
                attn = ragged_paged_attention_decode(
                    q[:, 0], *pool_args, page_table, kv_lens, **common
                )[:, None]
        elif prefill_kernel_ok:
            # chunked prefill: ragged packed grid + contiguous-KV DMA ring
            # (+ fused paged-KV write when the pools ride the carry)
            from production_stack_tpu.ops.pallas.prefill_attention import (
                ragged_paged_attention_prefill,
            )

            kernel_kw = dict(
                window=window, sm_scale=sm_scale,
                logit_softcap=cfg.attn_logit_softcap,
                interpret=cfg.attn_impl == "pallas_interpret",
                pages_per_block=cfg.prefill_pages_per_block or None,
                prefetch_pages=cfg.prefill_prefetch_pages or None,
                layer=li,
            )
            if quant:
                kernel_kw["k_scales"] = ksc_c if fused_prefill else k_scales
                kernel_kw["v_scales"] = vsc_c if fused_prefill else v_scales
            kernel_args = (
                q,
                kp_c if fused_prefill else k_pages,
                vp_c if fused_prefill else v_pages,
                page_table, positions, kv_lens,
                k.astype(pool_dt), v.astype(pool_dt),
                jnp.sum(positions >= 0, axis=1).astype(jnp.int32),
            )
            if fused_prefill and quant:
                attn, kp_c, vp_c, ksc_c, vsc_c = ragged_paged_attention_prefill(
                    *kernel_args, fused_write=True, **kernel_kw
                )
            elif fused_prefill:
                attn, kp_c, vp_c = ragged_paged_attention_prefill(
                    *kernel_args, fused_write=True, **kernel_kw
                )
            else:
                attn = ragged_paged_attention_prefill(
                    *kernel_args, **kernel_kw
                )
        elif post_write:
            if quant:
                from production_stack_tpu.ops.quant import (
                    gather_kv_pages_quant,
                )

                kc, vc = gather_kv_pages_quant(
                    kp, vp, ksl, vsl, page_table, dtype=cfg.dtype
                )
            else:
                kc, vc = gather_kv_pages(kp, vp, page_table)
            if burst:
                kc = jnp.concatenate([kc, kwin.astype(kc.dtype)], axis=1)
                vc = jnp.concatenate([vc, vwin.astype(vc.dtype)], axis=1)
            else:
                kc = jnp.concatenate([kc, k.astype(kc.dtype)], axis=1)
                vc = jnp.concatenate([vc, v.astype(vc.dtype)], axis=1)
            attn = flash_attention(
                q, kc, vc, q_positions=positions, kv_lens=kv_lens,
                sm_scale=sm_scale, window=window,
                logit_softcap=cfg.attn_logit_softcap, kv_positions=kv_pos,
            )
        else:
            kc, vc = gather_kv_pages(kp, vp, page_table)
            attn = flash_attention(
                q, kc, vc, q_positions=positions, kv_lens=kv_lens,
                sm_scale=sm_scale, window=window,
                logit_softcap=cfg.attn_logit_softcap,
            )
        attn = (attn.reshape(B, T, -1)) @ lp["wo"]
        x = x + _rms_norm_1p(attn, lp["post_attn_norm"], eps)

        h = _rms_norm_1p(x, lp["mlp_norm"], eps)
        mlp = (jax.nn.gelu(h @ lp["w_gate"], approximate=True) * (h @ lp["w_up"])) @ lp["w_down"]
        x = x + _rms_norm_1p(mlp, lp["post_mlp_norm"], eps)
        if fused_prefill:
            # the kernel already committed this layer's K/V to the pool
            if quant:
                return (x, kp_c, vp_c, ksc_c, vsc_c), None
            return (x, kp_c, vp_c), None
        if burst:
            out_kv = (kwin, vwin)
        elif post_write:
            out_kv = (k.astype(pool_dt), v.astype(pool_dt))
        else:
            out_kv = (kp, vp)
        return x, out_kv

    if stream_pools:
        xs = (
            params["layers"],
            jnp.arange(cfg.num_layers, dtype=jnp.int32),
            _layer_windows(cfg),
        )
    elif quant:
        xs = (
            params["layers"], k_pages, v_pages, k_scales, v_scales,
            _layer_windows(cfg),
        )
    else:
        xs = (params["layers"], k_pages, v_pages, _layer_windows(cfg))
    if burst:
        x, (k_acc, v_acc) = lax.scan(layer, x, xs + (k_acc0, v_acc0))
        # no pool write: the caller commits the burst once (deferred mode)
    elif fused_prefill and quant:
        # no post-scan scatter: every layer's kernel wrote its pool + scale
        # slices in place
        (x, k_pages, v_pages, k_scales, v_scales), _ = lax.scan(
            layer, (x, k_pages, v_pages, k_scales, v_scales), xs
        )
    elif fused_prefill:
        # no post-scan scatter: every layer's kernel wrote its pool slice
        (x, k_pages, v_pages), _ = lax.scan(
            layer, (x, k_pages, v_pages), xs
        )
    elif post_write and quant:
        x, (k_new, v_new) = lax.scan(layer, x, xs)
        from production_stack_tpu.ops.quant import (
            write_kv_pages_all_layers_quant,
        )

        k_pages, v_pages, k_scales, v_scales = write_kv_pages_all_layers_quant(
            k_pages, v_pages, k_scales, v_scales, k_new, v_new,
            page_table, positions,
        )
    elif post_write:
        x, (k_new, v_new) = lax.scan(layer, x, xs)
        k_pages, v_pages = write_kv_pages_all_layers(
            k_pages, v_pages, k_new, v_new, page_table, positions
        )
    else:
        x, (k_pages, v_pages) = lax.scan(layer, x, xs)

    x = _rms_norm_1p(x, params["final_norm"], eps)
    if not all_logits:
        # select each sequence's last valid token before the vocab projection
        last_idx = jnp.maximum(jnp.sum(positions >= 0, axis=1) - 1, 0)
        x = jnp.take_along_axis(x, last_idx[:, None, None], axis=1)[:, 0]
    logits = (x @ params["embed"].T).astype(jnp.float32)
    cap = cfg.final_logit_softcap
    if cap is not None:  # HF checkpoints may null the cap to disable it
        logits = cap * jnp.tanh(logits / cap)
    if burst:
        return logits, k_acc, v_acc
    if quant:
        return logits, k_pages, v_pages, k_scales, v_scales
    return logits, k_pages, v_pages
