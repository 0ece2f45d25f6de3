"""LFM2-MoE (LiquidAI LFM2-8B-A1B, ``model_type: lfm2_moe``) as pure
functional JAX.

Layer ``i`` is ``r = x; x = r + mixer_i(rmsnorm(x)); x = x + ffn_i(rmsnorm(x))``
then a final rmsnorm (``embedding_norm``) and the head TIED to the embedding.

- ``layer_types[i] == "conv"``, the short-convolution mixer (``L =
  conv_L_cache``): ``[B, C, u] = h W_in``; ``g = B * u``; a causal depthwise
  convolution of width L over time, no bias, no activation; ``out = (C * c)
  W_out``. The state a sequence keeps is the last ``L - 1`` rows of ``g``.
- ``"full_attention"``: ``q, k, v = h Wq, h Wk, h Wv`` (no bias); ``q`` and
  ``k`` through an RMS norm over head_dim with a weight of their own, THEN
  rope (rotate-half); causal softmax attention, GQA; ``Wo``.
- ``ffn_i`` for ``i < num_dense_layers``: the dense SwiGLU. Otherwise the
  sparse experts (ops/moe.py): sigmoid router in float32, ``expert_bias``
  decides the selection only, top-k renormalised, no shared expert, no token
  dropped.

Two kinds of state live between steps: pages of keys and values for the
attention layers (``num_kv_layers`` of them) and, for every running sequence,
the convolution tails of the conv layers (``init_state``: one slot a sequence
+ a null slot that padded rows write; a chunk that starts at position 0
starts from zeros inside the program). This family has no scan kernel: the
slot mechanism of models/jamba.py serves a second row shape.

Structure: weights stacked BY KIND (``conv_layers``, ``attn_layers``,
``dense_ffn``, ``moe_ffn``) and the layers walked by ``lax.scan``, one scan
over the leading dense layers and one over the sparse ones; where a scan's
layers mix both mixers a ``lax.cond`` picks the layer's, so the program holds
each body once whatever the depth. A layer's weights are indexed out of their
stack where they are used (no slice of a stack or of a pool is materialised).
``forward`` returns what its expert layers routed (ops/moe.py) as a last
element of its own, int32 [``cfg.step_counters``]: the step programs hand it
out beside the tokens.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax import lax

from production_stack_tpu.ops import moe
from production_stack_tpu.ops.attention import (
    burst_attention,
    burst_kv_positions,
    flash_attention,
    gather_kv_pages,
    stale_kv_positions,
    write_kv_pages_all_layers,
)
from production_stack_tpu.ops.norms import rms_norm
from production_stack_tpu.ops.rope import apply_rope, rope_cos_sin

LAYER_TYPES_8B = ("conv", "conv", "full_attention", "conv", "conv", "conv",
                  "full_attention", "conv", "conv", "conv", "full_attention",
                  "conv", "conv", "conv", "full_attention", "conv", "conv",
                  "conv", "full_attention", "conv", "conv", "full_attention",
                  "conv", "conv")


#: how much of an expert's drawn weights is its own (the rest is shared by its
#: layer's experts); ``init_params`` says why
EXPERT_OWN_SHARE = 0.1


@dataclass(frozen=True)
class Lfm2Config:
    vocab_size: int = 65536
    hidden_size: int = 2048
    intermediate_size: int = 7168       # the leading dense layers
    moe_intermediate_size: int = 1792   # one expert
    layer_types: tuple[str, ...] = LAYER_TYPES_8B
    num_dense_layers: int = 2
    num_experts: int = 32
    num_experts_per_tok: int = 4
    norm_topk_prob: bool = True
    use_expert_bias: bool = True
    routed_scaling_factor: float = 1.0
    # the chip's share of every expert layer: (first, count); None = all; the
    # parameter stacks hold the experts held and no others. No published key:
    # a deployment that spreads its experts sets it (ROADMAP M1)
    experts_held: Optional[tuple[int, int]] = None
    num_heads: int = 32
    num_kv_heads: int = 8
    head_dim: int = 64
    conv_L_cache: int = 3
    rope_theta: float = 1000000.0
    norm_eps: float = 1e-5
    max_model_len: int = 4096
    dtype: Any = jnp.bfloat16
    # attention: same contract as LlamaConfig.attn_impl; head_dim 64 runs the
    # XLA path (runner.kernel_refusal; ROADMAP M5)
    attn_impl: str = "auto"
    kv_write_mode: str = "post"
    # grouped product: "auto" (by platform at trace time), "pallas",
    # "pallas_interpret" (tests on the CPU), "xla" (jax.lax.ragged_dot)
    moe_impl: str = "auto"

    @property
    def num_layers(self) -> int:
        return len(self.layer_types)

    @property
    def tie_word_embeddings(self) -> bool:
        return True

    @property
    def sliding_window(self):
        return None

    @property
    def num_kv_layers(self) -> int:
        """Layers that hold pages (``num_layers`` counts the model's)."""
        return self.layer_types.count("full_attention")

    @property
    def num_conv_layers(self) -> int:
        return self.layer_types.count("conv")

    @property
    def num_moe_layers(self) -> int:
        return self.num_layers - self.num_dense_layers

    @property
    def decode_one_page_width(self) -> bool:
        """The scheduler pads every decode dispatch's page table to
        max_model_len's width, so a run meets 7 decode programs and not 21
        (each 3-7 s to compile cold, in whichever run first meets it).
        ``burst_attention`` reads the padding: 8 KiB a token in 4 of 16 layers
        beside 6-9 GB of experts a step, 2-3% of a burst of 8 rows and 7.5%
        of ``tpot_p50_ms`` in its cell (PERF.md PR 46, fourth session)."""
        return True

    @property
    def state_bytes_per_slot(self) -> int:
        """What one running sequence keeps beside its pages: the tails."""
        return (self.num_conv_layers * (self.conv_L_cache - 1) * self.hidden_size
                * jnp.dtype(self.dtype).itemsize)

    @property
    def step_counters(self) -> int:
        """int32 counters a step program returns beside the tokens."""
        return moe.num_counters(self.num_experts)

    @staticmethod
    def from_hf_config(cfg: dict) -> "Lfm2Config":
        """Build from a HuggingFace ``config.json`` (Lfm2MoeForCausalLM)."""
        if cfg.get("conv_bias", False):
            raise NotImplementedError("lfm2_moe with conv_bias")
        if not cfg.get("tie_word_embeddings", True):
            raise NotImplementedError("lfm2_moe with an untied head")
        kinds = tuple(cfg["layer_types"])
        if len(kinds) != cfg["num_hidden_layers"] or set(kinds) - {
            "conv", "full_attention"
        }:
            raise ValueError(
                f"layer_types {kinds} do not name num_hidden_layers="
                f"{cfg['num_hidden_layers']} conv / full_attention layers"
            )
        hidden, heads = cfg["hidden_size"], cfg["num_attention_heads"]
        return Lfm2Config(
            vocab_size=cfg["vocab_size"],
            hidden_size=hidden,
            intermediate_size=cfg["intermediate_size"],
            moe_intermediate_size=cfg["moe_intermediate_size"],
            layer_types=kinds,
            num_dense_layers=cfg.get("num_dense_layers", 0),
            num_experts=cfg["num_experts"],
            num_experts_per_tok=cfg["num_experts_per_tok"],
            norm_topk_prob=cfg.get("norm_topk_prob", True),
            use_expert_bias=cfg.get("use_expert_bias", True),
            routed_scaling_factor=float(cfg.get("routed_scaling_factor", 1.0)),
            num_heads=heads,
            num_kv_heads=cfg.get("num_key_value_heads", heads),
            head_dim=cfg.get("head_dim") or hidden // heads,
            conv_L_cache=cfg.get("conv_L_cache", 3),
            rope_theta=float(cfg.get("rope_theta", 1000000.0)),
            norm_eps=cfg.get("norm_eps", 1e-5),
            max_model_len=cfg.get("max_position_embeddings", 4096),
        )


PRESETS: dict[str, Lfm2Config] = {
    # LFM2-8B-A1B as published: 24 layers (18 conv + 6 attention), 2 dense
    # then 22 sparse of 32 experts top-4: 16.7 GB in bf16, over one v5e chip
    "lfm2-8b-a1b": Lfm2Config(max_model_len=128000),
    # its first 16 layers (four whole periods conv, conv, attention, conv;
    # both dense layers and 14 sparse ones) with all 32 experts and the whole
    # vocabulary: 10.8 GB, what one chip of a two-stage pipeline would hold
    "lfm2-8b-a1b-d16": Lfm2Config(
        layer_types=LAYER_TYPES_8B[:16], max_model_len=128000
    ),
    # the toy: 2 dense + 6 sparse layers in the published order, 8 experts
    # top-2, widths that divide the tiles
    "lfm2-debug": Lfm2Config(
        vocab_size=512,
        hidden_size=128,
        intermediate_size=256,
        moe_intermediate_size=64,
        layer_types=LAYER_TYPES_8B[:8],
        num_dense_layers=2,
        num_experts=8,
        num_experts_per_tok=2,
        num_heads=4,
        num_kv_heads=2,
        head_dim=32,
        max_model_len=256,
    ),
}


def init_params(cfg: Lfm2Config, key: jax.Array) -> dict:
    """Seeded parameter tree. ``expert_bias`` is DRAWN, not zeros: a dropped
    bias must show (the published buffer is a load-balancing offset of the
    order of the score differences).

    A layer's experts are drawn CORRELATED: each is ``sqrt(1 - rho^2)`` of a
    matrix its layer shares + ``rho`` = ``EXPERT_OWN_SHARE`` of its own (same
    variance, same routing, same bytes and products). With independent experts
    the comparison with the float32 reference measures the ROUTER'S TIES, not
    the arithmetic: bf16 activations move a router logit by ~1%, the 4th and
    5th of 32 Gaussian logits lie ~0.15 sigma apart, so ~7% of the (token,
    layer) choices swap an expert, each swap moves the block's output by a
    quarter of itself, and a sound bf16 program reads 0.18-0.51 where the
    reference on float8 weights reads 0.37-1.1 (on the chip; PERF.md section
    6, PR 46). With correlated experts a swap moves the output by ~``rho`` of
    that. ``rho`` is the LARGEST share at which the sound program still stands
    clear of the tolerance, so that WHICH expert a row meets still shows: at
    0.1 a grouped product that reads every expert's neighbour reads 0.23-0.49
    (caught in 11 of 12 seeds) against the sound program's 0.06-0.12 (scripts/
    lfm2_lowprec_control.py, ``wrong_expert``); at 0.05 it read 0.18-0.28."""
    k_embed, k_conv, k_attn, k_dense, k_moe = jax.random.split(key, 5)
    H, I, Im = cfg.hidden_size, cfg.intermediate_size, cfg.moe_intermediate_size
    NH, KH, D, E = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim, cfg.num_experts
    Lc, La = cfg.num_conv_layers, cfg.num_kv_layers
    Ld, Lm = cfg.num_dense_layers, cfg.num_moe_layers
    held = cfg.experts_held[1] if cfg.experts_held else E

    def normal(key, shape, scale, dtype=cfg.dtype):
        return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)

    def experts(key, shape, scale):
        """A layer's experts: a part they share and a part of their own
        (``EXPERT_OWN_SHARE`` of the variance's root)."""
        shared, own = jax.random.split(key)
        rho = EXPERT_OWN_SHARE
        return (scale * (
            (1.0 - rho**2) ** 0.5
            * jax.random.normal(shared, (Lm, 1) + shape, jnp.float32)
            + rho * jax.random.normal(own, (Lm, held) + shape, jnp.float32)
        )).astype(cfg.dtype)

    kc = jax.random.split(k_conv, 3)
    ka = jax.random.split(k_attn, 6)
    kd = jax.random.split(k_dense, 3)
    km = jax.random.split(k_moe, 4)
    return {
        "embed": normal(k_embed, (cfg.vocab_size, H), H**-0.5),
        "conv_layers": {
            "mixer_norm": jnp.ones((Lc, H), cfg.dtype),
            "in_proj": normal(kc[0], (Lc, H, 3 * H), H**-0.5),
            "conv_w": normal(kc[1], (Lc, cfg.conv_L_cache, H), cfg.conv_L_cache**-0.5),
            "out_proj": normal(kc[2], (Lc, H, H), H**-0.5),
        },
        "attn_layers": {
            "mixer_norm": jnp.ones((La, H), cfg.dtype),
            "wq": normal(ka[0], (La, H, NH * D), H**-0.5),
            "wk": normal(ka[1], (La, H, KH * D), H**-0.5),
            "wv": normal(ka[2], (La, H, KH * D), H**-0.5),
            "wo": normal(ka[3], (La, NH * D, H), (NH * D) ** -0.5),
            # drawn, not ones: under a uniform weight the norm commutes with
            # rope's rotation, and a norm applied AFTER rope could not show
            "q_norm": 1.0 + normal(ka[4], (La, D), 0.25),
            "k_norm": 1.0 + normal(ka[5], (La, D), 0.25),
        },
        "dense_ffn": {
            "mlp_norm": jnp.ones((Ld, H), cfg.dtype),
            "w_gate": normal(kd[0], (Ld, H, I), H**-0.5),
            "w_up": normal(kd[1], (Ld, H, I), H**-0.5),
            "w_down": normal(kd[2], (Ld, I, H), I**-0.5),
        },
        "moe_ffn": {
            "mlp_norm": jnp.ones((Lm, H), cfg.dtype),
            "router": normal(km[0], (Lm, H, E), H**-0.5),
            # sigmoid scores of unit-variance logits lie ~0.2 apart
            "expert_bias": normal(km[1], (Lm, E), 0.1, jnp.float32),
            "w13": experts(km[2], (H, 2 * Im), H**-0.5),
            "w2": experts(km[3], (Im, H), Im**-0.5),
        },
        "final_norm": jnp.ones((H,), cfg.dtype),
    }


def init_kv_pages(
    cfg: Lfm2Config, num_pages: int, page_size: int, dtype=None
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Page pools of the ATTENTION layers: [num_kv_layers, P, page, 1, KH *
    D]. A token's kv heads lie side by side in one row of KH * D lanes: with
    head_dim 64 a [.., KH, 64] pool is stored lane-sparse, twice its size, and
    relaid out whole by every step (seen compiling for the v5e)."""
    dtype = dtype or cfg.dtype
    shape = (cfg.num_kv_layers, num_pages, page_size, 1,
             cfg.num_kv_heads * cfg.head_dim)
    return jnp.zeros(shape, dtype), jnp.zeros(shape, dtype)


def init_state(cfg: Lfm2Config, slots: int) -> dict:
    """The convolution tails, ``slots`` sequences + the null slot (index
    ``slots``) that padded rows read and write: ``conv`` [conv layers, slots +
    1, L - 1, H] in the model's dtype."""
    return {
        "conv": jnp.zeros(
            (cfg.num_conv_layers, slots + 1, cfg.conv_L_cache - 1, cfg.hidden_size),
            cfg.dtype,
        ),
    }


def counter_stats(cfg: Lfm2Config, totals) -> dict:
    """``/stats`` keys from the step counters summed over dispatches."""
    return moe.counter_stats(totals, cfg.num_experts)


def _dot_f32(a, w):
    """bf16 into the MXU, float32 out: the projections back into the float32
    residual stream (no rounding before the addition) and the head."""
    return jnp.dot(a.astype(w.dtype), w, preferred_element_type=jnp.float32)


def _at(stack: dict, i):
    """Layer ``i`` of a stacked group, indexed where it is used."""
    return jax.tree.map(
        lambda a: lax.dynamic_index_in_dim(a, i, 0, keepdims=False), stack
    )


def _rows(positions, state_slots):
    """What the conv layers need to know of each row: its slot, whether this
    chunk starts the sequence (then the tail is zeros, whatever the slot's
    last owner left), its valid positions (left-aligned)."""
    return {
        "slots": state_slots.astype(jnp.int32),
        "first": positions[:, 0] == 0,
        "lens": jnp.sum(positions >= 0, axis=1).astype(jnp.int32),
    }


def _taps(seq, w, T: int):
    """Causal depthwise convolution: ``seq`` [B, L - 1 + T, H] is the tail then
    the chunk, tap ``j`` meets the row ``L - 1 - j`` steps back."""
    return sum(
        w[j].astype(jnp.float32) * seq[:, j:j + T].astype(jnp.float32)
        for j in range(w.shape[0])
    )


def _qk_norm_rope(q, k, lp, cos, sin, eps):
    """q and k through their per-head RMS norm, THEN rope."""
    return (apply_rope(rms_norm(q, lp["q_norm"], eps), cos, sin),
            apply_rope(rms_norm(k, lp["k_norm"], eps), cos, sin))


def _conv_mixer(x, lp, cfg: Lfm2Config, pool, li, row):
    """One short-convolution mixer over a chunk. ``row``: per-row ``slots``,
    ``first`` (the chunk starts the sequence), ``lens`` (valid positions,
    left-aligned). Returns (mixer output float32, tail pool)."""
    T, L = x.shape[1], cfg.conv_L_cache
    f32 = jnp.float32
    h = rms_norm(x, lp["mixer_norm"], cfg.norm_eps).astype(cfg.dtype)
    b_gate, c_gate, u = jnp.split(h @ lp["in_proj"], 3, axis=-1)
    g = b_gate * u
    # causal depthwise convolution over [the sequence's last L-1 rows, chunk]
    tail = jnp.where(row["first"][:, None, None], 0, pool[li, row["slots"]])
    seq = jnp.concatenate([tail.astype(g.dtype), g], axis=1)  # [B, L-1+T, H]
    conv = _taps(seq, lp["conv_w"], T)
    # the L-1 rows that end at the row's last valid position (a padded row
    # keeps the tail it read)
    keep = row["lens"][:, None] + jnp.arange(L - 1, dtype=jnp.int32)[None, :]
    pool = pool.at[li, row["slots"]].set(
        jnp.take_along_axis(seq, keep[:, :, None], axis=1).astype(pool.dtype)
    )
    y = (c_gate.astype(f32) * conv).astype(cfg.dtype)
    return _dot_f32(y, lp["out_proj"]), pool


def _dense_ffn(x, lp, cfg: Lfm2Config):
    with jax.named_scope("mlp"):
        h = rms_norm(x, lp["mlp_norm"], cfg.norm_eps).astype(cfg.dtype)
        return _dot_f32(
            jax.nn.silu(h @ lp["w_gate"]) * (h @ lp["w_up"]), lp["w_down"]
        )


def _moe_ffn(x, mp, experts_flat, cfg: Lfm2Config, li, valid, impl):
    """Expert layer ``li`` of the sparse stack ``mp``: (output [B, T, H]
    float32, counters). ``experts_flat``: the experts' two stacks as [layers *
    experts, ...]."""
    B, T, H = x.shape
    # the router reads the normed stream BEFORE it is rounded to the experts'
    # dtype (ops/moe.route says why)
    h32 = rms_norm(
        x, lax.dynamic_index_in_dim(mp["mlp_norm"], li, 0, keepdims=False),
        cfg.norm_eps,
    ).reshape(B * T, H)
    h = h32.astype(cfg.dtype)
    experts, weights = moe.route(
        h32,
        lax.dynamic_index_in_dim(mp["router"], li, 0, keepdims=False),
        lax.dynamic_index_in_dim(mp["expert_bias"], li, 0, keepdims=False),
        cfg.num_experts_per_tok, norm_topk=cfg.norm_topk_prob,
        scaling=cfg.routed_scaling_factor, use_bias=cfg.use_expert_bias,
    )
    out, counters = moe.expert_ffn(
        h, experts, weights, *experts_flat, li,
        num_experts=cfg.num_experts, experts_held=cfg.experts_held,
        valid=valid.reshape(B * T), impl=impl,
    )
    return out.reshape(B, T, H), counters


def forward(
    params: dict,
    cfg: Lfm2Config,
    input_ids: jnp.ndarray,
    positions: jnp.ndarray,
    k_pages: jnp.ndarray,
    v_pages: jnp.ndarray,
    page_table: jnp.ndarray,
    kv_lens: jnp.ndarray,
    all_logits: bool = False,
    kv_burst: Optional[tuple] = None,
    state: Optional[dict] = None,
    state_slots: Optional[jnp.ndarray] = None,
):
    """One forward step (prefill chunk or decode) with paged KV and slotted
    convolution tails.

    Same contract as models/jamba.py ``forward``: ``state`` is ``init_state``'s
    pool, ``state_slots`` [B] int32 (the null slot for padded rows); returns
    ``(logits, k_pages, v_pages, state, counters)``, or ``(logits, k_acc,
    v_acc, state, counters)`` with ``kv_burst``: ``counters`` int32
    [``cfg.step_counters``] is what THIS call's expert layers routed."""
    if cfg.attn_impl not in ("auto", "xla"):
        raise ValueError(
            f"attn_impl={cfg.attn_impl!r}: this family's attention layers run "
            "the XLA path only (head_dim 64; ROADMAP M5)"
        )
    if cfg.kv_write_mode != "post":
        raise ValueError("this family writes pages after attending (kv_write_mode='post')")
    if state is None or state_slots is None:
        raise ValueError("this family's forward needs state= and state_slots=")
    impl = cfg.moe_impl
    if impl == "auto":
        impl = moe.resolve_moe_impl(jax.default_backend())
    B, T = input_ids.shape
    NH, KH, D = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    La, P = k_pages.shape[:2]
    burst = kv_burst is not None
    with jax.named_scope("embed"):
        # a float32 residual stream, as models/jamba.py: 32 additions each
        # rounded to bfloat16 cost more than the [B, T, H] stream does
        x = params["embed"][input_ids].astype(jnp.float32)
    valid = positions >= 0
    row = _rows(positions, state_slots)
    cos, sin = rope_cos_sin(jnp.maximum(positions, 0), D, cfg.rope_theta)
    if burst:
        if T != 1:
            raise ValueError("kv_burst is the decode shape (T == 1)")
        k_acc, v_acc, counts = kv_burst
        kv_pos = burst_kv_positions(
            kv_lens, counts + 1, page_table.shape[1] * k_pages.shape[2], k_acc.shape[2]
        )
        rows = jnp.arange(B, dtype=jnp.int32)
    else:
        kv_pos = stale_kv_positions(page_table, positions, k_pages.shape[2])
    # this step's keys and values by attention layer ([La, B, T or window, 1,
    # KH * D], rows as the pool stores them): the burst's window, or what the
    # commit below writes to the pages
    if burst:
        k_new, v_new = k_acc, v_acc
    else:
        k_new = jnp.zeros((La, B, T, 1, KH * D), k_pages.dtype)
        v_new = jnp.zeros((La, B, T, 1, KH * D), v_pages.dtype)
    pools_flat = (
        k_pages.reshape((La * P,) + k_pages.shape[2:]),
        v_pages.reshape((La * P,) + v_pages.shape[2:]),
    )
    # the experts' whole stacks as [layers * experts, ...] (a bitcast) with
    # the layer a scalar: ops/moe.py says why
    mp = params["moe_ffn"]
    experts_flat = tuple(mp[n].reshape((-1,) + mp[n].shape[2:]) for n in ("w13", "w2"))

    def conv_mixer(x, pool, k_new, v_new, j):
        with jax.named_scope("conv_mixer"):
            out, pool = _conv_mixer(x, _at(params["conv_layers"], j), cfg, pool, j, row)
        return out, pool, k_new, v_new

    def attn_mixer(x, pool, k_new, v_new, j):
        lp = _at(params["attn_layers"], j)
        with jax.named_scope("attn_mixer"):
            h = rms_norm(x, lp["mixer_norm"], cfg.norm_eps).astype(cfg.dtype)
            q, k = _qk_norm_rope(
                (h @ lp["wq"]).reshape(B, T, NH, D), (h @ lp["wk"]).reshape(B, T, KH, D),
                lp, cos, sin, cfg.norm_eps,
            )
            k = k.astype(k_pages.dtype).reshape(B, T, 1, KH * D)
            v = (h @ lp["wv"]).astype(v_pages.dtype).reshape(B, T, 1, KH * D)
            # pages of layer ``j`` out of the pools seen as [La * P, ...] (a
            # bitcast): ``k_pages[j]`` would be a copy of both whole pools
            kc, vc = gather_kv_pages(*pools_flat, page_table + j * P)
            if burst:
                # the burst's window, not the pool, carries this burst's K/V
                k = lax.dynamic_index_in_dim(k_new, j, 0, keepdims=False).at[
                    rows, counts].set(k[:, 0])
                v = lax.dynamic_index_in_dim(v_new, j, 0, keepdims=False).at[
                    rows, counts].set(v[:, 0])
            heads = lambda a: a.reshape(B, -1, KH, D)  # noqa: E731
            if burst:
                attn = burst_attention(
                    q, kc[:, :, 0], vc[:, :, 0], k[:, :, 0], v[:, :, 0], kv_pos,
                    positions, KH,
                )
            else:
                attn = flash_attention(
                    q, heads(jnp.concatenate([kc, k], axis=1)),
                    heads(jnp.concatenate([vc, v], axis=1)),
                    q_positions=positions, kv_lens=kv_lens, kv_positions=kv_pos,
                )
            out = _dot_f32(attn.reshape(B, T, NH * D), lp["wo"])
        k_new = lax.dynamic_update_index_in_dim(k_new, k, j, 0)
        v_new = lax.dynamic_update_index_in_dim(v_new, v, j, 0)
        return out, pool, k_new, v_new

    def layers(carry, lo: int, hi: int, sparse: bool):
        """Layers [lo, hi) as one scan (all dense, or all sparse)."""
        kinds = cfg.layer_types[lo:hi]
        is_attn = [k == "full_attention" for k in kinds]
        # each layer's index in its mixer's stack
        mixer_at = [cfg.layer_types[:lo + i].count(k) for i, k in enumerate(kinds)]
        xs = (
            jnp.asarray(is_attn), jnp.asarray(mixer_at, jnp.int32),
            jnp.arange(hi - lo, dtype=jnp.int32),
        )

        def body(carry, xs):
            x, pool, k_new, v_new, counters = carry
            attn, j, f = xs
            if all(is_attn) or not any(is_attn):
                mixer = attn_mixer if is_attn[0] else conv_mixer
                out, pool, k_new, v_new = mixer(x, pool, k_new, v_new, j)
            else:
                out, pool, k_new, v_new = lax.cond(
                    attn, attn_mixer, conv_mixer, x, pool, k_new, v_new, j
                )
            x = x + out
            if sparse:
                out, routed = _moe_ffn(x, mp, experts_flat, cfg, f, valid, impl)
                counters = counters + routed
            else:
                out = _dense_ffn(x, _at(params["dense_ffn"], f), cfg)
            return (x + out, pool, k_new, v_new, counters), None

        return lax.scan(body, carry, xs)[0]

    carry = (x, state["conv"], k_new, v_new,
             jnp.zeros((cfg.step_counters,), jnp.int32))
    nd = cfg.num_dense_layers
    if nd:
        carry = layers(carry, 0, nd, sparse=False)
    if cfg.num_layers > nd:
        carry = layers(carry, nd, cfg.num_layers, sparse=True)
    x, pool, k_new, v_new, routed = carry
    if not burst:
        with jax.named_scope("kv_commit"):
            k_new, v_new = write_kv_pages_all_layers(
                k_pages, v_pages, k_new, v_new, page_table, positions
            )
    with jax.named_scope("lm_head"):
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        if not all_logits:
            # the last valid token alone meets the vocabulary ([B, V], not [B, T, V])
            last = jnp.maximum(row["lens"] - 1, 0)
            x = jnp.take_along_axis(x, last[:, None, None], axis=1)[:, 0]
        logits = _dot_f32(x, params["embed"].T)
    return logits, k_new, v_new, {"conv": pool}, routed
