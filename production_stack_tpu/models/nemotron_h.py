"""Nemotron-H (NVIDIA Nemotron-3-Nano-30B-A3B, ``model_type: nemotron_h``) as
pure functional JAX.

``hybrid_override_pattern`` names one block a letter; every block is ``x <- x
+ f(rmsnorm(x))`` with ``f`` a mixer OR a feed-forward, never both; then a
final rmsnorm and an UNTIED head. No projection has a bias; the convolution
has one.

- ``M``, the Mamba-2 mixer (``NH = mamba_num_heads`` heads of ``P =
  mamba_head_dim`` channels, ``Di = NH * P``, NOT ``expand * hidden``; ``G =
  n_groups`` groups of ``N = ssm_state_size``): ``[z | xBC | dt] = h W_in``
  (widths ``Di | Di + 2 G N | NH``); ``xBC <- silu(causal depthwise conv1d(xBC,
  width conv_kernel) + conv_bias)``, split ``x [NH, P]``, ``B [G, N]``, ``C
  [G, N]``; ``dt = softplus(dt + dt_bias)``, ``A = -exp(A_log)`` (a scalar a
  head); the SSD recurrence (ops/pallas/ssd_scan.py: ``S_t[h] = exp(dt A) S +
  dt x (outer) B[g]``, ``y = S C[g] + D x``); the GATED norm ``y <-
  rmsnorm_grouped(y * silu(z))`` (the mean square over each of the G groups of
  ``Di / G`` channels, one weight ``[Di]``); ``W_out``.
- ``E``, the expert layer (ops/moe.py): sigmoid router in float32 over ALL
  ``n_routed_experts``, ``e_score_correction_bias`` decides the selection
  only (``n_group = topk_group = 1``: no group step), top-k renormalised,
  times ``routed_scaling_factor``; an expert is ``down(relu(up x)^2)``, no
  gate; plus ONE shared expert of the same form at its own width, every token,
  weight 1. ``experts_held = (first, count)`` is the chip's share of every
  expert layer: the parameter tree holds those ``count`` experts alone, the
  router keeps its published width, an assignment to an expert not held adds
  nothing (model-configs guide, section 4).
- ``*``, attention: ``q, k, v = h Wq, h Wk, h Wv``, GQA, causal softmax at
  scale ``head_dim ** -0.5``, NO rotary embedding and no other position
  signal (the family's published modelling code applies none; the config's
  ``rope_theta`` and ``partial_rotary_factor`` are unused there), ``Wo``.

Departures from the published code, each for precision (the reference,
perfbench/reference/nemotron_h.py, is float32 throughout): the residual stream
is float32 (``residual_in_fp32`` is false in the published file; fifty-two
additions each rounded to bfloat16 cost more than the ``[B, T, H]`` stream
does, models/jamba.py); ``dt`` leaves its projection in float32 (it is an
EXPONENT summed along the sequence), so ``W_in``'s last ``NH`` columns are kept
as a matrix of their own (``dt_proj``: the same parameters); the router reads
the normed stream before it is rounded; softplus, ``exp(dt A)``, the state and
both norms are float32.

Two kinds of state live between steps: pages of keys and values for the ``*``
blocks (``num_kv_layers``) and, for every running sequence, one slot of
``init_state``'s pools: the SSD state (float32, ``NH * P * N * 4`` bytes a
layer: 2 MiB at the published sizes) and the convolution's last ``conv_kernel
- 1`` input rows (the model's dtype). A row whose chunk starts at position 0
starts from zeros inside the program; padded rows read and write the null
slot.

Structure: weights stacked BY KIND (``ssm_layers``, ``attn_layers``,
``moe_layers``). The pattern is cut into UNITS ``M? *? E?`` (the published one
is 23 units ``M E``, six of them ``M * E``) and each run of units of one shape
is ONE ``lax.scan`` whose body holds the mixer, the expert layer and, where
the run's units differ in it, the attention block under a ``lax.cond``: a step
program holds each body once whatever the depth (52 unrolled blocks compile
for minutes a shape). A layer's weights are indexed out of their stack where
they are used; the experts' stacks go to the grouped product whole.
``forward`` returns what its layers did as a last element of its own, int32
[``cfg.step_counters``]: ops/moe.py's counters, then the tokens the SSD
layers stepped (decode), walked in chunks (prefill), the chunks and the rows.
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
from production_stack_tpu.ops.pallas import ssd_scan

PATTERN_30B = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"

#: counters of the SSD layers, behind ops/moe.py's
SSD_COUNTERS = ("ssd_decode_tokens_total", "ssd_prefill_tokens_total",
                "ssd_prefill_chunks_total", "ssd_prefill_rows_total")


@dataclass(frozen=True)
class NemotronHConfig:
    vocab_size: int = 131072
    hidden_size: int = 2688
    pattern: str = PATTERN_30B
    mamba_num_heads: int = 64
    mamba_head_dim: int = 64
    n_groups: int = 8
    ssm_state_size: int = 128
    conv_kernel: int = 4
    chunk_size: int = 128
    num_experts: int = 128              # the router's width, always
    num_experts_per_tok: int = 6
    moe_intermediate_size: int = 1856
    shared_intermediate_size: int = 3712
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 2.5
    # the chip's share of every expert layer: (first, count); None = all. The
    # parameter tree holds these experts alone
    experts_held: Optional[tuple[int, int]] = None
    num_heads: int = 32
    num_kv_heads: int = 2
    head_dim: int = 128
    norm_eps: float = 1e-5
    max_model_len: int = 4096
    dtype: Any = jnp.bfloat16
    # the state a sequence keeps between steps: float32 (what the model's
    # publisher recommends to servers too)
    ssm_state_dtype: Any = jnp.float32
    # attention: this family's six attention blocks run the XLA path, as
    # models/jamba.py's and models/lfm2.py's do. 2 kv heads x 128 would fit
    # the ragged kernels' rule (runner.kernel_refusal); nothing here calls
    # them and no chip run has held them to this family (ROADMAP M5)
    attn_impl: str = "xla"
    kv_write_mode: str = "post"
    # SSD recurrence: "auto" (ModelRunner resolves by platform), "pallas",
    # "pallas_interpret" (tests on the CPU), "xla" (plain jax.numpy)
    ssm_impl: str = "auto"
    # grouped product: as Lfm2Config.moe_impl
    moe_impl: str = "auto"

    @property
    def num_layers(self) -> int:
        return len(self.pattern)

    @property
    def tie_word_embeddings(self) -> bool:
        return False

    @property
    def sliding_window(self):
        return None

    @property
    def num_kv_layers(self) -> int:
        """Layers that hold pages (``num_layers`` counts the model's)."""
        return self.pattern.count("*")

    @property
    def num_ssm_layers(self) -> int:
        return self.pattern.count("M")

    @property
    def num_moe_layers(self) -> int:
        return self.pattern.count("E")

    @property
    def d_inner(self) -> int:
        return self.mamba_num_heads * self.mamba_head_dim

    @property
    def conv_dim(self) -> int:
        """Channels the convolution runs over: x, B and C."""
        return self.d_inner + 2 * self.n_groups * self.ssm_state_size

    @property
    def experts_stored(self) -> int:
        return self.experts_held[1] if self.experts_held else self.num_experts

    @property
    def expert_cols(self) -> int:
        """Columns an expert's ``up`` matrix is STORED with: its width rounded
        up to whole 128-lane tiles, the rest zeros (1,856 -> 1,920). A TPU
        array's minor dimension is tiled to 128 lanes in HBM whatever its
        shape says, so the zeros take no byte that the 1,856-wide array would
        not; but handed to the grouped product's custom call as it was, XLA
        copied the whole 3.5 GB stack into a padded buffer at every dispatch
        (seen compiling for the v5e)."""
        return -(-self.moe_intermediate_size // 128) * 128

    @property
    def decode_one_page_width(self) -> bool:
        """One decode page-table width, as Lfm2Config's: a run meets 6-7
        decode programs and not three times that, each seconds to compile
        cold; ``burst_attention`` reads the padding (6 KiB a token in 6 of 52
        blocks beside 3 GB of state and 9 GB of weights a step)."""
        return True

    @property
    def state_bytes_per_slot(self) -> int:
        """What one running sequence keeps beside its pages, all layers."""
        ssm = (self.d_inner * self.ssm_state_size
               * jnp.dtype(self.ssm_state_dtype).itemsize)
        conv = (self.conv_kernel - 1) * self.conv_dim * jnp.dtype(self.dtype).itemsize
        return self.num_ssm_layers * (ssm + conv)

    @property
    def step_counters(self) -> int:
        """int32 counters a step program returns beside the tokens."""
        return moe.num_counters(self.num_experts) + len(SSD_COUNTERS)

    @staticmethod
    def from_hf_config(cfg: dict) -> "NemotronHConfig":
        """Build from a HuggingFace ``config.json`` (NemotronHForCausalLM).
        ``experts_held: {"first", "count", "of"}`` beside the published keys is
        the chip's share: ``n_routed_experts`` then counts the experts held
        and ``of`` is the router's published width."""
        pattern = cfg["hybrid_override_pattern"]
        if len(pattern) != cfg["num_hidden_layers"] or set(pattern) - set("ME*"):
            raise ValueError(
                f"hybrid_override_pattern {pattern!r} does not name "
                f"num_hidden_layers={cfg['num_hidden_layers']} blocks M / E / *"
            )
        unsupported = {
            "mlp_hidden_act": cfg.get("mlp_hidden_act", "relu2") != "relu2",
            "mamba_hidden_act": cfg.get("mamba_hidden_act", "silu") != "silu",
            "n_shared_experts": cfg.get("n_shared_experts", 1) != 1,
            "n_group / topk_group": (cfg.get("n_group", 1), cfg.get("topk_group", 1)) != (1, 1),
            "use_conv_bias": not cfg.get("use_conv_bias", True),
            "a projection bias": any(cfg.get(k, False) for k in (
                "mamba_proj_bias", "attention_bias", "mlp_bias", "use_bias")),
            "tie_word_embeddings": cfg.get("tie_word_embeddings", False),
            "sliding_window": bool(cfg.get("sliding_window")),
        }
        if any(unsupported.values()):
            raise NotImplementedError(
                "nemotron_h is implemented at the published "
                "Nemotron-3-Nano settings; not: "
                + ", ".join(k for k, bad in unsupported.items() if bad)
            )
        held = cfg.get("experts_held")
        if held and held["count"] != cfg["n_routed_experts"]:
            raise ValueError(
                f"experts_held counts {held['count']} experts, "
                f"n_routed_experts {cfg['n_routed_experts']}"
            )
        hidden, heads = cfg["hidden_size"], cfg["num_attention_heads"]
        return NemotronHConfig(
            vocab_size=cfg["vocab_size"],
            hidden_size=hidden,
            pattern=pattern,
            mamba_num_heads=cfg["mamba_num_heads"],
            mamba_head_dim=cfg["mamba_head_dim"],
            n_groups=cfg["n_groups"],
            ssm_state_size=cfg["ssm_state_size"],
            conv_kernel=cfg.get("conv_kernel", 4),
            chunk_size=cfg.get("chunk_size", 128),
            num_experts=held["of"] if held else cfg["n_routed_experts"],
            num_experts_per_tok=cfg["num_experts_per_tok"],
            moe_intermediate_size=cfg["moe_intermediate_size"],
            shared_intermediate_size=cfg["moe_shared_expert_intermediate_size"],
            norm_topk_prob=cfg.get("norm_topk_prob", True),
            routed_scaling_factor=float(cfg.get("routed_scaling_factor", 1.0)),
            experts_held=(held["first"], held["count"]) if held else None,
            num_heads=heads,
            num_kv_heads=cfg.get("num_key_value_heads", heads),
            head_dim=cfg.get("head_dim") or hidden // heads,
            norm_eps=cfg.get("layer_norm_epsilon", cfg.get("norm_eps", 1e-5)),
            max_model_len=cfg.get("max_position_embeddings", 4096),
        )


PRESETS: dict[str, NemotronHConfig] = {
    # NVIDIA-Nemotron-3-Nano-30B-A3B (as published 128 experts a layer: 31.6 B
    # parameters, 63 GB in bf16, four times one v5e chip: no preset, nothing
    # here can serve it until experts spread over chips, ROADMAP M1 (a)):
    # one chip's share of a v5e-8 whose 8 chips share every layer: all 52
    # blocks, every mixer, router, shared expert and the whole vocabulary, 16
    # of the 128 routed experts of each expert layer: 11.75 GB
    "nemotron3-nano-30b-ep8": NemotronHConfig(
        experts_held=(0, 16), max_model_len=262144
    ),
    # the toy: all three kinds of block in three runs of units (E | M E, M * E,
    # M E | M, M *), 8 experts top-2 at a width no multiple of 128 divides,
    # the kernels' own head and state sizes
    "nemotron-h-debug": NemotronHConfig(
        vocab_size=512,
        hidden_size=128,
        pattern="EMEM*EMEMM*",
        mamba_num_heads=4,
        mamba_head_dim=64,
        n_groups=2,
        ssm_state_size=128,
        num_experts=8,
        num_experts_per_tok=2,
        moe_intermediate_size=80,
        shared_intermediate_size=160,
        num_heads=4,
        num_kv_heads=2,
        head_dim=32,
        max_model_len=256,
    ),
}


def init_params(cfg: NemotronHConfig, key: jax.Array) -> dict:
    """Seeded parameter tree. What a zero or a one would hide is DRAWN:
    ``expert_bias`` (``e_score_correction_bias``, sigma 0.02: a dropped bias
    selects other experts; NOT models/lfm2.py's 0.1: there every expert is
    held, here a sixteenth of a bias that large moves an expert's share of the
    rows threefold, the load of the 16 HELD experts then swings +-15% with the
    seed, which no trained, load-balancing bias does, and ``tpot_p50_ms`` of
    the cell with it: 14.9-16.7 over six seeds, PERF.md section 6, PR 51), ``a_log_head`` (``A`` uniform in [1, 16], the
    published initialisation), ``dt_bias`` (the inverse softplus of a
    log-uniform step in [1e-3, 1e-1]: with plain normal draws the decay is 0
    or 1 and a lost state could not show), ``d_skip`` and the gated norm's
    weight (1 + 0.25 x normal), ``conv_b``.

    Every BLOCK's output into the residual stream is drawn at ``1 /
    sqrt(num_layers)`` of unit variance (``out_proj``, ``wo``, the experts'
    and the shared expert's ``down`` at that share of their fan-in scale): the
    published ``rescale_prenorm_residual`` (the published code rescales the
    mixers' ``out_proj``; here every block's, as the scheme it cites does). An
    expert block adds TWO branches, the routed sum (2.5 x six weights that sum
    to one: 1.02 of one expert's rms where all 128 are held) and the shared
    expert: each is drawn at ``1 / sqrt(2)`` of the block's share. (The first
    draw of this PR gave each branch the whole share and then, after the
    readings, the routed experts alone 0.7 of it: a constant fitted to the
    check. The rule replaces it; PERF.md section 6, PR 51, has the readings of
    both.) Without the rescaling 52 blocks of unit variance each, with a
    squared activation and a top-6 of 128 among them, on an embedding of
    negligible size, amplify a bfloat16 rounding until the sound program reads 0.11-0.46 against the float32
    reference, whatever the experts' correlation and with the recurrence in
    float32 too (PERF.md section 6, PR 51): the comparison would measure the
    random network's chaos, not the arithmetic. For the same reason the
    embedding is drawn at unit variance an element (the blocks then CORRECT a
    stream of their own size, 52 x 1/52 of variance against 1, instead of
    being it). Shapes, bytes and the routing's statistics are what they
    were."""
    k_embed, k_head, k_ssm, k_attn, k_moe = jax.random.split(key, 5)
    H, Di, C = cfg.hidden_size, cfg.d_inner, cfg.conv_dim
    NH, K = cfg.mamba_num_heads, cfg.conv_kernel
    QH, KH, D = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    E, Es = cfg.num_experts, cfg.experts_stored
    I, Ish = cfg.moe_intermediate_size, cfg.shared_intermediate_size
    Ls, La, Le = cfg.num_ssm_layers, cfg.num_kv_layers, cfg.num_moe_layers
    f32 = jnp.float32
    back = cfg.num_layers ** -0.5   # rescale_prenorm_residual
    branch = back * 2.0 ** -0.5     # an expert block: routed sum + shared expert

    def normal(key, shape, scale, dtype=cfg.dtype):
        return (jax.random.normal(key, shape, f32) * scale).astype(dtype)

    def experts(key, shape, scale):
        """A layer's held experts, drawn INDEPENDENT (models/lfm2.py draws its
        alike; here that did not lower the sound program's reading against the
        reference, 0.17-0.34 at an own share of 0.1 and 0.11-0.46 at 1.0 on
        the chip: the scales below did, PERF.md section 6, PR 51)."""
        return normal(key, (Le, Es) + shape, scale)

    ks = jax.random.split(k_ssm, 9)
    step = jnp.exp(
        jax.random.uniform(ks[5], (Ls, NH), f32)
        * (jnp.log(1e-1) - jnp.log(1e-3)) + jnp.log(1e-3)
    )
    ka = jax.random.split(k_attn, 4)
    km = jax.random.split(k_moe, 6)
    return {
        "embed": normal(k_embed, (cfg.vocab_size, H), 1.0),
        "ssm_layers": {
            "mixer_norm": jnp.ones((Ls, H), cfg.dtype),
            "in_proj": normal(ks[0], (Ls, H, Di + C), H**-0.5),      # [z | xBC]
            "dt_proj": normal(ks[1], (Ls, H, NH), H**-0.5),          # its dt columns
            "conv_w": normal(ks[2], (Ls, K, C), K**-0.5),
            "conv_b": normal(ks[3], (Ls, C), 0.5),
            "dt_bias": (step + jnp.log(-jnp.expm1(-step))).astype(f32),
            "a_log_head": jnp.log(
                jax.random.uniform(ks[4], (Ls, NH), f32, 1.0, 16.0)),
            "d_skip": 1.0 + normal(ks[6], (Ls, NH), 0.25, f32),
            "gate_norm": 1.0 + normal(ks[7], (Ls, Di), 0.25),
            "out_proj": normal(ks[8], (Ls, Di, H), back * Di**-0.5),
        },
        "attn_layers": {
            "mixer_norm": jnp.ones((La, H), cfg.dtype),
            "wq": normal(ka[0], (La, H, QH * D), H**-0.5),
            "wk": normal(ka[1], (La, H, KH * D), H**-0.5),
            "wv": normal(ka[2], (La, H, KH * D), H**-0.5),
            "wo": normal(ka[3], (La, QH * D, H), back * (QH * D) ** -0.5),
        },
        "moe_layers": {
            "mlp_norm": jnp.ones((Le, H), cfg.dtype),
            "router": normal(km[0], (Le, H, E), H**-0.5),
            "expert_bias": normal(km[1], (Le, E), 0.02, f32),
            # zero columns up to whole lane tiles (``expert_cols`` says why)
            "w1": jnp.pad(
                experts(km[2], (H, I), H**-0.5),
                [(0, 0)] * 3 + [(0, cfg.expert_cols - I)],
            ),
            # relu(x)^2 of a unit normal has second moment 1.5; ``branch``:
            # the block's two branches share its 1 / sqrt(num_layers)
            "w2": experts(km[3], (I, H), branch * (1.5 * I) ** -0.5),
            "w_up": normal(km[4], (Le, H, Ish), H**-0.5),
            "w_down": normal(km[5], (Le, Ish, H), branch * (1.5 * Ish) ** -0.5),
        },
        "final_norm": jnp.ones((H,), cfg.dtype),
        "lm_head": normal(k_head, (H, cfg.vocab_size), H**-0.5),
    }


def init_kv_pages(
    cfg: NemotronHConfig, num_pages: int, page_size: int, dtype=None
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Page pools of the ATTENTION blocks: [num_kv_layers, P, page, 1, KH *
    D], a token's kv heads side by side in one row of lanes (the layout
    ``burst_attention`` reads; a [.., 2, 128] pool would pad its two heads to
    a 16-row tile)."""
    dtype = dtype or cfg.dtype
    shape = (cfg.num_kv_layers, num_pages, page_size, 1,
             cfg.num_kv_heads * cfg.head_dim)
    return jnp.zeros(shape, dtype), jnp.zeros(shape, dtype)


def init_state(cfg: NemotronHConfig, slots: int) -> dict:
    """State pools of the Mamba-2 layers, ``slots`` sequences + the null slot
    (index ``slots``) that padded rows read and write: ``conv`` [Ls, slots +
    1, K - 1, Di + 2 G N] in the model's dtype and ``ssm`` in the SSD kernels'
    own layout (``ssd_scan.state_pool_shape``), float32."""
    Ls = cfg.num_ssm_layers
    return {
        "conv": jnp.zeros(
            (Ls, slots + 1, cfg.conv_kernel - 1, cfg.conv_dim), cfg.dtype),
        "ssm": jnp.zeros(
            ssd_scan.state_pool_shape(
                Ls, slots, cfg.mamba_num_heads, cfg.mamba_head_dim,
                cfg.ssm_state_size),
            cfg.ssm_state_dtype,
        ),
    }


def counter_stats(cfg: NemotronHConfig, totals) -> dict:
    """``/stats`` keys from the step counters summed over dispatches."""
    n = moe.num_counters(cfg.num_experts)
    out = moe.counter_stats(totals[:n], cfg.num_experts)
    out.update({name: int(totals[n + i]) for i, name in enumerate(SSD_COUNTERS)})
    return out


def _dot_f32(a, w):
    """bf16 into the MXU, float32 out: the projections back into the float32
    residual stream (no rounding before the addition) and the head."""
    return jnp.dot(a.astype(w.dtype), w, preferred_element_type=jnp.float32)


def _at(stack: dict, i, skip=()):
    """Layer ``i`` of a stacked group, indexed where it is used."""
    return {
        n: lax.dynamic_index_in_dim(a, i, 0, keepdims=False)
        for n, a in stack.items() if n not in skip
    }


def _rows(positions, state_slots):
    """What the Mamba-2 layers need to know of each row: its slot, whether
    this chunk starts the sequence (then conv tail and state start from zero,
    whatever the slot's last owner left), its valid positions (left-aligned)."""
    valid = positions >= 0
    return {
        "slots": state_slots.astype(jnp.int32),
        "first": positions[:, 0] == 0,
        "lens": jnp.sum(valid, axis=1).astype(jnp.int32),
        "valid": valid,
    }


def _relu2(x):
    return jnp.square(jax.nn.relu(x.astype(jnp.float32)))


def _gated_norm(y, z, w, groups: int, eps: float):
    """``rmsnorm(y * silu(z))`` with the mean square taken over each of
    ``groups`` groups of channels, float32."""
    y = y * jax.nn.silu(z.astype(jnp.float32))
    shape = y.shape
    y = y.reshape(shape[:-1] + (groups, shape[-1] // groups))
    y = y * lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True) + eps)
    return y.reshape(shape) * w.astype(jnp.float32)


def _by_kind(a, kinds):
    """``a`` [.., tokens, C] cut into each kind's own [B, T, C] rows (``kinds``:
    the ``_rows`` of the chunk, then of the riders, in the order they lie
    along the token axis); with one kind it is ``a`` itself."""
    if len(kinds) == 1:
        return [a]
    out, at = [], 0
    for row in kinds:
        B, T = row["valid"].shape
        out.append(a[0, at:at + B * T].reshape(B, T, a.shape[-1]))
        at += B * T
    return out


def _conv_and_scan(xbc, dt, lp, cfg: NemotronHConfig, state, li, row):
    """The part of a Mamba-2 mixer that knows rows: the causal convolution
    over [the sequence's tail, its new inputs] and the recurrence, for rows of
    ONE kind (a chunk: ``ssd_scan_prefill``; one token a row: ``ssd_step_decode``).
    ``xbc`` [B, T, C] before the convolution, ``dt`` [B, T, NH] after its
    softplus. Returns (y [B, T, Di] float32, state)."""
    B, T, _ = xbc.shape
    Di, K = cfg.d_inner, cfg.conv_kernel
    NH, P, G, N = (cfg.mamba_num_heads, cfg.mamba_head_dim, cfg.n_groups,
                   cfg.ssm_state_size)
    f32 = jnp.float32
    # causal depthwise convolution over [the sequence's last K-1 inputs, chunk]
    tail = jnp.where(row["first"][:, None, None], 0, state["conv"][li, row["slots"]])
    seq = jnp.concatenate([tail.astype(xbc.dtype), xbc], axis=1)   # [B, K-1+T, C]
    conv = lp["conv_b"].astype(f32) + sum(
        lp["conv_w"][j].astype(f32) * seq[:, j:j + T].astype(f32) for j in range(K)
    )
    xbc = jax.nn.silu(conv).astype(cfg.dtype)
    # the K-1 inputs that end at the row's last valid position (a padded row
    # keeps the tail it read)
    keep = row["lens"][:, None] + jnp.arange(K - 1, dtype=jnp.int32)[None, :]
    conv_pool = state["conv"].at[li, row["slots"]].set(
        jnp.take_along_axis(seq, keep[:, :, None], axis=1).astype(state["conv"].dtype)
    )
    xs, b_mat, c_mat = jnp.split(xbc, [Di, Di + G * N], axis=-1)
    dt = jnp.where(row["valid"][..., None], dt, 0.0)  # a zero step leaves the state
    y, ssm_pool = ssd_scan.ssd_scan(
        xs.reshape(B, T, NH, P), dt, -jnp.exp(lp["a_log_head"].astype(f32)),
        b_mat.reshape(B, T, G, N), c_mat.reshape(B, T, G, N), lp["d_skip"],
        state["ssm"], row["slots"], row["first"], row["lens"], li,
        impl=cfg.ssm_impl,
    )
    return y.reshape(B, T, Di), {"conv": conv_pool, "ssm": ssm_pool}


def _ssd_mixer(x, lp, cfg: NemotronHConfig, state, li, kinds):
    """One Mamba-2 mixer over ``x`` [B, T, H], or [1, tokens, H] where decode
    rows ride the chunk: the projections and the gated norm see one token
    axis, the convolution and the recurrence each kind of row by itself, the
    chunk's first. Returns (mixer output float32, state)."""
    Di, G = cfg.d_inner, cfg.n_groups
    h = rms_norm(x, lp["mixer_norm"], cfg.norm_eps).astype(cfg.dtype)
    z, xbc = jnp.split(h @ lp["in_proj"], [Di], axis=-1)
    # the step is an exponent summed along the whole sequence: float32 from
    # the projection on
    dt = jax.nn.softplus(
        jnp.dot(h, lp["dt_proj"], preferred_element_type=jnp.float32) + lp["dt_bias"]
    )
    ys = []
    for row, xbc_k, dt_k in zip(kinds, _by_kind(xbc, kinds), _by_kind(dt, kinds)):
        y, state = _conv_and_scan(xbc_k, dt_k, lp, cfg, state, li, row)
        ys.append(y)
    y = ys[0] if len(ys) == 1 else jnp.concatenate(
        [y.reshape(1, -1, Di) for y in ys], axis=1)
    y = _gated_norm(y, z, lp["gate_norm"], G, cfg.norm_eps)
    return _dot_f32(y, lp["out_proj"]), state


def _moe_layer(x, mp, experts_flat, cfg: NemotronHConfig, li, valid, impl):
    """Expert layer ``li``: (routed + shared output [B, T, H] float32,
    ops/moe.py's counters)."""
    B, T, H = x.shape
    lp = _at(mp, li, skip=("w1", "w2"))
    # the router reads the normed stream BEFORE it is rounded to the experts'
    # dtype (ops/moe.route says why)
    h32 = rms_norm(x, lp["mlp_norm"], cfg.norm_eps).reshape(B * T, H)
    h = h32.astype(cfg.dtype)
    experts, weights = moe.route(
        h32, lp["router"], lp["expert_bias"], cfg.num_experts_per_tok,
        norm_topk=cfg.norm_topk_prob, scaling=cfg.routed_scaling_factor,
    )
    out, counters = moe.expert_ffn(
        h, experts, weights, *experts_flat, li,
        num_experts=cfg.num_experts, experts_held=cfg.experts_held,
        valid=valid.reshape(B * T), impl=impl, form="relu2",
    )
    with jax.named_scope("moe_shared"):
        out = out + _dot_f32(_relu2(h @ lp["w_up"]).astype(cfg.dtype), lp["w_down"])
    return out.reshape(B, T, H), counters


def _units(pattern: str):
    """The pattern as units ``M? *? E?``: (index into the Mamba-2 stack or
    None, into the attention stack or None, into the expert stack or None)."""
    units, at, seen = [], 0, {"M": 0, "*": 0, "E": 0}
    while at < len(pattern):
        unit = []
        for kind in "M*E":
            if at < len(pattern) and pattern[at] == kind:
                unit.append(seen[kind])
                seen[kind] += 1
                at += 1
            else:
                unit.append(None)
        units.append(tuple(unit))
    return units


def _runs(units):
    """Consecutive units that hold the same of M and E: one scan each."""
    runs = []
    for unit in units:
        shape = (unit[0] is not None, unit[2] is not None)
        if runs and runs[-1][0] == shape:
            runs[-1][1].append(unit)
        else:
            runs.append((shape, [unit]))
    return runs


def forward(
    params: dict,
    cfg: NemotronHConfig,
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
    riders: Optional[tuple] = None,
):
    """One forward step (prefill chunk or decode) with paged KV and slotted
    recurrent state.

    Same contract as models/lfm2.py ``forward``: ``state`` is ``init_state``'s
    pools, ``state_slots`` [B] int32 (the null slot for padded rows); returns
    ``(logits, k_pages, v_pages, state, counters)``, or ``(logits, k_acc,
    v_acc, state, counters)`` with ``kv_burst``.

    ``riders``: (ids [R, 1], positions [R, 1], page_table [R, Pr], kv_lens
    [R], state_slots [R]): decode rows that take ONE step inside this prefill
    dispatch, as models/llama.py's do. Their tokens join the chunk's on one
    token axis [1, B * T + R] for the embedding, every norm, every projection,
    the router, the experts, the shared expert and the head, so the weights
    are read once. Per kind, each through the code it runs without riders:
    the convolution and the recurrence (the chunk's rows ``ssd_scan`` at T >
    1, a rider's at T == 1 against its OWN slot) and attention (the chunk's
    rows ``flash_attention``, a rider's one query ``burst_attention`` over its
    own pages and a window of its one new token). An inert row has position
    -1, kv_len 0 and the null slot: it writes no page and leaves the state it
    reads. Logits come back [B + R, V], the riders' rows last."""
    if cfg.attn_impl not in ("auto", "xla"):
        raise ValueError(
            f"attn_impl={cfg.attn_impl!r}: this family's attention blocks run "
            "the XLA path only (ROADMAP M5)"
        )
    if cfg.kv_write_mode != "post":
        raise ValueError("this family writes pages after attending (kv_write_mode='post')")
    if state is None or state_slots is None:
        raise ValueError("this family's forward needs state= and state_slots=")
    if cfg.ssm_impl == "auto":
        raise ValueError("ssm_impl='auto' is resolved by the ModelRunner; pass xla or pallas")
    impl = cfg.moe_impl
    if impl == "auto":
        impl = moe.resolve_moe_impl(jax.default_backend())
    B, T = input_ids.shape
    QH, KH, D = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    La, P = k_pages.shape[:2]
    burst = kv_burst is not None
    row = _rows(positions, state_slots)
    kinds, valid = [row], row["valid"]
    if riders is not None:
        if burst or all_logits or T == 1:
            raise ValueError("riders ride a prefill chunk (T > 1, no kv_burst, no all_logits)")
        r_ids, r_pos, r_table, r_lens, r_slots = riders
        R = r_ids.shape[0]
        kinds.append(_rows(r_pos, r_slots))
        # ONE token axis: [1, B * T + R] through everything but the
        # convolution, the recurrence and attention
        input_ids = jnp.concatenate(
            [input_ids.reshape(1, B * T), r_ids.reshape(1, R)], axis=1)
        valid = jnp.concatenate(
            [valid.reshape(1, B * T), kinds[1]["valid"].reshape(1, R)], axis=1)
    with jax.named_scope("embed"):
        x = params["embed"][input_ids].astype(jnp.float32)
    if burst:
        if T != 1:
            raise ValueError("kv_burst is the decode shape (T == 1)")
        k_acc, v_acc, counts = kv_burst
        kv_pos = burst_kv_positions(
            kv_lens, counts + 1, page_table.shape[1] * k_pages.shape[2], k_acc.shape[2]
        )
        rows = jnp.arange(B, dtype=jnp.int32)
        new = {"k": k_acc, "v": v_acc}
    else:
        kv_pos = stale_kv_positions(page_table, positions, k_pages.shape[2])
        # this step's keys and values by attention block, rows as the pool
        # stores them: what the commit below writes to the pages
        new = {"k": jnp.zeros((La, B, T, 1, KH * D), k_pages.dtype),
               "v": jnp.zeros((La, B, T, 1, KH * D), v_pages.dtype)}
    if riders is not None:
        # a rider's pages are stale for its token as the chunk's are: its K/V
        # ride a window of one entry and are committed beside the chunk's
        r_kv_pos = burst_kv_positions(
            r_lens, jnp.ones_like(r_lens), r_table.shape[1] * k_pages.shape[2], 1)
        new.update(rk=jnp.zeros((La, R, 1, 1, KH * D), k_pages.dtype),
                   rv=jnp.zeros((La, R, 1, 1, KH * D), v_pages.dtype))
    pools_flat = (
        k_pages.reshape((La * P,) + k_pages.shape[2:]),
        v_pages.reshape((La * P,) + v_pages.shape[2:]),
    )
    # the experts' whole stacks as [layers * experts held, ...] (a bitcast)
    # with the layer a scalar: ops/moe.py says why
    mp = params["moe_layers"]
    experts_flat = tuple(mp[n].reshape((-1,) + mp[n].shape[2:]) for n in ("w1", "w2"))

    def put(stack, a, j):
        return lax.dynamic_update_index_in_dim(stack, a, j, 0)

    def attn_block(x, new, j):
        lp = _at(params["attn_layers"], j)
        with jax.named_scope("attn_mixer"):
            h = rms_norm(x, lp["mixer_norm"], cfg.norm_eps).astype(cfg.dtype)
            q = h @ lp["wq"]
            k = (h @ lp["wk"]).astype(k_pages.dtype)
            v = (h @ lp["wv"]).astype(v_pages.dtype)
            if riders is not None:
                # attention is per kind: the chunk's rows go on as they would
                # without riders, the riders' follow below
                (q, qr), (k, kr), (v, vr) = (_by_kind(a, kinds) for a in (q, k, v))
            q = q.reshape(B, T, QH, D)
            k, v = k.reshape(B, T, 1, KH * D), v.reshape(B, T, 1, KH * D)
            # pages of block ``j`` out of the pools seen as [La * P, ...] (a
            # bitcast): ``k_pages[j]`` would be a copy of both whole pools
            kc, vc = gather_kv_pages(*pools_flat, page_table + j * P)
            if burst:
                # the burst's window, not the pool, carries this burst's K/V
                k = lax.dynamic_index_in_dim(new["k"], j, 0, keepdims=False).at[
                    rows, counts].set(k[:, 0])
                v = lax.dynamic_index_in_dim(new["v"], j, 0, keepdims=False).at[
                    rows, counts].set(v[:, 0])
                attn = burst_attention(
                    q, kc[:, :, 0], vc[:, :, 0], k[:, :, 0], v[:, :, 0], kv_pos,
                    positions, KH,
                )
            else:
                heads = lambda a: a.reshape(B, -1, KH, D)  # noqa: E731
                attn = flash_attention(
                    q, heads(jnp.concatenate([kc, k], axis=1)),
                    heads(jnp.concatenate([vc, v], axis=1)),
                    q_positions=positions, kv_lens=kv_lens, kv_positions=kv_pos,
                )
            new = dict(new, k=put(new["k"], k, j), v=put(new["v"], v, j))
            if riders is not None:
                # a decode step's attention for one token a row: the rider's
                # own pages and a window that holds its new K/V alone
                kc, vc = gather_kv_pages(*pools_flat, r_table + j * P)
                attn_r = burst_attention(
                    qr.reshape(R, 1, QH, D), kc[:, :, 0], vc[:, :, 0], kr, vr,
                    r_kv_pos, r_pos, KH,
                )
                attn = jnp.concatenate(
                    [attn.reshape(1, B * T, QH * D), attn_r.reshape(1, R, QH * D)],
                    axis=1)
                new.update(rk=put(new["rk"], kr[:, :, None], j),
                           rv=put(new["rv"], vr[:, :, None], j))
            x = x + _dot_f32(attn.reshape(x.shape[:2] + (QH * D,)), lp["wo"])
        return x, new

    def run(carry, shape, units):
        """One run of units of one shape (``has_m``, ``has_e``) as a scan."""
        has_m, has_e = shape
        attends = [u[1] is not None for u in units]
        index = lambda k: jnp.asarray([u[k] or 0 for u in units], jnp.int32)  # noqa: E731

        def body(carry, xs):
            x, st, new, counters = carry
            m, a, e, attend = xs
            if has_m:
                with jax.named_scope("ssd_mixer"):
                    out, st = _ssd_mixer(x, _at(params["ssm_layers"], m), cfg, st, m, kinds)
                    x = x + out
            if all(attends):
                x, new = attn_block(x, new, a)
            elif any(attends):
                x, new = lax.cond(
                    attend, attn_block, lambda x, new, j: (x, new), x, new, a,
                )
            if has_e:
                out, routed = _moe_layer(x, mp, experts_flat, cfg, e, valid, impl)
                x = x + out
                counters = counters.at[:routed.shape[0]].add(routed)
            return (x, st, new, counters), None

        return lax.scan(
            body, carry, (index(0), index(1), index(2), jnp.asarray(attends))
        )[0]

    carry = (x, state, new, jnp.zeros((cfg.step_counters,), jnp.int32))
    for shape, units in _runs(_units(cfg.pattern)):
        carry = run(carry, shape, units)
    x, state, new, counters = carry
    # what the SSD layers did, counted where the positions are: tokens stepped
    # (decode rows, and the LIVE riders of a prefill dispatch: both run
    # ``ssd_step_decode``); tokens walked in chunks (prefill), those chunks,
    # and the rows they belonged to (a row's state crosses HBM in and out
    # once a layer)
    tokens = jnp.sum(row["lens"])
    chunks = jnp.sum(-(-row["lens"] // cfg.chunk_size))
    stepped = 0 if riders is None else jnp.sum(kinds[1]["lens"])
    ssd = (tokens, 0, 0, 0) if T == 1 else (
        stepped, tokens, chunks, jnp.sum(row["lens"] > 0))
    counters = counters.at[-len(SSD_COUNTERS):].set(
        jnp.stack([jnp.asarray(c, jnp.int32) for c in ssd]))
    k_new, v_new = new["k"], new["v"]
    if not burst:
        with jax.named_scope("kv_commit"):
            k_new, v_new = write_kv_pages_all_layers(
                k_pages, v_pages, k_new, v_new, page_table, positions
            )
            if riders is not None:
                # the riders' one token each, all six blocks' in one scatter
                # (a row's own page, never one of the chunk's; position -1
                # is dropped)
                k_new, v_new = write_kv_pages_all_layers(
                    k_new, v_new, new["rk"], new["rv"], r_table, r_pos
                )
    with jax.named_scope("lm_head"):
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        if not all_logits:
            # the last valid token alone meets the vocabulary ([B, V], not [B, T, V])
            last = jnp.maximum(row["lens"] - 1, 0)
            if riders is not None:
                x, x_riders = x[0, :B * T].reshape(B, T, -1), x[0, B * T:]
            x = jnp.take_along_axis(x, last[:, None, None], axis=1)[:, 0]
            if riders is not None:
                x = jnp.concatenate([x, x_riders], axis=0)   # [B + R, H]
        logits = _dot_f32(x, params["lm_head"])
    return logits, k_new, v_new, state, counters
