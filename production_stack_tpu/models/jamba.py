"""Jamba (AI21 Jamba / Jamba2: Mamba-1 state-space layers with an attention
layer every ``attn_layer_period``) as pure functional JAX.

Layer ``i`` is an attention layer iff ``i % attn_layer_period ==
attn_layer_offset``; every other layer is a state-space layer. Every layer is
``x = x + mixer(rmsnorm(x)); x = x + W_down(silu(W_gate h) * W_up h), h =
rmsnorm(x)`` (``num_experts == 1``: the dense SwiGLU), then a final rmsnorm and
the TIED head.

- Attention mixer: ``q, k, v = h Wq, h Wk, h Wv`` (no bias), NO positional
  embedding of any kind, causal softmax attention, ``Wo``.
- State-space mixer (``Di = mamba_expand * hidden``, ``N = mamba_d_state``,
  ``R = mamba_dt_rank``, ``K = mamba_d_conv``): ``[u, z] = h W_in``; a causal
  depthwise convolution of width K with bias, then silu; ``[dt, B, C] = u
  W_x``, EACH through an rmsnorm of its own (Jamba's addition to Mamba-1);
  ``delta = softplus(dt W_dt + b_dt)``; ``A = -exp(A_log)``; the selective scan
  (ops/pallas/ssm_scan.py); ``out = (y * silu(z)) W_out``.

Two kinds of state live between steps. The attention layers keep pages of keys
and values (``init_kv_pages``: ``num_kv_layers`` layers, NOT ``num_layers``);
the state-space layers keep, for every running sequence, a float32 ``[N, Di]``
state and the last ``K - 1`` pre-activation convolution inputs
(``init_state``: one slot a sequence + a null slot that padded rows write).
``forward`` takes the state pools and a ``[B]`` slot index and returns the
updated pools beside the page pools; a row whose chunk starts at position 0
starts from zero state inside the program, so the host never clears a slot.

TPU-first structure as in models/llama.py: layer-stacked weights (the
state-space layers under ``layers``, the attention layers under
``attn_layers``), each run of consecutive state-space layers one ``lax.scan``
that indexes the stack by layer (no slice of the stack is ever copied), -1
positions for padding, write-after-attend pages committed by one scatter.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax import lax

from production_stack_tpu.ops.attention import (
    burst_kv_positions,
    flash_attention,
    gather_kv_pages,
    stale_kv_positions,
    write_kv_pages_all_layers,
)
from production_stack_tpu.ops.norms import rms_norm
from production_stack_tpu.ops.pallas.ssm_scan import selective_scan, state_pool_shape


@dataclass(frozen=True)
class JambaConfig:
    vocab_size: int = 65536
    hidden_size: int = 2560
    intermediate_size: int = 8192
    num_layers: int = 28
    num_heads: int = 20
    num_kv_heads: int = 1
    head_dim: int = 128
    attn_layer_period: int = 14
    attn_layer_offset: int = 7
    mamba_d_state: int = 16
    mamba_d_conv: int = 4
    mamba_expand: int = 2
    mamba_dt_rank: int = 160
    rms_norm_eps: float = 1e-6
    max_model_len: int = 4096
    dtype: Any = jnp.bfloat16
    # the state a sequence keeps between steps: float32 (a recurrence summed
    # over thousands of steps in bfloat16 drifts)
    ssm_state_dtype: Any = jnp.float32
    # attention: same contract as LlamaConfig.attn_impl; this family's two
    # attention layers run the XLA path only (1 kv head a shard, ROADMAP M5)
    attn_impl: str = "auto"
    kv_write_mode: str = "post"
    # selective scan: "auto" (ModelRunner resolves by platform), "pallas",
    # "pallas_interpret" (tests on the CPU), "xla" (plain jax.numpy)
    ssm_impl: str = "auto"

    @property
    def tie_word_embeddings(self) -> bool:
        return True

    @property
    def sliding_window(self):
        return None

    @property
    def layer_kinds(self) -> tuple[str, ...]:
        return tuple(
            "attn" if i % self.attn_layer_period == self.attn_layer_offset else "ssm"
            for i in range(self.num_layers)
        )

    @property
    def num_kv_layers(self) -> int:
        """Layers that hold pages (``num_layers`` counts the model's)."""
        return self.layer_kinds.count("attn")

    @property
    def num_ssm_layers(self) -> int:
        return self.layer_kinds.count("ssm")

    @property
    def d_inner(self) -> int:
        return self.mamba_expand * self.hidden_size

    @property
    def state_bytes_per_slot(self) -> int:
        """What one running sequence keeps beside its pages, all layers."""
        return self.num_ssm_layers * self.d_inner * (
            self.mamba_d_state * jnp.dtype(self.ssm_state_dtype).itemsize
            + (self.mamba_d_conv - 1) * jnp.dtype(self.dtype).itemsize
        )

    @staticmethod
    def from_hf_config(cfg: dict) -> "JambaConfig":
        """Build from a HuggingFace ``config.json`` (JambaForCausalLM)."""
        if cfg.get("num_experts", 1) != 1:
            raise NotImplementedError(
                f"Jamba with num_experts={cfg['num_experts']}: only the dense "
                "feed-forward (num_experts == 1) is implemented"
            )
        if cfg.get("mamba_proj_bias", False) or not cfg.get("mamba_conv_bias", True):
            raise NotImplementedError(
                "Jamba is implemented with mamba_proj_bias false and "
                "mamba_conv_bias true (the published Jamba2 settings)"
            )
        if not cfg.get("tie_word_embeddings", True):
            raise NotImplementedError("Jamba with an untied head")
        if cfg.get("sliding_window"):
            raise NotImplementedError("Jamba with a sliding window")
        hidden, heads = cfg["hidden_size"], cfg["num_attention_heads"]
        rank = cfg.get("mamba_dt_rank", "auto")
        return JambaConfig(
            vocab_size=cfg["vocab_size"],
            hidden_size=hidden,
            intermediate_size=cfg["intermediate_size"],
            num_layers=cfg["num_hidden_layers"],
            num_heads=heads,
            num_kv_heads=cfg.get("num_key_value_heads", heads),
            head_dim=cfg.get("head_dim") or hidden // heads,
            attn_layer_period=cfg["attn_layer_period"],
            attn_layer_offset=cfg["attn_layer_offset"],
            mamba_d_state=cfg.get("mamba_d_state", 16),
            mamba_d_conv=cfg.get("mamba_d_conv", 4),
            mamba_expand=cfg.get("mamba_expand", 2),
            mamba_dt_rank=-(-hidden // 16) if rank == "auto" else rank,
            rms_norm_eps=cfg.get("rms_norm_eps", 1e-6),
            max_model_len=cfg.get("max_position_embeddings", 4096),
        )


PRESETS: dict[str, JambaConfig] = {
    # AI21-Jamba2-3B as published: 28 layers, attention at layers 7 and 21
    "jamba2-3b": JambaConfig(max_model_len=262144),
    # the toy: two periods of 4 with the attention layer at offset 1
    # (S A S S | S A S S), widths that divide the kernel's tiles
    "jamba-debug": JambaConfig(
        vocab_size=512,
        hidden_size=128,
        intermediate_size=256,
        num_layers=8,
        num_heads=4,
        num_kv_heads=1,
        head_dim=32,
        attn_layer_period=4,
        attn_layer_offset=1,
        mamba_d_state=16,
        mamba_d_conv=4,
        mamba_expand=2,
        mamba_dt_rank=8,
        max_model_len=256,
    ),
}


def init_params(cfg: JambaConfig, key: jax.Array) -> dict:
    """Seeded parameter tree. The state-space layers follow the published
    Mamba initialisation (``A_log = log(1..N)``, ``b_dt`` the inverse softplus
    of a log-uniform step in [1e-3, 1e-1], ``D = 1``) and ``b_conv`` is drawn
    non-zero: with plain normal draws the decay ``exp(delta A)`` is 0 or 1 and
    a lost state, like a dropped bias left at zero, could not show."""
    k_embed, k_ssm, k_attn = jax.random.split(key, 3)
    H, I, Di = cfg.hidden_size, cfg.intermediate_size, cfg.d_inner
    N, K, R = cfg.mamba_d_state, cfg.mamba_d_conv, cfg.mamba_dt_rank
    NH, KH, D = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    Ls, La = cfg.num_ssm_layers, cfg.num_kv_layers

    def normal(key, shape, scale):
        return (jax.random.normal(key, shape, jnp.float32) * scale).astype(cfg.dtype)

    def mlp(keys, L):
        return {
            "mlp_norm": jnp.ones((L, H), cfg.dtype),
            "w_gate": normal(keys[0], (L, H, I), H**-0.5),
            "w_up": normal(keys[1], (L, H, I), H**-0.5),
            "w_down": normal(keys[2], (L, I, H), I**-0.5),
        }

    ks = jax.random.split(k_ssm, 10)
    step = jnp.exp(
        jax.random.uniform(ks[5], (Ls, Di), jnp.float32)
        * (jnp.log(1e-1) - jnp.log(1e-3)) + jnp.log(1e-3)
    )
    ssm = {
        "mixer_norm": jnp.ones((Ls, H), cfg.dtype),
        "in_proj": normal(ks[0], (Ls, H, 2 * Di), H**-0.5),
        "conv_w": normal(ks[1], (Ls, K, Di), K**-0.5),
        "conv_b": normal(ks[2], (Ls, Di), 0.5),
        "x_proj": normal(ks[3], (Ls, Di, R + 2 * N), Di**-0.5),
        "dt_norm": jnp.ones((Ls, R), cfg.dtype),
        "b_norm": jnp.ones((Ls, N), cfg.dtype),
        "c_norm": jnp.ones((Ls, N), cfg.dtype),
        "dt_proj": normal(ks[4], (Ls, R, Di), R**-0.5),
        "dt_bias": (step + jnp.log(-jnp.expm1(-step))).astype(cfg.dtype),
        "a_log": jnp.broadcast_to(
            jnp.log(jnp.arange(1, N + 1, dtype=jnp.float32)), (Ls, Di, N)
        ).astype(cfg.dtype),
        "d_skip": jnp.ones((Ls, Di), cfg.dtype),
        "out_proj": normal(ks[6], (Ls, Di, H), Di**-0.5),
        **mlp(ks[7:10], Ls),
    }
    ka = jax.random.split(k_attn, 7)
    attn = {
        "mixer_norm": jnp.ones((La, H), cfg.dtype),
        "wq": normal(ka[0], (La, H, NH * D), H**-0.5),
        "wk": normal(ka[1], (La, H, KH * D), H**-0.5),
        "wv": normal(ka[2], (La, H, KH * D), H**-0.5),
        "wo": normal(ka[3], (La, NH * D, H), (NH * D) ** -0.5),
        **mlp(ka[4:7], La),
    }
    return {
        "embed": normal(k_embed, (cfg.vocab_size, H), H**-0.5),
        "layers": ssm,
        "attn_layers": attn,
        "final_norm": jnp.ones((H,), cfg.dtype),
    }


def init_kv_pages(
    cfg: JambaConfig, num_pages: int, page_size: int, dtype=None
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Page pools of the ATTENTION layers: [num_kv_layers, P, page, KH, D]."""
    dtype = dtype or cfg.dtype
    shape = (cfg.num_kv_layers, num_pages, page_size, cfg.num_kv_heads, cfg.head_dim)
    return jnp.zeros(shape, dtype), jnp.zeros(shape, dtype)


def init_state(cfg: JambaConfig, slots: int) -> dict:
    """State pools of the state-space layers, ``slots`` sequences + the null
    slot (index ``slots``) that padded rows read and write:
    ``conv`` [Ls, slots + 1, K - 1, Di] and ``ssm`` [Ls, slots + 1, N, Di / 128,
    128] (the selective scan's own layout)."""
    Ls, Di = cfg.num_ssm_layers, cfg.d_inner
    return {
        "conv": jnp.zeros((Ls, slots + 1, cfg.mamba_d_conv - 1, Di), cfg.dtype),
        "ssm": jnp.zeros(
            state_pool_shape(Ls, slots, cfg.mamba_d_state, Di), cfg.ssm_state_dtype
        ),
    }


def _dot_f32(a, w):
    """bf16 into the MXU, float32 out: the projections back into the float32
    residual stream (no rounding before the addition) and the head."""
    return jnp.dot(a.astype(w.dtype), w, preferred_element_type=jnp.float32)


def _mlp_residual(x, lp, cfg: JambaConfig):
    with jax.named_scope("mlp"):
        h = rms_norm(x, lp["mlp_norm"], cfg.rms_norm_eps).astype(cfg.dtype)
        return x + _dot_f32(
            jax.nn.silu(h @ lp["w_gate"]) * (h @ lp["w_up"]), lp["w_down"]
        )


def _norm_dt_b_c(dt, b_mat, c_mat, lp, eps):
    """Jamba's addition to Mamba-1: the step, B and C each go through an
    rmsnorm of their own before they are used."""
    return (
        rms_norm(dt, lp["dt_norm"], eps),
        rms_norm(b_mat, lp["b_norm"], eps),
        rms_norm(c_mat, lp["c_norm"], eps),
    )


def _rows(positions, state_slots):
    """What the state-space layers need to know of each row: its slot, whether
    this chunk starts the sequence (then the state starts from zero, whatever
    the slot's last owner left), its valid positions (left-aligned)."""
    valid = positions >= 0
    return {
        "slots": state_slots.astype(jnp.int32),
        "first": positions[:, 0] == 0,
        "lens": jnp.sum(valid, axis=1).astype(jnp.int32),
        "valid": valid,
    }


def _ssm_mixer(x, lp, cfg: JambaConfig, state, li, row):
    """One state-space mixer over a chunk. ``row``: per-row ``slots``,
    ``first`` (the chunk starts the sequence), ``lens`` (valid positions,
    left-aligned) and ``valid`` [B, T]. Returns (mixer output, state)."""
    B, T, _ = x.shape
    Di, N, K, R = cfg.d_inner, cfg.mamba_d_state, cfg.mamba_d_conv, cfg.mamba_dt_rank
    f32 = jnp.float32
    h = rms_norm(x, lp["mixer_norm"], cfg.rms_norm_eps).astype(cfg.dtype)
    u_pre, z = jnp.split(h @ lp["in_proj"], 2, axis=-1)
    # causal depthwise convolution over [the sequence's last K-1 inputs, chunk]
    tail = jnp.where(row["first"][:, None, None], 0, state["conv"][li, row["slots"]])
    seq = jnp.concatenate([tail.astype(u_pre.dtype), u_pre], axis=1)  # [B, K-1+T, Di]
    conv = lp["conv_b"].astype(f32) + sum(
        lp["conv_w"][j].astype(f32) * seq[:, j:j + T].astype(f32) for j in range(K)
    )
    valid = row["valid"][..., None]
    u = jnp.where(valid, jax.nn.silu(conv), 0.0).astype(cfg.dtype)
    # the K-1 inputs that end at the row's last valid position (a padded row
    # keeps the tail it read)
    keep = row["lens"][:, None] + jnp.arange(K - 1, dtype=jnp.int32)[None, :]
    new_tail = jnp.take_along_axis(seq, keep[:, :, None], axis=1)
    conv_pool = state["conv"].at[li, row["slots"]].set(
        new_tail.astype(state["conv"].dtype)
    )
    # the step, B and C stay float32 from here on (192 and Di columns: cheap):
    # the step is an EXPONENT summed along the whole sequence, and a bfloat16
    # step (3 digits) was the largest part of the distance to the reference
    dt, b_mat, c_mat = jnp.split(
        jnp.dot(u, lp["x_proj"], preferred_element_type=f32), [R, R + N], axis=-1
    )
    dt, b_mat, c_mat = _norm_dt_b_c(dt, b_mat, c_mat, lp, cfg.rms_norm_eps)
    delta = jax.nn.softplus(
        jnp.dot(dt, lp["dt_proj"].astype(f32), precision=lax.Precision.HIGHEST)
        + lp["dt_bias"].astype(f32)
    )
    delta = jnp.where(valid, delta, 0.0)  # a zero step leaves the state as it is
    y, ssm_pool = selective_scan(
        u, delta, z, b_mat, c_mat,
        -jnp.exp(lp["a_log"].astype(f32)).T, lp["d_skip"].astype(f32),
        state["ssm"], row["slots"], row["first"], row["lens"], li,
        impl=cfg.ssm_impl,
    )
    return _dot_f32(y, lp["out_proj"]), {"conv": conv_pool, "ssm": ssm_pool}


def _segments(kinds: tuple[str, ...]):
    """Runs of the layer order: ("ssm", lo, hi) over the state-space stack or
    ("attn", j, j + 1) over the attention stack."""
    out, n_ssm, n_attn, i = [], 0, 0, 0
    while i < len(kinds):
        if kinds[i] == "attn":
            out.append(("attn", n_attn, n_attn + 1))
            n_attn, i = n_attn + 1, i + 1
            continue
        j = i
        while j < len(kinds) and kinds[j] == "ssm":
            j += 1
        out.append(("ssm", n_ssm, n_ssm + j - i))
        n_ssm, i = n_ssm + j - i, j
    return out


def forward(
    params: dict,
    cfg: JambaConfig,
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
    recurrent state.

    Same contract as models/llama.py ``forward`` (``kv_burst`` included: the
    deferred-scatter decode burst), plus ``state`` (``init_state``'s pools) and
    ``state_slots`` [B] int32 (the null slot for padded rows). Returns
    ``(logits, k_pages, v_pages, state)``, or ``(logits, k_acc, v_acc, state)``
    with ``kv_burst``."""
    if cfg.attn_impl not in ("auto", "xla"):
        raise ValueError(
            f"attn_impl={cfg.attn_impl!r}: this family's attention layers run "
            "the XLA path only (1 kv head a shard; ROADMAP M5)"
        )
    if cfg.kv_write_mode != "post":
        raise ValueError("this family writes pages after attending (kv_write_mode='post')")
    if state is None or state_slots is None:
        raise ValueError("this family's forward needs state= and state_slots=")
    if cfg.ssm_impl == "auto":
        raise ValueError("ssm_impl='auto' is resolved by the ModelRunner; pass xla or pallas")
    B, T = input_ids.shape
    NH, KH, D = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    burst = kv_burst is not None
    with jax.named_scope("embed"):
        # the residual stream is float32 (the published Mamba code's
        # ``residual_in_fp32``): 56 additions over 28 layers each rounded to
        # bfloat16 were a tenth of the distance to the reference, and a
        # [B, T, H] float32 stream costs nothing beside the weights
        x = params["embed"][input_ids].astype(jnp.float32)
    row = _rows(positions, state_slots)
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
    k_new, v_new = [], []

    def ssm_layer(carry, li):
        x, st = carry
        lp = jax.tree.map(
            lambda a: lax.dynamic_index_in_dim(a, li, 0, keepdims=False),
            params["layers"],
        )
        with jax.named_scope("ssm_mixer"):
            out, st = _ssm_mixer(x, lp, cfg, st, li, row)
            x = x + out
        return (_mlp_residual(x, lp, cfg), st), None

    for kind, lo, hi in _segments(cfg.layer_kinds):
        if kind == "ssm":
            (x, state), _ = lax.scan(
                ssm_layer, (x, state), jnp.arange(lo, hi, dtype=jnp.int32)
            )
            continue
        lp = jax.tree.map(lambda a: a[lo], params["attn_layers"])
        with jax.named_scope("attn_mixer"):
            h = rms_norm(x, lp["mixer_norm"], cfg.rms_norm_eps).astype(cfg.dtype)
            q = (h @ lp["wq"]).reshape(B, T, NH, D)
            k = (h @ lp["wk"]).reshape(B, T, KH, D).astype(k_pages.dtype)
            v = (h @ lp["wv"]).reshape(B, T, KH, D).astype(v_pages.dtype)
            # pages of layer ``lo`` out of the pools seen as [L * P, page, KH,
            # D] (a bitcast): ``k_pages[lo]`` would be materialised, a copy of
            # BOTH whole pools at every dispatch (seen in the first trace)
            L, P = k_pages.shape[:2]
            kc, vc = gather_kv_pages(
                k_pages.reshape((L * P,) + k_pages.shape[2:]),
                v_pages.reshape((L * P,) + v_pages.shape[2:]),
                page_table + lo * P,
            )
            if burst:
                # the burst's window, not the pool, carries this burst's K/V
                k = k_acc[lo].at[rows, counts].set(k[:, 0])
                v = v_acc[lo].at[rows, counts].set(v[:, 0])
            attn = flash_attention(
                q, jnp.concatenate([kc, k], axis=1), jnp.concatenate([vc, v], axis=1),
                q_positions=positions, kv_lens=kv_lens, kv_positions=kv_pos,
            )
            x = x + _dot_f32(attn.reshape(B, T, NH * D), lp["wo"])
        k_new.append(k)
        v_new.append(v)
        x = _mlp_residual(x, lp, cfg)

    k_new, v_new = jnp.stack(k_new), jnp.stack(v_new)
    if not burst:
        with jax.named_scope("kv_commit"):
            k_new, v_new = write_kv_pages_all_layers(
                k_pages, v_pages, k_new, v_new, page_table, positions
            )
    with jax.named_scope("lm_head"):
        x = rms_norm(x, params["final_norm"], cfg.rms_norm_eps)
        if not all_logits:
            # the last valid token alone meets the vocabulary ([B, V], not [B, T, V])
            last = jnp.maximum(row["lens"] - 1, 0)
            x = jnp.take_along_axis(x, last[:, None, None], axis=1)[:, 0]
        logits = _dot_f32(x, params["embed"].T)
    return logits, k_new, v_new, state
