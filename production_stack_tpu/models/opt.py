"""OPT family (facebook/opt-125m … opt-66b) as pure functional JAX.

Same TPU-first structure as models/llama.py (layer-stacked weights under one
``lax.scan``, paged KV, -1-position padding), with the OPT architectural
differences: learned positional embeddings (HF offset of 2), pre-LayerNorm
blocks with biases everywhere, ReLU MLP, no RoPE, no GQA.

Reference parity: the reference stack's CPU smoke test serves
``facebook/opt-125m`` (tutorials/assets/values-01-minimal-example.yaml and
.github/workflows/functionality-helm-chart.yml in /root/reference); this module
makes that same model a first-class citizen of the TPU engine.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any

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
from production_stack_tpu.ops.norms import layer_norm

# HF OPT reserves the first 2 position-embedding rows (legacy padding offset).
POS_OFFSET = 2


@dataclass(frozen=True)
class OPTConfig:
    vocab_size: int = 50272
    hidden_size: int = 768
    intermediate_size: int = 3072
    num_layers: int = 12
    num_heads: int = 12
    layer_norm_eps: float = 1e-5
    max_model_len: int = 2048
    dtype: Any = jnp.bfloat16
    attn_impl: str = "auto"  # same contract as LlamaConfig.attn_impl
    kv_write_mode: str = "post"  # same contract as LlamaConfig.kv_write_mode
    decode_pages_per_block: int = 0  # same contract as LlamaConfig
    decode_prefetch_pages: int = 0
    prefill_pages_per_block: int = 0  # same contract as LlamaConfig
    prefill_prefetch_pages: int = 0
    # accepted for config-threading uniformity; OPT's layer scan carries
    # pools as per-layer xs slices (no stacked-pool streaming), so its
    # prefill kernel path keeps the post-scan scatter regardless
    prefill_fused_kv_write: bool = True

    # uniform accessors used by the runner/engine (OPT has no GQA)
    @property
    def num_kv_heads(self) -> int:
        return self.num_heads

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_heads

    @property
    def num_kv_layers(self) -> int:
        return self.num_layers

    @property
    def tie_word_embeddings(self) -> bool:
        return True

    @property
    def sliding_window(self):
        return None

    @staticmethod
    def from_hf_config(cfg: dict) -> "OPTConfig":
        """Build from a HuggingFace `config.json` (OPTForCausalLM)."""
        if cfg.get("word_embed_proj_dim", cfg["hidden_size"]) != cfg["hidden_size"]:
            raise NotImplementedError("OPT word_embed_proj_dim != hidden_size")
        return OPTConfig(
            vocab_size=cfg["vocab_size"],
            hidden_size=cfg["hidden_size"],
            intermediate_size=cfg["ffn_dim"],
            num_layers=cfg["num_hidden_layers"],
            num_heads=cfg["num_attention_heads"],
            max_model_len=cfg.get("max_position_embeddings", 2048),
        )


PRESETS: dict[str, OPTConfig] = {
    "opt-125m": OPTConfig(),
    "opt-debug": OPTConfig(
        vocab_size=512,
        hidden_size=128,
        intermediate_size=256,
        num_layers=2,
        num_heads=4,
        max_model_len=256,
    ),
}


def init_params(cfg: OPTConfig, key: jax.Array) -> dict:
    """Random-normal initialized parameter tree (layer-stacked)."""
    k_embed, k_pos, k_layers = jax.random.split(key, 3)
    L, H, I = cfg.num_layers, cfg.hidden_size, cfg.intermediate_size

    def normal(key, shape, scale):
        return (jax.random.normal(key, shape, jnp.float32) * scale).astype(cfg.dtype)

    ks = jax.random.split(k_layers, 6)
    scale = H**-0.5
    return {
        "embed": normal(k_embed, (cfg.vocab_size, H), scale),
        "pos_embed": normal(k_pos, (cfg.max_model_len + POS_OFFSET, H), scale),
        "layers": {
            "attn_norm_w": jnp.ones((L, H), cfg.dtype),
            "attn_norm_b": jnp.zeros((L, H), cfg.dtype),
            "wq": normal(ks[0], (L, H, H), scale),
            "bq": jnp.zeros((L, H), cfg.dtype),
            "wk": normal(ks[1], (L, H, H), scale),
            "bk": jnp.zeros((L, H), cfg.dtype),
            "wv": normal(ks[2], (L, H, H), scale),
            "bv": jnp.zeros((L, H), cfg.dtype),
            "wo": normal(ks[3], (L, H, H), scale),
            "bo": jnp.zeros((L, H), cfg.dtype),
            "mlp_norm_w": jnp.ones((L, H), cfg.dtype),
            "mlp_norm_b": jnp.zeros((L, H), cfg.dtype),
            "fc1": normal(ks[4], (L, H, I), scale),
            "fc1_b": jnp.zeros((L, I), cfg.dtype),
            "fc2": normal(ks[5], (L, I, H), I**-0.5),
            "fc2_b": jnp.zeros((L, H), cfg.dtype),
        },
        "final_norm_w": jnp.ones((H,), cfg.dtype),
        "final_norm_b": jnp.zeros((H,), cfg.dtype),
    }


def init_kv_pages(
    cfg: OPTConfig, num_pages: int, page_size: int, dtype=None
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Layer-stacked page pools: [L, num_pages, page_size, NH, D]."""
    dtype = dtype or cfg.dtype
    shape = (cfg.num_layers, num_pages, page_size, cfg.num_heads, cfg.head_dim)
    return jnp.zeros(shape, dtype), jnp.zeros(shape, dtype)


def forward(
    params: dict,
    cfg: OPTConfig,
    input_ids: jnp.ndarray,
    positions: jnp.ndarray,
    k_pages: jnp.ndarray,
    v_pages: jnp.ndarray,
    page_table: jnp.ndarray,
    kv_lens: jnp.ndarray,
    all_logits: bool = False,
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """One forward step (prefill chunk or decode) with paged KV.

    Same contract as models/llama.py `forward` (returns last-valid-token
    logits [B, V] and the updated page pools).
    """
    B, T = input_ids.shape
    NH, D = cfg.num_heads, cfg.head_dim
    pos_ids = jnp.maximum(positions, 0) + POS_OFFSET
    x = (params["embed"][input_ids] + params["pos_embed"][pos_ids]).astype(cfg.dtype)

    post_write = cfg.kv_write_mode == "post"
    if post_write:
        # write-after-attend (see models/llama.py): stale pool + in-register
        # chunk K/V, one batched all-layer scatter after the scan
        kv_pos = stale_kv_positions(page_table, positions, k_pages.shape[2])

    def layer(x, layer_in):
        lp, kp, vp = layer_in
        h = layer_norm(x, lp["attn_norm_w"], lp["attn_norm_b"], cfg.layer_norm_eps)
        q = (h @ lp["wq"] + lp["bq"]).reshape(B, T, NH, D)
        k = (h @ lp["wk"] + lp["bk"]).reshape(B, T, NH, D)
        v = (h @ lp["wv"] + lp["bv"]).reshape(B, T, NH, D)
        if not post_write:
            kp, vp = write_kv_pages(
                kp, vp, k.astype(kp.dtype), v.astype(vp.dtype), page_table, positions
            )
        if T == 1 and cfg.attn_impl.startswith("pallas"):
            from production_stack_tpu.ops.pallas.paged_attention import (
                ragged_paged_attention_decode,
            )

            attn = ragged_paged_attention_decode(
                q[:, 0], kp, vp, page_table, kv_lens,
                interpret=cfg.attn_impl == "pallas_interpret",
                k_cur=k[:, 0].astype(kp.dtype) if post_write else None,
                v_cur=v[:, 0].astype(vp.dtype) if post_write else None,
                pages_per_block=cfg.decode_pages_per_block or None,
                prefetch_pages=cfg.decode_prefetch_pages or None,
            )[:, None]
        elif (
            T >= 16 and post_write
            and cfg.attn_impl in ("pallas_prefill", "pallas_interpret")
        ):
            # chunked prefill via kernel v2 (see models/llama.py); OPT's
            # scan carries per-layer pool slices, so the post-scan scatter
            # stays and fused_write is not used here
            from production_stack_tpu.ops.pallas.prefill_attention import (
                ragged_paged_attention_prefill,
            )

            attn = ragged_paged_attention_prefill(
                q, kp, vp, page_table, positions, kv_lens,
                k.astype(kp.dtype), v.astype(vp.dtype),
                jnp.sum(positions >= 0, axis=1).astype(jnp.int32),
                interpret=cfg.attn_impl == "pallas_interpret",
                pages_per_block=cfg.prefill_pages_per_block or None,
                prefetch_pages=cfg.prefill_prefetch_pages or None,
            )
        elif post_write:
            kc, vc = gather_kv_pages(kp, vp, page_table)
            kc = jnp.concatenate([kc, k.astype(kc.dtype)], axis=1)
            vc = jnp.concatenate([vc, v.astype(vc.dtype)], axis=1)
            attn = flash_attention(
                q, kc, vc, q_positions=positions, kv_lens=kv_lens,
                kv_positions=kv_pos,
            )
        else:
            kc, vc = gather_kv_pages(kp, vp, page_table)
            attn = flash_attention(q, kc, vc, q_positions=positions, kv_lens=kv_lens)
        x = x + attn.reshape(B, T, -1) @ lp["wo"] + lp["bo"]
        h = layer_norm(x, lp["mlp_norm_w"], lp["mlp_norm_b"], cfg.layer_norm_eps)
        x = x + jax.nn.relu(h @ lp["fc1"] + lp["fc1_b"]) @ lp["fc2"] + lp["fc2_b"]
        out_kv = (
            (k.astype(kp.dtype), v.astype(vp.dtype)) if post_write else (kp, vp)
        )
        return x, out_kv

    if post_write:
        x, (k_new, v_new) = lax.scan(layer, x, (params["layers"], k_pages, v_pages))
        k_pages, v_pages = write_kv_pages_all_layers(
            k_pages, v_pages, k_new, v_new, page_table, positions
        )
    else:
        x, (k_pages, v_pages) = lax.scan(layer, x, (params["layers"], k_pages, v_pages))

    x = layer_norm(x, params["final_norm_w"], params["final_norm_b"], cfg.layer_norm_eps)
    if all_logits:  # speculative verify scores every position
        return (x @ params["embed"].T).astype(jnp.float32), k_pages, v_pages
    last_idx = jnp.maximum(jnp.sum(positions >= 0, axis=1) - 1, 0)
    x_last = jnp.take_along_axis(x, last_idx[:, None, None], axis=1)[:, 0]
    logits = (x_last @ params["embed"].T).astype(jnp.float32)
    return logits, k_pages, v_pages
