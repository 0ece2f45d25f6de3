"""Llama superfamily (Llama 2/3/3.x, Mistral, Qwen2/2.5, Mixtral-MoE) as
pure functional JAX.

One forward covers the whole family through static config switches (resolved at
trace time, so each variant still compiles to a single straight-line program):
``attention_bias`` (Qwen2), ``sliding_window`` (Mistral/Qwen2),
``num_experts>0`` (Mixtral sparse-MoE MLP with top-k routing; expert weights
carry a leading [E] axis sharded on the ``ep`` mesh axis — SURVEY.md §2.3
"mesh axis reserved" made real).

TPU-first choices:
- Layers are *stacked*: every per-layer weight is one array with a leading
  ``[num_layers, ...]`` axis and the decoder runs as a single ``lax.scan``.
  One layer gets traced/compiled instead of 32, and the KV page pools ride the
  scan as per-layer slices ``xs``/``ys`` (compile time and HBM layout both win).
- bfloat16 weights/activations, fp32 softmax/norm statistics.
- No data-dependent Python control flow: padding is handled by -1 positions
  (dropped KV writes, masked attention), so one compiled program serves any
  ragged batch within a (batch, pages) bucket.

Reference parity: the stack's engine contract serves `meta-llama/Llama-3.1-8B-
Instruct` (reference README.md:20-46) and `facebook/opt-125m` (CPU smoke,
tutorials/assets/values-01-minimal-example.yaml); see models/opt.py for the
latter.
"""

from __future__ import annotations

from dataclasses import dataclass, field
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
from production_stack_tpu.ops.norms import rms_norm
from production_stack_tpu.ops.rope import RopeScaling, apply_rope, rope_cos_sin


@dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 128256
    hidden_size: int = 4096
    intermediate_size: int = 14336
    num_layers: int = 32
    num_heads: int = 32
    num_kv_heads: int = 8
    head_dim: int = 128
    rope_theta: float = 500000.0
    rope_scaling: Optional[RopeScaling] = None
    rms_norm_eps: float = 1e-5
    max_model_len: int = 8192
    tie_word_embeddings: bool = False
    attention_bias: bool = False          # Qwen2: bias on q/k/v projections
    sliding_window: Optional[int] = None  # Mistral/Qwen2: windowed attention
    num_experts: int = 0                  # Mixtral: >0 switches MLP to sparse MoE
    num_experts_per_tok: int = 2
    dtype: Any = jnp.bfloat16
    # decode attention implementation: "auto" (ModelRunner resolves), "xla"
    # (gather + flash, partitions under GSPMD), "pallas" (page-streaming
    # kernel, single-shard meshes), "pallas_interpret" (tests on CPU).
    # "auto" outside a runner falls back to the XLA path.
    attn_impl: str = "auto"
    # KV write placement. "pre": write each layer's K/V into its pool slice
    # before attending (pool updates ride the layer scan — simple, but XLA
    # materializes pool-sized copies per layer). "post" (default): attend
    # over the stale pool + in-register current-chunk K/V, stack per-layer
    # K/V as scan outputs, and write ALL layers with one batched scatter
    # after the scan (donated pools update in place — no per-layer copies;
    # measured -26% per decode burst on v5e).
    kv_write_mode: str = "post"
    # decode-kernel memory pipeline tuning (0 = kernel auto; see
    # ops/pallas/paged_attention.py and engine/config.py): pages per packed
    # grid cell, and DMA-ring depth (page copies kept in flight)
    decode_pages_per_block: int = 0
    decode_prefetch_pages: int = 0
    # prefill-kernel memory pipeline tuning (0 = kernel auto; see
    # ops/pallas/prefill_attention.py): KV pages landed contiguously per
    # packed grid cell (one wide matmul each), and how many page DMAs stay
    # in flight ahead of the cell being consumed
    prefill_pages_per_block: int = 0
    prefill_prefetch_pages: int = 0
    # fused paged-KV write: the prefill kernel scatters the chunk's K/V
    # into its pool pages in-kernel (pools aliased input->output), so the
    # layer scan stops stacking per-layer K/V and the post-scan
    # write_kv_pages_all_layers pass disappears from the prefill path
    prefill_fused_kv_write: bool = True
    # KV cache dtype: "auto" (= cfg.dtype), "bf16"/"fp16" (explicit fp), or
    # "int8" — quantized pages with per-page per-kv-head scales in a
    # parallel scales pool (ops/quant.py): HALF the HBM bytes every decode
    # step streams and double the effective pool capacity. Dequantization
    # happens inside the kernels' VMEM copy rings (and at the XLA gather on
    # the fallback path); quantization inside the fused prefill write and
    # on the decode feedback commit. Requires kv_write_mode="post";
    # ModelRunner builds the scales pools and threads them as ``kv_scales``.
    kv_cache_dtype: str = "auto"

    @property
    def num_kv_layers(self) -> int:
        """Layers that hold pages: every layer attends."""
        return self.num_layers

    @staticmethod
    def from_hf_config(cfg: dict) -> "LlamaConfig":
        """Build from a HuggingFace `config.json` dict. Handles
        LlamaForCausalLM, MistralForCausalLM, Qwen2ForCausalLM, and
        MixtralForCausalLM (arch read from `architectures[0]`)."""
        arch = (cfg.get("architectures") or ["LlamaForCausalLM"])[0]
        scaling = None
        rs = cfg.get("rope_scaling") or None
        if rs and rs.get("rope_type", rs.get("type")) == "llama3":
            scaling = RopeScaling(
                factor=rs.get("factor", 8.0),
                low_freq_factor=rs.get("low_freq_factor", 1.0),
                high_freq_factor=rs.get("high_freq_factor", 4.0),
                original_max_position=rs.get("original_max_position_embeddings", 8192),
            )
        hidden = cfg["hidden_size"]
        heads = cfg["num_attention_heads"]
        # Qwen2 always biases q/k/v; Mistral/Qwen2 may window attention.
        window = cfg.get("sliding_window")
        if arch.startswith("Qwen2") and not cfg.get("use_sliding_window", False):
            window = None
        return LlamaConfig(
            vocab_size=cfg["vocab_size"],
            hidden_size=hidden,
            intermediate_size=cfg["intermediate_size"],
            num_layers=cfg["num_hidden_layers"],
            num_heads=heads,
            num_kv_heads=cfg.get("num_key_value_heads", heads),
            head_dim=cfg.get("head_dim") or hidden // heads,
            rope_theta=cfg.get("rope_theta", 10000.0),
            rope_scaling=scaling,
            rms_norm_eps=cfg.get("rms_norm_eps", 1e-5),
            max_model_len=cfg.get("max_position_embeddings", 8192),
            tie_word_embeddings=cfg.get("tie_word_embeddings", False),
            attention_bias=cfg.get("attention_bias", arch.startswith("Qwen2")),
            sliding_window=window,
            num_experts=cfg.get("num_local_experts", 0)
            if arch.startswith("Mixtral")
            else 0,
            num_experts_per_tok=cfg.get("num_experts_per_tok", 2),
        )


# Small presets used by tests, the benchmark, and the graft entry.
PRESETS: dict[str, LlamaConfig] = {
    "llama-3-8b": LlamaConfig(),
    "llama-3.2-1b": LlamaConfig(
        hidden_size=2048,
        intermediate_size=8192,
        num_layers=16,
        num_heads=32,
        num_kv_heads=8,
        head_dim=64,
        rope_scaling=RopeScaling(factor=32.0),
        tie_word_embeddings=True,
    ),
    "mistral-7b": LlamaConfig(
        vocab_size=32000,
        rope_theta=10000.0,
        sliding_window=4096,
        max_model_len=32768,
    ),
    "qwen2.5-7b": LlamaConfig(
        vocab_size=152064,
        hidden_size=3584,
        intermediate_size=18944,
        num_layers=28,
        num_heads=28,
        num_kv_heads=4,
        rope_theta=1000000.0,
        rms_norm_eps=1e-6,
        attention_bias=True,
        max_model_len=32768,
    ),
    "mixtral-8x7b": LlamaConfig(
        vocab_size=32000,
        rope_theta=1000000.0,
        num_experts=8,
        num_experts_per_tok=2,
        max_model_len=32768,
    ),
    "llama-debug": LlamaConfig(
        vocab_size=512,
        hidden_size=128,
        intermediate_size=256,
        num_layers=2,
        num_heads=4,
        num_kv_heads=2,
        head_dim=32,
        rope_theta=10000.0,
        max_model_len=256,
    ),
}


def _debug_variant(**kw) -> LlamaConfig:
    import dataclasses as _dc

    return _dc.replace(PRESETS["llama-debug"], **kw)


PRESETS["qwen2-debug"] = _debug_variant(attention_bias=True)
# tp=4-shardable debug preset: 8 q / 4 kv heads divide over tp in {1, 2, 4}
# so the paged pool's kv-head axis genuinely shards per chip (llama-debug's
# 2 kv heads cap at tp=2) — the CPU-mesh stand-in for the flagship
# llama-3.2-1b (32 q / 8 kv heads) tensor-parallel serving path
PRESETS["llama-debug-4kv"] = _debug_variant(num_heads=8, num_kv_heads=4)
# f32 twin for tp token-identity tests: tp changes all-reduce partial-sum
# order, and on RANDOM weights (near-flat logits) bf16 reduction noise flips
# greedy near-ties — f32 keeps tp=1/2/4 logits equal to ~1e-6, so greedy
# output is genuinely token-identical across tp shapes
PRESETS["llama-debug-4kv-f32"] = _debug_variant(
    num_heads=8, num_kv_heads=4, dtype=jnp.float32
)
PRESETS["mistral-debug"] = _debug_variant(sliding_window=8)
PRESETS["mixtral-debug"] = _debug_variant(num_experts=4, num_experts_per_tok=2)


def init_params(cfg: LlamaConfig, key: jax.Array) -> dict:
    """Random-normal initialized parameter tree (layer-stacked)."""
    k_embed, k_layers, k_head = jax.random.split(key, 3)
    L, H, I = cfg.num_layers, cfg.hidden_size, cfg.intermediate_size
    NH, KH, D = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim

    def normal(key, shape, scale):
        return (jax.random.normal(key, shape, jnp.float32) * scale).astype(cfg.dtype)

    ks = jax.random.split(k_layers, 8)
    scale = H**-0.5
    layers: dict = {
        "attn_norm": jnp.ones((L, H), cfg.dtype),
        "wq": normal(ks[0], (L, H, NH * D), scale),
        "wk": normal(ks[1], (L, H, KH * D), scale),
        "wv": normal(ks[2], (L, H, KH * D), scale),
        "wo": normal(ks[3], (L, NH * D, H), (NH * D) ** -0.5),
        "mlp_norm": jnp.ones((L, H), cfg.dtype),
    }
    if cfg.attention_bias:
        layers["bq"] = jnp.zeros((L, NH * D), cfg.dtype)
        layers["bk"] = jnp.zeros((L, KH * D), cfg.dtype)
        layers["bv"] = jnp.zeros((L, KH * D), cfg.dtype)
    if cfg.num_experts:
        E = cfg.num_experts
        layers["moe_router"] = normal(ks[7], (L, H, E), scale)
        layers["moe_gate"] = normal(ks[4], (L, E, H, I), scale)
        layers["moe_up"] = normal(ks[5], (L, E, H, I), scale)
        layers["moe_down"] = normal(ks[6], (L, E, I, H), I**-0.5)
    else:
        layers["w_gate"] = normal(ks[4], (L, H, I), scale)
        layers["w_up"] = normal(ks[5], (L, H, I), scale)
        layers["w_down"] = normal(ks[6], (L, I, H), I**-0.5)
    params = {
        "embed": normal(k_embed, (cfg.vocab_size, H), scale),
        "layers": layers,
        "final_norm": jnp.ones((H,), cfg.dtype),
    }
    if not cfg.tie_word_embeddings:
        params["lm_head"] = normal(k_head, (H, cfg.vocab_size), scale)
    return params


def init_kv_pages(
    cfg: LlamaConfig, num_pages: int, page_size: int, dtype=None
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Layer-stacked page pools: [L, num_pages, page_size, KH, D]."""
    dtype = dtype or cfg.dtype
    shape = (cfg.num_layers, num_pages, page_size, cfg.num_kv_heads, cfg.head_dim)
    return jnp.zeros(shape, dtype), jnp.zeros(shape, dtype)


def lora_dims(cfg: LlamaConfig) -> dict[str, tuple[int, int]]:
    """(in_dim, out_dim) per LoRA-targetable projection."""
    H, I = cfg.hidden_size, cfg.intermediate_size
    dims = {
        "wq": (H, cfg.num_heads * cfg.head_dim),
        "wk": (H, cfg.num_kv_heads * cfg.head_dim),
        "wv": (H, cfg.num_kv_heads * cfg.head_dim),
        "wo": (cfg.num_heads * cfg.head_dim, H),
    }
    if not cfg.num_experts:  # MoE expert weights are not LoRA targets
        dims.update({"w_gate": (H, I), "w_up": (H, I), "w_down": (I, H)})
    return dims


def init_lora_buffers(
    cfg: LlamaConfig,
    max_loras: int,
    max_rank: int,
    targets: tuple[str, ...] = ("wq", "wk", "wv", "wo"),
) -> dict:
    """Slot-stacked LoRA buffers for batched multi-adapter serving.

    Layout is TPU-first: per target ``a_<t>: [L, S, in, R]`` and
    ``b_<t>: [L, S, R, out]`` with the layer axis leading so the buffers ride
    the decoder's ``lax.scan`` alongside the base weights, and the slot axis
    ``S`` gathered per sequence at trace time (one compiled program serves a
    batch mixing any adapters — the TPU analogue of punica/S-LoRA batched
    LoRA, which the reference stack reaches through vLLM's ``--enable-lora``,
    helm/templates/deployment-vllm-multi.yaml:197-207 in /root/reference).

    Slot 0 is reserved for "no adapter" and stays all-zero; ``scale`` is the
    per-slot ``alpha / r`` factor.
    """
    dims = lora_dims(cfg)
    unknown = set(targets) - set(dims)
    if unknown:
        raise ValueError(f"unknown LoRA targets {sorted(unknown)}; known: {sorted(dims)}")
    L, S, R = cfg.num_layers, max_loras, max_rank
    layers = {}
    for t in targets:
        din, dout = dims[t]
        layers["a_" + t] = jnp.zeros((L, S, din, R), cfg.dtype)
        layers["b_" + t] = jnp.zeros((L, S, R, dout), cfg.dtype)
    return {"layers": layers, "scale": jnp.zeros((S,), jnp.float32)}


def _qkv(h, lp, cfg: LlamaConfig, B: int, T: int, cos, sin, proj):
    """Shared q/k/v projection + bias + rope (forward and encode paths)."""
    q = proj(h, "wq").reshape(B, T, cfg.num_heads, cfg.head_dim)
    k = proj(h, "wk").reshape(B, T, cfg.num_kv_heads, cfg.head_dim)
    v = proj(h, "wv").reshape(B, T, cfg.num_kv_heads, cfg.head_dim)
    if cfg.attention_bias:
        q = q + lp["bq"].reshape(cfg.num_heads, cfg.head_dim)
        k = k + lp["bk"].reshape(cfg.num_kv_heads, cfg.head_dim)
        v = v + lp["bv"].reshape(cfg.num_kv_heads, cfg.head_dim)
    return apply_rope(q, cos, sin), apply_rope(k, cos, sin), v


def _mlp_residual(x, lp, cfg: LlamaConfig, proj):
    """Shared post-attention MLP (dense or MoE) residual block."""
    h = rms_norm(x, lp["mlp_norm"], cfg.rms_norm_eps)
    if cfg.num_experts:
        return x + _moe_block(h, lp, cfg)
    return x + proj(jax.nn.silu(proj(h, "w_gate")) * proj(h, "w_up"), "w_down")


def _plain_proj(lp):
    return lambda h, name: h @ lp[name]


def encode(
    params: dict,
    cfg: LlamaConfig,
    input_ids: jnp.ndarray,
    positions: jnp.ndarray,
) -> jnp.ndarray:
    """Pooled-embedding forward: one dense causal pass (no KV pages), masked
    mean-pool over valid tokens of the final hidden layer, L2-normalized.

    Serves /v1/embeddings, /v1/rerank, /v1/score — surface parity with the
    reference router's passthrough endpoints (routers/main_router.py:45-231 in
    /root/reference), which assume an engine that can embed.

    Args:
      input_ids: [B, T] int32, padded rows have position -1.
      positions: [B, T] absolute positions, -1 for padding.
    Returns [B, H] float32 unit vectors.
    """
    B, T = input_ids.shape
    x = params["embed"][input_ids].astype(cfg.dtype)
    cos, sin = rope_cos_sin(
        jnp.maximum(positions, 0), cfg.head_dim, cfg.rope_theta, cfg.rope_scaling
    )
    valid = positions >= 0  # [B, T]

    def layer(x, lp):
        proj = _plain_proj(lp)
        h = rms_norm(x, lp["attn_norm"], cfg.rms_norm_eps)
        q, k, v = _qkv(h, lp, cfg, B, T, cos, sin, proj)
        attn = flash_attention(
            q, k, v, q_positions=positions, kv_lens=jnp.sum(valid, axis=1),
            window=cfg.sliding_window,
        )
        x = x + proj(attn.reshape(B, T, -1), "wo")
        return _mlp_residual(x, lp, cfg, proj), None

    x, _ = lax.scan(layer, x, params["layers"])
    x = rms_norm(x, params["final_norm"], cfg.rms_norm_eps).astype(jnp.float32)
    mask = valid.astype(jnp.float32)[..., None]
    pooled = (x * mask).sum(1) / jnp.maximum(mask.sum(1), 1.0)
    return pooled / jnp.maximum(jnp.linalg.norm(pooled, axis=-1, keepdims=True), 1e-9)


def _moe_block(h: jnp.ndarray, lp: dict, cfg: LlamaConfig) -> jnp.ndarray:
    """Mixtral sparse-MoE MLP, computed densely over experts.

    Routing follows HF Mixtral: softmax over all experts, take top-k, renormalize.
    The dispatch is *dense* — every token multiplies every expert, with
    non-selected experts zeroed by the gate — which XLA maps cleanly onto the
    MXU with static shapes. With expert weights sharded on the ``ep`` mesh axis
    each device computes only its E/ep experts and the final contraction over E
    becomes one psum over ICI (classic expert parallelism). A sort-based
    capacity dispatch (token-choice) is the future optimization for large E at
    small batch; at serving batch sizes the dense form wins on compile
    simplicity and avoids ragged all-to-alls.
    """
    B, T, H = h.shape
    E, K = cfg.num_experts, cfg.num_experts_per_tok
    router_logits = (h @ lp["moe_router"]).astype(jnp.float32)     # [B, T, E]
    probs = jax.nn.softmax(router_logits, axis=-1)
    topw, topi = lax.top_k(probs, K)                               # [B, T, K]
    topw = topw / jnp.maximum(topw.sum(-1, keepdims=True), 1e-9)
    # scatter the renormalized top-k weights back to a dense [B, T, E] gate
    gate = (jax.nn.one_hot(topi, E, dtype=jnp.float32) * topw[..., None]).sum(-2)
    g = jnp.einsum("bth,ehi->btei", h, lp["moe_gate"])
    u = jnp.einsum("bth,ehi->btei", h, lp["moe_up"])
    y = jax.nn.silu(g) * u * gate.astype(h.dtype)[..., None]
    return jnp.einsum("btei,eih->bth", y, lp["moe_down"])


# mesh axes this family's forward actually implements (runner gates sp/pp
# on this — a mesh kwarg alone doesn't imply ring attention or pipelining)
MESH_AXES = ("dp", "tp", "sp", "ep", "pp")


def forward(
    params: dict,
    cfg: LlamaConfig,
    input_ids: jnp.ndarray,
    positions: jnp.ndarray,
    k_pages: jnp.ndarray,
    v_pages: jnp.ndarray,
    page_table: jnp.ndarray,
    kv_lens: jnp.ndarray,
    lora: Optional[dict] = None,
    lora_ids: Optional[jnp.ndarray] = None,
    all_logits: bool = False,
    mesh=None,
    kv_burst: Optional[tuple] = None,
    kv_scales: Optional[tuple] = None,
    riders: Optional[tuple] = None,
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """One forward step (prefill chunk or decode) with paged KV.

    Args:
      input_ids:  [B, T] int32 (T=1 for decode; padded rows have position -1).
      positions:  [B, T] absolute positions, -1 for padding.
      k_pages/v_pages: [L, P, page_size, KH, D] pools (donate for in-place).
      page_table: [B, max_pages] physical page ids per sequence.
      kv_lens:    [B] total valid KV length *including* this step's tokens.
      lora:       optional ``init_lora_buffers`` tree for batched multi-LoRA.
      lora_ids:   [B] int32 adapter slot per sequence (0 = base model).
      all_logits: static; True returns logits for *every* position (used by
                  speculative verify, which scores k draft tokens at once).
      mesh:       serving mesh, passed by ModelRunner when it has sp>1 (ring-
                  attention prefill over the sequence axis) or pp>1 (layer
                  stack pipelined over stages); None = plain GSPMD tp/dp.
      kv_burst:   deferred-scatter decode mode (T=1, kv_write_mode='post'
                  only): (k_acc [L, B, C, KH, D], v_acc, counts [B]) — the
                  burst's accumulated K/V windows plus how many entries are
                  valid per row. The POOLS ARE NOT WRITTEN: attention reads
                  pool slots < kv_lens - (counts+1) plus the window, and the
                  return value is (logits, k_acc', v_acc') with the current
                  token appended at slot ``counts``. The caller commits once
                  per burst (runner._multi_step_fn) — this is what keeps the
                  burst scan free of pool-sized copies.
      kv_scales:  (k_scales, v_scales) [L, P, KH] f32 when the pools are
                  int8 (cfg.kv_cache_dtype="int8", ops/quant.py contract):
                  reads dequantize in-kernel (or at the XLA gather), writes
                  quantize (fused prefill write / post-scan commit), and
                  the return grows to (logits, k_pages, v_pages, k_scales,
                  v_scales). kv_burst keeps its 3-tuple return (the pools
                  and scales stay read-only through the burst).
      riders:     (ids [R, 1], positions [R, 1], page_table [R, Pr], kv_lens
                  [R]): decode rows that take ONE step inside this prefill
                  dispatch (write-after-attend, one device, no LoRA, fp
                  pools). Their tokens join the chunk's on one token axis for
                  the embedding, the norms, every projection, the MLP and the
                  head, so the weights are read once; attention is per kind
                  (the chunk as without riders, a rider through the decode
                  path against its own pages); their K/V is committed after
                  the layer scan. An inert row has position -1 and kv_len 0:
                  it writes nothing. Logits come back [B + R, V], the riders'
                  rows last.

    Returns (logits[B, V] for each sequence's last valid token — or [B, T, V]
             when ``all_logits`` — and k_pages, v_pages updated; with
             ``kv_burst``: (logits, k_acc', v_acc'); with ``kv_scales``:
             (logits, k_pages, v_pages, k_scales, v_scales)).
    """
    sp = mesh.shape.get("sp", 1) if mesh is not None else 1
    pp = mesh.shape.get("pp", 1) if mesh is not None else 1
    B, T = input_ids.shape
    single_dev = mesh is None or mesh.devices.size == 1
    rope_positions = positions
    if riders is not None:
        if not (cfg.kv_write_mode == "post" and kv_burst is None
                and kv_scales is None and lora is None and not all_logits
                and single_dev and T > 1):
            raise ValueError(
                "riders ride a prefill chunk in write-after-attend mode on "
                "one device, with fp pools and no LoRA"
            )
        r_ids, r_pos, r_table, r_lens = riders
        R = r_ids.shape[0]
        # ONE token axis: [1, B * T + R] through everything but attention
        input_ids = jnp.concatenate(
            [input_ids.reshape(1, B * T), r_ids.reshape(1, R)], axis=1
        )
        rope_positions = jnp.concatenate(
            [positions.reshape(1, B * T), r_pos.reshape(1, R)], axis=1
        )
    with jax.named_scope("embed"):
        x = params["embed"][input_ids].astype(cfg.dtype)  # [B, T, H]
    if sp > 1 and T > 1:
        # sequence parallelism: spread the chunk's token dim over sp so the
        # norm/QKV/MLP FLOPs parallelize too, not just attention
        from jax.sharding import NamedSharding, PartitionSpec

        x = jax.lax.with_sharding_constraint(
            x, NamedSharding(mesh, PartitionSpec("dp", "sp", None))
        )
    cos, sin = rope_cos_sin(
        jnp.maximum(rope_positions, 0), cfg.head_dim, cfg.rope_theta,
        cfg.rope_scaling,
    )
    lora_scale = None if lora is None else lora["scale"][lora_ids].astype(cfg.dtype)

    post_write = cfg.kv_write_mode == "post"
    burst = kv_burst is not None
    quant = kv_scales is not None
    if quant:
        k_scales, v_scales = kv_scales
        if not post_write:
            raise ValueError("kv_cache_dtype=int8 requires kv_write_mode='post'")
        if sp > 1 or pp > 1:
            # the ring's sp sharding and the pipeline's stage relay both
            # move raw pool slices without their scales
            raise ValueError(
                "kv_cache_dtype=int8 does not compose with sp/pp meshes"
            )
    else:
        k_scales = v_scales = None
    if burst:
        if not post_write or T != 1:
            raise ValueError("kv_burst requires kv_write_mode='post' decode")
        k_acc, v_acc, burst_counts = kv_burst
        C = k_acc.shape[2]
        # pool slots >= the stale boundary hold this burst's tokens, whose
        # K/V live in the accumulator window instead (shared helper keeps
        # the XLA fallback and the kernel's masking in lockstep)
        from production_stack_tpu.ops.attention import burst_kv_positions

        kv_pos = burst_kv_positions(
            kv_lens, burst_counts + 1,
            page_table.shape[1] * k_pages.shape[2], C,
        )
    elif post_write:
        # write-after-attend: the pool is stale for this chunk, so attention
        # runs over [gathered pages at positions < chunk start] ++ [current
        # chunk K/V in-register]; per-layer K/V stack as scan outputs and one
        # batched scatter commits them after the scan (no per-layer pool
        # copies).
        kv_pos = stale_kv_positions(page_table, positions, k_pages.shape[2])

    # per-sequence aux threaded explicitly (not closed over) so the pp path
    # can slice it per microbatch; the plain path passes it whole
    aux = {
        "cos": cos, "sin": sin, "positions": positions,
        "page_table": page_table, "kv_lens": kv_lens,
        "kv_pos": kv_pos if post_write else None,
        "burst_counts": burst_counts if burst else None,
        "lora_ids": lora_ids, "lora_scale": lora_scale,
        "riders": None if riders is None else (r_pos, r_table, r_lens),
    }

    # pallas kernels stream pages straight from the STACKED pools (layer
    # index in scalar prefetch): slicing k_pages[l] per layer at the call
    # site would materialize a pool-sized copy every layer, since XLA cannot
    # fuse a dynamic-slice into a pallas_call operand (~1.5 ms/step on v5e).
    # Decode (T == 1) streams on any mesh (sharded kernel); chunked prefill
    # (T >= 16, post-write) streams single-device — multi-device prefill
    # keeps the XLA/ring path (GSPMD cannot partition a pallas_call and the
    # sp axis owns long chunks).
    # prefill kernel v2 (attn_impl="pallas_prefill", the TPU auto default /
    # "pallas_interpret" in tests): packed ragged grid + contiguous-KV DMA
    # ring — v1's page-granular (64-slot) matmuls fragmented the MXU and
    # only reached XLA parity; v2 lands N pages contiguously in VMEM and
    # folds them as ONE wide matmul (ops/pallas/prefill_attention.py).
    prefill_kernel_ok = (
        T >= 16 and single_dev and sp == 1 and kv_burst is None
        and cfg.attn_impl in ("pallas_prefill", "pallas_interpret")
    )
    stream_pools = (
        cfg.attn_impl.startswith("pallas")
        and pp == 1
        and post_write
        and (T == 1 or prefill_kernel_ok)
    )
    # fused paged-KV write: the kernel commits the chunk's K/V to the pool
    # in-kernel, the pools ride the layer scan as an aliased CARRY, and the
    # post-scan write_kv_pages_all_layers pass disappears — the chunk's KV
    # crosses HBM once instead of three times (stack write + read + scatter)
    fused_prefill = (
        prefill_kernel_ok and stream_pools and T > 1
        and getattr(cfg, "prefill_fused_kv_write", False)
    )

    def layer(x_aux, layer_in):
        if fused_prefill:
            # the pools ride the scan as CARRY: each layer's kernel writes
            # its own slice in place (aliased input->output), so the carry
            # chain is copy-free and the scan emits no stacked K/V (under
            # int8 the scales pools ride the same carry)
            if quant:
                x, aux, kp_c, vp_c, ksc_c, vsc_c = x_aux
            else:
                x, aux, kp_c, vp_c = x_aux
                ksc_c = vsc_c = None
        else:
            x, aux = x_aux
            kp_c = vp_c = ksc_c = vsc_c = None
        ksl = vsl = None  # per-layer scale slices (non-stream int8 path)
        if stream_pools:
            if burst:
                lp, li, ll, ka, va = layer_in
            else:
                lp, li, ll = layer_in  # per-layer params + layer index
            kp = vp = None
        elif quant and burst:
            lp, kp, vp, ksl, vsl, ll, ka, va = layer_in
        elif quant:
            lp, kp, vp, ksl, vsl, ll = layer_in
        elif burst:
            lp, kp, vp, ll, ka, va = layer_in
        else:
            lp, kp, vp, ll = layer_in  # per-layer params, pools, LoRA slices
        Bm, Tm = x.shape[:2]

        def proj(h, name):
            """h @ W with the batched per-sequence LoRA delta folded in."""
            y = h @ lp[name]
            if ll is not None and ("a_" + name) in ll:
                a = ll["a_" + name][aux["lora_ids"]]  # [B, in, R]
                b = ll["b_" + name][aux["lora_ids"]]  # [B, R, out]
                delta = jnp.einsum("bti,bir->btr", h, a)
                y = y + jnp.einsum("btr,bro->bto", delta, b) * (
                    aux["lora_scale"][:, None, None]
                )
            return y

        def decode_kernel(q1, pools, table, lens, cur_kw, **scales):
            """[rows, NH, D]: each row's one query against its own pages,
            streamed HBM->VMEM (no gather materialization); in post mode the
            current token's K/V fold in from registers. On a multi-device
            dp x tp mesh the kernel runs per shard via shard_map (GSPMD cannot
            partition a pallas_call). ``pools`` are the stacked pools (the
            layer's index rides along) or the layer's own slices."""
            from production_stack_tpu.ops.pallas.paged_attention import (
                ragged_paged_attention_decode,
                ragged_paged_attention_decode_sharded,
            )

            pallas_kw = dict(
                window=cfg.sliding_window,
                interpret=cfg.attn_impl == "pallas_interpret",
                pages_per_block=cfg.decode_pages_per_block or None,
                prefetch_pages=cfg.decode_prefetch_pages or None,
                **cur_kw, **scales,
            )
            if stream_pools:
                pallas_kw["layer"] = li
            # under pp the kernel runs INSIDE the pipeline's manual region;
            # the sharded call nests there and maps the remaining axes
            if mesh is not None and mesh.devices.size > 1:
                return ragged_paged_attention_decode_sharded(
                    mesh, q1, *pools, table, lens, **pallas_kw
                )
            return ragged_paged_attention_decode(
                q1, *pools, table, lens, **pallas_kw
            )

        with jax.named_scope("attention"):
            h = rms_norm(x, lp["attn_norm"], cfg.rms_norm_eps)
            q, k, v = _qkv(h, lp, cfg, Bm, Tm, aux["cos"], aux["sin"], proj)
            if riders is not None:
                # attention is per kind: the chunk's rows go on as they would
                # without riders, the riders' follow below
                qr, kr, vr = q[0, B * T:], k[0, B * T:], v[0, B * T:]
                q, k, v = (
                    a[0, : B * T].reshape(B, T, *a.shape[2:]) for a in (q, k, v)
                )
                Bm, Tm = B, T
            if burst:
                # append the current token into the burst window at slot
                # ``counts`` (entries 0..counts-1 hold earlier burst tokens);
                # the window, not the pool, carries this burst's K/V
                rows = jnp.arange(Bm, dtype=jnp.int32)
                cnt = aux["burst_counts"]
                kwin = ka.at[rows, cnt].set(k[:, 0].astype(ka.dtype))
                vwin = va.at[rows, cnt].set(v[:, 0].astype(va.dtype))
            if not post_write:
                kp, vp = write_kv_pages(
                    kp, vp, k.astype(kp.dtype), v.astype(vp.dtype),
                    aux["page_table"], aux["positions"],
                )
            if Tm == 1 and cfg.attn_impl.startswith("pallas"):
                # the in-register window stays fp under int8 pools — it is the
                # quantizer's INPUT, committed by the post-scan quant scatter
                cur_dt = cfg.dtype if quant else k_pages.dtype
                if burst:
                    cur_kw = dict(
                        k_cur=kwin, v_cur=vwin,
                        cur_lens=aux["burst_counts"] + 1,
                    )
                elif post_write:
                    cur_kw = dict(
                        k_cur=k[:, 0].astype(cur_dt),
                        v_cur=v[:, 0].astype(cur_dt),
                    )
                else:
                    cur_kw = dict(k_cur=None, v_cur=None)
                scales = {}
                if quant:
                    scales = dict(
                        k_scales=k_scales if stream_pools else ksl,
                        v_scales=v_scales if stream_pools else vsl,
                    )
                attn = decode_kernel(
                    q[:, 0], (k_pages, v_pages) if stream_pools else (kp, vp),
                    aux["page_table"], aux["kv_lens"], cur_kw, **scales,
                )[:, None]
            elif (
                Tm > 1
                and cfg.attn_impl.startswith("pallas")
                and stream_pools
                and not burst
            ):
                # chunked prefill: pallas flash kernel streams pages HBM->VMEM
                # (no [B, S, KH, D] pool gather) and folds the chunk's own K/V
                # in-register — the XLA scan ran at <20% MFU at 16k context
                # (ops/pallas/prefill_attention.py)
                from production_stack_tpu.ops.pallas.prefill_attention import (
                    ragged_paged_attention_prefill,
                )

                chunk_dt = cfg.dtype if quant else k_pages.dtype
                kernel_kw = dict(
                    window=cfg.sliding_window,
                    interpret=cfg.attn_impl == "pallas_interpret",
                    pages_per_block=getattr(cfg, "prefill_pages_per_block", 0)
                    or None,
                    prefetch_pages=getattr(cfg, "prefill_prefetch_pages", 0)
                    or None,
                    layer=li,
                )
                if quant:
                    kernel_kw["k_scales"] = ksc_c if fused_prefill else k_scales
                    kernel_kw["v_scales"] = vsc_c if fused_prefill else v_scales
                kernel_args = (
                    q,
                    kp_c if fused_prefill else k_pages,
                    vp_c if fused_prefill else v_pages,
                    aux["page_table"], aux["positions"], aux["kv_lens"],
                    k.astype(chunk_dt), v.astype(chunk_dt),
                    jnp.sum(aux["positions"] >= 0, axis=1).astype(jnp.int32),
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
            else:
                if quant:
                    from production_stack_tpu.ops.quant import (
                        gather_kv_pages_quant,
                    )

                    kc, vc = gather_kv_pages_quant(
                        kp, vp, ksl, vsl, aux["page_table"], dtype=cfg.dtype
                    )
                else:
                    kc, vc = gather_kv_pages(kp, vp, aux["page_table"])
                if burst:
                    kc = jnp.concatenate([kc, kwin.astype(kc.dtype)], axis=1)
                    vc = jnp.concatenate([vc, vwin.astype(vc.dtype)], axis=1)
                elif post_write:
                    kc = jnp.concatenate([kc, k.astype(kc.dtype)], axis=1)
                    vc = jnp.concatenate([vc, v.astype(vc.dtype)], axis=1)
                if sp > 1 and Tm > 1 and cfg.sliding_window is None:
                    # sequence-parallel prefill: ring attention over the sp axis
                    # (KV blocks rotate via ppermute while queries stay local)
                    from production_stack_tpu.parallel.ring_attention import (
                        ring_attention_serving,
                    )

                    if post_write:
                        # stale_kv_positions already covers pool slots + chunk
                        kvp = aux["kv_pos"]
                    else:
                        S = kc.shape[1]
                        kvp = jnp.broadcast_to(
                            jnp.arange(S, dtype=jnp.int32), (Bm, S)
                        )
                    attn = ring_attention_serving(
                        mesh, q, kc, vc, aux["positions"], kvp
                    )
                else:
                    attn = flash_attention(
                        q, kc, vc, q_positions=aux["positions"],
                        kv_lens=aux["kv_lens"],
                        window=cfg.sliding_window,
                        kv_positions=aux["kv_pos"] if post_write else None,
                    )
            rider_kv = None
            if riders is not None:
                r_pos, r_table, r_lens = aux["riders"]
                # the pools are stale for the rider's token as for the chunk's:
                # its K/V fold in from registers and are committed after the scan
                rider_kv = (kr[:, None].astype(k_pages.dtype),
                            vr[:, None].astype(v_pages.dtype))
                if cfg.attn_impl.startswith("pallas"):
                    # behind the chunk's kernel where that one writes the
                    # pools (a read of what it returns: no copy of a pool)
                    pools = (
                        (kp_c, vp_c) if fused_prefill
                        else (k_pages, v_pages) if stream_pools else (kp, vp)
                    )
                    attn_r = decode_kernel(
                        qr, pools, r_table, r_lens,
                        dict(k_cur=rider_kv[0][:, 0], v_cur=rider_kv[1][:, 0]),
                    )
                else:
                    kc, vc = gather_kv_pages(kp, vp, r_table)
                    attn_r = flash_attention(
                        qr[:, None],
                        jnp.concatenate([kc, rider_kv[0]], axis=1),
                        jnp.concatenate([vc, rider_kv[1]], axis=1),
                        q_positions=r_pos, kv_lens=r_lens,
                        window=cfg.sliding_window,
                        kv_positions=stale_kv_positions(
                            r_table, r_pos, k_pages.shape[2]
                        ),
                    )
                attn = jnp.concatenate(
                    [attn.reshape(1, B * T, -1), attn_r.reshape(1, R, -1)], axis=1
                )
                Bm, Tm = attn.shape[:2]
            x = x + proj(attn.reshape(Bm, Tm, -1), "wo")
        with jax.named_scope("mlp"):
            x = _mlp_residual(x, lp, cfg, proj)
        if fused_prefill:
            # the kernel already committed this layer's K/V to the pool
            if quant:
                return (x, aux, kp_c, vp_c, ksc_c, vsc_c), None
            return (x, aux, kp_c, vp_c), rider_kv
        if burst:
            out_kv = (kwin, vwin)  # stacked by the scan -> [L, B, C, KH, D]
        elif post_write:
            # int8 pools: stack fp — the post-scan commit is the quantizer
            store_dt = cfg.dtype if quant else k_pages.dtype
            out_kv = (k.astype(store_dt), v.astype(store_dt))
        else:
            out_kv = (kp, vp)
        if rider_kv is not None:
            out_kv = out_kv + rider_kv
        return (x, aux), out_kv

    lora_layers = None if lora is None else lora["layers"]
    if stream_pools:
        scan_xs = (
            params["layers"],
            jnp.arange(cfg.num_layers, dtype=jnp.int32),
            lora_layers,
        )
    elif quant:
        # per-layer scale slices ride the scan next to the pool slices
        scan_xs = (
            params["layers"], k_pages, v_pages, k_scales, v_scales,
            lora_layers,
        )
    else:
        scan_xs = (params["layers"], k_pages, v_pages, lora_layers)
    if burst:
        if pp > 1:
            raise ValueError("kv_burst does not compose with pipeline stages")
        (x, _), (k_acc, v_acc) = lax.scan(
            layer, (x, aux), scan_xs + (kv_burst[0], kv_burst[1])
        )
        # NO pool write: the caller commits the accumulated windows once per
        # burst — the pools stay loop constants through the burst scan
    elif pp > 1:
        if not post_write:
            raise ValueError("pipeline parallelism requires kv_write_mode='post'")
        from production_stack_tpu.parallel.pipeline import serving_layer_pipeline

        x, (k_new, v_new) = serving_layer_pipeline(mesh, layer, x, aux, scan_xs)
        k_pages, v_pages = write_kv_pages_all_layers(
            k_pages, v_pages, k_new, v_new, page_table, positions
        )
    elif fused_prefill and quant:
        # no post-scan scatter: every layer's kernel wrote its pool + scale
        # slices in place
        (x, _, k_pages, v_pages, k_scales, v_scales), _ = lax.scan(
            layer, (x, aux, k_pages, v_pages, k_scales, v_scales), scan_xs
        )
    elif fused_prefill:
        # no post-scan scatter: every layer's kernel wrote its pool slice
        (x, _, k_pages, v_pages), rider_new = lax.scan(
            layer, (x, aux, k_pages, v_pages), scan_xs
        )
    elif post_write and quant:
        (x, _), (k_new, v_new) = lax.scan(layer, (x, aux), scan_xs)
        from production_stack_tpu.ops.quant import (
            write_kv_pages_all_layers_quant,
        )

        with jax.named_scope("kv_commit"):
            k_pages, v_pages, k_scales, v_scales = (
                write_kv_pages_all_layers_quant(
                    k_pages, v_pages, k_scales, v_scales, k_new, v_new,
                    page_table, positions,
                )
            )
    elif post_write:
        (x, _), (k_new, v_new, *rider_new) = lax.scan(layer, (x, aux), scan_xs)
        with jax.named_scope("kv_commit"):
            k_pages, v_pages = write_kv_pages_all_layers(
                k_pages, v_pages, k_new, v_new, page_table, positions
            )
    else:
        (x, _), (k_pages, v_pages) = lax.scan(layer, (x, aux), scan_xs)
    if riders is not None:
        # the riders' one token each, every layer's in one scatter (a row's
        # own page, never one of the chunk's; position -1 is dropped)
        with jax.named_scope("kv_commit"):
            k_pages, v_pages = write_kv_pages_all_layers(
                k_pages, v_pages, *rider_new, r_table, r_pos
            )

    with jax.named_scope("lm_head"):
        x = rms_norm(x, params["final_norm"], cfg.rms_norm_eps)
        head = params["embed"].T if cfg.tie_word_embeddings else params["lm_head"]
        if all_logits:
            # speculative verify: T is small (1 + draft length), so [B, T, V] fits
            if quant:
                return (
                    (x @ head).astype(jnp.float32),
                    k_pages, v_pages, k_scales, v_scales,
                )
            return (x @ head).astype(jnp.float32), k_pages, v_pages
        # Select each sequence's last valid token before the vocab projection so the
        # logits tensor is [B, V], not [B, T, V] (a 2 GB save at V=128k, T=1k).
        last_idx = jnp.maximum(jnp.sum(positions >= 0, axis=1) - 1, 0)  # [B]
        x_riders = None
        if riders is not None:
            x, x_riders = x[0, : B * T].reshape(B, T, -1), x[0, B * T:]
        x_last = jnp.take_along_axis(x, last_idx[:, None, None], axis=1)[:, 0]  # [B, H]
        if x_riders is not None:
            x_last = jnp.concatenate([x_last, x_riders], axis=0)  # [B + R, H]
        logits = (x_last @ head).astype(jnp.float32)
    if burst:
        return logits, k_acc, v_acc
    if quant:
        return logits, k_pages, v_pages, k_scales, v_scales
    return logits, k_pages, v_pages
