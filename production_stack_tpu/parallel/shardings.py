"""Sharding rules (GSPMD PartitionSpecs) for model parameters, KV page pools,
and per-step batch inputs.

Megatron-style tensor parallelism expressed declaratively: column-parallel
projections shard their output dim on ``tp``, row-parallel shard their input
dim; XLA inserts the (reduce-scatter/all-reduce) collectives. No NCCL —
this is the TPU replacement for the reference's in-engine TP
(SURVEY.md §2.3: "jax.sharding/pjit mesh over ICI within a slice").

KV page pools shard the *kv-head* axis on ``tp`` so each chip holds only its
heads' pages — the paged-attention gather then never crosses chips.
"""

from __future__ import annotations

import functools

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# Per-parameter PartitionSpecs, keyed by leaf name. Top-level leaves are plain
# tensors; leaves under "layers" are layer-stacked and get a leading None for
# the [L] axis prepended by param_specs_for. Covers every model family
# (Llama/Mistral/Qwen2/Mixtral in models/llama.py, OPT in models/opt.py).
_TOP_SPECS = {
    "embed": P("tp", None),            # vocab-sharded; GSPMD handles the gather
    "pos_embed": P(None, None),
    "final_norm": P(None),
    "final_norm_w": P(None),
    "final_norm_b": P(None),
    "lm_head": P(None, "tp"),
}
_LAYER_SPECS = {
    "attn_norm": P(None),
    "attn_norm_w": P(None),
    "attn_norm_b": P(None),
    "wq": P(None, "tp"),               # column parallel (+ bias on the out dim)
    "bq": P("tp"),
    "wk": P(None, "tp"),
    "bk": P("tp"),
    "wv": P(None, "tp"),
    "bv": P("tp"),
    "wo": P("tp", None),               # row parallel (bias after the all-reduce)
    "bo": P(None),
    "mlp_norm": P(None),
    "mlp_norm_w": P(None),
    "mlp_norm_b": P(None),
    "post_attn_norm": P(None),         # Gemma-2 sandwich norms
    "post_mlp_norm": P(None),
    "w_gate": P(None, "tp"),
    "w_up": P(None, "tp"),
    "w_down": P("tp", None),
    "fc1": P(None, "tp"),
    "fc1_b": P("tp"),
    "fc2": P("tp", None),
    "fc2_b": P(None),
    # MoE (Mixtral): experts sharded over ep, each expert's FFN over tp — the
    # contraction over E inserts one psum over the ep axis (expert parallelism).
    "moe_router": P(None, None),
    "moe_gate": P("ep", None, "tp"),
    "moe_up": P("ep", None, "tp"),
    "moe_down": P("ep", "tp", None),
    # state-space mixer (models/jamba.py): replicated, the family serves on
    # one chip (tp > 1 is refused at start-up)
    "mixer_norm": P(None),
    "in_proj": P(None, None),
    "conv_w": P(None, None),
    "conv_b": P(None),
    "x_proj": P(None, None),
    "dt_norm": P(None),
    "b_norm": P(None),
    "c_norm": P(None),
    "dt_proj": P(None, None),
    "dt_bias": P(None),
    "a_log": P(None, None),
    "d_skip": P(None),
    "out_proj": P(None, None),
    # LFM2-MoE (models/lfm2.py): replicated too, one chip; the experts lie
    # [E, ...] under their layer (experts spread over chips: ROADMAP M1)
    "q_norm": P(None),
    "k_norm": P(None),
    "router": P(None, None),
    "expert_bias": P(None),
    "w13": P(None, None, None),
    "w2": P(None, None, None),
    # Nemotron-H (models/nemotron_h.py): replicated, one chip; Mamba-2's
    # per-head scalars, its gated norm, and ungated experts ([E, H, I] up)
    "a_log_head": P(None),
    "gate_norm": P(None),
    "w1": P(None, None, None),
}

# [L, P, page_size, KH, D] pools: shard kv heads over tp.
KV_PAGES_SPEC = P(None, None, None, "tp", None)
# Under pipeline parallelism each stage holds only its own layers' pages.
KV_PAGES_SPEC_PP = P("pp", None, None, "tp", None)

BATCH_SPECS = {
    "input_ids": P("dp", None),
    "positions": P("dp", None),
    "page_table": P("dp", None),
    "kv_lens": P("dp"),
    "logits": P("dp", "tp"),
}


def param_specs_for(params: dict, pp: bool = False) -> dict:
    """PartitionSpec tree matching the structure of `params` (any model
    family), built from the per-leaf-name tables above.

    With ``pp`` the layer-stacked leaves shard their leading [L] axis over the
    ``pp`` mesh axis (each pipeline stage holds a contiguous layer slice);
    embed/lm_head stay replicated so first/last stages need no gathers.
    """
    layer_lead = "pp" if pp else None
    specs: dict = {}
    for k, v in params.items():
        if isinstance(v, dict):  # a layer-stacked group ("layers", "attn_layers")
            specs[k] = {n: P(layer_lead, *_LAYER_SPECS[n]) for n in v}
        else:
            specs[k] = _TOP_SPECS[k]
    return specs


def shard_tree(tree, specs, mesh: Mesh):
    """Device_put a pytree with per-leaf PartitionSpecs."""
    return jax.tree.map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)), tree, specs,
        is_leaf=lambda x: x is None,
    )


@functools.lru_cache(maxsize=64)
def _sharded_init(init_params, cfg, mesh: Mesh, pp: bool):
    specs = param_specs_for(
        jax.eval_shape(lambda: init_params(cfg, jax.random.key(0))), pp=pp
    )
    out = jax.tree.map(
        lambda s: NamedSharding(mesh, s), specs,
        is_leaf=lambda x: isinstance(x, P),
    )
    return jax.jit(lambda key: init_params(cfg, key), out_shardings=out)


def init_sharded(init_params, cfg, key, mesh: Mesh, pp: bool = False):
    """``init_params(cfg, key)`` under jit with the parameter shardings as
    ``out_shardings``: every device materializes only its own shard. Built
    eagerly, a 7B random tree lands whole (14.5 GB) on device 0 of a 16 GB
    chip before ``shard_tree`` can spread it. Values are sharding-invariant
    (jax's threefry PRNG is partitionable). The jitted builder is cached per
    (family, config, mesh): a process that builds many engines traces and
    compiles it once."""
    return _sharded_init(init_params, cfg, mesh, pp)(key)


@functools.lru_cache(maxsize=64)
def _sharded_builder(make, args: tuple, shardings: tuple):
    return jax.jit(lambda: make(*args), out_shardings=shardings)


def build_sharded(make, args: tuple, shardings: tuple):
    """``make(*args)`` (a constant builder returning a tuple of arrays: the
    zeroed page pools, the scales pools) under jit with ``shardings`` as
    ``out_shardings`` — pool-sized buffers come up shard by shard instead of
    whole on device 0. Cached like :func:`init_sharded`."""
    return _sharded_builder(make, args, shardings)()


def named(mesh: Mesh, spec: P) -> NamedSharding:
    return NamedSharding(mesh, spec)
