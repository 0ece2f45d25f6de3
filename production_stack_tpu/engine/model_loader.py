"""Load model weights.

Two paths:
- preset name (llama-debug / llama-3.2-1b / qwen2.5-7b / mixtral-8x7b /
  opt-125m ...): seeded random init — used by tests, benchmarks, and hermetic
  environments. ``params`` comes back ``None``: the runner builds the tree
  under jit straight into its shards (parallel/shardings.init_sharded).
- local HuggingFace directory (config.json + *.safetensors): production path;
  weights live on a PVC exactly like the reference's HF_HOME cache
  (helm/templates/deployment-vllm-multi.yaml:191-196 in /root/reference).
  Architecture is dispatched on `config.json["architectures"][0]`
  (Llama/Mistral/Qwen2/Mixtral → models/llama.py; OPT → models/opt.py).

HF per-layer tensors are mapped onto the layer-stacked trees the models use
(every per-layer weight stacked on a leading [L] axis for the scan).

Returns (module, config, params) — the module is the models/* family module
whose `forward` the runner will jit. HF params are HOST (numpy) leaves, so the
runner places each leaf on its shards and no device ever holds a whole tree.
"""

from __future__ import annotations

import dataclasses
import json
import os
from typing import Any

import numpy as np

from production_stack_tpu import models
from production_stack_tpu.models import gemma2, llama, opt


def is_hf_dir(path: str) -> bool:
    return os.path.isdir(path) and os.path.exists(os.path.join(path, "config.json"))


def load_model(model: str, max_model_len: int | None = None):
    """Returns (module, config, params); params is None for a preset."""
    if is_hf_dir(model):
        mod, cfg, params = load_from_hf(model)
        if max_model_len:
            if mod is opt and max_model_len > cfg.max_model_len:
                # OPT's learned position table is checkpoint-sized; it cannot
                # be extended (positions past it would clamp-gather silently)
                raise ValueError(
                    f"max_model_len={max_model_len} exceeds OPT position table "
                    f"({cfg.max_model_len})"
                )
            cfg = dataclasses.replace(cfg, max_model_len=max_model_len)
    else:
        hit = models.find_preset(model)
        if hit is None:
            names = sorted(n for m in models.MODULES for n in m.PRESETS)
            raise ValueError(
                f"model '{model}' is neither a preset ({names}) nor a local HF dir"
            )
        mod, cfg = hit
        if max_model_len:
            # before init_params: OPT sizes its position table from this
            cfg = dataclasses.replace(cfg, max_model_len=max_model_len)
        params = None
    return mod, cfg, params


def _safetensor_shards(path: str):
    from safetensors import safe_open

    files = sorted(f for f in os.listdir(path) if f.endswith(".safetensors"))
    if not files:
        raise FileNotFoundError(f"no .safetensors in {path}")
    tensors: dict[str, Any] = {}
    for fname in files:
        with safe_open(os.path.join(path, fname), framework="np") as f:
            for key in f.keys():
                tensors[key] = f.get_tensor(key)
    return tensors


def load_from_hf(path: str):
    """Load any supported architecture from a local HF directory."""
    with open(os.path.join(path, "config.json")) as f:
        hf_cfg = json.load(f)
    arch = (hf_cfg.get("architectures") or ["LlamaForCausalLM"])[0]
    mod = models.module_for_arch(arch)
    if mod is opt:
        cfg, params = _load_opt(hf_cfg, path)
    elif mod is gemma2:
        cfg, params = _load_gemma2(hf_cfg, path)
    else:
        cfg, params = _load_llama_family(hf_cfg, path)
    return mod, cfg, params


def _weight_helpers(tensors: dict, num_layers: int, dtype):
    def get(name: str) -> np.ndarray:
        return np.asarray(tensors[name])

    def stack(fmt: str, transpose: bool = True) -> np.ndarray:
        ws = [get(fmt.format(i)) for i in range(num_layers)]
        arr = np.stack([w.T if transpose else w for w in ws])
        return arr.astype(dtype)

    return get, stack


def _load_llama_family(hf_cfg: dict, path: str) -> tuple[llama.LlamaConfig, dict]:
    cfg = llama.LlamaConfig.from_hf_config(hf_cfg)
    t = _safetensor_shards(path)
    dt = cfg.dtype
    get, stack = _weight_helpers(t, cfg.num_layers, dt)

    layers = {
        "attn_norm": stack("model.layers.{}.input_layernorm.weight", transpose=False),
        "wq": stack("model.layers.{}.self_attn.q_proj.weight"),
        "wk": stack("model.layers.{}.self_attn.k_proj.weight"),
        "wv": stack("model.layers.{}.self_attn.v_proj.weight"),
        "wo": stack("model.layers.{}.self_attn.o_proj.weight"),
        "mlp_norm": stack("model.layers.{}.post_attention_layernorm.weight", transpose=False),
    }
    if cfg.attention_bias:
        layers["bq"] = stack("model.layers.{}.self_attn.q_proj.bias", transpose=False)
        layers["bk"] = stack("model.layers.{}.self_attn.k_proj.bias", transpose=False)
        layers["bv"] = stack("model.layers.{}.self_attn.v_proj.bias", transpose=False)
    if cfg.num_experts:
        # Mixtral: block_sparse_moe.gate + per-expert w1 (gate), w2 (down), w3 (up)
        L, E = cfg.num_layers, cfg.num_experts

        def stack_experts(w: str) -> np.ndarray:
            arr = np.stack([
                np.stack([
                    get(f"model.layers.{i}.block_sparse_moe.experts.{e}.{w}.weight").T
                    for e in range(E)
                ])
                for i in range(L)
            ])  # [L, E, in, out]
            return arr.astype(dt)

        layers["moe_router"] = stack("model.layers.{}.block_sparse_moe.gate.weight")
        layers["moe_gate"] = stack_experts("w1")
        layers["moe_down"] = stack_experts("w2")
        layers["moe_up"] = stack_experts("w3")
    else:
        layers["w_gate"] = stack("model.layers.{}.mlp.gate_proj.weight")
        layers["w_up"] = stack("model.layers.{}.mlp.up_proj.weight")
        layers["w_down"] = stack("model.layers.{}.mlp.down_proj.weight")

    params = {
        "embed": get("model.embed_tokens.weight").astype(dt),
        "layers": layers,
        "final_norm": get("model.norm.weight").astype(dt),
    }
    if not cfg.tie_word_embeddings:
        params["lm_head"] = get("lm_head.weight").T.astype(dt)
    return cfg, params


def _load_opt(hf_cfg: dict, path: str) -> tuple[opt.OPTConfig, dict]:
    cfg = opt.OPTConfig.from_hf_config(hf_cfg)
    raw = _safetensor_shards(path)
    dt = cfg.dtype
    # OPTForCausalLM checkpoints prefix with "model."; bare OPTModel ones don't.
    t = {
        (k[len("model."):] if k.startswith("model.") else k): v
        for k, v in raw.items()
    }
    get, stack = _weight_helpers(t, cfg.num_layers, dt)
    lf = "decoder.layers.{}."
    params = {
        "embed": get("decoder.embed_tokens.weight").astype(dt),
        "pos_embed": get("decoder.embed_positions.weight").astype(dt),
        "layers": {
            "attn_norm_w": stack(lf + "self_attn_layer_norm.weight", transpose=False),
            "attn_norm_b": stack(lf + "self_attn_layer_norm.bias", transpose=False),
            "wq": stack(lf + "self_attn.q_proj.weight"),
            "bq": stack(lf + "self_attn.q_proj.bias", transpose=False),
            "wk": stack(lf + "self_attn.k_proj.weight"),
            "bk": stack(lf + "self_attn.k_proj.bias", transpose=False),
            "wv": stack(lf + "self_attn.v_proj.weight"),
            "bv": stack(lf + "self_attn.v_proj.bias", transpose=False),
            "wo": stack(lf + "self_attn.out_proj.weight"),
            "bo": stack(lf + "self_attn.out_proj.bias", transpose=False),
            "mlp_norm_w": stack(lf + "final_layer_norm.weight", transpose=False),
            "mlp_norm_b": stack(lf + "final_layer_norm.bias", transpose=False),
            "fc1": stack(lf + "fc1.weight"),
            "fc1_b": stack(lf + "fc1.bias", transpose=False),
            "fc2": stack(lf + "fc2.weight"),
            "fc2_b": stack(lf + "fc2.bias", transpose=False),
        },
        "final_norm_w": get("decoder.final_layer_norm.weight").astype(dt),
        "final_norm_b": get("decoder.final_layer_norm.bias").astype(dt),
    }
    return cfg, params


def _load_gemma2(hf_cfg: dict, path: str) -> tuple["gemma2.Gemma2Config", dict]:
    cfg = gemma2.Gemma2Config.from_hf_config(hf_cfg)
    t = _safetensor_shards(path)
    dt = cfg.dtype
    get, stack = _weight_helpers(t, cfg.num_layers, dt)
    lf = "model.layers.{}."
    params = {
        "embed": get("model.embed_tokens.weight").astype(dt),
        "layers": {
            "attn_norm": stack(lf + "input_layernorm.weight", transpose=False),
            "post_attn_norm": stack(lf + "post_attention_layernorm.weight", transpose=False),
            "mlp_norm": stack(lf + "pre_feedforward_layernorm.weight", transpose=False),
            "post_mlp_norm": stack(lf + "post_feedforward_layernorm.weight", transpose=False),
            "wq": stack(lf + "self_attn.q_proj.weight"),
            "wk": stack(lf + "self_attn.k_proj.weight"),
            "wv": stack(lf + "self_attn.v_proj.weight"),
            "wo": stack(lf + "self_attn.o_proj.weight"),
            "w_gate": stack(lf + "mlp.gate_proj.weight"),
            "w_up": stack(lf + "mlp.up_proj.weight"),
            "w_down": stack(lf + "mlp.down_proj.weight"),
        },
        "final_norm": get("model.norm.weight").astype(dt),
    }
    return cfg, params


def load_llama_from_hf(path: str) -> tuple[llama.LlamaConfig, dict]:
    """Back-compat shim (Llama-family only)."""
    with open(os.path.join(path, "config.json")) as f:
        hf_cfg = json.load(f)
    return _load_llama_family(hf_cfg, path)
