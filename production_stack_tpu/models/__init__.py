"""Model registry.

Every model module exposes the same duck-typed interface consumed by
engine/runner.py and engine/model_loader.py:

- ``Config`` dataclass (``from_hf_config``, ``attn_impl``, ``num_layers``,
  ``num_kv_heads``, ``head_dim``, ``max_model_len``, ``dtype``)
- ``PRESETS: dict[str, Config]``
- ``init_params(cfg, key)`` / ``init_kv_pages(cfg, num_pages, page_size)``
- ``forward(params, cfg, input_ids, positions, k_pages, v_pages, page_table,
  kv_lens) -> (logits, k_pages, v_pages)``
- ``Config.num_kv_layers``: the layers that hold pages (``num_layers`` counts
  the model's; the two differ where not every layer attends)
- optionally ``init_state(cfg, slots)``: a family that keeps recurrent state
  beside the pages (models/jamba.py, models/lfm2.py, models/nemotron_h.py);
  its ``forward`` takes
  ``state=`` and ``state_slots=`` and returns the updated state as a fourth
  value
- optionally ``Config.step_counters`` (a count) with ``counter_stats(cfg,
  totals)``: ``forward`` returns that many int32 as the LAST element of its
  result, whatever else it returns: what this call did on the device, which
  the step programs sum over a burst and hand out beside the tokens
  (models/lfm2.py: what the expert layers routed; models/nemotron_h.py: that
  and the tokens its SSD layers walked)

Sharding specs are name-based (parallel/shardings.py) so new families only
need to reuse the leaf-name vocabulary or extend the spec tables.
"""

from __future__ import annotations

from production_stack_tpu.models import gemma2, jamba, lfm2, llama, nemotron_h, opt

#: module search order for preset names and HF architectures
MODULES = (llama, opt, gemma2, jamba, lfm2, nemotron_h)

_ARCH_TO_MODULE = {
    "LlamaForCausalLM": llama,
    "MistralForCausalLM": llama,
    "Qwen2ForCausalLM": llama,
    "MixtralForCausalLM": llama,
    "OPTForCausalLM": opt,
    "Gemma2ForCausalLM": gemma2,
    "JambaForCausalLM": jamba,
    "Lfm2MoeForCausalLM": lfm2,
    "NemotronHForCausalLM": nemotron_h,
}


def module_for_arch(arch: str):
    """Map a HuggingFace `architectures[0]` string to a model module."""
    try:
        return _ARCH_TO_MODULE[arch]
    except KeyError:
        raise ValueError(
            f"unsupported architecture {arch!r}; supported: {sorted(_ARCH_TO_MODULE)}"
        ) from None


def module_for_config(cfg):
    """Map a model config instance back to its module."""
    if isinstance(cfg, llama.LlamaConfig):
        return llama
    if isinstance(cfg, opt.OPTConfig):
        return opt
    if isinstance(cfg, gemma2.Gemma2Config):
        return gemma2
    if isinstance(cfg, jamba.JambaConfig):
        return jamba
    if isinstance(cfg, lfm2.Lfm2Config):
        return lfm2
    if isinstance(cfg, nemotron_h.NemotronHConfig):
        return nemotron_h
    raise ValueError(f"unknown model config type {type(cfg).__name__}")


def find_preset(name: str):
    """Return (module, config) for a preset name, or None."""
    for mod in MODULES:
        if name in mod.PRESETS:
            return mod, mod.PRESETS[name]
    return None
