"""Operations and bytes the algorithm needs, from shapes and the request log.

These are the benchmark's own counts (what the mathematics requires, not what
an implementation happens to move), so a roofline share computed from them can
only fall when an implementation does extra work and can never pass 100%.
"""

from __future__ import annotations

DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4, "int8": 1}


def _dims(doc: dict) -> dict:
    heads = doc["num_attention_heads"]
    return {
        "H": doc["hidden_size"], "I": doc["intermediate_size"], "NH": heads,
        "KH": doc.get("num_key_value_heads", heads),
        "D": doc.get("head_dim") or doc["hidden_size"] // heads,
        "L": doc["num_hidden_layers"], "V": doc["vocab_size"],
        "bias": bool(doc.get("attention_bias", False)),
        "tied": bool(doc.get("tie_word_embeddings", False)),
    }


def window(doc: dict):
    """The attention window in tokens, or None (Qwen2 windows nothing unless
    use_sliding_window is true)."""
    w = doc.get("sliding_window")
    if (doc.get("architectures") or [""])[0].startswith("Qwen2") and not doc.get(
            "use_sliding_window", False):
        return None
    return w


def kv_bytes_per_token(doc: dict, kv_dtype: str | None = None) -> int:
    """Bytes of K and V one token holds over all layers."""
    d = _dims(doc)
    size = DTYPE_BYTES[kv_dtype or doc.get("torch_dtype", "bfloat16")]
    return 2 * d["L"] * d["KH"] * d["D"] * size


def params_per_layer(doc: dict) -> int:
    d = _dims(doc)
    attn = d["H"] * d["NH"] * d["D"] * 2 + d["H"] * d["KH"] * d["D"] * 2
    if d["bias"]:
        attn += (d["NH"] + 2 * d["KH"]) * d["D"]
    return attn + 3 * d["H"] * d["I"] + 2 * d["H"]


def params_total(doc: dict) -> int:
    d = _dims(doc)
    return d["L"] * params_per_layer(doc) + d["V"] * d["H"] * (1 if d["tied"] else 2) + d["H"]


def decode_attn_bytes(doc: dict, contexts, kv_dtype: str | None = None) -> float:
    """Bytes decode attention must read: one output token at context c reads
    the K and V of min(c, window) tokens over all layers, once."""
    w = window(doc)
    per = kv_bytes_per_token(doc, kv_dtype)
    return float(sum(min(c, w) if w else c for c in contexts) * per)
