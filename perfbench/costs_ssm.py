"""Bytes the selective scan of a state-space (Mamba-1) layer needs, from shapes.

The benchmark's own counts, like costs.py's: what the mathematics requires, not
what an implementation happens to move, so a roofline share computed from them
can only fall when an implementation does extra work and can never pass 100%.
Each quantity is counted at the narrowest type the configuration states for it
(the kernel may well move float32 where bfloat16 is counted here: that lowers
its share, as it should). The convolution's tail is NOT counted: the scan does
not need it.
"""

from __future__ import annotations

import costs


def dims(doc: dict) -> dict:
    period, offset = doc["attn_layer_period"], doc["attn_layer_offset"]
    layers = doc["num_hidden_layers"]
    return {
        "Di": doc.get("mamba_expand", 2) * doc["hidden_size"],
        "N": doc.get("mamba_d_state", 16),
        "Ls": sum(1 for i in range(layers) if i % period != offset),
        "act": costs.DTYPE_BYTES[doc.get("torch_dtype", "bfloat16")],
    }


def state_bytes(doc: dict) -> int:
    """One layer's float32 [N, Di] state of one sequence, crossing HBM once."""
    d = dims(doc)
    return d["Di"] * d["N"] * 4


def token_bytes(doc: dict) -> int:
    """One layer, one token: the rows u, z (in) and y (out) at the activation
    type, delta in float32 (an exponent), and B, C in float32."""
    d = dims(doc)
    return d["Di"] * (3 * d["act"] + 4) + 2 * d["N"] * 4


def decode_bytes(doc: dict, tokens: int) -> float:
    """Decode: every output token reads and writes its sequence's state in
    every state-space layer, and moves its own rows."""
    d = dims(doc)
    return float(tokens * d["Ls"] * (2 * state_bytes(doc) + token_bytes(doc)))


def prefill_bytes(doc: dict, prompts, chunk: int = 512) -> float:
    """Prefill: every prompt token moves its rows; the state crosses HBM in
    and out once a chunk."""
    d = dims(doc)
    chunks = sum(-(-p // chunk) for p in prompts)
    return float(d["Ls"] * (sum(prompts) * token_bytes(doc) + chunks * 2 * state_bytes(doc)))
