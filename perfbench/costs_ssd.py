"""Bytes and operations the Mamba-2 (SSD) recurrence of a layer needs, from
shapes.

The benchmark's own counts, like costs.py's: what the mathematics requires, not
what an implementation happens to move, so a roofline share computed from them
can only fall when an implementation does extra work. Each quantity is counted
at the narrowest type the configuration states for it. NH heads of P channels,
G groups of N: a sequence's state in one layer is [NH, P, N] float32 (2 MiB at
Nemotron-3-Nano's sizes). The convolution's tail is NOT counted (the
recurrence does not need it), nor z (the gate is applied outside it).
"""

from __future__ import annotations

import costs


def dims(doc: dict) -> dict:
    return {
        "NH": doc["mamba_num_heads"], "P": doc["mamba_head_dim"],
        "G": doc["n_groups"], "N": doc["ssm_state_size"],
        "Q": doc.get("chunk_size", 128),
        "Ls": doc["hybrid_override_pattern"].count("M"),
        "act": costs.DTYPE_BYTES[doc.get("torch_dtype", "bfloat16")],
    }


def state_bytes(doc: dict) -> int:
    """One layer's float32 [NH, P, N] state of one sequence, crossing HBM once."""
    d = dims(doc)
    return d["NH"] * d["P"] * d["N"] * 4


def token_bytes(doc: dict) -> int:
    """One layer, one token: the rows x (in) and y (out) and B, C at the
    activation type, dt in float32 (an exponent)."""
    d = dims(doc)
    return 2 * d["NH"] * d["P"] * d["act"] + d["NH"] * 4 + 2 * d["G"] * d["N"] * d["act"]


def token_flops(doc: dict) -> int:
    """One layer, one token of the chunked (SSD) form at blocks of Q
    positions: C B^T once a group (2 Q N), and a head's three products, (L o C
    B^T) X (2 Q P), C S^T (2 N P) and B^T X (2 N P)."""
    d = dims(doc)
    return d["G"] * 2 * d["Q"] * d["N"] + d["NH"] * (2 * d["Q"] * d["P"] + 4 * d["N"] * d["P"])


def decode_least_seconds(doc: dict, counted: dict, peaks: dict) -> float:
    """Decode: every token stepped reads and writes its sequence's state in
    every Mamba-2 layer and moves its own rows. `counted`: the change of
    `ssd_decode_tokens_total`."""
    tokens = counted["ssd_decode_tokens_total"]
    d = dims(doc)
    return tokens * d["Ls"] * (2 * state_bytes(doc) + token_bytes(doc)) / peaks["hbm_bytes_per_s"]


def prefill_least_seconds(doc: dict, counted: dict, peaks: dict) -> float:
    """Prefill: the larger of the bytes (every token's rows; a dispatch's row
    moves its state in and out once a layer) over the HBM peak and the block
    products over the bf16 peak. `counted`: the changes of
    `ssd_prefill_tokens_total` and `ssd_prefill_rows_total`."""
    tokens, rows = counted["ssd_prefill_tokens_total"], counted["ssd_prefill_rows_total"]
    d = dims(doc)
    moved = d["Ls"] * (tokens * token_bytes(doc) + rows * 2 * state_bytes(doc))
    return max(moved / peaks["hbm_bytes_per_s"],
               d["Ls"] * tokens * token_flops(doc) / peaks["bf16_flops_per_s"])
