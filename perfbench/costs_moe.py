"""Bytes and operations the routed experts' feed-forward needs, from shapes.

The benchmark's own counts, like costs.py's: what the mathematics requires, not
what an implementation happens to move. An expert that a step routes at least
one row to has to be read once (its three H x I matrices); every routed row
costs its three products. Activations are not counted (they are small beside
the weights and an implementation may keep them on the chip), so a roofline
share computed from these counts can only fall when an implementation does
extra work.
"""

from __future__ import annotations

import costs


def dims(doc: dict) -> dict:
    return {
        "H": doc["hidden_size"],
        "I": doc["moe_intermediate_size"],
        "w": costs.DTYPE_BYTES[doc.get("torch_dtype", "bfloat16")],
    }


def expert_bytes(doc: dict) -> int:
    """One expert's weights: W1, W3 (H x I each) and W2 (I x H)."""
    d = dims(doc)
    return 3 * d["H"] * d["I"] * d["w"]


def row_flops(doc: dict) -> int:
    """One routed row through one expert: three products of H x I."""
    d = dims(doc)
    return 6 * d["H"] * d["I"]


def least_seconds(doc: dict, expert_reads: float, routed_rows: float, peaks: dict) -> float:
    """The least time the chip could take: the larger of the weight reads over
    the HBM peak and the products over the bf16 peak."""
    return max(
        expert_reads * expert_bytes(doc) / peaks["hbm_bytes_per_s"],
        routed_rows * row_flops(doc) / peaks["bf16_flops_per_s"],
    )
