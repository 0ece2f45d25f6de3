"""Bytes and operations the routed experts' feed-forward needs where an expert
has NO gate: `down(relu(up x)^2)`, TWO H x I matrices (costs_moe.py counts the
gated SwiGLU's three). An expert that a step routes at least one row to has to
be read once; every routed row costs its two products. I is the published
width (1,856 for Nemotron-3-Nano), whatever an implementation pads it to.
Activations are not counted, so a share computed from these counts can only
fall when an implementation does extra work.
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
    """One expert's weights: up (H x I) and down (I x H)."""
    d = dims(doc)
    return 2 * d["H"] * d["I"] * d["w"]


def row_flops(doc: dict) -> int:
    """One routed row through one expert: two products of H x I."""
    d = dims(doc)
    return 4 * d["H"] * d["I"]


def least_seconds(doc: dict, counted: dict, peaks: dict) -> float:
    """The larger of the weight reads over the HBM peak and the products over
    the bf16 peak. `counted`: the changes of `moe_expert_reads_total` and
    `moe_routed_rows_total`; rows routed to experts the chip does not hold are
    no rows of its product, so the counter of rows BY HELD expert is what
    counts: `moe_routed_rows_total` sums exactly those."""
    return max(
        counted["moe_expert_reads_total"] * expert_bytes(doc) / peaks["hbm_bytes_per_s"],
        counted["moe_routed_rows_total"] * row_flops(doc) / peaks["bf16_flops_per_s"],
    )
