"""Startup host<->device link-bandwidth probe.

``kv_offload_max_io_pages`` — the per-operation page budget for KV offload
spills and restores — used to be a hand-tuned constant. The right value
is a pure function of the host<->device link bandwidth, so the engine now
measures it once at startup (a few round trips of an ~8 MB buffer) and
derives the cap; the measured bandwidth and chosen cap are exported on
/metrics so operators can see what the probe decided. An explicit
``--kv-offload-max-io-pages >= 0`` skips the probe entirely (manual override
honored).
"""

from __future__ import annotations

import time
from typing import Optional

from production_stack_tpu.utils.logging import init_logger

logger = init_logger(__name__)

# links at or above this are "PCIe-class": restore always beats recompute,
# so the I/O budget stays unbounded
FAST_LINK_BYTES_PER_S = 1.0e9
# worst-case engine-loop stall one capped offload operation may cost
STALL_BUDGET_S = 0.25


def probe_link_bandwidth(
    nbytes: int = 8 << 20, trials: int = 3
) -> Optional[float]:
    """Measured host->device->host round-trip bandwidth in bytes/second
    (best of ``trials``), or None when the device runtime refuses the probe.
    Uses the same transfer primitives the offload connector pays for
    (device_put upload, np.asarray fetch), so the number reflects what a
    spill/restore batch would actually see.

    Staged so a SLOW link never pays a big probe: a ~1 MB pilot decides
    first — on a clearly-slow link (the very case the cap exists for) the
    pilot's estimate already settles the cap decision and the full-size
    trials are skipped, keeping the startup stall ~milliseconds instead of
    seconds; only fast links (where the transfer is cheap anyway) run the
    larger trials for an accurate number."""
    try:
        import jax
        import numpy as np

        def round_trip(buf) -> float:
            t0 = time.perf_counter()
            dev = jax.device_put(buf)
            dev.block_until_ready()
            np.asarray(dev)  # device -> host leg
            dt = time.perf_counter() - t0
            return 2 * buf.nbytes / dt if dt > 0 else 0.0

        pilot_bytes = min(nbytes, 1 << 20)
        pilot = np.zeros(pilot_bytes, np.uint8)
        warm = jax.device_put(pilot)
        warm.block_until_ready()  # absorb transfer-path setup
        np.asarray(warm)
        pilot_bw = max(round_trip(pilot), round_trip(pilot))
        if not pilot_bw:
            return None
        if pilot_bw < FAST_LINK_BYTES_PER_S / 8:
            return pilot_bw  # unambiguously slow: decision already made
        host = np.zeros(nbytes, np.uint8)
        best = max(round_trip(host) for _ in range(trials))
        return max(best, pilot_bw) or None
    except Exception as e:  # noqa: BLE001 - probe must never kill startup
        logger.warning("link-bandwidth probe failed (%s); cap stays unbounded", e)
        return None


def derive_max_io_pages(
    bandwidth_bytes_per_s: Optional[float],
    page_bytes: int,
    *,
    stall_budget_s: float = STALL_BUDGET_S,
    fast_link_bytes_per_s: float = FAST_LINK_BYTES_PER_S,
) -> int:
    """Offload I/O page cap for a measured link bandwidth.

    - unknown bandwidth (failed probe) or PCIe-class links -> 0 (unbounded);
    - slow links -> the page count one ``stall_budget_s`` stall can move, at
      least 1 so chain heads stay restorable.
    """
    if not bandwidth_bytes_per_s or bandwidth_bytes_per_s >= fast_link_bytes_per_s:
        return 0
    return max(1, int(bandwidth_bytes_per_s * stall_budget_s / max(page_bytes, 1)))
