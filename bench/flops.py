"""What the chip can do, and the counting that every architecture shares;
each architecture counts its own model's work from shapes in
``bench/arch/<arch>.py``.

Operations count a multiply and an add as two. Bytes are the least the
work has to move through HBM: the weights once per step, and for an
attention call the live tokens' keys and values, the queries and the
output. Nothing here is measured; the per-layer readers divide these
counts by times from the device trace.
"""
from __future__ import annotations

from typing import Dict

# Published peaks of one chip, keyed by JAX's ``device_kind``. Source:
# Google Cloud documentation, "TPU v5e" (197 TFLOP/s bf16, 819 GB/s HBM,
# 16 GB HBM).
PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9},
}

BF16 = 2


def peaks(device_kind: str) -> Dict[str, float]:
    """The chip's peaks; an unknown device is an error, not a default."""
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind {device_kind!r}"
                       f"; known: {sorted(PEAKS)}")
    return PEAKS[device_kind]


def attention_flops(sz: Dict, queries_by_keys: int) -> int:
    """Scores and the weighted sum for one layer: 2·2·H·h per query-key
    pair."""
    return 4 * sz["num_heads"] * sz["head_dim"] * queries_by_keys


def roofline_seconds(work: Dict[str, int], peak: Dict) -> float:
    """The least time the chip could take for ``work`` (operations and
    bytes): the larger of the two bounds."""
    return max(work["flops"] / peak["bf16_flops"],
               work["bytes"] / peak["hbm_bytes_per_s"])
