"""What the algorithm needs, counted from shapes, and what the chip can do.

Operations count a multiply and an add as two. Bytes are the least the
work has to move through HBM: the weights once per step, and for an
attention call the live tokens' keys and values, the queries and the
output. Nothing here is measured; the per-layer readers divide these by
times from the device trace.
"""
from __future__ import annotations

from typing import Dict, Sequence

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


def layer_params(sz: Dict) -> int:
    """Weights of one decoder layer that take part in a matrix product."""
    d, h, F = sz["d_model"], sz["head_dim"], sz["d_ff"]
    q, kv = sz["num_heads"] * h, sz["num_kv_heads"] * h
    return d * (q + 2 * kv) + q * d + 3 * d * F


def attention_flops(sz: Dict, queries_by_keys: int) -> int:
    """Scores and the weighted sum for one layer: 2·2·H·h per query-key
    pair."""
    return 4 * sz["num_heads"] * sz["head_dim"] * queries_by_keys


def decode_attention(sz: Dict, lens: Sequence[int]) -> Dict[str, int]:
    """One layer of the paged decode kernel over a batch whose rows hold
    ``lens`` stored tokens each (the new token is merged outside it)."""
    h, K, H = sz["head_dim"], sz["num_kv_heads"], sz["num_heads"]
    live = int(sum(lens))
    return {"flops": attention_flops(sz, live),
            "bytes": 2 * K * h * BF16 * live + 2 * len(lens) * H * h * BF16}


def chunk_attention(sz: Dict, prefix: int, chunk: int) -> Dict[str, int]:
    """One layer of the paged prefill-chunk kernel: ``chunk`` queries over
    ``prefix`` pooled tokens and the chunk itself, causal inside it."""
    h, K, H = sz["head_dim"], sz["num_kv_heads"], sz["num_heads"]
    pairs = chunk * prefix + chunk * (chunk + 1) // 2
    return {"flops": attention_flops(sz, pairs),
            "bytes": 2 * K * h * BF16 * (prefix + chunk)
            + 2 * chunk * H * h * BF16}


def decode_step_flops(sz: Dict, lens: Sequence[int]) -> int:
    """Model operations of one decode step: each row's token through every
    layer and the output head, attending to its stored tokens and itself."""
    L, d, V = sz["num_layers"], sz["d_model"], sz["vocab"]
    rows = len(lens)
    dense = 2 * rows * (L * layer_params(sz) + d * V)
    return dense + L * attention_flops(sz, int(sum(lens)) + rows)


def chunk_flops(sz: Dict, prefix: int, chunk: int) -> int:
    """Model operations of one prefill chunk; the output head runs on its
    last position only."""
    L, d, V = sz["num_layers"], sz["d_model"], sz["vocab"]
    pairs = chunk * prefix + chunk * (chunk + 1) // 2
    return (2 * chunk * L * layer_params(sz) + 2 * d * V
            + L * attention_flops(sz, pairs))


def roofline_seconds(work: Dict[str, int], peak: Dict) -> float:
    """The least time the chip could take for ``work`` (operations and
    bytes): the larger of the two bounds."""
    return max(work["flops"] / peak["bf16_flops"],
               work["bytes"] / peak["hbm_bytes_per_s"])
