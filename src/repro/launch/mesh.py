"""Production mesh construction (deliverable e).

A FUNCTION, not a module-level constant: importing this module never touches
jax device state (jax locks the device count on first backend init, and the
dry-run needs the XLA_FLAGS host-device override to land first)."""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False, attn_pool: int = 0):
    """Single pod: (data=16, model=16) = 256 chips (TPU v5e target).
    Multi-pod: (pod=2, data=16, model=16) = 512 chips; the `pod` axis rides
    the DCN and carries data parallelism.

    attn_pool > 0 carves an ATTENTION-POOL axis `attn` of that many chips
    out of the model dimension (model axis shrinks to 16 // attn_pool): the
    memory devices of the paper's disaggregation. The paged KV pool's block
    axis is sharded over `attn` — `block_parallel_paged_decode_attention`
    round-robins one sequence's blocks across it, so a single `long_500k`
    request's KV spans every pool chip; head-/request-level partitions use
    the same axis. Requires 16 % attn_pool == 0."""
    if attn_pool:
        if 16 % attn_pool:
            raise ValueError(f"attn_pool ({attn_pool}) must divide 16")
        shape = ((2, 16, 16 // attn_pool, attn_pool) if multi_pod
                 else (16, 16 // attn_pool, attn_pool))
        axes = (("pod", "data", "model", "attn") if multi_pod
                else ("data", "model", "attn"))
    else:
        shape = (2, 16, 16) if multi_pod else (16, 16)
        axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_test_mesh(shape=(2, 4), axes=("data", "model")):
    """Small mesh for CPU tests (requires host-device override)."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(shape))


def make_test_attn_pool_mesh(n_pool: int = 4, model: int = 2):
    """CPU-test rendering of the disaggregated mesh: a `model` axis for the
    dense slices and an `attn` pool axis the paged KV blocks shard over."""
    return make_test_mesh((model, n_pool), ("model", "attn"))
