"""Kernel-level microbenchmarks: Pallas (interpret) vs jnp-oracle wall time
at CPU scale + the analytic VMEM working set per BlockSpec tile (the
quantity that matters on real TPU)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.common import time_call
from repro.kernels import ref


def run():
    rows = []
    key = jax.random.PRNGKey(0)
    # decode attention
    B, S, Hkv, G, hd, block_k = 4, 1024, 2, 4, 128, 512
    q = jax.random.normal(key, (B, Hkv * G, hd), jnp.float32)
    kc = jax.random.normal(key, (B, Hkv, S, hd), jnp.float32)
    vc = jax.random.normal(key, (B, Hkv, S, hd), jnp.float32)
    clen = jnp.full((B,), S, jnp.int32)
    t_ref = time_call(
        lambda: ref.decode_attention_ref(q.reshape(B, Hkv, G, hd), kc, vc,
                                         clen))
    vmem_kib = (2 * block_k * hd * 2 + G * hd * 4 + 2 * G * 128 * 4) / 1024
    rows.append({"name": "kernel_decode_attn_ref",
                 "us_per_call": round(t_ref * 1e6, 1),
                 "derived": f"S={S};vmem_per_tile_kib={vmem_kib:.0f}"})
    # int8 paged decode with fused dequant: the kernel streams 1-byte K/V
    # tiles + one fp32 scale per token-head and folds the scales into the
    # score/PV products — per-tile VMEM drops to ~half the bf16 tile
    from repro.models import kv_quant
    bs_blk = 128
    nb = S // bs_blk
    kq, ks = kv_quant.quantize_kv(kc)
    vq, vs = kv_quant.quantize_kv(vc)
    k_pool = jnp.swapaxes(kq, 0, 1).reshape(Hkv, B * nb, bs_blk, hd)
    v_pool = jnp.swapaxes(vq, 0, 1).reshape(Hkv, B * nb, bs_blk, hd)
    ks_pool = jnp.swapaxes(ks, 0, 1).reshape(Hkv, B * nb, 1, bs_blk)
    vs_pool = jnp.swapaxes(vs, 0, 1).reshape(Hkv, B * nb, 1, bs_blk)
    bt = jnp.arange(B * nb, dtype=jnp.int32).reshape(B, nb)
    t_int8 = time_call(
        lambda: ref.paged_decode_attention_int8_ref(
            q.reshape(B, Hkv, G, hd), k_pool, v_pool, ks_pool, vs_pool,
            bt, clen))
    vmem8_kib = (2 * bs_blk * hd * 1 + 2 * bs_blk * 4 + G * hd * 4 +
                 2 * G * 128 * 4) / 1024
    vmem16_kib = (2 * bs_blk * hd * 2 + G * hd * 4 + 2 * G * 128 * 4) / 1024
    rows.append({"name": "kernel_decode_attn_int8_ref",
                 "us_per_call": round(t_int8 * 1e6, 1),
                 "derived": (f"S={S};block={bs_blk};"
                             f"vmem_per_tile_kib={vmem8_kib:.0f};"
                             f"bf16_tile_kib={vmem16_kib:.0f}")})
    # rwkv6
    Bs, Ss, H, P = 2, 256, 4, 64
    r = jax.random.normal(key, (Bs, Ss, H, P)) * 0.5
    k2 = jax.random.normal(key, (Bs, Ss, H, P)) * 0.5
    v2 = jax.random.normal(key, (Bs, Ss, H, P)) * 0.5
    w2 = jax.nn.sigmoid(jax.random.normal(key, (Bs, Ss, H, P))) * 0.5 + 0.5
    u2 = jax.random.normal(key, (H, P)) * 0.3
    t_ref = time_call(lambda: ref.rwkv6_scan_ref(r, k2, v2, w2, u2))
    rows.append({"name": "kernel_rwkv6_ref",
                 "us_per_call": round(t_ref * 1e6, 1),
                 "derived": f"state_vmem_kib={P*P*4/1024:.0f}"})
    # ssm
    N = 64
    x = jax.random.normal(key, (Bs, Ss, H, P)) * 0.5
    Bi = jax.random.normal(key, (Bs, Ss, N)) * 0.5
    Ci = jax.random.normal(key, (Bs, Ss, N)) * 0.5
    a = jax.nn.sigmoid(jax.random.normal(key, (Bs, Ss, H))) * 0.5 + 0.4
    t_ref = time_call(lambda: ref.ssm_scan_ref(x, None, Bi, Ci, a))
    rows.append({"name": "kernel_ssm_ref",
                 "us_per_call": round(t_ref * 1e6, 1),
                 "derived": f"state_vmem_kib={H*P*N*4/1024:.0f}"})
    return rows
