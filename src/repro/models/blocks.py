"""Transformer blocks for every architecture family.

Each block is a function ``(params, cfg, x, ...) -> (x, extras)`` operating on
one layer's (un-stacked) parameters. Stacking/scanning over layers lives in
``transformer.py``. ``mode`` is STATIC: "train" | "prefill" | "decode".
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models import ssm
from repro.models.attention import (attention_decode_step,
                                    attention_decode_step_paged,
                                    attention_forward, blockwise_attention,
                                    init_attention, out_project, qkv_project)
from repro.models.common import ModelConfig, rms_norm
from repro.models.ffn import ffn_forward, init_ffn
from repro.models.moe import init_moe, moe_forward


# ---------------------------------------------------------------------------
# Dense (llama/glm/tinyllama/pixtral/gemma2) + MoE blocks
# ---------------------------------------------------------------------------
def init_dense_block(key, cfg: ModelConfig, use_moe: bool = False) -> Dict:
    k1, k2 = jax.random.split(key)
    p = {
        "norm1": jnp.zeros((cfg.d_model,), cfg.dtype),
        "norm2": jnp.zeros((cfg.d_model,), cfg.dtype),
        "attn": init_attention(k1, cfg),
    }
    if use_moe:
        p["moe"] = init_moe(k2, cfg)
    else:
        p["ffn"] = init_ffn(k2, cfg)
    if cfg.post_norms:
        p["norm_post_attn"] = jnp.zeros((cfg.d_model,), cfg.dtype)
        p["norm_post_ffn"] = jnp.zeros((cfg.d_model,), cfg.dtype)
    return p


def dense_block(params: Dict, cfg: ModelConfig, x: jax.Array, *,
                mode: str, positions: Optional[jax.Array] = None,
                cache: Optional[Dict] = None, is_local: bool = False,
                backend: str = "jnp", moe_group_size: int = 256,
                prefix_kv: Optional[Tuple[jax.Array, jax.Array]] = None,
                paged_prefix: Optional[Tuple[jax.Array, jax.Array,
                                             jax.Array]] = None,
                paged_prefix_scales: Optional[Tuple[jax.Array,
                                                    jax.Array]] = None
                ) -> Tuple[jax.Array, Dict, jax.Array]:
    """Returns (x, new_cache_entries, aux_loss). ``prefix_kv`` (prefill
    only): this layer's head-major (B, Hkv, P, hd) K/V of an already-cached
    prompt prefix; ``paged_prefix`` the paged form — this layer's
    (k_pool, v_pool, block_table) read in place (chunked prefill) — see
    ``attention_forward``."""
    h = rms_norm(x, params["norm1"], cfg.norm_eps)
    new_cache: Dict = {}
    if mode == "decode":
        if "k_pool" in cache:  # paged: attend over the block pool in place
            attn, k_new, v_new = attention_decode_step_paged(
                params["attn"], cfg, h, cache["k_pool"], cache["v_pool"],
                cache["block_tables"], cache["len"],
                is_local=is_local, backend=backend,
                k_scale=cache.get("k_scale_pool"),
                v_scale=cache.get("v_scale_pool"))
        else:
            attn, k_new, v_new = attention_decode_step(
                params["attn"], cfg, h, cache["k"], cache["v"], cache["len"],
                is_local=is_local, backend=backend,
                k_scale=cache.get("k_scale"), v_scale=cache.get("v_scale"))
        new_cache = {"k_new": k_new, "v_new": v_new}
    else:
        attn, k, v = attention_forward(params["attn"], cfg, h, positions,
                                       is_local=is_local, prefix_kv=prefix_kv,
                                       paged_prefix=paged_prefix,
                                       paged_prefix_scales=paged_prefix_scales,
                                       backend=backend)
        if mode == "prefill":
            new_cache = {"k": k, "v": v}
    if cfg.post_norms:
        attn = rms_norm(attn, params["norm_post_attn"], cfg.norm_eps)
    x = x + attn

    h = rms_norm(x, params["norm2"], cfg.norm_eps)
    aux = jnp.zeros((), jnp.float32)
    if "moe" in params:
        f, aux = moe_forward(params["moe"], cfg, h, group_size=moe_group_size)
    else:
        f = ffn_forward(params["ffn"], h)
    if cfg.post_norms:
        f = rms_norm(f, params["norm_post_ffn"], cfg.norm_eps)
    return x + f, new_cache, aux


# ---------------------------------------------------------------------------
# RWKV6 block
# ---------------------------------------------------------------------------
def init_rwkv_block(key, cfg: ModelConfig) -> Dict:
    k1, k2 = jax.random.split(key)
    return {
        "norm1": jnp.zeros((cfg.d_model,), cfg.dtype),
        "norm2": jnp.zeros((cfg.d_model,), cfg.dtype),
        "tmix": ssm.init_rwkv_time_mix(k1, cfg),
        "cmix": ssm.init_rwkv_channel_mix(k2, cfg),
    }


def rwkv_block(params: Dict, cfg: ModelConfig, x: jax.Array, *, mode: str,
               state: Optional[Dict] = None) -> Tuple[jax.Array, Dict]:
    h = rms_norm(x, params["norm1"], cfg.norm_eps)
    if mode == "decode":
        tm, tstate = ssm.rwkv_time_mix_decode(params["tmix"], cfg, h, state)
    else:
        tm = ssm.rwkv_time_mix_forward(params["tmix"], cfg, h)
        tstate = {"x_tm": h[:, -1]}
        if mode == "prefill":
            # reconstruct final recurrence state for decoding
            tstate = _rwkv_final_state(params["tmix"], cfg, h)
    x = x + tm
    h = rms_norm(x, params["norm2"], cfg.norm_eps)
    if mode == "decode":
        cm, _ = ssm.rwkv_channel_mix_forward(params["cmix"], cfg, h,
                                             state["x_cm"])
    else:
        cm, _ = ssm.rwkv_channel_mix_forward(
            params["cmix"], cfg, h, jnp.zeros_like(h[:, 0]))
    new_state = dict(tstate)
    new_state["x_cm"] = h[:, -1]
    return x + cm, new_state


def _rwkv_final_state(tmix: Dict, cfg: ModelConfig, h: jax.Array) -> Dict:
    """Run the recurrence once more to extract S after the whole prefix."""
    H, P = ssm.rwkv_dims(cfg)
    B_, S, d = h.shape
    x_prev = ssm._token_shift(h, jnp.zeros((B_, d), h.dtype))
    r, k, v, g, w = ssm._rwkv_rkvwg(tmix, cfg, h, x_prev)

    def step(S_h, inp):
        k_t, v_t, w_t = [a.astype(jnp.float32) for a in inp]
        kv = k_t[..., :, None] * v_t[..., None, :]
        return w_t[..., :, None] * S_h + kv, None

    S0 = jnp.zeros((B_, H, P, P), jnp.float32)
    xs = tuple(a.transpose(1, 0, 2, 3) for a in (k, v, w))
    S_fin, _ = jax.lax.scan(step, S0, xs)
    return {"S": S_fin, "x_tm": h[:, -1]}


# ---------------------------------------------------------------------------
# Mamba2 block (zamba2 hybrid)
# ---------------------------------------------------------------------------
def init_mamba_block(key, cfg: ModelConfig) -> Dict:
    return {
        "norm": jnp.zeros((cfg.d_model,), cfg.dtype),
        "mamba": ssm.init_mamba(key, cfg),
    }


def mamba_block(params: Dict, cfg: ModelConfig, x: jax.Array, *, mode: str,
                state: Optional[Dict] = None) -> Tuple[jax.Array, Dict]:
    h = rms_norm(x, params["norm"], cfg.norm_eps)
    if mode == "decode":
        y, new_state = ssm.mamba_decode_step(params["mamba"], cfg, h, state)
    else:
        y = ssm.mamba_forward(params["mamba"], cfg, h)
        new_state = {}
        if mode == "prefill":
            new_state = _mamba_final_state(params["mamba"], cfg, h)
    return x + y, new_state


def _mamba_final_state(mp: Dict, cfg: ModelConfig, h: jax.Array) -> Dict:
    d_inner, H, P, N = ssm.mamba_dims(cfg)
    B_, S, _ = h.shape
    z, xh, Bm, Cm, dt, conv_state = ssm._mamba_project(mp, cfg, h)
    decay = jnp.exp(-jnp.exp(mp["a_log"]) * dt)
    xdt = xh.astype(jnp.float32) * dt[..., None]

    def step(hs, inp):
        xdt_t, B_t, C_t, decay_t = inp
        hs = hs * decay_t[:, :, None, None] + \
            xdt_t[..., None] * B_t[:, :, None, :]
        return hs, None

    h0 = jnp.zeros((B_, H, P, N), jnp.float32)
    xs = (xdt.transpose(1, 0, 2, 3),
          ssm._per_head(Bm.astype(jnp.float32), H).transpose(1, 0, 2, 3),
          ssm._per_head(Cm.astype(jnp.float32), H).transpose(1, 0, 2, 3),
          decay.transpose(1, 0, 2))
    h_fin, _ = jax.lax.scan(step, h0, xs)
    # conv state: last K-1 *pre-activation* conv inputs
    return {"h": h_fin, "conv": conv_state}


# ---------------------------------------------------------------------------
# Encoder block (bidirectional) + decoder block w/ cross-attention (seamless)
# ---------------------------------------------------------------------------
def init_encoder_block(key, cfg: ModelConfig) -> Dict:
    k1, k2 = jax.random.split(key)
    return {
        "norm1": jnp.zeros((cfg.d_model,), cfg.dtype),
        "norm2": jnp.zeros((cfg.d_model,), cfg.dtype),
        "attn": init_attention(k1, cfg),
        "ffn": init_ffn(k2, cfg),
    }


def encoder_block(params: Dict, cfg: ModelConfig, x: jax.Array,
                  positions: jax.Array) -> jax.Array:
    h = rms_norm(x, params["norm1"], cfg.norm_eps)
    q, k, v = qkv_project(params["attn"], cfg, h, positions)
    out = blockwise_attention(q, k, v, causal=False,
                              q_positions=positions,
                              block_size=max(512, x.shape[1] // 8)
                              if cfg.lower_unrolled else 512,
                              unroll=cfg.lower_unrolled)
    x = x + out_project(params["attn"], out)
    h = rms_norm(x, params["norm2"], cfg.norm_eps)
    return x + ffn_forward(params["ffn"], h)


def init_decoder_block(key, cfg: ModelConfig) -> Dict:
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "norm1": jnp.zeros((cfg.d_model,), cfg.dtype),
        "norm2": jnp.zeros((cfg.d_model,), cfg.dtype),
        "norm3": jnp.zeros((cfg.d_model,), cfg.dtype),
        "attn": init_attention(k1, cfg),
        "cross": init_attention(k2, cfg),
        "ffn": init_ffn(k3, cfg),
    }


def decoder_block(params: Dict, cfg: ModelConfig, x: jax.Array,
                  enc_kv: Tuple[jax.Array, jax.Array], *, mode: str,
                  positions: Optional[jax.Array] = None,
                  cache: Optional[Dict] = None,
                  backend: str = "jnp") -> Tuple[jax.Array, Dict]:
    """enc_kv: precomputed (k, v) of the encoder output for this layer."""
    # self attention
    h = rms_norm(x, params["norm1"], cfg.norm_eps)
    new_cache: Dict = {}
    if mode == "decode":
        attn, k_new, v_new = attention_decode_step(
            params["attn"], cfg, h, cache["k"], cache["v"], cache["len"],
            backend=backend)
        new_cache = {"k_new": k_new, "v_new": v_new}
    else:
        attn, k, v = attention_forward(params["attn"], cfg, h, positions)
        if mode == "prefill":
            new_cache = {"k": k, "v": v}
    x = x + attn
    # cross attention (encoder K/V are fixed — computed once per request)
    h = rms_norm(x, params["norm2"], cfg.norm_eps)
    q = jnp.einsum("bsd,dhk->bshk", h, params["cross"]["wq"])
    ek, ev = enc_kv
    if mode == "decode":
        # enc_kv arrives HEAD-MAJOR (B, Hkv, S_enc, hd) from the cache; the
        # single-token cross attention uses the decode partial path directly
        from repro.core import combine as Comb
        from repro.models.attention import decode_attention_partial_jnp
        B = q.shape[0]
        full = jnp.full((B,), ek.shape[2], jnp.int32)
        part = decode_attention_partial_jnp(q[:, 0], ek, ev, full)
        out = Comb.finalize(part).astype(q.dtype)[:, None]
    else:
        out = blockwise_attention(q, ek, ev, causal=False,
                                  block_size=max(512, ek.shape[1] // 8)
                                  if cfg.lower_unrolled else 512,
                                  unroll=cfg.lower_unrolled)
    x = x + out_project(params["cross"], out)
    # ffn
    h = rms_norm(x, params["norm3"], cfg.norm_eps)
    return x + ffn_forward(params["ffn"], h), new_cache


def encoder_cross_kv(params: Dict, cfg: ModelConfig,
                     enc_out: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Project encoder output into this decoder layer's cross K/V."""
    k = jnp.einsum("bsd,dhk->bshk", enc_out, params["cross"]["wk"])
    v = jnp.einsum("bsd,dhk->bshk", enc_out, params["cross"]["wv"])
    return k, v
