"""Attention-free sequence mixers: Mamba2 (SSD) and RWKV6 (Finch).

Both are implemented as (a) a full-sequence training/prefill path using
``lax.scan`` over time (hot-spot Pallas kernels in ``repro/kernels`` replace
the inner recurrence where perf-critical), and (b) an O(1)-state single-token
decode step. State pytrees are head-sharded over the ``model`` mesh axis and
batch-sharded over ``data``.

Mamba2 follows the scalar-decay SSD formulation (one decay per head);
RWKV6 follows the Finch data-dependent-decay recurrence with token-shift
lerps and LoRA-modulated mixing.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models.common import ModelConfig, dense_init, rms_norm


# ===========================================================================
# Mamba2
# ===========================================================================
def mamba_dims(cfg: ModelConfig):
    P = cfg.ssm_head_dim
    H = cfg.ssm_heads or cfg.ssm_expand * cfg.d_model // P
    return H * P, H, P, cfg.ssm_state


def _conv_channels(cfg: ModelConfig) -> int:
    d_inner, _, _, N = mamba_dims(cfg)
    return d_inner + 2 * cfg.ssm_groups * N


def init_mamba(key, cfg: ModelConfig, dtype=None) -> Dict:
    dtype = dtype or cfg.dtype
    d_inner, H, P, N = mamba_dims(cfg)
    d = cfg.d_model
    ks = jax.random.split(key, 6)
    conv_ch = _conv_channels(cfg)  # conv over x, B, C
    return {
        # in_proj -> [z (d_inner), x (d_inner), B (G·N), C (G·N), dt (H)]
        "w_in": dense_init(ks[0], (d, d_inner + conv_ch + H), dtype),
        "conv_w": dense_init(ks[1], (cfg.ssm_conv, conv_ch), dtype, 0.5),
        "conv_b": jnp.zeros((conv_ch,), dtype),
        "a_log": jnp.zeros((H,), jnp.float32),
        "dt_bias": jnp.zeros((H,), jnp.float32),
        "d_skip": jnp.ones((H,), jnp.float32),
        "out_norm": jnp.zeros((d_inner,), dtype),
        "w_out": dense_init(ks[2], (d_inner, d), dtype),
    }


def _mamba_project(params, cfg, x, conv_state=None, length=None):
    """Shared pre-recurrence math. x: (B, S, d).

    Returns (z, xh, Bm, Cm, dt, new_conv_state) with z, xh: (B, S, H, P);
    Bm, Cm: (B, S, G, N); dt: (B, S, H) fp32. ``conv_state`` (B, K-1,
    conv_ch) holds the pre-activation conv inputs of the K-1 tokens before
    x (None: the sequence starts here); the new one holds those of x's
    last K-1 tokens, or of its first ``length`` tokens' last K-1 where x
    is padded past ``length`` (a traced count).
    """
    d_inner, H, P, N = mamba_dims(cfg)
    GN = cfg.ssm_groups * N
    proj = jnp.einsum("bsd,de->bse", x, params["w_in"])
    z, conv_in, dt = jnp.split(proj, [d_inner, 2 * d_inner + 2 * GN],
                               axis=-1)
    # causal depthwise conv over (x, B, C)
    K = cfg.ssm_conv
    if conv_state is None:  # the sequence starts here: pad left
        conv_state = jnp.zeros(conv_in.shape[:1] + (K - 1,)
                               + conv_in.shape[2:], conv_in.dtype)
    padded = jnp.concatenate([conv_state.astype(conv_in.dtype), conv_in],
                             axis=1)
    if length is None:
        new_conv_state = padded[:, -(K - 1):, :]
    else:
        new_conv_state = jax.lax.dynamic_slice_in_dim(padded, length, K - 1,
                                                      axis=1)
    conv = sum(padded[:, i:i + conv_in.shape[1], :] * params["conv_w"][i]
               for i in range(K)) + params["conv_b"]
    conv = jax.nn.silu(conv.astype(jnp.float32)).astype(x.dtype)
    xr, Bm, Cm = jnp.split(conv, [d_inner, d_inner + GN], axis=-1)
    B_, S = x.shape[0], x.shape[1]
    xh = xr.reshape(B_, S, H, P)
    z = z.reshape(B_, S, H, P)
    Bm = Bm.reshape(B_, S, cfg.ssm_groups, N)
    Cm = Cm.reshape(B_, S, cfg.ssm_groups, N)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + params["dt_bias"])
    return z, xh, Bm, Cm, dt, new_conv_state


def _per_head(m: jax.Array, H: int) -> jax.Array:
    """(…, G, N) group rows -> (…, H, N), head h reading group h·G/H."""
    return jnp.repeat(m, H // m.shape[-2], axis=-2)


def _mamba_finish(params, cfg, y, z, B_, S):
    """Gate, then RMSNorm over each group's d_inner/G channels (Mamba-2's
    RMSNormGated with the norm after the gate), then the out projection."""
    d_inner, H, P, N = mamba_dims(cfg)
    G = cfg.ssm_groups
    y = y * jax.nn.silu(z.astype(jnp.float32)).astype(y.dtype)
    y = y.reshape(B_, S, G, d_inner // G)
    y = rms_norm(y, params["out_norm"].reshape(G, d_inner // G), cfg.norm_eps)
    return jnp.einsum("bse,ed->bsd", y.reshape(B_, S, d_inner),
                      params["w_out"])


def _skip(params, y, xh):
    return y + xh.astype(jnp.float32) * params["d_skip"][None, None, :, None]


def mamba_forward(params: Dict, cfg: ModelConfig, x: jax.Array) -> jax.Array:
    """Full-sequence Mamba2 mixer. x: (B, S, d) -> (B, S, d)."""
    B_, S, _ = x.shape
    d_inner, H, P, N = mamba_dims(cfg)
    z, xh, Bm, Cm, dt, _ = _mamba_project(params, cfg, x)
    decay = jnp.exp(-jnp.exp(params["a_log"]) * dt)  # (B, S, H)
    xdt = xh.astype(jnp.float32) * dt[..., None]  # (B, S, H, P)

    if cfg.use_pallas_kernels:
        from repro.kernels import ops as kops
        y = kops.ssm_scan(xdt, Bm.astype(jnp.float32),
                          Cm.astype(jnp.float32), decay)
        y = _skip(params, y, xh)
        return _mamba_finish(params, cfg, y.astype(x.dtype), z, B_, S)

    def step(h, inp):
        xdt_t, B_t, C_t, decay_t = inp
        # h: (B, H, P, N)
        h = h * decay_t[:, :, None, None] + \
            xdt_t[..., None] * B_t[:, :, None, :]
        y = jnp.einsum("bhpn,bhn->bhp", h, C_t)
        return h, y

    h0 = jnp.zeros((B_, H, P, N), jnp.float32)
    xs = (xdt.transpose(1, 0, 2, 3),
          _per_head(Bm.astype(jnp.float32), H).transpose(1, 0, 2, 3),
          _per_head(Cm.astype(jnp.float32), H).transpose(1, 0, 2, 3),
          decay.transpose(1, 0, 2))
    _, ys = jax.lax.scan(step, h0, xs)  # ys: (S, B, H, P)
    y = _skip(params, ys.transpose(1, 0, 2, 3), xh)
    return _mamba_finish(params, cfg, y.astype(x.dtype), z, B_, S)


def init_mamba_state(cfg: ModelConfig, batch: int) -> Dict:
    d_inner, H, P, N = mamba_dims(cfg)
    return {
        "h": jnp.zeros((batch, H, P, N), jnp.float32),
        "conv": jnp.zeros((batch, cfg.ssm_conv - 1, _conv_channels(cfg)),
                          cfg.dtype),
    }


def mamba_decode_step(params: Dict, cfg: ModelConfig, x: jax.Array,
                      state: Dict) -> Tuple[jax.Array, Dict]:
    """x: (B, 1, d) -> (y (B,1,d), new_state)."""
    B_, S, _ = x.shape
    d_inner, H, P, N = mamba_dims(cfg)
    z, xh, Bm, Cm, dt, conv_state = _mamba_project(
        params, cfg, x, conv_state=state["conv"])
    decay = jnp.exp(-jnp.exp(params["a_log"]) * dt)  # (B, 1, H)
    h = state["h"] * decay[:, 0, :, None, None] + \
        (xh.astype(jnp.float32) * dt[..., None])[:, 0, ..., None] * \
        _per_head(Bm.astype(jnp.float32), H)[:, 0, :, None, :]
    y = jnp.einsum("bhpn,bhn->bhp", h,
                   _per_head(Cm.astype(jnp.float32), H)[:, 0])
    y = _skip(params, y[:, None], xh)
    out = _mamba_finish(params, cfg, y.astype(x.dtype), z, B_, S)
    return out, {"h": h, "conv": conv_state}


# ---------------------------------------------------------------------------
# Mamba2 over state slots (the serving engine's prefill chunks and decode)
# ---------------------------------------------------------------------------
def state_slot_shapes(cfg: ModelConfig, n_layers: int, n_slots: int
                      ) -> Dict[str, Tuple[Tuple[int, ...], Any]]:
    """Shapes and dtypes of the state of ``n_layers`` Mamba layers for
    ``n_slots`` requests: the SSM state in the kernels' layout
    (``kernels/ssm_scan.pair_state``), kept in fp32, and the K-1
    pre-activation conv inputs, in the model's dtype."""
    from repro.kernels.ssm_scan import heads_per_row

    d_inner, H, P, N = mamba_dims(cfg)
    r = heads_per_row(H // cfg.ssm_groups, P)
    return {"ssm": ((n_layers, n_slots, H // r, N, r * P), jnp.float32),
            "conv": ((n_layers, n_slots, cfg.ssm_conv - 1,
                      _conv_channels(cfg)), cfg.dtype)}


def mamba_chunk(params: Dict, cfg: ModelConfig, x: jax.Array,
                ssm0: Optional[jax.Array], conv0: Optional[jax.Array],
                length: jax.Array) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """One prefill chunk of one request from its state. x: (1, C, d)
    normed input whose first ``length`` tokens (a traced count) are the
    chunk's and the rest padding; ssm0: (H/r, N, rP) fp32 and conv0:
    (K-1, conv_ch), or None where the request starts here. Returns (y
    (1, C, d), ssm1, conv1): the chunk's output and the state after its
    last token. A padded token takes no step (dt = 0: no decay, no
    input), so the state is the real tokens' alone."""
    from repro.kernels import ops as kops

    B_, S, _ = x.shape
    z, xh, Bm, Cm, dt, conv1 = _mamba_project(
        params, cfg, x, None if conv0 is None else conv0[None], length)
    dt = jnp.where((jnp.arange(S) < length)[None, :, None], dt, 0.0)
    log_decay = -jnp.exp(params["a_log"]) * dt          # (1, C, H)
    xdt = xh.astype(jnp.float32) * dt[..., None]
    y, ssm1 = kops.ssm_chunk_scan(xdt, log_decay, Bm, Cm,
                                  None if ssm0 is None else ssm0[None])
    y = _skip(params, y, xh)
    return (_mamba_finish(params, cfg, y.astype(x.dtype), z, B_, S),
            ssm1[0], conv1[0])


def mamba_decode_slots(params: Dict, cfg: ModelConfig, x: jax.Array,
                       state: Dict, slots: jax.Array, layer: int
                       ) -> Tuple[jax.Array, Dict]:
    """One decode token per row, row b's state in slot ``slots[b]`` of
    Mamba layer ``layer`` of ``state`` ({"ssm", "conv"}, as
    ``state_slot_shapes``). x: (B, 1, d) normed input. The SSM state is
    updated in place by ``ssm_decode_update``; returns (y (B, 1, d), the
    new state)."""
    from repro.kernels import ops as kops

    B_, S, _ = x.shape
    conv = state["conv"]
    z, xh, Bm, Cm, dt, conv1 = _mamba_project(
        params, cfg, x, conv[layer, slots])
    decay = jnp.exp(-jnp.exp(params["a_log"]) * dt[:, 0])      # (B, H)
    xdt = xh[:, 0].astype(jnp.float32) * dt[:, 0, :, None]
    y, ssm = kops.ssm_decode_update(state["ssm"], slots, xdt, decay,
                                    Bm[:, 0], Cm[:, 0], layer=layer)
    y = _skip(params, y[:, None], xh)
    out = _mamba_finish(params, cfg, y.astype(x.dtype), z, B_, S)
    return out, {"ssm": ssm, "conv": conv.at[layer, slots].set(conv1)}


# ===========================================================================
# RWKV6 (Finch)
# ===========================================================================
def rwkv_dims(cfg: ModelConfig):
    P = cfg.rwkv_head_dim
    H = cfg.d_model // P
    return H, P


_RWKV_MIX = ("r", "k", "v", "w", "g")


def init_rwkv_time_mix(key, cfg: ModelConfig, dtype=None) -> Dict:
    """Time-mix params. The five ddlerp loras and the four r/k/v/g
    projections are stored FUSED ((5, d, l) / (4, d, d)) so the stacked
    einsums in _rwkv_rkvwg touch the residual once and need no runtime
    restacking of differently-sharded weights (§Perf #5)."""
    dtype = dtype or cfg.dtype
    H, P = rwkv_dims(cfg)
    d = cfg.d_model
    lora = max(32, d // 64)
    ks = jax.random.split(key, 16)
    p: Dict = {}
    p["mu"] = jnp.zeros((len(_RWKV_MIX), d), dtype)
    p["lora_a"] = dense_init(ks[0], (len(_RWKV_MIX), d, lora), dtype, 0.1)
    p["lora_b"] = dense_init(ks[1], (len(_RWKV_MIX), lora, d), dtype, 0.1)
    p["w_rkvg"] = dense_init(ks[2], (4, d, d), dtype)
    p["w_o"] = dense_init(ks[14], (d, d), dtype)
    p["decay_base"] = jnp.full((d,), -6.0, jnp.float32)
    # small positive bonus so first-token wkv output is non-degenerate
    # (u=0 makes step-0 output exactly 0 -> rms_norm amplifies by 1/sqrt(eps))
    p["bonus_u"] = jnp.full((H, P), 0.5, jnp.float32)
    p["ln_x"] = jnp.zeros((d,), dtype)
    return p


def init_rwkv_channel_mix(key, cfg: ModelConfig, dtype=None) -> Dict:
    dtype = dtype or cfg.dtype
    d = cfg.d_model
    ks = jax.random.split(key, 3)
    return {
        "mu_k": jnp.zeros((d,), dtype),
        "mu_r": jnp.zeros((d,), dtype),
        "w_k": dense_init(ks[0], (d, cfg.d_ff), dtype),
        "w_v": dense_init(ks[1], (cfg.d_ff, d), dtype),
        "w_r": dense_init(ks[2], (d, d), dtype),
    }


def _token_shift(x: jax.Array, last: jax.Array) -> jax.Array:
    """x: (B, S, d); last: (B, d) previous token (zeros at start)."""
    return jnp.concatenate([last[:, None, :], x[:, :-1, :]], axis=1)


def _time_mix_inputs(params, x, x_prev):
    """Data-dependent lerps for r/k/v/w/g (RWKV6 ddlerp).

    §Perf #5: the five lora paths are FUSED into stacked einsums — the naive
    per-name loop touched the (B, S, d) residual ten times, which under
    hidden-sharded activations cost ~26 activation all-gathers per layer on
    the production mesh (EXPERIMENTS.md §Perf). One stacked read instead."""
    xx = x_prev - x
    lora = jnp.tanh(jnp.einsum("bsd,xdl->bxsl", xx, params["lora_a"]))
    mix = params["mu"][None, :, None, :] + jnp.einsum(
        "bxsl,xld->bxsd", lora, params["lora_b"])
    mixed = x[:, None] + xx[:, None] * mix  # (B, 5, S, d)
    # keep `mixed` in the residual's layout: without the pin GSPMD gathers
    # the full (B, 5, S, d) tensor instead of reduce-scattering the fused
    # projection output (§Perf #5)
    from repro.models.common import constrain_activation
    mixed = constrain_activation(mixed)
    return {nm: mixed[:, i] for i, nm in enumerate(_RWKV_MIX)}, mixed


def _rwkv_rkvwg(params, cfg, x, x_prev):
    H, P = rwkv_dims(cfg)
    B_, S, d = x.shape
    m, mixed = _time_mix_inputs(params, x, x_prev)
    # fused r/k/v/g projection: one (4, d, d) einsum over the mixed inputs.
    # _RWKV_MIX order is (r, k, v, w, g): the projected four are 0,1,2,4
    proj = jnp.einsum("bxsd,xde->bxse", mixed[:, jnp.array([0, 1, 2, 4])],
                      params["w_rkvg"])
    r = proj[:, 0].reshape(B_, S, H, P)
    k = proj[:, 1].reshape(B_, S, H, P)
    v = proj[:, 2].reshape(B_, S, H, P)
    # bf16 on the wire, fp32 only inside the recurrence step / accumulators:
    # fp32 activations here doubled every cross-chip gather (§Perf #5c)
    g = jax.nn.silu(proj[:, 3].astype(jnp.float32)).astype(x.dtype)
    # data-dependent decay: w in (0,1), per channel; reuse the "w" ddlerp
    # (index 3 of `mixed` is x + xx*mix_w; the decay lora consumes xx via
    # the fused lora tensors)
    wlog = params["decay_base"] + jnp.einsum(
        "bsl,ld->bsd",
        jnp.tanh(jnp.einsum("bsd,dl->bsl", x_prev - x,
                            params["lora_a"][3])),
        params["lora_b"][3]).astype(jnp.float32)
    w = jnp.exp(-jnp.exp(wlog)).reshape(B_, S, H, P).astype(x.dtype)
    return r, k, v, g, w


def _rwkv_out(params, cfg, wkv, g, B_, S):
    d = cfg.d_model
    out = wkv.reshape(B_, S, d)
    out = rms_norm(out, params["ln_x"], cfg.norm_eps)
    out = out * g.reshape(B_, S, d).astype(out.dtype)
    return jnp.einsum("bse,ed->bsd", out, params["w_o"])


def rwkv_time_mix_forward(params: Dict, cfg: ModelConfig, x: jax.Array,
                          ) -> jax.Array:
    """Full-sequence RWKV6 time-mix. x: (B, S, d)."""
    H, P = rwkv_dims(cfg)
    B_, S, d = x.shape
    x_prev = _token_shift(x, jnp.zeros((B_, d), x.dtype))
    r, k, v, g, w = _rwkv_rkvwg(params, cfg, x, x_prev)
    u = params["bonus_u"]

    if cfg.use_pallas_kernels:
        from repro.kernels import ops as kops
        wkv = kops.rwkv6_scan(r, k, v, w, u).astype(x.dtype)
        return _rwkv_out(params, cfg, wkv, g, B_, S)

    # recurrence: S_h (B, H, P, P); y_t = r_t @ (S_h + u * k_t v_t^T)
    def step2(S_h, inp):
        r_t, k_t, v_t, w_t = [a.astype(jnp.float32) for a in inp]
        kv = k_t[..., :, None] * v_t[..., None, :]  # (B, H, P, P)
        y = jnp.einsum("bhp,bhpq->bhq", r_t, S_h + u[..., None] * kv)
        S_h = w_t[..., :, None] * S_h + kv
        return S_h, y.astype(r.dtype)  # bf16 out of the loop (§Perf #5c)

    S0 = jnp.zeros((B_, H, P, P), jnp.float32)
    xs = tuple(a.transpose(1, 0, 2, 3) for a in (r, k, v, w))
    _, ys = jax.lax.scan(step2, S0, xs)  # (S, B, H, P)
    wkv = ys.transpose(1, 0, 2, 3).astype(x.dtype)
    return _rwkv_out(params, cfg, wkv, g, B_, S)


def init_rwkv_state(cfg: ModelConfig, batch: int) -> Dict:
    H, P = rwkv_dims(cfg)
    return {
        "S": jnp.zeros((batch, H, P, P), jnp.float32),
        "x_tm": jnp.zeros((batch, cfg.d_model), cfg.dtype),  # time-mix shift
        "x_cm": jnp.zeros((batch, cfg.d_model), cfg.dtype),  # chan-mix shift
    }


def rwkv_time_mix_decode(params: Dict, cfg: ModelConfig, x: jax.Array,
                         state: Dict) -> Tuple[jax.Array, Dict]:
    """x: (B, 1, d) single token."""
    H, P = rwkv_dims(cfg)
    B_, S, d = x.shape
    x_prev = state["x_tm"][:, None, :]
    r, k, v, g, w = _rwkv_rkvwg(params, cfg, x, x_prev)
    u = params["bonus_u"]
    r_t, k_t, v_t, w_t = [a[:, 0].astype(jnp.float32) for a in (r, k, v, w)]
    kv = k_t[..., :, None] * v_t[..., None, :]
    y = jnp.einsum("bhp,bhpq->bhq", r_t, state["S"] + u[..., None] * kv)
    S_new = w_t[..., :, None] * state["S"] + kv
    out = _rwkv_out(params, cfg, y[:, None].astype(x.dtype), g, B_, S)
    new_state = dict(state)
    new_state["S"] = S_new
    new_state["x_tm"] = x[:, 0]
    return out, new_state


def rwkv_channel_mix_forward(params: Dict, cfg: ModelConfig, x: jax.Array,
                             last: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """x: (B, S, d); last: (B, d). Returns (y, new_last)."""
    x_prev = _token_shift(x, last)
    xk = x + (x_prev - x) * params["mu_k"]
    xr = x + (x_prev - x) * params["mu_r"]
    kk = jnp.einsum("bsd,df->bsf", xk, params["w_k"])
    kk = jnp.square(jax.nn.relu(kk.astype(jnp.float32))).astype(x.dtype)
    vv = jnp.einsum("bsf,fd->bsd", kk, params["w_v"])
    rr = jax.nn.sigmoid(jnp.einsum("bsd,de->bse", xr,
                                   params["w_r"]).astype(jnp.float32))
    return (rr.astype(x.dtype) * vv), x[:, -1]
