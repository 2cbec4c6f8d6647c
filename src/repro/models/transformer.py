"""Model assembly: embedding -> layer stack (lax.scan) -> head, for every
assigned architecture family, with a uniform API:

    init_params(key, cfg)                         -> params
    forward(params, cfg, batch)                   -> (logits, aux)
    loss_fn(params, cfg, batch)                   -> (loss, metrics)
    init_cache(cfg, batch_size, max_seq)          -> cache pytree
    prefill(params, cfg, batch, max_seq)          -> (last_logits, cache)
    decode_step(params, cfg, tokens, cache, ...)  -> (logits, cache)

``batch``: {"tokens": (B, S) int32, ["frontend"]: (B, F, d) modality embeds,
["frames"]: (B, S_enc, d) audio frames for enc-dec, ["labels"], ["mask"]}.

Per-layer params are stacked on axis 0 so every stack lowers as one
``lax.scan`` (compact HLO, fast 61-layer dry-run compiles).
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models import blocks, ssm
from repro.models.attention import (attention_forward, init_attention,
                                    out_project, qkv_project)
from repro.models.common import (ATTENTION, MAMBA, MLP, ModelConfig,
                                 cross_entropy_loss, dense_init, relu2_mlp,
                                 rms_norm, softcap)

Params = Dict[str, Any]

# Optional activation-sharding hook (Megatron-style sequence/hidden
# activation partitioning over the TP axis). The launcher installs a
# with_sharding_constraint closure before tracing; unset it is identity.
# (Storage lives in models.common so ssm/moe modules can constrain their
# intermediates without import cycles.)
from repro.models.common import (constrain_activation as _constrain,  # noqa
                                 set_activation_constraint)


def _maybe_remat(fn, cfg: ModelConfig, mode: str):
    return jax.checkpoint(fn) if (cfg.remat and mode == "train") else fn


def _tree_index(tree, i):
    return jax.tree.map(lambda a: a[i], tree)


def _tree_stack_init(key, n, init_fn):
    return jax.vmap(init_fn)(jax.random.split(key, n))


# ===========================================================================
# Init
# ===========================================================================
def init_params(key, cfg: ModelConfig) -> Params:
    keys = jax.random.split(key, 8)
    params: Params = {
        "embed": dense_init(keys[0], (cfg.vocab_size, cfg.d_model), cfg.dtype),
        "final_norm": jnp.zeros((cfg.d_model,), cfg.dtype),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = dense_init(keys[1], (cfg.d_model, cfg.vocab_size),
                                       cfg.dtype)
    fam = cfg.family
    if fam in ("dense", "vlm", "moe"):
        use_moe = fam == "moe"
        params["layers"] = _tree_stack_init(
            keys[2], cfg.num_layers,
            lambda k: blocks.init_dense_block(k, cfg, use_moe=use_moe))
    elif fam == "ssm":  # rwkv6
        params["layers"] = _tree_stack_init(
            keys[2], cfg.num_layers, lambda k: blocks.init_rwkv_block(k, cfg))
    elif fam == "hybrid":  # zamba2
        n_super, period, tail = _zamba_split(cfg)
        params["layers"] = jax.tree.map(
            lambda a: a.reshape((n_super, period) + a.shape[1:]),
            _tree_stack_init(keys[2], n_super * period,
                             lambda k: blocks.init_mamba_block(k, cfg)))
        if tail:
            params["tail"] = _tree_stack_init(
                keys[3], tail, lambda k: blocks.init_mamba_block(k, cfg))
        params["shared_attn"] = blocks.init_dense_block(keys[4], cfg)
    elif fam == "mixed":  # one mixer per layer, by layer_pattern
        params["layers"] = {
            STACKS[kind]: _tree_stack_init(
                keys[2 + i], len(cfg.layers_of(kind)),
                lambda k, kind=kind: _init_mixed_layer(k, cfg, kind))
            for i, kind in enumerate(STACKS) if cfg.layers_of(kind)}
    elif fam == "audio":  # seamless enc-dec
        enc_cfg = cfg
        params["enc_layers"] = _tree_stack_init(
            keys[2], cfg.encoder_layers,
            lambda k: blocks.init_encoder_block(k, enc_cfg))
        params["enc_norm"] = jnp.zeros((cfg.d_model,), cfg.dtype)
        params["layers"] = _tree_stack_init(
            keys[3], cfg.num_layers,
            lambda k: blocks.init_decoder_block(k, cfg))
    else:
        raise ValueError(f"unknown family {fam}")
    return params


# a mixed stack's weights: one stack per layer kind, over its layers
STACKS = {MAMBA: "mamba", MLP: "mlp", ATTENTION: "attention"}


def _init_mixed_layer(key, cfg: ModelConfig, kind: str) -> Params:
    p = {"norm": jnp.zeros((cfg.d_model,), cfg.dtype)}
    if kind == MAMBA:
        p["mamba"] = ssm.init_mamba(key, cfg)
    elif kind == MLP:
        ku, kd = jax.random.split(key)
        p["w_up"] = dense_init(ku, (cfg.d_model, cfg.d_ff), cfg.dtype)
        p["w_down"] = dense_init(kd, (cfg.d_ff, cfg.d_model), cfg.dtype)
    else:
        p["attn"] = init_attention(key, cfg)
    return p


def _zamba_split(cfg: ModelConfig) -> Tuple[int, int, int]:
    period = cfg.shared_attn_period
    n_super = cfg.num_layers // period
    tail = cfg.num_layers - n_super * period
    return n_super, period, tail


# ===========================================================================
# Embedding / head
# ===========================================================================
def _embed(params: Params, cfg: ModelConfig,
           batch: Dict) -> Tuple[jax.Array, jax.Array, int]:
    """Returns (x, positions, n_frontend)."""
    tok = jnp.take(params["embed"], batch["tokens"], axis=0)
    if cfg.tie_embeddings:
        tok = tok * jnp.asarray(jnp.sqrt(float(cfg.d_model)), tok.dtype)
    n_front = 0
    if cfg.modality in ("vision", "audio_embeds") and "frontend" in batch:
        front = batch["frontend"].astype(tok.dtype)
        tok = jnp.concatenate([front, tok], axis=1)
        n_front = front.shape[1]
    B, S = tok.shape[:2]
    positions = jnp.arange(S)[None, :].repeat(B, 0)
    return tok, positions, n_front


def _head(params: Params, cfg: ModelConfig, x: jax.Array) -> jax.Array:
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    if cfg.tie_embeddings:
        logits = jnp.einsum("...d,vd->...v", x, params["embed"])
    else:
        logits = jnp.einsum("...d,dv->...v", x, params["lm_head"])
    return softcap(logits, cfg.final_logit_softcap)


# ===========================================================================
# Full-sequence forward (train / prefill)
# ===========================================================================
def _dense_stack(params, cfg: ModelConfig, x, positions, *, mode: str,
                 moe_group_size: int = 256):
    """Scan over dense/moe layers. gemma2 (local_global) scans layer *pairs*
    so local/global get separate static traces. Returns (x, aux, cache_kv).

    ``params["layers"]`` may be a LIST of per-layer trees instead of a
    stacked tree: then layers are separate XLA buffers and the loop is
    unrolled python-side — the production-serving layout (per-layer KV/weight
    buffers) used by the dry-run cost pass, where stacked+sliced layers would
    make every layer fusion charge the whole stack (see EXPERIMENTS.md §Perf
    #2)."""
    pair = 2 if cfg.local_global else 1
    layers = params["layers"]
    if isinstance(layers, (list, tuple)):
        aux = jnp.zeros((), jnp.float32)
        caches = []
        h = x
        for i, p in enumerate(layers):
            is_local = (i % 2 == 0) if cfg.local_global else False

            def run(p_, h_, _loc=is_local):
                return blocks.dense_block(
                    p_, cfg, h_, mode=mode, positions=positions,
                    is_local=_loc, moe_group_size=moe_group_size)

            h, cache, a = _maybe_remat(run, cfg, mode)(p, h)
            h = _constrain(h)
            caches.append(cache)
            aux = aux + a
        return h, aux, caches
    if pair == 2:
        layers = jax.tree.map(
            lambda a: a.reshape((a.shape[0] // 2, 2) + a.shape[1:]), layers)

    def body(carry, layer_p):
        h, aux = carry
        caches = []
        for j in range(pair):
            p = _tree_index(layer_p, j) if pair == 2 else layer_p
            is_local = (j == 0) if cfg.local_global else False

            def run(p_, h_, _loc=is_local):
                return blocks.dense_block(
                    p_, cfg, h_, mode=mode, positions=positions,
                    is_local=_loc, moe_group_size=moe_group_size)

            h, cache, a = _maybe_remat(run, cfg, mode)(p, h)
            h = _constrain(h)
            caches.append(cache)
            aux = aux + a
        ys = jax.tree.map(lambda *c: jnp.stack(c), *caches) if pair == 2 \
            else caches[0]
        return (h, aux), ys

    (x, aux), kv = jax.lax.scan(body, (x, jnp.zeros((), jnp.float32)),
                                layers, unroll=cfg.lower_unrolled)
    if mode == "prefill" and pair == 2:
        kv = jax.tree.map(
            lambda a: a.reshape((a.shape[0] * 2,) + a.shape[2:]), kv)
    return x, aux, kv


def _rwkv_stack(params, cfg, x, *, mode: str):
    run = _maybe_remat(
        lambda p_, h_: blocks.rwkv_block(p_, cfg, h_, mode=mode), cfg, mode)
    if isinstance(params["layers"], (list, tuple)):
        states = []
        for p in params["layers"]:
            x, st = run(p, x)
            x = _constrain(x)
            states.append(st)
        return x, states

    def body(h, layer_p):
        h, state = run(layer_p, h)
        return _constrain(h), state

    x, states = jax.lax.scan(body, x, params["layers"],
                             unroll=cfg.lower_unrolled)
    return x, states


def _zamba_stack(params, cfg, x, positions, *, mode: str):
    n_super, period, tail = _zamba_split(cfg)

    if isinstance(params["layers"], (list, tuple)):
        attn_caches, msts = [], []
        h = x
        for sup in params["layers"]:  # list over superblocks
            h, attn_cache, _ = blocks.dense_block(
                params["shared_attn"], cfg, h, mode=mode,
                positions=positions)
            sup_states = []
            for mp in sup:  # list over the period's mamba layers
                h, st = blocks.mamba_block(mp, cfg, h, mode=mode)
                sup_states.append(st)
            h = _constrain(h)
            attn_caches.append(attn_cache)
            msts.append(sup_states)
        tail_states = []
        for mp in params["tail"] if tail else []:
            h, st = blocks.mamba_block(mp, cfg, h, mode=mode)
            tail_states.append(st)
        return h, attn_caches, msts, tail_states

    def body(carry, xs):
        h = carry

        def run(xs_, shared_, h_):
            h_, attn_cache, _ = blocks.dense_block(
                shared_, cfg, h_, mode=mode, positions=positions)
            mamba_states = []
            for i in range(period):
                h_, st = blocks.mamba_block(_tree_index(xs_, i), cfg, h_,
                                            mode=mode)
                mamba_states.append(st)
            states = jax.tree.map(lambda *s: jnp.stack(s), *mamba_states) \
                if mamba_states and mamba_states[0] else {}
            return h_, attn_cache, states

        h, attn_cache, states = _maybe_remat(run, cfg, mode)(
            xs, params["shared_attn"], h)
        return _constrain(h), (attn_cache, states)

    x, (attn_kv, mstates) = jax.lax.scan(body, x, params["layers"],
                                         unroll=cfg.lower_unrolled)
    tail_states = []
    for i in range(tail):
        x, st = blocks.mamba_block(_tree_index(params["tail"], i), cfg, x,
                                   mode=mode)
        tail_states.append(st)
    return x, attn_kv, mstates, tail_states


def _encdec_stacks(params, cfg, batch, *, mode: str):
    frames = batch["frames"].astype(cfg.dtype)  # (B, S_enc, d) stub embeds
    B, S_enc, _ = frames.shape
    enc_pos = jnp.arange(S_enc)[None, :].repeat(B, 0)

    enc_run = _maybe_remat(
        lambda p_, h_: blocks.encoder_block(p_, cfg, h_, enc_pos), cfg, mode)
    if isinstance(params["enc_layers"], (list, tuple)):
        enc_out = frames
        for p in params["enc_layers"]:
            enc_out = _constrain(enc_run(p, enc_out))
    else:
        def enc_body(h, layer_p):
            return _constrain(enc_run(layer_p, h)), None

        enc_out, _ = jax.lax.scan(enc_body, frames, params["enc_layers"],
                                  unroll=cfg.lower_unrolled)
    enc_out = rms_norm(enc_out, params["enc_norm"], cfg.norm_eps)

    tok = jnp.take(params["embed"], batch["tokens"], axis=0)
    S_dec = tok.shape[1]
    dec_pos = jnp.arange(S_dec)[None, :].repeat(B, 0)

    def run(p_, h_):
        ekv = blocks.encoder_cross_kv(p_, cfg, enc_out)
        h2, cache = blocks.decoder_block(p_, cfg, h_, ekv, mode=mode,
                                         positions=dec_pos)
        cache = dict(cache, ck=ekv[0], cv=ekv[1]) \
            if mode == "prefill" else cache
        return h2, cache

    dec_run = _maybe_remat(run, cfg, mode)
    if isinstance(params["layers"], (list, tuple)):
        x = tok
        caches = []
        for p in params["layers"]:
            x, cache = dec_run(p, x)
            x = _constrain(x)
            caches.append(cache)
        return x, caches

    def dec_body(h, layer_p):
        h, cache = dec_run(layer_p, h)
        return _constrain(h), cache

    x, caches = jax.lax.scan(dec_body, tok, params["layers"],
                             unroll=cfg.lower_unrolled)
    return x, caches


def forward(params: Params, cfg: ModelConfig,
            batch: Dict) -> Tuple[jax.Array, jax.Array]:
    """Full-sequence logits. Returns (logits, aux_loss)."""
    aux = jnp.zeros((), jnp.float32)
    if cfg.family == "audio":
        x, _ = _encdec_stacks(params, cfg, batch, mode="train")
    elif cfg.family == "ssm":
        x_in, _, _ = _embed(params, cfg, batch)
        x, _ = _rwkv_stack(params, cfg, x_in, mode="train")
    elif cfg.family == "hybrid":
        x_in, positions, _ = _embed(params, cfg, batch)
        x, _, _, _ = _zamba_stack(params, cfg, x_in, positions, mode="train")
    else:
        x_in, positions, n_front = _embed(params, cfg, batch)
        x, aux, _ = _dense_stack(params, cfg, x_in, positions, mode="train")
        if n_front:
            x = x[:, n_front:]
    return _head(params, cfg, x), aux


def loss_fn(params: Params, cfg: ModelConfig,
            batch: Dict) -> Tuple[jax.Array, Dict]:
    logits, aux = forward(params, cfg, batch)
    labels = batch.get("labels")
    if labels is None:
        labels = jnp.pad(batch["tokens"][:, 1:], ((0, 0), (0, 1)))
    mask = batch.get("mask")
    ce = cross_entropy_loss(logits, labels, mask)
    total = ce + cfg.router_aux_weight * aux
    return total, {"ce": ce, "aux": aux}


# ===========================================================================
# KV cache / recurrent state
# ===========================================================================
def init_cache(cfg: ModelConfig, batch: int, max_seq: int) -> Dict:
    hd = cfg.resolved_head_dim
    L = cfg.num_layers
    fam = cfg.family
    cache: Dict[str, Any] = {"len": jnp.zeros((batch,), jnp.int32)}
    if fam in ("dense", "vlm", "moe"):
        # head-major KV layout (B, Hkv, S, hd): both decode einsums contract
        # without layout transposes (§Perf #3)
        kv_dtype = jnp.int8 if cfg.kv_cache_bits == 8 else cfg.dtype
        cache["k"] = jnp.zeros((L, batch, cfg.num_kv_heads, max_seq, hd),
                               kv_dtype)
        cache["v"] = jnp.zeros_like(cache["k"])
        if cfg.kv_cache_bits == 8:  # per-token per-head scales (paper §7)
            cache["k_scale"] = jnp.zeros(
                (L, batch, cfg.num_kv_heads, max_seq), jnp.float32)
            cache["v_scale"] = jnp.zeros_like(cache["k_scale"])
    elif fam == "ssm":
        H, P = ssm.rwkv_dims(cfg)
        cache["S"] = jnp.zeros((L, batch, H, P, P), jnp.float32)
        cache["x_tm"] = jnp.zeros((L, batch, cfg.d_model), cfg.dtype)
        cache["x_cm"] = jnp.zeros((L, batch, cfg.d_model), cfg.dtype)
    elif fam == "hybrid":
        n_super, period, tail = _zamba_split(cfg)
        d_inner, H, P, N = ssm.mamba_dims(cfg)
        conv_ch = d_inner + 2 * N
        cache["k"] = jnp.zeros(
            (n_super, batch, cfg.num_kv_heads, max_seq, hd), cfg.dtype)
        cache["v"] = jnp.zeros_like(cache["k"])
        cache["h"] = jnp.zeros((n_super, period, batch, H, P, N), jnp.float32)
        cache["conv"] = jnp.zeros(
            (n_super, period, batch, cfg.ssm_conv - 1, conv_ch), cfg.dtype)
        if tail:
            cache["tail_h"] = jnp.zeros((tail, batch, H, P, N), jnp.float32)
            cache["tail_conv"] = jnp.zeros(
                (tail, batch, cfg.ssm_conv - 1, conv_ch), cfg.dtype)
    elif fam == "audio":
        cache["k"] = jnp.zeros((L, batch, cfg.num_kv_heads, max_seq, hd),
                               cfg.dtype)
        cache["v"] = jnp.zeros_like(cache["k"])
        # cross KV sized by encoder length — filled at prefill; dry-run decode
        # supplies it via input_specs
        cache["ck"] = jnp.zeros((L, batch, cfg.num_kv_heads, 0, hd), cfg.dtype)
        cache["cv"] = jnp.zeros_like(cache["ck"])
    return cache


# ===========================================================================
# Prefill
# ===========================================================================
def prefill(params: Params, cfg: ModelConfig, batch: Dict,
            max_seq: int) -> Tuple[jax.Array, Dict]:
    """Run the prompt, return (last-position logits, filled cache)."""
    fam = cfg.family
    listed = isinstance(params["layers"], (list, tuple))
    B = batch["tokens"].shape[0]
    cache: Dict[str, Any] = {} if listed else init_cache(cfg, B, max_seq)
    if fam == "audio":
        x, caches = _encdec_stacks(params, cfg, batch, mode="prefill")
        S = x.shape[1]
        if listed:
            cache["k"] = [_pad_seq(_hm(c["k"]), max_seq, axis=2)
                          for c in caches]
            cache["v"] = [_pad_seq(_hm(c["v"]), max_seq, axis=2)
                          for c in caches]
            cache["ck"] = [_hm(c["ck"]) for c in caches]
            cache["cv"] = [_hm(c["cv"]) for c in caches]
        else:
            cache["k"] = _pad_seq(_hm(caches["k"], 2), max_seq, axis=3)
            cache["v"] = _pad_seq(_hm(caches["v"], 2), max_seq, axis=3)
            cache["ck"] = _hm(caches["ck"], 2)
            cache["cv"] = _hm(caches["cv"], 2)
        cache["len"] = jnp.full((x.shape[0],), S, jnp.int32)
    elif fam == "ssm":
        x_in, _, _ = _embed(params, cfg, batch)
        x, states = _rwkv_stack(params, cfg, x_in, mode="prefill")
        if listed:
            for key in states[0]:
                cache[key] = [s[key] for s in states]
        else:
            cache.update(states)
        cache["len"] = jnp.full((x.shape[0],), x.shape[1], jnp.int32)
    elif fam == "hybrid":
        x_in, positions, _ = _embed(params, cfg, batch)
        x, attn_kv, mstates, tail_states = _zamba_stack(
            params, cfg, x_in, positions, mode="prefill")
        if listed:
            cache["k"] = [_pad_seq(_hm(c["k"]), max_seq, axis=2)
                          for c in attn_kv]
            cache["v"] = [_pad_seq(_hm(c["v"]), max_seq, axis=2)
                          for c in attn_kv]
            cache["h"] = [[s["h"] for s in sup] for sup in mstates]
            cache["conv"] = [[s["conv"] for s in sup] for sup in mstates]
            if tail_states:
                cache["tail_h"] = [s["h"] for s in tail_states]
                cache["tail_conv"] = [s["conv"] for s in tail_states]
        else:
            cache["k"] = _pad_seq(_hm(attn_kv["k"], 2), max_seq, axis=3)
            cache["v"] = _pad_seq(_hm(attn_kv["v"], 2), max_seq, axis=3)
            cache["h"], cache["conv"] = mstates["h"], mstates["conv"]
            if tail_states:
                cache["tail_h"] = jnp.stack([s["h"] for s in tail_states])
                cache["tail_conv"] = jnp.stack(
                    [s["conv"] for s in tail_states])
        cache["len"] = jnp.full((x.shape[0],), x.shape[1], jnp.int32)
    else:
        x_in, positions, n_front = _embed(params, cfg, batch)
        x, aux, kv = _dense_stack(params, cfg, x_in, positions, mode="prefill")
        if listed:
            cache["k"] = [_pad_seq(_hm(c["k"]), max_seq, axis=2) for c in kv]
            cache["v"] = [_pad_seq(_hm(c["v"]), max_seq, axis=2) for c in kv]
            if cfg.kv_cache_bits == 8:
                from repro.models import kv_quant
                kq = [kv_quant.quantize_kv(k) for k in cache["k"]]
                vq = [kv_quant.quantize_kv(v) for v in cache["v"]]
                cache["k"] = [a for a, _ in kq]
                cache["k_scale"] = [b for _, b in kq]
                cache["v"] = [a for a, _ in vq]
                cache["v_scale"] = [b for _, b in vq]
        else:
            cache["k"] = _pad_seq(_hm(kv["k"], 2), max_seq, axis=3)
            cache["v"] = _pad_seq(_hm(kv["v"], 2), max_seq, axis=3)
            if cfg.kv_cache_bits == 8:
                from repro.models import kv_quant
                cache["k"], cache["k_scale"] = kv_quant.quantize_kv(
                    cache["k"])
                cache["v"], cache["v_scale"] = kv_quant.quantize_kv(
                    cache["v"])
        cache["len"] = jnp.full((x.shape[0],), x.shape[1], jnp.int32)
    logits = _head(params, cfg, x[:, -1])
    return logits, cache


def prefill_suffix(params: Params, cfg: ModelConfig, batch: Dict,
                   k_prefix: jax.Array, v_prefix: jax.Array
                   ) -> Tuple[jax.Array, Dict]:
    """Prefix-cached prefill: run only a prompt's unshared SUFFIX, with the
    shared prefix's KV supplied from the paged pool — the prefix-sharing
    engine's prefill-skip path (matched blocks are never recomputed).

    batch["tokens"]: (B, S_suf) suffix tokens; k_prefix/v_prefix:
    HEAD-MAJOR (L, B, Hkv, P, hd) — the pool layout
    ``PagedKVCache.gather_prefix`` returns. Suffix queries attend over
    concat(prefix, suffix) keys at global positions, so hidden states,
    suffix KV, and last-position logits are BIT-IDENTICAL to the
    corresponding slice of a full :func:`prefill` over prefix+suffix
    (see ``attention_forward``). Returns (last-position logits,
    {"k", "v", "len"}) with SUFFIX-ONLY head-major KV (L, B, Hkv, S_suf,
    hd) and len = P + S_suf. Dense/vlm/moe stacked-layer stacks only (the
    serving engines' families)."""
    if cfg.family not in ("dense", "vlm", "moe"):
        raise ValueError("prefix-cached prefill serves KV-cache dense "
                         f"stacks; got family={cfg.family}")
    if isinstance(params["layers"], (list, tuple)):
        raise ValueError("prefix-cached prefill requires stacked layer "
                         "params (per-layer buffer layout is the dry-run "
                         "path)")
    P = k_prefix.shape[3]
    x, positions, _ = _embed(params, cfg, batch)
    positions = positions + P           # suffix tokens sit at P + i
    pair = 2 if cfg.local_global else 1
    layers, kp, vp = params["layers"], k_prefix, v_prefix
    if pair == 2:
        layers, kp, vp = jax.tree.map(
            lambda a: a.reshape((a.shape[0] // 2, 2) + a.shape[1:]),
            (layers, kp, vp))

    def body(carry, xs):
        h, aux = carry
        layer_p, kp_l, vp_l = xs
        caches = []
        for j in range(pair):
            p = _tree_index(layer_p, j) if pair == 2 else layer_p
            is_local = (j == 0) if cfg.local_global else False
            h, c, a = blocks.dense_block(
                p, cfg, h, mode="prefill", positions=positions,
                is_local=is_local,
                prefix_kv=(kp_l[j] if pair == 2 else kp_l,
                           vp_l[j] if pair == 2 else vp_l))
            caches.append(c)
            aux = aux + a
        ys = jax.tree.map(lambda *c: jnp.stack(c), *caches) if pair == 2 \
            else caches[0]
        return (h, aux), ys

    (x, _), kv = jax.lax.scan(body, (x, jnp.zeros((), jnp.float32)),
                              (layers, kp, vp), unroll=cfg.lower_unrolled)
    if pair == 2:
        kv = jax.tree.map(
            lambda a: a.reshape((a.shape[0] * 2,) + a.shape[2:]), kv)
    cache = {"k": _hm(kv["k"], 2), "v": _hm(kv["v"], 2),
             "len": jnp.full((x.shape[0],), P + x.shape[1], jnp.int32)}
    return _head(params, cfg, x[:, -1]), cache


def prefill_chunk(params: Params, cfg: ModelConfig, batch: Dict,
                  k_pool: jax.Array, v_pool: jax.Array,
                  prefix_blocks: jax.Array, *, backend: str = "jnp",
                  k_scale_pool: Optional[jax.Array] = None,
                  v_scale_pool: Optional[jax.Array] = None
                  ) -> Tuple[jax.Array, Dict]:
    """Chunked paged prefill: run ONE block-aligned chunk of a prompt, its
    queries attending over the ALREADY-WRITTEN pool blocks plus the
    in-chunk causal mask — the generalisation of :func:`prefill_suffix`
    where the prefix context stays paged (and may be empty: an all-zero-
    block ``prefix_blocks`` of shape (0,) is the first chunk of a fresh
    prompt, equivalent to a plain :func:`prefill` over the chunk).

    batch["tokens"]: (1, C) — the chunk's tokens (B must be 1, the serving
    prefill shape); k_pool/v_pool: HEAD-MAJOR (L, Hkv, num_blocks, bs, hd)
    — the PagedKVCache pools by reference; prefix_blocks: (nb,) int32 pool
    ids of this sequence's first nb blocks, all fully written
    (P = nb·bs tokens). Chunk queries sit at global positions [P, P+C).

    On the jnp backend each layer gathers its own prefix slice dense (peak
    context slab O(P) for ONE layer, not L·P) and runs the same blockwise
    scan as a one-shot prefill, so hidden states, chunk KV, and
    last-position logits are BIT-IDENTICAL to the corresponding slice of a
    full :func:`prefill` over prefix+chunk; ``backend="pallas"`` streams
    the prefix straight from the pool (no densify — see
    ``kernels/paged_prefill_attention.py``). Returns (last-position logits,
    {"k", "v", "len"}) with CHUNK-ONLY head-major KV (L, 1, Hkv, C, hd) and
    len = P + C — the slab ``PagedKVCache.write_prefill_chunk`` scatters.

    Dense/vlm/moe stacked-layer stacks only. NOTE: for MoE families the
    chunk boundary changes capacity-dispatch groups, so chunked outputs are
    NOT bit-stable against the one-shot prefill — the serving engine runs
    MoE prompts one-shot (same reason prefix sharing recomputes them).

    k_scale_pool/v_scale_pool: the int8 pool's fp32 scale sidecars
    (L, Hkv, num_blocks, 1, bs), threaded per layer next to the value pools
    (int8 readback makes chunked outputs quantization-, not chunking-,
    dependent; chunked-vs-oneshot bit-stability is a bf16-pool contract)."""
    if cfg.family not in ("dense", "vlm", "moe"):
        raise ValueError("chunked paged prefill serves KV-cache dense "
                         f"stacks; got family={cfg.family}")
    if isinstance(params["layers"], (list, tuple)):
        raise ValueError("chunked paged prefill requires stacked layer "
                         "params (per-layer buffer layout is the dry-run "
                         "path)")
    if batch["tokens"].shape[0] != 1:
        raise ValueError("chunked paged prefill is per-request (B == 1); "
                         f"got B={batch['tokens'].shape[0]}")
    bs = k_pool.shape[3]
    P = prefix_blocks.shape[0] * bs
    x, positions, _ = _embed(params, cfg, batch)
    positions = positions + P           # chunk tokens sit at P + i
    pair = 2 if cfg.local_global else 1
    quant = k_scale_pool is not None
    # 5-tuple scan xs either way (dummy per-layer zeros when bf16) so the
    # scan tree structure is kv_dtype-independent
    ks_, vs_ = (k_scale_pool, v_scale_pool) if quant else (
        jnp.zeros((k_pool.shape[0],)), jnp.zeros((k_pool.shape[0],)))
    layers, kp, vp = params["layers"], k_pool, v_pool
    if pair == 2:
        layers, kp, vp, ks_, vs_ = jax.tree.map(
            lambda a: a.reshape((a.shape[0] // 2, 2) + a.shape[1:]),
            (layers, kp, vp, ks_, vs_))

    def body(carry, xs):
        h, aux = carry
        layer_p, kp_l, vp_l, ks_l, vs_l = xs
        caches = []
        for j in range(pair):
            p = _tree_index(layer_p, j) if pair == 2 else layer_p
            is_local = (j == 0) if cfg.local_global else False
            scales = None
            if quant:
                scales = (ks_l[j] if pair == 2 else ks_l,
                          vs_l[j] if pair == 2 else vs_l)
            h, c, a = blocks.dense_block(
                p, cfg, h, mode="prefill", positions=positions,
                is_local=is_local, backend=backend,
                paged_prefix=(kp_l[j] if pair == 2 else kp_l,
                              vp_l[j] if pair == 2 else vp_l,
                              prefix_blocks),
                paged_prefix_scales=scales)
            caches.append(c)
            aux = aux + a
        ys = jax.tree.map(lambda *c: jnp.stack(c), *caches) if pair == 2 \
            else caches[0]
        return (h, aux), ys

    (x, _), kv = jax.lax.scan(body, (x, jnp.zeros((), jnp.float32)),
                              (layers, kp, vp, ks_, vs_),
                              unroll=cfg.lower_unrolled)
    if pair == 2:
        kv = jax.tree.map(
            lambda a: a.reshape((a.shape[0] * 2,) + a.shape[2:]), kv)
    cache = {"k": _hm(kv["k"], 2), "v": _hm(kv["v"], 2),
             "len": jnp.full((x.shape[0],), P + x.shape[1], jnp.int32)}
    return _head(params, cfg, x[:, -1]), cache


def _mixed_layers(params: Params, cfg: ModelConfig):
    """(kind, index among its kind's layers, that layer's weights) for
    every layer of a mixed stack, in order."""
    seen = {kind: 0 for kind in STACKS}
    for kind in cfg.layer_pattern:
        n = seen[kind]
        seen[kind] += 1
        yield kind, n, _tree_index(params["layers"][STACKS[kind]], n)


def prefill_chunk_mixed(params: Params, cfg: ModelConfig, batch: Dict,
                        k_pool: jax.Array, v_pool: jax.Array,
                        prefix_blocks: jax.Array, state: Dict,
                        slot: jax.Array, length: jax.Array, *,
                        backend: str = "jnp"
                        ) -> Tuple[jax.Array, Dict, Dict]:
    """One prefill chunk of one request through a mixed stack (the
    chunked-prefill contract of :func:`prefill_chunk`; B == 1), whose
    first ``length`` tokens (a traced count) are the chunk's and the rest
    padding, so that chunks of many lengths share a program. Attention
    layers attend over the request's pool blocks ``prefix_blocks`` (the
    pools hold the attention layers only, in order) and the chunk; Mamba
    layers run from the request's state in slot ``slot`` of ``state``
    ({"ssm", "conv"}: ``ssm.state_slot_shapes``), or from zeros where the
    chunk is the request's first (no prefix blocks), and leave there the
    state after the chunk's last token. Padding follows every real token,
    so causal attention keeps it out of their results, and it takes no
    Mamba step. Returns (the last real position's logits, KV {"k", "v",
    "len"} with k/v (L_attn, 1, Hkv, C, hd), padding included, state)."""
    if batch["tokens"].shape[0] != 1:
        raise ValueError("chunked paged prefill is per-request (B == 1); "
                         f"got B={batch['tokens'].shape[0]}")
    P = prefix_blocks.shape[0] * k_pool.shape[3]
    x, positions, _ = _embed(params, cfg, batch)
    positions = positions + P
    ks, vs = [], []
    for kind, n, p in _mixed_layers(params, cfg):
        h = rms_norm(x, p["norm"], cfg.norm_eps)
        if kind == MAMBA:
            first = P == 0
            y, s1, c1 = ssm.mamba_chunk(
                p["mamba"], cfg, h,
                None if first else state["ssm"][n, slot],
                None if first else state["conv"][n, slot], length)
            state = {"ssm": state["ssm"].at[n, slot].set(s1),
                     "conv": state["conv"].at[n, slot].set(c1)}
        elif kind == MLP:
            y = relu2_mlp(h, p["w_up"], p["w_down"])
        else:
            y, k, v = attention_forward(
                p["attn"], cfg, h, positions, backend=backend,
                paged_prefix=(k_pool[n], v_pool[n], prefix_blocks))
            ks.append(k)
            vs.append(v)
        x = x + y
    cache = {"k": _hm(jnp.stack(ks), 2), "v": _hm(jnp.stack(vs), 2),
             "len": jnp.full((1,), P + length, jnp.int32)}
    last = jax.lax.dynamic_index_in_dim(x, length - 1, axis=1,
                                        keepdims=False)
    return _head(params, cfg, last), cache, state


def decode_step_mixed(params: Params, cfg: ModelConfig, tokens: jax.Array,
                      lens: jax.Array, state: Dict, slots: jax.Array,
                      attend) -> Tuple[jax.Array, Dict]:
    """One decode token per row through a mixed stack. Row b's Mamba state
    is in slot ``slots[b]`` of ``state`` and is updated in place;
    ``attend(n, q, k_new, v_new)`` is attention layer n's paged attention
    over the stored tokens and the new one ((B, H, hd) queries; the
    placement decides where it runs). ``lens``: tokens already stored.
    Returns (logits, {"k_new", "v_new": (L_attn, B, Hkv, hd), "len",
    "state"})."""
    x, _, _ = _embed(params, cfg, {"tokens": tokens[:, None]})
    ks, vs = [], []
    for kind, n, p in _mixed_layers(params, cfg):
        h = rms_norm(x, p["norm"], cfg.norm_eps)
        if kind == MAMBA:
            y, state = ssm.mamba_decode_slots(p["mamba"], cfg, h, state,
                                              slots, n)
        elif kind == MLP:
            y = relu2_mlp(h, p["w_up"], p["w_down"])
        else:
            q, k, v = qkv_project(p["attn"], cfg, h, lens[:, None])
            ks.append(k[:, 0])
            vs.append(v[:, 0])
            y = out_project(p["attn"], attend(n, q[:, 0], k[:, 0],
                                              v[:, 0])[:, None])
        x = x + y
    updates = {"k_new": jnp.stack(ks), "v_new": jnp.stack(vs),
               "len": lens + 1, "state": state}
    return _head(params, cfg, x[:, 0]), updates


def _hm(kv: jax.Array, seq_axis: int = 1) -> jax.Array:
    """(…, S, Hkv, hd) -> head-major (…, Hkv, S, hd)."""
    return jnp.swapaxes(kv, seq_axis, seq_axis + 1)


def _pad_seq(kv: jax.Array, max_seq: int, axis: int = 2) -> jax.Array:
    """Pad/trim the sequence axis to max_seq (axis=2 for stacked (L,B,S,..),
    axis=1 for per-layer (B,S,..) buffers)."""
    S = kv.shape[axis]
    if S >= max_seq:
        idx = [slice(None)] * kv.ndim
        idx[axis] = slice(0, max_seq)
        return kv[tuple(idx)]
    pad = [(0, 0)] * kv.ndim
    pad[axis] = (0, max_seq - S)
    return jnp.pad(kv, pad)


# ===========================================================================
# Decode step (the paper's target phase)
# ===========================================================================
def decode_step(params: Params, cfg: ModelConfig, tokens: jax.Array,
                cache: Dict, *, backend: str = "jnp",
                moe_group_size: int = 256) -> Tuple[jax.Array, Dict]:
    """One decoding iteration. tokens: (B,) int32 — the freshly sampled token.

    cache["len"] = tokens ALREADY stored (the new token is not in the cache);
    attention is combine(prefix partial, new-token partial) per §4.2.2.
    Returns (logits, updates): updates carries k_new/v_new (L, B, Hkv, hd)
    plus refreshed recurrent states and len+1 — KV *placement* is the memory
    pool's job (serving/kvcache.py) or apply_decode_updates for simple loops.
    """
    cur_len = cache["len"]
    new_len = cur_len + 1
    x = jnp.take(params["embed"], tokens[:, None], axis=0)
    if cfg.tie_embeddings:
        x = x * jnp.asarray(jnp.sqrt(float(cfg.d_model)), x.dtype)
    fam = cfg.family
    # read-only buffers (prefix KV, cross KV) stay out of the outputs — the
    # memory pool owns them; only per-step updates flow back
    new_cache = {k: v for k, v in cache.items()
                 if k not in ("k", "v", "ck", "cv", "k_scale", "v_scale")}
    new_cache["len"] = new_len

    if isinstance(params["layers"], (list, tuple)):
        return _decode_step_listed(params, cfg, x, cache, cur_len, new_cache,
                                   backend=backend,
                                   moe_group_size=moe_group_size)

    if fam in ("dense", "vlm", "moe"):
        pair = 2 if cfg.local_global else 1
        layers = params["layers"]
        quant = cfg.kv_cache_bits == 8
        kc, vc = cache["k"], cache["v"]
        ks_, vs_ = (cache["k_scale"], cache["v_scale"]) if quant else \
            (jnp.zeros((kc.shape[0],)),) * 2
        if pair == 2:
            layers, kc, vc, ks_, vs_ = jax.tree.map(
                lambda a: a.reshape((a.shape[0] // 2, 2) + a.shape[1:]),
                (layers, kc, vc, ks_, vs_))

        def body(carry, xs):
            h, aux = carry
            layer_p, k_l, v_l, ks_l, vs_l = xs
            new_kv = []
            for j in range(pair):
                p = _tree_index(layer_p, j) if pair == 2 else layer_p
                kj = k_l[j] if pair == 2 else k_l
                vj = v_l[j] if pair == 2 else v_l
                lc = {"k": kj, "v": vj, "len": cur_len}
                if quant:
                    lc["k_scale"] = ks_l[j] if pair == 2 else ks_l
                    lc["v_scale"] = vs_l[j] if pair == 2 else vs_l
                is_local = (j == 0) if cfg.local_global else False
                h, c, a = blocks.dense_block(
                    p, cfg, h, mode="decode", is_local=is_local,
                    cache=lc, backend=backend,
                    moe_group_size=moe_group_size)
                new_kv.append(c)
                aux = aux + a
            ys = jax.tree.map(lambda *c: jnp.stack(c), *new_kv) if pair == 2 \
                else new_kv[0]
            return (h, aux), ys

        (x, _), kv = jax.lax.scan(body, (x, jnp.zeros((), jnp.float32)),
                                  (layers, kc, vc, ks_, vs_),
                                  unroll=cfg.lower_unrolled)
        if pair == 2:
            kv = jax.tree.map(
                lambda a: a.reshape((a.shape[0] * 2,) + a.shape[2:]), kv)
        new_cache["k_new"], new_cache["v_new"] = kv["k_new"], kv["v_new"]

    elif fam == "ssm":
        def body(h, xs):
            layer_p, st = xs
            h, new_st = blocks.rwkv_block(layer_p, cfg, h, mode="decode",
                                          state=st)
            return h, new_st

        states = {k: cache[k] for k in ("S", "x_tm", "x_cm")}
        x, new_states = jax.lax.scan(body, x, (params["layers"], states),
                                     unroll=cfg.lower_unrolled)
        new_cache.update(new_states)

    elif fam == "hybrid":
        n_super, period, tail = _zamba_split(cfg)

        def body(h, xs):
            layer_p, k_l, v_l, h_l, conv_l = xs
            h_x, attn_c, _ = blocks.dense_block(
                params["shared_attn"], cfg, h, mode="decode",
                cache={"k": k_l, "v": v_l, "len": cur_len}, backend=backend)
            h = h_x
            new_h, new_conv = [], []
            for i in range(period):
                h, st = blocks.mamba_block(
                    _tree_index(layer_p, i), cfg, h, mode="decode",
                    state={"h": h_l[i], "conv": conv_l[i]})
                new_h.append(st["h"])
                new_conv.append(st["conv"])
            return h, (attn_c["k_new"], attn_c["v_new"], jnp.stack(new_h),
                       jnp.stack(new_conv))

        x, (nk, nv, nh, nconv) = jax.lax.scan(
            body, x, (params["layers"], cache["k"], cache["v"], cache["h"],
                      cache["conv"]), unroll=cfg.lower_unrolled)
        new_cache.update({"k_new": nk, "v_new": nv, "h": nh, "conv": nconv})
        new_tail_h, new_tail_conv = [], []
        for i in range(tail):
            x, st = blocks.mamba_block(
                _tree_index(params["tail"], i), cfg, x, mode="decode",
                state={"h": cache["tail_h"][i], "conv": cache["tail_conv"][i]})
            new_tail_h.append(st["h"])
            new_tail_conv.append(st["conv"])
        if tail:
            new_cache["tail_h"] = jnp.stack(new_tail_h)
            new_cache["tail_conv"] = jnp.stack(new_tail_conv)

    elif fam == "audio":
        def body(h, xs):
            layer_p, k_l, v_l, ck_l, cv_l = xs
            h, c = blocks.decoder_block(
                layer_p, cfg, h, (ck_l, cv_l), mode="decode",
                cache={"k": k_l, "v": v_l, "len": cur_len}, backend=backend)
            return h, (c["k_new"], c["v_new"])

        x, (nk, nv) = jax.lax.scan(
            body, x, (params["layers"], cache["k"], cache["v"], cache["ck"],
                      cache["cv"]), unroll=cfg.lower_unrolled)
        new_cache["k_new"], new_cache["v_new"] = nk, nv
    else:
        raise ValueError(fam)

    logits = _head(params, cfg, x[:, 0])
    return logits, new_cache


def decode_step_paged(params: Params, cfg: ModelConfig, tokens: jax.Array,
                      k_pool: jax.Array, v_pool: jax.Array,
                      block_tables: jax.Array, cache_len: jax.Array, *,
                      backend: str = "jnp",
                      k_scale_pool: Optional[jax.Array] = None,
                      v_scale_pool: Optional[jax.Array] = None,
                      moe_group_size: int = 256) -> Tuple[jax.Array, Dict]:
    """One decoding iteration straight over the paged KV block pool — the
    serving engines' default hot path (no per-step dense gather/transposes).

    tokens: (B,) int32; k_pool/v_pool: HEAD-MAJOR (L, Hkv, num_blocks,
    block_size, hd) — the PagedKVCache pools passed by reference;
    block_tables: (B, nb) int32; cache_len: (B,) tokens ALREADY stored.
    Returns (logits, updates) with k_new/v_new (L, B, Hkv, hd) — placement
    stays the memory pool's job (PagedKVCache.write_tokens).

    k_scale_pool/v_scale_pool: the int8 pool's fp32 per-token scale sidecars
    (L, Hkv, num_blocks, 1, block_size), threaded per layer next to the value
    pools so dequantization fuses into the attention kernels (no dense
    dequantized slab on this path — the tentpole invariant).
    """
    if cfg.family not in ("dense", "vlm", "moe"):
        raise ValueError("paged decode serves KV-cache dense stacks; "
                         f"got family={cfg.family}")
    if isinstance(params["layers"], (list, tuple)):
        raise ValueError("paged decode requires stacked layer params "
                         "(per-layer buffer layout uses the dense path)")
    cur_len = cache_len
    x = jnp.take(params["embed"], tokens[:, None], axis=0)
    if cfg.tie_embeddings:
        x = x * jnp.asarray(jnp.sqrt(float(cfg.d_model)), x.dtype)

    pair = 2 if cfg.local_global else 1
    quant = k_scale_pool is not None
    # the scan xs keep a 5-tuple structure either way (dummy per-layer
    # zeros when bf16) so chunked/unchunked programs share one tree shape
    ks_, vs_ = (k_scale_pool, v_scale_pool) if quant else (
        jnp.zeros((k_pool.shape[0],)), jnp.zeros((k_pool.shape[0],)))
    layers, kp, vp = params["layers"], k_pool, v_pool
    if pair == 2:
        layers, kp, vp, ks_, vs_ = jax.tree.map(
            lambda a: a.reshape((a.shape[0] // 2, 2) + a.shape[1:]),
            (layers, kp, vp, ks_, vs_))

    def body(carry, xs):
        h, aux = carry
        layer_p, kp_l, vp_l, ks_l, vs_l = xs
        new_kv = []
        for j in range(pair):
            p = _tree_index(layer_p, j) if pair == 2 else layer_p
            lc = {"k_pool": kp_l[j] if pair == 2 else kp_l,
                  "v_pool": vp_l[j] if pair == 2 else vp_l,
                  "block_tables": block_tables, "len": cur_len}
            if quant:
                lc["k_scale_pool"] = ks_l[j] if pair == 2 else ks_l
                lc["v_scale_pool"] = vs_l[j] if pair == 2 else vs_l
            is_local = (j == 0) if cfg.local_global else False
            h, c, a = blocks.dense_block(
                p, cfg, h, mode="decode", is_local=is_local, cache=lc,
                backend=backend, moe_group_size=moe_group_size)
            new_kv.append(c)
            aux = aux + a
        ys = jax.tree.map(lambda *c: jnp.stack(c), *new_kv) if pair == 2 \
            else new_kv[0]
        return (h, aux), ys

    (x, _), kv = jax.lax.scan(body, (x, jnp.zeros((), jnp.float32)),
                              (layers, kp, vp, ks_, vs_),
                              unroll=cfg.lower_unrolled)
    if pair == 2:
        kv = jax.tree.map(
            lambda a: a.reshape((a.shape[0] * 2,) + a.shape[2:]), kv)
    updates = {"k_new": kv["k_new"], "v_new": kv["v_new"],
               "len": cur_len + 1}
    logits = _head(params, cfg, x[:, 0])
    return logits, updates


def _decode_step_listed(params, cfg: ModelConfig, x, cache, cur_len,
                        new_cache, *, backend: str, moe_group_size: int):
    """Decode with per-layer buffer layout (see _dense_stack docstring)."""
    fam = cfg.family
    if fam in ("dense", "vlm", "moe"):
        k_new, v_new = [], []
        for i, p in enumerate(params["layers"]):
            is_local = (i % 2 == 0) if cfg.local_global else False
            lc = {"k": cache["k"][i], "v": cache["v"][i], "len": cur_len}
            if cfg.kv_cache_bits == 8:
                lc["k_scale"] = cache["k_scale"][i]
                lc["v_scale"] = cache["v_scale"][i]
            x, c, _ = blocks.dense_block(
                p, cfg, x, mode="decode", is_local=is_local,
                cache=lc, backend=backend, moe_group_size=moe_group_size)
            k_new.append(c["k_new"])
            v_new.append(c["v_new"])
        new_cache["k_new"], new_cache["v_new"] = k_new, v_new
    elif fam == "ssm":
        states = []
        for i, p in enumerate(params["layers"]):
            st = {key: cache[key][i] for key in ("S", "x_tm", "x_cm")}
            x, new_st = blocks.rwkv_block(p, cfg, x, mode="decode", state=st)
            states.append(new_st)
        for key in ("S", "x_tm", "x_cm"):
            new_cache[key] = [s[key] for s in states]
    elif fam == "hybrid":
        n_super, period, tail = _zamba_split(cfg)
        k_new, v_new, hs, convs = [], [], [], []
        for si, sup in enumerate(params["layers"]):
            x, c, _ = blocks.dense_block(
                params["shared_attn"], cfg, x, mode="decode",
                cache={"k": cache["k"][si], "v": cache["v"][si],
                       "len": cur_len}, backend=backend)
            k_new.append(c["k_new"])
            v_new.append(c["v_new"])
            sup_h, sup_conv = [], []
            for mi, mp in enumerate(sup):
                x, st = blocks.mamba_block(
                    mp, cfg, x, mode="decode",
                    state={"h": cache["h"][si][mi],
                           "conv": cache["conv"][si][mi]})
                sup_h.append(st["h"])
                sup_conv.append(st["conv"])
            hs.append(sup_h)
            convs.append(sup_conv)
        new_cache.update({"k_new": k_new, "v_new": v_new, "h": hs,
                          "conv": convs})
        tail_h, tail_conv = [], []
        for i, mp in enumerate(params.get("tail", []) if tail else []):
            x, st = blocks.mamba_block(
                mp, cfg, x, mode="decode",
                state={"h": cache["tail_h"][i],
                       "conv": cache["tail_conv"][i]})
            tail_h.append(st["h"])
            tail_conv.append(st["conv"])
        if tail:
            new_cache["tail_h"], new_cache["tail_conv"] = tail_h, tail_conv
    elif fam == "audio":
        k_new, v_new = [], []
        for i, p in enumerate(params["layers"]):
            x, c = blocks.decoder_block(
                p, cfg, x, (cache["ck"][i], cache["cv"][i]), mode="decode",
                cache={"k": cache["k"][i], "v": cache["v"][i],
                       "len": cur_len}, backend=backend)
            k_new.append(c["k_new"])
            v_new.append(c["v_new"])
        new_cache["k_new"], new_cache["v_new"] = k_new, v_new
    else:
        raise ValueError(fam)
    logits = _head(params, cfg, x[:, 0])
    return logits, new_cache


def apply_decode_updates(cache: Dict, updates: Dict) -> Dict:
    """Write the step's k_new/v_new into the dense cache at the old length
    and adopt refreshed recurrent state — the host-side placement used by
    simple generation loops and tests (serving engines use the paged pool)."""
    new_cache = dict(cache)
    if "k_new" in updates:
        B = updates["k_new"].shape[1]
        idx = cache["len"]  # position of the token just processed
        b = jnp.arange(B)
        # head-major cache (L, B, Hkv, S, hd): write one S-position per seq
        kn = jnp.swapaxes(updates["k_new"], 0, 1)  # (B, L, Hkv, hd)
        vn = jnp.swapaxes(updates["v_new"], 0, 1)
        if cache["k"].dtype == jnp.int8:
            from repro.models import kv_quant
            kn, kns = kv_quant.quantize_token(kn)
            vn, vns = kv_quant.quantize_token(vn)
            new_cache["k_scale"] = cache["k_scale"].at[:, b, :, idx].set(kns)
            new_cache["v_scale"] = cache["v_scale"].at[:, b, :, idx].set(vns)
        new_cache["k"] = cache["k"].at[:, b, :, idx].set(kn)
        new_cache["v"] = cache["v"].at[:, b, :, idx].set(vn)
    for key, val in updates.items():
        if key not in ("k_new", "v_new"):
            new_cache[key] = val
    return new_cache
