"""GQA attention: blockwise (flash-style) training/prefill path and a cached
single-token decode path.

The blockwise path carries running ``(max, denom, acc)`` statistics across KV
chunks — the same partial-softmax combine identity the paper exploits in
§4.2.2 (``core/combine.py``) and that the Pallas decode kernel uses on-chip.
Supports: causal masking, sliding windows (gemma2 local layers, llama3
sliding-window variant) and attention-logit soft-capping.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models.common import ModelConfig, apply_rope, dense_init

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# Parameters
# ---------------------------------------------------------------------------
def init_attention(key, cfg: ModelConfig, dtype=None) -> Dict[str, jax.Array]:
    dtype = dtype or cfg.dtype
    hd = cfg.resolved_head_dim
    kq, kk, kv, ko = jax.random.split(key, 4)
    params = {
        "wq": dense_init(kq, (cfg.d_model, cfg.num_heads, hd), dtype),
        "wk": dense_init(kk, (cfg.d_model, cfg.num_kv_heads, hd), dtype),
        "wv": dense_init(kv, (cfg.d_model, cfg.num_kv_heads, hd), dtype),
        "wo": dense_init(ko, (cfg.num_heads, hd, cfg.d_model), dtype),
    }
    if cfg.qk_norm:
        params["q_norm"] = jnp.zeros((hd,), dtype)
        params["k_norm"] = jnp.zeros((hd,), dtype)
    return params


def qkv_project(params, cfg: ModelConfig, x: jax.Array,
                positions: jax.Array) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """x: (B, S, d) -> q (B,S,H,hd), k/v (B,S,Hkv,hd) with RoPE applied
    (none where ``rope_theta`` is 0)."""
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, params["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, params["wv"])
    if "q_norm" in params:
        from repro.models.common import rms_norm
        q = rms_norm(q, params["q_norm"])
        k = rms_norm(k, params["k_norm"])
    if cfg.rope_theta:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def out_project(params, attn_out: jax.Array) -> jax.Array:
    return jnp.einsum("bshk,hkd->bsd", attn_out, params["wo"])


# ---------------------------------------------------------------------------
# Blockwise (flash-style) attention over a full sequence
# ---------------------------------------------------------------------------
def _expand_kv(k: jax.Array, group: int) -> jax.Array:
    """(B,S,Hkv,hd) -> (B,S,H,hd) by repeating each KV head `group` times."""
    if group == 1:
        return k
    return jnp.repeat(k, group, axis=2)


def blockwise_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    sliding_window: int = 0,
    attention_sinks: int = 0,
    logit_softcap: float = 0.0,
    q_positions: Optional[jax.Array] = None,
    kv_positions: Optional[jax.Array] = None,
    block_size: int = 512,
    unroll: bool = False,
) -> jax.Array:
    """Memory-O(S·block) attention via lax.scan over KV blocks.

    q: (B, Sq, H, hd); k, v: (B, Skv, Hkv, hd). Returns (B, Sq, H, hd).
    Uses the running-softmax combine: for each new KV block the partial
    numerator/denominator are merged exactly as in core/combine.py.
    """
    B, Sq, H, hd = q.shape
    Skv = k.shape[1]
    group = H // k.shape[2]
    k = _expand_kv(k, group)
    v = _expand_kv(v, group)
    if q_positions is None:
        q_positions = jnp.arange(Sq)[None, :].repeat(B, 0)
    if kv_positions is None:
        kv_positions = jnp.arange(Skv)[None, :].repeat(B, 0)

    nb = -(-Skv // block_size)
    pad = nb * block_size - Skv
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        kv_positions = jnp.pad(kv_positions, ((0, 0), (0, pad)),
                               constant_values=-1)
    k = k.reshape(B, nb, block_size, H, hd).transpose(1, 0, 2, 3, 4)
    v = v.reshape(B, nb, block_size, H, hd).transpose(1, 0, 2, 3, 4)
    kv_positions = kv_positions.reshape(B, nb, block_size).transpose(1, 0, 2)

    scale = 1.0 / jnp.sqrt(jnp.asarray(hd, jnp.float32))
    qf = q.astype(jnp.float32) * scale

    def step(carry, blk):
        m, l, acc = carry
        kb, vb, posb = blk  # (B, bs, H, hd), (B, bs)
        s = jnp.einsum("bqhk,bjhk->bhqj", qf, kb.astype(jnp.float32))
        if logit_softcap > 0.0:
            s = logit_softcap * jnp.tanh(s / logit_softcap)
        valid = posb[:, None, None, :] >= 0  # (B,1,1,bs)
        if causal:
            valid &= posb[:, None, None, :] <= q_positions[:, None, :, None]
        if sliding_window > 0:
            in_window = posb[:, None, None, :] > (
                q_positions[:, None, :, None] - sliding_window)
            if attention_sinks > 0:  # StreamingLLM: sinks stay attendable
                in_window |= posb[:, None, None, :] < attention_sinks
            valid &= in_window
        s = jnp.where(valid, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        # rescale previous partials to the new max (combine identity)
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[..., None])
        l_new = l * alpha + jnp.sum(p, axis=-1)
        acc_new = acc * alpha[..., None] + jnp.einsum(
            "bhqj,bjhk->bhqk", p, vb.astype(jnp.float32))
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((B, H, Sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, H, Sq), jnp.float32)
    acc0 = jnp.zeros((B, H, Sq, hd), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(step, (m0, l0, acc0),
                                  (k, v, kv_positions), unroll=unroll)
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.transpose(0, 2, 1, 3).astype(q.dtype)  # (B, Sq, H, hd)


# ---------------------------------------------------------------------------
# Single-token decode with KV cache
# ---------------------------------------------------------------------------
# Backends compute the PARTIAL triple (a, s, m) over the *cached* tokens only
# (positions [0, cache_len)); the new token's k/v never touch the cache
# inside the step — its 1-token partial is merged with the paper-§4.2.2
# combine identity. This keeps the lowered serve_step free of cross-shard
# scatters into the sequence-sharded cache (which force involuntary full
# rematerialisation in GSPMD) and mirrors Lamina's ownership split: the
# memory pool places KV, the model program only reads it.
# 'jnp' is the oracle backend; 'pallas' (repro/kernels/ops.py) the TPU kernel.
# Each name has two registrations: the dense-cache partial (B, Hkv, S, hd)
# and the PAGED partial that attends over the serving engines' block pool
# (Hkv, num_blocks, block_size, hd) through a (B, nb) block table — the
# default decode hot path (no per-step dense gather).
_DECODE_BACKENDS = {}
_PAGED_DECODE_BACKENDS = {}


def register_decode_backend(name: str, fn) -> None:
    _DECODE_BACKENDS[name] = fn


def register_paged_decode_backend(name: str, fn) -> None:
    _PAGED_DECODE_BACKENDS[name] = fn


def decode_attention_partial_jnp(q, k_cache, v_cache, cache_len, *,
                                 sliding_window: int = 0,
                                 attention_sinks: int = 0,
                                 logit_softcap: float = 0.0,
                                 k_scale=None, v_scale=None,
                                 positions=None, window_total=None):
    """Partial attention over the cached prefix.

    q: (B, H, hd) (RoPE applied); caches: HEAD-MAJOR (B, Hkv, S, hd);
    cache_len: (B,) = number of tokens stored (the new token is NOT there).
    Window masks are computed w.r.t. total length cache_len + 1.
    Returns core.combine.Partial with fields shaped (B, H, hd)/(B, H).

    positions: optional (B, S) global sequence position per cache slot —
    block-sharded callers hold a NON-CONTIGUOUS subset of the sequence, so
    slot index ≠ position (foreign slots carry the POS_PAD sentinel and mask
    out). window_total: optional (B,) total length the sliding window is
    anchored to (defaults to cache_len + 1, the serving contract; the
    shard_map backends anchor to cache_len to match the dense oracle).

    §Perf iterations 1+3: the einsums contract the head-major cache in its
    native layout with fp32 accumulation via preferred_element_type — no
    cache-sized transposes/copies (XLA materialised four of them per layer
    in the original (B,S,Hkv,hd) layout) and no materialised fp32 KV cast.
    See EXPERIMENTS.md §Perf.
    """
    from repro.core import combine as C

    B, H, hd = q.shape
    Hkv, S = k_cache.shape[1], k_cache.shape[2]
    G = H // Hkv
    qg = q.reshape(B, Hkv, G, hd)
    scale = 1.0 / jnp.sqrt(jnp.asarray(hd, jnp.float32))
    kc = k_cache.astype(q.dtype) if k_cache.dtype == jnp.int8 else k_cache
    s = jnp.einsum("bhgk,bhsk->bhgs", (qg.astype(jnp.float32) * scale
                                       ).astype(q.dtype), kc,
                   preferred_element_type=jnp.float32)  # (B, Hkv, G, S) f32
    if k_scale is not None:  # int8 KV: fold per-token scales into scores
        s = s * k_scale[:, :, None, :]
    if logit_softcap > 0.0:
        s = logit_softcap * jnp.tanh(s / logit_softcap)
    pos = jnp.arange(S)[None, :] if positions is None else positions
    total = cache_len + 1 if window_total is None else window_total
    valid = pos < cache_len[:, None]
    if sliding_window > 0:
        in_window = pos >= (total[:, None] - sliding_window)
        if attention_sinks > 0:
            in_window |= pos < attention_sinks
        valid &= in_window
    s = jnp.where(valid[:, None, None, :], s, -jnp.inf)
    m = jnp.max(s, axis=-1)
    m_safe = jnp.where(jnp.isfinite(m), m, 0.0)
    p = jnp.where(jnp.isfinite(s), jnp.exp(s - m_safe[..., None]), 0.0)
    denom = jnp.sum(p, axis=-1)
    if v_scale is not None:  # int8 KV: fold per-token scales into weights
        pv = (p * v_scale[:, :, None, :]).astype(q.dtype)
        vc = v_cache.astype(q.dtype)
    else:
        pv = p.astype(v_cache.dtype)
        vc = v_cache
    a = jnp.einsum("bhgs,bhsk->bhgk", pv, vc,
                   preferred_element_type=jnp.float32)
    return C.Partial(a=a.reshape(B, H, hd).astype(jnp.float32),
                     s=denom.reshape(B, H),
                     m=jnp.where(jnp.isfinite(m), m,
                                 -jnp.inf).reshape(B, H))


register_decode_backend("jnp", decode_attention_partial_jnp)


def paged_decode_attention_partial_jnp(q, k_pool, v_pool, block_tables,
                                       cache_len, *,
                                       k_scale=None, v_scale=None,
                                       sliding_window: int = 0,
                                       attention_sinks: int = 0,
                                       logit_softcap: float = 0.0):
    """Paged partial over the block pool — jnp reference path (CPU tests).

    q: (B, H, hd); pools HEAD-MAJOR (Hkv, num_blocks, block_size, hd);
    block_tables: (B, nb) int32; cache_len: (B,) stored tokens. Gathers the
    dense head-major view through the table (the copy the Pallas kernel
    avoids) and reuses the dense partial math, so 'jnp' and 'pallas' paged
    backends are bit-comparable. k_scale/v_scale: optional
    (Hkv, num_blocks, 1, block_size) fp32 scale pools for int8 k_pool/v_pool —
    gathered through the same table and folded into the score/PV einsums
    (the dense reference may gather; only the kernels are bound by the
    no-dense-dequant invariant)."""
    from repro.kernels.paged_decode_attention import (paged_gather_dense,
                                                      paged_gather_scales)

    kc, vc = paged_gather_dense(k_pool, v_pool, block_tables)
    kw = {}
    if k_scale is not None:
        kw = {"k_scale": paged_gather_scales(k_scale, block_tables),
              "v_scale": paged_gather_scales(v_scale, block_tables)}
    return decode_attention_partial_jnp(
        q, kc, vc, cache_len, sliding_window=sliding_window,
        attention_sinks=attention_sinks, logit_softcap=logit_softcap, **kw)


register_paged_decode_backend("jnp", paged_decode_attention_partial_jnp)


def paged_decode_attention_partial_pos_jnp(q, k_pool, v_pool, block_tables,
                                           block_positions, cache_len, *,
                                           k_scale=None, v_scale=None,
                                           window_total=None,
                                           sliding_window: int = 0,
                                           attention_sinks: int = 0,
                                           logit_softcap: float = 0.0):
    """Positions-aware paged partial for BLOCK-SHARDED tables (jnp path).

    One shard of a cross-chip sequence split holds a non-contiguous subset of
    the sequence's blocks: block_tables (B, nb) are the shard's LOCAL pool
    ids and block_positions (B, nb) each slot's global base position (POS_PAD
    on slots the shard does not own, so they mask out entirely). A shard with
    zero live blocks yields the empty partial (s = 0, m = -inf) — the §4.2.2
    combine identity. window_total as in decode_attention_partial_jnp."""
    from repro.kernels.paged_decode_attention import paged_gather_dense

    B, nb = block_tables.shape
    bs = k_pool.shape[2]
    kc, vc = paged_gather_dense(k_pool, v_pool, block_tables)
    pos = (block_positions[:, :, None] +
           jnp.arange(bs, dtype=jnp.int32)[None, None, :]).reshape(B, nb * bs)
    kw = {}
    if k_scale is not None:
        from repro.kernels.paged_decode_attention import paged_gather_scales
        kw = {"k_scale": paged_gather_scales(k_scale, block_tables),
              "v_scale": paged_gather_scales(v_scale, block_tables)}
    return decode_attention_partial_jnp(
        q, kc, vc, cache_len, sliding_window=sliding_window,
        attention_sinks=attention_sinks, logit_softcap=logit_softcap,
        positions=pos, window_total=window_total, **kw)


def paged_decode_attention_partial_pos(q, k_pool, v_pool, block_tables,
                                       block_positions, cache_len, *,
                                       backend: str = "jnp",
                                       k_scale=None, v_scale=None,
                                       sliding_window: int = 0,
                                       attention_sinks: int = 0,
                                       logit_softcap: float = 0.0):
    """Backend dispatch for the positions-aware paged partial (serving
    contract: window anchored to cache_len + 1). 'pallas' streams the
    shard's pool slice through the paged kernel in place — no gather;
    'jnp' is the CPU gather reference. k_scale/v_scale: optional int8-pool
    scale pools, fused in-kernel on 'pallas' (no dense dequant)."""
    kw = dict(k_scale=k_scale, v_scale=v_scale,
              sliding_window=sliding_window, attention_sinks=attention_sinks,
              logit_softcap=logit_softcap)
    if backend == "pallas":
        from repro.kernels import ops
        return ops.pallas_paged_decode_partial_pos(
            q, k_pool, v_pool, block_tables, block_positions, cache_len, **kw)
    return paged_decode_attention_partial_pos_jnp(
        q, k_pool, v_pool, block_tables, block_positions, cache_len, **kw)


def _new_token_partial(q, k_new, v_new, *, logit_softcap: float = 0.0):
    """The freshly projected token's 1-token §4.2.2 partial (B, H, ·)."""
    from repro.core import combine as C

    B, H, hd = q.shape
    Hkv = k_new.shape[1]
    G = H // Hkv
    qg = q.reshape(B, Hkv, G, hd)
    p_new = C.partial_attention(qg, k_new[:, :, None, None],
                                v_new[:, :, None, None],
                                logit_softcap=logit_softcap)
    return C.Partial(a=p_new.a.reshape(B, H, hd),
                     s=p_new.s.reshape(B, H), m=p_new.m.reshape(B, H))


def paged_decode_attention_combine(q, k_pool, v_pool, block_tables,
                                   cache_len, k_new, v_new, *,
                                   backend: str = "jnp",
                                   k_scale=None, v_scale=None,
                                   sliding_window: int = 0,
                                   attention_sinks: int = 0,
                                   logit_softcap: float = 0.0) -> jax.Array:
    """Full paged decode attention = combine(pool partial, new-token partial).

    The pool is read in place through the block table — the decode step's KV
    traffic is exactly one pass over the live KV (paper §3's memory-bound
    operand) plus the wire-delivered k_new/v_new (B, Hkv, hd)."""
    from repro.core import combine as C

    if backend not in _PAGED_DECODE_BACKENDS and backend == "pallas":
        import repro.kernels.ops  # noqa: F401 — registers the kernel backend

    kw = {}
    if k_scale is not None:
        kw = {"k_scale": k_scale, "v_scale": v_scale}
    p_prev = _PAGED_DECODE_BACKENDS[backend](
        q, k_pool, v_pool, block_tables, cache_len,
        sliding_window=sliding_window, attention_sinks=attention_sinks,
        logit_softcap=logit_softcap, **kw)
    p_new = _new_token_partial(q, k_new, v_new, logit_softcap=logit_softcap)
    return C.finalize(C.combine(p_prev, p_new)).astype(q.dtype)


def decode_attention_combine(q, k_cache, v_cache, cache_len, k_new, v_new, *,
                             backend: str = "jnp", sliding_window: int = 0,
                             attention_sinks: int = 0,
                             logit_softcap: float = 0.0,
                             k_scale=None, v_scale=None) -> jax.Array:
    """Full decode attention = combine(prefix partial, new-token partial).

    k_new/v_new: (B, Hkv, hd) — the current token's keys/values."""
    from repro.core import combine as C

    if backend not in _DECODE_BACKENDS and backend == "pallas":
        import repro.kernels.ops  # noqa: F401 — registers the kernel backend

    kw = {}
    if k_scale is not None:
        kw = {"k_scale": k_scale, "v_scale": v_scale}
    p_prev = _DECODE_BACKENDS[backend](
        q, k_cache, v_cache, cache_len, sliding_window=sliding_window,
        attention_sinks=attention_sinks, logit_softcap=logit_softcap, **kw)
    p_new = _new_token_partial(q, k_new, v_new, logit_softcap=logit_softcap)
    return C.finalize(C.combine(p_prev, p_new)).astype(q.dtype)


def decode_attention_jnp(q, k_cache, v_cache, cache_len, *,
                         sliding_window: int = 0,
                         logit_softcap: float = 0.0) -> jax.Array:
    """Legacy oracle: cache ALREADY contains the new token at cache_len-1.
    Kept for kernel sweeps and the attention_parallel shard_map paths."""
    B, H, hd = q.shape
    S = k_cache.shape[1]
    group = H // k_cache.shape[2]
    kc = _expand_kv(k_cache, group).astype(jnp.float32)
    vc = _expand_kv(v_cache, group).astype(jnp.float32)
    scale = 1.0 / jnp.sqrt(jnp.asarray(hd, jnp.float32))
    s = jnp.einsum("bhk,bjhk->bhj", q.astype(jnp.float32) * scale, kc)
    if logit_softcap > 0.0:
        s = logit_softcap * jnp.tanh(s / logit_softcap)
    pos = jnp.arange(S)[None, :]
    valid = pos < cache_len[:, None]
    if sliding_window > 0:
        valid &= pos >= (cache_len[:, None] - sliding_window)
    s = jnp.where(valid[:, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhj,bjhk->bhk", p, vc)
    return out.astype(q.dtype)


# ---------------------------------------------------------------------------
# Full layer entry points
# ---------------------------------------------------------------------------
def attention_forward(params, cfg: ModelConfig, x: jax.Array,
                      positions: jax.Array, *, is_local: bool = False,
                      block_size: int = 512,
                      prefix_kv: Optional[Tuple[jax.Array, jax.Array]] = None,
                      paged_prefix: Optional[Tuple[jax.Array, jax.Array,
                                                   jax.Array]] = None,
                      paged_prefix_scales: Optional[Tuple[jax.Array,
                                                          jax.Array]] = None,
                      backend: str = "jnp") -> jax.Array:
    """Full-sequence attention (train / prefill). x: (B, S, d).

    ``is_local`` is STATIC: alternating local/global stacks (gemma2) scan over
    layer *pairs* so each variant is traced once with its own static window.

    ``prefix_kv``: optional HEAD-MAJOR (B, Hkv, P, hd) K/V of an already-
    cached prompt prefix (the prefix-sharing suffix prefill). `x` then holds
    only the suffix tokens and `positions` their global positions (P + i);
    queries attend over concat(prefix, suffix) keys. Because every softmax
    row is computed over the same keys in the same scan order as a full
    prefill, suffix outputs are BIT-IDENTICAL to the corresponding rows of
    the unsliced prefill. Returned k/v cover the suffix only.

    ``paged_prefix``: the PAGED form of the same contract — this layer's
    ``(k_pool, v_pool, block_table)``: head-major pool slices
    (Hkv, num_blocks, bs, hd) plus the sequence's first ``nb`` block ids
    (P = nb·bs). Requires B == 1 (the serving prefill shape). With
    ``backend="pallas"`` the prefix is streamed straight from the pool
    (``ops.paged_prefill_chunk_attention`` — no dense gather); the jnp
    backend gathers this one layer's prefix dense (the reference copy) and
    falls into the ``prefix_kv`` concat path, staying bit-identical to the
    one-shot prefill. Mutually exclusive with ``prefix_kv``."""
    q, k, v = qkv_project(params, cfg, x, positions)
    window = cfg.sliding_window if (is_local or not cfg.local_global) else 0
    if paged_prefix is not None:
        assert prefix_kv is None, "pass prefix_kv OR paged_prefix, not both"
        if x.shape[0] != 1:
            raise ValueError("paged_prefix serves the per-request prefill "
                             f"shape (B == 1); got B={x.shape[0]}")
        kp_pool, vp_pool, table = paged_prefix
        ks_pool = vs_pool = None
        if paged_prefix_scales is not None:
            ks_pool, vs_pool = paged_prefix_scales
        if backend == "pallas":
            from repro.kernels import ops
            out = ops.paged_prefill_chunk_attention(
                q[0], kp_pool, vp_pool, table, k[0], v[0], backend="pallas",
                k_scale=ks_pool, v_scale=vs_pool,
                sliding_window=int(window),
                attention_sinks=cfg.attention_sinks if window else 0,
                logit_softcap=cfg.attn_logit_softcap)[None]
            return out_project(params, out), k, v
        Hkv, _, bs, hd = kp_pool.shape
        P = table.shape[0] * bs
        kp_d = kp_pool[:, table].reshape(Hkv, P, hd)
        vp_d = vp_pool[:, table].reshape(Hkv, P, hd)
        if ks_pool is not None:  # int8 pool: dequantize the gathered copy
            ks_d = ks_pool[:, table].reshape(Hkv, P)
            vs_d = vs_pool[:, table].reshape(Hkv, P)
            kp_d = (kp_d.astype(jnp.float32) * ks_d[..., None]).astype(k.dtype)
            vp_d = (vp_d.astype(jnp.float32) * vs_d[..., None]).astype(v.dtype)
        prefix_kv = (kp_d[None], vp_d[None])
    k_all, v_all = k, v
    if prefix_kv is not None:
        pk, pv = prefix_kv           # head-major -> seq-major for blockwise
        k_all = jnp.concatenate([jnp.swapaxes(pk, 1, 2), k], axis=1)
        v_all = jnp.concatenate([jnp.swapaxes(pv, 1, 2), v], axis=1)
    # unrolled lowering (roofline cost pass) uses larger KV blocks so the
    # fully-unrolled chunk count stays small
    if cfg.lower_unrolled:
        block_size = max(block_size, x.shape[1] // 8)
    out = blockwise_attention(
        q, k_all, v_all, causal=True, sliding_window=int(window),
        attention_sinks=cfg.attention_sinks if window else 0,
        logit_softcap=cfg.attn_logit_softcap, q_positions=positions,
        block_size=block_size, unroll=cfg.lower_unrolled)
    return out_project(params, out), k, v


def attention_decode_step(params, cfg: ModelConfig, x: jax.Array,
                          k_cache: jax.Array, v_cache: jax.Array,
                          cache_len: jax.Array, *, is_local: bool = False,
                          backend: str = "jnp", k_scale=None, v_scale=None):
    """One-token decode. x: (B, 1, d); cache_len = tokens ALREADY stored.

    Returns (y, k_new, v_new) with k_new/v_new: (B, Hkv, hd) — the caller
    (serving engine / memory pool) owns KV placement; the step itself never
    scatters into the sharded cache (see module docstring + DESIGN.md §3).
    ``is_local`` is STATIC (see attention_forward)."""
    positions = cache_len[:, None]  # new token position, 0-based
    q, k, v = qkv_project(params, cfg, x, positions)
    window = cfg.sliding_window if (is_local or not cfg.local_global) else 0
    out = decode_attention_combine(
        q[:, 0], k_cache, v_cache, cache_len, k[:, 0], v[:, 0],
        backend=backend, sliding_window=int(window),
        attention_sinks=cfg.attention_sinks if window else 0,
        logit_softcap=cfg.attn_logit_softcap,
        k_scale=k_scale, v_scale=v_scale)
    y = out_project(params, out[:, None])
    return y, k[:, 0], v[:, 0]


def attention_decode_step_paged(params, cfg: ModelConfig, x: jax.Array,
                                k_pool: jax.Array, v_pool: jax.Array,
                                block_tables: jax.Array,
                                cache_len: jax.Array, *,
                                is_local: bool = False,
                                backend: str = "jnp",
                                k_scale=None, v_scale=None):
    """One-token decode straight over the paged block pool (the serving hot
    path — no dense per-step gather). x: (B, 1, d); pools HEAD-MAJOR
    (Hkv, num_blocks, block_size, hd); block_tables (B, nb);
    cache_len = tokens ALREADY stored. Returns (y, k_new, v_new) — KV
    placement stays the memory pool's job (serving/kvcache.py)."""
    positions = cache_len[:, None]  # new token position, 0-based
    q, k, v = qkv_project(params, cfg, x, positions)
    window = cfg.sliding_window if (is_local or not cfg.local_global) else 0
    out = paged_decode_attention_combine(
        q[:, 0], k_pool, v_pool, block_tables, cache_len, k[:, 0], v[:, 0],
        backend=backend, k_scale=k_scale, v_scale=v_scale,
        sliding_window=int(window),
        attention_sinks=cfg.attention_sinks if window else 0,
        logit_softcap=cfg.attn_logit_softcap)
    y = out_project(params, out[:, None])
    return y, k[:, 0], v[:, 0]
