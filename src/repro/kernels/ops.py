"""jit'd dispatch wrappers around the Pallas kernels.

Each call picks the mode from the default backend (:func:`interpret_mode`):
on the CPU the kernels run with interpret=True, on the TPU the same call
sites compile the Mosaic kernels. Importing this module touches no backend.
``repro.models.attention`` registers the decode kernel as the "pallas"
backend so any model's serve path can switch with
``EngineConfig.decode_backend``.
"""
from __future__ import annotations


import jax
import jax.numpy as jnp

from repro.kernels import decode_attention as _da
from repro.kernels import paged_decode_attention as _pda
from repro.kernels import paged_prefill_attention as _ppa
from repro.kernels import rwkv6_scan as _rw
from repro.kernels import ssm_decode_update as _ssd
from repro.kernels import ssm_scan as _ssm


def interpret_mode() -> bool:
    """True on the CPU (Pallas interpreter), False on the TPU (Mosaic).
    Any other platform is an error: the kernels are written for the TPU,
    and silently interpreting them elsewhere would hide that."""
    platform = jax.default_backend()
    if platform not in ("cpu", "tpu"):
        raise RuntimeError(
            f"Pallas kernels run on the TPU, or interpreted on the CPU; the "
            f"default backend is {platform!r}")
    return platform == "cpu"


def decode_attention(q, k_cache, v_cache, cache_len, *, block_k: int = 512,
                     sliding_window: int = 0, logit_softcap: float = 0.0):
    """q: (B, H, hd); caches HEAD-MAJOR (B, Hkv, S, hd) (kernels/ref.py)."""
    B, H, hd = q.shape
    Hkv = k_cache.shape[1]
    G = H // Hkv
    qg = q.reshape(B, Hkv, G, hd)
    out = _da.decode_attention(qg, k_cache, v_cache, cache_len,
                               block_k=block_k, sliding_window=sliding_window,
                               logit_softcap=logit_softcap,
                               interpret=interpret_mode())
    return out.reshape(B, H, hd)


def paged_prefill_chunk_attention(q, k_pool, v_pool, block_table,
                                  k_chunk, v_chunk, *, backend: str = "jnp",
                                  k_scale=None, v_scale=None,
                                  sliding_window: int = 0,
                                  attention_sinks: int = 0,
                                  logit_softcap: float = 0.0):
    """Paged-context chunk-prefill attention — backend dispatch.

    One prefill chunk's queries ``q (C, H, hd)`` (positions [P, P+C), with
    P = len(block_table)·block_size tokens already written to the pool)
    attend over the prefix pool blocks plus the in-chunk causal mask
    (``k_chunk/v_chunk (C, Hkv, hd)`` are this chunk's freshly projected
    K/V). 'pallas' streams the prefix HBM→VMEM through the block table in
    place — peak context memory O(block); 'jnp' is the gather reference
    whose math is bit-identical to the corresponding rows of a one-shot
    prefill (the serving engines' default path — see
    ``kernels/paged_prefill_attention.py``)."""
    kw = dict(k_scale=k_scale, v_scale=v_scale,
              sliding_window=sliding_window, attention_sinks=attention_sinks,
              logit_softcap=logit_softcap)
    if backend == "pallas":
        return _ppa.paged_prefill_chunk_attention(
            q, k_pool, v_pool, block_table, k_chunk, v_chunk,
            interpret=interpret_mode(), **kw)
    return _ppa.paged_prefill_chunk_attention_jnp(
        q, k_pool, v_pool, block_table, k_chunk, v_chunk, **kw)


def rwkv6_scan(r, k, v, w, u, *, chunk: int = 128):
    return _rw.rwkv6_scan(r, k, v, w, u, chunk=chunk,
                          interpret=interpret_mode())


def ssm_scan(x, B_in, C_in, decay, *, chunk: int = 128):
    """x (B, S, H, P) dt-scaled; B_in/C_in (B, S, G, N) by group; decay
    (B, S, H). y from a zero state."""
    y, _ = _ssm.ssm_chunk_scan(x, jnp.log(decay), B_in, C_in, chunk=chunk,
                               interpret=interpret_mode())
    return y


def ssm_chunk_scan(xdt, log_decay, B_in, C_in, state0=None, *,
                   chunk: int = 128):
    return _ssm.ssm_chunk_scan(xdt, log_decay, B_in, C_in, state0,
                               chunk=chunk, interpret=interpret_mode())


def ssm_decode_update(state, slots, xdt, decay, B_in, C_in, *, layer: int):
    return _ssd.ssm_decode_update(state, slots, xdt, decay, B_in, C_in,
                                  layer=layer, interpret=interpret_mode())


# --- register the Pallas decode backend with the model layer --------------
def _serving_window(sliding_window: int, attention_sinks: int, cache_len):
    """Map the model-layer window contract (anchored to total length
    cache_len + 1 — the incoming token counts) onto the kernels' (anchored
    to cache_len): the kernel window shrinks by one. sliding_window == 1
    covers ONLY the incoming token, which the kernels cannot express as a
    window (0 means "no window"), so the stored prefix is clamped to the
    always-attendable sinks instead. Returns (kernel_sw, kernel_sinks,
    kernel_cache_len)."""
    if sliding_window == 1:
        return 0, 0, jnp.minimum(cache_len, attention_sinks)
    sw = max(sliding_window - 1, 0) if sliding_window > 0 else 0
    return sw, attention_sinks, cache_len


def _triple_to_partial(o, l, m, B, H, hd):
    from repro.core.combine import Partial

    return Partial(a=o.astype(jnp.float32).reshape(B, H, hd) *
                   l.reshape(B, H)[..., None],
                   s=l.reshape(B, H), m=m.reshape(B, H))


def _pallas_decode_partial_backend(q, k_cache, v_cache, cache_len, *,
                                   sliding_window: int = 0,
                                   attention_sinks: int = 0,
                                   logit_softcap: float = 0.0):
    """Partial triple over the cached prefix (model-layer backend contract:
    cache_len = stored tokens, window is w.r.t. total length cache_len+1)."""
    B, H, hd = q.shape
    Hkv = k_cache.shape[1]  # head-major cache (B, Hkv, S, hd)
    G = H // Hkv
    qg = q.reshape(B, Hkv, G, hd)
    sw, sinks, clen = _serving_window(sliding_window, attention_sinks,
                                      cache_len)
    o, l, m = _da.decode_attention(
        qg, k_cache, v_cache, clen, sliding_window=sw,
        attention_sinks=sinks, logit_softcap=logit_softcap,
        interpret=interpret_mode(), return_partials=True)
    return _triple_to_partial(o, l, m, B, H, hd)


def _pallas_paged_decode_partial_backend(q, k_pool, v_pool, block_tables,
                                         cache_len, *,
                                         k_scale=None, v_scale=None,
                                         sliding_window: int = 0,
                                         attention_sinks: int = 0,
                                         logit_softcap: float = 0.0):
    """Paged partial triple over the block pool (same backend contract as
    the dense variant: cache_len = stored tokens, window w.r.t. total length
    cache_len+1) — the serving engines' TPU hot path."""
    B, H, hd = q.shape
    Hkv = k_pool.shape[0]  # head-major pool (Hkv, num_blocks, bs, hd)
    G = H // Hkv
    qg = q.reshape(B, Hkv, G, hd)
    sw, sinks, clen = _serving_window(sliding_window, attention_sinks,
                                      cache_len)
    o, l, m = _pda.paged_decode_attention(
        qg, k_pool, v_pool, block_tables, clen,
        k_scale=k_scale, v_scale=v_scale, sliding_window=sw,
        attention_sinks=sinks, logit_softcap=logit_softcap,
        interpret=interpret_mode(), return_partials=True)
    return _triple_to_partial(o, l, m, B, H, hd)


def pallas_paged_decode_partial_pos(q, k_pool, v_pool, block_tables,
                                    block_positions, cache_len, *,
                                    k_scale=None, v_scale=None,
                                    sliding_window: int = 0,
                                    attention_sinks: int = 0,
                                    logit_softcap: float = 0.0):
    """Positions-aware paged partial for BLOCK-SHARDED local tables (same
    serving contract) — runs the kernel in place over one shard's pool
    slice; the block-partition AttentionWorkerPool's TPU hot path."""
    B, H, hd = q.shape
    Hkv = k_pool.shape[0]
    G = H // Hkv
    qg = q.reshape(B, Hkv, G, hd)
    sw, sinks, clen = _serving_window(sliding_window, attention_sinks,
                                      cache_len)
    o, l, m = _pda.paged_decode_attention(
        qg, k_pool, v_pool, block_tables, clen,
        block_positions=block_positions,
        k_scale=k_scale, v_scale=v_scale, sliding_window=sw,
        attention_sinks=sinks, logit_softcap=logit_softcap,
        interpret=interpret_mode(), return_partials=True)
    return _triple_to_partial(o, l, m, B, H, hd)


def register():
    from repro.models.attention import (register_decode_backend,
                                        register_paged_decode_backend)
    register_decode_backend("pallas", _pallas_decode_partial_backend)
    register_paged_decode_backend("pallas", _pallas_paged_decode_partial_backend)


register()
