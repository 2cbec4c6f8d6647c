"""Multi-device attention partitioning (paper §5 "Attention parallelism").

The paper distributes decode attention over a pool of memory devices either
request-level (imbalanced) or head-level (balanced, chosen by Lamina). On the
TPU mesh we express both, plus the split the §4.2.2 combine identity makes
exact — the variant that serves `long_500k` where a single request's KV
exceeds one chip. Three PAGED partitions of the serving engines' block pool:

  * head-level:    pool head axis sharded; each device owns its heads'
                   blocks wholesale; no combine (heads are independent)
  * block-level:   pool BLOCK axis sharded; a sequence's round-robin-placed
                   blocks span every device; each device computes the §4.2.2
                   partial (a, s, m) over its local blocks and psum_combine
                   merges — only the tiny triple crosses chips, never KV
  * request-level: batch/table sharded, pool replicated (the paper's
                   rejected baseline, kept for the load-imbalance benchmark)

NO-DENSIFY INVARIANT: every paged backend attends over the pool *in place*
through its (local) block table — the Pallas paged flash-decode kernel on
TPU, its head-major jnp reference on CPU. No backend gathers the pool into a
dense seq-major (B, S, Hkv, hd) slab; per-device KV traffic is exactly one
pass over that device's live blocks (Adrenaline, arXiv:2503.20552, makes the
same single-pass argument for attention-disaggregated throughput).

Dense-slab variants (seq/head/request over contiguous caches) survive below
for the non-paged kernel sweeps. All are written with ``shard_map`` so the
per-layer boundary communication is explicit — these collectives are the TPU
rendering of the paper's per-layer DCN transfers, and the dry-run's
collective roofline term measures them.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.core import combine as C


def _shard_map_norep(fn, **kw):
    """shard_map without the replication checker: pallas_call has no
    replication rule, and the paged backends may run the kernel in-shard."""
    return jax.shard_map(fn, check_vma=False, **kw)


def _masked_partial(q, k_cache, v_cache, valid, logit_softcap=0.0):
    """q: (B, H, hd); caches (B, S, Hkv, hd); valid: (B, S)."""
    B, H, hd = q.shape
    Hkv = k_cache.shape[2]
    group = H // Hkv
    qg = q.reshape(B, Hkv, group, hd)
    # scores per kv head without materialising repeated KV
    scale = 1.0 / jnp.sqrt(jnp.asarray(hd, jnp.float32))
    s = jnp.einsum("bhgk,bshk->bhgs", qg.astype(jnp.float32) * scale,
                   k_cache.astype(jnp.float32))
    if logit_softcap > 0.0:
        s = logit_softcap * jnp.tanh(s / logit_softcap)
    s = jnp.where(valid[:, None, None, :], s, -jnp.inf)
    m = jnp.max(s, axis=-1)
    m_safe = jnp.where(jnp.isfinite(m), m, 0.0)
    p = jnp.where(jnp.isfinite(s), jnp.exp(s - m_safe[..., None]), 0.0)
    denom = jnp.sum(p, axis=-1)
    a = jnp.einsum("bhgs,bshk->bhgk", p, v_cache.astype(jnp.float32))
    return C.Partial(a=a.reshape(B, H, hd), s=denom.reshape(B, H),
                     m=jnp.where(jnp.isfinite(m), m, -jnp.inf).reshape(B, H))


# ---------------------------------------------------------------------------
# Sequence-level split (partial-combine across the pool axis)
# ---------------------------------------------------------------------------
def seq_parallel_decode_attention(mesh: Mesh, axis: str, q, k_cache, v_cache,
                                  cache_len, *, sliding_window: int = 0,
                                  logit_softcap: float = 0.0,
                                  batch_axis: Optional[str] = None):
    """Decode attention with the KV sequence sharded over `axis`.

    q: (B, H, hd) replicated over `axis`; caches (B, S, Hkv, hd) with S
    sharded over `axis`; cache_len (B,). Each shard computes its partial
    (A, S, m) over its KV slice; psum_combine merges — the cross-chip form
    of paper §4.2.2.
    """
    n = mesh.shape[axis]
    S = k_cache.shape[1]
    S_shard = S // n
    bspec = P(batch_axis) if batch_axis else P()

    def shard_fn(q, kc, vc, clen):
        idx = jax.lax.axis_index(axis)
        pos = idx * S_shard + jnp.arange(S_shard)[None, :]  # global positions
        valid = pos < clen[:, None]
        if sliding_window > 0:
            valid &= pos >= (clen[:, None] - sliding_window)
        part = _masked_partial(q, kc, vc, valid, logit_softcap)
        return C.finalize(C.psum_combine(part, axis)).astype(q.dtype)

    return jax.shard_map(
        shard_fn, mesh=mesh,
        in_specs=(P(batch_axis, None, None), P(batch_axis, axis, None, None),
                  P(batch_axis, axis, None, None), bspec),
        out_specs=P(batch_axis, None, None),
    )(q, k_cache, v_cache, cache_len)


# ---------------------------------------------------------------------------
# Head-level split (the paper's choice for Lamina)
# ---------------------------------------------------------------------------
def head_parallel_decode_attention(mesh: Mesh, axis: str, q, k_cache, v_cache,
                                   cache_len, *, sliding_window: int = 0,
                                   logit_softcap: float = 0.0,
                                   batch_axis: Optional[str] = None):
    """KV heads sharded over `axis`; each device handles its heads fully.
    Requires Hkv % mesh.shape[axis] == 0 (the paper's divisibility caveat).
    """
    Hkv = k_cache.shape[2]
    n = mesh.shape[axis]
    if Hkv % n:
        raise ValueError(
            f"head-level partitioning needs kv_heads ({Hkv}) divisible by "
            f"pool size ({n}) — paper §5; use seq-level instead")
    bspec = P(batch_axis) if batch_axis else P()

    def shard_fn(q, kc, vc, clen):
        S = kc.shape[1]
        pos = jnp.arange(S)[None, :]
        valid = pos < clen[:, None]
        if sliding_window > 0:
            valid &= pos >= (clen[:, None] - sliding_window)
        part = _masked_partial(q, kc, vc, valid, logit_softcap)
        return C.finalize(part).astype(q.dtype)

    return jax.shard_map(
        shard_fn, mesh=mesh,
        in_specs=(P(batch_axis, axis, None), P(batch_axis, None, axis, None),
                  P(batch_axis, None, axis, None), bspec),
        out_specs=P(batch_axis, axis, None),
    )(q, k_cache, v_cache, cache_len)


# ---------------------------------------------------------------------------
# Paged variants: the pool-native backends. The KV operand is the serving
# engines' block pool (Hkv, num_blocks, block_size, hd) + a (B, nb) block
# table — what the paged flash-decode kernel consumes IN PLACE, in-shard.
# Head-level shards the pool's head axis (each device owns its heads' blocks
# wholesale); block-level shards the pool's BLOCK axis (a sequence spans
# devices, partials psum-combined); request-level shards the table/batch and
# replicates the pool. See the module docstring's no-densify invariant.
# ---------------------------------------------------------------------------
def _paged_shard_attend(q, kp, vp, bt, clen, *, sliding_window: int,
                        attention_sinks: int, logit_softcap: float,
                        backend: str, interpret: bool,
                        k_scale=None, v_scale=None):
    """Finalized paged attention over one device's pool slice, in place.

    q: (B, H_local, hd); kp/vp: (Hkv_local, NB, bs, hd); bt: (B, nb);
    clen: (B,). 'pallas' runs the paged flash-decode kernel; 'jnp' its
    head-major gather reference (the CPU data path). Int8 pool slices
    carry their (Hkv_local, NB, 1, bs) scale slices; dequant fuses in-shard
    inside the backend (no dense dequantized slab per device either)."""
    from repro.kernels.paged_decode_attention import (paged_decode_attention,
                                                     paged_decode_attention_jnp)

    B, H, hd = q.shape
    Hkv = kp.shape[0]
    qg = q.reshape(B, Hkv, H // Hkv, hd)
    fn = paged_decode_attention_jnp if backend == "jnp" else functools.partial(
        paged_decode_attention, interpret=interpret)
    skw = {} if k_scale is None else dict(k_scale=k_scale, v_scale=v_scale)
    out = fn(qg, kp, vp, bt, clen, sliding_window=sliding_window,
             attention_sinks=attention_sinks, logit_softcap=logit_softcap,
             **skw)
    return out.reshape(B, H, hd).astype(q.dtype)


def head_parallel_paged_decode_attention(mesh: Mesh, axis: str, q, k_pool,
                                         v_pool, block_tables, cache_len, *,
                                         sliding_window: int = 0,
                                         attention_sinks: int = 0,
                                         logit_softcap: float = 0.0,
                                         batch_axis: Optional[str] = None,
                                         backend: str = "jnp",
                                         interpret: bool = False,
                                         k_scale=None, v_scale=None):
    """Head-level split over the paged pool: each device owns Hkv/n heads of
    every pool block (pool head axis sharded over `axis`); the block table
    and lengths are replicated scalars. Each device runs the paged kernel
    (or its jnp reference) over its head slice in place — no dense view, no
    combine (heads are independent). Requires Hkv % mesh.shape[axis] == 0
    (paper §5). Int8 pools: the (Hkv, NB, 1, bs) scale pools shard with the
    same head axis as the value pools (scales-follow-blocks)."""
    Hkv = k_pool.shape[0]
    n = mesh.shape[axis]
    if Hkv % n:
        raise ValueError(
            f"head-level partitioning needs kv_heads ({Hkv}) divisible by "
            f"pool size ({n}) — paper §5; use block-level instead")
    bspec = P(batch_axis) if batch_axis else P()
    btspec = P(batch_axis, None) if batch_axis else P()
    kw = dict(sliding_window=sliding_window, attention_sinks=attention_sinks,
              logit_softcap=logit_softcap, backend=backend,
              interpret=interpret)

    def shard_fn(q, kp, vp, bt, clen, *scales):
        skw = dict(zip(("k_scale", "v_scale"), scales))
        return _paged_shard_attend(q, kp, vp, bt, clen, **kw, **skw)

    operands = [q, k_pool, v_pool, block_tables, cache_len]
    in_specs = [P(batch_axis, axis, None), P(axis, None, None, None),
                P(axis, None, None, None), btspec, bspec]
    if k_scale is not None:
        operands += [k_scale, v_scale]
        in_specs += [P(axis, None, None, None)] * 2
    return _shard_map_norep(
        shard_fn, mesh=mesh, in_specs=tuple(in_specs),
        out_specs=P(batch_axis, axis, None),
    )(*operands)


def request_parallel_paged_decode_attention(mesh: Mesh, axis: str, q, k_pool,
                                            v_pool, block_tables, cache_len,
                                            *, sliding_window: int = 0,
                                            attention_sinks: int = 0,
                                            logit_softcap: float = 0.0,
                                            backend: str = "jnp",
                                            interpret: bool = False,
                                            k_scale=None, v_scale=None):
    """Request-level split over the paged pool: the batch (q, block table,
    lengths) is sharded; the pool is replicated — each device walks only its
    requests' tables through the paged kernel (or its jnp reference), in
    place (the paper's load-imbalance baseline, pool-native). Int8 pools:
    the scale pools replicate exactly like the value pools they describe."""
    kw = dict(sliding_window=sliding_window, attention_sinks=attention_sinks,
              logit_softcap=logit_softcap, backend=backend,
              interpret=interpret)

    def shard_fn(q, kp, vp, bt, clen, *scales):
        skw = dict(zip(("k_scale", "v_scale"), scales))
        return _paged_shard_attend(q, kp, vp, bt, clen, **kw, **skw)

    operands = [q, k_pool, v_pool, block_tables, cache_len]
    in_specs = [P(axis, None, None), P(None, None, None, None),
                P(None, None, None, None), P(axis, None), P(axis)]
    if k_scale is not None:
        operands += [k_scale, v_scale]
        in_specs += [P(None, None, None, None)] * 2
    return _shard_map_norep(
        shard_fn, mesh=mesh, in_specs=tuple(in_specs),
        out_specs=P(axis, None, None),
    )(*operands)


def block_parallel_paged_decode_attention(mesh: Mesh, axis: str, q, k_pool,
                                          v_pool, shard_tables,
                                          shard_positions, cache_len, *,
                                          sliding_window: int = 0,
                                          attention_sinks: int = 0,
                                          logit_softcap: float = 0.0,
                                          backend: str = "jnp",
                                          interpret: bool = False,
                                          k_scale=None, v_scale=None):
    """Block-level split: ONE sequence's KV spans every pool device.

    The pool's block axis is sharded over `axis` (device s holds global
    blocks [s·npb, (s+1)·npb) — the PagedKVCache shard layout); q and
    cache_len are replicated. shard_tables/shard_positions (n, B, nbl) carry
    each device's LOCAL table + the global base position of every slot
    (``PagedKVCache.block_table_shards``) — positions, not slot indices,
    anchor the causal/window/sink masks because a shard's walk is
    non-contiguous in the sequence. Each device computes the §4.2.2 partial
    (a, s, m) over exactly one pass of its local live blocks — the paged
    kernel with return_partials=True, or the positions-aware jnp reference —
    and ``psum_combine`` merges exactly; only the tiny triple crosses chips,
    never KV. A device with zero live blocks for a sequence contributes the
    empty partial (s = 0, m = -inf), the combine identity. Int8 pools: the
    scale pools shard on the same BLOCK axis as the value pools — each
    device's partial dequantizes in-shard, and because dequant folds into
    the per-tile score/PV products before the combine, the psum partial
    merge is untouched (scales-follow-blocks under partitioning too)."""
    kernel_partials = backend != "jnp"

    def shard_fn(q, kp, vp, bt, bp, clen, *scales):
        from repro.kernels.ops import _triple_to_partial
        from repro.kernels.paged_decode_attention import paged_decode_attention
        from repro.models.attention import \
            paged_decode_attention_partial_pos_jnp

        skw = dict(zip(("k_scale", "v_scale"), scales))
        bt, bp = bt[0], bp[0]
        B, H, hd = q.shape
        if kernel_partials:
            Hkv = kp.shape[0]
            o, l, m = paged_decode_attention(
                q.reshape(B, Hkv, H // Hkv, hd), kp, vp, bt, clen,
                block_positions=bp, sliding_window=sliding_window,
                attention_sinks=attention_sinks, logit_softcap=logit_softcap,
                interpret=interpret, return_partials=True, **skw)
            part = _triple_to_partial(o, l, m, B, H, hd)
        else:
            part = paged_decode_attention_partial_pos_jnp(
                q, kp, vp, bt, bp, clen, window_total=clen,
                sliding_window=sliding_window,
                attention_sinks=attention_sinks, logit_softcap=logit_softcap,
                **skw)
        return C.finalize(C.psum_combine(part, axis)).astype(q.dtype)

    operands = [q, k_pool, v_pool, shard_tables, shard_positions, cache_len]
    in_specs = [P(), P(None, axis, None, None), P(None, axis, None, None),
                P(axis, None, None), P(axis, None, None), P()]
    if k_scale is not None:
        operands += [k_scale, v_scale]
        in_specs += [P(None, axis, None, None)] * 2
    return _shard_map_norep(
        shard_fn, mesh=mesh, in_specs=tuple(in_specs),
        out_specs=P(),
    )(*operands)


# ---------------------------------------------------------------------------
# Request-level split (paper's rejected baseline, for the imbalance bench)
# ---------------------------------------------------------------------------
def request_parallel_decode_attention(mesh: Mesh, axis: str, q, k_cache,
                                      v_cache, cache_len, *,
                                      sliding_window: int = 0,
                                      logit_softcap: float = 0.0):
    def shard_fn(q, kc, vc, clen):
        S = kc.shape[1]
        pos = jnp.arange(S)[None, :]
        valid = pos < clen[:, None]
        if sliding_window > 0:
            valid &= pos >= (clen[:, None] - sliding_window)
        return C.finalize(_masked_partial(q, kc, vc, valid,
                                          logit_softcap)).astype(q.dtype)

    return _shard_map_norep(
        shard_fn, mesh=mesh,
        in_specs=(P(axis, None, None), P(axis, None, None, None),
                  P(axis, None, None, None), P(axis)),
        out_specs=P(axis, None, None),
    )(q, k_cache, v_cache, cache_len)
