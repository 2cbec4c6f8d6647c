"""Paged flash-decode GQA attention Pallas kernel.

PagedAttention-style (the paper's baseline [28]) counterpart of
``decode_attention.py``: instead of a dense per-batch KV slab, the kernel
consumes the serving engine's block pool *in place* through a per-sequence
block table, so a decode step moves exactly one read of the live KV plus one
token write — no per-step dense gather, no pool-sized transposes (the
`kill-the-gather` tentpole; the paper's whole premise is that decode
attention is memory-bound, §3).

Mechanics:
  * the pool is HEAD-MAJOR ``(Hkv, num_blocks, block_size, hd)`` per layer,
    so one (head, block) tile is a contiguous ``(block_size, hd)`` DMA;
  * ``block_tables (B, nb)`` + ``cache_len (B,)`` ride in as scalar-prefetch
    operands (``PrefetchScalarGridSpec``) and drive the k/v BlockSpec index
    maps — the grid's KV dimension walks the table, streaming pool blocks
    HBM→VMEM;
  * ``block_positions (B, nb)`` (optional third prefetch operand) carries
    each table slot's global base position. For a contiguous table the
    default ``slot·block_size`` is implied; a BLOCK-SHARDED table (one shard
    of a cross-chip sequence split, ``core/attention_parallel.py``) walks a
    non-contiguous subset of the sequence's blocks, and the positions keep
    causal/window/sink masks exact. Slots a shard does not own carry the
    ``POS_PAD`` sentinel so every row masks out — the shard then yields the
    empty partial (l = 0, m = NEG_INF) the §4.2.2 combine treats as identity;
  * per block the kernel computes the partial (acc, denom, max) triple and
    merges it with the running state using the paper-§4.2.2 combine identity
    (``core/combine.py``) — identical math to ``decode_attention.py``, so the
    two backends are interchangeable and parity-testable;
  * table slots past a sequence's live blocks may point anywhere (the engine
    pads with block 0); their positions are ≥ cache_len so the masks kill
    them, and v is zero-filled under the mask so stale pool garbage can never
    poison the accumulator (0·Inf/NaN).

This layout is what the cross-chip block partition shards by: blocks, not
dense slabs (``block_parallel_paged_decode_attention`` runs this kernel with
``return_partials=True`` per device and psum-combines the triples).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
# Base-position sentinel for table slots a shard does not own (or pure pad):
# far beyond any real cache_len, so every mask (causal, window, sink) kills
# the whole block while staying comfortably inside int32.
POS_PAD = 1 << 30


def _block_masks(base, cache_len, *, block_size: int, sliding_window: int,
                 attention_sinks: int):
    """Validity of one pool block's rows, at global positions ``base +
    [0, block_size)``: ``base`` is the prefetched per-slot base
    (slot·block_size for contiguous tables; arbitrary — including POS_PAD —
    for block-sharded ones). Returned twice, as a ``(block_size, 1)`` column
    that masks the v tile and a ``(1, block_size)`` row that masks the
    scores: each comes from its own 2-D iota, because Mosaic cannot reshape
    a 1-D lane vector into a column."""
    def valid(pos):
        ok = pos < cache_len
        if sliding_window > 0:
            in_window = pos >= (cache_len - sliding_window)
            if attention_sinks > 0:  # StreamingLLM sinks stay attendable
                in_window |= pos < attention_sinks
            ok &= in_window
        return ok

    col = jax.lax.broadcasted_iota(jnp.int32, (block_size, 1), 0)
    row = jax.lax.broadcasted_iota(jnp.int32, (1, block_size), 1)
    return valid(base + col), valid(base + row)


def _paged_decode_kernel(bt_ref, bp_ref, len_ref, q_ref, k_ref, v_ref,
                         o_ref, lo_ref, mo_ref,
                         acc_ref, m_ref, l_ref, *,
                         block_size: int, sliding_window: int,
                         attention_sinks: int, logit_softcap: float, nb: int):
    b = pl.program_id(0)
    kb = pl.program_id(2)

    @pl.when(kb == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0, 0].astype(jnp.float32)          # (G, hd)
    k = k_ref[0, 0].astype(jnp.float32)          # (block_size, hd) pool block
    v = v_ref[0, 0].astype(jnp.float32)

    # stale pool blocks may hold anything — zero v under the mask so the
    # weighted sum can never see Inf/NaN through a 0-weight column
    v_mask, s_mask = _block_masks(
        bp_ref[b, kb], len_ref[b], block_size=block_size,
        sliding_window=sliding_window, attention_sinks=attention_sinks)
    v = jnp.where(v_mask, v, 0.0)

    hd = q.shape[-1]
    scale = 1.0 / jnp.sqrt(jnp.asarray(hd, jnp.float32))
    s = jax.lax.dot_general(q * scale, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # (G, bs)
    if logit_softcap > 0.0:
        s = logit_softcap * jnp.tanh(s / logit_softcap)
    valid = jnp.broadcast_to(s_mask, s.shape)
    s = jnp.where(valid, s, NEG_INF)

    # paper §4.2.2 combine: rebase running (acc, l) onto the new max
    m_prev = m_ref[...]                           # (G, 128) broadcast lanes
    m_cur = jnp.max(s, axis=-1, keepdims=True)    # (G, 1)
    m_new = jnp.maximum(m_prev, jnp.broadcast_to(m_cur, m_prev.shape))
    alpha = jnp.exp(m_prev[:, :1] - m_new[:, :1])  # (G, 1)
    p = jnp.exp(s - m_new[:, :1])                  # (G, block_size)
    p = jnp.where(valid, p, 0.0)
    l_new = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_ref[...] = m_new
    l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(kb == nb - 1)
    def _finalize():
        denom = jnp.maximum(l_ref[:, :1], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / denom).astype(o_ref.dtype)
        lo_ref[0, 0] = l_ref[...]   # partial denominator (§4.2.2 combine)
        mo_ref[0, 0] = m_ref[...]   # partial max


def _paged_decode_kernel_int8(bt_ref, bp_ref, len_ref, q_ref, k_ref, v_ref,
                              ks_ref, vs_ref, o_ref, lo_ref, mo_ref,
                              acc_ref, m_ref, l_ref, *,
                              block_size: int, sliding_window: int,
                              attention_sinks: int, logit_softcap: float,
                              nb: int):
    """int8-pool variant of :func:`_paged_decode_kernel`: k/v tiles arrive
    quantized with per-token fp32 scale tiles ``(1, block_size)`` riding the
    same block-table walk, and dequantization fuses into the score / PV
    products as ONE broadcast multiply per (G, block_size) tile — the k
    scale folds into ``s`` right after the QK product (before softcap, where
    the dense int8 reference applies it), the v scale folds into ``p``
    before the PV product. No dequantized (block_size, hd) slab is ever
    built; the bf16 kernel above is untouched."""
    b = pl.program_id(0)
    kb = pl.program_id(2)

    @pl.when(kb == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0, 0].astype(jnp.float32)          # (G, hd)
    k = k_ref[0, 0].astype(jnp.float32)          # (block_size, hd) int8->f32
    v = v_ref[0, 0].astype(jnp.float32)
    ks = ks_ref[0, 0]                            # (1, block_size) fp32 scales
    vs = vs_ref[0, 0]

    # int8 loads are always finite, but stale scales are arbitrary (finite)
    # numbers — zero v under the mask exactly like the bf16 kernel so the
    # masked columns contribute exact zeros through the zeroed p
    v_mask, s_mask = _block_masks(
        bp_ref[b, kb], len_ref[b], block_size=block_size,
        sliding_window=sliding_window, attention_sinks=attention_sinks)
    v = jnp.where(v_mask, v, 0.0)

    hd = q.shape[-1]
    scale = 1.0 / jnp.sqrt(jnp.asarray(hd, jnp.float32))
    s = jax.lax.dot_general(q * scale, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # (G, bs)
    s = s * ks                                   # fused k-dequant (pre-cap)
    if logit_softcap > 0.0:
        s = logit_softcap * jnp.tanh(s / logit_softcap)
    valid = jnp.broadcast_to(s_mask, s.shape)
    s = jnp.where(valid, s, NEG_INF)

    m_prev = m_ref[...]
    m_cur = jnp.max(s, axis=-1, keepdims=True)
    m_new = jnp.maximum(m_prev, jnp.broadcast_to(m_cur, m_prev.shape))
    alpha = jnp.exp(m_prev[:, :1] - m_new[:, :1])
    p = jnp.exp(s - m_new[:, :1])
    p = jnp.where(valid, p, 0.0)
    l_new = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        p * vs, v, (((1,), (0,)), ((), ())),  # fused v-dequant
        preferred_element_type=jnp.float32)
    m_ref[...] = m_new
    l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(kb == nb - 1)
    def _finalize():
        denom = jnp.maximum(l_ref[:, :1], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / denom).astype(o_ref.dtype)
        lo_ref[0, 0] = l_ref[...]
        mo_ref[0, 0] = m_ref[...]


def default_block_positions(B: int, nb: int, block_size: int) -> jax.Array:
    """Contiguous-table base positions: slot j starts at j·block_size."""
    return jnp.broadcast_to(
        jnp.arange(nb, dtype=jnp.int32)[None, :] * block_size, (B, nb))


@functools.partial(jax.jit, static_argnames=("sliding_window",
                                             "attention_sinks",
                                             "logit_softcap", "interpret",
                                             "return_partials"))
def paged_decode_attention(q, k_pool, v_pool, block_tables, cache_len, *,
                           block_positions=None,
                           k_scale=None, v_scale=None,
                           sliding_window: int = 0, attention_sinks: int = 0,
                           logit_softcap: float = 0.0,
                           interpret: bool = False,
                           return_partials: bool = False):
    """q: (B, Hkv, G, hd); k_pool/v_pool: HEAD-MAJOR
    (Hkv, num_blocks, block_size, hd); block_tables: (B, nb) int32 pool-block
    ids per sequence (pad slots with any valid id — masked); cache_len: (B,)
    live tokens. block_positions: optional (B, nb) int32 global base position
    per table slot (defaults to the contiguous slot·block_size; block-sharded
    callers pass their shard's true positions, POS_PAD on foreign slots).
    k_scale/v_scale: optional (Hkv, num_blocks, 1, block_size) fp32
    per-token scale pools for an int8 k_pool/v_pool — when given, the int8
    kernel variant streams the scale tiles through the SAME block-table walk
    and fuses dequantization into the score/PV products (no dense
    dequantized slab, in VMEM or HBM).
    Returns (B, Hkv, G, hd), or the (o, l, m) §4.2.2 triple over the cached
    subset when return_partials — mergeable with other partials (e.g. across
    the pool mesh axis via ``core.combine.psum_combine``).

    Per-step HBM traffic is exactly the live KV: each (head, block) tile is
    one contiguous (block_size, hd) DMA addressed through the prefetched
    block table; nothing is gathered into a dense slab first.
    """
    B, Hkv, G, hd = q.shape
    block_size = k_pool.shape[2]
    nb = block_tables.shape[1]
    if block_positions is None:
        block_positions = default_block_positions(B, nb, block_size)
    block_positions = block_positions.astype(jnp.int32)
    quantized = k_scale is not None

    kernel = functools.partial(
        _paged_decode_kernel_int8 if quantized else _paged_decode_kernel,
        block_size=block_size,
        sliding_window=sliding_window, attention_sinks=attention_sinks,
        logit_softcap=logit_softcap, nb=nb)
    kv_spec = pl.BlockSpec((1, 1, block_size, hd),
                           lambda b, h, kb, bt, bp, ln: (h, bt[b, kb], 0, 0))
    # scale tiles ride the same prefetched table walk as their value tiles;
    # each is a whole (1, block_size) row, as Mosaic's tiling rule asks
    scale_spec = pl.BlockSpec((1, 1, 1, block_size),
                              lambda b, h, kb, bt, bp, ln: (h, bt[b, kb], 0, 0))
    in_specs = [
        pl.BlockSpec((1, 1, G, hd),
                     lambda b, h, kb, bt, bp, ln: (b, h, 0, 0)),
        kv_spec, kv_spec,
    ] + ([scale_spec, scale_spec] if quantized else [])
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,   # block_tables, block_positions, cache_len
        grid=(B, Hkv, nb),       # kb innermost: scratch carries the combine
        in_specs=in_specs,
        out_specs=(
            pl.BlockSpec((1, 1, G, hd),
                         lambda b, h, kb, bt, bp, ln: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, G, 128),
                         lambda b, h, kb, bt, bp, ln: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, G, 128),
                         lambda b, h, kb, bt, bp, ln: (b, h, 0, 0)),
        ),
        scratch_shapes=[
            pltpu.VMEM((G, hd), jnp.float32),    # acc
            pltpu.VMEM((G, 128), jnp.float32),   # running max (lane bcast)
            pltpu.VMEM((G, 128), jnp.float32),   # running denom
        ],
    )
    operands = (block_tables, block_positions, cache_len, q, k_pool, v_pool)
    if quantized:
        operands += (k_scale, v_scale)
    out, l_out, m_out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=(
            jax.ShapeDtypeStruct((B, Hkv, G, hd), q.dtype),
            jax.ShapeDtypeStruct((B, Hkv, G, 128), jnp.float32),
            jax.ShapeDtypeStruct((B, Hkv, G, 128), jnp.float32),
        ),
        interpret=interpret,
    )(*operands)
    if return_partials:
        return out, l_out[..., 0], m_out[..., 0]
    return out


def paged_gather_dense(k_pool, v_pool, block_tables):
    """Block-table gather into head-major dense (B, Hkv, nb·bs, hd) views —
    the jnp reference data path (and the bytes the paged kernel avoids)."""
    Hkv, _, bs, hd = k_pool.shape
    B, nb = block_tables.shape
    kc = jnp.swapaxes(k_pool[:, block_tables], 0, 1)  # (B, Hkv, nb, bs, hd)
    vc = jnp.swapaxes(v_pool[:, block_tables], 0, 1)
    return (kc.reshape(B, Hkv, nb * bs, hd), vc.reshape(B, Hkv, nb * bs, hd))


def paged_gather_scales(scale_pool, block_tables):
    """Block-table gather of a (Hkv, num_blocks, 1, bs) scale pool into the
    dense (B, Hkv, nb·bs) per-token view the dense int8 references fold into
    the score/PV einsums — reference data path only."""
    Hkv, _, _, bs = scale_pool.shape
    B, nb = block_tables.shape
    s = jnp.swapaxes(scale_pool[:, block_tables], 0, 1)  # (B, Hkv, nb, 1, bs)
    return s.reshape(B, Hkv, nb * bs)


def paged_decode_attention_jnp(q, k_pool, v_pool, block_tables, cache_len, *,
                               k_scale=None, v_scale=None,
                               sliding_window: int = 0,
                               attention_sinks: int = 0,
                               logit_softcap: float = 0.0):
    """Pure-jnp reference for the paged kernel (CPU tests): gathers the dense
    view through the block table and runs the dense oracle math (int8 pools
    additionally gather the scale pools and fold them into the einsums)."""
    from repro.kernels import ref

    kc, vc = paged_gather_dense(k_pool, v_pool, block_tables)
    kw = {}
    if k_scale is not None:
        kw = {"k_scale": paged_gather_scales(k_scale, block_tables),
              "v_scale": paged_gather_scales(v_scale, block_tables)}
    return ref.decode_attention_ref(q, kc, vc, cache_len,
                                    sliding_window=sliding_window,
                                    attention_sinks=attention_sinks,
                                    logit_softcap=logit_softcap, **kw)
