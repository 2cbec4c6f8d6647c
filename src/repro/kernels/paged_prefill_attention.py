"""Paged-context chunk-prefill GQA attention Pallas kernel.

Chunked prefill splits a prompt into block-aligned chunks; chunk k's queries
(positions [P, P+C), P = tokens already written to the pool) must attend over

  * the ALREADY-WRITTEN prefix — the sequence's first P/block_size pool
    blocks, read IN PLACE through the block table exactly like the paged
    flash-decode kernel (``paged_decode_attention.py``), and
  * the chunk itself, under the in-chunk causal mask (the chunk's K/V are
    freshly projected this layer and are not in the pool yet).

This is the prefill-axis counterpart of the decode kernel: peak prefill
memory becomes O(chunk) — the only dense KV materialised per call is the
chunk's own (the slab ``PagedKVCache.write_prefill_chunk`` scatters) —
instead of the O(prompt) slab a one-shot prefill builds, and the prefix
context is streamed HBM→VMEM block by block rather than gathered.

Mechanics (mirroring the decode kernel's conventions):
  * the pool is HEAD-MAJOR ``(Hkv, num_blocks, block_size, hd)`` per layer;
    ``block_table (nb,)`` rides in as a scalar-prefetch operand and drives
    the k/v BlockSpec index maps for the first ``nb`` grid steps;
  * the chunk's K/V ride in as a separate (padded) operand; grid steps
    ``nb .. nb+nc`` walk them. Because the prefix is the sequence's
    CONTIGUOUS first P tokens, key position is uniformly
    ``step·block_size + offset`` across both operands;
  * per step the kernel folds the block's partial into the running
    (acc, max, denom) triple with the §4.2.2 combine identity — the same
    math as ``models.attention.blockwise_attention``, so the kernel is
    parity-testable against the jnp reference below;
  * masks are PER QUERY ROW (unlike decode's single position): causal
    ``pos_k <= pos_q``, sliding window ``pos_k > pos_q - window``, and
    StreamingLLM sinks ``pos_k < attention_sinks`` — identical to the
    blockwise prefill masks, so gemma2 local layers chunk exactly.

The jnp reference gathers the prefix dense through the table (the copy the
kernel avoids) and reuses ``blockwise_attention`` over the concatenation —
bit-identical to the corresponding rows of a one-shot prefill (same scan
boundaries; masked future blocks are exact no-ops). The engines' default
jnp path routes through that reference; the Pallas path is the TPU
no-densify hot path.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _chunk_masks(kb, rows: int, *, block_size: int, chunk_len: int,
                 prefix_blocks: int, total_len: int, sliding_window: int,
                 attention_sinks: int):
    """Masks of grid step ``kb``: a ``(block_size, 1)`` column of key rows
    that exist (chunk padding excluded), for the v tile, and the
    ``(rows, block_size)`` score mask. The prefix is the sequence's
    contiguous first P tokens and the chunk follows immediately, so every
    step's key base is kb·block_size; query row r = g·C + t holds chunk
    token t at P + t. Both masks come from 2-D iotas: Mosaic cannot reshape
    a 1-D lane vector into a column."""
    base = kb * block_size
    v_mask = (base + jax.lax.broadcasted_iota(
        jnp.int32, (block_size, 1), 0)) < total_len
    pos_k = base + jax.lax.broadcasted_iota(jnp.int32, (1, block_size), 1)
    pos_q = (prefix_blocks * block_size +
             jax.lax.broadcasted_iota(jnp.int32, (rows, block_size), 0)
             % chunk_len)                         # (rows, block_size)
    valid = (pos_k < total_len) & (pos_k <= pos_q)
    if sliding_window > 0:
        in_window = pos_k > (pos_q - sliding_window)
        if attention_sinks > 0:   # StreamingLLM sinks stay attendable
            in_window |= jnp.broadcast_to(pos_k < attention_sinks,
                                          valid.shape)
        valid &= in_window
    return v_mask, valid


def _paged_prefill_chunk_kernel(bt_ref, q_ref, k_ref, v_ref, kc_ref, vc_ref,
                                o_ref, acc_ref, m_ref, l_ref, *,
                                block_size: int, chunk_len: int,
                                prefix_blocks: int, total_len: int,
                                sliding_window: int, attention_sinks: int,
                                logit_softcap: float, nsteps: int):
    kb = pl.program_id(1)

    @pl.when(kb == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0].astype(jnp.float32)              # (G·C, hd)
    rows = q.shape[0]
    # operand select: the first `prefix_blocks` steps stream pool blocks
    # through the prefetched table; the rest walk the padded chunk K/V
    is_prefix = kb < prefix_blocks
    k_pool_blk = k_ref[0, 0].astype(jnp.float32)  # (block_size, hd)
    v_pool_blk = v_ref[0, 0].astype(jnp.float32)
    k_chk_blk = kc_ref[0, 0].astype(jnp.float32)
    v_chk_blk = vc_ref[0, 0].astype(jnp.float32)
    k = jnp.where(is_prefix, k_pool_blk, k_chk_blk)
    v = jnp.where(is_prefix, v_pool_blk, v_chk_blk)

    # padded chunk rows may hold anything — zero v under the column mask so
    # the weighted sum can never see Inf/NaN through a 0-weight column
    v_mask, valid = _chunk_masks(
        kb, rows, block_size=block_size, chunk_len=chunk_len,
        prefix_blocks=prefix_blocks, total_len=total_len,
        sliding_window=sliding_window, attention_sinks=attention_sinks)
    v = jnp.where(v_mask, v, 0.0)

    hd = q.shape[-1]
    scale = 1.0 / jnp.sqrt(jnp.asarray(hd, jnp.float32))
    s = jax.lax.dot_general(q * scale, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # (rows, bs)
    if logit_softcap > 0.0:
        s = logit_softcap * jnp.tanh(s / logit_softcap)
    s = jnp.where(valid, s, NEG_INF)

    # §4.2.2 running combine, per query row
    m_prev = m_ref[...]                            # (rows, 128) lane bcast
    m_cur = jnp.max(s, axis=-1, keepdims=True)     # (rows, 1)
    m_new = jnp.maximum(m_prev, jnp.broadcast_to(m_cur, m_prev.shape))
    alpha = jnp.exp(m_prev[:, :1] - m_new[:, :1])  # (rows, 1)
    p = jnp.exp(s - m_new[:, :1])
    p = jnp.where(valid, p, 0.0)
    l_new = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_ref[...] = m_new
    l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(kb == nsteps - 1)
    def _finalize():
        denom = jnp.maximum(l_ref[:, :1], 1e-30)
        o_ref[0] = (acc_ref[...] / denom).astype(o_ref.dtype)


def _paged_prefill_chunk_kernel_int8(bt_ref, q_ref, k_ref, v_ref,
                                     ks_ref, vs_ref, kc_ref, vc_ref,
                                     o_ref, acc_ref, m_ref, l_ref, *,
                                     block_size: int, chunk_len: int,
                                     prefix_blocks: int, total_len: int,
                                     sliding_window: int,
                                     attention_sinks: int,
                                     logit_softcap: float, nsteps: int):
    """int8-pool variant of :func:`_paged_prefill_chunk_kernel`: the
    ALREADY-WRITTEN prefix streams in quantized with per-token fp32 scale
    tiles on the same table walk; the chunk's own K/V are freshly projected
    this layer (not yet in the pool) and stay full precision — their scale
    is the exact multiplicative identity 1.0, selected by the same operand
    switch that picks the chunk tile. Dequant fuses into the score / PV
    products as one broadcast multiply per tile (k scale before softcap, v
    scale into p); no dequantized slab is ever built."""
    kb = pl.program_id(1)

    @pl.when(kb == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0].astype(jnp.float32)              # (G·C, hd)
    rows = q.shape[0]
    is_prefix = kb < prefix_blocks
    k_pool_blk = k_ref[0, 0].astype(jnp.float32)  # (block_size, hd) int8
    v_pool_blk = v_ref[0, 0].astype(jnp.float32)
    k_chk_blk = kc_ref[0, 0].astype(jnp.float32)
    v_chk_blk = vc_ref[0, 0].astype(jnp.float32)
    k = jnp.where(is_prefix, k_pool_blk, k_chk_blk)
    v = jnp.where(is_prefix, v_pool_blk, v_chk_blk)
    one = jnp.ones((1, block_size), jnp.float32)  # chunk steps: ×1.0 exact
    ks = jnp.where(is_prefix, ks_ref[0, 0], one)
    vs = jnp.where(is_prefix, vs_ref[0, 0], one)

    v_mask, valid = _chunk_masks(
        kb, rows, block_size=block_size, chunk_len=chunk_len,
        prefix_blocks=prefix_blocks, total_len=total_len,
        sliding_window=sliding_window, attention_sinks=attention_sinks)
    v = jnp.where(v_mask, v, 0.0)

    hd = q.shape[-1]
    scale = 1.0 / jnp.sqrt(jnp.asarray(hd, jnp.float32))
    s = jax.lax.dot_general(q * scale, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # (rows, bs)
    s = s * ks                                    # fused k-dequant (pre-cap)
    if logit_softcap > 0.0:
        s = logit_softcap * jnp.tanh(s / logit_softcap)
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

    @pl.when(kb == nsteps - 1)
    def _finalize():
        denom = jnp.maximum(l_ref[:, :1], 1e-30)
        o_ref[0] = (acc_ref[...] / denom).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("sliding_window",
                                             "attention_sinks",
                                             "logit_softcap", "interpret"))
def paged_prefill_chunk_attention(q, k_pool, v_pool, block_table,
                                  k_chunk, v_chunk, *,
                                  k_scale=None, v_scale=None,
                                  sliding_window: int = 0,
                                  attention_sinks: int = 0,
                                  logit_softcap: float = 0.0,
                                  interpret: bool = False):
    """q: (C, H, hd) — one chunk's RoPE'd queries at global positions
    [P, P+C) where P = len(block_table)·block_size; k_pool/v_pool:
    HEAD-MAJOR (Hkv, num_blocks, block_size, hd); block_table: (nb,) int32
    pool ids of the sequence's ALREADY-WRITTEN first nb blocks (the
    block-aligned prefix); k_chunk/v_chunk: (C, Hkv, hd) — this chunk's
    freshly projected K/V (not yet in the pool). k_scale/v_scale: optional
    (Hkv, num_blocks, 1, block_size) fp32 per-token scale pools for an int8
    k_pool/v_pool — the int8 kernel variant fuses dequant into the
    score/PV products; the chunk's own K/V stay full precision.
    Returns (C, H, hd).

    Per-call HBM traffic over the context is exactly one streamed read of
    the live prefix KV; nothing is gathered into a dense slab first."""
    C, H, hd = q.shape
    Hkv, _, block_size, _ = k_pool.shape
    G = H // Hkv
    nb = block_table.shape[0]
    nc = -(-C // block_size)
    pad = nc * block_size - C
    # (C, Hkv, hd) -> head-major (Hkv, nc·bs, hd), zero-padded chunk tail
    kc = jnp.swapaxes(k_chunk, 0, 1)
    vc = jnp.swapaxes(v_chunk, 0, 1)
    if pad:
        kc = jnp.pad(kc, ((0, 0), (0, pad), (0, 0)))
        vc = jnp.pad(vc, ((0, 0), (0, pad), (0, 0)))
    # (C, Hkv, G, hd) -> (Hkv, G·C, hd): row r = g·C + t
    qg = q.reshape(C, Hkv, G, hd).transpose(1, 2, 0, 3).reshape(
        Hkv, G * C, hd)
    # the pool BlockSpec must stay in-bounds on chunk steps (and with an
    # empty prefix): pad the table to ≥1 slot and clamp the walk index
    bt = block_table.astype(jnp.int32)
    if nb == 0:
        bt = jnp.zeros((1,), jnp.int32)
    nsteps = nb + nc

    quantized = k_scale is not None
    kernel = functools.partial(
        _paged_prefill_chunk_kernel_int8 if quantized
        else _paged_prefill_chunk_kernel,
        block_size=block_size, chunk_len=C,
        prefix_blocks=nb, total_len=nb * block_size + C,
        sliding_window=sliding_window, attention_sinks=attention_sinks,
        logit_softcap=logit_softcap, nsteps=nsteps)
    clamp = max(nb - 1, 0)
    pool_spec = pl.BlockSpec(
        (1, 1, block_size, hd),
        lambda h, kb, bt: (h, bt[jnp.minimum(kb, clamp)], 0, 0))
    # scale tiles ride the same clamped table walk as their value tiles;
    # each is a whole (1, block_size) row, as Mosaic's tiling rule asks
    scale_spec = pl.BlockSpec(
        (1, 1, 1, block_size),
        lambda h, kb, bt: (h, bt[jnp.minimum(kb, clamp)], 0, 0))
    chunk_spec = pl.BlockSpec(
        (1, 1, block_size, hd),
        lambda h, kb, bt: (h, jnp.maximum(kb - nb, 0), 0, 0))
    in_specs = [pl.BlockSpec((1, G * C, hd), lambda h, kb, bt: (h, 0, 0)),
                pool_spec, pool_spec]
    if quantized:
        in_specs += [scale_spec, scale_spec]
    in_specs += [chunk_spec, chunk_spec]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,    # block_table
        grid=(Hkv, nsteps),       # kb innermost: scratch carries the combine
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, G * C, hd), lambda h, kb, bt: (h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((G * C, hd), jnp.float32),    # acc
            pltpu.VMEM((G * C, 128), jnp.float32),   # running max
            pltpu.VMEM((G * C, 128), jnp.float32),   # running denom
        ],
    )
    operands = (bt, qg, k_pool, v_pool)
    if quantized:
        operands += (k_scale, v_scale)
    operands += (kc.reshape(Hkv, nc, block_size, hd),
                 vc.reshape(Hkv, nc, block_size, hd))
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        # the instruction name the device trace shows (and the benchmark's
        # chunk roofline matches): keep it stable
        name="paged_prefill_chunk_attention",
        out_shape=jax.ShapeDtypeStruct((Hkv, G * C, hd), q.dtype),
        interpret=interpret,
    )(*operands)
    # (Hkv, G·C, hd) -> (C, H, hd)
    return out.reshape(Hkv, G, C, hd).transpose(2, 0, 1, 3).reshape(C, H, hd)


def gather_prefix_dense(k_pool, v_pool, block_table):
    """Block-table gather of a contiguous prefix into seq-major dense
    (P, Hkv, hd) views — the jnp reference data path (and exactly the bytes
    the chunk kernel streams in place instead)."""
    Hkv, _, bs, hd = k_pool.shape
    nb = block_table.shape[0]
    kp = jnp.swapaxes(k_pool[:, block_table], 0, 1)  # (nb, Hkv, bs, hd)
    vp = jnp.swapaxes(v_pool[:, block_table], 0, 1)
    kp = jnp.swapaxes(kp, 1, 2).reshape(nb * bs, Hkv, hd)
    vp = jnp.swapaxes(vp, 1, 2).reshape(nb * bs, Hkv, hd)
    return kp, vp


def gather_prefix_scales(scale_pool, block_table):
    """Block-table gather of a (Hkv, num_blocks, 1, bs) scale pool into the
    seq-major (P, Hkv) per-token view — reference data path only."""
    Hkv, _, _, bs = scale_pool.shape
    nb = block_table.shape[0]
    s = scale_pool[:, block_table]            # (Hkv, nb, 1, bs)
    return s.reshape(Hkv, nb * bs).T          # (P, Hkv)


def paged_prefill_chunk_attention_jnp(q, k_pool, v_pool, block_table,
                                      k_chunk, v_chunk, *,
                                      k_scale=None, v_scale=None,
                                      sliding_window: int = 0,
                                      attention_sinks: int = 0,
                                      logit_softcap: float = 0.0):
    """Pure-jnp reference for the chunk kernel: gathers the prefix dense
    through the table and runs ``blockwise_attention`` over the
    concatenation — the SAME scan boundaries (512-key blocks from position
    0) as a one-shot prefill, so the result is bit-identical to the
    corresponding query rows of the unchunked prefill (masked-out future
    blocks are exact no-ops in the running combine). int8 pools pass the
    scale pools; the gathered prefix is dequantized dense here (the
    reference path is ALLOWED to densify — the kernel is not)."""
    from repro.models.attention import blockwise_attention

    C = q.shape[0]
    bs = k_pool.shape[2]
    P = block_table.shape[0] * bs
    kp, vp = gather_prefix_dense(k_pool, v_pool, block_table)
    if k_scale is not None:
        kp = (kp.astype(jnp.float32) *
              gather_prefix_scales(k_scale, block_table)[:, :, None]
              ).astype(k_chunk.dtype)
        vp = (vp.astype(jnp.float32) *
              gather_prefix_scales(v_scale, block_table)[:, :, None]
              ).astype(v_chunk.dtype)
    k_all = jnp.concatenate([kp, k_chunk], axis=0)[None]  # (1, P+C, Hkv, hd)
    v_all = jnp.concatenate([vp, v_chunk], axis=0)[None]
    q_pos = (P + jnp.arange(C, dtype=jnp.int32))[None]
    out = blockwise_attention(
        q[None], k_all, v_all, causal=True,
        sliding_window=int(sliding_window),
        attention_sinks=int(attention_sinks),
        logit_softcap=logit_softcap, q_positions=q_pos)
    return out[0]
