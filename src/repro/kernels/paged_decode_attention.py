"""Paged flash-decode GQA attention Pallas kernel.

PagedAttention-style (the paper's baseline [28]) counterpart of
``decode_attention.py``: instead of a dense per-batch KV slab, the kernel
consumes the serving engine's block pool *in place* through a per-sequence
block table, so a decode step moves exactly one read of the live KV plus one
token write — no per-step dense gather, no pool-sized transposes (the
`kill-the-gather` tentpole; the paper's whole premise is that decode
attention is memory-bound, §3).

Mechanics:
  * the pool is HEAD-MAJOR ``(Hkv, num_blocks, block_size, hd)`` per layer;
    one pool block of every head, ``(Hkv, block_size, hd)``, is one strided
    copy;
  * ``block_tables (B, nb)`` + ``cache_len (B,)`` ride in as scalar-prefetch
    operands (``PrefetchScalarGridSpec``); the grid is ``(B, ceil(nb / P))``
    and grid step ``(b, c)`` walks table slots ``[c·P, (c+1)·P)`` of row b
    for all of its heads. ``P`` (:func:`decode_blocks_per_step`) comes from
    the shapes: about ``CHUNK_TOKENS`` tokens a step, inside
    ``COPY_BUFFER_BYTES`` of VMEM, never more than ``nb``;
  * the copies are the kernel's own: the pools stay in HBM
    (``memory_space=ANY``) and each live slot's block is copied into a
    double-buffered VMEM chunk with ``make_async_copy``. While chunk c is
    computed, the next chunk's copies are in flight — the row's next chunk,
    or after its last one the next row's first, so rows hand over without
    a stall. A chunk is attended as one ``(P·block_size, hd)`` tile per
    head, its rows' positions laid out from the prefetched per-slot bases.
    Mosaic can copy by hand only lane-aligned slices (``hd % 128 == 0``)
    and no ``(1, block_size)`` scale row, so pools with narrower heads and
    int8 pools walk ``P = 1`` block a step with the grid pipeline doing
    the copies (same walk, same arithmetic);
  * each row stops at its own length: a contiguous table walks
    ``ceil(cache_len / block_size)`` slots, not the batch's ``nb``, and any
    slot whose rows all fall outside the masks (past ``cache_len``, before
    the window and past the sinks, or ``POS_PAD``) is neither copied nor
    computed. A dead block would add exactly nothing to the running
    (acc, denom, max), so skipping it changes no bit of the result;
  * ``block_positions (B, nb)`` (optional third prefetch operand) carries
    each table slot's global base position. For a contiguous table the
    default ``slot·block_size`` is implied; a BLOCK-SHARDED table (one shard
    of a cross-chip sequence split, ``core/attention_parallel.py``) walks a
    non-contiguous subset of the sequence's blocks, and the positions keep
    causal/window/sink masks exact. Slots a shard does not own carry the
    ``POS_PAD`` sentinel so every row masks out — a shard that owns none of
    a row's blocks then yields the empty partial (l = 0, m = NEG_INF) the
    §4.2.2 combine treats as identity;
  * per step the kernel computes the partial (acc, denom, max) triple in
    float32 and merges it with the running state using the paper-§4.2.2
    combine identity (``core/combine.py``) — identical math to
    ``decode_attention.py``, so the two backends are interchangeable and
    parity-testable;
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

# Tokens one grid step aims to walk, and the VMEM its K and V chunk buffers
# (two of each, for the copies in flight) may take: well under the 16 MiB
# of scoped VMEM a kernel gets on a TPU v5e. At a mistral-nemo-12b worker's
# shapes a call took 0.60 / 0.50 / 0.43 ms at 256 / 512 / 1024 tokens a
# step on a TPU v5e (PERF.md §6).
CHUNK_TOKENS = 1024
COPY_BUFFER_BYTES = 4 << 20


def _copies_by_hand(head_dim: int, quantized: bool) -> bool:
    """Whether the kernel copies pool blocks itself: Mosaic slices an HBM
    ref only along lane-aligned minor dims, so a head narrower than 128
    lanes, or an int8 pool's ``(1, block_size)`` scale row, rides the grid
    pipeline instead."""
    return head_dim % 128 == 0 and not quantized


def decode_blocks_per_step(block_size: int, head_dim: int, kv_heads: int,
                           nb: int, itemsize: int,
                           quantized: bool = False) -> int:
    """Pool blocks one grid step walks per row, from the shapes alone:
    ``CHUNK_TOKENS`` tokens a step, K and V chunks double-buffered within
    ``COPY_BUFFER_BYTES``, at least 1 and never more than the table's
    ``nb`` slots; 1 where the copies cannot be made by hand."""
    if not _copies_by_hand(head_dim, quantized):
        return 1
    per_block = 2 * 2 * kv_heads * block_size * head_dim * itemsize
    return max(1, min(nb, CHUNK_TOKENS // block_size,
                      COPY_BUFFER_BYTES // per_block))


def _valid(pos, cache_len, *, sliding_window: int, attention_sinks: int):
    """Which positions a query at ``cache_len`` attends: causal, inside the
    sliding window, or one of the StreamingLLM sinks."""
    ok = pos < cache_len
    if sliding_window > 0:
        in_window = pos >= (cache_len - sliding_window)
        if attention_sinks > 0:  # StreamingLLM sinks stay attendable
            in_window |= pos < attention_sinks
        ok &= in_window
    return ok


def _block_live(base, cache_len, *, block_size: int, sliding_window: int,
                attention_sinks: int):
    """Scalar twin of :func:`_valid` over one pool block: whether any of the
    rows ``base + [0, block_size)`` is valid. A block with none contributes
    exactly nothing to the running (acc, denom, max), so the walk skips its
    copies and its compute."""
    live = base < cache_len
    if sliding_window > 0:
        # the block's last valid row is min(base + bs, cache_len) - 1
        in_window = base + block_size > cache_len - sliding_window
        if attention_sinks > 0:
            in_window = jnp.logical_or(in_window, base < attention_sinks)
        live = jnp.logical_and(live, in_window)
    return live


def _row_walk(cache_len, *, nb: int, block_size: int, contiguous: bool):
    """Table slots a row walks: a contiguous table stops at the row's own
    live blocks; a block-sharded one may own any slot, so it walks all
    ``nb`` and skips its dead slots one by one."""
    if not contiguous:
        return nb
    return jnp.minimum(nb, (cache_len + block_size - 1) // block_size)


def _attend(q, k, v, ks, vs, pos_col, pos_row, cache_len, acc_ref, m_ref,
            l_ref, h, *, sliding_window: int, attention_sinks: int,
            logit_softcap: float):
    """Merge ``n`` pool rows of head ``h`` into its running state.

    q: (G, hd); k/v: (n, hd) tiles in the pool dtype, upcast to float32
    here; pos_col (n, 1) / pos_row (1, n): the rows' global positions, as a
    column that masks the v tile and a row that masks the scores (Mosaic
    cannot reshape a lane vector into a column). ks/vs: the (1, n) fp32
    scale rows of an int8 pool, else None: dequantization fuses into the
    score / PV products as ONE broadcast multiply per (G, n) tile — the k
    scale folds into ``s`` right after the QK product (before softcap,
    where the dense int8 reference applies it), the v scale folds into
    ``p`` before the PV product. No dequantized (n, hd) slab is built."""
    valid = functools.partial(_valid, cache_len=cache_len,
                              sliding_window=sliding_window,
                              attention_sinks=attention_sinks)
    q = q.astype(jnp.float32)
    k = k.astype(jnp.float32)
    # stale pool blocks may hold anything — zero v under the mask so the
    # weighted sum can never see Inf/NaN through a 0-weight column
    v = jnp.where(valid(pos_col), v.astype(jnp.float32), 0.0)

    hd = q.shape[-1]
    scale = 1.0 / jnp.sqrt(jnp.asarray(hd, jnp.float32))
    s = jax.lax.dot_general(q * scale, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # (G, n)
    if ks is not None:
        s = s * ks                               # fused k-dequant (pre-cap)
    if logit_softcap > 0.0:
        s = logit_softcap * jnp.tanh(s / logit_softcap)
    s_valid = jnp.broadcast_to(valid(pos_row), s.shape)
    s = jnp.where(s_valid, s, NEG_INF)

    # paper §4.2.2 combine: rebase running (acc, l) onto the new max
    m_prev = m_ref[h]                             # (G, 128) broadcast lanes
    m_cur = jnp.max(s, axis=-1, keepdims=True)    # (G, 1)
    m_new = jnp.maximum(m_prev, jnp.broadcast_to(m_cur, m_prev.shape))
    alpha = jnp.exp(m_prev[:, :1] - m_new[:, :1])  # (G, 1)
    p = jnp.exp(s - m_new[:, :1])                  # (G, n)
    p = jnp.where(s_valid, p, 0.0)
    l_new = l_ref[h] * alpha + jnp.sum(p, axis=-1, keepdims=True)
    pv = p if vs is None else p * vs               # fused v-dequant
    acc_ref[h] = acc_ref[h] * alpha + jax.lax.dot_general(
        pv, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_ref[h] = m_new
    l_ref[h] = jnp.broadcast_to(l_new, m_prev.shape)


def _paged_decode_kernel(bt_ref, bp_ref, len_ref, q_ref, k_ref, v_ref,
                         *refs, P: int, by_hand: bool, quantized: bool,
                         contiguous: bool, nb: int, block_size: int,
                         sliding_window: int, attention_sinks: int,
                         logit_softcap: float):
    """Grid step (b, c): table slots [c·P, (c+1)·P) of row b, all heads.

    By hand, k_ref/v_ref are the whole pools in HBM and ``copy`` holds the
    two chunk buffers, a DMA semaphore per (buffer, slot), the chunk's
    positions and the SMEM index of the buffer the next chunk to compute
    lands in; a chunk is attended as one ``(P·block_size, hd)`` tile per
    head. Otherwise (P = 1) the grid pipeline hands k_ref/v_ref (and the
    int8 scale rows) in as the slot's ``(Hkv, block_size, ·)`` tiles."""
    if quantized:
        ks_ref, vs_ref, *refs = refs
    o_ref, lo_ref, mo_ref, acc_ref, m_ref, l_ref, *copy = refs
    b, c = pl.program_id(0), pl.program_id(1)
    n_rows, n_steps = len_ref.shape[0], pl.cdiv(nb, P)
    Hkv = acc_ref.shape[0]
    cache_len = len_ref[b]
    masks = dict(sliding_window=sliding_window,
                 attention_sinks=attention_sinks)
    attend = functools.partial(_attend, cache_len=cache_len, acc_ref=acc_ref,
                               m_ref=m_ref, l_ref=l_ref,
                               logit_softcap=logit_softcap, **masks)

    def walk(row):
        return _row_walk(len_ref[row], nb=nb, block_size=block_size,
                         contiguous=contiguous)

    def live(row, slot):
        return _block_live(bp_ref[row, slot], len_ref[row],
                           block_size=block_size, **masks)

    @pl.when(c == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    if by_hand:
        kbuf, vbuf, sem, pos_ref, buf_ref = copy
        n = P * block_size

        def copies(row, chunk, buf, j):
            blk = bt_ref[row, chunk * P + j]
            return (pltpu.make_async_copy(k_ref.at[:, blk],
                                          kbuf.at[buf, :, j], sem.at[buf, j]),
                    pltpu.make_async_copy(v_ref.at[:, blk],
                                          vbuf.at[buf, :, j], sem.at[buf, j]))

        def slots(row, chunk):
            """Slots of ``chunk`` that ``row`` walks (0 past its walk); the
            table is never read past ``nb``."""
            return jnp.clip(walk(row) - chunk * P, 0, P)

        def start(row, chunk, buf):
            def body(j, carry):
                @pl.when(live(row, chunk * P + j))
                def _():
                    for cp in copies(row, chunk, buf, j):
                        cp.start()
                return carry
            jax.lax.fori_loop(0, slots(row, chunk), body, 0)

        # The row's last chunk that walks anything; an empty row still
        # takes step 0 here, to hand the next row's first chunk on.
        last = jnp.maximum((walk(b) + P - 1) // P, 1) - 1

        @pl.when((b == 0) & (c == 0))
        def _first():
            buf_ref[0] = 0
            start(0, 0, 0)

        @pl.when(c <= last)
        def _step():
            cur = buf_ref[0]
            nxt = 1 - cur

            @pl.when(c < last)
            def _():
                start(b, c + 1, nxt)

            @pl.when((c == last) & (b + 1 < n_rows))
            def _():
                start(b + 1, 0, nxt)

            # Wait for this chunk's live slots and lay out the positions of
            # its rows; a slot not copied (dead, or past the walk) gets
            # POS_PAD, so the masks kill whatever its buffer holds.
            rows = jax.lax.broadcasted_iota(jnp.int32, (block_size, 128), 0)

            def wait(j, n_live):
                slot = c * P + j
                ok = (j < slots(b, c)) & live(b, jnp.minimum(slot, nb - 1))

                @pl.when(ok)
                def _():
                    for cp in copies(b, c, cur, j):
                        cp.wait()
                base = jnp.where(ok, bp_ref[b, jnp.minimum(slot, nb - 1)],
                                 POS_PAD)
                pos_ref[pl.ds(pl.multiple_of(j * block_size, block_size),
                              block_size), :] = base + rows
                return n_live + ok.astype(jnp.int32)
            n_live = jax.lax.fori_loop(0, P, wait, 0)

            @pl.when(n_live > 0)
            def _():
                pos = pos_ref[...]                       # (n, 128)
                pos_col, pos_row = pos[:, :1], pos.T[:1, :]

                def head(h, carry):
                    k = kbuf[cur, h].astype(jnp.float32).reshape(n, -1)
                    v = vbuf[cur, h].astype(jnp.float32).reshape(n, -1)
                    attend(q_ref[h], k, v, None, None, pos_col, pos_row, h=h)
                    return carry
                jax.lax.fori_loop(0, Hkv, head, 0)
            buf_ref[0] = nxt
    else:
        base = bp_ref[b, c]
        pos_col = base + jax.lax.broadcasted_iota(jnp.int32,
                                                  (block_size, 1), 0)
        pos_row = base + jax.lax.broadcasted_iota(jnp.int32,
                                                  (1, block_size), 1)

        @pl.when((c < walk(b)) & live(b, c))
        def _():
            # one small tile a head: unrolled, the heads' chains overlap
            for h in range(Hkv):
                scales = ((ks_ref[h], vs_ref[h]) if quantized
                          else (None, None))
                attend(q_ref[h], k_ref[h], v_ref[h], *scales, pos_col,
                       pos_row, h=h)

    @pl.when(c == n_steps - 1)
    def _finalize():
        denom = jnp.maximum(l_ref[:, :, :1], 1e-30)
        o_ref[...] = (acc_ref[...] / denom).astype(o_ref.dtype)
        lo_ref[...] = l_ref[...]   # partial denominator (§4.2.2 combine)
        mo_ref[...] = m_ref[...]   # partial max


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
    per-token scale pools for an int8 k_pool/v_pool — when given, the scale
    rows ride the SAME block-table walk and dequantization fuses into the
    score/PV products (no dense dequantized slab, in VMEM or HBM).
    Returns (B, Hkv, G, hd), or the (o, l, m) §4.2.2 triple over the cached
    subset when return_partials — mergeable with other partials (e.g. across
    the pool mesh axis via ``core.combine.psum_combine``).

    Per-step HBM traffic is the live KV: each live table slot is one strided
    ``(Hkv, block_size, hd)`` copy per pool, addressed through the
    prefetched block table; nothing is gathered into a dense slab first.
    """
    B, Hkv, G, hd = q.shape
    block_size = k_pool.shape[2]
    nb = block_tables.shape[1]
    contiguous = block_positions is None
    if contiguous:
        block_positions = default_block_positions(B, nb, block_size)
    block_positions = block_positions.astype(jnp.int32)
    quantized = k_scale is not None
    by_hand = _copies_by_hand(hd, quantized)
    P = decode_blocks_per_step(block_size, hd, Hkv, nb,
                               jnp.dtype(k_pool.dtype).itemsize, quantized)

    kernel = functools.partial(
        _paged_decode_kernel, P=P, by_hand=by_hand, quantized=quantized,
        contiguous=contiguous, nb=nb, block_size=block_size,
        sliding_window=sliding_window, attention_sinks=attention_sinks,
        logit_softcap=logit_softcap)

    def row_spec(lanes):
        return pl.BlockSpec((None, Hkv, G, lanes),
                            lambda b, c, bt, bp, ln: (b, 0, 0, 0))

    scratch = [
        pltpu.VMEM((Hkv, G, hd), jnp.float32),    # acc
        pltpu.VMEM((Hkv, G, 128), jnp.float32),   # running max (lane bcast)
        pltpu.VMEM((Hkv, G, 128), jnp.float32),   # running denom
    ]
    if by_hand:
        pool_spec = pl.BlockSpec(memory_space=pl.ANY)
        in_specs = [row_spec(hd), pool_spec, pool_spec]
        scratch += [
            pltpu.VMEM((2, Hkv, P, block_size, hd), k_pool.dtype),
            pltpu.VMEM((2, Hkv, P, block_size, hd), v_pool.dtype),
            pltpu.SemaphoreType.DMA((2, P)),
            pltpu.VMEM((P * block_size, 128), jnp.int32),  # row positions
            pltpu.SMEM((1,), jnp.int32),          # buffer of the next chunk
        ]
    else:
        def tile(b, c, bt, bp, ln):
            # slots past the row's walk repeat its last block, which the
            # pipeline then does not copy again
            walk = _row_walk(ln[b], nb=nb, block_size=block_size,
                             contiguous=contiguous)
            return bt[b, jnp.maximum(jnp.minimum(c, walk - 1), 0)]
        kv_spec = pl.BlockSpec(
            (Hkv, None, block_size, hd),
            lambda b, c, bt, bp, ln: (0, tile(b, c, bt, bp, ln), 0, 0))
        # scale rows ride the same walk as their value tiles; each is a
        # whole (1, block_size) row, as Mosaic's tiling rule asks
        scale_spec = pl.BlockSpec(
            (Hkv, None, 1, block_size),
            lambda b, c, bt, bp, ln: (0, tile(b, c, bt, bp, ln), 0, 0))
        in_specs = [row_spec(hd), kv_spec, kv_spec] + (
            [scale_spec, scale_spec] if quantized else [])
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,   # block_tables, block_positions, cache_len
        grid=(B, pl.cdiv(nb, P)),  # chunks innermost: scratch carries the
        in_specs=in_specs,         # combine, and the copies cross steps
        out_specs=(row_spec(hd), row_spec(128), row_spec(128)),
        scratch_shapes=scratch,
    )
    operands = (block_tables, block_positions, cache_len, q, k_pool, v_pool)
    if quantized:
        operands += (k_scale, v_scale)
    out, l_out, m_out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        # the instruction name the device trace shows (and the benchmark's
        # decode roofline matches): keep it stable
        name="paged_decode_attention",
        out_shape=(
            jax.ShapeDtypeStruct((B, Hkv, G, hd), q.dtype),
            jax.ShapeDtypeStruct((B, Hkv, G, 128), jnp.float32),
            jax.ShapeDtypeStruct((B, Hkv, G, 128), jnp.float32),
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
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
