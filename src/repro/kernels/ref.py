"""Pure-jnp oracles for every Pallas kernel (the correctness contract the
shape/dtype sweep tests assert against)."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def decode_attention_ref(q, k_cache, v_cache, cache_len, *,
                         sliding_window: int = 0, attention_sinks: int = 0,
                         logit_softcap: float = 0.0,
                         k_scale=None, v_scale=None) -> jax.Array:
    """q: (B, Hkv, G, hd); caches: HEAD-MAJOR (B, Hkv, S, hd); cache_len:
    (B,). Returns (B, Hkv, G, hd). fp32 math throughout.

    int8 caches pass per-token ``k_scale``/``v_scale`` (B, Hkv, S): the k
    scale folds into the scores right after the QK einsum (before softcap),
    the v scale into the probabilities before the PV einsum — the fused
    dequant convention every int8 backend (kernel and jnp) follows."""
    B, Hkv, G, hd = q.shape
    S = k_cache.shape[2]
    scale = 1.0 / jnp.sqrt(jnp.asarray(hd, jnp.float32))
    s = jnp.einsum("bhgk,bhsk->bhgs", q.astype(jnp.float32) * scale,
                   k_cache.astype(jnp.float32))
    if k_scale is not None:
        s = s * k_scale[:, :, None, :].astype(jnp.float32)
    if logit_softcap > 0.0:
        s = logit_softcap * jnp.tanh(s / logit_softcap)
    pos = jnp.arange(S)[None, :]
    valid = pos < cache_len[:, None]
    if sliding_window > 0:
        in_window = pos >= (cache_len[:, None] - sliding_window)
        if attention_sinks > 0:
            in_window |= pos < attention_sinks
        valid &= in_window
    s = jnp.where(valid[:, None, None, :], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    if v_scale is not None:
        p = p * v_scale[:, :, None, :].astype(jnp.float32)
    out = jnp.einsum("bhgs,bhsk->bhgk", p, v_cache.astype(jnp.float32))
    return out.astype(q.dtype)


def paged_decode_attention_ref(q, k_pool, v_pool, block_tables, cache_len, *,
                               sliding_window: int = 0,
                               attention_sinks: int = 0,
                               logit_softcap: float = 0.0) -> jax.Array:
    """Oracle for the paged flash-decode kernel: gather the dense head-major
    view through the block table, then the dense oracle math.

    q: (B, Hkv, G, hd); k_pool/v_pool: HEAD-MAJOR (Hkv, num_blocks,
    block_size, hd); block_tables: (B, nb) int32; cache_len: (B,)."""
    from repro.kernels.paged_decode_attention import paged_gather_dense

    kc, vc = paged_gather_dense(k_pool, v_pool, block_tables)
    return decode_attention_ref(q, kc, vc, cache_len,
                                sliding_window=sliding_window,
                                attention_sinks=attention_sinks,
                                logit_softcap=logit_softcap)


def paged_decode_attention_int8_ref(q, k_pool, v_pool, k_scale, v_scale,
                                    block_tables, cache_len, *,
                                    block_positions=None,
                                    sliding_window: int = 0,
                                    attention_sinks: int = 0,
                                    logit_softcap: float = 0.0) -> jax.Array:
    """BIT-PARITY oracle for the int8 paged flash-decode kernel: replays the
    kernel's exact op sequence (same lax primitives, same order, same fp32
    intermediates, fused scale multiplies in the same places) per (b, h)
    grid cell in a host loop — interpret-mode Pallas executes the identical
    XLA ops, so the contract is ``assert_array_equal``, not allclose.

    q: (B, Hkv, G, hd); k_pool/v_pool: int8 (Hkv, num_blocks, bs, hd);
    k_scale/v_scale: fp32 (Hkv, num_blocks, 1, bs); block_tables: (B, nb).
    Test-scale only (python grid loop)."""
    from repro.kernels.paged_decode_attention import (NEG_INF,
                                                      default_block_positions)

    B, Hkv, G, hd = q.shape
    bs = k_pool.shape[2]
    nb = block_tables.shape[1]
    if block_positions is None:
        block_positions = default_block_positions(B, nb, bs)
    scale = 1.0 / jnp.sqrt(jnp.asarray(hd, jnp.float32))
    out = []
    for b in range(B):
        heads = []
        for h in range(Hkv):
            qf = q[b, h].astype(jnp.float32)                  # (G, hd)
            acc = jnp.zeros((G, hd), jnp.float32)
            m = jnp.full((G, 1), NEG_INF, jnp.float32)
            ell = jnp.zeros((G, 1), jnp.float32)
            for kb in range(nb):
                blk = block_tables[b, kb]
                k = k_pool[h, blk].astype(jnp.float32)        # (bs, hd)
                v = v_pool[h, blk].astype(jnp.float32)
                ks = k_scale[h, blk, 0]                       # (bs,)
                vs = v_scale[h, blk, 0]
                pos = block_positions[b, kb] + jax.lax.broadcasted_iota(
                    jnp.int32, (1, bs), 1)[0]
                row_valid = pos < cache_len[b]
                if sliding_window > 0:
                    in_window = pos >= (cache_len[b] - sliding_window)
                    if attention_sinks > 0:
                        in_window |= pos < attention_sinks
                    row_valid &= in_window
                v = jnp.where(row_valid[:, None], v, 0.0)
                s = jax.lax.dot_general(
                    qf * scale, k, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32)       # (G, bs)
                s = s * ks[None, :]
                if logit_softcap > 0.0:
                    s = logit_softcap * jnp.tanh(s / logit_softcap)
                valid = jnp.broadcast_to(row_valid[None, :], s.shape)
                s = jnp.where(valid, s, NEG_INF)
                m_cur = jnp.max(s, axis=-1, keepdims=True)
                m_new = jnp.maximum(m, jnp.broadcast_to(m_cur, m.shape))
                alpha = jnp.exp(m[:, :1] - m_new[:, :1])
                p = jnp.exp(s - m_new[:, :1])
                p = jnp.where(valid, p, 0.0)
                ell = ell * alpha + jnp.sum(p, axis=-1, keepdims=True)
                acc = acc * alpha + jax.lax.dot_general(
                    p * vs[None, :], v, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
                m = m_new
            denom = jnp.maximum(ell[:, :1], 1e-30)
            heads.append((acc / denom).astype(q.dtype))
        out.append(jnp.stack(heads))
    return jnp.stack(out)                                     # (B,Hkv,G,hd)


def paged_prefill_chunk_attention_int8_ref(q, k_pool, v_pool,
                                           k_scale, v_scale, block_table,
                                           k_chunk, v_chunk, *,
                                           sliding_window: int = 0,
                                           attention_sinks: int = 0,
                                           logit_softcap: float = 0.0
                                           ) -> jax.Array:
    """BIT-PARITY oracle for the int8 paged chunk-prefill kernel — the same
    exact-op-replay contract as :func:`paged_decode_attention_int8_ref`,
    per (h, step) grid cell. q: (C, H, hd); k_chunk/v_chunk: (C, Hkv, hd)
    full precision (chunk scale is the exact identity 1.0)."""
    from repro.kernels.paged_prefill_attention import NEG_INF

    C, H, hd = q.shape
    Hkv, _, bs, _ = k_pool.shape
    G = H // Hkv
    nb = block_table.shape[0]
    nc = -(-C // bs)
    pad = nc * bs - C
    kc = jnp.swapaxes(k_chunk, 0, 1)
    vc = jnp.swapaxes(v_chunk, 0, 1)
    if pad:
        kc = jnp.pad(kc, ((0, 0), (0, pad), (0, 0)))
        vc = jnp.pad(vc, ((0, 0), (0, pad), (0, 0)))
    kc = kc.reshape(Hkv, nc, bs, hd)
    vc = vc.reshape(Hkv, nc, bs, hd)
    qg = q.reshape(C, Hkv, G, hd).transpose(1, 2, 0, 3).reshape(
        Hkv, G * C, hd)
    rows = G * C
    total_len = nb * bs + C
    scale = 1.0 / jnp.sqrt(jnp.asarray(hd, jnp.float32))
    one = jnp.ones((bs,), jnp.float32)
    outs = []
    for h in range(Hkv):
        qf = qg[h].astype(jnp.float32)                        # (rows, hd)
        acc = jnp.zeros((rows, hd), jnp.float32)
        m = jnp.full((rows, 1), NEG_INF, jnp.float32)
        ell = jnp.zeros((rows, 1), jnp.float32)
        for kb in range(nb + nc):
            if kb < nb:
                blk = block_table[kb]
                k = k_pool[h, blk].astype(jnp.float32)
                v = v_pool[h, blk].astype(jnp.float32)
                ks, vs = k_scale[h, blk, 0], v_scale[h, blk, 0]
            else:
                k = kc[h, kb - nb].astype(jnp.float32)
                v = vc[h, kb - nb].astype(jnp.float32)
                ks = vs = one
            pos_k = kb * bs + jax.lax.broadcasted_iota(
                jnp.int32, (1, bs), 1)[0]
            col_valid = pos_k < total_len
            pos_q = (nb * bs + jax.lax.broadcasted_iota(
                jnp.int32, (rows, bs), 0) % C)
            valid = col_valid[None, :] & (pos_k[None, :] <= pos_q)
            if sliding_window > 0:
                in_window = pos_k[None, :] > (pos_q - sliding_window)
                if attention_sinks > 0:
                    in_window |= jnp.broadcast_to(
                        pos_k[None, :] < attention_sinks, valid.shape)
                valid &= in_window
            v = jnp.where(col_valid[:, None], v, 0.0)
            s = jax.lax.dot_general(
                qf * scale, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            s = s * ks[None, :]
            if logit_softcap > 0.0:
                s = logit_softcap * jnp.tanh(s / logit_softcap)
            s = jnp.where(valid, s, NEG_INF)
            m_cur = jnp.max(s, axis=-1, keepdims=True)
            m_new = jnp.maximum(m, jnp.broadcast_to(m_cur, m.shape))
            alpha = jnp.exp(m[:, :1] - m_new[:, :1])
            p = jnp.exp(s - m_new[:, :1])
            p = jnp.where(valid, p, 0.0)
            ell = ell * alpha + jnp.sum(p, axis=-1, keepdims=True)
            acc = acc * alpha + jax.lax.dot_general(
                p * vs[None, :], v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m = m_new
        denom = jnp.maximum(ell[:, :1], 1e-30)
        outs.append((acc / denom).astype(q.dtype))
    out = jnp.stack(outs)                                     # (Hkv,G·C,hd)
    return out.reshape(Hkv, G, C, hd).transpose(2, 0, 1, 3).reshape(C, H, hd)


def rwkv6_scan_ref(r, k, v, w, u) -> jax.Array:
    """RWKV6 recurrence oracle.

    r, k, v, w: (B, S, H, P) (w = per-step decay in (0,1), fp32 math);
    u: (H, P) bonus. Returns y: (B, S, H, P), fp32.
      y_t = r_t · (S_{t-1} + u ⊙ k_t ⊗ v_t);  S_t = w_t ⊙ S_{t-1} + k_t ⊗ v_t
    """
    B, S, H, P = r.shape
    rf, kf, vf, wf = [a.astype(jnp.float32) for a in (r, k, v, w)]
    uf = u.astype(jnp.float32)

    def step(state, inp):
        r_t, k_t, v_t, w_t = inp  # (B, H, P)
        kv = k_t[..., :, None] * v_t[..., None, :]  # (B, H, P, P)
        y = jnp.einsum("bhp,bhpq->bhq", r_t, state + uf[..., None] * kv)
        return w_t[..., :, None] * state + kv, y

    xs = tuple(a.transpose(1, 0, 2, 3) for a in (rf, kf, vf, wf))
    _, ys = jax.lax.scan(step, jnp.zeros((B, H, P, P), jnp.float32), xs)
    return ys.transpose(1, 0, 2, 3)


def ssm_scan_ref(x, dt, B_in, C_in, decay) -> jax.Array:
    """Mamba2 scalar-decay SSD oracle.

    x: (B, S, H, P) (already dt-scaled inputs), dt unused placeholder kept
    for API parity; B_in, C_in: (B, S, N); decay: (B, S, H) in (0,1].
    Returns y: (B, S, H, P) fp32:  h_t = decay_t h_{t-1} + x_t ⊗ B_t;
    y_t = h_t · C_t.
    """
    Bb, S, H, P = x.shape
    N = B_in.shape[-1]

    def step(h, inp):
        x_t, b_t, c_t, a_t = inp
        h = h * a_t[:, :, None, None] + x_t[..., None] * b_t[:, None, None, :]
        return h, jnp.einsum("bhpn,bn->bhp", h, c_t)

    xs = (x.astype(jnp.float32).transpose(1, 0, 2, 3),
          B_in.astype(jnp.float32).transpose(1, 0, 2),
          C_in.astype(jnp.float32).transpose(1, 0, 2),
          decay.astype(jnp.float32).transpose(1, 0, 2))
    _, ys = jax.lax.scan(step, jnp.zeros((Bb, H, P, N), jnp.float32), xs)
    return ys.transpose(1, 0, 2, 3)
