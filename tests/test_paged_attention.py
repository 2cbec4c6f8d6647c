"""Paged flash-decode attention: kernel (interpret) + jnp reference parity
against the dense oracle across ragged lengths / GQA / window+sinks /
softcap; §4.2.2 partial-merge; and the end-to-end pool invariant that
`write_tokens` + paged attention == `gather()` + dense attention under
random alloc/append/free interleavings (deterministic sweep + hypothesis)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hypothesis import given, settings, strategies as st
from repro.configs import registry
from repro.core import combine as C
from repro.kernels import paged_decode_attention as pda
from repro.kernels import ref
from repro.kernels.paged_decode_attention import (paged_decode_attention,
                                                 paged_decode_attention_jnp,
                                                 paged_gather_dense)
from repro.models.attention import (decode_attention_partial_jnp,
                                    paged_decode_attention_partial_jnp)
from repro.serving.kvcache import PagedKVCache


def _rand_paged(seed, B, Hkv, G, hd, bs, nb, spare_blocks=3):
    """Random pool + per-seq block tables with distinct blocks + ragged
    lengths. Returns (q, k_pool, v_pool, block_tables, cache_len)."""
    NB = B * nb + spare_blocks
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    q = jax.random.normal(ks[0], (B, Hkv, G, hd))
    k_pool = jax.random.normal(ks[1], (Hkv, NB, bs, hd))
    v_pool = jax.random.normal(ks[2], (Hkv, NB, bs, hd))
    bt = jax.random.permutation(ks[3], NB)[:B * nb].reshape(B, nb)
    bt = bt.astype(jnp.int32)
    clen = jax.random.randint(ks[4], (B,), 1, nb * bs + 1)
    return q, k_pool, v_pool, bt, clen


@pytest.mark.parametrize("B,Hkv,G,hd,bs,nb", [
    (1, 1, 1, 64, 16, 4),
    (2, 2, 4, 64, 16, 3),       # GQA groups
    (3, 4, 8, 128, 8, 5),       # many small blocks
    (2, 8, 2, 128, 32, 2),
    (1, 2, 16, 64, 16, 7),      # big GQA group, ragged
    (3, 2, 4, 128, 16, 140),    # several chunks of copied blocks, ragged
    (2, 1, 16, 128, 8, 150),    # one KV head (a glm4-9b worker), bs 8
])
def test_paged_kernel_matches_dense_oracle(B, Hkv, G, hd, bs, nb):
    q, kp, vp, bt, clen = _rand_paged(B * hd + nb, B, Hkv, G, hd, bs, nb)
    out = paged_decode_attention(q, kp, vp, bt, clen, interpret=True)
    kc, vc = paged_gather_dense(kp, vp, bt)
    want = ref.decode_attention_ref(q, kc, vc, clen)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=2e-5, rtol=2e-5)
    # the jnp reference path agrees too
    want2 = paged_decode_attention_jnp(q, kp, vp, bt, clen)
    np.testing.assert_allclose(np.asarray(want2), np.asarray(want),
                               atol=1e-6, rtol=1e-6)


@pytest.mark.parametrize("sw,sinks,cap", [
    (20, 0, 0.0), (0, 0, 30.0), (17, 4, 0.0), (11, 2, 50.0)])
def test_paged_kernel_window_sinks_softcap(sw, sinks, cap):
    B, Hkv, G, hd, bs, nb = 2, 2, 4, 64, 16, 4
    q, kp, vp, bt, clen = _rand_paged(7, B, Hkv, G, hd, bs, nb)
    out = paged_decode_attention(q, kp, vp, bt, clen, sliding_window=sw,
                                 attention_sinks=sinks, logit_softcap=cap,
                                 interpret=True)
    want = ref.paged_decode_attention_ref(q, kp, vp, bt, clen,
                                          sliding_window=sw,
                                          attention_sinks=sinks,
                                          logit_softcap=cap)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


# ---------------------------------------------------------------------------
# The chunked walk: a 128-lane head is copied by hand, P blocks a grid step
# (P = CHUNK_TOKENS / 16 at block 16 here), so nb = 2P + 6 walks two whole
# chunks and a ragged third.
# ---------------------------------------------------------------------------
def _chunk_pools(seed, B, Hkv, G, hd, bs, nb, kv):
    """Pools for the chunked walk: distinct random blocks, stale NaN in
    every block no table uses, in ``kv`` ('f32', 'bf16' or 'int8' with
    per-token scales)."""
    NB = B * nb + 5
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    q = jax.random.normal(ks[0], (B, Hkv, G, hd))
    bt = jax.random.permutation(ks[3], NB)[:B * nb].reshape(B, nb)
    bt = bt.astype(jnp.int32)
    unused = jnp.ones((NB,), bool).at[bt.reshape(-1)].set(False)
    scales = {}
    if kv == "int8":
        kp = jax.random.randint(ks[1], (Hkv, NB, bs, hd), -127, 128,
                                jnp.int32).astype(jnp.int8)
        vp = jax.random.randint(ks[2], (Hkv, NB, bs, hd), -127, 128,
                                jnp.int32).astype(jnp.int8)
        sc = [jax.random.uniform(k, (Hkv, NB, 1, bs), jnp.float32, 1e-3, 0.1)
              for k in ks[4:]]
        # stale scales poison nothing either: masked rows read exact zeros
        sc = [jnp.where(unused[None, :, None, None], jnp.nan, x) for x in sc]
        scales = {"k_scale": sc[0], "v_scale": sc[1]}
    else:
        dt = jnp.float32 if kv == "f32" else jnp.bfloat16
        kp = jax.random.normal(ks[1], (Hkv, NB, bs, hd)).astype(dt)
        vp = jax.random.normal(ks[2], (Hkv, NB, bs, hd)).astype(dt)
        pad = unused[None, :, None, None]
        kp, vp = jnp.where(pad, jnp.nan, kp), jnp.where(pad, jnp.nan, vp)
    return q, kp, vp, bt, scales


def _edge_lens(bs, nb, P):
    """One token; exactly a block boundary; exactly a chunk boundary; the
    full table."""
    return jnp.array([1, 3 * bs, P * bs, nb * bs], jnp.int32)


CHUNK = pda.CHUNK_TOKENS // 16   # blocks of 16 a step where copied by hand


@pytest.mark.parametrize("Hkv,G,sw,sinks,cap,kv", [
    (2, 4, 0, 0, 0.0, "f32"),          # nb above P, not a multiple of it
    (4, 4, 0, 0, 0.0, "bf16"),         # a mistral-nemo-12b worker's heads
    (1, 16, 0, 0, 0.0, "bf16"),        # a glm4-9b worker: one KV head, G 16
    (2, 4, 1000, 0, 0.0, "f32"),       # window edge inside the second chunk
    (2, 4, 1000, 5, 30.0, "f32"),      # + sinks in the first, softcap
    (2, 4, 40, 3, 0.0, "bf16"),        # window inside one block
    (2, 4, 0, 0, 0.0, "int8"),         # int8 pools: one block a step
    (2, 4, 1000, 5, 30.0, "int8"),
])
def test_paged_kernel_chunked_walk(Hkv, G, sw, sinks, cap, kv):
    """Rows of 1 token, a block boundary, a chunk boundary and the full
    table in one batch, across chunk boundaries, against the jnp paged
    reference and the dense oracle; NaN in unused pool blocks (and in
    their int8 scales) must not reach any output."""
    B, hd, bs, nb = 4, 128, 16, 2 * CHUNK + 6
    q, kp, vp, bt, scales = _chunk_pools(Hkv * G, B, Hkv, G, hd, bs, nb, kv)
    itemsize = jnp.dtype(kp.dtype).itemsize
    P = pda.decode_blocks_per_step(bs, hd, Hkv, nb, itemsize, bool(scales))
    assert P == (1 if scales else CHUNK)
    clen = _edge_lens(bs, nb, CHUNK)
    kw = dict(sliding_window=sw, attention_sinks=sinks, logit_softcap=cap)
    out = paged_decode_attention(q, kp, vp, bt, clen, interpret=True,
                                 **scales, **kw)
    assert np.isfinite(np.asarray(out)).all()
    want = paged_decode_attention_jnp(q, kp, vp, bt, clen, **scales, **kw)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=2e-5, rtol=2e-5)
    if not scales:
        kc, vc = paged_gather_dense(kp, vp, bt)
        oracle = ref.decode_attention_ref(q, kc, vc, clen, **kw)
        np.testing.assert_allclose(np.asarray(out), np.asarray(oracle),
                                   atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("sw,sinks,cap,kv", [
    (0, 0, 0.0, "f32"), (1000, 5, 30.0, "f32"), (0, 0, 0.0, "bf16"),
    (0, 0, 0.0, "int8")])
def test_block_sharded_chunked_walk(sw, sinks, cap, kv):
    """Block-sharded tables over the chunked walk: 3 shards own a row's
    blocks round robin (POS_PAD on every foreign slot), a fourth owns
    none. The first three merge to the full-table answer; the fourth
    yields the combine identity (l = 0, m = NEG_INF, o = 0)."""
    B, Hkv, G, hd, bs, nb, n = 4, 2, 4, 128, 16, 2 * CHUNK + 6, 3
    q, kp, vp, bt, scales = _chunk_pools(17, B, Hkv, G, hd, bs, nb, kv)
    clen = _edge_lens(bs, nb, CHUNK)
    kw = dict(sliding_window=sw, attention_sinks=sinks, logit_softcap=cap)
    want = paged_decode_attention_jnp(q, kp, vp, bt, clen, **scales, **kw)
    base = jnp.arange(nb, dtype=jnp.int32)[None, :] * bs
    owner = jnp.arange(nb)[None, :] % n
    parts = []
    for s in range(n + 1):
        pos = jnp.where(owner == s, base, pda.POS_PAD)
        o, l, m = paged_decode_attention(
            q, kp, vp, bt, clen, block_positions=pos, interpret=True,
            return_partials=True, **scales, **kw)
        if s == n:
            assert float(jnp.max(l)) == 0.0
            assert float(jnp.max(jnp.abs(o.astype(jnp.float32)))) == 0.0
            assert np.all(np.asarray(m) == pda.NEG_INF)
        parts.append(C.Partial(a=o.astype(jnp.float32) * l[..., None],
                               s=l, m=m))
    got = C.finalize(C.combine_many(parts))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


def test_paged_kernel_partials_merge():
    """The paged kernel's (o, l, m) triple must merge per §4.2.2: attention
    over [0, n) == combine(paged partial over [0, n-1), new token)."""
    B, Hkv, G, hd, bs, nb = 2, 2, 4, 64, 16, 4
    q, kp, vp, bt, clen = _rand_paged(3, B, Hkv, G, hd, bs, nb)
    clen = jnp.maximum(clen, 2)
    kc, vc = paged_gather_dense(kp, vp, bt)
    want = ref.decode_attention_ref(q, kc, vc, clen)
    o, l, m = paged_decode_attention(q, kp, vp, bt, clen - 1,
                                     interpret=True, return_partials=True)
    p_prev = C.Partial(a=o.astype(jnp.float32) * l[..., None], s=l, m=m)
    b = jnp.arange(B)
    p_new = C.partial_attention(q, kc[b, :, clen - 1][:, :, None, None],
                                vc[b, :, clen - 1][:, :, None, None])
    merged = C.finalize(C.combine(p_prev, p_new))
    np.testing.assert_allclose(np.asarray(merged), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


def test_paged_partial_backend_matches_dense_partial():
    """models.attention paged 'jnp' backend == dense partial over the
    gathered view (the engines' hot-path contract)."""
    B, Hkv, G, hd, bs, nb = 3, 4, 2, 64, 8, 5
    q, kp, vp, bt, clen = _rand_paged(11, B, Hkv, G, hd, bs, nb)
    qf = q.reshape(B, Hkv * G, hd)
    kc, vc = paged_gather_dense(kp, vp, bt)
    for kw in ({}, {"sliding_window": 9, "attention_sinks": 2},
               {"logit_softcap": 25.0}):
        p_paged = paged_decode_attention_partial_jnp(qf, kp, vp, bt, clen,
                                                     **kw)
        p_dense = decode_attention_partial_jnp(qf, kc, vc, clen, **kw)
        for a, b in zip(p_paged, p_dense):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-6, rtol=1e-6)


# ---------------------------------------------------------------------------
# Pool-level end-to-end invariant
# ---------------------------------------------------------------------------
def _run_pool_ops(ops, seed=0):
    """Drive a PagedKVCache through (kind, sid, n) ops, mirroring contents
    host-side; after every decode-like append the token lands via the
    batched write_tokens. Returns (kv, mirror: sid -> (k, v) head-major)."""
    from repro.serving.kvcache import OutOfBlocks

    cfg = registry.get_smoke_config("llama3-8b")
    L, Hkv, hd = cfg.num_layers, cfg.num_kv_heads, cfg.resolved_head_dim
    kv = PagedKVCache(cfg, num_blocks=32, block_size=4)
    rng = np.random.default_rng(seed)
    mirror = {}
    for kind, sid, n in ops:
        try:
            if kind == "alloc" and sid not in kv.tables:
                kv.allocate(sid, n)
                k = jnp.asarray(rng.standard_normal((L, Hkv, n, hd)),
                                cfg.dtype)
                v = jnp.asarray(rng.standard_normal((L, Hkv, n, hd)),
                                cfg.dtype)
                kv.write_prefill(sid, k, v)
                mirror[sid] = (k, v)
            elif kind == "append" and sid in kv.tables:
                pos = kv.lengths[sid]
                kv.append_token(sid)
                k1 = jnp.asarray(rng.standard_normal((L, 1, Hkv, hd)),
                                 cfg.dtype)
                v1 = jnp.asarray(rng.standard_normal((L, 1, Hkv, hd)),
                                 cfg.dtype)
                kv.write_tokens([sid], k1, v1, [pos])
                mirror[sid] = (
                    jnp.concatenate([mirror[sid][0],
                                     jnp.swapaxes(k1, 1, 2)], 2),
                    jnp.concatenate([mirror[sid][1],
                                     jnp.swapaxes(v1, 1, 2)], 2))
            elif kind == "free" and sid in kv.tables:
                kv.free_seq(sid)
                del mirror[sid]
        except OutOfBlocks:
            pass
    return kv, mirror


def _assert_paged_equals_dense(kv, mirror, seed=0):
    """For the live batch: block_table_batch + paged attention must equal
    gather() + dense attention — per layer, both jnp and kernel paths."""
    ids = sorted(kv.tables)
    if not ids:
        return
    cfg = kv.cfg
    Hkv, hd = cfg.num_kv_heads, cfg.resolved_head_dim
    G = cfg.num_heads // Hkv
    B = len(ids)
    tables, lens = kv.block_table_batch(ids)
    bt, ln = jnp.asarray(tables), jnp.asarray(lens)
    pad = int(tables.shape[1]) * kv.block_size
    kd, vd, _ = kv.gather(ids, pad)   # dense oracle (L, B, pad, Hkv, hd)
    q = jax.random.normal(jax.random.PRNGKey(seed), (B, Hkv, G, hd))
    for layer in (0, kd.shape[0] - 1):
        want = ref.decode_attention_ref(
            q, jnp.swapaxes(kd[layer], 1, 2).astype(jnp.float32),
            jnp.swapaxes(vd[layer], 1, 2).astype(jnp.float32), ln)
        got_jnp = paged_decode_attention_jnp(
            q, kv.k_pool[layer].astype(jnp.float32),
            kv.v_pool[layer].astype(jnp.float32), bt, ln)
        np.testing.assert_allclose(np.asarray(got_jnp), np.asarray(want),
                                   atol=1e-5, rtol=1e-5)
        got_krn = paged_decode_attention(
            q, kv.k_pool[layer].astype(jnp.float32),
            kv.v_pool[layer].astype(jnp.float32), bt, ln, interpret=True)
        np.testing.assert_allclose(np.asarray(got_krn), np.asarray(want),
                                   atol=2e-5, rtol=2e-5)
    # and the pool contents round-trip exactly (head-major mirror)
    for i, sid in enumerate(ids):
        n = kv.lengths[sid]
        np.testing.assert_array_equal(
            np.asarray(kd[:, i, :n]),
            np.asarray(jnp.swapaxes(mirror[sid][0], 1, 2)))


def test_paged_equals_dense_after_deterministic_interleaving():
    rng = np.random.default_rng(42)
    ops = []
    for _ in range(60):
        kind = rng.choice(["alloc", "append", "append", "free"])
        ops.append((str(kind), int(rng.integers(0, 6)),
                    int(rng.integers(1, 20))))
    kv, mirror = _run_pool_ops(ops, seed=1)
    _assert_paged_equals_dense(kv, mirror, seed=2)


@settings(deadline=None, max_examples=15)
@given(ops=st.lists(
    st.tuples(st.sampled_from(["alloc", "append", "append", "free"]),
              st.integers(0, 5), st.integers(1, 20)),
    min_size=1, max_size=40))
def test_paged_equals_dense_hypothesis(ops):
    kv, mirror = _run_pool_ops(ops, seed=3)
    _assert_paged_equals_dense(kv, mirror, seed=4)


# ---------------------------------------------------------------------------
# Block-sharded partials: positions-aware kernel/jnp paths
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("sw,sinks,cap", [
    (0, 0, 0.0), (20, 0, 0.0), (17, 4, 0.0), (11, 2, 30.0)])
def test_block_sharded_partials_merge_to_oracle(sw, sinks, cap):
    """Split a table's blocks over n shards (contiguous pool slices, masked
    foreign slots with POS_PAD positions); per-shard partials — Pallas
    kernel with block_positions AND the positions-aware jnp partial — must
    combine_many to the full-table oracle, window/sinks included."""
    from repro.kernels.paged_decode_attention import (POS_PAD,
                                                     paged_decode_attention)
    from repro.models.attention import paged_decode_attention_partial_pos_jnp

    B, Hkv, G, hd, bs, nb, n = 2, 2, 4, 64, 16, 5, 3
    NB = 24  # divisible by n
    ks = jax.random.split(jax.random.PRNGKey(5), 5)
    q = jax.random.normal(ks[0], (B, Hkv, G, hd))
    kp = jax.random.normal(ks[1], (Hkv, NB, bs, hd))
    vp = jax.random.normal(ks[2], (Hkv, NB, bs, hd))
    bt = jax.random.permutation(ks[3], NB)[:B * nb].reshape(B, nb)
    bt = bt.astype(jnp.int32)
    clen = jnp.array([nb * bs, 37], jnp.int32)
    want = ref.paged_decode_attention_ref(q, kp, vp, bt, clen,
                                          sliding_window=sw,
                                          attention_sinks=sinks,
                                          logit_softcap=cap)
    npb = NB // n
    base = jnp.arange(nb, dtype=jnp.int32)[None, :] * bs
    owner, local = bt // npb, bt % npb
    parts_k, parts_j = [], []
    qf = q.reshape(B, Hkv * G, hd)
    for s in range(n):
        pos = jnp.where(owner == s, base, POS_PAD)
        sl = slice(s * npb, (s + 1) * npb)
        o, l, m = paged_decode_attention(
            q, kp[:, sl], vp[:, sl], local, clen, block_positions=pos,
            sliding_window=sw, attention_sinks=sinks, logit_softcap=cap,
            interpret=True, return_partials=True)
        parts_k.append(C.Partial(a=o.astype(jnp.float32) * l[..., None],
                                 s=l, m=m))
        parts_j.append(paged_decode_attention_partial_pos_jnp(
            qf, kp[:, sl], vp[:, sl], local, pos, clen, window_total=clen,
            sliding_window=sw, attention_sinks=sinks, logit_softcap=cap))
    got_k = C.finalize(C.combine_many(parts_k))
    np.testing.assert_allclose(np.asarray(got_k), np.asarray(want),
                               atol=2e-5, rtol=2e-5)
    got_j = C.finalize(C.combine_many(parts_j)).reshape(B, Hkv, G, hd)
    np.testing.assert_allclose(np.asarray(got_j), np.asarray(want),
                               atol=1e-5, rtol=1e-5)


def test_empty_shard_partial_is_combine_identity():
    """A shard owning zero of a sequence's blocks (routine under block
    sharding) must contribute the identity partial."""
    from repro.kernels.paged_decode_attention import (POS_PAD,
                                                     paged_decode_attention)
    from repro.models.attention import paged_decode_attention_partial_pos_jnp

    B, Hkv, G, hd, bs, nb = 1, 2, 2, 64, 8, 3
    q, kp, vp, bt, clen = _rand_paged(9, B, Hkv, G, hd, bs, nb)
    pos_all_pad = jnp.full_like(bt, POS_PAD)
    o, l, m = paged_decode_attention(q, kp, vp, bt, clen,
                                     block_positions=pos_all_pad,
                                     interpret=True, return_partials=True)
    assert float(jnp.max(l)) == 0.0
    assert float(jnp.max(o.astype(jnp.float32))) == 0.0
    p_empty = paged_decode_attention_partial_pos_jnp(
        q.reshape(B, Hkv * G, hd), kp, vp, bt, pos_all_pad, clen)
    assert float(jnp.max(p_empty.s)) == 0.0
    assert np.all(np.asarray(p_empty.m) == -np.inf)
    # merging the empty partial into a real one changes nothing
    full = paged_decode_attention_partial_pos_jnp(
        q.reshape(B, Hkv * G, hd), kp, vp, bt,
        jnp.arange(nb, dtype=jnp.int32)[None, :] * bs, clen)
    merged = C.finalize(C.combine(full, p_empty))
    np.testing.assert_allclose(np.asarray(merged),
                               np.asarray(C.finalize(full)), atol=1e-6)


@pytest.mark.parametrize("sw,sinks", [(1, 0), (1, 3), (2, 0)])
def test_pallas_backend_matches_jnp_at_tiny_windows(sw, sinks):
    """Serving-contract window mapping: sliding_window=1 means only the
    incoming token is in-window (stored prefix reduces to the sinks) — the
    pallas partial backend must agree with the jnp one, not silently drop
    the mask (kernel sw=0 means 'no window')."""
    import repro.kernels.ops as ops
    from repro.models.attention import paged_decode_attention_partial_jnp

    B, Hkv, G, hd, bs, nb = 2, 2, 2, 64, 8, 3
    q, kp, vp, bt, clen = _rand_paged(13, B, Hkv, G, hd, bs, nb)
    qf = q.reshape(B, Hkv * G, hd)
    kw = dict(sliding_window=sw, attention_sinks=sinks)
    p_jnp = paged_decode_attention_partial_jnp(qf, kp, vp, bt, clen, **kw)
    p_pal = ops._pallas_paged_decode_partial_backend(qf, kp, vp, bt, clen,
                                                     **kw)
    # compare finalized outputs merged with nothing: a/s may differ in
    # normalisation base (m) but finalize(a/s) must agree; guard the empty
    # case (sw=1, sinks=0 -> s == 0 on both)
    np.testing.assert_allclose(np.asarray(p_pal.s), np.asarray(p_jnp.s),
                               atol=2e-5, rtol=2e-5)
    denom_j = np.maximum(np.asarray(p_jnp.s), 1e-30)[..., None]
    denom_p = np.maximum(np.asarray(p_pal.s), 1e-30)[..., None]
    np.testing.assert_allclose(np.asarray(p_pal.a) / denom_p,
                               np.asarray(p_jnp.a) / denom_j,
                               atol=2e-5, rtol=2e-5)
