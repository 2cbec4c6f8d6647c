"""Paged KV cache: hypothesis-driven allocator invariants + data movement."""
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.configs import registry
from repro.serving.kvcache import OutOfBlocks, PagedKVCache


def _cache(num_blocks=32, block_size=4):
    cfg = registry.get_smoke_config("llama3-8b")
    return PagedKVCache(cfg, num_blocks, block_size)


@settings(deadline=None, max_examples=30)
@given(ops=st.lists(
    st.tuples(st.sampled_from(["alloc", "append", "free"]),
              st.integers(0, 7), st.integers(1, 40)),
    min_size=1, max_size=60))
def test_allocator_invariants(ops):
    kv = _cache()
    total = kv.num_blocks
    for kind, sid, n in ops:
        try:
            if kind == "alloc" and sid not in kv.tables:
                kv.allocate(sid, n)
            elif kind == "append" and sid in kv.tables:
                kv.append_token(sid)
            elif kind == "free" and sid in kv.tables:
                kv.free_seq(sid)
        except OutOfBlocks:
            pass
        # invariants after every op:
        owned = [b for t in kv.tables.values() for b in t]
        assert len(owned) == len(set(owned)), "block owned twice"
        assert len(owned) + len(kv.free) == total, "blocks leaked"
        assert set(owned).isdisjoint(kv.free)
        for s, ln in kv.lengths.items():
            assert len(kv.tables[s]) * kv.block_size >= ln, \
                "capacity below token count"


def test_out_of_blocks_raises_and_preserves_state():
    kv = _cache(num_blocks=4, block_size=4)
    kv.allocate(1, 12)  # 3 blocks
    with pytest.raises(OutOfBlocks):
        kv.allocate(2, 12)
    assert 2 not in kv.tables
    assert len(kv.free) == 1
    kv.free_seq(1)
    assert len(kv.free) == 4


def test_write_gather_roundtrip():
    kv = _cache(num_blocks=16, block_size=4)
    cfg = kv.cfg
    L, Hkv, hd = cfg.num_layers, cfg.num_kv_heads, cfg.resolved_head_dim
    rng = np.random.default_rng(0)
    lens = {1: 7, 2: 10}
    data = {}
    for sid, n in lens.items():
        kv.allocate(sid, n)
        # prefill hands the pool HEAD-MAJOR (L, Hkv, S, hd) — no transpose
        k = jnp.asarray(rng.standard_normal((L, Hkv, n, hd)), cfg.dtype)
        v = jnp.asarray(rng.standard_normal((L, Hkv, n, hd)), cfg.dtype)
        kv.write_prefill(sid, k, v)
        data[sid] = (k, v)
    # append one token each: allocator bookkeeping + ONE batched scatter
    k1 = jnp.asarray(rng.standard_normal((L, 2, Hkv, hd)), cfg.dtype)
    v1 = jnp.asarray(rng.standard_normal((L, 2, Hkv, hd)), cfg.dtype)
    positions = [lens[sid] for sid in (1, 2)]
    for sid in lens:
        kv.append_token(sid)
    kv.write_tokens([1, 2], k1, v1, positions)
    for i, sid in enumerate((1, 2)):
        data[sid] = (jnp.concatenate([data[sid][0], k1[:, i, :, None]], 2),
                     jnp.concatenate([data[sid][1], v1[:, i, :, None]], 2))
    pad = 12
    k, v, out_lens = kv.gather([1, 2], pad)
    assert k.shape == (L, 2, pad, Hkv, hd)  # gather stays seq-major (oracle)
    for i, sid in enumerate([1, 2]):
        n = lens[sid] + 1
        assert int(out_lens[i]) == n
        np.testing.assert_array_equal(
            np.asarray(k[:, i, :n]),
            np.asarray(jnp.swapaxes(data[sid][0], 1, 2)))
        np.testing.assert_array_equal(
            np.asarray(v[:, i, :n]),
            np.asarray(jnp.swapaxes(data[sid][1], 1, 2)))


def test_write_token_single_matches_batched():
    """Per-sequence write_token (compat path) lands in the same slots as the
    batched write_tokens scatter."""
    cfg = registry.get_smoke_config("llama3-8b")
    L, Hkv, hd = cfg.num_layers, cfg.num_kv_heads, cfg.resolved_head_dim
    rng = np.random.default_rng(1)
    a, b = PagedKVCache(cfg, 16, 4), PagedKVCache(cfg, 16, 4)
    for kv in (a, b):
        kv.allocate(7, 5)
        kv.allocate(9, 3)
    k1 = jnp.asarray(rng.standard_normal((L, 2, Hkv, hd)), cfg.dtype)
    v1 = jnp.asarray(rng.standard_normal((L, 2, Hkv, hd)), cfg.dtype)
    for kv in (a, b):
        kv.append_token(7)
        kv.append_token(9)
    a.write_tokens([7, 9], k1, v1, [5, 3])
    b.write_token(7, k1[:, 0], v1[:, 0], 5)
    b.write_token(9, k1[:, 1], v1[:, 1], 3)
    np.testing.assert_array_equal(np.asarray(a.k_pool), np.asarray(b.k_pool))
    np.testing.assert_array_equal(np.asarray(a.v_pool), np.asarray(b.v_pool))


# ---------------------------------------------------------------------------
# Cross-chip block sharding (n_shards > 1): round-robin placement
# ---------------------------------------------------------------------------
def _sharded_cache(num_blocks=32, block_size=4, n_shards=4):
    cfg = registry.get_smoke_config("llama3-8b")
    return PagedKVCache(cfg, num_blocks, block_size, n_shards=n_shards)


def test_shards_must_divide_num_blocks():
    with pytest.raises(ValueError):
        _sharded_cache(num_blocks=30, n_shards=4)


def test_round_robin_spans_shards_within_one_block():
    """A single long sequence's blocks land round-robin: every shard holds
    KV and the per-shard live-token counts differ by at most one block —
    the `long_500k`-spans-chips acceptance criterion."""
    kv = _sharded_cache(num_blocks=64, block_size=4, n_shards=4)
    kv.allocate(0, 101)  # 26 blocks over 4 shards
    toks = kv.shard_live_tokens([0])
    assert (toks > 0).all()
    assert toks.max() - toks.min() <= kv.block_size
    assert toks.sum() == 101
    # appends keep the rotation going
    for _ in range(23):
        kv.append_token(0)
    toks = kv.shard_live_tokens([0])
    assert toks.max() - toks.min() <= kv.block_size
    assert toks.sum() == 124


def test_block_table_shards_local_ids_and_positions():
    """Local tables index each shard's contiguous pool slice; positions are
    the slot's global base; pad slots carry POS_PAD; the union reconstructs
    the global table exactly."""
    from repro.serving.kvcache import POS_PAD

    kv = _sharded_cache(num_blocks=32, block_size=4, n_shards=4)
    kv.allocate(0, 37)
    kv.allocate(1, 6)
    ids = [0, 1]
    lt, lp, st = kv.block_table_shards(ids)
    npb = kv.blocks_per_shard
    assert lt.shape == lp.shape and lt.shape[:2] == (4, 2)
    seen = {sid: {} for sid in ids}
    for s in range(4):
        for i, sid in enumerate(ids):
            for j in range(lt.shape[2]):
                if lp[s, i, j] == POS_PAD:
                    continue
                assert 0 <= lt[s, i, j] < npb
                slot = lp[s, i, j] // kv.block_size
                seen[sid][slot] = s * npb + int(lt[s, i, j])
    for sid in ids:
        assert [seen[sid][j] for j in range(len(kv.tables[sid]))] == \
            kv.tables[sid]
    # live-token accounting sums to the sequence lengths
    np.testing.assert_array_equal(st.sum(0), [37, 6])


def test_freed_blocks_return_to_owner_shard():
    kv = _sharded_cache(num_blocks=32, block_size=4, n_shards=4)
    kv.allocate(0, 40)
    kv.allocate(1, 24)
    kv.free_seq(0)
    kv.free_seq(1)
    npb = kv.blocks_per_shard
    for s, free in enumerate(kv._free_shard):
        assert len(free) == npb
        assert all(b // npb == s for b in free)


# ---------------------------------------------------------------------------
# Prefix sharing: refcounts, share_blocks, copy-on-write
# ---------------------------------------------------------------------------
def _check_ref_invariants(kv):
    """The refcount invariants that replace exclusive ownership."""
    refs = {}
    for t in kv.tables.values():
        for b in t:
            refs[b] = refs.get(b, 0) + 1
    assert refs == kv.refcounts, "refcount != live table references"
    assert len(refs) + len(kv.free) == kv.num_blocks, "blocks leaked"
    assert set(refs).isdisjoint(kv.free)
    npb = kv.blocks_per_shard
    for s in range(kv.n_shards):
        assert all(b // npb == s for b in kv._free_shard[s])
    for sid, ln in kv.lengths.items():
        assert len(kv.tables[sid]) * kv.block_size >= ln


def test_share_blocks_refcounts_and_free_order():
    kv = _cache(num_blocks=16, block_size=4)
    kv.allocate(0, 10)                       # 3 blocks
    assert kv.share_blocks(0, 1, 8) == 2     # 2 full blocks, no pool cost
    assert kv.used_blocks == 3               # physical, shared counted once
    assert [kv.refcounts[b] for b in kv.tables[0]] == [2, 2, 1]
    assert kv.tables[1] == kv.tables[0][:2]
    kv.allocate(1, 14)                       # extend: 2 shared + 2 private
    assert len(kv.tables[1]) == 4 and kv.used_blocks == 5
    _check_ref_invariants(kv)
    # donor frees first: shared blocks survive through the recipient
    donor_blocks = list(kv.tables[0])
    kv.free_seq(0)
    assert kv.refcounts[donor_blocks[0]] == 1
    assert donor_blocks[2] in kv.free        # donor-private block released
    assert donor_blocks[0] not in kv.free
    _check_ref_invariants(kv)
    kv.free_seq(1)
    assert len(kv.free) == kv.num_blocks
    assert kv.refcounts == {}


def test_share_blocks_validates_range_and_double_alloc():
    kv = _cache(num_blocks=8, block_size=4)
    kv.allocate(0, 6)
    with pytest.raises(ValueError):
        kv.share_blocks(0, 1, 7)             # beyond donor's stored tokens
    with pytest.raises(ValueError):
        kv.share_blocks(0, 1, 0)
    kv.share_blocks(0, 1, 4)
    with pytest.raises(AssertionError):
        kv.share_blocks(0, 1, 4)             # dst already allocated


def test_cow_fork_parity_vs_unshared_oracle():
    """Fork a sequence at a NON-aligned point (partial tail shared), let
    both sides append divergent tokens: pool contents must match two
    independent caches written with the same data, and the donor's bytes
    must never change."""
    cfg = registry.get_smoke_config("llama3-8b")
    L, Hkv, hd = cfg.num_layers, cfg.num_kv_heads, cfg.resolved_head_dim
    rng = np.random.default_rng(5)

    def tok(seed):
        r = np.random.default_rng(seed)
        return (jnp.asarray(r.standard_normal((L, Hkv, hd)), cfg.dtype),
                jnp.asarray(r.standard_normal((L, Hkv, hd)), cfg.dtype))

    shared = PagedKVCache(cfg, 16, 4)
    oracle = PagedKVCache(cfg, 16, 4)
    n0 = 6                                   # 1 full block + 2-token tail
    k = jnp.asarray(rng.standard_normal((L, Hkv, n0, hd)), cfg.dtype)
    v = jnp.asarray(rng.standard_normal((L, Hkv, n0, hd)), cfg.dtype)
    shared.allocate(0, n0)
    shared.write_prefill(0, k, v)
    shared.share_blocks(0, 1, n0)            # fork: partial tail shared too
    assert shared.used_blocks == 2
    oracle.allocate(0, n0)
    oracle.write_prefill(0, k, v)
    oracle.allocate(1, n0)
    oracle.write_prefill(1, k, v)
    # both sides diverge: different tokens at position 6. The FIRST writer
    # needs a fresh block (CoW fork); afterwards the tail is private on
    # both sides and the second write goes in place.
    for i, (sid, seed) in enumerate(((0, 10), (1, 11))):
        for kvc in (shared, oracle):
            expect = 1 if (kvc is shared and i == 0) else 0
            assert kvc.blocks_to_append(sid) == expect
            kvc.append_token(sid)
            ka, va = tok(seed)
            kvc.write_token(sid, ka, va, n0)
    assert shared.cow_forks == 1             # exactly the partial tail
    assert shared.used_blocks == 3           # full block still shared once
    _check_ref_invariants(shared)
    for sid in (0, 1):
        ks, vs, _ = shared.gather([sid], 8)
        ko, vo, _ = oracle.gather([sid], 8)
        np.testing.assert_array_equal(np.asarray(ks), np.asarray(ko))
        np.testing.assert_array_equal(np.asarray(vs), np.asarray(vo))


def test_borrower_prefill_cow_never_corrupts_donor():
    """A borrower re-prefilling over still-shared blocks (divergent write)
    forks them; the donor's bytes are untouched. The ORIGINAL allocator's
    write goes through in place — it is the canonical fill recipients that
    shared within the same admission wave are waiting on."""
    cfg = registry.get_smoke_config("llama3-8b")
    L, Hkv, hd = cfg.num_layers, cfg.num_kv_heads, cfg.resolved_head_dim
    rng = np.random.default_rng(6)
    kv = _cache(num_blocks=16, block_size=4)
    kv.allocate(0, 8)
    kv.share_blocks(0, 1, 8)                 # borrow BEFORE the donor fill
    k0 = jnp.asarray(rng.standard_normal((L, Hkv, 8, hd)), cfg.dtype)
    v0 = jnp.asarray(rng.standard_normal((L, Hkv, 8, hd)), cfg.dtype)
    kv.write_prefill(0, k0, v0)              # donor fill: NO fork, in place
    assert kv.cow_forks == 0
    assert kv.tables[1] == kv.tables[0]
    # borrower diverges with a full re-prefill: fork, donor intact
    k1 = jnp.asarray(rng.standard_normal((L, Hkv, 8, hd)), cfg.dtype)
    v1 = jnp.asarray(rng.standard_normal((L, Hkv, 8, hd)), cfg.dtype)
    kv.write_prefill(1, k1, v1)
    assert kv.cow_forks == 2
    assert set(kv.tables[1]).isdisjoint(kv.tables[0])
    kd, vd, _ = kv.gather([0], 8)
    np.testing.assert_array_equal(
        np.asarray(kd[:, 0]), np.asarray(jnp.swapaxes(k0, 1, 2)))
    kb, _, _ = kv.gather([1], 8)
    np.testing.assert_array_equal(
        np.asarray(kb[:, 0]), np.asarray(jnp.swapaxes(k1, 1, 2)))
    _check_ref_invariants(kv)


def test_gather_prefix_roundtrips_write_prefill():
    """gather_prefix returns the head-major (L, Hkv, P, hd) prefix exactly
    as write_prefill stored it — the layout contract the engine's fused
    suffix-prefill gather (LLMEngine._suffix_prefill) relies on — and a
    recipient's gather through SHARED blocks sees the donor's bytes."""
    cfg = registry.get_smoke_config("llama3-8b")
    L, Hkv, hd = cfg.num_layers, cfg.num_kv_heads, cfg.resolved_head_dim
    rng = np.random.default_rng(9)
    kv = _cache(num_blocks=16, block_size=4)
    kv.allocate(0, 11)
    k = jnp.asarray(rng.standard_normal((L, Hkv, 11, hd)), cfg.dtype)
    v = jnp.asarray(rng.standard_normal((L, Hkv, 11, hd)), cfg.dtype)
    kv.write_prefill(0, k, v)
    kv.share_blocks(0, 1, 8)
    kp, vp = kv.gather_prefix(1, 8)          # through the SHARED table
    assert kp.shape == (L, Hkv, 8, hd)
    np.testing.assert_array_equal(np.asarray(kp), np.asarray(k[:, :, :8]))
    np.testing.assert_array_equal(np.asarray(vp), np.asarray(v[:, :, :8]))
    with pytest.raises(ValueError, match="block-aligned"):
        kv.gather_prefix(0, 6)


def test_shared_accounting_counts_physical_blocks_once():
    kv = _sharded_cache(num_blocks=32, block_size=4, n_shards=4)
    kv.allocate(0, 16)                       # 4 blocks round-robin
    kv.share_blocks(0, 1, 16)
    kv.allocate(1, 20)                       # +1 private block
    assert kv.used_blocks == 5
    assert kv.unique_live_tokens() == 20
    assert int(kv.shard_live_tokens().sum()) == 20
    lt, lp, st_ = kv.block_table_shards([0, 1])
    assert int(st_.sum()) == 20              # shared blocks counted once
    # per-sequence tables still BOTH walk the shared blocks (reads)
    assert lt.shape[1] == 2
    # partial-tail share (fork): resident tokens use the DEEPEST fill among
    # sharers regardless of batch order — same rule everywhere
    kv2 = _cache(num_blocks=16, block_size=4)
    kv2.allocate(0, 6)
    kv2.share_blocks(0, 1, 5)
    for order in ([0, 1], [1, 0]):
        _, _, st2 = kv2.block_table_shards(order)
        assert int(st2.sum()) == 6
    assert kv2.unique_live_tokens() == 6
    assert int(kv2.shard_live_tokens().sum()) == 6


@settings(deadline=None, max_examples=30)
@given(ops=st.lists(
    st.tuples(st.sampled_from(["alloc", "append", "free", "share"]),
              st.integers(0, 5), st.integers(1, 30)),
    min_size=1, max_size=80))
def test_refcount_invariants_under_interleaved_share_append_free(ops):
    """The tentpole's allocator invariant: arbitrary interleavings of
    allocate / share_blocks / append_token (CoW) / free_seq keep refcounts
    exactly equal to live table references, never leak or double-free a
    block, and keep every free block in its owner shard's list."""
    kv = _sharded_cache(num_blocks=32, block_size=4, n_shards=2)
    for kind, sid, n in ops:
        try:
            if kind == "alloc" and sid not in kv.tables:
                kv.allocate(sid, n)
            elif kind == "append" and sid in kv.tables:
                kv.append_token(sid)
            elif kind == "free" and sid in kv.tables:
                kv.free_seq(sid)
            elif kind == "share" and sid in kv.tables:
                dst = (sid + 1) % 6
                if dst not in kv.tables and n <= kv.lengths[sid]:
                    kv.share_blocks(sid, dst, n)
        except OutOfBlocks:
            pass
        _check_ref_invariants(kv)


@settings(deadline=None, max_examples=20)
@given(ops=st.lists(
    st.tuples(st.sampled_from(["alloc", "append", "free"]),
              st.integers(0, 7), st.integers(1, 40)),
    min_size=1, max_size=60))
def test_sharded_allocator_invariants(ops):
    """The base allocator invariants hold under shard-aware round-robin,
    plus: every free block sits in its owner shard's free list."""
    kv = _sharded_cache(num_blocks=32, block_size=4, n_shards=4)
    total = kv.num_blocks
    npb = kv.blocks_per_shard
    for kind, sid, n in ops:
        try:
            if kind == "alloc" and sid not in kv.tables:
                kv.allocate(sid, n)
            elif kind == "append" and sid in kv.tables:
                kv.append_token(sid)
            elif kind == "free" and sid in kv.tables:
                kv.free_seq(sid)
        except OutOfBlocks:
            pass
        owned = [b for t in kv.tables.values() for b in t]
        assert len(owned) == len(set(owned)), "block owned twice"
        assert len(owned) + len(kv.free) == total, "blocks leaked"
        assert set(owned).isdisjoint(kv.free)
        for s in range(kv.n_shards):
            assert all(b // npb == s for b in kv._free_shard[s])
        for s_id, ln in kv.lengths.items():
            assert len(kv.tables[s_id]) * kv.block_size >= ln
