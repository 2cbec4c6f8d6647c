"""Int8 quantized KV pool: the scale sidecars follow every block-level
allocator invariant (copy-on-write forks the scale tile with its block,
shared blocks' scale bytes count once, quarantine never shrinks a scale
pool), handoff payloads round-trip scales bit-exactly across shard
geometries, byte accounting reflects the ~2× reduction, and the engine's
greedy outputs agree with bf16 on the smoke configs while resident /
per-step-read KV bytes drop by at least ~2×."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.configs import registry
from repro.models import transformer
from repro.serving import EngineConfig, LLMEngine, Request, SamplingParams
from repro.serving.kvcache import PagedKVCache


def _cache(num_blocks=32, block_size=4, n_shards=1, kv_dtype="int8"):
    cfg = registry.get_smoke_config("llama3-8b")
    return PagedKVCache(cfg, num_blocks, block_size, n_shards=n_shards,
                        kv_dtype=kv_dtype)


def _prefill(kv, sid, n, seed=0):
    """Allocate + write `n` random tokens; returns the (k, v) written."""
    L, Hkv, hd = kv.k_pool.shape[0], kv.k_pool.shape[1], kv.k_pool.shape[4]
    rng = np.random.default_rng(seed)
    k = jnp.asarray(rng.standard_normal((L, Hkv, n, hd)), kv.cfg.dtype)
    v = jnp.asarray(rng.standard_normal((L, Hkv, n, hd)), kv.cfg.dtype)
    kv.allocate(sid, n)
    kv.write_prefill(sid, k, v)
    return k, v


def _check_ref_invariants(kv):
    refs = {}
    for table in kv.tables.values():
        for b in table:
            refs[b] = refs.get(b, 0) + 1
    assert refs == kv.refcounts, "refcount != live table references"
    free = kv.free + [b for s in kv.quarantined_shards
                      for b in kv._free_shard[s]]
    assert set(refs).isdisjoint(free), "free block still referenced"
    assert len(refs) + len(free) == kv.num_blocks, "blocks leaked"


# ======================================================================
# scales follow blocks: CoW / sharing / quarantine
# ======================================================================

def test_cow_fork_copies_scale_tile_and_spares_donor():
    kv = _cache(num_blocks=16, block_size=4)
    _prefill(kv, 1, 6)                     # 2 blocks, partial tail
    kv.share_blocks(1, 2, 6)
    donor_tail = kv.tables[1][1]
    dk_pool = np.asarray(kv.k_pool[:, :, donor_tail])
    dk_s = np.asarray(kv.k_scale[:, :, donor_tail])
    dv_s = np.asarray(kv.v_scale[:, :, donor_tail])

    kv.append_token(2)                     # grows into the shared tail
    forked = kv.tables[2][1]
    assert forked != donor_tail and kv.cow_forks == 1
    # the fork carried the scale tile with the value tile
    np.testing.assert_array_equal(np.asarray(kv.k_scale[:, :, forked]), dk_s)
    np.testing.assert_array_equal(np.asarray(kv.v_scale[:, :, forked]), dv_s)

    # the divergent write lands in the fork; the donor tile AND its
    # scales stay bit-identical
    L, Hkv, hd = kv.k_pool.shape[0], kv.k_pool.shape[1], kv.k_pool.shape[4]
    rng = np.random.default_rng(7)
    tok = jnp.asarray(rng.standard_normal((L, Hkv, hd)), kv.cfg.dtype)
    kv.write_token(2, tok, tok, position=6)
    np.testing.assert_array_equal(
        np.asarray(kv.k_pool[:, :, donor_tail]), dk_pool)
    np.testing.assert_array_equal(
        np.asarray(kv.k_scale[:, :, donor_tail]), dk_s)
    assert float(kv.k_scale[0, 0, forked, 0, 2]) > 0.0   # fork got its scale
    _check_ref_invariants(kv)


@settings(deadline=None, max_examples=10)
@given(n_tok=st.integers(1, 24), share=st.integers(1, 24),
       appends=st.integers(1, 6), seed=st.integers(0, 5))
def test_donor_scales_survive_any_fork_depth(n_tok, share, appends, seed):
    """Property: whatever the share depth and however many tokens a
    borrower appends (partial-tail CoW, block-boundary growth, repeated
    appends), the donor's value AND scale tiles never change."""
    share = min(share, n_tok)
    kv = _cache(num_blocks=32, block_size=4)
    _prefill(kv, 1, n_tok, seed=seed)
    donor = jnp.asarray(kv.tables[1], jnp.int32)
    dk = np.asarray(kv.k_pool[:, :, donor])
    dv = np.asarray(kv.v_pool[:, :, donor])
    dks = np.asarray(kv.k_scale[:, :, donor])
    dvs = np.asarray(kv.v_scale[:, :, donor])
    kv.share_blocks(1, 2, share)
    L, Hkv, hd = kv.k_pool.shape[0], kv.k_pool.shape[1], kv.k_pool.shape[4]
    rng = np.random.default_rng(seed + 100)
    for i in range(appends):
        kv.append_token(2)
        tok_k = jnp.asarray(rng.standard_normal((L, Hkv, hd)), kv.cfg.dtype)
        tok_v = jnp.asarray(rng.standard_normal((L, Hkv, hd)), kv.cfg.dtype)
        kv.write_token(2, tok_k, tok_v, position=share + i)
        _check_ref_invariants(kv)
    np.testing.assert_array_equal(np.asarray(kv.k_pool[:, :, donor]), dk)
    np.testing.assert_array_equal(np.asarray(kv.v_pool[:, :, donor]), dv)
    np.testing.assert_array_equal(np.asarray(kv.k_scale[:, :, donor]), dks)
    np.testing.assert_array_equal(np.asarray(kv.v_scale[:, :, donor]), dvs)


def test_quarantine_never_shrinks_scale_pools():
    kv = _cache(num_blocks=16, block_size=4, n_shards=4)
    _prefill(kv, 1, 12)                    # round-robin spans shards
    shape = kv.k_scale.shape
    npb = kv.blocks_per_shard
    dead_tiles = np.asarray(kv.k_scale[:, :, npb:2 * npb])

    kv.quarantine_shard(1)
    assert kv.k_scale.shape == shape and kv.v_scale.shape == shape
    # allocations avoid the dead shard; scale writes still land
    _prefill(kv, 2, 8, seed=1)
    assert all(kv.shard_of(b) != 1 for b in kv.tables[2])
    # victims draining back leave the scale pool geometry (and the dead
    # shard's tiles) untouched
    kv.free_seq(1)
    assert kv.k_scale.shape == shape
    np.testing.assert_array_equal(
        np.asarray(kv.k_scale[:, :, npb:2 * npb]), dead_tiles)
    kv.rejoin_shard(1)
    assert kv.k_scale.shape == shape
    _check_ref_invariants(kv)


# ======================================================================
# byte accounting: resident, per-token, shared-once
# ======================================================================

def test_byte_accounting_counts_scales_and_shared_blocks_once():
    kv = _cache(num_blocks=16, block_size=4)
    bf = _cache(num_blocks=16, block_size=4, kv_dtype="bf16")
    L, Hkv, hd = kv.k_pool.shape[0], kv.k_pool.shape[1], kv.k_pool.shape[4]
    slots = 16 * 4                          # num_blocks * block_size
    e = jnp.dtype(bf.cfg.dtype).itemsize
    # int8: 1 value byte + 4 fp32 scale bytes per token-head, K and V
    assert kv.pool_bytes_resident == 2 * L * Hkv * slots * (hd + 4)
    assert bf.pool_bytes_resident == 2 * L * Hkv * slots * hd * e
    assert kv.pool_bytes_resident < 0.6 * bf.pool_bytes_resident
    assert kv.bytes_per_live_token() == 2 * L * Hkv * (hd + 4)
    assert bf.bytes_per_live_token() == 2 * L * Hkv * hd * e
    # a prefix-shared block reads/resides once, not once per sharer
    _prefill(kv, 1, 8)
    kv.share_blocks(1, 2, 8)
    assert kv.unique_live_tokens([1, 2]) == 8
    assert sum(kv.lengths.values()) == 16   # logical tokens double-count


# ======================================================================
# handoff: scales ride the wire, bit-exactly, across geometries
# ======================================================================

@pytest.mark.parametrize("src_shards,dst_shards",
                         [(1, 1), (1, 2), (2, 4), (4, 1)])
def test_handoff_roundtrip_scales_exact(src_shards, dst_shards):
    src = _cache(num_blocks=16, block_size=4, n_shards=src_shards)
    _prefill(src, 1, 10, seed=0)
    src.share_blocks(1, 2, 8)              # shared prefix rides once
    src.allocate(2, 11)
    L, Hkv, hd = src.k_pool.shape[0], src.k_pool.shape[1], src.k_pool.shape[4]
    rng = np.random.default_rng(1)
    suf_k = jnp.asarray(rng.standard_normal((L, Hkv, 3, hd)), src.cfg.dtype)
    suf_v = jnp.asarray(rng.standard_normal((L, Hkv, 3, hd)), src.cfg.dtype)
    src.write_prefill(2, suf_k, suf_v, start_token=8)

    payload = src.export_seqs([1, 2])
    assert payload.k_scales is not None and payload.v_scales is not None
    assert len(payload.block_ids) == len(set(payload.block_ids))

    dst = _cache(num_blocks=16, block_size=4, n_shards=dst_shards)
    mapping = dst.import_seqs(payload)
    # every unique block's int8 values AND fp32 scales land bit-exactly
    for b in payload.block_ids:
        d = mapping[b]
        np.testing.assert_array_equal(np.asarray(dst.k_pool[:, :, d]),
                                      np.asarray(src.k_pool[:, :, b]))
        np.testing.assert_array_equal(np.asarray(dst.v_pool[:, :, d]),
                                      np.asarray(src.v_pool[:, :, b]))
        np.testing.assert_array_equal(np.asarray(dst.k_scale[:, :, d]),
                                      np.asarray(src.k_scale[:, :, b]))
        np.testing.assert_array_equal(np.asarray(dst.v_scale[:, :, d]),
                                      np.asarray(src.v_scale[:, :, b]))
    # sharing survives the wire: the prefix blocks stay refcount-2
    for b in src.tables[1][:2]:
        assert dst.refcounts[mapping[b]] == 2
    # dequantized prefix readback is identical on both sides
    for sid in (1, 2):
        ks, vs = src.gather_prefix(sid, 8)
        kd, vd = dst.gather_prefix(sid, 8)
        np.testing.assert_array_equal(np.asarray(kd), np.asarray(ks))
        np.testing.assert_array_equal(np.asarray(vd), np.asarray(vs))
    _check_ref_invariants(dst)


def test_handoff_payload_bytes_halved_vs_bf16():
    i8 = _cache(num_blocks=16, block_size=4)
    bf = _cache(num_blocks=16, block_size=4, kv_dtype="bf16")
    for kv in (i8, bf):
        _prefill(kv, 1, 10, seed=0)
    p8, pbf = i8.export_seqs([1]), bf.export_seqs([1])
    hd = i8.k_pool.shape[4]
    e = jnp.dtype(bf.cfg.dtype).itemsize
    assert p8.nbytes / pbf.nbytes == pytest.approx((hd + 4) / (hd * e))
    assert p8.nbytes < 0.6 * pbf.nbytes
    # the per-block transfer accounting includes the scale tiles
    assert p8.bytes_of_blocks(1) * p8.n_blocks == p8.nbytes


def test_handoff_kv_dtype_mismatch_raises_both_directions():
    i8 = _cache(num_blocks=16, block_size=4)
    bf = _cache(num_blocks=16, block_size=4, kv_dtype="bf16")
    _prefill(i8, 1, 6, seed=0)
    _prefill(bf, 1, 6, seed=0)
    bf_dst = _cache(num_blocks=16, block_size=4, kv_dtype="bf16")
    with pytest.raises(ValueError, match="kv_dtype"):
        bf_dst.import_seqs(i8.export_seqs([1]))  # scales into bf16 pool
    i8_dst = _cache(num_blocks=16, block_size=4)
    with pytest.raises(ValueError, match="kv_dtype"):
        i8_dst.import_seqs(bf.export_seqs([1]))  # scaleless into int8 pool


# ======================================================================
# engine-level: greedy agreement with bf16 + the ~2× byte reduction
# ======================================================================

@pytest.fixture(scope="module")
def setup():
    cfg = registry.get_smoke_config("llama3-8b")
    params = transformer.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _reqs(cfg, lens=(5, 12, 9, 20), new=8):
    rng = np.random.default_rng(0)
    return [Request(prompt=rng.integers(0, cfg.vocab_size, size=n).tolist(),
                    params=SamplingParams(max_new_tokens=new))
            for n in lens]


def _run(cfg, params, **ekw):
    reqs = _reqs(cfg)
    eng = LLMEngine(cfg, params, EngineConfig(max_batch=4, num_blocks=64,
                                              **ekw))
    eng.submit(reqs)
    eng.run()
    return [r.output for r in reqs], eng.stats.summary()


@pytest.fixture(scope="module")
def bf16_ref(setup):
    cfg, params = setup
    return _run(cfg, params)


@pytest.mark.parametrize("pkw", [
    {"placement": "homogeneous"},
    {"placement": "attention_pool", "partition": "head"},
    {"placement": "attention_pool", "partition": "block"},
    {"placement": "attention_pool", "partition": "request"},
], ids=["homogeneous", "pool_head", "pool_block", "pool_request"])
def test_engine_int8_matches_bf16_greedy_and_halves_kv_bytes(
        setup, bf16_ref, pkw):
    cfg, params = setup
    ref_out, ref_stats = bf16_ref
    out, stats = _run(cfg, params, kv_dtype="int8", **pkw)
    assert out == ref_out
    # resident AND per-step read bytes drop by at least ~2× (more on
    # fp32-pool smoke configs: (hd+4)/(4·hd))
    assert stats["kv_pool_bytes_resident"] <= \
        0.55 * ref_stats["kv_pool_bytes_resident"]
    assert stats["kv_bytes_read_per_step"] <= \
        0.55 * ref_stats["kv_bytes_read_per_step"]
    assert stats["kv_bytes_read_per_step"] > 0


def _record_logits(eng, reqs):
    """Wrap the engine's sampler so each request's logits are kept, in
    order: entry t is what token t was sampled from."""
    rec = [[] for _ in reqs]
    sample = eng._sample

    def spy(batch, logits):
        for r, row in zip(batch, np.asarray(logits)):
            rec[next(i for i, q in enumerate(reqs) if q is r)].append(row)
        return sample(batch, logits)

    eng._sample = spy
    return rec


# int8 per-token-head symmetric quantization rounds each K/V element by up
# to max|x|/254 (0.4% of its row's max). Through the smoke model that moves
# logits of magnitude <= ~4 by <= ~0.02; 0.05 keeps a 2.5x margin, while a
# wrong, stale or unwritten block moves them by O(1).
INT8_LOGIT_ATOL = 0.05


def test_engine_int8_chunked_prefill_with_sharing_matches_bf16(setup):
    """Chunked prefill reads the quantized prefix through the fused-dequant
    chunk kernel; prefix sharing adds CoW forks of quantized blocks. Both
    must agree with the int8 one-shot path and with the bf16 pool, compared
    on logits: at each step whose inputs agree (every earlier token is the
    same in both runs), the logits match to quantization noise. Sampled
    tokens are not compared, because greedy flips wherever two logits lie
    within that noise of each other."""
    cfg, params = setup
    rng = np.random.default_rng(0)
    prefix = rng.integers(0, cfg.vocab_size, size=32).tolist()
    prompts = [prefix + rng.integers(0, cfg.vocab_size, size=s).tolist()
               for s in (3, 7)]
    outs, logits = {}, {}
    for key, ekw in (
            ("bf16", dict(kv_dtype="bf16", prefix_sharing=True,
                          prefill_chunk_tokens=16)),
            ("int8_chunk", dict(kv_dtype="int8", prefix_sharing=True,
                                prefill_chunk_tokens=16)),
            ("int8_oneshot", dict(kv_dtype="int8"))):
        reqs = [Request(prompt=list(p),
                        params=SamplingParams(max_new_tokens=6))
                for p in prompts]
        eng = LLMEngine(cfg, params, EngineConfig(
            max_batch=4, num_blocks=64, **ekw))
        logits[key] = _record_logits(eng, reqs)
        eng.submit(reqs)
        eng.run()
        if ekw.get("prefix_sharing"):
            assert eng.kv.blocks_shared_total > 0   # sharing engaged
        outs[key] = [r.output for r in reqs]
    for a, b in (("int8_chunk", "int8_oneshot"), ("int8_chunk", "bf16")):
        for i in range(len(prompts)):
            assert len(logits[a][i]) == len(logits[b][i]) == 6
            for t in range(6):
                if outs[a][i][:t] != outs[b][i][:t]:
                    break      # inputs differ from here on
                np.testing.assert_allclose(
                    logits[a][i][t], logits[b][i][t], rtol=0,
                    atol=INT8_LOGIT_ATOL, err_msg=f"{a} vs {b}, req {i}, "
                    f"token {t}")
