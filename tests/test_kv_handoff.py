"""Block-granular KV handoff (`PagedKVCache.export_seqs` / `import_seqs`):
the prefill→decode wire unit of the disaggregated cluster.

Hypothesis property: export→import round-trips EXACTLY — destination
tables isomorphic to the source tables under the returned src→dst block
mapping, refcounts equal to the referencing-table-entry count (so shared
prefixes stay shared on the destination pool), and every physical block's
pool bytes bit-identical — with each refcount-shared/CoW block crossing
the wire ONCE per physical block, across source/destination pools with
different shard counts.

Plus the interruption path: a decode-side shard death mid-transfer
(serving/faults.py injection, `transfer_blocks_per_step=1` stretching the
landing window) resets and retries the import with greedy outputs still
bit-identical, and exhausting the retry budget raises a contextual
:class:`HandoffError` (rid, replica, blocks in flight, stage — the PR 6
``PoolExhausted`` convention).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.configs import registry
from repro.models import transformer
from repro.serving import (DisaggConfig, EngineConfig, FaultInjector,
                           FaultScenario, LLMEngine, PagedKVCache,
                           PoolExhausted, Request, SamplingParams)
from repro.serving.cluster import (DecodeEngine, DisaggCluster,
                                   HandoffError, PrefillEngine)


@pytest.fixture(scope="module")
def setup():
    cfg = registry.get_smoke_config("llama3-8b")
    params = transformer.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _randomise(kv, seed):
    """Fill the pool with recognisable (non-zero) content so bit-exact
    comparisons are meaningful."""
    rng = np.random.default_rng(seed)
    kv.k_pool = jnp.asarray(rng.standard_normal(kv.k_pool.shape),
                            kv.k_pool.dtype)
    kv.v_pool = jnp.asarray(rng.standard_normal(kv.v_pool.shape),
                            kv.v_pool.dtype)


# ======================================================================
# the round-trip property
# ======================================================================
@settings(deadline=None, max_examples=12)
@given(data=st.data())
def test_export_import_roundtrip_exact(setup, data):
    """tables/refcounts/lengths/bytes survive the wire exactly, shared
    blocks transfer once, across differing shard geometries."""
    cfg, _ = setup
    bs = 4
    n_seqs = data.draw(st.integers(1, 4), label="n_seqs")
    lens = [data.draw(st.integers(1, 40), label=f"len{i}")
            for i in range(n_seqs)]
    src_shards = data.draw(st.sampled_from([1, 2, 4]), label="src_shards")
    dst_shards = data.draw(st.sampled_from([1, 2, 4]), label="dst_shards")
    src = PagedKVCache(cfg, num_blocks=64, block_size=bs,
                       n_shards=src_shards)
    src.allocate(0, lens[0])
    for i in range(1, n_seqs):
        shared = data.draw(st.integers(0, min(lens[0], lens[i])),
                           label=f"shared{i}")
        if shared > 0:
            src.share_blocks(0, i, shared)   # prefix sharing on the wire
            if lens[i] > shared:
                src.allocate(i, lens[i])     # extend past the prefix
        else:
            src.allocate(i, lens[i])
    # a CoW fork on a shared tail exercises the forked-block case too
    for i in range(1, n_seqs):
        if data.draw(st.booleans(), label=f"grow{i}"):
            src.append_token(i)
    _randomise(src, seed=sum(lens))

    sids = list(range(n_seqs))
    payload = src.export_seqs(sids)

    # every referenced physical block appears EXACTLY once on the wire
    unique_phys = {b for sid in sids for b in src.tables[sid]}
    assert len(payload.block_ids) == len(set(payload.block_ids))
    assert set(payload.block_ids) == unique_phys
    assert payload.n_blocks == len(unique_phys)
    assert payload.k_blocks.shape[2] == payload.n_blocks
    # shared prefixes make the wire smaller than the sum of table lengths
    total_entries = sum(len(src.tables[sid]) for sid in sids)
    assert payload.n_blocks <= total_entries

    dst = PagedKVCache(cfg, num_blocks=64, block_size=bs,
                       n_shards=dst_shards)
    mapping = dst.import_seqs(payload)
    assert set(mapping) == unique_phys
    assert dst.used_blocks == payload.n_blocks

    # tables isomorphic under the mapping; lengths preserved
    for sid in sids:
        assert dst.tables[sid] == [mapping[b] for b in src.tables[sid]]
        assert dst.lengths[sid] == src.lengths[sid]
    # refcounts == number of referencing table entries (sharing survives)
    refs = {}
    for sid in sids:
        for b in dst.tables[sid]:
            refs[b] = refs.get(b, 0) + 1
    assert {b: dst.refcounts[b] for b in refs} == refs
    # pool bytes bit-identical block-by-block
    sk, sv = np.asarray(src.k_pool), np.asarray(src.v_pool)
    dk, dv = np.asarray(dst.k_pool), np.asarray(dst.v_pool)
    for sb, db in mapping.items():
        assert (sk[:, :, sb] == dk[:, :, db]).all()
        assert (sv[:, :, sb] == dv[:, :, db]).all()


def test_export_unknown_seq_rejected(setup):
    cfg, _ = setup
    kv = PagedKVCache(cfg, num_blocks=16, block_size=4)
    with pytest.raises(ValueError, match="no table"):
        kv.export_seqs([7])


def test_import_rejects_block_size_mismatch(setup):
    cfg, _ = setup
    src = PagedKVCache(cfg, num_blocks=16, block_size=4)
    src.allocate(0, 10)
    payload = src.export_seqs([0])
    dst = PagedKVCache(cfg, num_blocks=16, block_size=8)
    with pytest.raises(ValueError, match="block_size"):
        dst.prealloc_handoff(payload)


def test_import_rejects_existing_rid(setup):
    cfg, _ = setup
    src = PagedKVCache(cfg, num_blocks=16, block_size=4)
    src.allocate(0, 10)
    payload = src.export_seqs([0])
    dst = PagedKVCache(cfg, num_blocks=16, block_size=4)
    dst.allocate(0, 4)      # rid collision on the destination
    with pytest.raises(ValueError, match="already has a table"):
        dst.prealloc_handoff(payload)


def test_prealloc_is_all_or_nothing(setup):
    """A destination pool that cannot cover the payload raises contextual
    PoolExhausted and allocates NOTHING (no partial tables, no leaked
    blocks)."""
    cfg, _ = setup
    src = PagedKVCache(cfg, num_blocks=32, block_size=4)
    src.allocate(0, 40)     # 10 blocks
    payload = src.export_seqs([0])
    dst = PagedKVCache(cfg, num_blocks=8, block_size=4)
    free_before = dst.num_free
    with pytest.raises(PoolExhausted) as ei:
        dst.prealloc_handoff(payload)
    assert ei.value.rid == 0
    assert dst.num_free == free_before
    assert dst.tables == {}


# ======================================================================
# transfer interrupted by shard death (serving/faults.py injection)
# ======================================================================
def _reqs(cfg, lens=(18, 25), new=8, seed=0):
    rng = np.random.default_rng(seed)
    return [Request(prompt=rng.integers(0, cfg.vocab_size, size=n).tolist(),
                    params=SamplingParams(max_new_tokens=new))
            for n in lens]


def _econf(**kw):
    base = dict(placement="attention_pool", partition="head",
                attention_workers=2, kv_shards=2, num_blocks=64,
                block_size=4, max_batch=4)
    base.update(kw)
    return EngineConfig(**base)


def test_transfer_interrupted_by_shard_death_recovers(setup):
    """A decode-side shard death mid-transfer (1 block/step stretches the
    landing window across steps) frees the partial import, requeues the
    handoff, and retries onto the survivors — greedy outputs stay
    bit-identical to a fault-free single engine."""
    cfg, params = setup
    econf = _econf()
    ref = _reqs(cfg)
    eng = LLMEngine(cfg, params, econf)
    eng.submit(ref)
    eng.run()

    reqs = _reqs(cfg)
    injector = FaultInjector(
        FaultScenario.parse("shard_death:shard=1,step=3"))
    cluster = DisaggCluster(
        cfg, params, econf, replicas=1,
        disagg=DisaggConfig(transfer_blocks_per_step=1),
        decode_faults={0: injector})
    cluster.submit(reqs)
    cluster.run()
    assert [r.output for r in reqs] == [r.output for r in ref]
    dec = cluster.registry[0].decode
    assert dec.stats.handoff_retries >= 1
    retries = [e for e in dec.event_log if e.kind == "handoff_retry"]
    assert retries and all(e.info["blocks_lost"] > 0 for e in retries)
    assert dec.kv.quarantined_shards == (1,)
    # all retried imports landed whole despite the lost blocks
    assert dec.stats.handoffs_completed == len(reqs)


def test_transfer_retry_budget_exhaustion_raises_contextual(setup):
    """max_transfer_attempts=1: the first mid-transfer shard death burns
    the whole budget — HandoffError with rid/replica/blocks-in-flight."""
    cfg, params = setup
    reqs = _reqs(cfg)
    injector = FaultInjector(
        FaultScenario.parse("shard_death:shard=1,step=3"))
    cluster = DisaggCluster(
        cfg, params, _econf(), replicas=1,
        disagg=DisaggConfig(transfer_blocks_per_step=1,
                            max_transfer_attempts=1),
        decode_faults={0: injector})
    cluster.submit(reqs)
    with pytest.raises(HandoffError) as ei:
        cluster.run()
    err = ei.value
    assert err.stage == "transfer"
    assert err.replica == 0
    assert err.rid in {r.rid for r in reqs}
    assert err.blocks_in_flight > 0
    assert "shard death" in str(err)


def test_oversized_handoff_fails_fast_at_enqueue(setup):
    """A payload that can never fit the decode pool (even empty) is
    rejected at enqueue with full context, not queued forever."""
    cfg, params = setup
    prefill = PrefillEngine(cfg, params, _econf())
    decode = DecodeEngine(
        cfg, params, EngineConfig(num_blocks=4, block_size=4, max_batch=4))
    prefill.on_handoff = decode.enqueue_handoff
    req = _reqs(cfg, lens=(30,))[0]      # 8 blocks > 4-block decode pool
    prefill.submit(req)
    with pytest.raises(HandoffError) as ei:
        prefill.run()
    assert ei.value.stage == "enqueue"
    assert ei.value.rid == req.rid
    assert ei.value.blocks_in_flight == 8
    assert "can never fit" in str(ei.value)
