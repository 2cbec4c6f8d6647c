"""Refcounted prefix sharing end to end: bit-identical greedy streams with
sharing on vs off for every placement/partition, prefill-skip accounting,
the block-granular prefix index, admission charging only the unshared
suffix, and preemption interplay (evicting a sharer or a donor never
corrupts anyone)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import registry
from repro.models import transformer
from repro.serving import (EngineConfig, LLMEngine, Request,
                           RequestScheduler, SamplingParams, State)
from repro.serving.kvcache import PagedKVCache
from repro.serving.scheduler import PrefixIndex

# Full and suffix prefill are separately compiled fp32 programs: XLA may
# fuse and order the same sums differently, which moves logits and K/V of
# magnitude <= ~4 at the smoke widths by <= 2e-6 (about 16 fp32 ulps). 2e-5
# leaves a 10x margin; a wrong prefix or a dropped mask moves them by orders
# of magnitude more.
PREFILL_ATOL = 2e-5


@pytest.fixture(scope="module")
def setup():
    cfg = registry.get_smoke_config("llama3-8b")
    params = transformer.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _common(cfg, n=40, seed=0):
    return np.random.default_rng(seed).integers(
        0, cfg.vocab_size, size=n).tolist()


def _family(cfg, common, tails=(5, 6, 7, 8), new=8, seed=42):
    """Requests sharing `common` as a prompt prefix, distinct suffixes."""
    r = np.random.default_rng(seed)
    return [Request(prompt=list(common) +
                    r.integers(0, cfg.vocab_size, size=t).tolist(),
                    params=SamplingParams(max_new_tokens=new))
            for t in tails]


# ======================================================================
# model layer: suffix prefill is bit-identical to the full prefill
# ======================================================================

@pytest.mark.parametrize("arch", ["llama3-8b", "gemma2-27b"])
def test_prefill_suffix_bit_parity(arch):
    """Suffix queries over gathered prefix context reproduce the full
    prefill (the reference) to fp32 rounding — logits and suffix KV —
    including gemma2's local windows, attention sinks, and logit
    softcap."""
    cfg = registry.get_smoke_config(arch)
    params = transformer.init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    S, P = 37, 16
    toks = rng.integers(0, cfg.vocab_size, size=(1, S))
    logits_full, cache = transformer.prefill(
        params, cfg, {"tokens": jnp.asarray(toks, jnp.int32)}, max_seq=S)
    kp, vp = cache["k"][:, :, :, :P], cache["v"][:, :, :, :P]
    logits_suf, c2 = transformer.prefill_suffix(
        params, cfg, {"tokens": jnp.asarray(toks[:, P:], jnp.int32)}, kp, vp)
    np.testing.assert_allclose(np.asarray(logits_suf),
                               np.asarray(logits_full),
                               rtol=0, atol=PREFILL_ATOL)
    np.testing.assert_allclose(np.asarray(c2["k"]),
                               np.asarray(cache["k"][:, :, :, P:]),
                               rtol=0, atol=PREFILL_ATOL)
    np.testing.assert_allclose(np.asarray(c2["v"]),
                               np.asarray(cache["v"][:, :, :, P:]),
                               rtol=0, atol=PREFILL_ATOL)
    assert int(c2["len"][0]) == S


def test_prefill_suffix_rejects_non_kv_families():
    cfg = registry.get_smoke_config("rwkv6-7b")
    with pytest.raises(ValueError, match="family"):
        transformer.prefill_suffix(None, cfg, {}, None, None)


# ======================================================================
# tentpole acceptance: greedy streams bit-identical, sharing on vs off,
# for all three placements and head/request/block partitions
# ======================================================================

@pytest.mark.parametrize("placement,partition,workers", [
    ("homogeneous", "head", 2),
    ("attention_pool", "head", 2),
    ("attention_pool", "request", 4),
    ("attention_pool", "block", 4),
])
def test_sharing_parity_across_placements(setup, placement, partition,
                                          workers):
    cfg, params = setup
    common = _common(cfg)
    res = {}
    for share in (False, True):
        reqs = _family(cfg, common)
        eng = LLMEngine(cfg, params, EngineConfig(
            placement=placement, partition=partition,
            attention_workers=workers, max_batch=4, num_blocks=64,
            block_size=16, prefix_sharing=share))
        eng.submit(reqs)
        eng.run()
        res[share] = ([r.output for r in reqs], eng.stats, eng.kv)
    assert res[True][0] == res[False][0]       # bit-identical greedy streams
    stats_on, kv_on = res[True][1], res[True][2]
    assert stats_on.blocks_shared == 6         # 3 sharers x 2 full blocks
    assert stats_on.prefill_tokens_skipped == 96
    assert res[False][1].blocks_shared == 0
    assert kv_on.used_blocks == 0              # everything released
    assert kv_on.refcounts == {}


def test_sharing_reduces_resident_pool_blocks(setup):
    """Mid-flight the shared run holds bytes(1 prefix) + K·bytes(suffix),
    the unshared run K·bytes(full prompt)."""
    cfg, params = setup
    common = _common(cfg)
    used = {}
    for share in (False, True):
        reqs = _family(cfg, common, new=4)
        eng = LLMEngine(cfg, params, EngineConfig(
            max_batch=4, num_blocks=64, block_size=16,
            prefix_sharing=share))
        eng.submit(reqs)
        eng.step()
        used[share] = eng.kv.used_blocks
        eng.run()
    # 4 prompts of 45-48 tokens: 12+ blocks unshared; shared: one 2-block
    # prefix + 4 private tails
    assert used[True] < used[False]
    assert used[False] - used[True] == 6       # 3 sharers x 2 blocks saved


def test_moe_offload_shares_memory_but_recomputes(setup):
    """MoE capacity dispatch couples a routing group's tokens, so suffix
    prefill is not bit-stable — the engine shares pool MEMORY (blocks
    mapped, suffix-only write, donor never rewritten) but recomputes the
    full prompt: outputs identical, blocks shared, zero tokens skipped."""
    cfg = registry.get_smoke_config("qwen3-moe-30b-a3b").replace(
        capacity_factor=64.0)
    params = transformer.init_params(jax.random.PRNGKey(0), cfg)
    common = _common(cfg, n=20, seed=3)
    res = {}
    for share in (False, True):
        reqs = _family(cfg, common, tails=(3, 4), new=5, seed=9)
        eng = LLMEngine(cfg, params, EngineConfig(
            placement="moe_offload", attention_workers=2, expert_workers=2,
            max_batch=2, num_blocks=64, block_size=8, prefix_sharing=share))
        eng.submit(reqs)
        eng.run()
        res[share] = ([r.output for r in reqs], eng.stats)
    assert res[True][0] == res[False][0]
    assert res[True][1].blocks_shared == 2     # 1 sharer x 2 full blocks
    assert res[True][1].prefill_tokens_skipped == 0


def test_gemma2_windowed_softcap_sharing_parity():
    """Sliding windows + sinks + softcap + post-norms through the suffix
    prefill: sharing must stay bit-identical on the most exotic config."""
    cfg = registry.get_smoke_config("gemma2-27b")
    params = transformer.init_params(jax.random.PRNGKey(0), cfg)
    common = _common(cfg, n=70, seed=2)        # longer than the 64 window
    res = {}
    for share in (False, True):
        reqs = _family(cfg, common, tails=(4, 9), new=8, seed=5)
        eng = LLMEngine(cfg, params, EngineConfig(
            placement="attention_pool", max_batch=2, num_blocks=64,
            block_size=16, prefix_sharing=share))
        eng.submit(reqs)
        eng.run()
        res[share] = [r.output for r in reqs]
    assert res[True] == res[False]


# ======================================================================
# prefix index (block-granular trie)
# ======================================================================

def test_prefix_index_match_register_unregister():
    idx = PrefixIndex(block_size=4)
    idx.register(1, list(range(10)))           # 2 full blocks indexed
    donor, n = idx.match(list(range(10)))
    assert (donor, n) == (1, 8)                # deepest full-block prefix
    donor, n = idx.match(list(range(6)))
    assert (donor, n) == (1, 4)
    donor, n = idx.match([9, 9, 9, 9])
    assert (donor, n) == (None, 0)
    # a second registrant deepens the index; donor picks the smallest rid
    idx.register(2, list(range(16)))
    donor, n = idx.match(list(range(16)))
    assert (donor, n) == (2, 16)               # only rid 2 covers 4 blocks
    donor, n = idx.match(list(range(8)))
    assert donor == 1                          # min(1, 2) at depth 2
    idx.unregister(1)
    donor, n = idx.match(list(range(8)))
    assert (donor, n) == (2, 8)
    idx.unregister(2)
    assert len(idx) == 0
    assert idx.match(list(range(16))) == (None, 0)


def test_admission_charges_only_unshared_suffix(setup):
    """A tight pool admits MORE concurrent requests with sharing: only the
    suffix counts against the free list."""
    cfg, _ = setup
    common = _common(cfg, n=32)
    admitted = {}
    for share in (False, True):
        kv = PagedKVCache(cfg, num_blocks=8, block_size=16)
        sched = RequestScheduler(kv, max_batch=8, decode_headroom=0,
                                 prefix_sharing=share)
        sched.submit(_family(cfg, common, tails=(8, 8, 8, 8), new=4))
        admitted[share] = len(sched.admit())
        if share:
            # every sharer: 2 shared blocks + 1 private suffix block
            assert kv.used_blocks == 3 + (admitted[True] - 1)
    assert admitted[False] == 2                # 8 blocks / 3-block prompts
    assert admitted[True] == 4                 # suffix-only charging


def test_match_capped_one_block_short_of_stored(setup):
    """A fully-matching prompt still prefalls at least one token: the match
    is capped a block short of the stored length (the last prompt token's
    logits seed sampling)."""
    cfg, _ = setup
    kv = PagedKVCache(cfg, num_blocks=16, block_size=4)
    sched = RequestScheduler(kv, max_batch=4, prefix_sharing=True)
    prompt = list(range(1, 9))                 # exactly 2 full blocks
    a, b = (Request(prompt=list(prompt),
                    params=SamplingParams(max_new_tokens=2))
            for _ in range(2))
    sched.submit([a, b])
    assert sched.admit() == [a, b]
    assert sched.shared_prefix_tokens(a.rid) == 0
    assert sched.shared_prefix_tokens(b.rid) == 4   # capped below 8
    assert kv.tables[b.rid][0] == kv.tables[a.rid][0]
    assert kv.tables[b.rid][1] != kv.tables[a.rid][1]


# ======================================================================
# preemption interplay: evicting sharers/donors never corrupts anyone
# ======================================================================

def test_preempt_with_sharing_matches_uncontended(setup):
    """Pool pressure forces evictions among prefix-sharing requests; every
    stream still finishes bit-identical to an uncontended run, and the pool
    drains to zero with empty refcounts."""
    cfg, params = setup
    common = _common(cfg, n=16, seed=7)

    def mk():
        return _family(cfg, common, tails=(2, 2, 2), new=16, seed=11)

    ref = mk()
    e_ref = LLMEngine(cfg, params, EngineConfig(max_batch=4, num_blocks=64,
                                                block_size=8,
                                                prefix_sharing=True))
    e_ref.submit(ref)
    e_ref.run()
    assert e_ref.stats.preemptions == 0

    tight = mk()
    eng = LLMEngine(cfg, params, EngineConfig(
        max_batch=4, num_blocks=10, block_size=8, scheduler="preempt",
        decode_headroom=2, prefix_sharing=True))
    eng.submit(tight)
    eng.run(max_steps=2000)
    assert eng.stats.preemptions > 0
    assert [r.output for r in tight] == [r.output for r in ref]
    assert eng.kv.used_blocks == 0
    assert eng.kv.refcounts == {}


def test_preempt_evicted_sharer_leaves_donor_intact(setup):
    """Directly evict a sharing recipient mid-flight: the donor's blocks
    and bytes are untouched (refcounts drop, nothing freed out from under
    it) and the donor finishes exactly like an unshared solo run."""
    cfg, params = setup
    common = _common(cfg, n=32, seed=4)
    solo = _family(cfg, common, tails=(5,), new=8, seed=13)[0]
    e0 = LLMEngine(cfg, params, EngineConfig(max_batch=2, num_blocks=64,
                                             block_size=16))
    e0.submit(solo)
    e0.run()

    donor, sharer = _family(cfg, common, tails=(5, 6), new=8, seed=13)
    eng = LLMEngine(cfg, params, EngineConfig(
        max_batch=2, num_blocks=64, block_size=16, scheduler="preempt",
        prefix_sharing=True))
    eng.submit([donor, sharer])
    eng.step()                                  # both admitted + 1 decode
    assert eng.sched.shared_prefix_tokens(sharer.rid) == 32
    donor_blocks = list(eng.kv.tables[donor.rid])
    eng.sched.preempt(sharer)                   # evict the recipient
    assert eng.kv.tables[donor.rid] == donor_blocks
    assert all(eng.kv.refcounts[b] == 1 for b in donor_blocks)
    eng.run()                                   # sharer re-admits, finishes
    assert donor.state == State.FINISHED
    assert sharer.state == State.FINISHED
    assert donor.output == solo.output
    assert eng.kv.used_blocks == 0


def test_donor_retires_while_sharer_lives(setup):
    """The donor finishes first: its refcounts drop but shared physical
    blocks survive through the sharer, which keeps decoding on them and
    matches its own solo run bit-for-bit."""
    cfg, params = setup
    common = _common(cfg, n=32, seed=8)
    reqs = _family(cfg, common, tails=(5, 6), new=10, seed=17)
    donor, sharer = reqs
    solo = Request(prompt=list(sharer.prompt),
                   params=SamplingParams(max_new_tokens=10))
    e0 = LLMEngine(cfg, params, EngineConfig(max_batch=2, num_blocks=64,
                                             block_size=16))
    e0.submit(solo)
    e0.run()
    donor.params.max_new_tokens = 2             # donor retires early
    eng = LLMEngine(cfg, params, EngineConfig(
        max_batch=2, num_blocks=64, block_size=16, prefix_sharing=True))
    eng.submit(reqs)
    eng.run()
    assert donor.state == State.FINISHED and sharer.state == State.FINISHED
    assert sharer.output == solo.output
    assert eng.kv.used_blocks == 0 and eng.kv.refcounts == {}


def test_second_wave_matches_index_of_running_request(setup):
    """A request submitted AFTER the first wave is admitted still matches
    the running donor's registered blocks (the index persists for the
    donor's lifetime)."""
    cfg, params = setup
    common = _common(cfg, n=32, seed=12)
    first = _family(cfg, common, tails=(4,), new=12, seed=19)[0]
    eng = LLMEngine(cfg, params, EngineConfig(
        max_batch=4, num_blocks=64, block_size=16, prefix_sharing=True))
    eng.submit(first)
    eng.step()
    late = _family(cfg, common, tails=(6,), new=4, seed=23)[0]
    eng.submit(late)
    eng.run()
    assert eng.stats.blocks_shared == 2
    assert eng.stats.prefill_tokens_skipped == 32
    solo = _family(cfg, common, tails=(6,), new=4, seed=23)[0]
    e2 = LLMEngine(cfg, params, EngineConfig(max_batch=4, num_blocks=64,
                                             block_size=16))
    e2.submit(solo)
    e2.run()
    assert late.output == solo.output


# ======================================================================
# surface: stats + config
# ======================================================================

def test_sharing_counters_in_summary(setup):
    cfg, params = setup
    reqs = _family(cfg, _common(cfg), new=2)
    eng = LLMEngine(cfg, params, EngineConfig(
        max_batch=4, num_blocks=64, block_size=16, prefix_sharing=True))
    eng.submit(reqs)
    s = eng.run().summary()
    assert s["blocks_shared"] == 6
    assert s["prefill_tokens_skipped"] == 96
    off = EngineConfig()
    assert off.prefix_sharing is False         # default stays off
