"""Chunked paged prefill end to end: bit-identical greedy streams with
chunking on vs off for every placement/partition (plus gemma2 windows/sinks/
softcap, MoE fallback, prefix-sharing and preemption interplay), the
paged-context chunk attention kernel vs its jnp reference, incremental
block allocation accounting, the write_prefill token-count validation, the
memoised gather indices, and chunked admission of a prompt larger than the
currently-free pool."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hypothesis import given, settings, strategies as st
from repro.configs import registry
from repro.kernels import ops
from repro.kernels.paged_prefill_attention import (
    paged_prefill_chunk_attention_jnp)
from repro.models import transformer
from repro.serving import (ChunkedPrefillPolicy, EngineConfig, LLMEngine,
                           PoolExhausted, Request, RequestScheduler,
                           SamplingParams, SchedulingStalled, State,
                           make_policy)
from repro.serving.kvcache import PagedKVCache

_PARAMS = {}

# One-shot and chunked (or suffix) prefill are separately compiled fp32
# programs: XLA may fuse and order the same sums differently, which moves
# logits and K/V of magnitude <= ~4 at the smoke widths by <= 2e-6 (about 16
# fp32 ulps). 2e-5 leaves a 10x margin; a wrong block, a dropped mask or a
# shifted position moves them by orders of magnitude more.
PREFILL_ATOL = 2e-5


def _setup(arch):
    if arch not in _PARAMS:
        cfg = registry.get_smoke_config(arch)
        _PARAMS[arch] = (cfg, transformer.init_params(
            jax.random.PRNGKey(0), cfg))
    return _PARAMS[arch]


@pytest.fixture(scope="module")
def setup():
    return _setup("llama3-8b")


def _reqs(cfg, lens, new=6, seed=3):
    rng = np.random.default_rng(seed)
    return [Request(prompt=rng.integers(0, cfg.vocab_size, size=n).tolist(),
                    params=SamplingParams(max_new_tokens=new)) for n in lens]


def _chunked_oneshot_pair(cfg, params, lens, econf_kw, chunk, new=6, seed=3):
    res = {}
    for c in (None, chunk):
        reqs = _reqs(cfg, lens, new=new, seed=seed)
        eng = LLMEngine(cfg, params, EngineConfig(
            prefill_chunk_tokens=c, **econf_kw))
        eng.submit(reqs)
        eng.run(max_steps=3000)
        res[c] = ([r.output for r in reqs], eng)
    return res[chunk], res[None]


# ======================================================================
# model layer: chunked prefill is bit-identical to the one-shot prefill
# ======================================================================

def _run_chunked(cfg, params, toks, chunk, block_size=8, num_blocks=64):
    """Drive prefill_chunk + the pool exactly like the engine does,
    asserting the incremental-allocation invariant after every chunk."""
    kv = PagedKVCache(cfg, num_blocks=num_blocks, block_size=block_size)
    S = toks.shape[1]
    cursor, logits = 0, None
    while cursor < S:
        target = min(cursor + chunk, S)
        idx = kv.gather_prefix_indices(0, cursor) if cursor else \
            jnp.zeros((0,), jnp.int32)
        logits, cache = transformer.prefill_chunk(
            params, cfg, {"tokens": jnp.asarray(toks[:, cursor:target],
                                                jnp.int32)},
            kv.k_pool, kv.v_pool, idx)
        kv.write_prefill_chunk(0, cache["k"][:, 0], cache["v"][:, 0],
                               start_token=cursor)
        # pool-accounting invariant: blocks allocated by chunk k cover
        # exactly the tokens written so far — nothing pre-allocated
        assert len(kv.tables[0]) == kv.blocks_needed(target)
        assert kv.lengths[0] == target
        assert int(cache["len"][0]) == target
        cursor = target
    return logits, kv


@pytest.mark.parametrize("arch", ["llama3-8b", "gemma2-27b"])
@pytest.mark.parametrize("chunk", [8, 16, 24, 37, 64])
def test_prefill_chunk_bit_parity(arch, chunk):
    """Chunked prefill — every chunk size, including a non-block-aligned
    final chunk and a single chunk covering the whole prompt — reproduces
    the one-shot prefill (the reference) to fp32 rounding: last-position
    logits and the pool KV, including gemma2's local windows, attention
    sinks, and softcap."""
    cfg, params = _setup(arch)
    rng = np.random.default_rng(0)
    S = 37
    toks = rng.integers(0, cfg.vocab_size, size=(1, S))
    logits_full, cache = transformer.prefill(
        params, cfg, {"tokens": jnp.asarray(toks, jnp.int32)}, max_seq=S)
    logits_chunked, kv = _run_chunked(cfg, params, toks, chunk)
    np.testing.assert_allclose(np.asarray(logits_chunked),
                               np.asarray(logits_full),
                               rtol=0, atol=PREFILL_ATOL)
    # pool contents == the one-shot cache (gather is the dense test
    # oracle; it returns seq-major (L, B, S, Hkv, hd))
    k, v = kv.gather([0], S)[:2]
    np.testing.assert_allclose(np.asarray(jnp.swapaxes(k, 2, 3)[:, 0]),
                               np.asarray(cache["k"][:, 0]),
                               rtol=0, atol=PREFILL_ATOL)
    np.testing.assert_allclose(np.asarray(jnp.swapaxes(v, 2, 3)[:, 0]),
                               np.asarray(cache["v"][:, 0]),
                               rtol=0, atol=PREFILL_ATOL)


def test_prefill_chunk_guards():
    cfg, params = _setup("llama3-8b")
    rcfg = registry.get_smoke_config("rwkv6-7b")
    with pytest.raises(ValueError, match="family"):
        transformer.prefill_chunk(None, rcfg, {}, None, None, None)
    kv = PagedKVCache(cfg, num_blocks=8, block_size=8)
    with pytest.raises(ValueError, match="B == 1"):
        transformer.prefill_chunk(
            params, cfg, {"tokens": jnp.zeros((2, 4), jnp.int32)},
            kv.k_pool, kv.v_pool, jnp.zeros((0,), jnp.int32))


@settings(max_examples=8, deadline=None)
@given(chunk=st.integers(1, 8), n_extra=st.integers(0, 15),
       arch=st.sampled_from(["llama3-8b", "gemma2-27b"]))
def test_chunked_prefill_property(chunk, n_extra, arch):
    """Hypothesis property: for ANY chunk size (in blocks) and prompt
    length, chunked prefill matches one-shot to fp32 rounding and every
    chunk allocates exactly blocks_needed(tokens so far) (the invariant is
    asserted inside _run_chunked after each chunk)."""
    cfg, params = _setup(arch)
    rng = np.random.default_rng(chunk * 31 + n_extra)
    S = 17 + n_extra
    toks = rng.integers(0, cfg.vocab_size, size=(1, S))
    logits_full, _ = transformer.prefill(
        params, cfg, {"tokens": jnp.asarray(toks, jnp.int32)}, max_seq=S)
    logits_chunked, _ = _run_chunked(cfg, params, toks, chunk * 8)
    np.testing.assert_allclose(np.asarray(logits_chunked),
                               np.asarray(logits_full),
                               rtol=0, atol=PREFILL_ATOL)


# ======================================================================
# kernel: pallas paged-context chunk attention vs jnp reference
# ======================================================================

@pytest.mark.parametrize("C,nb", [(5, 4), (8, 0), (13, 2), (1, 3)])
@pytest.mark.parametrize("sw,sinks,cap", [(0, 0, 0.0), (12, 0, 0.0),
                                          (12, 2, 0.0), (0, 0, 30.0)])
def test_paged_chunk_kernel_matches_jnp(C, nb, sw, sinks, cap):
    """The pallas chunk kernel (prefix streamed from the pool in place)
    matches the jnp gather reference across windows, sinks, softcap, an
    EMPTY prefix (first chunk), and a non-block-aligned chunk."""
    rng = np.random.default_rng(C * 17 + nb)
    Hkv, G, hd, bs = 2, 3, 16, 8
    kp = jnp.asarray(rng.standard_normal((Hkv, 16, bs, hd)), jnp.float32)
    vp = jnp.asarray(rng.standard_normal((Hkv, 16, bs, hd)), jnp.float32)
    table = jnp.asarray(rng.permutation(16)[:nb], jnp.int32)
    q = jnp.asarray(rng.standard_normal((C, Hkv * G, hd)), jnp.float32)
    kc = jnp.asarray(rng.standard_normal((C, Hkv, hd)), jnp.float32)
    vc = jnp.asarray(rng.standard_normal((C, Hkv, hd)), jnp.float32)
    kw = dict(sliding_window=sw, attention_sinks=sinks, logit_softcap=cap)
    ref = paged_prefill_chunk_attention_jnp(q, kp, vp, table, kc, vc, **kw)
    out = ops.paged_prefill_chunk_attention(q, kp, vp, table, kc, vc,
                                            backend="pallas", **kw)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(out),
                               rtol=2e-5, atol=2e-5)


def test_chunk_jnp_reference_bit_matches_oneshot_rows():
    """The jnp reference's output rows are BIT-equal to the corresponding
    rows of one flat blockwise pass over the whole sequence — the scan
    boundaries (512-key blocks from position 0) are identical, so masked
    future blocks are exact no-ops."""
    from repro.models.attention import blockwise_attention

    rng = np.random.default_rng(7)
    Hkv, G, hd, bs = 2, 2, 16, 8
    P, C = 24, 13
    H = Hkv * G
    k_all = jnp.asarray(rng.standard_normal((P + C, Hkv, hd)), jnp.float32)
    v_all = jnp.asarray(rng.standard_normal((P + C, Hkv, hd)), jnp.float32)
    q_all = jnp.asarray(rng.standard_normal((P + C, H, hd)), jnp.float32)
    full = blockwise_attention(q_all[None], k_all[None], v_all[None],
                               causal=True)[0]
    # scatter the prefix into a shuffled pool through a table
    table = rng.permutation(8)[:P // bs]
    kp = jnp.zeros((Hkv, 8, bs, hd), jnp.float32)
    vp = jnp.zeros((Hkv, 8, bs, hd), jnp.float32)
    pre_k = jnp.swapaxes(k_all[:P], 0, 1).reshape(Hkv, P // bs, bs, hd)
    pre_v = jnp.swapaxes(v_all[:P], 0, 1).reshape(Hkv, P // bs, bs, hd)
    kp = kp.at[:, table].set(pre_k)
    vp = vp.at[:, table].set(pre_v)
    out = ops.paged_prefill_chunk_attention(
        q_all[P:], kp, vp, jnp.asarray(table, jnp.int32),
        k_all[P:], v_all[P:], backend="jnp")
    np.testing.assert_array_equal(np.asarray(full[P:]), np.asarray(out))


# ======================================================================
# kvcache satellites: write validation, incremental chunk writes, memo
# ======================================================================

def test_write_prefill_rejects_token_count_mismatch(setup):
    """A k/v whose token count disagrees with the allocated length raises
    a contextual ValueError instead of silently zero-padding the tail
    block (which decode would then read as real context)."""
    cfg, _ = setup
    kv = PagedKVCache(cfg, num_blocks=8, block_size=4)
    kv.allocate(1, 7)
    hd = cfg.resolved_head_dim
    mk = lambda s: jnp.zeros((cfg.num_layers, cfg.num_kv_heads, s, hd))  # noqa: E731
    with pytest.raises(ValueError, match="expected exactly 7"):
        kv.write_prefill(1, mk(5), mk(5))       # short: silent corruption
    with pytest.raises(ValueError, match="expected exactly 7"):
        kv.write_prefill(1, mk(8), mk(8))       # long but within capacity
    with pytest.raises(ValueError, match="expected exactly 3"):
        kv.write_prefill(1, mk(4), mk(4), start_token=4)
    kv.write_prefill(1, mk(7), mk(7))           # exact: fine


def test_write_prefill_chunk_allocates_incrementally(setup):
    cfg, _ = setup
    kv = PagedKVCache(cfg, num_blocks=4, block_size=4)
    hd = cfg.resolved_head_dim
    mk = lambda s: jnp.ones((cfg.num_layers, cfg.num_kv_heads, s, hd))  # noqa: E731
    kv.allocate(1, 4)
    kv.write_prefill_chunk(1, mk(4), mk(4), start_token=0)
    assert len(kv.tables[1]) == 1
    kv.write_prefill_chunk(1, mk(4), mk(4), start_token=4)
    assert len(kv.tables[1]) == 2 and kv.lengths[1] == 8
    kv.write_prefill_chunk(1, mk(3), mk(3), start_token=8)  # partial final
    assert len(kv.tables[1]) == 3 and kv.lengths[1] == 11
    kv.allocate(2, 4)                       # take the last free block
    with pytest.raises(PoolExhausted, match="chunked"):
        kv.write_prefill_chunk(1, mk(4), mk(4), start_token=11)


def test_gather_prefix_indices_memoised(setup):
    """The gather-index array is memoised by block-id CONTENT: a sharing
    wave's recipients (same physical blocks) hit one entry, and a CoW fork
    (different ids) misses instead of aliasing."""
    cfg, _ = setup
    kv = PagedKVCache(cfg, num_blocks=16, block_size=4)
    kv.allocate(1, 8)
    a = kv.gather_prefix_indices(1, 8)
    assert kv.gather_prefix_indices(1, 8) is a          # memo hit
    kv.share_blocks(1, 2, 8)
    assert kv.gather_prefix_indices(2, 8) is a          # same physical ids
    kv._cow_block(2, 1)                                 # fork slot 1
    b = kv.gather_prefix_indices(2, 8)
    assert b is not a
    assert list(np.asarray(b)) == kv.tables[2][:2]
    with pytest.raises(ValueError, match="block-aligned"):
        kv.gather_prefix_indices(1, 3)


# ======================================================================
# engine: greedy parity for every placement x partition (+ exotic configs)
# ======================================================================

@pytest.mark.parametrize("placement,partition,workers", [
    ("homogeneous", "head", 2),
    ("attention_pool", "head", 2),
    ("attention_pool", "request", 4),
    ("attention_pool", "block", 4),
])
def test_chunked_parity_across_placements(setup, placement, partition,
                                          workers):
    cfg, params = setup
    (on, eng_on), (off, eng_off) = _chunked_oneshot_pair(
        cfg, params, lens=(70, 9, 33, 18), chunk=16,
        econf_kw=dict(placement=placement, partition=partition,
                      attention_workers=workers, max_batch=4, num_blocks=64,
                      block_size=16))
    assert on == off                    # bit-identical greedy streams
    assert eng_on.stats.prefill_chunks_run >= 9   # ceil(70/16)+1+3+2
    assert eng_on.stats.max_prefill_slab_tokens == 16
    assert eng_off.stats.prefill_chunks_run == 0
    assert eng_off.stats.max_prefill_slab_tokens == 70
    assert eng_on.kv.used_blocks == 0   # everything released


def test_chunked_pallas_backend_end_to_end(setup):
    """decode_backend='pallas' reaches the chunk KERNEL (prefix streamed
    from the pool in place — no dense gather): the engine completes and
    its greedy stream stays close to the jnp reference engine's (kernel
    numerics, like every pallas backend; bit-parity is the jnp contract)."""
    cfg, params = setup
    outs = {}
    for backend in ("jnp", "pallas"):
        reqs = _reqs(cfg, (40, 18), new=4, seed=12)
        eng = LLMEngine(cfg, params, EngineConfig(
            max_batch=2, num_blocks=64, block_size=16,
            prefill_chunk_tokens=16, decode_backend=backend))
        eng.submit(reqs)
        eng.run()
        assert eng.stats.prefill_chunks_run == 5    # ceil(40/16)+ceil(18/16)
        assert all(r.state == State.FINISHED for r in reqs)
        outs[backend] = [r.output for r in reqs]
    assert outs["pallas"] == outs["jnp"]   # tiny smoke logits: argmax agrees


def test_chunked_gemma2_parity():
    """Windows + sinks + softcap + post-norms through the chunk path, with
    a prompt longer than the sliding window."""
    cfg, params = _setup("gemma2-27b")
    (on, _), (off, _) = _chunked_oneshot_pair(
        cfg, params, lens=(81, 40), chunk=16, new=8,
        econf_kw=dict(placement="attention_pool", max_batch=2,
                      num_blocks=64, block_size=16))
    assert on == off


def test_chunked_moe_falls_back_to_oneshot():
    """A chunk boundary changes MoE capacity-dispatch groups, so the
    engine runs MoE prompts one-shot: outputs identical, zero chunks."""
    cfg = registry.get_smoke_config("qwen3-moe-30b-a3b").replace(
        capacity_factor=64.0)
    params = transformer.init_params(jax.random.PRNGKey(0), cfg)
    (on, eng_on), (off, _) = _chunked_oneshot_pair(
        cfg, params, lens=(20, 23), chunk=8, new=5,
        econf_kw=dict(placement="moe_offload", attention_workers=2,
                      expert_workers=2, max_batch=2, num_blocks=64,
                      block_size=8))
    assert on == off
    assert eng_on.stats.prefill_chunks_run == 0
    assert eng_on._chunk_tokens is None


def test_chunked_with_prefix_sharing_parity(setup):
    cfg, params = setup
    common = np.random.default_rng(0).integers(
        0, cfg.vocab_size, size=40).tolist()
    res = {}
    for chunk in (None, 16):
        r = np.random.default_rng(42)
        reqs = [Request(prompt=list(common) +
                        r.integers(0, cfg.vocab_size, size=t).tolist(),
                        params=SamplingParams(max_new_tokens=8))
                for t in (5, 6, 7, 8)]
        eng = LLMEngine(cfg, params, EngineConfig(
            max_batch=4, num_blocks=64, block_size=16, prefix_sharing=True,
            prefill_chunk_tokens=chunk))
        eng.submit(reqs)
        eng.run()
        res[chunk] = ([q.output for q in reqs], eng)
    assert res[None][0] == res[16][0]
    # same-wave sharing under chunking is capped at the donor's progress
    # (its first chunk) — still nonzero, and the pool still drains clean
    assert res[16][1].stats.blocks_shared > 0
    assert res[16][1].kv.used_blocks == 0
    assert res[16][1].kv.refcounts == {}


def test_late_sharer_of_mid_prefill_donor_is_bit_safe(setup):
    """A recipient arriving while its donor is MID-PREFILL may only share
    blocks the donor has written (the match is capped at the donor's
    allocated progress) — its stream is bit-identical to a solo run."""
    cfg, params = setup
    common = np.random.default_rng(0).integers(
        0, cfg.vocab_size, size=40).tolist()
    r = np.random.default_rng(9)
    donor = Request(prompt=list(common) +
                    r.integers(0, cfg.vocab_size, size=56).tolist(),
                    params=SamplingParams(max_new_tokens=4))
    prompt = list(common[:32]) + r.integers(0, cfg.vocab_size,
                                            size=8).tolist()
    solo = Request(prompt=list(prompt),
                   params=SamplingParams(max_new_tokens=6))
    e0 = LLMEngine(cfg, params, EngineConfig(max_batch=2, num_blocks=64,
                                             block_size=16))
    e0.submit(solo)
    e0.run()
    eng = LLMEngine(cfg, params, EngineConfig(
        max_batch=4, num_blocks=64, block_size=16, prefix_sharing=True,
        prefill_chunk_tokens=16))
    eng.submit(donor)
    eng.step()
    assert eng.sched.prefill_cursor(donor.rid) == 16   # donor mid-prefill
    late = Request(prompt=list(prompt),
                   params=SamplingParams(max_new_tokens=6))
    eng.submit(late)
    eng.run()
    assert late.output == solo.output
    assert donor.state == State.FINISHED
    assert eng.kv.used_blocks == 0 and eng.kv.refcounts == {}


def test_chunked_preemption_parity(setup):
    """Pool pressure forces evictions while prompts prefill chunked; every
    stream still finishes bit-identical to an uncontended run."""
    cfg, params = setup

    def mk():
        r = np.random.default_rng(7)
        return [Request(prompt=r.integers(0, cfg.vocab_size,
                                          size=18).tolist(),
                        params=SamplingParams(max_new_tokens=24))
                for _ in range(3)]

    ref = mk()
    e0 = LLMEngine(cfg, params, EngineConfig(max_batch=4, num_blocks=64,
                                             block_size=8))
    e0.submit(ref)
    e0.run()
    tight = mk()
    eng = LLMEngine(cfg, params, EngineConfig(
        max_batch=4, num_blocks=12, block_size=8, scheduler="preempt",
        decode_headroom=2, prefill_chunk_tokens=8))
    eng.submit(tight)
    eng.run(max_steps=3000)
    assert eng.stats.preemptions > 0
    assert [r.output for r in tight] == [r.output for r in ref]
    assert eng.kv.used_blocks == 0


# ======================================================================
# tentpole acceptance: admission beyond the currently-free pool + mixed
# iterations keep the decode batch moving
# ======================================================================

def test_long_prompt_admitted_into_mostly_held_pool(setup):
    """A prompt whose whole allocation exceeds the FREE pool at arrival is
    admitted on its first chunk and completes (blocks arrive as decoders
    retire) — one-shot admission must wait head-of-line for the full
    allocation."""
    cfg, params = setup
    r = np.random.default_rng(5)
    prompt = r.integers(0, cfg.vocab_size, size=176).tolist()
    solo = Request(prompt=list(prompt), params=SamplingParams(max_new_tokens=4))
    e0 = LLMEngine(cfg, params, EngineConfig(max_batch=4, num_blocks=32,
                                             block_size=8))
    e0.submit(solo)
    e0.run()
    waits = {}
    for chunk in (None, 16):
        eng = LLMEngine(cfg, params, EngineConfig(
            max_batch=4, num_blocks=24, block_size=8,
            prefill_chunk_tokens=chunk))
        shorts = _reqs(cfg, (17, 17), new=6, seed=8)
        eng.submit(shorts)
        eng.step()
        long_req = Request(prompt=list(prompt),
                           params=SamplingParams(max_new_tokens=4))
        free = len(eng.kv.free)
        assert free < eng.kv.blocks_needed(len(prompt))   # cannot one-shot
        eng.submit(long_req)
        eng.run(max_steps=1000)
        steps = {e.kind: e.step for e in eng.event_log
                 if e.rid == long_req.rid}
        waits[chunk] = steps["admit"] - steps["submit"]
        assert long_req.output == solo.output
    assert waits[16] < waits[None]


def test_decode_batch_advances_during_chunked_prefill(setup):
    """Mixed iterations: while the long prompt's chunks run, every running
    decoder still produces exactly one token per engine step."""
    cfg, params = setup
    eng = LLMEngine(cfg, params, EngineConfig(
        max_batch=4, num_blocks=256, block_size=16,
        prefill_chunk_tokens=16))
    shorts = _reqs(cfg, (16, 16), new=30, seed=2)
    eng.submit(shorts)
    eng.step(); eng.step()
    long_req = _reqs(cfg, (128,), new=2, seed=4)[0]
    eng.submit(long_req)
    for _ in range(50):
        before = [len(r.output) for r in shorts]
        eng.step()
        after = [len(r.output) for r in shorts]
        assert all(b - a == 1 for a, b in zip(before, after)
                   if a < 30)          # decoders advanced THIS step
        if long_req.state == State.RUNNING and \
                eng.sched.prefill_done(long_req.rid):
            break
    chunks = [e for e in eng.event_log
              if e.kind == "chunk" and e.rid == long_req.rid]
    assert len(chunks) == 8            # ceil(128 / 16), one per step
    assert [c.step for c in chunks] == \
        list(range(chunks[0].step, chunks[0].step + 8))
    eng.run()
    assert long_req.state == State.FINISHED


@pytest.mark.parametrize("scheduler", ["fcfs", "preempt"])
def test_concurrent_partial_prompts_never_deadlock(setup, scheduler):
    """Aggregate over-commitment guard: several long prompts whose first
    chunks all fit must NOT be co-admitted into a pool that cannot
    complete them (younger partial prompts' holdings are stuck until the
    oldest finishes) — the workload completes exactly like one-shot
    admission does, just with earlier overlap."""
    cfg, params = setup

    def mk():
        r = np.random.default_rng(21)
        return [Request(prompt=r.integers(0, cfg.vocab_size,
                                          size=100).tolist(),
                        params=SamplingParams(max_new_tokens=4))
                for _ in range(3)]

    ref = mk()
    e0 = LLMEngine(cfg, params, EngineConfig(max_batch=4, num_blocks=8,
                                             block_size=16,
                                             scheduler=scheduler))
    e0.submit(ref)
    e0.run(max_steps=2000)
    reqs = mk()
    eng = LLMEngine(cfg, params, EngineConfig(
        max_batch=4, num_blocks=8, block_size=16, scheduler=scheduler,
        prefill_chunk_tokens=16))
    eng.submit(reqs)
    eng.run(max_steps=2000)
    assert all(r.state == State.FINISHED for r in reqs)
    assert [r.output for r in reqs] == [r.output for r in ref]
    assert eng.kv.used_blocks == 0


def test_commitment_guard_counts_shared_blocks_once(setup):
    """The over-commitment guard counts a prefix-shared physical block
    ONCE across co-admitted partial prompts — a common-prefix family is
    admitted together (double-counting would serialise it and erase the
    sharing capacity win)."""
    cfg, _ = setup
    common = list(np.random.default_rng(2).integers(
        0, cfg.vocab_size, size=32))
    r = np.random.default_rng(5)
    reqs = [Request(prompt=common + r.integers(0, cfg.vocab_size,
                                               size=8).tolist(),
                    params=SamplingParams(max_new_tokens=2))
            for _ in range(3)]
    kv = PagedKVCache(cfg, num_blocks=6, block_size=16)
    sched = RequestScheduler(
        kv, max_batch=4, policy=make_policy("fcfs",
                                            prefill_chunk_tokens=16),
        decode_headroom=0, prefix_sharing=True)
    sched.submit(reqs)
    assert len(sched.admit()) == 3      # whole family co-admitted
    # each sharer borrowed the donor's first block — counted once
    assert kv.tables[reqs[1].rid][0] == kv.tables[reqs[0].rid][0]
    assert kv.tables[reqs[2].rid][0] == kv.tables[reqs[0].rid][0]


def test_scheduler_rejects_misaligned_chunk_tokens(setup):
    cfg, _ = setup
    kv = PagedKVCache(cfg, num_blocks=8, block_size=16)
    with pytest.raises(ValueError, match="multiple of the KV block size"):
        RequestScheduler(kv, max_batch=2,
                         policy=make_policy("fcfs",
                                            prefill_chunk_tokens=24))


def test_never_fitting_prompt_stalls_cleanly(setup):
    """A prompt the TOTAL pool can never hold is not admitted chunked (it
    could never finish): the engine surfaces SchedulingStalled."""
    cfg, params = setup
    eng = LLMEngine(cfg, params, EngineConfig(
        max_batch=2, num_blocks=8, block_size=8, prefill_chunk_tokens=8))
    eng.submit(_reqs(cfg, (100,), new=2))
    with pytest.raises(SchedulingStalled):
        eng.run()


# ======================================================================
# surface: events, stats, config, policy
# ======================================================================

def test_chunk_events_and_stats(setup):
    cfg, params = setup
    eng = LLMEngine(cfg, params, EngineConfig(
        max_batch=2, num_blocks=64, block_size=16,
        prefill_chunk_tokens=32))
    req = _reqs(cfg, (70,), new=2, seed=6)[0]
    eng.submit(req)
    eng.run()
    chunks = [e for e in eng.event_log if e.kind == "chunk"]
    assert [c.info["tokens"] for c in chunks] == [32, 32, 6]
    assert [c.info["start"] for c in chunks] == [0, 32, 64]
    assert chunks[-1].info["remaining"] == 0
    s = eng.stats.summary()
    assert s["prefill_chunks_run"] == 3
    assert s["max_prefill_slab_tokens"] == 32
    admit = [e for e in eng.event_log if e.kind == "admit"][0]
    assert admit.info.get("chunked") is True


def test_config_validates_chunk_tokens():
    with pytest.raises(ValueError, match="multiple of block_size"):
        EngineConfig(block_size=16, prefill_chunk_tokens=24)
    with pytest.raises(ValueError, match=">= 1"):
        EngineConfig(prefill_chunk_tokens=0)
    assert EngineConfig().prefill_chunk_tokens is None   # default off
    assert EngineConfig(block_size=16, prefill_chunk_tokens=32) \
        .prefill_chunk_tokens == 32


def test_chunked_policy_wraps_inner():
    p = make_policy("preempt", prefill_chunk_tokens=32)
    assert isinstance(p, ChunkedPrefillPolicy)
    assert p.preemptible and p.chunk_tokens == 32
    assert "preempt" in p.name
    assert make_policy("fcfs").__class__.__name__ == "FCFSPolicy"
    with pytest.raises(ValueError, match=">= 1"):
        ChunkedPrefillPolicy(make_policy("fcfs"), 0)


def test_chunked_admission_charges_only_first_chunk(setup):
    """Scheduler-level: chunked admission pops exactly the first chunk's
    blocks; the cursor starts at the shared prefix."""
    cfg, _ = setup
    kv = PagedKVCache(cfg, num_blocks=64, block_size=8)
    sched = RequestScheduler(kv, max_batch=4,
                             policy=make_policy("fcfs",
                                                prefill_chunk_tokens=16),
                             decode_headroom=0)
    req = _reqs(cfg, (100,), new=2)[0]
    sched.submit([req])
    assert sched.admit() == [req]
    assert kv.lengths[req.rid] == 16          # first chunk only
    assert len(kv.tables[req.rid]) == 2
    assert sched.prefill_cursor(req.rid) == 0
    assert not sched.prefill_done(req.rid)
    assert sched.next_prefill() is req
    sched.advance_prefill(req, 16)
    assert sched.prefill_cursor(req.rid) == 16
    sched.advance_prefill(req, 100)
    assert sched.prefill_done(req.rid)
    assert sched.next_prefill() is None
