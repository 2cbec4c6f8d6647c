"""Fault-tolerant attention-pool serving: shard fault injection, detection
(healthy → suspect → dead with bounded retry), and bit-exact request
recovery via the §5 preempt-and-recompute path.

The headline invariant is the parity matrix: greedy outputs through an
injected mid-decode shard failure are BIT-IDENTICAL to the fault-free run,
for attention_pool × {head, request, block} partitions, with prefix
sharing and chunked prefill enabled. Plus: transient/corrupt/straggler
scenarios, the shard-masked allocator's invariants under hypothesis,
degraded-capacity PoolExhausted context, the always-on non-finite-logits
guard, and graceful cancellation.
"""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.configs import registry
from repro.models import transformer
from repro.serving import (CorruptedLogitsError, EngineConfig, FaultEvent,
                           FaultInjector, FaultScenario, LLMEngine,
                           PagedKVCache, PoolExhausted, Request,
                           SamplingParams, SchedulingStalled,
                           ShardHealthTracker, State)
from repro.serving.faults import DEAD, HEALTHY, SUSPECT
from repro.serving.kvcache import OutOfBlocks


@pytest.fixture(scope="module")
def setup():
    cfg = registry.get_smoke_config("llama3-8b")
    params = transformer.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _reqs(cfg, lens=(9, 14, 6), new=10, prefix=6, seed=0):
    """Requests sharing a common prompt prefix (exercises prefix sharing
    through recovery) with per-request suffixes."""
    rng = np.random.default_rng(seed)
    common = rng.integers(0, cfg.vocab_size, size=prefix).tolist()
    return [Request(prompt=common +
                    rng.integers(0, cfg.vocab_size, size=n).tolist(),
                    params=SamplingParams(max_new_tokens=new))
            for n in lens]


def _econf(partition, **kw):
    base = dict(placement="attention_pool", partition=partition,
                attention_workers=2, num_blocks=64, block_size=4,
                max_batch=4, scheduler="preempt", prefix_sharing=True,
                prefill_chunk_tokens=8)
    # head/request partitions default to an unsharded pool — shard it
    # explicitly so there is a shard boundary to kill
    if partition != "block":
        base["kv_shards"] = 2
    base.update(kw)
    return EngineConfig(**base)


def _run(cfg, params, econf, scenario=None, **reqkw):
    injector = FaultInjector(FaultScenario.parse(scenario)) \
        if scenario else None
    eng = LLMEngine(cfg, params, econf, fault_injector=injector)
    reqs = _reqs(cfg, **reqkw)
    eng.submit(reqs)
    eng.run()
    return eng, [r.output for r in reqs]


# ======================================================================
# tentpole: the parity matrix — bit-exact recovery through shard death
# ======================================================================

@pytest.mark.parametrize("partition", ["head", "request", "block"])
def test_shard_death_bit_parity(setup, partition):
    """Mid-decode shard death (+ later rejoin): greedy outputs are
    bit-identical to the fault-free run across every pool partition, with
    prefix sharing AND chunked prefill enabled."""
    cfg, params = setup
    econf = _econf(partition)
    _, ref = _run(cfg, params, econf)
    eng, out = _run(cfg, params, econf,
                    scenario="shard_death:shard=1,step=5,rejoin=14")
    assert out == ref
    s = eng.stats
    assert s.shard_failures == 1
    assert s.shard_rejoins == 1
    assert s.requests_recovered >= 1
    assert len(s.recovery_latencies) == s.requests_recovered
    kinds = [e.kind for e in eng.event_log]
    for k in ("shard_suspect", "retry", "shard_down", "shard_up",
              "recover"):
        assert k in kinds, f"missing {k} event"
    down = next(e for e in eng.event_log if e.kind == "shard_down")
    assert down.rid == -1 and down.info["shard"] == 1
    assert down.info["victims"], "a mid-decode death must name victims"
    # after rejoin the pool is whole again
    assert eng.kv.quarantined_shards == ()
    assert eng.kv.capacity_blocks == econf.num_blocks


def test_shard_death_without_rejoin_still_recovers(setup):
    """No replacement hardware: victims still recover onto the surviving
    shard (capacity stays degraded) and outputs stay bit-identical."""
    cfg, params = setup
    econf = _econf("block")
    _, ref = _run(cfg, params, econf)
    eng, out = _run(cfg, params, econf,
                    scenario="shard_death:shard=0,step=4")
    assert out == ref
    assert eng.stats.shard_failures == 1
    assert eng.stats.shard_rejoins == 0
    assert eng.kv.quarantined_shards == (0,)
    assert eng.kv.capacity_blocks == econf.num_blocks // 2
    # the dead shard holds no live request's blocks after recovery
    assert eng.kv.seqs_on_shard(0) == []


def test_transient_fault_recovers_without_eviction(setup):
    """A blip below the retry budget: the shard recovers in place — no
    preemption, no quarantine, parity intact."""
    cfg, params = setup
    econf = _econf("block")
    ref_eng, ref = _run(cfg, params, econf)
    eng, out = _run(cfg, params, econf,
                    scenario="transient:shard=0,step=3,failures=2")
    assert out == ref
    s = eng.stats
    assert s.transient_faults_recovered == 1
    assert s.fault_retries == 2
    assert s.shard_failures == 0
    assert s.preemptions == ref_eng.stats.preemptions
    assert eng.kv.quarantined_shards == ()


def test_corrupt_partial_retries_bit_identically(setup):
    """NaN in the merged decode output: the engine re-runs the
    deterministic step (nothing was committed) — outputs bit-identical,
    the faulty shard goes suspect then recovers."""
    cfg, params = setup
    econf = _econf("block")
    _, ref = _run(cfg, params, econf)
    eng, out = _run(cfg, params, econf, scenario="corrupt:shard=1,step=6")
    assert out == ref
    s = eng.stats
    assert s.transient_faults_recovered == 1
    assert s.shard_failures == 0
    kinds = [e.kind for e in eng.event_log]
    assert "shard_suspect" in kinds and "recover" in kinds


def test_corrupt_past_retry_budget_kills_shard(setup):
    """Corruption that never clears exhausts the retry budget: the shard
    is declared dead and its requests recover — parity still holds."""
    cfg, params = setup
    econf = _econf("block", fault_retry_limit=2)
    _, ref = _run(cfg, params, econf)
    eng, out = _run(cfg, params, econf,
                    scenario="corrupt:shard=1,step=5,failures=5")
    assert out == ref
    assert eng.stats.shard_failures == 1
    assert eng.kv.quarantined_shards == (1,)


def test_straggler_is_observed_not_evicted(setup):
    cfg, params = setup
    econf = _econf("block")
    _, ref = _run(cfg, params, econf)
    eng, out = _run(cfg, params, econf,
                    scenario="straggle:shard=0,step=4,delay_ms=1")
    assert out == ref
    s = eng.stats
    assert s.straggle_steps == 1
    assert s.shard_failures == 0 and s.preemptions == 0
    sus = [e for e in eng.event_log if e.kind == "shard_suspect"]
    assert sus and sus[0].info["cause"] == "straggler"


def test_multi_fault_scenario_parity(setup):
    """Everything at once: transient, straggle, corruption, then a death
    with rejoin — outputs still bit-identical."""
    cfg, params = setup
    econf = _econf("block")
    _, ref = _run(cfg, params, econf)
    eng, out = _run(
        cfg, params, econf,
        scenario="transient:shard=0,step=2;straggle:shard=1,step=3,"
                 "delay_ms=1;corrupt:shard=0,step=4;"
                 "shard_death:shard=1,step=6,rejoin=15")
    assert out == ref
    assert eng.stats.shard_failures == 1
    assert eng.stats.transient_faults_recovered == 2


def test_recovery_stats_in_summary(setup):
    cfg, params = setup
    eng, _ = _run(cfg, params, _econf("block"),
                  scenario="shard_death:shard=1,step=5,rejoin=14")
    s = eng.stats.summary()
    for key in ("shard_failures", "shard_rejoins", "fault_retries",
                "transient_faults_recovered", "straggle_steps",
                "requests_recovered", "recovery_p50_s", "recovery_p99_s"):
        assert key in s
    assert s["shard_failures"] == 1
    assert s["recovery_p50_s"] >= 0.0


# ======================================================================
# health state machine
# ======================================================================

def test_health_tracker_state_machine():
    h = ShardHealthTracker(2, retry_limit=3)
    assert h.state(0) == HEALTHY
    assert h.strike(0) == SUSPECT
    assert h.strike(0) == SUSPECT
    h.clear(0)                      # retry succeeded before the limit
    assert h.state(0) == HEALTHY and h.strikes(0) == 0
    for _ in range(3):
        st_ = h.strike(0)
    assert st_ == DEAD and h.is_dead(0)
    h.clear(0)                      # clear never resurrects the dead
    assert h.is_dead(0)
    assert h.strike(0) == DEAD
    h.mark_up(0)                    # rejoin does
    assert h.state(0) == HEALTHY and h.strikes(0) == 0
    assert h.dead_shards == []
    with pytest.raises(ValueError):
        ShardHealthTracker(2, retry_limit=0)


# ======================================================================
# scenario parsing / injector determinism
# ======================================================================

def test_scenario_parse_inline_and_json(tmp_path):
    sc = FaultScenario.parse(
        "shard_death:shard=1,step=6,rejoin=20;"
        "corrupt:shard=0,step=9,failures=2;"
        "straggle:shard=1,step=3,delay_ms=5")
    assert [e.kind for e in sc] == ["straggle", "shard_death", "corrupt"]
    assert sc.events[1].rejoin_step == 20
    assert sc.events[0].delay_s == pytest.approx(5e-3)

    path = tmp_path / "scenario.json"
    path.write_text(json.dumps([
        {"kind": "shard_death", "shard": 0, "step": 4, "rejoin_step": 9},
        {"kind": "transient", "shard": 1, "step": 2},
    ]))
    sc2 = FaultScenario.parse(str(path))
    assert len(sc2) == 2 and sc2.events[1].kind == "shard_death"


def test_scenario_validation_errors():
    with pytest.raises(ValueError):
        FaultEvent("meteor_strike", 0, 1)
    with pytest.raises(ValueError):
        FaultEvent("shard_death", 0, 5, rejoin_step=5)   # rejoin <= death
    with pytest.raises(ValueError):
        FaultEvent("shard_death", 0, 0)                  # steps are 1-based
    with pytest.raises(ValueError):
        FaultScenario.parse("")
    with pytest.raises(ValueError):
        FaultScenario.parse("corrupt:shard=0,step=2,zorp=1")
    with pytest.raises(ValueError):                      # one life per shard
        FaultInjector(FaultScenario.parse(
            "shard_death:shard=0,step=2;shard_death:shard=0,step=9"))


def test_injector_probe_and_budget():
    inj = FaultInjector(FaultScenario.parse(
        "shard_death:shard=1,step=3,rejoin=7;"
        "transient:shard=0,step=2,failures=2"))
    assert inj.probe(1, 2)                    # alive before the death step
    assert not inj.probe(1, 3)
    assert not inj.probe(1, 6)                # dead until rejoin
    assert inj.probe(1, 7)                    # back at the rejoin step
    assert inj.rejoins(7) == [1]
    assert inj.pending_rejoins(5) and not inj.pending_rejoins(7)
    # the transient's budget burns down probe by probe, then clears
    assert not inj.probe(0, 2)
    assert not inj.probe(0, 2)
    assert inj.probe(0, 2)


def test_injector_filter_decode_consumes_budget():
    inj = FaultInjector(FaultScenario.parse("corrupt:shard=1,step=4"))
    clean = jnp.zeros((2, 8), jnp.float32)
    out, shard = inj.filter_decode(4, clean)
    assert shard == 1 and bool(jnp.isnan(out).all())
    out2, shard2 = inj.filter_decode(4, clean)   # budget spent: clean again
    assert shard2 is None and bool(jnp.isfinite(out2).all())


def test_random_scenario_deterministic():
    a = FaultScenario.random(7, n_shards=2, horizon=20)
    b = FaultScenario.random(7, n_shards=2, horizon=20)
    assert a.events == b.events
    assert FaultScenario.random(8, 2, 20).events != a.events


# ======================================================================
# shard-masked allocator: quarantine/rejoin invariants (hypothesis)
# ======================================================================

def _sharded_cache(num_blocks=32, block_size=4, n_shards=4):
    cfg = registry.get_smoke_config("llama3-8b")
    return PagedKVCache(cfg, num_blocks, block_size, n_shards=n_shards)


@settings(deadline=None, max_examples=25)
@given(ops=st.lists(
    st.tuples(st.sampled_from(["alloc", "share", "append", "free",
                               "quarantine", "rejoin"]),
              st.integers(0, 5), st.integers(1, 24)),
    min_size=1, max_size=50))
def test_shard_masked_allocator_invariants(ops):
    """Random quarantine/rejoin interleaved with alloc/share/append/free:
    refcounts stay the single source of truth, no block is lost or doubly
    free, and a quarantined shard's free list never shrinks (nothing is
    allocated from it while masked)."""
    kv = _sharded_cache()
    n_shards, total = kv.n_shards, kv.num_blocks
    for kind, sid, n in ops:
        shard = sid % n_shards
        try:
            if kind == "alloc" and sid not in kv.tables:
                kv.allocate(sid, n)
            elif kind == "share":
                src, dst = sid, sid + 100
                if src in kv.tables and dst not in kv.tables \
                        and kv.lengths[src] >= 1:
                    kv.share_blocks(src, dst,
                                    max(1, min(n, kv.lengths[src])))
            elif kind == "append" and sid in kv.tables:
                kv.append_token(sid)
            elif kind == "free" and sid in kv.tables:
                kv.free_seq(sid)
            elif kind == "quarantine":
                pre = len(kv._free_shard[shard])
                kv.quarantine_shard(shard)
                assert len(kv._free_shard[shard]) == pre
            elif kind == "rejoin":
                kv.rejoin_shard(shard)
        except OutOfBlocks:
            pass
        # ---- invariants after every op ----
        referenced = {b for t in kv.tables.values() for b in t}
        all_free = [b for s in kv._free_shard for b in s]
        # refcounts: value == number of tables referencing the block
        for b, rc in kv.refcounts.items():
            assert rc == sum(b in t for t in kv.tables.values())
            assert rc >= 1
        assert referenced == set(kv.refcounts)
        # conservation: referenced + free == every block, no overlap
        assert len(all_free) == len(set(all_free)), "block doubly free"
        assert set(all_free).isdisjoint(referenced)
        assert len(all_free) + len(referenced) == total
        # masking: quarantined shards contribute nothing allocatable
        for q in kv.quarantined_shards:
            assert all(kv.shard_of(b) != q for b in kv.free)
        assert kv.num_free == len(kv.free)
        assert kv.capacity_blocks == \
            kv.blocks_per_shard * len(kv.live_shards)


def test_quarantined_shard_never_allocated_and_balance_holds():
    kv = _sharded_cache(num_blocks=32, block_size=4, n_shards=4)
    kv.quarantine_shard(2)
    kv.allocate(1, 24)                     # 6 blocks over 3 live shards
    placed = [kv.shard_of(b) for b in kv.tables[1]]
    assert 2 not in placed
    counts = {s: placed.count(s) for s in kv.live_shards}
    assert max(counts.values()) - min(counts.values()) <= 1, \
        "shard-masked round-robin lost balance over survivors"
    # rejoin restores the shard to the rotation
    kv.rejoin_shard(2)
    kv.allocate(2, 16)                     # 4 blocks over 4 live shards
    placed2 = {kv.shard_of(b) for b in kv.tables[2]}
    assert 2 in placed2


def test_all_shards_quarantined_raises():
    kv = _sharded_cache(num_blocks=16, block_size=4, n_shards=2)
    kv.quarantine_shard(0)
    kv.quarantine_shard(1)
    with pytest.raises(OutOfBlocks, match="quarantined"):
        kv.allocate(1, 4)
    with pytest.raises(ValueError):
        kv.quarantine_shard(5)


# ======================================================================
# degraded-capacity exhaustion context (satellite 6)
# ======================================================================

def test_pool_exhausted_carries_degraded_context():
    kv = _sharded_cache(num_blocks=16, block_size=4, n_shards=2)
    kv.quarantine_shard(1)
    with pytest.raises(PoolExhausted) as ei:
        kv.allocate(1, 64)                 # needs 16 > 8 surviving blocks
    e = ei.value
    assert e.degraded
    assert e.quarantined_shards == (1,)
    assert e.live_shards == (0,)
    assert "DEGRADED" in str(e)


def test_healthy_pool_exhausted_not_degraded():
    kv = _sharded_cache(num_blocks=16, block_size=4, n_shards=2)
    with pytest.raises(PoolExhausted) as ei:
        kv.allocate(1, 100)
    assert not ei.value.degraded
    assert ei.value.quarantined_shards == ()
    assert "DEGRADED" not in str(ei.value)


def test_stall_after_unrecoverable_death_names_degradation(setup):
    """Both block-partition shards gone except capacity too small for the
    waiting head and no rejoin scheduled: SchedulingStalled (not a spin)
    and the message names the quarantine."""
    cfg, params = setup
    econf = _econf("block", num_blocks=16, prefix_sharing=False,
                   prefill_chunk_tokens=None)
    inj = FaultInjector(FaultScenario.parse("shard_death:shard=0,step=2"))
    eng = LLMEngine(cfg, params, econf, fault_injector=inj)
    # head needs more than one shard's 8 blocks: 30 tokens + headroom
    eng.submit([Request(prompt=list(range(1, 31)),
                        params=SamplingParams(max_new_tokens=4))])
    with pytest.raises(SchedulingStalled, match="DEGRADED"):
        eng.run()


# ======================================================================
# non-finite logits guard (satellite 1)
# ======================================================================

def test_corrupted_logits_error_names_request_and_step(setup):
    cfg, params = setup
    eng = LLMEngine(cfg, params, EngineConfig(num_blocks=32, block_size=4))
    req = Request(prompt=[1, 2, 3], params=SamplingParams(max_new_tokens=4))
    eng._step_no = 7
    bad = jnp.full((1, cfg.vocab_size), jnp.nan, jnp.float32)
    with pytest.raises(CorruptedLogitsError) as ei:
        eng._sample([req], bad)
    assert ei.value.rids == (req.rid,)
    assert ei.value.step == 7
    assert str(req.rid) in str(ei.value) and "step 7" in str(ei.value)


def test_finite_logits_pass_guard(setup):
    cfg, params = setup
    eng = LLMEngine(cfg, params, EngineConfig(num_blocks=32, block_size=4))
    req = Request(prompt=[1, 2, 3], params=SamplingParams(max_new_tokens=4))
    ok = jnp.zeros((1, cfg.vocab_size), jnp.float32)
    tok = eng._sample([req], ok)
    assert tok.shape == (1,)


# ======================================================================
# graceful cancellation (satellite 2's engine-side half)
# ======================================================================

def test_cancel_all_drains_cleanly(setup):
    cfg, params = setup
    eng = LLMEngine(cfg, params, _econf("block"))
    reqs = _reqs(cfg, lens=(8, 12), new=50)
    handles = eng.submit(reqs)
    for _ in range(3):
        eng.step()
    partial = [list(r.output) for r in reqs]
    assert any(partial), "requests should have tokens before cancel"
    n = eng.cancel_all()
    assert n == 2
    assert all(r.state == State.FINISHED for r in reqs)
    assert [r.output for r in reqs] == partial     # outputs kept, not wiped
    assert not eng.has_work()
    assert eng.kv.tables == {}                     # every block released
    assert eng.kv.num_free == eng.kv.capacity_blocks
    fins = [e for e in eng.event_log if e.kind == "finish"]
    assert len(fins) == 2
    assert all(e.info.get("cancelled") for e in fins)
    # handle iteration terminates without driving the engine further
    assert list(handles[0]) == partial[0]
    assert eng.cancel_all() == 0                   # idempotent


def test_fault_config_validation():
    with pytest.raises(ValueError):
        EngineConfig(fault_retry_limit=0)
    with pytest.raises(ValueError):
        EngineConfig(fault_retry_backoff_s=-1.0)
