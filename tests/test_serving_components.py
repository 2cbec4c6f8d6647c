"""Additional serving-substrate properties: sampler distribution/determinism,
scheduler FCFS + memory safety, request lifecycle."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.configs import registry
from repro.serving.kvcache import PagedKVCache
from repro.serving.request import Request, SamplingParams, State
from repro.serving.sampler import (request_key, sample, sample_per_request,
                                   sample_requests)
from repro.serving.scheduler import RequestScheduler


def test_greedy_sampling_is_argmax():
    logits = jnp.asarray([[1.0, 5.0, 2.0], [0.5, 0.1, 9.0]])
    out = sample(logits, jax.random.PRNGKey(0), temperature=0.0)
    assert out.tolist() == [1, 2]


def test_temperature_sampling_matches_distribution():
    logits = jnp.log(jnp.asarray([[0.7, 0.2, 0.1]]))
    counts = np.zeros(3)
    key = jax.random.PRNGKey(0)
    for i in range(400):
        key, sub = jax.random.split(key)
        counts[int(sample(logits, sub, temperature=1.0)[0])] += 1
    freq = counts / counts.sum()
    np.testing.assert_allclose(freq, [0.7, 0.2, 0.1], atol=0.08)


def test_top_k_restricts_support():
    logits = jnp.asarray([[5.0, 4.0, -1.0, -2.0, -3.0]])
    key = jax.random.PRNGKey(0)
    seen = set()
    for i in range(100):
        key, sub = jax.random.split(key)
        seen.add(int(sample(logits, sub, temperature=1.0, top_k=2)[0]))
    assert seen <= {0, 1}


@pytest.mark.parametrize("temps", [(0.0, 0.0, 0.0, 0.0),
                                   (0.0, 0.7, 1.0, 1.3)])
def test_one_program_sampler_draws_the_per_row_keys(temps):
    """The engine's one-program sampler draws each row from
    request_key(seed, token index), exactly as the per-row keys do, and
    reports each row's finiteness."""
    rng = np.random.default_rng(3)
    logits = jnp.asarray(rng.normal(size=(4, 97)) * 3, jnp.bfloat16)
    seeds, idx, topks = [0, 2**31 + 5, 7, 0], [0, 3, 511, 12], [0, 5, 0, 1]
    keys = jnp.stack([request_key(s, i) for s, i in zip(seeds, idx)])
    want = sample_per_request(logits, keys, np.float32(temps),
                              np.int32(topks))
    toks, finite = sample_requests(logits, seeds, idx, temps, topks)
    assert toks.dtype == np.int32 and toks.tolist() == want.tolist()
    assert finite.tolist() == [True] * 4
    _, finite = sample_requests(logits.at[2, 5].set(jnp.nan), seeds, idx,
                                temps, topks)
    assert finite.tolist() == [True, True, False, True]


@settings(deadline=None, max_examples=25)
@given(prompts=st.lists(st.integers(1, 40), min_size=1, max_size=10),
       max_batch=st.integers(1, 6), blocks=st.integers(4, 40))
def test_scheduler_never_overcommits(prompts, max_batch, blocks):
    cfg = registry.get_smoke_config("llama3-8b")
    kv = PagedKVCache(cfg, num_blocks=blocks, block_size=8)
    sched = RequestScheduler(kv, max_batch=max_batch)
    reqs = [Request(prompt=list(range(n)),
                    params=SamplingParams(max_new_tokens=1))
            for n in prompts]
    sched.submit(reqs)
    admitted = sched.admit()
    # invariants: batch cap, memory cap, FCFS prefix admission
    assert len(sched.running) <= max_batch
    assert kv.used_blocks <= blocks
    assert admitted == sched.running  # first admission takes a prefix
    assert [r.rid for r in admitted] == [r.rid for r in reqs[:len(admitted)]]
    # finishing everything releases all blocks
    for r in list(sched.running):
        r.state = State.FINISHED
    sched.retire_finished()
    assert kv.used_blocks == 0


def test_request_lifecycle_and_tbt():
    r = Request(prompt=[1, 2, 3], params=SamplingParams(max_new_tokens=3))
    assert not r.done()
    for t in (5, 6, 7):
        r.record_token(t)
    assert r.done() and r.state == State.FINISHED
    assert r.output == [5, 6, 7]
    assert r.total_len == 6
    assert r.first_token_s is not None and r.finish_s is not None
    assert r.tbt_s() >= 0.0


def test_eos_terminates_early():
    r = Request(prompt=[1], params=SamplingParams(max_new_tokens=10,
                                                  eos_token=99))
    r.record_token(5)
    assert not r.done()
    r.record_token(99)
    assert r.done()
    assert len(r.output) == 2
