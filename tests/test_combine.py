"""Property tests for the §4.2.2 partial-softmax combine identity — the
mathematical core of attention offloading, the flash-decode kernel, and the
sequence-parallel sharding."""
import jax.numpy as jnp
import numpy as np
from hypothesis import given, settings, strategies as st

from repro.core import combine as C


def _softmax_attention(q, k, v):
    s = (q @ k.T) / np.sqrt(q.shape[-1])
    p = np.exp(s - s.max(-1, keepdims=True))
    p = p / p.sum(-1, keepdims=True)
    return p @ v


@settings(deadline=None, max_examples=40)
@given(
    n=st.integers(2, 24),
    hd=st.sampled_from([4, 16]),
    cut=st.data(),
    seed=st.integers(0, 2**16),
)
def test_two_way_split_matches_full(n, hd, cut, seed):
    rng = np.random.default_rng(seed)
    q = rng.standard_normal((hd,)).astype(np.float32)
    k = rng.standard_normal((n, hd)).astype(np.float32)
    v = rng.standard_normal((n, hd)).astype(np.float32)
    i = cut.draw(st.integers(1, n - 1))
    p1 = C.partial_attention(jnp.asarray(q), jnp.asarray(k[:i]),
                             jnp.asarray(v[:i]))
    p2 = C.partial_attention(jnp.asarray(q), jnp.asarray(k[i:]),
                             jnp.asarray(v[i:]))
    got = np.asarray(C.finalize(C.combine(p1, p2)))
    want = _softmax_attention(q[None], k, v)[0]
    np.testing.assert_allclose(got, want, atol=1e-4, rtol=1e-4)


@settings(deadline=None, max_examples=25)
@given(
    n=st.integers(3, 30),
    parts=st.integers(2, 5),
    seed=st.integers(0, 2**16),
    permute=st.booleans(),
)
def test_many_way_split_associative_commutative(n, parts, seed, permute):
    """combine() over any disjoint partition, in any merge order."""
    rng = np.random.default_rng(seed)
    hd = 8
    q = rng.standard_normal((hd,)).astype(np.float32)
    k = rng.standard_normal((n, hd)).astype(np.float32)
    v = rng.standard_normal((n, hd)).astype(np.float32)
    cuts = sorted(rng.choice(np.arange(1, n), size=min(parts - 1, n - 1),
                             replace=False))
    segments = np.split(np.arange(n), cuts)
    partials = [C.partial_attention(jnp.asarray(q), jnp.asarray(k[idx]),
                                    jnp.asarray(v[idx]))
                for idx in segments if len(idx)]
    if permute:
        order = rng.permutation(len(partials))
        partials = [partials[i] for i in order]
    got = np.asarray(C.finalize(C.combine_many(partials)))
    want = _softmax_attention(q[None], k, v)[0]
    np.testing.assert_allclose(got, want, atol=1e-4, rtol=1e-4)


def test_empty_subset_is_identity():
    rng = np.random.default_rng(0)
    hd, n = 8, 6
    q = jnp.asarray(rng.standard_normal((hd,)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((n, hd)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((n, hd)), jnp.float32)
    full = C.partial_attention(q, k, v)
    empty = C.partial_attention(q, k, v, mask=jnp.zeros((n,), bool))
    merged = C.combine(full, empty)
    np.testing.assert_allclose(np.asarray(C.finalize(merged)),
                               np.asarray(C.finalize(full)), atol=1e-6)


@settings(deadline=None, max_examples=20)
@given(seed=st.integers(0, 2**16), extreme=st.sampled_from([40.0, 80.0]))
def test_numerical_stability_large_logits(seed, extreme):
    """Partials with wildly different maxima must still merge stably."""
    rng = np.random.default_rng(seed)
    hd = 8
    q = rng.standard_normal((hd,)).astype(np.float32) * extreme
    k = rng.standard_normal((10, hd)).astype(np.float32)
    v = rng.standard_normal((10, hd)).astype(np.float32)
    p1 = C.partial_attention(jnp.asarray(q), jnp.asarray(k[:5]),
                             jnp.asarray(v[:5]))
    p2 = C.partial_attention(jnp.asarray(q), jnp.asarray(k[5:]),
                             jnp.asarray(v[5:]))
    got = np.asarray(C.finalize(C.combine(p1, p2)))
    assert np.all(np.isfinite(got))
    want = _softmax_attention(q[None].astype(np.float64),
                              k.astype(np.float64), v.astype(np.float64))[0]
    np.testing.assert_allclose(got, want, atol=1e-3, rtol=1e-3)
