"""Token sampling: greedy / temperature / top-k — batch-uniform and
per-request variants (a continuous batch mixes every request's own
SamplingParams in one decode iteration)."""
from __future__ import annotations

import functools
from typing import Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np


def sample(logits: jax.Array, key, temperature: float = 0.0,
           top_k: int = 0) -> jax.Array:
    """logits: (B, V) -> (B,) int32. One set of params for the whole batch
    (prefill / single-request paths)."""
    if temperature <= 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    logits = logits.astype(jnp.float32) / temperature
    if top_k > 0:
        vals, _ = jax.lax.top_k(logits, top_k)
        cutoff = vals[..., -1:]
        logits = jnp.where(logits < cutoff, -jnp.inf, logits)
    return jax.random.categorical(key, logits, axis=-1).astype(jnp.int32)


def request_key(seed: int, token_index: int) -> jax.Array:
    """Per-request PRNG stream honoring ``SamplingParams.seed``: token `i`
    of a request seeded `s` is always drawn from fold_in(PRNGKey(s), i) —
    independent of batch composition, admission order, or preemption, so
    identical requests reproduce identically wherever they run."""
    return jax.random.fold_in(jax.random.PRNGKey(seed), token_index)


@functools.lru_cache(maxsize=4096)
def _seed_key(seed: int) -> np.ndarray:
    return np.asarray(jax.random.PRNGKey(seed))


def _scale_and_mask(logits: jax.Array, temperatures, top_ks):
    """Shared per-row temperature scaling + top-k cutoff for the batch
    samplers. Returns (greedy, scaled, temps): greedy argmax per row, the
    scaled logits with sub-cutoff entries at -inf (a sort-based cutoff,
    since ``lax.top_k`` needs a static k and k varies per row), and the
    float temps. The two samplers differ ONLY in how they draw from
    `scaled` — keep any cutoff/tie semantics change here so they can't
    diverge."""
    logits = logits.astype(jnp.float32)
    V = logits.shape[-1]
    temps = jnp.asarray(temperatures, jnp.float32)
    ks = jnp.asarray(top_ks, jnp.int32)
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    scaled = logits / jnp.maximum(temps, 1e-6)[:, None]
    srt = jnp.sort(scaled, axis=-1)[:, ::-1]            # descending
    kidx = jnp.where(ks > 0, jnp.minimum(ks, V) - 1, V - 1)
    cutoff = jnp.take_along_axis(srt, kidx[:, None], axis=-1)
    scaled = jnp.where(scaled < cutoff, -jnp.inf, scaled)
    return greedy, scaled, temps


def sample_per_request(logits: jax.Array, keys: jax.Array,
                       temperatures, top_ks) -> jax.Array:
    """Per-request sampling with per-request PRNG streams.

    logits: (B, V); keys: (B, 2) uint32 — one :func:`request_key` per row;
    temperatures: (B,) float (<= 0 → greedy for that row); top_ks: (B,) int
    (0 → full softmax). Same cutoff semantics as :func:`sample_batch`, but
    each row draws from its own key, so a request's stochastic stream is a
    pure function of (its seed, its token index). Returns (B,) int32."""
    greedy, scaled, temps = _scale_and_mask(logits, temperatures, top_ks)
    drawn = jax.vmap(lambda k, row: jax.random.categorical(k, row))(
        keys, scaled).astype(jnp.int32)
    return jnp.where(temps <= 0.0, greedy, drawn)


def sample_batch(logits: jax.Array, key, temperatures: jax.Array,
                 top_ks: jax.Array) -> jax.Array:
    """Per-request sampling for a continuous batch, one shared batch key.

    logits: (B, V); temperatures: (B,) float (<= 0 → greedy for that row);
    top_ks: (B,) int (0 → full softmax). Rows are independent: each gets its
    own temperature scaling and top-k cutoff. Greedy rows are argmax
    regardless of the drawn sample. Returns (B,) int32."""
    greedy, scaled, temps = _scale_and_mask(logits, temperatures, top_ks)
    drawn = jax.random.categorical(key, scaled, axis=-1).astype(jnp.int32)
    return jnp.where(temps <= 0.0, greedy, drawn)


@jax.jit
def _sample_rows(logits, seed_keys, token_indices, temperatures, top_ks):
    keys = jax.vmap(jax.random.fold_in)(seed_keys, token_indices)
    toks = jax.lax.cond(
        jnp.all(temperatures <= 0.0),
        lambda: jnp.argmax(logits.astype(jnp.float32),
                           axis=-1).astype(jnp.int32),
        lambda: sample_per_request(logits, keys, temperatures, top_ks))
    return toks, jnp.isfinite(logits).all(axis=-1)


def sample_requests(logits: jax.Array, seeds: Sequence[int],
                    token_indices: Sequence[int], temperatures,
                    top_ks) -> Tuple[np.ndarray, np.ndarray]:
    """:func:`sample_per_request` with row i's key ``request_key(seeds[i],
    token_indices[i])``, as one jitted program and one transfer to the
    host. A batch of greedy rows skips the top-k sort. Returns the (B,)
    int32 tokens and, per row, whether all its logits are finite."""
    seed_keys = np.stack([_seed_key(int(s)) for s in seeds])
    toks, finite = _sample_rows(
        logits, seed_keys, np.asarray(token_indices, np.uint32),
        np.asarray(temperatures, np.float32), np.asarray(top_ks, np.int32))
    return jax.device_get((toks, finite))
