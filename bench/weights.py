"""Random weights from a seed, in the benchmark's own layout.

Each weight is drawn from its own key, ``fold_in(fold_in(seed, leaf),
layer)``, so one layer can be drawn again alone and comes out bit for bit
as it was in the whole model. The served model gets all of them from one
jitted call (``stacked``); the reference draws them again layer by layer
(``layer``) after the served model is gone, and takes nothing from it.

Layout (d = hidden, H = query heads, K = key/value heads, h = head size,
F = feed-forward width, V = vocabulary):

    embed (V, d)  final_norm (d,)  lm_head (d, V)
    per layer: norm1 (d,), wq (d, H*h), wk (d, K*h), wv (d, K*h),
               wo (H*h, d), norm2 (d,), w_gate (d, F), w_up (d, F),
               w_down (F, d)

A norm is stored as the departure of its gain from 1 (gain = 1 + stored),
drawn at a tenth, so a norm that drops its gain shows. Matrices are drawn
at 1/sqrt(fan-in), the embedding at 1.
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

GLOBAL = ("embed", "final_norm", "lm_head")
LAYER = ("norm1", "wq", "wk", "wv", "wo", "norm2", "w_gate", "w_up",
         "w_down")
NORM_STD = 0.1


def _shapes(sz: Dict) -> Dict:
    d, h, F, V = sz["d_model"], sz["head_dim"], sz["d_ff"], sz["vocab"]
    q, kv = sz["num_heads"] * h, sz["num_kv_heads"] * h
    return {"embed": (V, d), "final_norm": (d,), "lm_head": (d, V),
            "norm1": (d,), "wq": (d, q), "wk": (d, kv), "wv": (d, kv),
            "wo": (q, d), "norm2": (d,), "w_gate": (d, F), "w_up": (d, F),
            "w_down": (F, d)}


def _std(name: str, shape) -> float:
    if name.startswith("norm") or name == "final_norm":
        return NORM_STD
    if name == "embed":
        return 1.0
    return float(shape[0]) ** -0.5


def seed_key(seed: int) -> jax.Array:
    """A key from a seed of any size (up to 64 bits)."""
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def _draw(key, name: str, shape, dtype) -> jax.Array:
    x = jax.random.normal(key, shape, jnp.float32) * _std(name, shape)
    return x.astype(dtype)


def _leaf_key(key, name: str):
    return jax.random.fold_in(key, (GLOBAL + LAYER).index(name))


def layer(sz: Dict, key: jax.Array, l: int, dtype=jnp.bfloat16) -> Dict:
    """Layer ``l``'s weights, as ``stacked`` holds them at index l."""
    shapes = _shapes(sz)
    return {n: _draw(jax.random.fold_in(_leaf_key(key, n), l), n, shapes[n],
                     dtype) for n in LAYER}


def global_weight(sz: Dict, key: jax.Array, name: str,
                  dtype=jnp.bfloat16) -> jax.Array:
    return _draw(_leaf_key(key, name), name, _shapes(sz)[name], dtype)


def stacked(sz: Dict, key: jax.Array, dtype=jnp.bfloat16) -> Dict:
    """Every weight: the globals, and each per-layer weight stacked on a
    leading layer axis. Call inside ``jax.jit`` to make them on the device
    in one program."""
    out = {n: global_weight(sz, key, n, dtype) for n in GLOBAL}
    out.update(jax.vmap(lambda l: layer(sz, key, l, dtype))(
        jnp.arange(sz["num_layers"])))
    return out
