"""Random weights from a seed, in the benchmark's own layout.

Each weight is drawn from its own key, ``fold_in(fold_in(seed, leaf),
layer)``, where ``leaf`` is the weight's index in ``leaf_names``, so one
layer can be drawn again alone and comes out bit for bit as it was in the
whole model. The served model gets all of them from one jitted call
(``stacked``); the reference draws them again layer by layer (``layer``)
after the served model is gone, and takes nothing from it.

Layout (d = hidden, V = vocabulary): embed (V, d), final_norm (d,),
lm_head (d, V); each layer's weights as its architecture's
``layer_leaves`` names, shapes and scales them for the layer's kind
(``bench/arch/<arch>.py``).

A norm is stored as the departure of its gain from 1 (gain = 1 + stored),
drawn at a tenth, so a norm that drops its gain shows. The embedding is
drawn at 1, the output head at 1/sqrt(d).
"""
from __future__ import annotations

from typing import Dict, List

import jax
import jax.numpy as jnp

GLOBAL = ("embed", "final_norm", "lm_head")
NORM_STD = 0.1


def _global_leaves(sz: Dict) -> Dict:
    d, V = sz["d_model"], sz["vocab"]
    return {"embed": ((V, d), 1.0), "final_norm": ((d,), NORM_STD),
            "lm_head": ((d, V), float(d) ** -0.5)}


def leaf_names(arch, sz: Dict) -> tuple:
    """Every weight's name in leaf order: the globals, then each kind's
    layer weights in ``arch.KINDS`` order, each name once."""
    names = dict.fromkeys(GLOBAL)
    for k in arch.KINDS:
        names.update(dict.fromkeys(arch.layer_leaves(sz, k)))
    return tuple(names)


def _layers_by_kind(arch, sz: Dict) -> Dict[str, List[int]]:
    """The layers of each kind, in order."""
    out = {k: [] for k in arch.KINDS}
    for l in range(sz["num_layers"]):
        out[arch.kind(sz, l)].append(l)
    return out


def seed_key(seed: int) -> jax.Array:
    """A key from a seed of any size (up to 64 bits)."""
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def _draw(key, shape, std: float, dtype) -> jax.Array:
    x = jax.random.normal(key, shape, jnp.float32) * std
    return x.astype(dtype)


def layer(arch, sz: Dict, key: jax.Array, l, dtype=jnp.bfloat16,
          kind: str = None) -> Dict:
    """Layer ``l``'s weights, as ``stacked`` holds them among its kind's.
    ``kind`` is l's kind, which has to be given where l is traced."""
    leaves = arch.layer_leaves(sz, kind or arch.kind(sz, l))
    index = leaf_names(arch, sz)
    return {n: _draw(jax.random.fold_in(
                jax.random.fold_in(key, index.index(n)), l), shape, std,
                dtype)
            for n, (shape, std) in leaves.items()}


def global_weight(sz: Dict, key: jax.Array, name: str,
                  dtype=jnp.bfloat16) -> jax.Array:
    shape, std = _global_leaves(sz)[name]
    return _draw(jax.random.fold_in(key, GLOBAL.index(name)), shape, std,
                 dtype)


def stacked(arch, sz: Dict, key: jax.Array, dtype=jnp.bfloat16) -> Dict:
    """Every weight: the globals, and under ``"layers"`` each kind's
    weights stacked on a leading axis over that kind's layers, in order.
    Call inside ``jax.jit`` to make them on the device in one program."""
    out = {n: global_weight(sz, key, n, dtype) for n in GLOBAL}
    out["layers"] = {
        k: jax.vmap(lambda l, k=k: layer(arch, sz, key, l, dtype, k))(
            jnp.asarray(ls, jnp.int32))
        for k, ls in _layers_by_kind(arch, sz).items() if ls}
    return out
