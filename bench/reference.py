"""Plain reference of the served model, in float32, and its control.

It imports nothing of the program under test. It draws its weights again
from the seed (``bench/weights.py``), one layer at a time, and runs every
sequence through that layer, by the architecture's own ``layer_forward``
(``bench/arch/<arch>.py``), before it draws the next, so that the whole
model never sits on the device in float32. The head (final RMSNorm and an
untied output matrix) and the pieces a layer is built from (``mm``,
``rms_norm``, ``rope``, ``attention``) are here.

Every matrix product runs at ``Precision.HIGHEST``: true float32 on the
TPU, where the default would take one bfloat16 pass. The control runs the
same arithmetic on operands rounded to float8 (e4m3, one scale per tensor
from its largest magnitude): the step below bfloat16, the precision that
the configurations state.

``compare`` answers the served-model check: at every position whose next
token was served, by how much the served token's logit lies below the
reference's best.
"""
from __future__ import annotations

import functools
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights

HIGHEST = jax.lax.Precision.HIGHEST
# sequences are padded up to one of these lengths, so that few programs
# serve every run; a longer sequence pads to a multiple of the last
LENGTH_BUCKETS = (1024, 2048, 4096, 8192)
QUERY_BLOCK = 512
ROW_BLOCK = 256


def _bucket(n: int) -> int:
    for b in LENGTH_BUCKETS:
        if n <= b:
            return b
    top = LENGTH_BUCKETS[-1]
    return -(-n // top) * top


def _fp8(x: jax.Array) -> jax.Array:
    """Round to float8 e4m3 with one scale for the tensor, and back."""
    x = x.astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _cast(x: jax.Array, control: bool) -> jax.Array:
    return _fp8(x) if control else x.astype(jnp.float32)


def mm(a, b, control: bool):
    """a @ b in float32 at HIGHEST; with ``control``, on float8 operands."""
    return jnp.matmul(_cast(a, control), _cast(b, control),
                      precision=HIGHEST)


def rms_norm(x, stored_gain, eps: float):
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * (1.0 + stored_gain)


def rope(x, theta: float):
    """x: (S, heads, h); positions 0..S-1."""
    S, _, h = x.shape
    freqs = theta ** (-jnp.arange(0, h, 2, dtype=jnp.float32) / h)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * freqs      # (S, h/2)
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., : h // 2], x[..., h // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(q, k, v, control: bool, window: int = 0):
    """Causal softmax attention, one block of queries at a time; with a
    ``window``, each query sees itself and the ``window`` - 1 keys before
    it. q: (S, H, h); k, v: (S, K, h). Returns (S, H*h)."""
    S, H, h = q.shape
    K = k.shape[1]
    q = q.reshape(S, K, H // K, h) / np.sqrt(h)
    k, v = _cast(k, control), _cast(v, control)
    out = []
    for s0 in range(0, S, QUERY_BLOCK):
        qb = _cast(q[s0:s0 + QUERY_BLOCK], control)
        n = qb.shape[0]
        s = jnp.einsum("qkgh,tkh->qkgt", qb, k, precision=HIGHEST)
        pos = (s0 + jnp.arange(n))[:, None]
        causal = jnp.arange(S)[None, :] <= pos
        if window:
            causal &= jnp.arange(S)[None, :] > pos - window
        causal = causal[:, None, None, :]
        p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        o = jnp.einsum("qkgt,tkh->qkgh", _cast(p, control), v,
                       precision=HIGHEST)
        out.append(o.reshape(n, H * h))
    return jnp.concatenate(out, 0)


@functools.partial(jax.jit, static_argnames=("sz", "arch", "kind"))
def _layer_weights(key, l, sz, arch, kind):
    return jax.tree.map(lambda a: a.astype(jnp.float32),
                        weights.layer(arch, dict(sz), key, l, kind=kind))


@functools.partial(jax.jit, static_argnames=("sz", "name"))
def _global(key, sz, name):
    return weights.global_weight(dict(sz), key, name).astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("eps", "control"))
def _head_rows(x, final_norm, lm_head, eps: float, control: bool):
    """Logits of some rows: (n, d) -> (n, V) float32."""
    return mm(rms_norm(x, final_norm, eps), lm_head, control)


def _frozen(sz: Dict) -> Tuple:
    return tuple(sorted(sz.items()))


def hidden_states(arch, sz: Dict, key, seqs: Sequence[np.ndarray],
                  control: bool = False) -> List[jax.Array]:
    """Final hidden states (before the last norm) of each token sequence,
    each padded at its end to a bucket length (padding follows every real
    token, so the causal mask keeps it out of their results)."""
    fz = _frozen(sz)
    embed = _global(key, fz, "embed")
    xs = []
    for toks in seqs:
        pad = np.zeros(_bucket(len(toks)), np.int32)
        pad[:len(toks)] = toks
        xs.append(embed[jnp.asarray(pad)])
    del embed
    for l in range(sz["num_layers"]):
        w = _layer_weights(key, l, fz, arch, arch.kind(sz, l))
        xs = [arch.layer_forward(w, x, fz, l, control) for x in xs]
        del w
    return xs


def head_rows(sz: Dict, key, xs: Sequence[jax.Array],
              rows: Sequence[np.ndarray], control: bool = False
              ) -> List[jax.Array]:
    """Logits at the given positions of each sequence, a block of rows at
    a time; returns (n, V) float32 arrays on the device."""
    fz = _frozen(sz)
    final_norm = _global(key, fz, "final_norm")
    lm_head = _global(key, fz, "lm_head")
    out = []
    for x, r in zip(xs, rows):
        parts = [_head_rows(x[jnp.asarray(r[i:i + ROW_BLOCK])], final_norm,
                            lm_head, sz["norm_eps"], control)
                 for i in range(0, len(r), ROW_BLOCK)]
        out.append(jnp.concatenate(parts, 0))
    return out


@jax.jit
def _gaps(ref, chosen):
    """How far each chosen token's reference logit lies below the row's
    best: (n, V), (n,) -> (n,)."""
    at = jnp.take_along_axis(ref, chosen[:, None], axis=1)[:, 0]
    return jnp.max(ref, axis=1) - at


def served_rows(prompt: np.ndarray, served: Sequence[int]
                ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """The sequence the reference reads for one request (its prompt and
    every served token but the last), the positions whose logits chose a
    served token, and those tokens."""
    toks = np.concatenate([np.asarray(prompt, np.int32),
                           np.asarray(served[:-1], np.int32)])
    rows = np.arange(len(prompt) - 1, len(prompt) - 1 + len(served))
    return toks, rows, np.asarray(served, np.int32)


def compare(arch, sz: Dict, seed: int, requests: Sequence[Tuple[np.ndarray,
                                                          Sequence[int]]],
            control: bool = False) -> Dict:
    """The widest gap by which a served token's logit lies below the
    reference's best, over every served token of ``requests`` (pairs of
    prompt and served tokens). With ``control``, also the widest gap of the
    tokens that the float8 control puts first at the same positions."""
    key = weights.seed_key(seed)
    parts = [served_rows(p, s) for p, s in requests]
    seqs = [t for t, _, _ in parts]
    rows = [r for _, r, _ in parts]
    out = {"tokens": int(sum(len(r) for r in rows))}
    if control:
        xs = hidden_states(arch, sz, key, seqs, control=True)
        picks = [jnp.argmax(lg, axis=1).astype(jnp.int32)
                 for lg in head_rows(sz, key, xs, rows, control=True)]
        del xs
    xs = hidden_states(arch, sz, key, seqs)
    ref = head_rows(sz, key, xs, rows)
    del xs
    out["max_gap"] = max(float(jnp.max(_gaps(lg, jnp.asarray(t))))
                         for lg, (_, _, t) in zip(ref, parts))
    if control:
        out["control_max_gap"] = max(float(jnp.max(_gaps(lg, c)))
                                     for lg, c in zip(ref, picks))
    return out
