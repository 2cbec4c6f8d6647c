"""The dense decoder block of Llama and Mistral: pre-norm RMSNorm,
grouped-query attention with rotary position (the rotate-half form), a
SwiGLU feed-forward block; an untied output head. One layer kind.

What the harness asks of an architecture (``bench/arch/<arch>.py``, named
by a configuration file's ``"arch"``):

    KINDS                         the layer kinds, in leaf order
    kind(sz, l)                   layer l's kind
    layer_leaves(sz, kind)        {name: (shape, draw scale)} of a kind's
                                  weights, in leaf order
    program(sz)                   keyword arguments of the program's
                                  ModelConfig, but for ``name``
    program_tree(sz, w)           ``weights.stacked`` in the program's layout
    layer_forward(w, x, sz, l, control)
                                  the float32 reference of layer l
    decode_attention(sz, lens)    operations and bytes of the decode
    chunk_attention(sz, prefix, chunk)
                                  and prefill-chunk kernels, all layers
    decode_step_flops(sz, lens)   model operations of a decode step
    chunk_flops(sz, prefix, chunk)
                                  and of a prefill chunk

``sz`` is the configuration's sizes (``spec.model_sizes``); where a
function is jitted on it, the sorted (name, value) pairs of that dict.
"""
from __future__ import annotations

import functools
from typing import Dict, Sequence

import jax
import jax.numpy as jnp

from bench import flops, weights
from bench.reference import attention, mm, rms_norm, rope

KINDS = ("dense",)


def kind(sz: Dict, l: int) -> str:
    return "dense"


def layer_leaves(sz: Dict, kind: str) -> Dict:
    """Layout (d = hidden, H = query heads, K = key/value heads, h = head
    size, F = feed-forward width): norm1 (d,), wq (d, H*h), wk (d, K*h),
    wv (d, K*h), wo (H*h, d), norm2 (d,), w_gate (d, F), w_up (d, F),
    w_down (F, d). Matrices are drawn at 1/sqrt(fan-in)."""
    d, h, F = sz["d_model"], sz["head_dim"], sz["d_ff"]
    q, kv = sz["num_heads"] * h, sz["num_kv_heads"] * h
    norm = ((d,), weights.NORM_STD)

    def mat(fan_in, fan_out):
        return (fan_in, fan_out), float(fan_in) ** -0.5

    return {"norm1": norm, "wq": mat(d, q), "wk": mat(d, kv),
            "wv": mat(d, kv), "wo": mat(q, d), "norm2": norm,
            "w_gate": mat(d, F), "w_up": mat(d, F), "w_down": mat(F, d)}


def program(sz: Dict) -> Dict:
    return dict(
        family="dense", num_layers=sz["num_layers"],
        d_model=sz["d_model"], num_heads=sz["num_heads"],
        num_kv_heads=sz["num_kv_heads"], head_dim=sz["head_dim"],
        d_ff=sz["d_ff"], vocab_size=sz["vocab"],
        rope_theta=float(sz["rope_theta"]), norm_eps=float(sz["norm_eps"]),
        tie_embeddings=False, dtype=jnp.bfloat16)


def program_tree(sz: Dict, w: Dict) -> Dict:
    """The benchmark's layout (``bench/weights.py``) in the program's."""
    L, d, h = sz["num_layers"], sz["d_model"], sz["head_dim"]
    H, K = sz["num_heads"], sz["num_kv_heads"]
    lw = w["layers"]["dense"]
    return {
        "embed": w["embed"], "final_norm": w["final_norm"],
        "lm_head": w["lm_head"],
        "layers": {
            "norm1": lw["norm1"], "norm2": lw["norm2"],
            "attn": {"wq": lw["wq"].reshape(L, d, H, h),
                     "wk": lw["wk"].reshape(L, d, K, h),
                     "wv": lw["wv"].reshape(L, d, K, h),
                     "wo": lw["wo"].reshape(L, H, h, d)},
            "ffn": {"w_gate": lw["w_gate"], "w_up": lw["w_up"],
                    "w_down": lw["w_down"]},
        },
    }


def layer_forward(w: Dict, x: jax.Array, sz, l: int,
                  control: bool = False) -> jax.Array:
    """Layer l over one sequence. x: (S, d) float32. Every layer runs the
    same program."""
    return _forward(w, x, sz, control)


@functools.partial(jax.jit, static_argnames=("sz", "control"))
def _forward(w: Dict, x: jax.Array, sz, control: bool):
    sz = dict(sz)
    S = x.shape[0]
    H, K, h = sz["num_heads"], sz["num_kv_heads"], sz["head_dim"]
    eps, theta = sz["norm_eps"], sz["rope_theta"]
    a = rms_norm(x, w["norm1"], eps)
    q = rope(mm(a, w["wq"], control).reshape(S, H, h), theta)
    k = rope(mm(a, w["wk"], control).reshape(S, K, h), theta)
    v = mm(a, w["wv"], control).reshape(S, K, h)
    x = x + mm(attention(q, k, v, control), w["wo"], control)
    a = rms_norm(x, w["norm2"], eps)
    gate = jax.nn.silu(mm(a, w["w_gate"], control))
    return x + mm(gate * mm(a, w["w_up"], control), w["w_down"], control)


def layer_params(sz: Dict) -> int:
    """Weights of one decoder layer that take part in a matrix product."""
    d, h, F = sz["d_model"], sz["head_dim"], sz["d_ff"]
    q, kv = sz["num_heads"] * h, sz["num_kv_heads"] * h
    return d * (q + 2 * kv) + q * d + 3 * d * F


def decode_attention(sz: Dict, lens: Sequence[int]) -> Dict[str, int]:
    """The paged decode kernel over a batch whose rows hold ``lens``
    stored tokens each (the new token is merged outside it), in every
    layer."""
    h, K, H = sz["head_dim"], sz["num_kv_heads"], sz["num_heads"]
    L, live = sz["num_layers"], int(sum(lens))
    return {"flops": L * flops.attention_flops(sz, live),
            "bytes": L * (2 * K * h * flops.BF16 * live
                          + 2 * len(lens) * H * h * flops.BF16)}


def chunk_attention(sz: Dict, prefix: int, chunk: int) -> Dict[str, int]:
    """The paged prefill-chunk kernel, in every layer: ``chunk`` queries
    over ``prefix`` pooled tokens and the chunk itself, causal inside it."""
    h, K, H = sz["head_dim"], sz["num_kv_heads"], sz["num_heads"]
    L = sz["num_layers"]
    pairs = chunk * prefix + chunk * (chunk + 1) // 2
    return {"flops": L * flops.attention_flops(sz, pairs),
            "bytes": L * (2 * K * h * flops.BF16 * (prefix + chunk)
                          + 2 * chunk * H * h * flops.BF16)}


def decode_step_flops(sz: Dict, lens: Sequence[int]) -> int:
    """Model operations of one decode step: each row's token through every
    layer and the output head, attending to its stored tokens and itself."""
    L, d, V = sz["num_layers"], sz["d_model"], sz["vocab"]
    rows = len(lens)
    dense = 2 * rows * (L * layer_params(sz) + d * V)
    return dense + L * flops.attention_flops(sz, int(sum(lens)) + rows)


def chunk_flops(sz: Dict, prefix: int, chunk: int) -> int:
    """Model operations of one prefill chunk; the output head runs on its
    last position only."""
    L, d, V = sz["num_layers"], sz["d_model"], sz["vocab"]
    pairs = chunk * prefix + chunk * (chunk + 1) // 2
    return (2 * chunk * L * layer_params(sz) + 2 * d * V
            + L * flops.attention_flops(sz, pairs))
