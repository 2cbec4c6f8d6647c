"""Nemotron-H: one mixer per layer, named per layer by the configuration's
``hybrid_override_pattern`` ("M" Mamba-2, "-" MLP, "*" attention). Every
layer is pre-norm RMSNorm, its mixer, and a residual add; the output head
is untied. Three layer kinds.

The mixers (the source's modelling code for ``model_type`` ``nemotron_h``):

* Mamba-2: ``in_proj`` gives [z (d_inner) | xBC (d_inner + 2·G·N) | dt
  (H)]. A causal depthwise conv (kernel K, with bias) over xBC, then SiLU,
  split into x, B, C, with one N-vector of B and of C per group.
  dt = softplus(dt + dt_bias), A = −exp(A_log) per head; head h of group
  g runs s_t = exp(dt·A)·s_{t−1} + dt·x_t ⊗ B_t[g], y_t = s_t·C_t[g] +
  D·x_t. Then y · silu(z), RMSNorm over each group's d_inner/G channels
  (the gate before the norm), and ``out_proj``.
* attention: grouped-query causal softmax at 1/sqrt(head size), with no
  rotary position (the source applies none).
* MLP: down(relu(up(x))²), no gate.

The harness's contract is listed in ``bench/arch/dense.py``; this module
adds ``ssm_decode`` and ``ssm_chunk``, the operations and bytes of the
state update in a decode step and of the scan over a prefill chunk.

The draws: A_log, dt_bias and D are stored as standard draws and mapped
(``ssm_params``) so that A lies in [−a_max, −a_min] and softplus(dt_bias)
in [dt_min, dt_max], log-spaced, as Mamba-2 initialises them; D = 1 +
stored, drawn at a tenth, like a norm's gain.
"""
from __future__ import annotations

import functools
from typing import Dict, Sequence

import jax
import jax.numpy as jnp

from bench import flops, weights
from bench.reference import HIGHEST, attention, mm, rms_norm

KINDS = ("mamba", "attention", "mlp")
_KIND = {"M": "mamba", "*": "attention", "-": "mlp"}


def kind(sz: Dict, l: int) -> str:
    return _KIND[sz["pattern"][l]]


def _count(sz: Dict, k: str) -> int:
    return sum(kind(sz, l) == k for l in range(sz["num_layers"]))


def _ssm_dims(sz: Dict):
    """(H, P, G, N, d_inner, conv channels) of the Mamba layers held."""
    H, P = sz["ssm_heads"], sz["ssm_head_dim"]
    G, N = sz["ssm_groups"], sz["ssm_state"]
    return H, P, G, N, H * P, H * P + 2 * G * N


def layer_leaves(sz: Dict, kind: str) -> Dict:
    """Layout (d = hidden, F = MLP width, H/K/h = attention heads, KV heads
    and head size; for Mamba H/P/G/N = heads, head size, groups, state,
    E = d_inner + conv channels + H):
    mamba: norm (d,), in_proj (d, E), conv_w (K, conv channels), conv_b,
    a_log (H,), dt_bias (H,), d_skip (H,), gate_norm (d_inner,), out_proj
    (d_inner, d); attention: norm, wq (d, H·h), wk, wv (d, K·h), wo (H·h,
    d); mlp: norm, up (d, F), down (F, d). Matrices are drawn at
    1/sqrt(fan-in), the conv weights at 1/sqrt(K)."""
    d = sz["d_model"]
    norm = ((d,), weights.NORM_STD)

    def mat(fan_in, fan_out):
        return (fan_in, fan_out), float(fan_in) ** -0.5

    if kind == "mamba":
        H, P, G, N, di, cc = _ssm_dims(sz)
        K = sz["ssm_conv"]
        return {"norm": norm, "in_proj": mat(d, di + cc + H),
                "conv_w": ((K, cc), float(K) ** -0.5),
                "conv_b": ((cc,), weights.NORM_STD),
                "a_log": ((H,), 1.0), "dt_bias": ((H,), 1.0),
                "d_skip": ((H,), weights.NORM_STD),
                "gate_norm": ((di,), weights.NORM_STD),
                "out_proj": mat(di, d)}
    if kind == "attention":
        q = sz["num_heads"] * sz["head_dim"]
        kv = sz["num_kv_heads"] * sz["head_dim"]
        return {"norm": norm, "wq": mat(d, q), "wk": mat(d, kv),
                "wv": mat(d, kv), "wo": mat(q, d)}
    F = sz["d_ff"]
    return {"norm": norm, "up": mat(d, F), "down": mat(F, d)}


def ssm_params(w: Dict, sz: Dict):
    """(A_log, dt_bias, D) in float32 from their stored draws."""
    f32 = jnp.float32
    a = jax.nn.sigmoid(w["a_log"].astype(f32))
    A_log = jnp.log(sz["a_min"] + (sz["a_max"] - sz["a_min"]) * a)
    lo, hi = jnp.log(sz["dt_min"]), jnp.log(sz["dt_max"])
    dt = jnp.exp(lo + (hi - lo) * jax.nn.sigmoid(w["dt_bias"].astype(f32)))
    dt_bias = dt + jnp.log(-jnp.expm1(-dt))          # softplus⁻¹(dt)
    return A_log, dt_bias, 1.0 + w["d_skip"].astype(f32)


def program(sz: Dict) -> Dict:
    return dict(
        family="mixed", num_layers=sz["num_layers"],
        layer_pattern=sz["pattern"], d_model=sz["d_model"],
        num_heads=sz["num_heads"], num_kv_heads=sz["num_kv_heads"],
        head_dim=sz["head_dim"], d_ff=sz["d_ff"], vocab_size=sz["vocab"],
        ssm_state=sz["ssm_state"], ssm_head_dim=sz["ssm_head_dim"],
        ssm_heads=sz["ssm_heads"], ssm_groups=sz["ssm_groups"],
        ssm_conv=sz["ssm_conv"], rope_theta=0.0,
        norm_eps=float(sz["norm_eps"]), tie_embeddings=False,
        dtype=jnp.bfloat16)


def program_tree(sz: Dict, w: Dict) -> Dict:
    """The benchmark's layout (``bench/weights.py``) in the program's: one
    stack per layer kind."""
    lw = w["layers"]
    m, a, f = lw["mamba"], lw["attention"], lw["mlp"]
    d, h = sz["d_model"], sz["head_dim"]
    H, K = sz["num_heads"], sz["num_kv_heads"]
    n = a["wq"].shape[0]
    A_log, dt_bias, D = ssm_params(m, sz)
    return {
        "embed": w["embed"], "final_norm": w["final_norm"],
        "lm_head": w["lm_head"],
        "layers": {
            "mamba": {"norm": m["norm"], "mamba": {
                "w_in": m["in_proj"], "conv_w": m["conv_w"],
                "conv_b": m["conv_b"], "a_log": A_log,
                "dt_bias": dt_bias, "d_skip": D,
                "out_norm": m["gate_norm"], "w_out": m["out_proj"]}},
            "attention": {"norm": a["norm"], "attn": {
                "wq": a["wq"].reshape(n, d, H, h),
                "wk": a["wk"].reshape(n, d, K, h),
                "wv": a["wv"].reshape(n, d, K, h),
                "wo": a["wo"].reshape(n, H, h, d)}},
            "mlp": {"norm": f["norm"], "w_up": f["up"], "w_down": f["down"]},
        },
    }


def layer_forward(w: Dict, x: jax.Array, sz, l: int,
                  control: bool = False) -> jax.Array:
    """Layer l over one sequence. x: (S, d) float32."""
    return _FORWARD[kind(dict(sz), l)](w, x, sz, control)


@functools.partial(jax.jit, static_argnames=("sz", "control"))
def _mamba(w: Dict, x: jax.Array, sz, control: bool):
    """The Mamba-2 mixer as a plain scan over time, state in float32."""
    sz = dict(sz)
    H, P, G, N, di, cc = _ssm_dims(sz)
    K, S = sz["ssm_conv"], x.shape[0]
    proj = mm(rms_norm(x, w["norm"], sz["norm_eps"]), w["in_proj"], control)
    z, xbc, dt = proj[:, :di], proj[:, di:di + cc], proj[:, di + cc:]
    past = jnp.concatenate([jnp.zeros((K - 1, cc), xbc.dtype), xbc], 0)
    conv = jax.nn.silu(sum(past[i:i + S] * w["conv_w"][i] for i in range(K))
                       + w["conv_b"])
    xs = conv[:, :di].reshape(S, H, P)
    Bm = conv[:, di:di + G * N].reshape(S, G, N)
    Cm = conv[:, di + G * N:].reshape(S, G, N)
    A_log, dt_bias, D = ssm_params(w, sz)
    dt = jax.nn.softplus(dt + dt_bias)                  # (S, H)
    A = -jnp.exp(A_log)
    group = jnp.arange(H) // (H // G)

    def step(s, inp):
        x_t, dt_t, b_t, c_t = inp
        s = (jnp.exp(dt_t * A)[:, None, None] * s
             + (dt_t[:, None] * x_t)[:, :, None] * b_t[group][:, None, :])
        return s, jnp.einsum("hpn,hn->hp", s, c_t[group],
                             precision=HIGHEST)

    _, y = jax.lax.scan(step, jnp.zeros((H, P, N), jnp.float32),
                        (xs, dt, Bm, Cm))
    y = (y + D[:, None] * xs).reshape(S, di) * jax.nn.silu(z)
    y = rms_norm(y.reshape(S, G, di // G),
                 w["gate_norm"].reshape(G, di // G), sz["norm_eps"])
    return x + mm(y.reshape(S, di), w["out_proj"], control)


@functools.partial(jax.jit, static_argnames=("sz", "control"))
def _attention(w: Dict, x: jax.Array, sz, control: bool):
    sz = dict(sz)
    S = x.shape[0]
    H, K, h = sz["num_heads"], sz["num_kv_heads"], sz["head_dim"]
    a = rms_norm(x, w["norm"], sz["norm_eps"])
    q = mm(a, w["wq"], control).reshape(S, H, h)
    k = mm(a, w["wk"], control).reshape(S, K, h)
    v = mm(a, w["wv"], control).reshape(S, K, h)
    return x + mm(attention(q, k, v, control), w["wo"], control)


@functools.partial(jax.jit, static_argnames=("sz", "control"))
def _mlp(w: Dict, x: jax.Array, sz, control: bool):
    a = rms_norm(x, w["norm"], dict(sz)["norm_eps"])
    up = jax.nn.relu(mm(a, w["up"], control))
    return x + mm(up * up, w["down"], control)


_FORWARD = {"mamba": _mamba, "attention": _attention, "mlp": _mlp}


# ---------------------------------------------------------------------------
# counts
# ---------------------------------------------------------------------------
def layer_params(sz: Dict, k: str) -> int:
    """Weights of one layer of kind k that take part in a matrix product."""
    d = sz["d_model"]
    if k == "mamba":
        H, P, G, N, di, cc = _ssm_dims(sz)
        return d * (di + cc + H) + di * d
    if k == "attention":
        q = sz["num_heads"] * sz["head_dim"]
        kv = sz["num_kv_heads"] * sz["head_dim"]
        return d * (q + 2 * kv) + q * d
    return 2 * d * sz["d_ff"]


def _stack_params(sz: Dict) -> int:
    return sum(layer_params(sz, kind(sz, l)) for l in range(sz["num_layers"]))


def _mamba_token_flops(sz: Dict) -> int:
    """A Mamba layer's work per token outside its projections: the conv
    (2·K per channel) and the recurrence (3 per state element for the
    update, 2 for the output)."""
    H, P, G, N, di, cc = _ssm_dims(sz)
    return 2 * sz["ssm_conv"] * cc + 5 * H * P * N


def decode_attention(sz: Dict, lens: Sequence[int]) -> Dict[str, int]:
    """The paged decode kernel over a batch whose rows hold ``lens``
    stored tokens each, in every attention layer."""
    h, K, H = sz["head_dim"], sz["num_kv_heads"], sz["num_heads"]
    n, live = _count(sz, "attention"), int(sum(lens))
    return {"flops": n * flops.attention_flops(sz, live),
            "bytes": n * (2 * K * h * flops.BF16 * live
                          + 2 * len(lens) * H * h * flops.BF16)}


def chunk_attention(sz: Dict, prefix: int, chunk: int) -> Dict[str, int]:
    """The paged prefill-chunk kernel, in every attention layer."""
    h, K, H = sz["head_dim"], sz["num_kv_heads"], sz["num_heads"]
    n = _count(sz, "attention")
    pairs = chunk * prefix + chunk * (chunk + 1) // 2
    return {"flops": n * flops.attention_flops(sz, pairs),
            "bytes": n * (2 * K * h * flops.BF16 * (prefix + chunk)
                          + 2 * chunk * H * h * flops.BF16)}


def decode_step_flops(sz: Dict, lens: Sequence[int]) -> int:
    """Model operations of one decode step: each row's token through every
    layer (projections, MLPs, the Mamba conv and recurrence, attention to
    its stored tokens and itself) and the output head."""
    rows = len(lens)
    weights_ = 2 * rows * (_stack_params(sz) + sz["d_model"] * sz["vocab"])
    return (weights_ + _count(sz, "attention") * flops.attention_flops(
                sz, int(sum(lens)) + rows)
            + _count(sz, "mamba") * rows * _mamba_token_flops(sz))


def chunk_flops(sz: Dict, prefix: int, chunk: int) -> int:
    """Model operations of one prefill chunk; the output head runs on its
    last position only."""
    pairs = chunk * prefix + chunk * (chunk + 1) // 2
    return (2 * chunk * _stack_params(sz) + 2 * sz["d_model"] * sz["vocab"]
            + _count(sz, "attention") * flops.attention_flops(sz, pairs)
            + _count(sz, "mamba") * chunk * _mamba_token_flops(sz))


def ssm_decode(sz: Dict, lens: Sequence[int]) -> Dict[str, int]:
    """The decode step's state update (``ssm_decode_update``), in every
    Mamba layer: per row, the float32 state read and written once, the
    row's x·dt and y (H·P each), dt (H), B and C (G·N each) in float32;
    5 operations per state element."""
    H, P, G, N, di, cc = _ssm_dims(sz)
    n, rows = _count(sz, "mamba"), len(lens)
    return {"flops": n * rows * 5 * H * P * N,
            "bytes": n * rows * 4 * (2 * H * P * N + 2 * H * P + H
                                     + 2 * G * N)}


def ssm_chunk(sz: Dict, prefix: int, chunk: int) -> Dict[str, int]:
    """The scan over one prefill chunk (``ssm_chunk_scan``), in every Mamba
    layer: the recurrence's 5 operations per state element and token; the
    float32 state read (where a prefix came before) and written once, and
    each token's x·dt, y, dt, B and C in float32."""
    H, P, G, N, di, cc = _ssm_dims(sz)
    n = _count(sz, "mamba")
    state = (2 if prefix else 1) * H * P * N
    return {"flops": n * chunk * 5 * H * P * N,
            "bytes": n * 4 * (state + chunk * (2 * H * P + H + 2 * G * N))}
