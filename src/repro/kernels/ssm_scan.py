"""Mamba-2 (scalar-decay SSD) chunk scan: a Pallas kernel over groups of
B/C, from a given state, returning the final state.

Per head h of group g, with a_t = dt_t·A_h ≤ 0 the log-decay:

    s_t = exp(a_t)·s_{t-1} + (x_t·dt_t) ⊗ B_t[g] ;   y_t = s_t · C_t[g]

The kernel runs the chunked (SSD) form on the MXU. The sequence is cut
into sub-chunks of ``chunk`` tokens; with cum_t the running sum of a
inside a sub-chunk, its outputs are the state carried in,
C_t·s_0·exp(cum_t), plus the sub-chunk's own causal mix,
Σ_{s≤t} (C_t·B_s)·exp(cum_t − cum_s)·x_s dt_s; the state carried out is
exp(cum_T)·s_0 + Σ_s exp(cum_T − cum_s)·(x_s dt_s) ⊗ B_s. Every exponent is
a difference with the later position first, so none is positive.

State layout (``pair_state``): a head's (P, N) state is stored transposed,
as (N, P), and ``r`` heads of one group sit side by side along the lanes,
so the state of H heads is (H/r, N, r·P); at P = 64, r = 2 fills the 128
lanes. The decode-step update (``kernels/ssm_decode_update.py``) reads and
writes the same layout in place, so a request's state slot never changes
layout between prefill and decode.

The grid tiles heads within a group (the fp32 state of 128 heads × 64 ×
256 is 8 MiB, more than scoped VMEM holds) and walks the sub-chunks
innermost, carrying each tile's state in VMEM scratch.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
HIGHEST = jax.lax.Precision.HIGHEST


def heads_per_row(heads_per_group: int, head_dim: int) -> int:
    """``r``: heads of one group laid side by side along the lanes."""
    return math.gcd(heads_per_group, max(1, LANES // head_dim))


def pair_state(s: jax.Array, r: int) -> jax.Array:
    """(…, H, P, N) -> the kernels' layout (…, H/r, N, r·P)."""
    *lead, H, P, N = s.shape
    s = s.reshape(*lead, H // r, r, P, N)
    s = jnp.moveaxis(s, -1, -3)                     # (…, H/r, N, r, P)
    return s.reshape(*lead, H // r, N, r * P)


def unpair_state(s: jax.Array, r: int) -> jax.Array:
    """The kernels' layout (…, H/r, N, r·P) -> (…, H, P, N)."""
    *lead, Hr, N, rP = s.shape
    P = rP // r
    s = s.reshape(*lead, Hr, N, r, P)
    s = jnp.moveaxis(s, -3, -1)                     # (…, H/r, r, P, N)
    return s.reshape(*lead, Hr * r, P, N)


def _tile_pairs(pairs_per_group: int, limit: int = 4) -> int:
    """Head pairs a grid step holds: the largest divisor of a group's pairs
    up to ``limit``."""
    return max(d for d in range(1, min(pairs_per_group, limit) + 1)
               if pairs_per_group % d == 0)


def _chunk_kernel(x_ref, cl_ref, cc_ref, cr_ref, c_ref, bt_ref, s0_ref,
                  y_ref, fin_ref, st_ref, *, tp: int, r: int, P: int,
                  n_sub: int):
    c = pl.program_id(2)

    @pl.when(c == 0)
    def _init():
        st_ref[...] = s0_ref[0]

    T = x_ref.shape[2]
    cm = c_ref[0, 0]                                    # (T, N)
    btm = bt_ref[0, 0]                                  # (N, T)
    cb = jnp.dot(cm, btm, precision=HIGHEST,
                 preferred_element_type=jnp.float32)    # (T, T)
    rows = jax.lax.broadcasted_iota(jnp.int32, (T, T), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (T, T), 1)
    causal = cols <= rows
    lane_head = jax.lax.broadcasted_iota(jnp.int32, (1, r * P), 1) // P
    for j in range(tp):
        s = st_ref[j]                                   # (N, rP)
        x = x_ref[0, j]                                 # (T, rP)
        cl = cl_ref[0, j]                               # (T, rP)
        y = jnp.dot(cm, s, precision=HIGHEST,
                    preferred_element_type=jnp.float32) * jnp.exp(cl)
        for i in range(r):
            h = j * r + i
            seg = jnp.where(causal, cc_ref[0, h] - cr_ref[0, h:h + 1, :],
                            -jnp.inf)
            mix = cb * jnp.exp(seg)                     # (T, T)
            xh = jnp.where(lane_head == i, x, 0.0)
            y = y + jnp.dot(mix, xh, precision=HIGHEST,
                            preferred_element_type=jnp.float32)
        y_ref[0, j] = y
        last = cl[T - 1:T, :]                           # (1, rP)
        s = jnp.exp(last) * s + jnp.dot(
            btm, x * jnp.exp(last - cl), precision=HIGHEST,
            preferred_element_type=jnp.float32)
        st_ref[j] = s

    @pl.when(c == n_sub - 1)
    def _fin():
        fin_ref[0] = st_ref[...]


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssm_chunk_scan(xdt, log_decay, B_in, C_in, state0=None, *,
                   chunk: int = 128, interpret: bool = False):
    """xdt: (B, S, H, P) dt-scaled inputs; log_decay: (B, S, H), a = dt·A
    (≤ 0); B_in, C_in: (B, S, G, N), heads h·G/H .. of group g; state0:
    (B, H/r, N, r·P) fp32 in ``pair_state`` layout, or None for zeros.
    Returns y (B, S, H, P) fp32 (without the D skip) and the final state
    in the same layout as state0."""
    Bb, S, H, P = xdt.shape
    G, N = B_in.shape[-2:]
    Hg = H // G
    r = heads_per_row(Hg, P)
    rP = r * P
    tp = _tile_pairs(Hg // r)
    tpg = Hg // r // tp                     # tiles per group
    n_sub = -(-S // chunk)
    Sp = n_sub * chunk
    pad = Sp - S
    f32 = jnp.float32
    xdt = xdt.astype(f32)
    a = log_decay.astype(f32)
    Bf, Cf = B_in.astype(f32), C_in.astype(f32)
    if pad:   # padded tokens neither decay the state nor add to it
        xdt = jnp.pad(xdt, [(0, 0), (0, pad), (0, 0), (0, 0)])
        a = jnp.pad(a, [(0, 0), (0, pad), (0, 0)])
        Bf = jnp.pad(Bf, [(0, 0), (0, pad), (0, 0), (0, 0)])
        Cf = jnp.pad(Cf, [(0, 0), (0, pad), (0, 0), (0, 0)])
    cum = jnp.cumsum(a.reshape(Bb, n_sub, chunk, H), axis=2
                     ).reshape(Bb, Sp, H)
    # per lane: (B, H/r, Sp, rP), lanes i·P + p of row j are head j·r + i
    lanes = lambda v: jnp.moveaxis(  # noqa: E731
        v.reshape(Bb, Sp, H // r, rP), 1, 2)
    x_l = lanes(xdt.reshape(Bb, Sp, H * P))
    cum_l = lanes(jnp.repeat(cum, P, axis=-1))
    cum_c = jnp.moveaxis(cum, 1, 2)[..., None]          # (B, H, Sp, 1)
    cum_r = jnp.moveaxis(cum, 1, 2)                     # (B, H, Sp)
    c_g = jnp.moveaxis(Cf, 2, 1)                        # (B, G, Sp, N)
    bt_g = jnp.transpose(Bf, (0, 2, 3, 1))              # (B, G, N, Sp)
    if state0 is None:
        state0 = jnp.zeros((Bb, H // r, N, rP), f32)

    kernel = functools.partial(_chunk_kernel, tp=tp, r=r, P=P, n_sub=n_sub)
    pair_spec = pl.BlockSpec((1, tp, chunk, rP), lambda b, t, c: (b, t, c, 0))
    state_spec = pl.BlockSpec((1, tp, N, rP), lambda b, t, c: (b, t, 0, 0))
    y, fin = pl.pallas_call(
        kernel,
        grid=(Bb, H // r // tp, n_sub),
        in_specs=[
            pair_spec, pair_spec,
            pl.BlockSpec((1, tp * r, chunk, 1), lambda b, t, c: (b, t, c, 0)),
            pl.BlockSpec((1, tp * r, chunk), lambda b, t, c: (b, t, c)),
            pl.BlockSpec((1, 1, chunk, N),
                         lambda b, t, c: (b, t // tpg, c, 0)),
            pl.BlockSpec((1, 1, N, chunk),
                         lambda b, t, c: (b, t // tpg, 0, c)),
            state_spec,
        ],
        out_specs=(pair_spec, state_spec),
        out_shape=(jax.ShapeDtypeStruct((Bb, H // r, Sp, rP), f32),
                   jax.ShapeDtypeStruct((Bb, H // r, N, rP), f32)),
        scratch_shapes=[pltpu.VMEM((tp, N, rP), f32)],
        # the instruction name the device trace shows (and the benchmark's
        # scan roofline matches): keep it stable
        name="ssm_chunk_scan",
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(x_l, cum_l, cum_c, cum_r, c_g, bt_g, state0.astype(f32))
    y = jnp.moveaxis(y, 1, 2).reshape(Bb, Sp, H, P)[:, :S]
    return y, fin


def ssm_scan(x, B_in, C_in, decay, *, chunk: int = 128,
             interpret: bool = False) -> jax.Array:
    """The single-group form from a zero state: x (B, S, H, P) dt-scaled;
    B_in/C_in (B, S, N); decay (B, S, H) in (0, 1]. Returns y (B, S, H, P)
    fp32."""
    y, _ = ssm_chunk_scan(x, jnp.log(decay.astype(jnp.float32)),
                          B_in[:, :, None], C_in[:, :, None], chunk=chunk,
                          interpret=interpret)
    return y
