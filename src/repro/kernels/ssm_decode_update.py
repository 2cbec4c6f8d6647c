"""Mamba-2 decode step: each row's recurrent state updated in place in its
state slot.

    s ← exp(dt·A)·s + (x·dt) ⊗ B[g] ;   y = s · C[g]

``state`` holds every Mamba layer's slots, (L, slots, H/r, N, r·P) fp32
in the chunk scan's layout (``kernels/ssm_scan.py``). One call updates
layer ``layer`` for a batch whose row b owns slot ``slots[b]``: the grid
walks (row, head tile), each step reads that row's state tile through the
slot index, and the result is written back to the same place through
``input_output_aliases``. Under a jit whose state argument is donated,
nothing but the visited tiles moves: the kernel is bound by reading and
writing the fp32 state once.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.ssm_scan import _tile_pairs, heads_per_row


def _update_kernel(slots_ref, x_ref, d_ref, b_ref, c_ref, s_ref,
                   y_ref, out_ref, *, tp: int):
    del slots_ref
    N, rP = s_ref.shape[-2:]
    # B and C as columns, broadcast over the lanes: (N, rP)
    bcol = jnp.broadcast_to(b_ref[0, 0], (rP, N)).T
    ccol = jnp.broadcast_to(c_ref[0, 0], (rP, N)).T
    for j in range(tp):
        s = d_ref[0, j:j + 1, :] * s_ref[0, 0, j] + x_ref[0, j:j + 1, :] * bcol
        out_ref[0, 0, j] = s
        y_ref[0, j:j + 1, :] = jnp.sum(s * ccol, axis=0, keepdims=True)


@functools.partial(jax.jit, static_argnames=("layer", "interpret"))
def ssm_decode_update(state, slots, xdt, decay, B_in, C_in, *, layer: int,
                      interpret: bool = False):
    """state: (L, slots, H/r, N, r·P) fp32; slots: (B,) int32, one slot per
    row, no two alike; xdt: (B, H, P) dt-scaled inputs; decay: (B, H) =
    exp(dt·A); B_in, C_in: (B, G, N). Returns y (B, H, P) fp32 (without the
    D skip) and the state with layer ``layer``'s rows updated."""
    L, n_slots, Hr, N, rP = state.shape
    Bb, H, P = xdt.shape
    G = B_in.shape[1]
    r = rP // P
    assert r == heads_per_row(H // G, P) and Hr == H // r, (state.shape,
                                                           xdt.shape)
    tp = _tile_pairs(H // G // r, limit=8)
    tpg = H // G // r // tp
    f32 = jnp.float32
    x_l = xdt.astype(f32).reshape(Bb, Hr, rP)
    d_l = jnp.repeat(decay.astype(f32), P, axis=-1).reshape(Bb, Hr, rP)
    b_g = B_in.astype(f32)[:, :, None, :]               # (B, G, 1, N)
    c_g = C_in.astype(f32)[:, :, None, :]

    row_spec = pl.BlockSpec((1, tp, rP), lambda b, t, sl: (b, t, 0))
    group_spec = pl.BlockSpec((1, 1, 1, N),
                              lambda b, t, sl: (b, t // tpg, 0, 0))
    state_spec = pl.BlockSpec((1, 1, tp, N, rP),
                              lambda b, t, sl: (layer, sl[b], t, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(Bb, Hr // tp),
        in_specs=[row_spec, row_spec, group_spec, group_spec, state_spec],
        out_specs=(row_spec, state_spec),
    )
    y, state = pl.pallas_call(
        functools.partial(_update_kernel, tp=tp),
        grid_spec=grid_spec,
        out_shape=(jax.ShapeDtypeStruct((Bb, Hr, rP), f32),
                   jax.ShapeDtypeStruct(state.shape, f32)),
        # operand 5 (slots, x, decay, B, C, state) is written in place
        input_output_aliases={5: 1},
        # the instruction name the device trace shows (and the benchmark's
        # decode-state roofline matches): keep it stable
        name="ssm_decode_update",
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
    )(slots.astype(jnp.int32), x_l, d_l, b_g, c_g, state)
    return y.reshape(Bb, H, P), state
