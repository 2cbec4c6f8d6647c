"""Int8 quantized KV pool: kernel parity + accuracy contracts.

The bit-parity contract is pinned with an EAGER REPLAY harness: the REAL
Pallas kernel bodies are driven per grid cell through `_Ref` shims (with
``pl.program_id`` patched to the replayed cell), so every op runs eagerly —
its own deterministic XLA program — exactly like the eager mirror refs in
``kernels/ref.py``. That makes the comparison compiler-independent:
interpret-mode ``pallas_call`` compiles the whole grid as one program, and
XLA CPU's fusion-context-dependent FMA contraction / reduction order then
produces ~1-ulp drift against ANY independently-compiled reference (the
chunk kernel demonstrably so), which would pin compiler behaviour, not
kernel semantics. The replay pins the kernel's op sequence itself: the int8
kernels match the int8 jnp references BIT-EXACTLY, tile for tile.

The interpret-mode wrappers are then held to the refs at tight tolerances
(decode happens to be bit-exact here too; the chunk wrapper is allclose for
the reason above), and the accuracy contract vs the bf16/fp32 path is
cosine >= 0.999 on unit-scale inputs plus greedy-token agreement end to end
(tests/test_int8_kvpool.py covers the pool/engine side).
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental import pallas as pl

from repro.kernels import paged_decode_attention as pda
from repro.kernels import paged_prefill_attention as ppa
from repro.kernels import ref
from repro.models import kv_quant

DEC_KW = [("plain", {}),
          ("window", {"sliding_window": 24}),
          ("window+sinks", {"sliding_window": 24, "attention_sinks": 4}),
          ("softcap", {"logit_softcap": 30.0})]
CHUNK_CASES = [("plain", 3, 24, {}),
               ("empty-prefix", 0, 24, {}),
               ("window+sinks", 3, 24,
                {"sliding_window": 20, "attention_sinks": 2}),
               ("softcap-ragged", 3, 19, {"logit_softcap": 30.0})]


# ---------------------------------------------------------------------------
# eager replay harness
# ---------------------------------------------------------------------------
class _Ref:
    """Minimal pl.Ref stand-in over a jnp array (eager load/store)."""

    def __init__(self, a):
        self.a = jnp.asarray(a)

    def __getitem__(self, idx):
        return self.a[idx]

    def __setitem__(self, idx, val):
        self.a = self.a.at[idx].set(val)

    @property
    def dtype(self):
        return self.a.dtype

    @property
    def shape(self):
        return self.a.shape

    def __jax_array__(self):
        return self.a


class _PID:
    """Context manager patching pl.program_id to the replayed grid cell,
    and pl.when to run its body eagerly: the replayed conditions are
    concrete (python bools, or arrays computed from the scalar operands)."""

    def __init__(self):
        self.ids = (0, 0, 0)

    def __enter__(self):
        self._orig = pl.program_id, pl.when
        pl.program_id = lambda i: self.ids[i]
        pl.when = lambda cond: lambda f: f() if bool(cond) else None
        return self

    def __exit__(self, *a):
        pl.program_id, pl.when = self._orig


def replay_decode(q, k_pool, v_pool, ks, vs, bt, bp, cl, **kw):
    """Drive the int8 decode kernel (one pool block a grid step, the grid
    pipeline doing the copies) per (b, slot) grid cell, eagerly, feeding
    exactly the operand tiles the BlockSpecs would map in."""
    B, Hkv, G, hd = q.shape
    bs = k_pool.shape[2]
    nb = bt.shape[1]
    assert pda.decode_blocks_per_step(bs, hd, Hkv, nb, 1, quantized=True) == 1
    kern = functools.partial(pda._paged_decode_kernel, P=1, by_hand=False,
                             quantized=True, contiguous=True, block_size=bs,
                             nb=nb,
                             sliding_window=kw.get("sliding_window", 0),
                             attention_sinks=kw.get("attention_sinks", 0),
                             logit_softcap=kw.get("logit_softcap", 0.0))
    o = jnp.zeros((B, Hkv, G, hd), q.dtype)
    with _PID() as pid:
        for b in range(B):
            acc = _Ref(jnp.zeros((Hkv, G, hd), jnp.float32))
            m = _Ref(jnp.zeros((Hkv, G, 128), jnp.float32))
            ell = _Ref(jnp.zeros((Hkv, G, 128), jnp.float32))
            o_r = _Ref(jnp.zeros((Hkv, G, hd), q.dtype))
            lo_r = _Ref(jnp.zeros((Hkv, G, 128), jnp.float32))
            mo_r = _Ref(jnp.zeros((Hkv, G, 128), jnp.float32))
            walk = min(nb, -(-int(cl[b]) // bs))
            for kb in range(nb):
                pid.ids = (b, kb)
                # slots past the row's walk map its last block in again
                blk = int(bt[b, max(min(kb, walk - 1), 0)])
                kern(_Ref(bt), _Ref(bp), _Ref(cl), _Ref(q[b]),
                     _Ref(k_pool[:, blk]), _Ref(v_pool[:, blk]),
                     _Ref(ks[:, blk]), _Ref(vs[:, blk]),
                     o_r, lo_r, mo_r, acc, m, ell)
            o = o.at[b].set(o_r.a)
    return o


def replay_chunk(q, k_pool, v_pool, ks, vs, bt, kc, vc, **kw):
    """Drive _paged_prefill_chunk_kernel_int8 per (h, kb) grid cell,
    mirroring the wrapper's chunk padding/reshape and index maps."""
    C, H, hd = q.shape
    Hkv, _, bs, _ = k_pool.shape
    G = H // Hkv
    nb = bt.shape[0]
    nc = -(-C // bs)
    pad = nc * bs - C
    kcm = jnp.swapaxes(kc, 0, 1)
    vcm = jnp.swapaxes(vc, 0, 1)
    if pad:
        kcm = jnp.pad(kcm, ((0, 0), (0, pad), (0, 0)))
        vcm = jnp.pad(vcm, ((0, 0), (0, pad), (0, 0)))
    kcm = kcm.reshape(Hkv, nc, bs, hd)
    vcm = vcm.reshape(Hkv, nc, bs, hd)
    qg = q.reshape(C, Hkv, G, hd).transpose(1, 2, 0, 3).reshape(
        Hkv, G * C, hd)
    btp = bt if nb else jnp.zeros((1,), jnp.int32)
    clamp = max(nb - 1, 0)
    nsteps = nb + nc
    kern = functools.partial(ppa._paged_prefill_chunk_kernel_int8,
                             block_size=bs, chunk_len=C, prefix_blocks=nb,
                             total_len=nb * bs + C, nsteps=nsteps,
                             sliding_window=kw.get("sliding_window", 0),
                             attention_sinks=kw.get("attention_sinks", 0),
                             logit_softcap=kw.get("logit_softcap", 0.0))
    out = jnp.zeros((Hkv, G * C, hd), q.dtype)
    with _PID() as pid:
        for h in range(Hkv):
            acc = _Ref(jnp.zeros((G * C, hd), jnp.float32))
            m = _Ref(jnp.zeros((G * C, 128), jnp.float32))
            ell = _Ref(jnp.zeros((G * C, 128), jnp.float32))
            o_r = _Ref(jnp.zeros((1, G * C, hd), q.dtype))
            for kb in range(nsteps):
                pid.ids = (h, kb)
                blk = int(btp[min(kb, clamp)])
                ci = max(kb - nb, 0)
                kern(_Ref(btp),
                     _Ref(qg[h:h + 1]),
                     _Ref(k_pool[h:h + 1, blk:blk + 1]),
                     _Ref(v_pool[h:h + 1, blk:blk + 1]),
                     _Ref(ks[h:h + 1, blk:blk + 1]),
                     _Ref(vs[h:h + 1, blk:blk + 1]),
                     _Ref(kcm[h:h + 1, ci:ci + 1]),
                     _Ref(vcm[h:h + 1, ci:ci + 1]),
                     o_r, acc, m, ell)
            out = out.at[h].set(o_r.a[0])
    return out.reshape(Hkv, G, C, hd).transpose(2, 0, 1, 3).reshape(C, H, hd)


def _rand_int8_pool(rng, Hkv, num_blocks, bs, hd):
    k_pool = jnp.asarray(rng.integers(-127, 128, (Hkv, num_blocks, bs, hd)),
                         jnp.int8)
    v_pool = jnp.asarray(rng.integers(-127, 128, (Hkv, num_blocks, bs, hd)),
                         jnp.int8)
    ks = jnp.asarray(rng.uniform(0.001, 0.1, (Hkv, num_blocks, 1, bs)),
                     jnp.float32)
    vs = jnp.asarray(rng.uniform(0.001, 0.1, (Hkv, num_blocks, 1, bs)),
                     jnp.float32)
    return k_pool, v_pool, ks, vs


# ---------------------------------------------------------------------------
# bit-exact replay parity (the kernel contract)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("case,kw", DEC_KW, ids=[c for c, _ in DEC_KW])
def test_decode_kernel_replay_bit_exact(case, kw):
    rng = np.random.default_rng(hash(case) % 2**32)
    B, Hkv, G, hd, bs, num_blocks, nb = 3, 2, 4, 64, 16, 32, 4
    kp, vp, ks, vs = _rand_int8_pool(rng, Hkv, num_blocks, bs, hd)
    q = jnp.asarray(rng.standard_normal((B, Hkv, G, hd)), jnp.float32)
    bt = jnp.asarray(np.stack([rng.choice(num_blocks, nb, replace=False)
                               for _ in range(B)]), jnp.int32)
    cl = jnp.asarray(rng.integers(1, nb * bs + 1, (B,)), jnp.int32)
    bp = pda.default_block_positions(B, nb, bs)
    got = replay_decode(q, kp, vp, ks, vs, bt, bp, cl, **kw)
    want = ref.paged_decode_attention_int8_ref(q, kp, vp, ks, vs, bt, cl,
                                               **kw)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("case,nb_c,C,kw", CHUNK_CASES,
                         ids=[c[0] for c in CHUNK_CASES])
def test_chunk_kernel_replay_bit_exact(case, nb_c, C, kw):
    rng = np.random.default_rng(hash(case) % 2**32)
    Hkv, G, hd, bs, num_blocks = 2, 4, 64, 16, 32
    kp, vp, ks, vs = _rand_int8_pool(rng, Hkv, num_blocks, bs, hd)
    q = jnp.asarray(rng.standard_normal((C, Hkv * G, hd)), jnp.float32)
    kc = jnp.asarray(rng.standard_normal((C, Hkv, hd)), jnp.float32)
    vc = jnp.asarray(rng.standard_normal((C, Hkv, hd)), jnp.float32)
    bt = jnp.asarray(rng.choice(num_blocks, nb_c, replace=False), jnp.int32)
    got = replay_chunk(q, kp, vp, ks, vs, bt, kc, vc, **kw)
    want = ref.paged_prefill_chunk_attention_int8_ref(q, kp, vp, ks, vs, bt,
                                                      kc, vc, **kw)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# ---------------------------------------------------------------------------
# interpret-mode wrappers against the refs (wiring: specs/index maps)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("case,kw", DEC_KW, ids=[c for c, _ in DEC_KW])
def test_decode_wrapper_interpret_matches_ref(case, kw):
    rng = np.random.default_rng(1 + hash(case) % 2**32)
    B, Hkv, G, hd, bs, num_blocks, nb = 3, 2, 4, 64, 16, 32, 4
    kp, vp, ks, vs = _rand_int8_pool(rng, Hkv, num_blocks, bs, hd)
    q = jnp.asarray(rng.standard_normal((B, Hkv, G, hd)), jnp.float32)
    bt = jnp.asarray(np.stack([rng.choice(num_blocks, nb, replace=False)
                               for _ in range(B)]), jnp.int32)
    cl = jnp.asarray(rng.integers(1, nb * bs + 1, (B,)), jnp.int32)
    got = pda.paged_decode_attention(q, kp, vp, bt, cl, k_scale=ks,
                                     v_scale=vs, interpret=True, **kw)
    want = jax.jit(functools.partial(ref.paged_decode_attention_int8_ref,
                                     **kw))(q, kp, vp, ks, vs, bt, cl)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_decode_wrapper_custom_block_positions():
    rng = np.random.default_rng(7)
    B, Hkv, G, hd, bs, num_blocks, nb = 2, 2, 4, 64, 16, 32, 4
    kp, vp, ks, vs = _rand_int8_pool(rng, Hkv, num_blocks, bs, hd)
    q = jnp.asarray(rng.standard_normal((B, Hkv, G, hd)), jnp.float32)
    bt = jnp.asarray(np.stack([rng.choice(num_blocks, nb, replace=False)
                               for _ in range(B)]), jnp.int32)
    cl = jnp.asarray(rng.integers(1, nb * bs + 1, (B,)), jnp.int32)
    bp = pda.default_block_positions(B, nb, bs)
    got = pda.paged_decode_attention(q, kp, vp, bt, cl, block_positions=bp,
                                     k_scale=ks, v_scale=vs, interpret=True)
    want = jax.jit(ref.paged_decode_attention_int8_ref)(
        q, kp, vp, ks, vs, bt, cl, block_positions=bp)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("case,nb_c,C,kw", CHUNK_CASES,
                         ids=[c[0] for c in CHUNK_CASES])
def test_chunk_wrapper_interpret_matches_ref(case, nb_c, C, kw):
    rng = np.random.default_rng(2 + hash(case) % 2**32)
    Hkv, G, hd, bs, num_blocks = 2, 4, 64, 16, 32
    kp, vp, ks, vs = _rand_int8_pool(rng, Hkv, num_blocks, bs, hd)
    q = jnp.asarray(rng.standard_normal((C, Hkv * G, hd)), jnp.float32)
    kc = jnp.asarray(rng.standard_normal((C, Hkv, hd)), jnp.float32)
    vc = jnp.asarray(rng.standard_normal((C, Hkv, hd)), jnp.float32)
    bt = jnp.asarray(rng.choice(num_blocks, nb_c, replace=False), jnp.int32)
    got = ppa.paged_prefill_chunk_attention(q, kp, vp, bt, kc, vc,
                                            k_scale=ks, v_scale=vs,
                                            interpret=True, **kw)
    want = ref.paged_prefill_chunk_attention_int8_ref(q, kp, vp, ks, vs, bt,
                                                      kc, vc, **kw)
    # interpret-mode pallas_call compiles the whole grid as one XLA program;
    # cross-program FMA/reduction-order variance bounds this at ~ulp level
    # (the REPLAY tests above carry the bit-exactness contract)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-5)


def test_decode_jnp_backend_matches_ref():
    """The jnp dispatcher path (dense gather + per-token scales) agrees
    with the fused int8 reference to fp32 rounding. The two are separately
    compiled programs that order the nb·bs-key sums differently, so the
    bound is the rounding of such a sum: nb·bs·eps·max|v|, with |v| <=
    127·0.1. A wrong block or a dropped mask moves outputs by O(1)."""
    rng = np.random.default_rng(11)
    B, Hkv, G, hd, bs, num_blocks, nb = 3, 2, 4, 64, 16, 32, 4
    kp, vp, ks, vs = _rand_int8_pool(rng, Hkv, num_blocks, bs, hd)
    q = jnp.asarray(rng.standard_normal((B, Hkv, G, hd)), jnp.float32)
    bt = jnp.asarray(np.stack([rng.choice(num_blocks, nb, replace=False)
                               for _ in range(B)]), jnp.int32)
    cl = jnp.asarray(rng.integers(1, nb * bs + 1, (B,)), jnp.int32)
    got = pda.paged_decode_attention_jnp(q, kp, vp, bt, cl, k_scale=ks,
                                         v_scale=vs)
    want = ref.paged_decode_attention_int8_ref(q, kp, vp, ks, vs, bt, cl)
    atol = nb * bs * np.finfo(np.float32).eps * 127 * 0.1
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=0, atol=atol)


# ---------------------------------------------------------------------------
# accuracy vs the unquantized path (cosine >= 0.999 on unit-scale inputs)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("kw", [{}, {"sliding_window": 24},
                                {"logit_softcap": 30.0}],
                         ids=["plain", "window", "softcap"])
def test_int8_cosine_vs_fp_oracle(kw):
    rng = np.random.default_rng(21)
    B, Hkv, G, hd, bs, nb = 3, 2, 4, 64, 16, 4
    num_blocks = B * nb
    kf = jnp.asarray(rng.standard_normal((Hkv, num_blocks, bs, hd)),
                     jnp.float32)
    vf = jnp.asarray(rng.standard_normal((Hkv, num_blocks, bs, hd)),
                     jnp.float32)
    kq, ks = kv_quant.quantize_kv(kf)
    vq, vs = kv_quant.quantize_kv(vf)
    ks, vs = ks[:, :, None], vs[:, :, None]   # the pool's (…, 1, bs) rows
    q = jnp.asarray(rng.standard_normal((B, Hkv, G, hd)), jnp.float32)
    bt = jnp.asarray(rng.permutation(num_blocks)[:B * nb].reshape(B, nb),
                     jnp.int32)
    cl = jnp.asarray(rng.integers(1, nb * bs + 1, (B,)), jnp.int32)
    got = pda.paged_decode_attention(q, kq, vq, bt, cl, k_scale=ks,
                                     v_scale=vs, interpret=True, **kw)
    want = pda.paged_decode_attention_jnp(q, kf, vf, bt, cl, **kw)
    g = np.asarray(got, np.float64).reshape(-1, hd)
    w = np.asarray(want, np.float64).reshape(-1, hd)
    cos = (g * w).sum(-1) / np.maximum(
        np.linalg.norm(g, axis=-1) * np.linalg.norm(w, axis=-1), 1e-30)
    assert cos.min() >= 0.999, f"min cosine {cos.min()}"


def test_quantize_roundtrip_extremes():
    """quantize_kv maps max-abs to ±127 and round-trips to <= 1/254
    relative error per token-head (symmetric per-token-head max-abs)."""
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.standard_normal((2, 5, 16, 64)) * 10.0, jnp.float32)
    xq, s = kv_quant.quantize_kv(x)
    assert int(jnp.abs(xq).max()) == 127
    back = kv_quant.dequantize_kv(xq, s)
    amax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
    err = jnp.abs(back - x) / jnp.maximum(amax, 1e-8)
    assert float(err.max()) <= 1.0 / 254 + 1e-6
