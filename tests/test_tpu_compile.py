"""The paged Pallas kernels compile for a TPU v5e at real widths.

Interpret mode (every other test) cannot see what the chip's compiler
refuses: tile shapes that break the tiling rule, vector reshapes Mosaic
cannot lay out. These tests compile the four served kernel variants (paged
decode and prefill-chunk, bf16 and int8 pools) at the served models'
widths for a described ``v5e:2x2`` topology, with no chip attached, the
decode kernel at the benchmark cell's size, and the cross-chip head and
block splits over its four devices; and the two Mamba-2 kernels (the
decode step's in-place state update and the prefill chunk's scan) at the
``nemotron-h-47b`` cell's widths.
Each asserts that the compiled program holds the Mosaic kernel, under the
instruction name that the device trace shows and the benchmark's roofline
readers match (``paged_decode`` for decode, ``paged_prefill`` for chunks,
``ssm_decode`` and ``ssm_chunk_scan`` for the Mamba-2 state).

The topology is described inside a fixture: only one process may load the
TPU compiler's library at a time, so nothing here may touch it while the
module is imported.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.core.attention_parallel import (
    block_parallel_paged_decode_attention,
    head_parallel_paged_decode_attention)
from repro.kernels.paged_decode_attention import paged_decode_attention
from repro.kernels.paged_prefill_attention import \
    paged_prefill_chunk_attention

# (Hkv, G, hd) of the served models; for the two benchmark models, one of
# the two attention workers' share of the KV heads
WIDTHS = {"tinyllama-1.1b": (4, 8, 64), "llama3-8b": (8, 4, 128),
          "mistral-nemo-12b": (4, 4, 128), "glm4-9b": (1, 16, 128)}
# a decode batch of 32 walking 64 blocks of a 4096 x 16 pool; 64-token chunks
B, NB, POOL, BS, C = 32, 64, 4096, 16, 64
# the benchmark cell's decode: 8 rows up to 392 blocks of a 4160-block pool
CELL_B, CELL_NB, CELL_POOL = 8, 392, 4160


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # the TPU compiler's library otherwise writes its logs to a fixed
    # directory outside the checkout
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one; keep these compiles out of it (the
    # reset drops a cache this process has already opened)
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


# instruction-name substrings the benchmark's roofline readers match
KERNEL_NAMES = {"decode": "paged_decode", "chunk": "paged_prefill",
                "ssm_decode": "ssm_decode", "ssm_scan": "ssm_chunk_scan"}
# the nemotron-h-47b cell's Mamba-2 layers: 64 heads of 64 in 2 groups,
# state 256, 9 layers' slots for 16 rows, 512-token prefill chunks
SSM_H, SSM_P, SSM_G, SSM_N, SSM_L, SSM_B, SSM_C = 64, 64, 2, 256, 9, 16, 512


def _compiled_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


def _kernel_instructions(text: str) -> list:
    """Instruction names of the Mosaic kernel calls in compiled HLO text."""
    return [line.split(" = ", 1)[0].strip().lstrip("%")
            for line in text.splitlines()
            if "custom-call(" in line and "tpu_custom_call" in line]


def _pool_args(sds, Hkv, hd, int8, pool=POOL):
    kv = jnp.int8 if int8 else jnp.bfloat16
    pools = [sds((Hkv, pool, BS, hd), kv)] * 2
    scales = [sds((Hkv, pool, 1, BS), jnp.float32)] * 2 if int8 else []
    return pools, scales


def _scale_kw(scales):
    return dict(zip(("k_scale", "v_scale"), scales))


@pytest.mark.parametrize("int8", [False, True], ids=["bf16", "int8"])
@pytest.mark.parametrize("kernel", ["decode", "chunk"])
@pytest.mark.parametrize("model", list(WIDTHS))
def test_paged_kernel_compiles_for_v5e(topo, model, kernel, int8):
    Hkv, G, hd = WIDTHS[model]
    one = SingleDeviceSharding(topo.devices[0])

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    pools, scales = _pool_args(sds, Hkv, hd, int8)
    if kernel == "decode":
        def fn(q, kp, vp, bt, cl, *s):
            return paged_decode_attention(q, kp, vp, bt, cl,
                                          return_partials=True,
                                          **_scale_kw(s))
        args = [sds((B, Hkv, G, hd), jnp.bfloat16), *pools,
                sds((B, NB), jnp.int32), sds((B,), jnp.int32), *scales]
    else:
        def fn(q, kp, vp, bt, kc, vc, *s):
            return paged_prefill_chunk_attention(q, kp, vp, bt, kc, vc,
                                                 **_scale_kw(s))
        args = [sds((C, Hkv * G, hd), jnp.bfloat16), *pools,
                sds((NB,), jnp.int32), sds((C, Hkv, hd), jnp.bfloat16),
                sds((C, Hkv, hd), jnp.bfloat16), *scales]
    names = _kernel_instructions(_compiled_text(fn, *args))
    assert names and all(KERNEL_NAMES[kernel] in n for n in names), names


@pytest.mark.parametrize("int8", [False, True], ids=["bf16", "int8"])
@pytest.mark.parametrize("model", ["mistral-nemo-12b", "glm4-9b"])
def test_paged_decode_compiles_at_cell_size(topo, model, int8):
    """The decode kernel at the benchmark cell's size: a worker's heads, 8
    rows of up to 392 blocks over the 4160-block pool, so the walk spans
    13 chunks of 32 copied blocks (bf16) or 392 pipelined ones (int8)."""
    Hkv, G, hd = WIDTHS[model]
    one = SingleDeviceSharding(topo.devices[0])

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    pools, scales = _pool_args(sds, Hkv, hd, int8, pool=CELL_POOL)

    def fn(q, kp, vp, bt, cl, *s):
        return paged_decode_attention(q, kp, vp, bt, cl, return_partials=True,
                                      **_scale_kw(s))
    args = [sds((CELL_B, Hkv, G, hd), jnp.bfloat16), *pools,
            sds((CELL_B, CELL_NB), jnp.int32), sds((CELL_B,), jnp.int32),
            *scales]
    names = _kernel_instructions(_compiled_text(fn, *args))
    assert names and all(KERNEL_NAMES["decode"] in n for n in names), names


@pytest.mark.parametrize("bs", [8, 16, 32])
@pytest.mark.parametrize("model", list(WIDTHS))
def test_decode_blocks_per_step_rule(model, bs):
    """The decode kernel's blocks per grid step, from the shapes alone:
    1 <= P <= nb, about CHUNK_TOKENS tokens a step where the copies are
    made by hand, and the two K and two V chunk buffers within
    COPY_BUFFER_BYTES. Needs no chip."""
    from repro.kernels import paged_decode_attention as pda

    Hkv, _, hd = WIDTHS[model]
    for quantized, itemsize in ((False, 2), (False, 4), (True, 1)):
        for nb in (1, 7, 64, CELL_NB):
            P = pda.decode_blocks_per_step(bs, hd, Hkv, nb, itemsize,
                                           quantized)
            assert 1 <= P <= nb
            if quantized or hd % 128:
                assert P == 1     # the grid pipeline copies one block
                continue
            def buffers(p):
                return 2 * 2 * p * Hkv * bs * hd * itemsize
            assert P * bs <= pda.CHUNK_TOKENS
            assert buffers(P) <= pda.COPY_BUFFER_BYTES
            # and no smaller than the three limits allow
            assert (P == nb or P * bs == pda.CHUNK_TOKENS
                    or buffers(P + 1) > pda.COPY_BUFFER_BYTES)


@pytest.mark.parametrize("split", ["head", "block"])
def test_paged_split_compiles_for_four_v5e_chips(topo, split):
    """tinyllama widths over a 4-device ``attn`` mesh: one KV head per chip
    for the head split, a quarter of the pool's blocks for the block
    split; each chip runs the kernel on its shard."""
    Hkv, G, hd = WIDTHS["tinyllama-1.1b"]
    mesh = Mesh(np.array(topo.devices[:4]), ("attn",))
    rep = NamedSharding(mesh, P())
    pool = NamedSharding(mesh, P("attn") if split == "head"
                         else P(None, "attn"))

    def sds(shape, dtype, sharding=rep):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    q = sds((B, Hkv * G, hd), jnp.bfloat16)
    kp = sds((Hkv, POOL, BS, hd), jnp.bfloat16, pool)
    cl = sds((B,), jnp.int32)
    if split == "head":
        def fn(q, kp, vp, bt, cl):
            return head_parallel_paged_decode_attention(
                mesh, "attn", q, kp, vp, bt, cl, backend="pallas")
        args = (q, kp, kp, sds((B, NB), jnp.int32), cl)
    else:
        def fn(q, kp, vp, bt, bp, cl):
            return block_parallel_paged_decode_attention(
                mesh, "attn", q, kp, vp, bt, bp, cl, backend="pallas")
        local = sds((4, B, NB // 4), jnp.int32,
                    NamedSharding(mesh, P("attn")))
        args = (q, kp, kp, local, local, cl)
    names = _kernel_instructions(_compiled_text(fn, *args))
    assert names and all(KERNEL_NAMES["decode"] in n for n in names), names


@pytest.mark.parametrize("kernel", ["ssm_decode", "ssm_scan"])
def test_ssm_kernel_compiles_for_v5e_at_cell_size(topo, kernel):
    """The decode step's state update under a jit that donates the state
    writes it in place: the program aliases the whole fp32 state and holds
    no temporary buffer a hundredth its size. The chunk scan, from a given
    state, over a 512-token chunk."""
    from repro.kernels.ssm_decode_update import ssm_decode_update
    from repro.kernels.ssm_scan import heads_per_row, ssm_chunk_scan

    one = SingleDeviceSharding(topo.devices[0])
    f32 = jnp.float32

    def sds(shape, dtype=f32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    H, P, G, N = SSM_H, SSM_P, SSM_G, SSM_N
    r = heads_per_row(H // G, P)
    if kernel == "ssm_decode":
        state = sds((SSM_L, SSM_B, H // r, N, r * P))

        def fn(st, sl, x, d, b, c):
            return ssm_decode_update(st, sl, x, d, b, c, layer=SSM_L - 1)
        compiled = jax.jit(fn, donate_argnums=0).lower(
            state, sds((SSM_B,), jnp.int32), sds((SSM_B, H, P)),
            sds((SSM_B, H)), sds((SSM_B, G, N)), sds((SSM_B, G, N))
        ).compile()
        mem = compiled.memory_analysis()
        state_bytes = SSM_L * SSM_B * H * P * N * 4
        assert mem.alias_size_in_bytes == state_bytes
        assert mem.temp_size_in_bytes < state_bytes / 100
        text = compiled.as_text()
    else:
        def fn(x, a, b, c, s0):
            return ssm_chunk_scan(x, a, b, c, s0)
        text = _compiled_text(
            fn, sds((1, SSM_C, H, P)), sds((1, SSM_C, H)),
            sds((1, SSM_C, G, N)), sds((1, SSM_C, G, N)),
            sds((1, H // r, N, r * P)))
    names = _kernel_instructions(text)
    assert names and all(KERNEL_NAMES[kernel] in n for n in names), names
