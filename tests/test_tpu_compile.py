"""The paged Pallas kernels compile for a TPU v5e at real widths.

Interpret mode (every other test) cannot see what the chip's compiler
refuses: tile shapes that break the tiling rule, vector reshapes Mosaic
cannot lay out. These tests compile the four served kernel variants (paged
decode and prefill-chunk, bf16 and int8 pools) at tinyllama-1.1b and
llama3-8b widths for a described ``v5e:2x2`` topology, with no chip
attached, and the cross-chip head and block splits over its four devices.
Each asserts that the compiled program holds the Mosaic kernel.

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

# (Hkv, G, hd) of the served models
WIDTHS = {"tinyllama-1.1b": (4, 8, 64), "llama3-8b": (8, 4, 128)}
# a decode batch of 32 walking 64 blocks of a 4096 x 16 pool; 64-token chunks
B, NB, POOL, BS, C = 32, 64, 4096, 16, 64


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


def _compiled_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


def _pool_args(sds, Hkv, hd, int8):
    kv = jnp.int8 if int8 else jnp.bfloat16
    pools = [sds((Hkv, POOL, BS, hd), kv)] * 2
    scales = [sds((Hkv, POOL, 1, BS), jnp.float32)] * 2 if int8 else []
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
    assert "tpu_custom_call" in _compiled_text(fn, *args)


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
    text = _compiled_text(fn, *args)
    assert "tpu_custom_call" in text
