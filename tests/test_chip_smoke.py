"""chip_smoke.py off the chip: it refuses every platform but the TPU, and
its kernel checks pass on the real kernels (interpreted here) and fail on a
wrong block walk or a dropped mask."""
import importlib.util
import os
import subprocess
import sys

import jax.numpy as jnp
import pytest

from repro.kernels import paged_decode_attention as pda
from repro.kernels import paged_prefill_attention as ppa

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_refuses_cpu_and_names_it():
    r = subprocess.run([sys.executable, "chip_smoke.py"], cwd=ROOT,
                       env={**os.environ, "JAX_PLATFORMS": "cpu"},
                       capture_output=True, text=True, timeout=300)
    assert r.returncode != 0
    assert "platform 'cpu'" in r.stderr
    assert '"ok"' not in r.stdout


@pytest.mark.parametrize("int8", [False, True], ids=["bf16", "int8"])
@pytest.mark.parametrize("check", ["check_decode", "check_chunk",
                                   "check_decode_wide"])
def test_kernel_check_passes_interpreted(smoke, check, int8):
    getattr(smoke, check)(int8, interpret=True)


def test_kernel_check_catches_wrong_block(smoke, monkeypatch):
    real = pda.paged_decode_attention
    monkeypatch.setattr(
        pda, "paged_decode_attention",
        lambda q, kp, vp, bt, cl, **kw: real(q, kp, vp, jnp.roll(bt, 1, 1),
                                             cl, **kw))
    with pytest.raises(AssertionError, match="max_err"):
        smoke.check_decode(False, interpret=True)


def test_kernel_check_catches_dropped_mask(smoke, monkeypatch):
    # one more row per sequence: the poisoned row past cache_len
    real = pda.paged_decode_attention
    monkeypatch.setattr(
        pda, "paged_decode_attention",
        lambda q, kp, vp, bt, cl, **kw: real(q, kp, vp, bt, cl + 1, **kw))
    with pytest.raises(AssertionError, match="max_err"):
        smoke.check_decode(True, interpret=True)


def test_kernel_check_catches_wrong_prefix_block(smoke, monkeypatch):
    # the next pool block instead of each prefix block (the prefix attends
    # unmasked, so only a block from outside the sequence changes it)
    real = ppa.paged_prefill_chunk_attention
    monkeypatch.setattr(
        ppa, "paged_prefill_chunk_attention",
        lambda q, kp, vp, bt, kc, vc, **kw: real(
            q, kp, vp, (bt + 1) % kp.shape[1], kc, vc, **kw))
    with pytest.raises(AssertionError, match="max_err"):
        smoke.check_chunk(False, interpret=True)


def test_pool_phase_int8_share_of_bf16(smoke):
    # on the CPU a pool array holds exactly its element bytes
    smoke.pool_phase(num_blocks=64)


def test_compile_log_names_cache_writes(smoke, tmp_path):
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    def written_to_the_cache(x):
        return x * 3 + 1

    keys = ("jax_enable_compilation_cache", "jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    saved = {k: getattr(jax.config, k) for k in keys}
    x = jnp.arange(7.0)
    log = smoke.CompileLog()
    try:
        # every compile, however small and quick, goes to the cache
        jax.config.update("jax_enable_compilation_cache", True)
        jax.config.update("jax_compilation_cache_dir", str(tmp_path))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
        compilation_cache.reset_cache()
        jax.jit(written_to_the_cache)(x).block_until_ready()
        counted = log.phase()
    finally:
        for k, v in saved.items():
            jax.config.update(k, v)
        compilation_cache.reset_cache()
    assert "cache_misses=1" in counted, counted
    assert "missed=['jit(written_to_the_cache)']" in counted, counted
    assert log.phase().startswith("programs=0 "), "phase() starts anew"
