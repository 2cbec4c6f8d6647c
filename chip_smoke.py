"""Proof that the served decode path runs on a TPU, Pallas kernels included.

    python chip_smoke.py             # one chip: kernel check + served run
    python chip_smoke.py --chips 4   # four chips: the cross-chip split only

One process, no fallback: on any platform other than the TPU it exits
non-zero before doing anything. With no option it

  1. runs the paged decode and prefill-chunk kernels (bf16 and int8 pools)
     at tinyllama-1.1b serving shapes, and the decode kernel at a
     mistral-nemo-12b worker's 128-lane heads, and compares each with its
     jnp reference, computed in fp32 at the highest matmul precision;
  2. makes the served KV pool, bf16 and int8, and checks that the int8
     pool holds no more of the device than its element bytes say;
  3. serves 8 azure-conv requests with tinyllama-1.1b at its published
     widths and depth (random weights from a seed) through ``LLMEngine``,
     built exactly as ``repro.launch.serve`` builds it, with the
     attention_pool placement and the Pallas backend, and checks that every
     request finishes with its tokens, that every logit is finite, and that
     the compiled decode and chunk programs hold the Mosaic kernel.

With ``--chips 4`` it runs only the head- and block-partitioned paged
attention over a four-device ``attn`` mesh and compares both with the
single-chip kernel. The printed times, compile counts and memory are
diagnostics, not benchmark numbers. The last line of output is a JSON
object naming the device.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

# tinyllama-1.1b attention widths (configs/tinyllama_1_1b.py) at the
# engine's serving block size
HKV, G, HD, BS = 4, 8, 64, 16
# a mistral-nemo-12b attention worker's share (4 of 8 KV heads, 128 lanes):
# the decode kernel copies these pool blocks itself, many a grid step
WIDE = (4, 4, 128)
# Stale pool rows (past cache_len, pad slots) hold these: a dropped mask
# lets them into the softmax and moves the output by orders of magnitude.
POISON_K, POISON_V = 8.0, 1e3


def log(msg: str) -> None:
    print(msg, flush=True)


# ---------------------------------------------------------------------------
# compile accounting (JAX's own monitoring events)
# ---------------------------------------------------------------------------
class CompileLog:
    """Counts backend compiles (persistent-cache loads included) and their
    seconds, plus the persistent cache's hits and misses, per phase. JAX
    counts a miss only where it writes the program to the cache (a compile
    of at least ``jax_persistent_cache_min_compile_time_secs``); the names
    of those programs are kept."""

    COMPILE = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax

        self.programs, self.seconds, self.hits, self.missed = 0, 0.0, 0, []
        pending = []   # a miss is recorded inside the compile it belongs to

        def on_duration(event, secs, fun_name="?", **_):
            if event == self.COMPILE:
                self.programs += 1
                self.seconds += secs
                if pending:
                    self.missed.append(fun_name)
                    pending.clear()

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.hits += 1
            elif event == "/jax/compilation_cache/cache_misses":
                pending.append(event)

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def phase(self) -> str:
        """What was counted since the last call, then start counting anew."""
        text = (f"programs={self.programs} compile_s={self.seconds:.1f} "
                f"cache_hits={self.hits} cache_misses={len(self.missed)} "
                f"missed={sorted(set(self.missed))}")
        self.programs, self.seconds, self.hits, self.missed = 0, 0.0, 0, []
        return text


# ---------------------------------------------------------------------------
# phase 1: kernels against their fp32 references
# ---------------------------------------------------------------------------
def _pools(rng, num_blocks: int, int8: bool, hkv: int = HKV, hd: int = HD):
    """Random K/V pools (bf16, or int8 values with fp32 per-token scales)."""
    import jax.numpy as jnp

    shape = (hkv, num_blocks, BS, hd)
    if not int8:
        return (jnp.asarray(rng.standard_normal(shape), jnp.bfloat16),
                jnp.asarray(rng.standard_normal(shape), jnp.bfloat16),
                None, None)
    sshape = (hkv, num_blocks, 1, BS)
    return (jnp.asarray(rng.integers(-127, 128, shape), jnp.int8),
            jnp.asarray(rng.integers(-127, 128, shape), jnp.int8),
            jnp.asarray(rng.uniform(0.005, 0.02, sshape), jnp.float32),
            jnp.asarray(rng.uniform(0.005, 0.02, sshape), jnp.float32))


def _poison(kp, vp, ks, vs, rows):
    """Overwrite pool rows [(block, first_row)] onward in that block."""
    for blk, r0 in rows:
        kp = kp.at[:, blk, r0:].set(127 if ks is not None else POISON_K)
        vp = vp.at[:, blk, r0:].set(127 if ks is not None else POISON_V)
        if ks is not None:
            ks = ks.at[:, blk, :, r0:].set(POISON_K / 127)
            vs = vs.at[:, blk, :, r0:].set(POISON_V / 127)
    return kp, vp, ks, vs


def _f32(*xs):
    """fp32 copies for the references; int8 pools stay int8 (their
    references dequantize in fp32 themselves)."""
    import jax.numpy as jnp

    return [x if x.dtype == jnp.int8 else x.astype(jnp.float32) for x in xs]


def _close(name: str, got, want) -> None:
    """max|got - want| <= 2^-7 · max|want|. The kernels emit bf16 (2^-9
    relative rounding) and may take single-pass bf16 products on the MXU
    (about 2^-8 relative per score and weight); 2^-7 of the output's range
    covers both. A wrong block moves outputs by several times this, a
    dropped mask (poisoned rows) by orders of magnitude."""
    import numpy as np

    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    assert got.shape == want.shape, (name, got.shape, want.shape)
    assert np.isfinite(got).all(), f"{name}: non-finite kernel output"
    err = float(np.abs(got - want).max())
    tol = 2.0 ** -7 * float(np.abs(want).max())
    log(f"kernel {name}: max_err={err:.3e} tol={tol:.3e} "
        f"{'ok' if err <= tol else 'FAIL'}")
    assert err <= tol, f"{name}: max_err {err:.3e} > tol {tol:.3e}"


def check_decode(int8: bool, *, interpret: bool = False, seed: int = 0,
                 widths=(HKV, G, HD), nb: int = 24):
    """Paged decode: B=8 ragged sequences of up to nb blocks over a pool
    of at least 512 blocks; stale tail rows and pad slots poisoned."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import paged_decode_attention as pda

    hkv, g, hd = widths
    rng = np.random.default_rng(seed)
    B = 8
    num_blocks = max(512, B * nb + 1)
    kp, vp, ks, vs = _pools(rng, num_blocks, int8, hkv, hd)
    lens = rng.integers(nb * BS // 3, nb * BS + 1, B)
    lens[0] = nb * BS                  # one full table
    lens[1] = 5 * BS + 3               # short, partial tail, 18 pad slots
    ids = rng.permutation(np.arange(1, num_blocks))[:B * nb].reshape(B, nb)
    tables = np.zeros((B, nb), np.int32)   # pad slots -> block 0
    stale = [(0, 0)]
    for b in range(B):
        live = -(-int(lens[b]) // BS)
        tables[b, :live] = ids[b, :live]
        if lens[b] % BS:
            stale.append((int(ids[b, live - 1]), int(lens[b]) % BS))
    kp, vp, ks, vs = _poison(kp, vp, ks, vs, stale)
    q = jnp.asarray(rng.standard_normal((B, hkv, g, hd)), jnp.bfloat16)
    bt, cl = jnp.asarray(tables), jnp.asarray(lens, jnp.int32)
    got = pda.paged_decode_attention(q, kp, vp, bt, cl, k_scale=ks,
                                     v_scale=vs, interpret=interpret)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(pda.paged_decode_attention_jnp)(
            *_f32(q, kp, vp), bt, cl, k_scale=ks, v_scale=vs)
    _close(f"decode_{'int8' if int8 else 'bf16'}_hd{hd}_nb{nb}", got, want)


def check_decode_wide(int8: bool, *, interpret: bool = False):
    """Paged decode at 128 lanes, walking 150 blocks: the bf16 kernel
    copies them by hand in chunks (two whole, one ragged at 16 tokens a
    block), the int8 one a block a step."""
    check_decode(int8, interpret=interpret, seed=2, widths=WIDE, nb=150)


def check_chunk(int8: bool, *, interpret: bool = False, seed: int = 1):
    """Paged prefill chunk: a 64-token chunk over a 20-block prefix, and a
    ragged 40-token final chunk over a 7-block prefix."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import paged_prefill_attention as ppa

    rng = np.random.default_rng(seed)
    num_blocks = 512
    kp, vp, ks, vs = _pools(rng, num_blocks, int8)
    for C, nb in ((64, 20), (40, 7)):
        table = jnp.asarray(rng.permutation(num_blocks)[:nb], jnp.int32)
        q = jnp.asarray(rng.standard_normal((C, HKV * G, HD)), jnp.bfloat16)
        kc = jnp.asarray(rng.standard_normal((C, HKV, HD)), jnp.bfloat16)
        vc = jnp.asarray(rng.standard_normal((C, HKV, HD)), jnp.bfloat16)
        got = ppa.paged_prefill_chunk_attention(
            q, kp, vp, table, kc, vc, k_scale=ks, v_scale=vs,
            interpret=interpret)
        with jax.default_matmul_precision("highest"):
            q32, k32, v32, kc32, vc32 = _f32(q, kp, vp, kc, vc)
            want = jax.jit(ppa.paged_prefill_chunk_attention_jnp)(
                q32, k32, v32, table, kc32, vc32, k_scale=ks, v_scale=vs)
        _close(f"chunk_{'int8' if int8 else 'bf16'}_C{C}_nb{nb}", got, want)


def kernel_phase(*, interpret: bool = False) -> None:
    for int8 in (False, True):
        check_decode(int8, interpret=interpret)
        check_decode_wide(int8, interpret=interpret)
        check_chunk(int8, interpret=interpret)


# ---------------------------------------------------------------------------
# phase 2: the served KV pool's bytes on the device
# ---------------------------------------------------------------------------
def pool_phase(num_blocks: int = 4096) -> None:
    """The served KV pool (tinyllama-1.1b, ``num_blocks`` x 16), bf16 and
    int8: the bytes the device holds for each pool array against its
    element bytes. The int8 pool, scales included, must hold at most
    (hd + 4) / (2 hd) of the bf16 pool's device bytes, as
    ``pool_bytes_resident`` counts; a scale layout padded on the device
    breaks that."""
    from repro.configs import registry
    from repro.serving.kvcache import PagedKVCache

    cfg = registry.get_config("tinyllama-1.1b")
    held = {}
    for kv_dtype in ("bf16", "int8"):
        kv = PagedKVCache(cfg, num_blocks, BS, kv_dtype=kv_dtype)
        arrays = {"k_pool": kv.k_pool, "v_pool": kv.v_pool}
        if kv.k_scale is not None:
            arrays.update(k_scale=kv.k_scale, v_scale=kv.v_scale)
        for name, x in arrays.items():
            lay = x.format.layout
            log(f"pool {kv_dtype} {name} {x.dtype}{list(x.shape)}: "
                f"device_bytes={x.on_device_size_in_bytes()} "
                f"element_bytes={x.nbytes} "
                f"layout={lay.major_to_minor}:{lay.tiling}")
        held[kv_dtype] = sum(x.on_device_size_in_bytes()
                             for x in arrays.values())
        log(f"pool {kv_dtype}: device_bytes={held[kv_dtype]} "
            f"pool_bytes_resident={kv.pool_bytes_resident}")
        del kv, arrays
    ratio, bound = held["int8"] / held["bf16"], (HD + 4) / (2 * HD)
    log(f"pool int8/bf16 device bytes: {ratio:.4f} (analytic {bound:.4f})")
    assert ratio <= bound, f"int8 pool holds {ratio:.4f} of bf16 > {bound}"


# ---------------------------------------------------------------------------
# phase 3: the served run
# ---------------------------------------------------------------------------
SERVE_ARGV = [
    "--arch", "tinyllama-1.1b", "--placement", "attention_pool",
    "--partition", "head", "--backend", "pallas",
    "--prefill-chunk-tokens", "64", "--trace", "azure-conv",
    "--requests", "8", "--scale", "0.25", "--max-batch", "8",
    "--num-blocks", "4096",
]


class LastCall:
    """Wraps a jitted function and keeps the abstract arguments of its last
    call, so that its compiled program can be inspected after the run."""

    def __init__(self, fn):
        self.fn, self.calls, self.args = fn, 0, None

    def __call__(self, *args, **kwargs):
        import jax

        self.calls += 1
        self.args = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                           sharding=x.sharding)
            if isinstance(x, jax.Array) else x, (args, kwargs))
        return self.fn(*args, **kwargs)

    def compiled_text(self) -> str:
        args, kwargs = self.args
        return self.fn.lower(*args, **kwargs).compile().as_text()


def served_run(argv=SERVE_ARGV) -> None:
    import numpy as np

    from repro.launch import serve
    from repro.serving import LLMEngine, State

    args = serve.parse_args(argv)
    t0 = time.time()
    cfg, params, reqs, econf = serve.build(args)
    eng = LLMEngine(cfg, params, econf)
    log(f"served: arch={cfg.name} layers={cfg.num_layers} "
        f"d_model={cfg.d_model} heads={cfg.num_heads}/{cfg.num_kv_heads} "
        f"vocab={cfg.vocab_size} placement={econf.placement} "
        f"partition={econf.partition} backend={econf.decode_backend} "
        f"blocks={econf.num_blocks}x{econf.block_size} "
        f"prompts={[len(r.prompt) for r in reqs]} "
        f"new_tokens={[r.params.max_new_tokens for r in reqs]} "
        f"setup_s={time.time() - t0:.1f}")
    sampled = {"batches": 0, "finite": True}
    sample = eng._sample

    def checked_sample(batch, logits):
        sampled["batches"] += 1
        sampled["finite"] &= bool(np.isfinite(np.asarray(logits)).all())
        return sample(batch, logits)

    eng._sample = checked_sample
    eng._decode_jit = decode = LastCall(eng._decode_jit)
    eng._prefill_chunk_jit = chunk = LastCall(eng._prefill_chunk_jit)
    t0 = time.time()
    eng.submit(reqs)
    eng.run()
    wall = time.time() - t0
    for r in reqs:
        assert r.state == State.FINISHED, (r.rid, r.state)
        assert len(r.output) == r.params.max_new_tokens, (
            r.rid, len(r.output), r.params.max_new_tokens)
    assert sampled["finite"], "non-finite logits in the served run"
    assert decode.calls and chunk.calls, (decode.calls, chunk.calls)
    tokens = sum(len(r.output) for r in reqs)
    log(f"served: requests={len(reqs)} finished={len(reqs)} "
        f"tokens={tokens} sampled_batches={sampled['batches']} "
        f"decode_calls={decode.calls} chunk_calls={chunk.calls} "
        f"logits_finite=True wall_s={wall:.1f}")
    for name, fn in (("decode", decode), ("chunk", chunk)):
        t0 = time.time()
        has = "tpu_custom_call" in fn.compiled_text()
        log(f"served: {name} program tpu_custom_call={has} "
            f"(inspected in {time.time() - t0:.1f}s)")
        assert has, f"{name} program holds no Mosaic kernel"


# ---------------------------------------------------------------------------
# --chips 4: head- and block-partitioned paged attention over 4 chips
# ---------------------------------------------------------------------------
def split_phase(n: int = 4, *, interpret: bool = False) -> None:
    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.configs import registry
    from repro.core.attention_parallel import (
        block_parallel_paged_decode_attention,
        head_parallel_paged_decode_attention)
    from repro.kernels.paged_decode_attention import paged_decode_attention
    from repro.serving.kvcache import PagedKVCache

    devices = jax.devices()[:n]
    assert len(devices) == n, f"needs {n} devices, found {len(devices)}"
    mesh = Mesh(np.array(devices), ("attn",))
    cfg = dataclasses.replace(registry.get_config("tinyllama-1.1b"),
                              num_layers=1)
    rng = np.random.default_rng(2)
    B, num_blocks = 8, 1024
    lens = rng.integers(40, 24 * BS, B)
    # the allocator's round-robin placement spreads each sequence's blocks
    # over the n pool shards (contiguous slices of the block axis)
    kv = PagedKVCache(cfg, num_blocks, BS, n_shards=n)
    for sid, ln in enumerate(lens):
        kv.allocate(sid, int(ln))
    ids = list(range(B))
    tables, cl = kv.block_table_batch(ids)
    loc_tables, loc_pos, _ = kv.block_table_shards(ids)
    for int8 in (False, True):
        kp, vp, ks, vs = _pools(rng, num_blocks, int8)
        # fp32 queries, so both sides emit fp32 and differ only by where
        # the partials are summed
        q = jnp.asarray(rng.standard_normal((B, HKV * G, HD)), jnp.float32)
        want = paged_decode_attention(
            q.reshape(B, HKV, G, HD), kp, vp, tables, cl, k_scale=ks,
            v_scale=vs, interpret=interpret).reshape(B, HKV * G, HD)
        for split, spec in (("head", P("attn", None, None, None)),
                            ("block", P(None, "attn", None, None))):
            put = lambda x: None if x is None else jax.device_put(
                x, NamedSharding(mesh, spec))
            kps, vps, kss, vss = put(kp), put(vp), put(ks), put(vs)
            where = {s.device for s in kps.addressable_shards}
            assert len(where) == n, f"{split}: pool on {len(where)} devices"
            kw = dict(backend="pallas", interpret=interpret, k_scale=kss,
                      v_scale=vss)
            if split == "head":
                got = head_parallel_paged_decode_attention(
                    mesh, "attn", q, kps, vps, tables, cl, **kw)
            else:
                got = block_parallel_paged_decode_attention(
                    mesh, "attn", q, kps, vps, loc_tables, loc_pos, cl, **kw)
            _close(f"{split}_split_{'int8' if int8 else 'bf16'} "
                   f"pool_devices={len(where)} "
                   f"shard={kps.addressable_shards[0].data.shape}",
                   got, want)


# ---------------------------------------------------------------------------
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, default=1, choices=[1, 4],
                    help="4: run only the cross-chip attention split")
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, but JAX found platform "
              f"{dev.platform!r} ({len(devices)} device(s)); nothing run",
              file=sys.stderr)
        return 2
    from repro.launch.serve import use_compile_cache

    cache = use_compile_cache()
    compiles = CompileLog()
    log(f"jax={jax.__version__} platform={dev.platform} "
        f"device_kind={dev.device_kind} device_count={len(devices)} "
        f"compile_cache={cache}")
    t0 = time.time()
    phases = ([("split", split_phase)] if args.chips == 4 else
              [("kernels", kernel_phase), ("pool", pool_phase),
               ("served", served_run)])
    for name, run in phases:
        t1 = time.time()
        run()
        log(f"phase {name}: passed in {time.time() - t1:.1f}s; "
            f"{compiles.phase()}")
    stats = dev.memory_stats() or {}
    log(f"peak_bytes_in_use={stats.get('peak_bytes_in_use')} "
        f"total_s={time.time() - t0:.1f}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
