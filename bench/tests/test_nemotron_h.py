"""Tests of the Nemotron-H block (``bench/arch/nemotron_h.py``) and its
cell, on the CPU and without a chip.

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests/test_nemotron_h.py

They check that each layer drawn alone is the stacked tree's; that the
float32 reference agrees with ``LLMEngine`` through chunked prefill, the
state slots and paged decode at tiny widths; the state-update and scan
counts by hand; that the new cell resolves, fits the chip, and its pool
holds its traffic; and that a whole run is correct where sound and not
where its precision is the control's or its state is dropped.
"""
from __future__ import annotations

import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import flops, spec  # noqa: E402

NEMO_H = spec.arch("nemotron_h")
CELL = "nemotron-h-47b.decode_wide"
# two groups of four 16-wide heads, state 16, every layer kind, two
# attention layers (the pool holds those two only)
TINY = {"num_layers": 6, "pattern": "M*-M-*", "d_model": 64,
        "num_heads": 4, "num_kv_heads": 2, "head_dim": 16, "d_ff": 96,
        "vocab": 512, "norm_eps": 1e-5, "ssm_heads": 8, "ssm_head_dim": 16,
        "ssm_groups": 2, "ssm_state": 16, "ssm_conv": 4, "dt_min": 0.001,
        "dt_max": 0.1, "a_min": 1.0, "a_max": 16.0}
TINY_CONFIG = {"name": "tiny_h", "arch": "nemotron_h",
               "keys": {k: k for k in TINY}, **TINY}
TINY_TRAFFIC = {
    "loop": "closed", "clients": 2, "requests_per_client": 40,
    "sizes_seed": 0,
    "prompt": {"dist": "lognormal", "mean": 48, "sigma": 0.6, "min": 20,
               "max": 80},
    "output": {"dist": "lognormal", "mean": 32, "sigma": 0.6, "min": 16,
               "max": 48}}
TINY_ENGINE = {
    "placement": "attention_pool", "partition": "head",
    "attention_workers": 2, "decode_backend": "pallas", "kv_dtype": "bf16",
    "block_size": 16, "prefill_chunk_tokens": 32, "scheduler": "fcfs",
    "prefix_sharing": False, "max_batch": 2, "num_blocks": 32,
    "check": {"requests": 4, "max_gap": 0.05}}


def test_one_layer_drawn_again_is_the_stacked_layer():
    import jax

    from bench import weights

    key = weights.seed_key(2**33 + 13)
    whole = jax.jit(lambda k: weights.stacked(NEMO_H, TINY, k))(key)
    seen = {k: 0 for k in NEMO_H.KINDS}
    for l in range(TINY["num_layers"]):
        kind = NEMO_H.kind(TINY, l)
        one = weights.layer(NEMO_H, TINY, key, l)
        for n in NEMO_H.layer_leaves(TINY, kind):
            np.testing.assert_array_equal(
                np.asarray(one[n]),
                np.asarray(whole["layers"][kind][n][seen[kind]]))
        seen[kind] += 1
    assert seen == {"mamba": 2, "attention": 2, "mlp": 2}


def test_drawn_decay_and_step_lie_in_their_ranges():
    import jax

    from bench import weights

    key = weights.seed_key(5)
    w = jax.jit(lambda k: weights.stacked(NEMO_H, TINY, k))(key)
    A_log, dt_bias, D = NEMO_H.ssm_params(w["layers"]["mamba"], TINY)
    A = -np.exp(np.asarray(A_log))
    dt = np.asarray(jax.nn.softplus(dt_bias))
    assert (A <= -1.0).all() and (A >= -16.0).all()
    assert (dt >= 0.001 * 0.999).all() and (dt <= 0.1 * 1.001).all()
    assert np.isfinite(np.asarray(D)).all()


def test_reference_agrees_with_the_engine_through_chunks_and_decode():
    """Prompts of 3 and 1 chunks (the second's length not a multiple of
    the scan's sub-chunk), then decode through the pool and the state
    slots: every logit the engine sampled from, against the reference at
    the same position. The engine computes in bfloat16 (2^-8 relative
    rounding per operation); its logits stay within 2^-4 of the row's
    range, where a wrong slot, state or position moves them by the whole
    range."""
    import jax.numpy as jnp

    from bench import reference, system, weights

    seed = 9
    params = system.make_params(NEMO_H, TINY, seed)
    eng = system.build_engine(NEMO_H, "tiny_h", TINY, TINY_ENGINE, params)
    seen = []
    sample = eng._sample

    def keep(reqs, logits):
        seen.append(([r.rid for r in reqs],
                     [len(r.output) for r in reqs],
                     np.asarray(logits.astype(jnp.float32))))
        return sample(reqs, logits)

    eng._sample = keep
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, TINY["vocab"], n).astype(np.int32)
               for n in (80, 27)]
    reqs = [system.request(p, 6) for p in prompts]
    eng.submit(reqs)
    eng.run()
    assert all(len(r.output) == 6 for r in reqs)
    got = {r.rid: {} for r in reqs}
    for rids, done, rows in seen:
        for rid, k, row in zip(rids, done, rows):
            got[rid][k] = row
    seqs, rows = [], []
    for r, p in zip(reqs, prompts):
        toks, pos, _ = reference.served_rows(p, r.output)
        seqs.append(toks)
        rows.append(pos)
    key = weights.seed_key(seed)
    xs = reference.hidden_states(NEMO_H, TINY, key, seqs)
    ref = [np.asarray(x) for x in reference.head_rows(TINY, key, xs, rows)]
    for r, want in zip(reqs, ref):
        have = np.stack([got[r.rid][k] for k in range(6)])
        span = want.max(axis=1) - want.min(axis=1)
        err = np.abs(have - want).max(axis=1)
        assert (err <= span / 16).all(), (err, span)
        best = want.max(axis=1)
        at = want[np.arange(6), r.output]
        assert (best - at <= span / 64).all()


# ---------------------------------------------------------------------------
# counts
# ---------------------------------------------------------------------------
def test_state_update_and_scan_counts_by_hand():
    sz = spec.model_sizes(spec.resolve(CELL).config)
    H, P, G, N = 64, 64, 2, 256
    assert (sz["ssm_heads"], sz["ssm_head_dim"], sz["ssm_groups"],
            sz["ssm_state"]) == (H, P, G, N)
    # 9 Mamba layers; per row 5 operations a state element; the fp32
    # state read and written, x·dt and y (H·P), dt (H), B and C (G·N)
    upd = NEMO_H.ssm_decode(sz, [100, 50, 7])
    assert upd["flops"] == 9 * 3 * 5 * 1_048_576
    assert upd["bytes"] == 9 * 3 * 4 * (2 * 1_048_576 + 2 * 4096 + 64
                                        + 2 * 512)
    # a chunk after a prefix reads the state too; a first chunk does not
    scan = NEMO_H.ssm_chunk(sz, prefix=512, chunk=512)
    first = NEMO_H.ssm_chunk(sz, prefix=0, chunk=512)
    assert scan["flops"] == first["flops"] == 9 * 512 * 5 * 1_048_576
    per_token = 2 * 4096 + 64 + 2 * 512
    assert scan["bytes"] == 9 * 4 * (2 * 1_048_576 + 512 * per_token)
    assert first["bytes"] == 9 * 4 * (1_048_576 + 512 * per_token)
    # the update is bound by bytes
    peak = flops.peaks("TPU v5 lite")
    assert flops.roofline_seconds(upd, peak) == upd["bytes"] / 819e9
    # one attention layer of 16 query and 2 key/value heads
    att = NEMO_H.decode_attention(sz, [100, 50])
    assert att["flops"] == 4 * 16 * 128 * 150
    assert att["bytes"] == 2 * 2 * 128 * 2 * 150 + 2 * 2 * 16 * 128 * 2
    # matrix weights: Mamba in 8192 x (4096 + 5120 + 64), out 4096 x 8192;
    # MLP 2 x 8192 x 30720; attention 8192 x (2048 + 512) + 2048 x 8192
    mamba = 8192 * 9280 + 4096 * 8192
    mlp = 2 * 8192 * 30720
    attn = 8192 * 2560 + 2048 * 8192
    assert NEMO_H.layer_params(sz, "mamba") == mamba
    assert NEMO_H.layer_params(sz, "mlp") == mlp
    assert NEMO_H.layer_params(sz, "attention") == attn
    conv_scan = 2 * 4 * 5120 + 5 * 1_048_576
    step = NEMO_H.decode_step_flops(sz, [100, 50])
    assert step == (2 * 2 * (9 * mamba + 9 * mlp + attn + 8192 * 32768)
                    + 4 * 16 * 128 * 152 + 9 * 2 * conv_scan)
    assert NEMO_H.chunk_flops(sz, 0, 4) == (
        2 * 4 * (9 * mamba + 9 * mlp + attn) + 2 * 8192 * 32768
        + 4 * 16 * 128 * 10 + 9 * 4 * conv_scan)


# ---------------------------------------------------------------------------
# the cell
# ---------------------------------------------------------------------------
def test_the_cell_resolves_fits_the_chip_and_its_pool_holds_its_traffic():
    import jax

    from bench import system, weights
    from repro.models import ssm

    cell = spec.resolve(CELL)
    assert cell.arch is not None and cell.arch.KINDS == NEMO_H.KINDS
    sz = spec.model_sizes(cell.config)
    assert set(sz) == set(TINY)
    assert [NEMO_H.kind(sz, l) for l in range(19)].count("mamba") == 9
    assert [NEMO_H.kind(sz, l) for l in range(19)].count("mlp") == 9
    assert NEMO_H.kind(sz, 17) == "attention"
    names = {m.name for m in cell.per_layer}
    assert {"ssm_decode_roofline", "ssm_scan_roofline", "state_idle_ms",
            "mfu", "decode_attn_roofline", "device_idle_share",
            "compiles_in_window"} <= names
    for m in cell.per_layer:
        assert callable(spec.reader(m.name))
    eng, mix = cell.engine, cell.traffic
    worst = mix["clients"] * (mix["prompt"]["max"] + mix["output"]["max"])
    assert worst <= eng["num_blocks"] * eng["block_size"]
    assert eng["max_batch"] == mix["clients"]
    # the weights, the state slots and the pool, by shape alone
    shapes = jax.eval_shape(
        lambda k: system._make_impl(k, tuple(sorted(sz.items())), NEMO_H),
        weights.seed_key(1))
    params = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes))
    assert 6.0e9 < params < 6.2e9
    cfg = system.model_config(NEMO_H, "nemotron-h-47b", sz)
    state = ssm.state_slot_shapes(cfg, 9, eng["max_batch"])
    state_bytes = sum(int(np.prod(s)) * np.dtype(d).itemsize
                      for s, d in state.values())
    pool_bytes = (2 * eng["num_blocks"] * eng["block_size"]
                  * sz["num_kv_heads"] * sz["head_dim"] * 2)
    assert 2 * params + state_bytes + pool_bytes < 0.85 * 16e9


# ---------------------------------------------------------------------------
# a whole run on the CPU: sound, control and broken timed paths
# ---------------------------------------------------------------------------
def _tiny_cell():
    b = spec.benchmark()
    return spec.Cell("tiny_h.decode_wide", 1, TINY_CONFIG, NEMO_H,
                     TINY_TRAFFIC, dict(TINY_ENGINE),
                     [spec._metric(m) for m in b["end_to_end"]],
                     [spec._metric(m) for m in b["per_layer"]])


def _drop_state(engine):
    """A decode step whose recurrent state never lands: every step hands
    back a zeroed state, as if the slots were not written."""
    import jax
    import jax.numpy as jnp

    decode = engine._decode_jit

    def dropped(*a, **k):
        logits, updates = decode(*a, **k)
        updates["state"] = jax.tree.map(jnp.zeros_like, updates["state"])
        return logits, updates

    engine._decode_jit = dropped


@pytest.mark.parametrize("fault", [None, _drop_state])
def test_a_sound_run_is_correct_and_a_broken_or_control_one_is_not(fault):
    import jax

    from bench import run

    res = run.execute(_tiny_cell(), 21, 1.0, False, jax.devices()[:1],
                      {"bf16_flops": 1e12, "hbm_bytes_per_s": 1e11},
                      fault=fault, control=fault is None)
    assert res["correct"] is (fault is None), res["check"]
    if fault is None:
        assert res["control"]["correct"] is False, res["control"]
        assert res["metrics"]["output_tok_s"]["value"] > 0
