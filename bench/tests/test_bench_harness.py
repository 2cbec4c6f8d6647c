"""Tests of the benchmark harness, on the CPU and without a chip.

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests

They check that every cell resolves by name and that a cell added as files
alone resolves too; that an architecture added as files alone, with two
layer kinds, runs end to end; the operation and byte counts against
hand-worked shapes; the trace reduction on a hand-made trace and on one
recorded on a TPU v5e; that a machine with no TPU gets no result; that the
weights and the reference give the bits they gave before the dense block
moved into ``bench/arch/dense.py``; that the reference agrees with
``LLMEngine`` through chunked prefill and paged decode at tiny widths; and
that a run whose timed path is broken, or whose precision is the
control's, comes out not correct.
"""
from __future__ import annotations

import hashlib
import inspect
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import flops, loadgen, spec, trace  # noqa: E402

NEMO = {"num_layers": 8, "d_model": 5120, "num_heads": 32,
        "num_kv_heads": 8, "head_dim": 128, "d_ff": 14336, "vocab": 131072,
        "norm_eps": 1e-5, "rope_theta": 1e6}
TINY = {"num_layers": 2, "d_model": 64, "num_heads": 4, "num_kv_heads": 2,
        "head_dim": 16, "d_ff": 128, "vocab": 2048, "norm_eps": 1e-5,
        "rope_theta": 10000.0}
TINY_CONFIG = {"name": "tiny", "arch": "dense", "keys": {k: k for k in TINY},
               **TINY}
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


DENSE = spec.arch("dense")


# ---------------------------------------------------------------------------
# resolving cells by name
# ---------------------------------------------------------------------------
def test_every_cell_resolves_and_fits_its_pool():
    b = spec.benchmark()
    assert b["paths"] == ["bench"]
    for w in b["workloads"]:
        cell = spec.resolve(w["name"])
        sz = spec.model_sizes(cell.config)
        assert set(sz) == set(TINY), w["name"]
        assert cell.config["name"] == w["config"]
        assert {m.name for m in cell.end_to_end} >= {"setup_s"}
        assert cell.per_layer, w["name"]
        for m in cell.per_layer:
            assert callable(spec.reader(m.name))
        eng = cell.engine
        mix = cell.traffic
        worst = mix["clients"] * (mix["prompt"]["max"] + mix["output"]["max"])
        assert worst <= eng["num_blocks"] * eng["block_size"]
        assert (cell.traffic["prompt"]["max"]
                + cell.traffic["output"]["max"]) % eng["block_size"] == 0


def test_config_files_hold_what_benchmark_json_says():
    b = spec.benchmark()
    for c in b["configs"]:
        conf = json.loads((ROOT / c["file"]).read_text())
        assert conf["source"] == c["source"]
        assert conf["reduced"] == c["reduced"]
        for key, was in conf["reduced_from"].items():
            assert conf[key] != was


def _copy_harness(tmp_path):
    """BENCHMARK.json and bench/, without its tests, under tmp_path."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    return tmp_path / "bench"


def _new_file(path: Path, text: str) -> None:
    assert not path.exists(), path
    path.write_text(text)


def _add_cell(root, config, traffic=TINY_TRAFFIC, engine=TINY_ENGINE):
    """A configuration, a traffic mix and their cell, added to the harness
    under ``root`` as new files and BENCHMARK.json entries; returns the
    cell's name."""
    name, mix = config["name"], "tiny_mix"
    bench = root / "bench"
    _new_file(bench / "configs" / f"{name}.json", json.dumps(config))
    if not (bench / "traffic" / f"{mix}.json").exists():
        _new_file(bench / "traffic" / f"{mix}.json", json.dumps(traffic))
    _new_file(bench / "cells" / f"{name}.{mix}.json", json.dumps(engine))
    b = json.loads((root / "BENCHMARK.json").read_text())
    b["configs"].append({"name": name, "source": "https://example.org",
                         "file": f"bench/configs/{name}.json", "reduced": [],
                         "why": "test"})
    b["workloads"].append({"name": f"{name}.{mix}", "config": name,
                           "traffic": mix, "chips": 1, "why": "test"})
    (root / "BENCHMARK.json").write_text(json.dumps(b))
    return f"{name}.{mix}"


def test_a_cell_added_as_files_resolves(tmp_path):
    bench = _copy_harness(tmp_path)
    _add_cell(tmp_path, TINY_CONFIG)
    _new_file(bench / "metrics" / "steps_in_window.py",
              "def read(run):\n    return len(run.steps) or None\n")
    b = json.loads((tmp_path / "BENCHMARK.json").read_text())
    b["per_layer"].append({"name": "steps_in_window", "unit": "count",
                           "better": "higher", "source": "host_clock",
                           "layer": "engine loop", "moves": "output_tok_s",
                           "workloads": ["tiny.tiny_mix"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(b))

    cell = spec.resolve("tiny.tiny_mix", root=tmp_path)
    assert spec.model_sizes(cell.config) == TINY
    assert cell.traffic == TINY_TRAFFIC and cell.engine == TINY_ENGINE
    names = [m.name for m in cell.per_layer]
    assert "steps_in_window" in names and "mfu" in names
    assert "steps_in_window" not in [
        m.name for m in spec.resolve(b["workloads"][0]["name"],
                                     root=tmp_path).per_layer]
    lists = loadgen.closed_loop(cell.traffic, TINY["vocab"], 5)
    assert len(lists) == 2 and len(lists[0]) == 40

    class FakeRun:
        steps = [1, 2, 3]
    assert spec.reader("steps_in_window", root=tmp_path)(FakeRun()) == 3
    with pytest.raises(KeyError):
        spec.resolve("tiny.no_such_mix", root=tmp_path)
    assert cell.arch.KINDS == ("dense",)


def test_a_config_must_name_its_arch(tmp_path):
    _copy_harness(tmp_path)
    conf = {k: v for k, v in TINY_CONFIG.items() if k != "arch"}
    name = _add_cell(tmp_path, conf)
    with pytest.raises(KeyError, match="arch"):
        spec.resolve(name, root=tmp_path)
    conf["arch"] = "no_such_block"
    (tmp_path / "bench" / "configs" / "tiny.json").write_text(
        json.dumps(conf))
    with pytest.raises(FileNotFoundError, match="no_such_block"):
        spec.resolve(name, root=tmp_path)


def test_a_cell_file_key_the_engine_does_not_know_is_an_error():
    from bench import system

    with pytest.raises(TypeError, match="num_blokcs"):
        system.build_engine(DENSE, "tiny", TINY,
                            dict(TINY_ENGINE, num_blokcs=32), None)


def test_every_seed_gets_the_same_sizes():
    mix = spec.resolve(spec.benchmark()["workloads"][0]["name"]).traffic
    sizes = []
    for seed in (1, 2**33 + 5):
        lists = loadgen.closed_loop(mix, 1000, seed)
        sizes.append(sorted((len(r.prompt), r.max_new_tokens)
                            for lst in lists for r in lst))
        assert all(mix["prompt"]["min"] <= len(r.prompt)
                   <= mix["prompt"]["max"] for lst in lists for r in lst)
    assert sizes[0] == sizes[1]
    a = loadgen.closed_loop(mix, 1000, 1)
    b = loadgen.closed_loop(mix, 1000, 1)
    assert all((x.prompt == y.prompt).all() for p, q in zip(a, b)
               for x, y in zip(p, q))


def test_every_gap_that_ends_in_the_window_counts():
    from bench import clients

    def track(times):
        return clients.Track(None, None, 0.0, times)

    # a token every 0.1 s from 0.0 to 2.0, and one stall of 0.5 s; the
    # window is (0.45, 1.85]
    steady = track([round(0.1 * i, 3) for i in range(21)])
    stalled = track([0.0, 0.1, 0.6, 0.7, 0.8, 0.9])
    got = clients.end_to_end([steady, stalled], 0.45, 1.85)
    # 14 gaps of the first end in the window (0.5 .. 1.8), all of 0.1 s;
    # of the second, 0.5 (ending at 0.6) and three of 0.1 s
    assert got["gaps"] == 18 and got["tokens"] == 18
    assert got["output_tok_s"] == pytest.approx(18 / 1.4)
    gaps = [0.1] * 17 + [0.5]
    assert got["tbt_p99_ms"] == pytest.approx(np.percentile(gaps, 99) * 1e3)
    # a request whose first token comes in the window has no gap there
    assert clients.end_to_end([track([1.0])], 0.45, 1.85)["tbt_p99_ms"] \
        is None


# ---------------------------------------------------------------------------
# operations, bytes and peaks
# ---------------------------------------------------------------------------
def test_flop_and_byte_counts_by_hand():
    # 5120·(4096 + 2·1024) + 4096·5120 + 3·5120·14336
    assert DENSE.layer_params(NEMO) == 272_629_760
    # the attention counts are one layer's, in each of the 8 layers
    att = DENSE.decode_attention(NEMO, [100, 50])
    assert att["flops"] == 8 * 4 * 32 * 128 * 150 == 8 * 2_457_600
    # K and V of 150 tokens on 8 heads, plus q and out of 2 rows
    assert att["bytes"] == 8 * (614_400 + 32_768)
    ch = DENSE.chunk_attention(NEMO, prefix=512, chunk=512)
    # 512·512 pooled pairs and 512·513/2 causal pairs in the chunk
    assert ch["flops"] == 8 * 4 * 32 * 128 * 393_472 == 8 * 6_446_645_248
    assert ch["bytes"] == 8 * (4_194_304 + 8_388_608)
    step = DENSE.decode_step_flops(NEMO, [100, 50])
    assert step == 2 * 2 * (8 * 272_629_760 + 5120 * 131072) \
        + 8 * 4 * 32 * 128 * 152
    assert DENSE.chunk_flops(NEMO, 0, 4) == (
        2 * 4 * 8 * 272_629_760 + 2 * 5120 * 131072 + 8 * 4 * 32 * 128 * 10)
    peak = flops.peaks("TPU v5 lite")
    assert peak["bf16_flops"] == 197e12 and peak["hbm_bytes_per_s"] == 819e9
    # decode attention is bound by bytes, a full chunk by operations
    assert flops.roofline_seconds(att, peak) == att["bytes"] / 819e9
    assert flops.roofline_seconds(ch, peak) == ch["flops"] / 197e12


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError, match="TPU v99"):
        flops.peaks("TPU v99")
    with pytest.raises(KeyError):
        flops.peaks("cpu")


# ---------------------------------------------------------------------------
# trace reduction
# ---------------------------------------------------------------------------
def test_trace_reduction_by_hand():
    ms = 1_000_000
    tr = trace.Trace(
        devices={"/device:TPU:0": [("fusion.1", 0, 10 * ms),
                                   ("paged_decode", 5 * ms, 10 * ms),
                                   ("copy.2", 40 * ms, 20 * ms),
                                   ("late", 95 * ms, 20 * ms)]},
        host=[("bench.window", 0, 100 * ms),
              ("bench.step", 0, 50 * ms),
              ("PjitFunction(step)", 16 * ms, 20 * ms),
              ("bench.step", 60 * ms, 40 * ms)])
    assert trace.window(tr) == (0, 100 * ms)
    r = trace.reduce(tr, 0, 100 * ms)
    assert r.window_s == pytest.approx(0.1)
    # union [0, 15) + [40, 60) + [95, 100): 40 ms busy
    assert r.busy_s == pytest.approx(0.040)
    assert r.seconds_matching(("paged_decode",)) == pytest.approx(0.010)
    assert r.op_seconds["late"] == pytest.approx(0.005)    # clipped
    assert r.top_ops(1) == [("copy.2", pytest.approx(0.020))]
    # gaps: [15, 40) in the jitted call, [60, 95) in the second step
    assert r.idle_gaps == [("bench.step", pytest.approx(0.035)),
                           ("PjitFunction(step)", pytest.approx(0.025))]


FIXTURE = ROOT / "bench" / "fixtures" / "decode_long_trace.json.gz"


@pytest.mark.skipif(not FIXTURE.exists(), reason="no recorded trace")
def test_trace_reduction_on_a_chip_trace():
    tr = trace.read_saved(FIXTURE)
    t0, t1 = trace.window(tr)
    r = trace.reduce(tr, t0, t1)
    assert 0 < r.busy_s <= r.window_s
    ops = r.top_ops(10)
    assert [s for _, s in ops] == sorted((s for _, s in ops), reverse=True)
    assert sum(r.op_seconds.values()) >= r.busy_s
    # every kernel that a listed roofline reader matches is in the trace,
    # under the instruction name the TPU gives it
    for m in spec.benchmark()["per_layer"]:
        patterns = getattr(inspect.getmodule(spec.reader(m["name"])),
                           "KERNEL", None)
        if patterns is not None:
            assert r.seconds_matching(patterns) > 0, m["name"]
    assert "paged_decode_attention.2" in r.op_seconds
    assert r.idle_gaps and all(s > 0 for _, s in r.idle_gaps)


# ---------------------------------------------------------------------------
# no chip, no result
# ---------------------------------------------------------------------------
def test_a_machine_with_no_tpu_gets_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    cell = spec.benchmark()["workloads"][0]["name"]
    p = subprocess.run(
        [sys.executable, str(ROOT / "bench" / "run.py"), "--workload", cell,
         "--seed", "3", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode == 3, p.stderr[-2000:]
    assert p.stdout == ""
    assert "TPU" in p.stderr


def test_the_benchmark_alone_gets_no_result(tmp_path):
    """A checkout of BENCHMARK.json and ``bench/`` alone, without the
    program under test, exits non-zero and prints no result."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    cell = spec.benchmark()["workloads"][0]["name"]
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", cell, "--seed", "3",
         "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=dict(env, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=300)
    assert p.returncode not in (0, 3), p.stderr[-2000:]
    assert p.stdout == ""
    assert "No module named 'repro'" in p.stderr


# ---------------------------------------------------------------------------
# weights and the reference, against the program, on the CPU
# ---------------------------------------------------------------------------
def test_one_layer_drawn_again_is_the_stacked_layer():
    import jax

    from bench import weights

    key = weights.seed_key(2**33 + 11)
    whole = jax.jit(lambda k: weights.stacked(DENSE, TINY, k))(key)
    for l in range(TINY["num_layers"]):
        one = weights.layer(DENSE, TINY, key, l)
        for n in DENSE.layer_leaves(TINY, "dense"):
            np.testing.assert_array_equal(
                np.asarray(one[n]), np.asarray(whole["layers"]["dense"][n][l]))
    np.testing.assert_array_equal(
        np.asarray(weights.global_weight(TINY, key, "lm_head")),
        np.asarray(whole["lm_head"]))


# sha256 of the bits at TINY sizes, seed 2**33 + 11, as the tree of commit
# d1ae6c994985498026b5e47df3b2c032a55f3294 gave them on the CPU, before the
# dense block moved into bench/arch/dense.py: every weight of ``stacked``
# in leaf order, each stacked over all layers, and the float32 reference
# logits at every position of 40 tokens drawn by default_rng(7)
PINNED_WEIGHTS = \
    "038a25d32af0b32b14d04fee8396655b6d10965de36df278f486bc0e468c0724"
PINNED_LOGITS = \
    "7d7386d2f80bd6b16946b45bd4fd4beeb041446e17e538eb071092bece089602"


def test_the_dense_weights_and_reference_give_the_pinned_bits():
    import jax

    from bench import reference, weights

    key = weights.seed_key(2**33 + 11)
    whole = jax.jit(lambda k: weights.stacked(DENSE, TINY, k))(key)
    flat = dict(whole["layers"]["dense"], **{n: whole[n]
                                             for n in weights.GLOBAL})
    h = hashlib.sha256()
    for n in weights.leaf_names(DENSE, TINY):
        h.update(np.asarray(flat[n]).tobytes())
    assert h.hexdigest() == PINNED_WEIGHTS
    seq = np.random.default_rng(7).integers(0, TINY["vocab"], 40)
    xs = reference.hidden_states(DENSE, TINY, key, [seq.astype(np.int32)])
    logits = reference.head_rows(TINY, key, xs, [np.arange(40)])[0]
    assert hashlib.sha256(np.asarray(logits, np.float32).tobytes()
                          ).hexdigest() == PINNED_LOGITS


def test_reference_agrees_with_the_engine_through_chunks_and_decode():
    """Prompts of 3 and 1 chunks, then paged decode: every logit the
    engine sampled from, against the reference at the same position. The
    engine computes in bfloat16 (2^-8 relative rounding per operation);
    its logits stay within 2^-4 of the row's range, where a wrong block,
    position or head moves them by the whole range."""
    import jax.numpy as jnp

    from bench import reference, system, weights

    seed = 9
    params = system.make_params(DENSE, TINY, seed)
    eng = system.build_engine(DENSE, "tiny", TINY, TINY_ENGINE, params)
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
    xs = reference.hidden_states(DENSE, TINY, key, seqs)
    ref = [np.asarray(x) for x in reference.head_rows(TINY, key, xs, rows)]
    for r, want in zip(reqs, ref):
        have = np.stack([got[r.rid][k] for k in range(6)])
        span = want.max(axis=1) - want.min(axis=1)
        err = np.abs(have - want).max(axis=1)
        assert (err <= span / 16).all(), (err, span)
        # the served tokens are the reference's best, or within rounding
        best = want.max(axis=1)
        at = want[np.arange(6), r.output]
        assert (best - at <= span / 64).all()


# ---------------------------------------------------------------------------
# a whole run on the CPU: sound, control and broken timed paths
# ---------------------------------------------------------------------------
def _tiny_cell(check=None):
    b = spec.benchmark()
    eng = dict(TINY_ENGINE)
    if check is not None:
        eng["check"] = check
    return spec.Cell("tiny.decode_long", 1, TINY_CONFIG, DENSE, TINY_TRAFFIC,
                     eng,
                     [spec._metric(m) for m in b["end_to_end"]],
                     [spec._metric(m) for m in b["per_layer"]])


def _execute(**kw):
    import jax

    from bench import run

    return run.execute(_tiny_cell(), 21, 1.0, False, jax.devices()[:1],
                       {"bf16_flops": 1e12, "hbm_bytes_per_s": 1e11}, **kw)


def _alter_tokens(engine):
    """A token altered where it is produced: every fourth sampling call
    hands back the next id in the vocabulary."""
    sample, calls = engine._sample, [0]

    def altered(reqs, logits):
        toks = sample(reqs, logits)
        calls[0] += 1
        if calls[0] % 4 == 0:
            toks = (toks + 1) % TINY["vocab"]
        return toks

    engine._sample = altered


def _drop_pool_writes(engine):
    """A step that returns its state unchanged: decode's pool write
    never lands, so later steps read stale keys and values."""
    engine.kv.write_tokens = lambda *a, **k: None


def _half_batch(engine):
    """Half of the batch left out: the decode step's rows past the first
    half get the first row's logits."""
    decode = engine._decode_jit

    def half(*a, **k):
        logits, updates = decode(*a, **k)
        h = (logits.shape[0] + 1) // 2
        return logits.at[h:].set(logits[0]), updates

    engine._decode_jit = half


def test_a_sound_run_is_correct_and_the_control_is_not():
    res = _execute(control=True)
    limit = TINY_ENGINE["check"]["max_gap"]
    assert res["correct"], res["check"]
    assert res["check"]["max_gap"]["value"] <= limit
    # the control is judged by the run's own limit, and fails it
    assert res["control"]["correct"] is False, res["control"]
    assert res["control"]["max_gap"] > limit
    assert res["metrics"]["output_tok_s"]["value"] > 0


@pytest.mark.parametrize("fault", [_alter_tokens, _drop_pool_writes,
                                   _half_batch])
def test_a_broken_timed_path_is_not_correct(fault):
    res = _execute(fault=fault)
    assert not res["correct"], res["check"]
    assert list(res)[-1] == "check"


# ---------------------------------------------------------------------------
# an architecture added as files: two layer kinds, served end to end
# ---------------------------------------------------------------------------
SWA_ARCH = '''"""Dense blocks in two layer kinds that alternate: even layers attend
within a sliding window of ``sliding_window`` tokens, odd layers to the
whole context (the program's ``local_global``)."""
import functools
from pathlib import Path

import jax
import jax.numpy as jnp

from bench import flops, spec
from bench.reference import attention, mm, rms_norm, rope

dense = spec.arch("dense", Path(__file__).resolve().parents[2])
KINDS = ("local", "global")


def kind(sz, l):
    return "local" if l % 2 == 0 else "global"


def layer_leaves(sz, kind):
    return dense.layer_leaves(sz, "dense")


def program(sz):
    return dict(dense.program(sz), sliding_window=sz["sliding_window"],
                local_global=True)


def program_tree(sz, w):
    """The two kinds' stacks interleaved into one over every layer."""
    loc, glo = w["layers"]["local"], w["layers"]["global"]
    both = {n: jnp.stack([loc[n], glo[n]], 1).reshape(
        (sz["num_layers"],) + loc[n].shape[1:]) for n in loc}
    return dense.program_tree(sz, dict(w, layers={"dense": both}))


def _window(sz, l):
    return sz["sliding_window"] if kind(sz, l) == "local" else 0


def layer_forward(w, x, sz, l, control=False):
    return _forward(w, x, sz, control, _window(dict(sz), l))


@functools.partial(jax.jit, static_argnames=("sz", "control", "window"))
def _forward(w, x, sz, control, window):
    sz = dict(sz)
    S, eps, theta = x.shape[0], sz["norm_eps"], sz["rope_theta"]
    H, K, h = sz["num_heads"], sz["num_kv_heads"], sz["head_dim"]
    a = rms_norm(x, w["norm1"], eps)
    q = rope(mm(a, w["wq"], control).reshape(S, H, h), theta)
    k = rope(mm(a, w["wk"], control).reshape(S, K, h), theta)
    v = mm(a, w["wv"], control).reshape(S, K, h)
    x = x + mm(attention(q, k, v, control, window), w["wo"], control)
    a = rms_norm(x, w["norm2"], eps)
    gate = jax.nn.silu(mm(a, w["w_gate"], control))
    return x + mm(gate * mm(a, w["w_up"], control), w["w_down"], control)


def _keys(sz, l, p):
    """Keys the query at position p sees in layer l, itself among them."""
    w = _window(sz, l)
    return min(p + 1, w) if w else p + 1


def _pairs(sz, l, prefix, chunk):
    return sum(_keys(sz, l, p) for p in range(prefix, prefix + chunk))


def _attend(sz, pairs, keys, queries):
    K, H, h = sz["num_kv_heads"], sz["num_heads"], sz["head_dim"]
    return {"flops": flops.attention_flops(sz, pairs),
            "bytes": (2 * K * keys + 2 * H * queries) * h * flops.BF16}


def _sum(works):
    return {k: sum(w[k] for w in works) for k in ("flops", "bytes")}


def decode_attention(sz, lens):
    stored = [[_keys(sz, l, n) - 1 for n in lens]
              for l in range(sz["num_layers"])]
    return _sum(_attend(sz, sum(s), sum(s), len(lens)) for s in stored)


def chunk_attention(sz, prefix, chunk):
    return _sum(_attend(sz, _pairs(sz, l, prefix, chunk),
                        _keys(sz, l, prefix + chunk - 1), chunk)
                for l in range(sz["num_layers"]))


def decode_step_flops(sz, lens):
    L, rows = sz["num_layers"], len(lens)
    weights = 2 * rows * (L * dense.layer_params(sz)
                          + sz["d_model"] * sz["vocab"])
    return weights + sum(flops.attention_flops(
        sz, sum(_keys(sz, l, n) for n in lens)) for l in range(L))


def chunk_flops(sz, prefix, chunk):
    L = sz["num_layers"]
    weights = (2 * chunk * L * dense.layer_params(sz)
               + 2 * sz["d_model"] * sz["vocab"])
    return weights + sum(flops.attention_flops(
        sz, _pairs(sz, l, prefix, chunk)) for l in range(L))
'''
SWA = dict(TINY, num_layers=4, sliding_window=24)
SWA_CONFIG = {"name": "tiny_swa", "arch": "dense_swa",
              "keys": {k: k for k in SWA}, **SWA}


@pytest.fixture(scope="module")
def swa_tree(tmp_path_factory):
    """A copy of the harness with the two-kind architecture, its
    configuration and its cell added as files and entries, and no file of
    the harness edited; returns its root and the cell's name."""
    root = tmp_path_factory.mktemp("swa")
    bench = _copy_harness(root)
    _new_file(bench / "arch" / "dense_swa.py", SWA_ARCH)
    return root, _add_cell(root, SWA_CONFIG)


def test_the_readers_count_through_the_cells_arch(swa_tree):
    from bench import run

    root, name = swa_tree
    cell = spec.resolve(name, root=root)
    assert cell.arch.KINDS == ("local", "global")

    class Step:
        decode_lens = [40, 10]
        chunks = [(32, 32)]

    class Trace:
        def seconds_matching(self, patterns):
            return 1e-3

    def read(metric, arch, sizes):
        r = run.Run(arch, sizes, {"bf16_flops": 1e12,
                                  "hbm_bytes_per_s": 1e11},
                    1.0, [Step()], None, Trace())
        return spec.reader(metric, root=root)(r)

    # the window's layers see fewer keys than the dense block's
    for metric in ("mfu", "decode_attn_roofline", "chunk_attn_roofline"):
        assert 0 < read(metric, cell.arch, SWA) < read(metric, DENSE, SWA)


@pytest.mark.parametrize("fault", [None, _alter_tokens, _drop_pool_writes])
def test_an_arch_added_as_files_runs_end_to_end(swa_tree, fault):
    """Two layer kinds, each stacked on its own, served by the program's
    alternating window and full layers through chunked prefill and paged
    decode, and judged by the architecture's own reference."""
    import jax

    from bench import run

    root, name = swa_tree
    res = run.execute(spec.resolve(name, root=root), 23, 1.0, False,
                      jax.devices()[:1],
                      {"bf16_flops": 1e12, "hbm_bytes_per_s": 1e11},
                      fault=fault)
    assert res["correct"] is (fault is None), res["check"]
