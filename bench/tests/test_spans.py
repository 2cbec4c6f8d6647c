"""Tests of the readers that put device idle time down to the engine's
spans (``bench/spans.py``, ``bench/metrics/*_idle_ms.py``), on hand-made
traces and on the recorded chip trace, without a chip.

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests
"""
from __future__ import annotations

import sys
import types
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import spans, spec, trace  # noqa: E402

MS = 1_000_000
READERS = {"sched_idle_ms": "engine.sched", "pool_idle_ms": "engine.pool",
           "model_idle_ms": "engine.model", "sample_idle_ms": "engine.sample"}
FIXTURE = ROOT / "bench" / "fixtures" / "decode_long_trace.json.gz"
# 0.5 s of a traced window of mistral-nemo-12b.decode_long on a TPU v5e,
# from a program that writes the engine's spans
SPANS_FIXTURE = ROOT / "bench" / "fixtures" / "decode_long_spans_trace.json.gz"


def _run(steps: int = 2, traced: bool = True):
    return types.SimpleNamespace(trace=object() if traced else None,
                                 steps=[None] * steps)


def _hand_made() -> trace.Trace:
    """Two devices: the first idle over [10, 30), [50, 90) and [95, 100)
    ms, the second never idle. One engine step over [5, 95) with a model
    call nested in the scheduler's span, and a host event that is no
    engine span."""
    return trace.Trace(
        devices={"/device:TPU:0": [("fusion.1", 0, 10 * MS),
                                   ("paged_decode", 30 * MS, 20 * MS),
                                   ("copy.2", 90 * MS, 5 * MS)],
                 "/device:TPU:1": [("fusion.1", 0, 100 * MS)]},
        host=[("bench.window", 0, 100 * MS),
              ("bench.step", 0, 100 * MS),
              ("engine.step", 5 * MS, 90 * MS),
              ("engine.sched", 5 * MS, 10 * MS),
              ("engine.model", 8 * MS, 4 * MS),
              ("engine.pool", 15 * MS, 10 * MS),
              ("engine.model", 25 * MS, 35 * MS),
              ("PjitFunction(decode_step)", 26 * MS, 2 * MS),
              ("engine.sample", 60 * MS, 20 * MS),
              ("engine.pool", 80 * MS, 12 * MS)])


def test_segments_take_the_innermost_span():
    segs = spans.segments(_hand_made().host)
    assert segs == [(5 * MS, 8 * MS, "engine.sched"),
                    (8 * MS, 12 * MS, "engine.model"),
                    (12 * MS, 15 * MS, "engine.sched"),
                    (15 * MS, 25 * MS, "engine.pool"),
                    (25 * MS, 60 * MS, "engine.model"),
                    (60 * MS, 80 * MS, "engine.sample"),
                    (80 * MS, 92 * MS, "engine.pool"),
                    (92 * MS, 95 * MS, "engine.step")]


def test_idle_is_integrated_over_the_spans_it_crosses():
    # device 0: [10, 30) splits 2 model, 3 sched, 10 pool, 5 model;
    # [50, 90) splits 10 model, 20 sample, 10 pool; [95, 100) is under no
    # engine span. Device 1 adds nothing, and the two are averaged.
    split = spans.idle_by_span(_hand_made())
    assert split == {"engine.model": pytest.approx(0.0085),
                     "engine.sched": pytest.approx(0.0015),
                     "engine.pool": pytest.approx(0.010),
                     "engine.sample": pytest.approx(0.010)}


def test_readers_give_the_exact_ms_per_step(tmp_path, monkeypatch):
    saved = tmp_path / "last_trace.json.gz"
    trace.save(_hand_made(), saved)
    monkeypatch.setattr(spans, "SAVED", saved)
    want = {"sched_idle_ms": 0.75, "pool_idle_ms": 5.0,
            "model_idle_ms": 4.25, "sample_idle_ms": 5.0}
    got = {name: spec.reader(name)(_run(steps=2)) for name in READERS}
    assert got == {k: pytest.approx(v) for k, v in want.items()}
    # their sum stays within the idle time per step of the whole window
    tr = trace.read_saved(saved)
    r = trace.reduce(tr, *trace.window(tr))
    assert sum(got.values()) <= 1e3 * (r.window_s - r.busy_s) / 2
    # an untraced run reads nothing, whatever trace lies on disk
    assert all(spec.reader(n)(_run(traced=False)) is None for n in READERS)


def test_every_reader_is_listed_with_its_layer():
    listed = {m["name"]: m for m in spec.benchmark()["per_layer"]}
    for name in READERS:
        m = listed[name]
        assert (m["unit"], m["better"], m["source"], m["moves"]) == (
            "ms", "lower", "program_span", "output_tok_s")
        assert "workloads" not in m


@pytest.mark.skipif(not FIXTURE.exists(), reason="no recorded trace")
def test_a_trace_without_engine_spans_reads_none(monkeypatch):
    assert spans.idle_by_span(trace.read_saved(FIXTURE)) is None
    monkeypatch.setattr(spans, "SAVED", FIXTURE)
    for name in READERS:
        assert spec.reader(name)(_run()) is None


@pytest.mark.skipif(not SPANS_FIXTURE.exists(), reason="no recorded trace")
def test_readers_on_a_chip_trace_with_engine_spans(monkeypatch):
    tr = trace.read_saved(SPANS_FIXTURE)
    t0, t1 = trace.window(tr)
    r = trace.reduce(tr, t0, t1)
    steps = sum(1 for n, s, _ in tr.host
                if n == "engine.step" and t0 <= s < t1)
    assert steps >= 3
    monkeypatch.setattr(spans, "SAVED", SPANS_FIXTURE)
    got = {name: spec.reader(name)(_run(steps)) for name in READERS}
    assert all(v is not None and v >= 0 for v in got.values()), got
    # the sampler holds the device idle longest in this cell
    assert max(got, key=got.get) == "sample_idle_ms"
    # the four phases hold nearly all the idle time inside engine.step,
    # and no more than the window's idle time
    split = spans.idle_by_span(tr)
    inside = sum(split.values())
    assert sum(got.values()) >= 0.9 * 1e3 * inside / steps
    assert sum(got.values()) <= 1e3 * (r.window_s - r.busy_s) / steps
    # the kernels the roofline readers match keep their names
    assert r.seconds_matching(("paged_decode",)) > 0
