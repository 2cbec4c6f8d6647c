"""The engine's profiler spans and its own timers.

``LLMEngine.step`` writes fixed-name ``jax.profiler.TraceAnnotation`` spans
that a benchmark reads back from the profiler's trace (the names are a
contract, stated in ``serving/llm_engine.py``): every step is one
``engine.step`` holding ``engine.sched``, ``engine.pool``, ``engine.model``
and ``engine.sample``, on the thread that called it, and a step that raises
leaves none open. The engine's timers: ``Request.arrival_s`` is stamped at
``submit``, ``stats.step_times`` holds whole steps, and steps that built a
new executable count in ``stats.compile_steps`` instead.
"""
import time
from pathlib import Path

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData, TraceAnnotation

from repro.configs import registry
from repro.models import transformer
from repro.serving import (EngineConfig, LLMEngine, Request, SamplingParams,
                           SchedulingStalled)

PHASES = {"engine.sched", "engine.pool", "engine.model", "engine.sample"}
CALLER = "test.caller"


@pytest.fixture(scope="module")
def setup():
    cfg = registry.get_smoke_config("llama3-8b")
    params = transformer.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _requests(cfg, lens, new):
    rng = np.random.default_rng(0)
    return [Request(prompt=rng.integers(0, cfg.vocab_size, size=n).tolist(),
                    params=SamplingParams(max_new_tokens=new))
            for n in lens]


def _traced(tmp_path: Path, body):
    """Run ``body`` under the profiler inside a ``test.caller`` span;
    returns each host line of the trace as a list of (name, start, end)."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with TraceAnnotation(CALLER):
            body()
    finally:
        jax.profiler.stop_trace()
    data = ProfileData.from_file(str(next(tmp_path.rglob("*.xplane.pb"))))
    plane = next(p for p in data.planes if p.name == "/host:CPU")
    return [[(e.name, int(e.start_ns), int(e.start_ns + e.duration_ns))
             for e in line.events] for line in plane.lines]


def _caller_line(lines):
    """The host line of the thread that ran the body; no engine span may
    lie on any other."""
    calling = [l for l in lines if any(n == CALLER for n, _, _ in l)]
    assert len(calling) == 1
    for line in lines:
        if line is not calling[0]:
            assert not [n for n, _, _ in line if n.startswith("engine.")]
    return calling[0]


def test_every_step_is_one_engine_step_holding_the_four_phases(
        setup, tmp_path):
    cfg, params = setup
    eng = LLMEngine(cfg, params, EngineConfig(
        max_batch=4, num_blocks=64, block_size=8, prefill_chunk_tokens=16,
        decode_backend="jnp"))
    # the first prompt fits one chunk, so a request decodes in every step
    # while the second one's chunks run
    eng.submit(_requests(cfg, lens=(12, 40), new=12))
    n_steps = 5

    def steps():
        for _ in range(n_steps):
            eng.step()

    line = _caller_line(_traced(tmp_path, steps))
    engine = [e for e in line if e[0].startswith("engine.")]
    assert {n for n, _, _ in engine} == PHASES | {"engine.step"}
    outer = [e for e in engine if e[0] == "engine.step"]
    assert len(outer) == n_steps
    for i, (_, s, e) in enumerate(outer):
        inside = {n for n, a, b in engine if s <= a and b <= e} - {
            "engine.step"}
        assert inside == PHASES, (i, inside)
    # every phase span lies inside a step
    for n, a, b in engine:
        assert any(s <= a and b <= e for _, s, e in outer), (n, a, b)
    assert eng.stats.prefill_chunks_run >= 3


def test_a_step_that_raises_leaves_no_span_open(setup, tmp_path):
    cfg, params = setup
    eng = LLMEngine(cfg, params, EngineConfig(max_batch=2, num_blocks=4,
                                              block_size=8))
    eng.submit(Request(prompt=list(range(200)),
                       params=SamplingParams(max_new_tokens=4)))

    def stalled():
        with pytest.raises(SchedulingStalled):
            eng.step()
        with TraceAnnotation("test.after"):
            pass

    line = _caller_line(_traced(tmp_path, stalled))
    after = next(s for n, s, _ in line if n == "test.after")
    engine = [(n, s, e) for n, s, e in line if n.startswith("engine.")]
    # both spans the raise crossed were closed, before the next one opened
    assert sorted(n for n, _, _ in engine) == ["engine.sched", "engine.step"]
    assert all(e <= after for _, _, e in engine)


def test_arrival_is_stamped_at_submit(setup):
    cfg, params = setup
    eng = LLMEngine(cfg, params, EngineConfig(max_batch=2, num_blocks=64))
    req = _requests(cfg, lens=(5,), new=2)[0]
    assert req.arrival_s is None
    time.sleep(0.01)
    before = time.perf_counter()
    eng.submit(req)
    assert before <= req.arrival_s <= time.perf_counter()
    eng.run()
    assert req.arrival_s < req.first_token_s <= req.finish_s


def test_step_times_hold_whole_steps_and_leave_out_compile_steps(setup):
    cfg, params = setup
    eng = LLMEngine(cfg, params, EngineConfig(max_batch=2, num_blocks=64))
    retire = eng._retire

    def slow_retire():          # host work outside the model call
        time.sleep(0.02)
        retire()

    eng._retire = slow_retire
    eng.submit(_requests(cfg, lens=(5, 9), new=6))
    kinds = []
    while eng.has_work():
        timed, compiled = len(eng.stats.step_times), eng.stats.compile_steps
        eng.step()
        grew = (len(eng.stats.step_times) - timed,
                eng.stats.compile_steps - compiled)
        assert grew in ((1, 0), (0, 1))
        kinds.append("timed" if grew == (1, 0) else "compile")
    assert kinds[0] == "compile"        # the first step builds its programs
    assert "timed" in kinds
    s = eng.stats
    # each timed step retired twice, so it took at least 40 ms of host time
    assert min(s.step_times) >= 0.04
    assert s.timed_tokens <= 2 * 6
    assert s.throughput == pytest.approx(s.timed_tokens / sum(s.step_times))
    summary = s.summary()
    assert summary["compile_steps"] == kinds.count("compile")
    assert summary["mean_tbt_s"] == pytest.approx(np.mean(s.step_times))
