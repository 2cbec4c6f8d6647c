"""Run one benchmark cell on the chip and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up makes the weights on the device from the seed, builds the engine,
and warms every program the window will use: it serves the cell's traffic
once (the warm pass) until its steps that compiled nothing add up to
``WARM_WINDOWS`` windows, then cancels it and serves the same traffic
again from the start. The window measures for ``--seconds``; with
``--trace 1`` a profiler trace of it gives the per-layer metrics instead
of the end-to-end ones. Once the window has closed and the served state is
freed, a float32 reference recomputes the logits behind a sample of the
served tokens, and ``correct`` says whether each served token lay within
the cell's limit of the reference's best.

Exits with 3, printing nothing on standard output, where JAX finds no TPU
or fewer chips than the cell asks for; a checkout without the program
fails on importing it, before any chip is looked for. The last line of standard output is
one JSON object.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import os
import shutil
import sys
import time
from pathlib import Path

T_START = time.perf_counter()
ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import spec  # noqa: E402

CACHE = ROOT / ".bench_cache"
# the warm pass covers this many windows of steps that compiled nothing; a
# closed loop's steps do not depend on timing, so the window, which runs
# the same traffic again, meets only programs that the warm pass built
WARM_WINDOWS = 1.5


@dataclasses.dataclass
class Run:
    """What the per-layer readers read."""
    arch: object             # the cell's bench/arch/<arch>.py
    sizes: dict
    peak: dict
    window_s: float
    steps: list              # clients.StepRecord of every step in the window
    compiles: object         # compilelog.Counts during the window
    trace: object = None     # trace.Reduction of the window, when traced


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def use_cache() -> None:
    """JAX's persistent compilation cache at a fixed path in the checkout,
    every program in it, however quick its compile."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE / "jax")
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CACHE / "jax"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def chips(cell) -> list:
    """The devices the cell runs on; exits 3 where they are not TPUs."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        log(f"needs {cell.chips} TPU chip(s); JAX found "
            f"{len(devices)} {devices[0].platform} device(s); nothing run")
        sys.exit(3)
    return devices


def sample(tracks, k: int, seed: int, finished):
    """The served requests the check compares: the longest finished one,
    and k - 1 other finished ones drawn from the seed; where fewer than k
    finished, the requests in flight with the most served tokens make up
    the number."""
    import numpy as np

    size = lambda t: len(t.spec.prompt) + len(t.times)  # noqa: E731
    done = sorted((t for t in tracks if finished(t.req)), key=size)
    live = sorted((t for t in tracks if t.times and not finished(t.req)),
                  key=size)
    pick, rest = done[-1:], done[:-1]
    rng = np.random.default_rng(seed)
    pick += [rest[i] for i in rng.permutation(len(rest))[:k - 1]]
    pick += live[::-1][:k - len(pick)]
    return [(t.spec.prompt, t.served) for t in pick]


def judge(gap: float, limit: float) -> bool:
    """The check: no served token lies more than ``limit`` below the
    reference's best logit."""
    return gap <= limit


def traced_window(loop, seconds: float):
    """Run the window under the profiler; returns the steps and the
    reduced trace."""
    import jax

    from bench import trace

    out = CACHE / "trace"
    shutil.rmtree(out, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(out), profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation(trace.WINDOW_SPAN):
            steps, t0, t1 = window(loop, seconds)
    finally:
        jax.profiler.stop_trace()
    tr = trace.load(str(next(out.rglob("*.xplane.pb"))))
    shutil.rmtree(out, ignore_errors=True)
    trace.save(tr, CACHE / "last_trace.json.gz")
    w0, w1 = trace.window(tr)
    return steps, t0, t1, trace.reduce(tr, w0, w1)


def window(loop, seconds: float):
    t0 = loop.clock()
    steps = []
    while loop.clock() - t0 < seconds:
        steps.append(loop.step())
    return steps, t0, loop.clock()


def main(argv=None) -> int:
    args = parse_args(argv)
    cell = spec.resolve(args.workload)
    use_cache()
    from bench import flops, system  # noqa: F401  (the program, before a chip)

    devices = chips(cell)[:cell.chips]

    result = execute(cell, args.seed, args.seconds, bool(args.trace),
                     devices, flops.peaks(devices[0].device_kind))
    print(json.dumps(result), flush=True)
    return 0


def execute(cell, seed: int, seconds: float, traced: bool, devices,
            peak: dict, *, compiles=None, fault=None,
            control: bool = False) -> dict:
    """Set up, measure and check one cell on ``devices``; returns the
    result line. The rest is for ``bench/control.py`` and the harness's
    tests: ``compiles`` shares one compile log between runs in a process,
    ``fault`` is called with the engine before the window, to break the
    timed path, and ``control`` also judges the float8 control by the
    same limit, under ``result["control"]``."""
    import jax

    from bench import clients, compilelog, loadgen, reference, system

    sizes = spec.model_sizes(cell.config)
    compiles = compiles or compilelog.CompileLog()
    eng_conf, mix = cell.engine, cell.traffic
    lists = loadgen.closed_loop(mix, sizes["vocab"], seed)
    params = system.make_params(cell.arch, sizes, seed)
    jax.block_until_ready(params)
    engine = system.build_engine(cell.arch, cell.config["name"], sizes,
                                 eng_conf, params)
    annotate = jax.profiler.TraceAnnotation if traced else None

    def new_loop():
        loop = clients.ClosedLoop(
            engine, lists, make_request=system.request,
            finished=system.finished, events_since=system.events_since,
            annotate=annotate)
        loop.start()
        while not loop.prefilled():
            loop.step()
        return loop

    # warm pass: the same traffic, until the steps that compiled nothing
    # cover WARM_WINDOWS windows
    loop, steady = new_loop(), 0.0
    while steady < WARM_WINDOWS * seconds:
        before, t = compiles.snapshot().programs, loop.clock()
        loop.step()
        if compiles.snapshot().programs == before:
            steady += loop.clock() - t
    engine.cancel_all()
    if fault is not None:
        fault(engine)
    loop = new_loop()
    setup_s = time.perf_counter() - T_START
    c0 = compiles.snapshot()
    log(f"setup_s={setup_s:.3f} programs={c0.programs} "
        f"compile_s={c0.seconds:.1f} cache_hits={c0.hits} "
        f"cache_misses={c0.misses}")

    reduced = None
    if traced:
        steps, t0, t1, reduced = traced_window(loop, seconds)
    else:
        steps, t0, t1 = window(loop, seconds)
    in_window = compiles.snapshot() - c0
    stats = [d.memory_stats() or {} for d in devices]
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(jax.devices()),
              "memory_peak_bytes": max(s.get("peak_bytes_in_use", 0)
                                       for s in stats)}
    e2e = clients.end_to_end(loop.tracks, t0, t1)
    attempted = sum(1 for t in loop.tracks if t.sent < t1)
    log(f"window_s={t1 - t0:.3f} steps={len(steps)} tokens={e2e['tokens']} "
        f"gaps={e2e['gaps']} compiles_in_window={in_window.programs} "
        f"memory_peak_bytes={device['memory_peak_bytes']}")

    metrics = {}
    if traced:
        device["busy_s"] = reduced.busy_s
        device["window_s"] = reduced.window_s
        run = Run(cell.arch, sizes, peak, t1 - t0, steps, in_window, reduced)
        for m in cell.per_layer:
            value = spec.reader(m.name)(run)
            if value is not None:
                metrics[m.name] = {"value": value, "unit": m.unit}
    else:
        e2e["setup_s"] = setup_s
        for m in cell.end_to_end:
            if e2e.get(m.name) is not None:
                metrics[m.name] = {"value": e2e[m.name], "unit": m.unit}

    # the check: the served state goes, then the reference runs
    check_conf = eng_conf["check"]
    picked = sample(loop.tracks, check_conf["requests"], seed,
                    system.finished)
    del loop, engine, params, steps
    gc.collect()
    t = time.perf_counter()
    got = reference.compare(cell.arch, sizes, seed, picked,
                            control=control)
    limit = check_conf["max_gap"]
    correct = judge(got["max_gap"], limit)
    log(f"reference: {len(picked)} requests, {got['tokens']} served tokens "
        f"in {time.perf_counter() - t:.1f}s")
    if control:
        control_ok = judge(got["control_max_gap"], limit)
        log(f"control max_gap={got['control_max_gap']!r} limit={limit!r} "
            f"{'ok' if control_ok else 'FAIL'}")
    log(f"check max_gap={got['max_gap']!r} limit={limit!r} "
        f"{'ok' if correct else 'FAIL'}")
    result = {"correct": bool(correct), "attempted": attempted, "failed": 0,
              "metrics": metrics, "device": device}
    if reduced is not None:
        result["breakdown"] = {"device_ops": [list(x) for x in
                                              reduced.top_ops(10)],
                               "idle_gaps": [list(x) for x in
                                             reduced.idle_gaps[:10]]}
    if control:
        result["control"] = {"max_gap": got["control_max_gap"],
                             "correct": control_ok}
    result["check"] = {"max_gap": {"value": got["max_gap"], "limit": limit,
                                   "tokens": got["tokens"]}}
    return result


if __name__ == "__main__":
    sys.exit(main())
