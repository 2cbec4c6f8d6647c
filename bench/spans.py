"""Device idle time put down to the phases of the engine's step.

``LLMEngine.step`` opens a ``jax.profiler.TraceAnnotation`` span for each
of its phases (``engine.sched``, ``engine.pool``, ``engine.model``,
``engine.sample``, inside ``engine.step``; the program's
``serving/llm_engine.py`` states the names). They land on the calling
thread's host line, on the device trace's clock. Each stretch of the
window in which no operation ran on a device is charged, for its whole
length, to the innermost ``engine.*`` span open over it: an idle interval
that crosses several spans is split between them, not named by its
middle. As for ``device_idle_share``, idle time is the complement of the
union of a device's ``XLA Ops`` intervals inside the window, averaged over
the devices.

A run's reduced trace holds no host events, so the readers take the
window's raw events from the trace that ``bench/run.py`` saves before any
reader runs. A run without a trace, or a window without engine spans (a
program that writes none), reads None.
"""
from __future__ import annotations

from pathlib import Path
from typing import Dict, List, Optional, Tuple

from bench import trace

PREFIX = "engine."
SAVED = Path(__file__).resolve().parents[1] / ".bench_cache" / \
    "last_trace.json.gz"

Interval = Tuple[int, int]


def segments(host: List[trace.Event], prefix: str = PREFIX
             ) -> List[Tuple[int, int, str]]:
    """Stretches of time (start ns, end ns, span) under the innermost open
    span whose name starts with ``prefix``, in order. The spans are one
    thread's, so they nest."""
    spans = sorted(((s, s + d, n) for n, s, d in host
                    if n.startswith(prefix)), key=lambda x: (x[0], -x[1]))
    out: List[Tuple[int, int, str]] = []
    stack: List[Tuple[int, str]] = []         # (end, name), innermost last
    cursor = 0
    for start, end, name in spans:
        while stack and stack[-1][0] <= start:
            top_end, top = stack.pop()
            out.append((cursor, top_end, top))
            cursor = top_end
        if stack:
            out.append((cursor, start, stack[-1][1]))
        stack.append((end, name))
        cursor = start
    while stack:
        top_end, top = stack.pop()
        out.append((cursor, top_end, top))
        cursor = top_end
    return [(a, b, n) for a, b, n in out if b > a]


def idle_intervals(ops: List[trace.Event], t0: int, t1: int
                   ) -> List[Interval]:
    """Stretches of [t0, t1) in which none of one device's ops ran."""
    busy = trace._union([(max(s, t0), min(s + d, t1)) for _, s, d in ops
                         if min(s + d, t1) > max(s, t0)])
    edges = [t0] + [x for iv in busy for x in iv] + [t1]
    return [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]


def charge(idle: List[Interval], segs: List[Tuple[int, int, str]]
           ) -> Dict[str, int]:
    """Nanoseconds of ``idle`` that fall in each span's stretches; both
    lists sorted and free of overlaps."""
    out: Dict[str, int] = {}
    i = j = 0
    while i < len(idle) and j < len(segs):
        (a, b), (c, d, name) = idle[i], segs[j]
        lo, hi = max(a, c), min(b, d)
        if hi > lo:
            out[name] = out.get(name, 0) + hi - lo
        if b <= d:
            i += 1
        else:
            j += 1
    return out


def idle_by_span(tr: trace.Trace) -> Optional[Dict[str, float]]:
    """Idle seconds in the window under each innermost ``engine.*`` span,
    averaged over devices; None where the window holds no such span."""
    t0, t1 = trace.window(tr)
    segs = [(max(a, t0), min(b, t1), n) for a, b, n in segments(tr.host)
            if min(b, t1) > max(a, t0)]
    if not segs:
        return None
    total: Dict[str, int] = {}
    for ops in tr.devices.values():
        for name, ns in charge(idle_intervals(ops, t0, t1), segs).items():
            total[name] = total.get(name, 0) + ns
    return {name: ns * 1e-9 / len(tr.devices) for name, ns in total.items()}


def idle_ms_per_step(run, span: str) -> Optional[float]:
    """Device idle ms per window step under the innermost span ``span``,
    read from the run's own trace, saved at ``SAVED``."""
    if run.trace is None or not run.steps:
        return None
    split = idle_by_span(trace.read_saved(SAVED))
    if split is None:
        return None
    return 1e3 * split.get(span, 0.0) / len(run.steps)
