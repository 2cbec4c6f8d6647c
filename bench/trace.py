"""Reduce a profiler trace to device busy and idle time, device time by
operation and by kernel, and idle gaps named by what the host was doing.

A trace is read from JAX's ``.xplane.pb`` with ``jax.profiler.ProfileData``
and kept as plain lists (``load``), so that the reduction (``reduce``) runs
on a recorded trace without a chip; ``bench/fixtures`` holds one recorded
on a TPU v5e, in the same plain form (``save``).

Device operations are the events of each device plane's ``XLA Ops`` line.
Busy time is the union of their intervals inside the window, averaged over
the devices; an idle gap is a stretch of the window with no operation on
the device, named by the innermost host event open on the driving thread
(the host line that holds the window's span) at its middle.
"""
from __future__ import annotations

import dataclasses
import gzip
import json
from pathlib import Path
from typing import Dict, List, Sequence, Tuple

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
WINDOW_SPAN = "bench.window"

Event = Tuple[str, int, int]          # name, start ns, duration ns


@dataclasses.dataclass
class Trace:
    devices: Dict[str, List[Event]]   # device plane -> its XLA ops
    host: List[Event]                 # the driving thread's host events


@dataclasses.dataclass
class Reduction:
    window_s: float
    busy_s: float                     # averaged over devices
    op_seconds: Dict[str, float]      # summed over devices
    idle_gaps: List[Tuple[str, float]]

    def seconds_matching(self, patterns: Sequence[str]) -> float:
        """Device time of the operations whose name holds any pattern."""
        return sum(s for n, s in self.op_seconds.items()
                   if any(p in n for p in patterns))

    def top_ops(self, n: int = 10) -> List[Tuple[str, float]]:
        return sorted(self.op_seconds.items(), key=lambda kv: -kv[1])[:n]


def op_name(event_name: str) -> str:
    """An XLA op's instruction name: a TPU trace names each op by its
    whole HLO text (``%paged_decode_attention.4 = (bf16[...]) custom-call(
    ...)``), which differs between programs of different shapes."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def load(path: str) -> Trace:
    """The device ops and the driving thread's host events of one
    ``.xplane.pb`` file."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices, host = {}, []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    devices[plane.name] = [
                        (op_name(e.name), int(e.start_ns),
                         int(e.duration_ns)) for e in line.events]
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                events = [(e.name, int(e.start_ns), int(e.duration_ns))
                          for e in line.events]
                if any(n == WINDOW_SPAN for n, _, _ in events):
                    host = events
    return Trace(devices, host)


def save(trace: Trace, path: Path) -> None:
    with gzip.open(path, "wt") as f:
        json.dump(dataclasses.asdict(trace), f)


def read_saved(path: Path) -> Trace:
    with gzip.open(path, "rt") as f:
        d = json.load(f)
    return Trace({k: [tuple(e) for e in v] for k, v in d["devices"].items()},
                 [tuple(e) for e in d["host"]])


def window(trace: Trace) -> Tuple[int, int]:
    """Start and end (ns) of the harness's window span."""
    spans = [(s, s + d) for n, s, d in trace.host if n == WINDOW_SPAN]
    if not spans:
        raise ValueError(f"no {WINDOW_SPAN!r} span on the host line")
    return spans[0]


def _union(intervals: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[List[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _host_label(host: List[Event], t: int) -> str:
    """The innermost (latest-starting) host event open at time t."""
    best, best_start = "host idle", -1
    for name, s, d in host:
        if s <= t < s + d and s > best_start:
            best, best_start = name, s
    return best


def reduce(trace: Trace, t0: int, t1: int, n_gaps: int = 10) -> Reduction:
    """Busy and idle time, device time by operation and the longest idle
    gaps, all inside the window [t0, t1) (ns)."""
    if not trace.devices:
        raise ValueError("the trace holds no device operations")
    busy, ops, gaps = 0, {}, []
    for events in trace.devices.values():
        clipped = []
        for name, s, d in events:
            a, b = max(s, t0), min(s + d, t1)
            if b > a:
                clipped.append((a, b))
                ops[name] = ops.get(name, 0.0) + (b - a) * 1e-9
        merged = _union(clipped)
        busy += sum(b - a for a, b in merged)
        edges = [t0] + [x for iv in merged for x in iv] + [t1]
        gaps += [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]
    gaps.sort(key=lambda g: g[0] - g[1])
    named = [(_host_label(trace.host, (a + b) // 2), (b - a) * 1e-9)
             for a, b in gaps[:n_gaps]]
    return Reduction(window_s=(t1 - t0) * 1e-9,
                     busy_s=busy * 1e-9 / len(trace.devices),
                     op_seconds=ops, idle_gaps=named)
