"""Drive the served path from one thread and time it by the host clock.

The closed loop keeps one request in flight per client: it submits the
requests due, calls ``LLMEngine.step()``, then stamps every token that the
step produced with the time the step returned, which is when a streaming
client would see it. A client whose request finished sends its next one
at once. Request times come from this clock, not from the engine's own
statistics.
"""
from __future__ import annotations

import contextlib
import dataclasses
import time
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from bench.loadgen import RequestSpec


@dataclasses.dataclass
class Track:
    spec: RequestSpec
    req: object                       # the program's Request
    sent: float
    times: List[float] = dataclasses.field(default_factory=list)

    @property
    def served(self) -> List[int]:
        return list(self.req.output)


@dataclasses.dataclass
class StepRecord:
    decode_lens: List[int]            # stored tokens of each decoded row
    chunks: List[Tuple[int, int]]     # (prefix tokens, chunk tokens)


class ClosedLoop:
    def __init__(self, engine, lists: Sequence[Sequence[RequestSpec]], *,
                 make_request: Callable, finished: Callable,
                 events_since: Callable, clock: Callable = time.perf_counter,
                 annotate: Optional[Callable] = None):
        self.engine, self.lists = engine, lists
        self._make, self._finished = make_request, finished
        self._events_since, self._n_events = events_since, 0
        self.clock = clock
        self._annotate = annotate or (lambda name: contextlib.nullcontext())
        self.next = [0] * len(lists)
        self.active: Dict[int, Tuple[int, Track]] = {}   # rid -> (client,)
        self.tracks: List[Track] = []

    def _send(self, client: int, now: float) -> None:
        lst = self.lists[client]
        if self.next[client] >= len(lst):
            raise RuntimeError(f"client {client} ran out of requests; give "
                               f"the mix more requests_per_client")
        spec = lst[self.next[client]]
        self.next[client] += 1
        req = self._make(spec.prompt, spec.max_new_tokens)
        track = Track(spec, req, now)
        with self._annotate("bench.submit"):
            self.engine.submit(req)
        self.active[req.rid] = (client, track)
        self.tracks.append(track)

    def start(self) -> None:
        now = self.clock()
        for c in range(len(self.lists)):
            self._send(c, now)
        self._n_events = len(self._events_since(self.engine, 0))

    def prefilled(self) -> bool:
        """Every request in flight has its first token."""
        return all(t.times for _, t in self.active.values())

    def step(self) -> StepRecord:
        with self._annotate("bench.step"):
            self.engine.step()
        now = self.clock()
        events = self._events_since(self.engine, self._n_events)
        self._n_events += len(events)
        rec = StepRecord([], [(e.info["start"], e.info["tokens"])
                              for e in events if e.kind == "chunk"])
        for rid, (client, t) in list(self.active.items()):
            prompt = len(t.spec.prompt)
            for k in range(len(t.times), len(t.req.output)):
                t.times.append(now)
                if k:   # token k (0-based) of a decode reads P + k - 1
                    rec.decode_lens.append(prompt + k - 1)
            if self._finished(t.req):
                del self.active[rid]
                self._send(client, now)
        return rec


def end_to_end(tracks: Sequence[Track], t0: float, t1: float) -> Dict:
    """Output tokens per second over the window (t0, t1], and the 99th
    percentile of every gap between consecutive tokens of a request that
    ends in it. Each gap spans at least one whole engine step, whose end is
    stamped after the step's tokens have reached the host."""
    tokens, gaps = 0, []
    for t in tracks:
        tokens += sum(1 for x in t.times if t0 < x <= t1)
        gaps += [b - a for a, b in zip(t.times, t.times[1:]) if t0 < b <= t1]
    return {"output_tok_s": tokens / (t1 - t0),
            "tbt_p99_ms": float(np.percentile(gaps, 99)) * 1e3
            if gaps else None,
            "tokens": tokens, "gaps": len(gaps)}
