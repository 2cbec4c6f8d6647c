"""The one traffic generator: turns a mix's parameters
(``bench/traffic/<mix>.json``) and a run's seed into requests.

Lengths are log-normal, as in the paper's Table 4 traces (the same
generator as ``repro.data.traces._lognormal_lengths``, copied so that the
yardstick does not move with the program), clipped to the mix's range.

Every seed gets the same set of sizes: they are drawn once from the mix's
own ``sizes_seed``, and the run's seed only decides which client gets which
list of requests and what their tokens are. So two seeds do the same work
in another order, and the spread between runs measures the system and not
the draw.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List

import numpy as np


@dataclasses.dataclass(frozen=True)
class RequestSpec:
    client: int
    index: int             # position in the client's list
    prompt: np.ndarray     # int32 token ids
    max_new_tokens: int


def lognormal_lengths(rng: np.random.Generator, mean: float, n: int,
                      sigma: float = 0.6, lo: int = 1) -> np.ndarray:
    mu = np.log(mean) - sigma ** 2 / 2.0
    out = rng.lognormal(mu, sigma, size=n).astype(np.int64)
    return np.maximum(out, lo)


def _lengths(rng: np.random.Generator, dist: Dict, n: int) -> np.ndarray:
    if dist["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {dist['dist']!r}")
    out = lognormal_lengths(rng, dist["mean"], n, dist["sigma"], dist["min"])
    return np.clip(out, dist["min"], dist["max"])


def closed_loop(mix: Dict, vocab: int, seed: int) -> List[List[RequestSpec]]:
    """One list of requests per client, in the order the client sends
    them. A client sends its next request when its previous one finishes."""
    if mix["loop"] != "closed":
        raise ValueError(f"unknown loop {mix['loop']!r}")
    clients, per = mix["clients"], mix["requests_per_client"]
    sizes = np.random.default_rng(mix["sizes_seed"])
    prompts = _lengths(sizes, mix["prompt"], clients * per)
    outputs = _lengths(sizes, mix["output"], clients * per)
    rng = np.random.default_rng(seed)
    order = rng.permutation(clients)          # client c sends list order[c]
    lists = []
    for c in range(clients):
        lst = []
        for i in range(per):
            j = order[c] * per + i
            toks = rng.integers(0, vocab, int(prompts[j]), dtype=np.int32)
            lst.append(RequestSpec(c, i, toks, int(outputs[j])))
        lists.append(lst)
    return lists

