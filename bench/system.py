"""The system under test: the only module of the benchmark that imports the
program. It builds the served model from a configuration file and its
architecture (``bench/arch/<arch>.py``), hands it the benchmark's weights,
and builds ``LLMEngine`` with the cell's settings.
"""
from __future__ import annotations

from typing import Dict

import jax

from bench import weights
from repro.models.common import ModelConfig
from repro.serving import EngineConfig, LLMEngine, Request, SamplingParams

def model_config(arch, name: str, sz: Dict) -> ModelConfig:
    return ModelConfig(name=name, **arch.program(sz))


def make_params(arch, sz: Dict, seed: int):
    """Every weight, made on the device from the seed in one jitted call,
    in bfloat16, in the program's layout."""
    key = weights.seed_key(seed)
    frozen = tuple(sorted(sz.items()))
    return _make(key, frozen, arch)


def _make_impl(key, frozen, arch):
    sz = dict(frozen)
    return arch.program_tree(sz, weights.stacked(arch, sz, key))


_make = jax.jit(_make_impl, static_argnums=(1, 2))


def finished(req: Request) -> bool:
    return req.state.value == "finished"


def events_since(engine: LLMEngine, i: int):
    """The engine's lifecycle events recorded since the i-th."""
    return engine._events[i:]


def build_engine(arch, name: str, sz: Dict, engine: Dict,
                 params) -> LLMEngine:
    """Every key of the cell file but ``check`` is an ``EngineConfig``
    field; one it does not know is an error."""
    econf = EngineConfig(**{k: v for k, v in engine.items() if k != "check"})
    return LLMEngine(model_config(arch, name, sz), params, econf)


def request(prompt, max_new_tokens: int) -> Request:
    """A greedy request with no end-of-sequence token: it runs to its
    length, so every seed's work is the same."""
    return Request(prompt=[int(t) for t in prompt],
                   params=SamplingParams(max_new_tokens=max_new_tokens,
                                         temperature=0.0))
