"""The system under test: the only module of the benchmark that imports the
program. It builds the served model from a configuration file, hands it the
benchmark's weights, and builds ``LLMEngine`` with the cell's settings.
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

from bench import weights
from repro.models.common import ModelConfig
from repro.serving import EngineConfig, LLMEngine, Request, SamplingParams

ENGINE_KEYS = ("placement", "partition", "attention_workers", "num_blocks",
               "block_size", "kv_dtype", "max_batch", "scheduler",
               "decode_headroom", "prefix_sharing", "prefill_chunk_tokens",
               "decode_backend")


def model_config(name: str, sz: Dict) -> ModelConfig:
    return ModelConfig(
        name=name, family="dense", num_layers=sz["num_layers"],
        d_model=sz["d_model"], num_heads=sz["num_heads"],
        num_kv_heads=sz["num_kv_heads"], head_dim=sz["head_dim"],
        d_ff=sz["d_ff"], vocab_size=sz["vocab"],
        rope_theta=float(sz["rope_theta"]), norm_eps=float(sz["norm_eps"]),
        tie_embeddings=False, dtype=jnp.bfloat16)


def _program_tree(sz: Dict, w: Dict) -> Dict:
    """The benchmark's layout (``bench/weights.py``) in the program's."""
    L, d, h = sz["num_layers"], sz["d_model"], sz["head_dim"]
    H, K = sz["num_heads"], sz["num_kv_heads"]
    return {
        "embed": w["embed"], "final_norm": w["final_norm"],
        "lm_head": w["lm_head"],
        "layers": {
            "norm1": w["norm1"], "norm2": w["norm2"],
            "attn": {"wq": w["wq"].reshape(L, d, H, h),
                     "wk": w["wk"].reshape(L, d, K, h),
                     "wv": w["wv"].reshape(L, d, K, h),
                     "wo": w["wo"].reshape(L, H, h, d)},
            "ffn": {"w_gate": w["w_gate"], "w_up": w["w_up"],
                    "w_down": w["w_down"]},
        },
    }


def make_params(sz: Dict, seed: int):
    """Every weight, made on the device from the seed in one jitted call,
    in bfloat16, in the program's layout."""
    key = weights.seed_key(seed)
    frozen = tuple(sorted(sz.items()))
    return _make(key, frozen)


def _make_impl(key, frozen):
    sz = dict(frozen)
    return _program_tree(sz, weights.stacked(sz, key))


_make = jax.jit(_make_impl, static_argnums=1)


def finished(req: Request) -> bool:
    return req.state.value == "finished"


def events_since(engine: LLMEngine, i: int):
    """The engine's lifecycle events recorded since the i-th."""
    return engine._events[i:]


def build_engine(name: str, sz: Dict, engine: Dict, params) -> LLMEngine:
    econf = EngineConfig(**{k: engine[k] for k in ENGINE_KEYS if k in engine})
    return LLMEngine(model_config(name, sz), params, econf)


def request(prompt, max_new_tokens: int) -> Request:
    """A greedy request with no end-of-sequence token: it runs to its
    length, so every seed's work is the same."""
    return Request(prompt=[int(t) for t in prompt],
                   params=SamplingParams(max_new_tokens=max_new_tokens,
                                         temperature=0.0))
