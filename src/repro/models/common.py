"""Common building blocks shared by every architecture family.

Pure-functional JAX: parameters are pytrees of arrays, modules are functions.
Per-layer parameters are *stacked* along a leading L axis so the transformer
stack lowers as a single ``lax.scan`` — this keeps dry-run compiles of 61-layer
models fast and the HLO compact.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

Params = Dict[str, Any]

# layer kinds of a ``mixed`` stack's ``layer_pattern``
MAMBA, MLP, ATTENTION = "M", "-", "*"


# ---------------------------------------------------------------------------
# Config
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class ModelConfig:
    """One config describes every supported family; unused fields stay 0."""

    name: str = "model"
    family: str = "dense"  # dense | moe | ssm | hybrid | mixed | vlm | audio
    num_layers: int = 2
    d_model: int = 256
    num_heads: int = 4
    num_kv_heads: int = 4
    head_dim: int = 0  # 0 -> d_model // num_heads
    d_ff: int = 1024
    vocab_size: int = 1024
    # --- MoE ---
    num_experts: int = 0
    experts_per_token: int = 0
    moe_d_ff: int = 0
    capacity_factor: float = 1.25
    router_aux_weight: float = 0.01
    # --- attention variants ---
    sliding_window: int = 0  # 0 = full attention
    # StreamingLLM-style decode: keep `attention_sinks` initial tokens
    # attendable alongside the sliding window (paper §7 proposes sparse
    # attention for cheap memory pools; sinks+window is the production
    # variant that preserves quality). Requires sliding_window > 0.
    attention_sinks: int = 0
    # KV-cache storage width (paper §7: "model quantization uses reduced-
    # precision formats to store KV caches"). 8 -> int8 values + per-token
    # per-head fp scales; halves the memory pool's capacity requirement and
    # the attention read bytes. 16 -> cfg.dtype (default).
    kv_cache_bits: int = 16
    local_global: bool = False  # gemma2-style alternating local/global
    attn_logit_softcap: float = 0.0
    final_logit_softcap: float = 0.0
    rope_theta: float = 10000.0  # 0 -> attention without rotary position
    qk_norm: bool = False
    post_norms: bool = False  # gemma2 pre+post sandwich norms
    # --- SSM / RWKV ---
    ssm_state: int = 0
    ssm_head_dim: int = 64
    ssm_expand: int = 2
    ssm_conv: int = 4
    # Mamba heads and B/C groups held here; 0 heads -> ssm_expand·d_model
    # wide. A chip's share of a model's heads is not expand·d_model wide,
    # so a share states its heads.
    ssm_heads: int = 0
    ssm_groups: int = 1
    rwkv_head_dim: int = 64
    # --- hybrid (zamba2): one shared attention block every `period` layers ---
    shared_attn_period: int = 0
    # --- mixed: one mixer per layer, its kind given per layer by this
    # pattern (Nemotron-H's hybrid_override_pattern notation): "M" Mamba-2,
    # "-" a ReLU-squared MLP, "*" attention; each after a pre-norm, each
    # followed by a residual add ---
    layer_pattern: str = ""
    # --- encoder/decoder (seamless) ---
    encoder_layers: int = 0
    is_encoder_decoder: bool = False
    # --- modality frontend stubs ---
    modality: str = "text"  # text | vision | audio
    frontend_tokens: int = 0  # patches / audio frames prepended (stub embeds)
    # --- kernels ---
    use_pallas_kernels: bool = False  # route hot-spots through repro.kernels
    # --- training memory policy ---
    # jax.checkpoint each layer body in train mode: activations saved per
    # layer boundary only, attention/FFN recomputed in backward (the llama3
    # train_4k dry-run is 470 GiB/chip without this, ~a few GiB with it).
    remat: bool = True
    # --- lowering mode ---
    # Unroll layer/KV-block scans when lowering. compiled.cost_analysis()
    # counts while-loop bodies ONCE (verified empirically), so the roofline
    # cost pass lowers an unrolled variant for exact HLO FLOP/byte/collective
    # counts; the natural scan variant stays the memory/compile-proof
    # artifact. Time-dimension recurrences (rwkv/mamba) stay loops and get
    # analytic corrections in launch/analytic.py.
    lower_unrolled: bool = False
    # --- numerics ---
    dtype: Any = jnp.bfloat16
    norm_eps: float = 1e-6
    tie_embeddings: bool = False
    source: str = ""  # citation

    @property
    def resolved_head_dim(self) -> int:
        return self.head_dim or self.d_model // self.num_heads

    @property
    def q_dim(self) -> int:
        return self.num_heads * self.resolved_head_dim

    @property
    def kv_dim(self) -> int:
        return self.num_kv_heads * self.resolved_head_dim

    @property
    def gqa_group(self) -> int:
        return self.num_heads // max(self.num_kv_heads, 1)

    def layers_of(self, kind: str) -> tuple:
        """Indices of the ``layer_pattern`` layers of one kind."""
        return tuple(i for i, k in enumerate(self.layer_pattern) if k == kind)

    @property
    def num_kv_layers(self) -> int:
        """Layers that keep keys and values: every layer but for the
        recurrent families."""
        if self.family == "mixed":
            return len(self.layers_of(ATTENTION))
        if self.family == "hybrid":
            return self.num_layers // self.shared_attn_period
        return self.num_layers

    def replace(self, **kw) -> "ModelConfig":
        return dataclasses.replace(self, **kw)


# ---------------------------------------------------------------------------
# Initialisation helpers
# ---------------------------------------------------------------------------
def dense_init(key, shape, dtype, scale: float = 1.0):
    fan_in = shape[0] if len(shape) >= 2 else max(shape[-1], 1)
    std = scale / jnp.sqrt(jnp.asarray(fan_in, jnp.float32))
    return (jax.random.truncated_normal(key, -3.0, 3.0, shape, jnp.float32)
            * std).astype(dtype)


def stacked(key, n: int, init_fn):
    """Initialise ``n`` per-layer params stacked on axis 0."""
    keys = jax.random.split(key, n)
    return jax.vmap(init_fn)(keys)


# ---------------------------------------------------------------------------
# Primitive ops
# ---------------------------------------------------------------------------
# ---------------------------------------------------------------------------
# Activation-sharding hook (installed by the launcher; identity by default).
# Lives here so every model module (transformer stacks, ssm mixers) can pin
# activation layouts without import cycles.
# ---------------------------------------------------------------------------
_ACT_CONSTRAINT = None


def set_activation_constraint(fn) -> None:
    global _ACT_CONSTRAINT
    _ACT_CONSTRAINT = fn


def constrain_activation(x):
    return _ACT_CONSTRAINT(x) if _ACT_CONSTRAINT is not None else x


def rms_norm(x: jax.Array, weight: jax.Array, eps: float = 1e-6) -> jax.Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    x = x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps)
    return (x * (1.0 + weight.astype(jnp.float32))).astype(dt)


def softcap(x: jax.Array, cap: float) -> jax.Array:
    """Gemma-2 logit soft-capping: cap * tanh(x / cap)."""
    if cap <= 0.0:
        return x
    return (cap * jnp.tanh(x.astype(jnp.float32) / cap)).astype(x.dtype)


def rope_frequencies(head_dim: int, theta: float) -> jax.Array:
    exponent = jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim
    return 1.0 / (theta ** exponent)  # (head_dim/2,)


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (..., seq, heads, head_dim); positions: broadcastable to (..., seq)."""
    head_dim = x.shape[-1]
    freqs = rope_frequencies(head_dim, theta)  # (hd/2,)
    angles = positions[..., :, None].astype(jnp.float32) * freqs  # (...,s,hd/2)
    cos = jnp.cos(angles)[..., :, None, :]  # (..., s, 1, hd/2)
    sin = jnp.sin(angles)[..., :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def swiglu(x: jax.Array, w_gate: jax.Array, w_up: jax.Array,
           w_down: jax.Array) -> jax.Array:
    gate = jnp.einsum("...d,df->...f", x, w_gate)
    up = jnp.einsum("...d,df->...f", x, w_up)
    hidden = jax.nn.silu(gate.astype(jnp.float32)).astype(x.dtype) * up
    return jnp.einsum("...f,fd->...d", hidden, w_down)


def relu2_mlp(x: jax.Array, w_up: jax.Array, w_down: jax.Array) -> jax.Array:
    """down(relu(up(x))²): the ungated MLP of Nemotron-H."""
    h = jax.nn.relu(jnp.einsum("...d,df->...f", x, w_up).astype(jnp.float32))
    return jnp.einsum("...f,fd->...d", jnp.square(h).astype(x.dtype), w_down)


def gelu_mlp(x: jax.Array, w_fc: jax.Array, w_proj: jax.Array) -> jax.Array:
    h = jax.nn.gelu(jnp.einsum("...d,df->...f", x, w_fc).astype(jnp.float32))
    return jnp.einsum("...f,fd->...d", h.astype(x.dtype), w_proj)


def cross_entropy_loss(logits: jax.Array, labels: jax.Array,
                       mask: Optional[jax.Array] = None) -> jax.Array:
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = logz - gold
    if mask is not None:
        mask = mask.astype(jnp.float32)
        return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    return jnp.mean(nll)
