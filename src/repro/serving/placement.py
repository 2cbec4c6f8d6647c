"""Placement strategies — the composable objects that make model-attention
disaggregation a *declarative* decision (paper thesis).

Each strategy owns everything placement-specific that the legacy engines
encoded as subclass overrides (``DisaggEngine._disagg_decode``,
``_decode_extra_args``, per-partition accounting in ``_decode_iteration``):

  * :meth:`PlacementStrategy.decode_fn` builds the jittable one-iteration
    decode step ``(params, tokens, k_pool, v_pool, block_tables, lens,
    *extra) -> (logits, updates)`` over the paged block pool;
  * :meth:`PlacementStrategy.decode_extra_args` supplies the per-iteration
    host-side operands the step needs (the block partition rides its
    compacted per-shard tables through here) and performs the
    data-dependent per-worker KV-read accounting;
  * :meth:`PlacementStrategy.log_step` does the analytic per-iteration
    transfer accounting (paper §3.1 — jit-safe, shape-derived).

``LLMEngine`` composes one strategy with the scheduler and the KV pool; no
placement ever subclasses the engine. The numerical contract is exact:
every placement decodes greedy token-for-token identically to the fused
baseline (the §4.2.2 combine identity), which the parity tests in
``tests/test_llm_engine.py`` pin against the pre-refactor engines.
"""
from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import transformer
from repro.models.attention import (out_project,
                                    paged_decode_attention_combine,
                                    qkv_project)
from repro.models.common import ModelConfig, rms_norm
from repro.models.ffn import ffn_forward
from repro.models.moe import moe_forward
from repro.serving.config import EngineConfig
from repro.serving.kvcache import PagedKVCache
from repro.serving.worker_pool import (BYTES, AttentionWorkerPool,
                                       ExpertWorkerPool, TransferLog)


def _tree_index(tree, i):
    return jax.tree.map(lambda a: a[i], tree)


def sliced_decode_step(cfg: ModelConfig, pool: AttentionWorkerPool,
                       params, tokens, k_pool, v_pool, block_tables, lens,
                       shard_tables=None, shard_positions=None,
                       expert_pool: Optional[ExpertWorkerPool] = None,
                       k_scale_pool=None, v_scale_pool=None):
    """One disaggregated decode iteration — the converter's slices, executed.

    Model slice 0 (norm1 + QKV) runs on the model worker, attention on the
    worker pool (which reads the paged block pool in place), model slice 1
    (o-proj + FFN) back on the model worker; when ``expert_pool`` is given
    (paper §7) the routed expert FFNs run on the expert workers instead.

    Int8 pools: k_scale_pool/v_scale_pool are the (L, Hkv, num_blocks, 1,
    block_size) scale pools; each layer's slice rides to the worker pool
    alongside its value pools and dequant fuses inside the workers'
    attention backends (no dense dequantized slab on this hot path).
    """
    cur_len = lens  # stored tokens
    x = jnp.take(params["embed"], tokens[:, None], axis=0)
    if cfg.tie_embeddings:
        x = x * jnp.asarray(jnp.sqrt(float(cfg.d_model)), x.dtype)
    positions = cur_len[:, None]
    ks, vs = [], []
    for layer in range(cfg.num_layers):
        p = _tree_index(params["layers"], layer)
        is_local = cfg.local_global and layer % 2 == 0
        window = cfg.sliding_window if (is_local or not cfg.local_global) \
            else 0
        # ---- model slice 0: norm1 + QKV (send q early — §4.2.2) ----
        h = rms_norm(x, p["norm1"], cfg.norm_eps)
        q, k, v = qkv_project(p["attn"], cfg, h, positions)
        ks.append(k[:, 0])
        vs.append(v[:, 0])
        # ---- attention pool: workers read the paged pool in place ----
        attn = pool.attend_paged(
            q[:, 0], k_pool[layer], v_pool[layer], block_tables, cur_len,
            k[:, 0], v[:, 0], sliding_window=int(window),
            attention_sinks=cfg.attention_sinks if window else 0,
            logit_softcap=cfg.attn_logit_softcap,
            shard_tables=shard_tables, shard_positions=shard_positions,
            k_scale=None if k_scale_pool is None else k_scale_pool[layer],
            v_scale=None if v_scale_pool is None else v_scale_pool[layer])
        # ---- model slice 1: o-proj + residual + FFN ----
        attn_out = out_project(p["attn"], attn[:, None])
        if cfg.post_norms:
            attn_out = rms_norm(attn_out, p["norm_post_attn"], cfg.norm_eps)
        x = x + attn_out
        h2 = rms_norm(x, p["norm2"], cfg.norm_eps)
        if "moe" in p:
            if expert_pool is not None:
                # router on the model worker, routed FFNs on the experts
                f = expert_pool.run_experts(p["moe"], h2)
            else:
                f, _ = moe_forward(p["moe"], cfg, h2)
        else:
            f = ffn_forward(p["ffn"], h2)
        if cfg.post_norms:
            f = rms_norm(f, p["norm_post_ffn"], cfg.norm_eps)
        x = x + f
    updates = {"k_new": jnp.stack(ks), "v_new": jnp.stack(vs),
               "len": cur_len + 1}
    logits = transformer._head(params, cfg, x[:, 0])
    return logits, updates


class PlacementStrategy:
    """Base placement: where each operator of the decode step executes."""

    name = "base"

    def __init__(self, cfg: ModelConfig, econf: EngineConfig):
        self.cfg = cfg
        self.econf = econf

    # ---- jittable decode step ----
    def decode_fn(self):
        raise NotImplementedError

    # ---- per-iteration host-side operands + data-dependent accounting ----
    def decode_extra_args(self, kv: PagedKVCache, ids: Sequence[int],
                          live_tokens: int) -> Tuple:
        """``live_tokens`` is ``kv.unique_live_tokens(ids)``, counted once
        per step by the engine."""
        return ()

    # ---- analytic per-iteration transfer accounting ----
    def log_step(self, batch: int) -> None:
        pass

    def log_prefill_chunk(self, tokens: int) -> None:
        """Account one prefill chunk's KV landing in the pool (chunked
        prefill ships each chunk's (L, Hkv, C, hd) K/V model->pool as it
        completes; homogeneous placement moves nothing off-worker)."""
        pass

    # ---- introspection (CLI / benchmarks) ----
    @property
    def pool(self) -> Optional[AttentionWorkerPool]:
        return None

    @property
    def expert_pool(self) -> Optional[ExpertWorkerPool]:
        return None

    @property
    def transfer_log(self) -> Optional[TransferLog]:
        return self.pool.log if self.pool is not None else None


class HomogeneousPlacement(PlacementStrategy):
    """vLLM-style baseline: every operator fused on the model workers."""

    name = "homogeneous"

    def decode_fn(self):
        cfg, backend = self.cfg, self.econf.decode_backend
        if cfg.family == "mixed":
            def mixed_step(params, tokens, k_pool, v_pool, block_tables,
                           lens, *, state, slots):
                def attend(n, q, k_new, v_new):
                    return paged_decode_attention_combine(
                        q, k_pool[n], v_pool[n], block_tables, lens, k_new,
                        v_new, backend=backend)
                return transformer.decode_step_mixed(
                    params, cfg, tokens, lens, state, slots, attend)
            return mixed_step

        def step(params, tokens, k_pool, v_pool, block_tables, lens,
                 k_scale_pool=None, v_scale_pool=None):
            return transformer.decode_step_paged(
                params, cfg, tokens, k_pool, v_pool, block_tables, lens,
                backend=backend, k_scale_pool=k_scale_pool,
                v_scale_pool=v_scale_pool)
        return step


class AttentionPoolPlacement(PlacementStrategy):
    """Lamina (paper §4): attention on a memory-optimized worker pool,
    partitioned ``head`` / ``request`` / ``block``."""

    name = "attention_pool"

    def __init__(self, cfg: ModelConfig, econf: EngineConfig):
        super().__init__(cfg, econf)
        self._pool = AttentionWorkerPool(
            cfg, econf.attention_workers, econf.partition,
            econf.decode_backend, kv_dtype=econf.kv_dtype)

    @property
    def pool(self) -> AttentionWorkerPool:
        return self._pool

    def decode_fn(self):
        cfg, pool = self.cfg, self._pool
        if cfg.family == "mixed":
            def mixed_step(params, tokens, k_pool, v_pool, block_tables,
                           lens, shard_tables=None, shard_positions=None, *,
                           state, slots):
                # the model worker runs the Mamba and MLP layers; each
                # attention layer's query goes to the pool, which reads
                # its slice of the paged pool in place
                def attend(n, q, k_new, v_new):
                    return pool.attend_paged(
                        q, k_pool[n], v_pool[n], block_tables, lens, k_new,
                        v_new, shard_tables=shard_tables,
                        shard_positions=shard_positions)
                return transformer.decode_step_mixed(
                    params, cfg, tokens, lens, state, slots, attend)
            return mixed_step

        def step(params, tokens, k_pool, v_pool, block_tables, lens,
                 shard_tables=None, shard_positions=None,
                 k_scale_pool=None, v_scale_pool=None):
            return sliced_decode_step(
                cfg, pool, params, tokens, k_pool, v_pool, block_tables,
                lens, shard_tables, shard_positions,
                expert_pool=self.expert_pool,
                k_scale_pool=k_scale_pool, v_scale_pool=v_scale_pool)
        return step

    def decode_extra_args(self, kv: PagedKVCache, ids: Sequence[int],
                          live_tokens: int) -> Tuple:
        """Per-worker live-token KV-read accounting (data-dependent, so
        host-side — the jitted step's python body fires at trace time only)
        plus, for the block partition, the compacted per-shard local tables
        that let each worker walk only its ~1/n of the live blocks."""
        pool, L = self._pool, self.cfg.num_kv_layers
        if pool.partition == "block":
            # one table walk serves both the jitted step's compacted shard
            # tables and the live-token accounting
            lt, lp, shard_tokens = kv.block_table_shards(ids)
            pool.log_paged_kv(shard_tokens.sum(axis=1), L)
            return (jnp.asarray(lt), jnp.asarray(lp))
        # byte accounting counts a prefix-SHARED physical block once (its
        # bytes are resident, and streamable, once per chip — not once per
        # sharer): unique_live_tokens dedupes; without sharing it equals
        # the plain per-sequence length sum
        if pool.partition == "head":
            pool.log_paged_kv([live_tokens] * pool.n, L,
                              kv_head_fraction=1.0 / pool.n)
        else:  # request: each worker walks only its requests' tables
            toks = [kv.unique_live_tokens([ids[i] for i in idx])
                    for idx in np.array_split(np.arange(len(ids)), pool.n)]
            pool.log_paged_kv(toks, L)
        return ()

    def log_step(self, batch: int) -> None:
        self._pool.log_iteration(batch)

    def log_prefill_chunk(self, tokens: int) -> None:
        """One chunk's KV crosses the wire model->pool once per layer (the
        prefill-axis counterpart of the per-step k_new/v_new transfer).
        Int8 pools ship quantized values + fp32 scales (hd + 4 bytes per
        token-head instead of hd·2) — the wire follows the pool dtype."""
        cfg = self.cfg
        hd = cfg.resolved_head_dim
        per_head = hd + 4 if self.econf.kv_dtype == "int8" else hd * BYTES
        self._pool.log.kv_bytes += (2 * tokens * cfg.num_kv_heads *
                                    per_head * cfg.num_kv_layers)
        self._pool.log.transfers += cfg.num_kv_layers


class MoEOffloadPlacement(AttentionPoolPlacement):
    """Paper §7: attention AND the routed expert FFNs on worker pools."""

    name = "moe_offload"

    def __init__(self, cfg: ModelConfig, econf: EngineConfig):
        if cfg.family != "moe":
            raise ValueError("moe_offload placement needs a MoE config; "
                             f"got family={cfg.family}")
        super().__init__(cfg, econf)
        self._expert_pool = ExpertWorkerPool(cfg, econf.expert_workers)

    @property
    def expert_pool(self) -> ExpertWorkerPool:
        return self._expert_pool

    def log_step(self, batch: int) -> None:
        super().log_step(batch)
        self._expert_pool.log_iteration(batch)


_PLACEMENTS = {
    "homogeneous": HomogeneousPlacement,
    "attention_pool": AttentionPoolPlacement,
    "moe_offload": MoEOffloadPlacement,
}


def make_placement(cfg: ModelConfig, econf: EngineConfig
                   ) -> PlacementStrategy:
    return _PLACEMENTS[econf.placement](cfg, econf)
