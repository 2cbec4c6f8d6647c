"""Memory-device worker pools + wire-byte accounting (paper §4.2.2, §7).

Canonical home of the pieces every placement strategy composes, rehomed
from the deleted legacy engine modules (``disagg_engine.py`` /
``moe_offload.py`` — their ``Engine``/``DisaggEngine``/``MoEOffloadEngine``
classes survived only as parity oracles and are gone; LLMEngine-vs-LLMEngine
cross-config checks replaced them):

  * :class:`AttentionWorkerPool` — owns partitioning + accounting of
    attention work over the engine's paged block pool, one of three ways:
    "head" (each worker owns Hkv/n heads of every pool block — Lamina's
    choice), "block" (the pool's block axis is sharded and a single
    sequence's round-robin-placed blocks span every worker; per-worker
    §4.2.2 partials merge exactly via the combine identity), or "request"
    (batch-sharded, the load-imbalance baseline). NO partition ever
    materialises a dense seq-major KV view — each worker reads its own
    slice of the block pool in place (the no-densify invariant,
    core/attention_parallel.py);
  * :func:`expected_transfer_bytes` — the paper's §3.1 per-iteration wire
    formula (2 + 2/G)·e·d_q·B·L that tests assert the pool's log matches;
  * :class:`ExpertWorkerPool` — MoE expert offloading (paper §7): expert
    weights live on memory-optimized workers with the same byte-accounting
    contract, plus the analytic bounds ``transfer_bytes_moe`` /
    ``min_bandwidth_moe``.
"""
from __future__ import annotations

import dataclasses
from typing import Dict

import jax
import jax.numpy as jnp

from repro.core import costmodel as cm
from repro.models.common import ModelConfig

BYTES = 2  # bf16/fp16 wire format (paper Table 2 "e")


@dataclasses.dataclass
class TransferLog:
    q_bytes: int = 0
    kv_bytes: int = 0
    out_bytes: int = 0
    transfers: int = 0

    @property
    def total(self) -> int:
        return self.q_bytes + self.kv_bytes + self.out_bytes


class AttentionWorkerPool:
    """The memory-device pool: stores nothing here (the paged pool is the
    engine's), but owns partitioning + accounting of attention work."""

    def __init__(self, cfg: ModelConfig, n_workers: int = 2,
                 partition: str = "head", backend: str = "jnp",
                 kv_dtype: str = "bf16"):
        self.cfg = cfg
        self.n = n_workers
        self.partition = partition
        self.backend = backend
        self.kv_dtype = kv_dtype
        self.log = TransferLog()
        self.per_worker_kv_bytes = [0] * n_workers
        if partition not in ("head", "request", "block"):
            raise ValueError(f"unknown partition {partition!r}")
        if partition == "head" and cfg.num_kv_heads % n_workers:
            raise ValueError(
                f"head partition needs kv_heads ({cfg.num_kv_heads}) "
                f"divisible by workers ({n_workers}) — paper §5")

    def _account(self, q, k_new, v_new, out, enabled: bool):
        # Only for direct (non-jit) calls: python side effects do not fire
        # per-execution under jit — the engine logs analytically instead.
        if not enabled:
            return
        self.log.q_bytes += q.size * BYTES
        self.log.kv_bytes += (k_new.size + v_new.size) * BYTES
        self.log.out_bytes += out.size * BYTES
        self.log.transfers += 2  # QKV out + result back

    def log_iteration(self, batch: int) -> None:
        """Shape-derived per-iteration accounting (jit-safe path)."""
        cfg = self.cfg
        hd = cfg.resolved_head_dim
        L = cfg.num_layers
        self.log.q_bytes += batch * cfg.num_heads * hd * BYTES * L
        self.log.kv_bytes += 2 * batch * cfg.num_kv_heads * hd * BYTES * L
        self.log.out_bytes += batch * cfg.num_heads * hd * BYTES * L
        self.log.transfers += 2 * L

    def attend(self, q, k_cache, v_cache, cache_len, k_new, v_new, *,
               sliding_window: int = 0, logit_softcap: float = 0.0,
               account: bool = False) -> jax.Array:
        """q: (B, H, hd); caches HEAD-MAJOR (B, Hkv, S, hd) hold the STORED prefix
        (cache_len tokens); k_new/v_new (B, Hkv, hd) arrive over the wire.
        Each worker computes combine(prefix partial, new partial) on its
        partition (§4.2.2 across workers too). Returns (B, H, hd)."""
        from repro.models.attention import decode_attention_combine

        B, H, hd = q.shape
        Hkv = k_cache.shape[1]
        kw = dict(sliding_window=sliding_window, logit_softcap=logit_softcap,
                  backend=self.backend)
        if self.partition == "head":
            hk = Hkv // self.n
            g = H // Hkv
            outs = []
            for wid in range(self.n):
                sl = slice(wid * hk, (wid + 1) * hk)
                qs = q.reshape(B, Hkv, g, hd)[:, sl].reshape(B, hk * g, hd)
                o = decode_attention_combine(
                    qs, k_cache[:, sl], v_cache[:, sl], cache_len,
                    k_new[:, sl], v_new[:, sl], **kw)
                outs.append(o.reshape(B, hk, g, hd))
                self.per_worker_kv_bytes[wid] += \
                    2 * k_cache[:, sl].size * BYTES
            out = jnp.concatenate(outs, axis=1).reshape(B, H, hd)
        elif self.partition == "request":
            splits = jnp.array_split(jnp.arange(B), self.n)
            outs = []
            for wid, idx in enumerate(splits):
                if len(idx) == 0:
                    continue
                o = decode_attention_combine(
                    q[idx], k_cache[idx], v_cache[idx], cache_len[idx],
                    k_new[idx], v_new[idx], **kw)
                outs.append(o)
                self.per_worker_kv_bytes[wid] += \
                    2 * k_cache[idx].size * BYTES
            out = jnp.concatenate(outs, axis=0)
        else:
            raise ValueError(self.partition)
        self._account(q, k_new, v_new, out, account)
        return out

    def attend_paged(self, q, k_pool, v_pool, block_tables, cache_len,
                     k_new, v_new, *, sliding_window: int = 0,
                     attention_sinks: int = 0,
                     logit_softcap: float = 0.0,
                     shard_tables=None, shard_positions=None,
                     k_scale=None, v_scale=None) -> jax.Array:
        """Paged variant of :meth:`attend` — the engine's decode hot path.

        q: (B, H, hd); k_pool/v_pool: one layer's HEAD-MAJOR pool slice
        (Hkv, num_blocks, block_size, hd) holding the STORED prefix;
        block_tables (B, nb); k_new/v_new (B, Hkv, hd) arrive over the wire.
        Each worker reads its partition of the pool *in place* (head-sliced
        pool, block-sliced pool, or request-sliced table) and the per-worker
        partials merge with the new token via §4.2.2.

        Block partition: shard_tables/shard_positions (n, B, nbl) are the
        COMPACTED per-worker local tables (PagedKVCache.block_table_shards)
        — each worker walks only its ~1/n of the sequence's blocks, the
        whole point of the split. When absent (direct callers without the
        cache at hand) an owner-masked view of the global table is derived
        in-trace instead: equally exact, but every worker then walks all nb
        slots, reading ~n× the live KV.

        Int8 pools (``kv_dtype="int8"``): k_scale/v_scale are the per-layer
        scale pools (Hkv, num_blocks, 1, block_size) and each worker's slice
        of them follows its pool slice exactly — head partition slices the
        head axis, block partition the block axis, request partition
        replicates (scales-follow-blocks invariant). Dequant stays fused
        inside each worker's backend; the partial-merge math is unchanged.

        No per-worker byte accounting happens here — this method runs
        inside the engine's jitted step, where python side effects fire at
        trace time only; the engine logs live-token bytes host-side per
        iteration via :meth:`log_paged_kv`."""
        from repro.core import combine as C
        from repro.models.attention import (_new_token_partial,
                                            paged_decode_attention_combine,
                                            paged_decode_attention_partial_pos)

        B, H, hd = q.shape
        Hkv, NB, bs, _ = k_pool.shape
        kw = dict(sliding_window=sliding_window,
                  attention_sinks=attention_sinks,
                  logit_softcap=logit_softcap)
        if self.partition == "head":
            hk = Hkv // self.n
            g = H // Hkv
            outs = []
            for wid in range(self.n):
                sl = slice(wid * hk, (wid + 1) * hk)
                qs = q.reshape(B, Hkv, g, hd)[:, sl].reshape(B, hk * g, hd)
                skw = {} if k_scale is None else dict(
                    k_scale=k_scale[sl], v_scale=v_scale[sl])
                o = paged_decode_attention_combine(
                    qs, k_pool[sl], v_pool[sl], block_tables, cache_len,
                    k_new[:, sl], v_new[:, sl], backend=self.backend,
                    **kw, **skw)
                outs.append(o.reshape(B, hk, g, hd))
            out = jnp.concatenate(outs, axis=1).reshape(B, H, hd)
        elif self.partition == "block":
            # the pool's block axis is cut into n contiguous shard slices
            # (PagedKVCache round-robins a sequence's blocks across them);
            # each worker computes the §4.2.2 partial over ITS live blocks
            # only — derived in-trace from the global table by masking the
            # slots it does not own (POS_PAD positions kill every row), so
            # the jitted step needs no per-shard host tables
            from repro.serving.kvcache import POS_PAD

            if NB % self.n:
                raise ValueError(
                    f"block partition needs num_blocks ({NB}) divisible by "
                    f"workers ({self.n}) — PagedKVCache(n_shards=...)")
            npb = NB // self.n
            if shard_tables is None:
                # fallback: owner-mask the global table in-trace (full walk)
                nb = block_tables.shape[1]
                base = jnp.arange(nb, dtype=jnp.int32)[None, :] * bs
                owner = block_tables // npb
                local = block_tables % npb
                per_worker = [(local, jnp.where(owner == wid, base, POS_PAD))
                              for wid in range(self.n)]
            else:
                per_worker = [(shard_tables[wid], shard_positions[wid])
                              for wid in range(self.n)]
            partials = []
            for wid, (bt_w, pos_w) in enumerate(per_worker):
                bsl = slice(wid * npb, (wid + 1) * npb)
                skw = {} if k_scale is None else dict(
                    k_scale=k_scale[:, bsl], v_scale=v_scale[:, bsl])
                partials.append(paged_decode_attention_partial_pos(
                    q, k_pool[:, bsl], v_pool[:, bsl],
                    bt_w, pos_w, cache_len, backend=self.backend,
                    **kw, **skw))
            p_new = _new_token_partial(q, k_new, v_new,
                                       logit_softcap=logit_softcap)
            out = C.finalize(C.combine(C.combine_many(partials),
                                       p_new)).astype(q.dtype)
        elif self.partition == "request":
            splits = jnp.array_split(jnp.arange(B), self.n)
            outs = []
            for wid, idx in enumerate(splits):
                if len(idx) == 0:
                    continue
                skw = {} if k_scale is None else dict(
                    k_scale=k_scale, v_scale=v_scale)
                o = paged_decode_attention_combine(
                    q[idx], k_pool, v_pool, block_tables[idx],
                    cache_len[idx], k_new[idx], v_new[idx],
                    backend=self.backend, **kw, **skw)
                outs.append(o)
            out = jnp.concatenate(outs, axis=0)
        else:
            raise ValueError(self.partition)
        return out

    def log_paged_kv(self, worker_tokens, n_layers: int,
                     kv_head_fraction: float = 1.0) -> None:
        """Per-worker live-token KV-read accounting for the paged hot path.

        worker_tokens: (n_workers,) live tokens each worker's partition
        reads this iteration (data-dependent, so logged host-side — see
        LLMEngine._decode_iteration, which derives them per partition);
        kv_head_fraction scales for head partitioning (each worker reads
        only Hkv/n heads of every token). Per-token-head bytes follow the
        pool's kv_dtype: bf16 reads hd·2 bytes, int8 reads hd·1 plus the
        fp32 scale (hd + 4) — the ~2× stream reduction the quantized pool
        buys on the decode hot path."""
        hd = self.cfg.resolved_head_dim
        per_head = hd + 4 if self.kv_dtype == "int8" else hd * BYTES
        per_tok = 2 * self.cfg.num_kv_heads * kv_head_fraction * \
            per_head * n_layers
        for wid in range(self.n):
            self.per_worker_kv_bytes[wid] += int(worker_tokens[wid] * per_tok)

    # overlap mode shares the same math (combine is exact); the distinction
    # is the *schedule* — prev-partial issues right after send-Q, the new
    # token merges after send-KV — which the latency model in
    # benchmarks/bench_overlap.py prices. The engine's hot path is PAGED, so
    # overlap shares the paged path (not the dense test-oracle one).
    attend_overlapped = attend_paged


def expected_transfer_bytes(cfg: ModelConfig, batch: int) -> int:
    """Paper §3.1: (2 + 2/G)·e·d_q·B·L per iteration."""
    G = cfg.gqa_group
    return int((2 + 2 / G) * BYTES * cfg.q_dim * batch * cfg.num_layers)


def transfer_bytes_moe(cfg: ModelConfig, batch: int) -> int:
    """Per-iteration wire bytes for expert offloading: token activations to
    the pool and expert outputs back, per MoE layer."""
    return int(2 * BYTES * cfg.d_model * batch * cfg.num_layers)


def min_bandwidth_moe(cfg: ModelConfig, batch: int, seq_len: float,
                      hw_model: cm.HardwareSpec, hw_exp: cm.HardwareSpec,
                      alpha: float = 0.2) -> float:
    """Paper-§3.1 style minimum-bandwidth bound for the MoE boundary."""
    t = cm.mtime(cfg, batch, hw_model) + cm.atime(cfg, batch, seq_len,
                                                  hw_model)
    return transfer_bytes_moe(cfg, batch) / (alpha * t)


class ExpertWorkerPool:
    """Memory-device pool owning the expert weights + FFN compute."""

    def __init__(self, cfg: ModelConfig, n_workers: int = 2):
        if cfg.num_experts % max(n_workers, 1):
            raise ValueError(
                f"expert partition needs num_experts ({cfg.num_experts}) "
                f"divisible by workers ({n_workers})")
        self.cfg = cfg
        self.n = n_workers
        self.log = TransferLog()
        self.per_worker_tokens = [0] * n_workers

    def run_experts(self, moe_params: Dict, x: jax.Array,
                    account: bool = False) -> jax.Array:
        """x: (B, S, d) routed-token activations arriving over the wire.
        Expert-partitioned across workers: each worker computes the routed
        contribution of its expert shard; outputs sum (experts are disjoint
        per token choice, so partial outputs add exactly)."""
        from repro.models.moe import moe_forward

        cfg = self.cfg
        y, _ = moe_forward(moe_params, cfg, x)
        if account:
            self.log.q_bytes += x.size * BYTES       # activations out
            self.log.out_bytes += y.size * BYTES     # expert outputs back
            self.log.transfers += 2
        return y

    def log_iteration(self, batch: int) -> None:
        d, L = self.cfg.d_model, self.cfg.num_layers
        self.log.q_bytes += batch * d * BYTES * L
        self.log.out_bytes += batch * d * BYTES * L
        self.log.transfers += 2 * L
