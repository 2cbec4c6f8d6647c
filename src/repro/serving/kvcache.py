"""Paged KV-cache manager (PagedAttention-style, paper baseline [28]).

Fixed-size blocks of `block_size` tokens from a global pool; per-sequence
block tables; allocation is O(1) off a free list. The pool arrays are the
single source of truth for KV bytes and are HEAD-MAJOR
``(L, Hkv, num_blocks, block_size, hd)`` so one (layer, head, block) tile is
a contiguous ``(block_size, hd)`` DMA — the layout the paged flash-decode
kernel (``kernels/paged_decode_attention.py``) streams in place through
``block_table_batch()``. The engines never gather a dense per-step view on
the hot path: attention reads the pool through the table, and the new
token's K/V lands with one batched ``write_tokens`` scatter. ``gather()``
survives only as the dense test oracle.

Cross-chip block sharding (``n_shards > 1``): the pool's block axis is cut
into `n_shards` contiguous ranges of ``num_blocks // n_shards`` blocks —
shard s owns global ids [s·npb, (s+1)·npb), exactly the slice shard_map's
block-axis partition hands each attention-pool device. Allocation places a
sequence's i-th block ROUND-ROBIN on shard i mod n_shards, so a single
`long_500k` request's KV spans every chip with per-shard live-token counts
within one block of even. ``block_table_shards()`` exposes the per-shard
LOCAL tables plus each slot's global base position (the §4.2.2
partial-combine backends need true positions because a shard's walk is
non-contiguous in the sequence).

Prefix sharing / copy-on-write (refcounted blocks): identical prompt
prefixes map multiple sequences' block tables onto the SAME physical blocks
(``share_blocks``), so the pool admits strictly more concurrent requests
for the same memory — the paper's scarce resource (§3, §4.2). Every block
carries a reference count; a shared block is freed only when the last
referencing sequence releases it, and the first divergent write into a
shared block (``append_token`` growing into a shared partial tail, or a
re-prefill over shared slots) triggers copy-on-write: the writer gets a
private copy of just that block (placed by the SAME round-robin slot rule,
so the shard-balance invariant survives forking), the donor keeps the
original untouched.

Quantized pool (``kv_dtype="int8"``): the pool arrays store int8 values
with per-token, per-kv-head fp32 scales in sidecar pools
``(L, Hkv, num_blocks, block_size)`` that mirror the value pools' block
axis exactly — *scales follow blocks*. Every write path quantizes at write
time (symmetric max-abs, ``models/kv_quant.py``); every block-level
operation (copy-on-write fork, free, quarantine, round-robin placement,
handoff export/import) moves the scale tile with its value tile, so the
refcount/CoW/quarantine invariants hold for the scale arrays by
construction. The decode/prefill-chunk hot paths hand the int8 pools plus
the scale pools to the attention kernels, which fuse dequantization into
the score/PV products as a broadcast multiply per tile — no dense
dequantized K/V slab is ever materialised (the no-densify invariant
extends to *no-dense-dequant*). Only the admission-time prefix gathers
(``gather_prefix``, one per admission) and the dense test oracle
dequantize to a materialised array.

Shard quarantine (fault recovery): a shard the engine declares dead is
masked out of the allocator (``quarantine_shard``) — the round-robin slot
rule walks the LIVE shards only, and every capacity view (``num_free``,
``capacity_blocks``, ``can_allocate``) drops to the survivors, so the
admission/headroom guards honour degraded capacity. The dead shard's free
list is retained: victim sequences release their refs through the normal
refcount path (a block shared by K sharers returns once, when the last ref
drops) and the blocks drain back in place, unallocatable until
``rejoin_shard`` restores the shard.

Invariants (hypothesis-tested in tests/test_kvcache.py and
tests/test_fault_tolerance.py):
  * a block's refcount == the number of live tables referencing it,
  * free + referenced == total (a block is free iff its refcount is zero),
  * an UNSHARED block is owned by at most one sequence,
  * a sequence's capacity always covers its token count,
  * freeing decrements refcounts and returns exactly the blocks that hit
    zero, each to the shard that owns it,
  * a writer never mutates a block another live sequence references
    (copy-on-write forks first),
  * no allocation ever lands on a quarantined shard, and rejoin restores
    exactly the blocks that drained back to the shard's free list.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.models import kv_quant, ssm
from repro.models.common import MAMBA, ModelConfig

# Base-position sentinel for table slots a shard does not own — the single
# definition lives with the kernel; its numeric value is load-bearing for
# mask correctness across the kernel, jnp partials, and the engines.
from repro.kernels.paged_decode_attention import POS_PAD  # noqa: F401,E402


class OutOfBlocks(RuntimeError):
    pass


class PoolExhausted(OutOfBlocks):
    """Pool-exhaustion with full context: which request hit the wall, how
    many tokens are live in the pool, and how many blocks remain free —
    the signal the preemption-capable scheduling policy consumes (and the
    clear error FCFS surfaces instead of failing deep in the allocator).

    ``quarantined_shards`` / ``live_shards`` carry the DEGRADED-capacity
    context when shard faults have quarantined part of the pool: an
    operator reading the error can distinguish "pool too small" (no
    quarantined shards) from "pool degraded" (exhaustion against the
    surviving shards only — e.g. during post-fault re-admission).

    Subclasses :class:`OutOfBlocks` so pre-existing handlers keep working.
    """

    def __init__(self, message: str, *, rid: Optional[int] = None,
                 live_tokens: int = 0, free_blocks: int = 0,
                 quarantined_shards: Tuple[int, ...] = (),
                 live_shards: Tuple[int, ...] = ()):
        super().__init__(message)
        self.rid = rid
        self.live_tokens = live_tokens
        self.free_blocks = free_blocks
        self.quarantined_shards = tuple(quarantined_shards)
        self.live_shards = tuple(live_shards)

    @property
    def degraded(self) -> bool:
        """True when the exhaustion happened against a fault-degraded pool
        (some shards quarantined) rather than a simply-too-small one."""
        return bool(self.quarantined_shards)


@dataclasses.dataclass
class PagedKVCache:
    cfg: ModelConfig
    num_blocks: int
    block_size: int = 16
    n_shards: int = 1
    kv_dtype: str = "bf16"             # "bf16" (cfg.dtype) | "int8"
    # recurrent state slots (stacks with Mamba layers): one per request
    # that can run at once
    state_slots: int = 0

    def __post_init__(self):
        if self.num_blocks % self.n_shards:
            raise ValueError(
                f"num_blocks ({self.num_blocks}) must divide evenly over "
                f"n_shards ({self.n_shards}) — the pool's block axis is "
                f"sharded contiguously over the attention-pool mesh axis")
        if self.kv_dtype not in ("bf16", "int8"):
            raise ValueError(f"kv_dtype must be 'bf16' or 'int8'; "
                             f"got {self.kv_dtype!r}")
        hd = self.cfg.resolved_head_dim
        L = self.cfg.num_kv_layers
        pool_dtype = jnp.int8 if self.kv_dtype == "int8" else self.cfg.dtype
        self.k_pool = jnp.zeros((L, self.cfg.num_kv_heads, self.num_blocks,
                                 self.block_size, hd), pool_dtype)
        self.v_pool = jnp.zeros_like(self.k_pool)
        # int8: per-token, per-kv-head fp32 scale pools mirroring the value
        # pools' block axis — block-level ops move scale tiles with their
        # value tiles ("scales follow blocks"). None on the bf16 path. Each
        # block's scales are one (1, block_size) row: the kernels read that
        # row whole, which is the tile shape the TPU compiler accepts.
        if self.kv_dtype == "int8":
            self.k_scale = jnp.zeros((L, self.cfg.num_kv_heads,
                                      self.num_blocks, 1, self.block_size),
                                     jnp.float32)
            self.v_scale = jnp.zeros_like(self.k_scale)
        else:
            self.k_scale = None
            self.v_scale = None
        # Recurrent layers keep a state per request, not per token: one
        # slot of every Mamba layer's (SSM, conv) state, taken with the
        # sequence's first allocation and released with its table. The
        # pools hold the attention layers only (cfg.num_kv_layers).
        n_rec = len(self.cfg.layers_of(MAMBA))
        self.state: Optional[Dict[str, jax.Array]] = None
        self.slot_of: Dict[int, int] = {}
        self._free_slots: List[int] = []
        if n_rec:
            if self.state_slots < 1:
                raise ValueError(
                    f"{self.cfg.name} has {n_rec} recurrent (Mamba) layers: "
                    f"give the pool state_slots >= 1, one per request that "
                    f"runs at once")
            self.state = {
                k: jnp.zeros(shape, dt) for k, (shape, dt) in
                ssm.state_slot_shapes(self.cfg, n_rec,
                                      self.state_slots).items()}
            self._free_slots = list(range(self.state_slots))[::-1]
        npb = self.blocks_per_shard
        # per-shard free lists: shard s owns global ids [s·npb, (s+1)·npb)
        self._free_shard: List[List[int]] = [
            list(range(s * npb, (s + 1) * npb)) for s in range(self.n_shards)]
        # shards quarantined by the fault-recovery path: their free lists
        # are retained (blocks drain back in as victims release refs) but
        # masked out of every allocation / capacity view until rejoin
        self._quarantined: set = set()
        self.tables: Dict[int, List[int]] = {}
        self.lengths: Dict[int, int] = {}
        # block id -> number of live tables referencing it (only blocks that
        # are currently referenced have an entry; free blocks have none)
        self.refcounts: Dict[int, int] = {}
        # seq -> block ids it BORROWED via share_blocks (vs allocated
        # itself). A borrower's prefill-write into a still-shared borrowed
        # block copy-on-writes; the original allocator's write is the
        # canonical fill the borrowers are waiting for (within one admission
        # wave a recipient maps the donor's blocks BEFORE the donor's
        # prefill has stored them) and goes through in place.
        self._borrowed: Dict[int, set] = {}
        # cumulative counters (benchmarks / EngineStats surface them)
        self.blocks_shared_total = 0   # refcount bumps via share_blocks
        self.cow_forks = 0             # copy-on-write block copies
        # memoised gather indices, keyed by the CONTENT of the gathered
        # table slice (the physical block-id tuple): a prefix-sharing
        # admission wave's K sharers map onto the same donor blocks, so
        # they hit one entry instead of K host->device conversions, and a
        # chunked prefill reuses its growing prefix without rebuilding the
        # array each chunk. Content keys can never go stale — the value is
        # a pure function of the ids (CoW/free/realloc just miss or alias
        # harmlessly); the dict is cleared when it outgrows its cap.
        self._gather_idx_cache: Dict[Tuple[int, ...], jax.Array] = {}

    @property
    def blocks_per_shard(self) -> int:
        return self.num_blocks // self.n_shards

    @property
    def free(self) -> List[int]:
        """All ALLOCATABLE free block ids (flattened across live shards;
        a quarantined shard's drained blocks are excluded) — read-only."""
        return [b for s, shard in enumerate(self._free_shard)
                for b in shard if s not in self._quarantined]

    @property
    def num_free(self) -> int:
        """Count of allocatable free blocks — O(shards), unlike
        ``len(self.free)`` which materialises every id (the per-iteration
        pressure checks run this on the serving hot loop). Quarantined
        shards contribute nothing."""
        return sum(len(s) for i, s in enumerate(self._free_shard)
                   if i not in self._quarantined)

    # ---------------- shard health (fault-recovery surface) ----------------
    @property
    def live_shards(self) -> List[int]:
        """Shards currently accepting allocations (not quarantined)."""
        return [s for s in range(self.n_shards) if s not in self._quarantined]

    @property
    def quarantined_shards(self) -> Tuple[int, ...]:
        return tuple(sorted(self._quarantined))

    @property
    def capacity_blocks(self) -> int:
        """Total blocks the pool can currently hold — ``num_blocks`` when
        healthy, the surviving shards' share when degraded. Every
        "can this request EVER fit" check must use this, not
        ``num_blocks``: admission guards and stall detection otherwise
        promise capacity a dead shard no longer provides."""
        return self.blocks_per_shard * (self.n_shards -
                                        len(self._quarantined))

    def seqs_on_shard(self, shard: int) -> List[int]:
        """Live sequences holding at least one block on `shard` — the
        victim set a shard death forces through recovery (a sequence that
        merely BORROWS a donor's block there is a victim too: its context
        includes the lost bytes)."""
        lo, hi = shard * self.blocks_per_shard, \
            (shard + 1) * self.blocks_per_shard
        return sorted(sid for sid, table in self.tables.items()
                      if any(lo <= b < hi for b in table))

    def quarantine_shard(self, shard: int) -> None:
        """Mask `shard` out of the allocator: no new block lands on it and
        every capacity view (``num_free`` / ``capacity_blocks`` /
        ``can_allocate``) drops to the surviving shards. Its free list is
        kept — blocks drain back as the recovery path releases victim
        refs — but stays unallocatable until :meth:`rejoin_shard`."""
        if not 0 <= shard < self.n_shards:
            raise ValueError(f"shard {shard} outside [0, {self.n_shards})")
        self._quarantined.add(shard)

    def rejoin_shard(self, shard: int) -> None:
        """Restore a quarantined shard's capacity (replacement hardware /
        restarted worker). Only blocks that drained back to its free list
        return — any block a live sequence somehow still references stays
        referenced (refcounts are the single source of truth)."""
        self._quarantined.discard(shard)

    def shard_of(self, block_id: int) -> int:
        return block_id // self.blocks_per_shard

    def _pop_block(self, seq_slot: int) -> int:
        """Pop a free block for a sequence's `seq_slot`-th table entry:
        round-robin over the LIVE shards (quarantined shards are masked
        out — the shard-masked round-robin keeps the balance invariant
        over survivors), falling back to the least-loaded (most-free)
        live shard when the target is exhausted."""
        live = self.live_shards
        if not live:
            raise OutOfBlocks("every pool shard is quarantined")
        target = live[seq_slot % len(live)]
        if not self._free_shard[target]:
            target = max(live, key=lambda s: len(self._free_shard[s]))
            if not self._free_shard[target]:
                raise OutOfBlocks("pool exhausted")
        return self._free_shard[target].pop()

    # ---------------- allocation ----------------
    def blocks_needed(self, n_tokens: int) -> int:
        return -(-n_tokens // self.block_size)

    def can_allocate(self, n_tokens: int) -> bool:
        return self.num_free >= self.blocks_needed(n_tokens)

    def _degraded_kw(self) -> Dict:
        """PoolExhausted kwargs carrying the shard-health context — every
        raise site attaches these so operators can tell "pool too small"
        from "pool degraded by a shard fault"."""
        return {"quarantined_shards": self.quarantined_shards,
                "live_shards": tuple(self.live_shards)}

    def _degraded_note(self) -> str:
        if not self._quarantined:
            return ""
        q = sorted(self._quarantined)
        return (f" [pool DEGRADED: shard(s) {q} quarantined after a fault; "
                f"{len(self.live_shards)} of {self.n_shards} shards live, "
                f"capacity {self.capacity_blocks} of {self.num_blocks} "
                f"blocks]")

    def allocate(self, seq_id: int, n_tokens: int) -> None:
        """Give `seq_id` capacity for `n_tokens`. A fresh sequence gets a new
        round-robin table; an EXISTING sequence is EXTENDED — fresh private
        blocks are appended until capacity covers `n_tokens`. Extension
        serves both admission flavours: a table seeded by
        :meth:`share_blocks` grows past its shared prefix (admission charges
        only the unshared suffix against the free list), and a CHUNKED
        prefill grows its table incrementally, one chunk's blocks per engine
        iteration, so peak up-front allocation is O(chunk) not O(prompt)."""
        if seq_id in self.tables:       # extend (share-seeded or chunked)
            table = self.tables[seq_id]
            assert n_tokens >= self.lengths[seq_id], \
                f"seq {seq_id}: cannot shrink allocation"
            need = self.blocks_needed(n_tokens) - len(table)
            have = self.num_free
            if need > have:
                raise PoolExhausted(
                    f"extending seq {seq_id}: need {need}, have {have}"
                    f"{self._degraded_note()}",
                    rid=seq_id, live_tokens=sum(self.lengths.values()),
                    free_blocks=have, **self._degraded_kw())
            for i in range(len(table), len(table) + need):
                b = self._pop_block(i)
                self.refcounts[b] = 1
                table.append(b)
            self.lengths[seq_id] = n_tokens
            return
        need = self.blocks_needed(n_tokens)
        have = self.num_free
        if need > have:
            raise PoolExhausted(
                f"allocating seq {seq_id}: need {need}, have {have}"
                f"{self._degraded_note()}",
                rid=seq_id, live_tokens=sum(self.lengths.values()),
                free_blocks=have, **self._degraded_kw())
        if self.state is not None and not self._free_slots:
            raise OutOfBlocks(f"allocating seq {seq_id}: every one of the "
                              f"{self.state_slots} state slots is taken")
        # round-robin over shards: the sequence's i-th block lands on shard
        # i mod n_shards, so its KV spans every pool chip near-evenly
        table = [self._pop_block(i) for i in range(need)]
        for b in table:
            self.refcounts[b] = 1
        self.tables[seq_id] = table
        self.lengths[seq_id] = n_tokens
        if self.state is not None:
            with TraceAnnotation("engine.state"):
                self.slot_of[seq_id] = self._free_slots.pop()

    def share_blocks(self, src_rid: int, dst_rid: int, n_tokens: int) -> int:
        """Map a NEW sequence `dst_rid`'s table onto `src_rid`'s existing
        physical blocks covering its first `n_tokens` — the prefix-sharing
        entry point. No pool memory is consumed: the shared blocks'
        refcounts are bumped instead. `n_tokens` need not be block-aligned:
        a trailing partial block is shared too (the fork case — the first
        divergent write into it copy-on-writes). Returns the number of
        blocks shared. Extend the table afterwards with :meth:`allocate`."""
        assert dst_rid not in self.tables, \
            f"seq {dst_rid} already allocated — share_blocks seeds new tables"
        if n_tokens < 1 or n_tokens > self.lengths[src_rid]:
            raise ValueError(
                f"share_blocks: n_tokens={n_tokens} outside donor {src_rid}'s"
                f" stored range [1, {self.lengths[src_rid]}]")
        shared = self.tables[src_rid][:self.blocks_needed(n_tokens)]
        for b in shared:
            self.refcounts[b] += 1
        self.tables[dst_rid] = list(shared)
        self.lengths[dst_rid] = n_tokens
        self._borrowed[dst_rid] = set(shared)
        self.blocks_shared_total += len(shared)
        return len(shared)

    def _cow_block(self, seq_id: int, slot: int) -> None:
        """Copy-on-write fork of `seq_id`'s table slot: pop a private block
        (same round-robin slot rule, so shard balance survives), copy the
        physical tile, decrement the donor refcount. The donor's data is
        never touched. Raises OutOfBlocks when no block is free."""
        old = self.tables[seq_id][slot]
        new = self._pop_block(slot)
        self.refcounts[old] -= 1
        self.refcounts[new] = 1
        self.tables[seq_id][slot] = new
        self._borrowed.get(seq_id, set()).discard(old)
        self.k_pool = self.k_pool.at[:, :, new].set(self.k_pool[:, :, old])
        self.v_pool = self.v_pool.at[:, :, new].set(self.v_pool[:, :, old])
        if self.k_scale is not None:   # the scale tile forks with its block
            self.k_scale = self.k_scale.at[:, :, new].set(
                self.k_scale[:, :, old])
            self.v_scale = self.v_scale.at[:, :, new].set(
                self.v_scale[:, :, old])
        self.cow_forks += 1

    def blocks_to_append(self, seq_id: int) -> int:
        """Fresh blocks the next :meth:`append_token` will consume: 1 when
        the sequence must grow its table OR copy-on-write a shared tail
        block, else 0 — the engine's pool-pressure check must count both."""
        n = self.lengths[seq_id]
        table = self.tables[seq_id]
        if self.blocks_needed(n + 1) > len(table):
            return 1
        if self.refcounts[table[n // self.block_size]] > 1:
            return 1
        return 0

    def append_token(self, seq_id: int) -> None:
        n = self.lengths[seq_id] + 1
        table = self.tables[seq_id]
        try:
            if self.blocks_needed(n) > len(table):
                b = self._pop_block(len(table))
                self.refcounts[b] = 1
                table.append(b)
            else:
                # the new token lands in an existing block: fork it first if
                # another live sequence still references it (shared tail)
                slot = (n - 1) // self.block_size
                if self.refcounts[table[slot]] > 1:
                    self._cow_block(seq_id, slot)
        except OutOfBlocks:
            free = self.num_free
            live = sum(self.lengths.values())
            raise PoolExhausted(
                f"KV pool exhausted growing request {seq_id} to token "
                f"{n}: {live} live tokens across {len(self.tables)} "
                f"sequences occupy all {self.capacity_blocks} usable "
                f"blocks ({free} free){self._degraded_note()} — preempt "
                f"a victim or raise num_blocks",
                rid=seq_id, live_tokens=live, free_blocks=free,
                **self._degraded_kw()) from None
        self.lengths[seq_id] = n

    def free_seq(self, seq_id: int) -> None:
        for b in self.tables.pop(seq_id):
            self.refcounts[b] -= 1
            if self.refcounts[b] == 0:
                del self.refcounts[b]
                self._free_shard[self.shard_of(b)].append(b)
        self._borrowed.pop(seq_id, None)
        del self.lengths[seq_id]
        if seq_id in self.slot_of:
            with TraceAnnotation("engine.state"):
                self._free_slots.append(self.slot_of.pop(seq_id))

    @property
    def used_blocks(self) -> int:
        """PHYSICAL blocks in use — a block shared by K sequences counts
        once (the memory actually occupied; what sharing saves)."""
        return self.num_blocks - sum(len(s) for s in self._free_shard)

    @property
    def pool_bytes_resident(self) -> int:
        """Resident bytes of the whole pool allocation: value pools plus
        (int8) the fp32 scale sidecars — the §3.1 capacity quantity
        ``EngineStats.kv_pool_bytes_resident`` surfaces. int8 ≈ 0.5× bf16
        for hd ≫ 4 (hd + 4 scale bytes vs 2·hd per token-head)."""
        total = int(self.k_pool.nbytes + self.v_pool.nbytes)
        if self.k_scale is not None:
            total += int(self.k_scale.nbytes + self.v_scale.nbytes)
        return total

    # ---------------- recurrent state slots ----------------
    @property
    def has_free_slot(self) -> bool:
        """A new sequence can take a state slot (always, without recurrent
        layers)."""
        return self.state is None or bool(self._free_slots)

    @property
    def state_bytes_resident(self) -> int:
        """Bytes of every state slot of every recurrent layer: the SSM
        state (fp32) and the conv inputs."""
        if self.state is None:
            return 0
        return int(sum(a.nbytes for a in self.state.values()))

    def slot_indices(self, seq_ids: Sequence[int]) -> np.ndarray:
        """(B,) int32 state slots of a batch's sequences, in order."""
        with TraceAnnotation("engine.state"):
            return np.asarray([self.slot_of[s] for s in seq_ids], np.int32)

    def bytes_per_live_token(self) -> int:
        """Pool bytes one token of context occupies (K + V across the KV
        layers, scale sidecars included) — the per-step KV read accounting
        unit (`kv_bytes_read_per_step ≈ live_tokens · this`)."""
        L, Hkv, _, _, hd = self.k_pool.shape
        e = self.k_pool.dtype.itemsize
        per = 2 * L * Hkv * hd * e
        if self.k_scale is not None:
            per += 2 * L * Hkv * 4
        return per

    def utilisation(self) -> float:
        toks = sum(self.lengths.values())
        return toks / (self.num_blocks * self.block_size)

    def unique_live_tokens(self, seq_ids: Optional[Sequence[int]] = None
                           ) -> int:
        """Live tokens over UNIQUE physical blocks — a block shared by K
        sequences counts once, at the deepest fill any sharer reaches (the
        residency/ideal-DMA accounting; ``sum(lengths)`` double-counts
        shared prefixes)."""
        if seq_ids is None:
            seq_ids = list(self.tables)
        per_block: Dict[int, int] = {}
        bs = self.block_size
        for sid in seq_ids:
            length = self.lengths[sid]
            for j, g in enumerate(self.tables[sid]):
                t = min(bs, max(0, length - j * bs))
                if t > per_block.get(g, 0):
                    per_block[g] = t
        return sum(per_block.values())

    # ---------------- hot-path views ----------------
    def block_table_batch(self, seq_ids: Sequence[int], width: int = 1
                          ) -> Tuple[np.ndarray, np.ndarray]:
        """Batched (B, nb) block table + (B,) lengths for the paged decode
        step. nb covers the longest live sequence, rounded up to a
        multiple of ``width`` (fewer distinct shapes, so fewer decode
        programs); pad slots are block 0 (their positions are ≥
        cache_len, so the kernel masks them)."""
        lens = np.array([self.lengths[sid] for sid in seq_ids], np.int32)
        nb = max(1, self.blocks_needed(int(lens.max()))) if len(lens) else 1
        nb = -(-nb // width) * width
        tables = np.zeros((len(seq_ids), nb), np.int32)
        for i, sid in enumerate(seq_ids):
            t = self.tables[sid][:nb]
            tables[i, :len(t)] = t
        return tables, lens

    def block_table_shards(self, seq_ids: Sequence[int]
                           ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        """Per-shard LOCAL block tables for the block-parallel decode step.

        Returns (local_tables, local_positions, shard_tokens):
          * local_tables (n_shards, B, nbl) int32 — pool-block ids LOCAL to
            each shard's contiguous slice (global − shard·blocks_per_shard),
            i.e. direct indices into the (npb, block_size, hd) pool slice
            shard_map hands that device. Pad slots are 0.
          * local_positions (n_shards, B, nbl) int32 — each slot's global
            base position in the sequence (slot index in the global table ×
            block_size); POS_PAD on pad slots so every mask kills them. A
            shard's walk is non-contiguous in the sequence, so these — not
            slot·block_size — anchor the causal/window/sink masks.
          * shard_tokens (n_shards, B) int32 — live tokens per (shard, seq):
            the per-chip KV-read accounting (round-robin placement keeps
            max−min ≤ block_size for any single sequence). A PHYSICAL block
            shared by several sequences in the batch is counted ONCE, for
            the first sequence that references it — a prefix-shared block
            lives on whatever shard the donor placed it and its bytes are
            resident (and streamable) once per chip, not once per sharer.
        """
        B = len(seq_ids)
        n, npb, bs = self.n_shards, self.blocks_per_shard, self.block_size
        per = [[[] for _ in range(B)] for _ in range(n)]  # (local id, base)
        shard_tokens = np.zeros((n, B), np.int32)
        # deepest fill across sharers, same rule as shard_live_tokens /
        # unique_live_tokens (a partial tail shared at different depths is
        # resident at the donor's deeper fill regardless of batch order)
        fill: Dict[int, int] = {}
        for sid in seq_ids:
            length = self.lengths[sid]
            for j, g in enumerate(self.tables[sid]):
                t = min(bs, max(0, length - j * bs))
                if t > fill.get(g, 0):
                    fill[g] = t
        counted: set = set()
        for i, sid in enumerate(seq_ids):
            for j, g in enumerate(self.tables[sid]):
                s = self.shard_of(g)
                per[s][i].append((g - s * npb, j * bs))
                if g not in counted:
                    counted.add(g)
                    shard_tokens[s, i] += fill[g]
        nbl = max([1] + [len(per[s][i]) for s in range(n) for i in range(B)])
        local_tables = np.zeros((n, B, nbl), np.int32)
        local_positions = np.full((n, B, nbl), POS_PAD, np.int32)
        for s in range(n):
            for i in range(B):
                for j, (lb, base) in enumerate(per[s][i]):
                    local_tables[s, i, j] = lb
                    local_positions[s, i, j] = base
        return local_tables, local_positions, shard_tokens

    def shard_live_tokens(self, seq_ids: Optional[Sequence[int]] = None
                          ) -> np.ndarray:
        """(n_shards,) live tokens held per pool shard (all sequences by
        default) — the per-chip KV balance the block benchmark reports.
        A shared physical block counts once, at the deepest fill any sharer
        reaches (residency, not per-sequence reads)."""
        if seq_ids is None:
            seq_ids = list(self.tables)
        totals = np.zeros((self.n_shards,), np.int64)
        bs = self.block_size
        per_block: Dict[int, int] = {}
        for sid in seq_ids:
            length = self.lengths[sid]
            for j, g in enumerate(self.tables[sid]):
                t = min(bs, max(0, length - j * bs))
                if t > per_block.get(g, 0):
                    per_block[g] = t
        for g, t in per_block.items():
            totals[self.shard_of(g)] += t
        return totals

    # ---------------- data movement ----------------
    def write_prefill(self, seq_id: int, k: jax.Array, v: jax.Array,
                      start_token: int = 0) -> None:
        """k/v: HEAD-MAJOR (L, Hkv, S, hd) for this sequence's prompt — the
        prefill cache layout, stored without any transpose.

        ``start_token`` (block-aligned) writes the slice starting at that
        position — the prefix-sharing path prefills only the unshared
        suffix, leaving the shared prefix blocks untouched. A re-prefill
        into a still-shared BORROWED block copy-on-write-forks it first (a
        divergent write must never corrupt the donor); a write by the
        block's original allocator goes through in place — it is the
        canonical fill recipients that shared within the same admission
        wave are waiting on."""
        if start_token % self.block_size:
            raise ValueError(
                f"write_prefill start_token ({start_token}) must be "
                f"block-aligned (block_size={self.block_size})")
        S = k.shape[2]
        table = self.tables[seq_id]
        if start_token + S > len(table) * self.block_size:
            free = self.num_free
            live = sum(self.lengths.values())
            raise PoolExhausted(
                f"request {seq_id}: write_prefill of {S} tokens at "
                f"{start_token} exceeds its allocated {len(table)} blocks × "
                f"{self.block_size} (= {len(table) * self.block_size} "
                f"tokens); pool holds {live} live tokens with {free} of "
                f"{self.num_blocks} blocks free{self._degraded_note()} — "
                f"allocate() must cover the prompt first", rid=seq_id,
                live_tokens=live, free_blocks=free, **self._degraded_kw())
        # within capacity, the token count must agree EXACTLY with the
        # sequence's allocated length — a short write used to zero-pad the
        # tail block silently while `lengths` claimed those tokens stored,
        # so decode read zeros as real context (and a long one overwrote
        # slack slots `lengths` never covered)
        expected = self.lengths[seq_id] - start_token
        if S != expected or k.shape != v.shape:
            raise ValueError(
                f"request {seq_id}: write_prefill got k/v of {S} tokens "
                f"(k {tuple(k.shape)}, v {tuple(v.shape)}) at start_token "
                f"{start_token}, but the sequence's allocated length is "
                f"{self.lengths[seq_id]} — expected exactly {expected} "
                f"tokens; allocate() the true token count first (chunked "
                f"prefill extends the allocation before each chunk write)")
        b0 = start_token // self.block_size
        nb = self.blocks_needed(S)
        borrowed = self._borrowed.get(seq_id, ())
        for slot in range(b0, b0 + nb):
            if table[slot] in borrowed and self.refcounts[table[slot]] > 1:
                self._cow_block(seq_id, slot)
        ks = vs = None
        if self.kv_dtype == "int8":    # quantize at write time, pre-pad
            k, ks = kv_quant.quantize_kv(k)
            v, vs = kv_quant.quantize_kv(v)
        pad = nb * self.block_size - S
        if pad:
            k = jnp.pad(k, [(0, 0), (0, 0), (0, pad), (0, 0)])
            v = jnp.pad(v, [(0, 0), (0, 0), (0, pad), (0, 0)])
        kb = k.reshape(k.shape[0], k.shape[1], nb, self.block_size,
                       k.shape[3])
        vb = v.reshape(*kb.shape)
        idx = jnp.asarray(table[b0:b0 + nb])
        self.k_pool = self.k_pool.at[:, :, idx].set(kb)
        self.v_pool = self.v_pool.at[:, :, idx].set(vb)
        if ks is not None:
            if pad:
                ks = jnp.pad(ks, [(0, 0), (0, 0), (0, pad)])
                vs = jnp.pad(vs, [(0, 0), (0, 0), (0, pad)])
            shp = (ks.shape[0], ks.shape[1], nb, 1, self.block_size)
            self.k_scale = self.k_scale.at[:, :, idx].set(ks.reshape(shp))
            self.v_scale = self.v_scale.at[:, :, idx].set(vs.reshape(shp))

    def write_prefill_chunk(self, seq_id: int, k: jax.Array, v: jax.Array,
                            start_token: int) -> None:
        """Incremental chunk write — the chunked-prefill data path: extend
        the sequence's allocation to cover exactly this chunk (fresh blocks
        are popped as the chunk completes, so peak up-front allocation is
        one chunk, not the prompt), then scatter the chunk's head-major
        (L, Hkv, C, hd) K/V at `start_token` (block-aligned; only the FINAL
        chunk may be a partial block). Raises the same contextual
        :class:`PoolExhausted` as the decode path when the pool cannot
        cover the chunk's new blocks."""
        target = start_token + k.shape[2]
        if target > self.lengths.get(seq_id, 0):
            try:
                self.allocate(seq_id, target)
            except OutOfBlocks:
                free = self.num_free
                live = sum(self.lengths.values())
                raise PoolExhausted(
                    f"KV pool exhausted growing request {seq_id}'s chunked "
                    f"prefill to token {target}: {live} live tokens across "
                    f"{len(self.tables)} sequences occupy all "
                    f"{self.capacity_blocks} usable blocks ({free} free)"
                    f"{self._degraded_note()} — preempt a victim or raise "
                    f"num_blocks", rid=seq_id, live_tokens=live,
                    free_blocks=free, **self._degraded_kw()) from None
        self.write_prefill(seq_id, k, v, start_token=start_token)

    def write_token(self, seq_id: int, k: jax.Array, v: jax.Array,
                    position: int) -> None:
        """k/v: (L, Hkv, hd) for one token at `position` (0-based)."""
        slot = position // self.block_size
        if self.refcounts[self.tables[seq_id][slot]] > 1:
            self._cow_block(seq_id, slot)      # never write a donor's block
        blk = self.tables[seq_id][slot]
        off = position % self.block_size
        if self.kv_dtype == "int8":
            k, ks = kv_quant.quantize_token(k)
            v, vs = kv_quant.quantize_token(v)
            self.k_scale = self.k_scale.at[:, :, blk, 0, off].set(ks)
            self.v_scale = self.v_scale.at[:, :, blk, 0, off].set(vs)
        self.k_pool = self.k_pool.at[:, :, blk, off].set(k)
        self.v_pool = self.v_pool.at[:, :, blk, off].set(v)

    def write_tokens(self, seq_ids: Sequence[int], k_new: jax.Array,
                     v_new: jax.Array, positions: Sequence[int]) -> None:
        """Batched scatter of one token per sequence — the decode step's
        single pool write. k_new/v_new: (L, B, Hkv, hd) as produced by the
        model's decode updates; positions: per-sequence 0-based slots
        (the pre-append lengths). Replaces the per-sequence host loop.
        Shared targets copy-on-write first (``append_token`` normally forked
        already — this is the allocator-level guarantee)."""
        for sid, p in zip(seq_ids, positions):
            slot = p // self.block_size
            if self.refcounts[self.tables[sid][slot]] > 1:
                self._cow_block(sid, slot)
        blk = jnp.asarray([self.tables[sid][p // self.block_size]
                           for sid, p in zip(seq_ids, positions)], jnp.int32)
        off = jnp.asarray([p % self.block_size for p in positions], jnp.int32)
        kn = jnp.swapaxes(k_new, 1, 2)  # (L, Hkv, B, hd)
        vn = jnp.swapaxes(v_new, 1, 2)
        if self.kv_dtype == "int8":
            kn, kns = kv_quant.quantize_token(kn)   # scales (L, Hkv, B)
            vn, vns = kv_quant.quantize_token(vn)
            self.k_scale = self.k_scale.at[:, :, blk, 0, off].set(kns)
            self.v_scale = self.v_scale.at[:, :, blk, 0, off].set(vns)
        self.k_pool = self.k_pool.at[:, :, blk, off].set(kn)
        self.v_pool = self.v_pool.at[:, :, blk, off].set(vn)

    def gather_prefix_indices(self, seq_id: int, n_tokens: int) -> jax.Array:
        """(nb,) int32 device array of the pool-block ids covering this
        sequence's first `n_tokens` (block-aligned) — the index operand of
        every prefix gather (suffix prefill, chunked prefill, recompute).

        MEMOISED by block-id content: a prefix-sharing admission wave's K
        recipients all map onto the donor's physical blocks, so the whole
        wave (and every later chunk / recompute over the same prefix) reuses
        ONE converted array instead of re-building it per call. Keys are the
        ids themselves, so copy-on-write forks or free/re-allocate cycles
        can never serve a wrong value — at worst they miss."""
        if n_tokens % self.block_size:
            raise ValueError(
                f"gather_prefix n_tokens ({n_tokens}) must be block-aligned "
                f"(block_size={self.block_size})")
        key = tuple(self.tables[seq_id][:n_tokens // self.block_size])
        idx = self._gather_idx_cache.get(key)
        if idx is None:
            if len(self._gather_idx_cache) > 4096:   # bound the memo
                self._gather_idx_cache.clear()
            idx = jnp.asarray(key, jnp.int32)
            self._gather_idx_cache[key] = idx
        return idx

    def gather_prefix(self, seq_id: int, n_tokens: int
                      ) -> Tuple[jax.Array, jax.Array]:
        """HEAD-MAJOR (L, Hkv, n_tokens, hd) K/V of this sequence's first
        `n_tokens` (block-aligned) — the context operand of the prefix-
        cached suffix prefill. One gather per ADMISSION (not per decode
        step), so the no-densify invariant on the decode hot path holds."""
        idx = self.gather_prefix_indices(seq_id, n_tokens)
        L, Hkv = self.k_pool.shape[0], self.k_pool.shape[1]
        hd = self.k_pool.shape[4]
        k = self.k_pool[:, :, idx].reshape(L, Hkv, n_tokens, hd)
        v = self.v_pool[:, :, idx].reshape(L, Hkv, n_tokens, hd)
        if self.kv_dtype == "int8":   # admission-time dequant (off hot path)
            ks = self.k_scale[:, :, idx].reshape(L, Hkv, n_tokens)
            vs = self.v_scale[:, :, idx].reshape(L, Hkv, n_tokens)
            k = kv_quant.dequantize_kv(k, ks, self.cfg.dtype)
            v = kv_quant.dequantize_kv(v, vs, self.cfg.dtype)
        return k, v

    def gather(self, seq_ids: List[int], pad_len: int
               ) -> Tuple[jax.Array, jax.Array, jax.Array]:
        """Dense (L, B, pad_len, Hkv, hd) views + lengths for the batch.

        TEST ORACLE ONLY: the serving engines attend over the pool in place
        (block_table_batch + the paged kernel); this materialised copy is
        exactly the per-step traffic the paged path eliminates."""
        nb = -(-pad_len // self.block_size)
        tables = np.zeros((len(seq_ids), nb), np.int32)
        lens = np.zeros((len(seq_ids),), np.int32)
        for i, sid in enumerate(seq_ids):
            t = self.tables[sid][:nb]
            tables[i, :len(t)] = t
            lens[i] = self.lengths[sid]
        idx = jnp.asarray(tables)      # (B, nb)
        k = self.k_pool[:, :, idx]     # (L, Hkv, B, nb, bs, hd)
        v = self.v_pool[:, :, idx]
        if self.kv_dtype == "int8":    # oracle only — dense dequant is fine
            k = kv_quant.dequantize_kv(k, self.k_scale[:, :, idx, 0],
                                       self.cfg.dtype)
            v = kv_quant.dequantize_kv(v, self.v_scale[:, :, idx, 0],
                                       self.cfg.dtype)
        L, Hkv = k.shape[0], k.shape[1]
        B = len(seq_ids)
        k = jnp.transpose(k, (0, 2, 3, 4, 1, 5)).reshape(
            L, B, nb * self.block_size, Hkv, -1)[:, :, :pad_len]
        v = jnp.transpose(v, (0, 2, 3, 4, 1, 5)).reshape(
            L, B, nb * self.block_size, Hkv, -1)[:, :, :pad_len]
        return k, v, jnp.asarray(lens)

    # ---------------- block-granular KV handoff (disaggregated cluster) ----
    def export_seqs(self, seq_ids: Sequence[int]) -> "KVHandoffPayload":
        """Serialize the given sequences' KV state into a block-granular
        :class:`KVHandoffPayload` — the prefill→decode wire unit of the
        disaggregated cluster (serving/cluster/).

        The payload carries each sequence's LOGICAL table (its source block
        ids, in slot order) plus every referenced PHYSICAL block exactly
        once: a block shared by several exported sequences (refcounted
        prefix sharing) appears once in ``block_ids`` / the stacked tiles,
        so sharing survives the wire without re-transferring bytes. Tiles
        stay in the pool's head-major ``(L, Hkv, n, bs, hd)`` layout — the
        importer scatters them block-by-block into its own pool (the
        no-densify invariant holds across the wire: no dense seq-major view
        is ever built on either side).

        The source sequences are NOT freed — the prefill engine decides
        whether to retain them as prefix donors or release them."""
        missing = [sid for sid in seq_ids if sid not in self.tables]
        if missing:
            raise ValueError(
                f"export_seqs: sequence(s) {missing} have no table in this "
                f"pool — only admitted, prefilled sequences can be exported")
        ids: List[int] = []
        seen: set = set()
        for sid in seq_ids:
            for b in self.tables[sid]:
                if b not in seen:
                    seen.add(b)
                    ids.append(b)
        idx = jnp.asarray(ids, jnp.int32)
        # one device gather per payload, then host-side tiles (the "wire")
        k = np.asarray(self.k_pool[:, :, idx])
        v = np.asarray(self.v_pool[:, :, idx])
        ks = vs = None
        if self.k_scale is not None:   # scales ship with their blocks
            ks = np.asarray(self.k_scale[:, :, idx])
            vs = np.asarray(self.v_scale[:, :, idx])
        return KVHandoffPayload(
            tables={sid: tuple(self.tables[sid]) for sid in seq_ids},
            lengths={sid: self.lengths[sid] for sid in seq_ids},
            block_ids=tuple(ids), k_blocks=k, v_blocks=v,
            block_size=self.block_size, k_scales=ks, v_scales=vs)

    def prealloc_handoff(self, payload: "KVHandoffPayload"
                         ) -> Dict[int, int]:
        """Phase 1 of a handoff import: reserve destination blocks for every
        sequence in `payload` and rebuild its table/refcount/length state —
        no bytes move yet (that is :meth:`write_handoff_blocks`, the
        incremental phase 2 a decode replica's TransferQueue drives).

        Each UNIQUE source physical block gets exactly ONE destination
        block, popped by the same round-robin slot rule as a local
        allocation (using the slot of its first referencing table entry, so
        the shard-balance invariant survives the wire); per-sequence tables
        are then rebuilt through the src→dst mapping and refcounts are set
        to the number of referencing tables — shared prefixes stay shared
        on the destination pool. Returns the src→dst block-id mapping the
        transfer phase scatters through.

        Raises contextual :class:`PoolExhausted` (degraded-shard context
        included) when the destination pool cannot cover the payload; on
        failure nothing is allocated (all-or-nothing)."""
        if payload.block_size != self.block_size:
            raise ValueError(
                f"prealloc_handoff: payload block_size "
                f"({payload.block_size}) != destination pool block_size "
                f"({self.block_size}) — handoff is block-granular and "
                f"never re-chunks tiles")
        for rid in payload.tables:
            if rid in self.tables:
                raise ValueError(
                    f"prealloc_handoff: seq {rid} already has a table on "
                    f"the destination pool — a handoff import must land on "
                    f"a fresh rid")
        need = len(payload.block_ids)
        have = self.num_free
        if need > have:
            live = sum(self.lengths.values())
            raise PoolExhausted(
                f"handoff prealloc of {len(payload.tables)} seq(s) needs "
                f"{need} blocks, have {have}{self._degraded_note()}",
                rid=next(iter(payload.tables)), live_tokens=live,
                free_blocks=have, **self._degraded_kw())
        # slot of each unique block's FIRST reference drives placement
        first_slot: Dict[int, int] = {}
        for table in payload.tables.values():
            for slot, b in enumerate(table):
                first_slot.setdefault(b, slot)
        mapping: Dict[int, int] = {}
        try:
            for b in payload.block_ids:
                mapping[b] = self._pop_block(first_slot[b])
        except OutOfBlocks:
            for dst in mapping.values():   # all-or-nothing: roll back
                self._free_shard[self.shard_of(dst)].append(dst)
            live = sum(self.lengths.values())
            raise PoolExhausted(
                f"handoff prealloc exhausted the pool after "
                f"{len(mapping)} of {need} blocks{self._degraded_note()}",
                rid=next(iter(payload.tables)), live_tokens=live,
                free_blocks=self.num_free, **self._degraded_kw()) from None
        owners: Dict[int, int] = {}     # dst block -> first referencing rid
        for rid, src_table in payload.tables.items():
            dst_table = [mapping[b] for b in src_table]
            self.tables[rid] = dst_table
            self.lengths[rid] = payload.lengths[rid]
            for d in dst_table:
                self.refcounts[d] = self.refcounts.get(d, 0) + 1
                owners.setdefault(d, rid)
        for rid, src_table in payload.tables.items():
            borrowed = {mapping[b] for b in src_table
                        if owners[mapping[b]] != rid}
            if borrowed:
                self._borrowed[rid] = borrowed
        return mapping

    def write_handoff_blocks(self, payload: "KVHandoffPayload",
                             mapping: Dict[int, int],
                             start: int, stop: int) -> int:
        """Phase 2 of a handoff import: land payload blocks [start, stop)
        (indices into ``payload.block_ids``) at their mapped destination
        ids — one batched block-granular scatter, never a dense view. The
        sub-range IS the simulated wire budget: a decode replica's
        TransferQueue calls this with ``transfer_blocks_per_step`` blocks
        per engine step. Returns the bytes written."""
        # validate dtype compatibility BEFORE any scatter: a mismatched
        # payload must fail cleanly, not corrupt the pool and then raise
        if payload.k_scales is not None and self.k_scale is None:
            raise ValueError(
                "write_handoff_blocks: payload carries int8 scales but "
                "the destination pool is not kv_dtype='int8' — source "
                "and destination tiers must agree on kv_dtype")
        if payload.k_scales is None and self.k_scale is not None:
            raise ValueError(
                "write_handoff_blocks: destination pool is kv_dtype='int8' "
                "but the payload carries no scales — source and destination "
                "tiers must agree on kv_dtype")
        ids = payload.block_ids[start:stop]
        if not ids:
            return 0
        dst = jnp.asarray([mapping[b] for b in ids], jnp.int32)
        k = jnp.asarray(payload.k_blocks[:, :, start:stop])
        v = jnp.asarray(payload.v_blocks[:, :, start:stop])
        self.k_pool = self.k_pool.at[:, :, dst].set(k)
        self.v_pool = self.v_pool.at[:, :, dst].set(v)
        if payload.k_scales is not None:
            self.k_scale = self.k_scale.at[:, :, dst].set(
                jnp.asarray(payload.k_scales[:, :, start:stop]))
            self.v_scale = self.v_scale.at[:, :, dst].set(
                jnp.asarray(payload.v_scales[:, :, start:stop]))
        return payload.bytes_of_blocks(stop - start)

    def import_seqs(self, payload: "KVHandoffPayload") -> Dict[int, int]:
        """One-shot import: prealloc + write every payload block. The
        decode replicas drive the two phases separately (incremental
        transfer); this convenience wrapper serves tests and single-step
        callers. Returns the src→dst mapping."""
        mapping = self.prealloc_handoff(payload)
        self.write_handoff_blocks(payload, mapping, 0, payload.n_blocks)
        return mapping


@dataclasses.dataclass(frozen=True)
class KVHandoffPayload:
    """Block-granular KV handoff unit (prefill engine → decode replica).

    ``tables`` keeps each sequence's logical block chain in SOURCE ids;
    ``block_ids`` lists every referenced physical block exactly once (a
    refcount-shared block transfers once per physical block, not once per
    sharer), in the order the stacked head-major tiles ``k_blocks`` /
    ``v_blocks`` ``(L, Hkv, n_unique, bs, hd)`` are packed. The importer
    never sees source pool geometry beyond the ids — `prealloc_handoff`
    remaps them onto its own shards (source and destination pools may have
    different ``n_shards``).

    int8 pools additionally ship ``k_scales`` / ``v_scales``
    ``(L, Hkv, n_unique, 1, bs)`` fp32 tiles packed in the same block order —
    scales follow their blocks across the wire, and the int8 + scale bytes
    together ≈ halve ``nbytes`` vs a bf16 payload of the same blocks."""
    tables: Dict[int, Tuple[int, ...]]
    lengths: Dict[int, int]
    block_ids: Tuple[int, ...]
    k_blocks: np.ndarray
    v_blocks: np.ndarray
    block_size: int
    k_scales: Optional[np.ndarray] = None
    v_scales: Optional[np.ndarray] = None

    @property
    def n_blocks(self) -> int:
        return len(self.block_ids)

    @property
    def nbytes(self) -> int:
        """Total wire bytes (K + V tiles, plus scale tiles when int8)."""
        total = int(self.k_blocks.nbytes + self.v_blocks.nbytes)
        if self.k_scales is not None:
            total += int(self.k_scales.nbytes + self.v_scales.nbytes)
        return total

    def bytes_of_blocks(self, n: int) -> int:
        """Wire bytes of `n` payload blocks (K + V)."""
        if not self.n_blocks:
            return 0
        return int(self.nbytes * n // self.n_blocks)
