"""LLMEngine — the unified streaming serving facade.

One engine serves every placement the paper studies. Placement is a
declarative :class:`~repro.serving.config.EngineConfig` decision
(``homogeneous`` | ``attention_pool`` | ``moe_offload`` × ``head`` |
``request`` | ``block``), realised by a composable
:class:`~repro.serving.placement.PlacementStrategy` instead of the deleted
legacy ``Engine`` → ``DisaggEngine`` → ``MoEOffloadEngine`` tower; and
scheduling is a pluggable :class:`~repro.serving.scheduler.SchedulingPolicy`
(FCFS, or preemption under pool pressure with recompute re-admission).

The request lifecycle is streaming, not batch:

  * :meth:`LLMEngine.submit` returns a :class:`RequestHandle` per request;
    iterating a handle drives the engine and yields token ids *as they are
    generated* — a handle's consumer sees tokens while the rest of the
    continuous batch is still decoding;
  * :meth:`LLMEngine.events` streams iteration-level lifecycle events
    (``submit`` / ``admit`` / ``readmit`` / ``chunk`` / ``preempt`` /
    ``finish``);
  * :meth:`LLMEngine.run` keeps the legacy drain-everything loop.

Chunked paged prefill (``EngineConfig(prefill_chunk_tokens=...)``) makes
every iteration MIXED: at most one prompt advances by one block-aligned
chunk — its queries attending over the pool blocks already written, its KV
scattered into incrementally-allocated blocks as the chunk completes —
while the full decode batch decodes in the same step. Peak prefill memory
is O(chunk) instead of O(prompt), admission charges only the first chunk
(a prompt larger than the currently-free pool is admitted and completes as
earlier requests retire), and decode TBT no longer stalls behind long
prefills. Greedy outputs are bit-identical with chunking on or off (MoE
models fall back to one-shot prefill: a chunk boundary changes
capacity-dispatch groups — the same coupling that makes prefix sharing
recompute them).

Preemption fixes the legacy engines' latent OOM: a request that outlives
its ``decode_headroom`` margin used to exhaust the pool with no recourse
(``OutOfBlocks`` deep in the allocator, pool stranded mid-decode). Now the
engine checks pool pressure *before* each decode iteration; under the
``preempt`` policy it evicts a victim's blocks back to the pool (generated
tokens kept) and later re-admits it via recompute — greedy decoding resumes
bit-identically (same mechanism as the paper-§5 fault-tolerance path).
Under ``fcfs`` the same condition surfaces a clear
:class:`~repro.serving.kvcache.PoolExhausted` naming the offending request,
live tokens, and free blocks.

Sampling honours ``SamplingParams.seed``: each request draws token `i` from
``fold_in(PRNGKey(its seed), i)`` — its stochastic stream is independent of
batch composition, admission order, and preemption, so identical requests
reproduce identically wherever and whenever they run.

Fault tolerance (``serving/faults.py``): cheap, numerous pool devices
straggle, corrupt results, and die — the engine survives all three with
greedy bit-parity intact. A :class:`~repro.serving.faults.FaultInjector`
(deterministic, seeded scenarios) exercises the machinery at the host-side
pool boundary; detection is a per-shard ``healthy → suspect → dead`` state
machine fed by heartbeat probes and NaN/inf validation of the merged decode
output, with bounded retry-with-backoff before a shard is declared dead.
Recovery is the §5 preempt-and-recompute path: the dead shard is
QUARANTINED (the allocator masks it out and every capacity/headroom guard
drops to the surviving shards), every request holding blocks on it is
evicted through the normal preemption path and re-admitted via recompute
onto survivors — since KV is recomputable from prompt + generated tokens,
outputs through a mid-decode shard death are bit-identical to a fault-free
run (shared blocks recover once per physical block via the refcounts). A
transient fault that clears within the retry budget recovers with no
eviction at all, and a validated retry is bit-identical because the decode
step is deterministic and nothing was committed before validation. NaN/inf
that is NOT attributable to an injected fault raises
:class:`CorruptedLogitsError` naming the requests and step — garbage is
never silently sampled.

Tracing: :meth:`LLMEngine.step` writes ``jax.profiler.TraceAnnotation``
spans into the profiler's own trace, so they share the device trace's
clock and cost only their enter and exit while no profiler runs. The
names are fixed, carry no arguments, and are read back exactly by the
benchmark (``bench/spans.py``), which charges each stretch of device idle
time to the innermost ``engine.*`` span open over it:

  * ``engine.step`` — the whole of ``step()``;
  * ``engine.sched`` — fault bookkeeping, the pre-admission hook,
    admission and the stall check, the choice of the decode batch and
    pool-pressure preemption, retirement;
  * ``engine.pool`` — block tables, prefix gather indices, the
    placement's per-worker table walk, token appends, every pool write,
    and the KV byte accounting;
  * ``engine.model`` — each jitted model call (decode step, prefill chunk,
    one-shot and suffix prefill) with its host-to-device inputs and, for
    decode, the wait for its result and its finiteness check;
  * ``engine.sample`` — ``_sample`` and the transfer of the sampled tokens
    into ``Request.record_token``;
  * ``engine.state`` — a stack with recurrent layers' state slots: taking
    a slot at admission and freeing it at retirement (in
    ``PagedKVCache``), and the slot indices of a decode step or chunk.

Spans nest (a one-shot prefill inside admission is ``engine.model``
inside ``engine.sched``) and close when the step raises.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Dict, Iterator, List, Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.models import transformer
from repro.models.common import ModelConfig
from repro.serving.config import EngineConfig
from repro.serving.stats import EngineStats
from repro.serving.faults import DEAD, FaultInjector, ShardHealthTracker
from repro.serving.kvcache import PagedKVCache, PoolExhausted
from repro.serving.placement import PlacementStrategy, make_placement
from repro.serving.request import Request, SamplingParams, State
from repro.serving.sampler import sample_requests
from repro.serving.scheduler import RequestScheduler, make_policy


# a mixed stack's programs are large, so its shapes are rounded: the
# decode batch's block tables to multiples of TABLE_WIDTH blocks (1024
# tokens at the benchmark's block size), a prefill chunk's tokens to
# multiples of CHUNK_STEP (the Mamba-2 scan's sub-chunk)
TABLE_WIDTH = 64
CHUNK_STEP = 128


def _refuse_recurrent(cfg: ModelConfig, econf: EngineConfig,
                      fault_injector) -> None:
    """What an engine cannot do yet where layers keep a recurrent state
    (one slot per request, updated in place) beside the KV pool."""
    why = None
    if econf.prefix_sharing:
        why = ("prefix sharing maps a prompt onto a donor's KV blocks, but "
               "the recurrent state after the shared prefix is not kept "
               "(it would need a state snapshot per shared block)")
    elif econf.kv_dtype != "bf16":
        why = f"a {econf.kv_dtype} KV pool is not wired to the mixed stack"
    elif fault_injector is not None:
        why = ("fault injection retries a decode step, and a retry would "
               "apply the in-place state update twice")
    if why:
        raise ValueError(f"{cfg.name} has recurrent (Mamba) layers: {why}")


class SchedulingStalled(RuntimeError):
    """Nothing is running and the head of the waiting queue can never be
    admitted — the engine would spin forever. Raised instead."""


class CorruptedLogitsError(RuntimeError):
    """Decode/prefill produced non-finite logits that no injected fault
    accounts for — sampling from them would silently emit garbage tokens.
    Carries the affected request ids and the engine step for triage."""

    def __init__(self, message: str, *, rids: Sequence[int] = (),
                 step: int = 0):
        super().__init__(message)
        self.rids = tuple(rids)
        self.step = step


@dataclasses.dataclass(frozen=True)
class EngineEvent:
    """One iteration-level lifecycle event (the ``events()`` stream)."""

    # submit | admit | readmit | chunk | preempt | finish, plus the fault
    # lifecycle: shard_suspect | retry | recover | shard_down | shard_up
    # (shard-level events carry rid=-1 and name the shard in info["shard"])
    kind: str
    rid: int
    step: int          # engine step counter when the event fired
    info: Dict = dataclasses.field(default_factory=dict)


class RequestHandle:
    """Streaming view of one submitted request.

    Iterating yields token ids incrementally, driving the engine only as
    far as needed — tokens arrive while the rest of the batch is still
    decoding. The handle never rewinds: preemption keeps generated tokens
    (re-admission recomputes KV, not text), so every yielded token is
    final.
    """

    __slots__ = ("request", "_engine")

    def __init__(self, engine: "LLMEngine", request: Request):
        self._engine = engine
        self.request = request

    @property
    def rid(self) -> int:
        return self.request.rid

    @property
    def finished(self) -> bool:
        return self.request.state == State.FINISHED

    @property
    def output(self) -> List[int]:
        return self.request.output

    def __iter__(self) -> Iterator[int]:
        sent = 0
        while True:
            out = self.request.output
            while sent < len(out):
                yield out[sent]
                sent += 1
            if self.request.state == State.FINISHED:
                return
            self._engine.step()

    def result(self) -> List[int]:
        """Drain the stream; returns the complete output token list."""
        for _ in self:
            pass
        return self.request.output

    def __repr__(self):
        return (f"RequestHandle(rid={self.rid}, "
                f"state={self.request.state.value}, "
                f"tokens={len(self.request.output)})")


class LLMEngine:
    """The unified serving facade: one engine, every placement."""

    def __init__(self, cfg: ModelConfig, params,
                 engine_config: Optional[EngineConfig] = None,
                 fault_injector: Optional[FaultInjector] = None,
                 **overrides):
        """``overrides`` are EngineConfig fields for call-site convenience:
        ``LLMEngine(cfg, params, placement="attention_pool", partition=
        "block")`` ≡ passing the equivalent validated EngineConfig.

        ``fault_injector`` attaches a deterministic fault scenario
        (``serving/faults.py``) at the pool boundary; the health machine
        and recovery paths are always live — the injector only supplies
        the faults."""
        if cfg.family not in ("dense", "moe", "vlm", "mixed"):
            raise ValueError("engine serves KV-cache architectures and "
                             f"mixed stacks; got family={cfg.family}")
        econf = engine_config or EngineConfig()
        if overrides:
            econf = econf.replace(**overrides)
        self.cfg = cfg
        self.config = econf
        self.params = params
        self.kv = PagedKVCache(cfg, econf.num_blocks, econf.block_size,
                               n_shards=econf.resolved_kv_shards,
                               kv_dtype=econf.kv_dtype,
                               state_slots=econf.max_batch)
        recurrent = self.kv.state is not None
        if recurrent:
            _refuse_recurrent(cfg, econf, fault_injector)
        self._table_width = TABLE_WIDTH if recurrent else 1
        self.placement: PlacementStrategy = make_placement(cfg, econf)
        # Chunked prefill is a COMPUTE decision like the prefix-sharing
        # skip: a chunk boundary changes MoE capacity-dispatch groups, so
        # chunked MoE prefill would not be bit-stable against the one-shot
        # — MoE models fall back to one-shot prefill (the config knob is
        # accepted and simply has no effect).
        self._chunk_tokens = (econf.prefill_chunk_tokens
                              if cfg.family != "moe" else None)
        self.policy = make_policy(econf.scheduler,
                                  prefill_chunk_tokens=self._chunk_tokens)
        self.sched = RequestScheduler(self.kv, econf.max_batch, self.policy,
                                      econf.decode_headroom,
                                      prefix_sharing=econf.prefix_sharing)
        self.stats = EngineStats()
        self.stats.kv_pool_bytes_resident = self.kv.pool_bytes_resident
        self.stats.state_bytes_resident = self.kv.state_bytes_resident
        # a recurrent state is updated in place: the steps take it donated
        donate = {"donate_argnames": ("state",)} if recurrent else {}
        self._decode_jit = jax.jit(self.placement.decode_fn(), **donate)

        def prefill(p, b):
            return transformer.prefill(p, cfg, b,
                                       max_seq=b["tokens"].shape[1])
        self._prefill_jit = jax.jit(prefill)
        def _suffix_prefill(p, b, k_pool, v_pool, idx,
                            k_scale=None, v_scale=None):
            # fused prefix gather: the shared blocks' KV is sliced out of
            # the pool INSIDE the jitted program (one compiled gather, no
            # eager dispatch / host round-trip per admission). Int8 pools
            # dequantize the gathered prefix here — admission-time, once
            # per shared prefix, explicitly off the per-step hot path.
            L, Hkv, _, bs, hd = k_pool.shape
            n_tok = idx.shape[0] * bs
            kp = k_pool[:, :, idx].reshape(L, Hkv, n_tok, hd)
            vp = v_pool[:, :, idx].reshape(L, Hkv, n_tok, hd)
            if k_scale is not None:
                ks = k_scale[:, :, idx].reshape(L, Hkv, n_tok)
                vs = v_scale[:, :, idx].reshape(L, Hkv, n_tok)
                kp = (kp.astype(jnp.float32) * ks[..., None]).astype(cfg.dtype)
                vp = (vp.astype(jnp.float32) * vs[..., None]).astype(cfg.dtype)
            return transformer.prefill_suffix(p, cfg, b, kp[:, None],
                                              vp[:, None])
        self._prefill_suffix_jit = jax.jit(_suffix_prefill)
        # chunked paged prefill: one chunk's queries over the already-
        # written pool blocks. The context path follows decode_backend:
        # 'jnp' gathers one layer's prefix at a time inside the scan and is
        # BIT-IDENTICAL to the one-shot prefill; 'pallas' streams the pool
        # in place through the chunk kernel (no densify — kernel numerics,
        # allclose to the reference like every other pallas backend).
        # Chunk shapes amortise across prompts: for a fixed chunk size the
        # prefix-index operand only takes shapes (0,), (cb,), (2·cb,), …,
        # so a second long prompt reuses the first one's compiled programs
        # (one-shot prefill, by contrast, compiles per distinct prompt
        # length); only the final partial chunk adds a per-length shape.
        def prefill_chunk(p, b, kp, vp, idx, ks=None, vs=None):
            return transformer.prefill_chunk(
                p, cfg, b, kp, vp, idx, backend=econf.decode_backend,
                k_scale_pool=ks, v_scale_pool=vs)

        def prefill_chunk_mixed(p, b, kp, vp, idx, state, slot, length):
            return transformer.prefill_chunk_mixed(
                p, cfg, b, kp, vp, idx, state, slot, length,
                backend=econf.decode_backend)
        self._prefill_chunk_jit = (
            jax.jit(prefill_chunk_mixed, **donate) if recurrent
            else jax.jit(prefill_chunk))
        # the programs whose new executables make a step a compile step,
        # held apart from the attributes so that a wrapper put in their
        # place still counts the program it wraps
        self._programs = (self._decode_jit, self._prefill_jit,
                          self._prefill_suffix_jit, self._prefill_chunk_jit)
        self._tokens_out = 0        # tokens recorded by this engine
        # Prefill COMPUTE can only be skipped when suffix-only prefill is
        # bit-identical to the full one. MoE capacity dispatch couples the
        # tokens of a routing group (expert capacity and reduction shapes
        # depend on the whole group), so MoE models share pool MEMORY but
        # recompute the full prompt, writing only the unshared suffix.
        self._skip_prefill_compute = cfg.family != "moe"
        # fault tolerance: per-shard health machine (always live) plus the
        # optional injector; _recovering maps a shard-death victim's rid to
        # the perf_counter instant its shard was declared dead, closed out
        # (into stats.recovery_latencies) when the request is decodable
        # again on the surviving shards
        self._fault = fault_injector
        self.health = ShardHealthTracker(self.kv.n_shards,
                                         econf.fault_retry_limit)
        self._backoff_s = econf.fault_retry_backoff_s
        self._recovering: Dict[int, float] = {}
        self._events: List[EngineEvent] = []
        self._step_no = 0

    # ------------------------------------------------------------------
    # submission / streaming surface
    # ------------------------------------------------------------------
    def submit(self, reqs: Union[Request, Sequence[Request]]
               ) -> Union[RequestHandle, List[RequestHandle]]:
        """Enqueue request(s); returns one streaming handle per request
        (a single handle for a single request)."""
        single = isinstance(reqs, Request)
        batch = [reqs] if single else list(reqs)
        handles = []
        for req in batch:
            req.arrival_s = time.perf_counter()
            self._emit("submit", req.rid)
            if not req.output and req.done():      # max_new_tokens == 0
                req.state = State.FINISHED
                req.finish_s = req.arrival_s
                self._emit("finish", req.rid, tokens=0)
            else:
                self.sched.submit([req])
            handles.append(RequestHandle(self, req))
        return handles[0] if single else handles

    def generate(self, prompt: Sequence[int],
                 params: Optional[SamplingParams] = None) -> RequestHandle:
        """Convenience: wrap a raw prompt in a Request and submit it."""
        return self.submit(Request(prompt=list(prompt),
                                   params=params or SamplingParams()))

    def events(self) -> Iterator[EngineEvent]:
        """Stream lifecycle events, driving the engine while work remains.
        Yields everything recorded so far, then steps the engine for more;
        ends when the engine drains. (``event_log`` is the passive view.)"""
        i = 0
        while True:
            while i < len(self._events):
                yield self._events[i]
                i += 1
            if not self.sched.has_work():
                return
            self.step()

    @property
    def event_log(self) -> List[EngineEvent]:
        return list(self._events)

    def _emit(self, kind: str, rid: int, **info) -> None:
        self._events.append(EngineEvent(kind, rid, self._step_no, info))

    # ------------------------------------------------------------------
    # the iteration
    # ------------------------------------------------------------------
    def step(self) -> None:
        """One MIXED engine iteration: admit (one-shot prefill / recompute,
        or chunked admission that only seeds a prefill cursor), resolve
        pool pressure (possibly preempting), advance at most one prefill
        chunk, decode one token for every running request whose prefill is
        complete, retire the finished. Fault bookkeeping (rejoins,
        heartbeat probes, straggler observation) runs first, so a shard
        death detected at the step boundary is recovered before this very
        step's admission wave — the evicted victims re-admit immediately
        onto the surviving shards.

        The whole step is timed into ``stats.step_times``, unless one of
        the engine's jitted programs built a new executable during it: it
        then counts in ``stats.compile_steps`` instead."""
        t0 = time.perf_counter()
        programs, tokens = self._programs_built(), self._tokens_out
        with TraceAnnotation("engine.step"):
            self._step_no += 1
            with TraceAnnotation("engine.sched"):
                self._fault_tick()
                self._pre_admit_tick()
                self._admission_wave()
                self._check_stall()
            self._prefill_chunk_iteration()
            with TraceAnnotation("engine.sched"):
                self._note_recoveries()
            self._decode_iteration()
            with TraceAnnotation("engine.sched"):
                self._retire()
        if self._programs_built() > programs:
            self.stats.compile_steps += 1
        else:
            self.stats.step_times.append(time.perf_counter() - t0)
            self.stats.timed_tokens += self._tokens_out - tokens

    def _programs_built(self) -> int:
        """Executables the engine's jitted programs hold so far."""
        return sum(f._cache_size() for f in self._programs)

    def _admission_wave(self) -> None:
        while True:
            admitted = self.sched.admit()
            self.stats.state_slots_peak = max(self.stats.state_slots_peak,
                                              len(self.kv.slot_of))
            for req in admitted:
                if self.sched.prefill_cursor(req.rid) is not None:
                    # chunked admission: only the first chunk's blocks were
                    # charged; the model runs via _prefill_chunk_iteration,
                    # one chunk per engine step, alongside the decode batch
                    shared = self.sched.shared_prefix_tokens(req.rid)
                    self.stats.blocks_shared += shared // self.kv.block_size
                    self.stats.prefill_tokens_skipped += shared
                    kind = "readmit" if req.output else "admit"
                    self._emit(kind, req.rid, prompt_len=len(req.prompt),
                               chunked=True)
                elif req.output:               # preempted earlier: recompute
                    self._recompute(req)
                    self._emit("readmit", req.rid,
                               recomputed_tokens=self.kv.lengths[req.rid])
                else:
                    self._emit("admit", req.rid, prompt_len=len(req.prompt))
                    self._prefill(req)
            self._retire()                     # EOS-at-prefill frees early
            # an admission wave that finished entirely at prefill just
            # returned its blocks — the next waiting request may fit NOW
            if self.sched.running or not admitted:
                break

    def _check_stall(self) -> None:
        if not self.sched.running and self.sched.waiting:
            head = self.sched.waiting[0]
            need = self.sched.stored_tokens(head) + self.sched.decode_headroom
            blocks = self.kv.blocks_needed(need)
            # degraded pool with a rejoin on the schedule: the head may fit
            # once the quarantined shard returns — idle this step instead
            # of declaring a permanent stall
            waitable = (self.kv.quarantined_shards
                        and self._fault is not None
                        and self._fault.pending_rejoins(self._step_no)
                        and blocks <= self.kv.num_blocks)
            if not waitable and not self._stall_waiver():
                raise SchedulingStalled(
                    f"request {head.rid} needs {blocks} "
                    f"blocks ({need} tokens incl. headroom) but the pool "
                    f"only has {self.kv.capacity_blocks} blocks "
                    f"({self.kv.num_free} free) and nothing is running — "
                    f"it can never be admitted; shrink the prompt or grow "
                    f"num_blocks" + self.kv._degraded_note())

    def run(self, max_steps: int = 10_000) -> EngineStats:
        steps = 0
        while self.has_work() and steps < max_steps:
            self.step()
            steps += 1
        return self.stats

    def has_work(self) -> bool:
        return self.sched.has_work()

    # ------------------------------------------------------------------
    # disaggregation hooks (serving/cluster/ overrides these)
    # ------------------------------------------------------------------
    def _pre_admit_tick(self) -> None:
        """Hook between fault bookkeeping and this step's admission wave.
        The disaggregated cluster engines live here: a DecodeEngine drains
        its Prealloc→Transfer→Waiting handoff queues (so a transfer that
        completes this step joins this step's decode batch), a
        PrefillEngine evicts retained prefix donors under pool pressure
        (so retained blocks never block the admission the stall check is
        about to judge). Runs AFTER ``_fault_tick`` so a shard death this
        step is visible to mid-transfer recovery."""

    def _stall_waiver(self) -> bool:
        """Hook: return True to suppress this step's SchedulingStalled
        check. A DecodeEngine with handoffs in flight waives it — the
        queued imports hold pool blocks while nothing is running yet, a
        state the single-engine stall logic would misread as permanent."""
        return False

    def _retire(self) -> None:
        for req in self.sched.retire_finished():
            self.stats.observe_request(req)
            self._emit("finish", req.rid, tokens=len(req.output))

    def cancel_all(self) -> int:
        """Graceful shutdown: cancel every in-flight request (running AND
        waiting), freeing their pool blocks and marking each FINISHED so
        handle iterators terminate cleanly. Partial outputs are kept —
        every already-yielded token stays final. Returns the number of
        requests cancelled."""
        cancelled = self.sched.cancel_all()
        now = time.perf_counter()
        for req in cancelled:
            req.state = State.FINISHED
            req.finish_s = now
            self.stats.observe_request(req)
            self._emit("finish", req.rid, tokens=len(req.output),
                       cancelled=True)
        self._recovering.clear()
        return len(cancelled)

    # ------------------------------------------------------------------
    # fault detection / recovery
    # ------------------------------------------------------------------
    def _fault_tick(self) -> None:
        """Per-step fault bookkeeping at the pool boundary: scheduled
        rejoins restore quarantined capacity, stragglers are observed
        (slow is suspect, not wrong — no eviction), then every live shard
        is heartbeat-probed with bounded retry-with-backoff. A shard that
        answers within the retry budget recovers (transient blip, no
        eviction); one that doesn't is declared dead and its requests are
        recovered via :meth:`_handle_shard_death`."""
        if self._fault is None:
            return
        self._fault.begin_step(self._step_no)
        for s in self._fault.rejoins(self._step_no):
            if self.health.is_dead(s):
                self.kv.rejoin_shard(s)
                self.health.mark_up(s)
                self.stats.shard_rejoins += 1
                self._emit("shard_up", -1, shard=s,
                           capacity_blocks=self.kv.capacity_blocks)
        for s, delay in self._fault.straggles(self._step_no):
            if self.health.is_dead(s):
                continue
            self.stats.straggle_steps += 1
            if delay > 0:
                time.sleep(delay)
            self._emit("shard_suspect", -1, shard=s, cause="straggler",
                       delay_s=delay)
            self._emit("recover", -1, shard=s, cause="straggler")
        for s in range(self.kv.n_shards):
            if self.health.is_dead(s):
                continue
            attempt = 0
            suspected = False
            while not self._fault.probe(s, self._step_no):
                self.stats.fault_retries += 1
                if not suspected:
                    suspected = True
                    self._emit("shard_suspect", -1, shard=s,
                               cause="heartbeat")
                if self.health.strike(s) == DEAD:
                    self._handle_shard_death(s, cause="heartbeat")
                    break
                self._emit("retry", -1, shard=s, attempt=attempt + 1)
                self._backoff(attempt)
                attempt += 1
            else:
                if suspected:
                    self.health.clear(s)
                    self.stats.transient_faults_recovered += 1
                    self._emit("recover", -1, shard=s, cause="heartbeat",
                               retries=attempt)

    def _handle_shard_death(self, shard: int, cause: str) -> None:
        """Quarantine a dead shard and recover its requests: the allocator
        masks the shard out (capacity drops to the survivors — every
        admission/headroom guard sees the degraded pool), every request
        holding blocks there is evicted through the normal preemption path
        (generated tokens kept), and re-admission recomputes its KV onto
        the surviving shards — the §5 path, so greedy outputs are
        bit-identical to a fault-free run. Shared/CoW blocks need no
        special casing: eviction drops refcounts, survivors keep their
        physical blocks, and each physical block recovers at most once.

        Eviction bypasses ``policy.select_victim`` deliberately: shard
        death names its victims by block placement, not by scheduling
        policy, so recovery works under ``fcfs`` too — and MID-PREFILL
        victims are allowed here (their prefill cursor resets with the
        eviction), the one place that invariant yields."""
        t0 = time.perf_counter()
        victims = set(self.kv.seqs_on_shard(shard))
        # quarantine BEFORE freeing: the dead shard's blocks must not be
        # handed back out to the re-admission wave
        self.kv.quarantine_shard(shard)
        self.stats.shard_failures += 1
        self._emit("shard_down", -1, shard=shard, cause=cause,
                   victims=sorted(victims),
                   live_shards=list(self.kv.live_shards),
                   capacity_blocks=self.kv.capacity_blocks)
        for r in list(self.sched.running):
            if r.rid in victims:
                freed = self.sched.preempt(r)
                self.stats.preemptions = self.sched.n_preemptions
                self._emit("preempt", r.rid, freed_blocks=freed,
                           generated_tokens=len(r.output),
                           cause="shard_down")
                self._recovering[r.rid] = t0

    def _note_recoveries(self) -> None:
        """Close out recovery-latency timers: a shard-death victim counts
        as recovered the moment it is decodable again (running, prefill
        complete) on the surviving shards."""
        if not self._recovering:
            return
        for r in self.sched.running:
            t0 = self._recovering.get(r.rid)
            if t0 is not None and self.sched.prefill_done(r.rid):
                lat = time.perf_counter() - t0
                del self._recovering[r.rid]
                self.stats.recovery_latencies.append(lat)
                self.stats.requests_recovered += 1
                self._emit("recover", r.rid, latency_s=lat,
                           cause="readmitted")

    def _backoff(self, attempt: int) -> None:
        if self._backoff_s > 0:
            time.sleep(self._backoff_s * (2 ** attempt))

    def _guard_finite(self, reqs: List[Request], logits: jax.Array) -> None:
        """Refuse to sample from non-finite logits (satellite guard — live
        with or without an injector): name the offending requests and the
        engine step instead of silently emitting garbage tokens."""
        self._refuse_non_finite(reqs,
                                np.asarray(jnp.isfinite(logits).all(axis=-1)))

    def _refuse_non_finite(self, reqs: List[Request],
                           finite: np.ndarray) -> None:
        if bool(finite.all()):
            return
        bad = [r.rid for r, ok in zip(reqs, finite) if not ok]
        raise CorruptedLogitsError(
            f"non-finite logits at engine step {self._step_no} for "
            f"request(s) {bad} — refusing to sample; no injected fault "
            f"accounts for this (check model numerics / KV integrity)",
            rids=bad, step=self._step_no)

    # ------------------------------------------------------------------
    # prefill / recompute
    # ------------------------------------------------------------------
    def _scale_kwargs(self, k_name: str, v_name: str) -> Dict:
        """The int8 pool's scale operands for a jitted call, keyed by the
        callee's kwarg names; empty for bf16 pools (scales-follow-blocks:
        every compute path that reads the pool also receives its scales)."""
        if self.kv.k_scale is None:
            return {}
        return {k_name: self.kv.k_scale, v_name: self.kv.v_scale}

    def _prefill(self, req: Request) -> None:
        logits = self._prefill_known(req.rid, req.prompt)
        self._sample_and_record([req], logits)
        # the sampled token's K/V gets stored by the next decode pass (it is
        # that step's input token); kv.lengths stays = stored tokens

    def _recompute(self, req: Request) -> None:
        """Re-admission of a preempted request: rebuild its pool KV by
        re-prefilling prompt + generated tokens minus the still-unstored
        last one (the next decode input) — the §5 recovery path. No token
        is sampled: the stream continues from ``req.output[-1]``. Prefix
        sharing applies here too: a readmitted request whose prompt prefix
        matched a live donor at re-admission skips those blocks."""
        known = req.prompt + req.output[:-1]
        self._prefill_known(req.rid, known)

    def _prefill_known(self, rid: int, known: Sequence[int]) -> jax.Array:
        """Compute and store pool KV for `known` tokens, honouring the
        prefix the scheduler mapped onto a donor's blocks at admission.
        Returns the last position's logits.

        With a shared prefix: the matched blocks' KV is already resident
        (bit-identical — the donor stored the same tokens at the same
        positions), so only the suffix runs through the model
        (``transformer.prefill_suffix`` attends suffix queries over the
        gathered prefix context) and only the suffix is written. MoE
        recomputes the full prompt (see ``_skip_prefill_compute``) but
        still writes only the suffix — the donor's blocks are never
        rewritten, so no copy-on-write fires and the memory stays shared.
        """
        shared = self.sched.shared_prefix_tokens(rid)
        # increment-based (like prefill_tokens_skipped below) so a stats
        # reset mid-engine-lifetime stays consistent; the allocator's
        # kv.blocks_shared_total keeps the engine-lifetime cumulative view
        self.stats.blocks_shared += shared // self.kv.block_size
        if shared and self._skip_prefill_compute:
            # memoised gather indices: a prefix-sharing admission wave's K
            # recipients all resolve to the donor's physical blocks, so the
            # whole wave reuses one converted index array
            with TraceAnnotation("engine.pool"):
                idx = self.kv.gather_prefix_indices(rid, shared)
            with TraceAnnotation("engine.model"):
                toks = jnp.asarray([list(known[shared:])], jnp.int32)
                logits, cache = self._prefill_suffix_jit(
                    self.params, {"tokens": toks}, self.kv.k_pool,
                    self.kv.v_pool, idx, **self._scale_kwargs("k_scale",
                                                              "v_scale"))
            # suffix cache k/v are head-major (L, 1, Hkv, S-shared, hd)
            with TraceAnnotation("engine.pool"):
                self.kv.write_prefill(rid, cache["k"][:, 0],
                                      cache["v"][:, 0], start_token=shared)
            self.stats.prefill_tokens_skipped += shared
            self.stats.max_prefill_slab_tokens = max(
                self.stats.max_prefill_slab_tokens, len(known) - shared)
            return logits
        self.stats.max_prefill_slab_tokens = max(
            self.stats.max_prefill_slab_tokens, len(known))
        with TraceAnnotation("engine.model"):
            if self.kv.state is None:
                toks = jnp.asarray([list(known)], jnp.int32)
                logits, cache = self._prefill_jit(self.params,
                                                  {"tokens": toks})
            else:   # recurrent layers: the whole of `known` as one chunk
                logits, cache = self._run_chunk(
                    rid, list(known), self.kv.gather_prefix_indices(rid, 0))
        # cache k/v are head-major (L, 1, Hkv, S, hd) — the pool's layout
        with TraceAnnotation("engine.pool"):
            self.kv.write_prefill(rid, cache["k"][:, 0, :, shared:],
                                  cache["v"][:, 0, :, shared:],
                                  start_token=shared)
        return logits

    # ------------------------------------------------------------------
    # chunked prefill (mixed iterations)
    # ------------------------------------------------------------------
    def _prefill_chunk_iteration(self) -> None:
        """Advance the OLDEST incomplete prefill by one chunk (the
        per-iteration prefill token budget, ``prefill_chunk_tokens``) while
        the decode batch keeps decoding — the paper-§4 overlap on the
        prefill axis. The chunk's queries attend over the already-written
        pool blocks (plus the in-chunk causal mask), its KV is written as
        it completes (blocks allocated incrementally), and only the FINAL
        chunk samples the request's first token."""
        with TraceAnnotation("engine.sched"):
            req = self.sched.next_prefill()
            if req is None:
                return
            rid = req.rid
            # re-admission after preemption recomputes prompt + generated
            # tokens minus the still-unstored last one (the §5 recovery
            # path)
            known = list(req.prompt) + req.output[:-1] if req.output \
                else req.prompt
            total = len(known)
            cursor = self.sched.prefill_cursor(rid)
            target = min(cursor + self._chunk_tokens, total)
            grow = self.kv.blocks_needed(target) - len(self.kv.tables[rid])
            # the FINAL chunk re-establishes the decode headroom one-shot
            # admission reserves up front: completing a prefill with zero
            # slack would strand the request at its first decode-growth
            # block
            headroom = 0
            if target >= total:
                headroom = (self.kv.blocks_needed(total +
                                                  self.sched.decode_headroom)
                            - self.kv.blocks_needed(total))
            if grow + headroom > 0:
                # the chunk may not starve the decode batch either: reserve
                # the blocks this iteration's decodes are about to append
                # before taking any for the chunk (the decoders are what
                # retires and frees the rest of this prompt's allocation)
                reserve = sum(self.kv.blocks_to_append(r.rid)
                              for r in self.sched.running
                              if r.state == State.RUNNING
                              and self.sched.prefill_done(r.rid))
                if not self._free_blocks_for_chunk(
                        req, grow + headroom + reserve):
                    return  # stall this iteration: admission charged only
                    # the first chunk, so the rest of the allocation
                    # arrives as running requests retire — decode
                    # continues meanwhile
        with TraceAnnotation("engine.pool"):
            idx = self.kv.gather_prefix_indices(rid, cursor)
        with TraceAnnotation("engine.model"):
            logits, cache = self._run_chunk(rid, list(known[cursor:target]),
                                            idx)
        with TraceAnnotation("engine.pool"):
            # chunk cache k/v are head-major (L, 1, Hkv, C, hd) — the
            # pool's layout; write_prefill_chunk extends the allocation
            # then scatters
            self.kv.write_prefill_chunk(rid, cache["k"][:, 0],
                                        cache["v"][:, 0], start_token=cursor)
            self.stats.prefill_chunks_run += 1
            self.stats.max_prefill_slab_tokens = max(
                self.stats.max_prefill_slab_tokens, target - cursor)
            self.placement.log_prefill_chunk(target - cursor)
        with TraceAnnotation("engine.sched"):
            self._emit("chunk", rid, start=cursor, tokens=target - cursor,
                       remaining=total - target)
            self.sched.advance_prefill(req, target)
        if target >= total and not req.output:
            # last chunk's last position seeds sampling — same contract as
            # the one-shot prefill (TTFT lands here)
            self._sample_and_record([req], logits)

    def _run_chunk(self, rid: int, tokens: List[int], idx: jax.Array):
        """The jitted prefill-chunk program for ``tokens`` over ``idx``,
        the request's pool blocks before them. With recurrent layers it
        also reads the request's state slot (a fresh state where ``idx``
        is empty) and leaves there the state after the chunk; the tokens
        are padded to a multiple of CHUNK_STEP, so that chunks of many
        lengths share a program, and the padding's KV is cut off."""
        if self.kv.state is None:
            return self._prefill_chunk_jit(
                self.params, {"tokens": jnp.asarray([tokens], jnp.int32)},
                self.kv.k_pool, self.kv.v_pool, idx,
                **self._scale_kwargs("ks", "vs"))
        n = len(tokens)
        toks = jnp.asarray([tokens + [0] * (-n % CHUNK_STEP)], jnp.int32)
        with TraceAnnotation("engine.state"):
            slot = np.int32(self.kv.slot_of[rid])
        logits, cache, self.kv.state = self._prefill_chunk_jit(
            self.params, {"tokens": toks}, self.kv.k_pool, self.kv.v_pool,
            idx, state=self.kv.state, slot=slot, length=np.int32(n))
        return logits, {k: cache[k][:, :, :, :n] for k in ("k", "v")}

    def _free_blocks_for_chunk(self, req: Request, need: int) -> bool:
        """Check `need` blocks are free before a chunk allocation. Chunk
        growth NEVER preempts: while any decoder is still running the
        chunk simply STALLS this iteration (returns False) — decoders
        retire (or are themselves evicted by the decode-side pool-pressure
        path) and the freed blocks arrive over the next iterations, which
        is chunked admission's whole point. This also makes the sharing
        safety invariant enforced rather than emergent: a MID-PREFILL
        request is never a preemption victim anywhere (the decode path
        only selects among prefill-complete requests), so blocks a donor
        has allocated are always eventually written — a recipient mapped
        onto them can never gather garbage. Raises contextual
        :class:`PoolExhausted` only when no running decoder is left to
        ever free a block."""
        if self.kv.num_free >= need:
            return True
        if any(r.state == State.RUNNING and r is not req
               and self.sched.prefill_done(r.rid)
               for r in self.sched.running):
            return False             # decoders still running: wait them out
        free = self.kv.num_free
        fix = ("raise num_blocks" if self.policy.preemptible
               else "use scheduler='preempt' or raise num_blocks")
        raise PoolExhausted(
            f"KV pool exhausted mid chunked prefill: request "
            f"{req.rid} needs {need} blocks for its next chunk and "
            f"{free} of {self.kv.capacity_blocks} are free "
            f"({sum(self.kv.lengths.values())} live tokens across "
            f"{len(self.kv.tables)} sequences) with no running "
            f"decoder left to retire: {fix}" + self.kv._degraded_note(),
            rid=req.rid,
            live_tokens=sum(self.kv.lengths.values()),
            free_blocks=free,
            **self.kv._degraded_kw())

    # ------------------------------------------------------------------
    # decode
    # ------------------------------------------------------------------
    def _decode_iteration(self) -> None:
        with TraceAnnotation("engine.sched"):
            running = [r for r in self.sched.running
                       if r.state == State.RUNNING
                       and self.sched.prefill_done(r.rid)]
            if running:
                running = self._resolve_pool_pressure(running)
        if not running:
            return
        ids = [r.rid for r in running]
        with TraceAnnotation("engine.pool"):
            # one walk of the batch's tables counts the live KV tokens the
            # step reads, for the placement's per-worker accounting and
            # for stats.kv_bytes_read alike
            live = self.kv.unique_live_tokens(ids)
            # placement-specific per-iteration operands + per-worker
            # accounting
            extra = self.placement.decode_extra_args(self.kv, ids, live)
            tables, lens = self.kv.block_table_batch(ids, self._table_width)
        slots = None
        if self.kv.state is not None:
            with TraceAnnotation("engine.state"):
                slots = jnp.asarray(self.kv.slot_indices(ids))
        with TraceAnnotation("engine.model"):
            tokens = jnp.asarray([r.output[-1] for r in running], jnp.int32)
            out = self._decode_validated(running, tokens, tables, lens,
                                         extra, slots)
        if out is None:
            # a shard died mid-decode: this iteration is aborted with
            # NOTHING committed (no append, no pool write, no sample) —
            # its victims were evicted, survivors decode next step with
            # outputs unchanged, so greedy bit-parity holds
            return
        logits, updates = out
        with TraceAnnotation("engine.pool"):
            # placement is the memory pool's job: append the input token's
            # K/V (allocator bookkeeping per sequence, then ONE batched
            # scatter)
            positions = [int(n) for n in lens]
            for r in running:
                self.kv.append_token(r.rid)
            self.kv.write_tokens(ids, updates["k_new"], updates["v_new"],
                                 positions)
            self.placement.log_step(len(running))
            self.stats.steps += 1
            self.stats.kv_pool_bytes_resident = self.kv.pool_bytes_resident
            self.stats.kv_bytes_read += live * self.kv.bytes_per_live_token()
            self.stats.tokens_generated += len(running)
            self.stats.batch_sizes.append(len(running))
        self._sample_and_record(running, logits)

    def _decode_validated(self, running: List[Request], tokens, tables,
                          lens, extra, slots=None):
        """Run the jitted decode step and VALIDATE the merged output
        before anything is committed (no token append, no pool write, no
        sampling has happened yet). Injected corruption — NaN partials
        from a pool shard, the stand-in for a per-shard checksum / sender
        identity a real RPC fabric attaches — strikes the shard and
        retries; the decode step is deterministic, so a retry that
        succeeds is bit-identical to an unfaulted step. Strikes past the
        retry budget declare the shard dead (returns ``None`` — the
        caller aborts the iteration; victims were already evicted).
        Non-finite logits NO fault accounts for raise
        :class:`CorruptedLogitsError`.

        With recurrent layers (``slots`` given) the step updates their
        state in place, and the engine takes the new state at once: such
        an engine takes no fault injector, so the step is never retried."""
        attempt = 0
        suspect = None
        while True:
            kw = (self._scale_kwargs("k_scale_pool", "v_scale_pool")
                  if slots is None else
                  {"state": self.kv.state, "slots": slots})
            logits, updates = self._decode_jit(
                self.params, tokens, self.kv.k_pool, self.kv.v_pool,
                jnp.asarray(tables), jnp.asarray(lens), *extra, **kw)
            if slots is not None:
                self.kv.state = updates.pop("state")
            logits.block_until_ready()
            shard = None
            if self._fault is not None:
                logits, shard = self._fault.filter_decode(self._step_no,
                                                          logits)
            if bool(jnp.isfinite(logits).all()):
                if suspect is not None:
                    self.health.clear(suspect)
                    self.stats.transient_faults_recovered += 1
                    self._emit("recover", -1, shard=suspect,
                               cause="corrupt_partial", retries=attempt)
                return logits, updates
            if shard is None:
                # non-finite output with no injected fault to blame: the
                # always-on guard refuses to sample garbage
                self._guard_finite(running, logits)
            if suspect is None:
                suspect = shard
                self._emit("shard_suspect", -1, shard=shard,
                           cause="corrupt_partial")
            self.stats.fault_retries += 1
            if self.health.strike(shard) == DEAD:
                self._handle_shard_death(shard, cause="corrupt_partial")
                return None
            self._emit("retry", -1, shard=shard, attempt=attempt + 1)
            self._backoff(attempt)
            attempt += 1

    def _resolve_pool_pressure(self, running: List[Request]
                               ) -> List[Request]:
        """Ensure every running sequence can store one more token. Each
        grower needs exactly one fresh block — because its table must grow
        OR because its tail block is shared and the divergent append will
        copy-on-write (``blocks_to_append`` counts both); when the pool
        can't cover them, the policy evicts victims (blocks freed back to
        the pool, re-admission via recompute) or — non-preemptible — the
        engine surfaces the allocator's PoolExhausted signal up front
        instead of stranding the pool mid-iteration."""
        def needs_block(r: Request) -> bool:
            return self.kv.blocks_to_append(r.rid) > 0

        while True:
            growers = [r for r in running if needs_block(r)]
            free = self.kv.num_free
            if len(growers) <= free:
                return running
            victim = self.policy.select_victim(running)
            if victim is None:
                g = growers[0]
                fix = ("a sole running request has no viable victim — "
                       "raise num_blocks" if self.policy.preemptible
                       else "use scheduler='preempt' or raise num_blocks")
                raise PoolExhausted(
                    f"KV pool exhausted: request {g.rid} "
                    f"({self.kv.lengths[g.rid]} stored tokens) needs a "
                    f"block and {free} of {self.kv.capacity_blocks} are "
                    f"free ({sum(self.kv.lengths.values())} live tokens "
                    f"across {len(self.kv.tables)} sequences); the "
                    f"{self.policy.name!r} policy found no victim: "
                    f"{fix}" + self.kv._degraded_note(),
                    rid=g.rid,
                    live_tokens=sum(self.kv.lengths.values()),
                    free_blocks=free,
                    **self.kv._degraded_kw())
            freed = self.sched.preempt(victim)
            # the scheduler's counter is the source of truth; stats mirrors
            # it (assignment, not increment — the two can never diverge)
            self.stats.preemptions = self.sched.n_preemptions
            self._emit("preempt", victim.rid, freed_blocks=freed,
                       generated_tokens=len(victim.output))
            running = [r for r in running if r is not victim]

    # ------------------------------------------------------------------
    # sampling (per-request PRNG streams — SamplingParams.seed honoured)
    # ------------------------------------------------------------------
    def _sample_and_record(self, reqs: List[Request],
                           logits: jax.Array) -> None:
        with TraceAnnotation("engine.sample"):
            toks = np.asarray(self._sample(reqs, logits))
            for r, tok in zip(reqs, toks.tolist()):
                r.record_token(tok)
        self._tokens_out += len(reqs)

    def _sample(self, reqs: List[Request], logits: jax.Array) -> np.ndarray:
        # token i of a request always draws from stream index i, via the
        # one canonical seed→stream mapping (sampler.request_key); a request
        # without its own seed falls back to the engine's
        toks, finite = sample_requests(
            logits,
            [self.config.seed if r.params.seed is None else r.params.seed
             for r in reqs],
            [len(r.output) for r in reqs],
            [r.params.temperature for r in reqs],
            [r.params.top_k for r in reqs])
        self._refuse_non_finite(reqs, finite)
        return toks

    # ------------------------------------------------------------------
    # introspection (CLI / benchmarks)
    # ------------------------------------------------------------------
    @property
    def pool(self):
        """The attention worker pool (None for homogeneous placement)."""
        return self.placement.pool

    @property
    def expert_pool(self):
        """The expert worker pool (moe_offload placement only)."""
        return self.placement.expert_pool

    @property
    def transfer_log(self):
        return self.placement.transfer_log

