"""Continuous-batching serving engine over the paged quantized KV cache.

The static engine (`serving/engine.py`) runs one batch to completion: short
requests strand their slot until the longest request drains, and nothing new
is admitted mid-flight. This engine keeps a fixed set of decode *slots* and
a global page pool, and drives three host-side control-plane moves between
jit'd device steps:

  admission   — when a slot and enough pages are free, the next queued
                request is admitted: its pages are allocated, its prompt is
                prefilled in fixed-size chunks (each chunk one jit call that
                attends over the raw K/V prefix with `q_offset`, exactly the
                math of full causal prefill), and the quantized chunk codes
                are scattered into its pages. With the copy-on-write prefix
                cache on (`SchedulerConfig.prefix_cache == "share"`), the
                prompt first walks a trie of already-served token blocks
                (`serving/prefix.py`): cached prefix pages are mapped into
                the page table by reference (refcount += 1) and only the
                uncovered suffix is prefilled.
  decode      — ONE fixed-shape jit step advances every active slot one
                token through `decode_step_paged` (page-table indirection in
                the attention path; inactive slots are masked to the trash
                page and their logits ignored).
  eviction    — a slot finishing (EOS or its token budget) frees its pages
                back to the allocator immediately and the slot becomes
                admissible in the same scheduler tick.

With `SchedulerConfig.speculate` on, the decode move becomes the
draft–verify–rollback round of `serving/speculate.py`: every dispatch
feeds each slot its pending token plus up to `draft_len` self-drafted
(prompt-lookup) tokens, appends their K/V optimistically, scores all
positions at once through the expanded-row paged attention path, commits
the accepted run, and pops the rejected suffix (`pages.pop_tokens`).
Greedy tokens stay bitwise-identical to plain decode; the win is strictly
fewer sequential forward passes per token whenever output repeats
structure (stats["spec"]["steps_per_token"] < 1).

All device shapes are static: (num_slots, max_pages) page table, fixed page
pool, fixed prefill chunk. The page table / lengths / active mask live as
host numpy and are shipped per step (tiny); the pool arrays stay on device
and are donated through every step.

Token parity: with greedy sampling the per-request tokens are identical to
the static engine's (chunk attention is the same causal math; the paged
Pallas kernel accumulates bit-for-bit like the contiguous kernel at
block_t == page_size) — pinned by tests/test_scheduler.py and gated by
benchmarks/serve_throughput.py.
"""
from __future__ import annotations

import dataclasses
import time
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core import sensitivity
from repro.core.mixedkv import MixedKVSchedule
from repro.core.quantizer import KVQuantizer
from repro.distributed import sharding as sharding_lib
from repro.models import attention, common, moe as moe_lib, transformer
from repro.serving import decode as decoding
from repro.serving import engine as engine_lib
from repro.serving import families as families_lib
from repro.serving import pages as pages_lib
from repro.serving import prefix as prefix_lib
from repro.serving import speculate as speculate_lib
from repro.serving import spill as spill_lib
from repro.serving import statecache as statecache_lib
from repro.serving import telemetry as telemetry_lib
from repro.serving.backends import AttentionBackend


def _tree_nbytes(tree) -> int:
    """Total bytes held by a pytree of (host or device) arrays."""
    return int(sum(x.nbytes for x in jax.tree.leaves(tree)))


@dataclasses.dataclass(frozen=True)
class Request:
    """One serving request. `arrival` is seconds relative to trace start
    (0.0 = already queued); `max_new_tokens` caps generation (EOS may end
    it earlier).

    SLO class: `priority` orders admission when the scheduler runs in
    preemptive mode (`SchedulerConfig.preempt`; higher wins, FCFS within
    a class) and entitles an arrival to preempt strictly-lower-priority
    victims under resource pressure. `deadline_ms` is an ADMISSION
    deadline: a request still queued that long after its arrival is shed
    with a typed result (`status="shed"`) instead of waiting forever —
    explicit overload behavior, never a hang.
    """

    rid: int
    tokens: np.ndarray  # (plen,) int32 prompt
    max_new_tokens: int
    arrival: float = 0.0
    priority: int = 0  # higher = more important (preempt mode only)
    deadline_ms: Optional[float] = None  # admission deadline (any mode)

    def __post_init__(self):
        if len(self.tokens) < 1:
            raise ValueError(f"request {self.rid}: empty prompt")
        if self.max_new_tokens < 1:
            raise ValueError(f"request {self.rid}: max_new_tokens < 1")
        if self.deadline_ms is not None and self.deadline_ms < 0:
            raise ValueError(
                f"request {self.rid}: deadline_ms must be >= 0")


#: `RequestResult.status` values: "completed" (ran to EOS/budget),
#: "shed" (admission deadline expired while queued — no tokens),
#: "cancelled" (`PagedServingEngine.cancel`; carries tokens generated
#: before the cancel landed).
RESULT_STATUSES = ("completed", "shed", "cancelled")


class RequestResult(NamedTuple):
    rid: int
    tokens: np.ndarray  # generated ids (includes the EOS if one fired)
    prompt_len: int
    ttft_s: float  # arrival -> first token
    latency_s: float  # arrival -> last token
    admitted_s: float  # arrival -> admission (queueing delay)
    # speculative-decoding accounting (zeros when speculation is off)
    draft_proposed: int = 0  # draft tokens fed to verify steps
    draft_accepted: int = 0  # of those, how many the model confirmed
    verify_steps: int = 0  # sequential forward passes spent decoding
    # perf accounting: device->host round-trips that advanced this request
    # (admission readback + one per decode/verify burst it rode) — the
    # dispatch-count observability ISSUE 6 adds so O(steps) host syncs
    # cannot sneak back into the hot loop unnoticed
    host_sync_count: int = 0
    # SLO / robustness accounting (ISSUE 7): how this request ended and
    # what the pressure ladder did to it on the way
    status: str = "completed"  # see RESULT_STATUSES
    priority: int = 0
    preemptions: int = 0  # times this request was spilled out of its slot
    restore_retries: int = 0  # transient alloc failures its restores ate
    degraded: bool = False  # pages recompressed to the tier-2 schedule
    # per-request observability (ISSUE 8): decode-phase seconds per token
    # (excluding the prefill-sampled first token) and the lifecycle
    # timeline — ((label, trace_seconds), ...) over arrival / admit /
    # first_token / spill / restore / degrade / done, in event order
    tpot_s: float = 0.0
    timeline: tuple = ()


#: `SchedulerConfig.prefix_cache` modes. "off" is the legacy raw-buffer
#: chunked prefill (bitwise-identical to the static engine). "cold" swaps
#: in the requantized-prefix prefill numerics (see `_prefill_fn`) WITHOUT a
#: trie — every request computes its whole prompt; this is the no-sharing
#: baseline the prefix benchmark compares against. "share" adds the
#: copy-on-write prefix trie on top of the exact same numerics, so a trace
#: served under "share" emits bitwise-identical greedy tokens to "cold"
#: while skipping the prefill of every cached prefix block.
PREFIX_MODES = ("off", "cold", "share")


@dataclasses.dataclass(frozen=True)
class DegradeConfig:
    """Tiered-precision degradation: under pool pressure, recompress a
    victim's pages into a second pool built for a lower-bit
    `MixedKVSchedule` instead of spilling it (the "degrade" rung of the
    pressure ladder, docs/serving.md). The tier-2 schedule is `schedule`
    when given, else picked by the sensitivity machinery
    (`sensitivity.pick_degraded`: the cheapest halving rung of the
    backend's schedule that stays at or above `floor_angle_bits` mean
    angle bits/element). The floor is ALWAYS enforced — an explicit
    schedule below it is rejected at engine construction.

    num_pages: physical size of the tier-2 pool (including its own
    reserved trash page 0). Degradation only fires for a victim whose
    full span reservation fits the tier-2 pool; otherwise the ladder
    falls through to spilling.
    """

    num_pages: int = 64
    floor_angle_bits: float = 1.0
    schedule: Optional[MixedKVSchedule] = None

    def __post_init__(self):
        if self.num_pages < 2:
            raise ValueError(
                f"degrade num_pages must be >= 2 (page 0 is reserved), "
                f"got {self.num_pages}")


@dataclasses.dataclass(frozen=True)
class SchedulerConfig:
    """Static configuration of the continuous-batching engine.

    num_slots:      concurrent decode slots (the fixed device batch).
    page_size:      tokens per physical page (== paged-kernel block size).
    num_pages:      physical pool size, including the reserved trash page 0.
    max_context:    longest prompt+generation any one slot may reach; sets
                    the page-table width (`max_pages`).
    prefill_chunk:  tokens per chunked-prefill step (a multiple of
                    page_size so chunk writes land on page boundaries).
    max_burst:      decode steps fused into one device dispatch.
    eos_id:         stop a request early when it samples this token.
    sampling:       temperature / top-k / top-p (greedy at temperature 0).
    prefix_cache:   "off" | "cold" | "share" — see `PREFIX_MODES`.
    prefix_pages:   LRU bound on pages the prefix trie may pin (mode
                    "share" only). The trie can never pin the whole pool.
    speculate:      draft-verify-rollback decoding (serving/speculate.py):
                    each decode dispatch scores the pending token plus up
                    to `draft_len` self-drafted tokens and commits the
                    accepted run — fewer sequential steps, bitwise-equal
                    greedy tokens. Requires greedy sampling (the lossless
                    guarantee is argmax equality; stochastic sampling
                    would need rejection-sampling corrections).
    draft_len:      draft tokens proposed per verify step (the verify
                    dispatch is always padded to q_len = draft_len + 1 —
                    one compiled variant per table-width bucket, never one
                    per acceptance count).
    draft_max_ngram: longest trailing n-gram the prompt-lookup drafter
                    tries to match (it backs off to shorter ones).
    spec_device:    fuse up to `max_burst` draft->verify->accept rounds
                    into ONE device dispatch: drafting runs on device
                    (`speculate.propose_draft_device`) over a resident
                    token buffer and the host reads tokens back only at
                    burst boundaries. False falls back to the host-driven
                    one-round-per-dispatch loop (`_spec_step`), kept as
                    the parity oracle — both emit bitwise-identical greedy
                    tokens (tests/test_speculate.py).
    """

    num_slots: int = 4
    page_size: int = 16
    num_pages: int = 256  # physical pages incl. the reserved trash page
    max_context: int = 1024  # longest prompt+generation any slot may reach
    prefill_chunk: int = 32  # tokens per chunked-prefill jit call
    max_burst: int = 8  # decode steps fused per device dispatch
    eos_id: Optional[int] = None
    sampling: engine_lib.SamplingConfig = engine_lib.SamplingConfig()
    prefix_cache: str = "off"
    prefix_pages: int = 128  # LRU bound on trie-pinned pages ("share" mode)
    speculate: bool = False
    draft_len: int = 4  # draft tokens per verify step (q_len = draft_len+1)
    draft_max_ngram: int = speculate_lib.DEFAULT_MAX_NGRAM
    spec_device: bool = True  # fused on-device spec burst (see docstring)
    # --- SLO / robustness (ISSUE 7) -------------------------------------
    # preempt:  priority-ordered admission + preemption-by-spill: when the
    #           highest-priority arrived request cannot be admitted, a
    #           strictly-lower-priority victim's pages are spilled to host
    #           memory (serving/spill.py) and its slot freed; the victim
    #           resumes later, bitwise-losslessly. Off = legacy FCFS.
    # degrade:  tiered-precision degradation config (None = off). The
    #           ladder under pressure is shed -> degrade -> spill ->
    #           evict (docs/serving.md). Mutually exclusive with
    #           `speculate` and prefix_cache "share" (the tiered decode
    #           step composes with neither; spill/preempt compose with
    #           both).
    # restore_max_retries / restore_backoff_s: transient-alloc-failure
    #           retry policy of a spilled request's restore; retries
    #           beyond the per-tick budget re-queue the restore with
    #           exponential backoff instead of blocking the loop.
    # debug_conservation: run `PageAllocator.check_conservation()` (both
    #           pools) after EVERY admission / burst / preemption tick
    #           instead of only at the end of run() — on in all
    #           scheduler/speculate/prefix/preempt tests.
    # max_wall_s: wall-clock watchdog on run(): a trace exceeding it
    #           raises `SchedulerWatchdogError` with a diagnostic dump
    #           (live slots, pool occupancy, last dispatch key) instead
    #           of hanging CI forever. None = no watchdog.
    preempt: bool = False
    degrade: Optional[DegradeConfig] = None
    restore_max_retries: int = 3
    restore_backoff_s: float = 0.002
    debug_conservation: bool = False
    max_wall_s: Optional[float] = None
    # --- observability (ISSUE 8) ----------------------------------------
    # telemetry: gates the tracer's event RING (serving/telemetry.py).
    #           Spans reach a running jax.profiler session either way.
    #           Metrics (counters/gauges/histograms) stay on either way —
    #           they are the same host-side arithmetic the stats dicts
    #           always did and never touch device state or rng, so a
    #           telemetry-off run is bitwise-identical by construction
    #           (pinned in tests/test_telemetry.py).
    # trace_capacity: ring-buffer bound on recorded trace events (oldest
    #           fall off first), keeping soak-length traces memory-safe.
    telemetry: bool = True
    trace_capacity: int = 4096
    # --- multi-device sharding (ISSUE 9) --------------------------------
    # mesh: a jax Mesh with a "model" axis — the paged pool's kv-head dim
    #           (and the matching GQA q-head groups) shards over it, every
    #           jit'd step runs under shard_map, and per-shard
    #           PageAllocators are kept in lockstep
    #           (pages.ShardedPageAllocators). The page table, params and
    #           all control-plane state stay replicated, so admission /
    #           spill / evict remain single host-side decisions applied to
    #           all shards atomically. None = the legacy single-device
    #           path, bitwise- and dispatch-count-identical to pre-mesh
    #           builds (docs/sharding.md).
    mesh: Optional[jax.sharding.Mesh] = None

    def __post_init__(self):
        if self.trace_capacity < 16:
            raise ValueError(
                f"trace_capacity must be >= 16, got {self.trace_capacity}")
        if self.prefill_chunk % self.page_size:
            raise ValueError(
                f"prefill_chunk ({self.prefill_chunk}) must be a multiple "
                f"of page_size ({self.page_size}) so chunk writes land on "
                f"page boundaries")
        if self.max_burst < 1:
            raise ValueError(f"max_burst must be >= 1, got {self.max_burst}")
        if self.speculate:
            if self.draft_len < 1:
                raise ValueError(
                    f"draft_len must be >= 1 with speculate, got "
                    f"{self.draft_len}")
            if self.draft_max_ngram < 1:
                raise ValueError(
                    f"draft_max_ngram must be >= 1, got "
                    f"{self.draft_max_ngram}")
            if not self.sampling.is_greedy:
                raise ValueError(
                    "speculative decoding requires greedy sampling "
                    "(temperature 0): losslessness is argmax equality; "
                    "stochastic acceptance is not implemented")
        if self.degrade is not None:
            if self.speculate:
                raise ValueError(
                    "degrade is mutually exclusive with speculate: the "
                    "tiered decode step has no verify variant (spill-based "
                    "preemption composes with speculation; use that)")
            if self.prefix_cache == "share":
                raise ValueError(
                    "degrade is mutually exclusive with prefix_cache "
                    "'share': tier migration would strand trie references "
                    "to recompressed pages")
        if self.restore_max_retries < 1:
            raise ValueError(
                f"restore_max_retries must be >= 1, got "
                f"{self.restore_max_retries}")
        if self.restore_backoff_s < 0:
            raise ValueError(
                f"restore_backoff_s must be >= 0, got "
                f"{self.restore_backoff_s}")
        if self.max_wall_s is not None and self.max_wall_s <= 0:
            raise ValueError(
                f"max_wall_s must be > 0 (or None), got {self.max_wall_s}")
        if self.mesh is not None and "model" not in self.mesh.axis_names:
            raise ValueError(
                f"sharded serving needs a 'model' mesh axis, got "
                f"{self.mesh.axis_names}")
        if self.prefix_cache not in PREFIX_MODES:
            raise ValueError(
                f"prefix_cache must be one of {PREFIX_MODES}, got "
                f"{self.prefix_cache!r}")
        if self.prefix_cache == "share":
            if self.prefix_pages < 1:
                raise ValueError(
                    f"prefix_pages must be >= 1 in share mode, got "
                    f"{self.prefix_pages}")
            if self.prefix_pages >= self.num_pages - 1:
                raise ValueError(
                    f"prefix_pages ({self.prefix_pages}) would let the trie "
                    f"pin the whole pool ({self.num_pages - 1} usable "
                    f"pages); leave headroom for live requests")

    @property
    def max_pages(self) -> int:
        return pages_lib.pages_for_tokens(self.max_context, self.page_size)


class SchedulerWatchdogError(RuntimeError):
    """The wall-clock watchdog (`SchedulerConfig.max_wall_s`) fired.

    `diagnostic` is the dump the satellite asks for: tick, wall seconds,
    every live slot (rid / priority / length / tokens generated /
    remaining budget), pool occupancy for both tiers, pending and spilled
    rids, the last device dispatch key, AND `trace_tail` — the last N
    structured trace events from the telemetry ring buffer, so a watchdog
    fire ships its own flight recorder: WHAT the scheduler was doing
    leading up to the hang, not just a state snapshot."""

    def __init__(self, msg: str, diagnostic: dict):
        super().__init__(f"{msg}\ndiagnostic: {diagnostic}")
        self.diagnostic = diagnostic


class _Slot:
    """Host-side state of one decode slot's in-flight request."""

    def __init__(self, req: Request, first_token: int, t_admit: float,
                 t_first: float):
        self.req = req
        self.generated = [int(first_token)]
        self.t_admit = t_admit
        self.t_first = t_first
        # speculative-decoding accounting (stay zero when speculation off)
        self.draft_proposed = 0
        self.draft_accepted = 0
        self.verify_steps = 0
        self.host_syncs = 1  # the admission readback itself
        # SLO / pressure-ladder accounting (ISSUE 7)
        self.priority = req.priority
        self.preemptions = 0
        self.restore_retries = 0
        self.degraded = False
        # lifecycle timeline (trace-relative seconds) -> RequestResult
        self.marks = [("arrival", req.arrival), ("admit", t_admit),
                      ("first_token", t_first)]

    @classmethod
    def from_spilled(cls, sp: "spill_lib.SpilledRequest") -> "_Slot":
        """Rebuild a slot from a restored spill — counters carry over and
        no first-token is re-sampled (the pending token rode the spill)."""
        st = cls.__new__(cls)
        st.req = sp.req
        st.generated = sp.generated
        st.t_admit = sp.t_admit
        st.t_first = sp.t_first
        st.marks = sp.marks
        st.draft_proposed = sp.draft_proposed
        st.draft_accepted = sp.draft_accepted
        st.verify_steps = sp.verify_steps
        st.host_syncs = sp.host_syncs
        st.priority = sp.priority
        st.preemptions = sp.preemptions
        st.restore_retries = sp.restore_retries
        st.degraded = sp.degraded
        return st


class PagedServingEngine:
    """Continuous-batching serving engine over the paged quantized pool.

    Drives the admission / burst-decode / eviction loop described in the
    module docstring. Construct once per (params, model config, backend,
    scheduler config) and call `run` with a request trace; the engine and
    its compiled executables are reusable across traces (the benchmark
    replays the same trace several times on one engine).

    With `sched.prefix_cache == "share"` the engine additionally keeps a
    copy-on-write prefix trie (`serving/prefix.py`): admission maps the
    pages of an already-served prompt prefix straight into the new
    request's page table (refcount += 1 per page, no recompute, no copy)
    and chunk-prefills only the uncovered suffix. See docs/serving.md for
    the page/refcount lifecycle.
    """

    def __init__(self, params, cfg: ModelConfig,
                 backend: AttentionBackend, sched: SchedulerConfig,
                 telemetry: Optional[telemetry_lib.Telemetry] = None,
                 state_cache: Optional[
                     statecache_lib.StateCacheConfig] = None):
        # capability-based admission (serving/families.py): either the
        # (cfg, sched, backend) combination is servable and we get the
        # family's adapter, or construction raises one typed
        # UnsupportedFamilyError naming the missing capability.
        self.family = families_lib.check_supported(cfg, sched, backend)
        # MoE serving is dropless (models/moe.py): capacity-based token
        # drops depend on batch composition, so the same prompt through a
        # chunked prefill vs the static engine's full prefill would round
        # differently. Raising capacity to experts/top_k makes every MoE
        # dispatch batch-shape-deterministic — paged decode stays bitwise
        # the static engine run with this same (dropless) config.
        cfg = moe_lib.dropless_serving_config(cfg)
        self.moe_dropless = bool(cfg.moe_experts)
        self.params = params
        self.cfg = cfg
        self.backend = backend
        self.sched = sched
        # --- kv-head sharding (ISSUE 9): with a mesh, the pool's head
        # axis splits over "model", params/tables replicate, each shard
        # gets a mirror allocator kept in lockstep, and every jit'd step
        # runs under shard_map (`_mesh_jit`). mesh=None is the legacy
        # single-device path, bitwise- and dispatch-count-identical.
        self._shard: Optional[decoding.ShardInfo] = None
        self._replicated: Optional[jax.sharding.NamedSharding] = None
        if sched.mesh is not None:
            n_sh = sharding_lib.kv_shard_count(cfg, sched.mesh)
            self._shard = decoding.ShardInfo("model", n_sh)
            self._replicated = jax.sharding.NamedSharding(
                sched.mesh, jax.sharding.PartitionSpec())
            self.params = sharding_lib.replicate(self.params, sched.mesh)
        self.allocator = self._make_allocator(sched.num_pages)
        self.pool = None
        if self.family.paged_kv:
            self.pool = self._commit_pool(backend.init_paged_cache(
                sched.num_pages, sched.page_size, sched.num_slots,
                sched.max_pages))
        # host-side control plane (shipped per step; tiny)
        s = sched.num_slots
        # --- quantized recurrent-state cache (ISSUE 10,
        # serving/statecache.py): state-slot families keep per-slot
        # SSM/xLSTM state in fixed-size FWHT+angle-coded slots, decoded
        # on read and re-encoded on write at slot granularity. Hybrid
        # families use BOTH planes in the same tick (attention KV on
        # pages, recurrent state on slots).
        self.store: Optional[statecache_lib.StateStore] = None
        self.states = None  # packed per-leaf tuple (device-resident)
        self.state_slots: Optional[statecache_lib.StateSlotAllocator] = None
        if self.family.state_slots:
            self.store = statecache_lib.StateStore(cfg, s, state_cache)
            self.states = self.store.init_data()
            self.state_slots = statecache_lib.StateSlotAllocator(s)
        self.page_table = np.zeros((s, sched.max_pages), np.int32)
        self.lengths = np.zeros((s,), np.int32)
        self.active = np.zeros((s,), bool)
        self.next_tok = np.zeros((s,), np.int32)
        self.slots: list[Optional[_Slot]] = [None] * s
        # --- telemetry spine (ISSUE 8, serving/telemetry.py): the metrics
        # registry is ALWAYS live (host-side arithmetic only — the
        # stats[...] blocks run() returns are per-run delta views over it,
        # one source of truth); the tracer ring is gated by
        # sched.telemetry. Streaming consumers (serving/server.py) hook
        # `on_tokens(rid, [ids])` / `on_result(RequestResult)`.
        self.telemetry = telemetry or telemetry_lib.Telemetry(
            enabled=sched.telemetry, trace_capacity=sched.trace_capacity)
        self._tracer = self.telemetry.tracer
        self._m = self._build_metrics(self.telemetry.registry)
        self.on_tokens = None
        self.on_result = None
        self._tick = 0
        self.trie: Optional[prefix_lib.PrefixTrie] = None
        if sched.prefix_cache == "share":
            self.trie = prefix_lib.PrefixTrie(
                self.allocator, sched.page_size, sched.prefix_pages,
                telemetry=self.telemetry)
        # --- tier-2 (degraded-precision) pool: a second, genuinely
        # smaller pool built for a lower-bit schedule (narrower packed
        # words), its own allocator and page table; `tier2[i]` marks a
        # slot whose pages were migrated there under pressure
        self.backend2: Optional[AttentionBackend] = None
        self.allocator2: Optional[pages_lib.PageAllocator] = None
        self.pool2 = None
        self.page_table2 = np.zeros((0, 0), np.int32)
        self.tier2 = np.zeros((s,), bool)
        if sched.degrade is not None:
            qz1 = backend.quantizer
            d = sched.degrade
            if d.schedule is not None:
                sched2 = d.schedule
                if sched2.angle_bits() < d.floor_angle_bits:
                    raise ValueError(
                        f"degrade schedule {sched2.describe()} "
                        f"({sched2.angle_bits():.2f} angle bits/elem) is "
                        f"below the quality floor {d.floor_angle_bits}")
            else:
                sched2 = sensitivity.pick_degraded(
                    qz1.config.schedule,
                    floor_angle_bits=d.floor_angle_bits).schedule
            qz2 = KVQuantizer(
                dataclasses.replace(qz1.config, schedule=sched2))
            self.backend2 = dataclasses.replace(backend, quantizer=qz2)
            self.allocator2 = self._make_allocator(d.num_pages)
            self.pool2 = self._commit_pool(self.backend2.init_paged_cache(
                d.num_pages, sched.page_size, s, sched.max_pages))
            self.page_table2 = np.zeros((s, sched.max_pages), np.int32)
            # one jitted dequant->requant migration fn; jit caches per
            # pow-2 page-count bucket internally
            self._migrate_fn = spill_lib.make_migrate_fn(qz1, qz2)
        # SLO / preemption control plane
        self._spilled: dict[int, spill_lib.SpilledRequest] = {}
        self._cancel_req: set[int] = set()
        self._last_dispatch_key: Optional[tuple] = None
        self._faults = None  # FaultInjector of the current run (or None)
        # device-resident token streams for on-device drafting: slot i's
        # prompt + every emitted token (ending with the pending token),
        # shipped to the spec-burst dispatch and read back only at burst
        # boundaries. Width = the token capacity any slot can reach.
        cap_tokens = sched.max_pages * sched.page_size
        self.ctx_buf = np.zeros((s, cap_tokens), np.int32)
        self.ctx_len = np.zeros((s,), np.int32)
        self._decode_fn = self._build_decode()
        self._verify_fn = self._build_verify() if sched.speculate else None
        self._spec_fn = (self._build_spec()
                         if sched.speculate and sched.spec_device else None)
        # (suffix bucket width, skipped prefix tokens) -> jit fn
        self._prefill_fns: dict[tuple[int, int], object] = {}
        self._prefix_load_fns: dict[int, object] = {}  # prefix pages -> fn
        self._sprefill_fns: dict[int, object] = {}  # state-prefill, width
        # --- perf observability (serving/compile_cache.py wires warmup):
        # every device dispatch routes through `_dispatch`, which counts
        # distinct jit-variant keys and prefers AOT-compiled executables
        self._compiled_keys: set = set()
        self._exec: dict = {}  # variant key -> AOT-compiled executable
        self._warmed = False
        self._perf = dict(jit_variants_compiled=0, compile_wall_s=0.0,
                          warmup_wall_s=0.0, post_warmup_variants=0)
        self._wait_s = 0.0  # readback + idle-wait seconds of this tick

    # ------------------------------------------------------------ sharding --
    def _make_allocator(self, num_pages: int):
        """One PageAllocator — or N lockstep mirrors under a mesh."""
        if self._shard is None:
            return pages_lib.PageAllocator(num_pages)
        return pages_lib.ShardedPageAllocators(num_pages, self._shard.size)

    def _commit_pool(self, pool):
        """(Re-)commit a pool's k/v trees to the kv-head sharding.

        Applied at init and after every pressure-path scatter that builds
        fresh pool arrays outside shard_map (restore, tier migration), so
        the decode hot path never sees a silently resharded operand. No-op
        without a mesh."""
        if self._shard is None or pool is None:
            return pool
        mesh = self.sched.mesh
        return pool._replace(
            k=sharding_lib.shard_paged_pool(pool.k, mesh),
            v=sharding_lib.shard_paged_pool(pool.v, mesh))

    # ------------------------------------------------------------ telemetry --
    def _build_metrics(self, reg: telemetry_lib.MetricsRegistry) -> dict:
        """Resolve every scheduler metric handle once (get-or-create), so
        instrumentation sites are plain attribute arithmetic. Names are
        the contract docs/observability.md pins; the stats[...] blocks
        run() returns are per-run deltas over exactly these metrics."""
        c, g, h = reg.counter, reg.gauge, reg.histogram
        m = {
            # pressure ladder / SLO (stats["slo"] views)
            "shed": c("sched_shed", "requests shed past their admission "
                      "deadline"),
            "cancelled": c("sched_cancelled", "requests cancelled (any "
                           "state: queued, spilled, or live)"),
            "spills": c("sched_spills", "live slots preempted by spilling "
                        "their pages to host memory"),
            "spill_bytes": c("sched_spill_bytes", "packed page bytes "
                             "copied device->host by spills"),
            "restores": c("sched_restores", "spilled requests resumed "
                          "into a slot"),
            "restore_retries": c("sched_restore_retries", "transient "
                                 "alloc failures eaten by restores"),
            "restore_delays": c("sched_restore_delays", "restores that "
                                "served an injected upload delay"),
            "degraded": c("sched_degraded", "live slots recompressed "
                          "into the tier-2 (lower-bit) pool"),
            # work counters (top-level stats views)
            "prefill_chunks": c("prefill_chunks", "chunked-prefill device "
                                "chunks computed (pow-2 padding included)"),
            "prefill_tokens": c("prefill_tokens", "prefill tokens "
                                "computed (pow-2 padding included)"),
            "prefill_wall_s": c("prefill_wall_s", "seconds spent in "
                                "admission prefill dispatches"),
            "decode_steps": c("decode_steps", "sequential decode/verify "
                              "steps the device executed"),
            "new_tokens": c("new_tokens", "generated tokens delivered in "
                            "RequestResults"),
            "host_syncs": c("host_syncs", "device->host readbacks on the "
                            "serving hot path"),
            "decode_bursts": c("decode_bursts", "plain decode-burst "
                               "dispatches"),
            "sched_host_s": c("sched_host_seconds", "seconds the host spent "
                              "in scheduler ticks outside device readbacks "
                              "and idle waits"),
            # speculative decoding (stats["spec"] views)
            "draft_proposed": c("spec_draft_proposed", "draft tokens fed "
                                "to verify steps"),
            "draft_accepted": c("spec_draft_accepted", "draft tokens the "
                                "model confirmed"),
            "verify_steps": c("spec_verify_steps", "sequential verify "
                              "forward passes"),
            # request outcomes
            "fin_completed": c("requests_finished", "requests retired, by "
                               "terminal status", status="completed"),
            "fin_shed": c("requests_finished", "requests retired, by "
                          "terminal status", status="shed"),
            "fin_cancelled": c("requests_finished", "requests retired, by "
                               "terminal status", status="cancelled"),
            # latency distributions (completed requests only, seconds)
            "ttft": h("ttft_seconds", "arrival -> first token"),
            "tpot": h("tpot_seconds", "decode seconds per token after "
                      "the first"),
            "latency": h("request_latency_seconds", "arrival -> last "
                         "token"),
            # point-in-time occupancy (refreshed every scheduler tick)
            "pool_free": g("pool_free_pages", "free physical pages",
                           tier="1"),
            "pool_live": g("pool_live_pages", "referenced physical pages",
                           tier="1"),
            "slots_active": g("slots_active", "live decode slots"),
            "pending": g("requests_pending", "arrived requests waiting "
                         "for admission"),
            "spilled": g("requests_spilled", "preempted requests parked "
                         "in host memory"),
            "spec_rate": g("spec_acceptance_rate", "lifetime draft "
                           "acceptance rate"),
            "variants": g("jit_variants_compiled", "distinct jit variant "
                          "keys dispatched"),
            "post_warmup": g("post_warmup_variants", "variant keys first "
                             "seen after warmup (CI pins 0)"),
        }
        if self.sched.degrade is not None:
            m["pool_free2"] = g("pool_free_pages", "free physical pages",
                                tier="2")
            m["pool_live2"] = g("pool_live_pages",
                                "referenced physical pages", tier="2")
        if self.family.state_slots:
            m["state_bytes"] = g("state_cache_bytes", "packed bytes held "
                                 "by the quantized recurrent-state cache")
            m["state_encode_s"] = c("state_encode_seconds", "seconds "
                                    "spent in state-cache encode/prefill "
                                    "dispatches")
        return m

    def _refresh_gauges(self, n_pending: int) -> None:
        m = self._m
        m["pool_free"].set(self.allocator.num_free)
        m["pool_live"].set(self.allocator.num_live)
        if self.allocator2 is not None:
            m["pool_free2"].set(self.allocator2.num_free)
            m["pool_live2"].set(self.allocator2.num_live)
        if self.store is not None:
            m["state_bytes"].set(self.store.physical_bytes(self.states))
        m["slots_active"].set(int(self.active.sum()))
        m["pending"].set(n_pending)
        m["spilled"].set(len(self._spilled))
        m["variants"].set(self._perf["jit_variants_compiled"])
        m["post_warmup"].set(self._perf["post_warmup_variants"])
        prop = m["draft_proposed"].value
        if prop:
            m["spec_rate"].set(m["draft_accepted"].value / prop)

    # ------------------------------------------------------------ builders --
    def _mesh_jit(self, fn, *, n_in, pool_in, n_out, pool_out, donate):
        """jit one step function — plain on the legacy path, under
        `shard_map` when the engine has a mesh.

        `pool_in`/`pool_out` index the arguments/outputs that are paged
        pool trees (kv-head-sharded, `paged_pool_pspec`); everything else
        is replicated. Specs are pytree prefixes, so one spec covers a
        whole QuantizedKV tree. check_vma=False: the replicated outputs
        (logits, tokens, counters) are replicated by construction — every
        device runs the same math on the same replicated operands after
        the all-gather — but shard_map cannot infer that statically."""
        if self._shard is None:
            return jax.jit(fn, donate_argnums=donate)
        pp = sharding_lib.paged_pool_pspec()
        rep = jax.sharding.PartitionSpec()
        in_specs = tuple(pp if i in pool_in else rep for i in range(n_in))
        out_specs = tuple(pp if i in pool_out else rep for i in range(n_out))
        wrapped = jax.shard_map(fn, mesh=self.sched.mesh, in_specs=in_specs,
                                out_specs=out_specs, check_vma=False)
        return jax.jit(wrapped, donate_argnums=donate)

    def _build_decode(self):
        """Burst decode: up to `k_steps` (<= max_burst) decode steps fused
        into ONE device dispatch — a jitted while_loop whose body is
        `decode_step_paged`. Slots that hit their budget (or EOS) mid-burst
        freeze on device (active mask) and stop appending; the host picks
        the burst length as min(remaining budget) over active slots, so in
        the common case no slot idles inside a burst. This amortizes the
        per-step dispatch the host-driven control plane would otherwise pay
        per token (the static engine's fused loop pays it once per batch).

        The host slices the page table to the pages actually live (bucketed
        to powers of two, capped at max_pages — `_live_table_width`) before
        each call, so the kernel's grid — and therefore the decode cost —
        scales with the batch's real context, not the engine-wide maximum.
        jit specializes one trace per sliced width, O(log max_pages) total.
        """
        if self.family.state_slots:
            return self._build_decode_state()
        cfg, backend, sc = self.cfg, self.backend, self.sched.sampling
        s = self.sched.num_slots
        max_burst = self.sched.max_burst
        eos = self.sched.eos_id
        backend2 = self.backend2
        shard = self._shard

        if backend2 is not None:
            # tiered variant (DegradeConfig on): the burst body runs
            # `decode_step_paged_tiered` over BOTH pools — a slot's pages
            # live in exactly one (the tier2 mask routes appends and
            # selects attention outputs). Signature grows the tier-2 pool
            # / table / mask; everything else (burst while_loop, EOS,
            # sampling) is identical to the single-pool variant below.
            def decode_burst_tiered(params, pk1, pv1, pk2, pv2, table1,
                                    table2, tier2, lengths, active, owned,
                                    tokens, remaining, k_steps, rng):
                out0 = jnp.full((s, max_burst), -1, jnp.int32)
                emitted0 = jnp.zeros((s,), jnp.int32)

                def cond(c):
                    return (c[0] < k_steps) & jnp.any(c[6])

                def body(c):
                    (step, pk1, pv1, pk2, pv2, lens, act, toks, emitted,
                     out, rng) = c
                    rng, sub = jax.random.split(rng)
                    c1 = pages_lib.PagedKVCache(pk1, pv1, table1, lens)
                    c2 = pages_lib.PagedKVCache(pk2, pv2, table2, lens)
                    logits, n1, n2 = decoding.decode_step_paged_tiered(
                        params, cfg, c1, c2, toks[:, None], act, tier2,
                        backend=backend, backend2=backend2,
                        write_mask=owned, shard=shard)
                    nxt = engine_lib.sample_tokens(sub, logits, sc)
                    nxt = jnp.where(act, nxt, toks)
                    out = jax.lax.dynamic_update_slice(
                        out, jnp.where(act, nxt, -1)[:, None], (0, step))
                    emitted = emitted + act.astype(jnp.int32)
                    done = emitted >= remaining
                    if eos is not None:
                        done = done | (act & (nxt == eos))
                    return (step + 1, n1.k, n1.v, n2.k, n2.v, n1.lengths,
                            act & ~done, nxt, emitted, out, rng)

                init = (jnp.asarray(0, jnp.int32), pk1, pv1, pk2, pv2,
                        lengths, active, tokens, emitted0, out0, rng)
                fin = jax.lax.while_loop(cond, body, init)
                # pools (both tiers), emitted, out
                return fin[1], fin[2], fin[3], fin[4], fin[8], fin[9]

            return self._mesh_jit(decode_burst_tiered, n_in=15,
                                  pool_in={1, 2, 3, 4}, n_out=6,
                                  pool_out={0, 1, 2, 3}, donate=(1, 2, 3, 4))

        def decode_burst(params, pool_k, pool_v, page_table, lengths, active,
                         owned, tokens, remaining, k_steps, rng):
            out0 = jnp.full((s, max_burst), -1, jnp.int32)
            emitted0 = jnp.zeros((s,), jnp.int32)

            def cond(c):
                return (c[0] < k_steps) & jnp.any(c[4])

            def body(c):
                step, pk, pv, lens, act, toks, emitted, out, rng = c
                rng, sub = jax.random.split(rng)
                cache = pages_lib.PagedKVCache(pk, pv, page_table, lens)
                logits, new_cache = decoding.decode_step_paged(
                    params, cfg, cache, toks[:, None], act, backend=backend,
                    write_mask=owned, shard=shard)
                nxt = engine_lib.sample_tokens(sub, logits, sc)
                nxt = jnp.where(act, nxt, toks)
                out = jax.lax.dynamic_update_slice(
                    out, jnp.where(act, nxt, -1)[:, None], (0, step))
                emitted = emitted + act.astype(jnp.int32)
                done = emitted >= remaining
                if eos is not None:
                    done = done | (act & (nxt == eos))
                return (step + 1, new_cache.k, new_cache.v,
                        new_cache.lengths, act & ~done, nxt, emitted, out,
                        rng)

            init = (jnp.asarray(0, jnp.int32), pool_k, pool_v, lengths,
                    active, tokens, emitted0, out0, rng)
            fin = jax.lax.while_loop(cond, body, init)
            return fin[1], fin[2], fin[6], fin[7]  # pool_k, pool_v, emitted, out

        return self._mesh_jit(decode_burst, n_in=11, pool_in={1, 2}, n_out=4,
                              pool_out={0, 1}, donate=(1, 2))

    def _build_decode_state(self):
        """Burst decode for state-slot families (serving/statecache.py).

        Same fused-while_loop shape as the paged burst, but the per-slot
        recurrent state rides the carry in RAW form: the packed
        quantized store is decoded ONCE at burst entry, stepped raw for
        up to `k_steps` tokens, then re-encoded and merged back at burst
        exit — only burst-entry-active slots' packed bytes are rewritten
        (`StateStore.merge`), so idle slots' codes stay bit-exact without
        relying on encode∘decode idempotence. Hybrid families
        (zamba2-style) additionally thread the shared-attention paged
        pool through the same dispatch: pages and state slots advance in
        the same tick. State families never run under a mesh
        (families.py rejects it), so these are plain `jax.jit`.
        """
        cfg, backend, sc = self.cfg, self.backend, self.sched.sampling
        s = self.sched.num_slots
        max_burst = self.sched.max_burst
        eos = self.sched.eos_id
        store = self.store

        if self.family.paged_kv:  # hybrid: pages + state slots per tick
            def state_decode_burst(params, pool_k, pool_v, page_table,
                                   lengths, active, tokens, remaining,
                                   k_steps, rng, packed):
                states0 = store.decode(packed)
                out0 = jnp.full((s, max_burst), -1, jnp.int32)
                emitted0 = jnp.zeros((s,), jnp.int32)

                def cond(c):
                    return (c[0] < k_steps) & jnp.any(c[4])

                def body(c):
                    (step, pk, pv, lens, act, states, toks, emitted, out,
                     rng) = c
                    rng, sub = jax.random.split(rng)
                    cache = pages_lib.PagedKVCache(pk, pv, page_table,
                                                   lens)
                    logits, new_cache, new_states = (
                        decoding.decode_step_paged_hybrid(
                            params, cfg, cache, states, toks[:, None],
                            act, backend=backend))
                    nxt = engine_lib.sample_tokens(sub, logits, sc)
                    nxt = jnp.where(act, nxt, toks)
                    out = jax.lax.dynamic_update_slice(
                        out, jnp.where(act, nxt, -1)[:, None], (0, step))
                    emitted = emitted + act.astype(jnp.int32)
                    done = emitted >= remaining
                    if eos is not None:
                        done = done | (act & (nxt == eos))
                    return (step + 1, new_cache.k, new_cache.v,
                            new_cache.lengths, act & ~done, new_states,
                            nxt, emitted, out, rng)

                init = (jnp.asarray(0, jnp.int32), pool_k, pool_v,
                        lengths, active, states0, tokens, emitted0, out0,
                        rng)
                fin = jax.lax.while_loop(cond, body, init)
                new_packed = store.merge(store.encode(fin[5]), packed,
                                         active)
                # pool_k, pool_v, emitted, out, packed
                return fin[1], fin[2], fin[7], fin[8], new_packed

            return jax.jit(state_decode_burst, donate_argnums=(1, 2, 10))

        def state_decode_burst(params, active, tokens, remaining, k_steps,
                               rng, packed):
            states0 = store.decode(packed)
            out0 = jnp.full((s, max_burst), -1, jnp.int32)
            emitted0 = jnp.zeros((s,), jnp.int32)

            def cond(c):
                return (c[0] < k_steps) & jnp.any(c[1])

            def body(c):
                step, act, states, toks, emitted, out, rng = c
                rng, sub = jax.random.split(rng)
                logits, new_ds = decoding.decode_step(
                    params, cfg,
                    decoding.DecodeState(cache=None, states=states),
                    toks[:, None], backend=backend)
                new_states = decoding.mask_states(cfg, act, new_ds.states,
                                                  states)
                nxt = engine_lib.sample_tokens(sub, logits, sc)
                nxt = jnp.where(act, nxt, toks)
                out = jax.lax.dynamic_update_slice(
                    out, jnp.where(act, nxt, -1)[:, None], (0, step))
                emitted = emitted + act.astype(jnp.int32)
                done = emitted >= remaining
                if eos is not None:
                    done = done | (act & (nxt == eos))
                return (step + 1, act & ~done, new_states, nxt, emitted,
                        out, rng)

            init = (jnp.asarray(0, jnp.int32), active, states0, tokens,
                    emitted0, out0, rng)
            fin = jax.lax.while_loop(cond, body, init)
            new_packed = store.merge(store.encode(fin[2]), packed, active)
            return fin[4], fin[5], new_packed  # emitted, out, packed

        return jax.jit(state_decode_burst, donate_argnums=(6,))

    def _state_width(self, plen: int) -> int:
        """Pow-2 prompt-width bucket for a state-prefill dispatch."""
        cap = self.sched.max_pages * self.sched.page_size
        w = 1
        while w < plen:
            w *= 2
        return min(w, max(cap, plen))

    def _sprefill_fn(self, width: int):
        """State-family admission prefill, one jit variant per pow-2
        prompt-width bucket.

        There is no chunked-prefill shortcut for recurrent state: the
        state after the prompt IS the prompt's sequential scan, so the
        slot's tokens are fed one step at a time through the SAME
        fixed-shape full-batch decode step the burst loop uses (a
        `lax.scan` over the padded width; positions past the real prompt
        length are masked inactive). Other live slots ride along masked:
        their state and lengths are untouched and their appends hit the
        trash page. The first generated token is sampled in-dispatch
        from the last valid position's logits, and the freshly scanned
        state is encoded and merged into ONLY the admitted slot's packed
        bytes.
        """
        key = ("sprefill", width)
        if width in self._sprefill_fns:
            return key, self._sprefill_fns[width]
        cfg, backend, sc = self.cfg, self.backend, self.sched.sampling
        s = self.sched.num_slots
        store = self.store

        if self.family.paged_kv:  # hybrid
            def state_prefill(params, tokens, slot, plen, pool_k, pool_v,
                              page_table, lengths, packed, rng):
                onehot = jnp.arange(s) == slot
                # slot reuse: the packed bytes still hold the PREVIOUS
                # owner's final state — select the initial state for the
                # admitted slot before scanning the new prompt into it
                states0 = decoding.mask_states(
                    cfg, onehot, store.init_states(), store.decode(packed))
                last0 = jnp.zeros((cfg.vocab_size,), jnp.float32)

                def body(carry, xs):
                    states, pk, pv, lens, last = carry
                    tok, pos = xs
                    act = onehot & (pos < plen)
                    toks = jnp.where(onehot, tok, 0).astype(jnp.int32)
                    cache = pages_lib.PagedKVCache(pk, pv, page_table,
                                                   lens)
                    logits, new_cache, new_states = (
                        decoding.decode_step_paged_hybrid(
                            params, cfg, cache, states, toks[:, None],
                            act, backend=backend))
                    row = jax.lax.dynamic_index_in_dim(
                        logits, slot, 0, keepdims=False)
                    last = jnp.where(pos == plen - 1,
                                     row.astype(jnp.float32), last)
                    return (new_states, new_cache.k, new_cache.v,
                            new_cache.lengths, last), None

                init = (states0, pool_k, pool_v, lengths, last0)
                (fstates, pk, pv, _, last), _ = jax.lax.scan(
                    body, init, (tokens, jnp.arange(width)))
                first = engine_lib.sample_tokens(rng, last[None], sc)[0]
                new_packed = store.merge(store.encode(fstates), packed,
                                         onehot)
                return first, pk, pv, new_packed

            fn = jax.jit(state_prefill, donate_argnums=(4, 5, 8))
        else:
            def state_prefill(params, tokens, slot, plen, packed, rng):
                onehot = jnp.arange(s) == slot
                # reused slot: reset to the initial state (see the hybrid one)
                states0 = decoding.mask_states(
                    cfg, onehot, store.init_states(), store.decode(packed))
                last0 = jnp.zeros((cfg.vocab_size,), jnp.float32)

                def body(carry, xs):
                    states, last = carry
                    tok, pos = xs
                    act = onehot & (pos < plen)
                    toks = jnp.where(onehot, tok, 0).astype(jnp.int32)
                    logits, new_ds = decoding.decode_step(
                        params, cfg,
                        decoding.DecodeState(cache=None, states=states),
                        toks[:, None], backend=backend)
                    new_states = decoding.mask_states(
                        cfg, act, new_ds.states, states)
                    row = jax.lax.dynamic_index_in_dim(
                        logits, slot, 0, keepdims=False)
                    last = jnp.where(pos == plen - 1,
                                     row.astype(jnp.float32), last)
                    return (new_states, last), None

                (fstates, last), _ = jax.lax.scan(
                    body, (states0, last0), (tokens, jnp.arange(width)))
                first = engine_lib.sample_tokens(rng, last[None], sc)[0]
                new_packed = store.merge(store.encode(fstates), packed,
                                         onehot)
                return first, new_packed

            fn = jax.jit(state_prefill, donate_argnums=(4,))
        self._sprefill_fns[width] = fn
        return key, fn

    def _build_verify(self):
        """Speculative verify: ONE device dispatch scores q_len =
        draft_len + 1 tokens per slot (the pending token plus a padded
        draft) through `verify_step_paged`, derives the greedy target at
        every position, and computes the accepted-run length on device
        (`speculate.accepted_counts`). The host then commits each slot's
        accepted tokens and rolls the rejected suffix back with
        `pages.pop_tokens` — the draft -> verify -> accept/rollback loop.

        q_len is STATIC: short (or empty) drafts are padded and masked via
        `n_fed`, so a verify dispatch compiles one trace per live
        page-table width bucket (the same pow-2 bucketing plain bursts
        use) and never a fresh jit variant per acceptance count — asserted
        in the run loop before dispatch.
        """
        cfg, backend = self.cfg, self.backend
        eos = self.sched.eos_id
        shard = self._shard

        def verify(params, pool_k, pool_v, page_table, lengths, active, owned,
                   fed, n_fed):
            cache = pages_lib.PagedKVCache(pool_k, pool_v, page_table,
                                           lengths)
            logits, new_cache = decoding.verify_step_paged(
                params, cfg, cache, fed, active, n_fed, backend=backend,
                write_mask=owned, shard=shard)
            # greedy targets: bitwise the tokens sample_tokens(T=0) emits
            targets = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            emit = speculate_lib.accepted_counts(targets, fed, n_fed, eos)
            emit = jnp.where(active, jnp.minimum(emit, n_fed), 0)
            return new_cache.k, new_cache.v, targets, emit

        return self._mesh_jit(verify, n_in=9, pool_in={1, 2}, n_out=4,
                              pool_out={0, 1}, donate=(1, 2))

    def _build_spec(self):
        """Fused speculative burst: up to `k_rounds` (<= max_burst)
        draft -> verify -> accept rounds in ONE device dispatch — a jitted
        while_loop whose body drafts on device from the resident token
        stream (`speculate.propose_draft_device`), verifies through
        `verify_step_paged`, computes acceptance (`accepted_counts`), and
        commits on device: accepted tokens are appended to the stream, the
        frontier advances by the accepted count, and the next round's
        optimistic appends overwrite the rejected suffix in place (rejected
        codes past the frontier are dead bytes — no pop dispatch needed).
        The host reads tokens/counters back ONCE per burst instead of once
        per round, which is what turns speculation's step savings into
        wall-clock: O(1) host syncs per burst, not O(rounds).

        Adaptive rounds: a slot whose verify rejected its ENTIRE draft
        stops drafting for the rest of the burst (drafts there are pure
        verify-row cost), and a round in which no slot drafts runs the
        plain single-token decode step via `lax.cond` instead of a padded
        q_len-row verify — emitting bitwise the same token (verify row 0
        is exactly the decode accumulation) at a fraction of the kernel
        cost. Divergent-output requests therefore degrade to plain-decode
        cost instead of paying the verify multiplier for nothing.

        Token parity: each round's math is exactly `_spec_step`'s (same
        drafts — pinned token-for-token, same verify kernel, same
        acceptance rule), and rounds are sequential in both, so greedy
        tokens are bitwise the host loop's (tests/test_speculate.py pins
        device-vs-host burst equality end to end).
        """
        cfg, backend = self.cfg, self.backend
        s = self.sched.num_slots
        dl = self.sched.draft_len
        q_len = dl + 1
        max_ng = self.sched.draft_max_ngram
        max_burst = self.sched.max_burst
        eos = self.sched.eos_id
        out_w = max_burst * q_len
        c_tok = self.ctx_buf.shape[1]
        rows = jnp.arange(s)
        shard = self._shard

        def spec_burst(params, pool_k, pool_v, page_table, lengths, active,
                       owned, ctx, ctx_len, remaining, k_rounds):
            out0 = jnp.full((s, out_w), -1, jnp.int32)
            zeros = jnp.zeros((s,), jnp.int32)

            def cond(c):
                return (c[0] < k_rounds) & jnp.any(c[4])

            def body(c):
                (step, pk, pv, lens, act, dok, ctx_b, clen, emitted, out,
                 n_prop, n_acc, n_steps) = c
                # draft cap mirrors the host's remaining-1 budget clamp:
                # even a fully accepted run cannot overshoot the budget
                # (or the admission page reservation)
                cap = remaining - emitted - 1
                draft, nd = speculate_lib.propose_draft_device(
                    ctx_b, clen, dl, max_ng, cap)
                # adaptive throttle: a slot whose last verify rejected its
                # whole draft stops drafting for the rest of the burst
                # (re-enabled at the next burst boundary) — its proposals
                # were costing verify rows and yielding nothing
                nd = jnp.where(dok, nd, 0)
                pending = jnp.take_along_axis(
                    ctx_b, jnp.clip(clen - 1, 0)[:, None], axis=1)[:, 0]
                cache = pages_lib.PagedKVCache(pk, pv, page_table, lens)

                def verify_round(_):
                    fed = jnp.concatenate([pending[:, None], draft],
                                          axis=1)
                    n_fed = jnp.where(act, 1 + nd, 1)
                    logits, new_cache = decoding.verify_step_paged(
                        params, cfg, cache, fed, act, n_fed,
                        backend=backend, write_mask=owned, shard=shard)
                    targets = jnp.argmax(logits,
                                         axis=-1).astype(jnp.int32)
                    emit = speculate_lib.accepted_counts(targets, fed,
                                                         n_fed, eos)
                    emit = jnp.where(act, jnp.minimum(emit, n_fed), 0)
                    return (new_cache.k, new_cache.v, targets, emit,
                            n_fed)

                def decode_round(_):
                    # nobody drafted: a verify over q_len padded rows
                    # would emit exactly one token per slot at q_len times
                    # the kernel rows — run the plain single-token step
                    # instead (bitwise the same emitted token: verify row
                    # 0 IS the decode accumulation)
                    logits, new_cache = decoding.decode_step_paged(
                        params, cfg, cache, pending[:, None], act,
                        backend=backend, write_mask=owned, shard=shard)
                    t1 = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                    targets = jnp.zeros((s, q_len),
                                        jnp.int32).at[:, 0].set(t1)
                    return (new_cache.k, new_cache.v, targets,
                            jnp.where(act, 1, 0).astype(jnp.int32),
                            jnp.ones((s,), jnp.int32))

                pk2, pv2, targets, emit, n_fed = jax.lax.cond(
                    jnp.any(act & (nd > 0)), verify_round, decode_round,
                    operand=None)
                # throttle slots that proposed and accepted nothing
                dok = dok & ~(act & (n_fed > 1) & (emit <= 1))
                # on-device commit: accepted targets go to the output
                # buffer (at each slot's running offset) and the token
                # stream — ONE scatter each (every (slot, j) pair hits a
                # distinct position); invalid positions index out of
                # bounds and drop
                jj = jnp.arange(q_len, dtype=jnp.int32)[None, :]
                valid = act[:, None] & (jj < emit[:, None])
                rows2 = jnp.broadcast_to(rows[:, None], (s, q_len))
                out = out.at[
                    rows2, jnp.where(valid, emitted[:, None] + jj,
                                     out_w)].set(targets, mode="drop")
                ctx_b = ctx_b.at[
                    rows2, jnp.where(valid, clen[:, None] + jj,
                                     c_tok)].set(targets, mode="drop")
                last = jnp.take_along_axis(
                    targets, jnp.clip(emit - 1, 0)[:, None], axis=1)[:, 0]
                emitted = emitted + emit
                clen = clen + emit
                lens = lens + emit  # commit: frontier over accepted run
                n_prop = n_prop + jnp.where(act, n_fed - 1, 0)
                n_acc = n_acc + jnp.where(act, emit - 1, 0)
                n_steps = n_steps + act.astype(jnp.int32)
                done = emitted >= remaining
                if eos is not None:
                    done = done | (act & (last == eos))
                return (step + 1, pk2, pv2, lens,
                        act & ~done, dok, ctx_b, clen, emitted, out,
                        n_prop, n_acc, n_steps)

            init = (jnp.asarray(0, jnp.int32), pool_k, pool_v, lengths,
                    active, active, ctx, ctx_len, zeros, out0, zeros,
                    zeros, zeros)
            fin = jax.lax.while_loop(cond, body, init)
            # pool_k, pool_v, emitted, out, n_prop, n_acc, n_steps
            return (fin[1], fin[2], fin[8], fin[9], fin[10], fin[11],
                    fin[12])

        return self._mesh_jit(spec_burst, n_in=11, pool_in={1, 2}, n_out=7,
                              pool_out={0, 1}, donate=(1, 2))

    def warmup(self, skips=(0,)) -> dict:
        """AOT-compile every enumerable dispatch variant up front — see
        `serving/compile_cache.py`. After warmup, any NEW variant the run
        loop compiles is a bucketing regression, counted in
        stats["perf"]["post_warmup_variants"] (CI pins it at zero)."""
        from repro.serving import compile_cache
        return compile_cache.warmup(self, skips=skips)

    def executable(self, key: tuple):
        """The AOT-compiled executable `warmup` installed for a dispatch
        key such as ("decode", table_width) — for inspecting the compiled
        program (`.as_text()`, `.memory_analysis()`). KeyError when the key
        was not warmed (mesh engines warm their lazy jit cache instead)."""
        return self._exec[key]

    def _dispatch(self, key: tuple, fn, *args, tid: int = 0):
        """Route a device dispatch through the jit-variant table.

        `key` names the compiled variant ((kind, *static bucket values));
        its kind names the `dispatch` span (on slot lane `tid`).
        AOT-warmed executables (`serving/compile_cache.py`) are preferred
        over the lazy jit path, first-seen keys are counted into
        stats["perf"]["jit_variants_compiled"], and keys first seen after
        warmup increment `post_warmup_variants` — the counter the
        perf-smoke CI job asserts stays zero.
        """
        self._last_dispatch_key = key  # watchdog diagnostic breadcrumb
        with self._tracer.region("dispatch", tid, kind=key[0]):
            args = self._on_mesh(args)
            if key not in self._compiled_keys:
                self._compiled_keys.add(key)
                self._perf["jit_variants_compiled"] += 1
                if self._warmed:
                    self._perf["post_warmup_variants"] += 1
            ex = self._exec.get(key)
            return fn(*args) if ex is None else ex(*args)

    def _split(self, rng: jax.Array) -> tuple[jax.Array, jax.Array]:
        """(rng, sub): one split of the engine's key, a device dispatch of
        its own."""
        with self._tracer.region("dispatch", kind="rng_split"):
            rng, sub = jax.random.split(rng)
        return rng, sub

    def _readback(self, *arrays, tid: int = 0, **args) -> list:
        """Copy a dispatch's outputs to the host: where the host waits for
        the device, and the one host sync a dispatch costs (counted
        here)."""
        with self._tracer.region("readback", tid, **args) as r:
            out = [np.asarray(a) for a in arrays]
        self._m["host_syncs"].inc()
        self._wait_s += r.elapsed
        return out

    def _on_mesh(self, args):
        """With a mesh, commit every array argument that is not already
        on it to replication: host (numpy) step inputs go straight from
        the host, so none is parked on device 0, and so do device values
        such as rng keys and zero-width prefix buffers. Pools and params
        keep their own committed shardings. Identity without a mesh."""
        if self._replicated is None:
            return args
        mesh = self._replicated.mesh

        def commit(a):
            sh = getattr(a, "sharding", None)
            if isinstance(sh, jax.sharding.NamedSharding) and sh.mesh == mesh:
                return a
            return jax.device_put(a, self._replicated)

        return jax.tree.map(commit, args)

    def _live_table_width(self, k: int) -> int:
        """Page-table columns a k-step burst can touch, bucketed to the next
        power of two (so at most O(log max_pages) decode variants compile)."""
        ps = self.sched.page_size
        longest = int(self.lengths[self.active].max()) + k
        need = max(1, pages_lib.pages_for_tokens(longest, ps))
        mp = 1
        while mp < need:
            mp *= 2
        return min(mp, self.sched.max_pages)

    def _owned_write_mask(self, k) -> np.ndarray:
        """(num_slots,) append guard for a burst/verify dispatch writing
        up to k tokens per slot (int, or a (num_slots,) vector — the
        speculative path passes each slot's real fed count, since padded
        verify positions never write): True iff every page the slot's
        appends could touch is owned exclusively (refcount == 1).

        Shared prefix pages always cover whole prompt blocks and appends
        start at the prompt frontier, so in correct operation every active
        slot passes; a failure means refcount bookkeeping broke, and
        rather than let the device silently write a page the trie (or
        another request) is reading, the scheduler raises — the device
        mask exists so *other* callers of `decode_step_paged` get the
        trash-redirect containment instead of corruption.
        """
        mask = np.ones((self.sched.num_slots,), bool)
        if self.trie is None:
            return mask  # nothing ever calls share: every page rc == 1
        ps = self.sched.page_size
        k = np.broadcast_to(np.asarray(k), (self.sched.num_slots,))
        for i in range(self.sched.num_slots):
            if not self.active[i]:
                continue
            lo = int(self.lengths[i]) // ps
            hi = (int(self.lengths[i]) + int(k[i]) - 1) // ps
            for j in range(lo, min(hi, self.sched.max_pages - 1) + 1):
                page = int(self.page_table[i, j])
                if page == 0 or self.allocator.refcount(page) != 1:
                    mask[i] = False
                    break
        if not mask[self.active].all():
            bad = [i for i in range(self.sched.num_slots)
                   if self.active[i] and not mask[i]]
            raise RuntimeError(
                f"copy-on-write violation: slots {bad} would append into "
                f"a page they do not own exclusively")
        return mask

    # ------------------------------------------------------------ speculate --
    def _spec_step(self, remaining: np.ndarray, results: list) -> None:
        """One draft -> verify -> accept/rollback round over every active
        slot (serving/speculate.py is the subsystem overview).

        Host side: self-draft up to `draft_len` tokens per slot from its
        own prompt+generated stream (capped at remaining-1 so even a fully
        accepted run cannot overshoot the budget or the page reservation).
        Device side: ONE verify dispatch appends the fed tokens' K/V
        optimistically, scores every position, and returns the greedy
        targets plus each slot's accepted-run length. Host again:
        commit the accepted tokens, pop the rejected suffix
        (`pages.pop_tokens` — validated bookkeeping; pages stay reserved
        for the slot's span unless the request just finished, in which
        case wholly-speculative tail pages are freed through the pop path
        before eviction releases the rest).
        """
        s = self.sched.num_slots
        ps = self.sched.page_size
        q_len = self.sched.draft_len + 1
        with self._tracer.region("spec-round", tick=self._tick,
                                 rounds=1) as span:
            fed = np.zeros((s, q_len), np.int32)
            n_fed = np.ones((s,), np.int32)
            for i in range(s):
                if not self.active[i]:
                    continue
                st = self.slots[i]
                ctx = np.concatenate([st.req.tokens,
                                      np.asarray(st.generated, np.int32)])
                draft = speculate_lib.propose_draft(
                    ctx, min(self.sched.draft_len, int(remaining[i]) - 1),
                    self.sched.draft_max_ngram, tracer=self._tracer)
                m = 1 + len(draft)
                fed[i, 0] = self.next_tok[i]
                fed[i, 1:m] = draft
                n_fed[i] = m
                st.draft_proposed += m - 1
                st.verify_steps += 1
                self._m["draft_proposed"].inc(m - 1)
                self._m["verify_steps"].inc()
            # jit-variant discipline (see kernels/qattn: verify_rows): the
            # dispatch shape is the STATIC q_len — acceptance counts and
            # short drafts ride in n_fed — and the page table is sliced to
            # the same pow-2 live-width buckets plain bursts use, so verify
            # compiles O(log max_pages) variants total, never one per
            # acceptance count.
            assert fed.shape == (s, q_len)
            mp = self._live_table_width(q_len)
            assert mp & (mp - 1) == 0 or mp == self.sched.max_pages
            owned = self._owned_write_mask(n_fed)
            pk, pv, targets, emit = self._dispatch(
                ("verify", mp), self._verify_fn,
                self.params, self.pool.k, self.pool.v,
                np.asarray(self.page_table[:, :mp]),
                np.asarray(self.lengths), np.asarray(self.active),
                np.asarray(owned), np.asarray(fed), np.asarray(n_fed))
            self.pool = self.pool._replace(k=pk, v=pv)
            targets, emit = self._readback(targets, emit)
            t_now = time.perf_counter() - self._t0
            with self._tracer.region("emit"):
                self._commit_verify(targets, emit, n_fed, results, t_now)
            span.set(proposed=int(n_fed.sum() - s),
                     accepted=int(emit.sum()))

    def _commit_verify(self, targets: np.ndarray, emit: np.ndarray,
                       n_fed: np.ndarray, results: list,
                       t_now: float) -> None:
        """Host commit of one verify round: each slot's accepted tokens go
        to its stream and the rejected suffix is popped."""
        s = self.sched.num_slots
        ps = self.sched.page_size
        # mid-verify cancellation window: cancels injected between the
        # verify dispatch and this host commit land HERE — the cancelled
        # slot's speculative tail is popped through the validated
        # pop_tokens path and its pages free in the same tick
        if self._faults is not None:
            for rid in self._faults.mid_burst_cancels():
                self.cancel(rid)
        for i in range(s):
            if not self.active[i] or emit[i] == 0:
                continue
            st = self.slots[i]
            e, m = int(emit[i]), int(n_fed[i])
            st.generated.extend(int(t) for t in targets[i, :e])
            st.draft_accepted += e - 1
            st.host_syncs += 1
            self._m["draft_accepted"].inc(e - 1)
            if self.on_tokens is not None:
                self.on_tokens(st.req.rid,
                               [int(t) for t in targets[i, :e]])
            cl = int(self.ctx_len[i])
            self.ctx_buf[i, cl:cl + e] = targets[i, :e]
            self.ctx_len[i] = cl + e
            self.next_tok[i] = int(targets[i, e - 1])
            finished = self._finished(st)
            cancelled = (not finished) and st.req.rid in self._cancel_req
            # transactional commit: the verify appended m tokens' K/V
            # optimistically; commit the accepted e, pop the rejected
            # suffix. Pages stay reserved mid-flight (freeing them would
            # re-introduce mid-flight OOM against the admission
            # reservation); a finishing (or mid-verify-cancelled) request
            # frees its emptied speculative tail through the validated
            # pop path instead.
            new_len, _ = pages_lib.pop_tokens(
                self.allocator, st.req.rid, self.page_table[i],
                int(self.lengths[i]) + m, m - e, ps,
                min_length=len(st.req.tokens),
                free_empty=finished or cancelled)
            self.lengths[i] = new_len
            if finished:
                self._evict(i, results, t_now)
            elif cancelled:
                self._evict(i, results, t_now, status="cancelled")

    def _spec_burst(self, remaining: np.ndarray, results: list,
                    queued: bool = False) -> int:
        """Up to max_burst fused draft->verify->accept rounds in ONE
        dispatch (`_build_spec`), host readback only at the burst boundary.
        Returns the number of sequential rounds the device executed.

        Page bookkeeping: the device commits by advancing each slot's
        frontier; rejected codes past it are dead bytes the next round's
        appends overwrite, so no per-round `pop_tokens` dispatch is needed
        — page references are reconciled wholesale at eviction. The
        admission reservation covers every position a burst can touch
        (appends stay < lengths + remaining by the on-device draft cap).
        """
        s = self.sched.num_slots
        q_len = self.sched.draft_len + 1
        with self._tracer.region("spec-round", tick=self._tick) as span:
            rem_act = remaining[self.active]
            rem_max = int(rem_act.max())
            mp = self._live_table_width(rem_max + q_len)
            owned = self._owned_write_mask(remaining)
            if queued:
                # requests are waiting for a slot: burst only as far as the
                # fastest any active slot can finish (a round emits at most
                # q_len tokens, so that is ceil(remaining / q_len) rounds) —
                # past that, a fully-accepting slot would sit frozen in-burst
                # while the queue waits at the host. The floor of 4 keeps the
                # per-dispatch launch overhead amortized over >= 4 rounds.
                k_rounds = max(4, min(self.sched.max_burst,
                                      int((-(-rem_act // q_len)).min())))
            else:
                # empty queue: a freed slot has nothing to take anyway, and
                # the device loop exits early once every slot is done — so
                # burst long and amortize the dispatch launch cost
                k_rounds = min(self.sched.max_burst, rem_max)
            pk, pv, emitted, out, n_prop, n_acc, n_steps = self._dispatch(
                ("spec", mp), self._spec_fn,
                self.params, self.pool.k, self.pool.v,
                np.asarray(self.page_table[:, :mp]), np.asarray(self.lengths),
                np.asarray(self.active), np.asarray(owned),
                np.asarray(self.ctx_buf), np.asarray(self.ctx_len),
                np.asarray(remaining), np.asarray(k_rounds, jnp.int32))
            self.pool = self.pool._replace(k=pk, v=pv)
            emitted, out, n_prop, n_acc, n_steps = self._readback(
                emitted, out, n_prop, n_acc, n_steps)
            t_now = time.perf_counter() - self._t0
            with self._tracer.region("emit"):
                for i in range(s):
                    if not self.active[i] or emitted[i] == 0:
                        continue
                    st = self.slots[i]
                    n = int(emitted[i])
                    toks = out[i, :n]
                    st.generated.extend(int(t) for t in toks)
                    st.draft_proposed += int(n_prop[i])
                    st.draft_accepted += int(n_acc[i])
                    st.verify_steps += int(n_steps[i])
                    st.host_syncs += 1
                    self._m["draft_proposed"].inc(int(n_prop[i]))
                    self._m["draft_accepted"].inc(int(n_acc[i]))
                    self._m["verify_steps"].inc(int(n_steps[i]))
                    if self.on_tokens is not None:
                        self.on_tokens(st.req.rid, [int(t) for t in toks])
                    self.next_tok[i] = int(toks[-1])
                    self.lengths[i] += n
                    cl = int(self.ctx_len[i])
                    self.ctx_buf[i, cl:cl + n] = toks
                    self.ctx_len[i] = cl + n
                    if self._finished(st):
                        self._evict(i, results, t_now)
                # mid-verify cancellation window: cancels injected while
                # the fused burst ran on device land here. No pop dispatch
                # is needed — the device committed only accepted tokens;
                # eviction reconciles the page references wholesale, same
                # tick.
                if self._faults is not None:
                    for rid in self._faults.mid_burst_cancels():
                        self.cancel(rid)
                if self._cancel_req:
                    for i in range(s):
                        if (self.active[i]
                                and self.slots[i].req.rid in self._cancel_req):
                            self._evict(i, results, t_now, status="cancelled")
            rounds = int(n_steps.max(initial=0))
            span.set(rounds=rounds, width=mp, proposed=int(n_prop.sum()),
                     accepted=int(n_acc.sum()), emitted=int(emitted.sum()))
        return rounds

    def _prefill_fn(self, width: int, skip: int):
        """Chunked prefill for a `width`-token suffix after a `skip`-token
        shared prefix — ONE device dispatch per admission.

        An outer lax.scan walks the suffix's chunks: chunk c embeds tokens
        [skip+cC, skip+cC+C), appends its raw K/V into a carried
        (L, 1, skip+width, n_kv, h) buffer, and attends causally over the
        buffer with q_offset = skip + cC — token t sees exactly keys
        [0, t], the same set as full-width prefill — while the chunk's
        quantized codes scatter into its pool pages in-jit. The request's
        first token is sampled in-jit from the last valid position. One
        compile per (suffix bucket, skip) pair.

        Prefix modes ("cold"/"share") add one twist: after a chunk's codes
        are written, its buffer slice is overwritten with the *decoded*
        codes, so every cross-chunk attention reads the requantized K/V — a
        deterministic function of the codes alone. A later request that
        maps the same pages (bit-identical codes) and prefills only its
        suffix therefore reproduces the cold run's suffix computation
        bit-for-bit: that is the whole parity story of the prefix cache.
        Within-chunk attention still reads the raw K/V in both runs (chunk
        boundaries are deterministic, so the two paths agree on that too).
        Mode "off" keeps the raw buffer everywhere, which is what makes the
        scheduler bitwise-match the *static* engine instead.
        """
        key = (width, skip)
        if key in self._prefill_fns:
            return self._prefill_fns[key]
        cfg, qz = self.cfg, self.backend.quantizer
        chunk = self.sched.prefill_chunk
        ps = self.sched.page_size
        sc = self.sched.sampling
        requant = self.sched.prefix_cache != "off"
        n_chunks = width // chunk
        nk, nv = transformer._layer_bins(qz, cfg.num_layers)
        shard = self._shard

        def one_chunk(params, tokens_c, chunk_idx, buf_k, buf_v):
            x = transformer.embed_inputs(params, cfg, {"tokens": tokens_c})
            offset = skip + chunk_idx * chunk
            positions = offset + jnp.arange(chunk)[None, :]

            def body(carry, xs):
                layer_params, bk, bv, lnk, lnv = xs
                with jax.named_scope("qkv"):
                    q, k, v = attention.project_qkv(
                        layer_params["attn"],
                        common.rms_norm(carry, layer_params["norm1"],
                                        cfg.norm_eps),
                        positions, cfg)
                with jax.named_scope("attend"):
                    bk = jax.lax.dynamic_update_slice_in_dim(
                        bk, k.astype(bk.dtype), offset, axis=1)
                    bv = jax.lax.dynamic_update_slice_in_dim(
                        bv, v.astype(bv.dtype), offset, axis=1)
                    out = attention.blockwise_attention(
                        q, bk, bv, causal=True, q_offset=offset)
                    out = out.reshape(1, chunk,
                                      cfg.num_heads * cfg.head_dim)
                with jax.named_scope("mlp"):
                    h = jnp.einsum("bsk,kd->bsd", out,
                                   layer_params["attn"]["wo"])
                    xx = transformer.ffn_residual(
                        layer_params, common.radd(carry, h), cfg)
                with jax.named_scope("kv_append"):
                    ck = qz.encode(k, lnk, qz.config.k_norm)
                    cv = qz.encode(v, lnv, qz.config.v_norm)
                    if requant:
                        # cross-chunk attention must see decode(codes),
                        # the same bits a prefix-sharing run reconstructs
                        # from the pool — overwrite AFTER this chunk's own
                        # attention
                        bk = jax.lax.dynamic_update_slice_in_dim(
                            bk, qz.decode(ck, lnk, qz.config.k_norm
                                          ).astype(bk.dtype), offset,
                            axis=1)
                        bv = jax.lax.dynamic_update_slice_in_dim(
                            bv, qz.decode(cv, lnv, qz.config.v_norm
                                          ).astype(bv.dtype), offset,
                            axis=1)
                return xx, (bk, bv, ck, cv)

            x, (nbk, nbv, ck, cv) = common.uscan(
                body, x, (params["layers"], buf_k, buf_v, nk, nv))
            return x, nbk, nbv, ck, cv

        def prefill(params, tokens, page_groups, last_chunk, last_off,
                    prefix_k, prefix_v, rng, pool_k, pool_v):
            # tokens (n_chunks, C) suffix; page_groups (n_chunks, C/ps)
            # SUFFIX page ids (trash page for pow-2 padding chunks);
            # last_chunk: index of the last REAL chunk (the bucket may pad
            # past it); prefix_k/v (L, 1, skip, n_kv, h) decoded
            # shared-prefix K/V (zero-width when skip == 0)
            dt = jnp.dtype(cfg.compute_dtype)
            sfx_shape = (cfg.num_layers, 1, width, cfg.num_kv_heads,
                         cfg.head_dim)
            buf0 = (
                jnp.concatenate([prefix_k.astype(dt),
                                 jnp.zeros(sfx_shape, dt)], axis=2),
                jnp.concatenate([prefix_v.astype(dt),
                                 jnp.zeros(sfx_shape, dt)], axis=2),
            )

            def chunk_body(carry, xs):
                (bk, bv), (pk, pv) = carry[:2], carry[2:]
                tok_c, cidx, ids = xs
                x, bk, bv, ck, cv = one_chunk(params, tok_c[None], cidx,
                                              bk, bv)
                with jax.named_scope("kv_append"):
                    ck = jax.tree.map(lambda a: a[:, 0], ck)  # batch=1
                    cv = jax.tree.map(lambda a: a[:, 0], cv)
                    if shard is not None:
                        # prefill compute is replicated; only the pool
                        # write is sharded — each device scatters its own
                        # kv-head slice of the chunk codes ((L, C, n_kv,
                        # X), head axis 2) into its pool shard
                        nkv_l = cfg.num_kv_heads // shard.size
                        sidx = jax.lax.axis_index(shard.axis)
                        cut = lambda a: jax.lax.dynamic_slice_in_dim(
                            a, sidx * nkv_l, nkv_l, axis=2)
                        ck = jax.tree.map(cut, ck)
                        cv = jax.tree.map(cut, cv)
                    pk = pages_lib.write_prompt_pages(pk, ck, ids, ps)
                    pv = pages_lib.write_prompt_pages(pv, cv, ids, ps)
                return (bk, bv, pk, pv), x

            (_, _, pool_k, pool_v), xs = jax.lax.scan(
                chunk_body, (*buf0, pool_k, pool_v),
                (tokens, jnp.arange(n_chunks, dtype=jnp.int32),
                 page_groups))
            # sample the first token in-jit from the last valid position —
            # inside the last REAL chunk (pow-2 buckets may pad chunks past
            # it; those compute garbage into the trash page only)
            x_final = jax.lax.dynamic_index_in_dim(
                xs, last_chunk, axis=0, keepdims=False)  # (1, C, D)
            x_last = jax.lax.dynamic_slice_in_dim(x_final, last_off, 1,
                                                  axis=1)
            logits = transformer.lm_logits(params, cfg, x_last)[:, 0]
            tok = engine_lib.sample_tokens(rng, logits, sc)
            return tok, pool_k, pool_v

        fn = self._mesh_jit(prefill, n_in=10, pool_in={8, 9}, n_out=3,
                            pool_out={1, 2}, donate=(8, 9))
        self._prefill_fns[key] = fn
        return fn

    def _prefix_load_fn(self, n_pages: int):
        """jit'd (page_ids, pool_k, pool_v) -> decoded (L, 1, n*ps, n_kv, h)
        K/V of a shared prefix, for the suffix prefill's carried buffer.

        This is the only prefix cost a sharing request pays: an O(S·d)
        gather + dequant instead of the O(S·d²) transformer forward the
        cold path runs. Decoding here and decoding inside the cold path's
        requant overwrite see bit-identical codes (pool scatter is
        lossless), which is what makes shared and cold runs emit identical
        tokens. One compile per prefix page count.
        """
        if n_pages in self._prefix_load_fns:
            return self._prefix_load_fns[n_pages]
        cfg, qz = self.cfg, self.backend.quantizer
        nk, nv = transformer._layer_bins(qz, cfg.num_layers)
        dt = jnp.dtype(cfg.compute_dtype)
        shard = self._shard

        def prefix_load(page_ids, pool_k, pool_v):
            def take(pool_a):  # (L, P, n_kv, ps, X) -> (L, 1, n*ps, ...)
                return pages_lib.pages_to_tokens(pool_a[:, page_ids],
                                                 axis=1)[:, None]

            kq = jax.tree.map(take, pool_k)
            vq = jax.tree.map(take, pool_v)

            def body(carry, xs):
                kq_l, vq_l, lnk, lnv = xs
                bk = qz.decode(kq_l, lnk, qz.config.k_norm).astype(dt)
                bv = qz.decode(vq_l, lnv, qz.config.v_norm).astype(dt)
                return carry, (bk, bv)

            _, (bk, bv) = jax.lax.scan(body, 0, (kq, vq, nk, nv))
            if shard is not None:
                # decode is per-head (reductions stay inside head_dim), so
                # gathering the per-shard decodes along the head axis is
                # bitwise the unsharded decode of the full pool
                bk = jax.lax.all_gather(bk, shard.axis, axis=3, tiled=True)
                bv = jax.lax.all_gather(bv, shard.axis, axis=3, tiled=True)
            return bk, bv

        fn = self._mesh_jit(prefix_load, n_in=3, pool_in={1, 2}, n_out=2,
                            pool_out=set(), donate=())
        self._prefix_load_fns[n_pages] = fn
        return fn

    # ------------------------------------------------------------ admission --
    def _bucket_width(self, n_tokens: int) -> int:
        """Pow-2 prefill-variant bucket for an `n_tokens` suffix: the chunk
        count rounded up to the next power of two, clamped to the engine's
        token capacity (never below the real chunk count). Compute-only
        padding — padded chunks scatter to the trash page and reserve no
        pool pages — so O(log max_context) prefill variants compile in
        total, enumerable up front by `serving/compile_cache.py`, instead
        of one per distinct prompt chunk count."""
        chunk = self.sched.prefill_chunk
        cap_chunks = max(1, (self.sched.max_pages * self.sched.page_size)
                         // chunk)
        nc = max(1, -(-n_tokens // chunk))
        b = 1
        while b < nc:
            b *= 2
        return min(b, max(cap_chunks, nc)) * chunk

    def _pages_needed(self, req: Request) -> tuple[int, int]:
        """(exact chunked prompt width, worst-case pages for the whole
        span) — the reservation a cold admission makes (a prefix hit
        shrinks the fresh allocation by the shared pages at admission
        time). The reservation uses the EXACT chunk count — the pow-2
        prefill-variant padding (`_bucket_width`) writes only to the trash
        page, so it never inflates a request's page footprint."""
        chunk = self.sched.prefill_chunk
        width = -(-len(req.tokens) // chunk) * chunk  # exact chunked prompt
        if self.family.state_slots:
            # state families prefill token-by-token (`_sprefill_fn`), so
            # no chunk padding ever lands in real pages; pure-recurrent
            # families (xlstm) hold no pages at all
            if not self.family.paged_kv:
                return width, 0
            span = len(req.tokens) + req.max_new_tokens
            return width, pages_lib.pages_for_tokens(
                span, self.sched.page_size)
        span = max(width, len(req.tokens) + req.max_new_tokens)
        return width, pages_lib.pages_for_tokens(span, self.sched.page_size)

    def _match_prefix(self, req: Request) -> tuple[np.ndarray, int]:
        """Trie walk for admission: (shared page ids, tokens skipped).

        The raw hit is capped to whole prefill chunks and to one chunk
        short of the full prompt (`prefix.usable_prefix_tokens`); pages
        beyond the cap stay in the trie but are not mapped."""
        if self.trie is None:
            return np.zeros((0,), np.int32), 0
        hit = self.trie.match(req.tokens)
        skip = prefix_lib.usable_prefix_tokens(
            len(hit) * self.sched.page_size, len(req.tokens),
            self.sched.prefill_chunk)
        return hit[:skip // self.sched.page_size], skip

    def _admit(self, req: Request, slot: int, shared_ids: np.ndarray,
               fresh_ids: np.ndarray, skip: int, rng: jax.Array,
               t_admit: float) -> None:
        """Prefill the request's uncovered suffix and activate its slot.

        `shared_ids` are the prefix pages mapped from the trie (already
        refcounted to this request, covering tokens [0, skip)); `fresh_ids`
        are exclusively-owned pages for the suffix + generation span. The
        suffix prefill writes ONLY into fresh pages — a request never
        scatters into a page it does not own exclusively.
        """
        if self.family.state_slots:
            # recurrent state has no chunked-prefill shortcut: the
            # prompt is scanned token-by-token into the slot's state
            # (and, for hybrids, its pages) in one dispatch
            self._admit_state(req, slot, fresh_ids, rng, t_admit)
            return
        chunk = self.sched.prefill_chunk
        ps = self.sched.page_size
        plen = len(req.tokens)
        width = self._bucket_width(plen - skip)  # pow-2 variant bucket
        n_chunks = width // chunk
        n_real = -(-(plen - skip) // chunk)  # chunks that hold real tokens
        pad = np.zeros((width,), np.int32)
        pad[:plen - skip] = req.tokens[skip:]
        pages_per_chunk = chunk // ps
        last_chunk = n_real - 1
        last_off = (plen - skip - 1) - last_chunk * chunk
        # padded chunks (>= n_real) scatter their codes to the trash page
        # (physical page 0) — compute-only padding, zero pool footprint
        groups = np.zeros((n_chunks, pages_per_chunk), np.int32)
        groups[:n_real] = fresh_ids[:n_real * pages_per_chunk].reshape(
            n_real, pages_per_chunk)
        tid = slot + 1
        if skip:
            with self._tracer.region("prefix-load", tid, rid=req.rid,
                                     pages=skip // ps):
                pfx_k, pfx_v = self._dispatch(
                    ("prefix_load", skip // ps),
                    self._prefix_load_fn(skip // ps),
                    np.asarray(shared_ids), self.pool.k, self.pool.v,
                    tid=tid)
        with self._tracer.region("prefill-chunk", tid, rid=req.rid,
                                 tick=self._tick, chunks=n_chunks,
                                 width=width, skip=skip):
            if not skip:
                empty = (self.cfg.num_layers, 1, 0, self.cfg.num_kv_heads,
                         self.cfg.head_dim)
                with self._tracer.region("dispatch", tid,
                                         kind="empty_prefix"):
                    pfx_k = pfx_v = jnp.zeros(empty, self.cfg.compute_dtype)
            tok, pk, pv = self._dispatch(
                ("prefill", width, skip), self._prefill_fn(width, skip),
                self.params, np.asarray(pad.reshape(n_chunks, chunk)),
                np.asarray(groups), np.asarray(last_chunk, jnp.int32),
                np.asarray(last_off, jnp.int32), pfx_k, pfx_v, rng,
                self.pool.k, self.pool.v, tid=tid)
            self.pool = self.pool._replace(k=pk, v=pv)
            self._m["prefill_chunks"].inc(n_chunks)
            self._m["prefill_tokens"].inc(width)
            (tok,) = self._readback(tok, tid=tid, rid=req.rid)  # 1st token
            first = int(tok[0])
        page_ids = np.concatenate([shared_ids, fresh_ids]).astype(np.int32)
        row = np.zeros((self.sched.max_pages,), np.int32)
        row[:len(page_ids)] = page_ids
        self.page_table[slot] = row
        self.lengths[slot] = plen
        self.active[slot] = True
        self.next_tok[slot] = first
        # device-resident visible stream for on-device drafting: prompt +
        # every emitted token (the pending token last)
        self.ctx_buf[slot] = 0
        self.ctx_buf[slot, :plen] = req.tokens
        self.ctx_buf[slot, plen] = first
        self.ctx_len[slot] = plen + 1
        self.slots[slot] = _Slot(req, first, t_admit,
                                 time.perf_counter() - self._t0)
        if self.on_tokens is not None:
            self.on_tokens(req.rid, [first])
        if self.trie is not None:
            # register every full prompt block (idempotent along the hit
            # path; the trie takes its own page refs, LRU-bounded)
            self.trie.insert(req.tokens, page_ids)

    def _admit_state(self, req: Request, slot: int, fresh_ids: np.ndarray,
                     rng: jax.Array, t_admit: float) -> None:
        """State-family admission: scan the prompt into the slot's
        recurrent state (and, for hybrids, append its KV into the slot's
        fresh pages) in one `_sprefill_fn` dispatch, claim the state
        slot, and activate. The dispatch samples the first token
        in-device and merges the scanned state into only this slot's
        packed bytes."""
        plen = len(req.tokens)
        width = self._state_width(plen)
        pad = np.zeros((width,), np.int32)
        pad[:plen] = req.tokens
        tid = slot + 1
        with self._tracer.region("state-prefill", tid, rid=req.rid,
                                 tick=self._tick, width=width,
                                 plen=plen) as span:
            key, fn = self._sprefill_fn(width)
            if self.family.paged_kv:
                # pages first: the prefill scan appends through the table
                row = np.zeros((self.sched.max_pages,), np.int32)
                row[:len(fresh_ids)] = fresh_ids.astype(np.int32)
                self.page_table[slot] = row
                self.lengths[slot] = 0
                tok, pk, pv, packed = self._dispatch(
                    key, fn, self.params, np.asarray(pad),
                    np.asarray(slot, jnp.int32), np.asarray(plen, jnp.int32),
                    self.pool.k, self.pool.v, np.asarray(self.page_table),
                    np.asarray(self.lengths), self.states, rng, tid=tid)
                self.pool = self.pool._replace(k=pk, v=pv)
            else:
                tok, packed = self._dispatch(
                    key, fn, self.params, np.asarray(pad),
                    np.asarray(slot, jnp.int32), np.asarray(plen, jnp.int32),
                    self.states, rng, tid=tid)
            self.states = packed
            self._m["prefill_tokens"].inc(width)
            (tok,) = self._readback(tok, tid=tid, rid=req.rid)  # 1st token
            first = int(tok)
        self._m["state_encode_s"].inc(span.elapsed)
        self.state_slots.claim(slot, req.rid)
        self.lengths[slot] = plen if self.family.paged_kv else 0
        self.active[slot] = True
        self.next_tok[slot] = first
        self.ctx_buf[slot] = 0
        self.ctx_buf[slot, :plen] = req.tokens
        self.ctx_buf[slot, plen] = first
        self.ctx_len[slot] = plen + 1
        self.slots[slot] = _Slot(req, first, t_admit,
                                 time.perf_counter() - self._t0)
        if self.on_tokens is not None:
            self.on_tokens(req.rid, [first])

    def _evict(self, slot: int, results: list, t_now: float,
               status: str = "completed") -> None:
        """Retire a finished (or cancelled) request: drop its page
        references — on BOTH allocators; tier-2 frees are a no-op for a
        tier-1 slot — (exclusive pages return to the free list
        immediately; prefix pages survive on the trie's / other sharers'
        refcounts), clear the slot, and record the typed result."""
        st = self.slots[slot]
        self.allocator.free(st.req.rid)
        if self.state_slots is not None:
            self.state_slots.release(st.req.rid)
        self.page_table[slot] = 0
        if self.allocator2 is not None:
            self.allocator2.free(st.req.rid)
            self.page_table2[slot] = 0
        self.tier2[slot] = False
        self.lengths[slot] = 0
        self.active[slot] = False
        self.next_tok[slot] = 0
        self.ctx_buf[slot] = 0
        self.ctx_len[slot] = 0
        self.slots[slot] = None
        self._cancel_req.discard(st.req.rid)
        if status == "cancelled":
            self._m["cancelled"].inc()
            self._tracer.instant("cancel", tid=slot + 1, rid=st.req.rid,
                                 tick=self._tick,
                                 generated=len(st.generated))
        ttft = st.t_first - st.req.arrival
        latency = t_now - st.req.arrival
        tpot = (latency - ttft) / max(len(st.generated) - 1, 1)
        self._m["fin_" + status].inc()
        self._m["new_tokens"].inc(len(st.generated))
        if status == "completed":
            # the latency distributions the stats percentiles summarize —
            # completed requests only, matching those percentiles
            self._m["ttft"].observe(ttft)
            self._m["tpot"].observe(tpot)
            self._m["latency"].observe(latency)
        results.append(RequestResult(
            rid=st.req.rid,
            tokens=np.asarray(st.generated, np.int32),
            prompt_len=len(st.req.tokens),
            ttft_s=ttft,
            latency_s=latency,
            admitted_s=st.t_admit - st.req.arrival,
            draft_proposed=st.draft_proposed,
            draft_accepted=st.draft_accepted,
            verify_steps=st.verify_steps,
            host_sync_count=st.host_syncs,
            status=status,
            priority=st.priority,
            preemptions=st.preemptions,
            restore_retries=st.restore_retries,
            degraded=st.degraded,
            tpot_s=tpot,
            timeline=tuple(st.marks) + (("done", t_now),),
        ))
        if self.on_result is not None:
            self.on_result(results[-1])

    def _finished(self, st: _Slot) -> bool:
        if (self.sched.eos_id is not None
                and st.generated[-1] == self.sched.eos_id):
            return True
        return len(st.generated) >= st.req.max_new_tokens

    # --------------------------------------------- SLO / pressure ladder --
    def cancel(self, request_id: int) -> None:
        """Request cancellation of `request_id` (any state: queued,
        spilled, or live in a slot — including mid-verify with
        speculation on).

        The cancel is recorded and lands at the current tick: a live
        slot's pages free in the SAME scheduler tick (a mid-verify cancel
        pops its speculative tail through the validated `pop_tokens`
        path first), and a typed `RequestResult(status="cancelled")`
        carrying any already-generated tokens is emitted. Unknown /
        already-finished rids are dropped silently at the next tick
        boundary."""
        self._cancel_req.add(int(request_id))

    def _emit_unserved(self, req: Request, results: list, now: float,
                       status: str, sp=None) -> None:
        """Typed result for a request retired OUTSIDE a slot: shed from
        the queue, or cancelled while queued/spilled. `sp` carries a
        spilled request's partial progress into the result."""
        self._tracer.instant("cancel" if status == "cancelled" else status,
                             rid=req.rid, tick=self._tick, queued=sp is None)
        if sp is not None:
            ttft = sp.t_first - req.arrival
            latency = now - req.arrival
            results.append(RequestResult(
                rid=req.rid,
                tokens=np.asarray(sp.generated, np.int32),
                prompt_len=len(req.tokens),
                ttft_s=ttft,
                latency_s=latency,
                admitted_s=sp.t_admit - req.arrival,
                draft_proposed=sp.draft_proposed,
                draft_accepted=sp.draft_accepted,
                verify_steps=sp.verify_steps,
                host_sync_count=sp.host_syncs,
                status=status, priority=sp.priority,
                preemptions=sp.preemptions,
                restore_retries=sp.restore_retries,
                degraded=sp.degraded,
                tpot_s=(latency - ttft) / max(len(sp.generated) - 1, 1),
                timeline=tuple(sp.marks) + (("done", now),)))
            self._m["new_tokens"].inc(len(sp.generated))
        else:
            results.append(RequestResult(
                rid=req.rid,
                tokens=np.zeros((0,), np.int32),
                prompt_len=len(req.tokens),
                ttft_s=0.0,
                latency_s=now - req.arrival,
                admitted_s=now - req.arrival,
                status=status, priority=req.priority,
                timeline=(("arrival", req.arrival), ("done", now))))
        self._m["fin_" + status].inc()
        if self.on_result is not None:
            self.on_result(results[-1])

    def _process_cancels(self, pending: list, results: list,
                         now: float) -> None:
        """Land every recorded cancel at a tick boundary. Live slots go
        through `_evict` (pages free now); spilled/queued requests emit
        their typed result directly; unknown rids are dropped."""
        for rid in sorted(self._cancel_req):
            slot = next((i for i in range(self.sched.num_slots)
                         if self.active[i]
                         and self.slots[i].req.rid == rid), None)
            if slot is not None:
                self._evict(slot, results, now, status="cancelled")
                continue  # _evict discards the rid
            if rid in self._spilled:
                sp = self._spilled.pop(rid)
                self._emit_unserved(sp.req, results, now, "cancelled",
                                    sp=sp)
                self._m["cancelled"].inc()
                self._cancel_req.discard(rid)
                continue
            hit = next((r for r in pending if r.rid == rid), None)
            if hit is not None:
                pending.remove(hit)
                self._emit_unserved(hit, results, now, "cancelled")
                self._m["cancelled"].inc()
            self._cancel_req.discard(rid)

    def _shed_expired(self, pending: list, results: list,
                      now: float) -> None:
        """Admission-deadline shedding (any mode): a request still queued
        past `arrival + deadline_ms` is retired with status "shed" —
        explicit overload behavior instead of unbounded queueing. Runs
        AFTER admission, so a request gets its last admission chance at
        the deadline tick."""
        for r in list(pending):
            if r.deadline_ms is None:
                continue
            if now > r.arrival + r.deadline_ms / 1e3:
                pending.remove(r)
                self._emit_unserved(r, results, now, "shed")
                self._m["shed"].inc()

    def _check_conservation(self) -> None:
        self.allocator.check_conservation()
        if self.allocator2 is not None:
            self.allocator2.check_conservation()
        if self.state_slots is not None:
            self.state_slots.check_conservation()

    def _watchdog(self, tick: int, pending: list) -> None:
        """Wall-clock watchdog (`SchedulerConfig.max_wall_s`): abort a
        hung trace with a diagnostic dump instead of hanging forever."""
        if self.sched.max_wall_s is None:
            return
        wall = time.perf_counter() - self._t0
        if wall <= self.sched.max_wall_s:
            return
        # emit the fire itself FIRST so the flight-recorder tail below is
        # never empty, even when the watchdog trips on the very first tick
        self._tracer.instant(
            "watchdog", tick=tick, wall_s=round(wall, 3),
            max_wall_s=self.sched.max_wall_s,
            last_dispatch_key=self._last_dispatch_key)
        live = [
            {"slot": i, "rid": self.slots[i].req.rid,
             "priority": self.slots[i].priority,
             "length": int(self.lengths[i]),
             "generated": len(self.slots[i].generated),
             "remaining": (self.slots[i].req.max_new_tokens
                           - len(self.slots[i].generated)),
             "tier2": bool(self.tier2[i]) if len(self.tier2) else False}
            for i in range(self.sched.num_slots) if self.active[i]]
        diag = {
            "tick": tick,
            "wall_s": round(wall, 3),
            "max_wall_s": self.sched.max_wall_s,
            "live_slots": live,
            "pool": {"free": self.allocator.num_free,
                     "live": self.allocator.num_live},
            "pool2": (None if self.allocator2 is None else
                      {"free": self.allocator2.num_free,
                       "live": self.allocator2.num_live}),
            "pending_rids": [r.rid for r in pending],
            "spilled_rids": sorted(self._spilled),
            "last_dispatch_key": self._last_dispatch_key,
            # the flight recorder: the last N structured trace events
            # leading up to the fire ([] only when tracing is disabled)
            "trace_tail": self._tracer.tail(64),
        }
        raise SchedulerWatchdogError(
            f"trace exceeded max_wall_s={self.sched.max_wall_s}", diag)

    def _spill_slot(self, slot: int) -> None:
        """Preempt a live slot: copy its packed pages to host memory,
        release the page references (shared prefix pages survive on the
        trie's refs), clear the slot. The request parks in `_spilled`
        until `_try_restore` resumes it bit-for-bit."""
        st = self.slots[slot]
        rid = st.req.rid
        with self._tracer.region("spill", slot + 1, rid=rid,
                                 tick=self._tick) as span:
            tier2 = bool(self.tier2[slot]) if len(self.tier2) else False
            alloc = self.allocator2 if tier2 else self.allocator
            pool = self.pool2 if tier2 else self.pool
            row = self.page_table2[slot] if tier2 else self.page_table[slot]
            n_total = int(np.count_nonzero(row))
            n_data = pages_lib.pages_for_tokens(int(self.lengths[slot]),
                                                self.sched.page_size)
            payload = None
            if pool is not None:
                payload = spill_lib.spill_pages(pool, row[:n_data],
                                                tracer=self._tracer)
            alloc.free(rid)
            state = None
            state_bytes = 0
            if self.store is not None:
                # the state-slot half of the preemption: snapshot the slot's
                # PACKED bytes (already quantized — the spill is bit-exact
                # over the stored representation) and release the slot
                with self._tracer.region("state-spill", slot + 1, rid=rid,
                                         tick=self._tick) as sspan:
                    state = self.store.snapshot_slot(self.states, slot)
                    state_bytes = _tree_nbytes(state)
                    self.state_slots.release(rid)
                    sspan.set(bytes=state_bytes)
            st.marks.append(("spill", time.perf_counter() - self._t0))
            sp = spill_lib.SpilledRequest(
                req=st.req, priority=st.priority, generated=st.generated,
                next_tok=int(self.next_tok[slot]),
                length=int(self.lengths[slot]),
                ctx=self.ctx_buf[slot, :int(self.ctx_len[slot])].copy(),
                payload=payload, n_pages=n_total, tier2=tier2, state=state,
                t_admit=st.t_admit, t_first=st.t_first,
                draft_proposed=st.draft_proposed,
                draft_accepted=st.draft_accepted,
                verify_steps=st.verify_steps, host_syncs=st.host_syncs,
                preemptions=st.preemptions + 1,
                spill_count=st.preemptions + 1,
                restore_retries=st.restore_retries, degraded=st.degraded,
                marks=st.marks)
            self.page_table[slot] = 0
            if self.allocator2 is not None:
                self.page_table2[slot] = 0
            self.tier2[slot] = False
            self.lengths[slot] = 0
            self.active[slot] = False
            self.next_tok[slot] = 0
            self.ctx_buf[slot] = 0
            self.ctx_len[slot] = 0
            self.slots[slot] = None
            self._spilled[rid] = sp
            self._m["spills"].inc()
            page_bytes = payload.nbytes() if payload is not None else 0
            self._m["spill_bytes"].inc(page_bytes + state_bytes)
            span.set(pages=n_total, bytes=page_bytes + state_bytes,
                     tier2=tier2)

    def _try_restore(self, sp: "spill_lib.SpilledRequest",
                     now: float) -> str:
        """Resume a spilled request: allocate its full span reservation,
        upload the payload, rewrite the page-table row, reactivate the
        slot. Returns "ok", or why not: "backoff" (transient failures ate
        the per-tick retry budget — re-queued with exponential backoff),
        "no_slot", "no_pages" (genuine shortage — the pressure ladder's
        problem, not a retry's)."""
        if now < sp.not_before:
            return "backoff"
        free = [i for i in range(self.sched.num_slots)
                if not self.active[i]]
        if not free:
            return "no_slot"
        slot = free[0]
        nbytes = (sp.payload.nbytes() if sp.payload is not None
                  else _tree_nbytes(sp.state))
        with self._tracer.region(
                "restore", slot + 1, rid=sp.req.rid, tick=self._tick,
                pages=sp.n_pages, bytes=nbytes, tier2=sp.tier2) as span:
            why = self._restore_into(sp, slot, now)
            span.set(retries=sp.restore_retries, outcome=why)
        return why

    def _restore_into(self, sp: "spill_lib.SpilledRequest", slot: int,
                      now: float) -> str:
        """The allocation retries and uploads of `_try_restore`, into free
        slot `slot`."""
        alloc = self.allocator2 if sp.tier2 else self.allocator
        faults = self._faults
        delay = faults.take_restore_delay() if faults is not None else 0.0
        if delay > 0:
            time.sleep(delay)
            self._m["restore_delays"].inc()
        backoff = self.sched.restore_backoff_s
        for attempt in range(self.sched.restore_max_retries):
            if faults is not None and faults.take_alloc_fail():
                sp.restore_retries += 1
                self._m["restore_retries"].inc()
                if backoff > 0:
                    time.sleep(backoff * (2 ** attempt))
                continue
            if not alloc.can_alloc(sp.n_pages):
                return "no_pages"
            ids = alloc.alloc(sp.n_pages, sp.req.rid)
            if faults is not None and faults.take_restore_fail():
                # the upload "failed" after allocation: release and back
                # off — the alloc/release conservation path under failure
                alloc.release(sp.req.rid)
                sp.restore_retries += 1
                self._m["restore_retries"].inc()
                if backoff > 0:
                    time.sleep(backoff * (2 ** attempt))
                continue
            n_data = pages_lib.pages_for_tokens(sp.length,
                                                self.sched.page_size)
            if sp.payload is not None:
                if sp.tier2:
                    self.pool2 = self._commit_pool(spill_lib.restore_pages(
                        self.pool2, sp.payload, ids[:n_data],
                        tracer=self._tracer))
                else:
                    self.pool = self._commit_pool(spill_lib.restore_pages(
                        self.pool, sp.payload, ids[:n_data],
                        tracer=self._tracer))
            if sp.state is not None:
                # upload the slot's packed state bytes back — bit-exact
                # (the snapshot WAS the stored representation)
                with self._tracer.region(
                        "state-restore", slot + 1, rid=sp.req.rid,
                        tick=self._tick, bytes=_tree_nbytes(sp.state)):
                    self.states = self.store.write_slot(self.states, slot,
                                                        sp.state)
                    self.state_slots.claim(slot, sp.req.rid)
            row = np.zeros((self.sched.max_pages,), np.int32)
            row[:sp.n_pages] = ids
            if sp.tier2:
                self.page_table2[slot] = row
                self.page_table[slot] = 0
            else:
                self.page_table[slot] = row
                if self.allocator2 is not None:
                    self.page_table2[slot] = 0
            self.tier2[slot] = sp.tier2
            self.lengths[slot] = sp.length
            self.active[slot] = True
            self.next_tok[slot] = sp.next_tok
            self.ctx_buf[slot] = 0
            self.ctx_buf[slot, :len(sp.ctx)] = sp.ctx
            self.ctx_len[slot] = len(sp.ctx)
            sp.marks.append(("restore", time.perf_counter() - self._t0))
            self.slots[slot] = _Slot.from_spilled(sp)
            del self._spilled[sp.req.rid]
            self._m["restores"].inc()
            return "ok"
        # per-tick retry budget exhausted: re-queue with backoff so the
        # loop never blocks on one unlucky restore
        sp.not_before = now + backoff * (2 ** self.sched.restore_max_retries)
        return "backoff"

    def _degrade_slot(self, slot: int) -> bool:
        """Tier migration (the "degrade" pressure rung): recompress a
        live tier-1 slot's pages into the lower-bit tier-2 pool, freeing
        its tier-1 pages WITHOUT preempting it. Lossy by one
        requantization — recorded on the slot / its result. Only fires
        when the victim's full span reservation fits tier-2."""
        st = self.slots[slot]
        rid = st.req.rid
        row = self.page_table[slot]
        n_total = int(np.count_nonzero(row))
        if not self.allocator2.can_alloc(n_total):
            return False
        if self._faults is not None and self._faults.take_alloc_fail():
            return False
        with self._tracer.region("degrade", slot + 1, rid=rid,
                                 tick=self._tick, pages=n_total):
            n_data = pages_lib.pages_for_tokens(int(self.lengths[slot]),
                                                self.sched.page_size)
            ids2 = self.allocator2.alloc(n_total, rid)
            self.pool2 = self._commit_pool(spill_lib.migrate_pages(
                self.pool, row[:n_data], self.backend.quantizer,
                self.backend2.quantizer, self.pool2, ids2[:n_data],
                migrate_fn=self._migrate_fn))
            self.allocator.free(rid)
            self.page_table[slot] = 0
            row2 = np.zeros((self.sched.max_pages,), np.int32)
            row2[:n_total] = ids2
            self.page_table2[slot] = row2
            self.tier2[slot] = True
            st.degraded = True
            st.marks.append(("degrade", time.perf_counter() - self._t0))
            self._m["degraded"].inc()
        return True

    def _pick_victim(self, priority: int,
                     holding_tier2: Optional[bool] = None
                     ) -> Optional[int]:
        """Preemption victim: the lowest-priority active slot STRICTLY
        below `priority`; ties broken by most pages held (frees the
        most), then slot index. `holding_tier2` restricts to slots whose
        pages live in that tier (a tier-1 page shortage is only relieved
        by a tier-1 holder)."""
        best_key, best = None, None
        for i in range(self.sched.num_slots):
            if not self.active[i]:
                continue
            t2 = bool(self.tier2[i]) if len(self.tier2) else False
            if holding_tier2 is not None and t2 != holding_tier2:
                continue
            st = self.slots[i]
            if st.priority >= priority:
                continue
            row = self.page_table2[i] if t2 else self.page_table[i]
            key = (st.priority, -int(np.count_nonzero(row)), i)
            if best_key is None or key < best_key:
                best_key, best = key, i
        return best

    def _apply_pressure(self, priority: int, need_slot: bool,
                        pool_tier2: bool = False) -> bool:
        """One pressure-ladder rung on one victim (shed happens in
        `_shed_expired`; evict happens on its own when requests finish):
        degrade if the shortage is tier-1 pages and a tier-2 pool exists,
        else spill. Returns True when resources were freed — the caller
        re-checks admissibility and may ask again."""
        if need_slot:
            victim = self._pick_victim(priority)
            if victim is None:
                return False
            self._spill_slot(victim)
            return True
        # page shortage in the pool `pool_tier2` selects
        if not pool_tier2 and self.backend2 is not None:
            victim = self._pick_victim(priority, holding_tier2=False)
            if victim is not None and self._degrade_slot(victim):
                return True
        victim = self._pick_victim(priority, holding_tier2=pool_tier2)
        if victim is None:
            return False
        self._spill_slot(victim)
        return True

    # ------------------------------------------------------------ admission --
    def _try_admit_one(self, req: Request, pending: list, results: list,
                       now: float, rng: jax.Array
                       ) -> tuple[str, jax.Array]:
        """Admit `req` if a slot + pages are available (the legacy FCFS
        admission body, verbatim semantics — including the rng split
        order). Returns ("ok" | "no_slot" | "no_pages" | "fault", rng);
        only "ok" consumes the request from `pending`."""
        free_slots = [i for i in range(self.sched.num_slots)
                      if not self.active[i]]
        if not free_slots:
            return "no_slot", rng
        _, need = self._pages_needed(req)
        shared, skip = self._match_prefix(req)
        # take the request's refs on the hit pages FIRST so trie
        # reclamation below can never free them out from under it
        self.allocator.share(shared, req.rid)
        n_fresh = need - len(shared)
        while (self.trie is not None
               and not self.allocator.can_alloc(n_fresh)
               and self.trie.evict_one()):
            pass  # reclaim cached-but-unused prefix pages
        if (self._faults is not None and n_fresh > 0
                and self._faults.take_alloc_fail()):
            # injected transient allocation failure: plain backpressure —
            # the request stays queued and retries next tick
            self.allocator.release(req.rid)
            return "fault", rng
        if not self.allocator.can_alloc(n_fresh):
            self.allocator.release(req.rid)
            return "no_pages", rng
        pending.remove(req)
        if self.trie is not None:
            self.trie.record(skip)
        fresh = self.allocator.alloc(n_fresh, req.rid)
        rng, sub = self._split(rng)
        slot = free_slots[0]
        with self._tracer.region(
                "admit", slot + 1, rid=req.rid, tick=self._tick,
                prompt_len=len(req.tokens), pages=need,
                shared_pages=len(shared), skip=skip,
                priority=req.priority) as span:
            self._admit(req, slot, shared, fresh, skip, sub, now)
        self._m["prefill_wall_s"].inc(span.elapsed)
        st = self.slots[slot]
        if self._finished(st):  # budget 1 or instant EOS
            self._evict(slot, results, time.perf_counter() - self._t0)
        return "ok", rng

    def _admission_preempt(self, pending: list, results: list, now: float,
                           rng: jax.Array) -> jax.Array:
        """Priority-ordered admission with the pressure ladder.

        Candidates are every arrived queued request plus every spilled
        request, ordered (priority desc, arrival, rid) — restores compete
        with fresh arrivals at their ORIGINAL priority and arrival time.
        The head candidate gets the tick's resources; when it cannot be
        served, one pressure rung fires on a strictly-lower-priority
        victim and the ladder re-evaluates. Backoff-parked restores are
        skipped (their shortage is transient, not a resource hole).
        Head-of-line blocking within the ladder is deliberate: admitting
        a lower-priority candidate past a resource-starved higher one
        would invert the SLO ordering."""
        while True:
            cands: list[tuple] = [
                ("req", r.priority, r.arrival, r.rid, r)
                for r in pending if r.arrival <= now]
            cands += [
                ("spill", sp.priority, sp.req.arrival, sp.req.rid, sp)
                for sp in self._spilled.values()]
            cands.sort(key=lambda c: (-c[1], c[2], c[3]))
            progressed = False
            for kind, prio, _, _, obj in cands:
                if kind == "spill":
                    why = self._try_restore(obj, now)
                    if why == "ok":
                        progressed = True
                        break
                    if why == "backoff":
                        continue  # transient; next candidate may proceed
                    if self._apply_pressure(prio, why == "no_slot",
                                            pool_tier2=obj.tier2):
                        progressed = True
                        break
                    return rng  # resource-starved head of line
                why, rng = self._try_admit_one(obj, pending, results,
                                               now, rng)
                if why == "ok":
                    progressed = True
                    break
                if why == "fault":
                    return rng  # transient failure: retry next tick
                if self._apply_pressure(prio, why == "no_slot"):
                    progressed = True
                    break
                return rng
            if not progressed:
                return rng

    # ------------------------------------------------------------ main loop --
    def validate_request(self, r: Request) -> None:
        """Reject a request whose worst-case span cannot fit the pool or
        the page table — checked up-front (and per intake arrival) so
        admission can never OOM mid-flight. The HTTP front-end
        (serving/server.py) runs the same check at submit time to turn
        the ValueError into a 400 instead of killing the serve loop."""
        width, need = self._pages_needed(r)
        if self.family.state_slots:
            # state families bound the span by the token capacity (the
            # device-resident ctx stream); xlstm has no page bound at all
            cap = self.sched.max_pages * self.sched.page_size
            if len(r.tokens) + r.max_new_tokens > cap:
                raise ValueError(
                    f"request {r.rid} span ({len(r.tokens)} prompt + "
                    f"{r.max_new_tokens} new) exceeds the token capacity "
                    f"{cap}")
            if not self.family.paged_kv:
                return
        if need > self.sched.num_pages - 1:
            raise ValueError(
                f"request {r.rid} needs {need} pages; pool only has "
                f"{self.sched.num_pages - 1}")
        if need > self.sched.max_pages:
            # the chunk-bucketed prefill width also bounds the span:
            # a prompt bucketed past max_context would overflow the
            # page-table row even if plen + max_new fits
            raise ValueError(
                f"request {r.rid} span (bucketed prompt {width} + "
                f"generation, {need} pages) exceeds max_context "
                f"{self.sched.max_context} ({self.sched.max_pages} "
                f"pages)")

    def _serve_tick(self, tick: int, pending: list, results: list,
                    rng: jax.Array, intake, stop) -> jax.Array:
        """One tick of `run`: intake, admission and the pressure ladder,
        then one decode burst or an idle wait. Returns the advanced rng."""
        faults = self._faults
        now = time.perf_counter() - self._t0
        if intake is not None:
            with self._tracer.region("intake"):
                fresh = intake()
                if fresh:
                    for r in fresh:
                        try:
                            self.validate_request(r)
                        except ValueError:
                            # an unservable mid-flight submission must
                            # not kill the serve loop: retire it typed
                            # (the front-end 400s these before intake,
                            # so this is defense in depth)
                            self._emit_unserved(r, results, now, "shed")
                            self._m["shed"].inc()
                            continue
                        # stamp arrival trace-relative: queueing delay
                        # runs from the tick the scheduler saw it
                        pending.append(
                            dataclasses.replace(r, arrival=now))
                    pending.sort(key=lambda r: (r.arrival, r.rid))
        self._watchdog(tick, pending)
        self._refresh_gauges(len(pending))
        if faults is not None:
            faults.on_tick(self, tick)
        if self._cancel_req:
            self._process_cancels(pending, results, now)
        # --- admission: priority-ordered + pressure ladder in preempt
        # mode, legacy FCFS (identical rng order) otherwise
        if self.sched.preempt:
            rng = self._admission_preempt(pending, results, now, rng)
        else:
            while pending and pending[0].arrival <= now:
                why, rng = self._try_admit_one(pending[0], pending,
                                               results, now, rng)
                if why != "ok":
                    break  # FCFS head-of-line: wait for an eviction
        self._shed_expired(pending, results, now)
        if self.sched.debug_conservation:
            self._check_conservation()
        if not self.active.any():
            self._idle(pending, stop)
            return rng
        remaining = np.ones((self.sched.num_slots,), np.int32)
        for i in range(self.sched.num_slots):
            if self.active[i]:
                st = self.slots[i]
                remaining[i] = (st.req.max_new_tokens
                                - len(st.generated))
        if self.sched.speculate:
            if self.sched.spec_device:
                # --- fused burst: up to max_burst draft->verify->
                # accept rounds, ONE dispatch, one host sync
                self._m["decode_steps"].inc(self._spec_burst(
                    remaining, results,
                    queued=bool(pending or self._spilled)))
            else:
                # --- host-driven oracle: one round per dispatch
                self._spec_step(remaining, results)
                self._m["decode_steps"].inc()
        else:
            # --- one decode burst: k fused steps, k = min remaining budget
            k = int(min(self.sched.max_burst,
                        remaining[self.active].min()))
            mp = self._live_table_width(k) if self.family.paged_kv else 0
            with self._tracer.region("decode-burst", tick=tick, k=k,
                                     width=mp) as span:
                rng, emitted = self._decode_burst(k, mp, remaining,
                                                  results, rng)
                span.set(emitted=emitted)
        if self.sched.debug_conservation:
            self._check_conservation()
        return rng

    def _idle(self, pending: list, stop) -> None:
        """Nothing is live: sleep until the next arrival, a restore's
        backoff, or a streaming server's next intake."""
        nap = 0.0
        if pending:  # idle until the next arrival
            nap = min(pending[0].arrival - (time.perf_counter() - self._t0),
                      0.01)
        elif self._spilled:
            # every live request is spilled and restores are backing off
            # — yield briefly, then retry
            nap = 0.001
        elif stop is not None:
            # streaming server, nothing to do: idle cheaply until the
            # next intake or the stop signal
            nap = 0.002
        if nap > 0:
            with self._tracer.region("idle-wait") as span:
                time.sleep(nap)
            self._wait_s += span.elapsed

    def _decode_burst(self, k: int, mp: int, remaining: np.ndarray,
                      results: list, rng: jax.Array) -> tuple[jax.Array,
                                                             int]:
        """Dispatch one k-step decode burst over page-table width `mp`,
        read its tokens back and hand them out. Returns (rng, tokens
        emitted)."""
        owned = self._owned_write_mask(k)
        rng, sub = self._split(rng)
        if self.family.state_slots:
            # state-family burst: the packed recurrent-state store
            # rides the dispatch (decoded once at entry, merged back
            # at exit); hybrids advance their shared-attention pages
            # and their state slots in the SAME tick
            if self.family.paged_kv:
                pk, pv, emitted, out, packed = self._dispatch(
                    ("decode", mp), self._decode_fn,
                    self.params, self.pool.k, self.pool.v,
                    np.asarray(self.page_table[:, :mp]),
                    np.asarray(self.lengths),
                    np.asarray(self.active),
                    np.asarray(self.next_tok),
                    np.asarray(remaining),
                    np.asarray(k, jnp.int32), sub, self.states)
                self.pool = self.pool._replace(k=pk, v=pv)
            else:
                emitted, out, packed = self._dispatch(
                    ("decode", 0), self._decode_fn,
                    self.params, np.asarray(self.active),
                    np.asarray(self.next_tok),
                    np.asarray(remaining),
                    np.asarray(k, jnp.int32), sub, self.states)
            self.states = packed
        elif self.backend2 is not None:
            # tiered dispatch: both pools ride the burst; a slot's
            # pages live in exactly one (tier2 routes)
            pk, pv, pk2, pv2, emitted, out = self._dispatch(
                ("decode", mp), self._decode_fn,
                self.params, self.pool.k, self.pool.v,
                self.pool2.k, self.pool2.v,
                np.asarray(self.page_table[:, :mp]),
                np.asarray(self.page_table2[:, :mp]),
                np.asarray(self.tier2),
                np.asarray(self.lengths),
                np.asarray(self.active), np.asarray(owned),
                np.asarray(self.next_tok),
                np.asarray(remaining), np.asarray(k, jnp.int32),
                sub)
            self.pool2 = self.pool2._replace(k=pk2, v=pv2)
            self.pool = self.pool._replace(k=pk, v=pv)
        else:
            pk, pv, emitted, out = self._dispatch(
                ("decode", mp), self._decode_fn,
                self.params, self.pool.k, self.pool.v,
                np.asarray(self.page_table[:, :mp]),
                np.asarray(self.lengths),
                np.asarray(self.active), np.asarray(owned),
                np.asarray(self.next_tok),
                np.asarray(remaining), np.asarray(k, jnp.int32),
                sub)
            self.pool = self.pool._replace(k=pk, v=pv)
        emitted, out = self._readback(emitted, out)
        self._m["decode_steps"].inc(int(emitted.max(initial=0)))
        self._m["decode_bursts"].inc()
        t_now = time.perf_counter() - self._t0
        with self._tracer.region("emit"):
            for i in range(self.sched.num_slots):
                if not self.active[i] or emitted[i] == 0:
                    continue
                n = int(emitted[i])
                self.lengths[i] += n  # each fed token's KV was appended
                self.next_tok[i] = out[i, n - 1]
                self.slots[i].generated.extend(int(t) for t in out[i, :n])
                self.slots[i].host_syncs += 1
                if self.on_tokens is not None:
                    self.on_tokens(self.slots[i].req.rid,
                                   [int(t) for t in out[i, :n]])
                cl = int(self.ctx_len[i])
                self.ctx_buf[i, cl:cl + n] = out[i, :n]
                self.ctx_len[i] = cl + n
                if self._finished(self.slots[i]):
                    self._evict(i, results, t_now)
            # mid-burst cancellation window (plain decode): cancels
            # injected while the burst ran land here, same tick
            if self._faults is not None:
                for rid in self._faults.mid_burst_cancels():
                    self.cancel(rid)
            if self._cancel_req:
                for i in range(self.sched.num_slots):
                    if (self.active[i]
                            and self.slots[i].req.rid in self._cancel_req):
                        self._evict(i, results, t_now, status="cancelled")
        return rng, int(emitted.sum())

    def run(self, requests: list[Request],
            rng: Optional[jax.Array] = None,
            faults=None, *, intake=None,
            stop=None) -> tuple[list[RequestResult], dict]:
        """Serve a request trace to completion.

        Requests are admitted FCFS as their `arrival` times pass and a
        decode slot plus enough pool pages free up; the call blocks until
        every request has finished (or was shed / cancelled — every
        request yields exactly one typed `RequestResult`, never a hang).
        Raises ValueError up-front for any request whose worst-case span
        cannot fit the pool or the page table, so admission can never OOM
        mid-flight.

        With `sched.preempt` admission is priority-ordered instead of
        FCFS and backed by the pressure ladder (shed -> degrade -> spill
        -> evict, docs/serving.md): a high-priority arrival that cannot
        be admitted preempts a strictly-lower-priority victim by spilling
        its pages to host memory; the victim resumes later,
        bitwise-losslessly. `faults` (serving/faults.py FaultInjector)
        injects deterministic adversity — forced allocation failures,
        delayed/failed restores, mid-verify cancels, pool exhaustion —
        through the exact code paths real failures would take.

        Returns `(results, stats)`: per-request `RequestResult`s sorted by
        rid, and an aggregate dict with wall/throughput/latency
        percentiles (over COMPLETED requests), pool accounting, prefill
        work counters (`prefill_chunks`, `prefill_tokens_computed`,
        `prefill_wall_s`), the plain `decode_bursts` and `sched_host_s`
        (host seconds of the ticks outside device readbacks and idle
        waits, from the `tick` spans), an `slo` sub-dict (shed/cancelled/spill/
        restore/degrade counters + per-priority-class latency), in
        prefix-cache "share" mode a `prefix` sub-dict with this run's
        trie hits/misses/hit_tokens/evictions, and with speculation on a
        `spec` sub-dict (aggregate + per-request draft_proposed /
        draft_accepted / acceptance_rate / verify_steps /
        steps_per_token).

        The engine is reusable: a second `run` on the same instance keeps
        compiled executables and (in "share" mode) the populated prefix
        trie, which is how repeated traces get warm-prefix service.

        Streaming mode (serving/server.py): `intake` is an optional
        zero-arg callable returning newly-submitted Requests, drained at
        every tick boundary — each drained request is re-stamped with
        `arrival = now` (trace-relative), so queueing delay is measured
        from when the scheduler saw it. `stop` is an optional zero-arg
        predicate: while it returns False the loop keeps running (idling
        cheaply when empty) even with nothing queued; once True, the loop
        drains in-flight work and returns. Both default to None, which is
        exactly the legacy batch behavior.

        The returned `stats[...]` blocks are per-run DELTA VIEWS over the
        engine's metrics registry (`self.telemetry.registry`, one source
        of truth — what `GET /metrics` exposes cumulatively), plus
        `ttft_hist` / `tpot_hist` / `latency_hist` histogram views.
        """
        if rng is None:
            rng = jax.random.PRNGKey(0)
        for r in requests:
            self.validate_request(r)
        pending = sorted(requests, key=lambda r: (r.arrival, r.rid))
        results: list[RequestResult] = []
        self._t0 = time.perf_counter()
        self._faults = faults
        # stats are built from the per-run registry delta at the end —
        # the registry itself stays cumulative across runs (Prometheus
        # counter semantics; the engine is reusable)
        snap0 = self.telemetry.registry.snapshot()
        self._tracer.reset_epoch()
        self._tracer.instant("run-start", n_requests=len(requests),
                             streaming=intake is not None)
        trie0 = self.trie.stats() if self.trie is not None else None
        tick = -1
        if faults is not None:
            faults.begin(self)
        while (pending or self._spilled or self.active.any()
               or (stop is not None and not stop())):
            tick += 1
            self._tick = tick
            self._wait_s = 0.0
            with self._tracer.region("tick", step_num=tick,
                                     tick=tick) as span:
                rng = self._serve_tick(tick, pending, results, rng, intake,
                                       stop)
            # host time on the path between bursts: the tick less its
            # waits on the device and for arrivals
            self._m["sched_host_s"].inc(span.elapsed - self._wait_s)
        wall = time.perf_counter() - self._t0
        if faults is not None:
            faults.finish(self)  # return stolen pages before the audit
        self._faults = None
        self._check_conservation()
        self._refresh_gauges(0)
        self._tracer.instant("run-end", n_results=len(results), wall_s=wall)
        results.sort(key=lambda r: r.rid)
        completed = [r for r in results if r.status == "completed"]
        total_new = int(sum(len(r.tokens) for r in results))
        lat = np.asarray([r.latency_s for r in completed] or [0.0])
        ttft = np.asarray([r.ttft_s for r in completed] or [0.0])
        # stats are per-run views over the registry: the registry itself is
        # cumulative across run() calls (Prometheus counter semantics), so
        # everything below is a delta against the snapshot taken at entry
        d = self.telemetry.registry.delta(snap0)
        stats = {
            "num_requests": len(results),
            "decode_steps": int(d.value("decode_steps")),
            "wall_s": wall,
            "new_tokens": total_new,
            "tokens_per_sec": total_new / max(wall, 1e-9),
            "latency_p50_s": float(np.percentile(lat, 50)),
            "latency_p99_s": float(np.percentile(lat, 99)),
            "ttft_p50_s": float(np.percentile(ttft, 50)),
            "pool_bytes": (pages_lib.cache_physical_bytes(self.pool)
                           if self.pool is not None else 0),
            "pages_total": self.sched.num_pages - 1,
            "page_size": self.sched.page_size,
            "prefill_chunks": int(d.value("prefill_chunks")),
            "prefill_tokens_computed": int(d.value("prefill_tokens")),
            "prefill_wall_s": float(d.value("prefill_wall_s")),
            "decode_bursts": int(d.value("decode_bursts")),
            "sched_host_s": float(d.value("sched_host_seconds")),
            "ttft_hist": d.hist("ttft_seconds"),
            "tpot_hist": d.hist("tpot_seconds"),
            "latency_hist": d.hist("request_latency_seconds"),
        }
        assert int(d.value("new_tokens")) == total_new, \
            "registry/results disagree on emitted token count"
        # dispatch/compile observability: cumulative over the engine's
        # lifetime (compile cost is paid once and amortized across runs —
        # see serving/compile_cache.py and docs/serving.md "Performance")
        stats["perf"] = dict(
            self._perf, warmed=self._warmed,
            host_sync_count=int(self._m["host_syncs"].value))
        # family adapter view (serving/families.py): which capability
        # plane served this run, plus state-cache byte accounting for
        # state-slot families
        fam = self.family
        stats["family"] = dict(
            name=fam.family, paged_kv=fam.paged_kv,
            state_slots=fam.state_slots, speculate=fam.speculate,
            prefix_share=fam.prefix_share, degrade=fam.degrade,
            mesh=fam.mesh, moe_dropless=self.moe_dropless)
        if self.store is not None:
            stats["family"].update(
                state_cache_bytes=self.store.physical_bytes(self.states),
                state_bytes_per_slot=self.store.bytes_per_slot(
                    self.states),
                state_raw_bytes_per_slot=self.store.raw_bytes_per_slot(),
                state_encode_seconds=float(
                    d.value("state_encode_seconds")))
        # SLO / pressure-ladder accounting for THIS run: what the ladder
        # did (spill/restore/degrade/shed/cancel counters) and how each
        # priority class fared (completed requests only)
        per_class = {}
        for p in sorted({r.priority for r in completed}):
            cl = [r.latency_s for r in completed if r.priority == p]
            per_class[str(p)] = {
                "n": len(cl),
                "latency_p50_s": float(np.percentile(cl, 50)),
                "latency_p99_s": float(np.percentile(cl, 99)),
            }
        stats["slo"] = dict(
            shed=int(d.value("sched_shed")),
            cancelled=int(d.value("sched_cancelled")),
            spills=int(d.value("sched_spills")),
            spill_bytes=int(d.value("sched_spill_bytes")),
            restores=int(d.value("sched_restores")),
            restore_retries=int(d.value("sched_restore_retries")),
            restore_delays=int(d.value("sched_restore_delays")),
            degraded=int(d.value("sched_degraded")),
            completed=len(completed),
            preempted=sum(1 for r in results if r.preemptions > 0),
            per_class=per_class)
        if faults is not None:
            stats["faults"] = faults.stats()
        if self.sched.speculate:
            # draft/verify accounting: a request's decode-emitted tokens
            # exclude its first token (sampled by prefill), so
            # steps_per_token is sequential verify dispatches per token
            # the decode loop produced — < 1.0 means speculation beat
            # one-token-per-forward-pass.
            proposed = sum(r.draft_proposed for r in results)
            accepted = sum(r.draft_accepted for r in results)
            vsteps = sum(r.verify_steps for r in results)
            # each served request's first token came from prefill, not a
            # verify step (shed requests contribute zero either way)
            decode_tokens = sum(max(len(r.tokens) - 1, 0) for r in results)
            stats["spec"] = {
                "draft_len": self.sched.draft_len,
                "draft_proposed": proposed,
                "draft_accepted": accepted,
                "acceptance_rate": accepted / max(proposed, 1),
                "verify_steps": vsteps,
                "decode_tokens": decode_tokens,
                "steps_per_token": vsteps / max(decode_tokens, 1),
                "per_request": [
                    {"rid": r.rid,
                     "draft_proposed": r.draft_proposed,
                     "draft_accepted": r.draft_accepted,
                     "acceptance_rate": (r.draft_accepted
                                         / max(r.draft_proposed, 1)),
                     "verify_steps": r.verify_steps,
                     "steps_per_token": (r.verify_steps
                                         / max(len(r.tokens) - 1, 1))}
                    for r in results],
            }
        if self.trie is not None:
            self.trie.check_bound()
            t1 = self.trie.stats()
            stats["prefix"] = dict(
                t1, **{k: t1[k] - trie0.get(k, 0)
                       for k in ("hits", "misses", "hit_tokens", "evictions",
                                 "evictions_lru", "evictions_reclaim")})
        return results, stats
