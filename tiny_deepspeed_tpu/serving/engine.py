# Copyright 2026 tiny-deepspeed-tpu authors
# SPDX-License-Identifier: Apache-2.0

"""Continuous batching over the paged KV pool.

`GPT2Model.generate` serves exactly one request at a time: fixed shapes,
one compiled loop, the whole batch enters and leaves together.  Serving
traffic needs the scheduler in between: `ServingEngine` keeps a FIXED
array of `max_active` slots (so the compiled decode step never changes
shape) and, BETWEEN decode steps, admits queued requests, evicts
finished ones, and returns their pool blocks to the free list — batch
occupancy stays high because a finished request's slot and blocks are
reused immediately instead of padding out the longest neighbor.

Phase split, two compiled programs:

  * PREFILL — one request's prompt through the training forward
    (`paged_prefill`, the `return_kv` hook), K/V scattered into its pool
    blocks, first token sampled from the true last-prompt position.
    Prompts pad to power-of-two block-multiple buckets, so distinct
    compiled shapes stay O(log block_size).
  * DECODE — ONE token for EVERY active slot: (S, 1, D) activations,
    each slot reading the pool through its block table at its own
    position (vector `pos`) — a span of one through the same layer
    loop the verify and suffix-prefill programs use — and its K/V rows
    of all layers written once, after the loop.  Invalid slots carry
    scratch coordinates; no branch, no recompile as occupancy changes.
    A tick dispatches the NEXT tick's step before it fetches its own
    (`_next_step`): a slot that goes on takes its token from the step
    still on the chip, so the chip decodes while the host commits,
    schedules and comes back.  Every token a tick commits is on the
    request when it returns; the step in flight is dropped (and
    re-run, the same) where anything changes what it must do.

Block exhaustion preempts the YOUNGEST active request (its blocks free
immediately; it re-queues at the FRONT and later re-prefills from
prompt + tokens-produced-so-far, which continues the exact sequence).
A request that could never fit the pool at all is refused at submit().

Speculative decoding (`spec_draft=` / `spec_k=`; serving/spec.py +
serving/drafter.py): the decode step's ONE-token-per-slot contract
relaxes to 1..k+1 — a drafter proposes up to k continuation tokens per
slot, one shape-stable verify program scores all k+1 span positions
through the same paged attention, and the acceptance core commits the
longest target-exact prefix (greedy output bit-identical to
`generate`; only VERIFIED tokens reach the request, the journal, or
the pool — rejected draft K/V routes to the scratch block inside the
verify program itself).  Growth/admission extend block ownership to
the span horizon, the SLO shed price re-bases on wall per committed
token, and the guard/journal/preemption machinery is shared: the spec
path is one more decode implementation under the same scheduler.

Fault posture (the serving robustness layer):

  * SLOs — `submit(..., deadline_s=)` attaches a completion deadline
    (seconds from arrival).  The scheduler SHEDS queued requests whose
    deadline is overdue or unmeetable (priced from the measured
    per-tick decode-wall history), EXPIRES active requests that blow
    their deadline, and REFUSES admission outright above the
    `max_queue` / `shed_pool_util` watermarks — so a deadline-blind
    queue can never grow unboundedly.  Every outcome is a distinct
    terminal status on the request and its JSONL record:
    `ok` / `shed` / `expired` / `failed`.
  * Decode health — the compiled decode step reduces each slot's
    logits to a per-slot non-finite flag fetched alongside the sampled
    tokens (no extra device sync); poisoned slots are QUARANTINED
    (blocks freed, request `failed`, the rest of the batch keeps
    serving), and a watchdog WARM-RESTARTS the engine — fresh pool +
    slot array, compiled programs kept — after `guard_k_restart`
    consecutive poisoned ticks or any exception out of a tick
    (serving/guard.py).
  * Crash recovery — an append-only request journal (admissions +
    produced tokens, fsync batched per tick; serving/journal.py) lets
    `recover()` re-queue a dead engine's in-flight requests
    front-of-line with their produced prefix, riding the preemption
    resume path.

Determinism guarantee: sampling keys derive ONLY from (request seed,
output position) — `models/sampling.request_position_key` — never from
the scheduler tick, batch composition, preemption count, or restarts.
Greedy (temperature == 0) continuation is token-exact by argmax;
temperature > 0 re-samples the SAME tokens after preemption, warm
restart, or journal recovery because position i of request r always
draws from the same key (categorical is Gumbel argmax, sharing greedy's
robustness to the prefill-vs-decode numeric path difference).  A
request's token sequence is therefore a pure function of
(params, prompt, seed) — which is exactly what makes the journal's
"re-queue with produced prefix" recovery exact.

Telemetry: batch-occupancy / pool-utilization / queue-depth /
eviction-rate gauges plus the fault-path serve_shed / serve_expired /
serve_quarantined / serve_restarts gauges (telemetry/schema.GAUGES),
admission/eviction/preemption/token counters, TTFT + inter-token latency
histograms, and a per-request `request` record (terminal `status` field)
into the JSONL metrics stream at every terminal outcome.

Observability layer (the serving twin of the training step traces):

  * Request-lifecycle spans — every Request accumulates timestamped
    lifecycle events (submitted -> admitted(slot) -> preempted /
    restart_requeued / quarantined / expired -> terminal:<status>)
    recorded inside the scheduler hooks, serialized on its `request`
    record; `scripts/trace_view.py` lays them out as a Perfetto
    timeline with one track per decode slot plus a queue track.
  * Tail-latency attribution — each terminal request's latency is
    decomposed into queue-wait / prefill / decode-active /
    preempted-wait / restart-overhead components that PARTITION
    `lat_s` (sum == terminal latency, pinned by test), so "why was p99
    400 ms" has a named answer; `scripts/serve_report.py` rolls them up.
  * Per-tick time series — a `tick` JSONL record (wall split: host
    scheduling vs prefill vs decode dispatch vs token fetch; occupancy,
    pool utilization, queue depth; per-tick admission/eviction/
    preemption/shed counts), emitted when a scheduler event happened OR
    every `tick_record_every` ticks — long traces stay bounded while
    every eventful tick is captured.
  * Serving flight recorder — the last `flight_ticks` tick entries ride
    a telemetry/flight.py ring (host dicts only, no device sync) and
    flush as ONE `flight` record when quarantine, a watchdog restart, a
    shed burst, or `recover()` fires: every postmortem carries its
    lead-up, not just the event.

All of it is host-side bookkeeping around the SAME compiled programs —
the decode/prefill HLO is byte-identical with observability on or off
(the existing serving-off-path pin covers it).
"""

from __future__ import annotations

import contextlib
import dataclasses
import itertools
import os
import time
from collections import deque
from typing import Deque, Dict, List, NamedTuple, Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np

from ..models.gpt2 import resolved_cache_dtype
from ..models.sampling import sample_logits_at, sample_logits_per_slot
from ..utils import profiling
from .guard import DecodeHealthGuard
from .journal import RequestJournal, ServingKilled
from .pool import (
    SCRATCH_BLOCK, BlockPayload, PagedKVPool, export_blocks,
    import_blocks, page_ref, paged_append_span,
)
from .prefix import PrefixCache
from .tenancy import TenantPolicy, TenantQueue

# decode-wall samples needed before deadline shedding trusts its price
# estimate (a cold engine must not shed on compile-time noise)
_MIN_GAP_SAMPLES = 5

# ticks `ServingEngine.tick_records` keeps
_TICK_RECORDS = 512
# a tick record's segment -> the `tick` JSONL record's wall-split field
# (what is in no field is sched_s, the remainder)
_SEGMENT_OF = {"admit": "prefill_s", "prefill.dispatch": "prefill_s",
               "prefill.fetch": "prefill_s", "decode.dispatch": "decode_s",
               "decode.fetch": "fetch_s", "draft": "draft_s"}


class _TickSpan:
    """One named part of a tick, written twice from the same two instants:
    as a `tds.tick.<name>` span on the profiler's clock (dead without a
    session) and as (name, start, end) on time.monotonic() in the tick's
    record."""

    __slots__ = ("segments", "name", "ann", "t0")

    def __init__(self, segments: list, name: str, ids: dict):
        self.segments = segments
        self.name = name
        self.ann = profiling.span("tds.tick." + name, **ids)

    def __enter__(self):
        self.ann.__enter__()
        self.t0 = time.monotonic()
        return self

    def __exit__(self, *exc):
        self.segments.append((self.name, self.t0, time.monotonic()))
        self.ann.__exit__(*exc)


@dataclasses.dataclass(frozen=True)
class ServeConfig:
    """Engine knobs.  `num_blocks` * `block_tokens` is the pool's total
    token capacity shared by every concurrent request; `max_active` is
    the compiled decode step's slot count (occupancy ceiling)."""

    max_active: int = 4
    num_blocks: int = 32
    block_tokens: int = 16
    # paged-pool cache compression: None (rest at the model's
    # resolved_cache_dtype) | "int8" | "fp8" — blockwise-absmax per head
    # vector, scales per (block, token, layer, head); serving/pool.py
    quant: Optional[str] = None
    temperature: float = 0.0
    top_k: Optional[int] = None
    # sampling stops at this token when set (the token itself is kept,
    # so outputs stay comparable with fixed-length `generate` prefixes)
    eos_id: Optional[int] = None
    seed: int = 0
    # per-request length ceiling (prompt + generated), default the model
    # context.  This SIZES THE COMPILED STEP: block tables are
    # max_seq_tokens/block_tokens wide and each decode gathers that many
    # cache positions per slot, so a serving tier whose traffic is
    # bounded well under block_size should say so — a 256-context model
    # serving <=40-token requests would otherwise pay a 256-position
    # panel (6x the attention read) every token
    max_seq_tokens: Optional[int] = None
    # admission watermarks: submit() SHEDS (terminal status "shed",
    # never queued) when the queue already holds max_queue requests, or
    # when the pool is at shed_pool_util utilization with a backlog —
    # load shedding at the door instead of unbounded queue growth
    max_queue: Optional[int] = None
    shed_pool_util: Optional[float] = None
    # decode-health guard (serving/guard.py): per-tick non-finite logit
    # check + quarantine + warm-restart watchdog.  guard_k_restart =
    # consecutive poisoned ticks before the watchdog trips.
    health_guard: bool = True
    guard_k_restart: int = 3
    # per-tick `tick` record sampling cadence: an eventful tick (any
    # admission/eviction/preemption/shed/expiry/quarantine/restart)
    # always emits when a logger is attached; a quiet decode tick emits
    # every this-many ticks (0 = eventful ticks only) — bounded metrics
    # files on long-running servers
    tick_record_every: int = 16
    # serving flight recorder: ring capacity in ticks (0 disables);
    # flushed as one `flight` record on quarantine / watchdog restart /
    # shed burst / recover()
    flight_ticks: int = 64
    # sheds within one tick window that count as a "shed burst" and
    # trigger a flight flush (overload postmortems need the lead-up too)
    shed_burst: int = 3
    # speculative decoding (serving/spec.py): None = plain one-token
    # decode (the exact pre-spec programs); "ngram" = model-free
    # prompt-lookup drafter; "model:self" / "model:<preset>" = a small
    # same-family draft model with its own cache (serving/drafter.py).
    # Each tick the drafter proposes up to spec_k tokens per slot and
    # ONE verify pass through the target commits 1..spec_k+1 of them —
    # greedy output stays bit-identical to `generate` (acceptance is
    # token equality), temperature>0 stays target-exact and
    # deterministic under the (seed, position) keys.
    spec_draft: Optional[str] = None
    spec_k: int = 4
    # shared-prefix KV reuse (serving/prefix.py): admission walks a
    # radix tree of committed full blocks keyed by token prefix,
    # aliases matched blocks into the new request's block table
    # (refcounted — copy-on-write discipline: every writable block
    # stays private), and prefills only the unmatched suffix through a
    # span program riding the spec-verify attention.  Greedy output is
    # token-identical with the cache on or off; the tree keeps finished
    # requests' prompt blocks warm and yields them LRU under pool
    # pressure.  Does not compose with spec_draft (the suffix prefill
    # and the draft span both own the span path — refused loudly).
    prefix_cache: bool = False
    # paged-attention kernel dispatch (ops/paged_attn_pallas.py):
    # "auto" (default) runs the Pallas fused block-table-gather kernel
    # on TPU kernel targets and the XLA materialized-panel path
    # elsewhere; "on"/"off" force one arm — "off" is the byte-identical
    # pre-kernel program (the A/B baseline), "on" on a CPU mesh needs
    # the kernel's interpret mode (tests).  Applied at trace time to
    # every program this engine compiles (decode, spec verify, suffix
    # prefill), scoped so sibling engines' choices never mix.
    paged_kernel: str = "auto"
    # multi-tenant serving (serving/tenancy.py): {tenant: TenantPolicy}
    # swaps FIFO admission for weighted-fair stride scheduling with
    # per-tenant token budgets, door watermarks, and SLO-class default
    # deadlines; submit() takes tenant=.  Tenants NOT in the dict get
    # default policy (weight 1, no budget) — set it empty ({}) to tag
    # requests per tenant with everyone at defaults.
    tenants: Optional[Dict[str, TenantPolicy]] = None


class Request:
    """One generation request through its lifecycle:
    queued -> active -> done (possibly bouncing back to queued on
    preemption, warm restart, or journal recovery).  `status` is the
    terminal outcome: "ok" (finished), "shed" (never served — refused
    at the watermark or deadline-unmeetable in queue), "expired"
    (served but blew its deadline), "failed" (quarantined on
    non-finite decode logits).  Wall-clock marks use time.monotonic()."""

    _ids = itertools.count()

    def __init__(self, prompt: Sequence[int], max_new_tokens: int, *,
                 deadline_s: Optional[float] = None,
                 seed: Optional[int] = None, id: Optional[int] = None,
                 tenant: Optional[str] = None,
                 trace_id: Optional[str] = None):
        self.id = next(Request._ids) if id is None else int(id)
        # cross-engine trace correlation: stamped ONCE at submit and
        # carried through disagg migration (the object itself moves),
        # failover adoption (handles are reused), and journal recovery
        # (persisted on the submit line).  The default derives from the
        # id, so a pre-v15 journal replays to the SAME trace_id the
        # original submit stamped — correlation survives even journals
        # that predate the field.
        self.trace_id = (f"t{self.id:06d}" if trace_id is None
                         else str(trace_id))
        self.prompt = [int(t) for t in prompt]
        self.max_new_tokens = int(max_new_tokens)
        self.deadline_s = None if deadline_s is None else float(deadline_s)
        # multi-tenant serving: which tenant submitted this request
        # (None on untagged traffic) — drives the weighted-fair queue,
        # per-tenant watermarks/SLO class, and the record's attribution
        self.tenant = None if tenant is None else str(tenant)
        # shared-prefix cache accounting, cumulative over this
        # request's admissions: blocks aliased from the radix tree and
        # prompt tokens whose prefill those aliases avoided
        self.prefix_blocks = 0
        self.prefix_tokens = 0
        # per-request sampling seed: with temperature > 0, token i draws
        # from fold(fold(engine_base_key, seed), i) — deterministic
        # across preemption/restart/recovery (module docstring)
        self.seed = self.id if seed is None else int(seed)
        self.tokens: List[int] = []  # generated (includes eos when hit)
        # speculative-decoding accounting (stays 0 with spec off):
        # drafts proposed for / accepted into this request's sequence
        self.spec_proposed = 0
        self.spec_accepted = 0
        self.state = "queued"
        self.status: Optional[str] = None  # terminal: ok/shed/expired/failed
        self.finish_reason: Optional[str] = None
        self.preemptions = 0
        now = time.monotonic()
        self.t_arrival = now
        self.t_admitted: Optional[float] = None  # first admission
        self.t_first: Optional[float] = None
        self.t_done: Optional[float] = None
        self.active_s = 0.0  # completed active windows (preemptions)
        self.token_lat: List[float] = []  # per-token completion gaps
        self._journaled = False
        # lifecycle event timeline: (name, t_monotonic[, slot]) tuples,
        # serialized on the request record — trace_view.py's queue/slot
        # tracks are built from these
        self.events: List[tuple] = [("submitted", now)]
        # tail-latency attribution: the components PARTITION the terminal
        # latency — at any instant the request is in exactly one bucket
        # (waiting with a reason, prefilling, or decode-active), and
        # every transition closes one window with the same timestamp
        # that opens the next, so the sum telescopes to t_done-t_arrival
        self.lat_components = {"queue": 0.0, "prefill": 0.0,
                               "decode": 0.0, "preempt": 0.0,
                               "restart": 0.0, "migrate": 0.0}
        self._wait_since: Optional[float] = now
        self._wait_kind = "queue"
        self.last_slot: Optional[int] = None
        # disaggregated serving (fleet/disagg.py): the priced paged-KV
        # handoff this request paid — resting-dtype bytes moved between
        # the prefill and decode pools, and which link class carried
        # them ("ici" / "dcn", the wire_link_split granule logic).
        # Zero/None on single-engine paths; serialized on the request
        # record only when a migration happened.
        self.kv_migration_bytes = 0
        self.kv_migration_link: Optional[str] = None

    def event(self, name: str, t: float, slot: Optional[int] = None,
              replica: Optional[int] = None):
        """Append a lifecycle event.  `replica` stamps the CROSS-ENGINE
        markers (exported/imported/recovered/engine_lost) with the
        engine they left or arrived at, so one request's spans render
        on correlated per-replica tracks: a marker that leaves an
        engine (exported, engine_lost) attributes the events since the
        previous marker to its replica; one that arrives (imported,
        recovered) attributes the events after it.  Serialized as
        [name, t], [name, t, slot], or [name, t, slot, replica] —
        single-engine events keep their historical 2/3-tuple shape."""
        e: tuple = (name, t)
        if slot is not None or replica is not None:
            e += (slot,)
        if replica is not None:
            e += (replica,)
        self.events.append(e)

    @property
    def done(self) -> bool:
        return self.state == "done"

    @property
    def deadline(self) -> Optional[float]:
        """Absolute monotonic deadline (None = no SLO).  Recovered
        requests re-base on their recovery time — the original arrival
        clock died with the old process."""
        if self.deadline_s is None:
            return None
        return self.t_arrival + self.deadline_s


class _Slot:
    """An active request's device-side coordinates: its block table and
    current cache length (== the next write position)."""

    def __init__(self, req: Request, table: List[int], pos: int,
                 last_token: int, admitted_at: float,
                 prefill_s: float = 0.0,
                 summary: Optional[List[int]] = None):
        self.req = req
        self.table = table
        # a second table, of summary blocks, where the model keeps two
        # kinds of cache (models/evabyte.EvaLayout); else empty
        self.summary: List[int] = summary or []
        self.pos = pos
        self.last = last_token
        self.admitted_at = admitted_at
        # this admission's prefill wall — subtracted from the active
        # window when it closes, so the decode-active component never
        # double-counts the prefill component
        self.prefill_s = prefill_s

    @property
    def blocks(self) -> List[int]:
        """Every block the slot owns, of either table."""
        return self.table + self.summary


@dataclasses.dataclass
class _Step:
    """A decode step in flight: the slots it decodes as they were when it
    went out, its outputs on the device (`out`: tokens, the layout's
    `fetched` counts, health flags), when it was dispatched, what the
    layout counted of it (`counts` for the record of the tick that
    commits it, `ids` for the layout's span) and whether it went out a
    tick before that one."""

    slots: List[tuple]
    out: jax.Array
    logits: jax.Array
    t0: float
    counts: dict
    ids: dict
    ahead: bool


class _At(NamedTuple):
    """A slot as the step it is counted for sees it: one position on
    where the step in flight commits its next token first."""

    pos: int
    table: List[int]
    summary: List[int]


@dataclasses.dataclass
class KVHandoff:
    """One request in transit between two engines — the disaggregated
    prefill->decode migration unit (fleet/disagg.py).  `payload` holds
    the request's pool blocks in the SOURCE pool's resting dtype
    (quantized pools migrate 1-byte blocks + scales); `pos`/`last` are
    the slot coordinates the importing engine seats the request at."""

    req: Request
    payload: BlockPayload
    pos: int
    last: int
    block_tokens: int
    src_replica: Optional[int] = None


class ServingEngine:
    """Continuous-batching inference engine over one model + params.

    See the module docstring for the scheduling and fault-handling
    contract; the determinism guarantee (sampling keys from (request
    seed, position) only) is what makes preemption resume, warm restart,
    and `recover()` all token-exact — at temperature 0 AND above."""

    def __init__(self, model, params, config: ServeConfig = ServeConfig(),
                 *, telemetry=None, logger=None,
                 journal: Union[None, str, RequestJournal] = None,
                 replica_id: Optional[int] = None):
        if not getattr(model, "paged_decode_capable", False):
            raise ValueError(
                f"{type(model).__name__} does not support the paged "
                "decode step (paged_decode_capable=False) — a model "
                "that cannot batch slots at mixed positions (MoEGPT's "
                "static expert capacity is sized for one sequence "
                "length; dropless experts are served, models/mimo.py)"
            )
        c = model.config
        if c.block_size % config.block_tokens:
            raise ValueError(
                f"block_tokens={config.block_tokens} must divide the "
                f"model context block_size={c.block_size} (prefill "
                "buckets and block tables are block-multiples)"
            )
        if config.max_active < 1:
            raise ValueError("max_active must be >= 1")
        if config.prefix_cache and config.spec_draft is not None:
            raise ValueError(
                "prefix_cache does not compose with spec_draft: the "
                "suffix prefill and the draft span both own the span "
                "program, and the drafter's accept-or-residual commit "
                "is not wired through the suffix path — run one or "
                "the other"
            )
        # what a slot holds in the pool and how it fills its table row
        # is the model's to say (serving/pool.DenseLayout); what cannot
        # follow its cache yet is refused here, in the layout's words
        self._layout = model.paged_layout(
            config.max_seq_tokens or c.block_size, config.block_tokens)
        self.model = model
        self._refuse(*(feature for feature, on in (
            ("prefix_cache", config.prefix_cache),
            ("spec_draft", config.spec_draft is not None),
            ("quant", config.quant is not None)) if on))
        self.params = params
        self.config = config
        self.telemetry = telemetry
        self.logger = logger
        # live observability plane (telemetry/live.py): when attached,
        # each tick pushes the registry snapshot (host dicts only) into
        # the aggregator the /metrics exporter reads — opt-in, strictly
        # off the compiled path
        self.live = None
        # SLO error budgets (telemetry/slo.py): when attached, every
        # terminal request is observed and fast-burn alerts arm the
        # flight ring
        self.slo = None
        # fleet identity: stamped on this engine's request/tick records
        # when set (fleet/router.py, fleet/disagg.py) so one metrics
        # stream can carry a whole fleet; None keeps single-engine
        # records byte-compatible with pre-fleet readers
        self.replica_id = replica_id
        self._journal: Optional[RequestJournal] = None
        self.max_seq = config.max_seq_tokens or c.block_size
        if not 1 <= self.max_seq <= c.block_size:
            raise ValueError(
                f"max_seq_tokens={config.max_seq_tokens} must be in "
                f"[1, block_size={c.block_size}]"
            )
        # journal attach (property: stamps the serving geometry into the
        # file) — after max_seq so the stamp reflects the real geometry
        self.journal = journal
        # one block table row per slot, wide enough for a max_seq
        # request (both tables side by side where there are two);
        # unused entries point at scratch
        self.max_blocks_per_req = self._layout.width
        # the pool is built from the kinds of block the layout states.
        # A layout that states what a slot CAN hold bounds each kind,
        # whatever num_blocks says: a block beyond max_active slots'
        # worst case could never be allocated (a caller that sizes the
        # pool as slots x context / block_tokens would ask for 8 times
        # what a window and its summaries take)
        self._pool_args = dict(
            kinds=tuple(kind._replace(blocks=min(
                config.num_blocks, config.max_active * kind.blocks)
                if self._layout.bounds_pool else config.num_blocks)
                for kind in self._layout.kinds),
            block_tokens=config.block_tokens,
            dtype=resolved_cache_dtype(c), quant=config.quant,
        )
        self.pool = PagedKVPool(**self._pool_args)
        self._slots: List[Optional[_Slot]] = [None] * config.max_active
        # admission queue: plain FIFO, or the weighted-fair per-tenant
        # stride scheduler when tenants are configured
        self._queue: Union[Deque[Request], TenantQueue] = (
            TenantQueue(config.tenants) if config.tenants is not None
            else deque())
        # shared-prefix radix tree (None = cache off; rebuilt empty
        # with the pool on warm restart)
        self._prefix: Optional[PrefixCache] = (
            PrefixCache(config.block_tokens) if config.prefix_cache
            else None)
        self._guard = (DecodeHealthGuard(config.guard_k_restart)
                       if config.health_guard else None)
        self._ticks = 0
        self._evictions = 0
        self._shed = 0
        self._expired = 0
        self._quarantined = 0
        self._restarts = 0
        self._restarts_since_progress = 0
        # serving flight recorder (telemetry/flight.py ring reused with
        # tick entries): record() every tick, flush on fault triggers
        if config.flight_ticks:
            from ..telemetry.flight import FlightRecorder
            self._flight = FlightRecorder(config.flight_ticks)
        else:
            self._flight = None
        self._flight_reason: Optional[str] = None
        # the last ticks, one plain host record each, kept with or
        # without a logger: tick number, start `t0` and end `t1`
        # (time.monotonic()), `segments` [(name, start, end), ..] as the
        # tds.tick.* spans name them, `admitted` with each prefill
        # `buckets`, `active` slots, tokens `produced`.  `_record_tick`
        # and the flight ring read the newest; so can anyone else.
        self.tick_records: Deque[dict] = deque(maxlen=_TICK_RECORDS)
        self._tick: dict = {"tick": -1, "segments": [], "buckets": []}
        # the decode step dispatched a tick ahead (`_next_step`), which
        # the next tick commits.  Whatever changes what that step must do
        # between two ticks drops it (poison, export, recover, restart,
        # preemption), and the next tick dispatches its own: the dropped
        # step wrote only the K/V of each slot's committed last token at
        # its position, which the new one writes again, the same
        self._ahead: Optional[_Step] = None
        # when the last decode step's tokens reached the host
        self._t_fetch = 0.0
        self._tick_counts = dict.fromkeys(
            ("admitted", "evicted", "preempted", "expired",
             "quarantined", "restarted"), 0)
        self._shed_seen = 0
        # recent decode walls PER COMMITTED TOKEN: the measured
        # inter-token service price for deadline feasibility.  On the
        # plain path one tick commits one token per active slot, so the
        # entry is just the tick's decode wall; under speculation a
        # tick's wall divides by its per-slot token yield — the tick
        # walls go bimodal (draft+verify vs plain) and yield-dependent,
        # and pricing from the raw wall would over-fire shedding on
        # cheap high-acceptance ticks
        self._gap_hist: Deque[float] = deque(maxlen=128)
        # speculative-decoding accounting (engine lifetime)
        self._spec_proposed = 0
        self._spec_accepted = 0
        self._spec_ticks = 0
        self._spec_tokens = 0
        # chaos / fault-injection hooks (resilience/chaos.py)
        self._poison_pending: set = set()
        self._prefill_exc: Optional[BaseException] = None
        # (S, V) f32 of the last PLAIN decode tick (debug surface; a
        # speculative engine's verify logits are (S, K+1, V) and are
        # consumed in-program — it leaves this None)
        self.last_logits = None

        from ..ops.paged_attn_pallas import (
            PAGED_KERNEL_MODES, paged_kernel_forced,
        )
        if config.paged_kernel not in PAGED_KERNEL_MODES:
            raise ValueError(
                f"paged_kernel={config.paged_kernel!r} must be one of "
                f"{PAGED_KERNEL_MODES}"
            )

        def _kwrap(fn):
            """Bracket a compiled program's CALLS with this engine's
            paged-kernel mode: jit traces lazily at first call, so the
            trace-time gate reads the right mode, and later (cached)
            calls pay one no-op context enter.  "auto" skips the
            wrapper entirely — the default engine's call path (and its
            programs) stay byte-identical to the pre-kernel tier.
            Forced windows hold the module's mode lock, so two FORCED
            engines on parallel fleet threads serialize their calls
            instead of racing the trace-time gate; an "auto" engine
            lazily tracing a fresh shape bucket during a sibling's
            forced window remains a (documented) mixed-fleet hazard —
            don't mix forced and auto replicas in one parallel fleet."""
            if config.paged_kernel == "auto":
                return fn

            def call(*a, **kw):
                with paged_kernel_forced(config.paged_kernel):
                    return fn(*a, **kw)
            return call

        bt = config.block_tokens
        temp, top_k = config.temperature, config.top_k
        base_key = jax.random.PRNGKey(config.seed)

        # the programs' names are what a device trace's `XLA Modules` line
        # reads (`jit_tds_decode`, `jit_tds_prefill`), the named scopes
        # what its operations carry: utils/profiling.TABLE
        def tds_decode(params, stacked, view, tokens, prev, take, pos,
                       tables, seeds, nprod, poison):
            with jax.named_scope("tds.decode"):
                # a slot whose token the step before has not yet handed
                # to the host takes it from that step's output, `prev`,
                # still on the chip (`_next_step`); the others the host's
                tokens = jnp.where(take, prev[:tokens.shape[0]], tokens)
                x = model._embed_decode(params, tokens, pos)
                page = model.paged_page_ref(tables, pos, bt)
                # a model may count what its step did (the layout's
                # `fetched` names the counts): they ride behind the
                # tokens, in the one fetch the tick makes anyway
                x, view, *counted = model.paged_decode(stacked, x, view,
                                                       page)
                logits = model.head(params, x)[:, 0]
                # chaos operand: 0.0 off-path (tokens bit-identical —
                # x+0.0 never changes an argmax or a categorical draw),
                # NaN on a poisoned slot.  The per-slot health flag rides
                # behind the tokens, in the one fetch of the step.
                logits = logits + poison[:, None]
                bad = ~jnp.all(jnp.isfinite(logits), axis=-1)
                with jax.named_scope("tds.sample"):
                    nxt = sample_logits_per_slot(
                        model.sampling_logits(logits), base_key, seeds,
                        nprod, temp, top_k)
                out = jnp.concatenate(
                    [nxt, *(c.astype(nxt.dtype) for c in counted),
                     bad.astype(nxt.dtype)])
            return out, logits, view

        def tds_prefill(params, stacked, prompt, last_pos, block_ids,
                        view, seed, nprod):
            with jax.named_scope("tds.prefill"):
                logits, view = model.paged_prefill(
                    params, prompt, last_pos, block_ids, view, bt,
                    stacked=stacked,
                )
                with jax.named_scope("tds.sample"):
                    nxt = sample_logits_at(model.sampling_logits(logits),
                                           base_key, seed, nprod, temp,
                                           top_k)
            return nxt, view

        # the pool view is DONATED through both programs, so each step
        # writes into the pool's own buffers; and it rests in the shape
        # (blocks, bt, L * KVH * Dh) whose default device layout is the
        # one both programs index (serving/pool.py), so neither converts
        # it on entry or exit: donation alone could not spare that copy
        self._decode_fn = _kwrap(jax.jit(tds_decode, donate_argnums=(2,)))
        # `prev` of a step that takes every token from the host: the
        # shape of the program's first output, tokens, the layout's
        # counts and the health flags
        self._no_prev = jnp.zeros(
            (2 * config.max_active + len(self._layout.fetched),), jnp.int32)
        self._prefill_fn = _kwrap(
            jax.jit(tds_prefill, donate_argnums=(5,)))
        # "h.*" compute-dtype cast once — params are frozen while serving.
        # Not jitted: a tensor that already rests in compute dtype is
        # then handed on as it is, where a jitted cast would rest the
        # block weights a second time (2.2 GiB of evabyte-6.5b-6l in bf16)
        self._stacked = model.stacked_compute_params(params)
        # shared-prefix suffix prefill: when admission aliased m full
        # blocks, only the UNMATCHED suffix runs — a span program (the
        # spec-verify attention pointed at prefill): suffix tokens
        # embed at their absolute positions, attend to the aliased
        # prefix through the block tables plus themselves under the
        # windowed causal mask, the first token samples at the true
        # last prompt position, and the suffix K/V commits through
        # `paged_append_span` (pad offsets past `count` route to
        # scratch).  Compiled per power-of-two suffix bucket, exactly
        # like the full prefill's prompt buckets.
        if config.prefix_cache:
            block_size = c.block_size

            def tds_prefill_suffix(params, stacked, span, tables, pos0,
                                   last_off, count, view, seed, nprod):
                with jax.named_scope("tds.prefill"):
                    k1 = span.shape[1]
                    positions = jnp.minimum(
                        pos0[:, None] + jnp.arange(k1)[None, :],
                        block_size - 1)
                    x = model._embed_decode_span(params, span, positions)
                    page = page_ref(tables, pos0, bt)
                    x, sks, svs = model.paged_verify(stacked, x, view,
                                                     page)
                    logits = model.head(params, x,
                                        position=last_off)[:, 0]
                    with jax.named_scope("tds.sample"):
                        nxt = sample_logits_at(logits, base_key, seed,
                                               nprod, temp, top_k)
                    with jax.named_scope("tds.kv_write"):
                        view = paged_append_span(view, sks, svs, tables,
                                                 pos0, count, bt)
                return nxt, view

            self._prefill_suffix_fn = _kwrap(
                jax.jit(tds_prefill_suffix, donate_argnums=(7,)))
        else:
            self._prefill_suffix_fn = None
        # speculative decoding: the drafter + ONE compiled verify
        # program (serving/spec.py); imported lazily so the spec-off
        # engine's import graph — and its compiled programs — are
        # exactly the pre-spec ones
        if config.spec_draft is not None:
            from ..models.sampling import spec_prefill_commit
            from .spec import SpecDecoder
            self._spec = SpecDecoder(model, params, config, base_key,
                                     max_seq=self.max_seq)
            # a forced paged-kernel mode must cover EVERY compiled
            # program on the spec path, not just the engine's own: the
            # verify span program, and a model drafter's paged
            # prefill/rollout jits (they ride the same paged attention
            # and trace just as lazily) — otherwise a forced-"off"
            # A/B arm would still run the kernel inside the drafter
            self._spec._verify = _kwrap(self._spec._verify)
            for prog in ("_rollout", "_prefill"):
                if hasattr(self._spec.drafter, prog):
                    setattr(self._spec.drafter, prog,
                            _kwrap(getattr(self._spec.drafter, prog)))
            # the span horizon: growth/admission must own blocks out to
            # pos + spec_k so accepted drafts' K/V always land in-table
            self._span_k = config.spec_k

            def tds_prefill_spec(params, stacked, prompt, last_pos,
                                 block_ids, view, seed, nprod, prop):
                with jax.named_scope("tds.prefill"):
                    logits, view = model.paged_prefill(
                        params, prompt, last_pos, block_ids, view, bt,
                        stacked=stacked,
                    )
                    # a spec engine commits EVERY position through the
                    # one accept-or-residual rule — `prop` is the
                    # drafter's proposal for this position, so a
                    # re-admission (whose first token lands here instead
                    # of mid-verify) draws the same token the undisturbed
                    # run committed
                    with jax.named_scope("tds.sample"):
                        nxt = spec_prefill_commit(logits, prop, base_key,
                                                  seed, nprod, temp, top_k)
                return nxt, view

            self._prefill_fn = _kwrap(
                jax.jit(tds_prefill_spec, donate_argnums=(5,)))
        else:
            self._spec = None
            self._span_k = 0

    # -- public API ---------------------------------------------------------

    @property
    def journal(self) -> Optional[RequestJournal]:
        return self._journal

    @journal.setter
    def journal(self, j: Union[None, str, RequestJournal]) -> None:
        """Attach a request journal (path or instance) and stamp THIS
        engine's serving geometry into it — `recover()` validates that
        stamp against the recovering engine up front, so a journal
        replayed onto a mismatched sibling fails with both geometries
        named instead of deep inside pool scatter."""
        self._journal = RequestJournal(j) if isinstance(j, str) else j
        if self._journal is not None:
            self._journal.geometry(self._geometry())

    def _geometry(self) -> Dict[str, int]:
        """The compiled serving shapes replay-exactness depends on:
        a sibling engine must share ALL of these for a journal replay
        to re-prefill and continue token-exact."""
        c = self.model.config
        return dict(
            block_size=int(c.block_size),
            max_seq_tokens=int(self.max_seq),
            vocab=int(c.vocab_size),
            block_tokens=int(self.config.block_tokens),
        )

    def attach_slo(self, tracker) -> None:
        """Attach an SLO error-budget tracker (telemetry/slo.py): every
        terminal request is observed, fast burn arms the flight ring.
        A METHOD (not a bare attr) so chaos/fleet wrappers can fan it
        out — setattr on a delegating wrapper would strand the tracker
        on the wrapper while the inner engine reads its own None."""
        self.slo = tracker

    def attach_live(self, aggregator) -> None:
        """Attach a live-plane aggregator (telemetry/live.py): each
        tick pushes the registry snapshot for the /metrics exporter."""
        self.live = aggregator

    def submit(self, prompt: Sequence[int], max_new_tokens: int, *,
               deadline_s: Optional[float] = None,
               seed: Optional[int] = None,
               tenant: Optional[str] = None) -> Request:
        """Queue one request; returns its handle (tokens accumulate on
        it as ticks produce them).  `deadline_s` attaches a completion
        SLO (seconds from now); `seed` pins the temperature>0 sampling
        stream (default: the request id); `tenant` tags the request's
        owner when multi-tenancy is configured — its policy's SLO-class
        deadline applies when the request carries none, and its door
        watermark/budget/weight govern admission.  Above any admission
        watermark the request comes back already terminal with
        status "shed" — check `req.status`, not an exception: overload
        is an expected outcome, a malformed request is not (those still
        raise ValueError)."""
        with profiling.span("tds.submit"):
            return self._submit(prompt, max_new_tokens, deadline_s, seed,
                                tenant)

    def _submit(self, prompt, max_new_tokens, deadline_s, seed,
                tenant) -> Request:
        c = self.model.config
        if len(prompt) < 1 or max_new_tokens < 1:
            raise ValueError("need a non-empty prompt and >= 1 new token")
        total = len(prompt) + max_new_tokens
        if total > self.max_seq:
            raise ValueError(
                f"prompt {len(prompt)} + new {max_new_tokens} tokens > "
                + (f"max_seq_tokens {self.max_seq}"
                   if self.max_seq < c.block_size
                   else f"block_size {c.block_size}")
            )
        worst = sum(self._layout.need(total - 1))
        if worst > self.pool.num_usable:
            raise ValueError(
                f"request needs up to {worst} blocks but the pool has "
                f"{self.pool.num_usable} — raise num_blocks or shrink "
                "the request"
            )
        cfg = self.config
        if deadline_s is None and isinstance(self._queue, TenantQueue):
            # SLO class: the tenant's default completion deadline
            deadline_s = self._queue.policy(tenant).deadline_s
        req = Request(prompt, max_new_tokens, deadline_s=deadline_s,
                      seed=seed, tenant=tenant)
        self._count("serve_submitted")
        if isinstance(self._queue, TenantQueue):
            tq = self._queue.policy(tenant).max_queue
            if tq is not None and self._queue.depth(tenant) >= tq:
                # the isolation primitive: a flooding tenant's overflow
                # sheds at ITS OWN watermark and never reaches the
                # shared queue/pool
                self._queue.note_shed(tenant)
                self._shed_req(req, "tenant_queue_watermark")
                return req
        if cfg.max_queue is not None and len(self._queue) >= cfg.max_queue:
            self._shed_req(req, "queue_watermark")
            return req
        if (cfg.shed_pool_util is not None and self._queue
                # raw utilization first (O(1)): effective <= raw, so
                # the O(tree) reclaimable walk only runs when the raw
                # number already trips the watermark
                and (self.pool.blocks_in_use / self.pool.num_usable
                     >= cfg.shed_pool_util)
                and self._effective_pool_util() >= cfg.shed_pool_util):
            self._shed_req(req, "pool_watermark")
            return req
        if self.journal is not None:
            # admissions are durable at submit time (one fsync per
            # submit; token lines batch per tick) — a crash right after
            # submit() still replays the request
            self.journal.submit(req)
            req._journaled = True
            self.journal.commit()
        self._queue.append(req)
        return req

    def tick(self, *, decode: bool = True) -> int:
        """One scheduler step: enforce deadlines -> admit ->
        grow/preempt -> one decode step for every active slot ->
        quarantine/evict.  Returns the number of tokens produced
        (prefill first-tokens included).

        `decode=False` stops after admission — the PREFILL half of a
        disaggregated pair (fleet/disagg.py): prompts prefill into this
        engine's pool and first tokens sample, but no decode step runs;
        the admitted slots park until `export_request` hands them to a
        decode engine.

        Any exception out of the tick body (a poisoned pool view, a
        chaos-injected prefill failure) trips the watchdog warm restart
        when the health guard is on: in-flight requests re-queue
        front-of-line and continue token-exact.  `ServingKilled` (the
        chaos stand-in for process death) always propagates — a real
        kill leaves no engine to restart."""
        tick_i = self._ticks
        with profiling.span("tds.tick", tick=tick_i):
            t0 = time.monotonic()
            rec = self._tick = {"tick": tick_i, "t0": t0, "t1": t0,
                                "segments": [], "admitted": 0,
                                "buckets": [], "active": 0, "produced": 0}
            try:
                produced = self._tick_body(decode=decode)
            except ServingKilled:
                raise
            except Exception as e:
                if self._guard is None:
                    raise
                self._warm_restart(
                    f"tick exception: {type(e).__name__}: {e}")
                produced = 0
            if self.journal is not None:
                with self._span("commit"):
                    self.journal.commit()
            with self._span("observe"):
                self._ticks += 1
                if produced:
                    self._restarts_since_progress = 0
                rec["admitted"] = self._tick_counts["admitted"]
                rec["active"] = self.n_active
                rec["produced"] = produced
                self._update_gauges()
                self._record_tick(rec)
                if self.live is not None and self.telemetry is not None:
                    # push the tick's registry snapshot into the live
                    # plane: plain host dicts (floats), so the exporter
                    # thread can never reach a device value through the
                    # aggregator
                    self.live.ingest(self.telemetry.snapshot(),
                                     replica=self.replica_id)
            rec["t1"] = time.monotonic()
            self.tick_records.append(rec)
        return produced

    def _span(self, name: str, **ids) -> _TickSpan:
        """A part of the running tick: `tds.tick.<name>` in a trace, and
        (name, start, end) in the tick's record.  A request's span carries
        its id; the others the tick number."""
        if not ids:
            ids = {"tick": self._tick["tick"]}
        return _TickSpan(self._tick["segments"], name, ids)

    def _refuse(self, *features: str) -> None:
        """Raise for the first of `features` that the layout's cache
        cannot follow, with the layout's own sentence."""
        for feature in features:
            if feature in self._layout.refuses:
                raise ValueError(f"{type(self.model).__name__} cannot "
                                 + self._layout.refuses[feature])

    def drain(self, max_ticks: Optional[int] = None) -> int:
        """Tick until every submitted request is done; returns total
        tokens produced.  `max_ticks` bounds runaway loops in tests."""
        total = 0
        ticks = 0
        while self._queue or any(s is not None for s in self._slots):
            total += self.tick()
            ticks += 1
            if max_ticks is not None and ticks > max_ticks:
                raise RuntimeError(
                    f"drain exceeded {max_ticks} ticks with "
                    f"{len(self._queue)} queued"
                )
        return total

    def recover(self, journal: Union[None, str] = None, *,
                adopt: Optional[Dict[int, Request]] = None
                ) -> List[Request]:
        """Re-queue a crashed engine's in-flight requests from its
        journal, FRONT of the queue in their original admission order,
        each with the token prefix the journal had committed — they
        continue through the preemption resume path (re-prefill
        prompt + produced), token-exact under the (seed, position)
        sampling keys.  Requests the journal shows ALREADY finished —
        every token produced, or an eos in the prefix — but whose end
        line was torn away are closed out "ok" directly (re-queuing an
        eos-finished request would decode PAST its eos and diverge
        from the uninterrupted run).  Returns the
        re-queued handles.  Call on a FRESH engine built with the same
        model/params/config as the dead one (exactness needs the same
        programs); latency marks restart at recovery time.

        The journal's geometry stamp is validated against THIS engine
        up front — replay is only exact onto the same compiled shapes,
        and failover (fleet/failover.py) made the mismatched-sibling
        path load-bearing: without the check it fails deep inside pool
        scatter with no hint which side is wrong.

        Prefix cache: a recovering engine starts WARM-FROM-EMPTY — the
        radix tree indexed the dead engine's pool, which died with it.
        Replay is exact regardless (the cache only changes where K/V
        is read from, never the committed tokens), and the re-admitted
        requests re-warm the tree as they prefill.

        `adopt` maps request id -> an EXISTING Request handle to reuse
        (fleet failover: the dead replica's callers keep their handles
        — the sibling resets each to its committed prefix and continues
        it, so `submit`-returned objects survive engine loss).  When
        this engine journals to a DIFFERENT file than `journal`, every
        recovered request is re-journaled here (submit + committed
        prefix): the sibling's own journal stays self-contained for a
        second failure."""
        path = journal
        if path is None:
            if self.journal is None:
                raise ValueError(
                    "recover() needs a journal path (or an engine "
                    "constructed with journal=)"
                )
            path = self.journal.path
        geom = RequestJournal.read_geometry(path)
        if geom is not None:
            mine = self._geometry()
            bad = {k: (geom[k], mine[k]) for k in mine
                   if k in geom and geom[k] != mine[k]}
            if bad:
                raise ValueError(
                    "journal/engine geometry mismatch — replaying "
                    f"{path} onto this engine would fail inside pool "
                    "scatter (replay is only exact onto the same "
                    "compiled shapes): " + ", ".join(
                        f"{k}: journal={j} vs engine={e}"
                        for k, (j, e) in sorted(bad.items()))
                )
        # re-journal into a DIFFERENT journal than the one replayed:
        # the failover path, where the sibling's WAL must become
        # self-contained for the requests it adopts
        cross = (self.journal is not None
                 and os.path.abspath(self.journal.path)
                 != os.path.abspath(path))
        interrupted, done_ids = RequestJournal.replay(path)
        out: List[Request] = []
        max_seen = max(
            [e["id"] for e in interrupted] + done_ids, default=-1)
        for e in interrupted:
            req = adopt.get(e["id"]) if adopt else None
            if req is not None:
                # the caller's live handle: reset to the journal's
                # committed prefix (tokens past the last commit died
                # with the engine; re-decoding reproduces them exactly)
                # and keep its lifecycle/attribution history — the
                # abandon() that closed the dead engine already opened
                # the restart-overhead wait window
                now = time.monotonic()
                req.tokens = list(e["tokens"])
                # per-token latency entries past the committed prefix
                # belong to tokens that died with the engine — the
                # re-decode appends fresh ones
                req.token_lat = req.token_lat[:len(req.tokens)]
                req.state = "queued"
                req.status = None
                req.finish_reason = None
                if req._wait_since is None:
                    req._wait_since, req._wait_kind = now, "restart"
                req.event("recovered", now, replica=self.replica_id)
            else:
                req = Request(e["prompt"], e["max_new"],
                              deadline_s=e["deadline_s"], seed=e["seed"],
                              id=e["id"], tenant=e.get("tenant"),
                              trace_id=e.get("trace"))
                req.tokens = list(e["tokens"])
                # the wait from recovery to re-admission is restart
                # overhead, not queue wait: the crash-restart cycle (not
                # arrival pressure) is what the request is paying for
                req._wait_kind = "restart"
                req.event("recovered", req.t_arrival,
                          replica=self.replica_id)
            if cross:
                self.journal.submit(req)
                self.journal.tokens(req.id, req.tokens)
            req._journaled = self.journal is not None
            if self._finished(req):
                # finished before the crash (length OR eos) — only its
                # end line was lost; close it out, never re-queue
                self._terminal(req, "ok", req.finish_reason)
            else:
                out.append(req)
        for req in reversed(out):
            self._queue.appendleft(req)
        self._ahead = None
        # keep fresh ids clear of everything the journal ever issued
        nxt = next(Request._ids)
        Request._ids = itertools.count(max(nxt, max_seen + 1))
        self._count("serve_recovered", len(out))
        if self.journal is not None:
            self.journal.commit()  # the closed-out requests' end lines
        # postmortem marker: a fresh engine has no tick lead-up (it died
        # with the old process), but the flush stamps the recovery and
        # how many requests re-queued into the metrics stream
        if self._flight is not None and self.logger is not None:
            self._flight.flush(self.logger, "serve_recover",
                               at_step=self._ticks,
                               **({"replica_id": self.replica_id}
                                  if self.replica_id is not None else {}))
        return out

    # -- disaggregation hooks (fleet/disagg.py) -----------------------------

    def export_request(self, i: int) -> KVHandoff:
        """Pop active slot `i` and hand its request off WITH its paged
        K/V block contents — the source half of a disaggregated
        prefill->decode migration.  The payload leaves in the pool's
        resting dtype (a quantized pool migrates 1-byte blocks +
        scales, the same 4x compression it rests at); the slot's blocks
        return to this engine's free list immediately (the gather
        materialized fresh arrays).  The request re-opens a wait window
        — billed to migration-wait (`comp_migrate_s`) — until the
        importing engine seats it."""
        slot = self._slots[i]
        if slot is None:
            raise ValueError(f"slot {i} is empty — nothing to export")
        req = slot.req
        now = time.monotonic()
        self._refuse("export_request")
        self._ahead = None
        payload = export_blocks(self.pool.view, slot.table)
        self.pool.free_blocks(slot.blocks)
        self._slots[i] = None
        self._close_active(req, slot, now)
        req.state = "queued"
        # the window until the importing engine seats it is MIGRATION
        # wait, not queue wait: the request isn't contending for this
        # engine's slots, it's paying the cross-engine handoff — the
        # component serve_report's cross-engine tail attribution reads
        req._wait_since, req._wait_kind = now, "migrate"
        req.event("exported", now, i, replica=self.replica_id)
        return KVHandoff(req=req, payload=payload, pos=slot.pos,
                         last=slot.last,
                         block_tokens=self.config.block_tokens,
                         src_replica=self.replica_id)

    def can_import(self, n_blocks: int) -> bool:
        """Whether `import_request` of an `n_blocks` payload would seat
        right now — a free decode slot and enough free pool blocks.
        The disagg coordinator checks BEFORE exporting so a handoff is
        never left in limbo between two engines."""
        return (None in self._slots
                and self.pool.blocks_free >= n_blocks)

    def import_request(self, handoff: KVHandoff) -> bool:
        """Seat an exported request — the destination half of the
        migration: allocate blocks, scatter the payload into them, and
        occupy a decode slot at the handoff's (pos, last) coordinates,
        WITHOUT re-running prefill (the K/V moved instead).  Returns
        False (nothing consumed) when no slot or blocks are free;
        geometry/dtype mismatches between the pools raise with both
        sides named (serving/pool.import_blocks)."""
        self._refuse("import_request")
        if self._spec is not None:
            raise ValueError(
                "import_request on a speculative engine is unsupported "
                "— drafter state only rebuilds through the prefill "
                "admission path"
            )
        if handoff.block_tokens != self.config.block_tokens:
            raise ValueError(
                f"paged-KV migration geometry mismatch: payload blocks "
                f"hold {handoff.block_tokens} tokens but this engine's "
                f"hold {self.config.block_tokens}"
            )
        n = int(handoff.payload.k.shape[0])
        if n > self.max_blocks_per_req:
            raise ValueError(
                f"{n}-block payload exceeds this engine's "
                f"{self.max_blocks_per_req}-block table width "
                f"(max_seq_tokens={self.max_seq}) — source and "
                "destination engines must share max_seq_tokens"
            )
        try:
            slot_i = self._slots.index(None)
        except ValueError:
            return False
        ids = self.pool.alloc(n)
        if ids is None:
            return False
        self.pool.view = import_blocks(self.pool.view, ids,
                                       handoff.payload)
        req = handoff.req
        now = time.monotonic()
        if req._wait_since is not None:
            req.lat_components[req._wait_kind] += now - req._wait_since
            req._wait_since = None
        if req.t_admitted is None:
            req.t_admitted = now
        req.event("imported", now, slot_i, replica=self.replica_id)
        req.last_slot = slot_i
        req.state = "active"
        self._slots[slot_i] = _Slot(req, table=ids, pos=handoff.pos,
                                    last_token=handoff.last,
                                    admitted_at=now, prefill_s=0.0)
        self._count("serve_admissions")
        return True

    # -- fleet failover hooks (fleet/failover.py) ---------------------------

    def abandon(self) -> None:
        """Mark this engine DEAD after a fatal fault: close every active
        request's window (billed to restart-overhead — the engine, not
        the scheduler, took the slot away), clear the queue (the journal
        is the durable copy a sibling replays), and close the journal
        WITHOUT committing its buffer — an in-process death must look on
        disk exactly like a SIGKILL between append and fsync.  The pool
        is left as-is: it died with the engine."""
        now = time.monotonic()
        for i, s in enumerate(self._slots):
            if s is None:
                continue
            s.req.state = "queued"
            self._close_active(s.req, s, now)
            s.req._wait_since, s.req._wait_kind = now, "restart"
            s.req.event("engine_lost", now, i,
                        replica=self.replica_id)
        self._slots = [None] * self.config.max_active
        for req in self._queue:
            req.event("engine_lost", now, replica=self.replica_id)
        self._queue.clear()
        self._poison_pending.clear()
        self._ahead = None
        if self._journal is not None:
            self._journal.abandon()

    @property
    def n_active(self) -> int:
        return sum(s is not None for s in self._slots)

    @property
    def queue_depth(self) -> int:
        return len(self._queue)

    @property
    def restarts(self) -> int:
        return self._restarts

    def active_slots(self) -> List[int]:
        """Indices of occupied decode slots (chaos targets these)."""
        return [i for i, s in enumerate(self._slots) if s is not None]

    def active_block_tables(self) -> dict:
        """{request id: list of physical block ids} for every active
        slot — what the pool-accounting acceptance sums against
        pool.blocks_in_use at each tick."""
        return {s.req.id: list(s.table)
                for s in self._slots if s is not None}

    def poison_slot(self, i: int) -> None:
        """Arm a NaN poison on slot i's logits for the NEXT decode step
        (the chaos harness's slot-poison fault — resilience/chaos.py).
        The poison rides a per-slot operand that is 0.0 off-path, so an
        unpoisoned tick's tokens are bit-identical.  The fault model is
        SLOT-addressed (a bad device lane), not request-addressed: it
        hits whichever request occupies slot i at that decode step —
        which can differ from the occupant at arm time if the scheduler
        reseats the slot earlier in the same tick.  A tick that runs no
        decode step discards the arm rather than letting it linger.  A
        step already dispatched for the next tick is dropped, so that the
        step the arm hits is the one that tick commits."""
        if not 0 <= i < self.config.max_active:
            raise ValueError(f"slot {i} out of range")
        self._poison_pending.add(i)
        self._ahead = None

    def arm_prefill_exception(self, exc: BaseException) -> None:
        """Arm ONE exception raised at the next admission's prefill
        (chaos "prefill_raise"): the request re-queues, the watchdog
        warm-restarts."""
        self._prefill_exc = exc

    def _prefix_saved_bytes(self) -> int:
        """Pool bytes sharing is saving RIGHT NOW, measured from the
        refcounts: every holder beyond a block's first would need its
        own physical block without aliasing.  Block bytes come off the
        device arrays' dtypes (resting dtype + scales), not a model."""
        if self._prefix is None:
            return 0
        excess = sum(n - 1 for n in self.pool.ref_counts().values()
                     if n > 1)
        if not excess:
            return 0
        kb = self.pool.kv_bytes()
        total_blocks = self.pool.num_usable + 1  # + scratch
        return int(excess * kb["total_bytes"] / total_blocks)

    def prefix_stats(self) -> Optional[Dict]:
        """Shared-prefix cache outcomes (None with the cache off):
        hit rate = prompt tokens aliased / prompt tokens admitted,
        plus the raw counters and the measured bytes-of-pool saved."""
        if self._prefix is None:
            return None
        pc = self._prefix
        return {
            "hit_rate": round(
                pc.tokens_avoided / max(1, pc.prompt_tokens), 4),
            "hits": pc.hits, "misses": pc.misses,
            "blocks_aliased": pc.blocks_aliased,
            "prefill_tokens_avoided": pc.tokens_avoided,
            "prompt_tokens": pc.prompt_tokens,
            "cached_blocks": len(pc),
            "tree_evictions": pc.evicted,
            "pool_saved_bytes": self._prefix_saved_bytes(),
        }

    def tenant_stats(self) -> Optional[Dict]:
        """Per-tenant scheduler accounting (None without tenants):
        queued depth, admitted token cost, weight, door sheds, and
        budget utilization when a budget is configured."""
        if not isinstance(self._queue, TenantQueue):
            return None
        return self._queue.stats()

    def tenant_queue_full(self, tenant: Optional[str]) -> bool:
        """Whether a submit() for `tenant` would shed at its own door
        watermark right now — the fleet router's tenant-aware door
        check (fleet/router.py)."""
        if not isinstance(self._queue, TenantQueue):
            return False
        tq = self._queue.policy(tenant).max_queue
        return tq is not None and self._queue.depth(tenant) >= tq

    def describe(self) -> str:
        q = self.config.quant or str(jnp.dtype(self.pool.dtype))
        spec = (f", {self._spec.describe()}"
                if self._spec is not None else "")
        extras = ""
        if self._prefix is not None:
            extras += f", prefix_cache={len(self._prefix)} blocks"
        if isinstance(self._queue, TenantQueue):
            extras += f", tenants={len(self.config.tenants)}"
        return (
            f"serving(max_active={self.config.max_active}, "
            f"blocks={self.pool.num_usable}x"
            f"{self.config.block_tokens}, cache={q}, "
            f"guard={'on' if self._guard else 'off'}{spec}{extras})"
        )

    # -- scheduler internals ------------------------------------------------

    def _tick_body(self, decode: bool = True) -> int:
        with self._span("sched"):
            self._tick_counts = dict.fromkeys(self._tick_counts, 0)
            if isinstance(self._queue, TenantQueue):
                self._queue.on_tick()  # per-tenant budget accrual
            self._enforce_deadlines(time.monotonic())
            # growth first: existing slots claim the blocks their next
            # write needs BEFORE admission can take them — the other order
            # lets a fresh admission strand a grower, whose
            # preempt-youngest victim is then the just-prefilled request
            # (a full prefill thrown away per block boundary while the
            # pool is tight).  So do the next write's blocks of the slots
            # the step in flight carries on
            self._grow()
            if self._ahead is not None:
                self._grow_next(self._carried(self._ahead).values())
        produced = self._admit()
        active = [(i, s) for i, s in enumerate(self._slots)
                  if s is not None]
        if active and decode:
            if self._spec is not None:
                produced += self._decode_spec(active)
            else:
                produced += self._decode_plain(active)
        else:
            # no decode step ran: a poison armed for this tick must not
            # linger and hit whatever occupies the slot ticks later, nor
            # a step in flight be committed a tick late
            self._poison_pending.clear()
            self._ahead = None
        return produced

    def _slot_arrays(self, active, carried=frozenset()):
        """The decode/verify programs' per-slot operand vectors (empty
        slots carry scratch coordinates — branch-free, shape-stable).  A
        slot in `carried` is one position further on than the host has
        it: the step in flight commits its next token first, and the
        program takes that token from the step's output."""
        S = self.config.max_active
        tokens = np.zeros((S,), np.int32)
        pos = np.zeros((S,), np.int32)
        seeds = np.zeros((S,), np.int32)
        nprod = np.zeros((S,), np.int32)
        poison = np.zeros((S,), np.float32)
        tables = np.full((S, self.max_blocks_per_req), SCRATCH_BLOCK,
                         np.int32)
        fill_row = self._layout.fill_row
        for i, s in active:
            on = int(i in carried)
            tokens[i] = s.last
            pos[i] = s.pos + on
            seeds[i] = s.req.seed
            nprod[i] = len(s.req.tokens) + on
            fill_row(tables[i], s.table, s.summary)
        if self._poison_pending:
            for i in self._poison_pending:
                poison[i] = np.nan
            self._poison_pending.clear()
        return tokens, pos, seeds, nprod, poison, tables

    def _decode_plain(self, active) -> int:
        """One token for every active slot.  The step this tick commits
        is the one the tick before dispatched ahead, or, with none in
        flight (the first tick, a drop, a fallback) or none of its slots
        left, one dispatched now;
        and where the tick can tell what the step after it needs, that
        one goes out before this one is fetched (`_next_step`), so the
        chip runs it while the host commits this one and comes back for
        the next tick.  Spec off compiles and runs only this path."""
        step, self._ahead = self._ahead, None
        if step is None or not any(self._slots[i] is s
                                   for i, s in step.slots):
            step = self._dispatch(active)
        self._ahead = self._next_step(step)
        self._tick["ahead"] = int(self._ahead is not None)
        return self._commit(step)

    def _carried(self, step: _Step) -> Dict[int, _Slot]:
        """The slots of `step` that are still theirs and go on after it:
        all but those it brings to `max_new_tokens`, a count the host
        knows before the token.  One that `step` ends on eos is carried
        too, and the next step's token for it thrown away."""
        return {i: s for i, s in step.slots if self._slots[i] is s
                and len(s.req.tokens) + 1 < s.req.max_new_tokens}

    def _lacks(self, slot: _Slot, pos: int):
        """(the table of `slot` that lacks a block for a write at `pos`,
        the kind of block it draws from), or None where none does (a
        window ring that is whole never grows again)."""
        n_table, n_summary = self._layout.need(pos)
        if len(slot.table) < n_table:
            return slot.table, self._layout.tables[0]
        if len(slot.summary) < n_summary:
            return slot.summary, self._layout.tables[1]
        return None

    def _grow_next(self, slots) -> bool:
        """Each of `slots` owns the blocks its write one position on
        needs, taken without preemption; False where the pool has none
        to give (what could be taken stays taken)."""
        for s in slots:
            while (lacks := self._lacks(s, s.pos + 1)) is not None:
                table, kind = lacks
                ids = self._alloc(1, kind)
                if ids is None:
                    return False
                table.extend(ids)
        return True

    def _next_step(self, step: _Step) -> Optional[_Step]:
        """The step after `step`, dispatched before `step` is fetched, or
        None, and the next tick dispatches its own.  Not with the prefix
        tree, which commits a prompt's blocks to the tree at admission
        (speculation, whose yield a slot cannot know before the fetch,
        never comes here); and only once each slot carried on
        (`_carried`) owns its next write's blocks, taken without
        preempting anyone.  A slot seated since `step` went out takes its
        operands from the host."""
        if self._prefix is not None:
            return None
        carried = self._carried(step)
        if not self._grow_next(carried.values()):
            return None
        went = dict(step.slots)
        active = [(i, s) for i, s in enumerate(self._slots)
                  if s is not None and (i in carried or went.get(i) is not s)]
        if not active:
            return None
        return self._dispatch(active, carried.keys(), prev=step.out)

    def _dispatch(self, active, carried=frozenset(),
                  prev: Optional[jax.Array] = None) -> _Step:
        """Build a decode step's operands over `active` and dispatch it
        (async: the fetch is the sync, `_commit`).  `carried` slots take
        their token from `prev`, the output of the step in flight; with
        `prev`, the step goes out a tick ahead of the one that commits
        it, which its `tds.tick.decode.dispatch` span says (`ahead`)."""
        with self._operands_span(active, carried) as (counts, ids):
            tokens, pos, seeds, nprod, poison, tables = \
                self._slot_arrays(active, carried)
            take = np.zeros_like(tokens, bool)
            take[list(carried)] = True
        ahead = prev is not None
        with self._span("decode.dispatch", tick=self._tick["tick"],
                        ahead=int(ahead)) as disp:
            out, logits, view = self._decode_fn(
                self.params, self._stacked, self.pool.view, tokens,
                self._no_prev if prev is None else prev, take, pos,
                tables, seeds, nprod, poison,
            )
            self.pool.view = view
        return _Step(slots=list(active), out=out, logits=logits,
                     t0=disp.t0, counts=counts, ids=ids, ahead=ahead)

    def _commit(self, step: _Step) -> int:
        """Fetch `step` in one transfer (tokens, the layout's counts,
        health flags) and commit it to each slot it decoded that is still
        the same request's: tokens, finishes, guard, journal, the counts
        into the record of this tick, `last_logits`."""
        S = self.config.max_active
        lay = self._layout
        with self._span("decode.fetch", tick=self._tick["tick"],
                        ahead=int(step.ahead)):
            out = np.asarray(step.out)
            tnow = time.monotonic()
        # one step's wall: from its dispatch, or, where it went out before
        # the step ahead of it was fetched, from that fetch
        self._gap_hist.append(tnow - max(step.t0, self._t_fetch))
        self._t_fetch = tnow
        self.last_logits = step.logits
        fetched = dict(zip(lay.fetched, map(int, out[S:])))
        bad = out[S + len(lay.fetched):].astype(bool)
        self._tick.update(step.counts, **fetched)
        if lay.span != "decode.operands":
            # what the layout counted of the step, and what the program
            # counted of its own and handed back behind the tokens
            with self._span(lay.span, tick=self._tick["tick"], **step.ids,
                            **fetched):
                pass
        active = [(i, s) for i, s in step.slots if self._slots[i] is s]
        produced = 0
        with self._span("commit"):
            poisoned = (set(self._guard.observe(bad, [i for i, _ in
                                                      active]))
                        if self._guard is not None else set())
            for i, s in active:
                if i in poisoned:
                    self._quarantine(i, s)
                    continue
                t = int(out[i])
                s.pos += 1
                s.last = t
                self._append_token(s.req, t, tnow)
                if self.journal is not None:
                    self.journal.tokens(s.req.id, [t])
                produced += 1
                if self._finished(s.req):
                    self._finish(i, s)
            if self._guard is not None and self._guard.should_restart:
                self._warm_restart(
                    f"{self._guard.consecutive_poisoned} consecutive "
                    "poisoned decode ticks"
                )
        return produced

    def _decode_spec(self, active) -> int:
        """Speculative tick: drafter proposes up to K tokens per slot,
        ONE verify pass through the target scores all K+1 span
        positions, and 1..K+1 tokens commit per surviving slot.  Only
        VERIFIED tokens ever reach the request, the journal, or the
        pool (the verify program routes rejected-draft K/V to scratch);
        quarantine, the watchdog, and the deadline machinery see the
        same per-slot surface as the plain path."""
        k = self._spec.k
        produced = 0
        with self._span("draft") as draft:
            drafts = self._spec.propose(self._slots)  # (S, K+1) int32
        with self._operands_span(active) as (counts, _):
            tokens, pos, seeds, nprod, poison, tables = \
                self._slot_arrays(active)
            S = self.config.max_active
            # [head, d_1..d_K, extra]: columns 0..K are the scored span,
            # the trailing extra is the bonus position's proposal
            span = np.zeros((S, k + 2), np.int32)
            span[:, 0] = tokens
            span[:, 1:] = drafts
            # the last position whose K/V this request will ever need
            # (total-2: the final token's K/V is never read); -1 parks
            # empty slots at count 0 — every write routes to scratch
            limit_kv = np.full((S,), -1, np.int32)
            for i, s in active:
                limit_kv[i] = (len(s.req.prompt)
                               + s.req.max_new_tokens - 2)
        self._tick.update(counts, ahead=0)
        with self._span("decode.dispatch", tick=self._tick["tick"],
                        ahead=0):
            acc, final, bad, view = self._spec.verify(
                self.params, self._stacked, self.pool.view,
                span, pos, tables, seeds, nprod, limit_kv, poison,
            )
            self.pool.view = view
        with self._span("decode.fetch"):
            acc = np.asarray(acc)
            final = np.asarray(final)
            bad = np.asarray(bad)
            tnow = time.monotonic()
        with self._span("commit"):
            poisoned = (set(self._guard.observe(bad, [i for i, _ in
                                                      active]))
                        if self._guard is not None else set())
            eos = self.config.eos_id
            committed = 0
            for i, s in active:
                if i in poisoned:
                    self._quarantine(i, s)
                    continue
                n_acc = int(acc[i])
                toks = [int(t) for t in span[i, 1:1 + n_acc]]
                toks.append(int(final[i]))
                remaining = s.req.max_new_tokens - len(s.req.tokens)
                toks = toks[:remaining]
                if eos is not None and eos in toks:
                    toks = toks[:toks.index(eos) + 1]  # keep the eos
                s.req.spec_proposed += k
                s.req.spec_accepted += min(n_acc, len(toks))
                self._spec_proposed += k
                self._spec_accepted += min(n_acc, len(toks))
                for t in toks:
                    self._append_token(s.req, t, tnow)
                if self.journal is not None:
                    self.journal.tokens(s.req.id, toks)
                s.pos += len(toks)
                s.last = toks[-1]
                produced += len(toks)
                committed += len(toks)
                if self._finished(s.req):
                    self._finish(i, s)
            # deadline price: this tick's wall per COMMITTED token — the
            # draft+verify wall amortizes over the span yield, so a
            # high-acceptance tick prices CHEAPER per token than its raw
            # (bimodal) wall suggests
            wall = tnow - draft.t0
            if committed:
                self._gap_hist.append(wall * len(active) / committed)
                self._spec_ticks += 1
                self._spec_tokens += committed
            if self._guard is not None and self._guard.should_restart:
                self._warm_restart(
                    f"{self._guard.consecutive_poisoned} consecutive "
                    "poisoned decode ticks"
                )
        return produced

    def _gap_p50(self) -> Optional[float]:
        """Median measured decode wall PER COMMITTED TOKEN — the
        inter-token service price for deadline feasibility.  On the
        plain path each entry is a decode-tick wall (one token per slot
        per tick); under speculation each entry is the tick wall scaled
        by its per-slot token yield, so shedding prices the tokens
        actually delivered instead of over-firing on the bimodal
        draft+verify tick walls.  None until warm (a cold engine's
        first walls are XLA compiles, not service time)."""
        if len(self._gap_hist) < _MIN_GAP_SAMPLES:
            return None
        return float(np.median(np.asarray(self._gap_hist)))

    def _enforce_deadlines(self, now: float) -> None:
        """Shed queued requests that cannot meet their deadline; expire
        active ones that already blew it."""
        if self._queue and any(r.deadline is not None
                               for r in self._queue):
            gap = self._gap_p50()
            for req in list(self._queue):
                dl = req.deadline
                if dl is None:
                    continue
                reason = None
                if now >= dl:
                    reason = "deadline_overdue"
                else:
                    remaining = req.max_new_tokens - len(req.tokens)
                    # +1 tick for the prefill it still has to pay
                    if (gap is not None
                            and now + (remaining + 1) * gap > dl):
                        reason = "deadline_unmeetable"
                if reason is not None:
                    # remove() works on the plain deque AND the tenant
                    # queue (which keeps its per-tenant FIFOs intact)
                    self._queue.remove(req)
                    if isinstance(self._queue, TenantQueue):
                        self._queue.note_shed(req.tenant)
                    self._shed_req(req, reason)
        for i, s in enumerate(self._slots):
            if s is None:
                continue
            dl = s.req.deadline
            if dl is not None and now > dl:
                self._expire(i, s)

    def _bucket(self, p: int) -> int:
        """Prefill pad length: the smallest power-of-two multiple of
        block_tokens >= p (compiled prefill shapes stay O(log T))."""
        bt = self.config.block_tokens
        nb = -(-p // bt)
        b = 1
        while b < nb:
            b *= 2
        return min(b * bt, self.model.config.block_size)

    def _bucket_span(self, n: int) -> int:
        """Suffix-prefill pad length: the smallest power of two >= n
        (no block-multiple constraint — the span program commits
        through `count`, not a scatter panel)."""
        b = 1
        while b < n:
            b *= 2
        return min(b, self.model.config.block_size)

    def _prefill_operands(self, prompt_now: List[int], ids: List[int],
                          summary: Sequence[int] = ()):
        """The full-prompt prefill program's (padded prompt, block-id
        panel) operands — shared by the plain and spec admission
        paths.  The panel is the layout's: the table's entries, then
        the second table's where there is one (`prefill_panel`)."""
        p = len(prompt_now)
        bucket = self._bucket(p)
        padded = np.zeros((1, bucket), np.int32)
        padded[0, :p] = prompt_now
        nw, ns = self._layout.prefill_panel(bucket)
        block_ids = np.full((nw + ns,), SCRATCH_BLOCK, np.int32)
        # the prefill panel only spans the bucket; the +1 decode
        # block can lie past it (boundary p == bucket) — it is
        # reached through the slot table, not the prefill scatter
        k = min(len(ids), nw)
        block_ids[:k] = ids[:k]
        k = min(len(summary), ns)
        block_ids[nw:nw + k] = summary[:k]
        return padded, block_ids

    def _next_queued(self) -> Optional[Request]:
        """The next admission candidate: FIFO head, or the tenant
        queue's stride-selected request — None when requests are
        queued but every busy tenant is over budget this tick."""
        if isinstance(self._queue, TenantQueue):
            return self._queue.peek()
        return self._queue[0]

    def _pop_queued(self, req: Request) -> None:
        if isinstance(self._queue, TenantQueue):
            self._queue.pop(req)  # charges the tenant's pass + budget
        else:
            self._queue.popleft()

    def _alloc(self, n: int, kind: int = 0) -> Optional[List[int]]:
        """pool.alloc with prefix-tree reclaim: under pressure the
        radix tree yields its LRU unreferenced leaves (warm cache, no
        live holder) BEFORE the scheduler resorts to preemption —
        cached blocks are an optimization, never a reason to evict a
        running request."""
        ids = self.pool.alloc(n, kind)
        if ids is None and self._prefix is not None:
            if self._prefix.evict(self.pool,
                                  need=n - self.pool.blocks_free):
                ids = self.pool.alloc(n, kind)
        return ids

    def _effective_pool_util(self) -> float:
        """Pool utilization for the shed watermark: allocated blocks
        minus what the prefix tree could reclaim right now — a pool
        full of warm cache is not overloaded, and counting it would
        turn the cache itself into a shed trigger."""
        used = self.pool.blocks_in_use
        if self._prefix is not None:
            used -= self._prefix.reclaimable(self.pool)
        return used / self.pool.num_usable

    def _admit(self) -> int:
        """Admission: prefill queued requests into free slots while the
        pool can hold their prompts — FIFO (head-of-line blocking is
        deliberate: skipping ahead would starve long prompts), or the
        weighted-fair tenant schedule when tenants are configured.
        With the prefix cache on, admission first walks the radix tree:
        matched full blocks alias into the block table (refcounted)
        and only the unmatched suffix pays a prefill."""
        produced = 0
        while self._queue:
            try:
                slot_i = self._slots.index(None)
            except ValueError:
                break
            req = self._next_queued()
            if req is None:
                break  # every queued tenant over budget until next tick
            prompt_now = req.prompt + req.tokens  # preemption continuation
            p = len(prompt_now)
            bt = self.config.block_tokens
            # popped and stamped: a failure from here on puts it back
            taken = False
            try:
                with self._span("admit", request=req.id):
                    # shared-prefix match: alias at most (p-1)//bt full
                    # blocks — at least one prompt token always remains
                    # for the suffix program (which also samples the
                    # first token), and every block the request will
                    # WRITE stays private
                    alias: List[int] = []
                    if self._prefix is not None:
                        alias = self._prefix.match(
                            prompt_now, limit=(p - 1) // bt,
                            tick=self._ticks)
                        if alias:
                            # pin the aliased blocks (this table's
                            # refcount) BEFORE allocating: the fresh-block
                            # alloc may evict tree leaves, and a matched
                            # node must not be reclaimed out from under
                            # its own admission
                            self.pool.share(alias)
                    # blocks for the prompt AND its first decode write
                    # (position p): same count as ceil(p/bt) except when
                    # p lands exactly on a block boundary — without the
                    # extra block that first decode write would land in
                    # the scratch block (lost K/V), or need a _grow after
                    # admission that can preempt the admission itself.
                    # Under speculation the first write is a whole span
                    # (positions p..p+spec_k), so the horizon — clamped
                    # to the request's final position — replaces p: same
                    # worst-case block count as the plain path, claimed
                    # up front instead of across the first few grows
                    n_table, n_summary = self._layout.need(
                        self._write_horizon(req, p))
                    # each list from the kind of block the layout says
                    # it is of, both or neither
                    k_table, k_summary = self._layout.tables
                    ids_new = self._alloc(n_table - len(alias), k_table)
                    summary = (None if ids_new is None
                               else self._alloc(n_summary, k_summary))
                    if summary is None:
                        # roll back the table's blocks and the pin
                        self.pool.free_blocks(alias + (ids_new or []))
                        break
                    ids = alias + ids_new
                    self._pop_queued(req)
                    if self._prefill_exc is not None:
                        # chaos: the prefill "fails"; put everything back
                        # the way a real mid-admission fault would find
                        # it and let the watchdog take it from here
                        exc, self._prefill_exc = self._prefill_exc, None
                        self.pool.free_blocks(ids + summary)
                        if isinstance(self._queue, TenantQueue):
                            self._queue.refund(req)  # no work happened
                        self._queue.appendleft(req)
                        raise exc
                    t_adm = time.monotonic()
                    if req.t_admitted is None:
                        req.t_admitted = t_adm
                    # the wait window (queue / preempted-wait / restart-
                    # overhead, whichever re-queued it) closes at the
                    # same stamp the active window opens — the
                    # attribution components telescope
                    if req._wait_since is not None:
                        req.lat_components[req._wait_kind] += (
                            t_adm - req._wait_since)
                        req._wait_since = None
                    req.event("admitted", t_adm, slot_i)
                    req.last_slot = slot_i
                    taken = True
                    if alias:
                        # suffix prefill: the aliased blocks already hold
                        # positions < p0 — only the unmatched suffix
                        # runs, through the span program (padded to a
                        # power-of-two suffix bucket; pad offsets commit
                        # nothing)
                        p0 = len(alias) * bt
                        suffix = prompt_now[p0:]
                        bucket = self._bucket_span(len(suffix))
                        span = np.zeros((1, bucket), np.int32)
                        span[0, :len(suffix)] = suffix
                        tables = np.full((1, self.max_blocks_per_req),
                                         SCRATCH_BLOCK, np.int32)
                        self._layout.fill_row(tables[0], ids, summary)
                        fn, args = self._prefill_suffix_fn, (
                            self.params, self._stacked, span, tables,
                            np.asarray([p0], np.int32),
                            np.int32(p - 1 - p0),
                            np.asarray([len(suffix)], np.int32),
                            self.pool.view, np.int32(req.seed),
                            np.int32(len(req.tokens)),
                        )
                    else:
                        # under speculation the drafter rebuilds this
                        # slot's draft cache from the SAME committed
                        # prefix — the one admission path every resume
                        # (preemption, warm restart, recovery) rides, so
                        # drafter state never needs separate fault
                        # handling — and hands back its proposal for the
                        # first post-prefix position (the spec prefill's
                        # accept-or-residual operand)
                        prop = (() if self._spec is None else (np.int32(
                            self._spec.on_admit(slot_i, prompt_now)),))
                        padded, block_ids = self._prefill_operands(
                            prompt_now, ids, summary)
                        bucket = padded.shape[1]
                        fn, args = self._prefill_fn, (
                            self.params, self._stacked, padded, p - 1,
                            block_ids, self.pool.view, np.int32(req.seed),
                            np.int32(len(req.tokens)), *prop,
                        )
                with self._span("prefill.dispatch", request=req.id,
                                bucket=bucket, tokens=p):
                    nxt, view = fn(*args)
                    self.pool.view = view
                with self._span("prefill.fetch", request=req.id):
                    tok = int(np.asarray(nxt)[0])
            except Exception:
                if not taken:
                    raise
                # a REAL prefill failure (transient XLA error, wedged
                # view): put the request back exactly like the chaos
                # path does, or the watchdog's restart — which only
                # re-queues OCCUPIED slots — would drop it in a
                # non-terminal limbo forever.  Re-opening the wait
                # window at the admission stamp keeps the latency
                # partition telescoping (the aborted window bills to
                # the wait bucket it interrupted).
                self.pool.free_blocks(ids + summary)
                req.event("admission_aborted", time.monotonic(), slot_i)
                req._wait_since = t_adm
                if isinstance(self._queue, TenantQueue):
                    self._queue.refund(req)  # no work happened
                self._queue.appendleft(req)
                raise
            with self._span("commit", request=req.id):
                pf = time.monotonic() - t_adm
                self._tick["buckets"].append(bucket)
                req.lat_components["prefill"] += pf
                if self._prefix is not None:
                    # commit the prompt's full blocks to the radix tree
                    # — new nodes take their own refcount, which is what
                    # keeps them warm after this request's table frees
                    self._prefix.insert(prompt_now, ids[:p // bt],
                                        self.pool, tick=self._ticks)
                    self._prefix.note_admission(len(alias), p)
                    req.prefix_blocks += len(alias)
                    req.prefix_tokens += len(alias) * bt
                slot = _Slot(req, table=ids, pos=p, last_token=tok,
                             admitted_at=t_adm, prefill_s=pf,
                             summary=summary)
                self._slots[slot_i] = slot
                req.state = "active"
                self._count("serve_admissions")
                self._tick_counts["admitted"] += 1
                self._append_token(req, tok, time.monotonic())
                if self.journal is not None:
                    self.journal.tokens(req.id, [tok])
                produced += 1
                if self._finished(req):
                    self._finish(slot_i, slot)
        return produced

    def _write_horizon(self, req: Request, pos: int) -> int:
        """The furthest position this slot's NEXT decode step may write:
        `pos` on the plain path (byte-for-byte the pre-spec behavior),
        `pos + spec_k` under speculation (the whole draft span's K/V
        must land in owned blocks), clamped to the request's LAST
        WRITABLE position total-2 — the final token's K/V is never
        written (nothing attends past it; the verify program's
        limit_kv routes those offsets to scratch), so growing a block
        for it would burst the plain path's worst-case block count and
        preempt neighbors for storage nobody fills."""
        if not self._span_k:
            return pos
        total = len(req.prompt) + req.max_new_tokens
        return min(pos + self._span_k, total - 2)

    def _grow(self) -> None:
        """Allocate the next block for any slot whose write horizon
        crossed a block boundary; on exhaustion, preempt the youngest
        active request until the grower fits (or is itself preempted)."""
        for i, slot in enumerate(self._slots):
            if slot is None or self._slots[i] is not slot:
                continue
            while self._slots[i] is slot:
                lacks = self._lacks(slot, self._write_horizon(slot.req,
                                                              slot.pos))
                if lacks is None:
                    break
                table, kind = lacks
                # the prefix tree yields before preemption
                ids = self._alloc(1, kind)
                if ids is not None:
                    table.extend(ids)
                    continue
                victim_i, victim = max(
                    ((j, s) for j, s in enumerate(self._slots)
                     if s is not None),
                    key=lambda js: js[1].admitted_at,
                )
                self._preempt(victim_i, victim)

    def _close_active(self, req: Request, slot: _Slot,
                      now: float) -> None:
        """Close an active window at `now`: the decode-active component
        is the window minus this admission's prefill wall (already in
        the prefill component)."""
        win = now - slot.admitted_at
        req.active_s += win
        req.lat_components["decode"] += max(0.0, win - slot.prefill_s)

    def _preempt(self, i: int, slot: _Slot) -> None:
        req = slot.req
        now = time.monotonic()
        self.pool.free_blocks(slot.blocks)
        self._slots[i] = None
        self._ahead = None
        req.state = "queued"
        self._close_active(req, slot, now)
        req.preemptions += 1
        req._wait_since, req._wait_kind = now, "preempt"
        req.event("preempted", now, i)
        # front of the queue: it resumes (re-prefilling prompt + tokens
        # so far — an exact continuation under the (seed, position)
        # sampling keys) as soon as blocks free up
        self._queue.appendleft(req)
        self._count("serve_preemptions")
        self._tick_counts["preempted"] += 1

    def _warm_restart(self, reason: str) -> None:
        """Watchdog escalation: rebuild the pool and slot array, keep
        the compiled programs (same shapes/dtypes — no recompile),
        re-queue every in-flight request front-of-line with its
        produced prefix.  Raises after repeated restarts with zero
        progress between them — a fault the restart cannot clear must
        surface, not spin."""
        self._restarts += 1
        self._restarts_since_progress += 1
        if self._restarts_since_progress > 5:
            raise RuntimeError(
                f"serving engine warm-restarted "
                f"{self._restarts_since_progress} times without "
                f"producing a token (last reason: {reason}) — the fault "
                "is persistent; refusing to spin"
            )
        self._count("serve_restarts")
        now = time.monotonic()
        # oldest admission ends up frontmost (appendleft in reverse)
        occupied = sorted(
            ((i, s) for i, s in enumerate(self._slots) if s is not None),
            key=lambda js: js[1].admitted_at, reverse=True,
        )
        for i, s in occupied:
            s.req.state = "queued"
            self._close_active(s.req, s, now)
            s.req.preemptions += 1
            # restart-overhead, not preempted-wait: the engine (not pool
            # pressure) took the slot away — the attribution dashboard
            # must bill the watchdog, not the scheduler
            s.req._wait_since, s.req._wait_kind = now, "restart"
            s.req.event("restart_requeued", now, i)
            self._queue.appendleft(s.req)
        self._slots = [None] * self.config.max_active
        self._poison_pending.clear()
        self._ahead = None
        self.pool = PagedKVPool(**self._pool_args)
        if self._prefix is not None:
            # the tree indexes blocks of the pool that just died with
            # the restart — it rebuilds empty alongside (warm-from-
            # empty, same as journal recovery; lifetime stats carry on)
            old = self._prefix
            self._prefix = PrefixCache(self.config.block_tokens)
            for attr in ("hits", "misses", "blocks_aliased",
                         "tokens_avoided", "prompt_tokens", "evicted"):
                setattr(self._prefix, attr, getattr(old, attr))
        if self._guard is not None:
            self._guard.reset()
        self._tick_counts["restarted"] += 1
        self._arm_flight("serve_restart")
        if self.logger is not None:
            self.logger.log_meta(kind="fault", fault="serve_restart",
                                 at_step=self._ticks, action=reason)

    def _finished(self, req: Request) -> bool:
        if len(req.tokens) >= req.max_new_tokens:
            req.finish_reason = req.finish_reason or "length"
            return True
        eos = self.config.eos_id
        if eos is not None and req.tokens and req.tokens[-1] == eos:
            req.finish_reason = "eos"
            return True
        return False

    def _finish(self, i: int, slot: _Slot) -> None:
        req = slot.req
        now = time.monotonic()
        self.pool.free_blocks(slot.blocks)
        self._slots[i] = None
        self._evictions += 1
        self._count("serve_evictions")
        self._tick_counts["evicted"] += 1
        self._close_active(req, slot, now)
        self._terminal(req, "ok", req.finish_reason or "length",
                       now=now, slot=i)

    def _expire(self, i: int, slot: _Slot) -> None:
        req = slot.req
        now = time.monotonic()
        self.pool.free_blocks(slot.blocks)
        self._slots[i] = None
        self._expired += 1
        self._count("serve_expired")
        self._tick_counts["expired"] += 1
        self._close_active(req, slot, now)
        req.event("expired", now, i)
        self._terminal(req, "expired", "deadline", now=now, slot=i)

    def _quarantine(self, i: int, slot: _Slot) -> None:
        req = slot.req
        now = time.monotonic()
        self.pool.free_blocks(slot.blocks)
        self._slots[i] = None
        self._quarantined += 1
        self._count("serve_quarantined")
        self._tick_counts["quarantined"] += 1
        self._close_active(req, slot, now)
        req.event("quarantined", now, i)
        self._arm_flight("serve_quarantine")
        self._terminal(req, "failed", "nonfinite_logits", now=now, slot=i)

    def _shed_req(self, req: Request, reason: str) -> None:
        self._shed += 1
        self._count("serve_shed")
        self._terminal(req, "shed", f"shed:{reason}")

    def _terminal(self, req: Request, status: str, finish: str, *,
                  now: Optional[float] = None,
                  slot: Optional[int] = None) -> None:
        """The ONE exit for every request outcome: state, journal end
        line, JSONL `request` record with the terminal `status`.
        `now` is the timestamp the caller already closed its active
        window with — reusing it keeps the latency-component partition
        exact (sum(comp_*) == lat_s) instead of leaking the gap between
        two clock reads into neither bucket."""
        req.state = "done"
        req.status = status
        req.finish_reason = finish
        req.t_done = time.monotonic() if now is None else now
        if req._wait_since is not None:
            # terminal straight out of a wait (shed in queue, closed-out
            # recovery): the open wait window is the final component
            req.lat_components[req._wait_kind] += (
                req.t_done - req._wait_since)
            req._wait_since = None
        req.event(f"terminal:{status}", req.t_done, slot)
        if self.journal is not None and req._journaled:
            self.journal.end(req.id, status, finish)
        if self.slo is not None:
            # error-budget accounting observes every terminal outcome
            # (logger or not): good iff ok AND inside the objective's
            # latency bounds.  A fast-burn transition arms the flight
            # ring — the postmortem lands at the moment the budget
            # started dying — and persists an `slo` record.
            ttft = (None if req.t_first is None
                    else req.t_first - req.t_arrival)
            self.slo.observe(
                tenant=req.tenant, ok=(status == "ok"), ttft_s=ttft,
                latency_s=req.t_done - req.t_arrival,
                replica=self.replica_id, t=req.t_done)
            alerts = self.slo.check(t=req.t_done)
            if alerts:
                if any(a["kind"] == "fast_burn" for a in alerts):
                    self._arm_flight("slo_fast_burn")
                if self.logger is not None:
                    self.slo.record(self.logger, step=self._ticks)
        if self.logger is not None:
            comp = req.lat_components
            rec = dict(
                request_id=req.id,
                prompt_tokens=len(req.prompt),
                new_tokens=len(req.tokens),
                preemptions=req.preemptions,
                status=status,
                finish=finish,
                lat_s=round(req.t_done - req.t_arrival, 6),
                comp_queue_s=round(comp["queue"], 6),
                comp_prefill_s=round(comp["prefill"], 6),
                comp_decode_s=round(comp["decode"], 6),
                comp_preempt_s=round(comp["preempt"], 6),
                comp_restart_s=round(comp["restart"], 6),
                trace_id=req.trace_id,
                events=[[e[0], round(e[1], 6)] + list(e[2:])
                        for e in req.events],
            )
            if comp["migrate"]:
                # cross-engine handoff wait (disagg export -> import):
                # only migrated requests carry it, so single-engine
                # records keep the pre-v15 five-way partition
                rec["comp_migrate_s"] = round(comp["migrate"], 6)
            if req.last_slot is not None:
                rec["slot"] = req.last_slot
            if self.replica_id is not None:
                rec["replica_id"] = self.replica_id
            if req.kv_migration_bytes:
                # disaggregated handoff pricing: measured payload bytes
                # + which link class carried them (fleet/disagg.py)
                rec["kv_migration_bytes"] = int(req.kv_migration_bytes)
                rec["kv_migration_link"] = req.kv_migration_link or "ici"
            if self._spec is not None:
                # per-request speculation yield: drafts proposed for /
                # accepted into this sequence (accept rate = ratio)
                rec["spec_proposed"] = req.spec_proposed
                rec["spec_accepted"] = req.spec_accepted
            if req.tenant is not None:
                rec["tenant"] = req.tenant
            if self._prefix is not None:
                # shared-prefix yield, cumulative over admissions:
                # blocks aliased from the tree and the prompt tokens
                # whose prefill those aliases avoided
                rec["prefix_blocks"] = req.prefix_blocks
                rec["prefix_tokens"] = req.prefix_tokens
            if req.deadline_s is not None:
                rec["deadline_s"] = req.deadline_s
            if req.t_admitted is not None:
                rec["queue_s"] = round(req.t_admitted - req.t_arrival, 6)
            if req.t_first is not None:
                rec["ttft_s"] = round(req.t_first - req.t_arrival, 6)
            if req.tokens and req.active_s > 0:
                # rate over the ACTIVE windows only (each admission ->
                # preemption/terminal: prefill + decode) — queue waits
                # are reported by queue_s/preemptions, and folding them
                # in would collapse this into a duplicate of latency
                rec["decode_tokens_per_s"] = round(
                    len(req.tokens) / max(req.active_s, 1e-9), 3)
            self.logger.log_meta(kind="request", **rec)

    def _append_token(self, req: Request, tok: int, tnow: float) -> None:
        # per-token latency = gap since the previous token's completion
        # (arrival for the first — i.e. the first gap IS the TTFT)
        last_t = getattr(req, "_t_last", req.t_arrival)
        req.tokens.append(tok)
        req.token_lat.append(tnow - last_t)
        req._t_last = tnow
        if req.t_first is None:
            req.t_first = tnow
            if self.telemetry is not None:
                self.telemetry.histogram("serve_ttft_s").observe(
                    tnow - req.t_arrival)
        elif self.telemetry is not None:
            self.telemetry.histogram("serve_token_latency_s").observe(
                req.token_lat[-1])
        self._count("serve_tokens")

    def _count(self, name: str, n: int = 1) -> None:
        if self.telemetry is not None:
            self.telemetry.counter(name).inc(n)

    def _update_gauges(self) -> None:
        if self.telemetry is None:
            return
        t = self.telemetry
        # fleet replicas share one registry and tick in parallel: the
        # replica label keeps each engine's gauges on its OWN key
        # (serve_queue_depth{replica=0}) instead of last-writer-wins
        # over a shared one.  replica=None drops the label, so
        # single-engine runs keep their historical bare keys.
        rid = self.replica_id
        t.gauge("serve_batch_occupancy",
                self.n_active / self.config.max_active, replica=rid)
        t.gauge("serve_pool_utilization",
                self.pool.blocks_in_use / self.pool.num_usable,
                replica=rid)
        t.gauge("serve_queue_depth", float(len(self._queue)),
                replica=rid)
        t.gauge("serve_eviction_rate",
                self._evictions / max(1, self._ticks), replica=rid)
        t.gauge("serve_shed", float(self._shed), replica=rid)
        t.gauge("serve_expired", float(self._expired), replica=rid)
        t.gauge("serve_quarantined", float(self._quarantined),
                replica=rid)
        t.gauge("serve_restarts", float(self._restarts), replica=rid)
        if self._spec is not None:
            t.gauge("serve_spec_accept_rate",
                    self._spec_accepted / max(1, self._spec_proposed),
                    replica=rid)
            t.gauge("serve_spec_tokens_per_tick",
                    self._spec_tokens / max(1, self._spec_ticks),
                    replica=rid)
        if self._prefix is not None:
            pc = self._prefix
            t.gauge("serve_prefix_hit_rate",
                    pc.tokens_avoided / max(1, pc.prompt_tokens),
                    replica=rid)
            t.gauge("serve_prefix_blocks_aliased",
                    float(pc.blocks_aliased), replica=rid)
            t.gauge("serve_prefix_tokens_avoided",
                    float(pc.tokens_avoided), replica=rid)
            t.gauge("serve_prefix_cached_blocks", float(len(pc)),
                    replica=rid)
            t.gauge("serve_prefix_pool_saved_bytes",
                    float(self._prefix_saved_bytes()), replica=rid)
        if isinstance(self._queue, TenantQueue):
            active = {r.tenant for r in self._queue}
            active |= {s.req.tenant for s in self._slots
                       if s is not None}
            active.discard(None)
            t.gauge("serve_tenants_active", float(len(active)),
                    replica=rid)

    # -- per-tick time series + serving flight recorder ---------------------

    # flush-trigger precedence when several fire in one tick: the record
    # names the gravest one (a restart subsumes its quarantines)
    _FLIGHT_PRIORITY = {"serve_shed_burst": 1, "slo_fast_burn": 2,
                        "serve_quarantine": 2,
                        "serve_restart": 3, "serve_recover": 3}

    def _arm_flight(self, reason: str) -> None:
        cur = self._FLIGHT_PRIORITY.get(self._flight_reason, 0)
        if self._FLIGHT_PRIORITY[reason] > cur:
            self._flight_reason = reason

    @contextlib.contextmanager
    def _operands_span(self, active, carried=frozenset()):
        """`tds.tick.decode.operands`, around the building of a decode
        (or verify) step's operands; yields what the layout counts of the
        step's slots (`tick_counts`, a `carried` slot one position on):
        `counts` for the record of the tick that commits the step, `ids`
        for the span the layout names.  That is this one, or one of its
        own, which the commit opens after the fetch (`_commit`)."""
        lay = self._layout
        counts, ids = lay.tick_counts(
            [_At(s.pos + (i in carried), s.table, s.summary)
             for i, s in active], self.config.max_active)
        own = lay.span != "decode.operands"
        with self._span("decode.operands", tick=self._tick["tick"],
                        **({} if own else ids)):
            yield counts, ids

    def _record_tick(self, rec: dict) -> None:
        """End-of-tick bookkeeping, from the tick's own record (`rec`,
        the entry `tick_records` is about to take): append the tick
        entry to the flight ring (host dicts, no device sync), emit a
        `tick` JSONL record when the tick was eventful or the sampling
        cadence hit, and flush the flight ring if a fault trigger armed
        it this tick.

        The wall split sums the record's segments: prefill_s is each
        admission from its match to its first token on the host (admit +
        prefill.dispatch + prefill.fetch), decode_s the decode program's
        dispatch, fetch_s the token-fetch sync, draft_s the drafter;
        sched_s is the remainder — deadline enforcement, growth, operand
        building, commits, gauge updates.  A `tick` record also carries
        the segments themselves, `spans` = [[name, start - t_s, seconds],
        ..], for the timeline.  Submit-time sheds happen OUTSIDE ticks
        and land on the next tick's `shed` count.

        Without a logger none of this can ever be emitted (every flush
        path needs the sink), so it is skipped wholesale — a production
        engine with logging off pays nothing per tick, and the flight
        ring covers ticks from logger attach onward (serve_bench
        attaches AFTER warmup, so warm ticks stay out of postmortems by
        construction)."""
        if self.logger is None:
            self._flight_reason = None
            self._shed_seen = self._shed
            return
        tick_i, t0, produced = rec["tick"], rec["t0"], rec["produced"]
        wall = time.monotonic() - t0
        seg = dict.fromkeys(("prefill_s", "decode_s", "fetch_s",
                             "draft_s"), 0.0)
        for name, start, end in rec["segments"]:
            key = _SEGMENT_OF.get(name)
            if key is not None:
                seg[key] += end - start
        sched = max(0.0, wall - sum(seg.values()))
        shed_delta = self._shed - self._shed_seen
        self._shed_seen = self._shed
        if shed_delta >= self.config.shed_burst:
            self._arm_flight("serve_shed_burst")
        c = self._tick_counts
        counts = dict(c, shed=shed_delta, produced=produced)
        state = dict(
            occupancy=round(self.n_active / self.config.max_active, 4),
            pool_util=round(
                self.pool.blocks_in_use / self.pool.num_usable, 4),
            queue_depth=len(self._queue),
        )
        segments = dict(
            sched_s=round(sched, 6),
            prefill_s=round(seg["prefill_s"], 6),
            decode_s=round(seg["decode_s"], 6),
            fetch_s=round(seg["fetch_s"], 6),
        )
        # what the decode step's slots hold, by the kind of cache: the
        # paged kernel's grid, or a two-cache model's blocks
        counts.update((k, rec[k]) for k in (
            "kv_steps_live", "kv_steps", "window_blocks",
            "summary_blocks", "windows_rolled", "global_blocks",
            "pairs", "experts_touched", "ahead") if k in rec)
        if self._spec is not None:
            # the draft-vs-verify wall split: draft_s is the drafter's
            # proposal wall, decode_s+fetch_s the verify program's —
            # only spec runs emit the field, so spec-off tick records
            # are byte-identical to the pre-spec schema
            segments["draft_s"] = round(seg["draft_s"], 6)
        if self._flight is not None:
            # the ring reuses FlightRecorder's schema: the tick's state +
            # counts ride the `health` dict, the wall split `segments`
            self._flight.record(
                tick_i, step_s=wall,
                health={k: float(v) for k, v in
                        {**state, **counts}.items()},
                segments=segments,
            )
        eventful = any(counts[k] for k in
                       ("admitted", "evicted", "preempted", "shed",
                        "expired", "quarantined", "restarted"))
        every = self.config.tick_record_every
        sampled = bool(every) and tick_i % every == 0
        if eventful or sampled:
            extra = ({} if self.replica_id is None
                     else {"replica_id": self.replica_id})
            self.logger.log_meta(
                kind="tick", tick=tick_i,
                t_s=round(t0, 6), wall_s=round(wall, 6),
                **segments, **state, **counts, **extra,
                spans=[[name, round(start - t0, 6), round(end - start, 6)]
                       for name, start, end in rec["segments"]],
                emit="event" if eventful else "sample",
            )
        if self._flight_reason is not None:
            if self._flight is not None:
                # the flush carries the writer's replica so trace_view's
                # anchoring rule can pick among same-numbered ticks of a
                # SHARED fleet stream by key instead of file order
                self._flight.flush(self.logger, self._flight_reason,
                                   at_step=tick_i,
                                   **({"replica_id": self.replica_id}
                                      if self.replica_id is not None
                                      else {}))
            self._flight_reason = None
