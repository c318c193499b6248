"""Production serving subsystem: continuous batching over a paged KV
cache with chunked-prefill mixed steps, prefix caching and Pallas paged
attention kernels.

The static-batch engine (now the ``paged=False`` path of
:class:`ServeEngine`) allocates a dense ``(B, max_len, ...)`` KV cache
per call and decodes every request for the worst-case step count. This
package replaces that on the serving hot path:

=============  =====================================================
component      role
=============  =====================================================
slots          fixed decode-batch positions (``max_batch`` of them);
               a slot is FREE or ACTIVE (one request), evicted the
               step its request finishes (scheduler.py)
block pool     global per-layer KV tensors ``(num_blocks, Kh,
               block_size, dh)`` + a host-side refcounted free list; block 0
               is the reserved trash block dead rows write into
               (paged_cache.py, models/attention.init_paged_cache)
block tables   per-slot ``(nb,)`` int32 maps slot positions ->
               pool blocks; allocated atomically on admission
               (worst-case footprint), freed on completion — shared
               prefix blocks survive until their LAST holder frees
               them (refcounts)
scheduler      FCFS admission at tick granularity: queue -> free
               slot + blocks -> chunked prefill (FCFS chunk lanes,
               decode-priority token budget, starvation-bounded) ->
               decode until EOS / token budget / max_len
mixed step     ONE jitted call per tick (zoo.paged_mixed_step):
               ``max_batch`` decode rows + ``chunks_per_step`` prefill
               chunk lanes of ``chunk_size`` prompt tokens, a single
               compile signature (asserted via
               ``last_stats["compile_count"]``) — admissions never
               stall decodes and never mint new jit signatures.
               ``admission="prefill_on_join"`` keeps the pre-chunking
               per-admission B=1 prefill as the benchmark baseline
cache writes   ONE scatter per step for both lanes
               (models/attention.paged_row_write): every row writes
               its k/v at its absolute position in its slot's blocks;
               dead rows (free slots, idle lanes, padded chunk rows)
               land in the trash block
prefix cache   full prompt blocks indexed by content-chain hash
               (content + absolute position); admissions sharing a
               prompt prefix map them copy-free and skip their
               chunks; copy-on-write ONLY for the partial tail block
               (device-side block copy into the request's own
               block); freed blocks stay matchable until reallocated
               (paged_cache.py, ``prefix_hit_frac`` in engine stats)
attn kernels   decode rows: single-query block-table walk
               (kernels/decode_attention.py); chunk rows: q-tile x
               kv-block walk with causal masking against absolute
               positions (kernels/paged_prefill.py); XLA gather +
               masked-softmax oracles via ``ops.decode_attention`` /
               ``ops.prefill_attention``
MoE            dead rows masked out of routing entirely — expert
               FLOPs track live tokens; decode rows ride the sorted
               ragged dispatch, prefill chunks keep expert work dense
draft/verify   speculative decoding (``ServeConfig.draft`` != "none"):
               the dense parent sliced from the upcycled checkpoint
               (or a top-1 truncation) drafts ``spec_k`` tokens per
               slot against its own draft block lanes (doubled
               admission footprint, same pool), then the MoE scores
               all ``k+1`` positions per slot as verify lanes on the
               ONE mixed-step signature (zoo.paged_verify_step);
               exact rejection sampling (speculative.verify_accept)
               keeps outputs identical to vanilla — greedy ==
               vanilla token-for-token, ``q == p`` accepts at 1.0
               (``acceptance_rate`` / ``spec_drafted`` /
               ``spec_accepted`` in engine stats and per-request
               records)
in-flight      same-tick admissions sharing a prompt prefix map the
prefix map     donor's still-being-written full blocks immediately
               (scheduler ``_inflight``): pending until the donor's
               computed length passes each block's end, then promoted
               without burning chunk lanes; a dead donor
               preempts-and-requeues the follower. Hits surface in
               ``prefix_hit_frac`` / ``inflight_promotions``
sessions       ``ServeEngine.open_session`` returns a tick-steppable
               :class:`ChunkedSession` (solo ``serve()`` = open +
               submit + ``while tick()`` + ``close()``); sessions
               expose per-tick routing signals, mid-flight
               submit/cancel/queue-extraction, and a fleet mode whose
               clock advances exactly one tick per call (fleet.py)
fleet          :class:`Fleet` (fleet.py) drives N engine replicas as
               tick-interleaved sessions on one global clock behind a
               health-checked weighted least-loaded router
               (router.py): per-engine ``live`` / ``degraded`` /
               ``draining`` / ``dead`` from heartbeat age + engine
               signals; shed/failed retried with capped backoff;
               optional hedged re-dispatch for stragglers; failover
               migrates a dead engine's work to survivors with saved
               progress; per-tick JSONL signal timeline
               (router.TimelineWriter documents the schema)
autoscaling    :class:`Autoscaler` (fleet.py, ``FleetConfig.autoscale``
               = :class:`AutoscaleConfig`): sustained overload (mean
               occupancy / dispatchable backlog / shed-retry delta
               over ``up_ticks`` consecutive fleet ticks) spawns a
               replica via ``restart_factory``; sustained idleness
               (``down_ticks``) drains the highest-eid replica
               through the leak-checked retire path; decisions read
               ONLY exported per-tick signals on the fleet tick
               clock — no wall-clock, so seeded runs are replayable
observability  :class:`repro.obs.Tracker` rows through every layer
               (obs/README.md is the metric + row-schema reference):
               per-tick ``kind="engine"`` rows (occupancy,
               free_blocks, queue_depth, active, decoding,
               stall_ticks, tokens, mixed_steps, compiles — tagged
               ``engine=<eid>`` in fleet mode), per-tick
               ``kind="fleet"`` rows (tick, engines{eid: status/load/
               signals}, fleet{pending, inflight, finished, tokens,
               replicas, migrations, retries, hedges, scale_ups,
               scale_downs}), spans timing tick phases (admission /
               prefix / draft / mixed_step / host_sync / emit), and
               scheduler/checkpoint counters — all host-side reads
               of state the tick loop already owns (ZERO extra
               device syncs; ``compile_count == 1`` still holds).
               TimelineWriter is now a kind-filtered JSONL sink of
               this protocol, so engine + fleet rows share one file
               and one schema
=============  =====================================================

Request lifecycle::

    submit -> queued -> [slot + blocks free, arrival reached]
           -> prefix match (shared full blocks mapped copy-free,
              partial tail copy-on-write)
           -> chunked prefill (chunk lanes ride the mixed step while
              every decoding slot keeps decoding)
           -> first token from the final chunk's last-position logits
           -> decode (one token per tick, streamed via ``on_token``)
           -> finish (EOS / budget / max_len) -> blocks released
              (shared prefix blocks stay for other holders / the
              prefix index), slot admits the next queued request

Failure modes (the robustness layer; all knobs on :class:`ServeConfig`,
chunked admission only, every path exercised by tests/test_serve_chaos
and the seeded :class:`ChaosConfig` fault injector; lifecycle events
stream via ``serve(on_event=...)`` and per-request ``on_event``):

==========  ========================  =======================  ==========
mode        trigger                   policy                   status
==========  ========================  =======================  ==========
overload    visible queue over        ``queue_policy``:        ``shed``
            ``queue_limit``; pool     ``block`` waits;
            occupancy >=              ``shed-newest`` /
            ``shed_occupancy``; head  ``shed-oldest`` drop by
            block-starved >=          age to the bound, and
            ``shed_stall_ticks``      refuse arrivals while
            consecutive ticks         the signal is up
deadline    no first token by         request evicted (queued  ``timeout``
            arrival +                 or mid-flight; blocks
            ``ttft_deadline``; not    freed), reason ``ttft``
            finished by arrival +     or ``deadline``; checked
            ``deadline``              once per tick, zero
                                      extra host syncs
preemption  pool exhaustion with a    ``preempt=True``: evict  (not
            strictly-higher-priority  youngest lower-priority  terminal;
            admission (or a chaos     active slot, register    requeued +
            eviction)                 its computed blocks in   ``preempt-
            .                         the prefix index, free   ed-re-
            .                         + requeue; re-admission  queued``
            .                         recovers them copy-free  event)
            .                         so only the uncached
            .                         tail re-prefills
watchdog    request footprint >       fail the request with a  ``failed``
            pool capacity             diagnostic — at
            (structural), or a        admission for the
            visible head making       structural case, after
            zero progress for         ``watchdog_ticks``
            ``watchdog_ticks``        zero-progress ticks
            .                         otherwise — instead of
            .                         spinning forever
engine      chaos kill, or            fleet failover: migrate  (not
death       heartbeat age >=          queued + active work to  terminal;
            ``hb_dead`` fleet ticks   survivors with saved     counted in
            (FleetChaosConfig         progress (resume         per-request
            kills / kill_prob)        records re-prefill       ``migra-
            .                         prompt + generated and   tions``)
            .                         continue token-
            .                         identically); no audits
            .                         or leak checks on dead
            .                         memory
heartbeat   heartbeats suppressed     same failover — a false  (not
loss        ``hb_loss_ticks`` while   positive costs a         terminal)
            the engine still runs     migration, never a
            (FleetChaosConfig         duplicate token: a dead-
            hb_loss_prob)             declared engine is
            .                         never ticked again
hedge race  no new token for          duplicate copy on a      ``cancel-
            ``hedge_after`` ticks     second replica; same     led``
            (slow engine,             (rid, generated)         (engine-
            FleetChaosConfig          sampling key makes both  local
            slow_prob)                streams identical;       only)
            .                         first completion wins,
            .                         losers cancelled,
            .                         blocks freed
drain       operator                  no NEW admissions;       (not
            ``fleet.drain(eid)``      queued work migrates     terminal)
            .                         immediately, in-flight
            .                         finishes, then the
            .                         replica retires through
            .                         the full close() checks
            .                         (block-leak audit)
==========  ========================  =======================  ==========

Every submitted request ends in exactly ONE terminal status —
``completed`` / ``shed`` / ``timeout`` / ``failed`` (in
``stats[rid]["status"]``; preemptions are counted per request, not
terminal) — and ``BlockPool.check_invariants`` audits refcounts vs
block tables at every tick boundary under chaos/test. A fleet
preserves the contract fleet-WIDE: engine-local ``shed`` / ``failed``
are retried elsewhere (terminal only once the retry budget is spent),
``cancelled`` marks a raced-out duplicate copy and never surfaces, and
``Fleet.run`` asserts exactly one fleet-terminal record per request
(``timeout`` is a user contract — absolute deadlines ride through
migration un-reset and are never retried).

``repro.training.serve`` re-exports :class:`ServeConfig` /
:class:`ServeEngine` for back-compat.
"""
from repro.serve.engine import (
    ChaosConfig,
    ChunkedSession,
    ServeConfig,
    ServeEngine,
)
from repro.serve.fleet import (
    AutoscaleConfig,
    Autoscaler,
    Fleet,
    FleetChaosConfig,
    FleetConfig,
)
from repro.serve.paged_cache import (
    BlockPool,
    PrefixMatch,
    blocks_needed,
    bucket_len,
)
from repro.serve.router import Router, RouterConfig, TimelineWriter
from repro.serve.scheduler import Request, Scheduler, Slot
from repro.serve.speculative import SpecRunner, sample_token, verify_accept

__all__ = [
    "AutoscaleConfig",
    "Autoscaler",
    "BlockPool",
    "ChaosConfig",
    "ChunkedSession",
    "Fleet",
    "FleetChaosConfig",
    "FleetConfig",
    "PrefixMatch",
    "Request",
    "Router",
    "RouterConfig",
    "Scheduler",
    "ServeConfig",
    "ServeEngine",
    "Slot",
    "SpecRunner",
    "TimelineWriter",
    "blocks_needed",
    "bucket_len",
    "sample_token",
    "verify_accept",
]
