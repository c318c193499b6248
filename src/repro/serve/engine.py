"""Serving engines: static-batch (legacy) and paged continuous batching.

``ServeEngine`` keeps the original static-batch contract — ``generate``
packs requests into one fixed batch, prefills the right-padded prompts
and steps the decode loop over a dense ``(B, max_len, ...)`` KV cache.
With ``ServeConfig(paged=True)`` the same class runs the production
path instead:

* **paged KV cache** — per-layer global block pools + per-slot block
  tables (models/attention, repro.serve.paged_cache); attention reads
  scale with each sequence's live blocks, not ``max_len``.
* **continuous batching** — a fixed array of decode slots; finished
  sequences are evicted mid-flight (their blocks return to the pool)
  and queued requests are admitted the moment a slot and blocks free
  up (scheduler.py).
* **chunked-prefill mixed step** (``admission="chunked"``, the
  default) — every tick runs ONE jitted call carrying a fixed token
  budget: one decode row per slot plus ``chunks_per_step`` prefill
  chunk lanes of ``chunk_size`` prompt tokens (zoo.paged_mixed_step).
  Admissions never stall decodes and never mint new jit signatures —
  the engine asserts a SINGLE compiled signature for the step function
  (``last_stats["compile_count"]``), killing the bucketed-length
  per-admission prefill of ``admission="prefill_on_join"`` (kept as
  the pre-chunking baseline for benchmarks/serve_bench.py).
* **prefix caching** — the refcounted BlockPool indexes full prompt
  blocks by content-chain hash; admissions sharing a prompt prefix map
  those blocks copy-free (copy-on-write only for the partial tail
  block) and skip their prefill chunks entirely
  (``last_stats["prefix_hit_frac"]``).
* **Pallas kernels** — ``ApplyCfg(attn_impl="pallas")`` routes decode
  rows through the paged flash-decode kernel
  (kernels/decode_attention.py) and chunk rows through the paged
  prefill kernel (kernels/paged_prefill.py); "xla"/"auto"-on-CPU uses
  the gather oracles.
* **live-token MoE** — dead rows (free slots, idle chunk lanes, padded
  chunk rows) are masked out of routing entirely, so expert FLOPs
  track live tokens; prefill chunks keep expert work dense while
  decode rows ride the sorted ragged dispatch.

Decode routing stays Top-K token-choice (paper §3.1) — and, exactly as
the static engine's docstring warned, token-choice capacity can couple a
token's routing to its batch, so production decode should run dropless
(capacity_factor >= num_experts); the continuous-batching identity tests
pin that regime.
"""
from __future__ import annotations

import contextlib
import dataclasses
import time
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ArchConfig
from repro.models import model_zoo as zoo
from repro.obs.tracker import NULL, Tracker
from repro.serve.paged_cache import BlockPool, bucket_len
from repro.serve.scheduler import Request, Scheduler
from repro.serve.speculative import sample_token, verify_accept
from repro.sharding import ShardCtx


@dataclasses.dataclass(frozen=True)
class ChaosConfig:
    """Seeded, deterministic fault injection for the chunked serve loop.

    Every probability is evaluated once per tick from a single
    ``np.random.default_rng(seed)`` stream, so a (trace, ChaosConfig)
    pair replays the exact same fault schedule — failures found by the
    chaos sweep are reproducible by seed. All faults are host-side
    (scheduler/pool state); the device never sees them except as
    different admission patterns.
    """

    seed: int = 0
    # Random eviction: preempt-and-requeue a random ACTIVE slot.
    evict_prob: float = 0.0
    # Pool exhaustion: grab random free blocks for hold_ticks ticks.
    hold_prob: float = 0.0
    hold_max_blocks: int = 4
    hold_ticks: int = 3
    # Admission burst: inject burst_size synthetic requests at once.
    burst_prob: float = 0.0
    burst_size: int = 2
    burst_plen: int = 12
    burst_max_new: int = 4
    burst_priority: int = 0
    rid_base: int = 1 << 30  # synthetic rids start here — keep real rids below
    # Deadline storm: clamp every queued request's TTFT deadline.
    storm_prob: float = 0.0
    storm_ttft: int = 2


@dataclasses.dataclass
class ServeConfig:
    max_batch: int = 8
    max_len: int = 256
    temperature: float = 0.0  # 0 => greedy
    cache_dtype: str = "float32"
    # --- paged continuous-batching engine -------------------------------
    paged: bool = False
    block_size: int = 16  # KV tokens per pool block
    # 0 => auto: 1 trash block + max_batch * ceil(max_len / block_size)
    # (full capacity — admission never waits on blocks, only on slots).
    num_blocks: int = 0
    # Default EOS token for requests that don't set their own (None =
    # run to the token budget).
    eos_id: Optional[int] = None
    # --- admission path -------------------------------------------------
    # "chunked": ONE jitted mixed step per tick (decode rows + prefill
    # chunk lanes, single compile signature). "prefill_on_join": the
    # pre-chunking baseline — one bucketed B=1 prefill call per
    # admission that stalls in-flight decodes.
    admission: str = "chunked"
    chunk_size: int = 32  # prompt tokens per prefill chunk lane
    chunks_per_step: int = 1  # chunk lanes per mixed step
    # Content-hash prefix reuse across admissions (chunked mode only).
    prefix_cache: bool = True
    # --- robustness (chunked mode only; all off by default) --------------
    # Bounded wait queue: max VISIBLE (arrived, unadmitted) requests.
    # 0 = unbounded. Policy "block" waits indefinitely; "shed-newest" /
    # "shed-oldest" shed to the bound and while overloaded.
    queue_limit: int = 0
    queue_policy: str = "block"
    # Overload signals driving load shedding (with a shed-* policy):
    # pool occupancy fraction >= shed_occupancy, or the best visible
    # request block-starved for >= shed_stall_ticks consecutive ticks.
    shed_occupancy: Optional[float] = None
    shed_stall_ticks: int = 0  # 0 = off
    # Preempt-and-requeue: under pool exhaustion evict the youngest
    # strictly-lower-priority active request instead of waiting.
    preempt: bool = False
    # Default deadlines (ticks after arrival) for requests that don't
    # set their own; exceeded -> terminal status "timeout".
    default_ttft_deadline: Optional[int] = None
    default_deadline: Optional[int] = None
    # Stuck-tick watchdog: after this many zero-progress ticks with a
    # visible queue head, fail that request with a diagnostic instead
    # of spinning forever (a request whose worst-case footprint exceeds
    # the whole pool fails immediately at admission).
    watchdog_ticks: int = 32
    # --- speculative decoding (chunked mode only) -----------------------
    # draft != "none" arms speculation: a draft model drafts spec_k
    # tokens per decoding slot against private paged lanes, the target
    # verifies all spec_k + 1 positions in ONE pass (verify rows are
    # chunk lanes) and exact rejection sampling keeps the output
    # distribution identical to vanilla decoding. "dense" extracts the
    # dense parent from the upcycled checkpoint (expert-0 slice),
    # "top1" truncates the MoE's routing to top-1 sharing every weight
    # (models/draft.py) — or pass explicit draft_params/draft_cfg to
    # ServeEngine. Admission reserves a second same-size block set per
    # request for the draft lanes (2x footprint).
    spec_k: int = 4
    draft: str = "none"  # none | dense | top1
    # Run BlockPool.check_invariants at every tick boundary (always on
    # when chaos is set). Test/debug knob — O(capacity) per tick.
    audit_invariants: bool = False
    chaos: Optional[ChaosConfig] = None
    # Wrap the jitted mixed/verify step in a
    # jax.profiler.StepTraceAnnotation (visible when a profiler trace
    # is active, e.g. jax.profiler.start_trace; free otherwise).
    jax_profile: bool = False


class ServeEngine:
    def __init__(
        self,
        params,
        cfg: ArchConfig,
        sc: Optional[ServeConfig] = None,
        *,
        ac: zoo.ApplyCfg = zoo.ApplyCfg(),
        ctx: Optional[ShardCtx] = None,
        draft_params=None,
        draft_cfg: Optional[ArchConfig] = None,
        tracker: Optional[Tracker] = None,
    ):
        # sc defaults to None, NOT ServeConfig(): a dataclass default
        # would be one shared mutable instance across every engine.
        # (ApplyCfg is frozen, so its shared default is harmless.)
        sc = ServeConfig() if sc is None else sc
        if sc.paged and sc.admission not in ("chunked", "prefill_on_join"):
            raise ValueError(
                f"unknown admission mode {sc.admission!r} "
                "(chunked | prefill_on_join)"
            )
        if sc.paged and sc.admission == "chunked" and (
            sc.chunk_size < 1 or sc.chunks_per_step < 1
        ):
            raise ValueError(
                "chunked admission needs chunk_size >= 1 and "
                f"chunks_per_step >= 1; got {sc.chunk_size}, "
                f"{sc.chunks_per_step}"
            )
        if sc.paged and sc.admission != "chunked" and (
            sc.queue_limit or sc.queue_policy != "block"
            or sc.shed_occupancy is not None or sc.shed_stall_ticks
            or sc.preempt or sc.default_ttft_deadline is not None
            or sc.default_deadline is not None or sc.audit_invariants
            or sc.chaos is not None
        ):
            raise ValueError(
                "robustness features (backpressure / deadlines / "
                "preemption / chaos / audits) require "
                "admission='chunked'; prefill_on_join is the frozen "
                "pre-chunking baseline"
            )
        from repro.models.draft import DRAFT_KINDS

        if sc.draft not in DRAFT_KINDS:
            raise ValueError(
                f"unknown draft kind {sc.draft!r} (want {DRAFT_KINDS})"
            )
        self._spec = sc.paged and sc.draft != "none"
        if self._spec and sc.admission != "chunked":
            raise ValueError(
                "speculative decoding rides the chunked mixed step; "
                "set admission='chunked'"
            )
        if self._spec and sc.spec_k < 1:
            raise ValueError(
                f"speculative decoding needs spec_k >= 1; got {sc.spec_k}"
            )
        self.params, self.cfg, self.sc, self.ac, self.ctx = (
            params, cfg, sc, ac, ctx
        )
        # Engine-level default tracker; open_session / Fleet may pass a
        # per-session one (bound per replica). NULL = zero overhead.
        self.tracker = tracker if tracker is not None else NULL
        cdtype = jnp.bfloat16 if sc.cache_dtype == "bfloat16" else jnp.float32

        def _prefill(params, tokens, cache):
            return zoo.prefill(
                params, {"tokens": tokens}, cache, cfg, ac=ac, ctx=ctx
            )

        def _step(params, tokens, cache, index):
            return zoo.decode_step(
                params, tokens, cache, index, cfg, ac=ac, ctx=ctx
            )

        self._prefill = jax.jit(_prefill)
        self._step = jax.jit(_step, donate_argnums=(2,))
        self._cache_dtype = cdtype
        # Per-session engine stats of the LAST serve() call (compile
        # counts, prefix hit rate, tick wall clocks, ...).
        self.last_stats: dict = {}

        if sc.paged:
            # Fail fast on unsupported stacks (enc-dec / mamba / rwkv6):
            # a throwaway 2-block cache runs the same validation the real
            # allocation will.
            zoo.init_paged_serve_cache(cfg, 2, sc.block_size, dtype=cdtype)

            if sc.admission == "chunked":
                def _mstep(params, dec_tokens, chunk_tokens, cache,
                           dec_tables, dec_lengths, chunk_tables,
                           chunk_starts, chunk_lens):
                    return zoo.paged_mixed_step(
                        params, dec_tokens, chunk_tokens, cache,
                        dec_tables, dec_lengths, chunk_tables,
                        chunk_starts, chunk_lens, cfg, ac=ac, ctx=ctx,
                    )

                def _cow(cache, src, dst):
                    # Copy one pool block across every layer (the
                    # prefix cache's copy-on-write for partial tail
                    # blocks). Pool leaves carry a leading layer-stack
                    # dim: (reps, P, Kh, bs, dh).
                    return jax.tree.map(
                        lambda p: p.at[:, dst].set(p[:, src]), cache
                    )

                self._mixed_step = jax.jit(_mstep, donate_argnums=(3,))
                self._copy_block = jax.jit(_cow, donate_argnums=(0,))
                if self._spec:
                    from repro.models.draft import make_draft

                    if draft_params is None or draft_cfg is None:
                        draft_params, draft_cfg = make_draft(
                            params, cfg, sc.draft
                        )
                    self._draft_params = draft_params
                    self._draft_cfg = draft_cfg

                    def _vstep(params, vtoks, ctoks, cache, vtab,
                               vstart, vlen, ctab, cstart, clen):
                        return zoo.paged_verify_step(
                            params, vtoks, ctoks, cache, vtab, vstart,
                            vlen, ctab, cstart, clen, cfg, ac=ac,
                            ctx=ctx,
                        )

                    def _dstep(params, tokens, cache, tables, lengths):
                        return zoo.paged_decode_step(
                            params, tokens, cache, tables, lengths,
                            draft_cfg, ac=ac, ctx=ctx,
                        )

                    def _dpre(params, chunk_tokens, cache, chunk_tables,
                              chunk_starts, chunk_lens):
                        # Draft catch-up: a mixed step with ZERO decode
                        # rows — just chunk lanes over the draft cache.
                        nb = chunk_tables.shape[1]
                        return zoo.paged_mixed_step(
                            params,
                            jnp.zeros((0, 1), jnp.int32),
                            chunk_tokens, cache,
                            jnp.zeros((0, nb), jnp.int32),
                            jnp.zeros((0,), jnp.int32),
                            chunk_tables, chunk_starts, chunk_lens,
                            draft_cfg, ac=ac, ctx=ctx,
                        )

                    self._verify_step = jax.jit(
                        _vstep, donate_argnums=(3,)
                    )
                    self._draft_step = jax.jit(
                        _dstep, donate_argnums=(2,)
                    )
                    self._draft_prefill = jax.jit(
                        _dpre, donate_argnums=(2,)
                    )
            else:
                def _pprefill(params, tokens, cache, table, length):
                    return zoo.paged_prefill(
                        params, tokens, cache, table, length, cfg,
                        ac=ac, ctx=ctx,
                    )

                def _pstep(params, tokens, cache, tables, lengths):
                    return zoo.paged_decode_step(
                        params, tokens, cache, tables, lengths, cfg,
                        ac=ac, ctx=ctx,
                    )

                self._paged_prefill = jax.jit(_pprefill, donate_argnums=(2,))
                self._paged_step = jax.jit(_pstep, donate_argnums=(2,))

    # ------------------------------------------------------------------
    # static-batch path (legacy contract)
    # ------------------------------------------------------------------

    def generate(self, prompts: list[list[int]], max_new: int = 32,
                 *, rng=None) -> list[list[int]]:
        """Greedy/temperature generation for a batch of prompts.

        Paged engines route through :meth:`serve` (all requests arrive
        at tick 0; more prompts than ``max_batch`` simply queue);
        static engines keep the original fixed-batch loop.
        """
        if self.sc.paged:
            reqs = [
                Request(rid=i, prompt=list(p), max_new=max_new)
                for i, p in enumerate(prompts)
            ]
            outs, _ = self.serve(reqs, rng=rng)
            return [outs[i] for i in range(len(prompts))]
        sc, cfg = self.sc, self.cfg
        B = len(prompts)
        assert B <= sc.max_batch
        plen = max(len(p) for p in prompts)
        toks = np.zeros((B, plen), np.int32)
        for i, p in enumerate(prompts):
            toks[i, :len(p)] = p  # right padding handled by causality
        cache = zoo.init_serve_cache(
            cfg, B, plen + max_new, dtype=self._cache_dtype
        )
        cache, logits = self._prefill(self.params, jnp.asarray(toks), cache)
        out = [list(p) for p in prompts]
        index = jnp.asarray(plen, jnp.int32)
        rng = jax.random.PRNGKey(0) if rng is None else rng
        cur = self._sample(logits, rng)
        for t in range(max_new):
            for i in range(B):
                out[i].append(int(cur[i, 0]))
            if t == max_new - 1:
                break
            cache, logits = self._step(self.params, cur, cache, index)
            index = index + 1
            rng = jax.random.fold_in(rng, t)
            cur = self._sample(logits, rng)
        return out

    def _sample(self, logits, rng):
        lg = logits[:, -1]
        if self.sc.temperature <= 0.0:
            return jnp.argmax(lg, axis=-1)[:, None].astype(jnp.int32)
        return jax.random.categorical(
            rng, lg / self.sc.temperature
        )[:, None].astype(jnp.int32)

    # ------------------------------------------------------------------
    # continuous-batching path
    # ------------------------------------------------------------------

    def serve(
        self,
        requests: list[Request],
        *,
        on_token: Optional[Callable[[int, int], None]] = None,
        on_event: Optional[Callable[[int, str, str], None]] = None,
        rng=None,
        tracker: Optional[Tracker] = None,
    ):
        """Run a continuous-batching session over ``requests``.

        Requests become visible at their ``arrival`` tick; admission is
        priority-then-FCFS into free slots. With ``admission="chunked"``
        (default) each tick is ONE jitted mixed step — decode rows plus
        prefill chunk lanes — and prompt prefixes already in the pool
        are reused copy-free; ``admission="prefill_on_join"`` runs the
        pre-chunking per-admission B=1 prefill instead. Tokens stream
        through ``on_token(rid, token)`` (and each request's own
        ``on_token``) the moment they are sampled; lifecycle events
        (``admitted`` / ``re-admitted`` / ``preempted-requeued`` /
        ``completed`` / ``shed`` / ``timeout`` / ``failed``) stream
        through ``on_event(rid, event, detail)`` (chunked mode).

        Returns ``(outputs, stats)``: ``outputs[rid]`` is the full
        prompt + generated sequence (EOS included when hit);
        ``stats[rid]`` records arrival / admission / first-token /
        finish ticks, generated count, prefix-cached prompt tokens, the
        terminal ``status`` (completed | shed | timeout | failed), the
        detail ``reason`` and the ``preemptions`` count — EVERY
        submitted request gets exactly one terminal record. Engine
        counters (compile counts, prefix hit rate, per-tick wall
        clocks, shed/timeout/preempt/watchdog totals) land in
        ``self.last_stats``.
        """
        if not self.sc.paged:
            raise ValueError("serve() needs ServeConfig(paged=True)")
        if self.sc.admission == "chunked":
            return self._serve_chunked(requests, on_token=on_token,
                                       on_event=on_event, rng=rng,
                                       tracker=tracker)
        return self._serve_prefill_on_join(requests, on_token=on_token,
                                           rng=rng)

    def _session(self, requests, rng):
        """Shared session setup: pool, scheduler, rng seed, buffers."""
        sc = self.sc
        bs = sc.block_size
        nb_max = -(-sc.max_len // bs)
        # Speculation doubles the per-request footprint (private draft
        # lanes), so the full-capacity auto-sizing doubles too.
        lanes = 2 if self._spec else 1
        num_blocks = sc.num_blocks or (1 + lanes * sc.max_batch * nb_max)
        pool = BlockPool(
            num_blocks, bs,
            prefix_cache=sc.prefix_cache and sc.admission == "chunked",
        )
        if sc.admission == "chunked":
            sched = Scheduler(
                sc.max_batch, pool, sc.max_len,
                queue_limit=sc.queue_limit,
                queue_policy=sc.queue_policy,
                shed_occupancy=sc.shed_occupancy,
                shed_stall_ticks=sc.shed_stall_ticks,
                preempt=sc.preempt,
                default_ttft_deadline=sc.default_ttft_deadline,
                default_deadline=sc.default_deadline,
                # The watchdog (not a submit-time raise) owns the
                # oversized-request failure path in chunked mode, so
                # every submitted request gets a terminal status.
                reject_oversized=False,
                spec=self._spec,
                inflight_share=sc.prefix_cache,
            )
        else:
            sched = Scheduler(sc.max_batch, pool, sc.max_len)
        for r in requests:
            sched.submit(r)
        rng = jax.random.PRNGKey(0) if rng is None else rng
        # One device call per session: derive the host seed for the
        # per-token Gumbel draws (temperature sampling stays on host —
        # no per-slot device round-trips on the decode hot loop).
        seed0 = int(jax.random.randint(rng, (), 0, 2 ** 31 - 1))
        cache = zoo.init_paged_serve_cache(
            self.cfg, num_blocks, bs, dtype=self._cache_dtype
        )
        return pool, sched, seed0, cache, nb_max, num_blocks

    def _finisher(self, sched, clear_slot):
        """Shared finish policy of both paged loops (EOS / token
        budget): returns the per-token ``maybe_finish(slot, tok, step)``
        closure; ``clear_slot(i)`` zeroes the caller's host-side lane
        buffers for the freed slot."""
        sc = self.sc

        def maybe_finish(slot, tok, step):
            req = slot.request
            eos = req.eos_id if req.eos_id is not None else sc.eos_id
            reason = None
            if eos is not None and tok == eos:
                reason = "eos"
            elif slot.generated >= slot.budget:
                reason = "budget"
            if reason is None:
                return False
            clear_slot(slot.index)
            sched.finish(slot, step, reason)
            return True

        return maybe_finish

    def _emitter(self, requests, on_token):
        outs = {r.rid: list(r.prompt) for r in requests}

        def emit(req, slot, tok):
            outs[req.rid].append(tok)
            slot.generated += 1
            if on_token is not None:
                on_token(req.rid, tok)
            if req.on_token is not None:
                req.on_token(req.rid, tok)

        return outs, emit

    # -- chunked mixed-step loop (the paged default) --------------------

    def open_session(self, *, on_token=None, on_event=None, rng=None,
                     fleet_mode: bool = False,
                     tracker: Optional[Tracker] = None
                     ) -> "ChunkedSession":
        """Open a tick-steppable chunked serve session (the fleet hook).

        The solo :meth:`serve` path is ``open_session`` + submit all +
        ``while sess.tick(): pass`` + ``close()``. A
        :class:`repro.serve.fleet.Fleet` instead drives one session per
        replica in lockstep (``fleet_mode=True``: the clock advances
        exactly one tick per call, never fast-forwards, and an empty
        queue keeps the session open for later routing), migrating
        requests between sessions with :meth:`ChunkedSession.submit`'s
        ``resume`` records.
        """
        if not (self.sc.paged and self.sc.admission == "chunked"):
            raise ValueError(
                "sessions need ServeConfig(paged=True, "
                "admission='chunked')"
            )
        return ChunkedSession(self, on_token=on_token, on_event=on_event,
                              rng=rng, fleet_mode=fleet_mode,
                              tracker=tracker)

    def _serve_chunked(self, requests, *, on_token, on_event, rng,
                       tracker=None):
        sess = self.open_session(on_token=on_token, on_event=on_event,
                                 rng=rng, tracker=tracker)
        for r in requests:
            sess.submit(r)
        while sess.tick():
            pass
        return sess.close()

    # -- prefill-on-join loop (pre-chunking baseline) -------------------

    def _serve_prefill_on_join(self, requests, *, on_token, rng):
        sc = self.sc
        bs = sc.block_size
        pool, sched, seed0, cache, nb_max, _ = self._session(requests, rng)
        outs, emit = self._emitter(requests, on_token)

        B = sc.max_batch
        tables = np.zeros((B, nb_max), np.int32)
        lengths = np.zeros((B,), np.int32)
        cur = np.zeros((B, 1), np.int32)

        stats = {
            "mode": "prefill_on_join",
            "mixed_steps": 0,
            "compile_events": [],
            "decode_stall_ticks": 0,
            "prefix_hit_tokens": 0,
            "prompt_tokens": 0,
            "chunk_rows_used": 0,
            "tick_wall": {},
        }
        self.last_stats = stats

        def clear_slot(i):
            tables[i, :] = 0
            lengths[i] = 0
            cur[i, 0] = 0

        maybe_finish = self._finisher(sched, clear_slot)

        step = 0
        while sched.has_work:
            stats["tick_wall"].setdefault(step, time.perf_counter())
            # -- admission: prefill-on-join into freshly allocated blocks
            for slot in sched.admit(step):
                i, req = slot.index, slot.request
                plen = len(req.prompt)
                sp = bucket_len(plen, bs)
                tables[i, :] = 0
                tables[i, :len(slot.blocks)] = slot.blocks
                toks = np.zeros((1, sp), np.int32)
                toks[0, :plen] = req.prompt
                # Each admission is an EXTRA device call; every already-
                # decoding slot sits out this call — the decode stall
                # the chunked mixed step exists to remove.
                if any(s.decoding for s in sched.active if s is not slot):
                    stats["decode_stall_ticks"] += 1
                cache, lg = self._paged_prefill(
                    self.params, jnp.asarray(toks), cache,
                    jnp.asarray(tables[i:i + 1]),
                    jnp.asarray(plen, jnp.int32),
                )
                slot.length = plen
                lengths[i] = plen
                slot.first_token_at = step
                stats["prompt_tokens"] += plen
                tok = self._sample_one(
                    np.asarray(lg[0, 0]), seed0, req.rid, 0
                )
                emit(req, slot, tok)
                if not maybe_finish(slot, tok, step):
                    slot.decoding = True
                    cur[i, 0] = tok

            active = sched.active
            if not active:
                nxt = sched.next_arrival()
                if nxt is None:
                    break
                step = max(step + 1, nxt)  # idle: fast-forward the clock
                continue

            # -- one batched decode step over the slot array (free slots
            # masked out of MoE routing; their writes hit the trash block)
            cache, logits = self._paged_step(
                self.params, jnp.asarray(cur), cache,
                jnp.asarray(tables), jnp.asarray(lengths),
            )
            step += 1
            stats["mixed_steps"] += 1
            lg_host = np.asarray(logits[:, 0])  # ONE device sync per step
            for slot in active:
                i, req = slot.index, slot.request
                slot.length += 1  # cur token entered the cache
                lengths[i] += 1
                tok = self._sample_one(
                    lg_host[i], seed0, req.rid, slot.generated
                )
                emit(req, slot, tok)
                if not maybe_finish(slot, tok, step):
                    cur[i, 0] = tok

        stats["compile_count"] = (
            self._paged_prefill._cache_size()
            + self._paged_step._cache_size()
        )
        stats["prefix_hit_frac"] = 0.0
        assert pool.num_free == pool.capacity, "leaked KV blocks"
        return outs, sched.finished

    def _sample_one(self, logits_row, seed0: int, rid: int,
                    n: int) -> int:
        """Per-request sampling from a HOST (numpy) logits row: greedy,
        or Gumbel-max temperature sampling (== categorical in law)
        seeded on (session seed, rid, token index) — host-only and
        independent of slot placement and batch composition, so
        staggered admission reproduces solo runs. Delegates to
        ``speculative.sample_token`` so the vanilla and speculative
        paths share one stream definition (the parity contract)."""
        return sample_token(
            logits_row, self.sc.temperature, seed0, rid, n
        )


class ChunkedSession:
    """One open chunked-serve session on a :class:`ServeEngine`,
    advanced one tick at a time.

    This is the engine's fleet hook: everything the solo ``serve()``
    loop did per iteration lives in :meth:`tick`, so an external driver
    (repro.serve.fleet.Fleet) can interleave N engine replicas on one
    global clock and move requests between them mid-flight:

    * :meth:`submit` — admit a request mid-session; with ``resume``
      (the preempt-and-requeue record from another engine) decoding
      continues at token index ``generated``, token-identical because
      sampling is keyed on ``(rid, generated)`` and every replica in a
      fleet derives the same session seed from the same rng.
    * :meth:`cancel` — terminate this engine's copy of a request
      (hedge loser / post-migration duplicate) with engine-local
      terminal status ``cancelled``, freeing its blocks.
    * :meth:`extract_queue` — pull every unadmitted request (with any
      saved progress) for migration to another replica.
    * :meth:`signals` — the per-tick routing / autoscaling signals
      (occupancy, queue depth, stall ticks, active/decoding counts).
    * :meth:`skip_tick` — advance the clock without doing work (the
      fleet's slow-engine chaos; deadlines keep ticking globally).

    ``fleet_mode=True`` keeps the session open when the queue is empty
    (the fleet may route more work later) and never fast-forwards the
    clock, so every replica's ``step`` equals the fleet's global tick.
    Solo mode preserves the original ``serve()`` semantics exactly,
    including idle fast-forward to the next arrival.
    """

    def __init__(self, engine: ServeEngine, *, on_token=None,
                 on_event=None, rng=None, fleet_mode: bool = False,
                 tracker: Optional[Tracker] = None):
        self.eng = engine
        sc = engine.sc
        self.sc = sc
        self.fleet_mode = fleet_mode
        self.on_token = on_token
        self.on_event = on_event
        self.bs = sc.block_size
        self.B, self.NC, self.C = (
            sc.max_batch, sc.chunks_per_step, sc.chunk_size
        )
        B, NC, C = self.B, self.NC, self.C
        (self.pool, self.sched, self.seed0, self.cache, self.nb,
         self.nblk) = engine._session([], rng)
        self.outs: dict[int, list] = {}
        self.req_map: dict[int, Request] = {}

        nb = self.nb
        self.slot_tables = np.zeros((B, nb), np.int32)  # per-slot tables
        self.lengths = np.zeros((B,), np.int32)  # tokens in cache / slot
        self.cur = np.zeros((B, 1), np.int32)
        self.dec_tables = np.zeros((B, nb), np.int32)  # decode-lane view
        self.dec_lengths = np.zeros((B,), np.int32)
        self.ctoks = np.zeros((NC, C), np.int32)
        self.ctab = np.zeros((NC, nb), np.int32)
        self.cstart = np.zeros((NC,), np.int32)
        self.clen = np.zeros((NC,), np.int32)

        # -- speculative decoding: draft runner + verify lanes ----------
        self.spec = engine._spec
        self.runner = None
        self.K1 = sc.spec_k + 1
        if self.spec:
            from repro.serve.speculative import SpecRunner

            dcache = zoo.init_paged_serve_cache(
                engine._draft_cfg, self.nblk, self.bs,
                dtype=engine._cache_dtype,
            )
            self.runner = SpecRunner(
                draft_step=engine._draft_step,
                draft_prefill=engine._draft_prefill,
                params=engine._draft_params, cache=dcache,
                spec_k=sc.spec_k, temperature=sc.temperature,
                seed0=self.seed0, max_batch=B, num_chunks=NC,
                chunk_size=C, nb=nb,
            )
            self.vtoks = np.zeros((B, self.K1), np.int32)
            self.vtab = np.zeros((B, nb), np.int32)
            self.vstart = np.zeros((B,), np.int32)
            self.vlen = np.zeros((B,), np.int32)

        self.chaos = sc.chaos
        self.audit = sc.audit_invariants or self.chaos is not None
        self.stats: dict = {
            "mode": "chunked",
            "mixed_steps": 0,
            "compile_events": [],
            "decode_stall_ticks": 0,  # structurally 0: decode rows ride
            "prefix_hit_tokens": 0,   # every mixed step
            "prompt_tokens": 0,
            "chunk_rows_used": 0,
            "tick_wall": {},
            # -- robustness observability --------------------------------
            "events": [],  # (tick, rid, event, detail)
            "preemptions": 0,
            "watchdog_failures": 0,
            "status_counts": {},  # terminal status -> count (at drain)
            "peak_occupancy": 0.0,
            "stall_ticks_max": 0,  # longest block-starved head streak
            "audits": 0,
            # -- speculative decoding ------------------------------------
            "spec_drafted": 0,   # draft tokens proposed to the verifier
            "spec_accepted": 0,  # draft tokens accepted by the verifier
            "inflight_promotions": 0,  # pending shared blocks promoted
        }
        if self.chaos is not None:
            self.stats["chaos"] = {"evictions": 0, "holds": 0,
                                   "held_blocks": 0, "bursts": 0,
                                   "burst_reqs": 0, "storms": 0}
        engine.last_stats = self.stats
        self._compiled = 0
        self._maybe_finish = engine._finisher(self.sched,
                                              self._clear_slot)
        # Forced evictions (preempt / timeout / cancel) must clear the
        # victim's host-side lanes exactly like a normal finish does.
        self.sched.on_evict = lambda slot: self._clear_slot(slot.index)
        self._ev_cursor = 0
        self._crng = (np.random.default_rng(self.chaos.seed)
                      if self.chaos is not None else None)
        self.holds: list[list] = []  # [release_tick, blocks]
        self.step = 0
        self._stuck = 0
        self._closed = False
        self._tokens_emitted = 0
        # Session tracker: explicit > engine default > NULL. Solo
        # sessions stamp rows on their own step clock; fleet-bound
        # trackers arrive with the fleet tick clock already set.
        trk = tracker if tracker is not None else engine.tracker
        if trk.enabled and trk.clock is None:
            trk = trk.bind(clock=lambda: self.step)
        self.trk = trk
        # Lifecycle counters (admissions / sheds / timeouts / ...) are
        # emitted at the source, the scheduler's terminal chokepoints.
        self.sched.tracker = trk

    # -- request plumbing ----------------------------------------------
    def submit(self, req: Request, resume: Optional[dict] = None
               ) -> None:
        """Submit a request to this session. ``resume`` (a
        preempt-and-requeue record with the full token sequence so far)
        makes this a fleet re-admission: re-prefill covers prompt +
        already-generated tokens and decoding continues token-identical
        at index ``generated``. Deadlines stay anchored to the
        request's ORIGINAL arrival tick in both cases."""
        if resume is not None:
            self.sched.resubmit(req, resume)
            self.outs[req.rid] = list(resume["seq"])
        else:
            self.sched.submit(req)
            self.outs[req.rid] = list(req.prompt)
        self.req_map[req.rid] = req

    def cancel(self, rid: int, reason: str = "cancelled") -> bool:
        """Cancel this session's copy of ``rid`` (queued or active):
        blocks freed, engine-local terminal status ``cancelled``."""
        return self.sched.cancel(rid, self.step, reason)

    def forget(self, rid: int) -> None:
        """Drop a TERMINAL rid's record so the fleet can resubmit the
        same request here later (retry on the only surviving engine)."""
        self.sched.forget(rid)
        self.outs.pop(rid, None)
        self.req_map.pop(rid, None)

    def extract_queue(self):
        """Migration: pull every queued (unadmitted) request — with any
        saved preemption progress — out of this session, no terminal
        records. The fleet re-routes them to surviving replicas."""
        out = self.sched.extract_queue()
        for req, _ in out:
            self.outs.pop(req.rid, None)
            self.req_map.pop(req.rid, None)
        return out

    @property
    def active_requests(self) -> list:
        return [s.request for s in self.sched.active
                if s.request is not None]

    @property
    def has_work(self) -> bool:
        return self.sched.has_work

    def signals(self) -> dict:
        """Per-tick routing / health / autoscaling signals (the
        ROADMAP's 'shed/occupancy signals wired out'): pure host reads,
        exported into the fleet's JSONL timeline every tick."""
        pool, sched = self.pool, self.sched
        occ = (pool.capacity - pool.num_free) / pool.capacity
        return {
            "occupancy": occ,
            "free_blocks": pool.num_free,
            "queue_depth": len(sched.queue),
            "active": len(sched.active),
            "decoding": sum(1 for s in sched.active if s.decoding),
            "stall_ticks": sched.stall_ticks,
            "step": self.step,
        }

    def skip_tick(self) -> None:
        """Advance the session clock WITHOUT doing any work (fleet
        slow-engine degradation): deadlines keep ticking in global
        time, the engine just gets nothing done this tick."""
        self.step += 1

    def flush_events(self) -> int:
        """Deliver any undelivered lifecycle events NOW. A request can
        reach a terminal status in a tick's bookkeeping AFTER that
        tick's event dispatch ran — normally the next tick (or close())
        delivers it, but a fleet killing this engine must flush first
        or it would migrate already-finished work."""
        return self._dispatch_events()

    # -- internals ------------------------------------------------------
    def _clear_slot(self, i: int) -> None:
        self.slot_tables[i, :] = 0
        self.lengths[i] = 0
        self.cur[i, 0] = 0
        if self.runner is not None:
            self.runner.clear_slot(i)

    def _seq_of(self, rid: int) -> list:
        # Full sequence so far (prompt + generated) — what a preempted
        # victim must re-prefill, and what its computed blocks are
        # registered under for copy-free recovery.
        return self.outs[rid]

    def _emit(self, req, slot, tok: int) -> None:
        self.outs[req.rid].append(tok)
        slot.generated += 1
        self._tokens_emitted += 1
        if self.on_token is not None:
            self.on_token(req.rid, tok)
        if req.on_token is not None:
            req.on_token(req.rid, tok)

    def _dispatch_events(self) -> int:
        """Drain scheduler lifecycle events into stats + streaming
        callbacks; returns how many fired (the progress signal for the
        watchdog — sheds/timeouts ARE progress)."""
        new = self.sched.events[self._ev_cursor:]
        self._ev_cursor = len(self.sched.events)
        for tick, rid, ev, detail in new:
            self.stats["events"].append((tick, rid, ev, detail))
            if ev == "preempted-requeued":
                self.stats["preemptions"] += 1
            elif ev == "failed":
                self.stats["watchdog_failures"] += 1
            if self.on_event is not None:
                self.on_event(rid, ev, detail)
            req = self.req_map.get(rid)
            if req is not None and req.on_event is not None:
                req.on_event(rid, ev, detail)
        return len(new)

    def _chaos_tick(self, step: int) -> None:
        chaos, crng, pool, sched = (
            self.chaos, self._crng, self.pool, self.sched
        )
        cs = self.stats["chaos"]
        for h in self.holds[:]:
            if step >= h[0]:
                pool.free(h[1])
                self.holds.remove(h)
        if chaos.evict_prob and crng.random() < chaos.evict_prob:
            victims = sched.active
            if victims:
                v = victims[int(crng.integers(len(victims)))]
                sched.preempt_slot(v, step, self._seq_of)
                cs["evictions"] += 1
        if chaos.hold_prob and crng.random() < chaos.hold_prob:
            avail = pool.num_free
            if avail > 0:
                k = int(crng.integers(
                    1, min(chaos.hold_max_blocks, avail) + 1
                ))
                blks = pool.alloc(k)
                if blks is not None:
                    self.holds.append([step + chaos.hold_ticks, blks])
                    cs["holds"] += 1
                    cs["held_blocks"] += k
        if chaos.burst_prob and crng.random() < chaos.burst_prob:
            cs["bursts"] += 1
            for _ in range(chaos.burst_size):
                rid = chaos.rid_base + cs["burst_reqs"]
                cs["burst_reqs"] += 1
                prompt = [int(t) for t in
                          crng.integers(1, 97, size=chaos.burst_plen)]
                breq = Request(
                    rid=rid, prompt=prompt,
                    max_new=chaos.burst_max_new, arrival=step,
                    priority=chaos.burst_priority,
                )
                self.outs[rid] = list(prompt)
                self.req_map[rid] = breq
                sched.submit(breq)
        if chaos.storm_prob and crng.random() < chaos.storm_prob:
            if sched.storm_deadlines(step, chaos.storm_ttft):
                cs["storms"] += 1

    def _tick_audit(self) -> None:
        if self.audit:
            sched = self.sched
            self.pool.check_invariants(
                [s.blocks for s in sched.active]
                + [s.draft_blocks for s in sched.active
                   if s.draft_blocks]
                + [h[1] for h in self.holds]
            )
            self.stats["audits"] += 1

    # -- the tick -------------------------------------------------------
    def tick(self) -> bool:
        """Run ONE serve tick (deadlines -> backpressure -> admission ->
        chunk planning -> one mixed step -> bookkeeping -> audit), the
        loop body of the original chunked serve loop. Returns whether
        the session still has work afterwards — the solo loop is
        ``while sess.tick(): pass``.

        The tick is wrapped in a ``tick`` span, its phases nested under
        it: with a profiler trace active they are host events (``tick``,
        ``tick/admission``, ... ``tick/emit``) on the device trace's
        clock, tracker or not. With a tracker attached they are also
        span rows, and one ``engine`` row — the per-tick queue-depth /
        occupancy / stall time series — is emitted per call. All tracked
        values are pure host-side reads: tracking adds ZERO device syncs
        (the mixed step's single logits pull stays the only one)."""
        trk = self.trk
        with trk.span("tick"):
            alive = self._tick_inner()
        if trk.enabled:
            sig = self.signals()
            trk.row(
                "engine",
                occupancy=round(sig["occupancy"], 4),
                free_blocks=sig["free_blocks"],
                queue_depth=sig["queue_depth"],
                active=sig["active"],
                decoding=sig["decoding"],
                stall_ticks=sig["stall_ticks"],
                tokens=self._tokens_emitted,
                mixed_steps=self.stats["mixed_steps"],
                compiles=len(self.stats["compile_events"]),
            )
        return alive

    def _tick_inner(self) -> bool:
        eng, sc = self.eng, self.sc
        sched, pool, stats = self.sched, self.pool, self.stats
        bs, B, NC, C = self.bs, self.B, self.NC, self.C
        if not sched.has_work:
            # Terminal events from the LAST working tick's bookkeeping
            # are still undelivered (the mid-tick dispatch ran before
            # them) — flush here so a fleet session that idles, rather
            # than closes, still reports its completions.
            self._dispatch_events()
            if self.fleet_mode:
                self.step += 1  # idle fleet tick: the clock stays global
            return False
        step = self.step
        stats["tick_wall"].setdefault(step, time.perf_counter())
        if self._crng is not None:
            self._chaos_tick(step)
        # -- robustness sweeps: deadlines, then backpressure — pure
        # host bookkeeping, once per tick, no device syncs.
        occ = (pool.capacity - pool.num_free) / pool.capacity
        stats["peak_occupancy"] = max(stats["peak_occupancy"], occ)
        with self.trk.span("admission"):
            sched.expire(step)
            sched.enforce(step, occ)
            # -- admission: slots + blocks, shared prefix mapped
            # copy-free; CoW partial tails copied device-side. May
            # preempt-and-requeue lower-priority actives (preempt=True).
            admitted = sched.admit(step, seq_of=self._seq_of)
            for slot in admitted:
                i = slot.index
                self.slot_tables[i, :] = 0
                self.slot_tables[i, :len(slot.blocks)] = slot.blocks
                if slot.cow is not None:
                    src, dst, ntok = slot.cow
                    self.cache = eng._copy_block(
                        self.cache, jnp.asarray(src, jnp.int32),
                        jnp.asarray(dst, jnp.int32),
                    )
                    slot.length += ntok
                    slot.cow = None
                self.lengths[i] = slot.length
                stats["prefix_hit_tokens"] += slot.prefix_tokens
                stats["prompt_tokens"] += len(slot.eff_prompt)
                if self.runner is not None:
                    self.runner.set_slot(slot)
        # -- in-flight prefix promotion: a follower's shared-but-pending
        # blocks become readable only once the donor has computed past
        # their end (promote in contiguous order); a dead or recycled
        # donor invalidates the follower's mapped suffix ->
        # preempt-and-requeue (copy-free recovery re-prefills from
        # registered blocks).
        with self.trk.span("prefix"):
            for slot in list(sched.active):
                while slot.pending_shared:
                    end, donor, dseq = slot.pending_shared[0]
                    if donor.request is None or donor.admit_seq != dseq:
                        sched.preempt_slot(slot, step, self._seq_of)
                        break
                    if donor.length < end or slot.length + bs != end:
                        break
                    slot.pending_shared.pop(0)
                    slot.length = end
                    self.lengths[slot.index] = end
                    slot.prefix_tokens += bs
                    stats["prefix_hit_tokens"] += bs
                    stats["inflight_promotions"] += 1
        stats["stall_ticks_max"] = max(
            stats["stall_ticks_max"], sched.stall_ticks
        )
        progress = self._dispatch_events() > 0

        # -- chunk-lane assignment: strict FCFS over prefilling slots;
        # one slot may take several lanes in one tick (its later chunks
        # attend the earlier ones' in-step writes). eff_prompt (prompt +
        # recovered generated tokens after a preemption) is what needs
        # to be in the cache.
        chunks = []  # (slot, start, ntok)
        planned = {}
        for slot in sched.prefilling():
            if slot.pending_shared:
                # waiting on a donor's in-flight writes — burning lanes
                # here would recompute what the donor is about to hand
                # over for free.
                continue
            plen = len(slot.eff_prompt)
            pos = planned.get(slot.index, slot.length)
            while len(chunks) < NC and pos < plen:
                n = min(C, plen - pos)
                chunks.append((slot, pos, n))
                pos += n
            planned[slot.index] = pos
            if len(chunks) >= NC:
                break

        decoding = [s for s in sched.active if s.decoding]
        if not decoding and not chunks:
            pend = [s for s in sched.active if s.pending_shared]
            if pend:
                # Unreachable in normal operation (a pending slot
                # implies a live prefilling donor, which implies chunk
                # work), but a wedged donor chain must not spin the
                # watchdog — requeue the followers.
                for s in pend:
                    sched.preempt_slot(s, step, self._seq_of)
                self._dispatch_events()
                self._tick_audit()
                self.step = step + 1
                return True
            nxt = sched.next_arrival()
            if nxt is None:
                # Solo: the session drains (close() runs the final
                # checks). Fleet: stays open — more work may be routed
                # here next tick — but the clock must still advance.
                if self.fleet_mode:
                    self.step = step + 1
                return False
            # -- stuck-tick watchdog: a visible head that nothing will
            # ever unblock (chaos holds, block starvation with no
            # preemptible victim) must fail with a diagnostic, not spin
            # the clock forever. Sheds/timeouts/admissions this tick
            # count as progress.
            if progress or nxt > step:
                self._stuck = 0
            else:
                self._stuck += 1
                if self._stuck >= max(1, sc.watchdog_ticks):
                    free_slots = sum(
                        1 for s in sched.slots if s.request is None
                    )
                    diag = (
                        f"no progress for {self._stuck} ticks: "
                        f"free_blocks={pool.num_free}/"
                        f"{pool.capacity}, free_slots={free_slots}, "
                        f"queued={len(sched.queue)}, "
                        f"preempt={sc.preempt}"
                    )
                    if not sched.fail_stuck(step, diag):
                        raise RuntimeError(
                            f"serve watchdog wedged: {diag}"
                        )
                    self._dispatch_events()
                    self._stuck = 0
            self._tick_audit()
            # idle: fast-forward the clock (solo only — fleet clocks
            # are global and advance one tick per call).
            self.step = (step + 1 if self.fleet_mode
                         else max(step + 1, nxt))
            return True
        self._stuck = 0

        # -- build the fixed-shape lanes. Non-decoding slots are masked
        # out of the decode lane (zero table row, length 0 ->
        # trash-block write, no routing claims).
        ctoks, ctab = self.ctoks, self.ctab
        cstart, clen = self.cstart, self.clen
        ctoks[:] = 0
        ctab[:] = 0
        cstart[:] = 0
        clen[:] = 0
        for ci, (slot, start, n) in enumerate(chunks):
            ctoks[ci, :n] = slot.eff_prompt[start:start + n]
            ctab[ci] = self.slot_tables[slot.index]
            cstart[ci] = start
            clen[ci] = n

        # Optional profiler hook: annotates the jitted mixed/verify
        # step in a jax.profiler trace when one is active; a no-op
        # context otherwise.
        prof = (jax.profiler.StepTraceAnnotation("mixed_step",
                                                 step_num=step)
                if sc.jax_profile else contextlib.nullcontext())
        if self.spec:
            # draft first: catch behind draft caches up, then run the
            # lockstep k-token draft loop; decode slots become
            # width-(1+k_eff) verify lanes on the target.
            with self.trk.span("draft"):
                runner = self.runner
                runner.catch_up(sched.active, self._seq_of)
                dmap = runner.draft(decoding, self.cur)
                vtoks, vtab = self.vtoks, self.vtab
                vstart, vlen = self.vstart, self.vlen
                vtoks[:] = 0
                vtab[:] = 0
                vstart[:] = 0
                vlen[:] = 0
                for s in decoding:
                    i = s.index
                    drafted = dmap[i][0] if i in dmap else []
                    vtoks[i, 0] = self.cur[i, 0]
                    for dj, d in enumerate(drafted):
                        vtoks[i, 1 + dj] = d
                    vtab[i] = self.slot_tables[i]
                    vstart[i] = self.lengths[i]
                    vlen[i] = 1 + len(drafted)
            with self.trk.span("mixed_step"), prof:
                self.cache, logits = eng._verify_step(
                    eng.params, jnp.asarray(vtoks), jnp.asarray(ctoks),
                    self.cache, jnp.asarray(vtab), jnp.asarray(vstart),
                    jnp.asarray(vlen), jnp.asarray(ctab),
                    jnp.asarray(cstart), jnp.asarray(clen),
                )
            chunk_off = B * self.K1
        else:
            dec_tables, dec_lengths = self.dec_tables, self.dec_lengths
            dec_tables[:] = 0
            dec_lengths[:] = 0
            for s in decoding:
                dec_tables[s.index] = self.slot_tables[s.index]
                dec_lengths[s.index] = self.lengths[s.index]
            with self.trk.span("mixed_step"), prof:
                self.cache, logits = eng._mixed_step(
                    eng.params, jnp.asarray(self.cur), jnp.asarray(ctoks),
                    self.cache, jnp.asarray(dec_tables),
                    jnp.asarray(dec_lengths),
                    jnp.asarray(ctab), jnp.asarray(cstart),
                    jnp.asarray(clen),
                )
            chunk_off = B
        step += 1
        self.step = step
        stats["mixed_steps"] += 1
        stats["chunk_rows_used"] += int(clen.sum())
        n_compiled = (eng._verify_step if self.spec
                      else eng._mixed_step)._cache_size()
        if n_compiled != self._compiled:
            self._compiled = n_compiled
            stats["compile_events"].append(step)
            self.trk.count("serve.compile_events", t=step)
        with self.trk.span("host_sync"):
            lg_host = np.asarray(logits)  # ONE host sync per mixed step

        with self.trk.span("emit"):
            # -- chunk bookkeeping first: lengths advance, prefix
            # blocks register, completed prompts sample their next
            # token (the FIRST token for fresh admissions; for
            # re-admitted preemption victims, the continuation at
            # index generated).
            for ci, (slot, start, n) in enumerate(chunks):
                i, req = slot.index, slot.request
                slot.length = start + n
                self.lengths[i] = slot.length
                slot.reg_blocks, slot.reg_parent = pool.register_prefix(
                    slot.eff_prompt, slot.blocks, slot.length,
                    start_block=slot.reg_blocks, parent=slot.reg_parent,
                )
                if slot.length == len(slot.eff_prompt):
                    if not slot.first_done:
                        slot.first_token_at = step
                        slot.first_done = True
                    tok = eng._sample_one(lg_host[chunk_off + ci],
                                          self.seed0, req.rid,
                                          slot.generated)
                    self._emit(req, slot, tok)
                    if not self._maybe_finish(slot, tok, step):
                        slot.decoding = True
                        self.cur[i, 0] = tok

            # -- decode bookkeeping
            for slot in decoding:
                if slot.request is None:
                    continue  # evicted this tick (deadline / chaos)
                i, req = slot.index, slot.request
                if self.spec:
                    # Exact rejection sampling over this slot's verify
                    # rows: emit m accepted drafts + 1 correction/
                    # bonus. Rollback is overwrite-and-mask — length
                    # simply stops after the last emitted token; stale
                    # cache positions past it are never attended.
                    drafted, qrows = dmap.get(i, ([], []))
                    K1 = self.K1
                    p_rows = lg_host[i * K1:i * K1 + 1 + len(drafted)]
                    emitted, acc = verify_accept(
                        drafted, qrows, p_rows, sc.temperature,
                        self.seed0, req.rid, slot.generated,
                    )
                    stats["spec_drafted"] += len(drafted)
                    stats["spec_accepted"] += acc
                    slot.drafted += len(drafted)
                    slot.accepted += acc
                    fin = False
                    for tok in emitted:
                        slot.length += 1  # verified token is in cache
                        self.lengths[i] += 1
                        self._emit(req, slot, tok)
                        if self._maybe_finish(slot, tok, step):
                            fin = True
                            break
                    if not fin:
                        self.cur[i, 0] = emitted[-1]
                        if i in dmap:
                            # draft wrote positions length..
                            # length+k_eff in lockstep; the accepted
                            # region is valid.
                            slot.draft_length = slot.length
                    continue
                slot.length += 1  # cur token entered the cache
                self.lengths[i] += 1
                tok = eng._sample_one(lg_host[i], self.seed0, req.rid,
                                      slot.generated)
                self._emit(req, slot, tok)
                if not self._maybe_finish(slot, tok, step):
                    self.cur[i, 0] = tok
        self._tick_audit()
        return True

    def close(self):
        """Drain: release chaos holds, flush events, audit, and check
        every submitted request reached exactly one terminal status and
        zero KV blocks leaked. Returns ``(outputs, finished)`` exactly
        like ``serve()``."""
        assert not self._closed, "session already closed"
        self._closed = True
        pool, sched, stats = self.pool, self.sched, self.stats
        for h in self.holds:
            pool.free(h[1])
        self.holds.clear()
        self._dispatch_events()
        if self.audit:
            pool.check_invariants([])
            stats["audits"] += 1
        counts: dict = {}
        for rec in sched.finished.values():
            counts[rec["status"]] = counts.get(rec["status"], 0) + 1
        stats["status_counts"] = counts
        stats["compile_count"] = (
            self.eng._verify_step._cache_size() if self.spec
            else self.eng._mixed_step._cache_size()
        )
        if self.spec:
            stats["spec"] = {
                "k": self.sc.spec_k, "draft": self.sc.draft,
                **self.runner.stats,
            }
            stats["acceptance_rate"] = (
                stats["spec_accepted"] / max(stats["spec_drafted"], 1)
            )
            stats["draft_compile_count"] = self.runner.compile_count()
        stats["prefix_hit_frac"] = (
            stats["prefix_hit_tokens"] / max(stats["prompt_tokens"], 1)
        )
        assert pool.num_free == pool.capacity, "leaked KV blocks"
        missing = set(self.outs) - set(sched.finished)
        assert not missing, (
            f"requests without a terminal status: {sorted(missing)}"
        )
        # Flush span-duration histograms (``span.tick/...`` summary
        # rows) — the session tracker is a bind() child, so its
        # instrument state dies with the session.
        self.trk.summarize()
        return self.outs, sched.finished
