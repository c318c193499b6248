"""Serving cells: drive ``ServeEngine(paged=True)`` through
``ChunkedSession.submit``/``tick`` with a mix from ``traffic.py``, on the
host's clock, and check what it served against the reference.

Timing is from the client's side. A request is *due* at its arrival
time; it is submitted at the first tick boundary after that, and its
tokens "reach the client" when the engine's ``on_token`` callback hands
them over. TTFT runs from due to first token, so a stalled tick delays
every request due meanwhile.
"""
from __future__ import annotations

import contextlib
import gc
import heapq
import time
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np

import model
import traffic

WARM_RID = 1 << 40  # warm-up requests' ids, above every traffic rid
TERMINAL = ("completed", "shed", "timeout", "failed", "cancelled")


@dataclass
class Rec:
    """One request as the client saw it (times in s from window start)."""
    rid: int
    prompt_len: int
    max_new: int
    due: float
    window: bool
    sent: float | None = None
    admitted: float | None = None
    first: float | None = None
    last: float | None = None
    n: int = 0
    status: str | None = None

    @property
    def done(self) -> bool:
        return self.n >= self.max_new or self.status in TERMINAL


@dataclass
class TickLog:
    """Work of one traced tick, for the FLOP and byte counts."""
    dec_ctx: list = field(default_factory=list)  # keys per decode row
    first: int = 0  # first tokens (chunk lanes that finished a prompt)
    prefill: int = 0  # prompt tokens through chunk lanes


def serve_config(conf: dict, trace: bool):
    from repro.serve import ServeConfig

    s = conf["serve"]
    return ServeConfig(
        paged=True, max_batch=s["max_batch"], max_len=s["max_len"],
        num_blocks=s["num_blocks"], chunk_size=s["chunk_size"],
        chunks_per_step=s["chunks_per_step"], cache_dtype=conf["dtype"],
        temperature=0.0, jax_profile=trace,
    )


class Driver:
    """The load generator and the client-side bookkeeping of one run."""

    def __init__(self, sess, reqs, seconds, trace_dir=None):
        self.sess, self.seconds = sess, seconds
        self.recs = {r["rid"]: Rec(r["rid"], len(r["prompt"]),
                                   r["max_new"], r["due"], r["window"])
                     for r in reqs}
        self.reqs = {r["rid"]: r for r in reqs}
        self.t0 = None
        self.window_tokens = 0
        self.ticks = 0
        self.tick_log: TickLog | None = None
        self.traced: list[TickLog] = []
        self.trace_dir = trace_dir
        self.counters0 = self.counters1 = None
        self.due = [(r["due"], r["rid"]) for r in reqs]
        heapq.heapify(self.due)

    def now(self) -> float:
        return time.perf_counter() - self.t0

    # -- engine callbacks ---------------------------------------------------
    def on_token(self, rid, tok):
        r = self.recs.get(rid)
        if r is None:
            return
        t = self.now()
        r.n += 1
        if r.first is None:
            r.first = t
        r.last = t
        if t <= self.seconds:
            self.window_tokens += 1
        if self.tick_log is not None:
            if r.n == 1:
                self.tick_log.first += 1
            else:
                # the decode row fed token n-1 at position P+n-2, so it
                # attended to P+n-1 keys
                self.tick_log.dec_ctx.append(r.prompt_len + r.n - 1)

    def on_event(self, rid, ev, detail):
        r = self.recs.get(rid)
        if r is None:
            return
        if ev == "admitted" and r.admitted is None:
            r.admitted = self.now()
        elif ev in TERMINAL:
            r.status = ev

    # -- the loop -------------------------------------------------------------
    def _submit_due(self, now):
        from repro.serve import Request

        while self.due and self.due[0][0] <= now:
            _, rid = heapq.heappop(self.due)
            r, q = self.recs[rid], self.reqs[rid]
            r.sent = now
            self.sess.submit(Request(
                rid=rid, prompt=q["prompt"].tolist(), max_new=q["max_new"],
                arrival=self.sess.step))

    def _window_done(self) -> bool:
        return all(r.done for r in self.recs.values() if r.window)

    def _counters(self):
        st = self.sess.stats
        return {k: st[k] for k in ("mixed_steps", "prefix_hit_tokens",
                                   "prompt_tokens", "chunk_rows_used")} | {
            "compiles": len(st["compile_events"])}

    def run(self, trace_s: float, drain_s: float, clock) -> None:
        """Measure ``seconds``, then let the window's requests finish for
        at most ``drain_s`` more while arrivals go on. With ``trace_s``,
        profile that many seconds of ticks from the middle of the
        window."""
        tracing = self.trace_dir is not None
        ann = (jax.profiler.TraceAnnotation if tracing
               else (lambda name: contextlib.nullcontext()))
        t_on = max(0.0, self.seconds / 2 - trace_s / 2)
        traced_span = None
        compiles0 = clock.compiles
        self.counters0 = self._counters()
        self.t0 = time.perf_counter()
        while True:
            now = self.now()
            if tracing and traced_span is None and self.tick_log is None \
                    and now >= t_on and not self.traced:
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0
                jax.profiler.start_trace(str(self.trace_dir),
                                         profiler_options=opts)
                traced_span = jax.profiler.TraceAnnotation("bench.traced")
                traced_span.__enter__()
                self.tick_log = TickLog()
            elif traced_span is not None and now >= t_on + trace_s:
                traced_span.__exit__(None, None, None)
                jax.profiler.stop_trace()
                traced_span = None
                self.tick_log = None
            if now >= self.seconds and self.counters1 is None:
                self.counters1 = self._counters()
                self.window_compiles = clock.compiles - compiles0
            if now >= self.seconds and (self._window_done()
                                        or now >= self.seconds + drain_s):
                break
            with ann("bench.generate"):
                self._submit_due(now)
            if self.sess.has_work:
                before = self.sess.stats["chunk_rows_used"]
                with ann("bench.tick"):
                    self.sess.tick()
                self.ticks += 1
                if self.tick_log is not None:
                    self.tick_log.prefill = (
                        self.sess.stats["chunk_rows_used"] - before)
                    self.traced.append(self.tick_log)
                    self.tick_log = TickLog()
            else:
                nxt = self.due[0][0] if self.due else now + 1e-3
                with ann("bench.idle"):
                    time.sleep(max(0.0, min(nxt - now, 0.01)))
        if traced_span is not None:
            traced_span.__exit__(None, None, None)
            jax.profiler.stop_trace()
        self.end = self.now()

    # -- results ---------------------------------------------------------------
    def window_recs(self) -> list[Rec]:
        return [r for r in self.recs.values() if r.window]

    def metrics(self) -> dict:
        """The end-to-end metrics. A request of the window that failed or
        was not done when the run ended is a miss: its TTFT is counted as
        the time from its due time to the end of the run (a lower bound)."""
        win = self.window_recs()
        ttft = []
        for r in win:
            if r.status in (None, "completed") and r.first is not None \
                    and r.n >= r.max_new:
                ttft.append(r.first - r.due)
            else:
                ttft.append(self.end - r.due)
        tpot = [(r.last - r.first) / (r.n - 1) for r in win
                if r.n >= r.max_new and r.n >= 2]
        return {
            "ttft_p95_ms": 1e3 * float(np.percentile(ttft, 95)),
            "tpot_p95_ms": 1e3 * float(np.percentile(tpot, 95)),
            "output_tok_s": self.window_tokens / self.seconds,
        }

    def failed(self) -> int:
        return sum(1 for r in self.window_recs()
                   if not (r.n >= r.max_new and r.status in (None,
                                                             "completed")))


def warm_up(sess, conf, rng):
    """Run the engine's one mixed-step shape before the window."""
    from repro.serve import Request

    s = conf["serve"]
    n = s["chunk_size"] * s["chunks_per_step"] + 1
    prompt = rng.integers(1, model.dims_of(conf)["V"], n).tolist()
    sess.submit(Request(rid=WARM_RID, prompt=prompt, max_new=2,
                        arrival=sess.step))
    while sess.has_work:
        sess.tick()


def pick_sample(drv: Driver, seed: int, check: dict) -> list[int]:
    """Window requests to hold to the reference: the longest served one
    and others drawn from the seed, until ``min_tokens`` served tokens or
    ``max_requests`` requests."""
    done = [r for r in drv.window_recs()
            if r.n >= r.max_new and r.status in (None, "completed")]
    if not done:
        return []
    done.sort(key=lambda r: (-(r.prompt_len + r.n), r.rid))
    rest = done[1:]
    order = np.random.default_rng([seed, 1]).permutation(len(rest))
    out, toks = [done[0].rid], done[0].n
    for i in order:
        if toks >= check["min_tokens"] or len(out) >= check["max_requests"]:
            break
        out.append(rest[i].rid)
        toks += rest[i].n
    return out


def ref_gaps(dims: dict, seed: int, seqs: list, *, control: bool = False):
    """For each (sequence, prompt length): the gap, in logits, by which
    each served token lies below the float32 reference's best token.
    With ``control``, the gap of the token a bfloat16 reference would
    put first instead. Sequences run one at a time, padded to a multiple
    of 1024."""
    ref = model.family_of(dims).reference
    w = model.reference_weights(dims)(model.key_of(seed))

    def gaps(w, toks, served):
        with jax.default_matmul_precision("highest"):
            lg = ref.logits(w, toks, dims)
        best = lg.max(-1)
        if control:
            lo = ref.logits(w, toks, dims, dtype=jnp.bfloat16)
            served = jnp.argmax(lo, -1)
        got = jnp.take_along_axis(lg, served[:, None], -1)[:, 0]
        return best - got

    fn = jax.jit(gaps)
    out = []
    for seq, plen in seqs:
        n = len(seq) - 1  # the last served token is predicted, not fed
        pad = -(-n // 1024) * 1024
        toks = np.zeros(pad, np.int32)
        toks[:n] = seq[:-1]
        served = np.zeros(pad, np.int32)
        served[:n] = seq[1:]
        g = np.asarray(fn(w, jnp.asarray(toks), jnp.asarray(served)))
        out.append(g[plen - 1:n])
    return out


def run(conf, mix, args, clock, t_start, log) -> dict:
    from repro.serve import ServeEngine

    cfg, dims = model.arch_of(conf), model.dims_of(conf)
    seed = args.seed
    params = model.program_weights(cfg, dims)(model.key_of(seed))
    jax.block_until_ready(params)
    eng = ServeEngine(params, cfg, serve_config(conf, bool(args.trace)))
    reqs = traffic.serve_requests(mix, seed, args.seconds, dims["V"])
    drv = Driver(None, reqs, args.seconds,
                 trace_dir=args.trace_dir if args.trace else None)
    sess = eng.open_session(on_token=drv.on_token, on_event=drv.on_event)
    drv.sess = sess
    warm_up(sess, conf, np.random.default_rng([seed, 2]))
    setup_s = time.perf_counter() - t_start
    drv.run(mix["trace_s"], mix["drain_s"], clock)
    dev = jax.devices()[0]
    peak = (dev.memory_stats() or {}).get("peak_bytes_in_use")
    lateness = [r.sent - r.due for r in drv.recs.values()
                if r.sent is not None]
    log(f"[serve] ticks={drv.ticks} sent={len(lateness)} window="
        f"{len(drv.window_recs())} failed={drv.failed()} "
        f"window_compiles={drv.window_compiles} end_s={drv.end!r} "
        f"generator late p50/max ms={1e3 * np.median(lateness)!r}/"
        f"{1e3 * max(lateness)!r} peak_bytes_in_use={peak}")

    rids = pick_sample(drv, seed, mix["check"])
    seqs = [(np.asarray(sess.outs[rid], np.int32), drv.recs[rid].prompt_len)
            for rid in rids]
    served = sum(len(s) - p for s, p in seqs)
    # free the program's state (the driver held the session) before the
    # reference needs the memory
    drv.sess = None
    del sess, eng, params
    gc.collect()
    t_ref = time.perf_counter()
    gaps = ref_gaps(dims, seed, seqs)
    widest = max(float(g.max()) for g in gaps) if gaps else float("inf")
    log(f"[check] {len(seqs)} requests, {served} served tokens, "
        f"reference {time.perf_counter() - t_ref:.1f}s")
    return {
        "setup_s": setup_s, "driver": drv, "peak": peak,
        "metrics": drv.metrics(),
        "attempted": len(drv.window_recs()), "failed": drv.failed(),
        "checks": {"served_logit_gap": widest},
        "served_tokens": served, "sample": seqs, "gaps": gaps,
    }
