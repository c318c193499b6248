"""Serving launcher: load a checkpoint (or fresh params) and serve batched
requests — static batch or paged continuous batching.

    PYTHONPATH=src python -m repro.launch.serve --arch granite-moe-1b-a400m \
        --reduced [--ckpt-dir DIR] [--max-new 16] [--temperature 0.8] \
        [--paged] [--block-size 16] [--stream]

``--paged`` switches to the continuous-batching engine (paged KV cache,
mid-flight admission/eviction, Pallas paged attention kernels on TPU).
Paged admission defaults to the chunked MIXED step (one jitted call per
tick carrying decode rows + prefill chunk lanes, prefix caching across
admissions); ``--admission prefill_on_join`` selects the pre-chunking
per-admission prefill, ``--chunk-size`` / ``--chunks-per-step`` size
the prefill token budget, ``--no-prefix-cache`` disables block-level
prompt-prefix reuse. ``--draft dense`` (or ``top1``) turns on
speculative decoding — the dense parent sliced out of the (upcycled)
checkpoint drafts ``--spec-k`` tokens per slot and the MoE verifies
them in one mixed-step pass, exactly preserving the output
distribution (acceptance stats land in the engine line). ``--stream``
prints tokens as they are sampled instead of waiting for the full
batch.

Robustness knobs (chunked admission; failure-modes table in
``repro/serve/__init__.py``): ``--queue-limit`` / ``--queue-policy``
bound the wait queue (block / shed-newest / shed-oldest),
``--shed-occupancy`` / ``--shed-stall-ticks`` drive occupancy- and
starvation-triggered load shedding, ``--preempt`` enables
preempt-and-requeue under pool exhaustion, ``--ttft-deadline`` /
``--deadline`` set default per-request deadlines in ticks after
arrival, ``--watchdog-ticks`` bounds zero-progress spins, and
``--chaos SEED`` arms the seeded fault injector (random evictions,
pool holds, admission bursts, deadline storms) for soak testing.
Requests end in exactly one terminal status (completed / shed /
timeout / failed), printed per request and aggregated in the engine
stats line.

Fleet mode (``--fleet N``, paged + chunked admission only) drives N
replica sessions of the engine behind the health-checked router
(``repro/serve/fleet.py``): ``--fleet-kill TICK:EID`` arms
deterministic engine kills (repeatable), ``--fleet-hedge-after``
enables hedged re-dispatch for stragglers, ``--fleet-restart-after``
rejoins killed engines after a delay — with ``--ckpt-dir`` the
replacement engine is rebuilt from the latest checkpoint
(restart-from-checkpoint), otherwise the dead replica's params are
reused — and ``--fleet-timeline`` streams the per-tick engine + fleet
observability rows as JSONL (one schema for both kinds, documented on
``repro.serve.TimelineWriter`` and in ``repro/obs/README.md``).
``--fleet-autoscale MAX`` arms the signal-driven autoscaler: sustained
overload spawns replicas up to MAX, sustained idleness drains them
back down. Per-request records gain ``engine`` / ``migrations`` /
``retries``; the stats line aggregates across replicas.

``--obs-jsonl PATH`` streams the full observability feed (engine rows,
tick-phase spans, scheduler counters, end-of-run histogram summaries —
metric reference in ``src/repro/obs/README.md``) to PATH in solo and
fleet mode alike; ``--jax-profile`` wraps every jitted mixed step in a
``jax.profiler`` step annotation so device traces line up with ticks.
The persistent compile cache goes where ``JAX_COMPILATION_CACHE_DIR``
says, else to ``<checkout>/.jax_cache``.
"""
from __future__ import annotations

import argparse

import jax


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--paged", action="store_true",
                    help="continuous batching over a paged KV cache")
    ap.add_argument("--block-size", type=int, default=16,
                    help="KV tokens per pool block (--paged)")
    ap.add_argument("--admission", default="chunked",
                    choices=["chunked", "prefill_on_join"],
                    help="paged admission path (chunked = mixed step)")
    ap.add_argument("--chunk-size", type=int, default=32,
                    help="prompt tokens per prefill chunk lane")
    ap.add_argument("--chunks-per-step", type=int, default=1,
                    help="prefill chunk lanes per mixed step")
    ap.add_argument("--no-prefix-cache", action="store_true",
                    help="disable block-level prompt-prefix reuse")
    ap.add_argument("--draft", default="none",
                    choices=["none", "dense", "top1"],
                    help="speculative decoding draft model: the dense "
                         "parent sliced from the MoE checkpoint, or a "
                         "top-1 routing truncation (chunked admission)")
    ap.add_argument("--spec-k", type=int, default=4,
                    help="draft tokens per verify pass (--draft)")
    ap.add_argument("--stream", action="store_true",
                    help="print tokens as they are generated (--paged)")
    rb = ap.add_argument_group("robustness (chunked admission)")
    rb.add_argument("--queue-limit", type=int, default=0,
                    help="max visible waiting requests (0 = unbounded)")
    rb.add_argument("--queue-policy", default="block",
                    choices=["block", "shed-newest", "shed-oldest"])
    rb.add_argument("--shed-occupancy", type=float, default=None,
                    help="pool-occupancy fraction that triggers shedding")
    rb.add_argument("--shed-stall-ticks", type=int, default=0,
                    help="consecutive block-starved ticks that trigger "
                         "shedding (0 = off)")
    rb.add_argument("--preempt", action="store_true",
                    help="preempt-and-requeue lower-priority requests "
                         "under pool exhaustion")
    rb.add_argument("--ttft-deadline", type=int, default=None,
                    help="default first-token deadline (ticks after "
                         "arrival)")
    rb.add_argument("--deadline", type=int, default=None,
                    help="default completion deadline (ticks after "
                         "arrival)")
    rb.add_argument("--watchdog-ticks", type=int, default=32,
                    help="zero-progress ticks before the watchdog fails "
                         "the stuck head")
    rb.add_argument("--chaos", type=int, default=None, metavar="SEED",
                    help="arm the seeded fault injector")
    fl = ap.add_argument_group("fleet (paged + chunked admission)")
    fl.add_argument("--fleet", type=int, default=0, metavar="N",
                    help="serve through N replica sessions behind the "
                         "health-checked router (0/1 = solo engine)")
    fl.add_argument("--fleet-kill", action="append", default=[],
                    metavar="TICK:EID",
                    help="kill engine EID at fleet tick TICK "
                         "(repeatable; work migrates to survivors)")
    fl.add_argument("--fleet-hedge-after", type=int, default=0,
                    help="ticks without progress before a hedged "
                         "duplicate dispatch (0 = off)")
    fl.add_argument("--fleet-restart-after", type=int, default=0,
                    help="ticks after death before a fresh engine "
                         "rejoins (0 = never; with --ckpt-dir the "
                         "replacement reloads the latest checkpoint)")
    fl.add_argument("--fleet-timeline", default="",
                    metavar="PATH",
                    help="write the per-tick routing-signal JSONL here")
    fl.add_argument("--fleet-autoscale", type=int, default=0,
                    metavar="MAX",
                    help="autoscale replicas between --fleet and MAX "
                         "from exported overload/idle signals (0 = off)")
    ob = ap.add_argument_group("observability")
    ob.add_argument("--obs-jsonl", default="", metavar="PATH",
                    help="stream tracker rows (engine series, spans, "
                         "counters; see src/repro/obs/README.md) here")
    ob.add_argument("--jax-profile", action="store_true",
                    help="annotate each jitted mixed step for "
                         "jax.profiler traces")
    args = ap.parse_args()
    # --fleet 1 alone is just a solo engine; with --fleet-autoscale MAX
    # it is a real fleet that starts at one replica and grows.
    fleet_mode = args.fleet > 1 or (
        args.fleet >= 1 and args.fleet_autoscale > args.fleet)
    if fleet_mode and not (args.paged
                           and args.admission == "chunked"):
        ap.error("--fleet needs --paged with --admission chunked")

    from repro.configs import get_config, get_reduced
    from repro.launch.compile_cache import enable_compile_cache
    from repro.models import model_zoo as zoo
    from repro.models import param as pm
    from repro.obs import JsonlSink, Tracker
    from repro.serve import (
        AutoscaleConfig,
        ChaosConfig,
        Fleet,
        FleetChaosConfig,
        FleetConfig,
        Request,
        ServeConfig,
        ServeEngine,
    )

    enable_compile_cache()
    cfg = get_reduced(args.arch) if args.reduced else get_config(args.arch)

    # ONE manager for the whole process: restart_factory restores
    # through it, and its cumulative health() feeds the fleet's
    # store-health-aware restart gate.
    ckpt_mgr = None
    if args.ckpt_dir:
        from repro.checkpoint import CheckpointManager

        ckpt_mgr = CheckpointManager(args.ckpt_dir)

    def load_params():
        wrapped = zoo.init_params(jax.random.PRNGKey(0), cfg)
        p, _ = pm.split(wrapped)
        if ckpt_mgr is not None:
            restored, step, _ = ckpt_mgr.restore_latest({"params": p})
            if restored is not None:
                p = restored["params"]
                print(f"[serve] loaded checkpoint step {step}")
        return p

    params = load_params()
    chaos = (ChaosConfig(seed=args.chaos, evict_prob=0.1, hold_prob=0.15,
                         burst_prob=0.1, storm_prob=0.05)
             if args.chaos is not None else None)
    sc = ServeConfig(max_batch=args.max_batch, max_len=256,
                     temperature=args.temperature,
                     paged=args.paged, block_size=args.block_size,
                     admission=args.admission,
                     chunk_size=args.chunk_size,
                     chunks_per_step=args.chunks_per_step,
                     prefix_cache=not args.no_prefix_cache,
                     draft=args.draft, spec_k=args.spec_k,
                     queue_limit=args.queue_limit,
                     queue_policy=args.queue_policy,
                     shed_occupancy=args.shed_occupancy,
                     shed_stall_ticks=args.shed_stall_ticks,
                     preempt=args.preempt,
                     default_ttft_deadline=args.ttft_deadline,
                     default_deadline=args.deadline,
                     watchdog_ticks=args.watchdog_ticks,
                     chaos=chaos,
                     jax_profile=args.jax_profile)
    tracker = (Tracker((JsonlSink(args.obs_jsonl),))
               if args.obs_jsonl else None)
    eng = ServeEngine(params, cfg, sc, tracker=tracker)
    demo = [[1, 2, 3], [10, 20], [7, 7, 7, 7]][: args.max_batch]
    if args.paged:
        # Staggered arrivals show mid-flight admission; --stream prints
        # per-token, otherwise the final sequences.
        reqs = [
            Request(rid=i, prompt=p, max_new=args.max_new, arrival=2 * i)
            for i, p in enumerate(demo)
        ]
        on_token = (
            (lambda rid, t: print(f"[serve] req{rid} += {t}", flush=True))
            if args.stream else None
        )
        on_event = (
            (lambda rid, ev, detail: print(
                f"[serve] req{rid} event: {ev}"
                + (f" ({detail})" if detail else ""), flush=True))
            if args.admission == "chunked" else None
        )
        if fleet_mode:
            kills = tuple(
                (int(t), int(e))
                for t, e in (spec.split(":") for spec in args.fleet_kill)
            )
            restart_factory = None
            if args.fleet_restart_after:
                def restart_factory(eid):
                    # Restart-from-checkpoint: a rejoining engine is
                    # rebuilt from the latest valid step (or fresh
                    # params without --ckpt-dir), not the corpse's
                    # in-memory state.
                    print(f"[serve] engine {eid}: rebuilding replica "
                          f"from {args.ckpt_dir or 'fresh params'}")
                    return ServeEngine(load_params(), cfg, sc)
            autoscale = None
            if args.fleet_autoscale > args.fleet:
                autoscale = AutoscaleConfig(
                    min_engines=args.fleet,
                    max_engines=args.fleet_autoscale,
                )
            fleet = Fleet(eng, FleetConfig(
                num_engines=args.fleet,
                hedge_after=args.fleet_hedge_after,
                restart_after=args.fleet_restart_after,
                timeline_path=args.fleet_timeline or None,
                chaos=FleetChaosConfig(kills=kills) if kills else None,
                autoscale=autoscale,
            ), restart_factory=restart_factory,
               store_health=(ckpt_mgr.health if ckpt_mgr is not None
                             else None),
               tracker=tracker)
            outs, stats = fleet.run(reqs, on_token=on_token,
                                    on_event=on_event)
            for i, p in enumerate(demo):
                s = stats[i]
                print(f"[serve] req{i}: {p} -> {outs[i][len(p):]} "
                      f"({s['status']}/{s['reason']} "
                      f"engine={s['engine']} "
                      f"migrations={s['migrations']} "
                      f"retries={s['retries']})")
            es = fleet.last_stats
            print(f"[serve] fleet: engines={es['num_engines']} "
                  f"ticks={es['ticks']} "
                  f"status_counts={es['status_counts']} "
                  f"migrations={es['migrations']} "
                  f"retries={es['retries']} kills={es['kills']} "
                  f"restarts={es['restarts']} hedges={es['hedges']}"
                  + (f" timeline={es['timeline_path']}"
                     if es["timeline_path"] else "")
                  + (f" scale_ups={es['scale_ups']} "
                     f"scale_downs={es['scale_downs']}"
                     if autoscale is not None else ""))
            if tracker is not None:
                tracker.close()
            return
        outs, stats = eng.serve(reqs, on_token=on_token,
                                on_event=on_event)
        for i, p in enumerate(demo):
            s = stats[i]
            status = s.get("status", "completed")
            print(f"[serve] req{i}: {p} -> {outs[i][len(p):]} "
                  f"({status}/{s['reason']} admitted@{s['admitted_at']} "
                  f"done@{s['finished_at']} "
                  f"prefix_hit={s['prefix_tokens']})")
        es = eng.last_stats
        extra = ""
        if args.admission == "chunked":
            extra = (f" status_counts={es['status_counts']} "
                     f"preemptions={es['preemptions']} "
                     f"peak_occupancy={es['peak_occupancy']:.2f}")
            if chaos is not None:
                extra += f" chaos={es['chaos']}"
        if args.draft != "none":
            extra += (f" draft={args.draft} spec_k={args.spec_k} "
                      f"acceptance_rate={es['acceptance_rate']:.2f} "
                      f"drafted={es['spec_drafted']} "
                      f"accepted={es['spec_accepted']}")
        print(f"[serve] engine: mode={es['mode']} "
              f"steps={es['mixed_steps']} "
              f"compile_count={es['compile_count']} "
              f"prefix_hit_frac={es['prefix_hit_frac']:.2f}" + extra)
        if tracker is not None:
            tracker.close()
        return
    for i, seq in enumerate(eng.generate(demo, max_new=args.max_new)):
        print(f"[serve] req{i}: {demo[i]} -> {seq[len(demo[i]):]}")


if __name__ == "__main__":
    main()
