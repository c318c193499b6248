"""Training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch tinyllama-1.1b \
        --reduced --steps 200 --ckpt-dir artifacts/run1 [--upcycle-from DIR]

The step runs on one device: this launcher builds no mesh (the Trainer
gets ``ctx=None``). A sharded step over several chips takes a
``ShardCtx`` mesh, as ``chip_smoke.py --chips 4`` builds one.
Auto-resumes from the newest valid checkpoint in --ckpt-dir. The
persistent compile cache goes where ``JAX_COMPILATION_CACHE_DIR`` says,
else to ``<checkout>/.jax_cache``.
"""
from __future__ import annotations

import argparse

import jax


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true",
                    help="use the smoke-test-sized config (CPU-friendly)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--ckpt-dir", default="artifacts/train_run")
    ap.add_argument("--grad-accum", type=int, default=1)
    ap.add_argument("--compression", default="none",
                    choices=["none", "bf16", "int8"])
    ap.add_argument("--remat", default="none",
                    choices=["none", "full", "dots", "moe"],
                    help="'moe' saves only MoE-block outputs — the Pallas "
                         "VJP residuals, not full activations, set the "
                         "memory high-water mark")
    ap.add_argument("--impl", default="auto",
                    choices=["auto", "xla", "pallas", "ref"],
                    help="kernel implementation for MoE expert FFN and "
                         "attention; 'auto' = fused Pallas (fwd + "
                         "custom-VJP bwd) on TPU, XLA einsums on CPU")
    ap.add_argument("--ep", default="none", choices=["none", "a2a"],
                    help="expert parallelism of the MoE layer: "
                         "'none' = batch-sharded ragged buffer + FSDP "
                         "expert-weight gather (weights move); 'a2a' = "
                         "shard_map expert-parallel all-to-all over the "
                         "'model' mesh axis (tokens move, weights stay)")
    ap.add_argument("--ep-budget-factor", type=float, default=2.0,
                    help="EP a2a send-buffer row budget as a multiple of "
                         "the balanced per-peer share; overflow is "
                         "dropped like capacity overflow")
    ap.add_argument("--upcycle-from", default="",
                    help="dense checkpoint dir to sparse-upcycle from")
    ap.add_argument("--peak-lr", type=float, default=0.01)
    ap.add_argument("--warmup", type=int, default=100)
    ap.add_argument("--obs-jsonl", default="", metavar="PATH",
                    help="stream per-step train rows + checkpoint "
                         "counters as JSONL (src/repro/obs/README.md)")
    ap.add_argument("--spike-threshold", type=float, default=0.0,
                    help="divergence detector: roll back when a finite "
                         "loss exceeds this multiple of the trailing "
                         "baseline (0 = detector off)")
    ap.add_argument("--spike-window", type=int, default=32,
                    help="trailing-loss window the spike baseline is "
                         "computed over")
    ap.add_argument("--spike-mode", default="median",
                    choices=["median", "ewma"],
                    help="spike baseline: median of the window (robust) "
                         "or EWMA (tracks a falling curve tighter)")
    ap.add_argument("--max-rollbacks", type=int, default=3,
                    help="abort with the rollback history after this "
                         "many divergence rollbacks")
    ap.add_argument("--rollback-skip", type=int, default=8,
                    help="batches to fast-forward past the offending "
                         "batch after a rollback (PaLM-style skip)")
    ap.add_argument("--rollback-lr-decay", type=float, default=1.0,
                    help="LR multiplier applied for --rollback-cooldown "
                         "steps after a rollback (1.0 = no decay)")
    ap.add_argument("--rollback-cooldown", type=int, default=0,
                    help="steps the post-rollback LR decay stays active")
    ap.add_argument("--train-chaos", type=int, default=None,
                    metavar="SEED",
                    help="seeded train-side fault injection: loss "
                         "spikes, transient store IO faults, preemption "
                         "(repro.training.chaos; exercises the rollback "
                         "+ resume machinery end to end)")
    args = ap.parse_args()

    from repro.configs import get_config, get_reduced
    from repro.data import make_iterator
    from repro.launch.compile_cache import enable_compile_cache
    from repro.models import model_zoo as zoo
    from repro.optim import adafactor, inverse_sqrt
    from repro.training import TrainConfig, Trainer
    from repro.training.train_loop import PreemptionSignal

    enable_compile_cache()
    cfg = get_reduced(args.arch) if args.reduced else get_config(args.arch)
    if cfg.moe is not None and args.ep != "none":
        import dataclasses

        cfg = dataclasses.replace(
            cfg,
            moe=dataclasses.replace(
                cfg.moe, ep=args.ep,
                ep_budget_factor=args.ep_budget_factor,
            ),
        )
    opt = adafactor(inverse_sqrt(peak=args.peak_lr,
                                 warmup_steps=args.warmup))
    tc = TrainConfig(grad_accum=args.grad_accum,
                     compression=args.compression,
                     spike_threshold=args.spike_threshold,
                     spike_window=args.spike_window,
                     spike_mode=args.spike_mode,
                     max_rollbacks=args.max_rollbacks,
                     rollback_skip=args.rollback_skip,
                     rollback_lr_decay=args.rollback_lr_decay,
                     rollback_cooldown=args.rollback_cooldown)
    it = make_iterator(cfg, global_batch=args.batch, seq_len=args.seq)

    init_params = None
    if args.upcycle_from:
        from repro.checkpoint import CheckpointManager
        from repro.core.upcycle import upcycle_params
        from repro.models import param as pm

        if cfg.moe is None:
            raise SystemExit("--upcycle-from needs an arch with MoE")
        dense_cfg = cfg.dense_parent()
        wrapped = zoo.init_params(jax.random.PRNGKey(0), dense_cfg)
        dvals, axes = pm.split(wrapped)
        mgr = CheckpointManager(args.upcycle_from)
        like = {"params": dvals}
        restored, step, _ = mgr.restore_latest(like)
        if restored is None:
            raise SystemExit(f"no checkpoint in {args.upcycle_from}")
        sw = upcycle_params(
            pm.wrap(restored["params"], axes), dense_cfg, cfg,
            jax.random.PRNGKey(7),
        )
        init_params, _ = pm.split(sw)
        print(f"[train] upcycled from {args.upcycle_from} @ step {step}")

    sig = PreemptionSignal().install()
    ac = zoo.ApplyCfg(remat=args.remat, moe_impl=args.impl,
                      attn_impl=args.impl).resolve()
    print(f"[train] kernels: moe={ac.moe_impl} attn={ac.attn_impl} "
          f"sorted_block={ac.sorted_block} remat={ac.remat}")
    tracker = None
    if args.obs_jsonl:
        from repro.obs import JsonlSink, Tracker

        tracker = Tracker((JsonlSink(args.obs_jsonl),))
    chaos = None
    if args.train_chaos is not None:
        from repro.training.chaos import TrainChaosConfig

        chaos = TrainChaosConfig(
            seed=args.train_chaos, spike_prob=0.05,
            io_fault_prob=0.2, preempt_prob=0.0,
        )
    tr = Trainer(cfg, opt, it, args.ckpt_dir, ac=ac, tc=tc, preemption=sig,
                 tracker=tracker, chaos=chaos)
    out = tr.run(args.steps, init_params=init_params)
    if tracker is not None:
        tracker.close()
    if tr.stats.get("rollbacks"):
        print(f"[train] survived {len(tr.stats['rollbacks'])} "
              "divergence rollback(s)")
    print(f"[train] finished at step {int(out['state']['step'])}, "
          f"loss {float(out['metrics']['loss']):.4f}")


if __name__ == "__main__":
    main()
